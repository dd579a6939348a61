from .context import (DistContext, auto_axes, get_context, mesh_axis_size,
                      set_context, shard_map, use_context)

__all__ = ["DistContext", "auto_axes", "get_context", "mesh_axis_size",
           "set_context", "shard_map", "use_context"]
