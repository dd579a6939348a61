"""Ambient distribution context.

Model code is mesh-agnostic; the launcher installs a ``DistContext`` that
tells distribution-aware layers (MoE expert parallelism, sequence-parallel
attention) which mesh/axes to use. When no context is installed (unit
tests, single-host CPU), layers fall back to purely local math.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def shard_map(f, mesh: Mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with keyword arguments. Every shard-mapped launch
    in this package routes through here so the plan executor and the
    model layers agree on one call form."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def auto_axes(mesh: Optional[Mesh]) -> Optional[Mesh]:
    """The same devices and axis names with every axis ``Auto``.

    ``jax.make_mesh`` builds ``Explicit`` axes, on which an array's
    sharding is part of its type: indexing a lane-sharded launch output
    with a host integer then raises ``ShardingTypeError``. The plan
    layer shards only inside its own ``shard_map`` launches and slices
    their outputs per query on the host side, so it holds its mesh with
    ``Auto`` axes and leaves the partitioning of those slices to XLA."""
    if mesh is None:
        return None
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def mesh_axis_size(mesh: Optional[Mesh], axis: str) -> int:
    """Size of one named mesh axis; 1 when the mesh is absent OR simply
    does not carry the axis (a data-only ``("data",)`` mesh has no
    model axis — that is a size-1 degree of parallelism, not an
    error)."""
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get(axis, 1))


@dataclasses.dataclass
class DistContext:
    mesh: Optional[Mesh] = None
    batch_axes: Tuple[str, ...] = ("data",)  # batch shards over these
    model_axis: str = "model"
    # expert parallelism mode: none | allgather | a2a
    ep_mode: str = "none"
    # FSDP axis for expert weights (huge MoE archs); None = no FSDP
    fsdp_axis: Optional[str] = None
    # sequence axis used for context parallelism in long-prefill shapes
    seq_axis: Optional[str] = None

    @property
    def model_size(self) -> int:
        # absent axes are size 1, NOT a KeyError: a data-only mesh is a
        # perfectly valid context for layers that never shard weights
        return mesh_axis_size(self.mesh, self.model_axis)

    @property
    def data_size(self) -> int:
        """Product of the batch-axis sizes present on the mesh."""
        out = 1
        for a in self.batch_axes:
            out *= mesh_axis_size(self.mesh, a)
        return out

    @property
    def all_axes(self) -> Tuple[str, ...]:
        return tuple(self.batch_axes) + (self.model_axis,)


_CURRENT = DistContext()


def get_context() -> DistContext:
    return _CURRENT


def set_context(ctx: DistContext) -> None:
    global _CURRENT
    _CURRENT = ctx


@contextlib.contextmanager
def use_context(ctx: DistContext):
    global _CURRENT
    prev = _CURRENT
    _CURRENT = ctx
    try:
        yield ctx
    finally:
        _CURRENT = prev
