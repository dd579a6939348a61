"""The plan layer's Pallas kernels compile for a TPU v5e.

Interpret mode runs a kernel's Python on the CPU and never meets the
TPU compiler, which refuses block shapes off the (8, 128) tiling,
scalar stores to VMEM and relayouts it cannot lower. These tests compile
each kernel for a described (not attached) v5e chip at the bucket shapes
of ``chip_smoke.py``'s cohort and check that the kernel survives into
the compiled program as a ``tpu_custom_call``.

The topology is described inside a fixture, never while the module is
imported: only one process may load the TPU library at a time, and
every test worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_ehvi import fused_ehvi_pallas
from repro.kernels.fused_fit import fused_fit_pallas
from repro.kernels.fused_posterior import fused_posterior_ei_pallas
from repro.kernels.ranking_loss.ops import _pallas_padded

D = 7          # encoded scout space
Q = 69         # scout candidates


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _fit(m, n, steps):
    def shapes(s):
        f = jnp.float32
        return (s((m, n, D), f), s((m, n), f), s((m, n), f), s((m, D), f),
                s((m,), f))
    return fused_fit_pallas, shapes, {"steps": steps}


def _posterior(m, n):
    def shapes(s):
        f = jnp.float32
        return (s((m, D), f), s((m,), f), s((m, n, D), f), s((m, n), f),
                s((m, n, n), f), s((m, n), f), s((m, Q, D), f), s((m,), f))
    return fused_posterior_ei_pallas, shapes, {}


def _ehvi(lanes, boxes, n_obj, samples, q):
    def shapes(s):
        f = jnp.float32
        return (s((lanes, boxes, n_obj), f), s((lanes, boxes, n_obj), f),
                s((lanes, n_obj), f), s((lanes, n_obj, q), f),
                s((lanes, n_obj, q), f), s((lanes, n_obj), f),
                s((lanes, n_obj), f), s((lanes, n_obj, samples, q), f))
    return fused_ehvi_pallas, shapes, {}


def _ranking(rows, n):
    def shapes(s):
        return (s((rows, n), jnp.float32), s((rows, n), jnp.float32),
                s((rows,), jnp.int32))
    return _pallas_padded, shapes, {}


@pytest.mark.parametrize("case", [
    # fit buckets the cohort routes to Pallas: the cold 120-step rung at
    # 512 lanes of <= 8 observations, the warm 16-step rung at 1024
    # lanes of <= 16
    _fit(512, 8, 120),
    _fit(1024, 16, 16),
    # the posterior bucket of every target and support model
    _posterior(1024, 16),
    # 16 multi-objective tenants, 3 objectives, 64 draws, 72 candidates
    _ehvi(16, 128, 3, 64, 72),
    # 528 ensembles x 256 RGPE draws, rows padded to a power of two
    _ranking(1 << 18, 16),
], ids=["fused_fit_cold", "fused_fit_warm", "fused_posterior",
        "fused_ehvi", "ranking_loss_padded"])
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes, static = case

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(fn, static_argnames=tuple(static)).lower(
        *shapes(spec), **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
