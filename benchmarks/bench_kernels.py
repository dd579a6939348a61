"""Kernel microbenchmarks (XLA-path wall time on CPU; the Pallas kernels
target TPU and are correctness-validated in interpret mode by tests/)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import flash_attention
from repro.kernels.matern import matern52
from repro.kernels.pairwise_pearson import pairwise_pearson
from repro.kernels.ranking_loss import ranking_loss
from repro.kernels.ssm_scan import ssm_scan

from . import common as C


def _time(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps * 1e6


def main():
    key = jax.random.PRNGKey(0)

    # repository-scale similarity: 1k x 10k metric vectors
    a = jax.random.normal(key, (1000, 18))
    b = jax.random.normal(key, (10000, 18))
    f = jax.jit(lambda x, y: pairwise_pearson(x, y, impl="xla"))
    C.emit("kernel_pairwise_pearson_1kx10k", _time(f, a, b),
           "xla;pallas validated in tests")

    # RGPE weighting: 4096 samples x 20 observations
    p = jax.random.normal(key, (4096, 20))
    y = jax.random.normal(key, (20,))
    f = jax.jit(lambda x, z: ranking_loss(x, z, impl="xla"))
    C.emit("kernel_ranking_loss_4096x20", _time(f, p, y),
           "xla;pallas validated in tests")

    # GP kernel matrix: 2048 x 2048, d=7
    xa = jax.random.normal(key, (2048, 7))
    f = jax.jit(lambda x: matern52(x, x, impl="xla"))
    C.emit("kernel_matern52_2048sq", _time(f, xa),
           "xla;pallas validated in tests")

    # flash attention: 1x1024x8x64, GQA 8:2
    q = jax.random.normal(key, (1, 1024, 8, 64), jnp.bfloat16)
    kv = jax.random.normal(key, (1, 1024, 2, 64), jnp.bfloat16)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                impl="xla"))
    C.emit("kernel_flash_attention_1k", _time(f, q, kv, kv),
           "xla;pallas validated in tests")

    # ssm scan: 1x2048x8x64, n=64
    x = jax.random.normal(key, (1, 2048, 8, 64))
    dt = jax.nn.softplus(jax.random.normal(key, (1, 2048, 8)))
    decay = jnp.exp(-dt)
    B = jax.random.normal(key, (1, 2048, 64))
    Cc = jax.random.normal(key, (1, 2048, 64))
    f = jax.jit(lambda *a: ssm_scan(*a, impl="xla")[0])
    C.emit("kernel_ssm_scan_2k", _time(f, x, dt, decay, B, Cc),
           "xla;pallas validated in tests")

    # grouped GEMM (MoE experts): 8192 slots x 8 local experts, d=512
    from repro.kernels.grouped_gemm import grouped_gemm
    m, kk, nn, g = 8192, 512, 512, 8
    lhs = jax.random.normal(key, (m, kk), jnp.bfloat16)
    rhs = jax.random.normal(key, (g, kk, nn), jnp.bfloat16)
    sizes = jnp.full((g,), m // g, jnp.int32)
    f_bmm = jax.jit(lambda l, r, s: grouped_gemm(l, r, s, impl="xla"))
    f_rag = jax.jit(lambda l, r, s: grouped_gemm(l, r, s, impl="ragged"))
    t_bmm = _time(f_bmm, lhs, rhs, sizes)
    t_rag = _time(f_rag, lhs, rhs, sizes)
    C.emit("kernel_grouped_gemm_8kx8e_padded_bmm", t_bmm,
           f"vs ragged_dot {t_rag:.0f}us ({t_rag / t_bmm:.1f}x);"
           "pallas validated in tests")


if __name__ == "__main__":
    from repro.launch.compile_stats import use_compile_cache
    use_compile_cache()
    main()
