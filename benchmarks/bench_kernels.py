"""Kernel micro-benchmarks (interpret-mode wall time is NOT TPU perf — the
derived column reports the analytic FLOPs/bytes each call would execute on
TPU, which is what the BlockSpec tiling targets)."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def _time(fn, *args, iters=3):
    """Best-of-N microsecond timing.  Best-of (not mean-of): scheduler noise
    and lazy-allocation warm-up only ever ADD time, so the minimum is the
    cleanest estimate of the call's true cost on a shared CPU runner."""
    jax.block_until_ready(fn(*args))  # compile/warm
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def bench_flash_attention():
    from repro.kernels.ops import flash_attention

    B, S, H, Hkv, Dh = 1, 256, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh), jnp.float32)
    us = _time(lambda *a: flash_attention(*a), q, k, v, iters=2)
    flops = 4 * B * H * S * (S / 2) * Dh
    return [("kernel_flash_attention_256", us, f"tpu_flops={flops:.3g}")]


def bench_paged_attention():
    from repro.kernels.ops import paged_attention

    B, H, Hkv, Dh = 4, 4, 2, 64
    n_pages, ps, p_max = 32, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, Dh), jnp.float32)
    k_pool = jax.random.normal(ks[1], (n_pages + 1, Hkv, ps, Dh), jnp.float32)
    v_pool = jax.random.normal(ks[2], (n_pages + 1, Hkv, ps, Dh), jnp.float32)
    # ragged live lengths: 100 / 37 / 8 / 0 tokens
    lengths = jnp.array([100, 37, 8, 0], jnp.int32)
    table = -jnp.ones((B, p_max), jnp.int32)
    page = 0
    for b, ln in enumerate([100, 37, 8, 0]):
        for j in range(-(-ln // ps)):
            table = table.at[b, j].set(page)
            page += 1
    us = _time(lambda *a: paged_attention(*a), q, k_pool, v_pool, table, lengths, iters=2)
    live_pages = sum(-(-ln // ps) for ln in [100, 37, 8, 0])
    flops = 4 * H * Dh * live_pages * ps  # only live pages do work (pl.when skip)
    dense_flops = 4 * H * Dh * B * p_max * ps
    return [(
        "kernel_paged_attention_rag", us,
        f"tpu_flops={flops:.3g} (dense_equiv={dense_flops:.3g}, {dense_flops / flops:.2f}x)",
    )]


def bench_rwkv6_scan():
    from repro.kernels.ops import rwkv6_scan

    B, T, H, D = 1, 128, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, D)) for i in range(3))
    w = 0.5 + 0.49 * jax.random.uniform(ks[3], (B, T, H, D))
    u = jax.random.normal(ks[4], (H, D)) * 0.1
    us = _time(lambda *a: rwkv6_scan(*a), r, k, v, w, u, iters=2)
    chunk = 32
    flops = B * H * (T / chunk) * (2 * chunk * D * D * 3 + 2 * chunk * chunk * D * 2)
    return [("kernel_rwkv6_scan_128", us, f"tpu_flops={flops:.3g}")]


ALL = [bench_flash_attention, bench_paged_attention, bench_rwkv6_scan]
