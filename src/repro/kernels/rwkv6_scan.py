"""RWKV6 chunked recurrence — Pallas TPU kernel.

TPU adaptation of the token-serial CUDA wkv kernel: instead of one thread
per channel marching token-by-token, the sequence is processed in chunks of
``chunk`` tokens and the recurrence becomes three MXU matmuls per chunk
(state propagation (T,D)@(D,D), intra-chunk scores (T,D)@(D,T), value
combine (T,T)@(T,D)) plus a (D,D) state update.  The running state S lives
in VMEM scratch and persists across the sequential chunk grid dimension.

Numerics contract (shared with models/rwkv.py): per-token log-decay is
clamped to >= -4 upstream and chunk <= 32, so after mid-chunk recentering
every exponent is in [-64, 64] — overflow-free in fp32.  Tests sweep decay
down to the clamp boundary.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv6_kernel(
    r_ref,  # (1, T, D)
    k_ref,
    v_ref,
    lw_ref,  # (1, T, D) log decay
    u_ref,  # (1, D)
    s0_ref,  # (1, D, D)
    y_ref,  # (1, T, D)
    s_out_ref,  # (1, D, D)
    s_scr,  # (D, D) fp32 scratch
    *,
    chunk: int,
    num_chunks: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)  # (T, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # (D,)
    S = s_scr[...]

    L = jnp.cumsum(lw, axis=0)  # (T, D)
    Lprev = L - lw
    # state contribution
    r_dec = r * jnp.exp(Lprev)
    y_state = jax.lax.dot_general(
        r_dec, S, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    # intra-chunk (mid-recentering; see module docstring)
    Lmid = L[chunk // 2 - 1][None, :] if chunk > 1 else jnp.zeros_like(L[0])[None, :]
    q = r * jnp.exp(Lprev - Lmid)
    kk = k * jnp.exp(Lmid - L)
    scores = jax.lax.dot_general(
        q, kk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (T, T)
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(si < ti, scores, 0.0)  # strictly lower triangular
    diag = jnp.sum(r * u[None, :] * k, axis=1)  # (T,)
    y_intra = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) + diag[:, None] * v
    y_ref[0] = (y_state + y_intra).astype(y_ref.dtype)

    # state update: S' = diag(e^{L_end}) S + (k * e^{L_end - L})^T v
    Lend = L[-1][None, :]  # (1, D)
    k_dec = k * jnp.exp(Lend - L)  # (T, D)
    S_new = jnp.exp(Lend[0])[:, None] * S + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    s_scr[...] = S_new

    @pl.when(ci == num_chunks - 1)
    def _final():
        s_out_ref[0] = S_new.astype(s_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(
    r: jnp.ndarray,  # (B, T, H, D) fp32
    k: jnp.ndarray,
    v: jnp.ndarray,
    w: jnp.ndarray,  # per-token decay in (0, 1), log-decay >= -4
    u: jnp.ndarray,  # (H, D)
    s0: jnp.ndarray | None = None,  # (B, H, D, D)
    chunk: int = 32,
    *,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    B, T, H, D = r.shape
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk
    if s0 is None:
        s0 = jnp.zeros((B, H, D, D), jnp.float32)

    # (B,T,H,D) -> (B*H, T, D)
    def bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)

    rr, kk_, vv = bh(r), bh(k), bh(v)
    lw = bh(jnp.log(jnp.maximum(w, 1e-38)))
    uu = jnp.tile(u, (B, 1))  # (B*H, D)
    ss = s0.reshape(B * H, D, D)

    kernel = functools.partial(_rwkv6_kernel, chunk=chunk, num_chunks=nc)
    y, s_end = pl.pallas_call(
        kernel,
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, D), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, D), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, D), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, D), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, D), lambda bh, ci: (bh, 0)),
            pl.BlockSpec((1, D, D), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, D), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, D, D), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), r.dtype),
            jax.ShapeDtypeStruct((B * H, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        interpret=interpret,
    )(rr, kk_, vv, lw, uu, ss)
    return (
        y.reshape(B, H, T, D).transpose(0, 2, 1, 3),
        s_end.reshape(B, H, D, D),
    )
