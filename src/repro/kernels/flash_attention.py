"""Flash attention forward — Pallas TPU kernel.

TPU-native design (not a CUDA port):

* Grid = (B*H, num_q_blocks, num_kv_blocks). TPU grids iterate sequentially,
  so the kv dimension is the innermost reduction: the online-softmax state
  (m, l, acc) lives in VMEM scratch and persists across kv steps of one
  (head, q-block) cell — no atomics, no shared-memory tree, which is the
  TPU analogue of the CUDA warp-level reduction.
* BlockSpecs tile q/k/v into (block_q, head_dim) / (block_kv, head_dim)
  VMEM slabs; head_dim is the MXU lane dim (128-friendly: 64/128/256 all
  map onto the 128x128 systolic array with internal padding).
* GQA is an *index-map* trick: queries arrive as (B*H, Sq, Dh); the k/v
  BlockSpec maps query-head bh -> kv head (b*Hkv + h//G), so grouped heads
  re-read the same KV tile from HBM (the TPU prefetcher coalesces this).
* Causal + sliding-window masking via broadcasted iota inside the kernel;
  fully-masked kv blocks are skipped with ``pl.when`` (the roofline win of
  causal flash: ~2x fewer MACs than the masked dense form).

The kernel is forward-only and serves prefill.  Training on a TPU runs the
shipped splash kernel, which has its own backward
(`kernels/splash_attention.py`); elsewhere, and for what that kernel cannot
express, training runs the autodiffed blocked-jnp scan (`models/attention.py`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _flash_kernel(
    q_ref,  # (1, block_q, Dh)
    k_ref,  # (1, block_kv, Dh)
    v_ref,  # (1, block_kv, Dh)
    o_ref,  # (1, block_q, Dh)
    m_scr,  # (block_q,) fp32
    l_scr,  # (block_q,) fp32
    acc_scr,  # (block_q, Dh) fp32
    *,
    scale: float,
    causal: bool,
    window: int | None,
    softcap: float,
    q_offset: int,
    block_q: int,
    block_kv: int,
    num_kv_blocks: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    ok = jnp.ones((block_q, block_kv), jnp.bool_)
    if causal:
        ok &= k_pos <= q_pos
    if window is not None:
        ok &= k_pos > (q_pos - window)

    # Entire-block skip: the first k of this block vs the last q of this
    # q-block decides causal reachability (static per grid cell shapes).
    block_reachable = True
    if causal:
        last_q = q_offset + qi * block_q + block_q - 1
        first_k = ki * block_kv
        block_reachable = first_k <= last_q
    if window is not None:
        first_q = q_offset + qi * block_q
        last_k = ki * block_kv + block_kv - 1
        block_reachable = jnp.logical_and(block_reachable, last_k > first_q - window)

    @pl.when(block_reachable)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bkv)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-37)[:, None]
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal",
        "window",
        "softcap",
        "q_offset",
        "block_q",
        "block_kv",
        "interpret",
    ),
)
def flash_attention(
    q: jnp.ndarray,  # (B, Sq, H, Dh)
    k: jnp.ndarray,  # (B, Sk, Hkv, Dh)
    v: jnp.ndarray,  # (B, Sk, Hkv, Dh)
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float = 0.0,
    q_offset: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    B, Sq, H, Dh = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Sk)
    assert Sq % block_q == 0 and Sk % block_kv == 0, (Sq, block_q, Sk, block_kv)
    nq, nk = Sq // block_q, Sk // block_kv

    # (B, S, H, Dh) -> (B*H, S, Dh) query-head-major
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, Dh)
    kh = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, Dh)
    vh = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, Dh)

    def kv_index(bh, qi, ki):
        b = bh // H
        h = bh % H
        return (b * Hkv + h // G, ki, 0)

    kernel = functools.partial(
        _flash_kernel,
        scale=Dh**-0.5,
        causal=causal,
        window=window,
        softcap=softcap,
        q_offset=q_offset,
        block_q=block_q,
        block_kv=block_kv,
        num_kv_blocks=nk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, Dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_kv, Dh), kv_index),
            pl.BlockSpec((1, block_kv, Dh), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dh), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),  # m (running max)
            pltpu.VMEM((block_q,), jnp.float32),  # l (running denom)
            pltpu.VMEM((block_q, Dh), jnp.float32),  # acc
        ],
        interpret=interpret,
    )(qh, kh, vh)
    return out.reshape(B, H, Sq, Dh).transpose(0, 2, 1, 3)
