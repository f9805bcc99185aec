"""Causal GQA training attention on the shipped splash-attention kernel.

``jax.experimental.pallas.ops.tpu.splash_attention`` is a Pallas flash
kernel with its own backward (separate dq and dkv kernels): the softmax
statistics and every accumulation stay float32, the matmul operands keep the
input dtype, and the block mask skips blocks that causality (or a sliding
window) fully masks in the forward, dq and dkv passes alike.  Nothing is
autodiffed through an online-softmax scan.

GQA: queries are viewed as (B, Hkv, G, S, Dh) and the MQA form of the
kernel (G query heads over one K/V head) is vmapped over batch and KV heads,
so repeated KV heads are never materialised.  The kernel takes no softmax
scale, so q is scaled before it (for Dh 64 and 256 the scale is a power of
two, exact in bfloat16).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as sa

LANES = 128  # the kernel's block sizes and sequence lengths are multiples of this


def block_size(seq: int) -> int:
    """512, or the sequence if shorter, stepped down by 128 until it divides
    the sequence (``seq`` is a multiple of 128)."""
    b = min(512, seq)
    while seq % b:
        b -= LANES
    return b


def _mqa_kernel(seq: int, group: int, window: int | None, softcap: float, interpret: bool):
    b = block_size(seq)
    if window is None:
        head_mask = sa.CausalMask((seq, seq))
    else:  # k in (q - window, q]
        head_mask = sa.LocalMask((seq, seq), window_size=(window - 1, 0), offset=0)
    blocks = sa.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        block_q_dq=b, block_kv_dq=b,
    )
    return sa.make_splash_mqa_single_device(
        sa.MultiHeadMask([head_mask] * group),
        block_sizes=blocks,
        attn_logits_soft_cap=softcap if softcap > 0 else None,
        interpret=interpret,
    )


def causal_attention(q, k, v, *, window: int | None = None, softcap: float = 0.0, interpret: bool):
    """q: (B, S, H, Dh); k, v: (B, S, Hkv, Dh) -> (B, S, H, Dh) in q's dtype.

    Contiguous positions 0..S-1; S a multiple of 128."""
    B, S, H, Dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    if S % LANES:
        raise ValueError(f"sequence {S} is not a multiple of {LANES}")
    kernel = _mqa_kernel(S, G, window, softcap, interpret)
    qs = (q.astype(jnp.float32) * Dh**-0.5).astype(q.dtype)
    qg = qs.reshape(B, S, Hkv, G, Dh).transpose(0, 2, 3, 1, 4)  # (B, Hkv, G, S, Dh)
    kh = k.transpose(0, 2, 1, 3)  # (B, Hkv, S, Dh)
    vh = v.transpose(0, 2, 1, 3)
    out = jax.vmap(jax.vmap(kernel))(qg, kh, vh)  # (B, Hkv, G, S, Dh)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, Dh)
