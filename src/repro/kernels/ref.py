"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

These are deliberately the *simplest correct* implementations — no blocking,
no online softmax — so kernel tests compare against arithmetic that is easy
to audit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["flash_attention_ref", "paged_attention_ref", "rwkv6_scan_ref"]

NEG_INF = -2.0e38


def flash_attention_ref(
    q: jnp.ndarray,  # (B, Sq, H, Dh)
    k: jnp.ndarray,  # (B, Sk, Hkv, Dh)
    v: jnp.ndarray,  # (B, Sk, Hkv, Dh)
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float = 0.0,
    q_offset: int = 0,
) -> jnp.ndarray:
    """Materialized-scores attention with GQA grouping.

    ``q_offset``: absolute position of q[0] (decode: Sk_cached). Causality is
    ``k_pos <= q_pos`` with ``q_pos = q_offset + arange(Sq)``.
    """
    B, Sq, H, Dh = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Sq, Hkv, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32)) * (Dh**-0.5)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    ok = jnp.ones((Sq, Sk), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        ok &= k_pos[None, :] > (q_pos[:, None] - window)
    s = jnp.where(ok[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


def paged_attention_ref(
    q: jnp.ndarray,  # (B, H, Dh)
    k_pool: jnp.ndarray,  # (n_pages + 1, Hkv, page_size, Dh)
    v_pool: jnp.ndarray,  # (n_pages + 1, Hkv, page_size, Dh)
    pages: jnp.ndarray,  # (B, num_page_slots) int32, -1 = unallocated
    lengths: jnp.ndarray,  # (B,) int32 live tokens per slot
    k_scale: jnp.ndarray | None = None,  # (n_pages + 1, Hkv, page_size) int8 pools
    v_scale: jnp.ndarray | None = None,
    *,
    window: int | None = None,
    softcap: float = 0.0,
) -> jnp.ndarray:
    """Gather-then-attend oracle for the paged decode kernel: materialize each
    slot's logical KV sequence from its page table, then run the dense masked
    softmax.  Slot b's position p lives in page ``pages[b, p // page_size]``
    at offset ``p % page_size``; it attends positions 0..lengths[b]-1 (its
    query sits at position lengths[b]-1)."""
    B, H, Dh = q.shape
    n_pages_p1, Hkv, page_size, _ = k_pool.shape
    S = pages.shape[1] * page_size
    G = H // Hkv
    pos = jnp.arange(S)
    pg = pages[:, pos // page_size]  # (B, S)
    safe = jnp.where(pg < 0, n_pages_p1 - 1, pg)
    off = pos % page_size

    def gather(pool):
        return pool[safe, :, off[None, :]].astype(jnp.float32)  # (B, S, Hkv, Dh)

    k = gather(k_pool)
    v = gather(v_pool)
    if k_pool.dtype == jnp.int8:
        k = k * gather(k_scale[..., None])
        v = v * gather(v_scale[..., None])
    qg = q.reshape(B, 1, Hkv, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) * (Dh**-0.5)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    valid = (pg >= 0) & (pos[None, :] < lengths[:, None])
    if window is not None:
        valid &= pos[None, :] > (lengths[:, None] - 1 - window)
    s = jnp.where(valid[:, None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked slot (lengths == 0): zero output, matching the kernel
    p = jnp.where(valid[:, None, None, None], p, 0.0)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
    return out.reshape(B, H, Dh).astype(q.dtype)


def rwkv6_scan_ref(r, k, v, w, u, s0=None):
    """Sequential RWKV6 recurrence (same as models.rwkv.wkv_scan, restated
    here so the kernels package is self-contained).

    r,k,v,w: (B,T,H,D) fp32; u: (H,D); s0: (B,H,D,D) or None.
    Returns (y (B,T,H,D), s_end).
    """
    B, T, H, D = r.shape
    if s0 is None:
        s0 = jnp.zeros((B, H, D, D), jnp.float32)

    def step(s, xs):
        rt, kt, vt, wt = xs
        kv = kt[..., :, None] * vt[..., None, :]
        y = jnp.einsum("bhk,bhkv->bhv", rt, s + u[None, :, :, None] * kv)
        return wt[..., :, None] * s + kv, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (r, k, v, w))
    s_end, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1), s_end
