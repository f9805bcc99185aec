"""Ragged paged-attention decode — Pallas TPU kernel.

One decode step attends each slot's single query against that slot's KV
*pages*: fixed-size blocks scattered through a shared pool, addressed by a
per-slot page table.  The serving win over the dense layout (attend over the
full ``(n_slots, max_seq)`` cache every tick) is that per-slot cost is
proportional to the slot's LIVE tokens, rounded up to page granularity:

* Grid = (B*H, num_page_slots).  TPU grids iterate sequentially, so the page
  dimension is the innermost reduction: the online-softmax state (m, l, acc)
  lives in VMEM scratch and persists across the pages of one (slot, head)
  cell — exactly the ``_flash_kernel`` recipe.
* Page-table indirection is a *BlockSpec index map* over scalar-prefetch
  operands (``pltpu.PrefetchScalarGridSpec``): the k/v index map reads
  ``pages[b, j]`` and returns that pool page as the block to fetch.  Dead
  entries (unallocated, causally empty, or fully outside the sliding window)
  map to the pool's trailing scratch page — consecutive dead entries fetch
  the *same* block, which the TPU pipeline elides, so skipped pages cost
  neither FLOPs (``pl.when``) nor fresh HBM traffic.
* GQA is the same index-map trick as the flash kernel: the grid runs over
  B*H query heads and the k/v map picks kv head ``(h // G)``.
* Variants: sliding-window masking (``window=``) and int8 KV pools with
  per-(token, head) scales dequantized in-kernel (``k_scale``/``v_scale``):
  a scale block holds one page's ``(Hkv, page_size)`` scales, the kernel
  picks its kv head's ``(1, page_size)`` row, and that row multiplies the
  scores (k) or the probabilities (v), so no block is ever transposed.

Forward-only by contract (like ``flash_attention``): decode never
differentiates through the cache.  ``interpret`` is decided by
``repro.kernels.ops`` (Mosaic on TPU, the interpreter elsewhere).

Layout contract (shared with ``models.attention`` and ``serve.engine``):
  q          (B, H, Dh)                          one query token per slot
  k/v pool   (n_pages + 1, Hkv, page_size, Dh)   LAST page is scratch
  k/v scale  (n_pages + 1, Hkv, page_size)       int8 pools only
  pages      (B, num_page_slots)   int32 page ids, -1 = unallocated
  lengths    (B,)                  live tokens per slot (0 = empty slot)
Slot b attends positions ``0 .. lengths[b]-1``; position p lives in pool
page ``pages[b, p // page_size]`` at offset ``p % page_size``.  Head-major
pages keep each block's last two dims equal to the pool's
(``(page_size, Dh)``, and ``(Hkv, page_size)`` for scales), which is what
Mosaic requires of a block narrower than one (8, 128) tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _paged_kernel(
    # scalar prefetch
    pages_ref,  # (B, num_page_slots) int32
    len_ref,  # (B,) int32
    # blocks
    q_ref,  # (1, Dh)
    k_ref,  # (page_size, Dh)
    v_ref,  # (page_size, Dh)
    *rest,  # [k_scale_ref, v_scale_ref,] o_ref, m_scr, l_scr, acc_scr
    scale: float,
    window: int | None,
    softcap: float,
    page_size: int,
    num_page_slots: int,
    n_heads: int,
    n_kv_heads: int,
    int8_kv: bool,
):
    if int8_kv:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    bh = pl.program_id(0)
    j = pl.program_id(1)
    b = bh // n_heads
    kv_head = (bh % n_heads) // (n_heads // n_kv_heads)
    length = len_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Live page: allocated AND overlaps [max(0, length-window), length).
    # The same predicate drives the index map (fetch scratch instead) — dead
    # pages are skipped end to end, which is what makes decode cost O(live).
    page_ok = (pages_ref[b, j] >= 0) & (j * page_size < length)
    if window is not None:
        page_ok &= (j + 1) * page_size > length - window

    @pl.when(page_ok)
    def _compute():
        q = q_ref[...].astype(jnp.float32)  # (1, Dh)
        k = k_ref[...].astype(jnp.float32)  # (page_size, Dh)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (1, page_size)
        if int8_kv:
            s = s * _head_row(ks_ref, kv_head, n_kv_heads)  # per-token k scales
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        ok = k_pos < length  # decode causality: q sits at position length-1
        if window is not None:
            ok &= k_pos > length - 1 - window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        pv = p * _head_row(vs_ref, kv_head, n_kv_heads) if int8_kv else p
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(j == num_page_slots - 1)
    def _finalize():
        # l == 0 (empty slot: every page dead) yields zeros, not NaN — the
        # engine ignores inactive slots' outputs.
        denom = jnp.maximum(l_scr[...], 1e-37)[:, None]
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _head_row(ref, head, n_rows: int):
    """Row ``head`` of a ``(n_rows, page_size)`` scale block as a
    ``(1, page_size)`` float32 row, selected by a mask-and-sum over the
    sublanes rather than a dynamic sublane slice."""
    block = ref[...].astype(jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
    return jnp.sum(jnp.where(rows == head, block, 0.0), axis=0, keepdims=True)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "interpret"),
)
def paged_attention(
    q: jnp.ndarray,  # (B, H, Dh)
    k_pool: jnp.ndarray,  # (n_pages + 1, Hkv, page_size, Dh)
    v_pool: jnp.ndarray,  # (n_pages + 1, Hkv, page_size, Dh)
    pages: jnp.ndarray,  # (B, num_page_slots) int32
    lengths: jnp.ndarray,  # (B,) int32
    k_scale: jnp.ndarray | None = None,  # (n_pages + 1, Hkv, page_size) for int8 pools
    v_scale: jnp.ndarray | None = None,
    *,
    window: int | None = None,
    softcap: float = 0.0,
    interpret: bool,
) -> jnp.ndarray:
    B, H, Dh = q.shape
    n_pages_p1, Hkv, page_size, _ = k_pool.shape
    num_page_slots = pages.shape[1]
    G = H // Hkv
    scratch_page = n_pages_p1 - 1
    int8_kv = k_pool.dtype == jnp.int8
    if int8_kv and (k_scale is None or v_scale is None):
        raise ValueError("int8 pools require k_scale/v_scale pools")

    out_dtype = q.dtype if not int8_kv else jnp.result_type(q.dtype, jnp.bfloat16)
    qh = q.reshape(B * H, 1, Dh)

    def q_index(bh, j, pages_ref, len_ref):
        return (bh, 0, 0)

    def kv_index(bh, j, pages_ref, len_ref):
        b = bh // H
        h = bh % H
        p = pages_ref[b, j]
        live = (p >= 0) & (j * page_size < len_ref[b])
        if window is not None:
            live &= (j + 1) * page_size > len_ref[b] - window
        return (jnp.where(live, p, scratch_page), h // G, 0, 0)

    def scale_index(bh, j, pages_ref, len_ref):
        page, _, _, _ = kv_index(bh, j, pages_ref, len_ref)
        return (page, 0, 0)

    in_specs = [
        pl.BlockSpec((None, 1, Dh), q_index),
        pl.BlockSpec((None, None, page_size, Dh), kv_index),
        pl.BlockSpec((None, None, page_size, Dh), kv_index),
    ]
    operands = [qh, k_pool, v_pool]
    if int8_kv:
        in_specs += [
            pl.BlockSpec((None, Hkv, page_size), scale_index),
            pl.BlockSpec((None, Hkv, page_size), scale_index),
        ]
        operands += [k_scale, v_scale]

    kernel = functools.partial(
        _paged_kernel,
        scale=Dh**-0.5,
        window=window,
        softcap=softcap,
        page_size=page_size,
        num_page_slots=num_page_slots,
        n_heads=H,
        n_kv_heads=Hkv,
        int8_kv=int8_kv,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * H, num_page_slots),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, 1, Dh), q_index),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),  # m (running max)
            pltpu.VMEM((1,), jnp.float32),  # l (running denom)
            pltpu.VMEM((1, Dh), jnp.float32),  # acc
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, 1, Dh), out_dtype),
        interpret=interpret,
    )(pages.astype(jnp.int32), lengths.astype(jnp.int32), *operands)
    return out.reshape(B, H, Dh)
