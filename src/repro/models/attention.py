"""Attention: GQA/MQA/MHA, causal + sliding-window, train / prefill / decode.

Four implementations (numerically equivalent, tested):

* ``naive``   — materializes (Sq, Sk) scores. Oracle + tiny smoke tests.
* ``blocked`` — pure-JAX flash algorithm: double scan over (q-chunk, kv-chunk)
  with online softmax, differentiated by autodiff. Bounded memory; it trains
  on every backend the kernel below does not take, and is what the dry-run
  lowers for large shapes.
* ``splash``  — training on a TPU: the shipped Pallas splash kernel with its
  own backward (``repro.kernels.splash_attention``).  The train step picks it
  where it is built (``dist/hetero_step.py``); :func:`attention_train` then
  falls back to ``blocked`` per call for a sequence that is not a multiple
  of 128.
* ``flash``   — Pallas TPU forward kernel (``repro.kernels.flash_attention``)
  for serving prefill.

Pallas kernels compile on TPU and run interpreted elsewhere
(``repro.kernels.ops``), wired lazily to avoid import cycles.

GQA avoids materializing repeated KV heads by grouping query heads:
q is viewed as (B, S, Hkv, G, Dh) and contracted against k (B, S, Hkv, Dh).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import dense_init, linear, rmsnorm, rmsnorm_init
from repro.models.rope import apply_rope

__all__ = [
    "PagedLayout",
    "paged_put",
    "init_attention",
    "attention_train",
    "attention_decode",
    "attention_prefill",
]

NEG_INF = -2.0e38  # large finite; avoids NaN from (-inf) - (-inf)


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Paged KV-cache geometry (see ``repro.kernels.paged_attention``).

    ``n_pages`` fixed-size pages (of ``page_size`` tokens each) live in one
    pool shared by every slot; each slot addresses up to ``pages_per_slot``
    of them through its page-table row, so a slot's context is bounded by
    pool capacity — not by a per-slot ``max_seq`` reservation.  Pools are
    allocated with one extra trailing *scratch* page that absorbs writes
    from slots with no allocated page (inactive slots keep decoding)."""

    page_size: int = 8
    n_pages: int = 32
    pages_per_slot: int = 0  # 0 -> n_pages (a slot may use the whole pool)

    def __post_init__(self) -> None:
        if self.page_size < 1 or self.n_pages < 1:
            raise ValueError(f"bad paged layout {self}")
        if self.pages_per_slot == 0:
            object.__setattr__(self, "pages_per_slot", self.n_pages)
        if self.pages_per_slot > self.n_pages:
            raise ValueError("pages_per_slot cannot exceed n_pages")

    @property
    def max_tokens_per_slot(self) -> int:
        return self.pages_per_slot * self.page_size

    def pages_for(self, tokens: int) -> int:
        return -(-tokens // self.page_size)


def init_attention(key: jax.Array, cfg: ModelConfig) -> dict[str, Any]:
    kq, kk, kv, ko = jax.random.split(key, 4)
    dt = cfg.dtype("param")
    p = {
        "wq": dense_init(kq, cfg.d_model, cfg.q_dim, dt),
        "wk": dense_init(kk, cfg.d_model, cfg.kv_dim, dt),
        "wv": dense_init(kv, cfg.d_model, cfg.kv_dim, dt),
        "wo": dense_init(ko, cfg.q_dim, cfg.d_model, dt, scale=(cfg.q_dim * 2 * cfg.n_layers) ** -0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(cfg.head_dim, dt)
        p["k_norm"] = rmsnorm_init(cfg.head_dim, dt)
    return p


def _qkv(p: dict, x: jnp.ndarray, cfg: ModelConfig, positions: jnp.ndarray):
    B, S, _ = x.shape
    q = linear(x, p["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = linear(x, p["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = linear(x, p["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mask_bias(q_pos: jnp.ndarray, k_pos: jnp.ndarray, window: int | None) -> jnp.ndarray:
    """(Sq, Sk) additive bias: 0 where k may attend, NEG_INF otherwise."""
    causal = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        causal &= k_pos[None, :] > (q_pos[:, None] - window)
    return jnp.where(causal, 0.0, NEG_INF).astype(jnp.float32)


def _softcap(scores: jnp.ndarray, cap: float) -> jnp.ndarray:
    if cap <= 0.0:
        return scores
    return cap * jnp.tanh(scores / cap)


# ---------------------------------------------------------------------------
# naive (oracle)
# ---------------------------------------------------------------------------


def _attend_naive(q, k, v, q_pos, k_pos, cfg: ModelConfig, window):
    B, Sq, H, Dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Sq, Hkv, G, Dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * (Dh**-0.5)
    scores = _softcap(scores, cfg.attn_logit_softcap)
    scores = scores + _mask_bias(q_pos, k_pos, window)[None, None, None]
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, H, Dh)


# ---------------------------------------------------------------------------
# blocked (pure-JAX flash; default for large shapes)
# ---------------------------------------------------------------------------


def _attend_blocked(q, k, v, q_pos, k_pos, cfg: ModelConfig, window, q_chunk=512, kv_chunk=512):
    """Online-softmax double scan. Memory O(q_chunk * kv_chunk) scores."""
    B, Sq, H, Dh = q.shape
    Sk = k.shape[1]
    Hkv = k.shape[2]
    G = H // Hkv
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    assert Sq % q_chunk == 0 and Sk % kv_chunk == 0, (Sq, q_chunk, Sk, kv_chunk)
    nq, nk = Sq // q_chunk, Sk // kv_chunk
    scale = Dh**-0.5

    qg = q.reshape(B, nq, q_chunk, Hkv, G, Dh).astype(jnp.float32)
    kc = k.reshape(B, nk, kv_chunk, Hkv, Dh).astype(jnp.float32)
    vc = v.reshape(B, nk, kv_chunk, Hkv, Dh).astype(jnp.float32)
    qp = q_pos.reshape(nq, q_chunk)
    kp = k_pos.reshape(nk, kv_chunk)

    def q_step(_, qi):
        qblk = qg[:, qi]  # (B, qc, Hkv, G, Dh)
        qpos = qp[qi]

        def kv_step(carry, ki):
            m, l, acc = carry
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk, kc[:, ki]) * scale
            s = _softcap(s, cfg.attn_logit_softcap)
            s = s + _mask_bias(qpos, kp[ki], window)[None, None, None]
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, vc[:, ki])
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_chunk, Dh), jnp.float32)
        # checkpoint: recompute the (qc, kc) score block in the backward pass
        # instead of saving it (flash-attention-style bwd; the score tensors
        # otherwise dominate activation memory at long seq).
        (m, l, acc), _ = jax.lax.scan(jax.checkpoint(kv_step), (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-37)[..., None]  # (B,Hkv,G,qc,Dh)
        return None, out.transpose(0, 3, 1, 2, 4)  # (B,qc,Hkv,G,Dh)

    _, outs = jax.lax.scan(jax.checkpoint(q_step), None, jnp.arange(nq))  # (nq,B,qc,Hkv,G,Dh)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, Dh)
    return out.astype(v.dtype)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def _attend(q, k, v, q_pos, k_pos, cfg: ModelConfig, window, impl: str):
    if impl == "naive":
        return _attend_naive(q, k, v, q_pos, k_pos, cfg, window)
    if impl == "blocked":
        return _attend_blocked(q, k, v, q_pos, k_pos, cfg, window)
    if impl == "splash":
        from repro.kernels import ops as kops  # lazy: avoid import cycle

        return kops.splash_attention(q, k, v, window=window, softcap=cfg.attn_logit_softcap)
    if impl == "flash":
        from repro.kernels import ops as kops  # lazy: avoid import cycle

        return kops.flash_attention(
            q, k, v, q_pos, k_pos,
            causal=True,
            window=window,
            softcap=cfg.attn_logit_softcap,
        )
    raise ValueError(f"unknown attention impl {impl!r}")


def attention_train(
    p: dict,
    x: jnp.ndarray,
    cfg: ModelConfig,
    attn_type: str,
    impl: str = "blocked",
) -> jnp.ndarray:
    """Full-sequence (training / prefill) attention. x: (B, S, d) -> (B, S, d).

    The splash kernel takes a sequence that is a multiple of 128 (causal,
    with or without window and softcap); any other length trains on the
    blocked scan."""
    B, S, _ = x.shape
    if impl == "splash" and S % 128:
        impl = "blocked"
    positions = jnp.arange(S)
    window = cfg.sliding_window if attn_type == "local" else None
    q, k, v = _qkv(p, x, cfg, positions)
    out = _attend(q, k, v, positions, positions, cfg, window, impl)
    return linear(out.reshape(B, S, cfg.q_dim), p["wo"])


def attention_decode(
    p: dict,
    x: jnp.ndarray,
    cache: dict,
    cfg: ModelConfig,
    attn_type: str,
) -> tuple[jnp.ndarray, dict]:
    """One-token decode against a (possibly ring-buffer) KV cache.

    x: (B, 1, d); cache: {"k","v": (B, S_cache, Hkv, Dh), "pos", "index"}.
    ``index`` is either a scalar (static batch: all rows share one position,
    ``pos`` is (S_cache,)) or a vector (B,) of independent per-slot positions
    (continuous batching: ``pos`` is (B, S_cache) and every row admits /
    retires on its own clock).  ``S_cache`` may be smaller than the context
    (windowed local-attention cache): entries live at slot ``pos % S_cache``
    and ``pos`` records each slot's absolute position (-1 = empty), so
    masking is exact across wraparound.  Returns (out (B,1,d), new cache).

    Paged layout: when the cache carries pools (``k_pool``/``v_pool``) and a
    page table (``pages``), the new token's K/V scatters into the slot's
    current page and attention runs through the Pallas ragged paged kernel —
    per-slot cost proportional to live tokens (see ``_decode_paged``).
    """
    B, one, _ = x.shape
    assert one == 1, "decode expects a single new token"
    if "k_pool" in cache:
        return _decode_paged(p, x, cache, cfg, attn_type)
    index = cache["index"]
    per_slot = index.ndim == 1
    if per_slot:
        positions = index[:, None]
    else:
        positions = jnp.broadcast_to(jnp.reshape(index, (1, 1)), (B, 1))
    q, k_new, v_new = _qkv(p, x, cfg, positions)

    S_cache = cache["k"].shape[1]
    slot = jnp.mod(index, S_cache)
    if per_slot:
        bidx = jnp.arange(B)

        def put(buf, new):  # new: (B, 1, ...) -> row-wise scatter at each slot
            return buf.at[bidx, slot].set(new[:, 0].astype(buf.dtype))

        pos = cache["pos"].at[bidx, slot].set(index)
    else:

        def put(buf, new):
            return jax.lax.dynamic_update_slice_in_dim(buf, new.astype(buf.dtype), slot, axis=1)

        pos = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], jnp.reshape(index, (1,)), slot, axis=0
        )
    int8_kv = cache["k"].dtype == jnp.int8
    if int8_kv:
        k_q, k_s = _quant_int8(k_new)
        v_q, v_s = _quant_int8(v_new)
        k_i = put(cache["k"], k_q)
        v_i = put(cache["v"], v_q)
        ks = put(cache["k_scale"], k_s)
        vs = put(cache["v_scale"], v_s)
        k = k_i.astype(jnp.bfloat16) * ks[..., None]
        v = v_i.astype(jnp.bfloat16) * vs[..., None]
    else:
        k = put(cache["k"], k_new)
        v = put(cache["v"], v_new)

    Hkv = cfg.n_kv_heads
    G = cfg.n_heads // Hkv
    Dh = cfg.head_dim
    qg = q.reshape(B, 1, Hkv, G, Dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * (Dh**-0.5)
    scores = _softcap(scores, cfg.attn_logit_softcap)
    bound = index[:, None] if per_slot else index
    valid = (pos >= 0) & (pos <= bound)  # (S_cache,) or (B, S_cache)
    if attn_type == "local":
        valid &= pos > (bound - cfg.sliding_window)
    vmask = valid[:, None, None, None, :] if per_slot else valid[None, None, None, None]
    scores = jnp.where(vmask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v).reshape(B, 1, cfg.q_dim)
    new_cache = {"pos": pos, "index": index + 1}
    if int8_kv:
        new_cache.update(k=k_i, v=v_i, k_scale=ks, v_scale=vs)
    else:
        new_cache.update(k=k, v=v)
    return linear(out.astype(x.dtype), p["wo"]), new_cache


def paged_put(pool: jnp.ndarray, dest: jnp.ndarray, off: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """Scatter per-token rows into a paged pool: row n lands in page
    ``dest[n]`` at offset ``off[n]``.  ``rows`` is (N, Hkv, Dh) for a k/v pool
    (n_pages+1, Hkv, page_size, Dh) or (N, Hkv) for an int8 scale pool
    (n_pages+1, Hkv, page_size)."""
    return pool.at[dest, :, off].set(rows.astype(pool.dtype))


def _decode_paged(
    p: dict,
    x: jnp.ndarray,
    cache: dict,
    cfg: ModelConfig,
    attn_type: str,
) -> tuple[jnp.ndarray, dict]:
    """One-token decode against a paged KV pool.

    cache: {"k_pool","v_pool": (n_pages+1, Hkv, page_size, Dh) [+ int8 scale
    pools (n_pages+1, Hkv, page_size)], "pages": (B, P_max) int32,
    "index": (B,)}.  The new token's K/V
    is scattered into the slot's page for position ``index`` (slots without
    an allocated page — inactive slots — write the trailing scratch page),
    then the ragged paged-attention kernel attends positions 0..index.
    Returns (out (B,1,d), new cache pieces {k_pool, v_pool[, scales]})."""
    B = x.shape[0]
    index = cache["index"]
    pages = cache["pages"]
    assert index.ndim == 1, "paged decode requires a per-slot cache (index (B,))"
    positions = index[:, None]
    q, k_new, v_new = _qkv(p, x, cfg, positions)

    k_pool = cache["k_pool"]
    page_size = k_pool.shape[2]
    scratch_page = k_pool.shape[0] - 1
    bidx = jnp.arange(B)
    pslot = jnp.clip(index // page_size, 0, pages.shape[1] - 1)
    pg = pages[bidx, pslot]
    # Unallocated (-1) -> scratch page: inactive slots keep decoding but their
    # writes land in garbage space and their reads are masked by the kernel.
    dest = jnp.where(pg >= 0, pg, scratch_page)
    off = index % page_size

    int8_kv = k_pool.dtype == jnp.int8
    k_scale = v_scale = None
    if int8_kv:
        k_q, k_s = _quant_int8(k_new)
        v_q, v_s = _quant_int8(v_new)
        k_pool = paged_put(k_pool, dest, off, k_q[:, 0])
        v_pool = paged_put(cache["v_pool"], dest, off, v_q[:, 0])
        k_scale = paged_put(cache["k_scale_pool"], dest, off, k_s[:, 0])
        v_scale = paged_put(cache["v_scale_pool"], dest, off, v_s[:, 0])
    else:
        k_pool = paged_put(k_pool, dest, off, k_new[:, 0])
        v_pool = paged_put(cache["v_pool"], dest, off, v_new[:, 0])

    from repro.kernels import ops as kops  # lazy: avoid import cycle

    window = cfg.sliding_window if attn_type == "local" else None
    out = kops.paged_attention(
        q[:, 0],  # (B, H, Dh)
        k_pool,
        v_pool,
        pages,
        index + 1,  # live tokens incl. the one just written
        k_scale,
        v_scale,
        window=window,
        softcap=cfg.attn_logit_softcap,
    )
    out = out.reshape(B, 1, cfg.q_dim)
    new_cache = {"k_pool": k_pool, "v_pool": v_pool}
    if int8_kv:
        new_cache.update(k_scale_pool=k_scale, v_scale_pool=v_scale)
    return linear(out.astype(x.dtype), p["wo"]), new_cache


def attention_prefill(
    p: dict,
    x: jnp.ndarray,
    cache: dict,
    cfg: ModelConfig,
    attn_type: str,
    lengths: jnp.ndarray,
    impl: str = "naive",
) -> tuple[jnp.ndarray, dict]:
    """Prompt-parallel prefill: one full-sequence attention over the padded
    prompt, then a collision-free scatter of K/V into the (possibly
    ring-buffer) per-slot cache.

    x: (B, S_p, d) right-padded prompts; lengths: (B,) valid counts (>= 1);
    cache: per-slot KV cache (``pos`` of shape (B, S_cache)).  Right padding
    keeps RoPE positions at 0..L-1 and causality keeps pad rows out of real
    rows' outputs.  Returns (out (B, S_p, d), new cache pieces).
    """
    B, S, _ = x.shape
    positions = jnp.arange(S)
    q, k, v = _qkv(p, x, cfg, positions)
    window = cfg.sliding_window if attn_type == "local" else None
    out = _attend(q, k, v, positions, positions, cfg, window, impl)
    out = linear(out.reshape(B, S, cfg.q_dim), p["wo"])

    S_cache = cache["k"].shape[1]
    s_idx = jnp.arange(S_cache)[None, :]  # (1, S_cache)
    L = lengths[:, None]  # (B, 1)
    # Ring slot s holds the NEWEST prompt position congruent to s mod S_cache:
    # p_win = s + floor((L-1-s)/S_cache)*S_cache (or -1 when the row has no
    # entry for that slot).  Expressing the scatter as a gather makes ring
    # wraparound (S_p > S_cache) collision-free — jnp scatter order on
    # duplicate indices is unspecified.
    p_win = jnp.where(L > s_idx, s_idx + ((L - 1 - s_idx) // S_cache) * S_cache, -1)
    gidx = jnp.clip(p_win, 0, S - 1)
    keep = p_win >= 0

    def gather(src, buf):
        shp = (B, S_cache) + (1,) * (src.ndim - 2)
        g = jnp.take_along_axis(src, gidx.reshape(shp), axis=1)
        return jnp.where(keep.reshape(shp), g, 0).astype(buf.dtype)

    new_cache = {"pos": p_win.astype(jnp.int32)}
    if cache["k"].dtype == jnp.int8:
        k_q, k_s = _quant_int8(k)
        v_q, v_s = _quant_int8(v)
        new_cache.update(
            k=gather(k_q, cache["k"]),
            v=gather(v_q, cache["v"]),
            k_scale=gather(k_s, cache["k_scale"]),
            v_scale=gather(v_s, cache["v_scale"]),
        )
    else:
        new_cache.update(k=gather(k, cache["k"]), v=gather(v, cache["v"]))
    return out, new_cache


def _quant_int8(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-(batch, position, head) int8 quantization.

    x: (B, S, H, Dh) -> (int8 same shape, bf16 scales (B, S, H))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def init_paged_kv_cache(cfg: ModelConfig, layout: PagedLayout, dtype=None) -> dict:
    """One attention layer's paged KV pool: ``layout.n_pages`` shared pages
    plus a trailing scratch page (writes from slots with no allocated page).
    The page table ("pages") and position clock ("index") are tracked once at
    the cache's top level — every layer shares the same allocation pattern."""
    dt = dtype or cfg.dtype("compute")
    shape = (layout.n_pages + 1, cfg.n_kv_heads, layout.page_size, cfg.head_dim)
    if cfg.kv_cache_dtype == "int8":
        scale_shape = (layout.n_pages + 1, cfg.n_kv_heads, layout.page_size)
        return {
            "k_pool": jnp.zeros(shape, jnp.int8),
            "v_pool": jnp.zeros(shape, jnp.int8),
            "k_scale_pool": jnp.zeros(scale_shape, jnp.bfloat16),
            "v_scale_pool": jnp.zeros(scale_shape, jnp.bfloat16),
        }
    return {"k_pool": jnp.zeros(shape, dt), "v_pool": jnp.zeros(shape, dt)}


def init_kv_cache(
    cfg: ModelConfig, batch: int, max_seq: int, dtype=None, window: bool = False, per_slot: bool = False
) -> dict:
    """``window=True``: ring buffer of sliding_window slots (local layers).
    ``per_slot=True``: each batch row keeps its own position bookkeeping
    (``pos`` (batch, S_cache), ``index`` (batch,)) so rows advance
    independently — the continuous-batching layout."""
    dt = dtype or cfg.dtype("compute")
    s_cache = min(max_seq, cfg.sliding_window) if window else max_seq
    cache = {
        "pos": jnp.full((batch, s_cache) if per_slot else (s_cache,), -1, jnp.int32),
        "index": jnp.zeros((batch,) if per_slot else (), jnp.int32),
    }
    if cfg.kv_cache_dtype == "int8":
        cache["k"] = jnp.zeros((batch, s_cache, cfg.n_kv_heads, cfg.head_dim), jnp.int8)
        cache["v"] = jnp.zeros((batch, s_cache, cfg.n_kv_heads, cfg.head_dim), jnp.int8)
        cache["k_scale"] = jnp.zeros((batch, s_cache, cfg.n_kv_heads), jnp.bfloat16)
        cache["v_scale"] = jnp.zeros((batch, s_cache, cfg.n_kv_heads), jnp.bfloat16)
    else:
        cache["k"] = jnp.zeros((batch, s_cache, cfg.n_kv_heads, cfg.head_dim), dt)
        cache["v"] = jnp.zeros((batch, s_cache, cfg.n_kv_heads, cfg.head_dim), dt)
    return cache
