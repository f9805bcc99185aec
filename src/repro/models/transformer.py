"""Decoder-LM assembly for every assigned architecture.

Layers execute in config order, but parameters are *stacked per repeating
pattern group* and the stack is traversed with ``lax.scan`` — one pattern's
HLO is compiled once regardless of depth (jamba: 9 scans over an 8-layer
superblock; gemma3: 10 scans over [5 local + 1 global] + a 2-layer tail).
Remat (``jax.checkpoint``) wraps the scan body, so activation memory is
O(pattern x chunk), the standard MaxText-style recipe.

Public entry points:
  init_params / forward / loss_fn          — training & prefill
  init_cache / decode_step                 — serving (1 token vs KV/SSM cache)
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models import mlp as mlp_lib
from repro.models import moe as moe_lib
from repro.models import rwkv as rwkv_lib
from repro.models.config import LayerSpec, ModelConfig
from repro.models.layers import dense_init, embed_init, norm_apply, rmsnorm_init

__all__ = [
    "init_params",
    "forward",
    "loss_fn",
    "init_cache",
    "decode_step",
    "prefill",
    "param_count",
]

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _norm_init(cfg: ModelConfig, dt):
    if cfg.norm == "rmsnorm":
        return rmsnorm_init(cfg.d_model, dt)
    from repro.models.layers import layernorm_init

    return layernorm_init(cfg.d_model, dt)


def _init_layer(key: jax.Array, cfg: ModelConfig, spec: LayerSpec) -> Params:
    dt = cfg.dtype("param")
    if spec.kind == "rwkv":
        return {"rwkv": rwkv_lib.init_rwkv(key, cfg)}
    k1, k2 = jax.random.split(key)
    p: Params = {"norm1": _norm_init(cfg, dt), "norm2": _norm_init(cfg, dt)}
    if cfg.post_block_norm:
        p["norm1_post"] = _norm_init(cfg, dt)
        p["norm2_post"] = _norm_init(cfg, dt)
    if spec.kind == "attn":
        p["mixer"] = attn_lib.init_attention(k1, cfg)
    elif spec.kind == "mamba":
        p["mixer"] = mamba_lib.init_mamba(k1, cfg)
    else:
        raise ValueError(spec.kind)
    p["ffn"] = moe_lib.init_moe(k2, cfg) if spec.moe else mlp_lib.init_mlp(k2, cfg)
    return p


def _init_pattern(key: jax.Array, cfg: ModelConfig, pattern) -> Params:
    keys = jax.random.split(key, len(pattern))
    return {f"layer{i}": _init_layer(k, cfg, s) for i, (k, s) in enumerate(zip(keys, pattern))}


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    k_embed, k_body, k_tail, k_head = jax.random.split(key, 4)
    dt = cfg.dtype("param")
    params: Params = {"embed": embed_init(k_embed, cfg.vocab_size, cfg.d_model, dt)}
    if cfg.n_repeats > 0:
        body_keys = jax.random.split(k_body, cfg.n_repeats)
        stacked = jax.vmap(lambda k: _init_pattern(k, cfg, cfg.block_pattern))(body_keys)
        params["body"] = stacked
    if cfg.tail_layers:
        params["tail"] = _init_pattern(k_tail, cfg, cfg.tail_layers)
    params["final_norm"] = _norm_init(cfg, dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.vocab_size, dt)
    return params


def param_count(params: Params) -> int:
    return int(sum(x.size for x in jax.tree.leaves(params)))


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _apply_layer(
    p: Params,
    *,
    spec: LayerSpec,
    h: jnp.ndarray,
    cfg: ModelConfig,
    attn_impl: str,
    wkv_impl: str,
    h_sharding=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One layer; returns (h, moe_aux_contribution)."""
    aux = jnp.zeros((), jnp.float32)
    if spec.kind == "rwkv":
        return (
            rwkv_lib.rwkv_train(p["rwkv"], h, cfg, wkv_impl=wkv_impl, h_sharding=h_sharding),
            aux,
        )
    # mixer sub-block
    hi = norm_apply(h, p["norm1"], cfg.norm, cfg.norm_eps)
    if spec.kind == "attn":
        mix = attn_lib.attention_train(p["mixer"], hi, cfg, spec.attn_type, impl=attn_impl)
    else:
        mix = mamba_lib.mamba_train(p["mixer"], hi, cfg)
    if cfg.post_block_norm:
        mix = norm_apply(mix, p["norm1_post"], cfg.norm, cfg.norm_eps)
    h = h + mix
    # ffn sub-block
    hi = norm_apply(h, p["norm2"], cfg.norm, cfg.norm_eps)
    if spec.moe:
        ffn, metrics = moe_lib.moe_apply(p["ffn"], hi, cfg)
        mo = cfg.moe
        aux = aux + mo.router_aux_weight * metrics["aux_loss"] + mo.router_z_weight * metrics["z_loss"]
    else:
        ffn = mlp_lib.mlp_apply(p["ffn"], hi, cfg)
    if cfg.post_block_norm:
        ffn = norm_apply(ffn, p["norm2_post"], cfg.norm, cfg.norm_eps)
    return h + ffn, aux


def _pattern_fn(cfg: ModelConfig, pattern, attn_impl: str, wkv_impl: str, h_sharding=None):
    """Apply one pattern group. Each *layer* is individually checkpointed so
    the backward pass holds one layer's residuals (and one layer's gathered
    FSDP weights) at a time — without this, an 8-layer jamba superblock keeps
    every layer's gathered expert weights + residuals live simultaneously."""

    def apply_pattern(block_params: Params, h: jnp.ndarray):
        aux = jnp.zeros((), jnp.float32)
        for i, spec in enumerate(pattern):
            layer_fn = partial(
                _apply_layer,
                spec=spec,
                cfg=cfg,
                attn_impl=attn_impl,
                wkv_impl=wkv_impl,
                h_sharding=h_sharding,
            )
            if cfg.remat and cfg.remat_policy != "none":
                layer_fn = jax.checkpoint(layer_fn)
            h, a = layer_fn(block_params[f"layer{i}"], h=h)
            aux = aux + a
        return h, aux

    return apply_pattern


_REMAT_POLICIES = {
    "none": None,
    "full": None,  # nothing saveable -> recompute everything
    "minimal": "dots",
}


def _maybe_remat(fn, cfg: ModelConfig):
    if not cfg.remat or cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "minimal":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)


def _wsc(x, sharding):
    """with_sharding_constraint if a sharding is provided (SPMD runs only —
    pure-CPU tests pass shardings=None and stay constraint-free)."""
    if sharding is None:
        return x
    return jax.lax.with_sharding_constraint(x, sharding)


def forward(
    params: Params,
    inputs: jnp.ndarray,
    cfg: ModelConfig,
    attn_impl: str = "blocked",
    wkv_impl: str = "chunked",
    shardings: dict | None = None,
    unroll: bool = False,
) -> tuple[jnp.ndarray, dict]:
    """inputs: int tokens (B, S) or, with cfg.embeds_input, embeddings (B, S, d).

    ``shardings``: optional {"h": NamedSharding for (B,S,d), "logits": for
    (B,S,V)} activation constraints.  Without them GSPMD is free to pick a
    replicated-batch feature-sharded layout, which costs ~batch_size x the
    activation memory (measured on jamba — see EXPERIMENTS.md §Perf).

    Returns (logits (B, S, V), metrics {"moe_aux": scalar}).
    """
    sh = shardings or {}
    cdt = cfg.dtype("compute")
    if cfg.embeds_input and inputs.dtype != jnp.int32 and inputs.ndim == 3:
        h = inputs.astype(cdt)
    else:
        h = jnp.take(params["embed"], inputs, axis=0).astype(cdt)
    if cfg.scale_embeddings:
        h = h * jnp.asarray(cfg.d_model**0.5, cdt)
    h = _wsc(h, sh.get("h"))

    aux_total = jnp.zeros((), jnp.float32)
    if cfg.n_repeats > 0:
        body_fn = _maybe_remat(_pattern_fn(cfg, cfg.block_pattern, attn_impl, wkv_impl, sh.get("h")), cfg)

        if unroll:
            # python loop over repeats: every op appears in the HLO, so
            # cost_analysis sees true FLOPs (lax.scan bodies are counted
            # once regardless of trip count) — used by the roofline bench.
            for rep in range(cfg.n_repeats):
                block = jax.tree.map(lambda x: x[rep], params["body"])
                h, a = body_fn(block, h)
                h = _wsc(h, sh.get("h"))
                aux_total = aux_total + a
        else:

            def scan_body(carry, block_params):
                h, aux = carry
                h, a = body_fn(block_params, h)
                return (_wsc(h, sh.get("h")), aux + a), None

            (h, aux_total), _ = jax.lax.scan(scan_body, (h, aux_total), params["body"])
    if cfg.tail_layers:
        tail_fn = _maybe_remat(_pattern_fn(cfg, cfg.tail_layers, attn_impl, wkv_impl, sh.get("h")), cfg)
        h, a = tail_fn(params["tail"], h)
        aux_total = aux_total + a

    h = norm_apply(h, params["final_norm"], cfg.norm, cfg.norm_eps)
    w_out = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = h @ w_out.astype(h.dtype)
    if cfg.final_logit_softcap > 0:
        c = cfg.final_logit_softcap
        logits = c * jnp.tanh(logits / c)
    logits = _wsc(logits, sh.get("logits"))
    return logits, {"moe_aux": aux_total}


def loss_fn(
    params: Params,
    batch: dict,
    cfg: ModelConfig,
    attn_impl: str = "blocked",
    wkv_impl: str = "chunked",
) -> tuple[jnp.ndarray, dict]:
    """Next-token cross entropy. batch: {"inputs", "targets", optional "mask"}.

    Returns (scalar loss incl. MoE aux, metrics). Loss is the *sum* over valid
    tokens divided by the valid count — exact under any task allocation (the
    paper's eq. 1 invariance relies on sample-count weighting).
    """
    logits, metrics = forward(params, batch["inputs"], cfg, attn_impl, wkv_impl)
    targets = batch["targets"]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones(targets.shape, jnp.float32)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    token_count = jnp.maximum(mask.sum(), 1.0)
    xent = -(ll * mask).sum() / token_count
    loss = xent + metrics["moe_aux"]
    out = {"xent": xent, "moe_aux": metrics["moe_aux"], "tokens": token_count}
    return loss, out


# ---------------------------------------------------------------------------
# decode (serving)
# ---------------------------------------------------------------------------


def _init_layer_cache(
    cfg: ModelConfig, spec: LayerSpec, batch: int, max_seq: int, per_slot: bool = False, paged=None
) -> Params:
    if spec.kind == "attn":
        if paged is not None:
            return attn_lib.init_paged_kv_cache(cfg, paged)
        window = cfg.windowed_cache and spec.attn_type == "local"
        c = attn_lib.init_kv_cache(cfg, batch, max_seq, window=window, per_slot=per_slot)
        del c["index"]  # tracked once at the top level
        return c
    if spec.kind == "mamba":
        return mamba_lib.init_mamba_cache(cfg, batch)
    return rwkv_lib.init_rwkv_cache(cfg, batch)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, per_slot: bool = False, paged=None) -> Params:
    """``per_slot=True``: the continuous-batching layout — ``index`` is (batch,)
    and attention ``pos`` tables are per-row, so each batch slot admits and
    retires independently (see ``repro.serve.engine``).  The default scalar
    ``index`` keeps the static lockstep layout.

    ``paged``: an ``attention.PagedLayout`` — attention layers keep shared
    fixed-size page pools instead of dense (batch, max_seq) buffers, and the
    cache gains a top-level page table ``pages`` (batch, pages_per_slot)
    shared by every layer (-1 = unallocated).  Requires ``per_slot=True``;
    recurrent (mamba/rwkv) layer caches are unchanged (their state is O(1)
    per slot already)."""
    if paged is not None and not per_slot:
        raise ValueError("paged cache layout requires per_slot=True")
    cache: Params = {"index": jnp.zeros((batch,) if per_slot else (), jnp.int32)}
    if paged is not None:
        cache["pages"] = jnp.full((batch, paged.pages_per_slot), -1, jnp.int32)
    if cfg.n_repeats > 0:
        per = [
            {
                f"layer{i}": _init_layer_cache(cfg, s, batch, max_seq, per_slot, paged)
                for i, s in enumerate(cfg.block_pattern)
            }
            for _ in range(cfg.n_repeats)
        ]
        cache["body"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per) if cfg.n_repeats > 1 else jax.tree.map(lambda x: x[None], per[0])
    if cfg.tail_layers:
        cache["tail"] = {
            f"layer{i}": _init_layer_cache(cfg, s, batch, max_seq, per_slot, paged)
            for i, s in enumerate(cfg.tail_layers)
        }
    return cache


def _decode_layer(p, spec: LayerSpec, h, layer_cache, index, cfg: ModelConfig, pages=None):
    if spec.kind == "rwkv":
        return rwkv_lib.rwkv_decode(p["rwkv"], h, layer_cache, cfg)
    hi = norm_apply(h, p["norm1"], cfg.norm, cfg.norm_eps)
    if spec.kind == "attn":
        c = dict(layer_cache, index=index)
        if pages is not None and "k_pool" in layer_cache:
            c["pages"] = pages
        mix, c2 = attn_lib.attention_decode(p["mixer"], hi, c, cfg, spec.attn_type)
        new_cache = {k: v for k, v in c2.items() if k not in ("index", "pages")}
    else:
        mix, new_cache = mamba_lib.mamba_decode(p["mixer"], hi, layer_cache, cfg)
    if cfg.post_block_norm:
        mix = norm_apply(mix, p["norm1_post"], cfg.norm, cfg.norm_eps)
    h = h + mix
    hi = norm_apply(h, p["norm2"], cfg.norm, cfg.norm_eps)
    if spec.moe:
        ffn, _ = moe_lib.moe_apply(p["ffn"], hi, cfg, group_size=hi.shape[0] * hi.shape[1])
    else:
        ffn = mlp_lib.mlp_apply(p["ffn"], hi, cfg)
    if cfg.post_block_norm:
        ffn = norm_apply(ffn, p["norm2_post"], cfg.norm, cfg.norm_eps)
    return h + ffn, new_cache


def decode_step(
    params: Params,
    cache: Params,
    tokens: jnp.ndarray,
    cfg: ModelConfig,
    shardings: dict | None = None,
    unroll: bool = False,
) -> tuple[jnp.ndarray, Params]:
    """One serving step: tokens (B,) int32 (or (B, d) embeds) -> (logits (B, V), cache')."""
    sh = shardings or {}
    cdt = cfg.dtype("compute")
    if cfg.embeds_input and tokens.ndim == 2:
        h = tokens[:, None, :].astype(cdt)
    else:
        h = jnp.take(params["embed"], tokens[:, None], axis=0).astype(cdt)
    if cfg.scale_embeddings:
        h = h * jnp.asarray(cfg.d_model**0.5, cdt)
    h = _wsc(h, sh.get("h"))
    index = cache["index"]
    pages = cache.get("pages")  # paged KV layout: (B, P_max) table, else None

    new_cache: Params = {"index": index + 1}
    if pages is not None:
        new_cache["pages"] = pages  # read-only in the step; the engine owns it
    if cfg.n_repeats > 0:
        # The cache rides in the scan CARRY (not xs/ys): carries can alias
        # in-place, so the multi-GB KV cache is updated rather than copied —
        # scan ys would force a second full cache allocation per step.

        def scan_body(carry, xs):
            h, body_cache = carry
            block_params, rep = xs
            block_cache = jax.tree.map(
                lambda c: jax.lax.dynamic_index_in_dim(c, rep, 0, keepdims=False), body_cache
            )
            new_block_cache = {}
            for i, spec in enumerate(cfg.block_pattern):
                key = f"layer{i}"
                h, nc = _decode_layer(block_params[key], spec, h, block_cache[key], index, cfg, pages)
                new_block_cache[key] = nc
            body_cache = jax.tree.map(
                lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n.astype(c.dtype), rep, 0),
                body_cache,
                new_block_cache,
            )
            return (h, body_cache), None

        if unroll:  # roofline accounting (see forward)
            carry = (h, cache["body"])
            for rep in range(cfg.n_repeats):
                block = jax.tree.map(lambda x: x[rep], params["body"])
                carry, _ = scan_body(carry, (block, jnp.int32(rep)))
            h, nb = carry
        else:
            (h, nb), _ = jax.lax.scan(
                scan_body,
                (h, cache["body"]),
                (params["body"], jnp.arange(cfg.n_repeats)),
            )
        new_cache["body"] = nb
    if cfg.tail_layers:
        new_cache["tail"] = {}
        for i, spec in enumerate(cfg.tail_layers):
            key = f"layer{i}"
            h, nc = _decode_layer(params["tail"][key], spec, h, cache["tail"][key], index, cfg, pages)
            new_cache["tail"][key] = nc

    h = norm_apply(h, params["final_norm"], cfg.norm, cfg.norm_eps)
    w_out = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (h @ w_out.astype(h.dtype))[:, 0]
    if cfg.final_logit_softcap > 0:
        c = cfg.final_logit_softcap
        logits = c * jnp.tanh(logits / c)
    logits = _wsc(logits, sh.get("logits"))
    return logits, new_cache


# ---------------------------------------------------------------------------
# prefill (serving: whole prompt -> cache in one jitted forward)
# ---------------------------------------------------------------------------


def _prefill_layer(p, spec: LayerSpec, h, layer_cache, lengths, cfg: ModelConfig, attn_impl, wkv_impl):
    if spec.kind == "rwkv":
        return rwkv_lib.rwkv_prefill(p["rwkv"], h, cfg, lengths, wkv_impl=wkv_impl)
    hi = norm_apply(h, p["norm1"], cfg.norm, cfg.norm_eps)
    if spec.kind == "attn":
        mix, new_cache = attn_lib.attention_prefill(
            p["mixer"], hi, layer_cache, cfg, spec.attn_type, lengths, impl=attn_impl
        )
    else:
        mix, new_cache = mamba_lib.mamba_prefill(p["mixer"], hi, cfg, lengths)
    if cfg.post_block_norm:
        mix = norm_apply(mix, p["norm1_post"], cfg.norm, cfg.norm_eps)
    h = h + mix
    hi = norm_apply(h, p["norm2"], cfg.norm, cfg.norm_eps)
    if spec.moe:
        ffn, _ = moe_lib.moe_apply(p["ffn"], hi, cfg)
    else:
        ffn = mlp_lib.mlp_apply(p["ffn"], hi, cfg)
    if cfg.post_block_norm:
        ffn = norm_apply(ffn, p["norm2_post"], cfg.norm, cfg.norm_eps)
    return h + ffn, new_cache


def prefill(
    params: Params,
    cache: Params,
    tokens: jnp.ndarray,
    lengths: jnp.ndarray,
    cfg: ModelConfig,
    attn_impl: str = "naive",
    wkv_impl: str = "chunked",
) -> tuple[jnp.ndarray, Params]:
    """Batched prompt-parallel prefill: ONE forward over the whole (padded)
    prompt writes every layer's cache — replaces the token-at-a-time prefill
    loop the old serve driver ran (S jitted dispatches -> 1).

    tokens: (B, S_p) int32 right-padded prompts, or (B, S_p, d) embeddings
    with ``cfg.embeds_input``; lengths: (B,) valid counts (>= 1, <= S_p);
    cache: per-slot cache from ``init_cache(..., per_slot=True)``.  Attention
    layers attend in parallel (causality keeps pad columns inert); recurrent
    layers (mamba / rwkv) freeze their state at each row's last real token.

    Returns (logits at each row's last real token (B, V), cache' with
    ``index == lengths``).
    """
    assert cache["index"].ndim == 1, "prefill requires a per-slot cache (init_cache(per_slot=True))"
    cdt = cfg.dtype("compute")
    if cfg.embeds_input and tokens.dtype != jnp.int32 and tokens.ndim == 3:
        h = tokens.astype(cdt)
    else:
        h = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
    if cfg.scale_embeddings:
        h = h * jnp.asarray(cfg.d_model**0.5, cdt)
    B = h.shape[0]
    lengths = lengths.astype(jnp.int32)

    new_cache: Params = {"index": lengths}
    if cfg.n_repeats > 0:
        # Cache in the scan carry for the same aliasing reason as decode_step.

        def scan_body(carry, xs):
            h, body_cache = carry
            block_params, rep = xs
            block_cache = jax.tree.map(
                lambda c: jax.lax.dynamic_index_in_dim(c, rep, 0, keepdims=False), body_cache
            )
            new_block_cache = {}
            for i, spec in enumerate(cfg.block_pattern):
                key = f"layer{i}"
                h, nc = _prefill_layer(
                    block_params[key], spec, h, block_cache[key], lengths, cfg, attn_impl, wkv_impl
                )
                new_block_cache[key] = nc
            body_cache = jax.tree.map(
                lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n.astype(c.dtype), rep, 0),
                body_cache,
                new_block_cache,
            )
            return (h, body_cache), None

        (h, nb), _ = jax.lax.scan(
            scan_body,
            (h, cache["body"]),
            (params["body"], jnp.arange(cfg.n_repeats)),
        )
        new_cache["body"] = nb
    if cfg.tail_layers:
        new_cache["tail"] = {}
        for i, spec in enumerate(cfg.tail_layers):
            key = f"layer{i}"
            h, nc = _prefill_layer(
                params["tail"][key], spec, h, cache["tail"][key], lengths, cfg, attn_impl, wkv_impl
            )
            new_cache["tail"][key] = jax.tree.map(
                lambda c, n: n.astype(c.dtype), cache["tail"][key], nc
            )

    h = norm_apply(h, params["final_norm"], cfg.norm, cfg.norm_eps)
    last = jnp.take_along_axis(h, jnp.broadcast_to((lengths - 1)[:, None, None], (B, 1, h.shape[-1])), axis=1)
    w_out = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (last @ w_out.astype(last.dtype))[:, 0]
    if cfg.final_logit_softcap > 0:
        c = cfg.final_logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits, new_cache
