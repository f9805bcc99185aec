"""The plain reference: a dense GQA RoPE / SwiGLU / RMSNorm decoder in
``jax.numpy`` and float32, with no kernels, cache or batching.  It imports
nothing of the program.

It follows the published Llama-style block, with two conventions the program
states and the configuration files record: RMSNorm gains are stored as ``g`` in
``x * rsqrt(mean(x^2) + eps) * (1 + g)``, and RoPE rotates split halves
(``[x1, x2] -> [x1 cos - x2 sin, x2 cos + x1 sin]``, the layout of HF Llama's
``rotate_half``).

Weights come in the benchmark's own layout (``bench/model.py``): ``embed``
(V, d), ``final_norm`` (d,), ``lm_head`` (d, V) when the embedding is not tied,
and ``layers``, each leaf stacked over layers: ``norm1``/``norm2`` (L, d),
``wq`` (L, d, H*Dh), ``wk``/``wv`` (L, d, Hkv*Dh), ``wo`` (L, H*Dh, d),
``w_gate``/``w_up`` (L, d, F), ``w_down`` (L, F, d).

``precision`` is ``"f32"`` (every matrix product at ``highest``) or ``"fp8"``:
the control, whose matrix-product operands, and in the backward pass their
cotangents, are rounded to float8 e4m3 with one amax scale per tensor (the
precision step below the configurations' bfloat16 compute), then multiplied at
``highest``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F8_MAX = 448.0  # largest finite float8_e4m3fn


def _scaled_fp8(x):
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@jax.custom_vjp
def _round_fp8(x):
    """``x`` rounded to float8 e4m3 under one amax scale; its cotangent is
    rounded the same way, so the backward pass runs in float8 too (a plain
    cast would flush the small cotangents to zero)."""
    return _scaled_fp8(x)


_round_fp8.defvjp(lambda x: (_scaled_fp8(x), None), lambda _, g: (_scaled_fp8(g),))


def _mm(a, b, precision: str, spec: str | None = None):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "fp8":
        a, b = _round_fp8(a), _round_fp8(b)
    if spec is None:
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g.astype(jnp.float32))


def _rope(x, theta):
    """x: (S, heads, Dh) at positions 0..S-1."""
    S, _, Dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv  # (S, Dh/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : Dh // 2], x[..., Dh // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(h, w, m: dict, precision: str):
    S = h.shape[0]
    H, Hkv, Dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    G = H // Hkv
    x = _rmsnorm(h, w["norm1"], m["norm_eps"])
    q = _rope(_mm(x, w["wq"], precision).reshape(S, H, Dh), m["rope_theta"])
    k = _rope(_mm(x, w["wk"], precision).reshape(S, Hkv, Dh), m["rope_theta"])
    v = _mm(x, w["wv"], precision).reshape(S, Hkv, Dh)
    qg = q.reshape(S, Hkv, G, Dh)
    scores = _mm(qg, k, precision, "qhgd,khd->hgqk") * Dh**-0.5
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = _mm(probs, v, precision, "hgqk,khd->qhgd").reshape(S, H * Dh)
    h = h + _mm(att, w["wo"], precision)
    x = _rmsnorm(h, w["norm2"], m["norm_eps"])
    ff = jax.nn.silu(_mm(x, w["w_gate"], precision)) * _mm(x, w["w_up"], precision)
    return h + _mm(ff, w["w_down"], precision)


def logits(weights: dict, tokens, m: dict, precision: str = "f32"):
    """(S,) int tokens -> (S, V) float32 next-token logits."""
    h = jnp.take(weights["embed"].astype(jnp.float32), tokens, axis=0)

    @jax.checkpoint
    def body(h, w):
        return _layer(h, w, m, precision), None

    h, _ = jax.lax.scan(body, h, weights["layers"])
    h = _rmsnorm(h, weights["final_norm"], m["norm_eps"])
    head = weights["embed"].T if m["tie_embeddings"] else weights["lm_head"]
    return _mm(h, head, precision)


def token_loss_sum(weights: dict, inputs, targets, m: dict, precision: str = "f32"):
    """Summed next-token cross entropy of one (S,) row."""
    lg = logits(weights, inputs, m, precision)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).sum()


@functools.partial(jax.jit, static_argnames=("m_items", "precision"))
def _row_grad(weights, inputs, targets, m_items, precision):
    m = dict(m_items)
    return jax.value_and_grad(token_loss_sum)(weights, inputs, targets, m, precision)


def step_gradient(weights: dict, rows: list, m: dict, precision: str = "f32"):
    """Mean loss and gradient over every token of ``rows`` ([(inputs, targets)]),
    one row at a time so that the reference fits beside nothing else."""
    items = tuple(sorted(m.items()))
    total, grad, n = 0.0, None, 0
    for x, y in rows:
        loss, g = _row_grad(weights, jnp.asarray(x), jnp.asarray(y), items, precision)
        total = total + loss
        grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
        n += int(x.shape[0])
    return total / n, jax.tree.map(lambda a: a / n, grad)


def adamw_step(weights, mu, nu, grads, count: int, lr: float, opt: dict):
    """One AdamW step in float32.  Weight decay applies to the matrices (the
    embedding, the head and every projection); norm gains are exempt."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    c1, c2 = 1.0 - b1**count, 1.0 - b2**count
    decayed = {"embed", "lm_head", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}

    def upd(path, p, g, m_, v_):
        name = path[-1].key
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        step = (m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
        if name in decayed:
            step = step + wd * p
        return p - lr * step, m_, v_

    out = jax.tree_util.tree_map_with_path(upd, weights, grads, mu, nu)
    pick = lambda i: jax.tree.map(lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))  # noqa: E731
    return pick(0), pick(1), pick(2)
