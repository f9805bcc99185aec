"""Training attention: the splash-kernel path against float32 naive attention
(the kernel runs in the Pallas interpreter on the CPU), where the step and
the per-call dispatcher choose it, and the tally of lowered paths."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.dist.hetero_step import train_attention
from repro.models import ModelConfig, transformer
from repro.models import attention as attn_lib
from repro.models.config import LayerSpec, MambaConfig

B, S, H, HKV, DH = 1, 256, 6, 2, 64
# mask kind -> (sliding window, logit softcap); the window is shorter than a
# 128 block, so blocks are skipped, partly masked and whole
MASKS = {"causal": (None, 0.0), "window": (96, 0.0), "softcap": (None, 2.0)}


def _rel(a, ref) -> float:
    return float(jnp.linalg.norm(a.astype(jnp.float32) - ref) / jnp.linalg.norm(ref))


@pytest.mark.parametrize("mask", MASKS)
def test_splash_path_matches_float32_naive(mask):
    """Output and q/k/v gradients of the kernel path lie within twice the
    blocked path's error against float32 naive attention on the same bf16
    inputs."""
    window, cap = MASKS[mask]
    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=H * DH, n_heads=H, n_kv_heads=HKV,
                      d_ff=64, vocab_size=101, attn_logit_softcap=cap)
    kq, kk, kv, ko = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (B, S, H, DH), jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, HKV, DH), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, HKV, DH), jnp.bfloat16)
    dout = jax.random.normal(ko, (B, S, H, DH), jnp.float32)
    pos = jnp.arange(S)

    def with_grads(f):
        out, vjp = jax.vjp(f, q, k, v)
        return (out, *vjp(dout.astype(out.dtype)))

    f32 = lambda *xs: [x.astype(jnp.float32) for x in xs]  # noqa: E731
    ref = with_grads(lambda q, k, v: attn_lib._attend_naive(*f32(q, k, v), pos, pos, cfg, window))
    kernel = with_grads(lambda q, k, v: attn_lib._attend(q, k, v, pos, pos, cfg, window, "splash"))
    blocked = with_grads(lambda q, k, v: attn_lib._attend_blocked(q, k, v, pos, pos, cfg, window, 128, 128))
    for name, got, base, want in zip(("out", "dq", "dk", "dv"), kernel, blocked, ref):
        assert got.dtype == base.dtype
        err, base_err = _rel(got, want), _rel(base, want)
        assert err <= 2 * base_err, (name, err, base_err)


# (platform, devices, mode, sequence, positions given) -> the path lowered
CHOICES = {
    "cpu-backend": ("cpu", 1, "masked", 256, False, "blocked"),
    "tpu-one-chip": ("tpu", 1, "masked", 256, False, "splash"),
    "tpu-length-not-multiple-of-128": ("tpu", 1, "masked", 200, False, "blocked"),
    "tpu-positions-given": ("tpu", 1, "masked", 256, True, "blocked"),
    "tpu-masked-multi-device": ("tpu", 4, "masked", 256, False, "blocked"),
    "tpu-while-multi-device": ("tpu", 4, "while", 256, False, "splash"),
}


@pytest.mark.parametrize("case", CHOICES)
def test_attention_path_choice(case):
    """The step's choice from the mesh, then the dispatcher's per-call check
    from the shapes, as the tally records it (traced only, nothing runs)."""
    platform, n_devices, mode, seq, given, want = CHOICES[case]
    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=H * DH, n_heads=H, n_kv_heads=HKV,
                      d_ff=64, vocab_size=101)
    impl = train_attention(platform, n_devices, mode)
    p = attn_lib.init_attention(jax.random.PRNGKey(0), cfg)
    x = jax.ShapeDtypeStruct((B, seq, cfg.d_model), jnp.bfloat16)
    positions = jnp.arange(seq) if given else None
    with attn_lib.record_paths() as tally:
        out = jax.eval_shape(lambda x: attn_lib.attention_train(p, x, cfg, "global", positions, impl=impl), x)
    assert out.shape == (B, seq, cfg.d_model)
    assert tally == {"splash": int(want == "splash"), "blocked": int(want == "blocked")}


@pytest.mark.parametrize("seq,want", [(256, "splash"), (200, "blocked")])
def test_tally_counts_every_layer_of_the_stack_and_tail(seq, want):
    """A scanned pattern counts once per repeat, tail layers once each, and
    non-attention layers not at all: 2 repeats of [local attn, global attn,
    mamba] plus a tail of [local attn, global attn] lower 6 attention layers."""
    pattern = (LayerSpec(attn_type="local"), LayerSpec(), LayerSpec(kind="mamba"))
    cfg = ModelConfig(name="t", family="hybrid", n_layers=8, d_model=64, n_heads=4, n_kv_heads=2,
                      d_ff=64, vocab_size=101, block_pattern=pattern, mamba=MambaConfig(d_inner=128), sliding_window=128)
    assert cfg.n_repeats == 2 and len(cfg.tail_layers) == 2
    params = jax.eval_shape(lambda k: transformer.init_params(cfg, k), jax.random.PRNGKey(0))
    batch = {"inputs": jax.ShapeDtypeStruct((1, seq), jnp.int32), "targets": jax.ShapeDtypeStruct((1, seq), jnp.int32)}
    grad = jax.grad(lambda p, b: transformer.loss_fn(p, b, cfg, attn_impl="splash")[0])
    with attn_lib.record_paths() as tally:
        jax.eval_shape(grad, params, batch)
    other = "blocked" if want == "splash" else "splash"
    assert tally == {want: 6, other: 0}
