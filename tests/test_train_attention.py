"""Training attention: the splash-kernel path against float32 naive attention
(the kernel runs in the Pallas interpreter on the CPU), where the step and
the per-call dispatcher choose it, read off the Pallas calls of the traced
program, at toy widths and at every registry configuration's head layout."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.jaxpr_walk import find_eqns
from repro.configs import get_config
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


def _kernels(fn, *args) -> list[tuple[str, str]]:
    """(path, kernel name) of every Pallas call in ``fn``'s traced program."""
    return [(path, eqn.params["name"]) for path, eqn in find_eqns(jax.make_jaxpr(fn)(*args), "pallas_call")]


# (platform, devices, mode, sequence) -> the path lowered
CHOICES = {
    "cpu-backend": ("cpu", 1, "masked", 256, "blocked"),
    "tpu-one-chip": ("tpu", 1, "masked", 256, "splash"),
    "tpu-length-not-multiple-of-128": ("tpu", 1, "masked", 200, "blocked"),
    "tpu-masked-multi-device": ("tpu", 4, "masked", 256, "blocked"),
    "tpu-while-multi-device": ("tpu", 4, "while", 256, "splash"),
}


@pytest.mark.parametrize("case", CHOICES)
def test_attention_path_choice(case):
    """The step's choice from the mesh, then the dispatcher's per-call check
    from the shapes: the traced layer holds one Pallas call on the splash
    path and none on the blocked scan (traced only, nothing runs)."""
    platform, n_devices, mode, seq, want = CHOICES[case]
    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=H * DH, n_heads=H, n_kv_heads=HKV,
                      d_ff=64, vocab_size=101)
    impl = train_attention(platform, n_devices, mode)
    p = jax.eval_shape(lambda k: attn_lib.init_attention(k, cfg), jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((B, seq, cfg.d_model), jnp.bfloat16)
    closed = jax.make_jaxpr(lambda p, x: attn_lib.attention_train(p, x, cfg, "global", impl=impl))(p, x)
    assert closed.out_avals[0].shape == (B, seq, cfg.d_model)
    assert len(find_eqns(closed, "pallas_call")) == int(want == "splash")


@pytest.mark.parametrize("seq,want", [(256, 1), (200, 0)])
def test_tally_counts_every_layer_of_the_stack_and_tail(seq, want):
    """Every attention call site lowers the kernel's forward, in the scanned
    pattern and in the tail alike, and no mamba layer does: 2 repeats of
    [local attn, global attn, mamba] plus a tail of [local attn, global
    attn] trace 2 call sites inside the scan and 2 outside it."""
    pattern = (LayerSpec(attn_type="local"), LayerSpec(), LayerSpec(kind="mamba"))
    cfg = ModelConfig(name="t", family="hybrid", n_layers=8, d_model=64, n_heads=4, n_kv_heads=2,
                      d_ff=64, vocab_size=101, block_pattern=pattern, mamba=MambaConfig(d_inner=128), sliding_window=128)
    assert cfg.n_repeats == 2 and len(cfg.tail_layers) == 2
    params = jax.eval_shape(lambda k: transformer.init_params(cfg, k), jax.random.PRNGKey(0))
    batch = {"inputs": jax.ShapeDtypeStruct((1, seq), jnp.int32), "targets": jax.ShapeDtypeStruct((1, seq), jnp.int32)}
    calls = _kernels(lambda p, b: transformer.loss_fn(p, b, cfg, attn_impl="splash")[0], params, batch)
    assert all("fwd" in name for _, name in calls)
    in_scan = [path for path, _ in calls if "scan/" in path]
    assert (len(in_scan), len(calls) - len(in_scan)) == (2 * want, 2 * want)


# every registry configuration with attention layers, at its published head
# layout; the step on one TPU chip picks the splash kernel for each
ATTENTION_CONFIGS = [
    "smollm-360m", "gemma-7b", "gemma3-27b", "yi-34b", "olmoe-1b-7b", "phi3.5-moe-42b-a6.6b",
    "jamba-1.5-large-398b", "llava-next-mistral-7b", "musicgen-large",
]


@pytest.mark.parametrize("arch", ATTENTION_CONFIGS)
def test_published_head_layouts_train_on_the_kernel(arch):
    """At a 2048-token sequence, each attention kind the configuration uses
    (global, and local where it has one) lowers its forward and its dq and
    dkv backward to Pallas calls (abstract parameters, traced only)."""
    cfg = get_config(arch)
    impl = train_attention("tpu", 1, "masked")
    kinds = sorted({s.attn_type for s in (*cfg.block_pattern, *cfg.tail_layers) if s.kind == "attn"})
    assert kinds
    p = jax.eval_shape(lambda k: attn_lib.init_attention(k, cfg), jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, 2048, cfg.d_model), cfg.dtype("compute"))
    for kind in kinds:
        def loss(p, x, kind=kind):
            return attn_lib.attention_train(p, x, cfg, kind, impl=impl).astype(jnp.float32).sum()

        names = [name for _, name in _kernels(jax.grad(loss, argnums=(0, 1)), p, x)]
        for part in ("fwd", "dq", "dkv"):
            assert any(part in name for name in names), (kind, part, names)
