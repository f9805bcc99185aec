"""The train step's remat choice (``dist.hetero_step.layer_remat``): the rule
against a device's memory, passed in by a stub device; the residual
reckoning against a layer-by-layer count; and the two programs it chooses
between, which must agree."""

from __future__ import annotations

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_walk import find_eqns
from repro.configs import get_config
from repro.dist import hetero_step
from repro.dist.hetero_step import HeteroStepConfig, build_train_step, init_train_state, layer_remat
from repro.launch.mesh import make_test_mesh
from repro.models import ModelConfig, transformer
from repro.models.config import LayerSpec

GiB = 2**30


class _Device:
    """A TPU device that reports ``bytes_limit`` (None: no statistics)."""

    platform = "tpu"

    def __init__(self, limit):
        self.limit = limit

    def memory_stats(self):
        return None if self.limit is None else {"bytes_limit": self.limit}


def _mesh(limit, data: int = 1):
    devs = np.array([[_Device(limit)] for _ in range(data)])
    return types.SimpleNamespace(devices=devs, shape={"data": data, "model": 1})


SMOLLM = get_config("smollm-360m")
# name -> (config, micro_bs, seq, mode, ranks, data devices, limit, remat kept)
RULE = {
    "smollm-mb1-fits-16GiB": (SMOLLM, 1, 2048, "masked", 1, 1, 16 * GiB, False),
    "smollm-mb4-keeps": (SMOLLM, 4, 2048, "masked", 1, 1, 16 * GiB, True),
    "yi34b-seq4096-keeps": (get_config("yi-34b"), 1, 4096, "masked", 1, 1, 16 * GiB, True),
    "no-limit-keeps-the-config": (SMOLLM, 1, 2048, "masked", 1, 1, None, True),
    "remat-off-stays-off": (dataclasses.replace(SMOLLM, remat=False), 1, 2048, "masked", 1, 1, 1 * GiB, False),
    "remat-off-no-limit": (dataclasses.replace(SMOLLM, remat=False), 1, 2048, "masked", 1, 1, None, False),
    "four-ranks-folded-keep": (SMOLLM, 1, 2048, "masked", 4, 1, 16 * GiB, True),
    "four-ranks-one-at-a-time-in-while": (SMOLLM, 1, 2048, "while", 4, 1, 16 * GiB, False),
}


@pytest.mark.parametrize("case", RULE)
def test_remat_rule(case):
    """Remat goes where the reckoned bytes fit three quarters of the device,
    and stays where they do not, where the device's memory is unknown, and
    as the configuration says where it already has none."""
    cfg, mb, seq, mode, ranks, data, limit, want = RULE[case]
    scfg = HeteroStepConfig(w_max=8, micro_bs=mb, seq_len=seq, mode=mode)
    remat, residual = layer_remat(cfg, scfg, _mesh(limit, data), ranks)
    assert remat is want
    assert residual > 0


def test_residuals_scale_with_the_rows_one_device_holds():
    """Masked mode's vmap keeps the residuals of every rank row a device
    holds; while mode runs one microbatch at a time."""
    scfg = HeteroStepConfig(w_max=8, micro_bs=1, seq_len=2048)
    one = layer_remat(SMOLLM, scfg, _mesh(16 * GiB), 1)[1]
    assert layer_remat(SMOLLM, scfg, _mesh(16 * GiB), 4)[1] == 4 * one
    assert layer_remat(SMOLLM, dataclasses.replace(scfg, mode="while"), _mesh(16 * GiB), 4)[1] == one


def _tiny(**kw) -> ModelConfig:
    """Two scanned [local, global] groups and a one-layer tail."""
    pattern = (LayerSpec(attn_type="local"), LayerSpec())
    return ModelConfig(name="t", family="dense", n_layers=5, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                       vocab_size=97, block_pattern=pattern, sliding_window=64, **kw)


def _leaf_bytes(tree) -> int:
    return sum(x.size * jnp.dtype(x.dtype).itemsize for x in jax.tree.leaves(tree))


def _vjp_bytes(f, *args) -> int:
    """Bytes of the residuals ``jax.vjp(f, *args)`` keeps, by ``eval_shape``."""
    return _leaf_bytes(jax.eval_shape(lambda *a: jax.vjp(f, *a)[1], *args))


def test_reckoning_matches_the_layer_by_layer_count():
    """The reckoning (one trace of the whole loss) lies within 10 % of each
    pattern group's residuals times its repeats, plus the tail's, plus those
    of the model with no layers (embedding, head and loss), each from
    ``eval_shape`` of its own ``jax.vjp``; no parameter is among them."""
    cfg = _tiny(remat=False)
    mb, seq = 2, 128
    params = jax.eval_shape(lambda k: transformer.init_params(cfg, k), jax.random.PRNGKey(0))
    h = jax.ShapeDtypeStruct((mb, seq, cfg.d_model), cfg.dtype("compute"))
    group = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), params["body"])
    body = _vjp_bytes(transformer._pattern_fn(cfg, cfg.block_pattern, "blocked", "chunked"), group, h)
    tail = _vjp_bytes(transformer._pattern_fn(cfg, cfg.tail_layers, "blocked", "chunked"), params["tail"], h)
    head_cfg = dataclasses.replace(cfg, n_layers=0)
    head_params = jax.eval_shape(lambda k: transformer.init_params(head_cfg, k), jax.random.PRNGKey(0))
    tokens = jnp.zeros((mb, seq), jnp.int32)
    head = _vjp_bytes(lambda p: hetero_step._micro_loss_sum(p, tokens, tokens, head_cfg)[0], head_params)
    count = cfg.n_repeats * body + tail + head

    got = hetero_step._residual_bytes(cfg, mb, seq, "blocked")
    assert abs(got - count) <= 0.1 * count, (got, count)
    whole = _vjp_bytes(lambda p: hetero_step._micro_loss_sum(p, tokens, tokens, cfg)[0], params)
    assert got <= whole < got + _leaf_bytes(params)
    # remat keeps only each group's input, far less
    assert hetero_step._residual_bytes(_tiny(), mb, seq, "blocked") < got / 4


def test_reckoning_leaves_out_the_parameters_it_passes_through():
    """With float32 compute the weights feed the matmuls as they are, so the
    backward keeps them: ``eval_shape`` of the whole loss's ``jax.vjp``
    counts them among the residuals, and the reckoning leaves them out (they
    are live anyway), most of the parameters' bytes."""
    cfg = _tiny(remat=False, compute_dtype="float32")
    params = jax.eval_shape(lambda k: transformer.init_params(cfg, k), jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 128), jnp.int32)
    whole = _vjp_bytes(lambda p: hetero_step._micro_loss_sum(p, tokens, tokens, cfg)[0], params)
    passed = whole - hetero_step._residual_bytes(cfg, 2, 128, "blocked")
    assert 0.5 * _leaf_bytes(params) < passed <= _leaf_bytes(params)


def _dots(fn, *args) -> int:
    return len(find_eqns(jax.make_jaxpr(fn)(*args), "dot_general"))


def test_steps_with_and_without_remat_agree(monkeypatch):
    """A step built where the device's memory fits the residuals (a stubbed
    limit) drops remat, and two of its steps give the loss, gradient norm and
    parameters of the step that keeps it, within float32 round-off."""
    cfg = _tiny(compute_dtype="float32")
    scfg = HeteroStepConfig(w_max=2, micro_bs=2, seq_len=32)
    mesh = make_test_mesh((1, 1), ("data", "model"))
    state = init_train_state(cfg, scfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (1, 2, 2, 33)).astype(np.int32)
    batch = {"inputs": jnp.asarray(toks[..., :-1]), "targets": jnp.asarray(toks[..., 1:]),
             "alloc": jnp.asarray([2], jnp.int32)}

    def run(limit):
        monkeypatch.setattr(hetero_step, "_bytes_limit", lambda mesh: limit)
        assert layer_remat(cfg, scfg, mesh, 1)[0] is (limit is None)
        dots = _dots(build_train_step(cfg, scfg, mesh, jit=False), state, batch)
        step = build_train_step(cfg, scfg, mesh)
        s, out = jax.tree.map(lambda x: x.copy(), state), []
        for _ in range(2):
            s, m = step(s, batch)
            out.append((float(m["loss"]), float(m["grad_norm"])))
        return dots, out, s["params"]

    dots_on, on, p_on = run(None)
    dots_off, off, p_off = run(64 * GiB)
    assert dots_off < dots_on  # the layers' forward matmuls run once
    np.testing.assert_allclose(np.array(off), np.array(on), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p_off), jax.tree.leaves(p_on)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


# name -> (layers in the scanned pattern, remat, forward kernels per call site)
KERNEL_CASES = {
    "one-layer-pattern-remat": (1, True, 2),
    "one-layer-pattern-no-remat": (1, False, 1),
    "two-layer-pattern-and-tail-no-remat": (2, False, 1),
}


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_kernel_forward_runs_once_a_layer_without_remat(case):
    """Each attention call site's splash forward kernel appears once in the
    gradient's program without remat (its residuals serve the backward) and
    twice with it, the primal and the recompute, where each scanned group
    is one layer (smollm-360m's stack); dq and dkv once either way
    (abstract parameters, traced only)."""
    pattern_len, remat, want = KERNEL_CASES[case]
    cfg = _tiny(remat=remat)
    if pattern_len == 1:
        cfg = dataclasses.replace(cfg, n_layers=3, block_pattern=(LayerSpec(),))
    params = jax.eval_shape(lambda k: transformer.init_params(cfg, k), jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    grad = jax.grad(lambda p, x, y: hetero_step._micro_loss_sum(p, x, y, cfg, "splash")[0])
    names = [eqn.params["name"] for _, eqn in find_eqns(jax.make_jaxpr(grad)(params, x, x), "pallas_call")]
    sites = len(cfg.block_pattern) + len(cfg.tail_layers)  # each scanned call site is traced once
    assert sum("fwd" in n for n in names) == want * sites, names
    assert sum("dq" in n for n in names) == sites and sum("dkv" in n for n in names) == sites, names
