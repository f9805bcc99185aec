"""Distribution-layer tests.

Multi-device behaviour (shard_map, while-mode, ring allreduce) runs in
subprocesses with ``--xla_force_host_platform_device_count`` because the
device count locks at first jax init — the main pytest process must stay
single-device for the smoke tests.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(code: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC
    # the child models virtual host devices; it must never reach for a chip
    # this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


@pytest.mark.slow
def test_while_equals_masked_equals_reference():
    """The paper's step: while-mode == masked-mode == manual per-rank loop."""
    out = run_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import ModelConfig
        from repro.dist import HeteroStepConfig, build_train_step, init_train_state
        from repro.dist.hetero_step import _micro_loss_sum
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh((4, 2), ("data", "model"))
        cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                          n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=101,
                          compute_dtype="float32", remat=False)
        kw = dict(w_max=4, micro_bs=8, seq_len=16, alloc_axis="data")
        sw = HeteroStepConfig(mode="while", **kw)
        sm = HeteroStepConfig(mode="masked", **kw)
        state = init_train_state(cfg, sw, jax.random.PRNGKey(0))
        R, W, mb, S = 4, 4, 8, 16
        inputs = jax.random.randint(jax.random.PRNGKey(7), (R, W, mb, S), 0, 101)
        targets = jax.random.randint(jax.random.PRNGKey(8), (R, W, mb, S), 0, 101)
        alloc = jnp.array([1, 2, 3, 4], jnp.int32)
        batch = {"inputs": inputs, "targets": targets, "alloc": alloc}
        s1, m1 = build_train_step(cfg, sw, mesh)(jax.tree.map(lambda x: x.copy(), state), batch)
        s2, m2 = build_train_step(cfg, sm, mesh)(jax.tree.map(lambda x: x.copy(), state), batch)
        # reference
        gf = jax.value_and_grad(lambda p, x, y: _micro_loss_sum(p, x, y, cfg), has_aux=True)
        toks, lsum = 0.0, 0.0
        for r in range(R):
            for j in range(int(alloc[r])):
                (ls, tk), _ = gf(state["params"], inputs[r, j], targets[r, j])
                toks += float(tk); lsum += float(ls)
        np.testing.assert_allclose(float(m1["loss"]), lsum / toks, rtol=1e-5)
        np.testing.assert_allclose(float(m2["loss"]), lsum / toks, rtol=1e-5)
        d = max(jax.tree.leaves(jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                                             s1["params"], s2["params"])))
        assert d < 1e-5, d
        print("OK")
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_masked_below_buffer_depth_equals_reference():
    """Masked mode loops only up to the largest allocation: with every
    allocation below w_max, masked, masked over FSDP-sharded params and
    while mode each equal the per-rank manual loop and its AdamW update; an
    all-zero allocation trains nothing and stays finite."""
    out = run_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding
        from repro.models import ModelConfig
        from repro.dist import HeteroStepConfig, build_train_step, init_train_state
        from repro.dist.hetero_step import _micro_loss_sum
        from repro.dist.sharding import state_specs
        from repro.launch.mesh import make_test_mesh
        from repro.optim import AdamWConfig, adamw_update

        mesh = make_test_mesh((4, 2), ("data", "model"))
        cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                          n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=101,
                          compute_dtype="float32", remat=False)
        kw = dict(w_max=4, micro_bs=8, seq_len=16, alloc_axis="data")
        modes = {
            "masked": HeteroStepConfig(mode="masked", **kw),
            "masked-fsdp": HeteroStepConfig(mode="masked", fsdp=True, **kw),
            "while": HeteroStepConfig(mode="while", **kw),
        }
        state = init_train_state(cfg, modes["masked"], jax.random.PRNGKey(0))
        specs = state_specs(state, mesh, fsdp=True, fsdp_axes=("data",))
        sharded = jax.tree.map(lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), state, specs)
        assert any(not x.sharding.is_fully_replicated for x in jax.tree.leaves(sharded["params"]))
        R, W, mb, S = 4, 4, 8, 16
        inputs = jax.random.randint(jax.random.PRNGKey(7), (R, W, mb, S), 0, 101)
        targets = jax.random.randint(jax.random.PRNGKey(8), (R, W, mb, S), 0, 101)

        def run(name, alloc):
            start = sharded if name == "masked-fsdp" else state
            batch = {"inputs": inputs, "targets": targets, "alloc": jnp.array(alloc, jnp.int32)}
            return build_train_step(cfg, modes[name], mesh)(jax.tree.map(lambda x: x.copy(), start), batch)

        alloc = [1, 2, 1, 0]  # max 2 < w_max 4
        gf = jax.value_and_grad(lambda p, x, y: _micro_loss_sum(p, x, y, cfg), has_aux=True)
        toks, lsum, gsum = 0.0, 0.0, jax.tree.map(jnp.zeros_like, state["params"])
        for r in range(R):
            for j in range(alloc[r]):
                (ls, tk), g = gf(state["params"], inputs[r, j], targets[r, j])
                toks += float(tk); lsum += float(ls)
                gsum = jax.tree.map(jnp.add, gsum, g)
        grads = jax.tree.map(lambda g: g / toks, gsum)
        ref_params, _ = adamw_update(grads, state["opt"], state["params"], 1e-3, AdamWConfig())
        for name in modes:
            s, m = run(name, alloc)
            np.testing.assert_allclose(float(m["loss"]), lsum / toks, rtol=1e-5, err_msg=name)
            assert float(m["tokens"]) == toks, name
            d = max(jax.tree.leaves(jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                                                 s["params"], ref_params)))
            assert d < 1e-5, (name, d)
        for name in modes:
            s, m = run(name, [0, 0, 0, 0])
            assert float(m["tokens"]) == 0.0, name
            vals = [float(v) for v in m.values()] + [float(jnp.abs(x).max()) for x in jax.tree.leaves(s)]
            assert all(np.isfinite(v) for v in vals), name
        print("OK")
        """
    )
    assert "OK" in out


def test_masked_grads_stop_at_the_largest_allocation():
    """One rank, w_max 8, allocation 4: the masked accumulation equals an
    explicit scan over the four live slots, bit for bit."""
    from repro.dist.hetero_step import _masked_grads, _micro_loss_sum, _zero_carry
    from repro.models import ModelConfig, transformer

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                      d_ff=64, vocab_size=101, compute_dtype="bfloat16", remat=False)
    grad_fn = jax.value_and_grad(lambda p, x, y: _micro_loss_sum(p, x, y, cfg), has_aux=True)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    inputs = jax.random.randint(jax.random.PRNGKey(7), (1, 8, 2, 16), 0, 101)
    targets = jax.random.randint(jax.random.PRNGKey(8), (1, 8, 2, 16), 0, 101)
    alloc = jnp.array([4], jnp.int32)
    got = jax.jit(lambda p, x, y, a: _masked_grads(p, x, y, a, grad_fn, jnp.float32))(params, inputs, targets, alloc)

    vgrad = jax.vmap(grad_fn, in_axes=(None, 0, 0))

    def four_slots(p, x, y):
        def slot(carry, xs):
            gsum, lsum, tsum = carry
            xj, yj = xs
            m = jnp.ones((1,), jnp.float32)
            (ls, tk), g = vgrad(p, xj, yj)
            gsum = jax.tree.map(lambda a, b: a + jnp.tensordot(m, b.astype(jnp.float32), axes=1), gsum, g)
            return (gsum, lsum + (m * ls).sum(), tsum + (m * tk).sum()), None

        xs = (x[:, :4].transpose(1, 0, 2, 3), y[:, :4].transpose(1, 0, 2, 3))
        return jax.lax.scan(slot, _zero_carry(p, jnp.float32), xs)[0]

    want = jax.jit(four_slots)(params, inputs, targets)
    assert float(got[2]) == 4 * 2 * 16
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_while_gather_fsdp_equals_masked_equals_reference():
    """The tentpole: while-mode with fsdp='gather' (state sharded, ONE
    all-gather per step, gradients reduce-scattered back) is numerically the
    masked/reference step — and the state actually lives sharded."""
    out = run_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import ModelConfig
        from repro.dist import HeteroStepConfig, build_train_step, init_train_state
        from repro.dist.hetero_step import _micro_loss_sum
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh((4, 2), ("data", "model"))
        cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                          n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=101,
                          compute_dtype="float32", remat=False)
        kw = dict(w_max=4, micro_bs=8, seq_len=16, alloc_axis="data")
        sg = HeteroStepConfig(mode="while", fsdp="gather", **kw)
        sr = HeteroStepConfig(mode="while", fsdp="gather", collective="ring", **kw)
        sm = HeteroStepConfig(mode="masked", **kw)
        state = init_train_state(cfg, sg, jax.random.PRNGKey(0))
        R, W, mb, S = 4, 4, 8, 16
        inputs = jax.random.randint(jax.random.PRNGKey(7), (R, W, mb, S), 0, 101)
        targets = jax.random.randint(jax.random.PRNGKey(8), (R, W, mb, S), 0, 101)
        alloc = jnp.array([1, 2, 3, 4], jnp.int32)
        batch = {"inputs": inputs, "targets": targets, "alloc": alloc}
        s1, m1 = build_train_step(cfg, sg, mesh)(jax.tree.map(lambda x: x.copy(), state), batch)
        s2, m2 = build_train_step(cfg, sm, mesh)(jax.tree.map(lambda x: x.copy(), state), batch)
        s3, m3 = build_train_step(cfg, sr, mesh)(jax.tree.map(lambda x: x.copy(), state), batch)
        # reference loss over the union of live microbatches
        gf = jax.value_and_grad(lambda p, x, y: _micro_loss_sum(p, x, y, cfg), has_aux=True)
        toks, lsum = 0.0, 0.0
        for r in range(R):
            for j in range(int(alloc[r])):
                (ls, tk), _ = gf(state["params"], inputs[r, j], targets[r, j])
                toks += float(tk); lsum += float(ls)
        np.testing.assert_allclose(float(m1["loss"]), lsum / toks, rtol=1e-5)
        for other in (s2, s3):
            d = max(jax.tree.leaves(jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                                                 s1["params"], other["params"])))
            assert d < 1e-5, d
        # params AND optimizer moments live sharded (ZeRO), not replicated
        n_dev = len(jax.devices())
        for tree in (s1["params"], s1["opt"]["mu"]):
            leaves = jax.tree.leaves(tree)
            assert any(not x.sharding.is_fully_replicated for x in leaves)
            frac = sum(x.addressable_shards[0].data.size for x in leaves) / sum(x.size for x in leaves)
            assert frac < 0.2, frac  # ~1/8 per device, far from full replication
        print("OK")
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_gather_collectives_match_psum_references():
    """ring/psum all-gather + reduce-scatter primitives against lax references."""
    out = run_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.dist import (all_gather_params, reduce_scatter_tree,
                                ring_all_gather, ring_reduce_scatter)
        from repro.dist.compat import shard_map
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh((8,), ("w",))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 16, 6))

        def prim(x):
            local = x[0]  # (16, 6): dim 0 divisible by the ring, dim 1 not
            ag = ring_all_gather(local, "w", 1) - jax.lax.all_gather(local, "w", axis=1, tiled=True)
            rs = ring_reduce_scatter(local, "w", 0) - jax.lax.psum_scatter(
                local, "w", scatter_dimension=0, tiled=True)
            return jnp.abs(ag).max()[None], jnp.abs(rs).max()[None]
        f = jax.jit(shard_map(prim, mesh, in_specs=P("w"), out_specs=(P("w"), P("w")), check_rep=False))
        a, b = f(x)
        assert float(a.max()) < 1e-5 and float(b.max()) < 1e-5, (a.max(), b.max())

        # tree round-trip: shards -> gather -> (simulated grads) reduce-scatter
        mesh2 = make_test_mesh((4, 2), ("data", "model"))
        specs = {"a": P("data", "model"), "b": P(None, "data"), "c": P()}
        full = {"a": jax.random.normal(jax.random.PRNGKey(1), (8, 4)),
                "b": jax.random.normal(jax.random.PRNGKey(2), (3, 8)),
                "c": jax.random.normal(jax.random.PRNGKey(3), (5,))}

        def body(tree):
            gathered = all_gather_params(tree, specs)
            # pretend each data-rank contributed gradient == gathered params:
            # the reduce-scattered sum must equal 4 * full, re-sharded
            back = reduce_scatter_tree(gathered, specs, reduce_axes=("data",))
            return jax.tree.map(lambda g, t: jnp.abs(g - 4.0 * t).max()[None], back, tree)
        g = jax.jit(shard_map(body, mesh2, in_specs=(specs,), out_specs=P(None)))
        errs = g(full)
        m = max(float(v.max()) for v in jax.tree.leaves(errs))
        assert m < 1e-5, m
        print("OK")
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_allocation_invariance_of_update():
    """Paper eq. 1: the SAME global batch split differently across ranks gives
    the SAME parameter update (convergence is allocation-independent)."""
    out = run_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import ModelConfig
        from repro.dist import HeteroStepConfig, build_train_step, init_train_state
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh((4, 2), ("data", "model"))
        cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                          n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=101,
                          compute_dtype="float32", remat=False)
        R, W, mb, S = 4, 4, 4, 16
        # 8 microbatches of real data, two different placements
        data = jax.random.randint(jax.random.PRNGKey(5), (8, mb, S), 0, 101)
        tgt = jax.random.randint(jax.random.PRNGKey(6), (8, mb, S), 0, 101)

        def place(order, alloc):
            xi = jnp.zeros((R, W, mb, S), jnp.int32)
            yi = jnp.zeros((R, W, mb, S), jnp.int32)
            k = 0
            for r in range(R):
                for j in range(alloc[r]):
                    xi = xi.at[r, j].set(data[order[k]])
                    yi = yi.at[r, j].set(tgt[order[k]])
                    k += 1
            return {"inputs": xi, "targets": yi, "alloc": jnp.array(alloc, jnp.int32)}

        for fsdp in (False, "gather"):  # replicated AND ZeRO gather-mode
            scfg = HeteroStepConfig(w_max=4, micro_bs=4, seq_len=16, mode="while",
                                    alloc_axis="data", fsdp=fsdp)
            step = build_train_step(cfg, scfg, mesh)
            state = init_train_state(cfg, scfg, jax.random.PRNGKey(0))
            b1 = place(list(range(8)), [2, 2, 2, 2])   # equal allocation
            b2 = place(list(range(8)), [1, 2, 2, 3])   # skewed allocation
            s1, m1 = step(jax.tree.map(lambda x: x.copy(), state), b1)
            s2, m2 = step(jax.tree.map(lambda x: x.copy(), state), b2)
            np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-6)
            d = max(jax.tree.leaves(jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                                                 s1["params"], s2["params"])))
            assert d < 1e-5, (fsdp, d)
        print("OK")
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_ring_allreduce_equals_psum():
    out = run_subprocess(
        """
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.dist import ring_allreduce
        from repro.dist.compat import shard_map
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh((8,), ("w",))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 16, 3))
        def f(x):
            local = x[0]
            return (ring_allreduce(local, "w") - jax.lax.psum(local, "w"))[None]
        g = jax.jit(shard_map(f, mesh, in_specs=P("w"), out_specs=P("w"), check_rep=False))
        assert float(jnp.abs(g(x)).max()) < 1e-5
        print("OK")
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_while_mode_fsdp_over_alloc_axis_rejected():
    from repro.dist import HeteroStepConfig
    from repro.launch.mesh import make_test_mesh

    scfg = HeteroStepConfig(w_max=2, micro_bs=2, seq_len=8, mode="while", alloc_axis="data", fsdp=True)
    out = run_subprocess(
        """
        import jax, pytest
        from repro.dist import HeteroStepConfig
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh((4, 2), ("data", "model"))
        scfg = HeteroStepConfig(w_max=2, micro_bs=2, seq_len=8, mode="while",
                                alloc_axis="data", fsdp=True)
        try:
            scfg.validate(mesh)
            print("NO-ERROR")
        except ValueError as e:
            assert "deadlock" in str(e)
            # ... but the uniform-collective gather mode IS legal on the same mesh
            HeteroStepConfig(w_max=2, micro_bs=2, seq_len=8, mode="while",
                             alloc_axis="data", fsdp="gather").validate(mesh)
            print("OK")
        """
    )
    assert "OK" in out


# ---------------------------------------------------------------------------
# single-device dist pieces
# ---------------------------------------------------------------------------


def test_step_config_rejects_bad_fsdp_combinations():
    from repro.dist import HeteroStepConfig

    with pytest.raises(ValueError, match="gather"):
        HeteroStepConfig(w_max=2, micro_bs=2, seq_len=8, mode="masked", fsdp="gather")
    with pytest.raises(ValueError, match="fsdp"):
        HeteroStepConfig(w_max=2, micro_bs=2, seq_len=8, fsdp="zero3")


def test_reduce_scatter_divisibility_error_names_param_path():
    """A bad spec must name the failing LEAF, not just a shape: the error is
    raised per-parameter so the user can trace it back to the spec table."""
    from jax.sharding import PartitionSpec as P

    from repro.dist import reduce_scatter_tree

    tree = {"layer0": {"w": jnp.zeros((3, 4))}}  # dim 0 = 3: indivisible by 2
    specs = {"layer0": {"w": P("r", None)}}

    def run(use_ring):
        def f(_x, t):
            return reduce_scatter_tree(t, specs, ("r",), use_ring=use_ring)

        # vmap(axis_name=...) stands in for a 2-rank mesh axis in-process
        jax.vmap(f, in_axes=(0, None), axis_name="r")(jnp.zeros((2,)), tree)

    for use_ring in (True, False):
        with pytest.raises(ValueError, match=r"layer0.*w") as ei:
            run(use_ring)
        assert "not divisible" in str(ei.value)


def test_build_train_step_rejects_alloc_over_w_max():
    """The while body clamps alloc to W silently; the host-side guard must
    turn that into a loud error before any microbatch is dropped."""
    import jax.numpy as jnp

    from repro.configs import smoke_config
    from repro.dist import HeteroStepConfig, build_train_step, init_train_state
    from repro.launch.mesh import make_test_mesh

    cfg = smoke_config("smollm-360m", seq=16)
    scfg = HeteroStepConfig(w_max=2, micro_bs=2, seq_len=16, mode="masked")
    mesh = make_test_mesh((1, 1), ("data", "model"))
    step = build_train_step(cfg, scfg, mesh)
    state = init_train_state(cfg, scfg, jax.random.PRNGKey(0))
    batch = {
        "inputs": jnp.zeros((2, 2, 2, 16), jnp.int32),
        "targets": jnp.zeros((2, 2, 2, 16), jnp.int32),
        "alloc": jnp.array([3, 1], jnp.int32),  # 3 > w_max=2
    }
    with pytest.raises(ValueError, match="w_max"):
        step(state, batch)
    # the guard must also cover eager jit=False callers (same silent clamp)
    raw_step = build_train_step(cfg, scfg, mesh, jit=False)
    with pytest.raises(ValueError, match="w_max"):
        raw_step(state, batch)


def test_serving_cells_report_param_state_bytes():
    """dryrun's `state GB/dev` column must be non-zero for prefill/decode
    cells too (their persistent state is the sharded param tree)."""
    from repro.launch.mesh import make_test_mesh
    from repro.launch.specs import plan_cell

    mesh = make_test_mesh((1, 1), ("data", "model"))
    plan = plan_cell("smollm-360m", "decode_32k", mesh)
    assert plan.kind == "decode"
    # unsharded 1x1 mesh: per-device bytes == full fp32 param bytes
    assert plan.state_bytes_per_dev > 100e6


def test_state_specs_memory_accounting():
    """fsdp state sharding: per-device params+opt bytes must drop to ~1/N on
    an N-way mesh (modulo the replicated norm gains / scalars)."""
    from repro.configs import get_config
    from repro.dist import state_specs
    from repro.models import transformer
    from repro.optim import AdamWConfig, adamw_init

    cfg = get_config("gemma-7b")
    state = jax.eval_shape(
        lambda k: {
            "params": transformer.init_params(cfg, k),
            "opt": adamw_init(jax.eval_shape(lambda q: transformer.init_params(cfg, q), k), AdamWConfig()),
            "step": jnp.zeros((), jnp.int32),
        },
        jax.random.PRNGKey(0),
    )

    class FakeMesh:
        shape = {"data": 8, "model": 1}
        axis_names = ("data", "model")

    def tree_bytes(shapes, specs):
        def leaf(x, s):
            shards = 1
            for entry in tuple(s):
                for ax in (entry if isinstance(entry, tuple) else (entry,)) if entry else ():
                    shards *= FakeMesh.shape[ax]
            return x.size * x.dtype.itemsize // shards

        return sum(jax.tree.leaves(jax.tree.map(leaf, shapes, specs)))

    replicated = tree_bytes(state, jax.tree.map(lambda _: jax.sharding.PartitionSpec(), state))
    specs = state_specs(state, FakeMesh(), fsdp=True, fsdp_axes=("data",))
    sharded = tree_bytes(state, specs)
    # acceptance: <= ~1/8 of full state (+ slack for unsharded 0/1-D leaves)
    assert sharded <= replicated / 8 * 1.05, (sharded, replicated)
    # moments are sharded identically to params (ZeRO), not left replicated
    assert specs["opt"]["mu"] == specs["params"]
    assert specs["opt"]["nu"] == specs["params"]


def test_grad_compression_error_feedback():
    from repro.dist import compress_error_feedback, decompress_update
    from repro.dist.collectives import init_error_state

    g = {"w": jnp.array([1.0 + 1e-4, -2.0, 3.0])}
    e = init_error_state(g)
    total_sent = jnp.zeros(3)
    total_true = jnp.zeros(3)
    for _ in range(50):
        comp, e = compress_error_feedback(g, e)
        total_sent = total_sent + decompress_update(comp)["w"]
        total_true = total_true + g["w"]
    # error feedback: accumulated compressed stream converges to the truth
    np.testing.assert_allclose(np.asarray(total_sent), np.asarray(total_true), rtol=1e-3)


def test_param_specs_shapes_divisible():
    """Sharding rules must only shard divisible dims (smollm's 15 heads)."""
    from repro.configs import get_config
    from repro.dist.sharding import param_specs
    from repro.models import transformer

    cfg = get_config("smollm-360m")
    params = jax.eval_shape(lambda k: transformer.init_params(cfg, k), jax.random.PRNGKey(0))

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    specs = param_specs(params, FakeMesh(), fsdp=True)
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "_normalized_spec_for_aval") or x.__class__.__name__ == "PartitionSpec")
    assert len(flat_p) == len(flat_s)
    for (path, leaf), spec in zip(flat_p, flat_s):
        offset = 0
        for i, ax in enumerate(spec):
            if ax is None:
                continue
            size = {"data": 16, "model": 16}[ax] if isinstance(ax, str) else 16 * 16
            assert leaf.shape[i] % size == 0, (path, leaf.shape, spec)


@pytest.mark.parametrize("mode", ["while", "masked"])
def test_driver_result_names_the_four_device_mesh(mode):
    """On a forced 4-device host, 4 ranks get a 4x1 mesh of distinct devices
    and the result names it."""
    out = run_subprocess(
        f"""
        import json
        from repro.launch import train
        r = train.main(["--arch", "smollm-360m", "--smoke", "--n-workers", "4", "--steps", "2",
                        "--total-micro", "8", "--micro-bs", "1", "--seq", "16", "--mode", "{mode}",
                        "--policy", "static", "--static-ratio", "4,2,1,1"])
        print("MESH=" + json.dumps(r["mesh"]))
        """,
        n_devices=4,
    )
    line = next(ln for ln in out.splitlines() if ln.startswith("MESH="))
    assert json.loads(line[5:]) == {"shape": [4, 1], "axes": ["data", "model"], "devices": 4, "platform": "cpu"}
