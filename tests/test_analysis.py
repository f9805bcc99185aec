"""Tests for ``repro.analysis`` — the static collective/kernel/specs auditors."""

import itertools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis import fixtures
from repro.analysis.collectives import check_collective_uniformity
from repro.analysis.costmodel import estimate_cost, per_device
from repro.analysis.findings import Finding, apply_pragmas, build_report, severity_counts
from repro.analysis.kernels import SentinelCheck, audit_traced
from repro.analysis.specs_audit import DECLARED_MESHES, audit_arch
from repro.dist.compat import make_mesh


def _data_mesh():
    return make_mesh((1,), ("data",))


def _errors(findings):
    return [f for f in findings if f.severity == "error" and not f.suppressed]


# ---------------------------------------------------------------------------
# collective-uniformity checker
# ---------------------------------------------------------------------------


def test_deadlock_fixture_flagged_with_eqn_path():
    """Acceptance: psum in a divergent-trip while body -> error naming the eqn."""
    findings, meta = check_collective_uniformity(
        fixtures.trace_deadlock_step(_data_mesh()), "fixture"
    )
    errs = _errors(findings)
    assert meta["verdict"] == "divergent"
    assert errs and errs[0].rule == "divergent-collective"
    # the path pins the offending eqn through the whole control-flow nest
    assert "shard_map" in errs[0].path and "while/body" in errs[0].path
    assert errs[0].path.endswith(":psum")
    assert "deadlock" in errs[0].message


def test_clean_fixture_passes():
    findings, meta = check_collective_uniformity(
        fixtures.trace_clean_step(_data_mesh()), "fixture"
    )
    assert meta["verdict"] == "uniform"
    assert not _errors(findings)
    # the hoisted psum still shows up in the footprint, executed once
    assert [(c["op"], c["times"]) for c in meta["collectives"]] == [("psum", 1)]


def test_pragma_suppresses_fixture_finding():
    findings, _ = check_collective_uniformity(
        fixtures.trace_suppressed_step(_data_mesh()), "fixture"
    )
    findings = apply_pragmas(findings)
    assert findings and all(f.suppressed for f in findings if f.rule == "divergent-collective")
    counts = severity_counts(findings)
    assert counts["n_error"] == 0 and counts["n_suppressed"] >= 1


def test_divergent_branch_detection():
    """A rank-varying cond whose branches differ in collective footprint."""
    mesh = _data_mesh()
    from repro.dist.compat import shard_map

    def per_rank(x, alloc):
        return jax.lax.cond(
            alloc[0] > 2,
            lambda v: jax.lax.psum(v, "data"),
            lambda v: v * 2.0,
            x,
        )

    f = shard_map(per_rank, mesh, in_specs=(P("data"), P("data")), out_specs=P("data"))
    closed = jax.make_jaxpr(f)(jnp.zeros((4, 8)), jnp.ones((1,), jnp.int32))
    findings, meta = check_collective_uniformity(closed, "t")
    errs = _errors(findings)
    assert meta["verdict"] == "divergent"
    assert any(f.rule == "divergent-branch" for f in errs)


def test_uniform_branch_collectives_pass():
    """Rank-varying cond is fine when both branches psum identically."""
    mesh = _data_mesh()
    from repro.dist.compat import shard_map

    def per_rank(x, alloc):
        return jax.lax.cond(
            alloc[0] > 2,
            lambda v: jax.lax.psum(v * 2.0, "data"),
            lambda v: jax.lax.psum(v, "data"),
            x,
        )

    f = shard_map(per_rank, mesh, in_specs=(P("data"), P("data")), out_specs=P("data"))
    closed = jax.make_jaxpr(f)(jnp.zeros((4, 8)), jnp.ones((1,), jnp.int32))
    findings, meta = check_collective_uniformity(closed, "t")
    assert meta["verdict"] == "uniform", [f.message for f in _errors(findings)]


# ---------------------------------------------------------------------------
# analyzer agrees with HeteroStepConfig.validate (satellite 1)
# ---------------------------------------------------------------------------

_ALL_COMBOS = list(itertools.product(["while", "masked"], [False, True, "gather"], ["psum", "ring"]))


@pytest.fixture(scope="module")
def smoke_setup():
    from repro.configs import smoke_config

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = smoke_config("smollm-360m", seq=16)
    return mesh, cfg


@pytest.mark.parametrize("mode,fsdp,collective", _ALL_COMBOS)
def test_analyzer_agrees_with_validate(mode, fsdp, collective, smoke_setup):
    """Trace every (mode, fsdp, collective) combination; the analyzer's
    uniformity verdict must agree with ``validate()``'s hand rule.

    * ``validate()`` rejects exactly while-mode + per-microbatch FSDP over
      the allocation axis; the analyzer independently flags that class (the
      deadlock fixture — per-microbatch gathers inside the divergent loop).
      Neither over- nor under-rejection was found: every combination
      ``validate()`` admits traces collective-uniform.
    * ``masked`` + ``fsdp="gather"`` is rejected at construction (post_init):
      gather-mode only pairs with while-mode loops.
    """
    from repro.dist.hetero_step import HeteroStepConfig, build_train_step, init_train_state
    from repro.optim import AdamWConfig

    mesh, cfg = smoke_setup
    kw = dict(
        w_max=2,
        micro_bs=1,
        seq_len=16,
        mode=mode,
        alloc_axis="data",
        fsdp=fsdp,
        fsdp_axes=("data",),
        collective=collective,
    )
    if mode == "masked" and fsdp == "gather":
        with pytest.raises(ValueError):
            HeteroStepConfig(**kw)
        return
    scfg = HeteroStepConfig(**kw)

    illegal = mode == "while" and fsdp is True  # alloc_axis in fsdp_axes
    if illegal:
        with pytest.raises(ValueError, match="deadlock"):
            scfg.validate(mesh)
        # the analyzer rejects the same class: a collective inside the
        # divergent-trip-count loop this config would build
        findings, meta = check_collective_uniformity(
            fixtures.trace_deadlock_step(_data_mesh()), "agreement"
        )
        assert meta["verdict"] == "divergent" and _errors(findings)
        return

    scfg.validate(mesh)
    step = build_train_step(cfg, scfg, mesh, opt_cfg=AdamWConfig(), jit=False)
    state_shape = jax.eval_shape(
        lambda k: init_train_state(cfg, scfg, k, AdamWConfig()), jax.random.PRNGKey(0)
    )
    R = int(mesh.shape["data"])
    batch = {
        "inputs": jax.ShapeDtypeStruct((R, scfg.w_max, scfg.micro_bs, scfg.seq_len), jnp.int32),
        "targets": jax.ShapeDtypeStruct((R, scfg.w_max, scfg.micro_bs, scfg.seq_len), jnp.int32),
        "alloc": jax.ShapeDtypeStruct((R,), jnp.int32),
    }
    closed = jax.make_jaxpr(step)(state_shape, batch)
    findings, meta = check_collective_uniformity(closed, f"train:{mode}-{fsdp}-{collective}")
    assert meta["verdict"] == "uniform", [f.message for f in _errors(findings)]
    assert not _errors(findings)


# ---------------------------------------------------------------------------
# specs audit (satellite 3): every config x every declared mesh, zero errors
# ---------------------------------------------------------------------------


def _all_archs():
    from repro.configs import list_archs

    return list_archs()


@pytest.mark.parametrize("mesh_name", sorted(DECLARED_MESHES))
@pytest.mark.parametrize("arch", _all_archs())
def test_specs_audit_no_errors(arch, mesh_name):
    findings, meta = audit_arch(arch, mesh_name, DECLARED_MESHES[mesh_name])
    assert not _errors(findings), [f.message for f in _errors(findings)]
    assert meta["params"]["n_leaves"] > 0


def test_specs_audit_flags_bad_axis_and_indivisible():
    """Negative control: a hand-broken spec trips the error rules."""
    from repro.analysis.specs_audit import _audit_tree, _standin

    mesh = _standin(data=4, model=2)
    shapes = {"w": jax.ShapeDtypeStruct((6, 8), jnp.float32)}
    findings, _ = _audit_tree(shapes, {"w": P("nope", None)}, mesh, "t", "params")
    assert any(f.rule == "specs-bad-axis" for f in _errors(findings))
    findings, _ = _audit_tree(shapes, {"w": P("data", None)}, mesh, "t", "params")
    assert any(f.rule == "specs-indivisible" for f in _errors(findings))
    findings, _ = _audit_tree(shapes, {"w": P(None, "model")}, mesh, "t", "params")
    assert not _errors(findings)


# ---------------------------------------------------------------------------
# Pallas kernel auditor
# ---------------------------------------------------------------------------


def test_pallas_oob_index_map_flagged():
    """A toy kernel whose index map runs one block past the array."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def toy(x):
        return pl.pallas_call(
            kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((8,), lambda i: (i + 1,))],  # off-by-one
            out_specs=pl.BlockSpec((8,), lambda i: (i,)),
            out_shape=jax.ShapeDtypeStruct((32,), jnp.float32),
            interpret=True,
        )(x)

    closed = jax.make_jaxpr(toy)(jax.ShapeDtypeStruct((32,), jnp.float32))
    findings, _ = audit_traced(closed, "toy")
    errs = _errors(findings)
    assert any(f.rule == "pallas-oob-block" for f in errs)
    assert any("overruns array dim 32" in f.message for f in errs)


def test_pallas_vmem_budget_flagged():
    from repro.kernels.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((1, 128, 2, 64), jnp.float32)
    closed = jax.make_jaxpr(lambda q, k, v: flash_attention(q, k, v, interpret=True))(q, q, q)
    findings, _ = audit_traced(closed, "flash", vmem_budget=1024)
    assert any(f.rule == "pallas-vmem-budget" for f in _errors(findings))
    findings, meta = audit_traced(closed, "flash")  # default budget: fits
    assert not _errors(findings)
    (m,) = meta.values()
    assert 0 < m["vmem_estimate_bytes"] <= 16 * 2**20


def _paged_trace(n_pages=6, page_size=8, slots=3, B=2, H=4, Hkv=2, Dh=16):
    from repro.kernels.paged_attention import paged_attention

    pool = jax.ShapeDtypeStruct((n_pages + 1, Hkv, page_size, Dh), jnp.float32)
    q = jax.ShapeDtypeStruct((B, H, Dh), jnp.float32)
    pages = jax.ShapeDtypeStruct((B, slots), jnp.int32)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32)
    return jax.make_jaxpr(
        lambda q_, kp, vp, pg, ln: paged_attention(q_, kp, vp, pg, ln, interpret=True)
    )(q, pool, pool, pages, lens)


def test_paged_sentinel_clamp_is_intentional():
    """Dead -1 pages land exactly on the scratch page; live pages never do."""
    n_pages, page_size, slots, B = 6, 8, 3, 2
    closed = _paged_trace(n_pages, page_size, slots, B)
    live = np.arange(B * slots, dtype=np.int32).reshape(B, slots)
    full = np.full((B,), slots * page_size, np.int32)
    dead = np.full((B, slots), -1, np.int32)
    sc = SentinelCheck(operand=1, dim=0, reserved_start=n_pages, live_args=(live, full), dead_args=(dead, full))
    findings, meta = audit_traced(closed, "paged", scalar_args=(live, full), sentinel=sc)
    assert not _errors(findings), [f.message for f in _errors(findings)]
    (m,) = meta.values()
    assert m["sentinel_checked"] == 1 and m["n_origin_evals"] > 0


def test_paged_sentinel_leak_detected():
    """A 'live' page table that names the scratch page is a leak."""
    n_pages, page_size, slots, B = 6, 8, 3, 2
    closed = _paged_trace(n_pages, page_size, slots, B)
    leaky = np.arange(B * slots, dtype=np.int32).reshape(B, slots)
    leaky[0, 0] = n_pages  # the reserved scratch page, reachable while live
    full = np.full((B,), slots * page_size, np.int32)
    dead = np.full((B, slots), -1, np.int32)
    sc = SentinelCheck(operand=1, dim=0, reserved_start=n_pages, live_args=(leaky, full), dead_args=(dead, full))
    findings, _ = audit_traced(closed, "paged", sentinel=sc)
    assert any(f.rule == "pallas-sentinel-leak" for f in _errors(findings))


def test_paged_sentinel_miss_detected():
    """Claiming the wrong reserved page makes the dead path a miss."""
    n_pages, page_size, slots, B = 6, 8, 3, 2
    closed = _paged_trace(n_pages, page_size, slots, B)
    live = np.arange(B * slots, dtype=np.int32).reshape(B, slots)
    full = np.full((B,), slots * page_size, np.int32)
    dead = np.full((B, slots), -1, np.int32)
    sc = SentinelCheck(operand=1, dim=0, reserved_start=2, live_args=(live, full), dead_args=(dead, full))
    findings, _ = audit_traced(closed, "paged", sentinel=sc)
    errs = _errors(findings)
    assert any(f.rule == "pallas-sentinel-miss" for f in errs)
    # the correct clamp target (the scratch page) now reads as a live leak too
    assert any(f.rule == "pallas-sentinel-leak" for f in errs)


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


def test_cost_model_counts_matmul_flops():
    a = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((32, 16), jnp.float32)
    est = estimate_cost(jax.make_jaxpr(lambda a, b: jax.lax.dot(a, b))(a, b))
    assert est["flops"] == 2 * 64 * 16 * 32
    assert est["flops_manual"] == 0
    assert est["bytes"] == (64 * 32 + 32 * 16 + 64 * 16) * 4


def test_cost_model_buckets_shard_map_as_manual():
    est = estimate_cost(fixtures.trace_clean_step(_data_mesh()))
    assert est["flops_manual"] > 0
    dev = per_device(est, 4)
    assert dev["flops"] >= est["flops_manual"]  # manual work is not divided


def test_cost_model_counts_loop_bodies_once():
    def loop(x):
        def body(i, acc):
            return acc @ acc

        return jax.lax.fori_loop(0, 10, body, x)

    x = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    est = estimate_cost(jax.make_jaxpr(loop)(x))
    # one body execution's matmul, not 10 (matching XLA cost_analysis)
    assert est["flops"] < 2 * (2 * 16 * 16 * 16)


# ---------------------------------------------------------------------------
# report format
# ---------------------------------------------------------------------------


def test_report_is_deterministic_and_severity_ranked():
    findings = [
        Finding(rule="b-rule", severity="warning", target="t", path="p1", message="w"),
        Finding(rule="a-rule", severity="error", target="t", path="p2", message="e"),
        Finding(rule="c-rule", severity="note", target="t", path="p3", message="n"),
    ]
    r1 = build_report(list(findings), {"x": 1})
    r2 = build_report(list(reversed(findings)), {"x": 1})
    assert json.dumps(r1, sort_keys=True) == json.dumps(r2, sort_keys=True)
    sevs = [f["severity"] for f in r1["findings"]]
    assert sevs == ["error", "warning", "note"]
    assert r1["summary"]["n_error"] == 1


def test_selftest_passes_on_healthy_checker():
    from repro.analysis.cli import selftest

    findings, meta = selftest(_data_mesh())
    assert not _errors(findings)
    assert meta["deadlock_verdict"] == "divergent"
    assert meta["pragma_suppressed"] == 1


# ---------------------------------------------------------------------------
# pragma accounting: used-site collection, stale waivers, per-pragma counts
# ---------------------------------------------------------------------------


def _pragma_file(tmp_path, name, lines):
    p = tmp_path / name
    p.write_text("\n".join(lines) + "\n")
    return str(p)


def test_apply_pragmas_collects_used_sites(tmp_path):
    from repro.analysis.findings import apply_pragmas

    src = _pragma_file(tmp_path, "mod.py", ["x = 1  # analysis: ignore[my-rule]"])
    used = set()
    out = apply_pragmas(
        [
            Finding(rule="my-rule", severity="error", target="t", path="p", message="m", src=f"{src}:1"),
            Finding(rule="other-rule", severity="error", target="t", path="p", message="m", src=f"{src}:1"),
        ],
        used=used,
    )
    assert [f.suppressed for f in out] == [True, False]  # rule must match the waiver
    assert used == {(src, 1, "my-rule")}


def test_scan_and_stale_pragma_findings(tmp_path):
    from repro.analysis.findings import scan_pragmas, stale_pragma_findings

    a = _pragma_file(tmp_path, "a.py", ["x = 1  # analysis: ignore[rule-one]", "y = 2"])
    b = _pragma_file(tmp_path, "b.py", ["z = 3  # analysis: ignore[rule-two, rule-three]"])
    assert scan_pragmas(str(tmp_path)) == [  # sorted triples
        (a, 1, "rule-one"),
        (b, 1, "rule-three"),
        (b, 1, "rule-two"),
    ]
    # rule-one was consumed this run; the b.py waivers suppressed nothing
    stale = stale_pragma_findings({(a, 1, "rule-one")}, str(tmp_path))
    assert [(f.rule, f.severity) for f in stale] == [("stale-pragma", "warning")] * 2
    assert {f.path for f in stale} == {f"{b}:1"}
    assert all("suppressed nothing" in f.message for f in stale)


def test_build_report_counts_suppressions_per_pragma_and_flags_stale(tmp_path):
    src = _pragma_file(
        tmp_path, "mod.py",
        ["a()  # analysis: ignore[waived-rule]", "b()  # analysis: ignore[dead-rule]"],
    )
    findings = [
        Finding(rule="waived-rule", severity="error", target="t", path=f"p{i}", message="m",
                src=f"{src}:1")
        for i in range(2)
    ]
    report = build_report(findings, {"x": 1}, pragma_scan_root=str(tmp_path))
    # both findings suppressed by the same pragma site -> counted against it
    assert report["summary"]["n_error"] == 0 and report["summary"]["n_suppressed"] == 2
    assert report["summary"]["by_pragma"] == {f"{src}:1[waived-rule]": 2}
    # the waiver that suppressed nothing is flagged, the used one is not
    stale = [f for f in report["findings"] if f["rule"] == "stale-pragma"]
    assert len(stale) == 1 and stale[0]["path"] == f"{src}:2" and "dead-rule" in stale[0]["message"]


def test_stale_pragma_only_on_full_runs():
    """The stale audit is gated on a full-target invocation: a partial run
    never generates the findings a waiver exists for."""
    from repro.analysis.cli import TARGETS, _pragma_scan_root

    assert _pragma_scan_root(["protocol"]) is None
    assert _pragma_scan_root(["train", "serve"]) is None
    root = _pragma_scan_root(list(TARGETS))
    assert root is not None and root.endswith("repro")
    # the one in-tree pragma (the selftest fixture waiver) must be consumed
    # by every run — scan must see it so an unconsumed copy would be flagged
    from repro.analysis.findings import scan_pragmas

    assert any(r == "divergent-collective" for _, _, r in scan_pragmas(root))
