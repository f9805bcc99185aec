"""The driver's host spans on the profiler's clock, and its microbatch-pass
counters.

A smoke-size ``ElasticTrainer`` runs three steps, and eight, under ``jax.profiler``; the
host plane of the ``.xplane.pb`` it writes must hold the ``driver.*`` spans
nested and ordered as the loop runs them, across an epoch boundary and a
membership rebuild.
"""

from __future__ import annotations

import collections
import glob
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_walk import find_eqns
from repro.dist import HeteroStepConfig, layer_remat, micro_passes
from repro.runtime.driver import DriverConfig, ElasticTrainer

# 2 ranks at 3:1 of C=4, w_max 4; 4 aggregations per epoch, so the loop pulls
# a fourth batch after the third step and drops it at the step budget
CFG = dict(
    arch="smollm-360m", smoke=True, steps=3, seq=16, n_workers=2, micro_bs=1, total_micro=4,
    policy="static", static_ratio="3,1", verbose=False,
)
STEP_PHASES = ("driver.put", "driver.dispatch", "driver.sync", "driver.record")


def _driver_spans(log_dir: str) -> dict:
    """``driver.*`` host events of the profile under ``log_dir``: name ->
    sorted [(start_ns, end_ns, stats)]."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("driver."):
                        out[ev.name].append((ev.start_ns, ev.end_ns, dict(ev.stats)))
    return {k: sorted(v, key=lambda t: t[0]) for k, v in out.items()}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    log_dir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(log_dir, profiler_options=opts):
        trainer = ElasticTrainer(DriverConfig(**CFG))
        trainer.run()
    return trainer, _driver_spans(log_dir)


@pytest.fixture(scope="module")
def untraced():
    trainer = ElasticTrainer(DriverConfig(**CFG))
    trainer.run()
    return trainer


def test_one_step_span_per_step_holding_each_phase_once(traced):
    _, spans = traced
    steps = spans["driver.step"]
    assert [st["step_num"] for _, _, st in steps] == [0, 1, 2]
    for s0, e0, _ in steps:
        inside = {name: [(s, e) for s, e, _ in spans[name] if s0 <= s and e <= e0] for name in STEP_PHASES}
        assert all(len(v) == 1 for v in inside.values()), inside
        # in the loop's order, one after the other
        bounds = [inside[name][0] for name in STEP_PHASES]
        assert all(a[1] <= b[0] for a, b in zip(bounds, bounds[1:]))
    assert sum(len(spans[name]) for name in STEP_PHASES) == 4 * len(steps)
    assert len(spans["driver.rebuild"]) == 1  # the construction's build
    assert "driver.epoch_end" not in spans  # the budget stopped the epoch


def test_batch_spans_end_before_the_step_that_uses_them(traced):
    _, spans = traced
    steps, batches = spans["driver.step"], spans["driver.batch"]
    # one pull per step, then the look-ahead pull the step budget drops
    assert len(batches) == len(steps) + 1
    prev_end = -np.inf
    for (bs, be, _), (ss, se, _) in zip(batches, steps):
        assert prev_end <= bs and be <= ss
        prev_end = se
    assert batches[-1][0] >= steps[-1][1]


def test_profiler_leaves_losses_and_grad_norms_bit_identical(traced, untraced):
    on, _ = traced
    assert len(on.losses) == 3
    assert on.losses == untraced.losses
    assert on.grad_norms == untraced.grad_norms


@pytest.mark.parametrize(
    "mode,alloc,w_max,want",
    [
        ("masked", [3, 1], 4, 6),  # every rank runs the largest allocation
        ("masked", [2, 0, 1], 3, 6),  # a rank with no microbatch still runs it
        ("while", [3, 1], 4, 4),  # each rank loops its own allocation
        ("while", [2, 0, 1], 3, 3),
        ("masked", [4], 8, 4),  # the benchmark's one-rank cell: no slot past the allocation
        ("masked", [0, 0], 2, 0),
        ("masked", [4, 2, 1, 1], 4, 16),  # largest allocation at w_max: every slot, as before
        ("masked", [5, 1], 4, 8),  # an allocation past the buffers is clamped to w_max
        ("while", [5, 1], 4, 5),
        ("while", [0, 0], 2, 0),
    ],
)
def test_micro_passes(mode, alloc, w_max, want):
    scfg = HeteroStepConfig(w_max=w_max, micro_bs=1, seq_len=16, mode=mode)
    assert micro_passes(scfg, np.array(alloc, np.int32)) == want


def test_masked_driver_counts_its_padding(untraced):
    tr = untraced
    per_step = micro_passes(tr.scfg, tr.alloc)
    assert list(tr.alloc) == [3, 1] and tr.w_max == 4
    assert per_step == 2 * 3  # two ranks, each running the largest allocation
    assert (tr.micro_passes_computed, tr.micro_passes_trained) == (3 * per_step, 3 * 4)


def test_while_driver_counts_and_measured_obs(tmp_path):
    """While mode computes exactly what it trains; under measured timing the
    obs timeline holds one ``step`` span per step and no per-worker spans,
    and the snapshot holds the driver's pass counters."""
    cfg = DriverConfig(**dict(CFG, steps=4, mode="while", trace_out=str(tmp_path / "t.json"),
                              metrics_out=str(tmp_path / "m.json")))
    tr = ElasticTrainer(cfg)
    tr.run()
    assert micro_passes(tr.scfg, tr.alloc) == 4
    assert (tr.micro_passes_computed, tr.micro_passes_trained) == (16, 16)
    snap = json.loads((tmp_path / "m.json").read_text())
    assert snap["counters"]["train.micro_passes_computed"] == 16
    assert snap["counters"]["train.micro_passes_trained"] == 16
    assert snap["histograms"]["train.agg_makespan_s"]["count"] == 4
    assert "train.worker_wait_s" not in snap["histograms"]
    evs = json.loads((tmp_path / "t.json").read_text())["traceEvents"]
    steps = [e for e in evs if e.get("name") == "step"]
    assert [e["args"]["step"] for e in steps] == [0, 1, 2, 3]
    assert [e["dur"] for e in steps] == pytest.approx([s * 1e6 for s in tr.step_s])
    assert not {"compute", "wait"} & {e.get("name") for e in evs}


# 8 steps: the first epoch's 4 end at an epoch boundary, a third rank joins at
# step 6 (a rebuild, and a new epoch from its first aggregation)
LONG = dict(CFG, steps=8, events="add@6:rtx2080ti")


@pytest.fixture(scope="module")
def long_traced(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("long")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp / "profile"), profiler_options=opts):
        trainer = ElasticTrainer(DriverConfig(**LONG, metrics_out=str(tmp / "m.json")))
        trainer.run()
    snap = json.loads((tmp / "m.json").read_text())
    return trainer, _driver_spans(str(tmp / "profile")), snap


def _step_bounds(spans):
    return [(s, e) for s, e, _ in spans["driver.step"]]


def test_step_num_runs_on_across_epochs_and_rebuilds(long_traced):
    tr, spans, _ = long_traced
    assert [st["step_num"] for _, _, st in spans["driver.step"]] == list(range(8))
    assert len(tr.gpus) == 3


def test_epoch_end_lies_between_the_epochs_steps(long_traced):
    _, spans, _ = long_traced
    steps = _step_bounds(spans)
    ((s, e, _),) = spans["driver.epoch_end"]
    assert steps[3][1] <= s and e <= steps[4][0]


def test_rebuild_spans_construction_and_membership(long_traced):
    _, spans, _ = long_traced
    steps = _step_bounds(spans)
    rebuilds = [(s, e) for s, e, _ in spans["driver.rebuild"]]
    assert len(rebuilds) == 2
    assert rebuilds[0][1] <= steps[0][0]
    assert steps[5][1] <= rebuilds[1][0] and rebuilds[1][1] <= steps[6][0]


def test_phases_only_inside_steps_and_batches_outside(long_traced):
    _, spans, _ = long_traced
    steps = _step_bounds(spans)

    def inside(s, e):
        return any(s0 <= s and e <= e0 for s0, e0 in steps)

    for name in STEP_PHASES:
        assert len(spans[name]) == len(steps)
        assert all(inside(s, e) for s, e, _ in spans[name])
    for name in ("driver.batch", "driver.epoch_end", "driver.rebuild"):
        assert not any(s < e0 and s0 < e for s, e, _ in spans[name] for s0, e0 in steps)


def test_counters_follow_the_allocation_through_a_rebuild(long_traced):
    """Masked mode: two ranks at allocation [3, 1] for six steps, then three
    ranks at [2, 1, 1]; each rank runs the largest allocation, and every step
    trains the whole C=4 allocation."""
    tr, _, snap = long_traced
    assert list(tr.alloc) == [2, 1, 1]
    want = 6 * 2 * 3 + 2 * 3 * 2
    assert (tr.micro_passes_computed, tr.micro_passes_trained) == (want, 8 * 4)
    assert snap["counters"]["train.micro_passes_computed"] == want
    assert snap["counters"]["train.micro_passes_trained"] == 8 * 4


def test_rebuilt_trainer_step_holds_no_pallas_call_on_the_cpu(long_traced):
    """On the CPU every attention layer trains on the blocked scan: the step
    the membership rebuild made holds matmuls and no Pallas call."""
    tr, _, _ = long_traced
    R, s = len(tr.alloc), tr.scfg
    rows = jax.ShapeDtypeStruct((R, s.w_max, s.micro_bs, s.seq_len), jnp.int32)
    batch = {"inputs": rows, "targets": rows, "alloc": jax.ShapeDtypeStruct((R,), jnp.int32)}
    closed = jax.make_jaxpr(tr.step_fn)(tr.state, batch)
    assert find_eqns(closed, "dot_general")
    assert not find_eqns(closed, "pallas_call")


def test_remat_gauges_follow_the_rows_through_a_rebuild(long_traced):
    """Each build reports the step's remat choice and its residual bytes:
    the CPU reports no memory, so the smoke configuration's setting stays;
    both ranks' rows and then all three fold onto the one device, so the
    residuals grow by half at the rebuild."""
    tr, _, snap = long_traced
    remat, one_row = layer_remat(tr.model_cfg, tr.scfg, tr.mesh, 1)
    assert remat is tr.model_cfg.remat
    g = snap["gauges"]
    assert g["train.layer_remat"] == {"value": int(remat), "min": int(remat), "max": int(remat)}
    assert g["train.residual_bytes"] == {"value": 3 * one_row, "min": 2 * one_row, "max": 3 * one_row}
