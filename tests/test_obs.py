"""repro.obs: histogram correctness, tracer determinism, hook bundles.

Histogram tests pin the two properties the latency BENCHes lean on —
merge-associativity (bucket counts and every derived percentile combine
exactly) and the sqrt(growth) relative percentile error bound vs exact
sample quantiles — plus the snapshot schema roundtrip the CI determinism
lanes byte-compare.  The serve-loop tests drive the real ``serve_loop``
with a tiny fake engine so the obs hook protocol and the ``tick_cost``
clock are covered without a jax model in the loop.
"""

from __future__ import annotations

import json
import math

import numpy as np
import pytest

from repro.obs import (
    NULL_TRACER,
    SCHEMA,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    ServeObs,
    Tracer,
    TrainObs,
    VirtualClock,
    bench_rows_snapshot,
    registry_from_snapshot,
)

# ---------------------------------------------------------------------------
# histogram
# ---------------------------------------------------------------------------


def _fill(h: Histogram, xs) -> Histogram:
    for x in xs:
        h.record(float(x))
    return h


def test_histogram_percentile_error_bound():
    """Any percentile read is within sqrt(growth) of the exact sample
    quantile (inverse-CDF convention), independent of the distribution."""
    rng = np.random.default_rng(0)
    for name, xs in [
        ("lognormal", rng.lognormal(0.0, 1.5, 4000)),
        ("uniform", rng.uniform(0.5, 50.0, 4000)),
        ("bimodal", np.concatenate([rng.normal(1.0, 0.05, 2000), rng.normal(30.0, 1.0, 2000)])),
    ]:
        xs = np.abs(xs)
        h = _fill(Histogram(), xs)
        bound = math.sqrt(h.growth) - 1.0 + 1e-9
        for q in (1, 10, 25, 50, 75, 90, 99):
            exact = float(np.percentile(xs, q, method="inverted_cdf"))
            got = h.percentile(q)
            rel = abs(got - exact) / exact
            assert rel <= bound, f"{name} p{q}: {got} vs exact {exact} (rel {rel:.4f})"


def test_histogram_merge_associativity_and_commutativity():
    rng = np.random.default_rng(1)
    xs = rng.lognormal(0.0, 2.0, 3000)
    parts = [Histogram(), Histogram(), Histogram()]
    for i, x in enumerate(xs):
        parts[i % 3].record(float(x))
    a = parts[0].merge(parts[1]).merge(parts[2])
    b = parts[0].merge(parts[1].merge(parts[2]))
    c = parts[2].merge(parts[0]).merge(parts[1])
    for other in (b, c):
        assert a.buckets == other.buckets
        assert (a.count, a.zero_count, a.vmin, a.vmax) == (
            other.count,
            other.zero_count,
            other.vmin,
            other.vmax,
        )
        # float addition order: sums agree to ulp-level, not bit-level
        assert a.total == pytest.approx(other.total, rel=1e-12)
        for q in (50, 90, 99):
            assert a.percentile(q) == other.percentile(q)
    # the merge equals the histogram of the union of samples
    whole = _fill(Histogram(), xs)
    assert a.buckets == whole.buckets and a.count == whole.count


def test_histogram_merge_rejects_mismatched_bucketing():
    with pytest.raises(ValueError, match="bucketing"):
        Histogram(growth=1.08).merge(Histogram(growth=1.5))
    with pytest.raises(ValueError, match="bucketing"):
        Histogram(min_value=1e-9).merge(Histogram(min_value=1e-3))


def test_histogram_edge_cases():
    h = Histogram()
    assert h.count == 0 and h.percentile(50) is None and h.mean is None
    # single value: every percentile is that value (clamped to [vmin, vmax])
    h.record(3.7)
    for q in (0, 50, 100):
        assert h.percentile(q) == pytest.approx(3.7)
    assert h.mean == pytest.approx(3.7)
    # zero/espilon values land in the dedicated zero bucket
    z = Histogram(min_value=1e-6)
    z.record(0.0)
    z.record(1e-9)
    assert z.zero_count == 2 and z.count == 2
    assert z.percentile(50) == 0.0  # vmin of the zero-bucket samples
    # invalid inputs
    with pytest.raises(ValueError):
        h.record(-1.0)
    with pytest.raises(ValueError):
        h.record(float("nan"))
    with pytest.raises(ValueError):
        h.percentile(101)
    with pytest.raises(ValueError):
        Histogram(growth=1.0)


def test_counter_and_gauge():
    c = Counter()
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = Gauge()
    g.set(2.0)
    g.set(-1.0)
    g.set(0.5)
    assert (g.value, g.min, g.max) == (0.5, -1.0, 2.0)


def test_snapshot_roundtrip_byte_identical():
    rng = np.random.default_rng(2)
    reg = MetricsRegistry()
    reg.counter("a.events").inc(7)
    reg.gauge("a.util").set(0.25)
    h = reg.histogram("a.lat")
    for x in rng.lognormal(0.0, 1.0, 500):
        h.record(float(x))
    reg.histogram("a.empty")
    snap = reg.snapshot()
    assert snap["schema"] == SCHEMA
    restored = registry_from_snapshot(snap).snapshot()
    assert json.dumps(snap, sort_keys=True) == json.dumps(restored, sort_keys=True)
    # derived percentile fields present and ordered
    hs = snap["histograms"]["a.lat"]
    assert hs["p50"] <= hs["p90"] <= hs["p99"]


def test_snapshot_rejects_unknown_schema():
    with pytest.raises(ValueError, match="schema"):
        registry_from_snapshot({"schema": "something/else"})


def test_bench_rows_snapshot_adapter():
    rows = [
        ("kernel_flash_64", 123.4, "tpu_flops=3.2e9 hbm_bytes=1048576"),
        ("kernel_scan", 5.0, "free text, no numbers"),
    ]
    snap = bench_rows_snapshot(rows)
    assert snap["schema"] == SCHEMA
    g = snap["gauges"]
    assert g["kernels.kernel_flash_64.us"]["value"] == pytest.approx(123.4)
    assert g["kernels.kernel_flash_64.tpu_flops"]["value"] == pytest.approx(3.2e9)
    assert g["kernels.kernel_flash_64.hbm_bytes"]["value"] == 1048576
    assert g["kernels.kernel_scan.us"]["value"] == 5.0
    assert "kernels.kernel_scan.free" not in g


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def _demo_trace() -> Tracer:
    tr = Tracer(clock=VirtualClock())
    tr.span("train/worker 0", "compute", 0.0, 1.5, {"alloc": 3})
    tr.span("train/worker 1", "compute", 0.0, 1.2)
    tr.span("train/worker 1", "wait", 1.2, 0.3)
    tr.instant("train/events", "checkpoint", 1.5, {"step": 4})
    tr.counter("serve/scheduler", "queue_depth", 2.0, {"queued": 4})
    return tr


def test_tracer_deterministic_bytes(tmp_path):
    p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
    _demo_trace().export(str(p1))
    _demo_trace().export(str(p2))
    assert p1.read_bytes() == p2.read_bytes()
    doc = json.loads(p1.read_text())
    assert set(doc) == {"traceEvents", "displayTimeUnit"}


def test_tracer_track_interning_and_event_shape():
    tr = _demo_trace()
    evs = tr.to_dict()["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    procs = {e["args"]["name"]: e["pid"] for e in meta if e["name"] == "process_name"}
    assert procs == {"train": 0, "serve": 1}  # first-use order
    threads = {(e["pid"], e["args"]["name"]): e["tid"] for e in meta if e["name"] == "thread_name"}
    assert threads[(0, "worker 0")] == 0 and threads[(0, "worker 1")] == 1
    spans = [e for e in evs if e["ph"] == "X"]
    assert spans[0]["ts"] == 0.0 and spans[0]["dur"] == 1.5e6  # seconds -> us
    assert [e["ph"] for e in evs if e["ph"] in "iC"] == ["i", "C"]
    assert len(tr) == len(evs)


def test_null_tracer_is_inert(tmp_path):
    assert not NULL_TRACER.enabled
    NULL_TRACER.span("a", "b", 0.0, 1.0)
    NULL_TRACER.instant("a", "b", 0.0)
    assert len(NULL_TRACER) == 0
    with pytest.raises(RuntimeError):
        NULL_TRACER.export(str(tmp_path / "x.json"))


# ---------------------------------------------------------------------------
# hook bundles on the real serve loop (fake engine: no jax in the loop)
# ---------------------------------------------------------------------------


class _FakeEngine:
    """Minimal serve_loop-compatible engine: each active slot emits one token
    per tick; requests retire after max_gen tokens.  Dense-style attended
    accounting so tick_cost models see realistic numbers."""

    def __init__(self, n_slots=2, max_seq=8):
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.pool = None
        self.slots = [None] * n_slots  # rid or None
        self._gen = {}  # rid -> [made, max_gen]
        self.ticks = 0
        self.prefills = 0
        self.prefill_tokens = 0
        self.tokens_out = 0
        self.active_slot_ticks = 0
        self.attended_key_tokens = 0
        self.last_tick_attended = 0
        self.last_tick_active = 0

    @property
    def has_active(self):
        return any(r is not None for r in self.slots)

    @property
    def free_slots(self):
        return [b for b, r in enumerate(self.slots) if r is None]

    def admissible(self, L, G):
        return L + G <= self.max_seq

    def can_admit_now(self, L, G):
        return self.admissible(L, G) and bool(self.free_slots)

    def admit(self, rid, prompt, max_gen):
        b = self.free_slots[0]
        self.prefills += 1
        self.prefill_tokens += int(prompt.shape[0])
        self.tokens_out += 1
        if max_gen <= 1:
            return b, (rid, [1])
        self.slots[b] = rid
        self._gen[rid] = [1, max_gen]
        return b, None

    def tick(self):
        self.last_tick_active = self.n_slots - len(self.free_slots)
        self.last_tick_attended = self.n_slots * self.max_seq
        self.attended_key_tokens += self.last_tick_attended
        self.ticks += 1
        self.active_slot_ticks += self.last_tick_active
        fins = []
        for b, rid in enumerate(self.slots):
            if rid is None:
                continue
            st = self._gen[rid]
            st[0] += 1
            self.tokens_out += 1
            if st[0] >= st[1]:
                self.slots[b] = None
                fins.append((rid, [1] * st[1]))
        return fins

    def metrics(self):
        return {
            "n_slots": self.n_slots,
            "ticks": self.ticks,
            "prefills": self.prefills,
            "prefill_tokens": self.prefill_tokens,
            "tokens_out": self.tokens_out,
            "attended_key_tokens": self.attended_key_tokens,
            "slot_utilization": self.active_slot_ticks / (self.ticks * self.n_slots) if self.ticks else 0.0,
        }


def _requests(n=6, max_gen=4):
    from repro.serve import Request

    return [
        Request(rid=i, prompt=np.zeros(2, np.int32), max_gen=max_gen, arrival=float(i // 2))
        for i in range(n)
    ]


def test_serve_loop_obs_hooks_fire():
    from repro.serve import SchedulerConfig, serve_loop

    obs = ServeObs(metrics=MetricsRegistry(), tracer=Tracer(clock=VirtualClock()))
    serve_loop(_FakeEngine(), _requests(), SchedulerConfig(max_waiting_prefill=1), obs=obs)
    snap = obs.metrics.snapshot()
    assert snap["counters"]["serve.completed"] == 6
    assert snap["counters"]["serve.prefills"] == 6
    assert snap["counters"]["serve.defers.prefill_cap"] >= 1  # cap 1, 2 arrivals/tick
    ttft = snap["histograms"]["serve.ttft"]
    per_tok = snap["histograms"]["serve.per_token"]
    assert ttft["count"] == 6 and per_tok["count"] == 6
    assert per_tok["p50"] == pytest.approx(1.0)  # unit ticks, 1 token/tick
    spans = [e for e in obs.tracer.to_dict()["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == 6  # one request span per completion


def test_serve_loop_tick_cost_scales_clock():
    from repro.serve import SchedulerConfig, serve_loop

    reqs_unit = _requests()
    reqs_half = _requests()
    s_unit = serve_loop(_FakeEngine(), reqs_unit, SchedulerConfig())
    s_half = serve_loop(_FakeEngine(), reqs_half, SchedulerConfig(), tick_cost=lambda e: 0.5)
    assert s_unit["ticks"] == s_half["ticks"]  # same work, different clock
    assert s_half["ticks_elapsed"] < s_unit["ticks_elapsed"]
    lat_u = [r.latency for r in reqs_unit]
    lat_h = [r.latency for r in reqs_half]
    assert max(lat_h) < max(lat_u)


def test_serve_loop_without_obs_unchanged():
    """Control: the obs/tick_cost defaults must leave behavior identical."""
    from repro.serve import SchedulerConfig, serve_loop

    a, b = _requests(), _requests()
    sa = serve_loop(_FakeEngine(), a, SchedulerConfig())
    sb = serve_loop(_FakeEngine(), b, SchedulerConfig(), obs=None, tick_cost=None)
    assert sa["ticks"] == sb["ticks"] and sa["ticks_elapsed"] == sb["ticks_elapsed"]
    assert [r.t_finish for r in a] == [r.t_finish for r in b]


def test_train_obs_epoch_spans_and_fault_windows(tmp_path):
    obs = TrainObs(trace_out=str(tmp_path / "t.json"), metrics_out=str(tmp_path / "m.json"))
    alloc, gpus = np.array([3, 1]), ["v100", "gtx1080ti"]
    obs.on_epoch(0, 4, 4, [0.5, 0.8], 0.1, alloc, gpus, per_agg=True, coll_bytes=1000)
    obs.on_fault(4, "slow@4:1*2~2", 2)
    obs.on_epoch(1, 8, 4, [0.5, 0.8], 0.1, alloc, gpus, per_agg=True, coll_bytes=1000)
    obs.on_checkpoint(8)
    obs.close()
    doc = json.loads((tmp_path / "t.json").read_text())
    evs = doc["traceEvents"]
    names = [e["name"] for e in evs]
    assert "compute" in names and "wait" in names and "collective" in names
    windows = [e for e in evs if e["name"].startswith("fault window")]
    assert len(windows) == 1
    # the window opened at step 4 (vt = 4 aggs * 0.9s) and spans 2 steps
    assert windows[0]["ts"] == pytest.approx(4 * 0.9 * 1e6)
    assert windows[0]["dur"] == pytest.approx(2 * 0.9 * 1e6)
    snap = json.loads((tmp_path / "m.json").read_text())
    assert snap["counters"]["train.steps"] == 8
    assert snap["counters"]["train.collective_bytes"] == 8000
    assert snap["histograms"]["train.worker_wait_s"]["count"] == 16


def test_train_obs_measured_steps(tmp_path):
    """Measured timing: one ``step`` span per step with its wall time on the
    virtual clock, and no per-worker split of one fused step's wall."""
    obs = TrainObs(trace_out=str(tmp_path / "t.json"), metrics_out=str(tmp_path / "m.json"))
    alloc, gpus = np.array([3, 1]), ["rtx2080ti", "rtx2080ti"]
    obs.on_epoch(0, 3, 3, [0.6, 0.2], 0.0, alloc, gpus, per_agg=False, coll_bytes=10, step_s=[0.25, 0.5, 0.25])
    obs.on_fault(3, "slow@3:1*2~1", 1)
    obs.on_epoch(1, 4, 1, [0.3, 0.1], 0.0, alloc, gpus, per_agg=False, coll_bytes=10, step_s=[0.5])
    obs.close()
    evs = json.loads((tmp_path / "t.json").read_text())["traceEvents"]
    steps = [e for e in evs if e.get("name") == "step"]
    assert [e["args"]["step"] for e in steps] == [0, 1, 2, 3]
    assert [e["ts"] for e in steps] == pytest.approx([0.0, 0.25e6, 0.75e6, 1.0e6])
    assert [e["dur"] for e in steps] == pytest.approx([0.25e6, 0.5e6, 0.25e6, 0.5e6])
    assert not {"compute", "wait", "collective"} & {e.get("name") for e in evs}
    (window,) = [e for e in evs if e["name"].startswith("fault window")]
    assert window["ts"] == pytest.approx(1.0e6) and window["dur"] == pytest.approx(0.5e6)
    snap = json.loads((tmp_path / "m.json").read_text())
    assert snap["counters"]["train.steps"] == 4
    assert snap["histograms"]["train.agg_makespan_s"]["count"] == 4
    assert "train.worker_compute_s" not in snap["histograms"]
    assert "train.worker_wait_s" not in snap["histograms"]


def test_train_obs_micro_pass_counters(tmp_path):
    obs = TrainObs(metrics_out=str(tmp_path / "m.json"))
    obs.on_micro_passes(8, 4)
    obs.on_micro_passes(8, 4)
    obs.close()
    snap = json.loads((tmp_path / "m.json").read_text())
    assert snap["counters"]["train.micro_passes_computed"] == 16
    assert snap["counters"]["train.micro_passes_trained"] == 8
    off = TrainObs()
    off.on_micro_passes(8, 4)  # disabled: no registry, no error
    assert off.metrics is None


def test_disabled_obs_bundles_do_no_work():
    obs = TrainObs()  # no outputs -> disabled
    assert not obs.enabled
    obs.on_epoch(0, 4, 4, [0.5], 0.1, np.array([4]), ["v100"], per_agg=True, coll_bytes=0)
    obs.on_fault(0, "x", None)
    obs.close()  # nothing to export, no error
    s = ServeObs()
    assert not s.enabled and len(s.tracer) == 0


# ---------------------------------------------------------------------------
# straggler flag context (satellite: observed/baseline/step on every flag)
# ---------------------------------------------------------------------------


def test_straggler_flags_carry_context():
    from repro.runtime.monitor import StragglerMonitor

    mon = StragglerMonitor(2, window=8)
    for k in range(6):
        mon.observe(np.array([1.0, 1.0]), epoch=k, step=4 * k)
    flags = mon.observe(np.array([1.0, 5.0]), epoch=6, step=24)
    assert len(flags) == 1
    f = flags[0]
    assert f.worker == 1 and f.observed == pytest.approx(5.0) and f.baseline == pytest.approx(1.0)
    entry = mon.flag_log[-1]
    assert entry["step"] == 24 and entry["epoch"] == 6
    assert entry["observed"] == pytest.approx(5.0) and entry["baseline"] == pytest.approx(1.0)


def test_ring_allreduce_bytes_formula():
    from repro.dist.collectives import ring_allreduce_bytes

    assert ring_allreduce_bytes(1000, 1) == 0
    assert ring_allreduce_bytes(1000, 2) == 1000  # 2 * (1/2) * B
    assert ring_allreduce_bytes(1000, 4) == 1500  # 2 * (3/4) * B
