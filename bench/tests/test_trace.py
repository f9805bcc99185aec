"""The trace reduction, on synthetic intervals and on a small recorded TPU
trace (``data/serve_ticks.xplane.pb``: three decode ticks of the paged engine
at smollm-360m width, recorded by ``record_trace.py``).

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_trace.py -q
"""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data" / "serve_ticks.xplane.pb"


def test_short_names():
    assert trace.short_name("%paged_attention.9 = bf16[240,1,64] custom-call(s32[16,512] %a)") == "paged_attention"
    assert trace.short_name("%constant_dynamic-slice_fusion.7 = bf16[1] fusion(%x)") == "constant_dynamic-slice_fusion"
    assert trace.short_name("%all-reduce.3 = f32[8] all-reduce(%g)") == "all-reduce"
    assert trace.short_name("jit_decode_fn(7771232792470528006)") == "jit_decode_fn"


def test_interval_arithmetic():
    u = trace._union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [[0, 3], [5, 9]]
    assert trace._length(u) == 7
    assert trace._intersect(u, [[2, 6]]) == [[2, 3], [5, 6]]
    assert trace._clip(u, 1, 8) == [[1, 3], [5, 8]]


def test_leaves_of_a_nested_line():
    # a while (0-10) holding two ops, the second holding one more
    events = [(0, 10, "%while.1 = x"), (1, 3, "%fusion.2 = x"), (4, 9, "%call.3 = x"), (5, 6, "%dot.4 = x")]
    assert [n for _, _, n in trace._leaves(events)] == ["%fusion.2 = x", "%dot.4 = x"]


@pytest.fixture(scope="module")
def recorded():
    if not DATA.exists():
        pytest.skip("no recorded trace")
    return trace.reduce(str(DATA), ("ServeEngine.tick",))


def test_recorded_trace(recorded):
    t = recorded
    assert [d.name for d in t.devices] == ["/device:TPU:0"]
    dev = t.devices[0]
    assert len(t.spans["ServeEngine.tick"]) == 3
    assert 0 < dev.busy_s <= t.window_s
    # three decode programs, each holding the paged kernel's 32 layer calls
    assert dev.module_s["jit_decode_fn"] <= dev.busy_s
    assert 0 < t.leaf_s("paged_attention") <= dev.module_s["jit_decode_fn"]
    # every idle second of the window is attributed to some span
    assert sum(t.idle_by_span.values()) == pytest.approx(t.window_s - dev.busy_s, rel=1e-9, abs=1e-9)
    assert dev.collective_s == 0.0 and dev.exposed_collective_s == 0.0
    # the raw events, summed by hand: three jit_decode_fn modules of 80698442,
    # 80696935 and 80698171 ns; 96 paged_attention events (3 ticks x 32 layers)
    assert dev.module_s["jit_decode_fn"] == pytest.approx(0.242093548, abs=1e-9)
    assert t.leaf_s("paged_attention") == pytest.approx(0.217425429, abs=1e-9)
    top = t.breakdown()["device_ops"]
    assert top[0][0] == "paged_attention" and len(top) <= 10
