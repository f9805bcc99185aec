"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

On a TPU the trace holds one plane per chip (``/device:TPU:<n>``) whose
``XLA Ops`` line lists every operation the chip ran, nested: a ``while`` holds
the operations of its body.  ``XLA Modules`` lists the jitted programs and
``Async XLA Ops`` the asynchronous copies and collectives.  The host plane
(``/host:CPU``) holds the harness's own spans (``jax.profiler.TraceAnnotation``)
on the same clock.

Per chip, within the traced window (the harness's ``bench.traced`` span):
busy time is the union of the intervals of ``XLA Ops``; an operation's own time
counts its innermost (leaf) events only; collective time is the union of the
collective operations' intervals, and its exposed part the share of it during
which no other leaf operation runs on that chip.  Idle gaps are attributed to
the innermost harness span open at the gap's midpoint.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import re

WINDOW_SPAN = "bench.traced"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
_OP = re.compile(r"^%?([A-Za-z0-9_.\-]+?)(?:\.\d+)?(?: =|$)")


def short_name(event_name: str) -> str:
    """``%paged_attention.9 = bf16[...] custom-call(...)`` -> ``paged_attention``;
    ``jit_decode_fn(7771...)`` -> ``jit_decode_fn``."""
    name = event_name.split("(")[0] if not event_name.startswith("%") else event_name
    m = _OP.match(name.strip())
    return m.group(1) if m else name.strip()[:80]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _intersect(a, b):
    """Intersection of two sorted disjoint interval lists."""
    i = j = 0
    out = []
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


@dataclasses.dataclass
class Device:
    name: str
    busy_s: float
    leaf_s: dict  # op short name -> seconds of its leaf events
    module_s: dict  # jitted program name -> seconds
    collective_s: float
    exposed_collective_s: float


@dataclasses.dataclass
class Trace:
    window_s: float
    devices: list
    spans: dict  # harness span name -> list of (start_s, end_s), relative to the window
    idle_by_span: dict  # span label -> idle seconds of the first chip

    @property
    def busy_s(self) -> float:
        return sum(d.busy_s for d in self.devices) / max(len(self.devices), 1)

    def leaf_s(self, prefix: str) -> float:
        """Seconds of leaf operations whose name starts with ``prefix``, summed
        over chips."""
        return sum(v for d in self.devices for k, v in d.leaf_s.items() if k.startswith(prefix))

    def module_s(self, prefix: str) -> float:
        return sum(v for d in self.devices for k, v in d.module_s.items() if k.startswith(prefix))

    def breakdown(self) -> dict:
        ops = collections.Counter()
        for d in self.devices:
            for k, v in d.leaf_s.items():
                ops[k] += v / len(self.devices)
        return {
            "device_ops": [[k, v] for k, v in ops.most_common(10)],
            "idle_gaps": [[k, v] for k, v in collections.Counter(self.idle_by_span).most_common(10)],
        }


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def reduce(path: str, span_names: tuple[str, ...]) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans = collections.defaultdict(list)
    device_planes = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in span_names or ev.name in (WINDOW_SPAN, "bench.window"):
                        spans[ev.name].append((ev.start_ns, ev.end_ns))
    if not spans.get(WINDOW_SPAN):
        raise RuntimeError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = spans[WINDOW_SPAN][0]
    devices = [_reduce_device(p, lo, hi) for p in sorted(device_planes, key=lambda p: p.name)]
    idle = {}
    if device_planes:
        idle = _idle_by_span(sorted(device_planes, key=lambda p: p.name)[0], lo, hi, spans)
    rel = {k: [((s - lo) / 1e9, (e - lo) / 1e9) for s, e in v if e > lo and s < hi] for k, v in spans.items()}
    return Trace(window_s=(hi - lo) / 1e9, devices=[d for d, _ in devices], spans=rel, idle_by_span=idle)


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            return [(ev.start_ns, ev.end_ns, ev.name) for ev in line.events]
    return []


def _leaves(events):
    """Events with no other event of the line inside them (the line nests)."""
    evs = sorted(events, key=lambda t: (t[0], -(t[1] - t[0])))
    out = []
    for i, (s, e, name) in enumerate(evs):
        if i + 1 < len(evs) and evs[i + 1][0] < e:
            continue
        out.append((s, e, name))
    return out


def _reduce_device(plane, lo, hi):
    ops = [(s, e, n) for s, e, n in _events(plane, "XLA Ops") if e > lo and s < hi]
    busy = _clip(_union([(s, e) for s, e, _ in ops]), lo, hi)
    leaves = _leaves(ops)
    leaf_s = collections.Counter()
    coll, compute = [], []
    for s, e, n in leaves:
        sn = short_name(n)
        s2, e2 = max(s, lo), min(e, hi)
        leaf_s[sn] += (e2 - s2) / 1e9
        (coll if COLLECTIVE.search(sn) else compute).append((s2, e2))
    for s, e, n in _events(plane, "Async XLA Ops"):
        if e > lo and s < hi and COLLECTIVE.search(short_name(n)):
            coll.append((max(s, lo), min(e, hi)))
    coll_u = _union(coll)
    exposed = _length(coll_u) - _length(_intersect(coll_u, _union(compute)))
    module_s = collections.Counter()
    for s, e, n in _events(plane, "XLA Modules"):
        if e > lo and s < hi:
            module_s[short_name(n)] += (min(e, hi) - max(s, lo)) / 1e9
    dev = Device(
        name=plane.name,
        busy_s=_length(busy) / 1e9,
        leaf_s=dict(leaf_s),
        module_s=dict(module_s),
        collective_s=_length(coll_u) / 1e9,
        exposed_collective_s=exposed / 1e9,
    )
    return dev, busy


def _idle_by_span(plane, lo, hi, spans):
    _, busy = _reduce_device(plane, lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    inner = [(s, e, name) for name, v in spans.items() if name != "bench.traced" for s, e in v]
    out = collections.Counter()
    for s, e in gaps:
        mid = (s + e) / 2
        open_ = [(ss, name) for ss, ee, name in inner if ss <= mid <= ee]
        label = max(open_)[1] if open_ else "no harness span"
        out[label] += (e - s) / 1e9
    return dict(out)
