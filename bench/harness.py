"""What every cell shares: the benchmark file, the cell's data files, the chip
check, the compile cache, host spans, the compile counter, the per-layer metric
readers and the result line.

Everything a cell is made of is found by name: the configuration in
``bench/configs/<config>.json``, the traffic mix in ``bench/traffic/<traffic>.json``
(its ``kind`` names the driver module, ``bench/<kind>_cell.py``) and each per-layer
metric in ``bench/metrics/<metric>.py``.  Adding a cell, a mix or a metric adds
files and entries; no file here changes.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import statistics
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# JAX's persistent compile cache: a fixed path inside the checkout, so only the
# first run of a cell in a checkout compiles (the path is part of the key).
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration file,
    traffic file, correctness limits (``bench/limits/<name>.json``) and metric
    lists resolved."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json; known: {sorted(cells)}")
    cell = dict(cells[name])
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cell["config_file"] = load_json(ROOT / config["file"])
    cell["traffic_file"] = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    cell["limits"] = load_json(BENCH / "limits" / f"{name}.json")

    def mine(m):
        return name in m.get("workloads", [name])

    cell["end_to_end"] = [m for m in spec["end_to_end"] if mine(m)]
    cell["per_layer"] = [m for m in spec["per_layer"] if mine(m)]
    return cell


# ---------------------------------------------------------------------------
# the chip
# ---------------------------------------------------------------------------


def accelerator(chips: int) -> list:
    """The TPU devices JAX sees.  Exits nonzero, printing no result, when the
    default backend is not a TPU or holds fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU: JAX's default backend is {devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} TPU chip(s), JAX sees {len(devs)}")
    return devs[:chips]


def use_compile_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself), else the
    fixed directory inside the checkout."""
    import jax

    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # every program of a cell, however quick to compile, comes from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return str(CACHE_DIR)


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown chip is an error."""
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise SystemExit(f"bench: no published peaks for device kind {device_kind!r} in bench/peaks.json")
    return table["devices"][device_kind]


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts XLA compilations (and compile-cache loads) while ``armed``."""

    def __init__(self) -> None:
        from jax import monitoring

        self.armed = False
        self.count = 0
        self.names: list[str] = []
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.names.append(str(kw.get("fun_name", "?")))


@contextlib.contextmanager
def span(name: str):
    """A host span from the harness's own files, written into the profiler's
    trace when one is being taken (a no-op cost otherwise)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


# ---------------------------------------------------------------------------
# statistics
# ---------------------------------------------------------------------------


def percentile(values, q: float) -> float:
    """The ``q``-th percentile by linear interpolation (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return statistics.median(values)


def end_to_end(cell: dict, values: dict) -> dict:
    """The cell's end-to-end metrics, each from ``values`` with its unit from
    ``BENCHMARK.json``; a value the cell does not declare goes to standard
    error only."""
    names = [m["name"] for m in cell["end_to_end"]]
    for name, value in values.items():
        if name not in names:
            log(f"not a metric of this cell: {name} {value!r}")
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in cell["end_to_end"]}


# ---------------------------------------------------------------------------
# per-layer metric readers
# ---------------------------------------------------------------------------


def read_per_layer(cell: dict, ctx) -> dict:
    """Run each per-layer metric's reader (``bench/metrics/<name>.py``,
    ``read(ctx) -> float | None``).  A reader that finds nothing to read returns
    None and its metric is left out of the line."""
    out = {}
    for m in cell["per_layer"]:
        path = BENCH / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(f"bench_metric_{len(out)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------


def finish(result: dict, checks: list[tuple[str, float, float]]) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, then the result line, with the same numbers under its last
    key, as the last line of standard output."""
    for name, value, limit in checks:
        log(f"check {name}: {value!r} (limit {limit!r}) {'ok' if value <= limit else 'FAILED'}")
    result["checks"] = {name: {"value": value, "limit": limit} for name, value, limit in checks}
    print(json.dumps(result), flush=True)


def now() -> float:
    return time.perf_counter()


def settle() -> None:
    """The end of set-up: collect garbage once and freeze what survives, so
    that no full collection over set-up's objects (JAX's traced programs
    among them) stalls the host inside the window."""
    gc.collect()
    gc.freeze()


def release() -> None:
    """After the window: unfreeze and collect, so that the program's state is
    freed before the reference runs."""
    gc.unfreeze()
    gc.collect()


def start_trace(log_dir: str) -> None:
    """The profiler, without its Python tracer (which records every Python
    call and slows the host several times over)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
