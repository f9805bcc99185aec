#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine with the TPU chips the cell asks for.
The run makes its weights and traffic from ``--seed``, warms up every shape the
cell uses (set-up, reported as ``setup_s``), measures for ``--seconds``, checks
what the timed path produced against the plain reference, and prints one JSON
object as the last line of standard output: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer metrics, read from a profiler trace of the window), ``device``,
``breakdown`` (with ``--trace 1``) and ``checks``, each compared number beside
its limit.  Without a TPU, or with fewer chips than the cell asks for, it exits
nonzero and prints no result.
"""

from __future__ import annotations

import argparse
import importlib
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main(argv=None, *, require_tpu: bool = True) -> int:
    """``require_tpu=False`` drives a run on whatever JAX finds, for the
    harness's own tests on the CPU; the command line always requires the TPU."""
    from bench import harness

    t_start = harness.now()
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"bench: {ROOT} holds no program (src/repro)")
    sys.path.insert(0, str(ROOT / "src"))

    cell = harness.load_cell(args.workload)
    import jax

    if require_tpu:
        devs = harness.accelerator(cell["chips"])
    else:
        devs = jax.devices()[: cell["chips"]]
    cache = harness.use_compile_cache() if require_tpu else "off"
    harness.log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; compile cache {cache}")
    counter = harness.CompileCounter()
    driver = importlib.import_module(f"bench.{cell['traffic_file']['kind']}_cell")
    result, checks = driver.run(cell, args, devs, counter, t_start)
    harness.finish(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
