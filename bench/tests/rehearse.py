"""Run every cell of BENCHMARK.json end to end on the CPU at a tiny size.

    JAX_PLATFORMS=cpu python bench/tests/rehearse.py [--trace 0|1] [cell ...]

A four-chip cell needs ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    from bench import run
    from bench.tests import tiny

    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seed", type=int, default=2**33 + 7)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("cells", nargs="*")
    args = ap.parse_args()
    cells = args.cells or [w["name"] for w in json.load(open(ROOT / "BENCHMARK.json"))["workloads"]]
    tiny.patch(setattr)
    for name in cells:
        run.main(["--workload", name, "--seed", str(args.seed), "--seconds", str(args.seconds),
                  "--trace", str(args.trace)], require_tpu=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
