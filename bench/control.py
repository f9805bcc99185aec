#!/usr/bin/env python3
"""The readings that set a training cell's correctness limits: the control,
and a fault planted in the reference put in the program's place.

    python3 bench/control.py --workload <name> --seeds 11,12,13

On a TPU, at the cell's own size.  Per seed, each of these is compared with the
float32 reference exactly as a run compares the program (loss gap, worst-leaf
first-gradient gap, worst-leaf change gap):

- ``control``: the reference computed in float8 (bench/reference.py), the
  precision step below the configuration's bfloat16 compute;
- ``half_batch``: the reference trained on the first half of each step's rows,
  the mean taken over them.

A state left unchanged reads 1 on the change gap and needs no run.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def train_readings(cell: dict, seed: int) -> dict:
    from bench import model, traffic, train_cell

    mix, m = cell["traffic_file"], cell["config_file"]["model"]
    key = model.seed_key(seed, 0)
    rows = traffic.TrainRows(m["vocab_size"], mix["seq"], 10**6, seed)
    n = sum(mix["allocation"]) * mix["micro_bs"]
    step_rows = [list(range(k * n, (k + 1) * n)) for k in range(train_cell.CHECK_STEPS)]
    base = train_cell.reference_run(m, mix, key, step_rows, "f32", rows.row)
    out = {}

    def reading(name, rows_of_steps, precision):
        losses, g1, change = train_cell.reference_run(m, mix, key, rows_of_steps, precision, rows.row)
        out[name], _ = train_cell.norm_gaps(base, losses, g1, change)

    reading("control", step_rows, "fp8")
    reading("half_batch", [r[: len(r) // 2] for r in step_rows], "f32")
    return out


def main() -> int:
    from bench import harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    harness.accelerator(1)  # the readings are the reference's alone, which runs on one chip
    harness.use_compile_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        got = train_readings(cell, seed)
        print(json.dumps({"workload": args.workload, "seed": seed, "readings": got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
