#!/usr/bin/env python3
"""Record the small TPU trace that ``test_trace.py`` reads: three decode ticks
of the paged engine at smollm-360m width (8 slots, 256 pages), inside the
harness's ``bench.traced`` and ``ServeEngine.tick`` spans.

    python3 bench/tests/record_trace.py <output.xplane.pb>

On a TPU, from the root of a checkout.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out: str) -> int:
    import jax
    import numpy as np

    from bench import harness, model, trace
    from repro.serve.engine import ServeEngine

    harness.accelerator(1)
    cfile = harness.load_json(harness.BENCH / "configs" / "smollm-360m.json")
    cfg = model.program_config(cfile)
    params = model.program_tree(model.make_weights(cfile["model"], model.seed_key(1, 0)), cfg)
    eng = ServeEngine(cfg, params, n_slots=8, max_seq=2048, attn_impl="paged", page_size=16, pool_pages=256, min_bucket=16)
    rng = np.random.default_rng(1)
    for b in range(8):
        eng.admit(b, rng.integers(0, cfg.vocab_size, 200, dtype=np.int32), 100)
    eng.tick()
    with tempfile.TemporaryDirectory(dir=ROOT) as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        with harness.span("bench.traced"):
            for _ in range(3):
                with harness.span("ServeEngine.tick"):
                    eng.tick()
        jax.profiler.stop_trace()
        shutil.copy(trace.find_xplane(d), out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
