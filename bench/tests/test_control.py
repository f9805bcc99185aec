"""The control, computed in float8 where the configuration states bfloat16,
must fail at least one of the cell's numbers, at a size a test run holds
(tiny, on the CPU).  The readings at the cell's own size on the chip, which
set the limits, are made by ``bench/control.py`` and listed in PERF.md.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

from bench import control, harness
from bench.tests import tiny


def test_train_control_fails():
    cell = harness.load_cell("smollm-360m.train-1rank")
    got = control.train_readings(tiny.shrink(cell), seed=2**33 + 21)
    lim = cell["limits"]
    assert any(got["control"][k] > lim[k] for k in lim), got
