"""A run with the timed path broken underneath must come out not correct.

Each test drives a whole run of a cell at a tiny size on the CPU (the look for
a chip skipped), with one fault planted in the program, and reads ``correct``
from the result line.  The cell's own limits apply.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import contextlib
import io
import json

import jax

from bench import run
from bench.tests import tiny

SEED = 2**33 + 11


def run_cell(monkeypatch, name: str) -> dict:
    tiny.patch(monkeypatch.setattr)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        run.main(["--workload", name, "--seed", str(SEED), "--seconds", "2"], require_tpu=False)
    return json.loads(out.getvalue().strip().splitlines()[-1])


def wrap_step(monkeypatch, wrap):
    import repro.runtime.driver as driver

    build = driver.build_train_step

    def broken(*a, **k):
        return wrap(build(*a, **k))

    monkeypatch.setattr(driver, "build_train_step", broken)


def test_sound_train_run_is_correct(monkeypatch):
    assert run_cell(monkeypatch, "smollm-360m.train-1rank")["correct"] is True


def test_step_that_returns_its_state_unchanged(monkeypatch):
    import repro.runtime.driver as driver

    build = driver.build_train_step

    def unchanged(*a, **k):
        step = build(*a, **dict(k, jit=False))
        return jax.jit(lambda state, batch: (state, step(state, batch)[1]))

    monkeypatch.setattr(driver, "build_train_step", unchanged)
    assert run_cell(monkeypatch, "smollm-360m.train-1rank")["correct"] is False


def test_half_of_the_batch_left_out(monkeypatch):
    def half(step):
        return lambda state, batch: step(state, dict(batch, alloc=(batch["alloc"] + 1) // 2))

    wrap_step(monkeypatch, half)
    assert run_cell(monkeypatch, "smollm-360m.train-1rank")["correct"] is False
