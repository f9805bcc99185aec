"""Tiny stand-ins for the benchmark's cells, for runs of the harness on the
CPU: the same files, with the model and the traffic shrunk."""

from __future__ import annotations

import copy

from bench import harness

_load_cell = harness.load_cell  # kept from before ``patch`` replaces it

TINY_MODEL = {
    "n_layers": 2,
    "d_model": 128,
    "n_heads": 6,
    "n_kv_heads": 2,
    "head_dim": 16,
    "d_ff": 128,
    "vocab_size": 4096,
    "tie_embeddings": True,
    "rope_theta": 10000.0,
    "norm_eps": 1e-06,
    "max_seq": 32,
    "param_dtype": "float32",
    "compute_dtype": "bfloat16",
}
TINY_TRAIN = {"seq": 256}


def shrink(cell: dict) -> dict:
    cell = copy.deepcopy(cell)
    mix = cell["traffic_file"]
    model = dict(TINY_MODEL, max_seq=TINY_TRAIN["seq"])
    cell["config_file"]["model"] = model
    cell["config_file"]["replace"] = dict(model)
    mix.update(TINY_TRAIN)
    return cell


def patch(monkeypatch_setattr) -> None:
    """Shrink every cell ``bench.harness.load_cell`` returns, and let the
    training driver build the shrunk model (it looks its architecture up in the
    registry).  ``monkeypatch_setattr(obj, name, value)`` sets an attribute."""
    from bench import model
    import repro.runtime.driver as driver

    shrunk = {}

    def load_tiny(name):
        shrunk[name] = shrink(_load_cell(name))
        return shrunk[name]

    def get_config(arch):
        cell = next(iter(shrunk.values()))
        return model.program_config(cell["config_file"])

    v5e = harness.load_json(harness.BENCH / "peaks.json")["devices"]["TPU v5 lite"]
    monkeypatch_setattr(harness, "load_cell", load_tiny)
    monkeypatch_setattr(harness, "peaks", lambda kind: v5e)  # the CPU has no entry: use the chip's
    monkeypatch_setattr(driver, "get_config", get_config)
