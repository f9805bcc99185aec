#!/usr/bin/env python3
"""Compile a cell's step for a described TPU v5e, without the chip, and print
its memory analysis: a rehearsal before a chip run.

    JAX_PLATFORMS=cpu python3 bench/aot.py --workload <name>

A training cell compiles the allocation-aware step (``build_train_step``) on a
mesh of the described chips in the mix's layout.  Nothing runs, so no time
comes of it.
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _report(name, compiled) -> None:
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    print(
        f"{name}: arguments {ma.argument_size_in_bytes} B, outputs {ma.output_size_in_bytes} B, "
        f"temporaries {ma.temp_size_in_bytes} B, aliased {ma.alias_size_in_bytes} B; "
        f"tpu_custom_call {'tpu_custom_call' in text}; all-reduce {'all-reduce' in text}",
        flush=True,
    )


def train(cell, topo) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from bench import model
    from repro.dist import HeteroStepConfig, build_train_step, init_train_state

    mix = cell["traffic_file"]
    cfg = model.program_config(cell["config_file"])
    n = mix["n_ranks"]
    C = mix["total_micro"]
    w_max = max(max(2 * C // n, C // n + 1), max(mix["allocation"]))  # the driver's buffer depth
    devs = np.array(topo.devices[: mix["mesh"][0] * mix["mesh"][1]]).reshape(mix["mesh"])
    mesh = Mesh(devs, ("data", "model"))
    scfg = HeteroStepConfig(w_max=w_max, micro_bs=mix["micro_bs"], seq_len=mix["seq"], mode=mix["mode"], optimizer="adamw")
    step = build_train_step(cfg, scfg, mesh, jit=False)
    rep = NamedSharding(mesh, P())
    state = jax.eval_shape(lambda k: init_train_state(cfg, scfg, k), jax.random.PRNGKey(0))
    state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), state)
    bs = NamedSharding(mesh, P("data"))
    shape = (n if n > 1 else 1, w_max, mix["micro_bs"], mix["seq"])
    batch = {
        "inputs": jax.ShapeDtypeStruct(shape, jnp.int32, sharding=bs),
        "targets": jax.ShapeDtypeStruct(shape, jnp.int32, sharding=bs),
        "alloc": jax.ShapeDtypeStruct((shape[0],), jnp.int32, sharding=bs),
    }
    compiled = jax.jit(step, donate_argnums=(0,)).lower(state, batch).compile()
    _report(f"train step w_max={w_max} mesh={mix['mesh']}", compiled)


def main() -> int:
    from jax.experimental import topologies

    from bench import harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    train(cell, topo)
    return 0


if __name__ == "__main__":
    sys.exit(main())
