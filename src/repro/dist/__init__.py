"""``repro.dist`` — the heterogeneous-allocation distribution layer.

* :mod:`repro.dist.hetero_step` — the per-rank variable-microbatch train
  step (the paper's core mechanism).
* :mod:`repro.dist.collectives` — ring allreduce + error-feedback gradient
  compression.
* :mod:`repro.dist.sharding` — divisibility-aware PartitionSpec assignment.
* :mod:`repro.dist.compat` — the shard_map and mesh entry points (Auto axes, device prefix).
"""

from repro.dist.collectives import (
    all_gather_params,
    compress_error_feedback,
    decompress_update,
    init_error_state,
    reduce_scatter_tree,
    ring_all_gather,
    ring_allreduce,
    ring_allreduce_tree,
    ring_reduce_scatter,
)
from repro.dist.hetero_step import HeteroStepConfig, build_train_step, init_train_state, micro_passes
from repro.dist.sharding import cache_specs, param_specs, state_specs

__all__ = [
    "HeteroStepConfig",
    "build_train_step",
    "init_train_state",
    "micro_passes",
    "ring_allreduce",
    "ring_allreduce_tree",
    "ring_all_gather",
    "ring_reduce_scatter",
    "all_gather_params",
    "reduce_scatter_tree",
    "init_error_state",
    "compress_error_feedback",
    "decompress_update",
    "param_specs",
    "state_specs",
    "cache_specs",
]
