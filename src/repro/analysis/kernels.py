"""Static auditor for Pallas kernels (``pallas_call`` eqns).

Three checks per kernel, all evaluated from the traced jaxpr without running
the kernel:

* **Block-origin bounds** — every BlockSpec index map is evaluated over the
  grid (full enumeration up to a cap, boundary sampling beyond it) using
  ``BlockMapping.compute_start_indices_interpret``, which accepts the real
  scalar-prefetch arrays.  A block whose element origin falls outside the
  operand (or overhangs it) is an ``error``: on TPU that is a silent
  wrong-read, not a crash.
* **Sentinel intent** — kernels that *clamp* an index into a reserved block
  (the paged-attention scratch page, reached via the dead-page ``-1``
  sentinel) declare a :class:`SentinelCheck`; the auditor proves the
  reserved block is reached *iff* the sentinel feeds the index map, so the
  clamp can never swallow a live page.
* **VMEM footprint + divisibility** — resident bytes are estimated as
  2x (double-buffered) in/out blocks plus scratch avals, compared against a
  configurable budget; array dims not divisible by their block dim get a
  warning (Pallas pads, but every kernel in this repo masks explicitly and
  an unintended remainder usually means a config drifted).
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Sequence

import numpy as np

from repro.analysis.findings import Finding
from repro.analysis.jaxpr_walk import eqn_src, find_eqns

__all__ = ["SentinelCheck", "audit_pallas_eqn", "audit_traced", "DEFAULT_VMEM_BUDGET"]

DEFAULT_VMEM_BUDGET = 16 * 2**20  # bytes of VMEM per core (TPU v4-class)
_GRID_ENUM_CAP = 4096  # full-enumeration limit; beyond it, boundary sampling


@dataclasses.dataclass(frozen=True)
class SentinelCheck:
    """Declares an *intentional* clamp onto a reserved block.

    ``live_args`` are scalar-prefetch arrays containing no sentinel values;
    ``dead_args`` are the same arrays with every index replaced by the
    sentinel.  The auditor asserts the reserved origin is unreachable under
    ``live_args`` and always reached (on ``dim``) under ``dead_args``.
    """

    operand: int  # block-mapping index (inputs first, then outputs)
    dim: int  # start-index dimension the clamp lands on
    reserved_start: int  # element origin of the reserved block on `dim`
    live_args: tuple
    dead_args: tuple


def _block_dims(block_shape) -> tuple:
    """Element extent of each block dim (a squeezed dim spans one element)."""
    return tuple(getattr(d, "block_size", 1) for d in block_shape)


def _grid_points(grid: Sequence[int]) -> tuple[list[tuple], bool]:
    """Grid index tuples to evaluate; ``(points, sampled)``."""
    sizes = [int(g) for g in grid]
    total = int(np.prod(sizes)) if sizes else 1
    if total <= _GRID_ENUM_CAP:
        return list(itertools.product(*[range(s) for s in sizes])), False
    per_dim = [sorted({0, 1, s // 2, s - 2, s - 1} & set(range(s))) for s in sizes]
    pts = list(itertools.islice(itertools.product(*per_dim), _GRID_ENUM_CAP))
    return pts, True


def _starts(bm, idx: tuple, scalar_args: tuple) -> tuple | None:
    try:
        raw = bm.compute_start_indices_interpret(idx, *scalar_args)
    except Exception:
        return None
    return tuple(int(np.asarray(s)) for s in raw)


def _itemsize(dtype) -> float:
    return np.dtype(dtype).itemsize


def audit_pallas_eqn(
    eqn,
    path: str,
    target: str,
    *,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    scalar_args: tuple = (),
    sentinel: SentinelCheck | tuple | None = None,
) -> tuple[list[Finding], dict]:
    """Audit one ``pallas_call`` eqn; returns ``(findings, meta)``."""
    sentinels: tuple[SentinelCheck, ...] = (
        () if sentinel is None else (sentinel,) if isinstance(sentinel, SentinelCheck) else tuple(sentinel)
    )
    findings: list[Finding] = []
    gm = eqn.params["grid_mapping"]
    src = eqn_src(eqn)
    grid = tuple(int(g) for g in gm.grid)
    mappings = list(gm.block_mappings)
    points, sampled = _grid_points(grid)

    # --- VMEM: 2x double-buffered blocks + scratch avals -------------------
    block_bytes = 0
    operands = []
    for bm in mappings:
        sd = bm.array_aval
        dims = _block_dims(bm.block_shape)
        nbytes = int(np.prod(dims) * _itemsize(sd.dtype))
        block_bytes += nbytes
        operands.append(
            {
                "origin": getattr(bm, "origin", ""),
                "array_shape": list(sd.shape),
                "block_shape": list(dims),
                "block_bytes": nbytes,
            }
        )
        for d, (a, b) in enumerate(zip(sd.shape, dims)):
            if b and a % b:
                findings.append(
                    Finding(
                        rule="pallas-grid-remainder",
                        severity="warning",
                        target=target,
                        path=f"{path}[{bm.origin}]",
                        message=(
                            f"dim {d} of {tuple(sd.shape)} is not divisible by block "
                            f"dim {b} — Pallas pads the remainder block; confirm the "
                            f"kernel masks it"
                        ),
                        src=src,
                    )
                )
    kernel = eqn.params["jaxpr"]
    n_scratch = gm.num_scratch_operands
    scratch_bytes = 0
    for v in kernel.invars[len(kernel.invars) - n_scratch :] if n_scratch else []:
        aval = v.aval
        scratch_bytes += int(np.prod(aval.shape) * _itemsize(aval.dtype))
    vmem_est = 2 * block_bytes + scratch_bytes
    if vmem_est > vmem_budget:
        findings.append(
            Finding(
                rule="pallas-vmem-budget",
                severity="error",
                target=target,
                path=path,
                message=(
                    f"estimated VMEM {vmem_est} B (2x {block_bytes} B blocks + "
                    f"{scratch_bytes} B scratch) exceeds budget {vmem_budget} B"
                ),
                src=src,
            )
        )

    # --- block-origin bounds over the grid ---------------------------------
    n_checked = 0
    for op_idx, bm in enumerate(mappings):
        sd = bm.array_aval
        dims = _block_dims(bm.block_shape)
        reserved = next((s for s in sentinels if s.operand == op_idx), None)
        seen_oob = False
        for idx in points:
            starts = _starts(bm, idx, scalar_args)
            if starts is None:
                continue
            n_checked += 1
            for d, (s, b, a) in enumerate(zip(starts, dims, sd.shape)):
                if reserved and d == reserved.dim:
                    continue  # judged by the sentinel check below
                if s < 0 or s + b > a:
                    if not seen_oob:  # one finding per operand, first offender
                        findings.append(
                            Finding(
                                rule="pallas-oob-block",
                                severity="error",
                                target=target,
                                path=f"{path}[{bm.origin}]",
                                message=(
                                    f"index map sends grid point {idx} to element "
                                    f"origin {starts}; dim {d} block [{s}, {s + b}) "
                                    f"overruns array dim {a}"
                                ),
                                src=src,
                            )
                        )
                    seen_oob = True

    # --- sentinel intent ----------------------------------------------------
    for sc in sentinels:
        bm = mappings[sc.operand]
        sd = bm.array_aval
        dims = _block_dims(bm.block_shape)
        leak = miss = None
        for idx in points:
            live = _starts(bm, idx, sc.live_args)
            dead = _starts(bm, idx, sc.dead_args)
            if live is not None:
                s = live[sc.dim]
                if s == sc.reserved_start:
                    leak = leak or (idx, live)
                elif s < 0 or s + dims[sc.dim] > sd.shape[sc.dim]:
                    leak = leak or (idx, live)  # escaping the array entirely
            if dead is not None and dead[sc.dim] != sc.reserved_start:
                miss = miss or (idx, dead)
        if leak:
            findings.append(
                Finding(
                    rule="pallas-sentinel-leak",
                    severity="error",
                    target=target,
                    path=f"{path}[{bm.origin}]",
                    message=(
                        f"reserved block at dim {sc.dim} start "
                        f"{sc.reserved_start} is reachable with live (non-sentinel) "
                        f"scalar args: grid point {leak[0]} -> origin {leak[1]} — the "
                        f"clamp would silently swallow a live block"
                    ),
                    src=src,
                )
            )
        if miss:
            findings.append(
                Finding(
                    rule="pallas-sentinel-miss",
                    severity="error",
                    target=target,
                    path=f"{path}[{bm.origin}]",
                    message=(
                        f"sentinel scalar args do NOT land on the reserved block: grid "
                        f"point {miss[0]} -> origin {miss[1]}, expected dim "
                        f"{sc.dim} start {sc.reserved_start} — dead entries "
                        f"would read live data"
                    ),
                    src=src,
                )
            )

    meta = {
        "grid": list(grid),
        "grid_points_checked": len(points),
        "grid_sampled": sampled,
        "n_origin_evals": n_checked,
        "operands": operands,
        "vmem_block_bytes": block_bytes,
        "vmem_scratch_bytes": scratch_bytes,
        "vmem_estimate_bytes": vmem_est,
        "vmem_budget_bytes": vmem_budget,
        "sentinel_checked": len(sentinels),
    }
    return findings, meta


def audit_traced(
    closed_jaxpr,
    target: str,
    *,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    scalar_args: tuple = (),
    sentinel: SentinelCheck | None = None,
) -> tuple[list[Finding], dict[str, Any]]:
    """Find and audit every ``pallas_call`` in a traced program."""
    findings: list[Finding] = []
    metas: dict[str, Any] = {}
    for path, eqn in find_eqns(closed_jaxpr, "pallas_call"):
        f, m = audit_pallas_eqn(
            eqn,
            path,
            target,
            vmem_budget=vmem_budget,
            scalar_args=scalar_args,
            sentinel=sentinel,
        )
        findings.extend(f)
        metas[path] = m
    if not metas:
        findings.append(
            Finding(
                rule="pallas-none-found",
                severity="note",
                target=target,
                path="",
                message="no pallas_call eqns in this trace (interpret path or pure-XLA)",
            )
        )
    return findings, metas
