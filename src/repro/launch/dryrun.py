import os

os.environ["XLA_FLAGS"] = " ".join(
    [os.environ.get("XLA_FLAGS", ""), "--xla_force_host_platform_device_count=512"]
).strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The statement above MUST run before any other import (jax locks the device
count at first backend init): 512 host devices back the 16x16 single-pod and
2x16x16 multi-pod production meshes.  It appends to the caller's XLA_FLAGS
rather than replacing them.  Never set this flag globally — smoke tests and
benchmarks see 1 device, and a process that drives a chip never imports this
module.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                    # all cells, both meshes
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi --hetero
  PYTHONPATH=src python -m repro.launch.dryrun --out results/dryrun.json

Per cell it records: compile wall-time, per-device memory analysis
(arguments / temp / output — the "fits in 16 GB HBM" proof), per-device HLO
FLOPs + bytes from cost_analysis, the collective-op inventory parsed
from the compiled HLO (op type, count, result bytes) for §Roofline, and the
``repro.analysis`` cost-model estimate next to the XLA numbers (warning on
>2x disagreement in either direction — estimate drift).
"""

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs import SHAPES, list_archs, skip_reason  # noqa: E402
from repro.launch.mesh import HW, make_production_mesh, make_test_mesh  # noqa: E402
from repro.launch.specs import plan_cell  # noqa: E402

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "pred": 1,
}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[\d,]*\][^ ]*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)\b"
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Collective result-bytes per op type, from the post-SPMD per-device HLO."""
    stats: dict[str, dict] = {}
    for shape_str, op in _COLL_RE.findall(hlo_text):
        b = _shape_bytes(shape_str)
        s = stats.setdefault(op, {"count": 0, "bytes": 0})
        s["count"] += 1
        s["bytes"] += b
    return stats


_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_COMP_HDR_RE = re.compile(r"^%?([\w.\-]+)\s*(?:\([^)]*\))?\s*->.*\{|^%?([\w.\-]+)\s*\{", re.M)


def loop_aware_collective_bytes(hlo_text: str, trips: list[int]) -> dict:
    """Collective bytes with while-loop bodies weighted by their trip counts.

    cost_analysis and a flat HLO scan both count loop bodies once.  We build
    the computation call graph, find each computation's loop DEPTH (number of
    while-bodies on its call path: depth 1 = accumulation loop, depth 2 =
    layer scan inside it, ...), and weight its collective bytes by
    ``prod(trips[:depth])``.  ``trips`` is outermost-first; deeper loops than
    given default to trip 1 beyond the list product.
    """
    blocks: dict[str, str] = {}
    current, buf = None, []
    for line in hlo_text.splitlines():
        if not line.startswith(" ") and "{" in line:
            if current:
                blocks[current] = "\n".join(buf)
            name = line.split("(")[0].strip().lstrip("%").split(" ")[0]
            current, buf = name, [line]
        else:
            buf.append(line)
    if current:
        blocks[current] = "\n".join(buf)

    body_ref = re.compile(r"(?:body|condition)=%?([\w.\-]+)")
    call_ref = re.compile(r"(?:to_apply|calls)=%?([\w.\-]+)")

    # BFS from every computation at depth 0; while-body edges add +1 depth.
    depth: dict[str, int] = {name: 0 for name in blocks}
    changed = True
    guard = 0
    while changed and guard < 50:
        changed = False
        guard += 1
        for name, text in blocks.items():
            d = depth[name]
            for child in body_ref.findall(text):
                if child in depth and depth[child] < d + 1:
                    depth[child] = d + 1
                    changed = True
            for child in call_ref.findall(text):
                if child in depth and depth[child] < d:
                    depth[child] = d
                    changed = True

    def weight(d: int) -> int:
        w = 1
        for t in trips[:d]:
            w *= t
        return w

    by_depth: dict[int, int] = {}
    weighted = 0
    for name, text in blocks.items():
        b = sum(_shape_bytes(s) for s, _ in _COLL_RE.findall(text))
        if not b:
            continue
        d = depth[name]
        by_depth[d] = by_depth.get(d, 0) + b
        weighted += b * weight(d)
    return {"by_depth_bytes": by_depth, "weighted_bytes": weighted, "trips": trips}


def _analysis_crosscheck(plan, mesh, rec: dict, warn_ratio: float = 2.0) -> dict:
    """Cross-check ``repro.analysis``'s jaxpr cost model against XLA.

    The analyzer estimates from the GLOBAL pre-SPMD trace; dividing by device
    count approximates the per-device share that ``cost_analysis`` reports.
    Both count loop bodies once, so the figures are comparable; a gap beyond
    ``warn_ratio``x in either direction (``--cost-warn-ratio``, default 2x)
    flags estimate drift (in the cost model or in what XLA fuses away)
    without failing the cell.
    """
    if warn_ratio <= 1.0:
        raise ValueError(f"warn_ratio must be > 1 (got {warn_ratio}): it bounds both directions")
    try:
        from repro.analysis.costmodel import estimate_cost, per_device

        n_dev = 1
        for s in dict(mesh.shape).values():
            n_dev *= int(s)
        closed = jax.make_jaxpr(plan.fn)(*plan.abstract_args)
        dev = per_device(estimate_cost(closed), n_dev)
        est_flops = dev["flops"]
        est_bytes = dev["bytes"]
        out = {
            "analysis_flops_per_dev": est_flops,
            "analysis_bytes_per_dev": est_bytes,
        }
        hlo_flops = rec.get("hlo_flops_per_dev", 0.0)
        if hlo_flops > 0 and est_flops > 0:
            ratio = est_flops / hlo_flops
            out["analysis_flops_ratio"] = round(ratio, 3)
            if ratio > warn_ratio or ratio < 1.0 / warn_ratio:
                out["analysis_flops_warn"] = True
                print(
                    f"[WARN] analysis/XLA flops disagree {ratio:.2f}x (warn at {warn_ratio:g}x) "
                    f"({est_flops:.3e} vs {hlo_flops:.3e} per dev) — cost model drift?",
                    flush=True,
                )
        return out
    except Exception as e:  # noqa: BLE001 — the cross-check must never fail a cell
        return {"analysis_crosscheck_error": f"{type(e).__name__}: {e}"}


def run_cell(
    arch: str, shape_name: str, mesh, mesh_name: str, hetero: bool, cost_warn_ratio: float = 2.0
) -> dict:
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "hetero": hetero}
    reason = skip_reason(arch, shape_name)
    if reason:
        rec.update(status="skipped", reason=reason)
        return rec
    t0 = time.time()
    try:
        plan = plan_cell(arch, shape_name, mesh, hetero=hetero)
        # donate the train state / decode cache (the real launchers do) so the
        # memory analysis reflects steady-state buffers, not double-buffering
        donate = (0,) if plan.kind == "train" else ((1,) if plan.kind == "decode" else ())
        jitted = jax.jit(
            plan.fn,
            in_shardings=plan.in_shardings,
            out_shardings=plan.out_shardings,
            donate_argnums=donate,
        )
        lowered = jitted.lower(*plan.abstract_args)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        colls = collective_stats(hlo)
        per_dev_bytes = ma.argument_size_in_bytes + ma.temp_size_in_bytes + ma.output_size_in_bytes
        rec.update(
            status="ok",
            kind=plan.kind,
            notes=plan.notes,
            compile_s=round(time.time() - t0, 1),
            # persistent params+optimizer bytes on ONE device under the cell's
            # state sharding — the figure the fsdp="gather" mode drives down
            # (full replication would be n_devices x this on an FSDP mesh)
            state_gb=round(plan.state_bytes_per_dev / 1e9, 3),
            arg_gb=round(ma.argument_size_in_bytes / 1e9, 3),
            temp_gb=round(ma.temp_size_in_bytes / 1e9, 3),
            out_gb=round(ma.output_size_in_bytes / 1e9, 3),
            peak_gb=round(per_dev_bytes / 1e9, 3),
            fits_hbm=bool(per_dev_bytes < HW.HBM_BYTES),
            hlo_flops_per_dev=float(ca.get("flops", 0.0)),
            hlo_bytes_per_dev=float(ca.get("bytes accessed", 0.0)),
            collectives=colls,
            collective_bytes_per_dev=int(sum(s["bytes"] for s in colls.values())),
        )
        rec.update(_analysis_crosscheck(plan, mesh, rec, warn_ratio=cost_warn_ratio))
    except Exception as e:  # noqa: BLE001 — a failed cell is a bug; record it
        rec.update(
            status="error",
            compile_s=round(time.time() - t0, 1),
            error=f"{type(e).__name__}: {e}",
            traceback=traceback.format_exc()[-2000:],
        )
    return rec


def _run_isolated(args) -> None:
    """Shell out one subprocess per cell and merge the JSON records."""
    import subprocess
    import sys
    import tempfile

    from repro.configs import list_archs as _archs

    archs = [args.arch] if args.arch else _archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    records = []
    n_fail = 0
    for mesh_name in meshes:
        for arch in archs:
            for shape_name in shapes:
                with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as tf:
                    cell_out = tf.name
                cmd = [
                    sys.executable, "-m", "repro.launch.dryrun",
                    "--arch", arch, "--shape", shape_name, "--mesh", mesh_name,
                    "--out", cell_out,
                    "--cost-warn-ratio", str(args.cost_warn_ratio),
                ] + (["--hetero"] if args.hetero else [])
                proc = subprocess.run(cmd, capture_output=True, text=True, timeout=1800)
                sys.stdout.write(proc.stdout)
                sys.stdout.flush()
                try:
                    with open(cell_out) as f:
                        recs = json.load(f)
                    records.extend(recs)
                    n_fail += sum(1 for r in recs if r["status"] == "error")
                except Exception:
                    n_fail += 1
                    records.append({
                        "arch": arch, "shape": shape_name,
                        "mesh": f"{mesh_name}_pod", "status": "error",
                        "error": f"subprocess died (rc={proc.returncode}): "
                        + proc.stderr.strip().splitlines()[-1][:300] if proc.stderr else "no stderr",
                    })
                    print(f"[FAIL] {mesh_name:18s} {arch:28s} {shape_name:12s} subprocess rc={proc.returncode}")
                os.unlink(cell_out)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(records, f, indent=1)
    ok = sum(1 for r in records if r["status"] == "ok")
    sk = sum(1 for r in records if r["status"] == "skipped")
    print(f"\n{ok} ok / {sk} skipped / {n_fail} failed -> {args.out}")
    if n_fail:
        raise SystemExit(1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="single arch id (default: all)")
    ap.add_argument("--shape", default=None, help="single shape name (default: all)")
    ap.add_argument(
        "--mesh",
        default="both",
        choices=["single", "multi", "both", "data8"],
        help="'data8' = an (8, 1) pure-data mesh: the fsdp='gather' memory "
        "demonstrator (per-device state must drop ~8x vs replication)",
    )
    ap.add_argument("--hetero", action="store_true", help="lower the while-mode hetero step with W_max headroom")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument(
        "--cost-warn-ratio",
        type=float,
        default=2.0,
        help="warn when the analyzer/XLA flops ratio leaves [1/R, R] (default 2.0; "
        "tighten to catch smaller cost-model drift, loosen for exotic fusions)",
    )
    ap.add_argument(
        "--isolate",
        action="store_true",
        help="run each cell in a subprocess (an XLA C++ CHECK failure in one cell "
        "then records as FAIL instead of killing the sweep)",
    )
    args = ap.parse_args()
    if args.cost_warn_ratio <= 1.0:
        ap.error(f"--cost-warn-ratio must be > 1 (got {args.cost_warn_ratio}): bounds both directions")

    if args.isolate:
        return _run_isolated(args)

    archs = [args.arch] if args.arch else list_archs()
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh()))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16", make_production_mesh(multi_pod=True)))
    if args.mesh == "data8":
        meshes.append(("data8_8x1", make_test_mesh((8, 1), ("data", "model"))))

    records = []
    n_fail = 0
    for mesh_name, mesh in meshes:
        for arch in archs:
            # iterate every assigned shape; skips are recorded with reasons
            shapes = [args.shape] if args.shape else list(SHAPES)
            for shape_name in shapes:
                rec = run_cell(
                    arch, shape_name, mesh, mesh_name, args.hetero,
                    cost_warn_ratio=args.cost_warn_ratio,
                )
                records.append(rec)
                if rec["status"] == "ok":
                    print(
                        f"[OK]   {mesh_name:18s} {arch:28s} {shape_name:12s} "
                        f"{rec['compile_s']:6.1f}s  peak {rec['peak_gb']:7.2f} GB/dev "
                        f"{'FITS' if rec['fits_hbm'] else 'OOM '}  "
                        f"state {rec['state_gb']:7.3f} GB/dev  "
                        f"flops/dev {rec['hlo_flops_per_dev']/1e12:8.3f}T  "
                        f"coll {rec['collective_bytes_per_dev']/1e9:7.3f} GB  ({rec['notes']})",
                        flush=True,
                    )
                elif rec["status"] == "skipped":
                    print(f"[SKIP] {mesh_name:18s} {arch:28s} {shape_name:12s} {rec['reason']}", flush=True)
                else:
                    n_fail += 1
                    print(f"[FAIL] {mesh_name:18s} {arch:28s} {shape_name:12s} {rec['error']}", flush=True)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(records, f, indent=1)
    ok = sum(1 for r in records if r["status"] == "ok")
    sk = sum(1 for r in records if r["status"] == "skipped")
    print(f"\n{ok} ok / {sk} skipped / {n_fail} failed -> {args.out}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
