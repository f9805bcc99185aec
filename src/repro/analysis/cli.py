"""``python -m repro.analysis`` — run every static check, emit the report.

Targets:

* ``train``  — trace `build_train_step` for the smoke-scale legal
  (mode, fsdp, collective) combinations and prove collective uniformity.
* ``serve``  — trace `decode_step` (dense + paged cache) and prove the
  decode path is collective-uniform; audit any Pallas calls in the trace.
* ``kernels`` — audit each Pallas kernel directly: block-origin bounds over
  the grid, the paged-attention dead-page sentinel clamp, VMEM budget,
  grid/block divisibility.
* ``specs``  — audit param/state/cache PartitionSpecs for every config in
  the registry against every declared mesh.
* ``protocol`` — bounded explicit-state model checking of the elastic
  membership protocol (FailureDetector/ElasticCoordinator/FaultInjector)
  and paged-KV admission (PagePool/Scheduler), exhaustively to the
  documented depth bounds; violations carry minimized replayable
  ``kind@step:spec`` counterexample scripts (``--cex-out`` writes them).

Every invocation also runs a selftest: the known-deadlock fixture
(``fixtures.trace_deadlock_step``) must be flagged, the clean twin must
pass, and the pragma-waived twin must come back suppressed — a broken
analyzer is itself an error-severity finding.  The ``protocol`` target
additionally checks itself against known-bad models (a rescale that remaps
detector state by position instead of survivor index; a retirement that
drops the page release): each must yield a minimized counterexample that
REPLAYS, or the run fails.  Exit status is nonzero iff any unsuppressed
error-severity finding exists.  Full-target runs also flag stale pragmas
(waivers that suppressed nothing).

The report is byte-deterministic (no timestamps, sorted findings, sorted
keys); CI runs this twice and byte-compares.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp

from repro.analysis.collectives import check_collective_uniformity
from repro.analysis.costmodel import estimate_cost
from repro.analysis.findings import Finding, build_report, dump_report
from repro.analysis.kernels import DEFAULT_VMEM_BUDGET, SentinelCheck, audit_traced
from repro.analysis.specs_audit import audit_all_specs

TARGETS = ("train", "serve", "kernels", "specs", "protocol")

# documented exploration bounds: the clean models' FULL reachable graphs to
# these depths fit comfortably in the explorer's state ceiling, and every
# seeded bug class is found well inside them
PROTOCOL_DEPTHS = {"elastic": 7, "serve": 12, "serve-faults": 12}

# legal smoke-scale combos; (while, fsdp=True) is rejected by validate() and
# covered by the deadlock fixture instead
TRAIN_COMBOS = (
    ("while", False, "psum"),
    ("while", False, "ring"),
    ("while", "gather", "psum"),
    ("while", "gather", "ring"),
    ("masked", False, "psum"),
    ("masked", True, "psum"),
)

SMOKE_ARCH = "smollm-360m"


def _mesh():
    """Largest (data, model) mesh the host devices allow."""
    from repro.dist.compat import make_mesh

    n = len(jax.devices())
    if n >= 8:
        return make_mesh((4, 2), ("data", "model"))
    if n >= 4:
        return make_mesh((4, 1), ("data", "model"))
    return make_mesh((1, 1), ("data", "model"))


def _smoke_cfg():
    from repro.configs import smoke_config

    return smoke_config(SMOKE_ARCH, seq=32)


def analyze_train(mesh) -> tuple[list[Finding], dict]:
    from repro.dist.hetero_step import HeteroStepConfig, build_train_step, init_train_state
    from repro.optim import AdamWConfig

    cfg = _smoke_cfg()
    findings: list[Finding] = []
    meta: dict = {}
    for mode, fsdp, collective in TRAIN_COMBOS:
        name = f"train:{mode}-fsdp={fsdp}-{collective}"
        scfg = HeteroStepConfig(
            w_max=3,
            micro_bs=2,
            seq_len=32,
            mode=mode,
            alloc_axis="data",
            fsdp=fsdp,
            fsdp_axes=("data",),
            collective=collective,
        ).validate(mesh)
        step = build_train_step(cfg, scfg, mesh, opt_cfg=AdamWConfig(), jit=False)
        key = jax.random.PRNGKey(0)
        state_shape = jax.eval_shape(
            lambda k, scfg=scfg: init_train_state(cfg, scfg, k, AdamWConfig()), key
        )
        R = int(mesh.shape[scfg.alloc_axis])
        batch_shape = {
            "inputs": jax.ShapeDtypeStruct((R, scfg.w_max, scfg.micro_bs, scfg.seq_len), jnp.int32),
            "targets": jax.ShapeDtypeStruct((R, scfg.w_max, scfg.micro_bs, scfg.seq_len), jnp.int32),
            "alloc": jax.ShapeDtypeStruct((R,), jnp.int32),
        }
        closed = jax.make_jaxpr(step)(state_shape, batch_shape)
        f, m = check_collective_uniformity(closed, name)
        findings.extend(f)
        m["cost"] = estimate_cost(closed)
        m["validate"] = "legal"
        meta[name] = m
    return findings, meta


def analyze_serve(mesh) -> tuple[list[Finding], dict]:
    from repro.models import transformer
    from repro.models.attention import PagedLayout

    cfg = _smoke_cfg()
    findings: list[Finding] = []
    meta: dict = {}
    B, S = 4, 64
    key = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(lambda k: transformer.init_params(cfg, k), key)
    variants = {
        "dense": dict(per_slot=False, paged=None),
        "paged": dict(per_slot=True, paged=PagedLayout(page_size=8, n_pages=16, pages_per_slot=8)),
    }
    for vname, kw in variants.items():
        name = f"serve:decode-{vname}"
        cache_shape = jax.eval_shape(lambda kw=kw: transformer.init_cache(cfg, B, S, **kw))
        toks = jax.ShapeDtypeStruct((B,), jnp.int32)

        def step(p, c, t):
            return transformer.decode_step(p, c, t, cfg)

        closed = jax.make_jaxpr(step)(params_shape, cache_shape, toks)
        f, m = check_collective_uniformity(closed, name)
        findings.extend(f)
        kf, km = audit_traced(closed, name)
        # scalar-prefetch index maps can't be evaluated without the live page
        # tables; the kernels target audits them with real tables + sentinel
        findings.extend(x for x in kf if x.rule != "pallas-none-found")
        m["pallas"] = km
        m["cost"] = estimate_cost(closed)
        meta[name] = m
    return findings, meta


def analyze_kernels(vmem_budget: int) -> tuple[list[Finding], dict]:
    import numpy as np

    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.rwkv6_scan import rwkv6_scan

    findings: list[Finding] = []
    meta: dict = {}

    # flash: plain BlockSpecs, no scalar prefetch
    B, Sq, Sk, H, Hkv, Dh = 2, 256, 256, 4, 2, 64
    q = jax.ShapeDtypeStruct((B, Sq, H, Dh), jnp.float32)
    kv = jax.ShapeDtypeStruct((B, Sk, Hkv, Dh), jnp.float32)
    closed = jax.make_jaxpr(lambda q, k, v: flash_attention(q, k, v, interpret=True))(q, kv, kv)
    f, m = audit_traced(closed, "kernels:flash_attention", vmem_budget=vmem_budget)
    findings += f
    meta["flash_attention"] = m

    # paged: scalar-prefetch page tables; the dead-page clamp onto the
    # trailing scratch page must be reachable ONLY via the -1 sentinel
    page_size, n_pages, slots, Bp = 8, 6, 3, 2
    pool = jax.ShapeDtypeStruct((n_pages + 1, Hkv, page_size, Dh), jnp.float32)
    qd = jax.ShapeDtypeStruct((Bp, H, Dh), jnp.float32)
    pages_t = jax.ShapeDtypeStruct((Bp, slots), jnp.int32)
    lens_t = jax.ShapeDtypeStruct((Bp,), jnp.int32)
    closed = jax.make_jaxpr(
        lambda q, kp, vp, pg, ln: paged_attention(q, kp, vp, pg, ln, interpret=True)
    )(qd, pool, pool, pages_t, lens_t)
    live_pages = np.arange(Bp * slots, dtype=np.int32).reshape(Bp, slots)
    full_lens = np.full((Bp,), slots * page_size, np.int32)
    dead_pages = np.full((Bp, slots), -1, np.int32)
    sentinels = tuple(
        SentinelCheck(
            operand=op,  # 0=q, 1=k pool, 2=v pool
            dim=0,
            reserved_start=n_pages,  # the trailing scratch page
            live_args=(live_pages, full_lens),
            dead_args=(dead_pages, full_lens),
        )
        for op in (1, 2)
    )
    f, m = audit_traced(
        closed,
        "kernels:paged_attention",
        vmem_budget=vmem_budget,
        scalar_args=(live_pages, full_lens),
        sentinel=sentinels,
    )
    findings += f
    meta["paged_attention"] = m

    # rwkv6: chunked recurrence
    Br, T, Hr, D = 2, 64, 2, 16
    r = jax.ShapeDtypeStruct((Br, T, Hr, D), jnp.float32)
    u = jax.ShapeDtypeStruct((Hr, D), jnp.float32)
    closed = jax.make_jaxpr(
        lambda r_, k_, v_, w_, u_: rwkv6_scan(r_, k_, v_, w_, u_, chunk=32, interpret=True)
    )(r, r, r, r, u)
    f, m = audit_traced(closed, "kernels:rwkv6_scan", vmem_budget=vmem_budget)
    findings += f
    meta["rwkv6_scan"] = m
    return findings, meta


def analyze_specs() -> tuple[list[Finding], dict]:
    return audit_all_specs()


def analyze_protocol() -> tuple[list[Finding], dict]:
    """Model-check the two protocol harnesses over the real classes."""
    from repro.analysis.protocol import (
        ElasticModel,
        ServeFaultModel,
        ServeModel,
        explore,
        format_script,
    )

    models = {
        "elastic": (ElasticModel(), PROTOCOL_DEPTHS["elastic"]),
        "serve": (ServeModel(), PROTOCOL_DEPTHS["serve"]),
        "serve-faults": (ServeFaultModel(), PROTOCOL_DEPTHS["serve-faults"]),
    }
    findings: list[Finding] = []
    meta: dict = {}
    for name, (model, depth) in models.items():
        target = f"protocol:{name}"
        res = explore(model, max_depth=depth)
        for v in res.violations:
            findings.append(
                Finding(
                    rule=f"protocol-{v.kind}",  # -invariant | -deadlock | -action-error
                    severity="error",
                    target=target,
                    path=format_script(v.script),
                    message=f"{v.message} [replay script: {format_script(v.script) or '<initial state>'}]",
                )
            )
        if not res.exhausted:
            findings.append(
                Finding(
                    rule="protocol-truncated",
                    severity="warning",
                    target=target,
                    path="",
                    message=(
                        f"exploration truncated by {res.truncated_by} — coverage below "
                        f"the documented depth bound ({depth}); shrink the model or "
                        "raise the ceiling"
                    ),
                )
            )
        meta[name] = dict(res.stats(), max_depth=depth)
    return findings, meta


def selftest_protocol() -> tuple[list[Finding], dict]:
    """Prove the model checker catches the bug classes it exists for, and
    that its counterexamples replay.  Known-bad models: a rescale that
    remaps detector state by position instead of survivor index, and a
    retirement that forgets the page release, and a delivery path that skips
    duplicate suppression (hedged completions delivered twice)."""
    from repro.analysis.protocol import (
        ElasticModel,
        ServeFaultModel,
        ServeModel,
        explore,
        format_script,
        parse_script,
        replay,
    )

    cases = {
        "elastic-remap-identity": (lambda: ElasticModel(buggy="remap-identity"), 6),
        "serve-drop-release": (lambda: ServeModel(buggy="drop-release"), 8),
        "serve-faults-double-deliver": (lambda: ServeFaultModel(buggy="double-deliver"), 6),
    }
    findings: list[Finding] = []
    meta: dict = {}
    for name, (make, depth) in cases.items():
        res = explore(make(), max_depth=depth, max_violations=1)
        script, replayed = "", False
        if res.violations:
            v = res.violations[0]
            script = format_script(v.script)
            rv = replay(make(), parse_script(script))
            replayed = rv is not None and rv.kind == v.kind
        if not replayed:
            findings.append(
                Finding(
                    rule="analysis-selftest",
                    severity="error",
                    target=f"selftest:protocol-{name}",
                    path="",
                    message=(
                        f"known-bad model {name!r} did not produce a minimized "
                        "REPLAYABLE counterexample — the protocol checker is broken"
                    ),
                )
            )
        meta[name] = {"counterexample": script, "replayed": replayed, "n_states": res.n_states}
    return findings, meta


def selftest(mesh, used_pragmas: set | None = None) -> tuple[list[Finding], dict]:
    """Prove the checker catches the deadlock class it exists for.

    The fixtures' own findings never enter the report — only meta-findings
    about whether detection worked.
    """
    from repro.analysis import fixtures
    from repro.analysis.findings import apply_pragmas

    findings: list[Finding] = []
    bad, bad_meta = check_collective_uniformity(
        fixtures.trace_deadlock_step(mesh), "selftest:deadlock"
    )
    flagged = [f for f in bad if f.rule == "divergent-collective" and f.severity == "error"]
    if not flagged:
        findings.append(
            Finding(
                rule="analysis-selftest",
                severity="error",
                target="selftest:deadlock",
                path="",
                message=(
                    "the known-deadlock fixture (psum inside a divergent-trip-count "
                    "while body) was NOT flagged — the checker is broken"
                ),
            )
        )
    clean, _ = check_collective_uniformity(fixtures.trace_clean_step(mesh), "selftest:clean")
    if any(f.severity == "error" for f in clean):
        findings.append(
            Finding(
                rule="analysis-selftest",
                severity="error",
                target="selftest:clean",
                path="",
                message="the known-good fixture (collective hoisted out of the loop) was flagged",
            )
        )
    supp, _ = check_collective_uniformity(
        fixtures.trace_suppressed_step(mesh), "selftest:suppressed"
    )
    supp = apply_pragmas(supp, used=used_pragmas)
    if not any(f.suppressed for f in supp):
        findings.append(
            Finding(
                rule="analysis-selftest",
                severity="error",
                target="selftest:suppressed",
                path="",
                message="the '# analysis: ignore[...]' pragma did not suppress the fixture finding",
            )
        )
    meta = {
        "deadlock_flagged_at": sorted(f.path for f in flagged),
        "deadlock_verdict": bad_meta["verdict"],
        "clean_errors": sum(1 for f in clean if f.severity == "error"),
        "pragma_suppressed": sum(1 for f in supp if f.suppressed),
    }
    return findings, meta


def run(targets: list[str], *, vmem_budget: int = DEFAULT_VMEM_BUDGET) -> dict:
    mesh = _mesh()
    findings: list[Finding] = []
    metas: dict = {"mesh": {a: int(s) for a, s in dict(mesh.shape).items()}}
    used_pragmas: set = set()
    f, m = selftest(mesh, used_pragmas=used_pragmas)
    findings += f
    metas["selftest"] = m
    if "train" in targets:
        f, m = analyze_train(mesh)
        findings += f
        metas["train"] = m
    if "serve" in targets:
        f, m = analyze_serve(mesh)
        findings += f
        metas["serve"] = m
    if "kernels" in targets:
        f, m = analyze_kernels(vmem_budget)
        findings += f
        metas["kernels"] = m
    if "specs" in targets:
        f, m = analyze_specs()
        findings += f
        metas["specs"] = m
    if "protocol" in targets:
        f, m = analyze_protocol()
        findings += f
        metas["protocol"] = m
        f, m = selftest_protocol()
        findings += f
        metas["selftest_protocol"] = m
    return build_report(
        findings, metas, used_pragmas=used_pragmas, pragma_scan_root=_pragma_scan_root(targets)
    )


def _pragma_scan_root(targets) -> str | None:
    """Stale-pragma audit root — only for full-target runs: a partial run
    never generates the findings a waiver exists for, so every waiver would
    look stale."""
    if not set(TARGETS).issubset(targets):
        return None
    import repro

    return list(repro.__path__)[0]  # namespace package: __file__ is None


def write_counterexamples(report: dict, out_dir: str) -> None:
    """One replayable script file per protocol violation (CI uploads these
    as artifacts when the analysis lane fails)."""
    os.makedirs(out_dir, exist_ok=True)
    n = 0
    for f in report["findings"]:
        if not f["rule"].startswith("protocol-") or not f["path"]:
            continue
        n += 1
        name = f"{f['target'].replace(':', '-')}-{n:02d}.txt"
        with open(os.path.join(out_dir, name), "w") as fh:
            fh.write(f"# {f['rule']} in {f['target']}\n# {f['message']}\n{f['path']}\n")
    for name, m in report["targets"].get("selftest_protocol", {}).items():
        if m.get("counterexample"):
            with open(os.path.join(out_dir, f"selftest-{name}.txt"), "w") as fh:
                fh.write(f"# selftest counterexample (replayed={m['replayed']})\n{m['counterexample']}\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro.analysis", description=__doc__)
    ap.add_argument("--target", default="all", choices=TARGETS + ("all",))
    ap.add_argument("--json-out", default=None, help="write the findings report here")
    ap.add_argument(
        "--vmem-budget", type=int, default=DEFAULT_VMEM_BUDGET, help="Pallas VMEM budget in bytes"
    )
    ap.add_argument(
        "--cex-out",
        default=None,
        help="directory for protocol counterexample scripts (one .txt per violation)",
    )
    args = ap.parse_args(argv)
    targets = list(TARGETS) if args.target == "all" else [args.target]

    report = run(targets, vmem_budget=args.vmem_budget)
    if args.json_out:
        dump_report(report, args.json_out)
    if args.cex_out:
        write_counterexamples(report, args.cex_out)

    s = report["summary"]
    print(
        f"repro.analysis [{' '.join(targets)}]: "
        f"{s['n_error']} errors, {s['n_warning']} warnings, {s['n_note']} notes, "
        f"{s['n_suppressed']} suppressed"
    )
    for f in report["findings"]:
        if f["suppressed"]:
            continue
        loc = f" ({f['src']})" if f["src"] else ""
        print(f"  [{f['severity']:7s}] {f['rule']:24s} {f['target']} {f['path']}{loc}")
        if f["severity"] == "error":
            print(f"            {f['message']}")
    if args.json_out:
        print(f"report -> {args.json_out}")
    if s["n_error"]:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
