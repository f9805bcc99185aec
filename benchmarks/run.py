"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Sections:
  * paper figures 6-13 (convergence, static ratios, adaptive trajectory,
    elastic cluster, AD-PSGD comparison, speedups) — run live (1 CPU device);
  * kernel micro-benches (interpret mode, analytic TPU work in `derived`);
  * roofline summary rows — read from results/roofline.json when present
    (produced by ``python -m benchmarks.roofline``, which needs the 512-device
    dry-run env and therefore runs as its own process).

Usage: PYTHONPATH=src python -m benchmarks.run [--only substring]
       PYTHONPATH=src python -m benchmarks.run --scenario elastic
       PYTHONPATH=src python -m benchmarks.run --scenario serve
       PYTHONPATH=src python -m benchmarks.run --scenario decode-perf

``--scenario elastic`` runs the fig. 11 membership experiment END-TO-END
through the elastic driver (real training steps, simulated speeds): a
weak-card fleet trains, the weak card is replaced by a V100 mid-run, and
the per-epoch time must drop.  Emits one ``BENCH {...}`` json line and
writes it to ``--json-out`` (default results/bench_elastic.json).

``--scenario serve`` benchmarks the serving engine (continuous batching vs
the static-batch baseline on one mixed-length workload — continuous must
sustain higher aggregate tok/s) and the adaptive traffic router (paper's
allocator as a serving plug-in: heterogeneous 2-replica cluster, adaptive
vs equal split — adaptive must win on makespan/p95).  ``--smoke`` shrinks
the workload for CI.

``--scenario faults`` runs the seeded fault-injection campaign (straggler /
netdeg / outage scenarios x seeds) through the elastic driver and scores
recovery_ticks, goodput retention, and allocation re-convergence.  All
scored metrics derive from seeded simulated timing, so the BENCH json is
bit-identical across reruns at a fixed ``--campaign-seed`` and CI gates on
it (determinism by byte-compare + summary floors).

``--scenario serve-faults`` runs the SERVING fault campaign
(``repro.traces.serve_campaign``): replica outage with re-dispatch,
slow replica with hedged duplicates (first-completion-wins, suppressed by
request id), and page-pool pressure relieved by paged preemption on a real
engine.  Gateable summary: duplicates must be 0, every request completes,
preempted outputs are token-identical, p99-TTFT inflation bounded.  All
scores derive from seeded virtual-clock timing, so the BENCH json is
bit-identical across reruns and CI double-runs + cmp's it.

``--scenario decode-perf`` A/Bs the dense per-slot KV cache against the
paged layout (page pool + Pallas ragged paged-decode kernel) on one
mixed-length workload: token output must be identical request-for-request,
and the analytic decode cost (FLOPs/bytes derived from attended KV
positions, the same accounting style as ``bench_kernels``) must drop >= 2x
because paged attends O(live tokens) instead of ``n_slots x max_seq``.
Also demonstrates the dense layout's hard rejection disappearing: a
``prompt + max_gen > max_seq`` request completes under the paged engine,
token-identical to a single-request dense reference.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _roofline_rows() -> list[tuple]:
    path = os.path.join(os.path.dirname(__file__), "..", "results", "roofline.json")
    if not os.path.exists(path):
        return [("roofline_table", 0.0, "missing: run `python -m benchmarks.roofline` first")]
    with open(path) as f:
        recs = json.load(f)
    rows = []
    for r in recs:
        if r.get("status") != "ok":
            continue
        name = f"roofline_{r['arch']}_{r['shape']}"
        derived = (
            f"bound={r['bound']} compute_ms={r['t_compute_s']*1e3:.3f} "
            f"mem_ms={r['t_memory_s']*1e3:.3f} coll_ms={r['t_collective_s']*1e3:.3f} "
            f"useful={r['useful_flops_ratio']:.2f} roofline_frac={r['roofline_frac']:.2f}"
        )
        rows.append((name, r.get("analysis_s", 0.0) * 1e6, derived))
    return rows


def run_elastic_scenario(json_out: str | None, steps: int = 48) -> dict:
    """Fig. 11 through the real driver: replace the weak card, time drops.

    Returns (and BENCH-prints) per-epoch times split at the replacement
    event; ``improvement`` is the relative drop of the mean per-aggregation
    makespan once the V100 is in the fleet.
    """
    from repro.runtime.driver import DriverConfig, ElasticTrainer

    replace_at = steps // 2
    cfg = DriverConfig(
        arch="smollm-360m",
        smoke=True,
        steps=steps,
        seq=16,
        micro_bs=1,
        total_micro=12,
        n_workers=3,
        hetero_gpus="rtx2080ti,rtx2080ti,gtx1080ti",  # fleet with one weak card
        steps_per_epoch=4,
        policy="adaptive",
        events=f"replace@{replace_at}:2=v100",  # fig. 11: weak -> strong
        seed=0,
        verbose=False,
    )
    res = ElasticTrainer(cfg).run()
    pre = [e["agg_s"] for e in res["epoch_log"] if "v100" not in e["gpus"]]
    post = [e["agg_s"] for e in res["epoch_log"] if "v100" in e["gpus"]]
    bench = {
        "scenario": "elastic",
        "arch": res["arch"],
        "steps": res["steps"],
        "replace_at_step": replace_at,
        "fleet_before": ["rtx2080ti", "rtx2080ti", "gtx1080ti"],
        "fleet_after": res["gpus"],
        "final_allocation": res["final_allocation"],
        "last_loss": res["last_loss"],
        "epoch_log": res["epoch_log"],
        "pre_replace_agg_s": pre,
        "post_replace_agg_s": post,
        "pre_mean_s": float(sum(pre) / len(pre)) if pre else None,
        "post_mean_s": float(sum(post) / len(post)) if post else None,
        "improvement": (
            float(1.0 - (sum(post) / len(post)) / (sum(pre) / len(pre))) if pre and post else None
        ),
    }
    print("BENCH " + json.dumps(bench))
    if json_out:
        os.makedirs(os.path.dirname(json_out) or ".", exist_ok=True)
        with open(json_out, "w") as f:
            json.dump(bench, f, indent=1)
    return bench


def run_faults_scenario(
    json_out: str | None, smoke: bool = False, campaign_seed: int = 0
) -> dict:
    """Seeded fault-injection campaign through the elastic driver (simulated
    heterogeneous timing): straggler onset/recovery, network degradation,
    correlated outages — swept over seeds, scored on recovery time, goodput
    retention, and allocation re-convergence (``repro.traces.campaign``).

    Every scored quantity derives from seeded SIMULATED timing, so the BENCH
    json is bit-identical across reruns at a fixed ``--campaign-seed`` — CI
    runs the smoke twice and byte-compares, then gates on the summary.
    ``--smoke`` trims the sweep to the three canonical scenarios x 2 seeds.
    """
    from repro.traces.campaign import CampaignConfig, run_campaign

    seeds = (campaign_seed, campaign_seed + 1)
    if smoke:
        cfg = CampaignConfig(scenarios=("straggler", "netdeg", "outage"), seeds=seeds)
    else:
        cfg = CampaignConfig(
            scenarios=("straggler", "netdeg", "outage", "mixed", "random"),
            seeds=seeds + (campaign_seed + 2,),
        )
    bench = run_campaign(cfg)
    print("BENCH " + json.dumps(bench))
    if json_out:
        os.makedirs(os.path.dirname(json_out) or ".", exist_ok=True)
        with open(json_out, "w") as f:
            json.dump(bench, f, indent=1)
    return bench


def run_serve_faults_scenario(
    json_out: str | None, smoke: bool = False, campaign_seed: int = 0
) -> dict:
    """Seeded fault campaign for the serving stack: replica outage /
    slow replica (routed virtual-clock fleets) + pool-pressure preemption
    (real paged engine).  See ``repro.traces.serve_campaign``."""
    from repro.traces.serve_campaign import ServeCampaignConfig, run_serve_campaign

    seeds = (campaign_seed, campaign_seed + 1)
    if smoke:
        cfg = ServeCampaignConfig(seeds=(campaign_seed,))
    else:
        cfg = ServeCampaignConfig(seeds=seeds)
    bench = run_serve_campaign(cfg)
    print("BENCH " + json.dumps(bench))
    if json_out:
        os.makedirs(os.path.dirname(json_out) or ".", exist_ok=True)
        with open(json_out, "w") as f:
            json.dump(bench, f, indent=1)
    return bench


def run_serve_scenario(json_out: str | None, smoke: bool = False) -> dict:
    """Continuous batching vs static batching, and adaptive routing vs equal
    split, through the real serving stack (smoke-scale model on CPU).

    Engine A/B: identical mixed-length closed workloads; continuous batching
    retires slots independently so it finishes in fewer decode ticks and
    sustains higher aggregate tok/s.  Router A/B: two real engine replicas
    on virtual clocks at the paper's GPU speed ratio (gtx1080ti vs v100);
    the adaptive router converges traffic shares to measured tokens/sec and
    must beat the equal split on makespan.
    """
    import dataclasses

    import jax

    from repro.configs import smoke_config
    from repro.core.hetero import GPU_RELATIVE_THROUGHPUT
    from repro.models import init_params
    from repro.serve import (
        EngineReplica,
        RouterConfig,
        SchedulerConfig,
        ServeEngine,
        WorkloadConfig,
        run_router,
        serve_loop,
        synthesize,
    )

    n_requests = 8 if smoke else 24
    max_seq = 48
    cfg = smoke_config("smollm-360m", seq=max_seq)
    cfg = dataclasses.replace(cfg, param_dtype="float32", compute_dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))

    engine = ServeEngine(cfg, params, n_slots=4, max_seq=max_seq, seed=0)
    wl = WorkloadConfig(
        n_requests=n_requests, rate=0.0, prompt_len=(4, 16), gen_len=(4, 28),
        vocab_size=cfg.vocab_size, seed=0,
    )

    # warm the jit caches (decode + every prompt bucket) so the A/B timing
    # compares steady-state serving, not compilation
    serve_loop(engine, synthesize(wl), SchedulerConfig(continuous=True))

    engine_runs = {}
    for mode, continuous in [("continuous", True), ("static", False)]:
        # best-of-3: tick counts are deterministic, wall time on a shared CPU
        # is not — take the cleanest run of each mode
        best = None
        for _ in range(3):
            engine.reset()
            reqs = synthesize(wl)
            summary = serve_loop(
                engine, reqs, SchedulerConfig(max_waiting_prefill=2, continuous=continuous)
            )
            if best is None or summary["wall_s"] < best["wall_s"]:
                best = summary
        engine_runs[mode] = best

    speedup = (
        engine_runs["continuous"]["throughput_tok_per_s"]
        / engine_runs["static"]["throughput_tok_per_s"]
        if engine_runs["static"]["throughput_tok_per_s"]
        else None
    )

    # -- router: heterogeneous 2-replica cluster, adaptive vs equal ----------
    # Sustained load (arrival rate ~ aggregate service rate): the split
    # decides how fast the backlog drains, which is where equal-split piles
    # work onto the slow replica — the serving mirror of the paper's fig. 8.
    speeds = {"gtx1080ti": GPU_RELATIVE_THROUGHPUT["gtx1080ti"], "v100": GPU_RELATIVE_THROUGHPUT["v100"]}
    router_wl = WorkloadConfig(
        n_requests=16 if smoke else 32, rate=0.9, prompt_len=(4, 12), gen_len=(6, 20),
        vocab_size=cfg.vocab_size, seed=1,
    )
    engines = {name: ServeEngine(cfg, params, n_slots=2, max_seq=max_seq, seed=0) for name in speeds}
    router_runs = {}
    for policy in ("adaptive", "equal"):
        for e in engines.values():
            e.reset()
        replicas = [EngineReplica(name, engines[name], speed=s) for name, s in speeds.items()]
        router_runs[policy] = run_router(
            replicas, synthesize(router_wl), RouterConfig(policy=policy, window=4 if smoke else 6)
        )

    improvement = (
        1.0 - router_runs["adaptive"]["makespan"] / router_runs["equal"]["makespan"]
        if router_runs["equal"]["makespan"]
        else None
    )
    bench = {
        "scenario": "serve",
        "arch": cfg.name,
        "engine": {
            **engine_runs,
            "throughput_speedup": round(speedup, 3) if speedup else None,
        },
        "router": {
            **router_runs,
            "replica_speeds": speeds,
            "makespan_improvement": round(improvement, 3) if improvement is not None else None,
        },
    }
    print("BENCH " + json.dumps(bench))
    if json_out:
        os.makedirs(os.path.dirname(json_out) or ".", exist_ok=True)
        with open(json_out, "w") as f:
            json.dump(bench, f, indent=1)
    return bench


def run_decode_perf_scenario(json_out: str | None, smoke: bool = False) -> dict:
    """Dense vs paged decode on identical mixed-length traffic (smoke-scale
    model on CPU, Pallas kernel in interpret mode).

    The derived FLOPs/bytes columns are ANALYTIC (what the attended KV
    positions cost on TPU), so the >= 2x acceptance gate is deterministic —
    interpret-mode wall time is reported but never gated on."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import smoke_config
    from repro.models import decode_step, init_cache, init_params
    from repro.serve import Request, SchedulerConfig, ServeEngine, WorkloadConfig, serve_loop, synthesize

    max_seq = 48
    page_size = 4
    n_slots = 4
    cfg = smoke_config("smollm-360m", seq=max_seq + 16)
    cfg = dataclasses.replace(cfg, param_dtype="float32", compute_dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    wl = WorkloadConfig(
        n_requests=6 if smoke else 16, rate=0.4, prompt_len=(4, 12), gen_len=(4, 24),
        vocab_size=cfg.vocab_size, seed=0,
    )

    engines = {
        "dense": ServeEngine(cfg, params, n_slots=n_slots, max_seq=max_seq, seed=0),
        "paged": ServeEngine(
            cfg, params, n_slots=n_slots, max_seq=max_seq, seed=0,
            attn_impl="paged", page_size=page_size,
        ),
    }
    outputs, runs = {}, {}
    for name, eng in engines.items():
        reqs = synthesize(wl)
        t0 = time.time()
        summary = serve_loop(eng, reqs, SchedulerConfig(max_waiting_prefill=2))
        runs[name] = {
            "ticks": summary["ticks"],
            "wall_s": round(time.time() - t0, 3),
            "attended_key_tokens": eng.attended_key_tokens,
            "slot_utilization": summary["slot_utilization"],
        }
        outputs[name] = {r.rid: r.output for r in reqs}
    tokens_identical = outputs["dense"] == outputs["paged"]

    # analytic decode cost per engine: attended KV positions x attention
    # layers x (4*H*Dh flops for qk+pv; k+v unique HBM bytes), as in
    # bench_kernels' derived columns
    n_attn = sum(1 for s in cfg.layer_specs() if s.kind == "attn")
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    itemsize = jnp.dtype(cfg.compute_dtype).itemsize
    for name, r in runs.items():
        r["analytic_flops"] = r["attended_key_tokens"] * n_attn * H * 4 * Dh
        r["analytic_hbm_bytes"] = r["attended_key_tokens"] * n_attn * Hkv * Dh * 2 * itemsize
    reduction = runs["dense"]["analytic_flops"] / runs["paged"]["analytic_flops"]

    # -- beyond-max_seq: the dense layout's hard rejection, gone --------------
    rng = np.random.default_rng(7)
    L, G = 12, max_seq - 12 + 24  # prompt + max_gen = 72 > max_seq = 48
    prompt = rng.integers(0, cfg.vocab_size, L).astype(np.int32)
    long_req = Request(rid=0, prompt=prompt, max_gen=G)
    engines["paged"].reset()
    serve_loop(engines["paged"], [long_req], SchedulerConfig())
    step = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg))
    cache = init_cache(cfg, 1, L + G + page_size)
    lg = None
    for t in range(L):
        lg, cache = step(params, cache, jnp.asarray(prompt[None, t]))
    ref = []
    for _ in range(G):
        tok = int(jnp.argmax(lg, axis=-1)[0])
        ref.append(tok)
        lg, cache = step(params, cache, jnp.array([tok]))
    long_ok = long_req.output == ref

    bench = {
        "scenario": "decode-perf",
        "arch": cfg.name,
        "n_slots": n_slots,
        "max_seq": max_seq,
        "page_size": page_size,
        "pool_pages": engines["paged"].layout.n_pages,
        "n_attn_layers": n_attn,
        "dense": runs["dense"],
        "paged": runs["paged"],
        "tokens_identical": tokens_identical,
        "analytic_flops_reduction": round(reduction, 3),
        "long_request": {
            "prompt_len": L,
            "max_gen": G,
            "exceeds_max_seq_by": L + G - max_seq,
            "completed": long_req.output is not None and len(long_req.output) == G,
            "matches_dense_reference": long_ok,
        },
    }
    print("BENCH " + json.dumps(bench))
    if json_out:
        os.makedirs(os.path.dirname(json_out) or ".", exist_ok=True)
        with open(json_out, "w") as f:
            json.dump(bench, f, indent=1)
    return bench


def run_latency_scenario(json_out: str | None, smoke: bool = False) -> dict:
    """Latency percentiles (p50/p90/p99 TTFT + per-token) on a bursty trace:
    continuous-vs-static admission and paged-vs-dense KV, same requests.

    Time is MODELED: each tick costs ``base + work_frac * attended /
    (n_slots * max_seq)`` modeled seconds, normalized so a dense tick is
    exactly 1.0 (dense always attends the full cache) and paged ticks are
    cheaper in proportion to live tokens — the same analytic accounting as
    ``decode-perf``, applied to the clock instead of FLOPs.  Every number
    derives from the seeded trace + the model, so the BENCH json is
    bit-identical across reruns and CI double-runs + cmp's it."""
    import dataclasses

    import jax

    from repro.configs import smoke_config
    from repro.models import init_params
    from repro.obs import MetricsRegistry, ServeObs
    from repro.serve import SchedulerConfig, ServeEngine, serve_loop
    from repro.traces import bundled_trace, to_requests

    trace = bundled_trace("pai_small")
    n_requests = 16 if smoke else 48
    time_scale = 0.35  # compress the trace's bursts so 4 slots saturate
    n_slots, page_size = 4, 4
    tasks = trace.tasks[:n_requests]
    max_seq = max(t.prompt_len + t.gen_len for t in tasks)
    cfg = smoke_config("smollm-360m", seq=max_seq + 16)
    cfg = dataclasses.replace(cfg, param_dtype="float32", compute_dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))

    dense_work = n_slots * max_seq  # what a dense tick always attends

    def tick_cost(engine) -> float:
        return 0.25 + 0.75 * engine.last_tick_attended / dense_work

    engines = {
        "dense": ServeEngine(cfg, params, n_slots=n_slots, max_seq=max_seq, seed=0),
        "paged": ServeEngine(
            cfg, params, n_slots=n_slots, max_seq=max_seq, seed=0,
            attn_impl="paged", page_size=page_size,
        ),
    }
    runs = {}
    for name, kv, continuous in [
        ("continuous_dense", "dense", True),
        ("static_dense", "dense", False),
        ("continuous_paged", "paged", True),
    ]:
        eng = engines[kv]
        eng.reset()
        reqs = to_requests(
            trace, vocab_size=cfg.vocab_size, seed=0, time_scale=time_scale, limit=n_requests
        )
        obs = ServeObs(metrics=MetricsRegistry())
        summary = serve_loop(
            eng, reqs, SchedulerConfig(max_waiting_prefill=2, continuous=continuous),
            obs=obs, tick_cost=tick_cost,
        )
        snap = obs.metrics.snapshot()

        def pcts(hist_name: str) -> dict | None:
            h = snap["histograms"].get(hist_name)
            if h is None:
                return None
            return {q: h[q] for q in ("p50", "p90", "p99")} | {"count": h["count"]}

        runs[name] = {
            "kv": kv,
            "continuous": continuous,
            "completed": snap["counters"].get("serve.completed", 0),
            "ticks": summary["ticks"],
            "makespan_modeled": round(summary["ticks_elapsed"], 6),
            "slot_utilization": summary["slot_utilization"],
            "defers": {
                k.rsplit(".", 1)[1]: v
                for k, v in snap["counters"].items()
                if k.startswith("serve.defers.")
            },
            "ttft": pcts("serve.ttft"),
            "per_token": pcts("serve.per_token"),
            "e2e_latency": pcts("serve.e2e_latency"),
        }

    bench = {
        "scenario": "latency",
        "arch": cfg.name,
        "trace": trace.name,
        "requests": n_requests,
        "n_slots": n_slots,
        "max_seq": max_seq,
        "page_size": page_size,
        "time_scale": time_scale,
        "tick_model": "0.25 + 0.75 * attended / (n_slots * max_seq)",
        "runs": runs,
        "continuous_ttft_p99_speedup": round(
            runs["static_dense"]["ttft"]["p99"] / max(runs["continuous_dense"]["ttft"]["p99"], 1e-9), 3
        ),
        "paged_per_token_p50_speedup": round(
            runs["continuous_dense"]["per_token"]["p50"]
            / max(runs["continuous_paged"]["per_token"]["p50"], 1e-9),
            3,
        ),
    }
    print("BENCH " + json.dumps(bench))
    if json_out:
        os.makedirs(os.path.dirname(json_out) or ".", exist_ok=True)
        with open(json_out, "w") as f:
            json.dump(bench, f, indent=1)
    return bench


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="run benches whose name contains this")
    ap.add_argument("--skip-paper", action="store_true")
    ap.add_argument(
        "--scenario",
        default=None,
        choices=["elastic", "serve", "serve-faults", "decode-perf", "faults", "latency"],
        help="run one end-to-end scenario (emits a BENCH json line) instead of the CSV benches",
    )
    ap.add_argument("--smoke", action="store_true", help="shrink the scenario workload (CI)")
    ap.add_argument("--json-out", default=None, help="scenario json path (default results/bench_<scenario>.json)")
    ap.add_argument("--campaign-seed", type=int, default=0, help="base seed for --scenario faults sweeps")
    ap.add_argument(
        "--metrics-out",
        default=None,
        help="CSV benches only: also write the rows as a repro.obs.metrics/v1 snapshot json",
    )
    args = ap.parse_args()

    if args.scenario == "faults":
        out = args.json_out or os.path.join(os.path.dirname(__file__), "..", "results", "bench_faults.json")
        run_faults_scenario(out, smoke=args.smoke, campaign_seed=args.campaign_seed)
        return
    if args.scenario == "elastic":
        out = args.json_out or os.path.join(os.path.dirname(__file__), "..", "results", "bench_elastic.json")
        run_elastic_scenario(out)
        return
    if args.scenario == "serve":
        out = args.json_out or os.path.join(os.path.dirname(__file__), "..", "results", "bench_serve.json")
        run_serve_scenario(out, smoke=args.smoke)
        return
    if args.scenario == "serve-faults":
        out = args.json_out or os.path.join(
            os.path.dirname(__file__), "..", "results", "bench_serve_faults.json"
        )
        run_serve_faults_scenario(out, smoke=args.smoke, campaign_seed=args.campaign_seed)
        return
    if args.scenario == "decode-perf":
        out = args.json_out or os.path.join(
            os.path.dirname(__file__), "..", "results", "bench_decode_perf.json"
        )
        run_decode_perf_scenario(out, smoke=args.smoke)
        return
    if args.scenario == "latency":
        out = args.json_out or os.path.join(os.path.dirname(__file__), "..", "results", "bench_latency.json")
        run_latency_scenario(out, smoke=args.smoke)
        return

    from benchmarks import bench_kernels, paper_figs

    benches = []
    if not args.skip_paper:
        benches += paper_figs.ALL
    benches += bench_kernels.ALL

    all_rows: list[tuple] = []
    print("name,us_per_call,derived")
    for bench in benches:
        if args.only and args.only not in bench.__name__:
            continue
        rows = bench()
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        all_rows += rows
        sys.stdout.flush()

    for name, us, derived in _roofline_rows():
        if args.only and args.only not in name:
            continue
        print(f"{name},{us:.1f},{derived}")
        all_rows.append((name, us, derived))

    if args.metrics_out:
        from repro.obs import bench_rows_snapshot

        os.makedirs(os.path.dirname(args.metrics_out) or ".", exist_ok=True)
        with open(args.metrics_out, "w") as f:
            json.dump(bench_rows_snapshot(all_rows), f, sort_keys=True, indent=1)
            f.write("\n")


if __name__ == "__main__":
    main()
