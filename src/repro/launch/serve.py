"""Serving CLI — a thin driver over the continuous-batching engine.

Synthesizes a mixed-length request workload (Poisson arrivals or a closed
backlog), drives it through ``repro.serve.ServeEngine`` with FIFO admission,
and prints a JSON summary (throughput, p50/p95 latency in decode ticks,
slot utilization).  ``--static`` switches to the static-batch baseline the
old driver implemented (admit a full batch, drain, repeat) for A/B runs;
``benchmarks/run.py --scenario serve`` does that comparison plus the
adaptive-router experiment end-to-end.

``--attn-impl`` selects the attention path end-to-end: ``naive``/``blocked``/
``flash`` pick the prefill implementation over the dense per-slot cache
(``flash`` runs the Pallas flash kernel: compiled on TPU, interpreted
elsewhere), and
``paged`` switches the whole KV layout to the shared page pool + Pallas
ragged paged-decode kernel — decode cost proportional to live tokens, and
``prompt + max_gen`` may exceed ``--max-seq`` (pool-bounded instead).

``--preempt`` (paged only) turns on graceful degradation: under page-pool
pressure the scheduler evicts the active slot with the most remaining
generation budget back to the pool (pages are the checkpoint) and restores
it token-identically once pressure clears.

``--trace`` replays a cluster trace's task arrivals (``repro.traces``)
instead of the synthetic Poisson stream — diurnal/bursty arrival shapes and
per-task prompt/gen lengths come from the trace, token payloads stay
synthesized from ``--seed``.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
      --slots 4 --requests 8 --prompt-lens 4,16 --gen-lens 8,24
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
      --attn-impl paged --page-size 8 --slots 8 --requests 16
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
      --trace pai_small --requests 12 --trace-time-scale 0.5
"""

from __future__ import annotations

import argparse
import json

import jax

from repro.configs import get_config, smoke_config
from repro.models import init_params
from repro.serve import SchedulerConfig, ServeEngine, WorkloadConfig, serve_loop, synthesize


def _span(text: str) -> tuple[int, int]:
    parts = [int(x) for x in text.split(",")]
    if len(parts) == 1:
        return parts[0], parts[0]
    if len(parts) != 2:
        raise argparse.ArgumentTypeError(f"expected LO,HI (or one int), got {text!r}")
    return parts[0], parts[1]


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4, help="engine batch slots")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-lens", type=_span, default=(4, 16), help="LO,HI inclusive")
    ap.add_argument("--gen-lens", type=_span, default=(8, 24), help="LO,HI inclusive")
    ap.add_argument("--rate", type=float, default=0.0, help="Poisson arrivals per tick; 0 = all at t=0")
    ap.add_argument("--max-seq", type=int, default=0, help="cache length (0 = prompt_max + gen_max)")
    ap.add_argument("--max-prefills-per-tick", type=int, default=2)
    ap.add_argument(
        "--attn-impl",
        default="naive",
        choices=["naive", "blocked", "flash", "paged"],
        help="prefill attention impl; 'paged' also switches the KV layout to "
        "the shared page pool + Pallas paged-decode kernel",
    )
    ap.add_argument("--page-size", type=int, default=8, help="tokens per KV page (paged impl)")
    ap.add_argument(
        "--pool-pages", type=int, default=0,
        help="shared pool size in pages (0 = match the dense footprint: slots*max_seq tokens)",
    )
    ap.add_argument(
        "--trace",
        default=None,
        help="bundled trace name (e.g. pai_small) or trace json path: replay its "
        "task arrivals/lengths instead of synthesizing (--requests truncates; "
        "--trace-time-scale maps trace time onto ticks)",
    )
    ap.add_argument("--trace-time-scale", type=float, default=1.0)
    ap.add_argument("--static", action="store_true", help="static-batch baseline (admit only when idle)")
    ap.add_argument(
        "--preempt",
        action="store_true",
        help="paged only: under pool pressure, evict the slot with the most "
        "remaining generation (pages are the checkpoint) and restore it "
        "token-identically once pressure clears",
    )
    ap.add_argument("--temperature", type=float, default=0.0, help="0 = greedy")
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--trace-out", default=None, help="write a Perfetto trace-event JSON (see README Observability)")
    ap.add_argument("--metrics-out", default=None, help="write a metrics snapshot JSON (repro.obs.metrics/v1)")
    args = ap.parse_args(argv)

    trace = None
    if args.trace:
        import os.path

        from repro.traces import bundled_trace, load_trace

        try:
            trace = load_trace(args.trace) if os.path.exists(args.trace) else bundled_trace(args.trace)
        except (ValueError, FileNotFoundError) as e:
            ap.error(str(e))
        tasks = trace.tasks[: args.requests] if args.requests else trace.tasks
        if not tasks:
            ap.error(f"trace {trace.name!r} has no tasks")
        # the admission gates below must see the TRACE's worst case
        args.prompt_lens = (min(t.prompt_len for t in tasks), max(t.prompt_len for t in tasks))
        args.gen_lens = (min(t.gen_len for t in tasks), max(t.gen_len for t in tasks))

    worst_case = args.prompt_lens[1] + args.gen_lens[1]
    paged = args.attn_impl == "paged"
    if args.preempt and not paged:
        ap.error("--preempt requires --attn-impl paged (pages are the preemption checkpoint)")
    max_seq = args.max_seq or worst_case
    if paged:
        # paged admission is pool-bounded: only the PROMPT must fit the
        # prefill buffer; generation may run past max_seq
        if max_seq < args.prompt_lens[1]:
            ap.error(f"--max-seq {max_seq} < prompt_max {args.prompt_lens[1]}")
    elif max_seq < worst_case:
        ap.error(
            f"--max-seq {max_seq} < prompt_max + gen_max = {worst_case}: "
            "the longest request could not be admitted"
        )
    cfg = smoke_config(args.arch, seq=max(max_seq, worst_case)) if args.smoke else get_config(args.arch)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    engine = ServeEngine(
        cfg,
        params,
        n_slots=args.slots,
        max_seq=max_seq,
        eos_id=args.eos_id,
        temperature=args.temperature,
        seed=args.seed,
        attn_impl=args.attn_impl,
        page_size=args.page_size,
        pool_pages=args.pool_pages or None,
    )
    if paged and not engine.admissible(args.prompt_lens[1], args.gen_lens[1]):
        ap.error(
            f"worst-case request ({args.prompt_lens[1]} + {args.gen_lens[1]} tokens) "
            f"does not fit the page pool — raise --pool-pages"
        )
    embed_dim = cfg.d_model if cfg.embeds_input else None
    if trace is not None:
        from repro.traces import to_requests

        requests = to_requests(
            trace,
            vocab_size=cfg.vocab_size,
            seed=args.seed,
            time_scale=args.trace_time_scale,
            limit=args.requests or None,
            embed_dim=embed_dim,
        )
    else:
        wl = WorkloadConfig(
            n_requests=args.requests,
            rate=args.rate,
            prompt_len=args.prompt_lens,
            gen_len=args.gen_lens,
            vocab_size=cfg.vocab_size,
            seed=args.seed,
        )
        requests = synthesize(wl, embed_dim=embed_dim)
    obs = None
    if args.trace_out or args.metrics_out:
        from repro.obs import ServeObs

        obs = ServeObs(trace_out=args.trace_out, metrics_out=args.metrics_out)
    summary = serve_loop(
        engine,
        requests,
        SchedulerConfig(
            max_waiting_prefill=args.max_prefills_per_tick,
            continuous=not args.static,
            preempt=args.preempt,
        ),
        obs=obs,
    )
    result = {
        "arch": cfg.name,
        "workload": f"trace:{trace.name}" if trace is not None else "synthetic",
        "mode": "static" if args.static else "continuous",
        "attn_impl": args.attn_impl,
        "slots": args.slots,
        "max_seq": max_seq,
        **summary,
        "sample_tokens": (requests[0].output or [])[:8],
    }
    if engine.pool is not None:
        result["pool"] = engine.pool.metrics()
        result["attended_key_tokens"] = engine.attended_key_tokens
    if obs is not None:
        obs.close()
        if obs.metrics is not None:
            snap = obs.metrics.snapshot()
            result["latency"] = {
                name.split(".", 1)[1]: {q: h[q] for q in ("p50", "p90", "p99")}
                for name, h in snap["histograms"].items()
                if name in ("serve.ttft", "serve.per_token", "serve.e2e_latency")
            }
    print(json.dumps(result, indent=1))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(result, f, indent=1)
    return result


if __name__ == "__main__":
    main()
