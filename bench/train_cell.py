"""A training cell: the driver's loop (``ElasticTrainer``) over the
allocation-aware step (``build_train_step``) at the mix's ranks, allocation and
microbatches.

Set-up builds one trainer, gives it the benchmark's weights and rows, and
drives it from the seed through its first three steps, through the driver's
own loop.  Those steps are what the reference checks; the window then goes on
with the same object.  The window counts trained tokens only (padding slots of
the step's buffers do not count), over all the steps it completed and all its
time.
"""

from __future__ import annotations

import dataclasses
import shutil
import types

import jax
import numpy as np

from bench import flops, harness, model, reference, trace, traffic

CHECK_STEPS = 3
TRACED_STEPS = 2  # a traced step holds some 400k device events: trace a few


def _driver(mix: dict, cfile: dict, seed: int):
    from repro.runtime.driver import DriverConfig, ElasticTrainer

    dcfg = DriverConfig(
        arch=cfile["base"],
        steps=10**9,
        seq=mix["seq"],
        n_workers=mix["n_ranks"],
        micro_bs=mix["micro_bs"],
        total_micro=mix["total_micro"],
        policy="static",
        static_ratio=",".join(str(a) for a in mix["allocation"]),
        mode=mix["mode"],
        lr=mix["optimizer"]["lr"],
        seed=model.small_seed(seed, 3),
        verbose=False,
    )
    return ElasticTrainer(dcfg)


def build(cell: dict, seed: int):
    """The trainer, its rows and the weights' key; checks that the driver runs
    the cell's model, ranks and allocation."""
    from repro.data import HeteroBatcher

    mix, cfile = cell["traffic_file"], cell["config_file"]
    m = cfile["model"]
    trainer = _driver(mix, cfile, seed)
    want = model.program_config(cfile)
    if trainer.model_cfg != want:
        raise SystemExit(f"bench: the driver runs {trainer.model_cfg}, the configuration file states {want}")
    if trainer.seq_len != mix["seq"] or list(np.asarray(trainer.alloc)) != mix["allocation"]:
        raise SystemExit(f"bench: the driver runs seq {trainer.seq_len}, allocation {trainer.alloc}")
    shape = list(trainer.mesh.devices.shape)
    if shape != mix["mesh"]:
        raise SystemExit(f"bench: the driver built a {shape} mesh, the mix asks for {mix['mesh']}")
    rows = traffic.TrainRows(m["vocab_size"], mix["seq"], len(trainer.dataset), seed)
    trainer.dataset = rows
    trainer.batcher = HeteroBatcher(rows, len(trainer.gpus), mix["micro_bs"], trainer.w_max, seed=trainer.cfg.seed)
    rows.clock = lambda: trainer.step_i
    key = model.seed_key(seed, 0)
    params = model.program_tree(model.make_weights(m, key), want)
    trainer.state = dict(trainer.state, params=params)  # the optimizer state is still all zeros
    trainer._reshard_state()
    return trainer, rows, key


def one_step(trainer) -> None:
    """One step through the driver's own loop (``ElasticTrainer._run_epoch``)."""
    trainer.cfg = dataclasses.replace(trainer.cfg, steps=trainer.step_i + 1)
    trainer._run_epoch()


def trained_tokens(mix: dict) -> int:
    return sum(mix["allocation"]) * mix["micro_bs"] * mix["seq"]


def run(cell: dict, args, devs, counter, t_start: float):
    mix, cfile = cell["traffic_file"], cell["config_file"]
    m = cfile["model"]
    trainer, rows, key = build(cell, args.seed)

    # set-up: the first steps (the first compiles), read for the reference
    g1_norms = None
    for i in range(CHECK_STEPS):
        one_step(trainer)
        if i == 0:
            mu = model.reference_tree(trainer.state["opt"]["mu"])
            g1_norms = model.flat_norms(model.leaf_norms(mu))
            g1_norms = {k: v / (1.0 - mix["optimizer"]["b1"]) for k, v in g1_norms.items()}
    losses = list(trainer.losses[:CHECK_STEPS])
    change_norms = model.flat_norms(
        model.diff_norms(model.reference_tree(trainer.state["params"]), model.make_weights(m, key))
    )
    step_rows = _rows_of_steps(rows, CHECK_STEPS)
    harness.settle()
    setup_s = harness.now() - t_start

    per_step = trained_tokens(mix)
    log_dir = None
    counter.armed = True
    t0 = harness.now()
    steps = 0
    with harness.span("bench.window"):
        if args.trace:
            log_dir = str(harness.ROOT / ".bench_trace" / "train")
            harness.start_trace(log_dir)
            with harness.span("bench.traced"):
                for _ in range(TRACED_STEPS):
                    with harness.span("ElasticTrainer.step"):
                        one_step(trainer)
                    steps += 1
            jax.profiler.stop_trace()
        while harness.now() - t0 < args.seconds:
            with harness.span("ElasticTrainer.step"):
                one_step(trainer)
            steps += 1
    window_s = harness.now() - t0
    counter.armed = False
    device = harness.device_info(devs)

    # the reference, once the program's state is freed
    rows.clock = None
    del trainer
    harness.release()
    checks, detail = compare(m, mix, key, rows, step_rows, losses, g1_norms, change_norms, cell["limits"])
    harness.log(f"train: {steps} steps in {window_s!r} s, losses {losses!r}; {detail}")
    harness.log(f"compilations inside the window: {counter.count} {counter.names[:5]}")

    result = {"correct": all(v <= lim for _, v, lim in checks) and counter.count == 0,
              "attempted": steps, "failed": 0, "device": device}
    if not args.trace:
        result["metrics"] = harness.end_to_end(cell, {"train_tokens_per_s": steps * per_step / window_s, "setup_s": setup_s})
        return result, checks
    red = trace.reduce(trace.find_xplane(log_dir), ("ElasticTrainer.step",))
    shutil.rmtree(log_dir)
    ctx = types.SimpleNamespace(
        kind="train", trace=red, model=m, mix=mix, chips=len(devs), peak=harness.peaks(devs[0].device_kind),
        steps=TRACED_STEPS, tokens=TRACED_STEPS * per_step,
        flops_per_token=flops.train_flops_per_token(m, mix["seq"]),
    )
    result["metrics"] = harness.read_per_layer(cell, ctx)
    result["device"].update(busy_s=red.busy_s, window_s=red.window_s)
    result["breakdown"] = red.breakdown()
    return result, checks


def _rows_of_steps(rows, n_steps: int) -> list:
    """The rows the dataset served for each of the first ``n_steps`` steps:
    calls tagged with the driver's step count before the step (a batch the loop
    assembled ahead and dropped is served again, the same rows)."""
    out = []
    for k in range(n_steps):
        seen, idx = set(), []
        for tag, indices in rows.served:
            if tag != k:
                continue
            for i in indices.tolist():
                if i not in seen:
                    seen.add(i)
                    idx.append(i)
        out.append(idx)
    return out


def lr_at(opt: dict, step: int) -> float:
    """The driver's schedule (``warmup_cosine`` over a run far longer than the
    window): linear warm-up, then the peak."""
    return opt["lr"] * min(step / opt["warmup"], 1.0)


def reference_run(m: dict, mix: dict, key, step_rows: list, precision: str = "f32", rows_fn=None):
    """The reference's losses, first-gradient norms and three-step change norms."""
    import jax.numpy as jnp

    opt = mix["optimizer"]
    w0 = jax.tree.map(lambda x: x.astype(jnp.float32), model.make_weights(m, key))
    w = w0
    mu = jax.tree.map(jnp.zeros_like, w)
    nu = jax.tree.map(jnp.zeros_like, w)
    losses, g1 = [], None
    for k, idx in enumerate(step_rows):
        rws = [rows_fn(i) for i in idx]
        loss, g = reference.step_gradient(w, [(r[:-1], r[1:]) for r in rws], m, precision)
        losses.append(float(loss))
        if k == 0:
            g1 = model.flat_norms(model.leaf_norms(g))
        w, mu, nu = reference.adamw_step(w, mu, nu, g, k + 1, lr_at(opt, k), opt)
    change = model.flat_norms(model.diff_norms(w, w0))
    return losses, g1, change


def norm_gaps(ref, losses, g1_norms, change_norms):
    """The three compared numbers against the reference's ``ref`` = (losses,
    first-gradient norms, change norms): the largest relative loss gap over the
    steps, and the worst leaf's gap of the first gradient's norm and of the
    three steps' change, each against the reference's norm of that leaf or of
    the median leaf, whichever is larger.  Leaves whose reference gradient is
    under a thousandth of the median leaf's are left out."""
    r_losses, r_g1, r_change = ref
    med_g = float(np.median(list(r_g1.values())))
    med_c = float(np.median(list(r_change.values())))
    keep = [k for k, v in r_g1.items() if v >= 1e-3 * med_g]

    def g_gap(k):
        return abs(g1_norms[k] - r_g1[k]) / max(r_g1[k], med_g)

    gaps = {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(losses, r_losses)),
        "grad_norm_gap": max(g_gap(k) for k in keep),
        "change_norm_gap": max(abs(change_norms[k] - r_change[k]) / max(r_change[k], med_c) for k in keep),
    }
    detail = (f"reference losses {r_losses!r}; leaves compared {len(keep)} of {len(r_g1)}; "
              f"worst gradient leaf {max(keep, key=g_gap)}")
    return gaps, detail


def compare(m, mix, key, rows, step_rows, losses, g1_norms, change_norms, limits):
    ref = reference_run(m, mix, key, step_rows, "f32", rows.row)
    gaps, detail = norm_gaps(ref, losses, g1_norms, change_norms)
    return [(name, gaps[name], limits[name]) for name in ("loss_gap", "grad_norm_gap", "change_norm_gap")], detail
