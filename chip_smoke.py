#!/usr/bin/env python3
"""Bring-up check of the main path on a TPU, at smollm-360m full width.

Run from the root of a checkout, on a machine with a TPU:

  python chip_smoke.py               # one chip: kernels, train, serve
  python chip_smoke.py --four-chips  # four chips: the heterogeneous step only

One chip:
  * kernels: the Pallas paged-decode kernel (bf16 and int8 pools, ragged
    lengths, one empty slot) and flash prefill at S=2048, each compiled with
    Mosaic and compared with its ``kernels/ref.py`` oracle;
  * train: ``repro.launch.train.main`` for a few steps, 2 ranks folded onto
    the one chip;
  * serve: ``repro.launch.serve.main`` with the paged engine, 8 slots and 16
    mixed-length requests.
Four chips: ``repro.launch.train.main`` with 4 ranks on a 4x1 mesh and a
4,2,1,1 allocation, in while mode, while mode with ``--fsdp gather`` and
masked mode.  The step is allocation-invariant, so every mode computes the
same gradient and the same update: each run's per-step gradient norms and
losses must agree with masked mode's.  A control run, while mode with
``--lr 0`` (a step that applies no update), must fail that comparison.

Everything runs in this one process.  It exits nonzero, without a result
line, when JAX finds no TPU.  Seconds printed here are set-up facts (compile
plus a few steps), not benchmark numbers.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

The persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says;
without it, to ``.jax_cache`` at the root of the checkout.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "smollm-360m"
# bf16 outputs against float32 oracles: |out - ref| <= ATOL + RTOL * |ref|
# elementwise.  One bf16 ulp is 2**-7 relative; the bound allows about one
# ulp of output rounding plus the f32-accumulated online softmax's error.
BF16_ATOL, BF16_RTOL = 1e-2, 1e-2
SEED = 0
# Four chips: each mode's per-step agreement with masked mode, relative.  The
# gradient norm checks the aggregated gradient: weighting every rank's mean
# equally, or dropping one rank's gradient, moved it by 25% and 10% in a
# smoke-size check on four CPU devices.  The loss checks that the update is
# applied; the first steps sit in the lr warm-up, where updates are small,
# hence 8 steps.  The ``--lr 0`` control proves on every run that the bound
# sees a missing update.  On a v5e at full width (bf16) the sound modes read
# at most 2.5e-3 (gradient norm; the trajectories drift apart in rounding
# from step 1's 1.8e-4) and 1.2e-4 (loss); the control 0.63 and 4.3e-2.
GRAD_RTOL = 1e-2
LOSS_RTOL = 1e-3
FOUR_CHIP_STEPS = 8


def log(msg: str) -> None:
    print(msg, flush=True)


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at ``JAX_COMPILATION_CACHE_DIR``
    when it is set (JAX reads it itself) and at ``<checkout>/.jax_cache``
    otherwise.  Returns the directory in use."""
    import jax

    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def tpu_devices() -> list:
    """The TPU devices JAX sees; exits nonzero, naming what it found, when the
    default backend is not a TPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX's default backend is {devs[0].platform!r} ({len(devs)} device(s))")
    return devs


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _bound_check(name: str, out, ref) -> None:
    import numpy as np

    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    if out.shape != ref.shape or not np.isfinite(out).all():
        raise RuntimeError(f"{name}: shape {out.shape} vs {ref.shape}, finite={bool(np.isfinite(out).all())}")
    err = np.abs(out - ref)
    ratio = float((err / (BF16_ATOL + BF16_RTOL * np.abs(ref))).max())
    log(f"kernel {name}: max_abs_err={float(err.max())!r} bound_ratio={ratio!r}")
    if ratio > 1.0:
        raise RuntimeError(f"{name}: error exceeds the bf16 bound (atol {BF16_ATOL}, rtol {BF16_RTOL})")


def _compiled(fn, *args):
    """Compile ``fn`` for ``args``, check Mosaic put a kernel in it, run it."""
    import jax

    exe = jax.jit(fn).lower(*args).compile()
    if "tpu_custom_call" not in exe.as_text():
        raise RuntimeError("compiled program holds no tpu_custom_call: the kernel did not compile with Mosaic")
    return exe(*args)


def kernel_phase() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels import ops, ref

    cfg = get_config(ARCH)
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    f32 = jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(SEED), 6)

    # paged decode: 8 slots, ragged (a full 256-token slot, a 1-token slot,
    # an empty slot), pages handed out in shuffled order
    page_size, n_pages, pages_per_slot = 16, 128, 16
    lengths = np.array([256, 1, 17, 100, 0, 63, 200, 16], np.int32)
    B = lengths.size
    order = np.random.default_rng(SEED).permutation(n_pages)
    table = np.full((B, pages_per_slot), -1, np.int32)
    nxt = 0
    for b, n in enumerate(lengths):
        for j in range(-(-int(n) // page_size)):
            table[b, j] = order[nxt]
            nxt += 1
    pool_shape = (n_pages + 1, Hkv, page_size, Dh)
    q = jax.random.normal(keys[0], (B, H, Dh), jnp.bfloat16)
    k_pool = jax.random.normal(keys[1], pool_shape, jnp.bfloat16)
    v_pool = jax.random.normal(keys[2], pool_shape, jnp.bfloat16)
    pages, lens = jnp.asarray(table), jnp.asarray(lengths)

    def quant(x):  # per-(token, head) symmetric int8, scales (P, Hkv, page_size)
        scale = jnp.maximum(jnp.max(jnp.abs(x.astype(f32)), axis=-1) / 127.0, 1e-8)
        qv = jnp.clip(jnp.round(x.astype(f32) / scale[..., None]), -127, 127).astype(jnp.int8)
        return qv, scale.astype(jnp.bfloat16)

    k_i8, k_s = quant(k_pool)
    v_i8, v_s = quant(v_pool)
    with jax.default_matmul_precision("highest"):
        want_bf16 = ref.paged_attention_ref(q.astype(f32), k_pool.astype(f32), v_pool.astype(f32), pages, lens)
        want_i8 = ref.paged_attention_ref(q.astype(f32), k_i8, v_i8, pages, lens, k_s, v_s)
    got = _compiled(ops.paged_attention, q, k_pool, v_pool, pages, lens)
    _bound_check(f"paged_decode bf16 B={B} H={H} Hkv={Hkv} Dh={Dh} page={page_size}", got, want_bf16)
    if np.asarray(got)[lengths == 0].any():
        raise RuntimeError("paged_decode: the empty slot's output is not zero")
    got = _compiled(ops.paged_attention, q, k_i8, v_i8, pages, lens, k_s, v_s)
    _bound_check(f"paged_decode int8 B={B} H={H} Hkv={Hkv} Dh={Dh} page={page_size}", got, want_i8)

    # flash prefill at S=2048
    S = 2048
    qf = jax.random.normal(keys[3], (1, S, H, Dh), jnp.bfloat16)
    kf = jax.random.normal(keys[4], (1, S, Hkv, Dh), jnp.bfloat16)
    vf = jax.random.normal(keys[5], (1, S, Hkv, Dh), jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        want = ref.flash_attention_ref(qf.astype(f32), kf.astype(f32), vf.astype(f32))
    got = _compiled(ops.flash_attention, qf, kf, vf)
    _bound_check(f"flash_prefill bf16 S={S} H={H} Hkv={Hkv} Dh={Dh}", got, want)


# ---------------------------------------------------------------------------
# train / serve through the CLI entry points
# ---------------------------------------------------------------------------


def train(extra: list[str], *, n_workers: int, steps: int) -> dict:
    from repro.launch import train as train_cli

    argv = ["--arch", ARCH, "--steps", str(steps), "--n-workers", str(n_workers), "--seed", str(SEED)] + extra
    result = train_cli.main(argv)
    losses, grad_norms, step_s = result["losses"], result["grad_norms"], result["step_s"]
    if result["steps"] != steps or len(losses) != steps or len(grad_norms) != steps:
        raise RuntimeError(f"train ran {result['steps']} steps ({len(losses)} losses), asked for {steps}")
    if not all(math.isfinite(x) for x in losses + grad_norms):
        raise RuntimeError(f"train produced a non-finite loss or gradient norm: {losses} {grad_norms}")
    mesh = result["mesh"]
    steady = statistics.median(step_s[2:])
    log(
        f"train {' '.join(extra)}: steps={steps} losses={losses!r} grad_norms={grad_norms!r} "
        f"step_s={step_s!r} first_step_s={step_s[0]!r} steady_step_s={steady!r} "
        f"mesh={mesh['shape']} over {mesh['devices']} {mesh['platform']} device(s)"
    )
    # only the first step compiles: a second compile (the state reaching the
    # step in another layout than the step returns) shows as a slow step 2
    if step_s[1] > 2 * steady + 0.5:
        raise RuntimeError(f"step 2 took {step_s[1]!r} s against a steady {steady!r} s: the step compiled again")
    return result


def _check_mesh(result: dict, shape: list[int], devices: int) -> None:
    mesh = result["mesh"]
    if mesh["shape"] != shape or mesh["devices"] != devices or mesh["platform"] != "tpu":
        raise RuntimeError(f"train built mesh {mesh}, expected shape {shape} over {devices} tpu device(s)")


def train_phase() -> None:
    # C=4 microbatches of one 2048-token sequence per step, 2 ranks; the
    # masked step (params, grads, AdamW moments and one vmapped microbatch per
    # rank) holds about 12 GB of the chip's 16
    result = train(["--micro-bs", "1", "--total-micro", "4"], n_workers=2, steps=4)
    _check_mesh(result, [1, 1], 1)


def serve_phase() -> None:
    from repro.launch import serve as serve_cli

    n_req = 16
    argv = [
        "--arch", ARCH, "--attn-impl", "paged", "--page-size", "16", "--slots", "8",
        "--requests", str(n_req), "--prompt-lens", "32,256", "--gen-lens", "16,64", "--seed", str(SEED),
    ]
    result = serve_cli.main(argv)
    pool = result["pool"]
    if result["completed"] != n_req or result["requests"] != n_req:
        raise RuntimeError(f"serve completed {result['completed']} of {n_req} requests")
    if pool["free_pages"] != pool["n_pages"] or pool["reserved_pages"] or pool["allocated_pages"]:
        raise RuntimeError(f"serve left pages held after every request retired: {pool}")
    log(
        f"serve paged: completed={result['completed']}/{n_req} gen_tokens={result['gen_tokens']} "
        f"ticks={result['ticks']} prefills={result['prefills']} wall_s={result['wall_s']!r} pool={pool}"
    )


def _max_rel(got: list[float], want: list[float]) -> float:
    return max(abs(a - b) / abs(b) for a, b in zip(got, want))


def four_chip_phase() -> None:
    common = ["--micro-bs", "1", "--total-micro", "8", "--policy", "static", "--static-ratio", "4,2,1,1"]
    runs = {
        "masked": ["--mode", "masked"],
        "while": ["--mode", "while"],
        "while+gather": ["--mode", "while", "--fsdp", "gather"],
        "control while --lr 0": ["--mode", "while", "--lr", "0"],
    }
    results = {}
    for name, extra in runs.items():
        results[name] = train(common + extra, n_workers=4, steps=FOUR_CHIP_STEPS)
        _check_mesh(results[name], [4, 1], 4)
        gc.collect()
    base = results["masked"]
    for name in ("while", "while+gather", "control while --lr 0"):
        grad = _max_rel(results[name]["grad_norms"], base["grad_norms"])
        loss = _max_rel(results[name]["losses"], base["losses"])
        agrees = grad <= GRAD_RTOL and loss <= LOSS_RTOL
        log(
            f"four-chip {name} vs masked: max_rel_grad_norm_diff={grad!r} (bound {GRAD_RTOL}) "
            f"max_rel_loss_diff={loss!r} (bound {LOSS_RTOL}) {'agrees' if agrees else 'disagrees'}"
        )
        if name.startswith("control"):
            if agrees:
                raise RuntimeError("the check cannot tell a step that applies no update from masked mode's")
        elif not agrees:
            raise RuntimeError(f"{name} disagrees with masked mode: the step is not allocation-invariant")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true", help="run only the four-chip heterogeneous step")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"chip_smoke: {ROOT} is not a checkout of this repo (no src/repro)")
    sys.path.insert(0, str(ROOT / "src"))

    devs = tpu_devices()
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        sys.exit(f"chip_smoke: needs {want} TPU device(s), JAX sees {len(devs)}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    log(f"device: platform={device['platform']} kind={device['kind']} count={device['count']}")
    log(f"compile cache: {use_compile_cache()}")

    if args.four_chips:
        four_chip_phase()
    else:
        kernel_phase()
        gc.collect()
        train_phase()
        gc.collect()
        serve_phase()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
