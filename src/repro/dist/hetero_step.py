"""The paper's heterogeneous train step: per-rank variable microbatch counts.

One SPMD step consumes rank-major padded buffers

    inputs/targets: (R, W_max, micro_bs, seq)   alloc: (R,) int32

where rank *r* trains on its first ``alloc[r]`` microbatches and the rest is
padding.  Two numerically identical executions of the same math:

* ``mode="while"`` — a ``shard_map`` manual region over the allocation axis;
  each rank runs a ``lax.while_loop`` with ITS OWN trip count (the fast path:
  a rank allocated 2 microbatches does 2 forward/backwards, not W_max), then
  the partial (grad_sum, loss_sum, token_sum) are reduced across ranks with
  ``psum`` or our :func:`~repro.dist.collectives.ring_allreduce`.
* ``mode="masked"`` — plain GSPMD arithmetic masking: loop over the first
  ``max(alloc)`` slots (the same trip count on every rank; slots past every
  rank's allocation are skipped, not multiplied by zero), vmap over ranks,
  weight each slot by ``1[j < alloc[r]]``.  Runs anywhere (including 1
  device) and stays legal when parameters are sharded over the allocation
  axis with per-microbatch FSDP gathers, where while-mode is forbidden —
  see :meth:`HeteroStepConfig.validate`.

While-mode additionally supports ``fsdp="gather"``: params and optimizer
state LIVE sharded over ``fsdp_axes`` (ZeRO-style, specs from
``dist/sharding.py``), and each step all-gathers the params exactly ONCE
before the per-rank loops, accumulates locally with divergent trip counts,
then reduce-scatters the gradient sum back to shards for the (sharded,
elementwise) optimizer update.  Every collective — the gather, the
reduce-scatter, the scalar psums — executes a uniform number of times per
rank, so while+FSDP becomes legal; only per-microbatch gathers
(``fsdp=True``) stay forbidden under while-mode.

All modes normalize the summed gradient by the GLOBAL token count, so the
update depends only on the union of microbatches, not on which rank computed
which (the paper's eq. 1 allocation-invariance: reallocating work between
ranks never changes the training trajectory).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import Literal
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.dist import compat
from repro.dist.collectives import all_gather_params, reduce_scatter_tree, ring_allreduce_tree
from repro.dist.sharding import state_specs
from repro.kernels import ops as kops
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.optim import (
    AdamWConfig,
    SGDConfig,
    adamw_init,
    adamw_update,
    clip_by_global_norm,
    constant,
    global_norm,
    sgd_init,
    sgd_update,
)

__all__ = [
    "HeteroStepConfig",
    "init_train_state",
    "build_train_step",
    "layer_remat",
    "micro_passes",
    "train_attention",
]

# The step keeps no layer residuals for recompute only while its reckoned bytes
# stay under this share of the device's memory: the reckoning leaves out XLA's
# temporaries (fusion buffers, the optimizer's intermediates) and the
# allocator's fragmentation, which the last quarter holds.
NO_REMAT_SHARE = 0.75


@dataclasses.dataclass(frozen=True)
class HeteroStepConfig:
    """Static configuration of the allocation-aware step."""

    w_max: int  # per-rank buffer depth (max microbatches any rank may get)
    micro_bs: int  # sequences per microbatch
    seq_len: int
    mode: str = "masked"  # "while" | "masked"
    alloc_axis: str = "data"  # mesh axis the allocation ranks live on
    # False: replicated params.  True: params sharded over fsdp_axes with
    # per-microbatch GSPMD gathers (masked mode only).  "gather": params AND
    # optimizer state sharded; ONE explicit all-gather per step outside the
    # per-rank loops, gradients reduce-scattered back (while mode only).
    fsdp: bool | str = False
    fsdp_axes: tuple[str, ...] = ("data",)
    optimizer: str = "adamw"  # "adamw" | "sgd"
    grad_dtype: str = "float32"  # accumulation dtype
    collective: str = "psum"  # "psum" | "ring" (while-mode gradient reduce)
    lr: float = 1e-3  # default when no lr_fn is passed
    clip_norm: float = 0.0  # 0 = no clipping

    def __post_init__(self) -> None:
        if self.mode not in ("while", "masked"):
            raise ValueError(f"mode must be 'while' or 'masked', got {self.mode!r}")
        if self.optimizer not in ("adamw", "sgd"):
            raise ValueError(f"optimizer must be 'adamw' or 'sgd', got {self.optimizer!r}")
        if self.collective not in ("psum", "ring"):
            raise ValueError(f"collective must be 'psum' or 'ring', got {self.collective!r}")
        if self.w_max < 1 or self.micro_bs < 1 or self.seq_len < 1:
            raise ValueError("w_max, micro_bs and seq_len must all be >= 1")
        if self.fsdp not in (False, True, "gather"):
            raise ValueError(f"fsdp must be False, True or 'gather', got {self.fsdp!r}")
        if self.fsdp == "gather" and self.mode != "while":
            raise ValueError(
                "fsdp='gather' is the while-mode state-sharding path (one gather per "
                "step outside the loops); masked mode shards params with fsdp=True "
                "and lets GSPMD place the per-microbatch gathers."
            )

    def validate(self, mesh) -> "HeteroStepConfig":
        """Check legality against a mesh.  The load-bearing invariant: in
        while-mode, ranks execute DIFFERENT trip counts, so any collective
        inside the loop body is executed a different number of times per
        rank.  Per-microbatch FSDP (``fsdp=True``) over the allocation axis
        puts parameter all-gathers inside every microbatch's forward — ranks
        with small allocations would stop participating while big ranks
        still wait on them: a deadlock on real hardware.  ``fsdp="gather"``
        hoists the gather OUT of the loops (one per step, uniform across
        ranks) and is therefore legal; so is masked mode (``max(alloc)``
        trips on every rank, masked arithmetic)."""
        axis_names = tuple(mesh.axis_names)
        if self.alloc_axis not in axis_names:
            raise ValueError(f"alloc_axis {self.alloc_axis!r} not in mesh axes {axis_names}")
        if self.mode == "while" and self.fsdp is True and self.alloc_axis in self.fsdp_axes:
            raise ValueError(
                "while-mode with per-microbatch FSDP over the allocation axis "
                f"{self.alloc_axis!r} would deadlock: per-rank trip counts diverge but "
                "FSDP all-gathers inside the loop body are collective over that axis. "
                "Use fsdp='gather' (one gather per step, outside the loops), "
                "mode='masked', or move FSDP off the allocation axis."
            )
        return self


def _micro_loss_sum(params, inputs, targets, cfg: ModelConfig, attn_impl: str = "blocked"):
    """Summed (not averaged) loss of ONE microbatch.

    Returns ``(loss_sum, token_count)``; dividing accumulated ``loss_sum``
    by accumulated ``token_count`` AFTER the cross-rank reduction is what
    makes the update allocation-invariant (per-microbatch averaging would
    weight ranks by their allocation).  MoE auxiliary losses are folded in
    per token so they renormalize identically.
    """
    loss, aux = transformer.loss_fn(params, {"inputs": inputs, "targets": targets}, cfg, attn_impl=attn_impl)
    tokens = aux["tokens"]
    return loss * tokens, tokens


def init_train_state(
    cfg: ModelConfig,
    scfg: HeteroStepConfig,
    key: jax.Array,
    opt_cfg: AdamWConfig | SGDConfig | None = None,
) -> dict:
    """``{"params", "opt", "step"}`` — the pytree every launcher checkpoints."""
    params = transformer.init_params(cfg, key)
    if scfg.optimizer == "adamw":
        opt = adamw_init(params, opt_cfg or AdamWConfig())
    else:
        opt = sgd_init(params)
    return {"params": params, "opt": opt, "step": jnp.zeros((), jnp.int32)}


# ---------------------------------------------------------------------------
# gradient accumulation bodies
# ---------------------------------------------------------------------------


def _zero_carry(params, grad_dtype):
    gz = jax.tree.map(lambda p: jnp.zeros(p.shape, grad_dtype), params)
    return gz, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)


def _masked_grads(params, inputs, targets, alloc, grad_fn, gdt):
    """Loop the first ``max(alloc)`` slots; vmap ranks; mask each slot.

    ``grad_fn(params, x, y) -> ((loss_sum, tokens), grads)`` is one
    microbatch's; ``gdt`` is the accumulation dtype.

    The trip count is one replicated scalar, so every rank runs it in
    lockstep (legal under per-microbatch FSDP gathers); a rank's slots past
    its own allocation but below the maximum are masked to zero.  Slots past
    every rank's allocation would only add zeros, so the loop stops there."""
    W = inputs.shape[1]
    vgrad = jax.vmap(grad_fn, in_axes=(None, 0, 0))

    def slot(j, carry):
        gsum, lsum, tsum = carry
        x = jax.lax.dynamic_index_in_dim(inputs, j, axis=1, keepdims=False)  # (R, mb, S)
        y = jax.lax.dynamic_index_in_dim(targets, j, axis=1, keepdims=False)
        m = (j < alloc).astype(jnp.float32)  # (R,)
        (ls, tk), g = vgrad(params, x, y)
        gsum = jax.tree.map(lambda a, b: a + jnp.tensordot(m, b.astype(jnp.float32), axes=1).astype(a.dtype), gsum, g)
        return gsum, lsum + (m * ls).sum(), tsum + (m * tk).sum()

    n = jnp.minimum(jnp.max(alloc), W)
    return jax.lax.fori_loop(0, n, slot, _zero_carry(params, gdt))


def _while_accum(params, inputs, targets, alloc, grad_fn, gdt):
    """Per-local-rank while loops with dynamic trip counts (NO collectives).

    Runs inside shard_map over ``scfg.alloc_axis``; ``inputs`` is the local
    (R_local, W, mb, S) block.  Each rank does exactly ``alloc[r]`` grads
    and returns its LOCAL (grad_sum, loss_sum, token_sum).
    """
    R_local, W = inputs.shape[:2]
    alloc = jnp.minimum(alloc, W)
    carry = _zero_carry(params, gdt)
    for r in range(R_local):  # static local-rank unroll (R_local is tiny)
        x_r, y_r, w_r = inputs[r], targets[r], alloc[r]

        def cond(c):
            return c[0] < w_r  # noqa: B023 — rebuilt per unrolled iteration

        def body(c):
            j, gsum, lsum, tsum = c
            (ls, tk), g = grad_fn(params, x_r[j], y_r[j])  # noqa: B023
            gsum = jax.tree.map(lambda a, b: a + b.astype(a.dtype), gsum, g)
            return j + 1, gsum, lsum + ls, tsum + tk

        init = (jnp.zeros((), jnp.int32),) + carry
        carry = jax.lax.while_loop(cond, body, init)[1:]
    return carry


def micro_passes(scfg: HeteroStepConfig, alloc) -> int:
    """Microbatch forward/backward passes one step computes for ``alloc``:
    masked mode runs ``max(alloc)`` slots (clamped to w_max) on every rank,
    while-mode loops each rank's own (clamped) allocation.  Against
    ``sum(alloc)``, the passes trained, it counts the padding the mode pays."""
    a = np.asarray(alloc)
    if scfg.mode == "masked":
        return a.size * min(int(a.max(initial=0)), scfg.w_max)
    return int(np.minimum(a, scfg.w_max).sum())


def _while_grads(params, inputs, targets, alloc, grad_fn, gdt, scfg):
    """While-mode with replicated params: local loops, then allreduce."""
    gsum, lsum, tsum = _while_accum(params, inputs, targets, alloc, grad_fn, gdt)
    # cross-rank reduction: the ONLY collective in the step — the paper's
    # plug-in point.  Scalars always ride psum; the gradient tree may take
    # the explicit ring.
    ax = scfg.alloc_axis
    if scfg.collective == "ring":
        gsum = ring_allreduce_tree(gsum, ax)
    else:
        gsum = jax.lax.psum(gsum, ax)
    lsum = jax.lax.psum(lsum, ax)
    tsum = jax.lax.psum(tsum, ax)
    return gsum, lsum, tsum


def _gathered_while_grads(shards, inputs, targets, alloc, grad_fn, gdt, scfg, pspecs):
    """While-mode over SHARDED params (``fsdp="gather"``).

    ``shards`` is the local param-shard tree laid out per ``pspecs``.  The
    whole tree is all-gathered ONCE (uniform collective count per rank —
    legal with divergent trip counts), grads accumulate locally, and the
    gradient sum is reduce-scattered straight back to the shard layout, so
    only one gathered params copy is ever live and the persistent state
    stays at 1/N per device.
    """
    ring = scfg.collective == "ring"
    params = all_gather_params(shards, pspecs, use_ring=ring)
    gsum, lsum, tsum = _while_accum(params, inputs, targets, alloc, grad_fn, gdt)
    ax = scfg.alloc_axis
    gsum = reduce_scatter_tree(gsum, pspecs, reduce_axes=(ax,), use_ring=ring)
    lsum = jax.lax.psum(lsum, ax)
    tsum = jax.lax.psum(tsum, ax)
    return gsum, lsum, tsum


def train_attention(platform: str, n_devices: int, mode: str) -> str:
    """The training attention a step built for ``n_devices`` devices of
    ``platform`` lowers: the splash kernel on a TPU where each call stays on
    one device, the blocked scan otherwise.  GSPMD cannot partition a Pallas
    call, so masked mode's vmap over ranks on more than one device would
    gather the batch around it; while mode's fully manual ``shard_map``
    region runs it per device on any mesh."""
    if platform == "tpu" and (n_devices == 1 or mode == "while"):
        return "splash"
    return "blocked"


def _bytes(tree) -> int:
    return sum(x.size * jnp.dtype(x.dtype).itemsize for x in jax.tree.leaves(tree))


def _bytes_limit(mesh) -> int | None:
    """The memory of the mesh's first device, or None where the device has no
    statistics (the CPU, a described device, a runtime that keeps none)."""
    try:
        stats = mesh.devices.flat[0].memory_stats()
    except jax.errors.JaxRuntimeError:
        return None
    return (stats or {}).get("bytes_limit")


# an abstract raw key: a concrete one, made while the step is traced, would
# stage a key op into the step's program
_KEY = jax.ShapeDtypeStruct((2,), jnp.uint32)


def _residual_bytes(cfg: ModelConfig, micro_bs: int, seq_len: int, attn_impl: str) -> int:
    """Bytes one microbatch's backward reads from its forward with ``cfg``'s
    remat setting: the residuals ``jax.vjp`` keeps, less the parameters it
    passes through (live anyway).  A trace of the loss only: the scan over the
    layer stack traces its body once, whatever the depth."""
    params = jax.eval_shape(lambda k: transformer.init_params(cfg, k), _KEY)
    tokens = jax.ShapeDtypeStruct((micro_bs, seq_len), jnp.int32)

    def pullback(p, x, y):
        return jax.vjp(lambda p: _micro_loss_sum(p, x, y, cfg, attn_impl)[0], p)[1]

    closed = jax.make_jaxpr(pullback)(params, tokens, tokens)
    seen = set(closed.jaxpr.invars)
    total = 0
    for v, aval in zip(closed.jaxpr.outvars, closed.out_avals):
        if not isinstance(v, Literal):
            if v in seen:
                continue
            seen.add(v)
        total += aval.size * aval.dtype.itemsize
    return total


def layer_remat(cfg: ModelConfig, scfg: HeteroStepConfig, mesh, ranks: int) -> tuple[bool, int]:
    """Whether the step recomputes each layer's forward in its backward
    (``cfg.remat``), and the residual bytes one device would hold without it.

    ``ranks`` is the batch's rank rows.  Masked mode vmaps the rows a device
    holds, so their microbatches keep residuals at once; while mode runs one
    microbatch at a time.  Without remat a device holds the state (parameters
    and optimizer moments), the accumulation carry, each row's microbatch
    gradient and its residuals, all reckoned whole (FSDP shards only lower
    them).  The step drops remat where that total is under
    :data:`NO_REMAT_SHARE` of the device's memory, and keeps ``cfg``'s
    setting where it is not or the memory is unknown."""
    no_remat = dataclasses.replace(cfg, remat=False)
    platform = mesh.devices.flat[0].platform
    attn_impl = train_attention(platform, mesh.devices.size, scfg.mode)
    rows = -(-ranks // int(dict(mesh.shape)[scfg.alloc_axis])) if scfg.mode == "masked" else 1
    residuals = rows * _residual_bytes(no_remat, scfg.micro_bs, scfg.seq_len, attn_impl)
    limit = _bytes_limit(mesh)
    if not cfg.remat or limit is None:
        return cfg.remat, residuals
    state = jax.eval_shape(lambda k: init_train_state(cfg, scfg, k), _KEY)
    params = _bytes(state["params"])
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    total = _bytes(state) + n_params * jnp.dtype(scfg.grad_dtype).itemsize + rows * params + residuals
    return total >= NO_REMAT_SHARE * limit, residuals


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------


def build_train_step(
    cfg: ModelConfig,
    scfg: HeteroStepConfig,
    mesh,
    lr_fn=None,
    opt_cfg: AdamWConfig | SGDConfig | None = None,
    jit: bool = True,
):
    """Build ``step(state, batch) -> (state, metrics)``.

    ``batch``: ``{"inputs": (R, W, mb, S), "targets": ..., "alloc": (R,)}``.
    ``metrics``: ``{"loss", "tokens", "grad_norm", "lr"}`` scalars; ``loss``
    is the global token-weighted mean cross-entropy BEFORE the update.
    ``jit=False`` returns the raw callable for callers that jit with
    explicit in/out shardings (dryrun, serving planners).

    Attention is chosen here from the mesh (:func:`train_attention`), and
    the one microbatch gradient function every accumulation body runs is
    built with it when the step is traced, on ``cfg`` without remat where
    :func:`layer_remat` finds the batch's residuals fit the device.
    """
    scfg.validate(mesh)
    platform = mesh.devices.flat[0].platform
    attn_impl = train_attention(platform, mesh.devices.size, scfg.mode)
    gdt = jnp.dtype(scfg.grad_dtype)
    lr_fn = lr_fn or constant(scfg.lr)
    if scfg.optimizer == "adamw":
        ocfg = opt_cfg or AdamWConfig()
        opt_update = lambda g, o, p, lr: adamw_update(g, o, p, lr, ocfg)  # noqa: E731
    else:
        ocfg = opt_cfg or SGDConfig()
        opt_update = lambda g, o, p, lr: sgd_update(g, o, p, lr, ocfg)  # noqa: E731

    n_rank_shards = int(dict(mesh.shape)[scfg.alloc_axis])

    use_gather = scfg.mode == "while" and scfg.fsdp == "gather"
    if use_gather:
        # Specs the persistent state lives under (and the shard_map in/out
        # layout).  Built from abstract shapes so no params are materialized.
        state_shape = jax.eval_shape(lambda k: init_train_state(cfg, scfg, k, opt_cfg=ocfg), jax.random.PRNGKey(0))
        sspecs = state_specs(state_shape, mesh, fsdp=True, fsdp_axes=scfg.fsdp_axes)
        pspecs = sspecs["params"]
    else:
        sspecs = pspecs = None

    def global_grads(params, inputs, targets, alloc):
        remat, _ = layer_remat(cfg, scfg, mesh, inputs.shape[0])
        step_cfg = cfg if remat else dataclasses.replace(cfg, remat=False)
        grad_fn = jax.value_and_grad(lambda p, x, y: _micro_loss_sum(p, x, y, step_cfg, attn_impl), has_aux=True)
        if scfg.mode == "masked":
            return _masked_grads(params, inputs, targets, alloc, grad_fn, gdt)
        if inputs.shape[0] % n_rank_shards:
            raise ValueError(
                f"while-mode batch has R={inputs.shape[0]} rank rows, not divisible by "
                f"mesh axis {scfg.alloc_axis!r} of size {n_rank_shards}"
            )
        # Fully-manual region (every mesh axis): partial-auto shard_map trips
        # the XLA SPMD partitioner CHECK (spmd_partitioner.cc:512) on the
        # transformer's gather/scan patterns — same limitation DESIGN.md §5
        # records for the multi-pod cells.  The psum/ring runs over the
        # allocation axis only.
        ax = scfg.alloc_axis
        batch_specs = (P(ax, None, None, None), P(ax, None, None, None), P(ax))
        if use_gather:
            # Params enter SHARDED per pspecs; one gather inside, gradients
            # leave as shards (out_specs = pspecs).
            body = compat.shard_map(
                lambda p, x, y, a: _gathered_while_grads(p, x, y, a, grad_fn, gdt, scfg, pspecs),
                mesh,
                in_specs=(pspecs,) + batch_specs,
                out_specs=(pspecs, P(), P()),
                check_rep=False,
            )
        else:
            # Params enter replicated (P()); non-allocation shards
            # redundantly compute identical grads.
            body = compat.shard_map(
                lambda p, x, y, a: _while_grads(p, x, y, a, grad_fn, gdt, scfg),
                mesh,
                in_specs=(P(),) + batch_specs,
                out_specs=(P(), P(), P()),
                check_rep=False,
            )
        return body(params, inputs, targets, alloc)

    def step(state, batch):
        with kops.lowering_for(platform):
            return _step(state, batch)

    def _step(state, batch):
        # host-side guard for eager (jit=False) callers; a no-op on tracers.
        # The jit=True wrapper below re-checks per call, because this body is
        # traced once and then bypassed by the compiled cache.
        _host_check_alloc(batch.get("alloc"), scfg.w_max)
        if use_gather:
            # Pin the persistent state to the ZeRO shard layout regardless of
            # how the caller placed it; everything downstream of the
            # reduce-scatter (normalize, clip, optimizer) is elementwise on
            # shards (clipping's global norm adds one scalar allreduce).
            state = jax.tree.map(
                lambda x, s: jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s)),
                state,
                sspecs,
            )
        inputs = batch["inputs"]
        targets = batch["targets"]
        alloc = batch["alloc"].astype(jnp.int32)
        gsum, lsum, tsum = global_grads(state["params"], inputs, targets, alloc)
        denom = jnp.maximum(tsum, 1.0)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32) / denom, gsum)
        if scfg.clip_norm > 0.0:
            grads, gnorm = clip_by_global_norm(grads, scfg.clip_norm)
        else:
            gnorm = global_norm(grads)
        lr = lr_fn(state["step"])
        params, opt = opt_update(grads, state["opt"], state["params"], lr)
        new_state = {"params": params, "opt": opt, "step": state["step"] + 1}
        metrics = {
            "loss": lsum / denom,
            "tokens": tsum,
            "grad_norm": gnorm,
            "lr": jnp.asarray(lr, jnp.float32),
        }
        return new_state, metrics

    if not jit:
        return step
    jitted = jax.jit(step, donate_argnums=(0,))

    def checked_step(state, batch):
        _host_check_alloc(batch.get("alloc"), scfg.w_max)
        return jitted(state, batch)

    return checked_step


def _host_check_alloc(alloc, w_max: int) -> None:
    """Reject ``alloc > w_max`` BEFORE tracing: inside the step the loop
    clamps ``alloc`` to the buffer depth, which would silently drop the
    overflowing microbatches instead of training on them."""
    if alloc is None:
        return
    try:
        a = np.asarray(alloc)
    except Exception:  # traced value (under jit): shapes only, skip
        return
    if a.dtype == object:  # abstract stand-in (ShapeDtypeStruct lowering)
        return
    if a.size and int(a.max()) > w_max:
        raise ValueError(
            f"allocation {int(a.max())} exceeds w_max={w_max}: the step buffer holds "
            "only w_max microbatch slots per rank, the excess would be silently "
            "clamped. Lower the allocation or rebuild with a larger w_max."
        )
