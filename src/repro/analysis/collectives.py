"""SPMD collective-uniformity checker over closed jaxprs.

The invariant this proves is the one ``HeteroStepConfig.validate`` encodes
by hand for ONE step family (src/repro/dist/hetero_step.py): *every rank of
a shard_map manual region executes the identical collective sequence*, even
when per-rank trip counts diverge.  A collective inside a loop whose trip
count differs across ranks (the while-mode FSDP deadlock class) hangs real
hardware: small-allocation ranks leave the loop while big ranks still wait
on them.

Method — a rank-variance taint analysis:

* Inside a ``shard_map`` manual region, a value is *rank-varying* over mesh
  axis ``a`` when it may differ between the ranks of ``a``: inputs whose
  ``in_names`` mention ``a``, ``axis_index(a)``, and anything data-dependent
  on those.  Uniform-output collectives (``psum``/``pmin``/``pmax``/
  ``all_gather``) *erase* the taint for their axes; rank-redistributing ones
  (``ppermute``/``psum_scatter``/``all_to_all``) keep it.
* A ``while`` whose cond output is tainted over ``a`` has a rank-divergent
  trip count over ``a``; any collective over ``a`` in its body (or cond) is
  an error (rule ``divergent-collective``).  ``scan`` trip counts are static
  and never divergent.
* A ``cond`` whose predicate is tainted over ``a`` takes different branches
  on different ranks; the branches must then have identical collective
  footprints over ``a`` (rule ``divergent-branch``).

Outside those two error classes the checker *extracts* the footprint — the
ordered (op, axes, times) sequence per rank — which is uniform by
construction in straight-line code, and reports it for the record.
"""

from __future__ import annotations

import dataclasses
from typing import Any

from jax._src.core import Literal

from repro.analysis.findings import Finding
from repro.analysis.jaxpr_walk import eqn_src, inner_jaxpr, subjaxprs

__all__ = ["check_collective_uniformity", "COLLECTIVE_PRIMS"]

# collective primitive name -> does its output become uniform over its axes?
COLLECTIVE_PRIMS = {
    "psum": True,
    "pmin": True,
    "pmax": True,
    "all_gather": True,
    "psum_scatter": False,
    "reduce_scatter": False,  # lax.psum_scatter binds reduce_scatter_p
    "ppermute": False,
    "pshuffle": False,
    "all_to_all": False,
}

_INLINE_PRIMS = {
    "pjit",
    "closed_call",
    "core_call",
    "remat2",
    "remat",
    "checkpoint",
    "custom_jvp_call",
    "custom_vjp_call",
    "custom_vjp_call_jaxpr",
    "custom_lin",
}

_EMPTY: frozenset = frozenset()
_MAX_FIXPOINT_ITERS = 16


def _collective_axes(eqn) -> frozenset:
    """String axis names a collective eqn runs over (ints are array dims)."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(axes, str):
        axes = (axes,)
    return frozenset(a for a in axes if isinstance(a, str))


@dataclasses.dataclass(frozen=True)
class _DivFrame:
    axes: frozenset  # axes the enclosing trip count / branch choice varies over
    path: str  # eqn path of the divergent loop/branch
    src: str


@dataclasses.dataclass(frozen=True)
class _Ctx:
    manual_axes: frozenset = _EMPTY  # shard_map axes we are inside
    divergent: tuple = ()  # stack of _DivFrame
    times: Any = 1  # static execution count ("dynamic" inside uniform loops)
    path: str = ""

    def nest(self, **kw) -> "_Ctx":
        return dataclasses.replace(self, **kw)


class _Sink:
    """Findings + footprint accumulator (a throwaway during fixpoint passes)."""

    def __init__(self, target: str):
        self.target = target
        self.findings: list[Finding] = []
        self.footprint: list[dict] = []

    def collective(self, eqn, path: str, ctx: _Ctx) -> None:
        axes = _collective_axes(eqn)
        self.footprint.append(
            {"op": eqn.primitive.name, "axes": sorted(axes), "times": ctx.times, "path": path}
        )
        for frame in ctx.divergent:
            hit = axes & frame.axes
            if hit:
                self.findings.append(
                    Finding(
                        rule="divergent-collective",
                        severity="error",
                        target=self.target,
                        path=path,
                        message=(
                            f"{eqn.primitive.name} over mesh axis {sorted(hit)} executes inside "
                            f"a control-flow region at {frame.path} whose trip count/branch is "
                            f"rank-varying over the same axis — ranks would run different "
                            f"collective counts and deadlock (the while-mode FSDP class "
                            f"HeteroStepConfig.validate guards)"
                        ),
                        src=eqn_src(eqn),
                    )
                )


def _taint_of(env: dict, v) -> frozenset:
    if isinstance(v, Literal):
        return _EMPTY
    return env.get(v, _EMPTY)


def _walk(jaxpr, env: dict, ctx: _Ctx, sink: _Sink) -> list[frozenset]:
    """Propagate rank-variance taint through one jaxpr; returns outvar taints.

    ``env`` maps Var -> frozenset of mesh axes the value may vary over.
    Constvars absent from ``env`` are uniform (trace-time constants).
    """
    env = dict(env)
    for i, eqn in enumerate(jaxpr.eqns):
        prim = eqn.primitive.name
        path = f"{ctx.path}/{i}:{prim}" if ctx.path else f"{i}:{prim}"
        in_taints = [_taint_of(env, v) for v in eqn.invars]
        joined = frozenset().union(*in_taints) if in_taints else _EMPTY

        if prim == "shard_map":
            out_t = _walk_shard_map(eqn, in_taints, ctx.nest(path=path), sink)
        elif prim in _INLINE_PRIMS:
            sub = next(subjaxprs(eqn), None)
            if sub is None:
                out_t = [joined] * len(eqn.outvars)
            else:
                inner = sub[1]
                n = len(inner.invars)
                # custom_jvp_call carries num_consts tracers ahead of the args
                sub_env = dict(zip(inner.invars, (in_taints + [_EMPTY] * n)[:n]))
                out_t = _walk(inner, sub_env, ctx.nest(path=path), sink)
        elif prim == "scan":
            out_t = _walk_scan(eqn, in_taints, ctx.nest(path=path), sink)
        elif prim == "while":
            out_t = _walk_while(eqn, in_taints, ctx.nest(path=path), sink)
        elif prim == "cond":
            out_t = _walk_cond(eqn, in_taints, ctx.nest(path=path), sink)
        elif prim == "axis_index":
            ax = eqn.params.get("axis_name")
            axes = frozenset(ax if isinstance(ax, tuple) else (ax,))
            out_t = [joined | (axes & ctx.manual_axes) or (joined | axes)]
        elif prim in COLLECTIVE_PRIMS:
            sink.collective(eqn, path, ctx)
            axes = _collective_axes(eqn)
            if COLLECTIVE_PRIMS[prim]:
                out_t = [joined - axes] * len(eqn.outvars)
            else:
                out_t = [joined | axes] * len(eqn.outvars)
        else:
            sub = next(subjaxprs(eqn), None)
            if sub is not None and prim not in ("pallas_call",):
                # unknown higher-order primitive: conservative blanket walk so
                # a collective hidden inside still registers
                inner = sub[1]
                sub_env = {v: joined for v in inner.invars}
                _walk(inner, sub_env, ctx.nest(path=path), sink)
            out_t = [joined] * len(eqn.outvars)

        for v, t in zip(eqn.outvars, out_t):
            env[v] = t
    return [_taint_of(env, v) for v in jaxpr.outvars]


def _axes_from_spec(spec) -> frozenset:
    """Mesh axes a ``PartitionSpec`` splits an operand over."""
    return frozenset(a for entry in spec if entry is not None for a in ((entry,) if isinstance(entry, str) else entry))


def _walk_shard_map(eqn, in_taints, ctx: _Ctx, sink: _Sink) -> list[frozenset]:
    manual = frozenset(eqn.params["manual_axes"])
    inner = inner_jaxpr(eqn.params["jaxpr"])
    env = {
        v: t | (_axes_from_spec(spec) & manual)
        for v, t, spec in zip(inner.invars, in_taints, eqn.params["in_specs"])
    }
    sub_ctx = ctx.nest(manual_axes=ctx.manual_axes | manual, path=f"{ctx.path}/body")
    return _walk(inner, env, sub_ctx, sink)


def _fixpoint_carry(body, consts_t, carry_t, xs_t, ctx: _Ctx, sink_target: str) -> list[frozenset]:
    """Iterate taint through a loop body until the carry taints stabilize."""
    for _ in range(_MAX_FIXPOINT_ITERS):
        env = dict(zip(body.invars, consts_t + carry_t + xs_t))
        out = _walk(body, env, ctx, _Sink(sink_target))  # silent pass
        new_carry = [a | b for a, b in zip(carry_t, out[: len(carry_t)])]
        if new_carry == carry_t:
            return carry_t
        carry_t = new_carry
    return carry_t


def _walk_scan(eqn, in_taints, ctx: _Ctx, sink: _Sink) -> list[frozenset]:
    nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
    length = eqn.params.get("length", 1)
    body = inner_jaxpr(eqn.params["jaxpr"])
    consts_t, carry_t, xs_t = in_taints[:nc], in_taints[nc : nc + ncar], in_taints[nc + ncar :]
    carry_t = _fixpoint_carry(body, consts_t, carry_t, xs_t, ctx, sink.target)
    times = ctx.times if ctx.times == "dynamic" else ctx.times * int(length)
    env = dict(zip(body.invars, consts_t + carry_t + xs_t))
    out = _walk(body, env, ctx.nest(times=times, path=f"{ctx.path}/body"), sink)
    return out[:ncar] + out[ncar:]  # carries then stacked ys, taints unchanged


def _walk_while(eqn, in_taints, ctx: _Ctx, sink: _Sink) -> list[frozenset]:
    cn, bn = eqn.params["cond_nconsts"], eqn.params["body_nconsts"]
    cond = inner_jaxpr(eqn.params["cond_jaxpr"])
    body = inner_jaxpr(eqn.params["body_jaxpr"])
    cond_consts_t = in_taints[:cn]
    body_consts_t = in_taints[cn : cn + bn]
    carry_t = list(in_taints[cn + bn :])
    carry_t = _fixpoint_carry(body, body_consts_t, carry_t, [], ctx, sink.target)

    cond_env = dict(zip(cond.invars, cond_consts_t + carry_t))
    pred_t = _walk(cond, cond_env, ctx, _Sink(sink.target))[0]
    div_axes = pred_t & ctx.manual_axes

    sub_ctx = ctx.nest(times="dynamic")
    if div_axes:
        frame = _DivFrame(axes=div_axes, path=ctx.path, src=eqn_src(eqn))
        sub_ctx = sub_ctx.nest(divergent=ctx.divergent + (frame,))
    # real passes (findings + footprint) over cond and body
    _walk(cond, cond_env, sub_ctx.nest(path=f"{ctx.path}/cond"), sink)
    body_env = dict(zip(body.invars, body_consts_t + carry_t))
    out = _walk(body, body_env, sub_ctx.nest(path=f"{ctx.path}/body"), sink)
    return [a | b for a, b in zip(carry_t, out)]


def _footprint_sig(entries: list[dict], axes: frozenset) -> tuple:
    return tuple(
        (e["op"], tuple(e["axes"]), e["times"])
        for e in entries
        if axes & set(e["axes"])
    )


def _walk_cond(eqn, in_taints, ctx: _Ctx, sink: _Sink) -> list[frozenset]:
    pred_t = in_taints[0]
    op_taints = in_taints[1:]
    div_axes = pred_t & ctx.manual_axes
    branch_sinks: list[_Sink] = []
    out_taints: list[list[frozenset]] = []
    # A rank-varying cond is judged by FOOTPRINT EQUALITY, not by blanket
    # divergence: when every branch runs the identical collective sequence
    # over the divergent axes, each rank executes that sequence exactly once
    # regardless of which branch it takes — uniform, no deadlock.  Enclosing
    # while-divergence frames still propagate through ctx.
    sub_ctx = ctx
    for i, br in enumerate(eqn.params["branches"]):
        bj = inner_jaxpr(br)
        bs = _Sink(sink.target)
        env = dict(zip(bj.invars, op_taints))
        out_taints.append(_walk(bj, env, sub_ctx.nest(path=f"{ctx.path}/branch{i}"), bs))
        branch_sinks.append(bs)
    for bs in branch_sinks:
        sink.findings.extend(bs.findings)
        sink.footprint.extend(bs.footprint)
    if div_axes:
        sigs = [_footprint_sig(bs.footprint, div_axes) for bs in branch_sinks]
        if len(set(sigs)) > 1:
            sink.findings.append(
                Finding(
                    rule="divergent-branch",
                    severity="error",
                    target=sink.target,
                    path=ctx.path,
                    message=(
                        f"cond predicate is rank-varying over {sorted(div_axes)} but its "
                        f"branches have different collective footprints over that axis "
                        f"({[len(s) for s in sigs]} collectives per branch) — ranks taking "
                        f"different branches would execute different collective sequences"
                    ),
                    src=eqn_src(eqn),
                )
            )
    n_out = len(eqn.outvars)
    merged = []
    for k in range(n_out):
        t = pred_t if div_axes else _EMPTY
        for bt in out_taints:
            t = t | bt[k]
        merged.append(t)
    return merged


def check_collective_uniformity(closed_jaxpr, target: str) -> tuple[list[Finding], dict]:
    """Analyze one traced program; returns ``(findings, footprint_meta)``.

    ``footprint_meta`` records the straight-line collective sequence (op,
    axes, times; ``times="dynamic"`` inside uniform-trip loops) and the
    verdict: ``"uniform"`` when no divergence errors were found.
    """
    jaxpr = inner_jaxpr(closed_jaxpr)
    sink = _Sink(target)
    env = {v: _EMPTY for v in jaxpr.invars}
    _walk(jaxpr, env, _Ctx(), sink)
    errors = [f for f in sink.findings if f.severity == "error"]
    meta = {
        "verdict": "divergent" if errors else "uniform",
        "n_collective_eqns": len(sink.footprint),
        "collectives": [
            {k: e[k] for k in ("op", "axes", "times", "path")} for e in sink.footprint
        ],
    }
    return sink.findings, meta
