"""Recursive jaxpr traversal shared by the static analyzers.

jax's higher-order primitives each stash their sub-programs under a
different param key (``pjit``/``scan``/``remat2`` -> ``jaxpr``, ``while`` ->
``cond_jaxpr``/``body_jaxpr``, ``cond`` -> ``branches``, ``custom_jvp_call``
-> ``call_jaxpr``, ``custom_vjp_call_jaxpr`` -> ``fun_jaxpr``, ``shard_map``
and ``pallas_call`` -> a *plain* ``Jaxpr``).  This module normalizes all of
that into one walk so the collective checker, the Pallas auditor and the
cost model never duplicate the dispatch.

Paths are structural and deterministic: ``"3:shard_map/body/7:while/body/2:psum"``
— the eqn index and primitive name at every level, so a finding pinpoints
the offending eqn even when source info is unavailable.
"""

from __future__ import annotations

from typing import Iterator

from jax._src import source_info_util
from jax._src.core import ClosedJaxpr, Jaxpr, Literal, Var

from repro.analysis.findings import src_of

__all__ = ["inner_jaxpr", "subjaxprs", "iter_eqns", "find_eqns", "eqn_src", "var_or_none"]


def inner_jaxpr(obj) -> Jaxpr | None:
    """Unwrap ClosedJaxpr/Jaxpr to the plain Jaxpr (else None)."""
    if isinstance(obj, ClosedJaxpr):
        return obj.jaxpr
    if isinstance(obj, Jaxpr):
        return obj
    return None


def subjaxprs(eqn) -> Iterator[tuple[str, Jaxpr]]:
    """Yield ``(tag, jaxpr)`` for every sub-program of an eqn.

    Tags name the role: ``body``/``cond`` for loops, ``branch0..N`` for
    ``cond``, ``body`` for everything single-bodied.
    """
    name = eqn.primitive.name
    if name == "while":
        yield "cond", eqn.params["cond_jaxpr"].jaxpr
        yield "body", eqn.params["body_jaxpr"].jaxpr
        return
    if name == "cond":
        for i, br in enumerate(eqn.params["branches"]):
            yield f"branch{i}", br.jaxpr
        return
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        j = inner_jaxpr(eqn.params.get(key))
        if j is not None:
            yield "body", j
            return
    # last resort: any jaxpr-valued param (unknown higher-order primitives)
    for key in sorted(eqn.params):
        val = eqn.params[key]
        for i, item in enumerate(val if isinstance(val, (tuple, list)) else (val,)):
            j = inner_jaxpr(item)
            if j is not None:
                yield f"{key}{i}", j


def iter_eqns(jaxpr: Jaxpr, path: str = "") -> Iterator[tuple[str, "object"]]:
    """Depth-first ``(path, eqn)`` over a jaxpr and every sub-program."""
    for i, eqn in enumerate(jaxpr.eqns):
        here = f"{path}/{i}:{eqn.primitive.name}" if path else f"{i}:{eqn.primitive.name}"
        yield here, eqn
        for tag, sub in subjaxprs(eqn):
            yield from iter_eqns(sub, f"{here}/{tag}")


def find_eqns(jaxpr: ClosedJaxpr | Jaxpr, prim_name: str) -> list[tuple[str, "object"]]:
    j = inner_jaxpr(jaxpr)
    return [(p, e) for p, e in iter_eqns(j) if e.primitive.name == prim_name]


def eqn_src(eqn) -> str:
    """``"file.py:123"`` of the user frame that created the eqn ('' if none)."""
    try:
        frame = source_info_util.user_frame(eqn.source_info.traceback)
    except Exception:
        return ""
    if frame is None:
        return ""
    line = getattr(frame, "start_line", None) or getattr(frame, "line_num", None)
    return src_of(frame.file_name, line)


def var_or_none(v) -> Var | None:
    return None if isinstance(v, Literal) else v
