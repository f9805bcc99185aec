"""Jit'd dispatch wrappers for the Pallas kernels.

The one place that decides how a kernel runs: ``interpret=None`` (the
default) compiles it with Mosaic when the program is built for a TPU and runs
it in the Pallas interpreter on any other platform (see
:func:`pallas_interpret`).  The platform is JAX's default backend, or the
platform a step is built for while it traces (:func:`lowering_for`, which
the train step opens from its mesh's devices).  Pass ``interpret`` explicitly
only to force one side, e.g. a test that compiles for a described TPU from a
CPU host.
"""

from __future__ import annotations

import contextlib
import contextvars

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _pa
from repro.kernels import rwkv6_scan as _rw
from repro.kernels import splash_attention as _sa

__all__ = [
    "flash_attention",
    "lowering_for",
    "paged_attention",
    "pallas_interpret",
    "rwkv6_scan",
    "splash_attention",
]

_PLATFORM: contextvars.ContextVar[str | None] = contextvars.ContextVar("pallas_platform", default=None)


@contextlib.contextmanager
def lowering_for(platform: str):
    """Decide for ``platform`` (a mesh's devices) instead of the default
    backend while a program traces inside the block."""
    token = _PLATFORM.set(platform)
    try:
        yield
    finally:
        _PLATFORM.reset(token)


def pallas_interpret() -> bool:
    """Mosaic on a TPU, the Pallas interpreter everywhere else."""
    return (_PLATFORM.get() or jax.default_backend()) != "tpu"


def _resolve(interpret: bool | None) -> bool:
    return pallas_interpret() if interpret is None else interpret


def flash_attention(q, k, v, q_pos=None, k_pos=None, *, causal=True, window=None, softcap=0.0, q_offset=0, interpret=None):
    """Signature-compatible with models.attention's kernel hook.

    q_pos/k_pos are accepted for interface parity; the kernel derives
    positions from q_offset (contiguous layouts only).
    """
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap, q_offset=q_offset, interpret=_resolve(interpret),
    )


def splash_attention(q, k, v, *, window=None, softcap=0.0, interpret=None):
    """Causal GQA training attention with its own backward (the shipped
    splash kernel); contiguous positions, S a multiple of 128.

    See ``repro.kernels.splash_attention``."""
    return _sa.causal_attention(q, k, v, window=window, softcap=softcap, interpret=_resolve(interpret))


def paged_attention(q, k_pool, v_pool, pages, lengths, k_scale=None, v_scale=None, *, window=None, softcap=0.0, interpret=None):
    """Ragged paged-decode attention (one query token per slot vs paged KV).

    See ``repro.kernels.paged_attention`` for the layout contract."""
    return _pa.paged_attention(
        q, k_pool, v_pool, pages, lengths, k_scale, v_scale,
        window=window, softcap=softcap, interpret=_resolve(interpret),
    )


def rwkv6_scan(r, k, v, w, u, s0=None, chunk: int = 32, interpret=None):
    return _rw.rwkv6_scan(r, k, v, w, u, s0, chunk=chunk, interpret=_resolve(interpret))
