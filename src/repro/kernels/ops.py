"""Jit'd dispatch wrappers for the Pallas kernels.

The one place that decides how a kernel runs: ``interpret=None`` (the
default) compiles it with Mosaic when JAX's default backend is a TPU and runs
it in the Pallas interpreter on any other backend (see
:func:`pallas_interpret`).  Pass ``interpret`` explicitly only to force one
side, e.g. a test that compiles for a described TPU from a CPU host.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _pa
from repro.kernels import rwkv6_scan as _rw
from repro.kernels import weighted_accum as _wa

__all__ = [
    "flash_attention",
    "paged_attention",
    "pallas_interpret",
    "rwkv6_scan",
    "weighted_accum",
    "weighted_accum_tree",
]


def pallas_interpret() -> bool:
    """Mosaic on a TPU backend, the Pallas interpreter everywhere else."""
    return jax.default_backend() != "tpu"


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


def paged_attention(q, k_pool, v_pool, pages, lengths, k_scale=None, v_scale=None, *, window=None, softcap=0.0, interpret=None):
    """Ragged paged-decode attention (one query token per slot vs paged KV).

    See ``repro.kernels.paged_attention`` for the layout contract."""
    return _pa.paged_attention(
        q, k_pool, v_pool, pages, lengths, k_scale, v_scale,
        window=window, softcap=softcap, interpret=_resolve(interpret),
    )


def rwkv6_scan(r, k, v, w, u, s0=None, chunk: int = 32, interpret=None):
    return _rw.rwkv6_scan(r, k, v, w, u, s0, chunk=chunk, interpret=_resolve(interpret))


def weighted_accum(acc, g, scale, interpret=None):
    return _wa.weighted_accum(acc, g, jnp.asarray(scale, jnp.float32), interpret=_resolve(interpret))


def weighted_accum_tree(acc_tree, g_tree, scale, interpret=None):
    return _wa.weighted_accum_tree(acc_tree, g_tree, scale, interpret=_resolve(interpret))
