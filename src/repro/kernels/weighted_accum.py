"""Fused weighted gradient accumulation — Pallas TPU kernel.

The inner operation of the paper's method: every microbatch iteration does
``acc += scale * grad`` over the whole gradient pytree.  Unfused, XLA emits
a multiply (read g, write tmp) and an add (read acc+tmp, write acc) — three
HBM round-trips of the gradient bytes; fused it is one read of each operand
and one write.  At w_i microbatches per step this runs w_i times per rank
per step, so it is squarely on the accumulation loop's memory roofline.

Scale arrives via scalar-prefetch (SMEM) so one compiled kernel serves every
(loss-scale x token-weight) combination.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _accum_kernel(scale_ref, acc_ref, g_ref, out_ref):
    s = scale_ref[0]
    out_ref[...] = (
        acc_ref[...].astype(jnp.float32) + s * g_ref[...].astype(jnp.float32)
    ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def weighted_accum(
    acc: jnp.ndarray,
    g: jnp.ndarray,
    scale: jnp.ndarray | float,
    block: int = 4096,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """acc + scale * g (elementwise, fp32 math), any matching shapes."""
    assert acc.shape == g.shape, (acc.shape, g.shape)
    orig_shape = acc.shape
    n = acc.size
    # pad flat length to a block multiple (TPU lane alignment)
    block = min(block, max(n, 1))
    pad = (-n) % block
    af = jnp.pad(acc.reshape(-1), (0, pad)).reshape(-1, block)
    gf = jnp.pad(g.reshape(-1), (0, pad)).reshape(-1, block)
    rows = af.shape[0]
    scale_arr = jnp.asarray(scale, jnp.float32).reshape(1)

    out = pl.pallas_call(
        _accum_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows,),
            in_specs=[
                pl.BlockSpec((1, block), lambda i, s: (i, 0)),
                pl.BlockSpec((1, block), lambda i, s: (i, 0)),
            ],
            out_specs=pl.BlockSpec((1, block), lambda i, s: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(af.shape, acc.dtype),
        interpret=interpret,
    )(scale_arr, af, gf)
    return out.reshape(-1)[:n].reshape(orig_shape)


def weighted_accum_tree(acc_tree, g_tree, scale, *, interpret: bool):
    """Apply over a full gradient pytree."""
    return jax.tree.map(lambda a, g: weighted_accum(a, g, scale, interpret=interpret), acc_tree, g_tree)
