"""Pallas TPU kernels for the perf-critical compute substrate.

The paper's contribution is scheduling (kernel-free); these cover the
compute hot spots the technique sits on.  Each kernel has a pure-jnp oracle
in ``ref.py`` and a jit'd dispatch wrapper in ``ops.py``:

* ``flash_attention`` — blocked causal GQA attention + sliding window (prefill)
* ``splash_attention`` — causal GQA training attention with its backward, on
  JAX's shipped splash kernel (oracle: naive attention in ``models.attention``)
* ``rwkv6_scan``      — chunked RWKV6 recurrence (MXU-shaped)
"""

from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
