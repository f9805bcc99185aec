"""Operations computed from shapes, for utilisation shares.  ``m`` is a
configuration file's ``model`` block.

The training count is the program's ``benchmarks/roofline.py:model_flops``
for a dense model: 6 operations per matrix parameter per token, plus the
attention products (QK^T and PV, 4 * S_eff * H * Dh per token and layer, three
times for forward and backward, S_eff = S / 2 under the causal mask).
Recomputation and padding do not count.
"""

from __future__ import annotations


def layer_matrix_params(m: dict) -> int:
    d, H, Hkv, Dh, F = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_ff"]
    return d * H * Dh * 2 + d * Hkv * Dh * 2 + 3 * d * F


def matrix_params(m: dict) -> int:
    """Parameters that take part in a matrix product: every layer's projections
    and the output head (the tied embedding counts once, as the head)."""
    return m["n_layers"] * layer_matrix_params(m) + m["vocab_size"] * m["d_model"]


def train_flops_per_token(m: dict, seq: int) -> float:
    attn = 3.0 * 4.0 * (seq / 2) * m["n_heads"] * m["head_dim"] * m["n_layers"]
    return 6.0 * matrix_params(m) + attn
