"""The plain reference against the program's forward pass, on the CPU at a
tiny size, with the program computing in float32: the two must agree to
float32 rounding.  Two head layouts: smollm-360m's (GQA, 3 query heads per KV
head) and yi-34b's share of one chip under 8-way tensor parallelism (7 query
heads over 1 KV head, a 128-wide head scaled down to 32)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model, reference
from bench.tests.tiny import TINY_MODEL

LAYOUTS = {
    "smollm-360m": dict(TINY_MODEL),
    "yi-34b-share": dict(TINY_MODEL, n_heads=7, n_kv_heads=1, head_dim=32, tie_embeddings=False, rope_theta=5e6),
}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_reference_matches_program_forward(layout):
    from repro.configs import get_config
    from repro.models import transformer

    m = dict(LAYOUTS[layout], compute_dtype="float32")
    cfg = dataclasses.replace(get_config("smollm-360m"), **m)
    weights = model.make_weights(m, model.seed_key(5, 0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (m["max_seq"],), 0, m["vocab_size"])
    with jax.default_matmul_precision("highest"):
        got, _ = transformer.forward(model.program_tree(weights, cfg), tokens[None], cfg)
    want = reference.logits(weights, tokens, m)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_fp8_control_departs_from_the_reference():
    m = dict(TINY_MODEL)
    weights = model.make_weights(m, model.seed_key(5, 0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (m["max_seq"],), 0, m["vocab_size"])
    f32 = reference.logits(weights, tokens, m)
    fp8 = reference.logits(weights, tokens, m, "fp8")
    assert float(jnp.max(jnp.abs(f32 - fp8))) > 1e-2
