"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode on
the CPU backend, through the ``repro.kernels.ops`` wrappers)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_walk import find_eqns
from repro.kernels import flash_attention as fa
from repro.kernels.ops import (
    flash_attention,
    paged_attention,
    pallas_interpret,
    rwkv6_scan,
)
from repro.kernels.ref import (
    flash_attention_ref,
    paged_attention_ref,
    rwkv6_scan_ref,
)

KEY = jax.random.PRNGKey(0)


def test_ops_interpret_on_cpu_backend():
    """The backend decides: on CPU every dispatch wrapper interprets."""
    assert jax.default_backend() == "cpu" and pallas_interpret()
    q = jax.ShapeDtypeStruct((2, 4, 16), jnp.float32)
    pool = jax.ShapeDtypeStruct((5, 2, 8, 16), jnp.float32)
    pages = jax.ShapeDtypeStruct((2, 2), jnp.int32)
    lens = jax.ShapeDtypeStruct((2,), jnp.int32)
    closed = jax.make_jaxpr(paged_attention)(q, pool, pool, pages, lens)
    calls = [eqn for _, eqn in find_eqns(closed, "pallas_call")]
    assert calls and all(eqn.params["interpret"] for eqn in calls)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # B, Sq, Sk, H, Hkv, Dh, causal, window, softcap, q_offset, bq, bk
    (2, 128, 128, 4, 2, 64, True, None, 0.0, 0, 64, 64),
    (1, 256, 256, 8, 8, 128, True, None, 0.0, 0, 128, 128),
    (2, 128, 128, 4, 1, 64, True, 32, 0.0, 0, 32, 32),  # MQA + sliding window
    (1, 64, 64, 4, 2, 64, False, None, 50.0, 0, 32, 32),  # softcap, non-causal
    (1, 8, 128, 4, 2, 64, True, None, 0.0, 120, 8, 64),  # decode-style offset
    (2, 64, 64, 2, 2, 256, True, None, 0.0, 0, 64, 64),  # gemma head_dim 256
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_matches_ref_fp32(case):
    B, Sq, Sk, H, Hkv, Dh, causal, window, softcap, qoff, bq, bk = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, Dh), jnp.float32)
    out = fa.flash_attention(q, k, v, causal=causal, window=window, softcap=softcap, q_offset=qoff,
                             block_q=bq, block_kv=bk, interpret=pallas_interpret())
    ref = flash_attention_ref(q, k, v, causal=causal, window=window, softcap=softcap, q_offset=qoff)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 128, 2, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 128, 2, 64), jnp.bfloat16)
    out = fa.flash_attention(q, k, v, block_q=64, block_kv=64, interpret=pallas_interpret())
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=3e-2, atol=3e-2
    )


# ---------------------------------------------------------------------------
# ragged paged-decode attention
# ---------------------------------------------------------------------------


def _paged_fixture(lengths, n_pages=12, page_size=4, p_max=6, H=4, Hkv=2, Dh=64, shuffle=0):
    """Pools + a page table covering ``lengths`` live tokens per slot.  Page
    ids are handed out in a seeded shuffled order so tests exercise genuinely
    scattered (non-contiguous, non-monotonic) tables."""
    B = len(lengths)
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, Dh), jnp.float32)
    k_pool = jax.random.normal(ks[1], (n_pages + 1, Hkv, page_size, Dh), jnp.float32)
    v_pool = jax.random.normal(ks[2], (n_pages + 1, Hkv, page_size, Dh), jnp.float32)
    order = np.random.default_rng(shuffle).permutation(n_pages)
    table = np.full((B, p_max), -1, np.int32)
    nxt = 0
    for b, ln in enumerate(lengths):
        for j in range(-(-ln // page_size)):
            table[b, j] = order[nxt]
            nxt += 1
    assert nxt <= n_pages, "fixture pool too small"
    return q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(np.array(lengths, np.int32))


PAGED_CASES = [
    # lengths, H, Hkv, window, softcap
    ([10, 3, 0], 4, 2, None, 0.0),  # GQA, ragged, one empty slot
    ([8, 8], 4, 1, None, 0.0),  # MQA, page-aligned lengths
    ([23, 1], 4, 4, None, 0.0),  # MHA, unaligned + single-token slot
    ([20, 9], 4, 2, 6, 0.0),  # sliding window: old pages fully masked
    ([13, 2], 4, 2, None, 30.0),  # logit softcap
    ([17, 5, 11], 8, 2, 5, 0.0),  # window + deeper GQA grouping
]


@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_attention_matches_ref(case):
    lengths, H, Hkv, window, softcap = case
    q, k_pool, v_pool, table, lens = _paged_fixture(lengths, H=H, Hkv=Hkv, shuffle=len(lengths))
    out = paged_attention(q, k_pool, v_pool, table, lens, window=window, softcap=softcap)
    ref = paged_attention_ref(q, k_pool, v_pool, table, lens, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_paged_attention_empty_slot_outputs_zero():
    q, k_pool, v_pool, table, lens = _paged_fixture([7, 0])
    out = paged_attention(q, k_pool, v_pool, table, lens)
    assert bool((np.asarray(out)[1] == 0).all())
    assert np.isfinite(np.asarray(out)).all()


def test_paged_attention_int8_dequant_matches_ref():
    def quant(x):  # (P, Hkv, ps, Dh) -> int8 pool + (P, Hkv, ps) scales
        amax = jnp.max(jnp.abs(x), axis=-1)
        scale = jnp.maximum(amax / 127.0, 1e-8)
        qv = jnp.clip(jnp.round(x / scale[..., None]), -127, 127).astype(jnp.int8)
        return qv, scale.astype(jnp.bfloat16)

    q, k_pool, v_pool, table, lens = _paged_fixture([10, 5])
    k_i, k_s = quant(k_pool)
    v_i, v_s = quant(v_pool)
    out = paged_attention(q, k_i, v_i, table, lens, k_s, v_s)
    ref = paged_attention_ref(q, k_i, v_i, table, lens, k_s, v_s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_paged_attention_matches_flash_oracle_contiguous():
    """On a contiguous single-slot layout the paged kernel must agree with
    the dense flash oracle attending the same live prefix (decode = last
    query row)."""
    L = 11
    q, k_pool, v_pool, table, lens = _paged_fixture([L], n_pages=4, p_max=4)
    out = paged_attention(q, k_pool, v_pool, table, lens)
    # materialize the contiguous K/V from the (shuffled) pages
    tb = np.asarray(table[0])
    k = jnp.concatenate([k_pool[p].transpose(1, 0, 2) for p in tb if p >= 0], axis=0)[:L]
    v = jnp.concatenate([v_pool[p].transpose(1, 0, 2) for p in tb if p >= 0], axis=0)[:L]
    ref = flash_attention_ref(q[:, None], k[None], v[None], causal=True, q_offset=L - 1)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0, 0]), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# rwkv6 chunked scan
# ---------------------------------------------------------------------------

RWKV_CASES = [
    # B, T, H, D, chunk, w_min
    (2, 64, 2, 16, 32, 0.5),
    (1, 96, 4, 64, 32, 0.02),
    (2, 32, 2, 32, 16, np.exp(-4.0)),  # clamp boundary decay
    (1, 64, 1, 128, 32, 0.2),
]


@pytest.mark.parametrize("case", RWKV_CASES)
def test_rwkv6_scan_matches_ref(case):
    B, T, H, D, chunk, wmin = case
    ks = jax.random.split(KEY, 6)
    r = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, H, D))
    v = jax.random.normal(ks[2], (B, T, H, D))
    w = wmin + (0.999 - wmin) * jax.random.uniform(ks[3], (B, T, H, D))
    u = jax.random.normal(ks[4], (H, D)) * 0.1
    s0 = jax.random.normal(ks[5], (B, H, D, D)) * 0.1
    y, s = rwkv6_scan(r, k, v, w, u, s0, chunk=chunk)
    y_ref, s_ref = rwkv6_scan_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=3e-4, atol=3e-4)


def test_rwkv6_state_carry_composes():
    """Running two halves with carried state == running the whole sequence."""
    B, T, H, D = 1, 64, 2, 16
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, H, D))
    v = jax.random.normal(ks[2], (B, T, H, D))
    w = 0.3 + 0.69 * jax.random.uniform(ks[3], (B, T, H, D))
    u = jax.random.normal(ks[4], (H, D)) * 0.1
    y_full, s_full = rwkv6_scan(r, k, v, w, u, chunk=16)
    h = T // 2
    y1, s1 = rwkv6_scan(r[:, :h], k[:, :h], v[:, :h], w[:, :h], u, chunk=16)
    y2, s2 = rwkv6_scan(r[:, h:], k[:, h:], v[:, h:], w[:, h:], u, s1, chunk=16)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)), np.asarray(y_full), rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), rtol=3e-4, atol=3e-4)
