"""AOT compiles of the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler that ships with jaxlib compiles for a
``v5e:2x2`` topology that is described, not attached, and refuses what Mosaic
would refuse on the chip (block shapes off the (8, 128) tiling, VMEM
overruns).  Shapes are smollm-360m's attention widths (15 query heads, 5 kv
heads, head_dim 64) in bf16.  The topology is described inside a fixture so
that importing this file never loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ops import flash_attention, paged_attention

H, HKV, DH = 15, 5, 64  # smollm-360m
SLOTS, N_PAGES, PAGES_PER_SLOT = 8, 128, 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this jaxlib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("page_size", [8, 16])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_decode_compiles_for_v5e(one_chip, page_size, kv_dtype):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((N_PAGES + 1, HKV, page_size, DH), jnp.dtype(kv_dtype))
    args = [sds((SLOTS, H, DH), jnp.bfloat16), pool, pool, sds((SLOTS, PAGES_PER_SLOT), jnp.int32), sds((SLOTS,), jnp.int32)]
    if kv_dtype == "int8":
        scales = sds((N_PAGES + 1, HKV, page_size), jnp.bfloat16)
        args += [scales, scales]
    text = _compiled_text(lambda *a: paged_attention(*a, interpret=False), *args)
    assert "tpu_custom_call" in text


def test_flash_prefill_compiles_for_v5e(one_chip):
    S = 2048

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, interpret=False), sds((1, S, H, DH)), sds((1, S, HKV, DH)), sds((1, S, HKV, DH))
    )
    assert "tpu_custom_call" in text
