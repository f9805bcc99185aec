"""AOT compiles of the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler that ships with jaxlib compiles for a
``v5e:2x2`` topology that is described, not attached, and refuses what Mosaic
would refuse on the chip (block shapes off the (8, 128) tiling, VMEM
overruns).  Shapes are smollm-360m's attention widths (15 query heads, 5 kv
heads, head_dim 64) in bf16; the train steps are smollm-360m's one-rank
masked step at full size and a tiny while-mode step on four chips, both with
training attention on the splash kernel.  The topology is described inside a
fixture so that importing this file never loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

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


def _train_step_text(cfg, scfg, devices):
    """Compile ``build_train_step`` on a (ranks, 1) mesh of described chips;
    the step picks its attention from the mesh's platform."""
    from repro.dist import build_train_step, init_train_state

    mesh = Mesh(np.array(devices).reshape(len(devices), 1), ("data", "model"))
    step = build_train_step(cfg, scfg, mesh, jit=False)
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    state = jax.eval_shape(lambda k: init_train_state(cfg, scfg, k), jax.random.PRNGKey(0))
    state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), state)
    shape = (len(devices), scfg.w_max, scfg.micro_bs, scfg.seq_len)
    batch = {
        "inputs": jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rows),
        "targets": jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rows),
        "alloc": jax.ShapeDtypeStruct((len(devices),), jnp.int32, sharding=rows),
    }
    return jax.jit(step, donate_argnums=(0,)).lower(state, batch).compile().as_text()


SPLASH_KERNELS = ("splash_mqa_fwd_residuals", "splash_mqa_dq_no_residuals", "splash_mqa_dkv_no_residuals")


def test_smollm_masked_train_step_compiles_with_splash_for_v5e(topo):
    """train-1rank's step: one rank, w_max 8, one 2048-token sequence per
    microbatch; every attention layer lowers to the kernel's forward, dq and
    dkv calls."""
    from repro.configs import get_config
    from repro.dist import HeteroStepConfig

    cfg = get_config("smollm-360m")
    scfg = HeteroStepConfig(w_max=8, micro_bs=1, seq_len=2048, mode="masked")
    text = _train_step_text(cfg, scfg, topo.devices[:1])
    assert "tpu_custom_call" in text
    assert all(name in text for name in SPLASH_KERNELS)


def test_while_train_step_runs_splash_per_chip_on_four_v5e(topo):
    """While mode's fully manual region holds the kernel on each of four
    chips (masked mode there keeps the blocked scan: GSPMD cannot partition
    a Pallas call)."""
    from repro.dist import HeteroStepConfig
    from repro.models import ModelConfig

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=256, n_heads=4, n_kv_heads=2,
                      d_ff=512, vocab_size=512)
    scfg = HeteroStepConfig(w_max=2, micro_bs=1, seq_len=256, mode="while")
    text = _train_step_text(cfg, scfg, topo.devices[:4])
    assert all(name in text for name in SPLASH_KERNELS)
