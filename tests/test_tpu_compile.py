"""Compiles for one TPU v5e chip that is described, not attached.

The chip's compiler refuses what the Pallas interpreter accepts (slices not
aligned to the tiling, block layouts that differ from XLA's, programs that
do not fit the device), so the main path's train step and the Pallas
kernels are compiled here at real widths.  Nothing runs: these tests say
nothing about results or times.

The topology is described only inside a fixture: only one process at a
time may load the TPU library, and every test worker imports this file.
"""
import os
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.surrogates import SURROGATES
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.rmsnorm import rms_norm_kernel
from repro.kernels.selective_scan import selective_scan_kernel
from repro.models import cnn
from repro.optim.adamw import AdamWConfig
from repro.train.step import init_train_state, make_train_step

#: chip_smoke.py's global batch: 4 nodes x SOLAR's padded capacity of 96.
SMOKE_GLOBAL_BATCH = 4 * 96
#: one TPU v5e chip's HBM.
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the compiler would otherwise write its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_ptychonn_train_step_fits_one_chip(one_chip):
    cfg = SURROGATES["ptychonn"]
    opt = AdamWConfig(lr=1e-3)
    step_cfg = types.SimpleNamespace(grad_accum=1, grad_accum_dtype="float32")
    step = make_train_step(step_cfg, opt,
                           lambda p, b: cnn.surrogate_loss(p, b, cfg))
    state = jax.eval_shape(
        lambda: init_train_state(cnn.init_surrogate(jax.random.PRNGKey(0), cfg),
                                 opt)
    )
    state = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, one_chip), state
    )
    b = SMOKE_GLOBAL_BATCH
    batch = {
        "x": _sds((b,) + cfg.input_shape, jnp.float32, one_chip),
        "y": _sds((b,) + cfg.output_shape, jnp.float32, one_chip),
        "weights": _sds((b,), jnp.float32, one_chip),
    }
    compiled = _compile(step, state, batch)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, mem


def test_flash_attention_compiles_for_tpu(one_chip):
    q = _sds((1, 32, 2048, 128), jnp.bfloat16, one_chip)
    compiled = _compile(
        lambda q, k, v: flash_attention_kernel(
            q, k, v, block_q=128, block_k=128, interpret=False
        ),
        q, q, q,
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_rms_norm_compiles_for_tpu(one_chip):
    x = _sds((4096, 4096), jnp.bfloat16, one_chip)
    scale = _sds((4096,), jnp.float32, one_chip)
    compiled = _compile(
        lambda x, s: rms_norm_kernel(x, s, interpret=False), x, scale
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_selective_scan_compiles_for_tpu(one_chip, dtype):
    bsz, s, di, n = 1, 2048, 8192, 16
    compiled = _compile(
        lambda *a: selective_scan_kernel(*a, interpret=False),
        _sds((bsz, s, di), dtype, one_chip),
        _sds((bsz, s, di), dtype, one_chip),
        _sds((di, n), jnp.float32, one_chip),
        _sds((bsz, s, n), dtype, one_chip),
        _sds((bsz, s, n), dtype, one_chip),
        _sds((di,), jnp.float32, one_chip),
    )
    assert "tpu_custom_call" in compiled.as_text()
