"""Compiles for a described TPU v5e, without a chip: the three Pallas kernels
at the widths of the models they serve, and TinyLlama-1.1B's served prefill
and decode at full width. What the chip's compiler refuses (block tiling,
VMEM, a program that does not fit HBM) fails here at no chip time. Nothing
runs, so these say nothing about results or speed.

The topology is described only inside the ``topo`` fixture, never while a
module is imported: one process at a time may load the TPU library, and
every test worker imports every test file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.models import init_params
from repro.models import model as model_lib
from repro.serving.workers import ModelWorker

HBM_BYTES = 16e9  # one TPU v5e chip
POOL_SLOTS, POOL_LEN = 8, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, sh):
    """(function, argument shapes) for one kernel at a served model's widths."""
    if name == "decode-tinyllama":  # 32 q-heads over 4 kv-heads, 2048 keys
        return (functools.partial(decode_attention, q_offset=1500, kv_len=1501,
                                  interpret=False),
                (_on(sh, (8, 1, 32, 64)), _on(sh, (8, 2048, 4, 64)),
                 _on(sh, (8, 2048, 4, 64))))
    if name == "decode-gemma2":  # head_dim 256, local window, softcap
        return (functools.partial(decode_attention, q_offset=1500, kv_len=1501,
                                  window=4096, softcap=50.0, interpret=False),
                (_on(sh, (8, 1, 8, 256)), _on(sh, (8, 2048, 4, 256)),
                 _on(sh, (8, 2048, 4, 256))))
    if name == "flash-tinyllama":
        return (functools.partial(flash_attention, causal=True, interpret=False),
                (_on(sh, (1, 2048, 32, 64)), _on(sh, (1, 2048, 4, 64)),
                 _on(sh, (1, 2048, 4, 64))))
    if name == "flash-gemma2":
        return (functools.partial(flash_attention, causal=True, window=4096,
                                  softcap=50.0, interpret=False),
                (_on(sh, (1, 2048, 8, 256)), _on(sh, (1, 2048, 4, 256)),
                 _on(sh, (1, 2048, 4, 256))))
    if name == "ssd-mamba2-2.7b":  # 80 heads of 64, d_state 128, chunk 256
        f32 = jnp.float32
        return (functools.partial(ssd_scan, chunk=256, interpret=False),
                (_on(sh, (1, 2048, 80, 64)), _on(sh, (1, 2048, 80), f32),
                 _on(sh, (1, 2048, 80), f32), _on(sh, (1, 2048, 128)),
                 _on(sh, (1, 2048, 128))))
    raise KeyError(name)


@pytest.mark.parametrize("name", ["decode-tinyllama", "decode-gemma2",
                                  "flash-tinyllama", "flash-gemma2",
                                  "ssd-mamba2-2.7b"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, not a fallback


@pytest.fixture(scope="module")
def tinyllama_worker(one_chip):
    """The served worker at full width, holding shapes on the described chip
    in place of weights."""
    cfg = get_config("tinyllama-1.1b")
    params = jax.eval_shape(functools.partial(init_params, cfg=cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: _on(one_chip, s.shape, s.dtype), params)
    return ModelWorker("tinyllama-1.1b", cfg, params, max_len=POOL_LEN)


def _cache(worker, sharding, batch):
    sds = jax.eval_shape(functools.partial(
        model_lib.init_cache, worker.cfg, batch, worker.max_len))
    return jax.tree.map(lambda s: _on(sharding, s.shape, s.dtype), sds)


def _fits_one_chip(compiled):
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used <= HBM_BYTES, f"{used / 1e9:.2f} GB > {HBM_BYTES / 1e9:.0f} GB"


def test_tinyllama_decode_step_compiles_for_v5e(tinyllama_worker, one_chip):
    w = tinyllama_worker
    compiled = w._decode.lower(
        w.params, _cache(w, one_chip, POOL_SLOTS),
        _on(one_chip, (POOL_SLOTS, 1), jnp.int32),
        _on(one_chip, (POOL_SLOTS,), jnp.int32)).compile()
    _fits_one_chip(compiled)


def test_tinyllama_prefill_compiles_for_v5e(tinyllama_worker, one_chip):
    w = tinyllama_worker
    compiled = w._prefill.lower(
        w.params, _cache(w, one_chip, 1),
        _on(one_chip, (1, POOL_LEN), jnp.int32)).compile()
    _fits_one_chip(compiled)
