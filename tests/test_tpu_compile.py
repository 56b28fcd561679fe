"""Compiles for a described TPU v5e, without a chip: the three Pallas kernels
at the widths of the models they serve, TinyLlama-1.1B's served prefill
and decode at full width, and the served decode's cache writes. What the chip's compiler refuses (block tiling,
VMEM, a program that does not fit HBM) fails here at no chip time. Nothing
runs, so these say nothing about results or speed.

The topology is described only inside the ``topo`` fixture, never while a
module is imported: one process at a time may load the TPU library, and
every test worker imports every test file.
"""
import dataclasses
import functools
import os
import re

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


def test_deepseek_stage_decode_compiles_for_v5e(one_chip):
    """DeepSeek-V2-Lite's first pipeline stage (the dense layer and 6 expert
    layers of 64 experts) decoding a pool of 64 slots x 1536 positions, with
    its routing counts: it fits one chip, and the routed experts run as
    grouped matmuls (``ragged-dot``), not as a dense expansion over experts."""
    import dataclasses

    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b"), num_layers=7)
    params = jax.eval_shape(functools.partial(init_params, cfg=cfg), jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: _on(one_chip, s.shape, s.dtype), params)
    w = ModelWorker("deepseek-v2-lite.pp4", cfg, params, max_len=1536)
    compiled = w._decode.lower(
        w.params, _cache(w, one_chip, 64), _on(one_chip, (64, 1), jnp.int32),
        _on(one_chip, (64,), jnp.int32), None, _on(one_chip, (64,), jnp.bool_)).compile()
    _fits_one_chip(compiled)
    assert compiled.as_text().count("ragged-dot-none") >= 3 * 6


_INSTR = re.compile(r"%([\w.\-]+) = \w+\[([\d,]*)\]\{([\d,]*)[^}]*\} ([\w\-]+)\(%?([\w.\-]*)")


def _cache_writes(compiled, cache):
    """Each ``copy`` and ``dynamic-update-slice`` of the compiled program whose
    result has a cache leaf's stacked shape, or one layer's slice of it, as
    (op, result shape, relayout): ``relayout`` marks a copy whose operand
    has another layout, which reorders a cache for its reader rather than
    copying its bytes."""
    watched = set()
    for leaf in jax.tree.leaves(cache):
        watched |= {leaf.shape, leaf.shape[1:], (1,) + leaf.shape[1:]}
    found, layout = [], {}
    for name, dims, lay, op, operand in _INSTR.findall(compiled.as_text()):
        layout[name] = lay
        shape = tuple(int(d) for d in dims.split(",") if d)
        if op in ("copy", "dynamic-update-slice") and shape in watched:
            found.append((op, shape, op == "copy" and layout.get(operand, lay) != lay))
    return found


def _served_decode(one_chip, cfg, slots, max_len, counts=False):
    """The worker's jitted decode (cache donated, (B,) positions) compiled
    for one chip, and the cache's shapes."""
    params = jax.eval_shape(functools.partial(init_params, cfg=cfg), jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: _on(one_chip, s.shape, s.dtype), params)
    w = ModelWorker(cfg.name, cfg, params, max_len=max_len)
    cache = _cache(w, one_chip, slots)
    extra = (None, _on(one_chip, (slots,), jnp.bool_)) if counts else ()
    compiled = w._decode.lower(
        w.params, cache, _on(one_chip, (slots, 1), jnp.int32),
        _on(one_chip, (slots,), jnp.int32), *extra).compile()
    return compiled, cache


def test_dense_decode_writes_only_new_rows_on_v5e(one_chip):
    """Granite's widths, 4 layers in one scanned stage, 32 slots x 768: the
    donated pool is written by the row scatter alone, in place. No copy or
    dynamic-update-slice of the whole stack or of one layer's slice is left,
    save the relayout of the slice the attention dot reads (heads before
    positions), and the program's temporaries stay under one layer's K."""
    cfg = dataclasses.replace(get_config("granite-3-8b"), num_layers=4)
    compiled, cache = _served_decode(one_chip, cfg, 32, 768)
    k = cache[0]["l0"]["k"]
    writes = _cache_writes(compiled, cache)
    assert writes and all(op == "copy" and shape != k.shape and relayout
                          for op, shape, relayout in writes), writes
    one_layer_k = k.size // k.shape[0] * k.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer_k


def test_mla_expert_decode_writes_only_new_rows_on_v5e(one_chip):
    """DeepSeek-V2-Lite's widths, 2 latent-attention expert layers in one
    scanned stage, 64 slots x 1536, with routing counts: no
    dynamic-update-slice of the latent caches, no copy of the latent stack
    and no copy of any cache's bytes, whole or per layer. The copies left
    are relayouts: of the slices the attention reads, and of the 64-wide
    rope-key stack between the device's layout (positions minor) and the
    row scatter's."""
    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b"), num_layers=2,
                              first_dense_layers=0)
    compiled, cache = _served_decode(one_chip, cfg, 64, 1536, counts=True)
    c_kv = cache[0]["l0"]["c_kv"]
    writes = _cache_writes(compiled, cache)
    assert writes and all(op == "copy" and shape != c_kv.shape and relayout
                          for op, shape, relayout in writes), writes
