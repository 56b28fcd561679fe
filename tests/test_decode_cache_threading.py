"""Decode carries each stage's stacked cache through the layer loop and
scatters a step's new rows into it (``transformer.apply_stack``). The
reference here threads the cache the other way, as ``xs``/``ys`` of the
layer scan: each layer gets its own slice and hands back a whole new one.
Both run the same layer arithmetic, so over ragged steps (per-slot
positions, a slot left inactive, a speculative verify) they give the same
tokens and caches equal to the bit.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.models import init_params
from repro.models import model as model_lib
from repro.models import transformer as tfm
from repro.models.layers import apply_norm, embed_tokens, lm_logits
from repro.serving.workers import ModelWorker
from repro.sharding.context import ExecContext

SLOTS, MAX_LEN, PROMPT = 4, 24, 6


def _config(name):
    if name == "dense-gqa":
        return dataclasses.replace(reduced(get_config("tinyllama-1.1b")), num_layers=3)
    if name == "sliding-window":  # (local, global) pairs; the window cuts in
        return dataclasses.replace(reduced(get_config("gemma2-2b")), num_layers=4,
                                   sliding_window=5)
    if name == "mla-experts":  # every layer latent attention + experts
        return dataclasses.replace(reduced(get_config("deepseek-v2-lite-16b")),
                                   num_layers=3, first_dense_layers=0)
    if name == "hybrid-ssm":  # (mamba + dense MLP, attention + experts) x 2
        return dataclasses.replace(reduced(get_config("jamba-v0.1-52b")), num_layers=4,
                                   layer_pattern=("mamba", "attn"))
    raise KeyError(name)


def _decode_xs_ys(params, cfg, token, cache, pos):
    """Reference decode step: each stage's cache is the layer scan's ``xs``
    and the written cache its ``ys``; every layer sees a stack of one."""
    ctx = ExecContext()
    x = embed_tokens(params["embed"], token, cfg).astype(jnp.dtype(cfg.dtype))
    new = []
    for st, sp, sc in zip(tfm.compute_stages(cfg), params["stages"], cache):
        def body(xc, xs, _pattern=st.pattern):
            lp, cin = xs
            cout = {}
            for j, (kind, mlp) in enumerate(_pattern):
                one = jax.tree.map(lambda a: a[None], cin[f"l{j}"])
                xc, _, cj, _ = tfm.apply_layer(lp[f"l{j}"], xc, cfg, kind, mlp, ctx,
                                               "decode", one, pos, 0)
                cout[f"l{j}"] = jax.tree.map(lambda a: a[0], cj)
            return xc, cout

        x, c = jax.lax.scan(body, x, (sp, sc))
        new.append(c)
    x = apply_norm(params["final_norm"], x, cfg)
    return lm_logits(params["embed"], x, cfg), new


def _assert_same(ref, new, ref_cache, new_cache):
    ref, new = np.asarray(ref, np.float32), np.asarray(new, np.float32)
    np.testing.assert_array_equal(ref.argmax(-1), new.argmax(-1))
    np.testing.assert_allclose(new, ref, rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(ref_cache), jax.tree.leaves(new_cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", ["dense-gqa", "sliding-window", "mla-experts",
                                  "hybrid-ssm"])
def test_carried_cache_matches_xs_ys_threading(name):
    cfg = _config(name)
    assert any(st.repeats > 1 for st in tfm.compute_stages(cfg))  # a scanned stage
    params = init_params(jax.random.PRNGKey(0), cfg)
    worker = ModelWorker(name, cfg, params, max_len=MAX_LEN)  # the served, donated step
    ref_step = jax.jit(lambda p, c, t, q: _decode_xs_ys(p, cfg, t, c, q))

    rng = np.random.RandomState(0)
    prompts = rng.randint(1, cfg.vocab_size, (SLOTS, PROMPT)).astype(np.int32)
    logits, cache = worker.prefill_batch(prompts)
    ref_cache = cache
    new_cache = jax.tree.map(jnp.copy, cache)  # the served step donates its cache
    tokens = np.asarray(jnp.argmax(logits, -1), np.int32)[:, None]
    # ragged depths; slot 2 is inactive: its token and position never move
    pos = np.array([PROMPT, PROMPT - 2, 3, PROMPT - 1], np.int32)
    active = np.array([True, True, False, True])
    # SSM decode is single-token, so only attention stacks verify drafts
    verify = all(k in tfm.ATTN_KINDS for k in cfg.layer_kinds())
    for step in range(8):
        T = 3 if (verify and step == 4) else 1
        tok = np.concatenate([tokens] + [(tokens + i) % cfg.vocab_size
                                         for i in range(1, T)], axis=1)
        ref, ref_cache = ref_step(params, ref_cache, jnp.asarray(tok), jnp.asarray(pos))
        call = worker._verify if T > 1 else worker._decode
        new, new_cache = call(params, new_cache, jnp.asarray(tok), jnp.asarray(pos))
        _assert_same(ref if T > 1 else ref[:, -1], new, ref_cache, new_cache)
        nxt = np.asarray(jnp.argmax(new, -1), np.int32).reshape(SLOTS, T)[:, -1:]
        tokens = np.where(active[:, None], nxt, tokens)
        pos = np.where(active, pos + T, pos).astype(np.int32)


def test_bucketed_scalar_position_matches_xs_ys_threading():
    """The position-synchronous (scalar ``pos``) step writes through the same
    scatter and gives the reference's logits and cache."""
    cfg = _config("dense-gqa")
    params = init_params(jax.random.PRNGKey(1), cfg)
    cache = model_lib.init_cache(cfg, 2, MAX_LEN)
    tok = jnp.array([[5], [9]], jnp.int32)
    ref, ref_cache = jax.jit(lambda c: _decode_xs_ys(params, cfg, tok, c, jnp.int32(3)))(cache)
    new, new_cache = jax.jit(lambda c: model_lib.decode_step(params, cfg, tok, c,
                                                             jnp.int32(3)))(cache)
    _assert_same(ref, new, ref_cache, new_cache)


def test_pallas_attention_reads_the_carried_stack():
    """``attn_impl="pallas"`` attends over the layer of the carried stack as
    the XLA path does (the kernel's decode takes one position for the whole
    batch: the bucketed path)."""
    cfg = _config("dense-gqa")
    params = init_params(jax.random.PRNGKey(2), cfg)
    tok = jnp.array([[1], [2], [3], [4]], jnp.int32)
    out = {}
    for impl in ("xla", "pallas"):
        step = jax.jit(lambda c, q, _ctx=ExecContext(attn_impl=impl):
                       model_lib.decode_step(params, cfg, tok, c, q, _ctx))
        cache = model_lib.init_cache(cfg, 4, MAX_LEN)
        for pos in range(3, 6):
            logits, cache = step(cache, jnp.int32(pos))
        out[impl] = logits, cache
    np.testing.assert_allclose(np.asarray(out["pallas"][0]), np.asarray(out["xla"][0]),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(out["pallas"][1]), jax.tree.leaves(out["xla"][1])):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-5)
