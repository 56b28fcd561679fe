"""Pad-token-safe SSM scans: bucketed (LEFT-padded) prompts must agree with
exact-length prefill on pure-SSM models — masked positions neither write
into nor decay the scan state (ROADMAP open item)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.models import init_params, model as model_lib
from repro.models.ssm import mamba1_forward, mamba2_forward
from repro.serving.engine import ModelWorker


@pytest.fixture(scope="module")
def mamba2():
    cfg = reduced(get_config("mamba2-2.7b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _left_pad(prompt: np.ndarray, to_len: int):
    """(padded prompt, (1, to_len) validity mask)."""
    pad = to_len - len(prompt)
    padded = np.concatenate([np.zeros(pad, np.int32), prompt])
    mask = np.zeros(to_len, bool)
    mask[pad:] = True
    return padded[None], mask[None]


def _ssm_leaves(cache):
    """The carried scan states of a cache tree (its ``ssm`` leaves)."""
    return [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
            if "ssm" in jax.tree_util.keystr(path)]


def test_mamba2_prefill_padded_matches_exact(mamba2):
    cfg, params = mamba2
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, cfg.vocab_size, 11, dtype=np.int32)
    logits_ref, cache_ref = model_lib.prefill(
        params, cfg, jnp.asarray(prompt[None]),
        model_lib.init_cache(cfg, 1, 32))
    padded, mask = _left_pad(prompt, 16)
    logits_pad, cache_pad = model_lib.prefill(
        params, cfg, jnp.asarray(padded), model_lib.init_cache(cfg, 1, 32),
        pad_mask=jnp.asarray(mask))
    # last-position logits and the carried (conv, ssm) states agree
    np.testing.assert_allclose(np.asarray(logits_pad[:, -1]),
                               np.asarray(logits_ref[:, -1]),
                               rtol=2e-5, atol=2e-5)
    for leaf_pad, leaf_ref in zip(jax.tree.leaves(cache_pad),
                                  jax.tree.leaves(cache_ref)):
        np.testing.assert_allclose(np.asarray(leaf_pad), np.asarray(leaf_ref),
                                   rtol=2e-5, atol=2e-5)


def test_mamba2_generate_padded_tokens_identical(mamba2):
    """Worker-level: a left-padded + masked bucket prompt generates the
    same greedy continuation as the exact-length prompt — the agreement the
    bucketed and continuous serving paths need on SSM models."""
    cfg, params = mamba2
    w = ModelWorker("m", cfg, params, max_len=48)
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, cfg.vocab_size, 13, dtype=np.int32)
    ref = w.generate(prompt[None], 6)
    padded, mask = _left_pad(prompt, 16)
    got = w.generate(padded, 6, pad_mask=mask)
    np.testing.assert_array_equal(got, ref)


def test_mamba2_unmasked_padding_pollutes_state(mamba2):
    """The bug the mask fixes: WITHOUT it, left padding shifts the scan
    state (pad embeddings decay and feed the SSM), asserting the mask is
    doing real work.

    Compared on the carried SSM state itself. Unmasked, its gap to the
    exact-length prefill is ~5.1e-3 on the CPU; masked, ~1e-9 (float32
    noise; test_mamba2_prefill_padded_matches_exact calls anything within
    2e-5 equal). The margin 5e-4 is 10x under the unmasked gap and 25x
    over that tolerance."""
    cfg, params = mamba2
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, cfg.vocab_size, 13, dtype=np.int32)
    _, ref_cache = model_lib.prefill(
        params, cfg, jnp.asarray(prompt[None]), model_lib.init_cache(cfg, 1, 48))
    padded, _ = _left_pad(prompt, 16)
    _, bad_cache = model_lib.prefill(
        params, cfg, jnp.asarray(padded), model_lib.init_cache(cfg, 1, 48))
    ssm_ref, ssm_bad = _ssm_leaves(ref_cache), _ssm_leaves(bad_cache)
    assert ssm_ref
    gap = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
              for a, b in zip(ssm_ref, ssm_bad))
    assert gap > 5e-4, gap


def test_mamba1_forward_masked_matches_truncated():
    """Function-level mamba1 (Jamba's mixer): the masked scan over a padded
    sequence yields the truncated scan's final state and tail outputs."""
    cfg = reduced(get_config("mamba2-2.7b"))  # supplies d_inner/d_state dims
    rng = jax.random.PRNGKey(1)
    from repro.models.ssm import init_mamba1

    p = init_mamba1(rng, cfg)
    B, S, pad = 2, 12, 5
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, cfg.d_model))
    x_pad = jnp.concatenate([jnp.zeros((B, pad, cfg.d_model)), x], axis=1)
    mask = jnp.concatenate([jnp.zeros((B, pad), bool),
                            jnp.ones((B, S), bool)], axis=1)
    y_ref, (conv_ref, ssm_ref) = mamba1_forward(p, x, cfg)
    y_pad, (conv_pad, ssm_pad) = mamba1_forward(p, x_pad, cfg, mask=mask)
    np.testing.assert_allclose(np.asarray(y_pad[:, pad:]), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ssm_pad), np.asarray(ssm_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(conv_pad), np.asarray(conv_ref),
                               rtol=2e-5, atol=2e-5)


def test_mamba2_forward_masked_matches_truncated_chunked():
    """Mask correctness must hold when padding crosses SSD chunk
    boundaries (cumulative decays reset per chunk)."""
    import dataclasses

    cfg = dataclasses.replace(reduced(get_config("mamba2-2.7b")), ssm_chunk=8)
    p = init_params(jax.random.PRNGKey(0), cfg)["stages"]
    mixer = jax.tree.map(lambda a: a[0], p[0]["l0"]["mixer"])
    B, S, pad = 1, 19, 10  # padded length 29 spans 4 chunks of 8
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, cfg.d_model))
    x_pad = jnp.concatenate([jnp.zeros((B, pad, cfg.d_model)), x], axis=1)
    mask = jnp.concatenate([jnp.zeros((B, pad), bool),
                            jnp.ones((B, S), bool)], axis=1)
    y_ref, (_, ssm_ref) = mamba2_forward(mixer, x, cfg)
    y_pad, (_, ssm_pad) = mamba2_forward(mixer, x_pad, cfg, mask=mask)
    np.testing.assert_allclose(np.asarray(y_pad[:, pad:]), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ssm_pad), np.asarray(ssm_ref),
                               rtol=2e-5, atol=2e-5)


def test_ssd_scan_kernel_mask_matches_truncated():
    """Pallas SSD kernel (interpret mode on CPU): the masked scan over a
    left-padded batch reproduces the unpadded scan's outputs and final
    state, across chunk boundaries."""
    from repro.kernels.ssd_scan import ssd_scan

    B, S, H, P, N, pad = 1, 17, 2, 4, 8, 7
    k = jax.random.PRNGKey(7)
    ks = jax.random.split(k, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dA = -jnp.abs(jax.random.normal(ks[1], (B, S, H))) * 0.1
    dt = jnp.abs(jax.random.normal(ks[2], (B, S, H))) * 0.5
    Bm = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, S, N), jnp.float32)

    def lpad(a):
        return jnp.concatenate([jnp.zeros((B, pad) + a.shape[2:], a.dtype), a],
                               axis=1)

    mask = jnp.concatenate([jnp.zeros((B, pad), bool),
                            jnp.ones((B, S), bool)], axis=1)
    y_ref, h_ref = ssd_scan(x, dA, dt, Bm, Cm, chunk=8, interpret=True)
    y_pad, h_pad = ssd_scan(lpad(x), lpad(dA), lpad(dt), lpad(Bm), lpad(Cm),
                            mask=mask, chunk=8, interpret=True)
    np.testing.assert_allclose(np.asarray(y_pad[:, pad:]), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_pad), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-5)


def test_engine_buckets_ssm_prompts_into_one_prefill(mamba2):
    """Engine-level pow2 prompt bucketing: mixed-length SSM admissions in
    the same length bucket prefill as ONE left-padded masked batch, with
    tokens identical to per-request exact-length generation."""
    from repro.serving.engine import ServingEngine
    from repro.serving.slots import Request

    cfg, params = mamba2
    rng = np.random.default_rng(9)
    reqs = [Request(i, rng.integers(1, cfg.vocab_size, plen, dtype=np.int32),
                    4) for i, plen in enumerate((11, 13))]

    def serve(buckets: bool):
        eng = ServingEngine(mode="continuous", max_slots=4,
                            ssm_prompt_buckets=buckets)
        eng.add_model("m", cfg, params, max_len=48)
        for r in reqs:
            eng.submit("m", r)
        res = eng.run_all()
        return eng, {r.uid: r.tokens for r in res}

    eng_b, got = serve(True)
    # lengths 11 and 13 share the pow2 bucket -> one admission prefill
    assert eng_b.prefill_batches == 1
    eng_e, got_exact = serve(False)
    assert eng_e.prefill_batches == 2  # exact-length grouping splits them
    w = ModelWorker("ref", cfg, params, max_len=48)
    for r in reqs:
        ref = w.generate(r.prompt[None], r.max_new_tokens)[0]
        np.testing.assert_array_equal(got[r.uid], ref)
        np.testing.assert_array_equal(got_exact[r.uid], ref)


def test_attention_stack_rejects_pad_mask():
    """Left padding shifts absolute (rope) positions, so attention stacks
    must refuse the mask loudly rather than silently mis-serve."""
    cfg = reduced(get_config("tinyllama-1.1b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.ones((1, 8), np.int32)
    mask = np.ones((1, 8), bool)
    with pytest.raises(ValueError, match="pure-SSM"):
        model_lib.prefill(params, cfg, jnp.asarray(prompt),
                          model_lib.init_cache(cfg, 1, 16),
                          pad_mask=jnp.asarray(mask))
