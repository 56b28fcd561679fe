"""XLA attention paths + SSM chunked impls vs first-principles oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels.ref import attention_ref, ssd_ref
from repro.models.attention import attend, chunked_attention, full_attention
from repro.models.ssm import _selective_scan_chunked, ssd_chunked


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 1000), st.sampled_from([1, 2]), st.sampled_from([17, 64, 130]),
       st.sampled_from([(4, 2), (4, 4), (8, 1)]), st.booleans())
def test_chunked_equals_full_property(seed, B, S, heads, causal):
    H, Hkv = heads
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, 32))
    k = jax.random.normal(ks[1], (B, S, Hkv, 32))
    v = jax.random.normal(ks[2], (B, S, Hkv, 32))
    a = full_attention(q, k, v, causal=causal)
    b = chunked_attention(q, k, v, causal=causal, block=48)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5)
    r = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(a), np.asarray(r), atol=3e-5, rtol=3e-5)


def test_sliding_window_masks_old_tokens():
    """With window w, token i must ignore tokens < i-w+1: moving distant
    context must not change the output."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    S, w = 64, 8
    q = jax.random.normal(ks[0], (1, S, 2, 16))
    k = jax.random.normal(ks[1], (1, S, 2, 16))
    v = jax.random.normal(ks[2], (1, S, 2, 16))
    out1 = full_attention(q, k, v, causal=True, window=w)
    k2 = k.at[:, :S - w].set(jax.random.normal(ks[3], (1, S - w, 2, 16)))
    out2 = full_attention(q, k2, v, causal=True, window=w)
    np.testing.assert_allclose(np.asarray(out1[:, -1]), np.asarray(out2[:, -1]),
                               atol=1e-6)


def test_mla_decode_absorbed_equals_naive():
    """MLA absorbed decode == expanding the latent cache and running GQA."""
    from repro.configs.base import get_config, reduced
    from repro.models.attention import init_mla, mla_decode, mla_forward

    cfg = reduced(get_config("deepseek-v2-lite-16b"))
    p = init_mla(jax.random.PRNGKey(0), cfg)
    B, S = 2, 24
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S + 1, cfg.d_model)) * 0.3
    # full forward over S+1 tokens = ground truth for last position
    out_full, _ = mla_forward(p, x, cfg)
    # prefill S, then absorbed decode of token S
    _, (c_kv, k_rope) = mla_forward(p, x[:, :S], cfg)
    cache_ckv = jnp.zeros((B, S + 4, cfg.kv_lora_rank))
    cache_kr = jnp.zeros((B, S + 4, cfg.qk_rope_dim))
    cache_ckv = cache_ckv.at[:, :S].set(c_kv)
    cache_kr = cache_kr.at[:, :S].set(k_rope)
    # a stage's stacked cache of one layer, decoded at layer 0
    out_dec, _ = mla_decode(p, x[:, S:S + 1], cfg, cache_ckv[None], cache_kr[None], 0,
                            jnp.int32(S))
    np.testing.assert_allclose(np.asarray(out_dec[:, 0]), np.asarray(out_full[:, S]),
                               atol=2e-3, rtol=2e-3)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 1000), st.sampled_from([32, 100]), st.sampled_from([16, 64]))
def test_ssd_chunked_property(seed, S, chunk):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    B, H, P, N = 1, 2, 16, 8
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    y, h = ssd_chunked(x, dt * A, dt, Bm, Cm, chunk)
    yr, hr = ssd_ref(x, dt * A, dt, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=2e-3, rtol=2e-3)


def test_mamba1_chunked_scan_vs_sequential():
    """Chunked associative selective scan == step-by-step recurrence."""
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    B, S, di, N = 2, 50, 8, 4
    u = jax.random.normal(ks[0], (B, S, di)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)))
    A = -jnp.exp(jax.random.normal(ks[2], (di, N)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    y, h = _selective_scan_chunked(u, dt, Bm, Cm, A, chunk=16)
    # sequential oracle
    hs = np.zeros((B, di, N))
    ys = np.zeros((B, S, di))
    for t in range(S):
        dA = np.exp(np.asarray(dt[:, t])[..., None] * np.asarray(A))
        hs = hs * dA + (np.asarray(dt[:, t]) * np.asarray(u[:, t]))[..., None] * np.asarray(Bm[:, t])[:, None, :]
        ys[:, t] = np.einsum("bdn,bn->bd", hs, np.asarray(Cm[:, t]))
    np.testing.assert_allclose(np.asarray(y), ys, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(h), hs, atol=2e-3, rtol=2e-3)


def test_attend_pallas_impl_smoke():
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 64))
    k = jax.random.normal(ks[1], (1, 64, 2, 64))
    v = jax.random.normal(ks[2], (1, 64, 2, 64))
    a = attend(q, k, v, impl="pallas")
    b = attend(q, k, v, impl="xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5)
