"""Pallas kernel validation (deliverable c): shape/dtype sweeps against the
pure-jnp oracles in repro.kernels.ref, interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ops import flash_attention as flash_dispatch
from repro.kernels.ref import attention_ref, ssd_ref
from repro.kernels.ssd_scan import ssd_scan

ATOL = {jnp.float32: 3e-5, jnp.bfloat16: 3e-2}


def _qkv(rng, B, Sq, Sk, H, Hkv, Dk, Dv, dtype):
    ks = jax.random.split(rng, 3)
    return (jax.random.normal(ks[0], (B, Sq, H, Dk), dtype),
            jax.random.normal(ks[1], (B, Sk, Hkv, Dk), dtype),
            jax.random.normal(ks[2], (B, Sk, Hkv, Dv), dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sq,Sk,H,Hkv,Dk,Dv", [
    (2, 128, 128, 4, 2, 64, 64),
    (1, 256, 256, 8, 8, 128, 128),
    (2, 96, 96, 4, 1, 64, 32),    # ragged seq (pad path), MQA, Dv != Dk
    (1, 64, 192, 6, 2, 32, 32),   # cross-len
])
def test_flash_vs_ref(dtype, B, Sq, Sk, H, Hkv, Dk, Dv):
    q, k, v = _qkv(jax.random.PRNGKey(0), B, Sq, Sk, H, Hkv, Dk, Dv, dtype)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               atol=ATOL[dtype], rtol=ATOL[dtype])


@pytest.mark.parametrize("window,softcap", [(None, None), (32, None), (None, 30.0), (48, 50.0)])
def test_flash_window_softcap(window, softcap):
    q, k, v = _qkv(jax.random.PRNGKey(1), 2, 128, 128, 4, 2, 64, 64, jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window, softcap=softcap,
                          block_q=32, block_k=32)
    ref = attention_ref(q, k, v, causal=True, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sk,H,Hkv,D,pos", [
    (2, 512, 8, 2, 64, 400),
    (1, 1024, 16, 8, 128, 1023),
    (2, 300, 4, 4, 64, 128),  # pad path
])
def test_decode_vs_ref(dtype, B, Sk, H, Hkv, D, pos):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, D), dtype)
    out = decode_attention(q, k, v, q_offset=pos, kv_len=pos + 1, block_k=128)
    ref = attention_ref(q, k, v, causal=False, q_offset=pos, kv_len=pos + 1)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               atol=ATOL[dtype], rtol=ATOL[dtype])


def test_ops_dispatch_decode():
    """ops.flash_attention routes q_len==1 to the flash-decode kernel."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 1, 4, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    out = flash_dispatch(q, k, v, causal=False, q_offset=100, kv_len=101)
    ref = attention_ref(q, k, v, causal=False, q_offset=100, kv_len=101)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 256, 4, 64, 32, 64),
    (1, 100, 2, 32, 16, 32),   # ragged pad path
    (2, 128, 8, 64, 128, 128),  # d_state=128 (mamba2-2.7b)
])
def test_ssd_vs_ref(dtype, B, S, H, P, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    dA = dt * A
    Bm = jax.random.normal(ks[3], (B, S, N), dtype)
    Cm = jax.random.normal(ks[4], (B, S, N), dtype)
    y, h = ssd_scan(x, dA, dt, Bm, Cm, chunk=chunk)
    yr, hr = ssd_ref(x, dA, dt, Bm, Cm)
    tol = 2e-3 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yr, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=tol, rtol=tol)


def test_models_pallas_impl_matches_xla():
    """attend(impl='pallas') (the real-TPU path) == xla path end to end."""
    from repro.models.attention import attend

    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 64))
    k = jax.random.normal(ks[1], (2, 128, 2, 64))
    v = jax.random.normal(ks[2], (2, 128, 2, 64))
    a = attend(q, k, v, causal=True, impl="pallas")
    b = attend(q, k, v, causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("interpret,backend,expected", [
    (None, "cpu", True),
    (None, "tpu", False),
    (True, "tpu", True),
    (False, "cpu", False),
    (True, "gpu", True),
    (None, "gpu", RuntimeError),
])
def test_interpret_mode_follows_caller_then_platform(monkeypatch, interpret,
                                                     backend, expected):
    """Interpreted only when asked or on the CPU; an accelerator that is not
    a TPU is refused rather than run through the interpreter."""
    import repro.kernels as kernels
    monkeypatch.setattr(kernels.jax, "default_backend", lambda: backend)
    if expected is RuntimeError:
        with pytest.raises(RuntimeError, match="gpu"):
            kernels.interpret_mode(interpret)
    else:
        assert kernels.interpret_mode(interpret) is expected
