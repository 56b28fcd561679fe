"""Shared building blocks: norms, MLPs, rotary embeddings, embeddings, init.

Pure-functional JAX: params are nested dicts of jnp arrays; every layer is
``init_*(rng, ...) -> params`` + ``apply`` functions. No framework deps.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(rng, d_in, d_out, dtype, scale=None):
    scale = scale if scale is not None else (1.0 / jnp.sqrt(d_in))
    return (jax.random.normal(rng, (d_in, d_out), dtype=jnp.float32) * scale).astype(dtype)


def split(rng, n):
    return jax.random.split(rng, n)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_norm(cfg, d=None):
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32)}


def apply_norm(p, x, cfg, eps=1e-6):
    xf = x.astype(jnp.float32)
    if "bias" in p:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:  # rmsnorm (gemma-style: scale offset by 1 is NOT used here; plain scale)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return out.astype(x.dtype)


def rms_norm_head(x, scale, eps=1e-6):
    """Per-head qk-norm (chameleon)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def yarn_mscale(factor, mscale):
    """YaRN's magnitude correction for a context stretched ``factor`` times."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_scaling(cfg):
    """The config's YaRN scaling as a hashable tuple (factor, original
    positions, beta_fast, beta_slow, cos/sin scale), or None for plain rope."""
    if cfg.yarn_factor is None:
        return None
    f = cfg.yarn_factor
    return (f, cfg.yarn_original_positions, cfg.yarn_beta_fast, cfg.yarn_beta_slow,
            yarn_mscale(f, cfg.yarn_mscale) / yarn_mscale(f, cfg.yarn_mscale_all_dim))


def yarn_ramp(head_dim, theta, original, beta_fast, beta_slow):
    """(low, high) frequency indices of YaRN's linear ramp: indices at or
    below ``low`` keep their frequency, those at or above ``high`` are
    interpolated, those between blend linearly."""
    def dim(rotations):
        return head_dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim(beta_fast)), 0)
    high = min(math.ceil(dim(beta_slow)), head_dim - 1)
    return low, (high if high > low else low + 0.001)


def rope_freqs(head_dim, theta, scaling=None):
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if scaling is None:
        return freqs
    factor, original, beta_fast, beta_slow, _ = scaling
    low, high = yarn_ramp(head_dim, theta, original, beta_fast, beta_slow)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x, positions, theta, scaling=None):
    """x: (..., S, H, Dh) ; positions: (..., S) int32. ``scaling`` is
    :func:`rope_scaling`'s tuple (YaRN) or None."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta, scaling)  # (Dh/2,)
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # (...,S,1,Dh/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    if scaling is not None and scaling[4] != 1.0:
        sin, cos = sin * scaling[4], cos * scaling[4]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU for rmsnorm models, GELU for layernorm enc-dec)
# ---------------------------------------------------------------------------


def init_mlp(rng, cfg, d_ff=None):
    d_ff = d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    r = split(rng, 3)
    if cfg.norm == "layernorm":  # classic transformer FFN
        return {
            "wi": dense_init(r[0], cfg.d_model, d_ff, dt),
            "wo": dense_init(r[1], d_ff, cfg.d_model, dt),
        }
    return {
        "w_gate": dense_init(r[0], cfg.d_model, d_ff, dt),
        "w_up": dense_init(r[1], cfg.d_model, d_ff, dt),
        "w_down": dense_init(r[2], d_ff, cfg.d_model, dt),
    }


def apply_mlp(p, x, cfg):
    if "wi" in p:
        h = jax.nn.gelu(x @ p["wi"])
        return h @ p["wo"]
    act = jax.nn.gelu if cfg.name.startswith("gemma2") else jax.nn.silu
    h = act(x @ p["w_gate"]) * (x @ p["w_up"])
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# embeddings / lm head
# ---------------------------------------------------------------------------


def init_embed(rng, cfg):
    dt = jnp.dtype(cfg.param_dtype)
    v = cfg.padded_vocab
    r = split(rng, 2)
    p = {"embedding": (jax.random.normal(r[0], (v, cfg.d_model), jnp.float32) * 0.02).astype(dt)}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(r[1], cfg.d_model, v, dt, scale=0.02)
    return p


def embed_tokens(p, ids, cfg):
    x = jnp.take(p["embedding"], ids, axis=0)
    if cfg.name.startswith("gemma2"):
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)
    return x


def lm_logits(p, x, cfg):
    if cfg.tie_embeddings:
        logits = x @ p["embedding"].T
    else:
        logits = x @ p["lm_head"]
    logits = logits.astype(jnp.float32)
    if cfg.final_softcap:
        c = cfg.final_softcap
        logits = c * jnp.tanh(logits / c)
    return logits
