"""Seeded weights of a dense GQA configuration, made by the benchmark.

Every leaf is drawn from its own key, ``fold_in(fold_in(root, leaf), layer)``,
so one layer can be made alone: the served model gets the whole stack from one
jitted call on the device (``served_params``), and the reference makes layer
after layer of the same numbers (``layer``) without touching what the program
holds. Biases and norm scales are random too, so that a path that drops them
shows in the comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# one id per leaf; the id, not the name, goes into the key
_LEAVES = ("embedding", "lm_head", "final_norm", "pre_norm", "mlp_norm",
           "wq", "wk", "wv", "wo", "bq", "bk", "bv", "w_gate", "w_up", "w_down")
_EMBED_SCALE = 0.02
_BIAS_SCALE = 0.5
_NORM_SPREAD = 0.1


def root_key(seed: int):
    """A key for any whole number up to 64 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _normal(root, leaf: str, layer, shape):
    key = jax.random.fold_in(jax.random.fold_in(root, _LEAVES.index(leaf)), layer)
    return jax.random.normal(key, shape, jnp.float32)


def padded_vocab(m: dict) -> int:
    return (m["vocab_size"] + 255) // 256 * 256


def layer(root, m: dict, i):
    """Layer ``i`` of a dense GQA stack, in the served dtypes: matrices in
    ``param_dtype``, norm scales and biases in float32."""
    d, dt = m["d_model"], jnp.dtype(m["param_dtype"])
    q, kv, ff = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"], m["d_ff"]

    def dense(leaf, d_in, d_out):
        return (_normal(root, leaf, i, (d_in, d_out)) / jnp.sqrt(d_in)).astype(dt)

    def norm(leaf):
        return {"scale": 1.0 + _NORM_SPREAD * _normal(root, leaf, i, (d,))}

    attn = {"wq": dense("wq", d, q), "wk": dense("wk", d, kv), "wv": dense("wv", d, kv),
            "wo": dense("wo", q, d)}
    if m["qkv_bias"]:
        for leaf, n in (("bq", q), ("bk", kv), ("bv", kv)):
            attn[leaf] = _BIAS_SCALE * _normal(root, leaf, i, (n,))
    return {"pre_norm": norm("pre_norm"), "attn": attn, "mlp_norm": norm("mlp_norm"),
            "mlp": {"w_gate": dense("w_gate", d, ff), "w_up": dense("w_up", d, ff),
                    "w_down": dense("w_down", ff, d)}}


def embed(root, m: dict):
    """Embedding (and untied LM head); the rows past ``vocab_size`` that the
    program pads to a multiple of 256 are zero, as in a deployed checkpoint."""
    d, dt, v, vp = m["d_model"], jnp.dtype(m["param_dtype"]), m["vocab_size"], padded_vocab(m)
    keep = (jnp.arange(vp) < v)[:, None]
    out = {"embedding": (jnp.where(keep, _normal(root, "embedding", 0, (vp, d)), 0.0)
                         * _EMBED_SCALE).astype(dt)}
    if not m["tie_embeddings"]:
        out["lm_head"] = (jnp.where(keep, _normal(root, "lm_head", 0, (vp, d)), 0.0).T
                          * _EMBED_SCALE).astype(dt)
    return out


def final_norm(root, m: dict):
    return {"scale": 1.0 + _NORM_SPREAD * _normal(root, "final_norm", 0, (m["d_model"],))}


def served_params(seed: int, m: dict):
    """The whole model in the program's layout, made on the device in one
    jitted call: one scanned stage of ``num_layers`` stacked layers, whose
    single layer of the pattern is ``l0``. The root key is the program's
    argument, not a constant in it, so one compiled program (kept in the
    persistent cache) makes every seed's weights."""
    def make(root):
        stack = jax.vmap(lambda i: layer(root, m, i))(jnp.arange(m["num_layers"]))
        return {"embed": embed(root, m), "final_norm": final_norm(root, m),
                "stages": [{"l0": stack}]}
    return jax.jit(make)(root_key(seed))
