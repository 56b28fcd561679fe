"""Seeded weights of a latent-attention, mixture-of-experts configuration
(DeepSeek-V2's layer: MLA and a routed MLP with shared experts, after
leading dense layers), made by the benchmark.

Every leaf is drawn from its own key, ``fold_in(fold_in(root, leaf), layer)``,
so one layer can be made alone: the served model gets the whole stack from
one jitted call on the device (``served_params``), and the reference makes
layer after layer of the same numbers (``dense_layer``, ``moe_layer``)
without touching what the program holds. Norm scales are random too, so that
a path that drops one shows in the comparison. A layer's experts are one
leaf each of ``w_gate``, ``w_up`` and ``w_down``, stacked over the experts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.weights.dense_gqa import embed, final_norm, root_key

# one id per leaf; the id, not the name, goes into the key (ids 0..2 are the
# tables and the final norm, which dense_gqa's embed and final_norm draw)
_LEAVES = ("embedding", "lm_head", "final_norm", "pre_norm", "mlp_norm",
           "wq", "w_dkv", "kv_norm", "w_ukv", "wo", "w_gate", "w_up", "w_down",
           "router", "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down")
_NORM_SPREAD = 0.1


def _normal(root, leaf: str, layer, shape):
    key = jax.random.fold_in(jax.random.fold_in(root, _LEAVES.index(leaf)), layer)
    return jax.random.normal(key, shape, jnp.float32)


def _dense(root, m, leaf, i, shape):
    """A matrix of ``shape`` whose second-to-last axis is its input, scaled
    so that a product keeps the input's variance, in ``param_dtype``."""
    return (_normal(root, leaf, i, shape) / jnp.sqrt(shape[-2])).astype(jnp.dtype(m["param_dtype"]))


def _norm(root, leaf, i, d):
    return 1.0 + _NORM_SPREAD * _normal(root, leaf, i, (d,))


def attention(root, m: dict, i):
    """Layer ``i``'s MLA weights: queries without a low-rank step, the joint
    down-projection to the latent and the shared rope key, the latent's norm,
    the up-projection stored (latent, heads, nope + v) and the output."""
    d, H, lr = m["d_model"], m["num_heads"], m["kv_lora_rank"]
    nope, rope, vd = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    w_ukv = _dense(root, m, "w_ukv", i, (lr, H * (nope + vd))).reshape(lr, H, nope + vd)
    return {"wq": _dense(root, m, "wq", i, (d, H * (nope + rope))),
            "w_dkv": _dense(root, m, "w_dkv", i, (d, lr + rope)),
            "kv_norm": _norm(root, "kv_norm", i, lr),
            "w_ukv": w_ukv,
            "wo": _dense(root, m, "wo", i, (H * vd, d))}


def dense_layer(root, m: dict, i):
    """Layer ``i`` as a leading dense layer: MLA and a SwiGLU MLP of ``d_ff``."""
    d, ff = m["d_model"], m["d_ff"]
    return {"pre_norm": {"scale": _norm(root, "pre_norm", i, d)},
            "attn": attention(root, m, i),
            "mlp_norm": {"scale": _norm(root, "mlp_norm", i, d)},
            "mlp": {"w_gate": _dense(root, m, "w_gate", i, (d, ff)),
                    "w_up": _dense(root, m, "w_up", i, (d, ff)),
                    "w_down": _dense(root, m, "w_down", i, (ff, d))}}


def moe_layer(root, m: dict, i):
    """Layer ``i`` as an expert layer: MLA, a float32 router over
    ``num_experts``, the routed experts stacked (E, ...) and the shared
    experts as one SwiGLU MLP of ``num_shared_experts * moe_d_ff``."""
    d, E, F = m["d_model"], m["num_experts"], m["moe_d_ff"]
    Fs = m["num_shared_experts"] * F
    mlp = {"router": _normal(root, "router", i, (d, E)) / jnp.sqrt(d),
           "w_gate": _dense(root, m, "e_gate", i, (E, d, F)),
           "w_up": _dense(root, m, "e_up", i, (E, d, F)),
           "w_down": _dense(root, m, "e_down", i, (E, F, d))}
    if Fs:
        mlp["shared"] = {"w_gate": _dense(root, m, "s_gate", i, (d, Fs)),
                         "w_up": _dense(root, m, "s_up", i, (d, Fs)),
                         "w_down": _dense(root, m, "s_down", i, (Fs, d))}
    return {"pre_norm": {"scale": _norm(root, "pre_norm", i, d)},
            "attn": attention(root, m, i),
            "mlp_norm": {"scale": _norm(root, "mlp_norm", i, d)},
            "mlp": mlp}


def is_moe(m: dict, i: int) -> bool:
    return i >= m["first_dense_layers"]


def stages(m: dict):
    """The program's grouping of the layers into scanned stages: a list of
    (first layer, repeats, period). Like the program, it takes the first
    split into a prefix and a periodic rest, prefixes tried from 0 and
    periods from 1, so a dense first layer makes one stage of one repeat."""
    seq = [is_moe(m, i) for i in range(m["num_layers"])]
    for prefix in range(len(seq)):
        rest = seq[prefix:]
        for p in range(1, len(rest) + 1):
            if len(rest) % p == 0 and all(rest[i] == rest[i % p] for i in range(len(rest))):
                return ([(0, 1, prefix)] if prefix else []) + [(prefix, len(rest) // p, p)]
    return [(0, 1, len(seq))]


def served_params(seed: int, m: dict):
    """The whole model in the program's layout, made on the device in one
    jitted call. The root key is the program's argument, not a constant in
    it, so one compiled program makes every seed's weights."""
    def make(root):
        out = []
        for first, repeats, period in stages(m):
            stage = {}
            for j in range(period):
                make_layer = moe_layer if is_moe(m, first + j) else dense_layer
                idx = first + j + period * jnp.arange(repeats)
                stage[f"l{j}"] = jax.vmap(lambda i: make_layer(root, m, i))(idx)
            out.append(stage)
        return {"embed": embed(root, m), "final_norm": final_norm(root, m), "stages": out}
    return jax.jit(make)(root_key(seed))
