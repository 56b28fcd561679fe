"""Plain float32 reference of a dense GQA decoder, and its lower-precision control.

Written from the equations, in straightforward ``jax.numpy``, with nothing
imported from the program: token embedding; per layer an RMSNorm (eps 1e-6,
plain scale), grouped-query attention with optional QKV bias and rotary
embeddings (half-split layout, ``theta`` from the configuration), a causal
softmax, the output projection and a residual add; then an RMSNorm and a
SwiGLU MLP with a residual add; a final RMSNorm and the LM head (the tied
embedding or its own matrix). Weights come from ``bench/weights/dense_gqa.py``
with the run's seed and are upcast to float32; matmuls run at the highest
precision.

The model is run layer by layer over a few sequences at a time, so that it
fits beside nothing: it runs after the program's state is freed.

``quant`` (the control) computes every product with a weight matrix in the
precision below the configuration's bf16: ``fp8`` (e4m3), the weight with one
scale per output column and the activations entering it with one scale per
row, accumulated in float32, as an fp8 matmul would.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import dense_gqa as weights

EPS = 1e-6
_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head")


def quantize(w, quant: Optional[str]):
    """Round a (d_in, d_out) matrix to ``quant`` with a per-column scale and
    back to float32; ``None`` returns it unchanged."""
    if quant is None:
        return w
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-30) / 448.0
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant: Optional[str]):
    """``x @ w``; under the control, ``x`` is first rounded to ``quant`` row by
    row (``w`` was rounded when its layer was made)."""
    return quantize(x.T, quant).T @ w if quant else x @ w


def _f32(tree, quant):
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out[k] = _f32(v, quant)
        else:
            v = v.astype(jnp.float32)
            out[k] = quantize(v, quant) if k in _MATRICES else v
    return out


def rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * scale


def rope(x, theta: float):
    """x (S, H, Dh) at positions 0..S-1; the first and second halves of the
    head dimension are the two parts of each rotated pair."""
    S, _, dh = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def block(m: dict, p: dict, x, quant: Optional[str] = None):
    """One decoder layer over one sequence x (S, d)."""
    S = x.shape[0]
    H, Hkv, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    a = p["attn"]
    h = rms_norm(x, p["pre_norm"]["scale"])
    q, k, v = _mm(h, a["wq"], quant), _mm(h, a["wk"], quant), _mm(h, a["wv"], quant)
    if m["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = rope(q.reshape(S, H, dh), m["rope_theta"])
    k = rope(k.reshape(S, Hkv, dh), m["rope_theta"])
    v = v.reshape(S, Hkv, dh)
    g = H // Hkv  # query heads per KV head: head j reads KV head j // g
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(dh)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v).reshape(S, H * dh)
    x = x + _mm(o, a["wo"], quant)
    h = rms_norm(x, p["mlp_norm"]["scale"])
    mp = p["mlp"]
    up = jax.nn.silu(_mm(h, mp["w_gate"], quant)) * _mm(h, mp["w_up"], quant)
    return x + _mm(up, mp["w_down"], quant)


class Reference:
    """Next-token logits of the reference (or of the control, with ``quant``)
    along a few token sequences. Sequences are right-padded to one length;
    causal attention keeps the padding out of every real position."""

    def __init__(self, m: dict, seed: int, quant: Optional[str] = None):
        self.m, self.seed, self.quant = m, seed, quant
        # the key is an argument of each program, so that one compile serves every seed
        self.root = weights.root_key(seed)
        v = m["vocab_size"]

        def tables(root):
            emb = weights.embed(root, m)
            table = emb["embedding"].astype(jnp.float32)[:v]
            head = table.T if m["tie_embeddings"] else emb["lm_head"].astype(jnp.float32)[:, :v]
            return table, quantize(head, quant), weights.final_norm(root, m)["scale"]

        def head_scores(x, rows, toks, head, scale):
            logits = _mm(rms_norm(x[rows], scale), head, quant)
            at = jnp.take_along_axis(logits, toks[:, None], axis=1)[:, 0]
            return logits.max(axis=1), at, logits.argmax(axis=1)

        self._tables = jax.jit(tables)
        self._layer = jax.jit(lambda root, i: _f32(weights.layer(root, m, i), quant))
        self._block = jax.jit(jax.vmap(lambda p, x: block(m, p, x, quant), in_axes=(None, 0)))
        self._head = jax.jit(head_scores)

    def score(self, seqs: Sequence[np.ndarray], rows: Sequence[np.ndarray],
              tokens: Sequence[np.ndarray], group: int = 4):
        """At each position ``rows[i]`` of ``seqs[i]``: the best next-token
        logit, the logit of ``tokens[i]`` there, and the argmax token. Returns
        one (best, logit_of_token, argmax) triple of arrays per sequence.
        Lengths are padded to multiples of 128, so that few shapes compile."""
        L = -(-max(len(s) for s in seqs) // 128) * 128
        R = -(-max(len(r) for r in rows) // 128) * 128
        with jax.default_matmul_precision("highest"):
            table, head, scale = self._tables(self.root)
            xs = []
            for g0 in range(0, len(seqs), group):
                ids = np.zeros((group, L), np.int32)
                for j, s in enumerate(seqs[g0:g0 + group]):
                    ids[j, : len(s)] = s
                xs.append(jnp.take(table, jnp.asarray(ids), axis=0))
            del table
            for i in range(self.m["num_layers"]):
                p = self._layer(self.root, i)
                xs = [self._block(p, x) for x in xs]
                del p
            out = []
            for n, (r, t) in enumerate(zip(rows, tokens)):
                rr, tt = np.zeros(R, np.int32), np.zeros(R, np.int32)
                rr[: len(r)], tt[: len(t)] = r, t
                res = self._head(xs[n // group][n % group], rr, tt, head, scale)
                out.append(tuple(np.asarray(a)[: len(r)] for a in res))
            return out
