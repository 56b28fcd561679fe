"""Plain float32 reference of a latent-attention, mixture-of-experts decoder
(DeepSeek-V2), and its lower-precision control.

Written from the published equations, in straightforward ``jax.numpy``, with
nothing imported from the program. Token embedding; per layer an RMSNorm
(eps 1e-6, plain scale) and multi-head latent attention in its expanded form:
queries from one projection (no low-rank step), each head split into a part
without rotary embedding (``qk_nope_dim``) and a rotary part
(``qk_rope_dim``); one down-projection of the input to the latent
(``kv_lora_rank``, RMS-normed) and to a single rotary key shared by every
head; the latent expanded per head into the keys' other part and the values
(``v_head_dim``); a causal softmax at scale ``(nope + rope) ** -0.5``, the
output projection and a residual add. Rotary embeddings take the half-split
layout (the published checkpoint's interleaved layout is a fixed permutation
of the rotary columns of the query and down projections) with YaRN: each
frequency blended between its original and its interpolated value (divided by
the factor) over a linear ramp between the frequency indices at which
``beta_fast`` and ``beta_slow`` rotations fit in the original context, cos and
sin times ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``, and the
softmax scale times ``mscale(factor, mscale_all_dim) ** 2``, where
``mscale(s, m) = 0.1 m ln s + 1``. Then an RMSNorm and either a SwiGLU MLP
(the leading dense layers) or the expert layer: a float32 softmax router,
greedy top-k, the gates the top-k probabilities themselves (renormalised to
sum to 1 only where ``norm_topk_prob``), each routed expert a SwiGLU MLP of
``moe_d_ff``, plus the shared experts (one SwiGLU MLP of
``num_shared_experts * moe_d_ff``) on every token, and a residual add. A final
RMSNorm and the untied LM head. Weights come from
``bench/weights/mla_moe.py`` with the run's seed and are upcast to float32;
matmuls run at the highest precision.

Routing is decided on the host: the router's top-k comes back from the
device, and each expert is computed on exactly the tokens routed to it,
gathered by index (their count padded up to a few sizes, the padding weighted
0). So the layer drops nothing by construction and costs the routed tokens'
operations only; it shares nothing with the program's dispatch.

The model is run layer by layer over a few sequences at a time, so that it
fits beside nothing: it runs after the program's state is freed.

``quant`` (the control) computes every product with a weight matrix (the
router's too) in the precision below the configuration's bf16: ``fp8``
(e4m3), the weight with one scale per output column and the activations
entering it with one scale per row, accumulated in float32, as an fp8 matmul
would.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import mla_moe as weights

EPS = 1e-6
_MATRICES = ("wq", "w_dkv", "w_ukv", "wo", "w_gate", "w_up", "w_down", "router", "lm_head")


def quantize(w, quant: Optional[str]):
    """Round a matrix (..., d_in, d_out) to ``quant`` with a scale per output
    column and back to float32; ``None`` returns it unchanged."""
    if quant is None:
        return w
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-30) / 448.0
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
            continue
        v = v.astype(jnp.float32)
        if k == "w_ukv":  # (latent, heads, nope + v): a column is one head's output
            out[k] = quantize(v.reshape(v.shape[0], -1), quant).reshape(v.shape)
        else:
            out[k] = quantize(v, quant) if k in _MATRICES else v
    return out


def rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * scale


def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(m: dict):
    """(inverse frequencies of the rotary pairs, cos/sin scale), YaRN's where
    the configuration scales its rope, else plain rope's and 1."""
    dim, base = m["qk_rope_dim"], m["rope_theta"]
    freq = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    factor = m.get("yarn_factor")
    if factor is None:
        return jnp.asarray(freq, jnp.float32), 1.0
    orig = m["yarn_original_positions"]

    def at(rotations):  # the pair index whose wavelength fits ``rotations`` in ``orig``
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(at(m["yarn_beta_fast"])), 0)
    high = min(math.ceil(at(m["yarn_beta_slow"])), dim - 1)
    if high == low:
        high += 0.001
    interp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    inv = freq / factor * interp + freq * (1.0 - interp)
    return (jnp.asarray(inv, jnp.float32),
            mscale(factor, m["yarn_mscale"]) / mscale(factor, m["yarn_mscale_all_dim"]))


def softmax_scale(m: dict) -> float:
    scale = (m["qk_nope_dim"] + m["qk_rope_dim"]) ** -0.5
    if m.get("yarn_factor") is not None and m.get("yarn_mscale_all_dim"):
        scale *= mscale(m["yarn_factor"], m["yarn_mscale_all_dim"]) ** 2
    return scale


def rope(x, inv, cs):
    """x (S, H, dim) at positions 0..S-1; the halves of the last axis are the
    two parts of each rotated pair."""
    S, _, dim = x.shape
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang) * cs, jnp.sin(ang) * cs
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(m: dict, a: dict, h, quant: Optional[str] = None):
    """Expanded multi-head latent attention over one normed sequence h (S, d)."""
    S = h.shape[0]
    H, lr = m["num_heads"], m["kv_lora_rank"]
    nope, rp, vd = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    inv, cs = yarn(m)
    q = _mm(h, a["wq"], quant).reshape(S, H, nope + rp)
    ckr = _mm(h, a["w_dkv"], quant)
    latent = rms_norm(ckr[:, :lr], a["kv_norm"])
    kv = _mm(latent, a["w_ukv"].reshape(lr, -1), quant).reshape(S, H, nope + vd)
    k_rope = rope(ckr[:, None, lr:], inv, cs)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], inv, cs)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (S, H, rp))], axis=-1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * softmax_scale(m)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), kv[..., nope:])
    return _mm(o.reshape(S, H * vd), a["wo"], quant)


def swiglu(p: dict, h, quant: Optional[str] = None):
    return _mm(jax.nn.silu(_mm(h, p["w_gate"], quant)) * _mm(h, p["w_up"], quant),
               p["w_down"], quant)


def route(m: dict, p: dict, x, quant: Optional[str] = None):
    """x (N, d) -> (h, gates, ids): the normed input of the expert layer and
    each token's top-k experts with their gates."""
    h = rms_norm(x, p["mlp_norm"]["scale"])
    probs = jax.nn.softmax(_mm(h, p["mlp"]["router"], quant), axis=-1)
    gates, ids = jax.lax.top_k(probs, m["top_k"])
    if m.get("norm_topk_prob", True):
        gates = gates / gates.sum(axis=-1, keepdims=True)
    return h, gates, ids


def _rows(n: int) -> int:
    """Rows an expert's gathered tokens are padded to: few sizes, so few compiles."""
    return max(16, 1 << (n - 1).bit_length())


class Reference:
    """Next-token logits of the reference (or of the control, with ``quant``)
    along a few token sequences. Sequences are right-padded to one length;
    causal attention keeps the padding out of every real position, and the
    padding is routed to no expert."""

    def __init__(self, m: dict, seed: int, quant: Optional[str] = None):
        self.m, self.seed, self.quant = m, seed, quant
        # the key is an argument of each program, so that one compile serves every seed
        self.root = weights.root_key(seed)
        v = m["vocab_size"]

        def tables(root):
            emb = weights.embed(root, m)
            table = emb["embedding"].astype(jnp.float32)[:v]
            head = emb["lm_head"].astype(jnp.float32)[:, :v]
            return table, quantize(head, quant), weights.final_norm(root, m)["scale"]

        def head_scores(x, rows, toks, head, scale):
            logits = _mm(rms_norm(x[rows], scale), head, quant)
            at = jnp.take_along_axis(logits, toks[:, None], axis=1)[:, 0]
            return logits.max(axis=1), at, logits.argmax(axis=1)

        def attend(p, x):
            return x + attention(m, p["attn"], rms_norm(x, p["pre_norm"]["scale"]), quant)

        def dense_mlp(p, x):
            return x + swiglu(p["mlp"], rms_norm(x, p["mlp_norm"]["scale"]), quant)

        def shared(p, h):
            return swiglu(p["mlp"]["shared"], h, quant) if "shared" in p["mlp"] else jnp.zeros_like(h)

        def expert_add(acc, h, rows, gates, e, mlp):
            w = {k: mlp[k][e] for k in ("w_gate", "w_up", "w_down")}
            return acc.at[rows].add(gates[:, None] * swiglu(w, h[rows], quant))

        self._tables = jax.jit(tables)
        self._dense_layer = jax.jit(lambda root, i: _f32(weights.dense_layer(root, m, i), quant))
        self._moe_layer = jax.jit(lambda root, i: _f32(weights.moe_layer(root, m, i), quant))
        self._attend = jax.jit(jax.vmap(attend, in_axes=(None, 0)))
        self._dense_mlp = jax.jit(jax.vmap(dense_mlp, in_axes=(None, 0)))
        self._route = jax.jit(lambda p, x: route(m, p, x, quant))
        self._shared = jax.jit(shared)
        self._expert_add = jax.jit(expert_add)
        self._head = jax.jit(head_scores)

    def _experts(self, p, x, valid: np.ndarray):
        """The expert layer over one group x (G, L, d); ``valid`` (G*L,) marks
        the real tokens, the only ones routed."""
        G, L, d = x.shape
        h, gates, ids = self._route(p, x.reshape(G * L, d))
        out = self._shared(p, h)
        gates, ids = np.asarray(gates), np.asarray(ids)
        for e in range(self.m["num_experts"]):
            tok, slot = np.nonzero((ids == e) & valid[:, None])
            if not tok.size:
                continue
            n = _rows(tok.size)
            rows, g = np.zeros(n, np.int32), np.zeros(n, np.float32)
            rows[: tok.size], g[: tok.size] = tok, gates[tok, slot]
            out = self._expert_add(out, h, rows, g, e, p["mlp"])
        return x + out.reshape(G, L, d)

    def score(self, seqs: Sequence[np.ndarray], rows: Sequence[np.ndarray],
              tokens: Sequence[np.ndarray], group: int = 4):
        """At each position ``rows[i]`` of ``seqs[i]``: the best next-token
        logit, the logit of ``tokens[i]`` there, and the argmax token. Returns
        one (best, logit_of_token, argmax) triple of arrays per sequence.
        Lengths are padded to multiples of 128, so that few shapes compile."""
        m = self.m
        L = -(-max(len(s) for s in seqs) // 128) * 128
        R = -(-max(len(r) for r in rows) // 128) * 128
        with jax.default_matmul_precision("highest"):
            table, head, scale = self._tables(self.root)
            xs, valid = [], []
            for g0 in range(0, len(seqs), group):
                ids, ok = np.zeros((group, L), np.int32), np.zeros((group, L), bool)
                for j, s in enumerate(seqs[g0:g0 + group]):
                    ids[j, : len(s)], ok[j, : len(s)] = s, True
                xs.append(jnp.take(table, jnp.asarray(ids), axis=0))
                valid.append(ok.reshape(-1))
            del table
            for i in range(m["num_layers"]):
                if weights.is_moe(m, i):
                    p = self._moe_layer(self.root, i)
                    xs = [self._experts(p, self._attend(p, x), ok) for x, ok in zip(xs, valid)]
                else:
                    p = self._dense_layer(self.root, i)
                    xs = [self._dense_mlp(p, self._attend(p, x)) for x in xs]
                del p
            out = []
            for n, (r, t) in enumerate(zip(rows, tokens)):
                rr, tt = np.zeros(R, np.int32), np.zeros(R, np.int32)
                rr[: len(r)], tt[: len(t)] = r, t
                res = self._head(xs[n // group][n % group], rr, tt, head, scale)
                out.append(tuple(np.asarray(a)[: len(r)] for a in res))
            return out
