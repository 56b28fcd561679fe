"""Attention: GQA (+bias, qk-norm, softcap, sliding window) and MLA.

Three execution paths:
  * ``full``    — direct softmax(QK^T)V, used for short KV (<=2048) and as oracle.
  * ``chunked`` — lax.scan over KV blocks with online softmax (flash-style in
                  XLA); O(S_kv * block) memory, checkpointed body. This is what
                  the dry-run lowers for long sequences.
  * ``pallas``  — the Pallas TPU kernels in repro.kernels (real-TPU default),
                  selected via impl="pallas".

Decode (q_len==1 against a KV cache) reuses the chunked path; MLA decode uses
the absorbed-latent trick (scores in the 512-d latent space, no per-step KV
expansion).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import (apply_rope, dense_init, rms_norm_head, rope_scaling, split,
                                 yarn_mscale)

_FULL_KV_LIMIT = 2048
_KV_BLOCK = 1024


# ---------------------------------------------------------------------------
# core attend: q (B,Sq,H,Dk) k (B,Sk,Hkv,Dk) v (B,Sk,Hkv,Dv)
# ---------------------------------------------------------------------------


def _mask(qpos, kpos, causal, window, kv_len):
    """qpos (Sq,) or (B,Sq), kpos (Sk,) absolute positions; kv_len scalar or
    (B,). Returns a bool keep-mask of shape (Sq,Sk) — or (B,Sq,Sk) when any
    input carries a per-sequence batch dim (the ragged continuous-batching
    decode path, where every slot has its own write position)."""
    qp = jnp.asarray(qpos)[..., :, None]  # (...,Sq,1)
    kp = jnp.asarray(kpos)  # (Sk,)
    m = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        m &= kp <= qp
    if window is not None:
        m &= (qp - kp) < window
    if kv_len is not None:
        kl = jnp.asarray(kv_len)
        if kl.ndim:
            kl = kl[:, None, None]  # (B,) -> (B,1,1)
        m &= kp < kl
    return m


def full_attention(q, k, v, *, causal=True, window=None, softcap=None,
                   q_offset=0, kv_len=None, scale=None):
    B, Sq, H, Dk = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = scale if scale is not None else Dk ** -0.5
    qh = q.reshape(B, Sq, Hkv, G, Dk)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qh.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qoff = jnp.asarray(q_offset)
    qpos = (qoff[..., None] if qoff.ndim else qoff) + jnp.arange(Sq)
    kpos = jnp.arange(Sk)
    m = _mask(qpos, kpos, causal, window, kv_len)
    m = m[:, None, None] if m.ndim == 3 else m[None, None, None]
    s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def chunked_attention(q, k, v, *, causal=True, window=None, softcap=None,
                      q_offset=0, kv_len=None, scale=None, block=_KV_BLOCK):
    """Flash-style online softmax over KV blocks (pure XLA).

    Heads are kept flat (B,S,H,D) — the KV block is repeated per q-head
    group *per block* (small transient) instead of reshaping q to
    (Hkv, G), which would break head sharding when Hkv doesn't divide the
    model axis. On real TPU the Pallas kernel replaces this path.
    """
    B, Sq, H, Dk = q.shape
    Sk, Hkv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // Hkv
    scale = scale if scale is not None else Dk ** -0.5
    nb = -(-Sk // block)
    pad = nb * block - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nb, block, Hkv, Dk).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, block, Hkv, Dv).transpose(1, 0, 2, 3, 4)
    qf = q.astype(jnp.float32)
    qoff = jnp.asarray(q_offset)
    qpos = (qoff[..., None] if qoff.ndim else qoff) + jnp.arange(Sq)
    eff_len = jnp.minimum(kv_len, Sk) if kv_len is not None else Sk

    def body(carry, xs):
        acc, m_run, l_run = carry
        kblk, vblk, j0 = xs
        kx = jnp.repeat(kblk, G, axis=2).astype(jnp.float32)  # (B,bk,H,Dk)
        vx = jnp.repeat(vblk, G, axis=2).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kx) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        kpos = j0 + jnp.arange(block)
        keep = _mask(qpos, kpos, causal, window, eff_len)
        keep = keep[:, None] if keep.ndim == 3 else keep[None, None]
        s = jnp.where(keep, s, -1e30)
        m_new = jnp.maximum(m_run, s.max(axis=-1))
        corr = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_run * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vx)
        return (acc_new, m_new, l_new), None

    init = (
        jnp.zeros((B, H, Sq, Dv), jnp.float32),
        jnp.full((B, H, Sq), -1e30, jnp.float32),
        jnp.zeros((B, H, Sq), jnp.float32),
    )
    offs = jnp.arange(nb) * block
    (acc, m_run, l_run), _ = jax.lax.scan(jax.checkpoint(body), init, (kb, vb, offs))
    o = acc / jnp.maximum(l_run[..., None], 1e-30)
    return o.transpose(0, 2, 1, 3).astype(q.dtype)


def attend(q, k, v, *, causal=True, window=None, softcap=None, q_offset=0,
           kv_len=None, scale=None, impl="xla"):
    if impl == "pallas":
        from repro.kernels import ops  # lazy: kernels are TPU-target

        return ops.flash_attention(q, k, v, causal=causal, window=window,
                                   softcap=softcap, q_offset=q_offset,
                                   kv_len=kv_len, scale=scale)
    # single-token decode is linear in KV either way: the direct path keeps
    # the KV sequence dim free (shardable along 'data' for long contexts)
    # instead of a sequential scan over a sharded leading dim.
    if k.shape[1] <= _FULL_KV_LIMIT or q.shape[1] == 1:
        return full_attention(q, k, v, causal=causal, window=window, softcap=softcap,
                              q_offset=q_offset, kv_len=kv_len, scale=scale)
    return chunked_attention(q, k, v, causal=causal, window=window, softcap=softcap,
                             q_offset=q_offset, kv_len=kv_len, scale=scale)


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------


def init_gqa(rng, cfg, cross=False):
    dt = jnp.dtype(cfg.param_dtype)
    r = split(rng, 5)
    p = {
        "wq": dense_init(r[0], cfg.d_model, cfg.q_dim, dt),
        "wk": dense_init(r[1], cfg.d_model, cfg.kv_dim, dt),
        "wv": dense_init(r[2], cfg.d_model, cfg.kv_dim, dt),
        "wo": dense_init(r[3], cfg.q_dim, cfg.d_model, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), jnp.float32)
        p["bk"] = jnp.zeros((cfg.kv_dim,), jnp.float32)
        p["bv"] = jnp.zeros((cfg.kv_dim,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
        p["k_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
    return p


def _project_qkv(p, x, cfg, positions, rope=True):
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"].astype(q.dtype), k + p["bk"].astype(k.dtype), v + p["bv"].astype(v.dtype)
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm_head(q, p["q_norm"])
        k = rms_norm_head(k, p["k_norm"])
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_forward(p, x, cfg, *, window=None, impl="xla", ctx=None):
    """Train/prefill: full causal self-attention. Returns (out, kv) so callers
    can build a cache."""
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = _project_qkv(p, x, cfg, positions)
    if ctx is not None and ctx.mesh is not None and ctx.plan.get("attn_seq_shard"):
        # §Perf knob: when head counts don't divide the model axis, shard the
        # QUERY SEQUENCE on 'model' instead (KV replicated once per layer) —
        # removes the per-layer head-resharding all-gather storm.
        from jax.sharding import NamedSharding, PartitionSpec as P

        bspec = ctx.batch_axes or None
        q = jax.lax.with_sharding_constraint(
            q, NamedSharding(ctx.mesh, P(bspec, ctx.model_axis, None, None)))
        k = jax.lax.with_sharding_constraint(
            k, NamedSharding(ctx.mesh, P(bspec, None, None, None)))
        v = jax.lax.with_sharding_constraint(
            v, NamedSharding(ctx.mesh, P(bspec, None, None, None)))
    o = attend(q, k, v, causal=True, window=window, softcap=cfg.attn_softcap, impl=impl)
    return o.reshape(B, S, cfg.q_dim) @ p["wo"], (k, v)


def _decode_positions(pos, B, T):
    """(B, T) positions of a decode step's tokens: ``pos`` is a scalar (every
    row at one depth, the bucketed path) or (B,) per-row depths (the ragged
    slot pool), and row b's T tokens sit at pos[b] .. pos[b] + T - 1."""
    return jnp.broadcast_to(pos, (B,))[:, None] + jnp.arange(T)


def _decode_mask(pos, T):
    """``attend``'s masking for a decode step: one new token sees the cache
    up to its own position; T > 1 new tokens (the speculative verify) see it
    causally, among themselves too."""
    if T > 1:
        return dict(causal=True, q_offset=pos, kv_len=None)
    return dict(causal=False, q_offset=pos, kv_len=pos + 1)


def _write_rows(stack, layer, positions, rows):
    """Scatter a decode step's new rows (B, T, ...) into a stage's stacked
    cache (layers, B, Smax, ...) at [layer, b, positions[b, t]], in place:
    nothing else of the stack is written. Out-of-range positions drop."""
    bidx = jnp.arange(rows.shape[0])[:, None]
    return stack.at[layer, bidx, positions].set(rows.astype(stack.dtype), mode="drop")


def gqa_decode(p, x, cfg, stack_k, stack_v, layer, pos, *, window=None, impl="xla"):
    """Decode step of GQA layer ``layer`` of a stage. x (B,T,D); stack_k/v
    (layers,B,Smax,Hkv,Dh), the stage's stacked cache, which comes back with
    this step's K/V rows scattered in and nothing else written.

    ``pos`` is either a scalar (position-synchronous batch, the bucketed
    serving path) or a (B,) vector of per-sequence write positions (the
    ragged continuous-batching path, where every cache slot sits at its own
    depth); each row attends up to its own position.

    Speculative verify (vector ``pos`` with T > 1): each row scores T
    candidate positions pos..pos+T-1 in one forward — K/V scatter at the
    (B,T) position grid (out-of-range writes drop), causal masking among
    the new queries. Stale cache entries past a row's committed frontier
    (rejected draft suffixes from an earlier round) sit at kpos > qpos, so
    the causal mask hides them until they are overwritten — rollback is
    free."""
    B, T = x.shape[0], x.shape[1]
    pos = jnp.asarray(pos)
    positions = _decode_positions(pos, B, T)
    q, k, v = _project_qkv(p, x, cfg, positions)
    stack_k = _write_rows(stack_k, layer, positions, k)
    stack_v = _write_rows(stack_v, layer, positions, v)
    o = attend(q, stack_k[layer], stack_v[layer], window=window,
               softcap=cfg.attn_softcap, impl=impl, **_decode_mask(pos, T))
    return o.reshape(B, T, cfg.q_dim) @ p["wo"], (stack_k, stack_v)


def gqa_cross(p, x, cfg, enc_k, enc_v, enc_len=None, impl="xla"):
    """Cross-attention (no rope, no causal mask)."""
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
    o = attend(q, enc_k, enc_v, causal=False, kv_len=enc_len, impl=impl)
    return o.reshape(B, S, cfg.q_dim) @ p["wo"]


def cross_kv(p, enc_out, cfg):
    B, S, _ = enc_out.shape
    k = (enc_out @ p["wk"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = (enc_out @ p["wv"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    return k, v


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# ---------------------------------------------------------------------------


def init_mla(rng, cfg):
    dt = jnp.dtype(cfg.param_dtype)
    r = split(rng, 5)
    H, nope, rope_d, vd, lr = (cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                               cfg.v_head_dim, cfg.kv_lora_rank)
    return {
        "wq": dense_init(r[0], cfg.d_model, H * (nope + rope_d), dt),
        "w_dkv": dense_init(r[1], cfg.d_model, lr + rope_d, dt),
        "kv_norm": jnp.ones((lr,), jnp.float32),
        # up-projection stored (lr, H, nope+vd) for easy absorbed slicing
        "w_ukv": (jax.random.normal(r[2], (lr, H, nope + vd), jnp.float32)
                  * (lr ** -0.5)).astype(dt),
        "wo": dense_init(r[3], H * vd, cfg.d_model, dt),
    }


def _mla_scale(cfg):
    """Softmax scale; under YaRN times mscale(factor, mscale_all_dim)^2, as
    DeepSeek-V2 sets it."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.yarn_factor is not None and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _mla_compress(p, x, cfg, positions):
    """x -> (c_kv normed, k_rope roped). c_kv (B,S,lr), k_rope (B,S,rope_d)."""
    ckr = x @ p["w_dkv"]
    c_kv, k_rope = ckr[..., : cfg.kv_lora_rank], ckr[..., cfg.kv_lora_rank:]
    c_kv = rms_norm_head(c_kv, p["kv_norm"])
    k_rope = apply_rope(k_rope[..., None, :], positions, cfg.rope_theta,
                        rope_scaling(cfg))[..., 0, :]
    return c_kv, k_rope


def _mla_queries(p, x, cfg, positions):
    B, S, _ = x.shape
    H, nope, rope_d = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    q = (x @ p["wq"]).reshape(B, S, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, rope_scaling(cfg))
    return q_nope, q_rope


def mla_forward(p, x, cfg, impl="xla"):
    """Train/prefill: expand latents to per-head K/V (naive form).
    Returns (out, (c_kv, k_rope)) — the latent cache."""
    B, S, _ = x.shape
    H, nope, vd = cfg.num_heads, cfg.qk_nope_dim, cfg.v_head_dim
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    c_kv, k_rope = _mla_compress(p, x, cfg, positions)
    q_nope, q_rope = _mla_queries(p, x, cfg, positions)
    kv = jnp.einsum("bsr,rhd->bshd", c_kv, p["w_ukv"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, cfg.qk_rope_dim))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = attend(q, k, v, causal=True, scale=_mla_scale(cfg), impl=impl)
    return o.reshape(B, S, H * vd) @ p["wo"], (c_kv, k_rope)


def mla_decode(p, x, cfg, stack_ckv, stack_krope, layer, pos, impl="xla"):
    """Absorbed decode of MLA layer ``layer`` of a stage: scores & values
    live in the kv_lora latent space. The stacked latent caches (layers, B,
    Smax, ...) come back with this step's rows scattered in, as in
    ``gqa_decode``, whose ``pos`` and speculative-verify conventions hold
    here too (stale rejected-suffix latents are causal-masked until
    overwritten)."""
    B, T = x.shape[0], x.shape[1]
    H, nope, vd, lr = cfg.num_heads, cfg.qk_nope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    pos = jnp.asarray(pos)
    positions = _decode_positions(pos, B, T)
    c_kv, k_rope = _mla_compress(p, x, cfg, positions)
    stack_ckv = _write_rows(stack_ckv, layer, positions, c_kv)
    stack_krope = _write_rows(stack_krope, layer, positions, k_rope)
    cache_ckv, cache_krope = stack_ckv[layer], stack_krope[layer]
    q_nope, q_rope = _mla_queries(p, x, cfg, positions)
    w_uk = p["w_ukv"][..., :nope]  # (lr, H, nope)
    # absorb: q' = q_nope @ W_uk^T  -> latent-space queries (B,T,H,lr)
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(jnp.float32), w_uk.astype(jnp.float32)).astype(x.dtype)
    q_eff = jnp.concatenate([q_lat, q_rope], axis=-1)  # (B,T,H,lr+rope)
    k_eff = jnp.concatenate([cache_ckv, cache_krope], axis=-1)[:, :, None, :]  # 1 kv head
    v_eff = cache_ckv[:, :, None, :]  # (B,Smax,1,lr)
    o_lat = attend(q_eff, k_eff, v_eff, scale=_mla_scale(cfg), impl=impl,
                   **_decode_mask(pos, T))  # (B,T,H,lr)
    w_uv = p["w_ukv"][..., nope:]  # (lr, H, vd)
    o = jnp.einsum("bqhr,rhd->bqhd", o_lat.astype(jnp.float32), w_uv.astype(jnp.float32)).astype(x.dtype)
    return o.reshape(B, T, H * vd) @ p["wo"], (stack_ckv, stack_krope)
