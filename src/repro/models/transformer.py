"""Decoder stack: periodic-stage scan over heterogeneous layers.

Layers are grouped into *stages*: a (possibly unrolled) repeating pattern of
period layers (e.g. gemma2's (local, global) pair, jamba's 8-layer
mamba/attn block) scanned ``repeats`` times with stacked params. This keeps
the HLO size O(period), independent of depth — essential for CPU-hosted
compiles of 61-layer trillion-param configs.

Modes:
  train   — full causal forward, no cache, remat per stage step.
  prefill — full causal forward writing mixer states / KV into a
            preallocated cache at positions [0, S).
  decode  — new tokens at ``pos`` against the cache: each stage's stacked
            cache is carried through the layer loop and gets only the new
            rows written into it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as att, moe as moe_mod, ssm
from repro.models.layers import apply_mlp, apply_norm, init_mlp, init_norm, split

ATTN_KINDS = ("attn", "local", "global")


# ---------------------------------------------------------------------------
# stage decomposition
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Stage:
    repeats: int
    pattern: Tuple[Tuple[str, str], ...]  # ((mixer_kind, mlp_kind), ...)


def compute_stages(cfg, cross=False) -> List[Stage]:
    seq = list(zip(cfg.layer_kinds(), cfg.mlp_kinds()))
    if cross:  # encoder stacks: non-causal attn + dense mlp
        seq = [("attn", "dense")] * cfg.num_encoder_layers
    for prefix in range(0, len(seq)):
        rest = seq[prefix:]
        if not rest:
            break
        for p in range(1, len(rest) + 1):
            if len(rest) % p:
                continue
            if all(rest[i] == rest[i % p] for i in range(len(rest))):
                stages = []
                if prefix:
                    stages.append(Stage(1, tuple(seq[:prefix])))
                stages.append(Stage(len(rest) // p, tuple(rest[:p])))
                return stages
    return [Stage(1, tuple(seq))]


# ---------------------------------------------------------------------------
# single layer
# ---------------------------------------------------------------------------


def init_layer(rng, cfg, kind, mlp_kind, decoder_cross=False):
    r = split(rng, 6)
    p = {"pre_norm": init_norm(cfg)}
    if kind in ATTN_KINDS:
        p["attn"] = att.init_mla(r[0], cfg) if cfg.use_mla else att.init_gqa(r[0], cfg)
    elif kind == "ssd":
        p["mixer"] = ssm.init_mamba2(r[0], cfg)
    elif kind == "mamba":
        p["mixer"] = ssm.init_mamba1(r[0], cfg)
    if cfg.post_block_norm:
        p["post_norm"] = init_norm(cfg)
    if decoder_cross:
        p["cross_norm"] = init_norm(cfg)
        p["cross"] = att.init_gqa(r[1], cfg)
    if mlp_kind != "none":
        p["mlp_norm"] = init_norm(cfg)
        if cfg.post_block_norm:
            p["mlp_post_norm"] = init_norm(cfg)
        p["mlp"] = moe_mod.init_moe(r[2], cfg) if mlp_kind == "moe" else init_mlp(r[2], cfg)
    return p


def init_layer_cache(cfg, kind, batch, max_len, dtype, decoder_cross=False, enc_len=0):
    c = {}
    if kind in ATTN_KINDS:
        if cfg.use_mla:
            c["c_kv"] = jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype)
            c["k_rope"] = jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype)
        else:
            c["k"] = jnp.zeros((batch, max_len, cfg.num_kv_heads, cfg.head_dim), dtype)
            c["v"] = jnp.zeros((batch, max_len, cfg.num_kv_heads, cfg.head_dim), dtype)
    elif kind == "ssd":
        conv_dim = cfg.d_inner + 2 * cfg.ssm_d_state
        c["conv"] = jnp.zeros((batch, cfg.ssm_d_conv - 1, conv_dim), dtype)
        c["ssm"] = jnp.zeros((batch, cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_d_state), jnp.float32)
    elif kind == "mamba":
        c["conv"] = jnp.zeros((batch, cfg.ssm_d_conv - 1, cfg.d_inner), dtype)
        c["ssm"] = jnp.zeros((batch, cfg.d_inner, cfg.ssm_d_state), jnp.float32)
    if decoder_cross:
        c["xk"] = jnp.zeros((batch, enc_len, cfg.num_kv_heads, cfg.head_dim), dtype)
        c["xv"] = jnp.zeros((batch, enc_len, cfg.num_kv_heads, cfg.head_dim), dtype)
    return c


def _window(cfg, kind):
    return cfg.sliding_window if kind == "local" else None


def apply_layer(lp, x, cfg, kind, mlp_kind, ctx, mode, cache, pos, layer=None,
                enc_out=None, causal=True, enc_len=None, ssm_mask=None,
                route_mask=None):
    """Returns (x, aux, new_cache, counts). In decode ``cache`` holds the
    stage's stacked leaves (layers, B, ...) and ``layer`` is this layer's
    index among them: the step writes only its new rows (or, for an SSM, its
    state) into the stack, in place, and returns the stack; in prefill
    ``cache`` is this layer's own slice. ``ssm_mask`` (B, S) marks valid
    prompt positions for pad-bucketed SSM prefill; attention mixers reject it
    (their positions are absolute, so left padding would shift rope).
    ``route_mask`` (B*S,) bool asks an expert layer for its per-expert
    assignment counts of the masked tokens (``counts``, (E,) int32); other
    layers, or no mask, give None."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = dict(cache) if cache is not None else None
    h = apply_norm(lp["pre_norm"], x, cfg)

    # ---- mixer ----
    if kind in ATTN_KINDS:
        if ssm_mask is not None:
            raise ValueError(
                "pad_mask/ssm_mask is only supported for pure-SSM stacks; "
                f"layer kind {kind!r} attends over absolute positions")
        if mode == "decode":
            if cfg.use_mla:
                mix, (ck, kr) = att.mla_decode(lp["attn"], h, cfg, cache["c_kv"],
                                               cache["k_rope"], layer, pos,
                                               impl=ctx.attn_impl)
                new_cache.update(c_kv=ck, k_rope=kr)
            else:
                mix, (ck, cv) = att.gqa_decode(lp["attn"], h, cfg, cache["k"], cache["v"],
                                               layer, pos, window=_window(cfg, kind),
                                               impl=ctx.attn_impl)
                new_cache.update(k=ck, v=cv)
        else:
            if cfg.use_mla:
                mix, (c_kv, k_rope) = att.mla_forward(lp["attn"], h, cfg, impl=ctx.attn_impl)
                if mode == "prefill":
                    new_cache["c_kv"] = jax.lax.dynamic_update_slice_in_dim(
                        cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), 0, axis=1)
                    new_cache["k_rope"] = jax.lax.dynamic_update_slice_in_dim(
                        cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), 0, axis=1)
            else:
                if causal:
                    mix, (k, v) = att.gqa_forward(lp["attn"], h, cfg,
                                                  window=_window(cfg, kind),
                                                  impl=ctx.attn_impl, ctx=ctx)
                else:  # encoder self-attention
                    B, S, _ = h.shape
                    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
                    q, k, v = att._project_qkv(lp["attn"], h, cfg, positions)
                    mix = att.attend(q, k, v, causal=False, impl=ctx.attn_impl)
                    mix = mix.reshape(B, S, cfg.q_dim) @ lp["attn"]["wo"]
                if mode == "prefill":
                    new_cache["k"] = jax.lax.dynamic_update_slice_in_dim(
                        cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
                    new_cache["v"] = jax.lax.dynamic_update_slice_in_dim(
                        cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
    elif kind in ("ssd", "mamba"):
        fwd = ssm.mamba2_forward if kind == "ssd" else ssm.mamba1_forward
        step = ssm.mamba2_decode if kind == "ssd" else ssm.mamba1_decode
        if mode == "decode":
            if h.shape[1] != 1:
                # recurrent state advances one token at a time; there is no
                # KV cache to roll a rejected suffix back from, so the
                # speculative multi-position verify cannot run through SSM
                # mixers (repro.serving.speculative gates drafts to
                # pure-attention decoder stacks for the same reason)
                raise ValueError(
                    f"SSM decode is single-token; got {h.shape[1]} positions "
                    f"for layer kind {kind!r}")
            mix, (conv_s, ssm_s) = step(lp["mixer"], h, cfg, cache["conv"][layer],
                                        cache["ssm"][layer])
            new_cache.update(conv=cache["conv"].at[layer].set(conv_s),
                             ssm=cache["ssm"].at[layer].set(ssm_s))
        else:
            mix, (conv_s, ssm_s) = fwd(lp["mixer"], h, cfg, mask=ssm_mask)
            if mode == "prefill":
                new_cache.update(conv=conv_s.astype(cache["conv"].dtype), ssm=ssm_s)
    else:
        raise ValueError(kind)

    if cfg.post_block_norm:
        mix = apply_norm(lp["post_norm"], mix, cfg)
    x = x + mix

    # ---- cross attention (enc-dec decoder layers) ----
    if "cross" in lp:
        hc = apply_norm(lp["cross_norm"], x, cfg)
        if mode == "decode":
            # enc_len masks rows to their own encoder length when the cache
            # region is preallocated wider (slot pools); None = exact length
            xo = att.gqa_cross(lp["cross"], hc, cfg, cache["xk"][layer],
                               cache["xv"][layer], enc_len=enc_len, impl=ctx.attn_impl)
        else:
            ek, ev = att.cross_kv(lp["cross"], enc_out, cfg)
            xo = att.gqa_cross(lp["cross"], hc, cfg, ek, ev, impl=ctx.attn_impl)
            if mode == "prefill":
                # slice-write so a cache preallocated at max_enc_len keeps its
                # shape (a slot pool scatters whole rows); exact-length caches
                # (the bucketed reference) are fully overwritten as before
                new_cache["xk"] = jax.lax.dynamic_update_slice_in_dim(
                    cache["xk"], ek.astype(cache["xk"].dtype), 0, axis=1)
                new_cache["xv"] = jax.lax.dynamic_update_slice_in_dim(
                    cache["xv"], ev.astype(cache["xv"].dtype), 0, axis=1)
        x = x + xo

    # ---- mlp ----
    counts = None
    if mlp_kind != "none":
        h2 = apply_norm(lp["mlp_norm"], x, cfg)
        if mlp_kind == "moe":
            y, aux, counts = moe_mod.moe_apply(lp["mlp"], h2, cfg, ctx, token_mask=route_mask)
        else:
            y = apply_mlp(lp["mlp"], h2, cfg)
        if cfg.post_block_norm:
            y = apply_norm(lp["mlp_post_norm"], y, cfg)
        x = x + y
    return x, aux, new_cache, counts


# ---------------------------------------------------------------------------
# stack init / apply
# ---------------------------------------------------------------------------


def init_stack(rng, cfg, cross=False, decoder_cross=False):
    """cross=True -> encoder stack. decoder_cross=True -> decoder w/ x-attn."""
    stages = compute_stages(cfg, cross=cross)
    params = []
    for st in stages:
        keys = jax.random.split(rng, st.repeats)
        rng = jax.random.fold_in(rng, 7)

        def one(k):
            ks = jax.random.split(k, len(st.pattern))
            return {f"l{j}": init_layer(ks[j], cfg, kind, mlp,
                                        decoder_cross=(decoder_cross and kind in ATTN_KINDS))
                    for j, (kind, mlp) in enumerate(st.pattern)}

        params.append(jax.vmap(one)(keys))
    return params


def init_stack_cache(cfg, batch, max_len, dtype, decoder_cross=False, enc_len=0):
    stages = compute_stages(cfg)
    caches = []
    for st in stages:
        one = {f"l{j}": init_layer_cache(cfg, kind, batch, max_len, dtype,
                                         decoder_cross=decoder_cross and kind in ATTN_KINDS,
                                         enc_len=enc_len)
               for j, (kind, mlp) in enumerate(st.pattern)}
        caches.append(jax.tree.map(lambda a: jnp.broadcast_to(a, (st.repeats,) + a.shape).copy(), one))
    return caches


def apply_stack(stage_params, cfg, x, ctx, mode, cache=None, pos=0,
                enc_out=None, cross=False, enc_len=None, ssm_mask=None,
                route_mask=None):
    """Returns (x, aux, caches, counts): ``counts`` is (expert layers, E)
    int32, each expert layer's assignments from the tokens ``route_mask``
    marks, where a mask is given and the stack has expert layers; else None.

    Prefill scans each stage's cache as ``xs`` and collects the written cache
    as ``ys``. Decode carries the stage's stacked cache through the layer
    loop instead, with the layer index as ``xs``: each layer scatters its new
    rows into the carried stack (the buffer a jitted caller donates), so a
    step writes only those rows and never copies the stack."""
    stages = compute_stages(cfg, cross=cross)
    aux_total = jnp.zeros((), jnp.float32)
    new_caches, counts = [], []
    carried = mode == "decode" and cache is not None
    for si, st in enumerate(stages):
        sp = stage_params[si]
        sc = cache[si] if cache is not None else None

        def body(carry, xs, _pattern=st.pattern):
            xc, aux, stack = carry
            lp, cin, layer = xs
            if carried:
                cin = stack
            cout, cnt = {}, []
            for j, (kind, mlp) in enumerate(_pattern):
                xc, a, cj, nj = apply_layer(
                    lp[f"l{j}"], xc, cfg, kind, mlp, ctx, mode,
                    cin[f"l{j}"] if cin is not None else None, pos, layer,
                    enc_out=enc_out, causal=not cross, enc_len=enc_len,
                    ssm_mask=ssm_mask, route_mask=route_mask)
                aux = aux + a
                cout[f"l{j}"] = cj
                if nj is not None:
                    cnt.append(nj)
            if carried:
                return (xc, aux, cout), (None, cnt)
            return (xc, aux, None), (cout if cin is not None else None, cnt)

        if mode == "train":
            # remat policy is a partitioner/plan knob (§Perf): "full" remats
            # everything (min memory, max recompute); "dots" saves matmul
            # outputs so the backward pass doesn't recompute attention twice
            # (the inner chunked-attention scan is checkpointed as well, so
            # full outer remat triples score traffic).
            policy = ctx.plan.get("remat_policy", "full") if hasattr(ctx, "plan") else "full"
            if policy == "dots":
                fn = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            elif policy == "none":
                fn = body
            else:
                fn = jax.checkpoint(body)
        else:
            fn = body
        pipe = ctx.plan.get("pipeline") if hasattr(ctx, "plan") else None
        if (pipe and mode == "train" and sc is None
                and int(pipe.get("stages", 0)) > 1
                and st.repeats % int(pipe["stages"]) == 0
                and x.shape[0] % int(pipe.get("microbatches", 1)) == 0):
            # circular pipeline parallelism over this stage's stacked layers
            # (repro.sharding.pipeline, maxtext rotation idiom); cacheless
            # train mode only — the scan below stays the reference path
            from repro.sharding.pipeline import circular_pipeline

            def stage_fn(group, xmb):
                (y, a, _), _ = jax.lax.scan(
                    fn, (xmb, jnp.zeros((), jnp.float32), None), (group, None, None))
                return y, a

            x, aux = circular_pipeline(stage_fn, sp, x, int(pipe["stages"]),
                                       int(pipe.get("microbatches", 1)))
            aux_total = aux_total + aux
            new_caches.append(None)
            continue
        if carried:
            carry, xs = (x, aux_total, sc), (sp, None, jnp.arange(st.repeats))
        else:
            carry, xs = (x, aux_total, None), (sp, sc, None)
        (x, aux_total, c_carried), (c_new, cnt) = jax.lax.scan(fn, carry, xs)
        new_caches.append(c_carried if carried else c_new)
        counts += cnt  # each (repeats, E)
    return (x, aux_total, (new_caches if cache is not None else None),
            jnp.concatenate(counts) if counts else None)
