"""Top-level model API: init, train forward/loss, prefill, decode.

Supports decoder-only (dense/moe/ssm/hybrid/vlm) and encoder-decoder (audio)
families through one interface:

  params                  = init_params(rng, cfg)
  logits, aux             = train_logits(params, cfg, batch, ctx)
  loss, metrics           = loss_fn(params, cfg, batch, ctx)
  cache                   = init_cache(cfg, B, max_len, enc_len=...)
  logits, cache           = prefill(params, cfg, inputs, cache, ctx, ...)
  logits, cache           = decode_step(params, cfg, token, cache, pos, ctx)
  logits, cache, counts   = decode_step(..., route_mask=mask)   # expert layers' routing

Inputs: token ids (B,S) int32 for ``input_mode=tokens``; for the audio
frontend stub, ``enc_inputs`` are precomputed frame embeddings (B,T,d_model).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import transformer as tfm
from repro.models.layers import apply_norm, embed_tokens, init_embed, init_norm, lm_logits
from repro.sharding.context import ExecContext


def init_params(rng, cfg):
    r = jax.random.split(rng, 4)
    params = {
        "embed": init_embed(r[0], cfg),
        "final_norm": init_norm(cfg),
        "stages": tfm.init_stack(r[1], cfg, decoder_cross=cfg.is_encoder_decoder),
    }
    if cfg.is_encoder_decoder:
        params["encoder"] = {
            "stages": tfm.init_stack(r[2], cfg, cross=True),
            "final_norm": init_norm(cfg),
        }
    return params


def encode(params, cfg, enc_inputs, ctx):
    """Audio/enc-dec: enc_inputs (B, T_frames, d_model) frame embeddings."""
    x = enc_inputs.astype(jnp.dtype(cfg.dtype))
    x, _, _, _ = tfm.apply_stack(params["encoder"]["stages"], cfg, x, ctx,
                                 mode="encode", cross=True)
    return apply_norm(params["encoder"]["final_norm"], x, cfg)


def _embed_inputs(params, cfg, inputs):
    if cfg.input_mode == "embeddings" and inputs.dtype != jnp.int32 and inputs.ndim == 3:
        return inputs.astype(jnp.dtype(cfg.dtype))
    return embed_tokens(params["embed"], inputs, cfg).astype(jnp.dtype(cfg.dtype))


def train_logits(params, cfg, batch, ctx=ExecContext()):
    """batch: {'tokens': (B,S)} (+ 'enc_inputs' for enc-dec)."""
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = encode(params, cfg, batch["enc_inputs"], ctx)
    x = _embed_inputs(params, cfg, batch["tokens"])
    x, aux, _, _ = tfm.apply_stack(params["stages"], cfg, x, ctx, mode="train", enc_out=enc_out)
    x = apply_norm(params["final_norm"], x, cfg)
    return lm_logits(params["embed"], x, cfg), aux


def loss_fn(params, cfg, batch, ctx=ExecContext()):
    logits, aux = train_logits(params, cfg, batch, ctx)
    labels = batch["labels"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (lse - gold).mean()
    loss = nll + cfg.router_aux_loss * aux
    return loss, {"nll": nll, "aux": aux}


def init_cache(cfg, batch, max_len, enc_len=0):
    dtype = jnp.dtype(cfg.dtype)
    return tfm.init_stack_cache(cfg, batch, max_len, dtype,
                                decoder_cross=cfg.is_encoder_decoder, enc_len=enc_len)


def write_cache_slot(pool_cache, one_cache, slot):
    """Copy a single-sequence cache (batch=1) into row ``slot`` of a slot-pool
    cache (batch=max_slots). Every cache leaf is (repeats, batch, ...) per
    stage, so one dynamic-slice update on axis 1 covers KV, SSM conv/state
    and cross-attention leaves alike. ``slot`` may be a traced scalar."""
    return jax.tree.map(
        lambda big, small: jax.lax.dynamic_update_slice_in_dim(
            big, small.astype(big.dtype), slot, axis=1),
        pool_cache, one_cache)


def write_cache_slots(pool_cache, group_cache, slots):
    """Scatter every row of a batched prefill cache (batch=G) into the slot
    rows named by ``slots`` (G,) int32 — the batched-admission counterpart of
    :func:`write_cache_slot`. Rows whose slot index is out of range (the
    pow2 batch-bucket padding rows) are dropped, so padding a prefill batch
    never clobbers a live slot."""
    return jax.tree.map(
        lambda big, small: big.at[:, slots].set(small.astype(big.dtype),
                                                mode="drop"),
        pool_cache, group_cache)


def prefill(params, cfg, inputs, cache, ctx=ExecContext(), enc_inputs=None,
            pad_mask=None):
    """Run the prompt through the model, writing mixer state into ``cache``.
    Returns (logits at every position, cache).

    ``pad_mask`` (B, S) bool — True at valid positions — makes bucketed
    (LEFT-padded) prompts safe for SSM mixers: masked positions neither
    update nor decay the scan state, so the state and last-position logits
    match an exact-length prefill. Supported for pure-SSM stacks only
    (attention layers raise: their rotary positions would shift)."""
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = encode(params, cfg, enc_inputs, ctx)
    x = _embed_inputs(params, cfg, inputs)
    x, _, cache, _ = tfm.apply_stack(params["stages"], cfg, x, ctx, mode="prefill",
                                     cache=cache, enc_out=enc_out,
                                     ssm_mask=pad_mask)
    x = apply_norm(params["final_norm"], x, cfg)
    return lm_logits(params["embed"], x, cfg), cache


def decode_step(params, cfg, token, cache, pos, ctx=ExecContext(), enc_len=None,
                route_mask=None):
    """token (B,1) int32; pos scalar int32 (position-synchronous batch) or
    (B,) int32 per-sequence write positions (ragged continuous batching).
    ``enc_len`` (enc-dec only): scalar or (B,) valid encoder-cache lengths —
    a slot pool preallocates the cross-attention region at ``max_enc_len``,
    so decode must mask each row's cross-attention to its own encoder
    length. ``None`` keeps the exact-length (unmasked) reference semantics.
    ``route_mask`` (B,) bool (single-token steps): also return the expert
    layers' per-expert assignment counts of the masked rows,
    (logits, cache, counts) with counts (expert layers, E) int32."""
    x = embed_tokens(params["embed"], token, cfg).astype(jnp.dtype(cfg.dtype))
    x, _, cache, counts = tfm.apply_stack(params["stages"], cfg, x, ctx, mode="decode",
                                          cache=cache, pos=pos, enc_len=enc_len,
                                          route_mask=route_mask)
    x = apply_norm(params["final_norm"], x, cfg)
    if route_mask is not None:
        return lm_logits(params["embed"], x, cfg), cache, counts
    return lm_logits(params["embed"], x, cfg), cache
