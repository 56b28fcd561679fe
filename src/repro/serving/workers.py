"""Per-model serving worker: jitted prefill/decode against a preallocated
KV/state cache, batch generation (bucketed reference path) and the
slot-pool primitives the continuous engine drives.

Sharded serving: when the :class:`~repro.sharding.context.ExecContext`
carries a mesh, the worker builds NamedShardings for its params via the
``repro.sharding.partition_specs`` rule table at construction (recording
replication decisions on ``shard_report``), places every cache it
allocates under the activation rules, and the jitted prefill/decode run
under GSPMD with the donated sharded caches. ``mesh=None`` (the default)
takes the identical single-device code path — the bit-exactness reference,
token-identical to a 1-device mesh (``tests/test_sharded_serving.py``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.telemetry import span
from repro.models import model as model_lib
from repro.serving.sampling import _sample_rows
from repro.sharding.context import ExecContext


class ModelWorker:
    def __init__(self, name: str, cfg, params, max_len: int = 512,
                 ctx: ExecContext = ExecContext(),
                 max_enc_len: Optional[int] = None, ledger=None):
        self.name = name
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.ctx = ctx
        # an expert model's decode steps count their routing into the
        # engine's ledger, under "<name>.moe_decode_*"
        self.ledger = ledger
        self._count_routes = bool(cfg.num_experts) and ledger is not None
        # enc-dec slot pools preallocate the cross-attention cache region at
        # this length; decoder-only models carry no encoder region
        self.max_enc_len = (max_enc_len if max_enc_len is not None
                            else (max_len if cfg.is_encoder_decoder else 0))
        # mesh-aware placement: shard params once per worker, caches per
        # (batch, enc_len) shape as they are allocated; shard_report tallies
        # the rule table's sharded-vs-replicated decisions for telemetry
        self.mesh = ctx.mesh
        self.shard_report = None
        self._cache_shardings: dict = {}
        if self.mesh is not None:
            from repro.sharding import partition_specs as ps
            self._ps = ps
            self._model_axis = ctx.model_axis or "model"
            self._batch_axes = tuple(ctx.batch_axes) or ("data",)
            self.shard_report = ps.ShardingReport()
            shardings = ps.params_shardings(
                jax.eval_shape(lambda p: p, params), cfg, self.mesh,
                model_axis=self._model_axis, batch_axes=self._batch_axes,
                report=self.shard_report)
            self.params = jax.device_put(params, shardings)
            self.param_shardings = shardings
        else:
            self.param_shardings = None
        self._prefill = jax.jit(self._prefill_impl)
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1,))
        self._verify = jax.jit(self._verify_impl, donate_argnums=(1,))
        self._write = jax.jit(model_lib.write_cache_slot, donate_argnums=(0,))
        self._write_many = jax.jit(model_lib.write_cache_slots,
                                   donate_argnums=(0,))

    def _new_cache(self, batch: int, enc_len: int):
        """Allocate a cache and, under a mesh, place it by the activation
        rules (batch -> data axes, kv-heads -> model with the KV-sequence
        fallback). The mesh=None path returns the allocation untouched."""
        cache = model_lib.init_cache(self.cfg, batch, self.max_len,
                                     enc_len=enc_len)
        if self.mesh is None:
            return cache
        key = (batch, enc_len)
        sh = self._cache_shardings.get(key)
        if sh is None:
            sds = jax.eval_shape(functools.partial(
                model_lib.init_cache, self.cfg, batch, self.max_len,
                enc_len=enc_len))
            sh = self._cache_shardings[key] = self._ps.cache_shardings(
                sds, self.cfg, self.mesh, batch,
                model_axis=self._model_axis, batch_axes=self._batch_axes,
                report=self.shard_report)
        return jax.device_put(cache, sh)

    def _prefill_impl(self, params, cache, tokens, enc_inputs=None,
                      pad_mask=None):
        logits, cache = model_lib.prefill(params, self.cfg, tokens, cache, self.ctx,
                                          enc_inputs=enc_inputs,
                                          pad_mask=pad_mask)
        return logits[:, -1], cache

    def _decode_impl(self, params, cache, token, pos, enc_len=None, active=None):
        if active is None:
            logits, cache = model_lib.decode_step(params, self.cfg, token, cache,
                                                  pos, self.ctx, enc_len=enc_len)
            return logits[:, -1], cache
        logits, cache, counts = model_lib.decode_step(
            params, self.cfg, token, cache, pos, self.ctx, enc_len=enc_len,
            route_mask=active)
        return logits[:, -1], cache, counts

    def _verify_impl(self, params, cache, tokens, pos):
        # multi-position decode (speculative verify / draft catch-up): keep
        # the full (B, T, V) logits — every position's distribution feeds the
        # acceptance rule, not just the last one
        return model_lib.decode_step(params, self.cfg, tokens, cache,
                                     pos, self.ctx)

    def generate(self, prompts: np.ndarray, max_new: int,
                 enc_inputs=None, temperature: float = 0.0, seed: int = 0,
                 row_keys=None, pad_mask=None):
        """prompts (B, S) equal-length. Greedy (T=0) or sampled decode.

        ``row_keys`` (B, 2) uint32: per-request sampling streams — token i of
        row b draws from ``fold_in(row_keys[b], i)``, matching the continuous
        engine's seed⊕model⊕uid⊕token-index streams so both serving modes
        emit identical sampled tokens. ``None`` keeps the legacy split-chain
        RNG (shared across rows) seeded by ``seed``.

        ``pad_mask`` (B, S) bool: valid-token mask for LEFT-padded prompts
        bucketed to a shared length — supported for pure-SSM stacks only
        (the scan passes masked positions through untouched; see
        ``docs/serving.md`` §Pad-safe SSM prompts)."""
        B, S = prompts.shape
        if pad_mask is not None and self.cfg.is_encoder_decoder:
            # enc-dec decoders carry attention layers, which would silently
            # mis-serve left-padded prompts — refuse like the stack does
            raise ValueError("pad_mask is only supported for pure-SSM "
                             "stacks, not encoder-decoder models")
        enc_len = enc_inputs.shape[1] if enc_inputs is not None else 0
        cache = self._new_cache(B, enc_len)
        args = (self.params, cache, jnp.asarray(prompts))
        if self.cfg.is_encoder_decoder:
            logits, cache = self._prefill(*args, jnp.asarray(enc_inputs))
        elif pad_mask is not None:
            logits, cache = self._prefill(*args, pad_mask=jnp.asarray(pad_mask))
        else:
            logits, cache = self._prefill(*args)
        out = np.zeros((B, max_new), np.int32)
        rng = jax.random.PRNGKey(seed)
        tok = self._pick(logits, temperature, rng, row_keys, 0)
        for i in range(max_new):
            out[:, i] = np.asarray(tok)[:, 0]
            if i == max_new - 1:
                break
            logits, cache = self._decode(self.params, cache, tok, jnp.int32(S + i))
            rng, k = jax.random.split(rng)
            tok = self._pick(logits, temperature, k, row_keys, i + 1)
        return out

    @staticmethod
    def _pick(logits, temperature, rng, row_keys=None, token_idx=0):
        if temperature <= 0.0:
            return jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        if row_keys is not None:
            idx = jnp.full((row_keys.shape[0],), token_idx, jnp.uint32)
            return _sample_rows(row_keys, idx,
                                logits / temperature)[:, None].astype(jnp.int32)
        return jax.random.categorical(rng, logits / temperature)[:, None].astype(jnp.int32)

    # ---- continuous-batching primitives (slot-pool cache) ----

    def init_pool(self, max_slots: int):
        """Preallocated KV/state cache with one row per request slot (plus a
        ``max_enc_len`` encoder cross-attention region for enc-dec models),
        placed under the activation sharding rules when the worker carries
        a mesh."""
        return self._new_cache(max_slots, self.max_enc_len)

    def prefill_one(self, prompt: np.ndarray, enc_inputs=None):
        """Prefill a single request at its exact length. Returns
        (last-position logits (1,V), batch-1 cache to scatter into a slot)."""
        return self.prefill_batch(
            prompt[None], None if enc_inputs is None else enc_inputs[None])

    def prefill_batch(self, prompts: np.ndarray, enc_inputs=None,
                      pad_mask=None):
        """Batched admission prefill: ``prompts`` (G, S) equal-length (the
        caller pads G to a pow2 bucket). Returns (last-position logits (G,V),
        batch-G cache whose rows scatter into slots via ``write_slots``).
        Every op is row-independent, so each row is bit-identical to a
        ``prefill_one`` of the same prompt.

        ``pad_mask`` (G, S) bool marks the valid tokens of LEFT-padded
        prompts bucketed to a shared length — pure-SSM stacks only (masked
        positions neither write into nor decay the scan state, so the
        resulting caches match exact-length prefill; see ``generate``)."""
        G = prompts.shape[0]
        if pad_mask is not None and self.cfg.is_encoder_decoder:
            raise ValueError("pad_mask is only supported for pure-SSM "
                             "stacks, not encoder-decoder models")
        cache = self._new_cache(G, self.max_enc_len)
        args = (self.params, cache, jnp.asarray(prompts))
        if self.cfg.is_encoder_decoder:
            return self._prefill(*args, jnp.asarray(enc_inputs))
        if pad_mask is not None:
            return self._prefill(*args, pad_mask=jnp.asarray(pad_mask))
        return self._prefill(*args)

    def write_slot(self, pool_cache, one_cache, slot: int):
        return self._write(pool_cache, one_cache, slot)

    def write_slots(self, pool_cache, group_cache, slots: np.ndarray):
        """Scatter a batched prefill cache into the rows named by ``slots``;
        out-of-range entries (pow2 batch padding) are dropped."""
        return self._write_many(pool_cache, group_cache,
                                jnp.asarray(slots, dtype=jnp.int32))

    def decode_pool(self, pool_cache, tokens: np.ndarray, pos: np.ndarray,
                    enc_len=None, active=None):
        """One ragged decode step over the whole slot pool. ``tokens``
        (max_slots,1) int32, ``pos`` (max_slots,) int32 per-slot write
        positions, ``enc_len`` (max_slots,) per-slot encoder lengths for
        enc-dec models (masks each row's cross-attention to its own encoder
        region). Reuses the jitted decode body — a (B,) position vector
        traces the ragged path in the model. Returns (greedy next tokens
        (max_slots,) np.int32, logits (max_slots, V) for per-slot sampling,
        cache).

        An expert model counts, per step, what its router did for the slots
        in ``active`` (every slot if None): the step's expert layers
        (``moe_decode_layer_steps``), the experts that got a token in each
        (``moe_decode_experts_touched``) and the busiest one's tokens
        (``moe_decode_load_max``), summed over the layers. The counts come
        back with the tokens, in the same host sync."""
        args = (self.params, pool_cache, jnp.asarray(tokens),
                jnp.asarray(pos, dtype=jnp.int32),
                None if enc_len is None else jnp.asarray(enc_len, dtype=jnp.int32))
        if not self._count_routes:
            logits, pool_cache = self._decode(*args)
            with span("repro.decode.wait"):  # the host waits for the step here
                next_tok = np.asarray(jnp.argmax(logits, -1).astype(jnp.int32))
            return next_tok, logits, pool_cache
        mask = np.zeros(len(pos), bool)
        mask[list(active) if active is not None else slice(None)] = True
        logits, pool_cache, counts = self._decode(*args, jnp.asarray(mask))
        with span("repro.decode.wait"):
            next_tok, counts = jax.device_get(
                (jnp.argmax(logits, -1).astype(jnp.int32), counts))
        self.ledger.count(f"{self.name}.moe_decode_layer_steps", counts.shape[0])
        self.ledger.count(f"{self.name}.moe_decode_experts_touched", int((counts > 0).sum()))
        self.ledger.count(f"{self.name}.moe_decode_load_max", int(counts.max(axis=1).sum()))
        return next_tok, logits, pool_cache

    def decode_verify(self, pool_cache, tokens: np.ndarray, pos: np.ndarray):
        """Multi-position ragged decode over the slot pool — the speculative
        verify / draft catch-up primitive. ``tokens`` (max_slots, T) int32
        feed positions pos..pos+T-1 per row against the cache (out-of-range
        writes drop; garbage rows beyond a slot's frontier are causal-masked,
        see ``gqa_decode``). Returns (greedy tokens (max_slots, T) np.int32,
        logits (max_slots, T, V), cache). T==1 is NOT routed here — the
        single-token path keeps its own jitted shape (``decode_pool``)."""
        logits, pool_cache = self._verify(
            self.params, pool_cache, jnp.asarray(tokens),
            jnp.asarray(pos, dtype=jnp.int32))
        return (np.asarray(jnp.argmax(logits, -1).astype(jnp.int32)),
                logits, pool_cache)
