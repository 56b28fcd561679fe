"""Mixture-of-Experts with expert parallelism, without dropped tokens.

Distribution scheme (paper-faithful "partition by processor class"
analogue): experts are sharded across the ``model`` mesh axis; every model
shard routes the full local token set, computes ONLY its local experts'
contributions, and the contributions are combined with a single ``psum``
over the model axis (one all-reduce of activations).

Dispatch has no capacity: the T*k (token, expert) assignments are sorted by
expert and the three expert matmuls run as grouped matmuls
(``jax.lax.ragged_dot``) over the sorted rows, so every assignment is
computed, however unevenly the router spreads them, and the FLOPs follow the
assignments. No (T, E) one-hot and no per-expert buffer is materialised.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init, split


def init_moe(rng, cfg):
    dt = jnp.dtype(cfg.param_dtype)
    E, F, D = cfg.num_experts, cfg.moe_d_ff, cfg.d_model
    r = split(rng, 5)
    p = {
        "router": dense_init(r[0], D, E, jnp.float32),
        "w_gate": (jax.random.normal(r[1], (E, D, F), jnp.float32) * D ** -0.5).astype(dt),
        "w_up": (jax.random.normal(r[2], (E, D, F), jnp.float32) * D ** -0.5).astype(dt),
        "w_down": (jax.random.normal(r[3], (E, F, D), jnp.float32) * F ** -0.5).astype(dt),
    }
    if cfg.num_shared_experts:
        Fs = cfg.num_shared_experts * F
        rs = split(r[4], 3)
        p["shared"] = {
            "w_gate": dense_init(rs[0], D, Fs, dt),
            "w_up": dense_init(rs[1], D, Fs, dt),
            "w_down": dense_init(rs[2], Fs, D, dt),
        }
    return p


def _local_expert_partial(xt, gates, ids, wg, wu, wd, e0):
    """Contribution of experts [e0, e0+E_l) to all T local tokens.

    xt (T,D); gates/ids (T,k); wg/wu (E_l,D,F); wd (E_l,F,D).
    Returns out (T,D) float32 partial sum. Assignments to experts this shard
    does not hold sort after the local ones, past the last group, where the
    grouped matmuls compute nothing for them.
    """
    T, D = xt.shape
    k = ids.shape[1]
    E_l = wg.shape[0]
    le = ids.reshape(-1) - e0
    local = (le >= 0) & (le < E_l)
    le = jnp.where(local, le, E_l)
    order = jnp.argsort(le, stable=True)
    sizes = jnp.zeros((E_l,), jnp.int32).at[le].add(1, mode="drop")
    rows = xt[order // k]  # assignment order -> its token's row
    h = (jax.nn.silu(jax.lax.ragged_dot(rows, wg, sizes))
         * jax.lax.ragged_dot(rows, wu, sizes))
    y = jax.lax.ragged_dot(h, wd, sizes)  # (T*k, D), sorted by expert
    # back to assignment order; rows of other shards' experts are not computed
    y = y[jnp.zeros_like(order).at[order].set(jnp.arange(T * k, dtype=order.dtype))]
    w = jnp.where(local, gates.reshape(-1), 0.0)
    y = jnp.where(local[:, None], y.astype(jnp.float32), 0.0) * w[:, None]
    return y.reshape(T, k, D).sum(axis=1)


def _route(xt, router_w, k, renormalize=True):
    """Softmax router in float32 (its matmul at full precision), greedy top-k;
    ``renormalize`` scales the k gates to sum to 1."""
    logits = jnp.dot(xt.astype(jnp.float32), router_w,
                     precision=jax.lax.Precision.HIGHEST)  # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)
    if renormalize:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return probs, gates, ids


def _route_counts(ids, mask, E):
    """(E,) int32: assignments per expert of the tokens where ``mask``."""
    return jnp.zeros((E,), jnp.int32).at[ids].add(mask[:, None].astype(jnp.int32))


def _aux_loss(probs, ids, E):
    """Switch-style load-balance loss: E * sum_e f_e * P_e."""
    P_e = probs.mean(axis=0)  # (E,)
    counts = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    f_e = counts / jnp.maximum(counts.sum(), 1.0)
    return E * jnp.sum(f_e * P_e)


def _moe_2d(p, x, cfg, ctx):
    """Weight-stationary 2D expert parallelism (§Perf beyond-paper variant,
    for decode: tokens are few). Experts sharded on 'model', every expert's
    FFN width F sharded on 'data'; tokens REPLICATED. Each (d, m) shard
    computes its local experts' partial-F contribution and a single psum
    over BOTH axes combines. No per-step FSDP weight gather — the 2 TB of
    kimi-k2 expert weights never move."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.top_k
    M = ctx.model_parallel
    E_l = E // M
    ax = ctx.model_axis
    daxes = tuple(ctx.batch_axes)

    def fn(x_l, rw, wg, wu, wd):
        xt = x_l.reshape(B * S, D)
        probs, gates, ids = _route(xt, rw, k, cfg.norm_topk_prob)
        m = jax.lax.axis_index(ax)
        out = _local_expert_partial(xt, gates, ids, wg, wu, wd, m * E_l)
        out = jax.lax.psum(out, (ax,) + daxes)
        aux = jax.lax.pmean(_aux_loss(probs, ids, E), ax)
        return out.reshape(B, S, D).astype(x_l.dtype), aux

    return jax.shard_map(
        fn, mesh=ctx.mesh,
        in_specs=(P(None, None, None), P(),
                  P(ax, None, daxes), P(ax, None, daxes), P(ax, daxes, None)),
        out_specs=(P(None, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def moe_apply(p, x, cfg, ctx, token_mask=None):
    """x (B,S,D) -> (out (B,S,D), aux_loss scalar f32, counts). With
    ``token_mask`` (B*S,) bool, ``counts`` is (E,) int32: the assignments the
    router made to each expert from the masked tokens (a decode step's active
    slots); else None."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.top_k
    M = ctx.model_parallel
    E_l = E // M if M > 1 and E % M == 0 else E

    if (M > 1 and E % M == 0 and ctx.plan.get("moe_2d")
            and cfg.moe_d_ff % max(1, ctx.batch_parallel) == 0):
        out, aux = _moe_2d(p, x, cfg, ctx)
    elif M > 1 and E % M == 0:
        mesh = ctx.mesh
        bspec = P(ctx.batch_axes if B % max(1, ctx.batch_parallel) == 0 and ctx.batch_parallel > 1 else None,
                  None, None)
        ax = ctx.model_axis

        def fn(x_l, rw, wg, wu, wd):
            Bl, Sl, _ = x_l.shape
            xt = x_l.reshape(Bl * Sl, D)
            probs, gates, ids = _route(xt, rw, k, cfg.norm_topk_prob)
            m = jax.lax.axis_index(ax)
            out = _local_expert_partial(xt, gates, ids, wg, wu, wd, m * E_l)
            out = jax.lax.psum(out, ax)
            aux = jax.lax.pmean(_aux_loss(probs, ids, E), ax)
            return out.reshape(Bl, Sl, D).astype(x_l.dtype), aux

        out, aux = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(bspec, P(), P(ax, None, None), P(ax, None, None), P(ax, None, None)),
            out_specs=(bspec, P()),
            check_vma=False,
        )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    else:
        xt = x.reshape(B * S, D)
        probs, gates, ids = _route(xt, p["router"], k, cfg.norm_topk_prob)
        out = _local_expert_partial(xt, gates, ids, p["w_gate"], p["w_up"], p["w_down"], 0)
        aux = _aux_loss(probs, ids, E)
        out = out.reshape(B, S, D).astype(x.dtype)

    counts = None
    if token_mask is not None:
        if M > 1 and E % M == 0:  # the sharded paths route inside their shard_map
            _, _, ids = _route(x.reshape(B * S, D), p["router"], k, cfg.norm_topk_prob)
        counts = _route_counts(ids, token_mask, E)

    if cfg.num_shared_experts:
        sp = p["shared"]
        h = jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])
        out = out + h @ sp["w_down"]
    return out, aux, counts
