"""MoE dispatch correctness: gating, expert isolation."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, reduced
from repro.models.moe import _local_expert_partial, _route, init_moe, moe_apply
from repro.sharding.context import ExecContext


def _cfg():
    return reduced(get_config("deepseek-v2-lite-16b"))


def test_route_normalised_topk():
    """With ``norm_topk_prob`` (every MoE config but DeepSeek-V2's), the k
    gates are the top-k softmax probabilities scaled to sum to 1."""
    xt = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    rw = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    probs, gates, ids = _route(xt, rw, 3, renormalize=True)
    assert gates.shape == (32, 3) and ids.shape == (32, 3)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, atol=1e-5)
    assert (np.asarray(ids) < 8).all()
    top = np.take_along_axis(np.asarray(probs), np.asarray(ids), axis=1)
    np.testing.assert_allclose(np.asarray(gates), top / top.sum(-1, keepdims=True), rtol=1e-5)


def test_moe_matches_dense_expert_computation():
    """With k=1, each token's output must equal running its routed expert's
    FFN directly."""
    cfg = _cfg()
    import dataclasses
    cfg = dataclasses.replace(cfg, top_k=1, num_shared_experts=0)
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model)) * 0.5
    out, aux, _ = moe_apply(p, x, cfg, ExecContext())
    xt = x.reshape(-1, cfg.d_model)
    probs, gates, ids = _route(xt, p["router"], 1, cfg.norm_topk_prob)
    manual = np.zeros_like(np.asarray(xt))
    for t in range(xt.shape[0]):
        e = int(ids[t, 0])
        h = jax.nn.silu(xt[t] @ p["w_gate"][e]) * (xt[t] @ p["w_up"][e])
        manual[t] = np.asarray((h @ p["w_down"][e]) * gates[t, 0])
    np.testing.assert_allclose(np.asarray(out).reshape(-1, cfg.d_model), manual,
                               atol=2e-4, rtol=2e-4)


def test_moe_partial_partition_covers_all_experts():
    """Sum of per-shard partials (experts [0,E/2), [E/2,E)) == full output."""
    cfg = _cfg()
    E, k = cfg.num_experts, cfg.top_k
    p = init_moe(jax.random.PRNGKey(2), cfg)
    xt = jax.random.normal(jax.random.PRNGKey(3), (16, cfg.d_model)) * 0.5
    probs, gates, ids = _route(xt, p["router"], k, cfg.norm_topk_prob)
    full = _local_expert_partial(xt, gates, ids, p["w_gate"], p["w_up"], p["w_down"], 0)
    h = E // 2
    p1 = _local_expert_partial(xt, gates, ids, p["w_gate"][:h], p["w_up"][:h],
                               p["w_down"][:h], 0)
    p2 = _local_expert_partial(xt, gates, ids, p["w_gate"][h:], p["w_up"][h:],
                               p["w_down"][h:], h)
    np.testing.assert_allclose(np.asarray(p1 + p2), np.asarray(full), atol=1e-4)


def test_aux_loss_penalises_imbalance():
    from repro.models.moe import _aux_loss
    E, T = 4, 64
    probs_bal = jnp.full((T, E), 1 / E)
    ids_bal = jnp.tile(jnp.arange(E), T // E)[:, None]
    probs_imb = jnp.zeros((T, E)).at[:, 0].set(1.0)
    ids_imb = jnp.zeros((T, 1), jnp.int32)
    assert float(_aux_loss(probs_imb, ids_imb, E)) > float(_aux_loss(probs_bal, ids_bal, E))
