"""DeepSeek-V2's layer as served: expert dispatch without drops, routing as
published (no renormalised gates), YaRN rope and softmax scale in latent
attention, and the serving path against the plain float32 reference
(``bench/reference/mla_moe.py``) on seeded random weights."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import mla_moe as reference
from bench.weights import mla_moe as weights
from repro.configs.base import get_config
from repro.models.attention import _mla_scale
from repro.models.layers import apply_rope, rope_freqs, rope_scaling, yarn_ramp
from repro.models.moe import _local_expert_partial, _route, init_moe, moe_apply
from repro.serving.engine import Request, ServingEngine
from repro.sharding.context import ExecContext

DEEPSEEK = get_config("deepseek-v2-lite-16b")
# a CPU-sized DeepSeek-V2 stack: a dense first layer, then two expert layers
# of 8 routed experts (top-2) and 2 shared, YaRN on, gates not renormalised
TINY = {"num_layers": 3, "d_model": 64, "num_heads": 4, "num_kv_heads": 4, "head_dim": 24,
        "d_ff": 128, "vocab_size": 500, "use_mla": True, "kv_lora_rank": 32,
        "qk_nope_dim": 16, "qk_rope_dim": 8, "v_head_dim": 16,
        "num_experts": 8, "num_shared_experts": 2, "top_k": 2, "moe_d_ff": 32,
        "moe_layer_period": 1, "first_dense_layers": 1, "norm_topk_prob": False,
        "rope_theta": 10000.0, "yarn_factor": 40.0, "yarn_original_positions": 4096,
        "yarn_beta_fast": 32.0, "yarn_beta_slow": 1.0, "yarn_mscale": 0.707,
        "yarn_mscale_all_dim": 0.707, "tie_embeddings": False, "norm": "rmsnorm",
        "dtype": "float32", "param_dtype": "float32"}
CFG = dataclasses.replace(DEEPSEEK, **TINY)


def _per_token(xt, gates, ids, wg, wu, wd):
    """Each token through each of its experts, one at a time."""
    out = np.zeros(xt.shape, np.float64)
    for t in range(xt.shape[0]):
        for j in range(ids.shape[1]):
            e = int(ids[t, j])
            h = jax.nn.silu(xt[t] @ wg[e]) * (xt[t] @ wu[e])
            out[t] += np.asarray(h @ wd[e]) * float(gates[t, j])
    return out


@pytest.mark.parametrize("routing", ["router", "all-to-one"])
def test_dispatch_without_drops_equals_per_token_loop(routing):
    """Every assignment is computed: with the router's choices, and with
    every token sent to one expert (a capacity of 1.25 x T k / E would have
    kept 3 of these 16 tokens). The expert-parallel shares add up to it."""
    cfg = dataclasses.replace(CFG, num_experts=8, top_k=2)
    p = init_moe(jax.random.PRNGKey(0), cfg)
    xt = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.d_model))
    _, gates, ids = _route(xt, p["router"], cfg.top_k, renormalize=False)
    if routing == "all-to-one":
        ids = jnp.stack([jnp.full((16,), 5), (jnp.arange(16) % 5)], axis=1).astype(ids.dtype)
    with jax.default_matmul_precision("highest"):
        got = _local_expert_partial(xt, gates, ids, p["w_gate"], p["w_up"], p["w_down"], 0)
        lo = _local_expert_partial(xt, gates, ids, p["w_gate"][:4], p["w_up"][:4], p["w_down"][:4], 0)
        hi = _local_expert_partial(xt, gates, ids, p["w_gate"][4:], p["w_up"][4:], p["w_down"][4:], 4)
    want = _per_token(xt, gates, ids, p["w_gate"], p["w_up"], p["w_down"])
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5, rtol=1e-5)  # float32 sums
    np.testing.assert_allclose(np.asarray(lo + hi), want, atol=1e-5, rtol=1e-5)
    assert (np.abs(np.asarray(got)).sum(-1) > 0).all()


def test_gates_without_renormalisation_are_the_topk_probabilities():
    xt = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    rw = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    probs, gates, ids = _route(xt, rw, 3, renormalize=False)
    top = np.take_along_axis(np.asarray(probs), np.asarray(ids), axis=1)
    np.testing.assert_array_equal(np.asarray(gates), top)
    assert (np.asarray(gates).sum(-1) < 1).all()
    assert np.all(np.sort(np.asarray(probs), axis=1)[:, -3:][:, ::-1] == np.asarray(gates))
    assert not DEEPSEEK.norm_topk_prob and get_config("kimi-k2-1t-a32b").norm_topk_prob


def test_yarn_frequencies_and_scale_are_the_closed_form():
    """DeepSeek-V2-Lite: correction dims 10.47 and 22.51 over 32 pairs give a
    ramp over indices 10..23; the softmax scale is 192 ** -0.5 times
    (0.1 x 0.707 x ln 40 + 1) ** 2 = 1.5896, 0.114721; cos/sin keep scale 1."""
    sc = rope_scaling(DEEPSEEK)
    assert yarn_ramp(64, 1e4, 4096, 32.0, 1.0) == (10, 23)
    assert sc[4] == 1.0
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    got = np.asarray(rope_freqs(64, 1e4, sc))
    np.testing.assert_allclose(got, base / 40 * ramp + base * (1 - ramp), rtol=1e-6)
    np.testing.assert_array_equal(got[:11], np.asarray(rope_freqs(64, 1e4))[:11])
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale ** 2 == pytest.approx(1.5896, abs=1e-4)
    assert _mla_scale(DEEPSEEK) == pytest.approx(0.114721, abs=1e-6)


def test_plain_rope_is_unchanged_without_scaling():
    """A config with no YaRN (every dense model) rotates as before, bit for bit."""
    assert rope_scaling(get_config("qwen2-7b")) is None
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 16))
    pos = jnp.broadcast_to(jnp.arange(5), (2, 5))
    freqs = 1.0 / (1e6 ** (jnp.arange(0, 16, 2, dtype=jnp.float32) / 16))
    ang = pos[..., None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., :8], x[..., 8:]
    want = jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)
    np.testing.assert_array_equal(np.asarray(apply_rope(x, pos, 1e6)), np.asarray(want))


SEED = 2**33 + 29


def _serve(prompts, max_new, max_slots=4):
    """Serve ``prompts`` through the continuous engine (slot pool, batched
    admission, ragged decode steps shared by every active request), noting
    the logits the program produced for each request's every token."""
    eng = ServingEngine(max_slots=max_slots)
    eng.add_model("ds", CFG, weights.served_params(SEED, TINY), max_len=64)
    w = eng.workers["ds"]
    seen = {}  # (uid, token index) -> logits row
    prefill_batch, decode_pool = w.prefill_batch, w.decode_pool

    def on_prefill(prompts_, *a, **k):
        logits, cache = prefill_batch(prompts_, *a, **k)
        for row, pr in enumerate(prompts_):
            for uid, p in enumerate(prompts):
                if len(p) == len(pr) and (p == pr).all():
                    seen.setdefault((uid, 0), np.asarray(logits[row]))
        return logits, cache

    def on_decode(cache, tokens, pos, *a, **k):
        now = {s: (q.req.uid, len(q.tokens)) for s, q in eng.pools["ds"].active.items()}
        out = decode_pool(cache, tokens, pos, *a, **k)
        for s, key in now.items():
            seen[key] = np.asarray(out[1][s])
        return out

    w.prefill_batch, w.decode_pool = on_prefill, on_decode
    for uid, p in enumerate(prompts):
        eng.submit("ds", Request(uid=uid, prompt=p, max_new_tokens=max_new[uid]))
    with jax.default_matmul_precision("highest"):
        done = {r.uid: np.asarray(r.tokens) for r in eng.run_all()}
    return done, seen, eng


PROMPTS = [np.random.default_rng(i).integers(1, 500, n, dtype=np.int32)
           for i, n in enumerate((9, 14, 9, 21))]
TOL = 2e-4  # float32 logits of order 0.2 through three layers, summed in other orders


def test_serving_path_matches_reference():
    """Prefill at every prompt position (the program's prefill forward) and
    each decode step of requests of different lengths sharing the pool agree
    with the plain float32 reference."""
    from repro.models import model as model_lib

    done, seen, _ = _serve(PROMPTS, [8, 7, 6, 8])
    ref = reference.Reference(TINY, SEED)
    rng = np.random.default_rng(5)
    seqs, rows, served, other, prog = [], [], [], [], []
    for uid, p in enumerate(PROMPTS):
        toks = done[uid]
        assert len(toks) >= 6
        seqs.append(np.concatenate([p, toks[:-1]]))
        rows.append(np.arange(len(p) - 1, len(p) + len(toks) - 1))
        served.append(toks)
        other.append(rng.integers(0, 500, len(toks)))
        prog.append(np.stack([seen[(uid, j)][:500] for j in range(len(toks))]))
    for toks, ref_out, prog_rows in zip(
            (served, other), (ref.score(seqs, rows, served), ref.score(seqs, rows, other)),
            (prog, prog)):
        for t, (best, at, am), pl in zip(toks, ref_out, prog_rows):
            np.testing.assert_allclose(best, pl.max(axis=1), atol=TOL)
            np.testing.assert_allclose(at, pl[np.arange(len(t)), t], atol=TOL)
    for t, (_, _, am) in zip(served, ref.score(seqs, rows, served)):
        assert (am == t).mean() > 0.9  # greedy tokens; a near tie may flip on rounding
    # every prompt position of the program's prefill forward
    params = weights.served_params(SEED, TINY)
    for p in PROMPTS:
        with jax.default_matmul_precision("highest"):
            cache = model_lib.init_cache(CFG, 1, 64)
            logits, _ = model_lib.prefill(params, CFG, jnp.asarray(p[None]), cache)
        lg = np.asarray(logits[0, :, :500])
        (best, at, _), = ref.score([p], [np.arange(len(p))], [p])
        np.testing.assert_allclose(best, lg.max(axis=1), atol=TOL)
        np.testing.assert_allclose(at, lg[np.arange(len(p)), p], atol=TOL)


def test_logits_do_not_depend_on_the_company_of_the_step():
    """A request's logits are the same served alone as beside three others
    in the same decode steps: nothing is dropped when experts crowd."""
    _, alone, _ = _serve(PROMPTS[1:2], [7], max_slots=4)
    _, crowd, _ = _serve(PROMPTS, [8, 7, 6, 8])
    for j in range(7):
        # float32 sums over other batch shapes round differently, no more
        np.testing.assert_allclose(crowd[(1, j)], alone[(0, j)], atol=1e-5)


def test_routing_counters_count_active_slots_only():
    """An expert model's decode steps count, per expert layer, the experts
    that took a token of an active slot and the busiest one's load; a dense
    model counts nothing new and its decode program returns nothing new."""
    _, _, eng = _serve(PROMPTS, [8, 7, 6, 8])
    c = eng.ledger.counters
    steps = c["ds.moe_decode_layer_steps"]
    assert steps > 0 and steps % 2 == 0
    assert steps <= c["ds.moe_decode_experts_touched"] <= min(8, 2 * 4) * steps
    # at most 4 active slots of top-2: a layer's busiest expert has 1..4 tokens
    assert steps <= c["ds.moe_decode_load_max"] <= 4 * steps
    dense = dataclasses.replace(get_config("qwen2-7b"), num_layers=1, d_model=32, num_heads=2,
                                num_kv_heads=1, head_dim=16, d_ff=64, vocab_size=256,
                                dtype="float32", param_dtype="float32")
    from repro.models import init_params
    e2 = ServingEngine(max_slots=2)
    e2.add_model("q", dense, init_params(jax.random.PRNGKey(0), dense), max_len=32)
    assert not e2.workers["q"]._count_routes
    e2.submit("q", Request(uid=0, prompt=np.arange(1, 6, dtype=np.int32), max_new_tokens=4))
    e2.run_all()
    assert not any("moe" in k for k in e2.ledger.counters)


def test_route_counts_follow_the_mask():
    cfg = CFG
    p = init_moe(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (6, 1, cfg.d_model))
    mask = jnp.array([True, False, True, True, False, False])
    _, _, counts = moe_apply(p, x, cfg, ExecContext(), token_mask=mask)
    _, _, ids = _route(x.reshape(6, -1), p["router"], cfg.top_k, cfg.norm_topk_prob)
    want = np.bincount(np.asarray(ids)[np.asarray(mask)].ravel(), minlength=cfg.num_experts)
    np.testing.assert_array_equal(np.asarray(counts), want)
    assert moe_apply(p, x, cfg, ExecContext())[2] is None
