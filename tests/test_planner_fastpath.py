"""Vectorized planning fast path: lambda-batched DP equivalence, cost-table
and plan caching, cache invalidation, and the incremental exit-boundary fix.

Pure-numpy property tests (no hypothesis) — this module must run on the
bare seed environment.
"""
import numpy as np
import pytest

from repro.core.opgraph import OpGraph, OpNode
from repro.core.partitioner import (
    _dp_solve,
    _dp_solve_batch,
    _edge_costs,
    _levels_for,
    dp_partition,
    incremental_repartition,
)
from repro.core.profiler import (
    FEATURE_DIM,
    RuntimeEnergyProfiler,
    op_features,
    op_features_batch,
    state_bucket,
)
from repro.core.simulator import DeviceSim, DeviceState


def _rand_graph(rng, n_ops, splittable_p=0.8):
    g = OpGraph("rand")
    for i in range(n_ops):
        g.nodes.append(OpNode(
            f"op{i}", "matmul",
            flops=float(rng.uniform(1e6, 5e9)),
            bytes_in=float(rng.uniform(1e4, 5e7)),
            bytes_out=float(rng.uniform(1e4, 5e7)),
            weight_bytes=float(rng.uniform(0, 5e7)),
            splittable=bool(rng.random() < splittable_p),
            split_grain=int(rng.choice([2, 4, 8, 16])),
            comm_bytes_if_split=float(rng.uniform(0, 1e6)),
        ))
    return g


def _sim_cost(sim):
    def fn(op, a, p):
        return sim.exec_op(op, a, p)
    return fn


def _plan_cost(graph, plan_alphas, cost_fn, lam):
    lat = en = 0.0
    prev = plan_alphas[0]
    for op, a in zip(graph.nodes, plan_alphas):
        l, e = cost_fn(op, float(a), float(prev))
        lat += l
        en += e
        prev = a
    return en + lam * lat, lat, en


# ---------------------------------------------------------------------------
# lambda-batched DP == scalar reference, bit for bit
# ---------------------------------------------------------------------------


def test_batched_dp_identical_to_scalar():
    """For random graphs and lambda grids, ``_dp_solve_batch`` must return
    exactly the scalar solver's (alphas, lat, en) for every lambda."""
    for seed in range(12):
        rng = np.random.default_rng(seed)
        g = _rand_graph(rng, int(rng.integers(2, 14)))
        sim = DeviceSim("moderate", seed=seed)
        tables = _edge_costs(g, _sim_cost(sim))
        lams = np.concatenate([
            [0.0], rng.uniform(1e-6, 1e3, 5),
            np.geomspace(1e-4, 1e8, 5), [1e12]])
        al, lat, en = _dp_solve_batch(tables, lams)
        for i, l in enumerate(lams):
            a_s, t_s, e_s = _dp_solve(tables, float(l))
            assert np.array_equal(a_s, al[i]), (seed, l)
            assert t_s == lat[i] and e_s == en[i], (seed, l)


def test_batched_dp_with_exit_costs_identical():
    for seed in range(6):
        rng = np.random.default_rng(100 + seed)
        g = _rand_graph(rng, int(rng.integers(3, 10)))
        sim = DeviceSim("moderate", seed=seed)
        cost = _sim_cost(sim)
        tables = _edge_costs(g, cost)
        boundary = _levels_for(g.nodes[-1])
        ex_lat = rng.uniform(1e-4, 1e-2, len(boundary))
        ex_en = rng.uniform(1e-3, 1e-1, len(boundary))
        lams = np.array([0.0, 0.7, 1e12])
        al, lat, en = _dp_solve_batch(tables, lams, exit_costs=(ex_lat, ex_en))
        for i, l in enumerate(lams):
            a_s, t_s, e_s = _dp_solve(tables, float(l), exit_costs=(ex_lat, ex_en))
            assert np.array_equal(a_s, al[i])
            assert t_s == lat[i] and e_s == en[i]


def test_dp_partition_vectorized_equals_scalar_edp():
    """``dp_partition(objective='edp')`` picks the identical plan through the
    batched sweep and the scalar per-lambda loop."""
    for seed in range(8):
        rng = np.random.default_rng(200 + seed)
        g = _rand_graph(rng, int(rng.integers(3, 12)))
        sim = DeviceSim("moderate", seed=seed)
        cost = _sim_cost(sim)
        pv = dp_partition(g, cost, objective="edp")
        ps = dp_partition(g, cost, objective="edp", vectorize=False)
        assert np.array_equal(pv.alphas, ps.alphas), seed
        assert pv.pred_latency == ps.pred_latency
        assert pv.pred_energy == ps.pred_energy


def test_slo_batched_handles_extreme_lambda_scale():
    """Cost magnitudes that push the feasibility threshold past 1e4 (huge
    energies vs tiny latencies) must not make the batched path fall back to
    the max-energy latency-optimal plan when a cheaper feasible plan exists."""
    rng = np.random.default_rng(99)
    g = _rand_graph(rng, 8)
    sim = DeviceSim("high", seed=9)

    def cost(op, a, p):  # energies scaled 1e6x: lambda* ~ E/T becomes ~1e7
        l, e = sim.exec_op(op, a, p)
        return l, e * 1e6

    p_lat = dp_partition(g, cost, objective="latency")
    slo = p_lat.pred_latency * 1.3
    pv = dp_partition(g, cost, slo=slo)
    ps = dp_partition(g, cost, slo=slo, vectorize=False)
    assert pv.pred_latency <= slo * (1 + 1e-9)
    # batched search must find a plan at least as good as the scalar bisection
    assert pv.pred_energy <= ps.pred_energy * (1 + 1e-6)


def test_feature_cache_invalidation_clears_alpha_levels():
    """Mutating op metadata + _invalidate_feature_cache() must drop BOTH the
    static feature block and the memoised alpha-level grid."""
    op = OpNode("x", "matmul", 1e9, 1e6, 1e6, 1e6, splittable=True, split_grain=4)
    lv4 = _levels_for(op)
    f4 = op.static_features().copy()
    op.split_grain = 16
    op.flops = 2e9
    op._invalidate_feature_cache()
    lv16 = _levels_for(op)
    assert len(lv16) > len(lv4), "stale alpha grid survived invalidation"
    assert not np.array_equal(op.static_features(), f4)
    # graph-level invalidation reaches every node and the stacked matrix
    g = OpGraph("g", [op])
    m1 = g.static_feature_matrix()
    op.flops = 3e9
    g._invalidate_feature_cache()
    assert not np.array_equal(g.static_feature_matrix(), m1)


def test_slo_batched_feasible_and_energy_bounded():
    for seed in range(6):
        rng = np.random.default_rng(300 + seed)
        g = _rand_graph(rng, 8)
        sim = DeviceSim("high", seed=seed)
        cost = _sim_cost(sim)
        p_lat = dp_partition(g, cost, objective="latency")
        slo = p_lat.pred_latency * 1.3
        p = dp_partition(g, cost, slo=slo)
        assert p.pred_latency <= slo * (1 + 1e-9)
        # E(lam) is weakly increasing, so the SLO plan never costs more
        # energy than the latency-optimal extreme
        assert p.pred_energy <= p_lat.pred_energy * (1 + 1e-9)


# ---------------------------------------------------------------------------
# incremental re-partition: exit-boundary edge is priced in
# ---------------------------------------------------------------------------


def test_incremental_never_worse_than_original_plan():
    """With pinned boundaries the original segment assignment stays feasible,
    so a segment re-solve must never increase total J = E + lam*T. (The old
    exit pin forced alphas[hi] == alphas[hi+1] without charging the exit
    edge, which could and did make plans globally worse.)"""
    worse = 0
    for seed in range(20):
        rng = np.random.default_rng(400 + seed)
        n = int(rng.integers(5, 14))
        g = _rand_graph(rng, n)
        sim = DeviceSim("moderate", seed=seed)
        cost = _sim_cost(sim)
        lam = float(rng.choice([0.0, 0.3, 1.0, 5.0]))
        # start from a plan solved under a DIFFERENT lambda so the segment
        # re-solve has real work to do
        plan0 = dp_partition(g, cost, lam=float(rng.choice([0.0, 1e12])))
        lo = int(rng.integers(0, n - 2))
        hi = int(rng.integers(lo, n - 1))
        inc = incremental_repartition(g, plan0, cost, (lo, hi), lam=lam)
        j0, _, _ = _plan_cost(g, plan0.alphas, cost, lam)
        j1, _, _ = _plan_cost(g, inc.alphas, cost, lam)
        if j1 > j0 * (1 + 1e-9) + 1e-15:
            worse += 1
    assert worse == 0, f"{worse}/20 segment re-solves made the plan worse"


def test_incremental_keeps_untouched_alphas():
    rng = np.random.default_rng(1)
    g = _rand_graph(rng, 10)
    sim = DeviceSim("moderate", seed=1)
    cost = _sim_cost(sim)
    plan = dp_partition(g, cost, lam=0.5)
    inc = incremental_repartition(g, plan, cost, (3, 6), lam=0.5)
    assert np.allclose(inc.alphas[:3], plan.alphas[:3])
    assert np.allclose(inc.alphas[7:], plan.alphas[7:])


# ---------------------------------------------------------------------------
# vectorized feature construction
# ---------------------------------------------------------------------------


def test_op_features_batch_matches_scalar():
    rng = np.random.default_rng(2)
    g = _rand_graph(rng, 12)
    state = DeviceState(1.49, 0.5, 0.79, 0.1)
    ops = [g.nodes[int(i)] for i in rng.integers(0, len(g), 64)]
    alphas = rng.choice([0.0, 0.25, 0.5, 1.0], 64)
    prevs = rng.choice([0.0, 0.5, 1.0], 64)
    X = op_features_batch(ops, alphas, prevs, state)
    assert X.shape == (64, FEATURE_DIM)
    for j in range(64):
        x = op_features(ops[j], float(alphas[j]), float(prevs[j]), state)
        assert np.array_equal(x, X[j]), j


def test_op_features_batch_with_counts():
    rng = np.random.default_rng(3)
    g = _rand_graph(rng, 4)
    state = DeviceState(1.0, 0.4, 0.5, 0.2)
    counts = [2, 3, 1, 4]
    alphas = rng.uniform(0, 1, sum(counts))
    prevs = rng.uniform(0, 1, sum(counts))
    X = op_features_batch(g.nodes, alphas, prevs, state, counts=counts)
    expanded = [op for op, c in zip(g.nodes, counts) for _ in range(c)]
    Xref = op_features_batch(expanded, alphas, prevs, state)
    assert np.array_equal(X, Xref)


# ---------------------------------------------------------------------------
# cost-table cache: reuse + invalidation
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_profiler():
    rng = np.random.default_rng(7)
    g = _rand_graph(rng, 8)
    prof = RuntimeEnergyProfiler(use_gru=True, seed=0)
    prof.offline_calibrate([g], n_samples=500, seed=0)
    return g, prof


def test_cost_table_cache_hit_on_same_bucket(small_profiler):
    g, prof = small_profiler
    prof.table_cache.clear()
    obs = DeviceState(1.5, 0.5, 0.8, 0.1)
    p1 = dp_partition(g, prof.cost_fn(obs), objective="edp")
    h0 = prof.table_cache.hits
    # tiny observation jitter that stays inside the quantization bucket
    obs2 = DeviceState(1.503, 0.501, 0.81, 0.104)
    assert state_bucket(obs) == state_bucket(obs2)
    p2 = dp_partition(g, prof.cost_fn(obs2), objective="edp")
    assert prof.table_cache.hits == h0 + 1
    assert np.array_equal(p1.alphas, p2.alphas)


def test_cost_table_cache_state_bucket_invalidation(small_profiler):
    g, prof = small_profiler
    prof.table_cache.clear()
    obs = DeviceState(1.5, 0.5, 0.8, 0.1)
    dp_partition(g, prof.cost_fn(obs), objective="edp")
    m0 = prof.table_cache.misses
    obs_far = DeviceState(2.2, 0.58, 0.2, 0.05)  # different bucket
    assert state_bucket(obs) != state_bucket(obs_far)
    dp_partition(g, prof.cost_fn(obs_far), objective="edp")
    assert prof.table_cache.misses > m0, "state-bucket change must miss"


def test_cost_table_cache_correction_invalidation(small_profiler):
    g, prof = small_profiler
    prof.table_cache.clear()
    obs = DeviceState(1.5, 0.5, 0.8, 0.1)
    dp_partition(g, prof.cost_fn(obs), objective="edp")
    v0 = prof.correction_version()
    # GRU feedback must bump the version and invalidate cached tables
    sim = DeviceSim("moderate", seed=3)
    lat, en = sim.exec_op(g.nodes[0], 1.0, 1.0)
    prof.feedback(g.nodes[0], 1.0, 1.0, obs, lat, en)
    assert prof.correction_version() > v0
    m0 = prof.table_cache.misses
    dp_partition(g, prof.cost_fn(obs), objective="edp")
    assert prof.table_cache.misses > m0, "correction update must miss"


def test_cost_table_cache_eviction_is_lru():
    """Explicit max-entries eviction order: the least-recently-*used* entry
    goes first, where both get() and put() refresh recency."""
    from repro.core.profiler import CostTableCache

    g = object()
    c = CostTableCache(max_entries=3)
    for k in ("a", "b", "c"):
        c.put(k, g, k.upper())
    # touch "a" (oldest-inserted) via get: "b" is now least recently used
    assert c.get("a", g) == "A"
    c.put("d", g, "D")
    assert c.get("b", g) is None, "LRU victim must be the untouched entry"
    assert c.get("a", g) == "A"
    assert len(c) == 3


def test_cost_table_cache_put_refreshes_recency():
    """Re-putting an existing key must move it to the MRU end, not leave it
    in insertion position to be evicted as if stale."""
    from repro.core.profiler import CostTableCache

    g = object()
    c = CostTableCache(max_entries=3)
    for k in ("a", "b", "c"):
        c.put(k, g, k.upper())
    c.put("a", g, "A2")  # overwrite refreshes both value and recency
    c.put("d", g, "D")   # evicts "b" (now the oldest), not "a"
    assert c.get("a", g) == "A2"
    assert c.get("b", g) is None


def test_cost_table_cache_guards_graph_identity(small_profiler):
    """A recycled id() must not alias another graph's tables."""
    _, prof = small_profiler
    prof.table_cache.clear()
    rng = np.random.default_rng(8)
    g1 = _rand_graph(rng, 6)
    g2 = _rand_graph(rng, 6)
    obs = DeviceState(1.5, 0.5, 0.8, 0.1)
    fn = prof.cost_fn(obs)
    _edge_costs(g1, fn)
    # same key shape but different graph object -> must not hit
    fake_key = (id(g1), 0, len(g1) - 1, fn.cache_key())
    assert prof.table_cache.get(fake_key, g2) is None


# ---------------------------------------------------------------------------
# scheduler plan cache: warm choose() does zero GBDT traversals
# ---------------------------------------------------------------------------


class _FixedSim:
    def __init__(self, state=None):
        self.state = state or DeviceState(1.49, 0.5, 0.79, 0.1)

    def observe(self, noise: bool = True):
        return self.state


@pytest.fixture(scope="module")
def sched_setup():
    from repro.configs.base import get_config, reduced
    from repro.core.opgraph import build_transformer_graph
    from repro.serving.engine import AdaOperScheduler

    cfg = reduced(get_config("tinyllama-1.1b"))
    g = build_transformer_graph(cfg, 2, 32)
    prof = RuntimeEnergyProfiler(use_gru=False)
    prof.offline_calibrate([g], n_samples=600, seed=0)
    return cfg, prof, AdaOperScheduler(prof, _FixedSim())


def test_scheduler_warm_cache_zero_gbdt_traversals(sched_setup):
    cfg, prof, sched = sched_setup
    c1 = sched.choose(cfg, n_waiting=8, prompt_len=32, max_new=4)
    cold = prof.energy_model.n_predict_calls + prof.latency_model.n_predict_calls
    assert cold > 0
    c2 = sched.choose(cfg, n_waiting=8, prompt_len=32, max_new=4)
    warm = prof.energy_model.n_predict_calls + prof.latency_model.n_predict_calls
    assert warm == cold, "warm-cache choose() must not traverse the GBDT"
    assert sched.ledger.counters.get("plan_cache_hits", 0) > 0
    assert c2["batch"] == c1["batch"] and c2["score"] == c1["score"]
    assert np.array_equal(c2["plan_prefill"].alphas, c1["plan_prefill"].alphas)


def test_scheduler_exact_fit_candidate(sched_setup):
    cfg, _, sched = sched_setup
    sched.choose(cfg, n_waiting=3, prompt_len=32, max_new=4)
    evaluated = {k[1] for k in sched._plan_cache}
    assert 3 in evaluated, "n_waiting=3 with candidates (1,2,4) must try b=3"


def test_scheduler_invalidate(sched_setup):
    cfg, prof, sched = sched_setup
    sched.choose(cfg, n_waiting=4, prompt_len=32, max_new=4)
    sched.invalidate()
    sched.choose(cfg, n_waiting=4, prompt_len=32, max_new=4)
    # plan cache was dropped; the cost-table cache may still serve tables,
    # but the decision must have been recomputed (plan_cache misses grew)
    assert len(sched._plan_cache) > 0


# ---------------------------------------------------------------------------
# serving queue drain
# ---------------------------------------------------------------------------


def test_engine_queue_drain_order_preserving():
    from repro.serving.engine import Request, ServingEngine

    class _StubWorker:
        cfg = None

        def generate(self, prompts, max_new, enc_inputs=None, temperature=0.0,
                     row_keys=None):
            return np.zeros((prompts.shape[0], max_new), np.int32)

    eng = ServingEngine()
    eng.workers["m"] = _StubWorker()
    eng.queues["m"] = []
    eng.stats["m"] = []
    rng = np.random.default_rng(0)
    # interleave two length buckets; default (schedulerless) batch cap is 8
    for i in range(20):
        plen = 8 if i % 2 == 0 else 12
        eng.queues["m"].append(
            Request(i, rng.integers(1, 100, plen, dtype=np.int32), 2))
    res = eng.step("m")
    served = {r.uid for r in res}
    # first request's length bucket (plen=8 -> even uids), FIFO order
    assert served == {0, 2, 4, 6, 8, 10, 12, 14}
    remaining = [r.uid for r in eng.queues["m"]]
    assert remaining == [i for i in range(20) if i not in served]
    # second step drains the other bucket's head
    res2 = eng.step("m")
    assert {r.uid for r in res2} == {1, 3, 5, 7, 9, 11, 13, 15}
