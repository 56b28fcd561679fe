"""Packed, deduplicated GBDT evaluation against boosting's per-tree loop.

``GBDTRegressor.predict`` walks every tree at once over the distinct binned
rows; it must return exactly (``np.array_equal``) what summing
``_Tree.predict`` tree by tree returns, so every plan, cache key and
decision built on it stays the same.
"""
import numpy as np
import pytest

from repro.core.gbdt import GBDTRegressor, fit_ensemble
from repro.core.partitioner import _edge_costs, dp_partition
from repro.core.profiler import RuntimeEnergyProfiler
from repro.core.simulator import DeviceState


def _per_tree(m: GBDTRegressor, X) -> np.ndarray:
    """The reference: base score, then each tree's leaf in tree order."""
    Xb = m._bin(np.asarray(X, np.float64))
    pred = np.full(Xb.shape[0], m._base)
    for t in m._trees:
        pred += m.learning_rate * t.predict(Xb)
    return m._itx(pred)


def _data(seed=0, n=600, f=6):
    rng = np.random.default_rng(seed)
    X = rng.uniform(-1, 1, (n, f))
    y = np.exp(2 * X[:, 0]) + np.abs(X[:, 1] * X[:, 2]) + 0.1 * rng.random(n)
    return X, y


_STATE = DeviceState(1.49, 0.5, 0.79, 0.1)


@pytest.fixture(scope="module")
def calibrated():
    """A profiler calibrated on the test-size configs of the benchmark's two
    models, and the graphs it was calibrated on."""
    from repro.configs.base import get_config, reduced
    from repro.core.opgraph import build_transformer_graph

    graphs = [build_transformer_graph(reduced(get_config(a)), 4, 64, kind=k)
              for a in ("qwen2-7b", "granite-3-8b") for k in ("prefill", "decode")]
    prof = RuntimeEnergyProfiler(use_gru=False)
    prof.offline_calibrate(graphs, n_samples=600, seed=0)
    return prof, graphs


def _edge_table(prof, graph) -> np.ndarray:
    """The feature rows ``_edge_costs`` sends the energy model for ``graph``."""
    seen = []
    model = prof.energy_model
    model.predict = lambda X: (seen.append(np.array(X)),
                               GBDTRegressor.predict(model, X))[1]
    try:
        prof.table_cache.clear()
        _edge_costs(graph, prof.cost_fn(_STATE))
    finally:
        del model.predict
    (X,) = seen
    return X


def _case_random(log_target):
    X, y = _data()
    m = GBDTRegressor(n_estimators=40, log_target=log_target).fit(X, y)
    return m, np.vstack([X, _data(seed=1)[0]])


def _case_edge_table(calibrated):
    prof, graphs = calibrated
    X = _edge_table(prof, graphs[-1])
    assert len(X) > 0
    return prof.energy_model, X


def _case_rows(n):
    X, y = _data()
    return GBDTRegressor(n_estimators=30).fit(X, y), X[:n]


def _case_duplicates():
    X, y = _data()
    return GBDTRegressor(n_estimators=30).fit(X, y), np.repeat(X[3:4], 50, axis=0)


def _case_stump(**kw):
    X, y = _data()
    return GBDTRegressor(n_estimators=20, **kw).fit(X, y), X


def _case_ensemble_member():
    X, y = _data()
    members = fit_ensemble(X, y, n_members=2, seed=3, n_estimators=25)
    return members[1], np.vstack([X, _data(seed=2)[0]])


def _case_refit():
    X, y = _data()
    m = GBDTRegressor(n_estimators=30).fit(X, y)
    before = m.predict(X)  # builds the pack of the first fit
    X2, y2 = _data(seed=5)
    m.fit(X2, 3.0 * y2 + 1.0)
    after = m.predict(X)
    assert not np.array_equal(before, after), "refit must drop the old pack"
    return m, X


CASES = {
    "random-log": lambda cal: _case_random(True),
    "random-linear": lambda cal: _case_random(False),
    "edge-costs-table": _case_edge_table,
    "zero-rows": lambda cal: _case_rows(0),
    "one-row": lambda cal: _case_rows(1),
    "all-duplicates": lambda cal: _case_duplicates(),
    "stump-depth-0": lambda cal: _case_stump(max_depth=0),
    "stump-min-samples": lambda cal: _case_stump(min_samples=10_000),
    "ensemble-member": lambda cal: _case_ensemble_member(),
    "refit": lambda cal: _case_refit(),
}


@pytest.mark.parametrize("case", list(CASES))
def test_packed_predict_matches_per_tree_reference(case, calibrated):
    m, X = CASES[case](calibrated)
    calls = m.n_predict_calls
    got = m.predict(X)
    want = _per_tree(m, X)
    assert got.shape == want.shape == (len(X),)
    assert np.array_equal(got, want)
    assert m.n_predict_calls == calls + 1


def test_cold_dp_partition_same_as_reference_evaluation(calibrated, monkeypatch):
    prof, graphs = calibrated
    graph = graphs[1]
    prof.table_cache.clear()
    packed = dp_partition(graph, prof.cost_fn(_STATE))
    monkeypatch.setattr(GBDTRegressor, "predict", _per_tree)
    prof.table_cache.clear()
    ref = dp_partition(graph, prof.cost_fn(_STATE))
    prof.table_cache.clear()
    assert np.array_equal(packed.alphas, ref.alphas)
    assert packed.pred_latency == ref.pred_latency
    assert packed.pred_energy == ref.pred_energy


def test_plan_cost_rows_count_cold_solves_only(calibrated):
    from repro.configs.base import get_config, reduced
    from repro.serving.engine import AdaOperScheduler

    class _Sim:
        def observe(self, noise=True):
            return _STATE

    prof, _ = calibrated
    prof.table_cache.clear()
    sched = AdaOperScheduler(prof, _Sim())
    cfg = reduced(get_config("granite-3-8b"))
    ctr = sched.ledger.counters

    sched.choose(cfg, n_waiting=4, prompt_len=32, max_new=4)
    rows, unique = ctr["plan_cost_rows"], ctr["plan_cost_unique_rows"]
    assert 0 < unique <= rows
    sched.step_plan(cfg, batch=3, seq_len=100, max_new=20)  # another cold key
    assert ctr["plan_cost_rows"] > rows and ctr["plan_cost_unique_rows"] > unique
    assert ctr["plan_cost_unique_rows"] <= ctr["plan_cost_rows"]

    seen = dict(ctr)
    sched.choose(cfg, n_waiting=4, prompt_len=32, max_new=4)  # warm
    sched.step_plan(cfg, batch=3, seq_len=100, max_new=20)
    assert ctr["plan_cost_rows"] == seen["plan_cost_rows"]
    assert ctr["plan_cost_unique_rows"] == seen["plan_cost_unique_rows"]
    assert ctr["plan_cache_misses"] == seen["plan_cache_misses"]
