"""Contention-aware joint co-execution planning (repro.core.coexec,
docs/coexec.md): simulator contention physics, the contention-priced cost
wrapper, joint-vs-independent fallback bit-identity, ledger-feedback
corrections, scheduler wiring, and the docs consistency checker."""
import os

import numpy as np
import pytest

from repro.core import (
    AdaOperController,
    CoexecPlanner,
    ContentionModel,
    DeviceSim,
    RuntimeEnergyProfiler,
    build_yolo_graph,
    dp_partition,
    joint_partition,
    plan_rail_load,
    predicted_rail_fractions,
)
from repro.core.coexec import FULL_DUTY, RAILS, RailLoad, combine_loads
from repro.core.opgraph import OpGraph


@pytest.fixture(scope="module")
def graphs():
    ga = build_yolo_graph(batch=1)
    gb = OpGraph(name="yolo_b2", nodes=build_yolo_graph(batch=2).nodes)
    return ga, gb


@pytest.fixture(scope="module")
def profiler(graphs):
    prof = RuntimeEnergyProfiler(use_gru=False, seed=0)
    prof.offline_calibrate(list(graphs), n_samples=200, seed=0)
    return prof


def _exec_all(sim, graph, alphas):
    lat = en = 0.0
    prev = alphas[0]
    for op, a in zip(graph.nodes, alphas):
        l, eb = sim.exec_op_rails(op, float(a), float(prev))
        lat += l
        en += eb.total_j
        prev = a
        sim.step(l)
    return lat, en


# ---------------------------------------------------------------------------
# DeviceSim.set_coexec physics
# ---------------------------------------------------------------------------


def test_set_coexec_one_is_bit_identical_noop(graphs):
    g, _ = graphs
    alphas = np.full(len(g.nodes), 0.5)
    a = DeviceSim("moderate", seed=0)
    b = DeviceSim("moderate", seed=0)
    b.set_coexec(1)  # declaring the single-task setting must change nothing
    la, ea = _exec_all(a, g, alphas)
    lb, eb = _exec_all(b, g, alphas)
    assert la == lb and ea == eb


def test_set_coexec_contention_monotone_in_n(graphs):
    g, _ = graphs
    alphas = np.full(len(g.nodes), 0.5)  # every op split: bus traffic exists
    results = []
    for n in (1, 2, 4):
        sim = DeviceSim("moderate", seed=0)
        sim.set_coexec(n)
        results.append(_exec_all(sim, g, alphas))
    (l1, e1), (l2, e2), (l4, e4) = results
    assert l1 < l2 < l4, "co-runners must strictly slow a split plan"
    assert e1 < e2 < e4, "co-runners must strictly cost a split plan energy"


# ---------------------------------------------------------------------------
# RailLoad / plan_rail_load
# ---------------------------------------------------------------------------


def test_plan_rail_load_ranges_and_extremes(graphs):
    g, _ = graphs
    n = len(g.nodes)
    for alphas in (np.zeros(n), np.ones(n), np.full(n, 0.5)):
        load = plan_rail_load(g, alphas)
        for v in (load.cpu, load.gpu, load.bus):
            assert 0.0 <= v <= 1.0
        assert load.cpu + load.gpu == pytest.approx(1.0)
    assert plan_rail_load(g, np.ones(n)).gpu == pytest.approx(1.0)
    assert plan_rail_load(g, np.zeros(n)).bus == 0.0
    assert plan_rail_load(g, np.full(n, 0.5)).bus > 0.0


def test_combine_loads_saturates():
    a = RailLoad(0.7, 0.6, 0.9)
    c = combine_loads([a, a])
    assert (c.cpu, c.gpu, c.bus) == (1.0, 1.0, 1.0)
    assert combine_loads([]) == RailLoad()


# ---------------------------------------------------------------------------
# ContentionModel pricing
# ---------------------------------------------------------------------------


def test_wrap_single_resident_returns_base_unchanged(profiler, graphs):
    cost_fn = profiler.cost_fn(DeviceSim("moderate", seed=0).observe())
    model = ContentionModel()
    assert model.wrap(cost_fn, 1, FULL_DUTY) is cost_fn


def test_contended_cost_never_cheaper_and_batches_agree(profiler, graphs):
    g, _ = graphs
    cost_fn = profiler.cost_fn(DeviceSim("moderate", seed=0).observe())
    wrapped = ContentionModel().wrap(cost_fn, 3, FULL_DUTY)
    items = [(op, a, p) for op in g.nodes[:8]
             for a, p in ((0.0, 0.0), (1.0, 1.0), (0.5, 0.0), (1.0, 0.0))]
    for op, a, p in items:
        l0, e0 = cost_fn(op, a, p)
        l1, e1 = wrapped(op, a, p)
        assert l1 >= l0 and e1 >= e0
    lb, eb = wrapped.batch(items)
    for j, (op, a, p) in enumerate(items):
        l1, e1 = wrapped(op, a, p)
        assert lb[j] == pytest.approx(l1) and eb[j] == pytest.approx(e1)


def test_contended_cache_key_scopes_contention(profiler):
    cost_fn = profiler.cost_fn(DeviceSim("moderate", seed=0).observe())
    model = ContentionModel()
    k2 = model.wrap(cost_fn, 2, FULL_DUTY).cache_key()
    k3 = model.wrap(cost_fn, 3, FULL_DUTY).cache_key()
    assert k2 != k3
    assert k2[0] == cost_fn.cache_key()  # extends, never replaces, the base
    model.corrections["bus"] = 2.0
    model._version += 1
    assert model.wrap(cost_fn, 2, FULL_DUTY).cache_key() != k2


# ---------------------------------------------------------------------------
# observe(): ledger feedback with hysteresis
# ---------------------------------------------------------------------------


def test_observe_hysteresis_and_version_bump():
    m = ContentionModel()
    v0 = m.version()
    # small residuals: EMA stays under the hysteresis, nothing moves
    assert m.observe((0.4, 0.5, 0.1), (0.41, 0.49, 0.1)) is False
    assert m.version() == v0 and all(m.corrections[r] == 1.0 for r in RAILS)
    # sustained large divergence crosses the hysteresis and applies
    changed = False
    for _ in range(6):
        changed = m.observe((0.6, 0.35, 0.05), (0.2, 0.75, 0.05)) or changed
    assert changed and m.version() > v0
    assert m.corrections["cpu"] < 1.0 < m.corrections["gpu"]
    lo, hi = m.correction_bounds
    assert all(lo <= m.corrections[r] <= hi for r in RAILS)


def test_observe_accepts_dict_and_rejects_empty():
    m = ContentionModel()
    assert m.observe(None, (0.3, 0.3, 0.4)) is False
    assert m.observe((0.3, 0.3, 0.4), {"cpu": 0.0, "gpu": 0.0, "bus": 0.0}) is False
    for _ in range(6):
        m.observe((0.6, 0.35, 0.05), {"cpu": 0.1, "gpu": 0.85, "bus": 0.05})
    assert m.corrections["cpu"] < 1.0


# ---------------------------------------------------------------------------
# joint_partition: fallback bit-identity + honest accounting
# ---------------------------------------------------------------------------


def test_joint_partition_fallback_bit_identical(profiler, graphs):
    ga, gb = graphs
    cost_fn = profiler.cost_fn(DeviceSim("moderate", seed=0).observe())
    indep = {g.name: dp_partition(g, cost_fn, objective="edp")
             for g in (ga, gb)}
    for kwargs in (dict(model=None),
                   dict(model=ContentionModel(), n_resident=1)):
        plans = joint_partition([ga, gb], cost_fn, **kwargs)
        for g in (ga, gb):
            assert np.array_equal(plans[g.name].alphas, indep[g.name].alphas)
            assert plans[g.name].pred_energy == indep[g.name].pred_energy
            assert plans[g.name].pred_latency == indep[g.name].pred_latency
    single = joint_partition([ga], cost_fn, model=ContentionModel(),
                             n_resident=4)
    assert np.array_equal(single[ga.name].alphas, indep[ga.name].alphas)


def test_joint_plans_scored_on_base_predictor(profiler, graphs):
    ga, gb = graphs
    cost_fn = profiler.cost_fn(DeviceSim("moderate", seed=0).observe())
    plans = joint_partition([ga, gb], cost_fn, model=ContentionModel(),
                            n_resident=2)
    from repro.core import score_plan
    for g in (ga, gb):
        rescored = score_plan(g, plans[g.name].alphas, cost_fn)
        assert plans[g.name].pred_energy == rescored.pred_energy
        assert plans[g.name].pred_latency == rescored.pred_latency


# ---------------------------------------------------------------------------
# CoexecPlanner cache + rails stamp
# ---------------------------------------------------------------------------


def test_planner_cache_and_version_invalidation(profiler, graphs):
    ga, gb = graphs
    cost_fn = profiler.cost_fn(DeviceSim("moderate", seed=0).observe())
    pl = CoexecPlanner()
    p1 = pl.plans([ga, gb], cost_fn, n_resident=2, fault_epoch=0)
    assert pl.cache_misses == 1
    p2 = pl.plans([ga, gb], cost_fn, n_resident=2, fault_epoch=0)
    assert p2[ga.name] is p1[ga.name] and pl.cache_hits == 1
    assert pl.plans([ga, gb], cost_fn, n_resident=2, fault_epoch=1)[ga.name] \
        is not p1[ga.name]  # fault transitions miss
    pl.model._version += 1  # contention correction applied
    assert pl.plans([ga, gb], cost_fn, n_resident=2, fault_epoch=0)[ga.name] \
        is not p1[ga.name]
    rails = p1[ga.name].coexec_rails
    assert rails is not None and sum(rails) == pytest.approx(1.0)


def test_planner_skips_cache_without_cache_key(graphs):
    ga, gb = graphs

    def plain_cost(op, a, p):  # no cache_key/table_cache protocol
        return 1e-4 * (1.0 + a), 1e-5 * (2.0 - a)

    pl = CoexecPlanner()
    pl.plans([ga, gb], plain_cost, n_resident=2)
    pl.plans([ga, gb], plain_cost, n_resident=2)
    assert pl.cache_hits == 0 and len(pl._cache) == 0


# ---------------------------------------------------------------------------
# controller wiring: joint predictions reconcile with the measured ledger
# ---------------------------------------------------------------------------


def test_run_concurrent_joint_rails_reconcile_with_ledger(profiler, graphs):
    ga, gb = graphs
    sim = DeviceSim("moderate", seed=0)
    ctl = AdaOperController(sim, profiler, objective="edp",
                            coexec=CoexecPlanner())
    ctl.run_concurrent([ga, gb], iters=6)
    infers = [ev for ev in sim.ledger.events if ev.kind == "infer"]
    assert len(infers) == 12
    # the planner's nominal-constants rail prediction must land in the same
    # neighborhood as the measured attribution — the residual is the
    # feedback signal, so it must be small enough for log-EMA corrections
    # to be meaningful rather than saturated at the clip
    for name in (ga.name, gb.name):
        plan = ctl.plans[name]
        pred = plan.coexec_rails
        assert pred is not None
        meas = [ev.energy.fractions() for ev in infers
                if ev.model == name and ev.energy.fractions()]
        mean = np.mean(np.array(meas), axis=0)
        assert np.abs(np.array(pred) - mean).max() < 0.3, (pred, tuple(mean))


def test_run_concurrent_without_planner_keeps_plans_unstamped(profiler, graphs):
    ga, gb = graphs
    sim = DeviceSim("moderate", seed=0)
    ctl = AdaOperController(sim, profiler, objective="edp")
    ctl.run_concurrent([ga, gb], iters=2)
    assert getattr(ctl.plans[ga.name], "coexec_rails", None) is None
    assert "coexec_corrections" not in sim.ledger.counters


# ---------------------------------------------------------------------------
# serving scheduler wiring
# ---------------------------------------------------------------------------


def test_scheduler_joint_keying_and_single_resident_fallback(profiler):
    from repro.serving.scheduler import AdaOperScheduler

    sim = DeviceSim("moderate", seed=0)
    sched = AdaOperScheduler(profiler, sim, coexec=CoexecPlanner())
    cost_fn = profiler.cost_fn(sim.observe())
    # single resident: the base callable and an empty key — bit-identical
    assert sched.set_resident(("m1",)) is True
    c1, k1 = sched._coexec_cost(cost_fn)
    assert c1 is cost_fn and k1 == ()
    # two resident: contention-wrapped, key carries set + n + version
    assert sched.set_resident(("m1", "m2")) is True
    assert sched.set_resident(("m2", "m1")) is False  # order-insensitive
    c2, k2 = sched._coexec_cost(cost_fn)
    assert c2 is not cost_fn and ("m1", "m2") in k2
    # no planner attached: always the base path
    plain = AdaOperScheduler(profiler, sim)
    plain.set_resident(("m1", "m2"))
    c3, k3 = plain._coexec_cost(cost_fn)
    assert c3 is cost_fn and k3 == ()


def test_scheduler_joint_plan_rescored_on_base(profiler):
    from repro.configs.base import get_config, reduced
    from repro.serving.scheduler import AdaOperScheduler

    cfg = reduced(get_config("tinyllama-1.1b"))
    sim = DeviceSim("moderate", seed=0)
    sched = AdaOperScheduler(profiler, sim, coexec=CoexecPlanner())
    sched.set_resident(("a", "b"))
    obs = sim.observe()
    cost_fn = profiler.cost_fn(obs)
    ent = sched._plan_one(cfg, 2, 32, "prefill", cost_fn, sched._cache_key(obs))
    g = sched._graph(cfg, 2, 32, "prefill")
    from repro.core import score_plan
    base = score_plan(g, ent.alphas, cost_fn)
    assert ent.pred_energy == base.pred_energy  # accounting on base predictor


# ---------------------------------------------------------------------------
# docs consistency checker (tools/check_docs.py)
# ---------------------------------------------------------------------------


def _make_repo(tmp_path, readme="", docs=(), arch=None):
    (tmp_path / "docs").mkdir()
    (tmp_path / "README.md").write_text(readme)
    for name, text in docs:
        (tmp_path / "docs" / name).write_text(text)
    if arch is not None:
        (tmp_path / "docs" / "architecture.md").write_text(arch)
    return str(tmp_path)


def _run_check(root):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "check_docs", os.path.join(os.path.dirname(__file__), "..",
                                   "tools", "check_docs.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main(root)


def test_check_docs_flags_broken_link(tmp_path, capsys):
    root = _make_repo(tmp_path,
                      readme="[gone](docs/nope.md) [ok](docs/a.md)",
                      docs=[("a.md", "fine")])
    assert _run_check(root) == 1
    assert "broken link" in capsys.readouterr().out


def test_check_docs_flags_orphan_doc(tmp_path, capsys):
    root = _make_repo(tmp_path, readme="[a](docs/a.md)",
                      docs=[("a.md", "fine"), ("orphan.md", "unreachable")])
    assert _run_check(root) == 1
    assert "orphan.md" in capsys.readouterr().out


def test_check_docs_transitive_reference_is_reachable(tmp_path):
    root = _make_repo(tmp_path, readme="[a](docs/a.md)",
                      docs=[("a.md", "[b](b.md)"), ("b.md", "leaf")])
    assert _run_check(root) == 0


def test_check_docs_flags_stale_package_map(tmp_path, capsys):
    arch = ("# arch\n\n## Package map\n\n```\nsrc/repro/\n  core/\n"
            "    ghost.py   does not exist\n```\n")
    root = _make_repo(tmp_path, readme="[arch](docs/architecture.md)",
                      arch=arch)
    (tmp_path / "src" / "repro" / "core").mkdir(parents=True)
    assert _run_check(root) == 1
    assert "ghost.py" in capsys.readouterr().out


def test_check_docs_passes_on_this_repo():
    root = os.path.join(os.path.dirname(__file__), "..")
    assert _run_check(os.path.abspath(root)) == 0


# ---------------------------------------------------------------------------
# predicted_rail_fractions edge cases
# ---------------------------------------------------------------------------


def test_predicted_rail_fractions_extremes(graphs):
    g, _ = graphs
    n = len(g.nodes)
    all_gpu = predicted_rail_fractions(g, np.ones(n))
    assert all_gpu[1] > 0.5 and all_gpu[2] == 0.0  # gpu-dominant, no bus
    all_cpu = predicted_rail_fractions(g, np.zeros(n))
    assert all_cpu[0] > 0.5
    split = predicted_rail_fractions(g, np.full(n, 0.5))
    assert split[2] > 0.0
    assert predicted_rail_fractions(g, np.array([])) is None
