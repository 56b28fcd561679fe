"""Fleet replay subsystem: trace determinism, population sampling,
hand-computed aggregate math, replay determinism, serving backend."""
import json

import numpy as np
import pytest

from repro.fleet import (
    SCENARIOS,
    TIERS,
    DeviceMetrics,
    DeviceReplay,
    FleetReplay,
    FleetReport,
    RequestRecord,
    latency_percentiles,
    make_trace,
    sample_population,
)
from repro.fleet.workloads import ASSISTANT

# ---------------------------------------------------------------------------
# trace generators
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_trace_determinism(scenario):
    a = make_trace(scenario, duration_s=10.0, seed=3)
    b = make_trace(scenario, duration_s=10.0, seed=3)
    assert a.requests == b.requests  # same seed => byte-identical trace
    c = make_trace(scenario, duration_s=10.0, seed=4)
    assert a.requests != c.requests  # different seed => different arrivals


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_trace_fields_and_ordering(scenario):
    t = make_trace(scenario, duration_s=10.0, seed=0)
    assert len(t) > 0
    arrivals = [r.t_arrival_s for r in t]
    assert arrivals == sorted(arrivals)
    assert [r.uid for r in t] == list(range(len(t)))  # uids in arrival order
    for r in t:
        assert 0.0 <= r.t_arrival_s < t.duration_s
        assert r.slo_s > 0.0
        if r.model == ASSISTANT:
            assert r.prompt_len > 0 and r.max_new_tokens > 0


def test_unknown_scenario_rejected():
    with pytest.raises(ValueError, match="unknown scenario"):
        make_trace("nope")


# ---------------------------------------------------------------------------
# population sampler
# ---------------------------------------------------------------------------


def test_population_determinism_and_tier_mix():
    a = sample_population(8, seed=1)
    b = sample_population(8, seed=1)
    assert a == b
    # largest-remainder apportionment of the default 25/50/25 mix
    tiers = [p.tier for p in a]
    assert tiers.count("flagship") == 2
    assert tiers.count("mid") == 4
    assert tiers.count("low") == 2
    for p in a:
        assert p.tier in TIERS
        lo, hi = TIERS[p.tier].battery_j
        assert lo <= p.battery_capacity_j <= hi


def test_population_tiers_order_performance():
    pop = sample_population(12, seed=0)
    mean_gflops = {
        tier: np.mean([p.gpu_spec.gflops_per_ghz for p in pop if p.tier == tier])
        for tier in ("flagship", "mid", "low")}
    assert mean_gflops["flagship"] > mean_gflops["mid"] > mean_gflops["low"]


def test_device_profile_builds_working_sim():
    p = sample_population(3, seed=2)[-1]
    sim = p.make_sim()
    assert sim.battery_pct == 100.0
    sim.drain(p.battery_capacity_j / 2)
    assert sim.battery_pct == pytest.approx(50.0)
    sim.advance_idle(1.0)  # leakage drain + relaxed dynamics
    assert sim.battery_pct < 50.0
    # calibration factory sweeps stock presets on THIS device's silicon
    cal = p.sim_factory()("high", 7)
    assert cal.battery_j is None
    assert cal.cpu_spec == p.cpu_spec


def test_zero_capacity_battery_is_dead_not_absent():
    from repro.core.simulator import DeviceSim

    dead = DeviceSim("moderate", battery_capacity_j=0.0)
    assert dead.battery_pct == 0.0  # dead battery, not "no battery" (100%)
    none = DeviceSim("moderate")
    assert none.battery_pct == 100.0


# ---------------------------------------------------------------------------
# aggregate metric math (hand-computed expectations)
# ---------------------------------------------------------------------------


def _rec(uid, lat, en, slo):
    return RequestRecord(uid=uid, model="m", priority=0, t_arrival_s=0.0,
                         t_done_s=lat, latency_s=lat, energy_j=en,
                         slo_s=slo, slo_met=lat <= slo)


def test_device_metrics_hand_computed():
    recs = [_rec(0, 0.1, 0.02, 0.15), _rec(1, 0.3, 0.04, 0.15)]
    m = DeviceMetrics.from_records("dev-a", "flagship", recs,
                                   battery_start_pct=100.0,
                                   battery_end_pct=99.5)
    assert m.n_requests == 2
    assert m.energy_j == pytest.approx(0.06)
    assert m.energy_per_request_j == pytest.approx(0.03)
    assert m.battery_drain_pct == pytest.approx(0.5)
    assert m.slo_attainment == pytest.approx(0.5)  # r1 misses its 150 ms SLO
    # linear-interpolation percentiles of [0.1, 0.3]
    assert m.latency_s["p50"] == pytest.approx(0.2)
    assert m.latency_s["p95"] == pytest.approx(0.29)
    assert m.latency_s["p99"] == pytest.approx(0.298)


def test_fleet_aggregate_hand_computed():
    dev_a = DeviceMetrics.from_records(
        "dev-a", "flagship",
        [_rec(0, 0.1, 0.02, 0.15), _rec(1, 0.3, 0.04, 0.15)],
        battery_start_pct=100.0, battery_end_pct=99.5,
        counters={"repartitions": 2})
    dev_b = DeviceMetrics.from_records(
        "dev-b", "low", [_rec(2, 0.2, 0.06, 0.5)],
        battery_start_pct=100.0, battery_end_pct=99.0,
        counters={"repartitions": 1})
    rep = FleetReport.build("mixed", 0, 10.0, "graph", [dev_a, dev_b],
                            all_latencies=[0.1, 0.3, 0.2])
    f = rep.fleet
    assert f["n_devices"] == 2
    assert f["tier_counts"] == {"flagship": 1, "low": 1}
    assert f["n_requests"] == 3
    assert f["energy_j"] == pytest.approx(0.12)
    # request-weighted: 0.12 J over 3 requests, NOT the mean of device means
    assert f["energy_per_request_j"] == pytest.approx(0.04)
    assert f["slo_attainment"] == pytest.approx(2.0 / 3.0)
    # per-device mean: each device owns one battery
    assert f["battery_drain_pct_mean"] == pytest.approx(0.75)
    assert f["counters"] == {"repartitions": 3}
    # pooled percentiles over [0.1, 0.2, 0.3]
    assert f["latency_s"]["p50"] == pytest.approx(0.2)
    assert f["latency_s"]["p95"] == pytest.approx(0.29)
    assert f["latency_s"]["p99"] == pytest.approx(0.298)


def test_latency_percentiles_empty_and_single():
    assert latency_percentiles([]) == {"p50": 0.0, "p95": 0.0, "p99": 0.0}
    assert latency_percentiles([0.4]) == {"p50": 0.4, "p95": 0.4, "p99": 0.4}


def test_report_json_roundtrip(tmp_path):
    dev = DeviceMetrics.from_records(
        "dev-a", "mid", [_rec(0, 0.1, 0.02, 0.15)],
        battery_start_pct=100.0, battery_end_pct=99.9)
    rep = FleetReport.build("voice", 7, 5.0, "graph", [dev], [0.1])
    path = tmp_path / "fleet.json"
    rep.write_json(str(path))
    back = FleetReport.read_json(str(path))
    assert back.to_dict() == rep.to_dict()
    # stable serialization (sorted keys) for diffable baselines
    assert json.loads(path.read_text()) == rep.to_dict()


# ---------------------------------------------------------------------------
# replay harness
# ---------------------------------------------------------------------------


def _small_replay(scenario="ar", **opts):
    pop = sample_population(1, seed=5)
    return FleetReplay(pop, scenario=scenario, duration_s=1.5, seed=5,
                       calib_samples=120, **opts)


@pytest.mark.parametrize("scenario,opts", [
    ("ar", {}),
    ("ar", {"joint": True}),
    ("ar", {"uncertainty": True}),
    ("video", {}),
], ids=["ar", "ar-joint", "ar-uncertainty", "video"])
def test_replay_graph_backend_deterministic_and_accounts(scenario, opts):
    rep_a = _small_replay(scenario, **opts).run()
    rep_b = _small_replay(scenario, **opts).run()
    assert rep_a.to_dict() == rep_b.to_dict()
    f = rep_a.fleet
    # every arrival is served: joint planning and the interval model change
    # plans, never which requests are served
    assert f["n_requests"] == len(make_trace(scenario, 1.5, seed=5)) > 0
    assert f["energy_per_request_j"] > 0.0
    assert f["battery_drain_pct_mean"] > 0.0  # replay drains the battery
    assert 0.0 <= f["slo_attainment"] <= 1.0
    # interval coverage reaches the report exactly when a model is attached
    assert ("interval_coverage" in f) == bool(opts.get("uncertainty"))
    d = rep_a.devices[0]
    assert d.battery_end_pct < d.battery_start_pct
    assert d.latency_s["p50"] <= d.latency_s["p95"] <= d.latency_s["p99"]
    assert d.counters["repartitions"] >= 1


def test_replay_rejects_unknown_model():
    pop = sample_population(1, seed=0)
    replay = FleetReplay(pop, scenario="video", duration_s=2.0, seed=0,
                         calib_samples=120, graphs={})
    with pytest.raises(ValueError, match="unknown models"):
        replay.run()


@pytest.mark.parametrize("backend", ["nope"])
def test_replay_rejects_unknown_backend(backend):
    pop = sample_population(1, seed=0)
    with pytest.raises(ValueError, match="backend"):
        DeviceReplay(pop[0], {}, backend=backend)


def test_serving_backend_serves_voice_trace():
    jax = pytest.importorskip("jax")
    from repro.configs.base import get_config, reduced
    from repro.models import init_params

    cfg = reduced(get_config("tinyllama-1.1b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    pop = sample_population(1, seed=1)

    def once():
        replay = FleetReplay(pop, scenario="voice", duration_s=20.0, seed=3,
                             calib_samples=120, backend="serving",
                             serving_models={ASSISTANT: (cfg, params)})
        return replay.run()

    rep = once()
    n_trace = len(make_trace("voice", 20.0, seed=3))
    assert rep.fleet["n_requests"] == n_trace  # every arrival served
    assert rep.backend == "serving"
    d = rep.devices[0]
    assert d.battery_end_pct < d.battery_start_pct
    assert all(np.isfinite(v) for v in d.latency_s.values())
    # virtual-time serving is deterministic run-to-run
    assert once().to_dict() == rep.to_dict()


def test_serving_backend_serves_mixed_trace():
    """The serving backend replays the mixed (vision+LLM) diurnal trace on
    one merged virtual timeline: vision/AR frames run through the graph
    path, LLM requests stream through the continuous engine — every arrival
    served, both modalities in the records, deterministically."""
    jax = pytest.importorskip("jax")
    from repro.configs.base import get_config, reduced
    from repro.fleet.replay import DeviceReplay, default_graph_registry
    from repro.fleet.workloads import AR, VISION
    from repro.models import init_params

    cfg = reduced(get_config("tinyllama-1.1b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    pop = sample_population(1, seed=1)
    trace = make_trace("mixed", 3.0, seed=2)
    by_model = trace.summary()["per_model"]
    assert by_model.get(ASSISTANT, 0) > 0  # the trace must mix modalities
    assert by_model.get(VISION, 0) + by_model.get(AR, 0) > 0

    def once():
        dr = DeviceReplay(pop[0], default_graph_registry(),
                          calib_samples=120, backend="serving",
                          serving_models={ASSISTANT: (cfg, params)})
        records, counters = dr.run(trace)
        return records, counters, dr

    records, counters, dr = once()
    assert sorted(r.uid for r in records) == list(range(len(trace)))
    served_models = {r.model for r in records}
    assert ASSISTANT in served_models  # LLM requests went through the engine
    assert served_models & {VISION, AR}  # frames went through the graph path
    assert "repartitions" in counters  # graph-path counters surfaced
    assert all(np.isfinite(r.latency_s) and r.latency_s >= 0 for r in records)
    assert dr.metrics(records, counters).battery_end_pct < 100.0
    # one merged virtual timeline is deterministic run-to-run
    records2, counters2, _ = once()
    assert records == records2 and counters == counters2


def test_serving_backend_rejected_request_counted_not_recorded():
    """A request the engine can never serve (oversized for the worker) is
    rejected with an error Response; the fleet rollup must surface it as a
    counter, not as a served record — no NaN energy in the aggregates, no
    phantom SLO attainment."""
    jax = pytest.importorskip("jax")
    from repro.configs.base import get_config, reduced
    from repro.fleet.replay import DeviceReplay, default_graph_registry
    from repro.fleet.workloads import ASSISTANT_SLO_S, Trace, TraceRequest
    from repro.models import init_params

    cfg = reduced(get_config("tinyllama-1.1b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    pop = sample_population(1, seed=1)
    trace = Trace("voice", 0, 5.0, (
        TraceRequest(0, 0.1, ASSISTANT, ASSISTANT_SLO_S, 1,
                     prompt_len=60, max_new_tokens=30),  # > max_len=64
        TraceRequest(1, 0.2, ASSISTANT, ASSISTANT_SLO_S, 1,
                     prompt_len=10, max_new_tokens=3),
    ))
    dr = DeviceReplay(pop[0], default_graph_registry(), calib_samples=120,
                      backend="serving",
                      serving_models={ASSISTANT: (cfg, params)})
    records, counters = dr.run(trace)
    assert counters["rejected"] == 1
    assert [r.uid for r in records] == [1]
    assert all(np.isfinite(r.energy_j) for r in records)


def test_serving_backend_rejects_model_unknown_to_both_registries():
    jax = pytest.importorskip("jax")
    from repro.configs.base import get_config, reduced
    from repro.models import init_params

    cfg = reduced(get_config("tinyllama-1.1b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    pop = sample_population(1, seed=0)
    # empty graph registry: the video trace's vision frames resolve nowhere
    replay = FleetReplay(pop, scenario="video", duration_s=2.0, seed=0,
                         calib_samples=120, backend="serving", graphs={},
                         serving_models={ASSISTANT: (cfg, params)})
    with pytest.raises(ValueError, match="neither a serving worker nor"):
        replay.run()
