"""Fault injection + graceful degradation (docs/robustness.md).

Unit coverage for the chaos layer (`repro.faults`): plan validation and
seeded determinism, the injector state machine and its ledger audit trail,
processor-fallback replanning, bounded transient-op retries, throttle caps,
battery exhaustion, the serving engine's deadline/shedding machinery, and
the end-to-end chaos replay invariant — every admitted request ends in a
completion or an explicit error, with counters reconciling against ledger
events exactly.
"""
import math
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core import (
    AdaOperController,
    DeviceSim,
    RuntimeEnergyProfiler,
    build_yolo_graph,
)
from repro.core.telemetry import EnergyLedger
from repro.faults import (
    FaultEvent,
    FaultInjector,
    FaultPlan,
    ProcessorFault,
    TransientOpFault,
    chaos_plan,
    pinned_partition,
    surviving_alpha,
)
from repro.serving.robustness import expire_and_shed
from repro.serving.slots import Request, SlotAllocator


@pytest.fixture(scope="module")
def profiler():
    g = build_yolo_graph()
    p = RuntimeEnergyProfiler()
    p.offline_calibrate([g], n_samples=400, seed=0)
    return p


def _op():
    return build_yolo_graph().nodes[4]


# ---------------------------------------------------------------------------
# fault plans
# ---------------------------------------------------------------------------


def test_fault_plan_validates_kinds_and_times():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan([FaultEvent("meteor_strike", 0.0, 1.0)])
    with pytest.raises(ValueError, match="non-negative"):
        FaultPlan([FaultEvent("gpu_dropout", -1.0, 1.0)])


def test_fault_plan_boundaries_order_clears_before_applies():
    """Back-to-back windows hand over cleanly: at the shared instant the
    outgoing fault clears before the incoming one applies; infinite and
    transient events have no clear boundary."""
    plan = FaultPlan([
        FaultEvent("gpu_dropout", 0.0, 1.0),
        FaultEvent("cpu_dropout", 1.0, 1.0),
        FaultEvent("transient_op", 0.5, 0.0, {"count": 2}),
        FaultEvent("battery_critical", 2.0, float("inf")),
    ])
    bounds = plan.boundaries()
    at_1 = [(action, ev.kind) for t, _, action, ev in bounds
            if abs(t - 1.0) < 1e-12]
    assert at_1 == [("clear", "gpu_dropout"), ("apply", "cpu_dropout")]
    actions = [(action, ev.kind) for _, _, action, ev in bounds]
    assert ("clear", "transient_op") not in actions
    assert ("clear", "battery_critical") not in actions


def test_chaos_plan_deterministic_and_scoped():
    a = chaos_plan("chaos_voice", 10.0, seed=5)
    b = chaos_plan("chaos_voice", 10.0, seed=5)
    assert a == b and len(a) == 4
    assert a != chaos_plan("chaos_voice", 10.0, seed=6)
    assert chaos_plan("voice", 10.0, seed=5) is None  # non-chaos: no plan
    assert chaos_plan("mixed", 10.0, seed=5) is None
    kinds = a.summary()
    assert kinds == {"mem_pressure": 1, "gpu_dropout": 1,
                     "thermal_throttle": 1, "battery_critical": 1}


# ---------------------------------------------------------------------------
# injector state machine + ledger audit
# ---------------------------------------------------------------------------


def _overlap_plan():
    return FaultPlan([
        FaultEvent("mem_pressure", 1.0, 1.0, {"inflation": 1.6}),
        FaultEvent("gpu_dropout", 3.0, 2.0),
        FaultEvent("thermal_throttle", 4.0, 2.0, {"scale": 0.5}),
        FaultEvent("battery_critical", 7.0, float("inf")),
    ])


def test_injector_transitions_compose_and_audit():
    sim = DeviceSim("moderate", seed=0)
    inj = FaultInjector(sim, _overlap_plan())
    assert sim.faults is inj and sim.fault_epoch == 0

    assert sim.advance_faults(0.5) == 0  # nothing scheduled yet
    sim.advance_faults(1.5)
    assert sim.lat_inflation == pytest.approx(1.6)
    sim.advance_faults(3.5)  # mem_pressure cleared, gpu down
    assert sim.lat_inflation == 1.0
    assert sim.faulted_rails == frozenset({"gpu"})
    sim.advance_faults(4.5)  # throttle overlaps the dropout
    assert sim.faulted_rails == frozenset({"gpu"})
    cap = sim.freq_cap
    assert cap is not None
    assert cap[0] == pytest.approx(
        max(sim.cpu_spec.f_min_ghz, 0.5 * sim.preset["cpu_f"]))
    assert sim.state.cpu_f <= cap[0] and sim.state.gpu_f <= cap[1]
    sim.advance_faults(5.5)  # dropout cleared, throttle still active
    assert sim.faulted_rails == frozenset() and sim.freq_cap is not None
    sim.advance_faults(8.0)  # throttle cleared; battery_critical forever
    assert sim.freq_cap is None and sim.battery_critical
    assert inj.done()

    c = sim.ledger.counters
    assert c["faults"] == 4 and c["recoveries"] == 3
    kinds = [ev.kind for ev in sim.ledger.events]
    assert kinds.count("fault") == c["faults"]
    assert kinds.count("recovery") == c["recoveries"]
    # every transition bumped the epoch exactly once
    assert sim.fault_epoch == c["faults"] + c["recoveries"]


def test_freq_cap_pins_the_dvfs_walk():
    sim = DeviceSim("high", seed=1)
    FaultInjector(sim, FaultPlan(
        [FaultEvent("thermal_throttle", 0.0, 100.0, {"scale": 0.5})]))
    sim.advance_faults(0.0)
    for _ in range(50):
        sim.step(0.05)
        assert sim.state.cpu_f <= sim.freq_cap[0] + 1e-12
        assert sim.state.gpu_f <= sim.freq_cap[1] + 1e-12


def test_dropped_rail_raises_and_mem_pressure_inflates():
    sim = DeviceSim("moderate", seed=0)
    op = _op()
    lat0, _ = sim.exec_op_rails(op, 0.5, 0.5)
    sim.faulted_rails = frozenset({"gpu"})
    with pytest.raises(ProcessorFault, match="gpu"):
        sim.exec_op_rails(op, 0.5, 0.5)
    lat_cpu, _ = sim.exec_op_rails(op, 0.0, 0.0)  # survivor still executes
    assert lat_cpu > 0
    sim.faulted_rails = frozenset()
    sim.lat_inflation = 1.6
    lat1, _ = sim.exec_op_rails(op, 0.5, 0.5)
    assert lat1 == pytest.approx(1.6 * lat0)


def test_attribution_calls_bypass_fault_checks():
    """`rail_fractions` re-executes plans for ledger attribution only — it
    must neither trip rail faults nor drain the transient budget."""
    sim = DeviceSim("moderate", seed=0)
    g = build_yolo_graph()
    alphas = np.full(len(g.nodes), 0.5)
    sim.faulted_rails = frozenset({"gpu"})
    sim.transient_fails = 3
    fr = sim.rail_fractions(g, alphas)
    assert fr is not None and sim.transient_fails == 3


# ---------------------------------------------------------------------------
# recovery: pinned plans, epoch invalidation, bounded retries
# ---------------------------------------------------------------------------


def test_surviving_alpha_cases():
    sim = SimpleNamespace(faulted_rails=frozenset())
    assert surviving_alpha(sim) is None
    sim.faulted_rails = frozenset({"gpu"})
    assert surviving_alpha(sim) == 0.0
    sim.faulted_rails = frozenset({"cpu"})
    assert surviving_alpha(sim) == 1.0
    sim.faulted_rails = frozenset({"cpu", "gpu"})
    with pytest.raises(ProcessorFault, match="no surviving"):
        surviving_alpha(sim)


def test_controller_pins_plan_to_survivor_and_restores(profiler):
    sim = DeviceSim("moderate", seed=2)
    ctl = AdaOperController(sim, profiler)
    g = build_yolo_graph()
    ctl.run_inference(g)  # healthy plan cached

    sim.faulted_rails = frozenset({"gpu"})
    sim.fault_epoch += 1
    lat, en = ctl.run_inference(g)
    assert np.isfinite(lat) and np.isfinite(en)
    plan = ctl.plans[g.name]
    assert np.all(plan.alphas == 0.0)  # everything on the surviving CPU
    assert sim.ledger.counters["fault_replans"] >= 1

    sim.faulted_rails = frozenset()
    sim.fault_epoch += 1
    ctl.run_inference(g)
    # restoration replanned against the healthy state: no longer pinned
    assert ctl.plans[g.name] is not plan


def test_transient_op_bounded_retry_recovers(profiler):
    sim = DeviceSim("moderate", seed=3)
    ctl = AdaOperController(sim, profiler)
    g = build_yolo_graph()
    sim.transient_fails = 2
    lat, en, _ = ctl.run_inference_rails(g)
    assert np.isfinite(lat) and sim.transient_fails == 0
    c = sim.ledger.counters
    assert c["op_retries"] == 2
    assert c["recoveries"] == 1  # one recovery record per retried inference
    recov = [ev for ev in sim.ledger.events if ev.kind == "recovery"]
    assert len(recov) == 1 and recov[0].meta["fault"] == "transient_op"


def test_transient_budget_beyond_retries_is_explicit(profiler):
    sim = DeviceSim("moderate", seed=3)
    ctl = AdaOperController(sim, profiler, max_op_retries=2)
    sim.transient_fails = 10_000
    with pytest.raises(TransientOpFault):
        ctl.run_inference_rails(build_yolo_graph())


def test_pinned_partition_prices_the_all_alpha_plan(profiler):
    sim = DeviceSim("moderate", seed=0)
    g = build_yolo_graph()
    cost_fn = profiler.cost_fn(sim.observe())
    plan = pinned_partition(g, cost_fn, 0.0)
    assert np.all(plan.alphas == 0.0)
    assert plan.pred_latency > 0 and plan.pred_energy > 0


# ---------------------------------------------------------------------------
# serving degradation: deadlines, shedding (unit level, no jax)
# ---------------------------------------------------------------------------


def _stub_engine(now=0.0, battery_critical=False, max_retries=1):
    eng = SimpleNamespace(
        queues={"m": []},
        ledger=EnergyLedger(),
        max_retries=max_retries,
        deadline_backoff=1.5,
        shed_below_priority=1,
        scheduler=SimpleNamespace(
            sim=SimpleNamespace(battery_critical=battery_critical)),
    )
    eng._now = lambda: eng._t
    eng._t = now
    return eng


def _pool(active=None):
    alloc = SlotAllocator(4)
    pool = SimpleNamespace(alloc=alloc, active={})
    for req in (active or []):
        pool.active[alloc.alloc()] = SimpleNamespace(req=req)
    return pool


def test_deadline_requeue_backoff_then_explicit_error():
    eng = _stub_engine(now=0.0)
    req = Request(7, np.zeros(4, np.int32), 4, deadline_s=1.0, t_submit=0.0)
    eng.queues["m"] = [req]
    out = []

    eng._t = 2.0  # blown: first expiry requeues with backoff
    expire_and_shed(eng, "m", _pool(), out)
    assert eng.queues["m"] == [req] and not out
    assert req.retries == 1 and req.t_submit == 2.0
    assert req.deadline_s == pytest.approx(1.5)
    assert eng.ledger.counters["deadline_requeues"] == 1

    eng._t = 4.0  # blown again: retries exhausted -> error Response
    expire_and_shed(eng, "m", _pool(), out)
    assert eng.queues["m"] == []
    assert len(out) == 1 and out[0].uid == 7
    assert "deadline exceeded after 1 retries" in out[0].error
    assert math.isnan(out[0].energy_j_pred)
    c = eng.ledger.counters
    assert c["deadline_misses"] == 1 and c["rejected"] == 1
    ev = [e for e in eng.ledger.events if e.kind == "rejected"]
    assert len(ev) == 1 and ev[0].uid == 7


def test_active_resident_evicted_then_requeued():
    eng = _stub_engine(now=5.0)
    req = Request(3, np.zeros(4, np.int32), 4, deadline_s=1.0, t_submit=0.0)
    pool = _pool(active=[req])
    out = []
    expire_and_shed(eng, "m", pool, out)
    assert pool.active == {} and pool.alloc.n_active == 0  # slot freed
    assert eng.queues["m"] == [req] and req.retries == 1
    assert eng.ledger.counters["deadline_evictions"] == 1
    assert not out  # requeued, not yet errored


def test_battery_critical_sheds_below_priority_floor():
    eng = _stub_engine(battery_critical=True)
    bg = Request(0, np.zeros(2, np.int32), 2, priority=0)
    fg = Request(1, np.zeros(2, np.int32), 2, priority=2)
    eng.queues["m"] = [bg, fg]
    out = []
    expire_and_shed(eng, "m", _pool(), out)
    assert eng.queues["m"] == [fg]  # interactive traffic survives
    assert len(out) == 1 and out[0].uid == 0
    assert "shed: battery critical" in out[0].error
    assert eng.ledger.counters["shed"] == 1
    assert eng.ledger.counters["rejected"] == 1


# ---------------------------------------------------------------------------
# battery exhaustion
# ---------------------------------------------------------------------------


def test_battery_clamps_at_zero_and_stamps_time_of_death():
    sim = DeviceSim("moderate", seed=0, battery_capacity_j=1.0)
    sim.now_s = 2.5
    sim.drain(5.0)
    assert sim.battery_j == 0.0 and sim.battery_pct == 0.0
    assert sim.battery_dead and sim.battery_critical
    assert sim.battery_dead_t_s == 2.5
    assert sim.ledger.counters["battery_dead"] == 1
    sim.drain(1.0)  # already dead: stays clamped, no double accounting
    sim.advance_idle(10.0)
    assert sim.battery_j == 0.0
    assert sim.ledger.counters["battery_dead"] == 1
    assert sim.battery_dead_t_s == 2.5


# ---------------------------------------------------------------------------
# error-message ergonomics
# ---------------------------------------------------------------------------


def test_unknown_serving_mode_lists_choices():
    from repro.serving.engine import ServingEngine

    with pytest.raises(ValueError) as exc:
        ServingEngine(mode="bogus")
    assert "continuous" in str(exc.value) and "bucketed" in str(exc.value)


def test_unknown_replay_backend_lists_choices():
    from repro.fleet.population import sample_population
    from repro.fleet.replay import DeviceReplay

    pop = sample_population(1, seed=0)
    with pytest.raises(ValueError) as exc:
        DeviceReplay(pop[0], {}, backend="bogus")
    assert "'graph', 'serving'" in str(exc.value)


def test_unknown_model_error_names_request_uids():
    from repro.fleet.population import sample_population
    from repro.fleet.replay import FleetReplay

    pop = sample_population(1, seed=0)
    replay = FleetReplay(pop, scenario="video", duration_s=2.0, seed=0,
                         calib_samples=120, graphs={})
    with pytest.raises(ValueError) as exc:
        replay.run()
    msg = str(exc.value)
    assert "'vision-det'" in msg and "request uids" in msg and "total" in msg


# ---------------------------------------------------------------------------
# end-to-end: chaos replay + error Responses in fleet reports (jax)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_llm():
    jax = pytest.importorskip("jax")
    from repro.configs.base import get_config, reduced
    from repro.models import init_params

    cfg = reduced(get_config("tinyllama-1.1b"))
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _serving_replay(tiny_llm, pop_seed=1):
    from repro.fleet.population import sample_population
    from repro.fleet.replay import DeviceReplay, default_graph_registry
    from repro.fleet.workloads import ASSISTANT

    cfg, params = tiny_llm
    pop = sample_population(1, seed=pop_seed)
    return DeviceReplay(pop[0], default_graph_registry(), calib_samples=120,
                        backend="serving",
                        serving_models={ASSISTANT: (cfg, params)})


def test_chaos_replay_every_request_accounted_and_reconciled(tiny_llm):
    """The acceptance invariant: a seeded gpu_dropout + thermal_throttle +
    battery_critical chaos replay completes with zero unhandled exceptions,
    every trace request ends as a served record or an explicit rejection,
    and the ledger's fault/recovery/rejected events reconcile exactly with
    the report counters. Deterministic run-to-run."""
    from repro.fleet.workloads import make_trace

    trace = make_trace("chaos_voice", 10.0, seed=5)

    def once():
        dr = _serving_replay(tiny_llm)
        mark = len(dr.sim.ledger.events)
        records, counters = dr.run(trace)
        return dr, records, counters, dr.sim.ledger.events[mark:]

    dr, records, counters, events = once()
    assert counters["faults"] == 4  # the full chaos_voice schedule fired
    assert counters["recoveries"] >= 1
    by_kind = {}
    for ev in events:
        by_kind.setdefault(ev.kind, []).append(ev)
    # counters and events move in lockstep
    assert counters["faults"] == len(by_kind.get("fault", []))
    assert counters["recoveries"] == len(by_kind.get("recovery", []))
    assert counters["rejected"] == len(by_kind.get("rejected", []))
    # every rejection is an explicit shed / deadline miss / abort
    assert counters["rejected"] == (counters.get("shed", 0)
                                    + counters.get("deadline_misses", 0)
                                    + counters.get("aborted", 0))
    # every trace uid ends served or explicitly rejected — nothing silent
    served = {r.uid for r in records}
    rejected = {ev.uid for ev in by_kind.get("rejected", [])}
    assert served | rejected == {r.uid for r in trace}
    assert served.isdisjoint(rejected)
    # degraded-mode replay is deterministic
    _, records2, counters2, _ = once()
    assert records == records2 and counters == counters2
    # the robustness counters surface through the fleet report schema
    m = dr.metrics(records, counters)
    assert m.counters["faults"] == counters["faults"]


def test_deadline_miss_surfaces_as_error_response_in_fleet(tiny_llm):
    """A request whose deadline can never be met (engine-side machinery,
    no fault plan attached) exits via requeue-with-backoff then an explicit
    deadline-miss rejection on the fleet serving backend."""
    from repro.fleet.workloads import (
        ASSISTANT,
        ASSISTANT_SLO_S,
        Trace,
        TraceRequest,
    )

    trace = Trace("voice", 0, 2.0, (
        TraceRequest(0, 0.1, ASSISTANT, ASSISTANT_SLO_S, 1,
                     prompt_len=10, max_new_tokens=4, deadline_s=1e-5),
        TraceRequest(1, 0.2, ASSISTANT, ASSISTANT_SLO_S, 1,
                     prompt_len=10, max_new_tokens=4),
    ))
    dr = _serving_replay(tiny_llm)
    records, counters = dr.run(trace)
    assert [r.uid for r in records] == [1]  # deadline-free request served
    assert counters["rejected"] == 1
    assert counters["deadline_misses"] == 1
    assert counters["deadline_requeues"] >= 1
    ev = [e for e in dr.sim.ledger.events if e.kind == "rejected"]
    assert ev[-1].uid == 0 and "deadline exceeded" in ev[-1].meta["error"]


def test_rejected_requests_reconcile_in_fleet_report(tiny_llm):
    """Satellite invariant: unservable requests (oversized prompt) become
    per-request error accounting end-to-end — ledger `rejected` events, the
    `rejected` counter and the FleetReport counters all agree, and the
    served records exclude them."""
    from repro.fleet.report import FleetReport
    from repro.fleet.workloads import (
        ASSISTANT,
        ASSISTANT_SLO_S,
        Trace,
        TraceRequest,
    )

    trace = Trace("voice", 0, 2.0, (
        TraceRequest(0, 0.1, ASSISTANT, ASSISTANT_SLO_S, 1,
                     prompt_len=60, max_new_tokens=30),  # > max_len=64
        TraceRequest(1, 0.2, ASSISTANT, ASSISTANT_SLO_S, 1,
                     prompt_len=10, max_new_tokens=3),
    ))
    dr = _serving_replay(tiny_llm)
    mark = len(dr.sim.ledger.events)
    records, counters = dr.run(trace)
    rejected_events = [e for e in dr.sim.ledger.events[mark:]
                       if e.kind == "rejected"]
    assert counters["rejected"] == len(rejected_events) == 1
    assert rejected_events[0].uid == 0
    report = FleetReport.build("voice", 0, 2.0, "serving",
                               [dr.metrics(records, counters)],
                               [r.latency_s for r in records])
    assert report.fleet["counters"]["rejected"] == 1
    assert report.fleet["n_requests"] == 1  # the error never became a record
    assert np.isfinite(report.fleet["energy_per_request_j"])
