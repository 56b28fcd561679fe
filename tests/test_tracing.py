"""Spans and counters inside the serving engine: every ``repro.*`` span
appears in a profiler trace of a served workload, nested as documented, and
the ledger's counters agree with what was served."""
import glob
import os
from collections import defaultdict
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.core import DeviceSim, RuntimeEnergyProfiler, build_transformer_graph
from repro.core.simulator import DeviceState
from repro.core.telemetry import EnergyLedger
from repro.models import init_params
from repro.serving import planning
from repro.serving.engine import AdaOperScheduler, Request, ServingEngine

# span -> the spans it may run inside (docs/serving.md §Tracing)
PARENTS = {
    "repro.engine.round": None,
    "repro.engine.step": {"repro.engine.round"},
    "repro.admission.admit": {"repro.engine.step"},
    "repro.admission.decide": {"repro.admission.admit"},
    "repro.prefill.group": {"repro.admission.admit"},
    "repro.prefill.wait": {"repro.prefill.group"},
    "repro.decode.step": {"repro.engine.step"},
    "repro.decode.wait": {"repro.decode.step"},
    "repro.plan.step": {"repro.admission.decide", "repro.decode.step"},
    "repro.plan.prefill": {"repro.prefill.group"},
    "repro.plan.drift": {"repro.engine.round", "repro.engine.step"},
    "repro.plan.solve": {"repro.plan.step", "repro.plan.prefill"},
    "repro.plan.cost": {"repro.plan.solve", "repro.plan.drift"},
}


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced(get_config("tinyllama-1.1b"))
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def profiler(tiny):
    cfg, _ = tiny
    prof = RuntimeEnergyProfiler(use_gru=False)
    prof.offline_calibrate([build_transformer_graph(cfg, 2, 32)], n_samples=600, seed=0)
    return prof


def _engine(tiny, profiler, max_slots=4, models=("a", "b")):
    cfg, params = tiny
    eng = ServingEngine(scheduler=AdaOperScheduler(profiler, DeviceSim("moderate", seed=0)),
                        max_slots=max_slots)
    for m in models:
        eng.add_model(m, cfg, params, max_len=48)
    return eng


def _submit_mixed(eng, cfg, n=12, seed=5):
    r = np.random.default_rng(seed)
    models = list(eng.workers)
    for i in range(n):
        eng.submit(models[i % len(models)],
                   Request(i, r.integers(1, cfg.vocab_size, int(r.choice([8, 12])), dtype=np.int32),
                           int(r.integers(2, 6))))


def _host_spans(log_dir):
    """{thread line: [(name, start_ns, end_ns)]} of the ``repro.*`` host events."""
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))[-1]
    lines = defaultdict(list)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    lines[(plane.name, line.name)].append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return lines


def test_served_trace_holds_every_span_nested(tiny, profiler, tmp_path):
    cfg, _ = tiny
    eng = _engine(tiny, profiler)
    _submit_mixed(eng, cfg, n=4)
    eng.run_all()  # compiles outside the trace
    _submit_mixed(eng, cfg, seed=6)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    eng.ledger.counters.clear()
    eng._plan_memo.clear()
    eng.scheduler.invalidate()
    profiler.table_cache.clear()
    out = eng.run_all()
    jax.profiler.stop_trace()
    assert len(out) == 12
    lines = _host_spans(str(tmp_path))
    seen = {name for evs in lines.values() for name, _, _ in evs}
    assert seen == set(PARENTS)
    for evs in lines.values():
        for name, s, e in evs:
            parents = PARENTS[name]
            if parents is None:
                continue
            assert any(p in parents and ps <= s and e <= pe for p, ps, pe in evs), (name, s, e)
    rounds = sum(1 for evs in lines.values() for name, _, _ in evs if name == "repro.engine.round")
    solves = sum(1 for evs in lines.values() for name, _, _ in evs if name == "repro.plan.solve")
    assert solves == eng.ledger.counters["plan_cache_misses"] > 0
    assert rounds > 0


def test_counters_agree_on_the_continuous_path(tiny, profiler):
    cfg, _ = tiny
    eng = _engine(tiny, profiler)
    _submit_mixed(eng, cfg, n=16)
    out = eng.run_all()
    c = eng.ledger.counters
    assert c["plan_lookups"] == c["plan_memo_hits"] + c.get("plan_cache_hits", 0) + c["plan_cache_misses"]
    assert c["plan_memo_hits"] > 0
    assert c["admitted"] == len(out) == 16
    assert c["queue_wait_us"] > 0  # 16 requests over 8 slots: some waited
    causes = sum(c.get(k, 0) for k in ("drift_by_state", "drift_by_version", "drift_by_epoch",
                                       "interval_repartitions"))
    assert causes == c.get("engine_drift_events", 0) == eng.drift_events


def test_queue_wait_is_exact_under_the_virtual_clock(tiny, profiler):
    """One slot: the second request waits, on the virtual clock, exactly as
    long as the first took; the third arrives to an idle engine."""
    cfg, _ = tiny
    eng = _engine(tiny, profiler, max_slots=1, models=("a",))
    prompt = np.arange(1, 9, dtype=np.int32)
    arrivals = [(0.0, "a", Request(0, prompt, 3)), (0.0, "a", Request(1, prompt, 3)),
                (50.0, "a", Request(2, prompt, 3))]
    out = {r.uid: r for r in eng.run_trace(arrivals)}
    c = eng.ledger.counters
    assert c["admitted"] == 3
    assert c["queue_wait_us"] == round(out[0].latency_s * 1e6)
    assert out[2].latency_s < 50.0  # it arrived to an idle engine


class _Sim:
    def __init__(self):
        self.state = DeviceState(1.49, 0.5, 0.79, 0.1)
        self.fault_epoch = 0

    def observe(self):
        return self.state


@pytest.mark.parametrize("cause", ["version", "epoch", "state"])
def test_drift_event_counts_its_cause(cause):
    sim = _Sim()
    version = [3]
    prof = SimpleNamespace(correction_version=lambda: version[0], uncertainty=None)
    eng = SimpleNamespace(scheduler=SimpleNamespace(sim=sim, profiler=prof), _drift_ref=None,
                          _plan_memo={"k": {}}, drift_events=0, ledger=EnergyLedger())
    assert planning.drift_event(eng) is False  # the first call sets the reference
    assert planning.drift_event(eng) is False
    if cause == "version":
        version[0] += 1
    elif cause == "epoch":
        sim.fault_epoch += 1
    else:
        sim.state = DeviceState(1.49 - 0.5, 0.5, 0.79, 0.1)
    assert planning.drift_event(eng) is True
    assert eng.ledger.counters == {"engine_drift_events": 1, f"drift_by_{cause}": 1}
    assert eng._plan_memo == {}
