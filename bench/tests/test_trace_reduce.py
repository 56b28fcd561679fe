"""The reduction from a trace to busy time, op times and labelled idle gaps."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def test_hand_made_trace():
    t = json.loads((DATA / "trace_hand.json").read_text())
    w = tr.window_of(t)
    assert w == (0.0, 0.1)
    # ops cover [0, 0.02], [0.05, 0.06] and [0.095, 0.1] of the window
    assert tr.busy_seconds(t, w) == pytest.approx(0.035)
    assert tr.idle_by_span(t, w) == [["_serve_round", pytest.approx(0.035)],
                                     ["_admit", pytest.approx(0.03)]]
    assert tr.top_ops(t, w)[0] == ["jit__decode_impl:fusion.2", pytest.approx(0.015)]
    assert tr.module_seconds(t, w, "_decode_impl") == (pytest.approx(0.02), 1)
    # decide lies inside _admit: the union counts it once
    assert tr.span_seconds(t, w, ["_admit", "admission.decide"]) == (pytest.approx(0.03), 2)


def test_recorded_chip_trace():
    """The first quarter second of a traced duo-chat window on a TPU v5e, as
    from_xplane read it (op names shortened)."""
    t = json.loads((DATA / "trace_chip.json").read_text())
    w = tr.window_of(t)
    busy = tr.busy_seconds(t, w)
    assert 0 < busy < w[1] - w[0]
    idle = sum(s for _, s in tr.idle_by_span(t, w, top=1000))
    assert busy + idle == pytest.approx(w[1] - w[0])
    secs, n = tr.module_seconds(t, w, "_decode_impl")
    assert n > 0 and 0 < secs <= busy
    assert tr.span_seconds(t, w, ["_serve_round"])[1] > 0


def test_from_xplane_reads_host_spans(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench._serve_round"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.from_xplane(tr.find_xplane(str(tmp_path)))
    w = tr.window_of(t)
    rounds = t["spans"]["bench._serve_round"]
    assert len(rounds) == 3
    assert all(w[0] <= s < e <= w[1] for s, e in rounds)


@pytest.fixture(scope="module")
def traced_cell(tmp_path_factory):
    """A tiny closed-loop cell built, warmed and served for a second and a
    half under the profiler, with the benchmark's spans installed as in a
    traced run; returns (cell, trace, counters before, counters after)."""
    from bench import harness, spans, traffic

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "CONFIG_DIR", DATA)
        mp.setattr(traffic, "MIX_DIR", DATA)
        mix = traffic.load_mix("tiny-closed")
        c = harness.Cell(mix, "tiny-granite", 2**32 + 9)
        c.warm()
        before = c.counters()
        spans.install(c.eng)
        log_dir = str(tmp_path_factory.mktemp("trace"))
        jax.profiler.start_trace(log_dir)
        with jax.profiler.TraceAnnotation("bench.window"):
            c.serve(1.5, traffic.Schedule(mix, 2**32 + 9, "tiny-granite"))
        jax.profiler.stop_trace()
        return c, tr.from_xplane(tr.find_xplane(log_dir)), before, c.counters()


def test_from_xplane_keeps_program_spans_beside_the_benchmarks(traced_cell):
    _, t, _, _ = traced_cell
    w = tr.window_of(t)
    assert {"bench._serve_round", "bench.decode_pool", "repro.engine.round",
            "repro.decode.step"} <= set(t["spans"])
    assert all(n.startswith(("bench.", "repro.")) for n in t["spans"])
    # the program's spans change no reading of the benchmark's: span_seconds
    # on the trace reads what it reads on the benchmark's spans alone
    alone = {"spans": {n: v for n, v in t["spans"].items() if n.startswith("bench.")}}
    for names in (["_serve_round"], ["_admit"], ["_plan_for", "_prefill_plan_for", "admission.decide"]):
        assert tr.span_seconds(t, w, names) == tr.span_seconds(alone, w, names)
    rounds = tr.span_seconds(t, w, ["_serve_round"])[1]
    assert rounds > 0
    assert len([s for s, _ in t["spans"]["repro.engine.round"] if w[0] <= s < w[1]]) == rounds
    # an idle moment inside a program span is named by its whole name
    index = tr.SpanIndex(t["spans"])
    s, e = t["spans"]["repro.decode.step"][-1]
    assert index.label((s + e) / 2).startswith("repro.")


def test_cell_counters_hold_the_programs_ledger(traced_cell):
    c, _, before, after = traced_cell
    for key in ("plan_lookups", "admitted", "queue_wait_us"):
        assert key in after
        assert after[key] - before.get(key, 0) > 0
    assert after["prefill_batches"] == c.eng.prefill_batches  # the engine's own are kept
