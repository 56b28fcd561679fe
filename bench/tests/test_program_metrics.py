"""The readers of the program's own spans and counters, on a hand-made trace,
and the readings of the accepted metrics on the recorded traces, pinned so
that a change to the reduction shows in them. ``decode_roofline`` and ``mfu``
read a fixed set of decode calls and tokens of both configurations beside
each trace; their pins were read before the costs were looked up by
architecture (``bench/arch.py``)."""
import json
from dataclasses import replace
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import arch
from bench import observed as ob
from bench import served as sv
from bench import trace_reduce as tr

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
COUNTERS = {"prefill_batches": 2, "prefill_batch_requests": 3,
            "plan_lookups": 40, "plan_memo_hits": 30, "plan_cache_hits": 2, "plan_cache_misses": 8,
            "admitted": 4, "queue_wait_us": 60000}
CONFS = {n: json.loads((BENCH / "configs" / f"{n}.json").read_text())
         for n in ("qwen2-7b.pp4", "granite-3-8b.pp4")}
DECODE_CALLS = [("qwen2-7b.pp4", [100, 300, 700]), ("granite-3-8b.pp4", [50, 1000])]
PREFILLS = [("qwen2-7b.pp4", 256), ("granite-3-8b.pp4", 1024)]
DECODED = [("qwen2-7b.pp4", 300), ("granite-3-8b.pp4", 64), ("granite-3-8b.pp4", 65)]


def _observed(name, counters=COUNTERS):
    t = json.loads((DATA / name).read_text())
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    return ob.Observed(trace=t, window=tr.window_of(t), counters=dict(counters), compiles=0,
                       decode_calls=DECODE_CALLS, prefills=PREFILLS, decoded=DECODED,
                       models={n: c["model"] for n, c in CONFS.items()},
                       costs={n: arch.parts(c).costs for n, c in CONFS.items()},
                       peaks=peaks, chips=1)


def test_program_span_readers():
    o = _observed("trace_program_hand.json")
    # two rounds start in the window; the solves inside it cover
    # [0.005, 0.018], [0.06, 0.065] and [0.0795, 0.081]: 19.5 ms
    assert ob.read("plan_solve_ms_per_round", o) == pytest.approx(9.75)
    # each 15-ms decode step waits or plans for 11 ms of it
    assert ob.read("decode_host_ms", o) == pytest.approx(4.0)


def test_program_counter_readers():
    o = _observed("trace_program_hand.json")
    assert ob.read("plan_reuse_share", o) == pytest.approx(80.0)
    assert ob.read("queue_wait_ms", o) == pytest.approx(15.0)


@pytest.mark.parametrize("metric", ["plan_solve_ms_per_round", "decode_host_ms",
                                    "plan_reuse_share", "queue_wait_ms"])
def test_program_readers_find_nothing_in_a_program_without_them(metric):
    """A program with no ``repro.*`` spans and no such counters: no reading,
    and no error."""
    o = _observed("trace_hand.json", {"prefill_batches": 2, "prefill_batch_requests": 3})
    assert ob.read(metric, o) is None


ACCEPTED = {
    "trace_hand.json": {"round_ms": 90.0, "admit_ms_per_round": 30.0, "plan_ms_per_round": 5.0,
                        "decode_call_ms": 21.0, "device_idle_share": 65.0, "prefill_rows_per_batch": 1.5,
                        "decode_roofline": 53.72097797313797, "mfu": 25.479247601705584},
    "trace_chip.json": {"round_ms": 40.4314615, "admit_ms_per_round": 6.4833105,
                        "plan_ms_per_round": 24.57469, "decode_call_ms": 9.2488605,
                        "device_idle_share": 91.339404, "prefill_rows_per_batch": 1.5,
                        "decode_roofline": 75.4423139038805, "mfu": 10.191699040682234},
}
IDLE = {
    "trace_hand.json": [["_serve_round", 0.035], ["_admit", 0.03]],
    "trace_chip.json": [["no span", 0.169183605], ["_plan_for", 0.05369483], ["prefill_batch", 0.003244565],
                        ["_admit", 0.002220307], ["decode_pool", 5.203e-06]],
}


@pytest.mark.parametrize("name", sorted(ACCEPTED))
def test_accepted_readings_unchanged(name):
    o = _observed(name)
    for metric, want in ACCEPTED[name].items():
        assert ob.read(metric, o) == pytest.approx(want, rel=1e-6), metric
    got = tr.idle_by_span(o.trace, o.window)
    assert [k for k, _ in got] == [k for k, _ in IDLE[name]]
    assert [v for _, v in got] == pytest.approx([v for _, v in IDLE[name]], rel=1e-6)


def test_traced_ttft_reads_as_the_end_to_end_reader():
    """``ttft_p90_s.traced`` is ``ttft_p90_s`` read in the traced run: the
    same requests give the same number; no requests, no reading."""
    recs = [SimpleNamespace(due=10.0 + i, tokens=[10.0 + i + 0.01 * (i + 1)], error=None)
            for i in range(20)]
    recs.append(SimpleNamespace(due=29.5, tokens=[], error=None))  # still waiting at the close
    s = sv.Served(recs, 10.0, 30.0, 5.0)
    o = replace(_observed("trace_hand.json"), served=s)
    assert ob.read("ttft_p90_s.traced", o) == sv.read("ttft_p90_s", s)
    assert ob.read("ttft_p90_s.traced", o) == pytest.approx(0.19)
    assert ob.read("ttft_p90_s.traced", _observed("trace_hand.json")) is None
    assert ob.read("ttft_p90_s.traced", replace(o, served=sv.Served([], 10.0, 30.0, 5.0))) is None


def test_benchmark_metrics_have_readers_and_move_what_their_cells_report():
    """Every per-layer metric of ``BENCHMARK.json`` has its reader, and the
    end-to-end metric it moves is reported in every cell that reads it."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: set(m.get("workloads", cells)) for m in bench["end_to_end"]}
    assert all(c in e2e["setup_s"] for c in cells)
    for m in bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]
        assert m["moves"] in e2e and m["moves"] != "setup_s", m["name"]
        assert set(m.get("workloads", cells)) <= e2e[m["moves"]], m["name"]
    for c in cells:
        assert any(c in w for n, w in e2e.items() if n != "setup_s"), c
