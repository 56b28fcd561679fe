"""The readers of the program's own spans and counters, on a hand-made trace,
and the readings of the accepted metrics on the recorded traces, pinned so
that a change to the reduction shows in them."""
import json
from pathlib import Path

import pytest

from bench import observed as ob
from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
COUNTERS = {"prefill_batches": 2, "prefill_batch_requests": 3,
            "plan_lookups": 40, "plan_memo_hits": 30, "plan_cache_hits": 2, "plan_cache_misses": 8,
            "admitted": 4, "queue_wait_us": 60000}


def _observed(name, counters=COUNTERS):
    t = json.loads((DATA / name).read_text())
    return ob.Observed(trace=t, window=tr.window_of(t), counters=dict(counters), compiles=0,
                       decode_calls=[], prefills=[], decoded=[], models={}, peaks={}, chips=1)


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
                        "decode_call_ms": 21.0, "device_idle_share": 65.0, "prefill_rows_per_batch": 1.5},
    "trace_chip.json": {"round_ms": 40.4314615, "admit_ms_per_round": 6.4833105,
                        "plan_ms_per_round": 24.57469, "decode_call_ms": 9.2488605,
                        "device_idle_share": 91.339404, "prefill_rows_per_batch": 1.5},
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
