"""The lookup of an architecture's parts by its configuration (``bench/arch.py``),
and a toy architecture that goes through the harness, the check and the cost
readers as new modules only.

The toy, ``toy_gqa``, lives in this file alone. For a test's length its three
modules are put where the lookup finds a new architecture's files
(``bench.weights.toy_gqa``, ``bench.reference.toy_gqa``, ``bench.costs.toy_gqa``).
It wraps dense GQA with a salted seed, so that a harness or a check that made
dense GQA's weights or reference in place of the toy's would serve or score
other numbers, and come out not correct."""
import json
import sys
import types
from pathlib import Path

import jax
import pytest

from bench import arch, harness, traffic
from bench import observed as ob
from bench import run as bench_run
from bench import trace_reduce as tr
from bench.costs import dense_gqa as dense_costs
from bench.reference import dense_gqa as dense_reference
from bench.weights import dense_gqa as dense_weights

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
TOY = "toy_gqa"
SALT = 0x70E
BENCH = {
    "workloads": [{"name": "t-toy", "config": "tiny-toy", "traffic": "tiny-closed", "chips": 1}],
    "end_to_end": [{"name": "ttft_p90_s", "unit": "s"}, {"name": "tokens_per_s", "unit": "tokens/s"}],
    "per_layer": [{"name": n, "unit": u} for n, u in (
        ("mfu", "%"), ("decode_roofline", "%"), ("round_ms", "ms"), ("plan_solve_ms_per_round", "ms"),
        ("plan_reuse_share", "%"), ("queue_wait_ms", "ms"), ("decode_host_ms", "ms"),
        ("ttft_p90_s.traced", "s"))],
}


def toy_modules(log: list, salt: int = SALT) -> list:
    """The toy's weights, reference and costs modules; each call is noted in
    ``log``. ``salt`` 0 makes the weights dense GQA's own."""
    weights = types.ModuleType(f"bench.weights.{TOY}")
    reference = types.ModuleType(f"bench.reference.{TOY}")
    costs = types.ModuleType(f"bench.costs.{TOY}")

    def served_params(seed, m):
        log.append(("weights", seed))
        return dense_weights.served_params(seed ^ salt, m)

    class Reference(dense_reference.Reference):
        def __init__(self, m, seed, quant=None):
            log.append(("reference", seed))
            super().__init__(m, seed ^ SALT, quant)

    def decode_step(m, positions, counters):
        log.append(("costs", m["d_model"], counters))
        return dense_costs.decode_step(m, positions, counters)

    def prefill(m, lengths, counters):
        log.append(("costs", m["d_model"], counters))
        return dense_costs.prefill(m, lengths, counters)

    weights.served_params = served_params
    reference.Reference = Reference
    costs.decode_step, costs.prefill = decode_step, prefill
    return [weights, reference, costs]


def _install(monkeypatch, modules) -> None:
    for mod in modules:
        monkeypatch.setitem(sys.modules, mod.__name__, mod)


@pytest.fixture
def toy(monkeypatch, tmp_path):
    """A configuration of the toy architecture at tiny-granite's sizes and
    its modules in place; returns the call log."""
    log: list = []
    _install(monkeypatch, toy_modules(log))
    conf = json.loads((DATA / "tiny-granite.json").read_text())
    conf.update(name="tiny-toy", reference=TOY)
    (tmp_path / "tiny-toy.json").write_text(json.dumps(conf))
    monkeypatch.setattr(harness, "CONFIG_DIR", tmp_path)
    monkeypatch.setattr(traffic, "MIX_DIR", DATA)
    return log


CONFIGS = [c["name"] for c in json.loads((ROOT / "BENCHMARK.json").read_text())["configs"]]


@pytest.mark.parametrize("name", CONFIGS)
def test_lookup_resolves_each_configuration(name):
    p = arch.parts(harness.load_config(name))
    assert p.weights is dense_weights
    assert p.reference is dense_reference.Reference
    assert p.costs is dense_costs


def test_toy_architecture_runs_correct_through_the_harness(toy):
    seed = 2**33 + 21
    res = bench_run.run("t-toy", seed, 3.0, False, require_chip=False, bench=BENCH)
    assert res["correct"], res["checks"]
    assert res["checks"]["gap.tiny-toy"]["tokens"] > 0
    assert ("weights", seed) in toy and ("reference", seed) in toy


def test_toy_reference_fails_dense_weights(toy, monkeypatch):
    """The control of the test above: the toy's reference against weights
    made without its salt (dense GQA's at the seed) comes out not correct."""
    _install(monkeypatch, toy_modules(toy, salt=0)[:1])
    res = bench_run.run("t-toy", 2**33 + 22, 3.0, False, require_chip=False, bench=BENCH)
    assert not res["correct"]


def test_toy_traced_run_reads_its_costs_and_the_programs_spans(toy, monkeypatch):
    """A traced run with the chip's look and peaks stood in for. On the CPU
    the trace has no device plane, so ``decode_roofline`` reads nothing;
    ``mfu`` reads the toy's costs. The program here also keys each count by
    the resident, as one that counts per model would: the costs are handed
    those counts, and not the engine-wide ones."""
    from repro.core.telemetry import EnergyLedger

    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())["TPU v5 lite"]
    monkeypatch.setattr(bench_run, "chip_or_exit", lambda chips: jax.devices())
    monkeypatch.setattr(bench_run, "peaks_for", lambda kind: peaks)
    count = EnergyLedger.count

    def count_per_resident(self, name, n=1):
        count(self, name, n)
        count(self, f"tiny-toy.{name}", n)

    monkeypatch.setattr(EnergyLedger, "count", count_per_resident)
    res = bench_run.run("t-toy", 2**33 + 23, 3.0, True, bench=BENCH)
    assert res["correct"], res["checks"]
    want = {m["name"] for m in BENCH["per_layer"]} - {"decode_roofline"}
    assert set(res["metrics"]) == want
    assert res["metrics"]["mfu"]["value"] > 0
    window = res["info"]["counters"]
    handed = [e[2] for e in toy if e[0] == "costs"]
    assert handed and all(c == handed[0] for c in handed)
    assert handed[0]["plan_lookups"] == window["tiny-toy.plan_lookups"] == window["plan_lookups"] > 0
    assert handed[0]["admitted"] == window["admitted"] > 0
    assert "prefill_batches" in window and "prefill_batches" not in handed[0]


def test_toy_costs_reach_decode_roofline_and_mfu(monkeypatch):
    """On the recorded chip trace, decode calls and tokens of both residents
    read through the toy's costs as through dense GQA's (whose readings
    ``test_program_metrics.py`` pins), and each resident's costs are handed
    its own counters alone."""
    log: list = []
    _install(monkeypatch, toy_modules(log))
    trace = json.loads((DATA / "trace_chip.json").read_text())
    confs = {n: harness.load_config(n) for n in ("qwen2-7b.pp4", "granite-3-8b.pp4")}
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())["TPU v5 lite"]
    counters = {"plan_lookups": 3, "qwen2-7b.pp4.routed_rows": 11,
                "granite-3-8b.pp4.routed_rows": 22}

    def observed(architecture):
        return ob.Observed(
            trace=trace, window=tr.window_of(trace), counters=counters, compiles=0,
            decode_calls=[("qwen2-7b.pp4", [100, 300, 700]), ("granite-3-8b.pp4", [50, 1000])],
            prefills=[("qwen2-7b.pp4", 256)], decoded=[("granite-3-8b.pp4", 64)],
            models={n: c["model"] for n, c in confs.items()},
            costs={n: arch.parts({"reference": architecture}).costs for n in confs},
            peaks=peaks, chips=1)

    toy, dense = observed(TOY), observed("dense_gqa")
    for metric in ("decode_roofline", "mfu"):
        want = ob.read(metric, dense)
        assert want is not None and 0 < want < 100
        assert ob.read(metric, toy) == want
    own = {confs[n]["model"]["d_model"]: {"routed_rows": counters[f"{n}.routed_rows"]}
           for n in confs}
    assert {d for _, d, _ in log} == set(own)
    assert all(c == own[d] for _, d, c in log)
