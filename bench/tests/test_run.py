"""Runs of the harness on the CPU at a test size: it refuses a CPU-only JAX;
driven past that look, a clean run comes out correct, and a run whose served
tokens are altered where they are produced comes out not correct."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import harness, traffic

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
BENCH = {
    "workloads": [{"name": "t-duo", "config": "tiny-qwen", "traffic": "tiny-duo", "chips": 1},
                  {"name": "t-closed", "config": "tiny-granite", "traffic": "tiny-closed", "chips": 1}],
    "end_to_end": [{"name": "ttft_p90_s", "unit": "s"}, {"name": "itl_p95_ms", "unit": "ms"},
                   {"name": "tokens_per_s", "unit": "tokens/s", "workloads": ["t-closed"]},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}


def test_refuses_cpu_only_jax():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "duo-chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setattr(harness, "CONFIG_DIR", DATA)
    monkeypatch.setattr(traffic, "MIX_DIR", DATA)
    sys.path.insert(0, str(ROOT / "bench"))
    import run
    return run


@pytest.mark.parametrize("workload", ["t-duo", "t-closed"])
def test_clean_run_is_correct(tiny, workload):
    res = tiny.run(workload, 2**33 + 1, 3.0, False, require_chip=False, bench=BENCH)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in BENCH["end_to_end"]
            if "workloads" not in m or workload in m["workloads"]}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    json.dumps(res)


def test_altered_token_is_not_correct(tiny, monkeypatch):
    """A token altered where it is produced: the decode step's greedy token
    of every active slot is moved to its neighbour in the vocabulary."""
    from repro.serving.workers import ModelWorker

    decode_pool = ModelWorker.decode_pool

    def altered(self, *a, **k):
        toks, logits, cache = decode_pool(self, *a, **k)
        return (toks + 1) % self.cfg.vocab_size, logits, cache

    monkeypatch.setattr(ModelWorker, "decode_pool", altered)
    res = tiny.run("t-closed", 5, 3.0, False, require_chip=False, bench=BENCH)
    assert not res["correct"]
    assert max(c["value"] for c in res["checks"].values()) > 0.5


def test_altered_first_token_is_not_correct(tiny, monkeypatch):
    """The token admission's prefill produces is altered instead."""
    from repro.serving import admission

    argmax = admission.jnp.argmax

    class Shifted:
        def __getattr__(self, name):
            return getattr(np, name) if name != "argmax" else (
                lambda x, axis=-1: (argmax(x, axis) + 1) % x.shape[-1])

    monkeypatch.setattr(admission, "jnp", Shifted())
    res = tiny.run("t-duo", 6, 3.0, False, require_chip=False, bench=BENCH)
    assert not res["correct"]
