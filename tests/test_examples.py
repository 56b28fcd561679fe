"""The example scripts run end to end on the CPU and print their summary.

Each case runs one script in its own interpreter, as a user would, and
checks its exit code and a line the script always prints once its work
is done."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TIMEOUT_S = 120

CASES = [
    (["concurrent_serving.py", "--steps", "2"], "admission decisions:"),
    (["fleet_replay.py", "--devices", "2", "--duration", "4"],
     "energy attribution (telemetry ledger):"),
    (["fleet_replay.py", "--devices", "1", "--duration", "3",
      "--backend", "serving"], "energy attribution (telemetry ledger):"),
    (["quickstart.py"], "per-op GPU fractions:"),
    (["energy_adaptation.py"], "current plan (GPU fraction per op):"),
]


@pytest.mark.parametrize("argv,line", CASES, ids=[
    "concurrent_serving", "fleet_replay-graph", "fleet_replay-serving",
    "quickstart", "energy_adaptation"])
def test_example_runs(argv, line):
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join("examples", argv[0]), *argv[1:]],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=TIMEOUT_S)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert line in proc.stdout, proc.stdout[-4000:]
