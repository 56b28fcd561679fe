"""The control of ``correct`` at a test size: the reference computed in fp8,
put in the program's place, comes out not correct through the same
``check.verdict`` that passes the program's own tokens (``bench/control.py``
makes the same readings on the chip at the cells' own sizes)."""
from pathlib import Path


from bench import control, harness, traffic

DATA = Path(__file__).resolve().parent / "data"
BENCH = {"workloads": [{"name": "t-closed", "config": "tiny-granite", "traffic": "tiny-closed",
                        "chips": 1}]}


def test_fp8_control_fails_and_program_passes(monkeypatch):
    monkeypatch.setattr(harness, "CONFIG_DIR", DATA)
    monkeypatch.setattr(traffic, "MIX_DIR", DATA)
    limit = harness.load_config("tiny-granite")["limits"]["served_gap"]
    rows = list(control.readings("t-closed", [11, 12], 3.0, bench=BENCH, require_chip=False))
    assert len(rows) == 2
    for row in rows:
        prog, ctrl = row["program"]["gap.tiny-granite"], row["control"]["gap.tiny-granite"]
        assert prog["tokens"] >= 200, row
        assert row["program_correct"] and prog["value"] <= limit, row
        assert not row["control_correct"] and ctrl["value"] > limit, row
