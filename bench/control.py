#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

For each seed, in one process: serve the cell's mix for ``--seconds`` at its
own load, and judge the window's requests twice through ``check.verdict``:
once with the served tokens (the program's reading, the lower end of a limit,
which has to come out correct), once with the control's tokens at the same
positions, the reference computed in fp8 (weights and the activations that
meet them) put in the program's place (the upper end, which has to come out
not correct). One JSON line per seed. The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CONTROL = "fp8"  # the precision below the configurations' bf16


def readings(workload: str, seeds, seconds: float, *, bench: dict = None,
             require_chip: bool = True):
    import jax

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import check, harness, traffic

    if bench is None:
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[workload]
    if require_chip and jax.devices()[0].platform != "tpu":
        sys.exit("bench/control.py: JAX found no TPU; nothing run")
    mix = traffic.load_mix(cell["traffic"])
    c = None
    for seed in seeds:
        if c is None:
            c = harness.Cell(mix, cell["config"], seed)
            c.warm()
        else:
            c.reseed(seed)  # the programs stay compiled; only the weights change
        c.serve(seconds, traffic.Schedule(mix, seed, cell["config"]))
        records, confs = list(c.records.values()), c.confs
        c.records.clear()
        for w in c.eng.workers.values():
            w.params = None  # room for the reference
        c.eng.pools.clear()
        gc.collect()
        row = {"workload": workload, "seed": seed}
        t = time.time()
        row["program_correct"], row["program"] = check.verdict(confs, seed, records)
        row["reference_s"] = time.time() - t
        row["control_correct"], row["control"] = check.verdict(confs, seed, records, CONTROL)
        yield row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "bench"))
    import run

    run.use_cache()
    for row in readings(args.workload, args.seeds, args.seconds):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
