#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest arrival rate it sustains.

    python3 bench/sweep.py --workload <cell> --seconds <s> --rates 3 5 7 9 12

One process builds and warms the cell once, then serves a window at each
rate with new weights and traffic from a new seed, and prints one JSON line
per rate: the TTFT of the window's first and second halves (a backlog that
grows makes the second half wait longer), the requests still waiting at the
close, the tails and the tokens per second. The cell's mix then fixes its
rate at about four fifths of the knee; the benchmark's runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=500)
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT, ROOT / "bench"):
        sys.path.insert(0, str(p))
    import run

    run.use_cache()
    run.chip_or_exit(1)
    from bench import harness, served, traffic

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    mix = traffic.load_mix(cell["traffic"])
    c = harness.Cell(mix, cell["config"], args.seed)
    c.warm()
    for k, rate in enumerate(args.rates):
        seed = args.seed + k
        c.reseed(seed)
        mix["rate_rps"] = rate
        t0, t1, rounds = c.serve(args.seconds, traffic.Schedule(mix, seed, cell["config"]))
        s = served.Served(list(c.records.values()), t0, t1, 0.0)
        tt, g, h = s.ttfts(), s.gaps(), len(s.records) // 2
        print(json.dumps({
            "rate": rate, "requests": len(tt), "rounds": rounds,
            "waiting_at_close": sum(1 for r in s.records if not r.tokens),
            "ttft_p50_s": float(np.median(tt)), "ttft_p90_s": float(np.percentile(tt, 90)),
            "ttft_first_half_s": float(tt[:h].mean()), "ttft_second_half_s": float(tt[h:].mean()),
            "itl_p50_ms": float(np.median(g) * 1e3), "itl_p95_ms": float(np.percentile(g, 95) * 1e3),
            "tokens_per_s": s.tokens_in_window() / s.window_s,
            "late_max_s": max(r.submitted - r.due for r in s.records)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
