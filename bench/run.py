#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix (``bench/mixes/<mix>.json``).
Set-up builds the engine with seeded weights and runs every shape the mix
uses; then the window serves the mix for ``--seconds``. With ``--trace 0`` the
last line of stdout reports the cell's end-to-end metrics; with ``--trace 1``
its per-layer metrics, read from a profiler trace of the window. Either way,
after the window a sample of the served requests is checked against the
plain float32 reference, and the numbers compared are printed beside their
limits: as the last lines of stderr, and under ``checks`` at the end of the
result line.

Where JAX finds no TPU, or fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()  # set-up is timed from here, before the imports

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def use_cache() -> None:
    """JAX's persistent compile cache, at one fixed path inside the checkout,
    whatever the environment says; every program is kept, however fast it
    compiled, so that a warm run compiles nothing."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chip_or_exit(chips: int):
    """The devices, or exit non-zero where they are not TPUs enough."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench/run.py: JAX found no TPU (platform {devices[0].platform}); nothing run")
    if len(devices) < chips:
        sys.exit(f"bench/run.py: the cell needs {chips} chips, JAX found {len(devices)}")
    return devices


def peaks_for(kind: str) -> dict:
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if kind not in table:
        sys.exit(f"bench/run.py: no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def _for_cell(entries, cell: str):
    return [e for e in entries if "workloads" not in e or cell in e["workloads"]]


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, t_start: float = T_START, bench: dict = None) -> dict:
    """One run; returns the result line's object. ``require_chip=False`` and
    ``bench`` (in place of ``BENCHMARK.json``) let a test drive the rest of a
    run on the CPU."""
    import jax

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import check, harness, served, spans, traffic
    from bench import observed as obs_mod
    from bench import trace_reduce as tr

    if bench is None:
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[workload]
    devices = chip_or_exit(cell["chips"]) if require_chip else jax.devices()
    peaks = peaks_for(devices[0].device_kind) if require_chip else {}
    mix = traffic.load_mix(cell["traffic"])

    clock = harness.CompileClock()
    c = harness.Cell(mix, cell["config"], seed)
    c.warm()
    sched = traffic.Schedule(mix, seed, cell["config"])
    before = c.counters()
    at_start = devices[0].memory_stats() or {}
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        spans.install(c.eng)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    window = jax.profiler.TraceAnnotation("bench.window") if trace else contextlib.nullcontext()
    with window:
        t0, t1, rounds = c.serve(seconds, sched)
    if trace:
        jax.profiler.stop_trace()
    after = c.counters()
    counters = {k: v - before.get(k, 0) for k, v in after.items()}
    stats = devices[0].memory_stats() or {}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": stats.get("peak_bytes_in_use")}

    records = [r for r in c.records.values() if r.due < t1]
    s = served.Served(records, t0, t1, t0 - t_start)
    result = {"correct": False, "attempted": len(records),
              "failed": sum(1 for r in records if r.error is not None)}
    names = _for_cell(bench["per_layer" if trace else "end_to_end"], workload)
    metrics = {}
    breakdown = None
    if trace:
        tdata = tr.from_xplane(tr.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        win = tr.window_of(tdata)
        prefills, decoded = [], []
        for r in records:
            for k, t in enumerate(r.tokens):
                if t0 <= t <= t1:
                    if k == 0:
                        prefills.append((r.spec.model, r.spec.prompt_len))
                    else:
                        decoded.append((r.spec.model, r.spec.prompt_len + k - 1))
        o = obs_mod.Observed(
            trace=tdata, window=win,
            counters=counters,
            compiles=sum(1 for t in clock.times if t0 <= t <= t1),
            decode_calls=[(m, a) for t, m, a in c.decode_calls if t0 <= t <= t1],
            prefills=prefills, decoded=decoded,
            models={n: conf["model"] for n, conf in c.confs.items()},
            costs={n: p.costs for n, p in c.parts.items()},
            peaks=peaks, chips=cell["chips"], served=s)
        for m in names:
            v = obs_mod.read(m["name"], o)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        busy = tr.busy_seconds(tdata, win)
        device.update(busy_s=busy, window_s=o.window_s)
        breakdown = {"device_ops": tr.top_ops(tdata, win), "idle_gaps": tr.idle_by_span(tdata, win)}
    else:
        for m in names:
            v = served.read(m["name"], s)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    gaps, ttfts = s.gaps(), s.ttfts()
    half = len(ttfts) // 2
    info = {"ttft_p50_s": float(np.median(ttfts)) if ttfts.size else None,
            "itl_p50_ms": float(np.median(gaps)) * 1e3 if gaps.size else None,
            # a backlog that grows through the window: later requests wait longer
            "ttft_mean_first_half_s": float(ttfts[:half].mean()) if half else None,
            "ttft_mean_second_half_s": float(ttfts[half:].mean()) if half else None,
            "rounds": rounds, "window_s": s.window_s, "requests": len(records),
            "finished": sum(1 for r in records if r.served is not None),
            "first_tokens": sum(1 for r in records if r.tokens), "token_gaps": int(gaps.size),
            "tokens": s.tokens_in_window(),
            "late_mean_s": sum(r.submitted - r.due for r in records) / max(len(records), 1),
            "late_max_s": max((r.submitted - r.due for r in records), default=0.0),
            "compiles_in_window": sum(1 for t in clock.times if t0 <= t <= t1),
            # resident bytes (weights, pools) beside the peak, which holds the
            # transients of set-up's and the window's prefill and decode programs
            "bytes_in_use_at_window_start": at_start.get("bytes_in_use"),
            "peak_bytes_at_window_start": at_start.get("peak_bytes_in_use"),
            "counters": counters}
    log(f"window: {json.dumps(info)}")

    confs = c.confs
    del c, s
    gc.collect()
    t_ref = time.time()
    correct, checks = check.verdict(confs, seed, records)
    info["reference_s"] = time.time() - t_ref
    result.update(correct=correct, metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["info"] = info
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    use_cache()
    result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
