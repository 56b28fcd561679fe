#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # one host of four chips

One chip: tinyllama-1.1b and gemma2-2b, at their published widths in bf16
with random weights from a seed, are resident on one engine together
(AdaOper's concurrent setting) and serve greedy requests through
``repro.launch.serve.serve``. TinyLlama's prefill logits are then checked
against the same weights run in float32 on the host CPU.

Four chips: qwen2-7b serves on a (data=1, model=4) mesh, and TinyLlama's
logits on that mesh are checked against one device.

Everything runs in this one process, which holds the chips. Any failure
raises and exits non-zero, as does a run where JAX finds no TPU. The last
line of stdout is the JSON result; the timings printed before it are
smoke readings from one pass, not benchmarks.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.launch import serve  # noqa: E402
from repro.launch.mesh import make_debug_mesh  # noqa: E402
from repro.models import model as model_lib  # noqa: E402
from repro.serving.workers import ModelWorker  # noqa: E402
from repro.sharding.context import ExecContext  # noqa: E402

REQUESTS, PROMPT_LEN, MAX_NEW = 4, 128, 16
SEED = 0
# Relative L2 error of bf16 last-position logits against the same weights in
# float32. bf16 keeps 8 significant bits, so every stored activation, K/V
# entry and matmul output carries up to 2^-9 (0.2%) relative rounding; over
# a 22-layer stack that compounds to 1-2% (1.0% at 2-4 layers and 1.9% at 22
# narrow layers, bf16 against float32 on a CPU). A wrong mask, position or
# weight gives an error of order 1, far outside 0.05.
REF_TOL = 0.05
# Mesh against one device, both bf16: only the order of the sharded
# reductions differs, each worth one bf16 rounding per layer, so the bound
# is the same as against float32.
MESH_TOL = 0.05


class CompileClock:
    """Seconds and names of backend compiles, from JAX's monitoring events."""

    def __init__(self):
        self.seconds, self.names = 0.0, []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.names.append(fun_name)


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def check_responses(eng, responses, n_per_model: int, max_new: int) -> None:
    """Every request came back, whole, with tokens inside its model's vocab."""
    for name, w in eng.workers.items():
        mine = [r for r in responses if r.model == name]
        if len(mine) != n_per_model:
            raise RuntimeError(f"{name}: {len(mine)} of {n_per_model} responses")
        for r in mine:
            if r.error is not None:
                raise RuntimeError(f"{name} uid {r.uid}: {r.error}")
            toks = np.asarray(r.tokens)
            if toks.shape != (max_new,):
                raise RuntimeError(f"{name} uid {r.uid}: {toks.shape[0]} of "
                                   f"{max_new} tokens")
            if toks.min() < 0 or toks.max() >= w.cfg.vocab_size:
                raise RuntimeError(f"{name} uid {r.uid}: token outside the "
                                   f"vocab of {w.cfg.vocab_size}")


def wait_for_pools(eng) -> None:
    for pool in eng.pools.values():
        jax.block_until_ready(pool.cache)


def float32_reference(cfg, params, prompt) -> np.ndarray:
    """Last-position prefill logits of ``params`` upcast to float32 and run
    on the host CPU at the highest matmul precision."""
    cpu = jax.devices("cpu")[0]
    cfg32 = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32),
                           jax.device_put(params, cpu))
        tokens = jnp.asarray(prompt[None])
        cache = model_lib.init_cache(cfg32, 1, len(prompt))
        logits, _ = jax.jit(lambda p, t, c: model_lib.prefill(p, cfg32, t, c))(
            p32, tokens, cache)
        return np.asarray(logits[0, -1])


def one_chip(models, *, reduced=False) -> None:
    """Serve ``models`` together on one device, then check the first one's
    prefill logits against float32 on the CPU."""
    clock = CompileClock()
    t0 = time.perf_counter()
    eng, responses = serve.serve(models, requests=REQUESTS, prompt_len=PROMPT_LEN,
                                 max_new=MAX_NEW, seed=SEED, reduced=reduced)
    wait_for_pools(eng)
    first_pass = time.perf_counter() - t0
    check_responses(eng, responses, REQUESTS, MAX_NEW)
    log(f"first pass (set-up and compiles included): {first_pass:.3f} s, "
        f"{len(clock.names)} compiles taking {clock.seconds:.3f} s")

    # second wave on the same engine. Admission may group its requests into
    # prefill batches of other sizes than the first wave's, and each new size
    # compiles, so the compiles inside this wave are counted and printed.
    compiles_before = len(clock.names)
    serve.submit_requests(eng, REQUESTS, PROMPT_LEN, MAX_NEW,
                          np.random.default_rng(SEED + 1), uid0=REQUESTS)
    t0 = time.perf_counter()
    responses = eng.run_all()
    wait_for_pools(eng)
    wall = time.perf_counter() - t0
    check_responses(eng, responses, REQUESTS, MAX_NEW)
    n_tok = sum(len(r.tokens) for r in responses)
    for name in eng.workers:
        ttft = [r.ttft_s for r in responses if r.model == name]
        log(f"{name}: time to first token mean {np.mean(ttft):.4f} s, "
            f"max {np.max(ttft):.4f} s")
    log(f"second wave: {n_tok} tokens in {wall:.3f} s = {n_tok / wall:.1f} tok/s "
        f"over {len(eng.workers)} models, "
        f"compiles inside it: {clock.names[compiles_before:]}")

    name = models[0]
    w = eng.workers[name]
    prompt = np.random.default_rng(SEED + 2).integers(
        1, w.cfg.vocab_size, PROMPT_LEN, dtype=np.int32)
    logits, _ = w.prefill_one(prompt)
    ref = float32_reference(w.cfg, w.params, prompt)
    err = rel_l2(logits[0], ref)
    log(f"{name} prefill logits vs float32 on cpu: relative L2 {err:.6f} "
        f"(tolerance {REF_TOL}), max abs {np.abs(np.asarray(logits[0]) - ref).max():.6f}")
    if not err <= REF_TOL:
        raise RuntimeError(f"{name} logits off the float32 reference: {err} > {REF_TOL}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak bytes in use: {stats.get('peak_bytes_in_use', 'not reported')}")


def four_chips(big: str, small: str, *, reduced=False) -> None:
    """Serve ``big`` on a (1, 4) mesh; check ``small``'s logits on that mesh
    against one device."""
    ctx = ExecContext(mesh=make_debug_mesh(1, 4), batch_axes=("data",),
                      model_axis="model")
    workers = {}
    for name in (big, small):
        t0 = time.perf_counter()
        eng, responses = serve.serve([name], requests=REQUESTS,
                                     prompt_len=PROMPT_LEN, max_new=MAX_NEW,
                                     seed=SEED, reduced=reduced, ctx=ctx)
        wait_for_pools(eng)
        check_responses(eng, responses, REQUESTS, MAX_NEW)
        w = workers[name] = eng.workers[name]
        log(f"{name} on the (1, 4) mesh: {len(responses)} requests complete in "
            f"{time.perf_counter() - t0:.3f} s (set-up and compiles included); "
            f"{w.shard_report.sharded} dims sharded, "
            f"{w.shard_report.replicated} replicated")
    for i, d in enumerate(jax.devices()):
        log(f"device {i} peak bytes in use: "
            f"{(d.memory_stats() or {}).get('peak_bytes_in_use', 'not reported')}")

    meshed = workers[small]
    one = ModelWorker(small, meshed.cfg,
                      jax.device_put(meshed.params, jax.devices()[0]),
                      meshed.max_len)
    prompt = np.random.default_rng(SEED + 2).integers(
        1, meshed.cfg.vocab_size, PROMPT_LEN, dtype=np.int32)
    a, cache_a = meshed.prefill_one(prompt)
    b, cache_b = one.prefill_one(prompt)
    tok = np.asarray(jnp.argmax(b, -1), np.int32)[:, None]
    pos = np.array([PROMPT_LEN], np.int32)
    _, a2, _ = meshed.decode_pool(cache_a, tok, pos)
    _, b2, _ = one.decode_pool(cache_b, tok, pos)
    for step, (x, y) in (("prefill", (a, b)), ("first decode step", (a2, b2))):
        err = rel_l2(x, y)
        log(f"{small} {step} logits, (1, 4) mesh vs one device: relative L2 "
            f"{err:.6f} (tolerance {MESH_TOL})")
        if not err <= MESH_TOL:
            raise RuntimeError(f"{small} {step}: mesh off one device: {err} > {MESH_TOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {devices[0].platform}); nothing run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found {len(devices)}")
    serve.use_compile_cache()
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    if args.chips == 1:
        one_chip(["tinyllama-1.1b", "gemma2-2b"])
    else:
        four_chips("qwen2-7b", "tinyllama-1.1b")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
