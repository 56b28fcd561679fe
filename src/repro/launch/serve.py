"""Serving driver: concurrent models + AdaOper energy-aware scheduling.

``python -m repro.launch.serve --models tinyllama-1.1b,gemma2-2b --requests 12``
serves the published configurations (bf16, full width) with random weights
made from ``--seed``; ``--reduced`` serves the 2-layer float32 CPU variants
instead. ``serve()`` is the same path as a function, with an ``ExecContext``
for meshed serving (``chip_smoke.py --chips 4``).
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path

import jax
import numpy as np

from repro.configs.base import get_config, reduced as make_reduced
from repro.core import DeviceSim, RuntimeEnergyProfiler, build_transformer_graph
from repro.models import init_params
from repro.serving.engine import AdaOperScheduler, Request, ServingEngine
from repro.sharding.context import ExecContext

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Keep JAX's persistent compile cache where JAX_COMPILATION_CACHE_DIR
    says; without it, at ``<repo>/.jax_cache``. The path stays fixed from
    run to run so that a later run finds what an earlier one compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def model_params(cfg, seed: int, ctx: ExecContext = ExecContext()):
    """Random weights from ``seed``, made by one jitted init on the device —
    already laid out under ``ctx``'s sharding rules when it has a mesh, so
    no device ever holds a whole model that only fits sharded."""
    def init():
        return init_params(jax.random.PRNGKey(seed), cfg)
    shardings = None
    if ctx.mesh is not None:
        from repro.sharding.partition_specs import params_shardings
        shardings = params_shardings(
            jax.eval_shape(init), cfg, ctx.mesh,
            model_axis=ctx.model_axis or "model",
            batch_axes=tuple(ctx.batch_axes) or ("data",))
    return jax.jit(init, out_shardings=shardings)()


def submit_requests(eng: ServingEngine, n: int, prompt_len: int, max_new: int,
                    rng: np.random.Generator, uid0: int = 0) -> None:
    """``n`` random-prompt requests to every model registered on ``eng``."""
    for name, w in eng.workers.items():
        cfg = w.cfg
        for i in range(n):
            enc = (rng.standard_normal((16, cfg.d_model)).astype(np.float32) * 0.1
                   if cfg.is_encoder_decoder else None)
            eng.submit(name, Request(uid=uid0 + i, max_new_tokens=max_new,
                                     prompt=rng.integers(1, cfg.vocab_size, prompt_len,
                                                         dtype=np.int32),
                                     enc_inputs=enc))


def serve(models, *, requests: int = 8, prompt_len: int = 32, max_new: int = 8,
          workload: str = "moderate", scheduler: bool = True, seed: int = 0,
          reduced: bool = False, ctx: ExecContext = ExecContext()):
    """Register ``models`` on one engine, submit ``requests`` to each and
    serve them all. Returns ``(engine, responses)``."""
    cfgs = {n: make_reduced(get_config(n)) if reduced else get_config(n)
            for n in models}

    sim = DeviceSim(workload, seed=seed)
    profiler = RuntimeEnergyProfiler()
    graphs = [build_transformer_graph(c, 4, prompt_len + max_new)
              for c in cfgs.values()]
    print("calibrating energy profiler (GBDT offline pass)...", flush=True)
    profiler.offline_calibrate(graphs, n_samples=1200)

    sched = AdaOperScheduler(profiler, sim) if scheduler else None
    eng = ServingEngine(scheduler=sched)
    for n, cfg in cfgs.items():
        eng.add_model(n, cfg, model_params(cfg, seed, ctx),
                      max_len=prompt_len + max_new + 8, ctx=ctx)
    submit_requests(eng, requests, prompt_len, max_new,
                    np.random.default_rng(seed))

    print(f"serving {requests} requests x {len(cfgs)} models "
          f"(workload={workload}, scheduler={'adaoper' if sched else 'fifo'})",
          flush=True)
    return eng, eng.run_all()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="tinyllama-1.1b,gemma2-2b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--workload", default="moderate", choices=["idle", "moderate", "high"])
    ap.add_argument("--no-scheduler", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the 2-layer float32 variants (CPU smoke size)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    eng, responses = serve(args.models.split(","), requests=args.requests,
                           prompt_len=args.prompt_len, max_new=args.max_new,
                           workload=args.workload,
                           scheduler=not args.no_scheduler, seed=args.seed,
                           reduced=args.reduced)
    for n, st in eng.stats.items():
        wall = sum(s["wall_s"] for s in st)
        epred = np.nansum([s["pred_energy_j"] for s in st])
        print(f"  {n:22s} iterations={len(st)} wall={wall:.2f}s "
              f"pred_energy={epred*1e3:.1f}mJ")
    print(f"served {len(responses)} responses, "
          f"{sum(len(r.tokens) for r in responses)} tokens")


if __name__ == "__main__":
    main()
