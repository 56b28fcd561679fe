"""One run of one cell: build the engine, warm its shapes, serve the window.

The engine is built as ``repro.launch.serve.serve`` builds it (DeviceSim
``moderate``, the GBDT profiler calibrated offline, ``AdaOperScheduler``,
continuous mode), with the benchmark's seeded weights, made by the weights
module of each configuration's architecture (``bench/arch.py``). The harness
owns the loop that ``ServingEngine.run_all`` would run: it submits each
request when it falls due, with ``t_submit`` set to its due time, calls the
engine's round for the busy models, and notes the host time at which each
sequence's token count grows. Nothing of the program is edited; the timing
hooks are wrappers set on the engine's and workers' instances.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import arch, traffic

BENCH_DIR = Path(__file__).resolve().parent
CONFIG_DIR = BENCH_DIR / "configs"


def load_config(name: str) -> dict:
    return json.loads((CONFIG_DIR / f"{name}.json").read_text())


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: its base config
    with the file's sizes. Any size that differs from the base and is not in
    ``reduced`` means the program's config moved: refuse to run."""
    from repro.configs.base import get_config

    base = get_config(conf["base"])
    for k, v in conf["model"].items():
        if k not in conf["reduced"] and getattr(base, k) != v:
            raise ValueError(f"{conf['name']}: {k} is {v} here but {getattr(base, k)} "
                             f"in the program's {conf['base']}, and not listed as reduced")
    return dataclasses.replace(base, **conf["model"])


class CompileClock:
    """Host times of program compiles and cache loads, from JAX's monitoring
    events (``backend_compile_duration`` fires for both)."""

    def __init__(self):
        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.time())


@dataclass
class Record:
    """What one request did, on the host clock (``time.time``)."""
    spec: traffic.RequestSpec
    due: float
    submitted: float
    tokens: List[float] = field(default_factory=list)  # time each token reached the host
    served: Optional[np.ndarray] = None  # the tokens, once finished
    prompt: Optional[np.ndarray] = None
    error: Optional[str] = None


class Cell:
    """An engine serving one cell's residents, with its records."""

    def __init__(self, mix: dict, cell_config: str, seed: int):
        from repro.core import DeviceSim, RuntimeEnergyProfiler, build_transformer_graph
        from repro.serving.engine import AdaOperScheduler, ServingEngine

        self.mix, self.seed = mix, seed
        self.names = traffic.residents(mix, cell_config)
        self.confs = {n: load_config(n) for n in self.names}
        self.cfgs = {n: model_config(c) for n, c in self.confs.items()}
        self.parts = {n: arch.parts(c) for n, c in self.confs.items()}
        # the simulated phone the planner prices against is part of the
        # deployment, not of the traffic: its seed is fixed
        t = time.time()
        sim = DeviceSim("moderate", seed=0)
        profiler = RuntimeEnergyProfiler()
        profiler.offline_calibrate(
            [build_transformer_graph(c, 4, mix["max_len"]) for c in self.cfgs.values()],
            n_samples=1200)
        self.eng = ServingEngine(scheduler=AdaOperScheduler(profiler, sim),
                                 max_slots=mix["max_slots"])
        _log(f"planner calibrated in {time.time() - t:.1f} s")
        for n in self.names:
            t = time.time()
            params = self.parts[n].weights.served_params(seed, self.confs[n]["model"])
            self._check_layout(n, params)
            self.eng.add_model(n, self.cfgs[n], params, max_len=mix["max_len"])
            jax.block_until_ready(params)
            _log(f"{n}: weights made in {time.time() - t:.1f} s")
        self.records: Dict[int, Record] = {}
        self.decode_calls: List[tuple] = []  # (t_end, model, active positions)
        self._last_decode: Dict[str, float] = {}
        self._hook()

    def reseed(self, seed: int) -> None:
        """New weights from ``seed`` in the same workers (their programs stay
        compiled), empty queues and pools, no records: the next window serves
        as a fresh cell would. For the knee sweep and the control readings."""
        self.seed = seed
        for n in self.names:
            w = self.eng.workers[n]
            w.params = None
            w.params = self.parts[n].weights.served_params(seed, self.confs[n]["model"])
            self.eng.queues[n].clear()
            self.eng.pools.pop(n, None)
            jax.block_until_ready(self.eng._pool(n).cache)
        self.records.clear()
        self.decode_calls.clear()

    def _check_layout(self, name, params):
        from repro.models import init_params

        want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), self.cfgs[name]))
        got = jax.eval_shape(lambda: params)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype)
                for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise ValueError(f"{name}: the benchmark's weights do not match the "
                             "program's parameter layout")

    # ---- timing hooks ----

    def _hook(self):
        eng = self.eng
        step = eng.step_continuous

        def step_continuous(model, *a, **k):
            out = step(model, *a, **k)
            self._note_tokens(model, out)
            return out

        eng.step_continuous = step_continuous
        for name, w in eng.workers.items():
            w.decode_pool = self._timed_decode(name, w.decode_pool)

    def _timed_decode(self, model, decode_pool):
        def wrapped(pool_cache, tokens, pos, *a, **k):
            pool = self.eng.pools[model]
            active = [int(pos[s]) for s in pool.active]
            out = decode_pool(pool_cache, tokens, pos, *a, **k)
            t = self._last_decode[model] = time.time()
            self.decode_calls.append((t, model, active))
            return out
        return wrapped

    def _note_tokens(self, model, retired):
        """Give each token that appeared in this step its host time: a first
        token the time the engine stamped after its prefill's host sync, any
        other the end of this step's decode call, which waits for the device."""
        pool = self.eng.pools.get(model)
        seqs = [(s.req.uid, s.tokens, s.t_first) for s in (pool.active.values() if pool else ())]
        for r in retired:
            rec = self.records.get(r.uid)
            if rec is None:
                continue
            if r.error is not None:
                rec.error = r.error
                continue
            rec.served = np.asarray(r.tokens)
            seqs.append((r.uid, r.tokens, rec.due + r.ttft_s))  # t_submit is the due time
        for uid, toks, t_first in seqs:
            rec = self.records.get(uid)
            if rec is None:
                continue
            for i in range(len(rec.tokens), len(toks)):
                rec.tokens.append(t_first if i == 0 else self._last_decode[model])

    # ---- requests ----

    def submit(self, spec: traffic.RequestSpec, due: float) -> None:
        from repro.serving.engine import Request

        cfg = self.cfgs[spec.model]
        prompt = traffic.prompt_tokens(self.seed, spec.uid, spec.prompt_len, cfg.vocab_size)
        self.eng.submit(spec.model, Request(uid=spec.uid, prompt=prompt,
                                            max_new_tokens=spec.max_new, t_submit=due))
        self.records[spec.uid] = Record(spec, due, time.time(), prompt=prompt)

    # ---- set-up ----

    def warm(self) -> None:
        """Run every shape this cell's traffic uses once, through the
        engine's own admission and decode machinery: for each resident, a
        prefill group of each pow2 batch up to ``max_slots`` at each prompt
        class (the program compiles one prefill per exact length and batch),
        groups of every other size for the slicing and argmax that follow a
        prefill, and a decode step over the pool. The pools are then made
        anew, empty."""
        from repro.serving.slots import Request, _ActiveSeq

        eng, slots = self.eng, self.mix["max_slots"]
        classes = sorted(self.mix["prompt"]["classes"])
        uid = -1
        for model in self.names:
            t = time.time()
            pool = eng._pool(model)
            groups = [(c, b) for c in classes for b in _pow2_upto(slots)]
            groups += [(classes[0], g) for g in range(1, slots + 1) if g not in _pow2_upto(slots)]
            for plen, size in groups:
                out, group = [], []
                for _ in range(size):
                    req = Request(uid=uid, prompt=np.ones(plen, np.int32),
                                  max_new_tokens=2, t_submit=time.time())
                    uid -= 1
                    slot = pool.alloc.alloc()
                    seq = pool.active[slot] = _ActiveSeq(req, slot, pos=plen, model=model)
                    group.append(seq)
                eng._prefill_group(model, pool, group, out, 0.0)
                eng.step_continuous(model, check_drift=False)  # decode, retire
            jax.block_until_ready(pool.cache)
            _log(f"{model}: {len(groups)} prefill groups and decode steps warmed in "
                 f"{time.time() - t:.1f} s")
        for model in self.names:
            eng.pools.pop(model)
            jax.block_until_ready(eng._pool(model).cache)
        self.decode_calls.clear()

    def counters(self) -> dict:
        """The engine's counters and every counter of the program's ledger
        (``eng.ledger.counters``); a ledger counter appears once first
        counted, so a delta over the window reads an absent one as 0. Where
        both count under one name, the engine's attribute is kept."""
        eng = self.eng
        return {**eng.ledger.counters,
                "prefill_batches": eng.prefill_batches,
                "prefill_batch_requests": eng.prefill_batch_requests,
                "preemptions": sum(eng.preemptions.values()),
                "drift_events": eng.drift_events,
                "admission_denials": sum(1 for e in eng.admission.log if not e["admit"])}

    # ---- the window ----

    def serve(self, seconds: float, sched: traffic.Schedule) -> tuple:
        """Serve for ``seconds``. Returns (t0, t_end, rounds) on the host clock."""
        eng, mix = self.eng, self.mix
        out: list = []
        closed = mix["loop"] == "closed"
        t0 = time.time()
        if closed:
            for _ in range(mix["clients"]):
                self.submit(sched.next(), t0)
            due = []
        else:
            due = sched.due(seconds)
        i, rounds, t_end = 0, 0, t0 + seconds
        while True:
            now = time.time()
            if now >= t_end:
                break
            while i < len(due) and t0 + due[i].due_s <= now:
                self.submit(due[i], t0 + due[i].due_s)
                i += 1
            busy = [m for m in eng.workers if eng._busy(m)]
            if not busy:
                nxt = t0 + due[i].due_s if i < len(due) else t_end
                time.sleep(max(0.0, min(nxt, t_end) - now))
                continue
            n_out = len(out)
            eng._serve_round(busy, out, 0.0)
            rounds += 1
            if closed:
                # each finished request frees its client, which sends the next
                now = time.time()
                for _ in out[n_out:]:
                    self.submit(sched.next(), now)
        return t0, time.time(), rounds


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _pow2_upto(n: int) -> List[int]:
    out, b = [], 1
    while b <= n:
        out.append(b)
        b *= 2
    return out
