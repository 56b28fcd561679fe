"""Concurrent serving engine with AdaOper energy-aware scheduling.

The paper's setting is several DNN tasks sharing one device; here several
models share the engine. This module is the *orchestrator* of the
``repro.serving`` package — the machinery lives in focused submodules
(``slots``, ``sampling``, ``workers``, ``admission``, ``scheduler``,
``bucketed``, ``planning``, ``decoding``, ``speculative``; see
``docs/architecture.md``) and is
re-exported here so pre-refactor import paths
(``from repro.serving.engine import ...``) keep working
(``tests/test_serving_imports.py``).

Two serving modes (docs/serving.md): ``continuous`` (default, Orca-style
iteration-level scheduling) and ``bucketed`` (the position-synchronous
reference, kept the way ``vectorize=False`` keeps the scalar DP). Every
energy number the engine produces is appended to the device's
:class:`~repro.core.telemetry.EnergyLedger` (``prefill``/``decode`` events
per iteration, one ``request`` event per retirement, split per rail by the
plan's physics fractions) — reports fold the ledger, never engine-private
tallies.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.core.telemetry import EnergyBreakdown, EnergyLedger
from repro.serving import (admission as adm, decoding, planning, robustness,
                           sampling, speculative)
from repro.serving.admission import AdmissionPolicy  # noqa: F401  (re-export)
from repro.serving.bucketed import step_bucketed
from repro.serving.sampling import _sample_rows  # noqa: F401  (re-export)
from repro.serving.scheduler import AdaOperScheduler, combine_rails  # noqa: F401
from repro.serving.slots import (  # noqa: F401  (re-export)
    Request,
    Response,
    SlotAllocator,
    _ActiveSeq,
    _SlotPool,
)
from repro.serving.workers import ModelWorker
from repro.sharding.context import ExecContext


class ServingEngine:
    """``mode="continuous"`` (default) serves at token granularity;
    ``mode="bucketed"`` keeps the position-synchronous reference path."""

    def __init__(self, scheduler: Optional[AdaOperScheduler] = None,
                 mode: str = "continuous", max_slots: int = 8,
                 slo_s: Optional[float] = None, sampling_seed: int = 0,
                 batch_prefill: bool = True, max_retries: int = 1,
                 deadline_backoff: float = 1.5, shed_below_priority: int = 1,
                 risk_level: Optional[float] = None,
                 legacy_drift: bool = False, ssm_prompt_buckets: bool = True):
        if mode not in ("continuous", "bucketed"):
            raise ValueError(f"unknown serving mode {mode!r}; choose from "
                             "('continuous', 'bucketed')")
        self.workers: Dict[str, ModelWorker] = {}
        self.queues: Dict[str, List[Request]] = {}
        self.scheduler = scheduler
        self.stats: Dict[str, list] = {}
        self.mode = mode
        self.max_slots = max_slots
        self.sampling_seed = sampling_seed
        # batched admission: one prefill per same-shape group; False = serial
        self.batch_prefill = batch_prefill
        self.prefill_batches = 0
        self.prefill_batch_requests = 0
        # telemetry spine: the simulator's ledger when a scheduler is attached
        self.ledger: EnergyLedger = (
            scheduler.sim.ledger
            if scheduler is not None and hasattr(scheduler.sim, "ledger")
            else EnergyLedger())
        # uncertainty knobs (docs/uncertainty.md; defaults inert): risk_level
        # prices admission at an interval upper quantile, legacy_drift pins
        # the fixed hysteresis, ssm_prompt_buckets pow2-pads SSM admission
        self.admission = AdmissionPolicy(scheduler, slo_s=slo_s,
                                         risk_level=risk_level)
        self.admission.ledger = self.ledger
        self.legacy_drift = legacy_drift
        self.ssm_prompt_buckets = ssm_prompt_buckets
        self.pools: Dict[str, _SlotPool] = {}
        # speculative decoding state per target model (repro.serving
        # .speculative); empty unless add_model was given a draft
        self.spec: Dict[str, speculative.SpecState] = {}
        self.priorities: Dict[str, int] = {}
        self.preemptions: Dict[str, int] = {}
        self.drift_events = 0
        # drift-scoped step-plan memo (see repro.serving.planning)
        self._plan_memo: Dict = {}
        self._drift_ref = None
        # graceful degradation (repro.serving.robustness): deadline requeue
        # with backoff then error Response; battery-critical priority shedding
        self.max_retries = max_retries
        self.deadline_backoff = deadline_backoff
        self.shed_below_priority = shed_below_priority
        # virtual clock for run_trace: None => wall time; a float advances
        # by predicted prefill/decode latencies
        self._vtime: Optional[float] = None

    def _now(self) -> float:
        return self._vtime if self._vtime is not None else time.time()

    def _advance_vtime(self, dt: float) -> None:
        """Advance the virtual clock (no-op in wall mode) and mirror it to
        the simulator so fault timestamps line up with the replay."""
        if self._vtime is not None:
            self._vtime += dt
            if self.scheduler is not None:
                self.scheduler.sim.now_s = self._vtime

    # ---- sampling delegates (logic in repro.serving.sampling) ----

    def _stream_key(self, model: str, uid):
        return sampling.stream_key(self.sampling_seed, model, uid)

    def _sample(self, model: str, seq: _ActiveSeq, logits,
                temperature: float) -> int:
        """Scalar reference draw for ``_sample_batch``; the stream is
        established lazily from the uid (greedy-admitted sequences can
        switch to sampled decode mid-flight)."""
        if seq.rng is None:
            seq.rng = self._stream_key(model, seq.req.uid)
        return sampling.sample_one(seq, logits, temperature)

    def _sample_batch(self, model: str, seqs: List[_ActiveSeq], logits,
                      temperature: float) -> List[int]:
        for seq in seqs:
            if seq.rng is None:
                seq.rng = self._stream_key(model, seq.req.uid)
        return sampling.sample_batch(seqs, logits, temperature)

    def _row_keys(self, model: str, reqs: List[Request]):
        """Stacked per-request streams for the bucketed path."""
        return jnp.stack([self._stream_key(model, r.uid) for r in reqs])

    # ---- registration + bucketed reference path ----

    def add_model(self, name, cfg, params, max_len=512, ctx=ExecContext(),
                  priority: int = 0, max_enc_len: Optional[int] = None,
                  draft=None, spec=None):
        """``draft=(draft_cfg, draft_params)`` attaches a speculative-
        decoding draft worker to this model (continuous mode; ``spec`` is an
        optional ``SpecConfig``); the default ``draft=None`` keeps every
        decode bit-identical to the pre-speculation engine."""
        self.workers[name] = ModelWorker(name, cfg, params, max_len, ctx,
                                         max_enc_len=max_enc_len)
        self.queues[name] = []
        self.stats[name] = []
        self.priorities[name] = priority
        self.preemptions[name] = 0
        if draft is not None:
            self.spec[name] = speculative.attach_draft(self, name, draft, spec)

    def submit(self, model: str, req: Request):
        if req.t_submit == 0.0:
            req.t_submit = self._now()
        self.queues[model].append(req)

    def step(self, model: str, temperature: float = 0.0) -> List[Response]:
        """Serve one batch from ``model``'s queue (same-length bucket) —
        the position-synchronous reference path (``repro.serving.bucketed``)."""
        return step_bucketed(self, model, temperature)

    # ------------------------------------------------------------------
    # continuous batching (iteration-level scheduling)
    # ------------------------------------------------------------------
    # drift-scoped plan memoisation lives in repro.serving.planning

    def _plan_for(self, model: str, batch: int, seq_len: int, max_new: int):
        return planning.step_plan_for(self, model, batch, seq_len, max_new)

    def _prefill_plan_for(self, model: str, batch: int, prompt_len: int):
        return planning.prefill_plan_for(self, model, batch, prompt_len)

    def _drift_event(self) -> bool:
        return planning.drift_event(self)

    def _pool(self, model: str) -> _SlotPool:
        pool = self.pools.get(model)
        if pool is None:
            pool = self.pools[model] = _SlotPool(self.workers[model], self.max_slots)
        return pool

    def _busy(self, model: str) -> bool:
        return bool(self.queues[model]) or bool(
            model in self.pools and self.pools[model].active)

    def _plan_shape(self, pool: _SlotPool, extra: Optional[Request] = None):
        """(seq-length, remaining-tokens) envelope of the pool for planning."""
        seqs = [int(a.pos) for a in pool.active.values()]
        rems = [a.req.max_new_tokens - len(a.tokens) for a in pool.active.values()]
        if extra is not None:
            seqs.append(len(extra.prompt))
            rems.append(extra.max_new_tokens)
        return max(seqs, default=1), max(max(rems, default=1), 1)

    def _retire(self, pool: _SlotPool, seq: _ActiveSeq, out: List[Response]):
        pool.alloc.free(seq.slot)
        del pool.active[seq.slot]
        energy = seq.energy_j if self.scheduler is not None else float("nan")
        latency = self._now() - seq.req.t_submit
        self.ledger.emit("request", latency, seq.rails, t_s=seq.req.t_submit,
                         model=seq.model, uid=seq.req.uid)
        out.append(Response(seq.req.uid,
                            np.asarray(seq.tokens[: seq.req.max_new_tokens], np.int32),
                            latency, energy, rails=seq.rails,
                            ttft_s=seq.t_first - seq.req.t_submit,
                            model=seq.model))

    # admission machinery lives in repro.serving.admission
    _validate = staticmethod(adm.validate_request)

    def _admit(self, model: str, pool: _SlotPool, out: List[Response],
               temperature: float = 0.0) -> int:
        return adm.admit_requests(self, model, pool, out, temperature)

    def _prefill_group(self, model: str, pool: _SlotPool,
                       group: List[_ActiveSeq], out: List[Response],
                       temperature: float) -> None:
        adm.prefill_group(self, model, pool, group, out, temperature)

    def step_continuous(self, model: str, decode: bool = True,
                        check_drift: bool = True,
                        temperature: float = 0.0) -> List[Response]:
        """One engine iteration for ``model``: admission, one ragged decode
        step over the slot pool, retirement. ``decode=False`` (preempted
        worker) holds the pool's state — no admitted request is dropped;
        ``check_drift=False`` is for drivers that already ran the per-round
        drift check; ``temperature > 0`` samples each slot from its own
        seed-derived stream."""
        if check_drift and self.scheduler is not None:
            self._drift_event()  # direct drivers still invalidate stale plans
        pool = self._pool(model)
        out: List[Response] = []
        # degradation pass first: expired deadlines requeue/error and
        # battery-critical shedding frees queue space before admission
        robustness.expire_and_shed(self, model, pool, out)
        # virtual clock: iterations are timed in _vtime deltas (predicted
        # latencies), not host speed; wall mode measures wall time
        t0 = self._now()
        n_admitted = self._admit(model, pool, out, temperature)
        if decode and pool.active:
            # one decode iteration: speculative draft-verify round for
            # models with a draft attached, the plain ragged step otherwise
            # (machinery in repro.serving.decoding / .speculative)
            decoding.decode_round(self, model, pool, out, temperature, t0)
        if n_admitted or pool.active or out:
            self.stats[model].append({
                "mode": "continuous", "active": len(pool.active),
                "admitted": n_admitted, "retired": len(out),
                "wall_s": self._now() - t0,
                "pred_energy_j": float(sum(r.energy_j_pred for r in out))
                if self.scheduler is not None else float("nan")})
        return out

    def _serve_round(self, busy: List[str], out: List[Response],
                     temperature: float = 0.0) -> None:
        """One continuous round over the busy models: declare the
        co-execution level, run the drift check once, preempt the
        lowest-priority decoding worker on a drift event, then step each
        model at token granularity."""
        if self.scheduler is not None:
            self.scheduler.sim.set_coexec(len(busy))
            # joint planning: the scheduler prices contention per resident
            # set; its plan caches key on residency, but the engine's memo
            # does not — clear it when the busy set moves under a coexec
            # planner (a no-op on the default independent path)
            if (self.scheduler.set_resident(busy)
                    and getattr(self.scheduler, "coexec", None) is not None):
                self._plan_memo.clear()
        victim = None
        if self.scheduler is not None and self._drift_event():
            decoding = [m for m in busy
                        if m in self.pools and self.pools[m].active]
            if len(decoding) > 1:
                # the cached plans just got invalidated: yield the
                # lowest-priority worker's iteration to the
                # higher-priority pools while the planner re-solves
                victim = min(decoding, key=lambda m: (self.priorities[m], m))
                self.preemptions[victim] += 1
                self.ledger.count("preemptions")
        for m in busy:
            out.extend(self.step_continuous(m, decode=(m != victim),
                                            check_drift=False,
                                            temperature=temperature))

    def run_all(self, temperature: float = 0.0) -> List[Response]:
        """Round-robin across models until all queues drain (the paper's
        concurrent-DNN workload); continuous mode interleaves models at
        token granularity under the declared co-execution level."""
        if self.mode == "bucketed":
            out = []
            while any(self.queues.values()):
                for m in list(self.workers):
                    out.extend(self.step(m, temperature))
            return out
        out: List[Response] = []
        while True:
            busy = [m for m in self.workers if self._busy(m)]
            if not busy:
                if self.scheduler is not None:
                    self.scheduler.sim.set_coexec(1)
                break
            self._serve_round(busy, out, temperature)
        return out

    def run_trace(self, arrivals, start_t: float = 0.0,
                  temperature: float = 0.0) -> List[Response]:
        """Trace-driven serving in *virtual* time: ``arrivals`` is an
        iterable of ``(t_arrival_s, model_name, Request)`` (any order). The
        clock starts at ``start_t`` and advances by the planner's
        *predicted* prefill/decode-step latencies; idle gaps jump to the
        next arrival while the simulator relaxes and drains at the leakage
        floor. Latencies are deterministic simulated seconds measured from
        arrival (queueing included). Requires continuous mode + scheduler."""
        if self.mode != "continuous" or self.scheduler is None:
            raise ValueError("run_trace requires mode='continuous' and a "
                             "scheduler (the virtual clock advances by "
                             "predicted step latencies)")
        items = sorted(((float(t), m, r) for t, m, r in arrivals),
                       key=lambda it: it[0])
        models = {m for _, m, _ in items}
        unknown = models - set(self.workers)
        if unknown:
            raise ValueError(
                f"run_trace arrivals name models with no registered worker: "
                f"{sorted(unknown)}")
        sim = self.scheduler.sim
        out: List[Response] = []
        self._vtime = float(start_t)
        i = 0
        try:
            while True:
                # fault/recovery boundaries scheduled up to now take effect
                # before this round (no-op without an attached injector)
                sim.advance_faults(self._vtime)
                while i < len(items) and items[i][0] <= self._vtime + 1e-12:
                    t_arr, model, req = items[i]
                    req.t_submit = t_arr
                    self.queues[model].append(req)
                    i += 1
                busy = [m for m in self.workers if self._busy(m)]
                if not busy:
                    if i >= len(items):
                        sim.set_coexec(1)
                        break
                    sim.advance_idle(items[i][0] - self._vtime)
                    self._vtime = items[i][0]
                    continue
                self._serve_round(busy, out, temperature)
        finally:
            self._vtime = None
        return out
