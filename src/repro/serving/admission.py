"""Energy-aware iteration-level admission + batched prefill.

``AdmissionPolicy`` is the decision rule (the AdaOper objective applied at
token granularity); ``admit_requests`` / ``prefill_group`` are the engine's
admission machinery: pull waiting requests into free slots while the policy
approves, then prefill the approved set in bucketed same-shape batches.
They operate *on* a ``ServingEngine`` so the engine module stays pure
orchestration; ``repro.serving.engine`` re-exports ``AdmissionPolicy``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.telemetry import EnergyBreakdown, span
from repro.serving import planning
from repro.serving.robustness import reject_request
from repro.serving.scheduler import AdaOperScheduler
from repro.serving.slots import Request, Response, _ActiveSeq, _SlotPool
from repro.serving.workers import ModelWorker

# the most prompt tokens (rows x length) one prefill call takes: a larger
# same-shape group is prefilled in pow2 sub-batches, which bounds a prefill's
# activations (expanded attention scores, an expert layer's T*k rows)
# whatever the pool's size
PREFILL_TOKENS = 32768


class AdmissionPolicy:
    """Energy-aware iteration-level admission (the AdaOper objective applied
    at token granularity): admit a waiting request into the slot pool only
    when the profiler/partitioner fast path predicts the per-request
    energy-delay product of a decode step does not worsen, and the added
    step latency does not push the pool past the SLO. A starvation guard
    admits regardless once the request's queueing delay exceeds the SLO,
    and an empty pool always admits (idle silicon costs leakage only)."""

    def __init__(self, scheduler: Optional[AdaOperScheduler] = None,
                 slo_s: Optional[float] = None, edp_slack: float = 1.05,
                 risk_level: Optional[float] = None):
        self.scheduler = scheduler
        self.slo_s = slo_s
        self.edp_slack = edp_slack
        # risk-aware admission (repro.uncertainty): 0..1 position between the
        # point prediction and the calibrated upper interval bound at which
        # latency/energy are priced — 1.0 admits on the full upper quantile.
        # None (default) keeps the exact point-estimate arithmetic; plans
        # without a stamped interval fall back to the point value too.
        self.risk_level = risk_level
        self.log: List[dict] = []
        # speculation pricing decisions (repro.serving.speculative) — kept
        # apart from the admission log so denial counts stay request-scoped
        self.spec_log: List[dict] = []
        # engine-attached ledger: denials are counted at the source so
        # fleet counters fold from telemetry, not from re-scanning the log
        self.ledger = None

    def _risk(self, plan: dict, which: str) -> float:
        """Latency ("latency") or energy ("energy") of one decode step at
        the configured risk level."""
        point = plan["step_latency" if which == "latency" else "step_energy"]
        if self.risk_level is None:
            return point
        iv = plan.get("interval")
        if iv is None:
            return point
        hi = iv[which][1]
        return point + self.risk_level * (hi - point)

    def decide(self, cfg, n_active: int, seq_len: int, max_new: int,
               wait_s: float, plan_fn=None) -> Tuple[bool, str]:
        """``plan_fn(batch)`` overrides the plan source (the engine passes
        its drift-scoped memo so steady-state decisions cost dict lookups)."""
        with span("repro.admission.decide"):
            if self.scheduler is None:
                return True, "no-scheduler"
            if n_active == 0:
                return True, "idle-pool"
            if self.slo_s is not None and wait_s > self.slo_s:
                return True, "slo-starvation"
            if plan_fn is None:
                plan_fn = lambda b: self.scheduler.step_plan(cfg, b, seq_len, max_new)  # noqa: E731
            cur = plan_fn(n_active)
            new = plan_fn(n_active + 1)
            # per-request EDP of one decode step: latency is shared by the actual
            # batch, energy scales ~linearly with the plan's (bucketed) batch.
            # With a risk level set, both sides are priced at the same upper
            # quantile (no systematic bias in the comparison); the SLO check
            # prices the risk-adjusted latency, so a wide (uncertain) interval
            # admits more conservatively than a confident one.
            edp_cur = ((self._risk(cur, "latency") / n_active)
                       * (self._risk(cur, "energy") / cur["batch"]))
            edp_new = ((self._risk(new, "latency") / (n_active + 1))
                       * (self._risk(new, "energy") / new["batch"]))
            if (self.slo_s is not None
                    and self._risk(new, "latency") * max_new > self.slo_s):
                return False, "slo-violation"
            if edp_new <= edp_cur * self.edp_slack:
                return True, "edp-improves"
            return False, "edp-worsens"

    def _record(self, admit: bool, reason: str, n_active: int, uid) -> None:
        self.log.append({"admit": admit, "reason": reason,
                         "n_active": n_active, "uid": uid})
        if self.ledger is not None and not admit:
            self.ledger.count("admission_denials")

    def spec_decision(self, base: dict, draft: dict, k: int,
                      alpha: float) -> Tuple[bool, str]:
        """Price one speculative round against the plain step it replaces:
        speculate only when the per-token EDP of the round (k draft steps +
        one k+1-position verify, divided by the expected committed tokens)
        beats the base step's per-token EDP.
        Both sides are priced at the configured ``risk_level`` quantile —
        the same interval arithmetic as admission, so an uncertain plan
        declines speculation more conservatively than a confident one. The
        energy premium is the AdaOper tension: verify latency amortises
        across positions but verify energy does not
        (``planning.SPEC_VERIFY_MARGINAL_*``), so a latency win can still
        lose on EDP — those rounds fall back to the plain step and count
        ``spec_fallbacks``."""
        if self.scheduler is None:
            return True, "no-scheduler"
        lat_b, en_b = self._risk(base, "latency"), self._risk(base, "energy")
        lat_d, en_d = self._risk(draft, "latency"), self._risk(draft, "energy")
        lat_s, en_s = planning.spec_round_cost(lat_b, en_b, lat_d, en_d, k)
        tau = planning.expected_tokens(alpha, k)
        edp_spec = (lat_s / tau) * (en_s / (tau * base["batch"]))
        edp_base = lat_b * (en_b / base["batch"])
        if edp_spec <= edp_base * self.edp_slack:
            return True, "spec-edp-wins"
        return False, "spec-edp-loses"


def ssm_prompt_bucketed(eng, w: ModelWorker) -> bool:
    """True when ``w``'s admission groups key on the pow2 prompt-length
    bucket instead of the exact length: pure-SSM stacks (every layer a
    mamba/ssd scan, no encoder) under ``eng.ssm_prompt_buckets`` — the
    pad-safe scan makes a LEFT-padded + masked bucket prefill bit-identical
    to exact-length prefill, so mixed-length admissions share one jitted
    shape. Attention stacks keep exact-length grouping (padding would
    corrupt their KV caches)."""
    if not getattr(eng, "ssm_prompt_buckets", True) or not eng.batch_prefill:
        return False
    if w.cfg.is_encoder_decoder:
        return False
    kinds = w.cfg.layer_kinds()
    return bool(kinds) and all(k in ("mamba", "ssd") for k in kinds)


def validate_request(w: ModelWorker, req: Request) -> Optional[str]:
    """Reason the request can never be served by ``w``, or None."""
    if len(req.prompt) + req.max_new_tokens > w.max_len:
        return (f"prompt {len(req.prompt)} + max_new "
                f"{req.max_new_tokens} exceeds max_len {w.max_len}")
    if w.cfg.is_encoder_decoder:
        if req.enc_inputs is None:
            return "encoder-decoder request without enc_inputs"
        if req.enc_inputs.shape[0] > w.max_enc_len:
            return (f"enc_inputs length {req.enc_inputs.shape[0]} "
                    f"exceeds max_enc_len {w.max_enc_len}")
    return None


def admit_requests(eng, model: str, pool: _SlotPool, out: List[Response],
                   temperature: float = 0.0) -> int:
    """Token-granularity admission: pull waiting requests into free slots
    while the energy-aware policy approves, then prefill the approved set
    in bucketed same-shape batches (``batch_prefill=False`` keeps the
    serial batch-1 reference). A request that can never be served
    (oversized, missing encoder inputs) is rejected with an error
    ``Response`` and the loop keeps draining — it must not crash the
    serving loop and strand the queue. Returns #admitted."""
    with span("repro.admission.admit"):
        w, q = eng.workers[model], eng.queues[model]
        admitted: List[_ActiveSeq] = []
        while q and pool.alloc.n_free:
            req = q[0]
            err = validate_request(w, req)
            if err is not None:
                q.pop(0)
                eng.admission._record(False, f"invalid: {err}",
                                      len(pool.active), req.uid)
                reject_request(eng, model, req, err, out)
                continue
            seq_len, max_new = eng._plan_shape(pool, extra=req)
            plan_fn = (None if eng.scheduler is None else
                       (lambda b: eng._plan_for(model, b, seq_len, max_new)))
            wait_s = eng._now() - req.t_submit
            admit, reason = eng.admission.decide(
                w.cfg, len(pool.active), seq_len, max_new, wait_s,
                plan_fn=plan_fn)
            eng.admission._record(admit, reason, len(pool.active), req.uid)
            if not admit:
                break
            q.pop(0)
            eng.ledger.count("admitted")
            eng.ledger.count("queue_wait_us", round(wait_s * 1e6))
            slot = pool.alloc.alloc()
            seq = _ActiveSeq(req, slot, pos=len(req.prompt), model=model)
            # resident immediately so the next decision's plan shape sees it
            pool.active[slot] = seq
            admitted.append(seq)
        if eng.batch_prefill:
            bucketed = ssm_prompt_bucketed(eng, w)
            groups: Dict[tuple, List[_ActiveSeq]] = {}
            for seq in admitted:
                enc = seq.req.enc_inputs
                plen = len(seq.req.prompt)
                key = (AdaOperScheduler._len_bucket(plen) if bucketed else plen,
                       None if enc is None else enc.shape)
                groups.setdefault(key, []).append(seq)
            group_list = list(groups.values())
        else:
            group_list = [[seq] for seq in admitted]
        for group in group_list:
            prefill_group(eng, model, pool, group, out, temperature)
        return len(admitted)


def prefill_group(eng, model: str, pool: _SlotPool,
                  group: List[_ActiveSeq], out: List[Response],
                  temperature: float) -> None:
    """One bucketed prefill for a same-shape group of admitted requests:
    the batch is padded to a pow2 bucket (bounding jit compiles), the
    resulting caches scatter into the slots in one ``write_slots`` call
    (padding rows are dropped), and the admission plan is charged once
    per bucket — per-request energy normalised by the plan's bucketed
    batch, the virtual clock advanced by one bucket latency, one
    ``prefill`` StepEvent appended to the ledger. A group of more than
    ``PREFILL_TOKENS`` prompt tokens goes in sub-batches."""
    longest = max(len(s.req.prompt) for s in group)
    rows = 1 << (max(1, PREFILL_TOKENS // longest).bit_length() - 1)
    if len(group) > rows:
        for i in range(0, len(group), rows):
            prefill_group(eng, model, pool, group[i:i + rows], out, temperature)
        return
    with span("repro.prefill.group"):
        w = eng.workers[model]
        G = len(group)
        b = AdaOperScheduler._new_bucket(G)
        pad = b - G
        lens = [len(s.req.prompt) for s in group]
        plan_len = lens[0]
        pad_mask = None
        if ssm_prompt_bucketed(eng, w) and lens:
            # pow2 prompt-length bucket: LEFT-pad every prompt to the group's
            # shared bucket with a validity mask (the pad-safe SSM scan leaves
            # masked positions out of the state entirely, so each row's cache
            # matches its exact-length prefill); per-seq positions stay the
            # true prompt lengths.
            plan_len = AdaOperScheduler._len_bucket(max(lens))
            if any(n != plan_len for n in lens):
                padded = np.zeros((G, plan_len), np.int32)
                mask = np.zeros((G, plan_len), bool)
                for i, s in enumerate(group):
                    padded[i, plan_len - lens[i]:] = s.req.prompt
                    mask[i, plan_len - lens[i]:] = True
                prompts = np.concatenate([padded, padded[:1].repeat(pad, 0)]) \
                    if pad else padded
                pad_mask = np.concatenate([mask, mask[:1].repeat(pad, 0)]) \
                    if pad else mask
                logits, g_cache = w.prefill_batch(prompts, None,
                                                  pad_mask=pad_mask)
        if pad_mask is None:
            prompts = np.stack([s.req.prompt for s in group]
                               + [group[0].req.prompt] * pad)
            enc = None
            if group[0].req.enc_inputs is not None:
                enc = np.stack([s.req.enc_inputs for s in group]
                               + [group[0].req.enc_inputs] * pad)
            logits, g_cache = w.prefill_batch(prompts, enc)
        slots = np.full(b, pool.alloc.n_slots, np.int32)  # pads drop
        slots[:G] = [s.slot for s in group]
        pool.cache = w.write_slots(pool.cache, g_cache, slots)
        with span("repro.prefill.wait"):  # the host waits for the prefill here
            if temperature > 0.0:
                toks = eng._sample_batch(model, group, logits[:G], temperature)
            else:
                toks = [int(t) for t in np.asarray(jnp.argmax(logits[:G], -1))]
        pp = None
        if eng.scheduler is not None:
            # bucketed SSM groups charge the bucket-length plan (same pow2 len
            # bucket the planner keys on, so exact-length groups are unchanged)
            pp = eng._prefill_plan_for(model, G, plan_len)
            eng.scheduler.sim.drain(pp["energy"] * G / pp["batch"])
            eng.ledger.emit(
                "prefill", pp["latency"],
                EnergyBreakdown.from_total(pp["energy"] * G / pp["batch"],
                                           pp["rails"]),
                t_s=eng._now(), model=model, n_active=G)
            # virtual replay charges the whole bucket at the planner's
            # predicted latency (wall-clock mode measures it)
            eng._advance_vtime(pp["latency"])
        spec = getattr(eng, "spec", {}).get(model)
        if spec is not None:
            # warm the draft cache for the admitted group (same prompts, the
            # draft's own params) so verify rounds only catch up 1-2 tokens;
            # charged as a spec_draft event with the draft plan's rails
            from repro.serving import speculative
            speculative.prefill_draft(eng, model, spec, group, prompts, slots, G,
                                      plan_len)
        t_first = eng._now()
        for seq, tok in zip(group, toks):
            seq.tokens.append(tok)
            seq.t_first = t_first
            if pp is not None:
                seq.rails += EnergyBreakdown.from_total(
                    pp["energy"] / pp["batch"], pp["rails"])
            pool.tokens[seq.slot, 0] = tok
            pool.pos[seq.slot] = seq.pos
            pool.enc_len[seq.slot] = (0 if seq.req.enc_inputs is None
                                      else seq.req.enc_inputs.shape[0])
            if len(seq.tokens) >= seq.req.max_new_tokens:
                eng._retire(pool, seq, out)
        eng.prefill_batches += 1
        eng.prefill_batch_requests += G
