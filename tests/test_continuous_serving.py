"""Continuous batching: slot allocator, ragged decode equivalence,
energy-aware admission, drift-triggered preemption."""
import jax
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.core import DeviceSim, RuntimeEnergyProfiler, build_transformer_graph
from repro.models import init_params
from repro.serving.engine import (
    AdaOperScheduler,
    AdmissionPolicy,
    ModelWorker,
    Request,
    ServingEngine,
    SlotAllocator,
)

# mixed prompt lengths AND mixed decode budgets: the bucketed reference
# fragments this into three buckets and pads each to its slowest member
MIXED = [(12, 4), (20, 6), (12, 2), (16, 5), (20, 1), (16, 6)]


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced(get_config("tinyllama-1.1b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _mixed_requests(cfg, seed=3):
    r = np.random.default_rng(seed)
    return [Request(i, r.integers(1, cfg.vocab_size, plen, dtype=np.int32), mn)
            for i, (plen, mn) in enumerate(MIXED)]


# ---------------------------------------------------------------------------
# slot allocator
# ---------------------------------------------------------------------------


def test_slot_allocator_exhaustion_and_reuse():
    a = SlotAllocator(3)
    got = [a.alloc() for _ in range(3)]
    assert sorted(got) == [0, 1, 2]
    assert a.n_free == 0 and a.n_active == 3
    assert a.alloc() is None  # full pool: admission must wait
    a.free(got[1])
    assert a.n_free == 1
    assert a.alloc() == got[1]  # LIFO: hottest row reused first


def test_slot_allocator_rejects_bad_frees():
    a = SlotAllocator(2)
    s = a.alloc()
    a.free(s)
    with pytest.raises(ValueError):
        a.free(s)  # double free
    with pytest.raises(ValueError):
        a.free(7)  # never allocated
    with pytest.raises(ValueError):
        SlotAllocator(0)


# ---------------------------------------------------------------------------
# continuous path: completion + bit-identity with the bucketed reference
# ---------------------------------------------------------------------------


def test_heterogeneous_requests_complete_token_identical(tiny):
    cfg, params = tiny
    eng = ServingEngine(mode="continuous", max_slots=4)
    eng.add_model("m", cfg, params, max_len=48)
    for r in _mixed_requests(cfg):
        eng.submit("m", r)
    res = eng.run_all()
    assert len(res) == len(MIXED)
    got = {r.uid: r.tokens for r in res}
    ref_worker = ModelWorker("ref", cfg, params, max_len=48)
    for req in _mixed_requests(cfg):
        assert got[req.uid].shape == (req.max_new_tokens,)
        ref = ref_worker.generate(req.prompt[None], req.max_new_tokens)[0]
        np.testing.assert_array_equal(got[req.uid], ref)


def test_more_requests_than_slots_all_complete(tiny):
    cfg, params = tiny
    eng = ServingEngine(mode="continuous", max_slots=2)
    eng.add_model("m", cfg, params, max_len=48)
    reqs = _mixed_requests(cfg, seed=5)
    for r in reqs:
        eng.submit("m", r)
    res = eng.run_all()
    assert sorted(r.uid for r in res) == [r.uid for r in reqs]
    pool = eng.pools["m"]
    assert pool.alloc.n_free == 2 and not pool.active  # every slot returned


def test_bucketed_flag_keeps_reference_path(tiny):
    cfg, params = tiny
    res = {}
    for mode in ("bucketed", "continuous"):
        eng = ServingEngine(mode=mode, max_slots=4)
        eng.add_model("m", cfg, params, max_len=48)
        for r in _mixed_requests(cfg, seed=7):
            eng.submit("m", r)
        res[mode] = {r.uid: r.tokens for r in eng.run_all()}
    assert set(res["bucketed"]) == set(res["continuous"])
    for uid in res["bucketed"]:
        np.testing.assert_array_equal(res["bucketed"][uid], res["continuous"][uid])


def test_oversized_request_rejected_without_stranding_queue(tiny):
    """An unservable request must NOT crash the serving loop: it is rejected
    with an error Response and every other queued request still completes."""
    cfg, params = tiny
    eng = ServingEngine(mode="continuous", max_slots=2)
    eng.add_model("m", cfg, params, max_len=32)
    r = np.random.default_rng(0)
    good_before = Request(0, r.integers(1, cfg.vocab_size, 12, dtype=np.int32), 4)
    oversized = Request(1, np.ones(30, np.int32), max_new_tokens=8)
    good_after = Request(2, r.integers(1, cfg.vocab_size, 12, dtype=np.int32), 3)
    for req in (good_before, oversized, good_after):
        eng.submit("m", req)
    res = {x.uid: x for x in eng.run_all()}
    assert sorted(res) == [0, 1, 2]  # nothing stranded, nothing dropped
    assert "exceeds max_len" in res[1].error
    assert res[1].tokens.shape == (0,)
    assert res[0].error is None and res[0].tokens.shape == (4,)
    assert res[2].error is None and res[2].tokens.shape == (3,)
    # the rejection is visible in the admission log with its reason
    assert any(d["uid"] == 1 and not d["admit"] for d in eng.admission.log)


def test_encdec_request_without_enc_inputs_rejected(tiny):
    cfg = reduced(get_config("seamless-m4t-medium"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(mode="continuous", max_slots=2)
    eng.add_model("m", cfg, params, max_len=32, max_enc_len=8)
    eng.submit("m", Request(0, np.ones(4, np.int32), max_new_tokens=2))
    (resp,) = eng.run_all()
    assert "without enc_inputs" in resp.error


# ---------------------------------------------------------------------------
# batched prefill admission
# ---------------------------------------------------------------------------


def test_prefill_batch_bit_identical_to_prefill_one(tiny):
    """Bucketed admission prefill: every row of one batched prefill call is
    bit-identical (logits AND cache leaves) to a serial prefill_one of the
    same prompt."""
    cfg, params = tiny
    w = ModelWorker("m", cfg, params, max_len=48)
    r = np.random.default_rng(2)
    prompts = r.integers(1, cfg.vocab_size, (3, 14), dtype=np.int32)
    logits_b, cache_b = w.prefill_batch(prompts)
    for i in range(3):
        logits_1, cache_1 = w.prefill_one(prompts[i])
        np.testing.assert_array_equal(np.asarray(logits_b[i]),
                                      np.asarray(logits_1[0]))
        for leaf_b, leaf_1 in zip(jax.tree.leaves(cache_b),
                                  jax.tree.leaves(cache_1)):
            np.testing.assert_array_equal(np.asarray(leaf_b[:, i]),
                                          np.asarray(leaf_1[:, 0]))


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_batched_admission_token_identical_to_serial(tiny, temperature):
    """batch_prefill=False keeps the serial batch-1 admission reference;
    the batched path must serve every request token-identically (greedy and
    sampled), and must actually batch same-length groups."""
    cfg, params = tiny

    def serve(batch_prefill):
        eng = ServingEngine(mode="continuous", max_slots=8,
                            sampling_seed=5, batch_prefill=batch_prefill)
        eng.add_model("m", cfg, params, max_len=48)
        for r in _mixed_requests(cfg, seed=13):
            eng.submit("m", r)
        res = {r.uid: r.tokens for r in eng.run_all(temperature=temperature)}
        return res, eng

    batched, eng_b = serve(True)
    serial, eng_s = serve(False)
    assert set(batched) == set(serial)
    for uid in batched:
        np.testing.assert_array_equal(batched[uid], serial[uid])
    # MIXED holds three same-length pairs: batching must merge prefills
    assert eng_b.prefill_batches < eng_s.prefill_batches
    assert eng_b.prefill_batch_requests == len(MIXED)


def test_prefill_token_cap_splits_a_group(tiny, monkeypatch):
    """A same-length group of more than ``PREFILL_TOKENS`` prompt tokens is
    prefilled in pow2 sub-batches of at most that many, serving the same
    tokens: with a cap of 24, MIXED's pair of 12-token prompts stays one
    batch and its pairs of 16 and 20 tokens go one request at a time."""
    from repro.serving import admission

    cfg, params = tiny

    def serve():
        eng = ServingEngine(mode="continuous", max_slots=8)
        eng.add_model("m", cfg, params, max_len=48)
        for r in _mixed_requests(cfg, seed=13):
            eng.submit("m", r)
        return {r.uid: r.tokens for r in eng.run_all()}, eng

    whole, eng_w = serve()
    monkeypatch.setattr(admission, "PREFILL_TOKENS", 24)
    split, eng_s = serve()
    for uid in whole:
        np.testing.assert_array_equal(split[uid], whole[uid])
    assert (eng_w.prefill_batches, eng_s.prefill_batches) == (3, 5)
    assert eng_s.prefill_batch_requests == len(MIXED)


# ---------------------------------------------------------------------------
# encoder-decoder slot caches (continuous path, no bucketed fallback)
# ---------------------------------------------------------------------------


def _encdec_requests(cfg, n=4, seed=3):
    r = np.random.default_rng(seed)
    shapes = [(6, 9, 4), (10, 5, 3), (6, 9, 2), (8, 7, 5)][:n]
    return [Request(i, r.integers(1, cfg.vocab_size, plen, dtype=np.int32), mn,
                    enc_inputs=r.normal(size=(tlen, cfg.d_model)).astype(np.float32))
            for i, (plen, tlen, mn) in enumerate(shapes)]


def test_encdec_continuous_matches_reference():
    """Enc-dec models serve on the continuous path (per-slot encoder cache
    regions masked to each row's encoder length) token-identically to the
    reference generate path — no more bucketed fallback."""
    cfg = reduced(get_config("seamless-m4t-medium"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    reqs = _encdec_requests(cfg)
    eng = ServingEngine(mode="continuous", max_slots=3)
    eng.add_model("m", cfg, params, max_len=32, max_enc_len=16)
    for req in reqs:
        eng.submit("m", req)
    res = {x.uid: x.tokens for x in eng.run_all()}
    # served through the slot pool, not the bucketed step() fallback
    assert "m" in eng.pools and eng.pools["m"].alloc.n_slots == 3
    assert all(s.get("mode") == "continuous" for s in eng.stats["m"])
    ref = ModelWorker("ref", cfg, params, max_len=32)
    for req in reqs:
        want = ref.generate(req.prompt[None], req.max_new_tokens,
                            enc_inputs=req.enc_inputs[None])[0]
        np.testing.assert_array_equal(res[req.uid], want)


# ---------------------------------------------------------------------------
# vmapped per-slot sampling
# ---------------------------------------------------------------------------


def test_vmapped_sampling_matches_scalar():
    """One batched jax.random.categorical over stacked fold-in keys must
    reproduce the scalar per-slot draws bit-for-bit (same seed⊕model⊕uid⊕
    token-index streams)."""
    from repro.serving.engine import _ActiveSeq

    eng = ServingEngine(mode="continuous", sampling_seed=11)
    rng = np.random.default_rng(4)
    seqs = []
    for uid, n_emitted in [(3, 0), (17, 2), (256, 5)]:
        seq = _ActiveSeq(Request(uid, np.ones(4, np.int32), 8), slot=uid % 4,
                         pos=4)
        seq.tokens = [1] * n_emitted
        seqs.append(seq)
    logits = rng.normal(size=(len(seqs), 64)).astype(np.float32)
    scalar = [eng._sample("m", seq, logits[i], 0.7)
              for i, seq in enumerate(seqs)]
    # fresh seqs so _sample_batch re-derives the streams itself
    for seq in seqs:
        seq.rng = None
    batched = eng._sample_batch("m", seqs, logits, 0.7)
    assert batched == scalar


def test_sampled_bucketed_matches_continuous(tiny):
    """Sampled decode is unified on the per-request uid-derived streams:
    mode='bucketed' and mode='continuous' emit identical tokens at
    temperature>0 (the token-identity guarantee now covers sampling)."""
    cfg, params = tiny
    res = {}
    for mode in ("bucketed", "continuous"):
        eng = ServingEngine(mode=mode, max_slots=4, sampling_seed=9)
        eng.add_model("m", cfg, params, max_len=48)
        for r in _mixed_requests(cfg, seed=21):
            eng.submit("m", r)
        res[mode] = {r.uid: r.tokens for r in eng.run_all(temperature=0.8)}
    assert set(res["bucketed"]) == set(res["continuous"])
    for uid in res["bucketed"]:
        np.testing.assert_array_equal(res["bucketed"][uid],
                                      res["continuous"][uid])


# ---------------------------------------------------------------------------
# energy-aware admission
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sched(tiny):
    cfg, _ = tiny
    g = build_transformer_graph(cfg, 2, 32)
    prof = RuntimeEnergyProfiler(use_gru=False)
    prof.offline_calibrate([g], n_samples=600, seed=0)
    return AdaOperScheduler(prof, DeviceSim("moderate", seed=0))


def test_admission_policy_idle_and_no_scheduler(sched, tiny):
    cfg, _ = tiny
    assert AdmissionPolicy(None).decide(cfg, 3, 32, 8, 0.0) == (True, "no-scheduler")
    pol = AdmissionPolicy(sched)
    assert pol.decide(cfg, 0, 32, 8, 0.0) == (True, "idle-pool")


def test_admission_policy_slo_paths(sched, tiny):
    cfg, _ = tiny
    pol = AdmissionPolicy(sched, slo_s=1e-12)
    # waited past the SLO -> starvation guard admits regardless
    assert pol.decide(cfg, 2, 32, 8, wait_s=1.0) == (True, "slo-starvation")
    # fresh request whose admission would blow the SLO -> denied
    admit, reason = pol.decide(cfg, 2, 32, 8, wait_s=0.0)
    assert (admit, reason) == (False, "slo-violation")


def test_admission_policy_edp_amortises(sched, tiny):
    """Within a pow2 batch bucket, another request shares the same step
    plan, so per-request EDP strictly improves -> admit."""
    cfg, _ = tiny
    pol = AdmissionPolicy(sched)
    admit, reason = pol.decide(cfg, 2, 32, 8, wait_s=0.0)
    assert admit and reason == "edp-improves"


class _FixedSim:
    """Noise-free device stand-in: observe() is deterministic, so plan-cache
    behaviour can be asserted exactly."""

    def __init__(self):
        self.state = DeviceSim("moderate", seed=0).state

    def observe(self, noise=True):
        return self.state


def test_step_plan_is_bucketed_and_cached(sched, tiny):
    cfg, _ = tiny
    fixed = AdaOperScheduler(sched.profiler, _FixedSim())
    p5 = fixed.step_plan(cfg, 5, 20, 6)
    assert p5["batch"] == 8  # pow2 batch bucket
    h0 = fixed.ledger.counters.get("plan_cache_hits", 0)
    p6 = fixed.step_plan(cfg, 6, 20, 5)  # same (batch, seq, horizon) buckets
    assert p6["batch"] == 8
    assert fixed.ledger.counters.get("plan_cache_hits", 0) > h0
    assert p6["step_latency"] == p5["step_latency"]


# ---------------------------------------------------------------------------
# drift-triggered preemption
# ---------------------------------------------------------------------------


def test_preemption_never_drops_admitted_requests(tiny):
    """Force a drift event every engine round: the lowest-priority worker is
    preempted while plans re-solve, but every admitted request completes
    with exactly its token budget."""
    cfg, params = tiny
    cfg2 = reduced(get_config("gemma2-2b"))
    params2 = init_params(jax.random.PRNGKey(1), cfg2)
    g = build_transformer_graph(cfg, 2, 32)
    prof = RuntimeEnergyProfiler(use_gru=False)
    prof.offline_calibrate([g], n_samples=600, seed=0)
    sim = DeviceSim("high", seed=0)
    eng = ServingEngine(scheduler=AdaOperScheduler(prof, sim),
                        mode="continuous", max_slots=3)
    eng.add_model("hi", cfg, params, max_len=48, priority=1)
    eng.add_model("lo", cfg2, params2, max_len=48, priority=0)
    def _always_drift():
        return True

    eng._drift_event = _always_drift  # every round is a drift event
    r = np.random.default_rng(11)
    n = 4
    for i in range(n):
        eng.submit("hi", Request(i, r.integers(1, cfg.vocab_size, 12, dtype=np.int32), 3))
        eng.submit("lo", Request(100 + i, r.integers(1, cfg2.vocab_size, 16, dtype=np.int32), 4))
    res = eng.run_all()
    assert len(res) == 2 * n
    by_uid = {x.uid: x for x in res}
    for i in range(n):
        assert by_uid[i].tokens.shape == (3,)
        assert by_uid[100 + i].tokens.shape == (4,)
    # only the low-priority worker was ever preempted, and it was preempted
    assert eng.preemptions["hi"] == 0
    assert eng.preemptions["lo"] > 0


def test_sampled_decode_deterministic_under_any_admission_order(tiny):
    """Per-slot sampling RNG: each request draws from its own seed-derived
    stream (seed ⊕ model ⊕ uid ⊕ token-index), so sampled outputs are
    identical whatever the submission order, pool size, or co-resident
    requests — and change when the engine's sampling seed changes."""
    cfg, params = tiny

    def serve(order, max_slots, sampling_seed=7):
        eng = ServingEngine(mode="continuous", max_slots=max_slots,
                            sampling_seed=sampling_seed)
        eng.add_model("m", cfg, params, max_len=48)
        reqs = _mixed_requests(cfg, seed=9)
        for i in order:
            eng.submit("m", reqs[i])
        return {r.uid: r.tokens for r in eng.run_all(temperature=0.8)}

    fwd = serve(range(len(MIXED)), max_slots=4)
    rev = serve(reversed(range(len(MIXED))), max_slots=2)
    assert set(fwd) == set(rev)
    for uid in fwd:
        np.testing.assert_array_equal(fwd[uid], rev[uid])
    other = serve(range(len(MIXED)), max_slots=4, sampling_seed=8)
    assert any(not np.array_equal(fwd[u], other[u]) for u in fwd), \
        "changing the sampling seed must change at least one stream"


def test_greedy_admitted_sequence_survives_sampled_step(tiny):
    """A sequence admitted at temperature=0 can finish under sampled decode:
    its stream is established lazily from the same uid derivation."""
    cfg, params = tiny
    eng = ServingEngine(mode="continuous", max_slots=2, sampling_seed=3)
    eng.add_model("m", cfg, params, max_len=48)
    r = np.random.default_rng(0)
    eng.submit("m", Request(0, r.integers(1, cfg.vocab_size, 12, dtype=np.int32), 4))
    out = eng.step_continuous("m")  # greedy admit + first decode step
    assert not out and eng.pools["m"].active
    res = eng.run_all(temperature=0.8)  # switch to sampled mid-flight
    assert len(res) == 1 and res[0].tokens.shape == (4,)


def test_run_trace_requires_scheduler(tiny):
    cfg, params = tiny
    eng = ServingEngine(mode="continuous")
    eng.add_model("m", cfg, params, max_len=48)
    with pytest.raises(ValueError, match="run_trace"):
        eng.run_trace([])


def test_run_trace_stats_use_virtual_time(sched, tiny, monkeypatch):
    """Under the virtual clock, per-iteration stats must be _vtime deltas
    (predicted latencies), not host speed: here the host clock jumps 1000 s
    per call, which would poison every wall_s if the engine read it."""
    cfg, params = tiny
    import repro.serving.engine as engine_mod

    t = [1e6]

    def fake_time():
        t[0] += 1000.0
        return t[0]

    monkeypatch.setattr(engine_mod.time, "time", fake_time)
    eng = ServingEngine(scheduler=sched, mode="continuous", max_slots=2)
    eng.add_model("m", cfg, params, max_len=48)
    r = np.random.default_rng(6)
    arrivals = [(0.01 * i, "m",
                 Request(i, r.integers(1, cfg.vocab_size, 8, dtype=np.int32), 2))
                for i in range(3)]
    res = eng.run_trace(arrivals)
    assert len(res) == 3
    rows = [s for s in eng.stats["m"] if s.get("mode") == "continuous"]
    assert rows
    for s in rows:
        assert 0.0 <= s["wall_s"] < 1.0  # virtual seconds, not host clock


def test_run_trace_rejects_unknown_model(sched, tiny):
    cfg, params = tiny
    eng = ServingEngine(scheduler=sched, mode="continuous")
    eng.add_model("m", cfg, params, max_len=48)
    with pytest.raises(ValueError, match="no registered worker"):
        eng.run_trace([(0.0, "typo", Request(0, np.ones(4, np.int32), 2))])


def test_drift_event_hysteresis(sched, tiny):
    cfg, params = tiny
    eng = ServingEngine(scheduler=sched, mode="continuous")
    eng.add_model("m", cfg, params, max_len=48)
    assert eng._drift_event() is False  # first observation seeds the reference
    assert eng._drift_event() is False  # observation noise alone: no event
    eng._plan_memo["sentinel"] = {"step_energy": 0.0}
    sched.profiler._version += 1  # a correction update invalidates plans
    assert eng._drift_event() is True
    assert "sentinel" not in eng._plan_memo  # memo dropped on the event
    assert eng.drift_events == 1
