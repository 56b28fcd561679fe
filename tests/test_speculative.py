"""Speculative decoding on the slot pool (repro.serving.speculative).

The load-bearing properties: greedy speculative decode is TOKEN-IDENTICAL
to plain greedy decode for any draft; sampled decode replays the exact
per-request RNG streams regardless of how many tokens a verify round
commits; ``draft=None`` leaves the engine bit-identical to the
pre-speculation code path; and every joule a round charges lands on the
ledger (draft and verify on their own rails) in agreement with the
per-request tallies.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.core import DeviceSim, RuntimeEnergyProfiler, build_transformer_graph
from repro.core.telemetry import fold_energy
from repro.models import init_params
from repro.serving import sampling
from repro.serving.engine import AdaOperScheduler, Request, ServingEngine
from repro.serving.speculative import SpecConfig, truncated_draft


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced(get_config("tinyllama-1.1b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def tiny_draft(tiny):
    """A separately-initialised 1-layer draft for the same vocab."""
    cfg, _ = tiny
    dcfg = dataclasses.replace(cfg, name=f"{cfg.name}-draft", num_layers=1)
    return dcfg, init_params(jax.random.PRNGKey(7), dcfg)


@pytest.fixture(scope="module")
def deep():
    """6-layer reduced target: deep enough that a 1-layer draft's priced
    step is cheap relative to the target's, so the EDP rule approves
    speculation (the scheduler-path fixtures need accepted rounds)."""
    cfg = dataclasses.replace(reduced(get_config("tinyllama-1.1b")),
                              num_layers=6)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _requests(cfg, n=6, seed=0, lo=3, hi=14):
    r = np.random.RandomState(seed)
    return [Request(i, r.randint(1, cfg.vocab_size,
                                 size=r.randint(4, 12)).astype(np.int32),
                    int(r.randint(lo, hi))) for i in range(n)]


def _sched(cfgs):
    prof = RuntimeEnergyProfiler(use_gru=False)
    prof.offline_calibrate([build_transformer_graph(c, 2, 32) for c in cfgs],
                           n_samples=600, seed=0)
    return AdaOperScheduler(prof, DeviceSim("moderate", seed=0))


def _serve(cfg, params, draft=None, temperature=0.0, scheduler=None,
           spec=None, mode="continuous", seed=0):
    eng = ServingEngine(scheduler=scheduler, mode=mode, max_slots=4)
    eng.add_model("m", cfg, params, max_len=96, draft=draft, spec=spec)
    if scheduler is not None:
        out = eng.run_trace([(0.0, "m", r) for r in _requests(cfg, seed=seed)],
                            temperature=temperature)
    else:
        for r in _requests(cfg, seed=seed):
            eng.submit("m", r)
        out = eng.run_all(temperature=temperature)
    return {r.uid: r.tokens.tolist() for r in out}, eng, out


# ---------------------------------------------------------------------------
# the verify primitive
# ---------------------------------------------------------------------------


def test_decode_verify_matches_sequential_logits(tiny):
    """Scoring k+1 positions in one ragged forward is bit-identical to
    feeding them one at a time — the property the acceptance rule rests on."""
    cfg, params = tiny
    from repro.serving.workers import ModelWorker
    w = ModelWorker("m", cfg, params, max_len=48)
    r = np.random.RandomState(1)
    prompts = r.randint(1, cfg.vocab_size, size=(4, 12)).astype(np.int32)
    _, g_cache = w.prefill_batch(prompts)
    base = w.write_slots(w.init_pool(4), g_cache, np.arange(4))
    seq_cache = jax.tree.map(jnp.copy, base)
    toks = r.randint(1, cfg.vocab_size, size=(4, 3)).astype(np.int32)
    pos = np.full(4, 12, np.int32)
    seq_logits = []
    for t in range(3):
        _, lg, seq_cache = w.decode_pool(seq_cache, toks[:, t: t + 1],
                                         pos + t)
        seq_logits.append(np.asarray(lg))
    _, ver_logits, _ = w.decode_verify(base, toks, pos)
    ver_logits = np.asarray(ver_logits)
    for t in range(3):
        np.testing.assert_array_equal(ver_logits[:, t], seq_logits[t])


def test_ssm_decode_rejects_multi_position():
    cfg = reduced(get_config("mamba2-2.7b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    from repro.serving.workers import ModelWorker
    w = ModelWorker("m", cfg, params, max_len=48)
    _, g_cache = w.prefill_batch(
        np.ones((2, 8), np.int32))
    pool = w.write_slots(w.init_pool(2), g_cache, np.arange(2))
    with pytest.raises(ValueError, match="single-token"):
        w.decode_verify(pool, np.ones((2, 3), np.int32),
                        np.full(2, 8, np.int32))


# ---------------------------------------------------------------------------
# token identity (greedy + sampled) and the draft=None baseline
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_spec_token_identical_random_draft(tiny, tiny_draft, temperature):
    """Any draft — even a randomly-initialised one proposing mostly-wrong
    tokens — leaves the served tokens identical: rejected suffixes roll
    back, and sampled draws depend only on (stream, token index), never on
    how many tokens a round committed (the per-slot RNG-stream contract
    under variable tokens-per-step)."""
    cfg, params = tiny
    base, _, _ = _serve(cfg, params, temperature=temperature)
    spec, eng, _ = _serve(cfg, params, draft=tiny_draft,
                          temperature=temperature)
    assert spec == base
    assert eng.ledger.counters["spec_rounds"] > 0


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_spec_token_identical_truncated_draft(tiny, temperature):
    """The logits-identical truncated self-draft accepts every proposal and
    still serves the exact baseline tokens."""
    cfg, params = tiny
    dcfg, dparams, tparams = truncated_draft(cfg, params)
    base, _, _ = _serve(cfg, tparams, temperature=temperature)
    spec, eng, _ = _serve(cfg, tparams, draft=(dcfg, dparams),
                          temperature=temperature)
    assert spec == base
    c = eng.ledger.counters
    assert c["spec_accepted"] == c["spec_drafted"] > 0


def test_draft_none_is_inert(tiny):
    """No draft => no spec state, counters, or ledger events anywhere."""
    cfg, params = tiny
    _, eng, _ = _serve(cfg, params)
    assert eng.spec == {}
    assert not any(k.startswith("spec") for k in eng.ledger.counters)
    assert not any(e.kind.startswith("spec") for e in eng.ledger.events)
    assert eng.admission.spec_log == []


def test_bucketed_mode_ignores_draft(tiny, tiny_draft):
    """The position-synchronous reference path never speculates: a draft
    registered on a bucketed engine changes nothing."""
    cfg, params = tiny
    base, _, _ = _serve(cfg, params, mode="bucketed")
    spec, eng, _ = _serve(cfg, params, draft=tiny_draft, mode="bucketed")
    assert spec == base
    assert not any(k.startswith("spec") for k in eng.ledger.counters)


# ---------------------------------------------------------------------------
# per-slot RNG streams under variable tokens-per-step (unit level)
# ---------------------------------------------------------------------------


def test_sample_grid_matches_sequential_sample_one(tiny):
    """The verify grid's draw for token index i is bit-identical to the
    scalar ``sample_one`` that plain decode would have used — per slot, per
    position, for any starting index."""
    cfg, _ = tiny

    class Seq:
        def __init__(self, uid, n):
            self.rng = sampling.stream_key(0, "m", uid)
            self.tokens = [0] * n  # only len() feeds the stream index

    r = np.random.RandomState(3)
    seqs = [Seq(uid, int(r.randint(0, 9))) for uid in range(5)]
    logits = r.randn(5, 4, cfg.vocab_size).astype(np.float32)
    grid = sampling.sample_grid(seqs, logits, temperature=0.7)
    for b, seq in enumerate(seqs):
        n0 = len(seq.tokens)
        for t in range(4):
            seq.tokens = [0] * (n0 + t)
            assert grid[b, t] == sampling.sample_one(seq, logits[b, t], 0.7)


# ---------------------------------------------------------------------------
# energy accounting + the admission policy's speculation pricing
# ---------------------------------------------------------------------------


def test_spec_energy_accounting_conserved(deep):
    """Accepted speculative rounds emit spec_draft/spec_verify events whose
    fold equals the summed per-request energies exactly — speculation never
    leaks unattributed joules."""
    cfg, params = deep
    dcfg, dparams, tparams = truncated_draft(cfg, params)
    _, eng, out = _serve(cfg, tparams, draft=(dcfg, dparams),
                         scheduler=_sched([cfg, dcfg]), seed=1)
    c = eng.ledger.counters
    assert c["spec_rounds"] > 0 and c["spec_accepted"] == c["spec_drafted"]
    draft_ev = eng.ledger.select(kind="spec_draft")
    verify_ev = eng.ledger.select(kind="spec_verify")
    assert draft_ev and verify_ev
    charged = fold_energy(
        [e for e in eng.ledger.events
         if e.kind in ("prefill", "decode", "spec_draft", "spec_verify")])
    total = sum(r.energy_j_pred for r in out)
    assert charged.total_j == pytest.approx(total, rel=1e-9)
    # draft and verify events carry their own plans' rail splits
    for ev in draft_ev + verify_ev:
        assert ev.energy.total_j > 0


def test_spec_decision_declines_losing_draft(tiny, deep):
    """A draft whose proposals never match collapses the acceptance
    estimate until the EDP rule declines every round (spec_fallbacks), and
    the engine falls back to plain steps — tokens stay identical."""
    cfg, params = deep
    dcfg = dataclasses.replace(cfg, name=f"{cfg.name}-rd", num_layers=1)
    dparams = init_params(jax.random.PRNGKey(9), dcfg)
    base, _, _ = _serve(cfg, params, scheduler=_sched([cfg]), seed=1)
    spec, eng, _ = _serve(cfg, params, draft=(dcfg, dparams),
                          scheduler=_sched([cfg, dcfg]), seed=1)
    assert spec == base
    assert eng.ledger.counters["spec_fallbacks"] > 0
    assert any(d["reason"] == "spec-edp-loses" for d in eng.admission.spec_log)


def test_spec_decision_edp_arithmetic():
    """Unit check of the pricing rule: a free draft at full acceptance wins;
    a draft as expensive as the target loses on the energy premium."""
    from repro.serving.admission import AdmissionPolicy
    pol = AdmissionPolicy(scheduler=object())  # non-None: price for real
    base = {"step_latency": 1.0, "step_energy": 1.0, "batch": 4}
    cheap = {"step_latency": 0.01, "step_energy": 0.01, "batch": 4}
    ok, reason = pol.spec_decision(base, cheap, k=3, alpha=1.0)
    assert ok and reason == "spec-edp-wins"
    ok, reason = pol.spec_decision(base, dict(base), k=3, alpha=1.0)
    assert not ok and reason == "spec-edp-loses"


def test_adaptive_k_window_bounded(tiny, tiny_draft):
    cfg, params = tiny
    knobs = SpecConfig(window=3)
    _, eng, _ = _serve(cfg, params, draft=tiny_draft, spec=knobs,
                       seed=2)
    assert eng.ledger.counters["spec_rounds"] > 0
    # retired seqs are gone; the windows that accrued stayed bounded
    for pool in eng.pools.values():
        for seq in pool.active.values():
            assert len(seq.spec_hist) <= 3


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------


def test_draft_validation_rejects_ssm(tiny):
    cfg, params = tiny
    eng = ServingEngine(max_slots=2)
    mcfg = reduced(get_config("mamba2-2.7b"))
    mparams = init_params(jax.random.PRNGKey(0), mcfg)
    with pytest.raises(ValueError, match="non-attention"):
        eng.add_model("m", cfg, params,
                      draft=(mcfg, mparams))


def test_draft_validation_rejects_encdec(tiny):
    cfg, params = tiny
    ecfg = reduced(get_config("seamless-m4t-medium"))
    eparams = init_params(jax.random.PRNGKey(0), ecfg)
    eng = ServingEngine(max_slots=2)
    with pytest.raises(ValueError, match="encoder-decoder"):
        eng.add_model("m", ecfg, eparams, draft=(cfg, params))


def test_draft_validation_rejects_vocab_mismatch(tiny):
    cfg, params = tiny
    bad = dataclasses.replace(cfg, name="bad-vocab",
                              vocab_size=cfg.vocab_size * 2)
    bparams = init_params(jax.random.PRNGKey(0), bad)
    eng = ServingEngine(max_slots=2)
    with pytest.raises(ValueError, match="vocab"):
        eng.add_model("m", cfg, params, draft=(bad, bparams))
