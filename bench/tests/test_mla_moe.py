"""The latent-attention, mixture-of-experts architecture (``mla_moe``): its
weights in the program's layout, its costs by hand, its reference against
the program in float32, its fp8 control, the harness's refusals, a clean run
with the router's counters, the expert reader, and the two mixes added with
it (``moe-decode``, ``duo-longprompt``)."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, harness, traffic
from bench import observed as ob
from bench import trace_reduce as tr
from bench.costs import mla_moe as costs
from bench.reference import mla_moe as reference
from bench.weights import mla_moe as weights

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
TINY = json.loads((DATA / "tiny-mla.json").read_text())
CELLS = {"workloads": [{"name": "t-mla", "config": "tiny-mla", "traffic": "tiny-closed", "chips": 1}],
         "end_to_end": [{"name": "itl_p95_ms", "unit": "ms"}, {"name": "setup_s", "unit": "s"}],
         "per_layer": []}


def deepseek():
    return json.loads((BENCH / "configs" / "deepseek-v2-lite.pp4.json").read_text())


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setattr(harness, "CONFIG_DIR", DATA)
    monkeypatch.setattr(traffic, "MIX_DIR", DATA)


def f32(conf):
    conf = json.loads(json.dumps(conf))
    conf["model"].update(dtype="float32", param_dtype="float32")
    conf["reduced"] += ["dtype", "param_dtype"]
    return conf


# ---- weights ----

@pytest.mark.parametrize("conf", [TINY, deepseek()], ids=["tiny-mla", "deepseek-v2-lite.pp4"])
def test_weights_take_the_programs_layout(conf):
    """Tree, shapes and dtypes of the served weights are the program's own
    (``init_params``), at a test size and, by shapes alone, at the cell's."""
    from repro.models import init_params

    cfg = harness.model_config(conf)
    want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    got = jax.eval_shape(lambda: weights.served_params(3, conf["model"]))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_layers_made_alone_are_the_served_numbers():
    m, seed = TINY["model"], 2**33 + 17
    served = weights.served_params(seed, m)
    root = weights.root_key(seed)
    (first, repeats, period), = weights.stages(m)
    assert (first, repeats, period) == (0, 1, m["num_layers"])  # a dense first layer: one repeat
    for i in range(m["num_layers"]):
        make = weights.moe_layer if weights.is_moe(m, i) else weights.dense_layer
        alone = jax.jit(lambda i: make(root, m, i))(i)
        stacked = jax.tree.map(lambda a: a[0], served["stages"][0][f"l{i}"])
        for a, b in zip(jax.tree.leaves(alone), jax.tree.leaves(stacked)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    emb = served["embed"]["embedding"]
    assert not np.asarray(emb[m["vocab_size"]:]).any()
    assert np.asarray(served["stages"][0]["l1"]["mlp"]["w_gate"]).std() > 0


# ---- costs ----

def test_weight_bytes_match_hand_arithmetic():
    """7 layers of DeepSeek-V2-Lite: 96,337,920 MLA, 67,239,936 dense-MLP,
    6 x (553,648,128 routed + 17,301,504 shared) expert-layer and 419,430,400
    table parameters in bf16, 4,008,706,048 in all (8.02 GB), 6.64 GB of
    them routed experts; the routers and norm scales in float32."""
    m = deepseek()["model"]
    assert 7 * costs.attention_params(m) == 96_337_920
    assert 64 * costs.expert_params(m) == 553_648_128
    assert costs.shared_params(m) == 17_301_504
    bf16 = 419_430_400 + 96_337_920 + 67_239_936 + 6 * (553_648_128 + 17_301_504)
    assert bf16 == 4_008_706_048
    f32 = 7 * (2 * 2048 + 512) + 2048 + 6 * 2048 * 64
    assert costs.weight_bytes(m) == 2 * bf16 + 4 * f32
    assert 2 * 6 * 553_648_128 / 1e9 == pytest.approx(6.64, abs=0.005)
    assert costs.latent_bytes_per_token(m) == 7 * 576 * 2 == 8064


def test_decode_step_reads_the_experts_the_counters_say_were_touched():
    m = deepseek()["model"]
    e = 2 * costs.expert_params(m)  # bytes of one expert
    # 600 layer-steps touched 37,800 experts in all: 63 a layer, 6 layers
    counters = {"moe_decode_layer_steps": 600, "moe_decode_experts_touched": 37_800,
                "moe_decode_load_max": 9_000}
    all_but_tables = costs.weight_bytes(m) - 2 * 102_400 * 2048
    f1, b1 = costs.decode_step(m, [99], counters)
    assert b1 == pytest.approx(all_but_tables - 6 * 64 * e + 6 * 63 * e + 100 * 8064)
    _, b2 = costs.decode_step(m, [99, 199], counters)
    assert b2 - b1 == pytest.approx(200 * 8064)
    # without counters, a step of one token reads its k experts a layer
    _, b0 = costs.decode_step(m, [99], {})
    assert b0 == pytest.approx(all_but_tables - 6 * 64 * e + 6 * 6 * e + 100 * 8064)
    # a token: absorbed MLA over 100 positions in 7 layers, the dense MLP,
    # router, 6 routed and the shared experts in 6 layers, the LM head
    proj = 2048 * 16 * 192 + 2048 * 576 + 16 * 128 * 512 + 16 * 512 * 128 + 16 * 128 * 2048
    attn = 2 * proj + 2 * 16 * 576 * 100 + 2 * 16 * 512 * 100
    mlp = 2 * 3 * 2048 * 10944 + 6 * 2 * (2048 * 64 + 6 * 3 * 2048 * 1408 + 3 * 2048 * 2816)
    assert f1 == 7 * attn + mlp + 2 * 2048 * 102_400


def test_expert_decode_counts_assignments_and_touched_experts():
    m = deepseek()["model"]
    counters = {"moe_decode_layer_steps": 6, "moe_decode_experts_touched": 6 * 63}
    flops, nbytes = costs.expert_decode(m, 64, counters)
    assert flops == 2 * 3 * 2048 * 1408 * 6 * 64 * 6
    assert nbytes == 2 * 3 * 2048 * 1408 * 63 * 6


def test_prefill_closed_form_matches_the_sum_over_positions():
    m = deepseek()["model"]
    H = 16
    per_pos = sum(2 * H * 192 * c + 2 * H * 128 * c for c in range(1, 301))
    attn = 7 * (2 * costs.attention_params(m) * 300 + per_pos)
    flops, _ = costs.prefill(m, [300], {})
    assert flops == attn + 300 * costs._mlp_flops(m) + 2 * 2048 * 102_400


# ---- reference ----

TOL = 2e-4  # float32 logits of order 0.2 through three layers, summed in other orders


def test_reference_matches_program_prefill_and_decode():
    """The program's forward in float32 (prefill at every position, then
    decode through the latent cache, absorbed) against the expanded
    reference, routed on the host; YaRN and unnormalised top-k gates on."""
    from repro.models import model as model_lib

    conf = f32(TINY)
    cfg = harness.model_config(conf)
    m, seed, S, extra = conf["model"], 2**32 + 3, 20, 6
    params = weights.served_params(seed, m)
    seq = np.random.default_rng(0).integers(1, m["vocab_size"], S + extra, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        cache = model_lib.init_cache(cfg, 1, 64)
        logits, cache = model_lib.prefill(params, cfg, jnp.asarray(seq[None, :S]), cache)
        prog = [np.asarray(logits[0, :, : m["vocab_size"]])]
        for k in range(extra):
            out, cache = model_lib.decode_step(params, cfg, jnp.asarray(seq[None, S + k: S + k + 1]),
                                               cache, jnp.asarray([S + k], jnp.int32))
            prog.append(np.asarray(out[0, :, : m["vocab_size"]]))
    prog = np.concatenate(prog)
    rows = np.arange(S + extra)
    toks = np.random.default_rng(1).integers(0, m["vocab_size"], S + extra)
    (best, at, am), = reference.Reference(m, seed).score([seq], [rows], [toks])
    np.testing.assert_allclose(best, prog.max(axis=1), atol=TOL)
    np.testing.assert_allclose(at, prog[rows, toks], atol=TOL)
    assert (am == prog.argmax(axis=1)).mean() > 0.95  # ties may flip on rounding


def test_reference_yarn_is_the_published_closed_form():
    """At DeepSeek-V2-Lite's settings the ramp runs over pair indices 10..23
    of 32 and the softmax scale is 192 ** -0.5 * 1.5896 = 0.114721."""
    m = deepseek()["model"]
    inv, cs = reference.yarn(m)
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(np.asarray(inv), base / 40 * ramp + base * (1 - ramp), rtol=1e-6)
    assert cs == 1.0
    assert reference.softmax_scale(m) == pytest.approx(0.114721, abs=1e-6)


def test_control_rounds_each_experts_columns():
    w = jax.random.normal(jax.random.PRNGKey(0), (4, 256, 64))
    w = w * jnp.arange(1, 5)[:, None, None]  # experts of different magnitudes
    q = reference.quantize(w, "fp8")
    rel = jnp.abs(q - w) / jnp.max(jnp.abs(w), axis=1, keepdims=True)
    assert 0 < float(rel.max()) <= 2.0 ** -4 * 1.01


# ---- control and runs ----

def test_fp8_control_fails_and_program_passes(tiny):
    limit = TINY["limits"]["served_gap"]
    rows = list(control.readings("t-mla", [11, 12], 3.0, bench=CELLS, require_chip=False))
    for row in rows:
        prog, ctrl = row["program"]["gap.tiny-mla"], row["control"]["gap.tiny-mla"]
        assert prog["tokens"] >= 200, row
        assert row["program_correct"] and prog["value"] <= limit, row
        assert not row["control_correct"] and ctrl["value"] > limit, row


def test_clean_run_is_correct_and_counts_routing(tiny):
    import sys
    sys.path.insert(0, str(BENCH))
    import run

    res = run.run("t-mla", 2**33 + 1, 3.0, False, require_chip=False, bench=CELLS)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    c = res["info"]["counters"]
    steps = c["tiny-mla.moe_decode_layer_steps"]
    assert steps > 0 and steps % 2 == 0  # two expert layers a step
    assert steps <= c["tiny-mla.moe_decode_experts_touched"] <= 8 * steps
    assert c["tiny-mla.moe_decode_experts_touched"] <= c["tiny-mla.moe_decode_load_max"] * 8
    assert not any("moe_decode" in k and not k.startswith("tiny-mla.") for k in c)


def test_harness_refuses_a_width_changed_without_reduced():
    conf = deepseek()
    assert harness.model_config(conf).num_experts == 64
    for key, value in (("moe_d_ff", 1024), ("num_experts", 32), ("yarn_factor", 1.0),
                       ("norm_topk_prob", True)):
        bad = json.loads(json.dumps(conf))
        bad["model"][key] = value
        with pytest.raises(ValueError, match=key):
            harness.model_config(bad)


def test_configuration_keeps_the_published_config():
    """Every key of the published config.json is in the file at its value,
    but the layers, cut to this stage's 7; the program's sizes agree."""
    conf = deepseek()
    m = conf["model"]
    assert conf["num_hidden_layers"] == m["num_layers"] == 7
    assert conf["published"]["num_layers"] == 27
    pairs = {"hidden_size": "d_model", "intermediate_size": "d_ff", "kv_lora_rank": "kv_lora_rank",
             "moe_intermediate_size": "moe_d_ff", "n_routed_experts": "num_experts",
             "n_shared_experts": "num_shared_experts", "num_attention_heads": "num_heads",
             "num_experts_per_tok": "top_k", "qk_nope_head_dim": "qk_nope_dim",
             "qk_rope_head_dim": "qk_rope_dim", "v_head_dim": "v_head_dim",
             "vocab_size": "vocab_size", "first_k_dense_replace": "first_dense_layers",
             "norm_topk_prob": "norm_topk_prob", "rope_theta": "rope_theta"}
    for published, ours in pairs.items():
        assert conf[published] == m[ours], published
    ys = conf["rope_scaling"]
    assert (ys["factor"], ys["original_max_position_embeddings"], ys["beta_fast"], ys["beta_slow"],
            ys["mscale"], ys["mscale_all_dim"]) == (
        m["yarn_factor"], m["yarn_original_positions"], m["yarn_beta_fast"], m["yarn_beta_slow"],
        m["yarn_mscale"], m["yarn_mscale_all_dim"])
    assert conf["q_lora_rank"] is None and conf["routed_scaling_factor"] == 1
    assert conf["scoring_func"] == "softmax" and conf["topk_method"] == "greedy"


# ---- the expert reader ----

def _observed(ops, counters):
    m = deepseek()["model"]
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    trace = {"spans": {"bench.window": [[0.0, 1.0]]},
             "devices": [{"name": "/device:TPU:0", "ops": ops,
                          "modules": [["jit__decode_impl(1)", 0.1, 0.2],
                                      ["jit__prefill_impl(2)", 0.3, 0.4]]}]}
    return ob.Observed(trace=trace, window=(0.0, 1.0), counters=counters, compiles=0,
                       decode_calls=[("ds", list(range(64)))], prefills=[], decoded=[],
                       models={"ds": m}, costs={"ds": costs}, peaks=peaks, chips=1)


def test_expert_reader_divides_least_time_by_the_grouped_matmuls():
    counters = {"ds.moe_decode_layer_steps": 6, "ds.moe_decode_experts_touched": 6 * 63}
    ops = [["%ragged-dot-none.3 = bf16[384,1408]{1,0", 0.10, 0.102],
           ["%ragged-dot-none.4 = bf16[384,2048]{1,0", 0.103, 0.105],
           ["%ragged-dot-metadata.1 = (s32[65]", 0.105, 0.106],
           ["%fusion.7 = bf16[64,2048]", 0.11, 0.15],
           ["%ragged-dot-none = bf16[2048,1408]{1,0", 0.31, 0.39]]  # a prefill's: not read
    o = _observed(ops, counters)
    least = max(2 * 3 * 2048 * 1408 * 63 * 6 / 819e9, 2 * 3 * 2048 * 1408 * 6 * 64 * 6 / 197e12)
    assert ob.read("expert_matmul_roofline", o) == pytest.approx(100 * least / 0.004)


def test_expert_reader_finds_nothing_without_experts_or_their_ops():
    o = _observed([["%fusion.7 = bf16[64,2048]", 0.11, 0.15]], {})
    assert ob.read("expert_matmul_roofline", o) is None
    dense = json.loads((BENCH / "configs" / "granite-3-8b.pp4.json").read_text())["model"]
    from bench.costs import dense_gqa
    o = dataclasses.replace(_observed([["%ragged-dot-none.3 = bf16[8,4]", 0.10, 0.102]], {}),
                            models={"ds": dense}, costs={"ds": dense_gqa})
    assert ob.read("expert_matmul_roofline", o) is None
    assert tr.window_of(o.trace) == (0.0, 1.0)


# ---- mixes ----

def test_moe_decode_mix_as_specified():
    mix = traffic.load_mix("moe-decode")
    assert (mix["loop"], mix["clients"], mix["max_slots"], mix["max_len"], mix["block"]) == (
        "closed", 64, 64, 1536, 64)
    s = traffic.Schedule(mix, 2**33 + 9, "cell")
    reqs = [s.next() for _ in range(128)]
    assert {r.prompt_len for r in reqs} == {512, 1024}
    assert sum(r.prompt_len == 512 for r in reqs) == 64
    outs = np.array([r.max_new for r in reqs])
    assert outs.min() >= 64 and outs.max() <= 512 and np.median(outs) == pytest.approx(256, abs=8)
    assert all(r.prompt_len + r.max_new <= mix["max_len"] for r in reqs)


def test_duo_longprompt_mix_as_specified():
    mix = traffic.load_mix("duo-longprompt")
    assert (mix["loop"], mix["max_slots"], mix["max_len"], mix["block"]) == ("open", 4, 2080, 10)
    s = traffic.Schedule(mix, 2**33 + 9, "cell")
    reqs = [s.next() for _ in range(60)]
    assert sum(r.model == "cell" for r in reqs) == 42  # 70% of each block of 10
    assert {r.model for r in reqs} == {"cell", "granite-3-8b.pp4"}
    assert {r.prompt_len for r in reqs} == {1024, 1536, 2048}
    assert all(8 <= r.max_new <= 32 and r.prompt_len + r.max_new <= mix["max_len"] for r in reqs)
    assert np.median([r.max_new for r in reqs]) == pytest.approx(16, abs=2)
    due = traffic.Schedule(mix, 0, "cell").due(60.0)
    assert len(due) == pytest.approx(60 * mix["rate_rps"], abs=mix["block"])
