"""The plain float32 reference against the program's model code, at a CPU size.

The program runs the same seeded weights in float32 here (its served dtype is
bf16 on the chip), so the two must agree to float32 rounding: prefill at every
position, and decode through the cache after it."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.reference import dense_gqa
from bench.weights import dense_gqa as weights

DATA = Path(__file__).resolve().parent / "data"
TOL = 2e-4  # float32 logits of order 1 through two layers, summed in other orders


def f32(conf):
    conf = json.loads(json.dumps(conf))
    conf["model"].update(dtype="float32", param_dtype="float32")
    conf["reduced"] += ["dtype", "param_dtype"]
    return conf


@pytest.mark.parametrize("name", ["tiny-qwen", "tiny-granite"])
def test_reference_matches_program_prefill_and_decode(name):
    from repro.models import model as model_lib

    conf = f32(json.loads((DATA / f"{name}.json").read_text()))
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
    prog = np.concatenate(prog)  # next-token logits at positions 0 .. S+extra-1
    rows = np.arange(S + extra)
    toks = np.random.default_rng(1).integers(0, m["vocab_size"], S + extra)
    ref = dense_gqa.Reference(m, seed)
    (best, at, am), = ref.score([seq], [rows], [toks])
    np.testing.assert_allclose(best, prog.max(axis=1), atol=TOL)
    np.testing.assert_allclose(at, prog[rows, toks], atol=TOL)
    assert (am == prog.argmax(axis=1)).mean() > 0.95  # ties may flip on rounding


def test_control_rounds_weights():
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 64))
    q = dense_gqa.quantize(w, "fp8")
    rel = jnp.abs(q - w) / jnp.max(jnp.abs(w), axis=0)
    assert 0 < float(rel.max()) <= 2.0 ** -4 * 1.01


def test_control_rounds_the_activations_that_meet_a_weight():
    """Under the control a product with a weight takes its left operand in
    fp8, row by row; without it the product is the plain one."""
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 256))
    w = jax.random.normal(jax.random.PRNGKey(2), (256, 64))
    assert (dense_gqa._mm(x, w, None) == x @ w).all()
    want = dense_gqa.quantize(x.T, "fp8").T @ w
    np.testing.assert_array_equal(np.asarray(dense_gqa._mm(x, w, "fp8")), np.asarray(want))
    assert not np.allclose(np.asarray(want), np.asarray(x @ w), rtol=1e-3)
