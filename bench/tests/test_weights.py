"""The served stack and the reference's layer-by-layer weights are the same numbers."""
import json
from pathlib import Path

import jax
import numpy as np

from bench import weights

DATA = Path(__file__).resolve().parent / "data"


def test_stacked_layers_equal_layers_made_alone():
    m = json.loads((DATA / "tiny-qwen.json").read_text())["model"]
    seed = 2**33 + 17
    served = weights.served_params(seed, m)
    root = weights.root_key(seed)
    for i in range(m["num_layers"]):
        alone = jax.jit(lambda i: weights.layer(root, m, i))(i)
        stacked = jax.tree.map(lambda a: a[i], served["stages"][0]["l0"])
        for a, b in zip(jax.tree.leaves(alone), jax.tree.leaves(stacked)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    emb = served["embed"]["embedding"]
    assert not np.asarray(emb[m["vocab_size"]:]).any()  # padded rows are zero
    assert np.asarray(emb[: m["vocab_size"]]).std() > 0


def test_seeds_give_different_weights():
    m = json.loads((DATA / "tiny-granite.json").read_text())["model"]
    a, b = (weights.served_params(s, m)["embed"]["embedding"] for s in (1, 2))
    assert not np.array_equal(np.asarray(a), np.asarray(b))
