"""The served stack and the reference's layer-by-layer weights are the same numbers."""
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench.weights import dense_gqa as weights

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


# sha256 over every leaf of served_params(2**33 + 17, m), in tree order: its
# path, dtype, shape and bytes. Recorded when the program that makes the
# weights held the seed as a constant; the seed as an argument, which lets one
# compiled program serve every seed, has to make the same numbers
DIGESTS = {"tiny-qwen": "b06550e15a359a8126c86749fb0f2b9b8e0ede497caf1315d1f0d2d1acecf46e",
           "tiny-granite": "d32abf6168260282ff98a9194c9936ab1d5f8e7874753791d42d2adc55c8766b"}


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_weights_are_the_recorded_numbers(name):
    m = json.loads((DATA / f"{name}.json").read_text())["model"]
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(weights.served_params(2**33 + 17, m))[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    assert h.hexdigest() == DIGESTS[name]
