"""costs/dense_gqa.py against the arithmetic by hand for both configurations."""
import json
from pathlib import Path

import pytest

from bench.costs import dense_gqa as fb

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


@pytest.mark.parametrize("name, layer_gb, tables_gb, total_gb", [
    # 3584 * (3584 + 2 * 512) + 3584 * 3584 + 3 * 3584 * 18944 params a layer,
    # two 152064 x 3584 tables, 7 layers: 7 x 0.466 + 2.180 = 5.44 GB
    ("qwen2-7b.pp4", 0.466, 2.180, 5.44),
    # 4096 * (4096 + 2 * 1024) + 4096 * 4096 + 3 * 4096 * 12800, one tied
    # 49155 x 4096 table, 10 layers: 10 x 0.398 + 0.403 = 4.39 GB
    ("granite-3-8b.pp4", 0.398, 0.403, 4.39),
])
def test_weight_bytes_match_hand_arithmetic(name, layer_gb, tables_gb, total_gb):
    m = model(name)
    assert 2 * fb.layer_matmul_params(m) / 1e9 == pytest.approx(layer_gb, abs=0.0005)
    assert fb.weight_bytes(m) / 1e9 == pytest.approx(total_gb, abs=0.005)
    tables = fb.weight_bytes(m) - m["num_layers"] * 2 * fb.layer_matmul_params(m)
    assert tables / 1e9 == pytest.approx(tables_gb, abs=0.002)


@pytest.mark.parametrize("name, kib", [("qwen2-7b.pp4", 2), ("granite-3-8b.pp4", 4)])
def test_kv_bytes_per_token_per_layer(name, kib):
    m = model(name)
    assert fb.kv_bytes_per_token(m) == m["num_layers"] * kib * 1024


def test_decode_step_counts_weights_once_and_kv_per_slot():
    m = model("granite-3-8b.pp4")
    f1, b1 = fb.decode_step(m, [99], {})
    f2, b2 = fb.decode_step(m, [99, 199], {})
    assert b1 == fb.decode_weight_bytes(m) + 100 * fb.kv_bytes_per_token(m)
    assert b2 - b1 == 200 * fb.kv_bytes_per_token(m)
    # a token is 2 FLOPs per matmul weight plus 4 * q_dim per key per layer
    per_token = 2 * (10 * fb.layer_matmul_params(m) + 4096 * 49155)
    assert f1 == per_token + 10 * 4 * 4096 * 100
    assert f2 == 2 * per_token + 10 * 4 * 4096 * 300


def test_prefill_closed_form_matches_the_sum_over_positions():
    m = model("qwen2-7b.pp4")
    direct = sum(fb._token_flops(m, c, False) for c in range(1, 301)) + 2 * 3584 * 152064
    assert fb.prefill(m, [300], {})[0] == direct
    assert fb.decode_weight_bytes(m) == fb.weight_bytes(m) - 2 * 152064 * 3584
