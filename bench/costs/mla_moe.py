"""Operations and bytes that a step of a latent-attention, mixture-of-experts
model (DeepSeek-V2's layers) needs, from its shapes and its router's counters.

What the algorithm needs, not what the program happens to do:

- Decode counts attention in its absorbed form, the one a decode step needs:
  the query's no-rope part is taken into the latent space once (heads x nope x
  latent), scores and values are read from the cached latent and rope key of
  the true context of each sequence (not the whole ``max_len`` cache), and
  the values leave the latent space once per head. The cache holds
  ``kv_lora_rank + qk_rope_dim`` numbers a position and layer, in bf16.
- Prefill counts attention in its expanded form, the one a whole prompt
  needs: the latent is expanded per head into keys and values at every
  position, then causal attention at the full head widths.
- Expert layers count, per token, the router, the top-k routed experts and
  the shared experts. The bytes a decode step reads of the routed experts are
  those of the experts the window's steps touched on average, from the
  program's counters (``moe_decode_experts_touched`` over
  ``moe_decode_layer_steps``); without them, the most k tokens a step could
  touch (``min(E, k x active)``) stand in.

Weights are bf16 (2 bytes), the router, norm scales in float32. The LM head
runs at the last prompt position only, over the vocabulary as configured.
``m`` is a configuration's ``model`` block (``bench/configs``).
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple


def _moe_layers(m: dict) -> int:
    return m["num_layers"] - m["first_dense_layers"]


def attention_params(m: dict) -> int:
    """bf16 matrix parameters of one MLA layer."""
    d, H, lr = m["d_model"], m["num_heads"], m["kv_lora_rank"]
    nope, rope, vd = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    return d * H * (nope + rope) + d * (lr + rope) + lr * H * (nope + vd) + H * vd * d


def expert_params(m: dict) -> int:
    """Parameters of one routed expert."""
    return 3 * m["d_model"] * m["moe_d_ff"]


def shared_params(m: dict) -> int:
    return 3 * m["d_model"] * m["num_shared_experts"] * m["moe_d_ff"]


def weight_bytes(m: dict) -> int:
    """Bytes of the weights held on the chip (embedding and untied LM head
    included)."""
    d, E = m["d_model"], m["num_experts"]
    bf16 = (2 * m["vocab_size"] * d + m["num_layers"] * attention_params(m)
            + m["first_dense_layers"] * 3 * d * m["d_ff"]
            + _moe_layers(m) * (E * expert_params(m) + shared_params(m)))
    f32 = (m["num_layers"] * (2 * d + m["kv_lora_rank"]) + d
           + _moe_layers(m) * d * E)
    return 2 * bf16 + 4 * f32


def latent_bytes_per_token(m: dict) -> int:
    """Bytes of the latent cache for one position over all layers."""
    return m["num_layers"] * (m["kv_lora_rank"] + m["qk_rope_dim"]) * 2


def experts_touched(m: dict, active: int, counters: Dict[str, int]) -> float:
    """Routed experts one expert layer of a decode step reads: the window's
    mean from the program's counters, else at most k per active token."""
    steps = counters.get("moe_decode_layer_steps", 0)
    if steps:
        return counters["moe_decode_experts_touched"] / steps
    return min(m["num_experts"], m["top_k"] * active)


def expert_decode(m: dict, active: int, counters: Dict[str, int]) -> Tuple[int, float]:
    """(flops, bytes) of the routed experts' grouped matmuls in one decode
    step over ``active`` sequences: the k assignments of each token through
    the three matmuls, and the touched experts' weights."""
    flops = 2 * expert_params(m) * m["top_k"] * active * _moe_layers(m)
    nbytes = 2 * expert_params(m) * experts_touched(m, active, counters) * _moe_layers(m)
    return flops, nbytes


def decode_weight_bytes(m: dict, active: int, counters: Dict[str, int]) -> float:
    """Weight bytes one decode step reads: every weight but the embedding
    (of which only the rows looked up) and but the routed experts no token
    reached."""
    untouched = m["num_experts"] * expert_params(m) * _moe_layers(m) * 2
    return (weight_bytes(m) - 2 * m["vocab_size"] * m["d_model"] - untouched
            + expert_decode(m, active, counters)[1])


def _mlp_flops(m: dict) -> int:
    """One token through the MLPs: dense layers, and in each expert layer the
    router, the top-k routed experts and the shared experts."""
    d = m["d_model"]
    dense = m["first_dense_layers"] * 2 * 3 * d * m["d_ff"]
    moe = 2 * (d * m["num_experts"] + m["top_k"] * expert_params(m) + shared_params(m))
    return dense + _moe_layers(m) * moe


def _decode_attention_flops(m: dict, context: int) -> int:
    """Absorbed MLA for one token over ``context`` cached positions, one layer."""
    d, H, lr = m["d_model"], m["num_heads"], m["kv_lora_rank"]
    nope, rope, vd = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    proj = d * H * (nope + rope) + d * (lr + rope) + H * nope * lr + H * lr * vd + H * vd * d
    return 2 * proj + 2 * H * (lr + rope) * context + 2 * H * lr * context


def _prefill_attention_flops(m: dict, n: int) -> int:
    """Expanded MLA over a prompt of ``n`` tokens, one layer: projections at
    every position, causal scores and values over positions 1..n."""
    H, nope, rope, vd = m["num_heads"], m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    keys = n * (n + 1) // 2  # the sum over positions of the keys each attends to
    return 2 * attention_params(m) * n + 2 * H * (nope + rope) * keys + 2 * H * vd * keys


def decode_step(m: dict, positions: Iterable[int], counters: Dict[str, int]):
    """(flops, bytes) of one decode step for the active sequences, each
    writing its new token at ``pos`` and attending over ``pos + 1`` positions."""
    positions = list(positions)
    L, d, v = m["num_layers"], m["d_model"], m["vocab_size"]
    flops = sum(L * _decode_attention_flops(m, p + 1) + _mlp_flops(m) + 2 * d * v
                for p in positions)
    nbytes = (decode_weight_bytes(m, len(positions), counters)
              + sum((p + 1) * latent_bytes_per_token(m) for p in positions))
    return flops, nbytes


def prefill(m: dict, lengths: Iterable[int], counters: Dict[str, int]):
    """(flops, bytes) of prefilling prompts of ``lengths``: expanded
    attention, every expert's weights read, the LM head at the last
    position, the latent cache written for every position."""
    lengths = list(lengths)
    L, d, v = m["num_layers"], m["d_model"], m["vocab_size"]
    flops = sum(L * _prefill_attention_flops(m, n) + n * _mlp_flops(m) + 2 * d * v
                for n in lengths)
    nbytes = (weight_bytes(m) - 2 * v * d) + sum(lengths) * latent_bytes_per_token(m)
    return flops, nbytes
