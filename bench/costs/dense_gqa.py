"""Operations and bytes that a step of a dense GQA model needs, from its shapes.

What the algorithm needs, not what the program happens to do: attention over
the true context of each sequence (not the whole ``max_len`` cache), the LM
head at the last prompt position only, the vocabulary as configured (not its
padding), and bf16 weights and KV (2 bytes) with float32 norm scales and
biases. ``m`` is a configuration's ``model`` block (``bench/configs``);
``counters`` are the program's counters for the resident over the window,
which a dense model's costs do not depend on.
"""
from __future__ import annotations

from typing import Dict, Iterable


def _widths(m: dict):
    q = m["num_heads"] * m["head_dim"]
    kv = m["num_kv_heads"] * m["head_dim"]
    return m["d_model"], q, kv, m["d_ff"], m["vocab_size"]


def layer_matmul_params(m: dict) -> int:
    d, q, kv, ff, _ = _widths(m)
    return d * (q + 2 * kv) + q * d + 3 * d * ff


def _layer_f32_params(m: dict) -> int:
    d, q, kv, _, _ = _widths(m)
    return 2 * d + ((q + 2 * kv) if m["qkv_bias"] else 0)


def weight_bytes(m: dict) -> int:
    """Bytes of the weights held on the chip (embedding and LM head included)."""
    d, _, _, _, v = _widths(m)
    tables = v * d * (1 if m["tie_embeddings"] else 2)
    return (m["num_layers"] * (2 * layer_matmul_params(m) + 4 * _layer_f32_params(m))
            + 2 * tables + 4 * d)


def decode_weight_bytes(m: dict) -> int:
    """Weight bytes one decode step reads: every layer and the LM head; of an
    untied embedding only the rows looked up, which are left out."""
    d, _, _, _, v = _widths(m)
    return weight_bytes(m) - (0 if m["tie_embeddings"] else 2 * v * d)


def kv_bytes_per_token(m: dict) -> int:
    """Bytes of K and V for one position over all layers."""
    return m["num_layers"] * 2 * m["num_kv_heads"] * m["head_dim"] * 2


def _token_flops(m: dict, context: int, lm_head: bool) -> int:
    """One token at position ``context - 1``: the matmuls, attention over
    ``context`` keys (scores and values), and the LM head if asked."""
    d, q, _, _, v = _widths(m)
    attn = 4 * q * context
    return (m["num_layers"] * (2 * layer_matmul_params(m) + attn)
            + (2 * d * v if lm_head else 0))


def decode_step(m: dict, positions: Iterable[int], counters: Dict[str, int]):
    """(flops, bytes) of one decode step for the active sequences, each
    writing its new token at ``pos`` and attending over ``pos + 1`` keys."""
    positions = list(positions)
    flops = sum(_token_flops(m, p + 1, True) for p in positions)
    kv = kv_bytes_per_token(m)
    nbytes = decode_weight_bytes(m) + sum((p + 1) * kv for p in positions)
    return flops, nbytes


def prefill(m: dict, lengths: Iterable[int], counters: Dict[str, int]):
    """(flops, bytes) of prefilling prompts of ``lengths``: causal attention,
    the LM head at the last position, KV written for every position."""
    lengths = list(lengths)
    d, q, _, _, v = _widths(m)
    flops = 0
    for n in lengths:
        # the sum over c = 1..n of _token_flops(m, c, False), in closed form
        flops += m["num_layers"] * (2 * layer_matmul_params(m) * n + 4 * q * n * (n + 1) // 2)
        flops += 2 * d * v
    nbytes = decode_weight_bytes(m) + sum(lengths) * kv_bytes_per_token(m)
    return flops, nbytes
