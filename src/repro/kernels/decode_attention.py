"""Flash-decode Pallas TPU kernel: one query token vs a long KV cache.

Decode attention is memory-bound (the whole KV cache streams through once
per token), so the kernel's job is to keep that stream dense: the KV axis is
the sequential grid dimension, each step pulls one MXU-aligned KV tile into
VMEM, and the (acc, m, l) online-softmax state for all G q-heads of the
group lives in VMEM scratch. All q-heads of a kv group are processed in one
tile (G x Dk), so the KV tile is read once per *group*, not per head —
the GQA bandwidth saving is realised structurally.

The wrapper puts the kv-head axis ahead of the sequence axis, so every
block's last two dims are (tile, head_dim): the TPU's (8, 128) tiling
accepts them at any head count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _decode_kernel(meta_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, scale, window, softcap, block_k, nk):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32)  # (G, Dk)
    k = k_ref[...].astype(jnp.float32)  # (bk, Dk)
    v = v_ref[...].astype(jnp.float32)  # (bk, Dv)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (G, bk)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    qpos = meta_ref[0]  # absolute position of the query token
    kv_len = meta_ref[1]
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = kpos < kv_len
    if window is not None:
        keep &= (qpos - kpos) < window
    s = jnp.where(keep, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _fin():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "scale", "block_k", "interpret"))
def decode_attention(q, k, v, *, q_offset=0, kv_len=None, window=None,
                     softcap=None, scale=None, block_k=512, interpret=None):
    """q (B,1,H,Dk); k (B,Sk,Hkv,Dk); v (B,Sk,Hkv,Dv) -> (B,1,H,Dv)."""
    B, _, H, Dk = q.shape
    Sk, Hkv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // Hkv
    scale = scale if scale is not None else Dk ** -0.5
    interpret = interpret_mode(interpret)

    block_k = min(block_k, Sk)
    nk = -(-Sk // block_k)
    pk = nk * block_k - Sk
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    qg = q.reshape(B, Hkv, G, Dk)  # q-heads h*G..h*G+G-1 share kv head h
    kt = k.transpose(0, 2, 1, 3)  # (B, Hkv, Sk, Dk)
    vt = v.transpose(0, 2, 1, 3)
    eff_len = jnp.asarray(Sk if kv_len is None else jnp.minimum(kv_len, Sk))
    meta = jnp.stack([jnp.asarray(q_offset, jnp.int32).reshape(()),
                      eff_len.astype(jnp.int32).reshape(())])

    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               softcap=softcap, block_k=block_k, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, None, G, Dk), lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((None, None, block_k, Dk), lambda b, h, ki: (b, h, ki, 0)),
            pl.BlockSpec((None, None, block_k, Dv), lambda b, h, ki: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, G, Dv), lambda b, h, ki: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, Dv), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(meta, qg, kt, vt)
    return out.reshape(B, 1, H, Dv)
