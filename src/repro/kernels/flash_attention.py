"""Flash attention Pallas TPU kernel (blockwise online softmax).

TPU-native design notes (vs the CUDA flash-attention the GPU world uses):
  * tiles are MXU-aligned — block_q x head_dim and block_k x head_dim with
    head_dim padded to a multiple of 128 so QK^T and PV land on the
    128x128 systolic array;
  * the KV loop is the innermost *grid* dimension (TPU grids execute
    sequentially per core), with the (acc, m, l) online-softmax state in
    VMEM scratch persisting across KV steps — no HBM round-trips;
  * GQA is handled by indexing the kv head as h // group in the BlockSpec
    index maps, so no repeated-KV materialisation in HBM;
  * causal/sliding-window/kv-length masking is computed from positions via
    broadcasted iota inside the kernel; (q_offset, kv_len) arrive as SMEM
    scalars so decode can trace them dynamically.

Supports: causal, sliding window, logit softcap, GQA, q_offset/kv_len.

The wrapper puts the head axis ahead of the sequence axis, so every block's
last two dims are (tile, head_dim) and meet the TPU's (8, 128) tiling at
any head count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _flash_kernel(meta_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, scale, causal, window, softcap, block_q, block_k, nk):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32)  # (bq, D)
    k = k_ref[...].astype(jnp.float32)  # (bk, D)
    v = v_ref[...].astype(jnp.float32)  # (bk, Dv)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (bq, bk)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_offset = meta_ref[0]
    kv_len = meta_ref[1]
    qpos = q_offset + qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    keep = kpos < kv_len
    if causal:
        keep &= kpos <= qpos
    if window is not None:
        keep &= (qpos - kpos) < window
    s = jnp.where(keep, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(keep, p, 0.0)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _fin():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    q_offset=0, kv_len=None, scale=None,
                    block_q=128, block_k=128, interpret=None):
    """q (B,Sq,H,Dk); k (B,Sk,Hkv,Dk); v (B,Sk,Hkv,Dv) -> (B,Sq,H,Dv)."""
    B, Sq, H, Dk = q.shape
    Sk, Hkv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // Hkv
    scale = scale if scale is not None else Dk ** -0.5
    interpret = interpret_mode(interpret)

    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, Sk)
    nq = -(-Sq // block_q)
    nk = -(-Sk // block_k)
    pq = nq * block_q - Sq
    pk = nk * block_k - Sk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    eff_len = jnp.asarray(Sk if kv_len is None else jnp.minimum(kv_len, Sk))
    meta = jnp.stack([jnp.asarray(q_offset, jnp.int32).reshape(()),
                      eff_len.astype(jnp.int32).reshape(())])

    qt = q.transpose(0, 2, 1, 3)  # (B, H, Sq, Dk)
    kt = k.transpose(0, 2, 1, 3)  # (B, Hkv, Sk, Dk)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, nk=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, None, block_q, Dk), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((None, None, block_k, Dk), lambda b, h, qi, ki, _G=G: (b, h // _G, ki, 0)),
            pl.BlockSpec((None, None, block_k, Dv), lambda b, h, qi, ki, _G=G: (b, h // _G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, Dv), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * block_q, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(meta, qt, kt, vt)
    return out[:, :, :Sq].transpose(0, 2, 1, 3)
