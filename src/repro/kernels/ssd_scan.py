"""Mamba2 SSD chunked-scan Pallas TPU kernel.

TPU adaptation of the SSD algorithm: the GPU implementation leans on warp
shuffles for the intra-chunk cumulative decays; here everything is cast as
dense MXU work — the intra-chunk term is a (Q x Q) masked "attention"
matmul and the inter-chunk state is a (N x Q)(Q x P) matmul, with the
running state (P x N) carried in VMEM scratch across the sequential chunk
grid dimension. Q = chunk length is the MXU tile knob.

Inputs: x (B,S,H,P), dA (B,S,H) log-decays, dt (B,S,H), Bm/Cm (B,S,N).
Outputs: y (B,S,H,P), final state (B,H,P,N).

The wrapper puts the head axis ahead of the sequence axis and hands each
chunk's per-head decays to the kernel as a (1, Q) row, so every block's
last two dims meet the TPU's (8, 128) tiling. Inside the kernel every
vector stays 2-D: the within-chunk cumulative sums are masked reductions
of that row, which yield both its column and its row form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _ssd_kernel(x_ref, da_ref, dt_ref, b_ref, c_ref, y_ref, hout_ref, state_ref,
                *, chunk, nc):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...].astype(jnp.float32)  # (Q,P)
    da = da_ref[...].astype(jnp.float32)  # (1,Q)
    dt = dt_ref[...].astype(jnp.float32)  # (1,Q)
    bm = b_ref[...].astype(jnp.float32)  # (Q,N)
    cm = c_ref[...].astype(jnp.float32)  # (Q,N)

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = ii >= jj
    diag = ii == jj
    # cum_i = sum_{j<=i} da_j as a column; the diagonal of its lane
    # broadcast, summed down the rows, is the same vector as a row
    cum_col = jnp.where(lower, jnp.broadcast_to(da, (chunk, chunk)),
                        0.0).sum(axis=1, keepdims=True)  # (Q,1)
    cum_row = jnp.where(diag, jnp.broadcast_to(cum_col, (chunk, chunk)),
                        0.0).sum(axis=0, keepdims=True)  # (1,Q)
    total = jnp.sum(da, axis=1, keepdims=True)  # (1,1) = cum of the last row
    dt_col = jnp.where(diag, jnp.broadcast_to(dt, (chunk, chunk)),
                       0.0).sum(axis=1, keepdims=True)  # (Q,1)

    # intra-chunk: M[i,j] = exp(cum_i - cum_j) (i>=j) * (C_i.B_j) * dt_j
    L = jnp.where(lower, jnp.exp(cum_col - cum_row), 0.0)
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())))  # (Q,Q)
    M = cb * L * dt
    y = jax.lax.dot(M, x)  # (Q,P)

    # inter-chunk: y += exp(cum_i) * C_i . h_in ; h_in (P,N)
    h = state_ref[...]
    y += jnp.exp(cum_col) * jax.lax.dot_general(cm, h, (((1,), (1,)), ((), ())))

    # state update: h_out = exp(cum_Q) h_in + sum_j exp(cum_Q - cum_j) dt_j x_j B_j^T
    w = jnp.exp(total - cum_col) * dt_col  # (Q,1)
    upd = jax.lax.dot_general(x * w, bm, (((0,), (0,)), ((), ())))  # (P,N)
    state_ref[...] = jnp.exp(total) * h + upd

    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _fin():
        hout_ref[...] = state_ref[...].astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dA, dt, Bm, Cm, *, mask=None, chunk=128, interpret=None):
    """``mask`` (B,S) bool/float — True at valid positions — makes bucketed
    prompt padding pad-token-safe: masked positions have ``dt``/``dA``/input
    zeroed before the scan, so they neither write into nor decay the carried
    state (decay ``exp(0) = 1``) and the final state equals the scan over
    the valid positions alone. The per-chunk tail padding below already uses
    the same identity (``jnp.pad`` zeros)."""
    if mask is not None:
        m = mask.astype(jnp.float32)
        x = x * m[:, :, None, None].astype(x.dtype)
        dA = dA * m[:, :, None].astype(dA.dtype)
        dt = dt * m[:, :, None].astype(dt.dtype)
        Bm = Bm * m[:, :, None].astype(Bm.dtype)
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    interpret = interpret_mode(interpret)
    Q = min(chunk, S)
    nc = -(-S // Q)
    pad = nc * Q - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dA = jnp.pad(dA, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    xt = x.transpose(0, 2, 1, 3)  # (B, H, S, P)

    def rows(a):  # (B, S, H) -> (B, H, nc, 1, Q): one (1, Q) row per chunk
        return a.transpose(0, 2, 1).reshape(B, H, nc, 1, Q)

    kernel = functools.partial(_ssd_kernel, chunk=Q, nc=nc)
    row_spec = pl.BlockSpec((None, None, None, 1, Q), lambda b, h, c: (b, h, c, 0, 0))
    y, hout = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((None, None, Q, P), lambda b, h, c: (b, h, c, 0)),
            row_spec,
            row_spec,
            pl.BlockSpec((None, Q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((None, Q, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc * Q, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xt, rows(dA), rows(dt), Bm, Cm)
    return y[:, :, :S].transpose(0, 2, 1, 3), hout
