"""Pallas TPU chunked SSD scan — the Mamba-2 (state-space duality) hot loop.

The SSD algorithm (arXiv:2405.21060) splits the sequence into chunks: within
a chunk the recurrence is a masked quadratic ("attention-like") contraction
that maps onto the MXU; across chunks only a small ``[head_dim, state]``
recurrent state is carried. On TPU the chunk axis is the innermost grid
dimension — sequential per (batch·head), with the carried state living in
VMEM scratch across grid steps (the same trick as the flash kernel's online
softmax state).

Grid: ``(batch*heads, num_chunks)``. Block shapes put one [chunk, ·] tile of
x/B/C/dt in VMEM; the [chunk, chunk] decay matrix is built in-register from a
cumulative-sum iota, and both the intra-chunk term and the state update are
expressed as ``dot_general`` MXU contractions in fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, ac_ref, ar_ref, b_ref, c_ref, h0_ref,  # in
                y_ref, hT_ref,                                      # out
                h_ref,                                              # VMEM
                *, chunk, num_chunks):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)          # [L, hd]
    dt = dt_ref[0]                            # [L, 1]
    a_col = ac_ref[0]                         # [L, 1] in-chunk cumsum of dt*A
    a_row = ar_ref[0]                         # [1, L] the same, as a row
    Bc = b_ref[0].astype(jnp.float32)         # [L, n]
    Cc = c_ref[0].astype(jnp.float32)         # [L, n]

    # intra-chunk quadratic term: scores[i,j] = (C_i·B_j)·exp(a_i-a_j)·1[i>=j]
    scores = jax.lax.dot_general(Cc, Bc, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [L,L]
    i_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = i_idx >= j_idx
    decay = jnp.exp(jnp.where(causal, a_col - a_row, 0.0))
    scores = jnp.where(causal, scores * decay, 0.0)
    xdt = x * dt                              # [L, hd]
    y_intra = jax.lax.dot_general(scores, xdt, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: y_i += exp(a_i) * C_i · h   (h: [hd, n])
    h = h_ref[...]
    Ch = jax.lax.dot_general(Cc, h, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [L, hd]
    y_ref[0] = (y_intra + jnp.exp(a_col) * Ch).astype(y_ref.dtype)

    # state update: h' = exp(a_end)·h + sum_j exp(a_end - a_j)·dt_j·x_j⊗B_j
    # a_end as a true scalar (a [1, 1] slice cannot broadcast to [hd, n])
    last = jax.lax.broadcasted_iota(jnp.int32, a_row.shape, 1) == chunk - 1
    a_end = jnp.sum(jnp.where(last, a_row, 0.0))
    xw = x * (jnp.exp(a_end - a_col) * dt)    # [L, hd]
    outer = jax.lax.dot_general(xw, Bc, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [hd, n]
    h_ref[...] = jnp.exp(a_end) * h + outer

    @pl.when(ic == num_chunks - 1)
    def _emit_state():
        hT_ref[0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(xh, dt, dA_log, Bh, Ch, h0, *, chunk=128, interpret=True):
    """Chunked SSD scan.

    xh: [B,S,nh,hd]; dt, dA_log: [B,S,nh]; Bh, Ch: [B,S,nh,n];
    h0: [B,nh,hd,n]. Returns (y [B,S,nh,hd] fp32, hT [B,nh,hd,n] fp32).
    """
    B, S, nh, hd = xh.shape
    n = Bh.shape[-1]
    chunk = min(chunk, S)
    nc = -(-S // chunk)
    Sp = nc * chunk

    def to_bh(a, feat):
        a = a.transpose(0, 2, 1, *range(3, a.ndim)) if a.ndim > 3 else \
            a.transpose(0, 2, 1)
        a = a.reshape((B * nh, S) + feat)
        if Sp != S:
            pad = [(0, 0), (0, Sp - S)] + [(0, 0)] * len(feat)
            a = jnp.pad(a, pad)
        return a

    xf = to_bh(xh, (hd,))
    Bf = to_bh(Bh, (n,))
    Cf = to_bh(Ch, (n,))
    h0f = h0.reshape(B * nh, hd, n)
    # dt and the in-chunk cumsum of dA_log are per-position scalars. They
    # go in as a column [BH, Sp, 1] (broadcast against [L, hd] rows) and
    # the cumsum also as a row [BH, 1, Sp] (the j side of the decay
    # matrix): both block shapes keep the (8, 128) tiling, which a 2-D
    # [BH, Sp] array blocked by (1, chunk) cannot. Zero padding of the tail
    # makes padded positions pass the state through unchanged.
    dtf = to_bh(dt, ()).astype(jnp.float32)
    acum = jnp.cumsum(to_bh(dA_log, ()).astype(jnp.float32)
                      .reshape(B * nh, nc, chunk), axis=-1).reshape(
                          B * nh, Sp)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, num_chunks=nc)
    y, hT = pl.pallas_call(
        kernel,
        grid=(B * nh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bh, ic: (bh, 0, ic)),
            pl.BlockSpec((1, chunk, n), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, hd, n), lambda bh, ic: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hd), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, hd, n), lambda bh, ic: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * nh, Sp, hd), jnp.float32),
            jax.ShapeDtypeStruct((B * nh, hd, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, n), jnp.float32)],
        interpret=interpret,
    )(xf, dtf[..., None], acum[..., None], acum[:, None], Bf, Cf, h0f)

    y = y[:, :S].reshape(B, nh, S, hd).transpose(0, 2, 1, 3)
    hT = hT.reshape(B, nh, hd, n)
    return y, hT
