"""Jit'd public entrypoints for the Pallas kernels.

TPU is the *target*: on a TPU backend every kernel compiles natively
(Mosaic, a ``tpu_custom_call`` in the compiled program) and never runs in
interpret mode. On any other backend (CPU tests) the kernel body runs in
the Pallas interpreter, for correctness only. Model code calls these
only under ``ParallelConfig.use_pallas``; the XLA reference paths in
``repro.models`` are used otherwise, so dry-run lowering never depends on
Pallas.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import grouped_matmul as _gmm
from . import paged_attention as _pa
from . import ssd_scan as _ssd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=not _on_tpu())


def paged_attention(q, k_pool, v_pool, block_tables, pos, *, window=0):
    """One-token decode attention through a block table (paged KV cache)."""
    return _pa.paged_attention(q, k_pool, v_pool, block_tables, pos,
                               window=window, interpret=not _on_tpu())


def grouped_matmul(x, w, group_sizes, *, block_c=128, block_f=128,
                   block_k=512):
    return _gmm.grouped_matmul(x, w, group_sizes, block_c=block_c,
                               block_f=block_f, block_k=block_k,
                               interpret=not _on_tpu())


def grouped_mlp(xe, w_gate, w_up, w_down, group_sizes):
    """SwiGLU expert MLP on a capacity-padded [E,C,d] buffer via three
    grouped GEMMs (the §2.1.8 hot path)."""
    gate = jax.nn.silu(grouped_matmul(xe, w_gate, group_sizes))
    up = grouped_matmul(xe, w_up, group_sizes)
    return grouped_matmul(gate * up, w_down, group_sizes)


def grouped_mlp_batched(xe, w_gate, w_up, w_down):
    """MoE path used by ``moe_apply`` under use_pallas.

    xe: [B, E, C, d] capacity-padded dispatch buffers (padding rows are exact
    zeros). Flattens the batch into the capacity dim so one kernel call
    covers all rows: [E, B*C, d].
    """
    B, E, C, d = xe.shape
    x = xe.transpose(1, 0, 2, 3).reshape(E, B * C, d)
    # all rows participate; padded rows are zero and produce zero
    sizes = jnp.full((E,), B * C, jnp.int32)
    y = grouped_mlp(x, w_gate, w_up, w_down, sizes)
    return y.reshape(E, B, C, w_down.shape[-1]).transpose(1, 0, 2, 3)


def ssd_scan(xh, dt, dA_log, Bh, Ch, h0, *, chunk=128):
    return _ssd.ssd_scan(xh, dt, dA_log, Bh, Ch, h0, chunk=chunk,
                         interpret=not _on_tpu())
