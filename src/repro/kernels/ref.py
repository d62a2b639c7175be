"""Pure-jnp reference oracles for every Pallas kernel.

These are deliberately naive (materialize the full score matrix, loop the
recurrence with ``lax.scan`` one step at a time) so that any algebraic
shortcut in the kernels is checked against first-principles math.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal=True, window=0):
    """q: [B,S,Hq,hd]; k,v: [B,S,Hkv,hd] -> [B,S,Hq,hd]. Full-score softmax."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    # materialize repeated KV heads (the thing the kernel avoids)
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * hd ** -0.5
    q_idx = jnp.arange(S)[:, None]
    k_idx = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= q_idx >= k_idx
    if window > 0:
        mask &= k_idx > q_idx - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_attention_ref(q, k_pool, v_pool, block_tables, pos):
    """One-token decode attention through a block table, done the naive
    way: gather each row's blocks into a dense cache, repeat the KV heads,
    softmax over the keys ``<= pos``. q: [B,1,Hq,hd]; pools:
    [num_blocks, bs, Hkv, hd]; block_tables: [B, max_blocks]; pos: [B]."""
    B, _, Hq, hd = q.shape
    Hkv = k_pool.shape[2]
    k = k_pool[block_tables].reshape(B, -1, Hkv, hd)
    v = v_pool[block_tables].reshape(B, -1, Hkv, hd)
    k = jnp.repeat(k, Hq // Hkv, axis=2).astype(jnp.float32)
    v = jnp.repeat(v, Hq // Hkv, axis=2).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k) * hd ** -0.5
    valid = jnp.arange(k.shape[1])[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(q.dtype)


def grouped_matmul_ref(x, w, group_sizes):
    """x: [E,C,d]; w: [E,d,f]; rows >= group_sizes[e] are zeroed."""
    y = jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                   w.astype(jnp.float32))
    row = jnp.arange(x.shape[1])[None, :, None]
    return jnp.where(row < group_sizes[:, None, None], y, 0.0).astype(x.dtype)


def ssd_scan_ref(xh, dt, dA_log, Bh, Ch, h0):
    """Step-by-step SSD recurrence (no chunking):

        h_t = exp(dA_log_t) * h_{t-1} + dt_t * (x_t ⊗ B_t)
        y_t = C_t · h_t

    xh: [B,S,nh,hd]; dt, dA_log: [B,S,nh]; Bh, Ch: [B,S,nh,n];
    h0: [B,nh,hd,n] -> (y [B,S,nh,hd] fp32, hT fp32).
    """
    xh = xh.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    dA_log = dA_log.astype(jnp.float32)
    Bh = Bh.astype(jnp.float32)
    Ch = Ch.astype(jnp.float32)

    def step(h, inp):
        x_t, dt_t, da_t, B_t, C_t = inp  # [B,nh,...]
        h = (jnp.exp(da_t)[..., None, None] * h
             + jnp.einsum("bh,bhd,bhn->bhdn", dt_t, x_t, B_t))
        y_t = jnp.einsum("bhn,bhdn->bhd", C_t, h)
        return h, y_t

    xs = (xh.transpose(1, 0, 2, 3), dt.transpose(1, 0, 2),
          dA_log.transpose(1, 0, 2), Bh.transpose(1, 0, 2, 3),
          Ch.transpose(1, 0, 2, 3))
    hT, ys = jax.lax.scan(step, h0.astype(jnp.float32), xs)
    return ys.transpose(1, 0, 2, 3), hT
