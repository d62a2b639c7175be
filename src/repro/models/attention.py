"""Grouped-query attention with full / blockwise / banded(SWA) / decode paths.

All activations use the BSHD layout [batch, seq, heads, head_dim]. GQA never
materializes repeated KV heads: queries are reshaped to
[B, S, kv_heads, group, hd] and contracted against KV directly.

Path selection (XLA reference paths; the Pallas flash kernel replaces the
blockwise path on TPU — see repro.kernels):
  - direct     S small: materialize scores (used by smoke tests; oracle)
  - blockwise  online-softmax scan over KV blocks: O(S·block) memory
  - banded     sliding-window: per-Q-block KV band via dynamic_slice so HLO
               FLOPs scale with S·window, not S².
  - decode     one query token vs a [B, S_max, Hkv, hd] cache, optionally
               windowed via dynamic_slice (reads O(window) not O(S_max)).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import apply_rope, dense_init, rmsnorm, rmsnorm_init

NEG_INF = -1e30


def _serve_gather_heads(x):
    """Serving tensor-parallel contract (sharded InferenceEngine only).

    Under an engine mesh, q/k/v projections are column-parallel and the KV
    cache is head-sharded over "model", so the attention output arrives
    head-sharded. Its flattened q_dim is the CONTRACTION dim of the ``wo``
    matmul: left sharded, GSPMD would partial-sum shard-local matmuls with
    an all-reduce, reordering float additions and breaking the engine's
    byte-identity parity gate. Constraining to replicated first makes the
    resolution an all-gather (exact concatenation), keeping the contraction
    unsharded and the dot products bitwise equal to the unsharded oracle.

    No-op unless a serve mesh is active (training paths never see this).
    """
    from repro.sharding.context import current_serve_mesh
    mesh = current_serve_mesh()
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec()))


def attn_init(key, cfg, dtype):
    d, qd, kvd, hd = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, qd, dtype),
        "wk": dense_init(ks[1], d, kvd, dtype),
        "wv": dense_init(ks[2], d, kvd, dtype),
        "wo": dense_init(ks[3], qd, d, dtype, scale=qd ** -0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def _project_qkv(params, x, positions, cfg):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ params["wq"]).reshape(B, S, cfg.num_heads, hd)
    k = (x @ params["wk"]).reshape(B, S, cfg.num_kv_heads, hd)
    v = (x @ params["wv"]).reshape(B, S, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, params["q_norm"], cfg.rms_eps)
        k = rmsnorm(k, params["k_norm"], cfg.rms_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _group_q(q, num_kv_heads):
    B, S, Hq, hd = q.shape
    return q.reshape(B, S, num_kv_heads, Hq // num_kv_heads, hd)


# ---------------------------------------------------------------------------
# Core attention paths (q: [B,Sq,Hq,hd]; k,v: [B,Skv,Hkv,hd])
# ---------------------------------------------------------------------------


def attention_direct(q, k, v, *, causal=True, window=0, q_offset=0, scale=None):
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    scale = scale or hd ** -0.5
    qg = _group_q(q, Hkv)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    q_idx = jnp.arange(Sq) + q_offset
    k_idx = jnp.arange(k.shape[1])
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= q_idx[:, None] >= k_idx[None, :]
    if window > 0:
        mask &= k_idx[None, :] > q_idx[:, None] - window
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(B, Sq, Hq, hd)


def attention_blockwise(q, k, v, *, causal=True, window=0, q_offset=0,
                        block_kv=512, scale=None):
    """Online-softmax scan over KV blocks. Differentiable; O(S·block) memory."""
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    scale = scale or hd ** -0.5
    nb = -(-Skv // block_kv)
    pad = nb * block_kv - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nb, block_kv, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, block_kv, Hkv, hd).transpose(1, 0, 2, 3, 4)
    qg = _group_q(q, Hkv).astype(jnp.float32)
    q_idx = jnp.arange(Sq) + q_offset

    def step(carry, inp):
        m, l, acc = carry
        blk_i, kblk, vblk = inp
        k_idx = blk_i * block_kv + jnp.arange(block_kv)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kblk.astype(jnp.float32)) * scale
        mask = k_idx[None, :] < Skv
        if causal:
            mask &= q_idx[:, None] >= k_idx[None, :]
        if window > 0:
            mask &= k_idx[None, :] > q_idx[:, None] - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, vblk.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    G = Hq // Hkv
    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (jnp.arange(nb), kb, vb))
    out = acc / jnp.maximum(l[..., None], 1e-37)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, hd).astype(q.dtype)


def attention_banded(q, k, v, *, window, block_q=512, scale=None):
    """Sliding-window attention with FLOPs ∝ S·(window+block_q).

    Scans over query blocks; each block attends to a KV band fetched with a
    single dynamic_slice. Causal by construction.
    """
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    scale = scale or hd ** -0.5
    nb = -(-S // block_q)
    pad = nb * block_q - S
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    band = window + block_q
    # left-pad kv so every band slice is in range
    kp = jnp.pad(k, ((0, 0), (window, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, pad), (0, 0), (0, 0)))
    qb = q.reshape(B, nb, block_q, Hq, hd).transpose(1, 0, 2, 3, 4)

    def block(i, qblk):
        # kv band covers original positions [i*block_q - window, (i+1)*block_q)
        start = i * block_q  # in padded coords == i*block_q - window original
        kblk = jax.lax.dynamic_slice_in_dim(kp, start, band, axis=1)
        vblk = jax.lax.dynamic_slice_in_dim(vp, start, band, axis=1)
        qg = _group_q(qblk, Hkv).astype(jnp.float32)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kblk.astype(jnp.float32)) * scale
        q_idx = i * block_q + jnp.arange(block_q)          # original coords
        k_idx = start - window + jnp.arange(band)          # original coords
        mask = (q_idx[:, None] >= k_idx[None, :])
        mask &= (k_idx[None, :] > q_idx[:, None] - window)
        mask &= (k_idx[None, :] >= 0) & (k_idx[None, :] < S)
        mask &= (q_idx[:, None] < S)
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", p, vblk.astype(jnp.float32))
        return out.reshape(B, block_q, Hq, hd)

    outs = jax.lax.map(lambda args: block(*args), (jnp.arange(nb), qb))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nb * block_q, Hq, hd)
    return out[:, :S].astype(q.dtype)


def attention_decode_ring(q, k_cache, v_cache, pos, *, scale=None):
    """SWA decode against a *ring-buffer* cache of length W == window.

    Slot j holds absolute position p_j = pos − ((pos − j) mod W) (the latest
    position congruent to j); slots with p_j < 0 have never been written.
    All written slots lie inside the window by construction, so the only
    mask is p_j ≥ 0. This is the long_500k decode path: cache memory is
    O(window), independent of the 512k context.
    """
    B, _, Hq, hd = q.shape
    W, Hkv = k_cache.shape[1], k_cache.shape[2]
    scale = scale or hd ** -0.5
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (B,))
    qg = _group_q(q, Hkv).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                   k_cache.astype(jnp.float32)) * scale
    j = jnp.arange(W)[None, :]
    slot_pos = pos[:, None] - ((pos[:, None] - j) % W)
    valid = slot_pos >= 0
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, Hq, hd).astype(q.dtype)


def attention_extend(q, k_cache, v_cache, q_pos, *, window=0, scale=None):
    """Multi-token continuation against a *linear* cache (engine sessions).

    q: [B, Sq, Hq, hd] — a block of new tokens already written into the
    caches; caches: [B, S_max, Hkv, hd]; q_pos: [B, Sq] absolute positions.
    Each query attends to every cache slot at k_idx <= q_pos (optionally
    windowed), i.e. the whole conversation prefix plus the new block's own
    causal triangle. Unwritten/padded cache tail slots sit above every
    valid q_pos, so the mask excludes them; masked lanes contribute exact
    zeros to the softmax, matching the full-prefill computation.

    This is also the speculative-verification contract: a verify block of
    k drafted candidates runs through this path, each candidate attending
    only to the committed prefix plus earlier candidates (``k_idx <=
    q_pos``), so the per-position logits are identical to what k
    sequential decode ticks would compute (up to reduction-order float
    noise). A rejected tail's cache writes sit above the rolled-back
    ``pos`` and are never read before being overwritten (dense rows) or
    dropped with their block refs (paged rows).
    """
    B, Sq, Hq, hd = q.shape
    S_max, Hkv = k_cache.shape[1], k_cache.shape[2]
    scale = scale or hd ** -0.5
    qg = _group_q(q, Hkv).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                   k_cache.astype(jnp.float32)) * scale
    k_idx = jnp.arange(S_max)
    valid = k_idx[None, None, :] <= q_pos[:, :, None]       # [B, Sq, S_max]
    if window:
        valid &= k_idx[None, None, :] > q_pos[:, :, None] - window
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, Sq, Hq, hd).astype(q.dtype)


def attention_decode(q, k_cache, v_cache, pos, *, window=0, scale=None):
    """One-token decode. q: [B,1,Hq,hd]; caches: [B,S_max,Hkv,hd]; pos: [B] or scalar.

    With a window, reads only a [window]-sized dynamic slice of the cache.

    The validity mask ``k_idx <= pos`` is the load-bearing invariant for
    every cache-manipulation fast path in the engine: right-padded bucketed
    prefill, session extend, the group-shared-prefill cache fork, and
    speculative-decode rollback all leave garbage K/V *above* a row's
    logical position, and all are sound because this mask never lets a
    query read it — decode then overwrites the garbage in place before
    ``pos`` can reach it. Rolling back a rejected speculative tail on a
    dense row is therefore a pure ``pos`` rewind; no cache bytes need
    restoring.
    """
    B, _, Hq, hd = q.shape
    S_max, Hkv = k_cache.shape[1], k_cache.shape[2]
    scale = scale or hd ** -0.5
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (B,))
    qg = _group_q(q, Hkv).astype(jnp.float32)  # [B,1,Hkv,G,hd]

    if window and window < S_max:
        start = jnp.clip(pos - window + 1, 0, S_max - window)  # [B]
        def slice_b(c, s):
            return jax.lax.dynamic_slice_in_dim(c, s, window, axis=0)
        kw = jax.vmap(slice_b)(k_cache, start)
        vw = jax.vmap(slice_b)(v_cache, start)
        k_idx = start[:, None] + jnp.arange(window)[None, :]
    else:
        kw, vw = k_cache, v_cache
        k_idx = jnp.broadcast_to(jnp.arange(S_max)[None, :], (B, S_max))

    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kw.astype(jnp.float32)) * scale
    valid = k_idx <= pos[:, None]
    if window:
        valid &= k_idx > (pos[:, None] - window)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, vw.astype(jnp.float32))
    return out.reshape(B, 1, Hq, hd).astype(q.dtype)


def _linear_rows(blocks):
    """Gathered blocks ``[B, max_blocks, bs, Hkv, hd]`` as dense rows
    ``[B, max_blocks·bs, Hkv, hd]``."""
    B, mb, bs = blocks.shape[:3]
    return blocks.reshape(B, mb * bs, *blocks.shape[3:])


def attention_paged_decode(q, k_pool, v_pool, block_tables, pos, *,
                           window=0, scale=None):
    """One-token decode against a *paged* KV cache — XLA gather fallback.

    q: [B, 1, Hq, hd]; pools: [num_blocks, block_size, Hkv, hd];
    block_tables: [B, max_blocks] physical block ids per logical block
    (entries past a row's allocation may be any valid id — every position
    they cover is masked by ``k_idx <= pos``); pos: [B].

    Linearizes each row's blocks with one gather —
    ``pool[table] -> [B, max_blocks·bs, Hkv, hd]`` — and defers to the
    dense ``attention_decode``. When ``max_blocks·bs`` equals the dense
    engine's ``max_seq`` the result is *bitwise* identical to the dense
    path (same shapes, same values at unmasked positions, exact-zero
    contributions from masked garbage), which is what the paged engine's
    stream-parity contract rests on. The Pallas kernel
    (``repro.kernels.paged_attention``) computes the same thing without
    ever materializing the gathered temporary.
    """
    return attention_decode(q, _linear_rows(k_pool[block_tables]),
                            _linear_rows(v_pool[block_tables]), pos,
                            window=window, scale=scale)


def attn_paged_decode_apply(params, x, k_pool, v_pool, layer, block_tables,
                            pos, write_block, write_off, cfg, *,
                            use_pallas=False):
    """One-token decode attention over the shared block pool.

    The paged sibling of ``attn_decode_apply``. ``k_pool``/``v_pool`` are
    the stacked pools ``[L, num_blocks, block_size, Hkv, hd]`` that the
    decode layer scan carries; this layer reads and writes them at
    ``layer``. Inserts the new token's K/V at physical ``(layer,
    write_block[b], write_off[b])`` — the caller maps ``pos`` through the
    block table and masks inactive rows to an out-of-bounds block id, so
    their writes drop instead of corrupting blocks owned (or shared,
    post-fork) by other rows — then gathers this layer's blocks through
    the block table straight from the written pool. The scatter is in
    place, and no pool-sized slice is copied. Returns (out [B,1,d],
    k_pool, v_pool).

    Ring (window-sized) caches are excluded by the engine's paging gate:
    the linear block table is the only slot→position mapping here, and
    sliding windows are handled by masking, not wraparound.
    """
    B = x.shape[0]
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (B,))
    q, k, v = _project_qkv(params, x, pos[:, None], cfg)
    k_pool = k_pool.at[layer, write_block, write_off].set(
        k[:, 0].astype(k_pool.dtype), mode="drop")
    v_pool = v_pool.at[layer, write_block, write_off].set(
        v[:, 0].astype(v_pool.dtype), mode="drop")
    if use_pallas:
        from repro.kernels import ops as kops
        out = kops.paged_attention(q, k_pool[layer], v_pool[layer],
                                   block_tables, pos,
                                   window=cfg.sliding_window)
    else:
        out = attention_decode(
            q, _linear_rows(k_pool[layer, block_tables]),
            _linear_rows(v_pool[layer, block_tables]), pos,
            window=cfg.sliding_window)
    out = _serve_gather_heads(out.reshape(B, 1, cfg.q_dim)) @ params["wo"]
    return out, k_pool, v_pool


# ---------------------------------------------------------------------------
# Attention block (projections + path dispatch)
# ---------------------------------------------------------------------------


def attn_apply(params, x, positions, cfg, *, use_pallas=False, causal=True,
               direct_threshold=2048, context_parallel=False):
    """Training/prefill attention. Returns (out [B,S,d], (k, v)) for caching.

    ``context_parallel``: Ring Attention (§2.1.6) over the "model" mesh axis
    — sequence-sharded Q/K/V with lax.ppermute KV rotation (full-attention
    archs only; SWA archs are already sub-quadratic and keep the banded
    path)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, x, positions, cfg)
    window = cfg.sliding_window if causal else 0
    if context_parallel and not window:
        from repro.sharding.context import current_mesh
        mesh = current_mesh()
        if mesh is not None and "model" in mesh.shape \
                and S % mesh.shape["model"] == 0:
            from repro.sharding.context_parallel import ring_attention
            out = ring_attention(q, k, v, mesh, causal=causal)
            out = _serve_gather_heads(out.reshape(B, S, cfg.q_dim)) \
                @ params["wo"]
            return out, (k, v)
    if use_pallas:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=causal, window=window)
    elif window and S > window:
        out = attention_banded(q, k, v, window=window,
                               block_q=min(512, max(128, window // 4)))
    elif S <= direct_threshold:
        out = attention_direct(q, k, v, causal=causal, window=window)
    else:
        out = attention_blockwise(q, k, v, causal=causal, window=window)
    out = _serve_gather_heads(out.reshape(B, S, cfg.q_dim)) @ params["wo"]
    return out, (k, v)


def attn_decode_apply(params, x, k_cache, v_cache, pos, cfg):
    """One-token decode attention.

    x: [B,1,d]; caches [B,S_max,Hkv,hd] already containing this token's K/V?
    No — this fn inserts the new token's K/V at `pos` then attends.
    Returns (out [B,1,d], new_k_cache, new_v_cache).

    A cache allocated with length == cfg.sliding_window is treated as a
    *ring buffer* (long_500k: O(window) memory): writes land at pos % W and
    the ring decode path handles slot->position mapping.
    """
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (B,))
    q, k, v = _project_qkv(params, x, pos[:, None], cfg)

    ring = bool(cfg.sliding_window) and k_cache.shape[1] == cfg.sliding_window
    write_pos = pos % k_cache.shape[1] if ring else pos

    def upd(cache, new):
        def one(c, n, p):
            return jax.lax.dynamic_update_slice_in_dim(c, n, p, axis=0)
        return jax.vmap(one)(cache, new, write_pos)

    k_cache = upd(k_cache, k.astype(k_cache.dtype))
    v_cache = upd(v_cache, v.astype(v_cache.dtype))
    if ring:
        out = attention_decode_ring(q, k_cache, v_cache, pos)
    else:
        out = attention_decode(q, k_cache, v_cache, pos,
                               window=cfg.sliding_window)
    out = _serve_gather_heads(out.reshape(B, 1, cfg.q_dim)) @ params["wo"]
    return out, k_cache, v_cache


def attn_extend_apply(params, x, k_cache, v_cache, positions, cfg):
    """Session-extend attention: insert a contiguous block of new tokens'
    K/V at ``positions`` (block start = positions[:, 0]) and attend each
    new token over the full cache prefix.

    x: [B, S_new, d]; caches: [B, S_max, Hkv, hd]; positions: [B, S_new].
    Returns (out [B, S_new, d], new_k_cache, new_v_cache).

    Linear caches only — a ring (sliding-window-sized) cache has a
    slot->position mapping this write does not respect; callers gate
    sessions off for ring/SSM families. The caller must guarantee
    ``positions[:, 0] + S_new <= S_max`` so the block write is not clamped
    into the live prefix.
    """
    B, S_new, _ = x.shape
    q, k, v = _project_qkv(params, x, positions, cfg)
    start = positions[:, 0]

    def upd(cache, new):
        def one(c, n, p):
            return jax.lax.dynamic_update_slice_in_dim(c, n, p, axis=0)
        return jax.vmap(one)(cache, new, start)

    k_cache = upd(k_cache, k.astype(k_cache.dtype))
    v_cache = upd(v_cache, v.astype(v_cache.dtype))
    out = attention_extend(q, k_cache, v_cache, positions,
                           window=cfg.sliding_window)
    out = _serve_gather_heads(out.reshape(B, S_new, cfg.q_dim)) \
        @ params["wo"]
    return out, k_cache, v_cache


def cross_attn_apply(params, x, k_cache, v_cache, cfg):
    """Encoder-decoder cross attention (whisper): precomputed K/V, no mask."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ params["wq"]).reshape(B, S, cfg.num_heads, hd)
    out = attention_direct(q, k_cache, v_cache, causal=False)
    return _serve_gather_heads(out.reshape(B, S, cfg.q_dim)) @ params["wo"]


def cross_attn_kv(params, enc_out, cfg):
    B, T, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    k = (enc_out @ params["wk"]).reshape(B, T, cfg.num_kv_heads, hd)
    v = (enc_out @ params["wv"]).reshape(B, T, cfg.num_kv_heads, hd)
    return k, v
