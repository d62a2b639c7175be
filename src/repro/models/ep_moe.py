"""Expert-parallel MoE via shard_map (paper §2.1.8, the EP branch).

The GSPMD capacity-buffer formulation cannot shard the sort-based dispatch
scatter (it replicates the [B, E, cap, d] buffer — measured ~60 GB/layer of
involuntary traffic at qwen3-moe scale). This module implements true
DeepSpeed-style expert parallelism as an explicit shard_map program:

  layout   tokens sharded over (batch x sequence): batch over ("pod","data"),
           sequence over "model"; experts sharded over "model" on the expert
           dim (each model-rank owns E/N experts, replicated across data).
  dispatch per device: route locally, sort (token,k) pairs by OWNER RANK,
           pack a static [n_ranks, cap_send] buffer, one all_to_all.
  compute  per device: sort received tokens by LOCAL expert, pack a static
           [E_local, cap_exp] buffer, SwiGLU expert GEMMs.
  combine  reverse all_to_all (the tiled a2a is an involution, so rows come
           back in send-slot order), weighted scatter-add into the output.

Wire cost per device per layer: 2 x T_local * top_k * d * bf16 — tokens
move, not experts. Capacity overflow drops tokens (mirrors the reference
path's capacity semantics); dropped fraction is returned for monitoring.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _pack_by_key(keys, values_list, num_buckets: int, cap: int, fill=0.0):
    """Sort-based static packing: rows with key k land in bucket k at the
    next free slot < cap (overflow dropped). keys: [N] int32 in [0, B) or -1.

    Returns (packed values [num_buckets*cap, ...] per input, keep [N],
    dest [N] (=num_buckets*cap for dropped), order)."""
    N = keys.shape[0]
    # invalid (-1) keys must sort LAST or they shift every bucket's offsets
    keys2 = jnp.where(keys < 0, num_buckets, keys)
    order = jnp.argsort(keys2, stable=True)
    sk = keys2[order]
    sizes = jnp.bincount(keys2, length=num_buckets + 1)[:num_buckets]
    starts = jnp.cumsum(sizes) - sizes
    pos = jnp.arange(N) - starts[jnp.clip(sk, 0, num_buckets - 1)]
    keep = (sk < num_buckets) & (pos < cap)
    dest = jnp.where(keep, jnp.clip(sk, 0, num_buckets - 1) * cap + pos,
                     num_buckets * cap)
    packed = []
    for v, f in values_list:
        sv = v[order]
        buf_shape = (num_buckets * cap + 1,) + sv.shape[1:]
        buf = jnp.full(buf_shape, f, sv.dtype)
        buf = buf.at[dest].set(jnp.where(
            keep.reshape((-1,) + (1,) * (sv.ndim - 1)), sv, f))
        packed.append(buf[:-1])
    return packed, keep, dest, order


def _ep_body(x, weights, experts, router_unused, wg, wu, wd, *,
             axis: str, E: int, cap_send: int, cap_exp: int):
    """Per-device shard_map body.

    x: [T_loc, d]; weights/experts: [T_loc, K]; wg/wu/wd: [E_loc, d, f]...
    Returns (y [T_loc, d], dropped_frac scalar).
    """
    T, d = x.shape
    K = experts.shape[1]
    n_ranks = jax.lax.axis_size(axis)
    rank = jax.lax.axis_index(axis)
    E_loc = E // n_ranks

    flat_e = experts.reshape(T * K)
    flat_w = weights.reshape(T * K)
    flat_slot = jnp.repeat(jnp.arange(T), K)
    owner = flat_e // E_loc

    (sx, se), keep_s, dest_s, order_s = _pack_by_key(
        owner, [(x[flat_slot], 0.0), (flat_e, -1)], n_ranks, cap_send)
    # combine-side views in SORTED order (aligned with keep_s/dest_s)
    sorted_slot = flat_slot[order_s]
    sorted_w = flat_w[order_s]
    # -> [n_ranks*cap_send, ...]; exchange chunks with every rank
    rx = jax.lax.all_to_all(sx, axis, split_axis=0, concat_axis=0, tiled=True)
    re = jax.lax.all_to_all(se, axis, split_axis=0, concat_axis=0, tiled=True)

    # received tokens -> local expert buckets
    le = jnp.where(re >= 0, re - rank * E_loc, -1)
    (ex,), keep_r, dest_r, order_r = _pack_by_key(
        le, [(rx, 0.0)], E_loc, cap_exp)
    ex = ex.reshape(E_loc, cap_exp, d)

    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ex, wg))
    up = jnp.einsum("ecd,edf->ecf", ex, wu)
    ey = jnp.einsum("ecf,efd->ecd", gate * up, wd)   # [E_loc, cap_exp, d]

    # un-pack back to recv-slot order (inverse of the pack permutation)
    ey_rows = jnp.concatenate(
        [ey.reshape(E_loc * cap_exp, d), jnp.zeros((1, d), ey.dtype)])[dest_r]
    recv_y = jnp.zeros((n_ranks * cap_send, d), x.dtype)
    recv_y = recv_y.at[order_r].set(ey_rows.astype(x.dtype))

    # reverse exchange: rows return to their senders in send-slot order
    back = jax.lax.all_to_all(recv_y, axis, split_axis=0, concat_axis=0,
                              tiled=True)

    # weighted combine at the source (sorted-order views)
    contrib = jnp.concatenate([back, jnp.zeros((1, d), back.dtype)])[dest_s]
    y = jnp.zeros((T, d), jnp.float32)
    y = y.at[sorted_slot].add(contrib.astype(jnp.float32)
                              * (sorted_w * keep_s)[:, None])
    dropped = 1.0 - keep_s.sum() / (T * K)
    return y.astype(x.dtype), jnp.float32(dropped)


def ep_moe_dispatch(params, x, weights, experts, cfg, mesh: Mesh, *,
                    model_axis: str = "model", capacity_factor: float = 1.5):
    """x: [B, S, d] (batch over data axes, seq over model axis);
    weights/experts: [B, S, K]. Returns (y [B, S, d], dropped_frac)."""
    m = cfg.moe
    B, S, d = x.shape
    K = m.top_k
    n_ranks = mesh.shape[model_axis]
    da = tuple(a for a in ("pod", "data") if a in mesh.shape)
    b_axes = da if len(da) != 1 else da[0]
    n_batch = 1
    for a in (da or ()):
        n_batch *= mesh.shape[a]
    B_loc = B // n_batch if (n_batch and B % n_batch == 0) else B
    S_loc = S // n_ranks
    T_loc = B_loc * S_loc
    cap_send = -(-T_loc * K // n_ranks)
    cap_send = -(-int(cap_send * capacity_factor) // 8) * 8
    E_loc = m.num_experts // n_ranks
    cap_exp = -(-int(n_ranks * cap_send / max(E_loc, 1) * capacity_factor)
                // 8) * 8

    x_spec = P(b_axes if n_batch > 1 and B % n_batch == 0 else None,
               model_axis, None)
    k_spec = P(x_spec[0], model_axis, None)
    w_spec = P(model_axis, None, None)

    def body(x_l, wgt_l, exp_l, wg, wu, wd):
        Bl, Sl, dd = x_l.shape
        y, dropped = _ep_body(
            x_l.reshape(Bl * Sl, dd), wgt_l.reshape(Bl * Sl, K),
            exp_l.reshape(Bl * Sl, K), None, wg, wu, wd,
            axis=model_axis, E=m.num_experts, cap_send=cap_send,
            cap_exp=cap_exp)
        return y.reshape(Bl, Sl, dd), dropped

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, k_spec, k_spec, w_spec, w_spec, w_spec),
        out_specs=(x_spec, P()),
        check_vma=False)
    # storage may shard expert features over "data" (full ZeRO-3 for the
    # optimizer state); gather that axis at use so each model-rank holds its
    # whole local experts for the shard_map GEMMs.
    gather = lambda w: jax.lax.with_sharding_constraint(
        w, P(model_axis, None, None))
    return fn(x, weights, experts, gather(params["w_gate"]),
              gather(params["w_up"]), gather(params["w_down"]))
