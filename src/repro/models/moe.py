"""Mixture-of-Experts layer (paper §2.1.8).

Sort-based token dispatch with a static per-expert capacity (TPU-native: all
shapes static, no host-side ragged bookkeeping). The expert GEMM runs as a
single batched einsum over a [E, C, d] buffer — the XLA analogue of
``torch._grouped_mm`` — or through the Pallas ``grouped_matmul`` kernel on the
ragged sorted layout when ``use_pallas``.

FLOPs scale with *active* parameters (E·C ≈ tokens·top_k·capacity_factor),
matching the paper's efficiency premise; a naive dense-over-all-experts
formulation would inflate the roofline compute term by E/top_k.

Also computes the paper's MaxViolation load-balance diagnostic:
    MaxViolation = (max_i Load_i - mean Load) / mean Load.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import dense_init


def moe_init(key, cfg, dtype):
    m = cfg.moe
    d, f = cfg.d_model, m.expert_d_ff
    ks = jax.random.split(key, 6)
    def experts(k, a, b, scale):
        kk = jax.random.split(k, m.num_experts)
        return jnp.stack([dense_init(kk[i], a, b, dtype, scale) for i in range(m.num_experts)])
    p = {
        "router": dense_init(ks[0], d, m.num_experts, jnp.float32),
        "w_gate": experts(ks[1], d, f, d ** -0.5),
        "w_up": experts(ks[2], d, f, d ** -0.5),
        "w_down": experts(ks[3], f, d, f ** -0.5),
    }
    if m.num_shared_experts:
        sf = m.shared_d_ff or m.expert_d_ff * m.num_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(k1, d, sf, dtype),
            "w_up": dense_init(k2, d, sf, dtype),
            "w_down": dense_init(k3, sf, d, dtype, scale=sf ** -0.5),
        }
        p["shared_gate"] = dense_init(ks[5], d, 1, dtype)
    return p


def _route(params, xf, m):
    """Router in fp32. xf: [T, d] -> (weights [T,k], experts [T,k], probs [T,E])."""
    logits = xf.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return weights, experts, probs


def _dispatch_row(xr, weights, experts, E, K, cap):
    """Per-row sort-based dispatch. xr: [S,d]; weights/experts: [S,K].

    Returns (xe [E,cap,d], combine info) — all shapes static, all ops local to
    the row so GSPMD never sorts across the (sharded) batch axis.
    """
    S, d = xr.shape
    SK = S * K
    flat_e = experts.reshape(SK)
    flat_t = jnp.repeat(jnp.arange(S), K)
    flat_w = weights.reshape(SK)

    order = jnp.argsort(flat_e, stable=True)
    sort_e = flat_e[order]
    sort_t = flat_t[order]
    sort_w = flat_w[order]

    group_sizes = jnp.bincount(flat_e, length=E)
    group_start = jnp.cumsum(group_sizes) - group_sizes
    pos_in_group = jnp.arange(SK) - group_start[sort_e]

    keep = pos_in_group < cap
    dest = jnp.where(keep, sort_e * cap + pos_in_group, E * cap)  # drop slot

    buf = jnp.zeros((E * cap + 1, d), xr.dtype)
    buf = buf.at[dest].set(jnp.where(keep[:, None], xr[sort_t], 0.0))
    xe = buf[: E * cap].reshape(E, cap, d)
    return xe, (sort_t, sort_w, keep, dest, group_sizes)


def _combine_row(ye, info, S, dtype):
    sort_t, sort_w, keep, dest, _ = info
    E_cap, d = ye.shape[0] * ye.shape[1], ye.shape[2]
    y_rows = jnp.concatenate([ye.reshape(E_cap, d),
                              jnp.zeros((1, d), ye.dtype)])[dest]
    y = jnp.zeros((S, d), jnp.float32)
    y = y.at[sort_t].add(y_rows.astype(jnp.float32) * sort_w[:, None])
    return y.astype(dtype)


def moe_apply(params, x, cfg, *, use_pallas=False, capacity_factor=1.25,
              expert_parallel=False):
    """x: [B, S, d] -> (y [B, S, d], aux dict).

    Dispatch is vmapped over the batch row so the argsort/scatter stay local
    to each (data-sharded) row; only the expert GEMM touches the (FSDP-
    sharded) expert weights.

    ``expert_parallel``: constrain the dispatch buffer's expert dim to the
    "model" mesh axis — tokens move to their (sharded) experts via
    GSPMD-inserted all-to-alls instead of the experts being gathered
    (§2.1.8 EP; requires a mesh context with a "model" axis).
    """
    m = cfg.moe
    B, S, d = x.shape
    E, K = m.num_experts, m.top_k
    xf = x.reshape(B * S, d)
    weights, experts, probs = _route(params, xf, m)
    weights = weights.reshape(B, S, K)
    experts = experts.reshape(B, S, K)

    if expert_parallel:
        from repro.sharding.context import current_mesh
        mesh = current_mesh()
        if mesh is not None:
            # serving meshes carry a dedicated "expert" axis; training
            # meshes reuse "model". Experts must divide the axis or the
            # a2a dispatch degenerates — fall through to the dense path.
            axis = next((a for a in ("expert", "model")
                         if mesh.shape.get(a, 0) > 1
                         and E % mesh.shape[a] == 0), None)
            if axis is not None:
                return _moe_apply_ep(params, x, weights, experts, probs,
                                     cfg, mesh, axis=axis)

    cap = int(S * K / E * capacity_factor) + 8
    cap = -(-cap // 8) * 8

    if not use_pallas:
        from repro.sharding.context import current_serve_mesh
        serve_mesh = current_serve_mesh()
        if serve_mesh is not None:
            return _moe_serve_apply(params, x, cfg, cap, serve_mesh)

    xe, info = jax.vmap(lambda xr, w, e: _dispatch_row(xr, w, e, E, K, cap))(
        x, weights, experts)
    # xe: [B, E, cap, d]
    if use_pallas:
        from repro.kernels import ops as kops
        ye = kops.grouped_mlp_batched(xe, params["w_gate"], params["w_up"],
                                      params["w_down"])
    else:
        gate = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, params["w_gate"]))
        up = jnp.einsum("becd,edf->becf", xe, params["w_up"])
        ye = jnp.einsum("becf,efd->becd", gate * up, params["w_down"])

    y = jax.vmap(lambda yr, i: _combine_row(yr, i, S, x.dtype))(ye, info)

    if m.num_shared_experts:
        sp = params["shared"]
        g = jax.nn.silu(xf @ sp["w_gate"]) * (xf @ sp["w_up"])
        shared_out = (g @ sp["w_down"]).reshape(B, S, d)
        sgate = jax.nn.sigmoid(xf @ params["shared_gate"]).reshape(B, S, 1)
        y = y + sgate * shared_out

    # aux: switch-style load-balance loss + the paper's MaxViolation metric
    group_sizes = info[4].sum(axis=0).astype(jnp.float32)   # [E] global
    TK = B * S * K
    load = group_sizes / TK                                 # fraction per expert
    importance = probs.mean(axis=0)                         # mean router prob
    aux_loss = E * jnp.sum(load * importance) * m.router_aux_loss_coef
    mean_load = jnp.mean(group_sizes)
    max_violation = (jnp.max(group_sizes) - mean_load) / jnp.maximum(mean_load, 1.0)
    dropped = jnp.sum(~info[2]) / TK

    aux = {"moe_aux_loss": aux_loss, "max_violation": max_violation,
           "dropped_frac": dropped}
    return y, aux


def _serve_expert_axis(mesh, E):
    """The serving layout's expert-dim mesh axis (serve_param_specs rule):
    "expert" when the mesh has one, else "model", and only when the expert
    count divides it — otherwise None (replicated)."""
    axis = "expert" if "expert" in mesh.shape else \
        ("model" if "model" in mesh.shape else None)
    if axis is not None and E % mesh.shape[axis] != 0:
        return None
    return axis


def _moe_serve_apply(params, x, cfg, cap, mesh):
    """Prefill/extend MoE under a serving mesh, byte-identical to the
    unsharded ``moe_apply`` body below it.

    Same contract as ``_moe_decode_serve``: token-side ops (routing,
    vmapped dispatch, scatter-add combine, shared experts, aux metrics)
    run inside fully-replicated ``shard_map`` blocks — every device
    executes the single-device program (routing must be inside too: a
    re-blocked router matmul can drift a top-k near-tie onto a different
    expert) — while the expert GEMM runs E-sharded (a batch dim:
    per-element contractions untouched, parameter bytes stay
    distributed). Without this, GSPMD re-blocks the dispatch/combine over
    whatever axes it likes and prefill logits drift ~1e-6 — enough to flip
    sampled tokens and break the engine's parity gate.
    """
    from jax.sharding import PartitionSpec
    m = cfg.moe
    B, S, d = x.shape
    E, K = m.num_experts, m.top_k
    rep = PartitionSpec()

    def dispatch(x, router):
        xf = x.reshape(B * S, d)
        weights, experts, probs = _route({"router": router}, xf, m)
        weights = weights.reshape(B, S, K)
        experts = experts.reshape(B, S, K)
        xe, info = jax.vmap(
            lambda xr, wr, er: _dispatch_row(xr, wr, er, E, K, cap))(
            x, weights, experts)
        return xe, info, probs

    xe, info, probs = jax.shard_map(
        dispatch, mesh=mesh, in_specs=(rep, rep),
        out_specs=(rep, (rep,) * 5, rep), check_vma=False)(
        x, params["router"])

    e_axis = _serve_expert_axis(mesh, E)
    xspec = PartitionSpec(None, e_axis, None, None)
    wspec = PartitionSpec(e_axis, None, None)

    def expert_mlp(xe, wg, wu, wd):
        gate = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, wg))
        up = jnp.einsum("becd,edf->becf", xe, wu)
        return jnp.einsum("becf,efd->becd", gate * up, wd)

    ye = jax.shard_map(expert_mlp, mesh=mesh,
                       in_specs=(xspec, wspec, wspec, wspec), out_specs=xspec,
                       check_vma=False)(
        xe, params["w_gate"], params["w_up"], params["w_down"])

    shared = m.num_shared_experts

    def combine(ye, info, x, probs, *sh):
        y = jax.vmap(lambda yr, i: _combine_row(yr, i, S, x.dtype))(ye, info)
        xf = x.reshape(B * S, d)
        if shared:
            wg, wu, wd, sg = sh
            g = jax.nn.silu(xf @ wg) * (xf @ wu)
            shared_out = (g @ wd).reshape(B, S, d)
            sgate = jax.nn.sigmoid(xf @ sg).reshape(B, S, 1)
            y = y + sgate * shared_out
        # aux metrics: identical formulas to the unsharded path
        group_sizes = info[4].sum(axis=0).astype(jnp.float32)
        TK = B * S * K
        load = group_sizes / TK
        importance = probs.mean(axis=0)
        aux_loss = E * jnp.sum(load * importance) * m.router_aux_loss_coef
        mean_load = jnp.mean(group_sizes)
        max_violation = (jnp.max(group_sizes) - mean_load) \
            / jnp.maximum(mean_load, 1.0)
        dropped = jnp.sum(~info[2]) / TK
        return y, aux_loss, max_violation, dropped

    sh_args = () if not shared else (
        params["shared"]["w_gate"], params["shared"]["w_up"],
        params["shared"]["w_down"], params["shared_gate"])
    n_in = 4 + len(sh_args)
    y, aux_loss, max_violation, dropped = jax.shard_map(
        combine, mesh=mesh,
        in_specs=(rep, (rep,) * 5) + (rep,) * (n_in - 2),
        out_specs=(rep, rep, rep, rep), check_vma=False)(
        ye, info, x, probs, *sh_args)
    aux = {"moe_aux_loss": aux_loss, "max_violation": max_violation,
           "dropped_frac": dropped}
    return y, aux


def _moe_apply_ep(params, x, weights, experts, probs, cfg, mesh,
                  axis="model"):
    """Expert-parallel branch: shard_map a2a dispatch (see ep_moe.py)."""
    from .ep_moe import ep_moe_dispatch
    m = cfg.moe
    B, S, d = x.shape
    y, dropped = ep_moe_dispatch(params, x, weights, experts, cfg, mesh,
                                 model_axis=axis)

    if m.num_shared_experts:
        xf = x.reshape(B * S, d)
        sp = params["shared"]
        g = jax.nn.silu(xf @ sp["w_gate"]) * (xf @ sp["w_up"])
        shared_out = (g @ sp["w_down"]).reshape(B, S, d)
        sgate = jax.nn.sigmoid(xf @ params["shared_gate"]).reshape(B, S, 1)
        y = y + sgate * shared_out

    # load-balance metrics from router probabilities (bincount of top-k
    # choices is a local argmax statistic; keep it cheap and global)
    TK = B * S * m.top_k
    counts = jnp.bincount(experts.reshape(-1), length=m.num_experts
                          ).astype(jnp.float32)
    importance = probs.mean(axis=0)
    aux_loss = m.num_experts * jnp.sum((counts / TK) * importance) \
        * m.router_aux_loss_coef
    mean_load = jnp.mean(counts)
    max_violation = (jnp.max(counts) - mean_load) / jnp.maximum(mean_load, 1.0)
    aux = {"moe_aux_loss": aux_loss, "max_violation": max_violation,
           "dropped_frac": dropped}
    return y, aux


def moe_decode_apply(params, x, cfg, *, capacity_factor=2.0):
    """Decode-path MoE: tokens are few (one per sequence), so dispatch is a
    single *global* sorted scatter across the whole batch (T·K elements —
    tiny), with a generous capacity so drops are ~impossible. Weight reads,
    not FLOPs, dominate here; the roofline memory term sees every expert's
    weights touched once, as on real hardware. x: [B, 1, d]."""
    m = cfg.moe
    B, S, d = x.shape
    E, K = m.num_experts, m.top_k
    T = B * S
    cap = max(8, int(T * K / E * capacity_factor) + 8)
    cap = -(-cap // 8) * 8
    from repro.sharding.context import current_serve_mesh
    mesh = current_serve_mesh()
    if mesh is not None:
        return _moe_decode_serve(params, x, cfg, cap, mesh)
    xf = x.reshape(T, d)
    weights, experts, _ = _route(params, xf, m)          # [T,K]
    xe, info = _dispatch_row(xf, weights, experts, E, K, cap)
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, params["w_gate"]))
    up = jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", gate * up, params["w_down"])
    y = _combine_row(ye, info, T, x.dtype)

    if m.num_shared_experts:
        sp = params["shared"]
        g = jax.nn.silu(xf @ sp["w_gate"]) * (xf @ sp["w_up"])
        y = y + jax.nn.sigmoid(xf @ params["shared_gate"]) * (g @ sp["w_down"])
    return y.reshape(B, S, d).astype(x.dtype)


def _moe_decode_serve(params, x, cfg, cap, mesh):
    """Decode MoE under a serving mesh, byte-identical to the unsharded
    path above.

    The token-side ops (router, sorted dispatch, scatter-add combine,
    shared experts) are NOT partition-invariant — GSPMD re-blocks the
    global argsort/scatter when the token dim is sharded over "data", and
    a replication *constraint* is not enough on multi-axis meshes because
    the partitioner may still re-block interior ops. They therefore run
    inside fully-replicated ``shard_map`` blocks: every device executes
    the exact single-device program on a full copy of the (tiny, one
    token per slot) arrays. Only the expert GEMM runs outside, where the
    expert dim — a batch dim of the einsum, never a contraction — carries
    the serving layout's "expert"/"model" sharding, so the parameter
    bytes stay distributed and each element's contraction is untouched.
    """
    from jax.sharding import PartitionSpec
    m = cfg.moe
    B, S, d = x.shape
    E, K = m.num_experts, m.top_k
    T = B * S
    rep = PartitionSpec()
    xf = x.reshape(T, d)

    def dispatch(xf, router):
        weights, experts, _ = _route({"router": router}, xf, m)
        return _dispatch_row(xf, weights, experts, E, K, cap)

    xe, info = jax.shard_map(dispatch, mesh=mesh, in_specs=(rep, rep),
                             out_specs=(rep, (rep,) * 5), check_vma=False)(
        xf, params["router"])

    # expert GEMM: explicitly pinned to the serving layout's expert-dim
    # sharding (the same rule as serve_param_specs) so the partitioner
    # cannot re-block it over the idle data axis — the expert dim is a
    # batch dim, so per-shard compute is per-element exact.
    espec = PartitionSpec(_serve_expert_axis(mesh, E))

    def expert_mlp(xe, wg, wu, wd):
        gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg))
        up = jnp.einsum("ecd,edf->ecf", xe, wu)
        return jnp.einsum("ecf,efd->ecd", gate * up, wd)

    ye = jax.shard_map(expert_mlp, mesh=mesh,
                       in_specs=(espec, espec, espec, espec), out_specs=espec,
                       check_vma=False)(
        xe, params["w_gate"], params["w_up"], params["w_down"])

    shared = m.num_shared_experts

    def combine(ye, sort_t, sort_w, keep, dest, gsz, xf, *sh):
        y = _combine_row(ye, (sort_t, sort_w, keep, dest, gsz), T, x.dtype)
        if shared:
            wg, wu, wd, sg = sh
            g = jax.nn.silu(xf @ wg) * (xf @ wu)
            y = y + jax.nn.sigmoid(xf @ sg) * (g @ wd)
        return y

    sh_args = () if not shared else (
        params["shared"]["w_gate"], params["shared"]["w_up"],
        params["shared"]["w_down"], params["shared_gate"])
    y = jax.shard_map(combine, mesh=mesh,
                      in_specs=(rep,) * (7 + len(sh_args)), out_specs=rep,
                      check_vma=False)(ye, *info, xf, *sh_args)
    return y.reshape(B, S, d).astype(x.dtype)
