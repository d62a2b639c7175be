"""Distributed Muon (paper §2.1.7) — Newton–Schulz over FSDP-sharded grads.

Muon needs the *full* gradient matrix; FSDP shards rows. The paper explored
two schemes, both implemented here as ``shard_map`` programs over a
row-sharded, layer-stacked gradient ``[L, m, n]``:

  * ``round_robin`` — their first approach: one gather per matrix ("issuing
    many overlapping gathers"), NS computed at the gathered site, results
    redistributed. In SPMD we express this as L per-layer ``all_gather`` ops
    (one collective per matrix — the message-count pattern that congested
    InfiniBand at scale) with redundant NS compute, which is the only
    rooted-gather analogue XLA can express. Collective bytes/rank:
    L·m·n·(N−1)/N received.

  * ``all_to_all`` — the adopted (Dion [2]) scheme: a single all-to-all
    reshuffles from row-sharded ``[L, m/N, n]`` to layer-sharded
    ``[L/N, m, n]``, NS runs locally on whole matrices, and a reverse
    all-to-all restores FSDP layout. Two collectives total, bytes/rank
    2·L·m·n/N — fewer messages AND less data, reproducing the paper's
    "significantly improves performance and avoids congestion" result.
    As the paper notes, L must be padded to a multiple of N ("may require
    padding tensors before communication").

The §Perf benchmark lowers both and compares collective op counts and bytes
from the HLO — the TPU/ICI restatement of the InfiniBand argument.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .muon import newton_schulz


# --------------------------------------------------------------------------
# shard_map bodies (run per-device; `g` is the local row shard [L, m/N, n])
# --------------------------------------------------------------------------


def _rr_body(g, *, axis: str, ns_steps: int):
    """Round-robin-as-SPMD: per-layer all_gather (L collectives), redundant
    NS, keep own row shard."""
    L = g.shape[0]
    idx = jax.lax.axis_index(axis)
    n_dev = jax.lax.axis_size(axis)
    rows = g.shape[1]
    outs = []
    for i in range(L):  # one collective per matrix — the congestion pattern
        full = jax.lax.all_gather(g[i], axis, tiled=True)     # [m, n]
        o = newton_schulz(full, ns_steps)
        outs.append(jax.lax.dynamic_slice_in_dim(o, idx * rows, rows, axis=0))
    return jnp.stack(outs)


def _a2a_body(g, *, axis: str, ns_steps: int):
    """Dion-style: all_to_all L→L/N & rows→m, local NS, reverse."""
    n_dev = jax.lax.axis_size(axis)
    L, rows, n = g.shape
    pad = (-L) % n_dev
    if pad:  # paper: "may require padding tensors before communication"
        g = jnp.concatenate([g, jnp.zeros((pad, rows, n), g.dtype)])
    # [L', rows, n] -> [L'/N, N*rows = m, n]
    shuffled = jax.lax.all_to_all(g, axis, split_axis=0, concat_axis=1,
                                  tiled=True)
    o = jax.vmap(lambda m: newton_schulz(m, ns_steps))(shuffled)
    out = jax.lax.all_to_all(o, axis, split_axis=1, concat_axis=0, tiled=True)
    return out[:L] if pad else out


_BODIES = {"round_robin": _rr_body, "all_to_all": _a2a_body}


def distributed_orthogonalize(g_stacked, mesh: Mesh, *, axis: str = "model",
                              scheme: str = "all_to_all", ns_steps: int = 5):
    """Orthogonalize a layer-stacked gradient [L, m, n] whose rows (m) are
    FSDP-sharded over ``mesh[axis]``. Returns the same sharding."""
    body = functools.partial(_BODIES[scheme], axis=axis, ns_steps=ns_steps)
    spec = P(None, axis, None)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec,
                       check_vma=False)
    return fn(g_stacked)


def lower_scheme(mesh: Mesh, shape, *, axis: str = "model",
                 scheme: str = "all_to_all", ns_steps: int = 5):
    """Lower (no execute) one scheme for collective analysis. shape=[L,m,n]."""
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    spec = NamedSharding(mesh, P(None, axis, None))
    f = jax.jit(functools.partial(distributed_orthogonalize, mesh=mesh,
                                  axis=axis, scheme=scheme, ns_steps=ns_steps),
                in_shardings=(spec,), out_shardings=spec)
    return f.lower(x)
