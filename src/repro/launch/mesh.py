"""Production meshes (assignment): single-pod 16x16, multi-pod 2x16x16.

A function, not a module constant, so importing never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _axis_kwargs(n: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kwargs(len(axes)))


def make_mesh(shape, axes) -> Mesh:
    """Arbitrary dev/test mesh (e.g. (8,) over 8 virtual CPU devices)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         **_axis_kwargs(len(axes)))


def make_engine_meshes(dp: int, tp: int, ep: int = 1, *,
                       devices=None) -> list:
    """Partition ``devices`` into ``dp`` disjoint engine shards, each a
    serving mesh for one ``InferenceEngine``.

    This is the sharded-serving topology: the paper's multi-client pool
    stays a set of *independent* engines (dp-way, no inter-engine
    collectives), but each engine now spans ``tp * ep`` devices as ONE
    mesh — axes ("data", "model") or ("data", "model", "expert") with the
    data axis always 1 per engine (cross-request parallelism comes from
    the pool's dp replicas; intra-engine slots stay whole so streams are
    byte-stable as slots fill). KV heads shard over "model", MoE expert
    stacks over "expert" (``serve_param_specs`` /
    ``decode_state_specs``).

    Raises ValueError when dp*tp*ep exceeds the device count. Extra
    devices are left idle (a deliberate remainder, e.g. 8 devices at
    dp=2, tp=2 leaves 4 idle).
    """
    if devices is None:
        devices = jax.devices()
    need = dp * tp * ep
    if dp < 1 or tp < 1 or ep < 1:
        raise ValueError(f"mesh factors must be >= 1, got {dp},{tp},{ep}")
    if need > len(devices):
        raise ValueError(
            f"--mesh {dp},{tp},{ep} needs {need} devices, "
            f"have {len(devices)}")
    per = tp * ep
    axes = ("data", "model") if ep == 1 else ("data", "model", "expert")
    shape = (1, tp) if ep == 1 else (1, tp, ep)
    meshes = []
    for i in range(dp):
        devs = list(devices[i * per:(i + 1) * per])
        meshes.append(jax.make_mesh(shape, axes, devices=devs,
                                    **_axis_kwargs(len(axes))))
    return meshes


# TPU v5e roofline constants (assignment)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
