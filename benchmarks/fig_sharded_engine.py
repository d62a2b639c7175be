"""Sharded inference engine: mesh-parallel paged decode on the real stack.

The sharded-serving claim, on the real engine: an ``InferenceEngine``
given a mesh lays its paged K/V pool out head-sharded over "model" (and
MoE expert stacks over "expert"), runs every dispatch path as a sharded
jitted computation, and still emits token / logprob / version streams
**byte-identical** to a mesh(1,1) engine — across prefill, decode, a
GRPO group fork and an in-flight weight relay. The payoff reported is
the memory shape: per-device KV bytes shrink by the model-axis size
while the streams don't move.

The measurement needs 8 devices, so it is a CPU simulation: a subprocess
with ``JAX_PLATFORMS=cpu`` and
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (same pattern as
tests/test_sharded_engine.py) — the parent benchmark process keeps
whatever device topology it started with, and its rows say
``platform=cpu``.
"""
from __future__ import annotations

import os
import subprocess
import sys

_WORKER = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.inference import InferenceEngine, InferencePool
from repro.launch.mesh import make_mesh
from repro.models import init_params

cfg = dataclasses.replace(get_config("qwen2-moe-a2.7b:reduced"),
                          vocab_size=512, num_layers=2)
params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)


def run(mesh):
    eng = InferenceEngine(params, cfg, num_slots=4, max_seq=64, seed=11,
                          mesh=mesh)
    pool = InferencePool([eng])
    rng = np.random.default_rng(5)
    reqs = [pool.submit_request(rng.integers(5, 500, int(rng.integers(
                2, 12))).astype(np.int32),
            max_new_tokens=int(rng.integers(3, 8)),
            temperature=0.8 + 0.1 * (i % 3)) for i in range(6)]
    reqs += pool.submit_group_request(
        rng.integers(5, 500, 10).astype(np.int32), 4,
        max_new_tokens=5, temperature=0.9)
    pushed = False
    for _ in range(300):
        pool.step()
        pool.drain_requests()
        if not pushed and eng.stats.decode_steps >= 3:
            pool.update_weights(jax.tree_util.tree_map(
                lambda x: x * 1.01, params), version=1)
            pushed = True
        if pushed and all(r.finished for r in reqs):
            break
    assert all(r.finished for r in reqs) and pool.policy_version == 1
    streams = sorted((r.request_id, tuple(r.completion),
                      np.asarray(r.logprobs, np.float32).tobytes(),
                      tuple(r.versions), r.finish_reason) for r in reqs)
    s = pool.stats()
    return streams, s["mesh_shapes"][0], s["kv_bytes_per_shard"][0], \\
        s["kv_bytes"], sum(len(r.completion) for r in reqs)

base, shape1, shard1, pool1, toks = run(make_mesh((1, 1), ("data", "model")))
wide, shape8, shard8, pool8, _ = run(make_mesh((2, 2, 2),
                                               ("data", "model", "expert")))
assert base == wide, "sharded streams diverged from mesh(1,1)"
assert shard1 == pool1, "mesh(1,1) shard must hold the full pool"
n_model = 2  # kv_heads=4 shards over model=2; expert axis carries the MoE
assert shard8 * n_model == pool8, (shard8, pool8)
print(f"RESULT|{shape1}|{shape8}|{pool8}|{shard8}|{toks}")
"""


def main():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("PYTHONPATH", "src")
    res = subprocess.run([sys.executable, "-c", _WORKER], env=env,
                         capture_output=True, text=True, timeout=600)
    if res.returncode != 0:
        raise RuntimeError(f"sharded-engine worker failed:\n{res.stderr}")
    line = [l for l in res.stdout.splitlines()
            if l.startswith("RESULT|")][0]
    _, shape1, shape8, pool_bytes, shard_bytes, toks = line.split("|")
    return [
        ("sharded_stream_parity", 0.0,
         f"byte-identical tokens+logprobs+versions on [{shape8}] vs "
         f"[{shape1}] ({toks} tokens incl. group fork + in-flight "
         f"weight relay) platform=cpu"),
        ("sharded_kv_bytes_per_shard", 0.0,
         f"{shard_bytes}B per device shard vs {pool_bytes}B full pool "
         f"(KV heads split over the model axis; expert stacks over "
         f"expert) platform=cpu"),
    ]


if __name__ == "__main__":
    for name, us, derived in main():
        print(f"{name},{us:.1f},{derived}")
