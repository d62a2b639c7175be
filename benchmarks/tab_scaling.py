"""§2.1.4 multi-client scaling + §2.1.7 distributed Muon collectives.

(1) Multi-client inference: decode wall-steps to drain a fixed workload vs
    number of independent engines (round-robin dispatch). The paper's fix
    for the vLLM multi-node plateau gives linear scaling in engines;
    with N engines stepping in lockstep the wall-step count must fall ~1/N.

(2) Distributed Muon: lowered collective op counts and wire bytes for the
    round-robin (many gathers) vs all-to-all (Dion) schemes on an 8-way
    FSDP axis — the ICI restatement of the InfiniBand congestion argument.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ParallelConfig
from repro.data import TOKENIZER
from repro.inference import InferenceEngine, InferencePool
from .common import run_with_devices

PCFG = ParallelConfig(remat="none", loss_chunk=0)


def multi_client_scaling():
    cfg = dataclasses.replace(get_config("minitron-4b:reduced"),
                              vocab_size=TOKENIZER.vocab_size, num_layers=2)
    from repro.models import init_params
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    rows = []
    base = None
    for n_eng in (1, 2, 4):
        pool = InferencePool([
            InferenceEngine(params, cfg, num_slots=4, max_seq=64, seed=i)
            for i in range(n_eng)])
        for i in range(32):
            pool.submit_group(f"p{i}", np.arange(4, dtype=np.int32) + 10,
                              group_size=1, max_new_tokens=8)
        wall_steps = 0
        while not pool.idle:
            pool.step()
            wall_steps += 1
        pool.drain_groups()
        base = base or wall_steps
        rows.append((f"scaling_{n_eng}_engines_wall_steps", float(wall_steps),
                     f"{base / wall_steps:.2f}x"))
    return rows


def muon_collectives():
    out = run_with_devices("""
from repro.optim import lower_scheme
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('model',))
from repro.launch.hlo_parse import collective_wire_bytes
for scheme in ('round_robin', 'all_to_all'):
    lo = lower_scheme(mesh, (48, 4096, 1024), scheme=scheme)
    stats = collective_wire_bytes(lo.compile().as_text())
    print(f"{scheme},{stats['total_count']},{stats['total_bytes']}")
""")
    rows = []
    vals = {}
    for line in out.strip().splitlines():
        scheme, count, byts = line.split(",")
        vals[scheme] = (int(count), int(byts))
        rows.append((f"muon_{scheme}_collectives", float(count),
                     f"{int(byts) / 1e6:.1f}MB wire platform=cpu"))
    rr, a2a = vals["round_robin"], vals["all_to_all"]
    rows.append(("muon_a2a_vs_rr_bytes_ratio", 0.0,
                 f"{rr[1] / max(a2a[1], 1):.1f}x less data, "
                 f"{rr[0] / max(a2a[0], 1):.1f}x fewer ops platform=cpu"))
    return rows


def main():
    return multi_client_scaling() + muon_collectives()


if __name__ == "__main__":
    for name, us, derived in main():
        print(f"{name},{us:.1f},{derived}")
