"""Dry-run lowering + roofline analysis core (assignment: MULTI-POD DRY-RUN,
ROOFLINE ANALYSIS).

``lower_pair`` lowers the right step function for an (arch × input-shape)
pair on a mesh with ShapeDtypeStruct inputs (no allocation):

  train_4k      -> RL train step (fwd + IcePop loss + bwd + Muon update) —
                   the paper's actual training unit of work
  prefill_32k   -> prefill (forward + cache fill)
  decode_32k    -> serve_step (one token, 32k KV cache)
  long_500k     -> serve_step (one token, sub-quadratic state: ring/SSM)

``analyze_compiled`` extracts the three roofline terms:
  compute    = HLO_FLOPs / (chips * 197e12)
  memory     = HLO_bytes / (chips * 819e9)
  collective = collective_bytes / (chips * 50e9)
collective_bytes is parsed from the post-SPMD HLO (sum of operand sizes of
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute).
"""
from __future__ import annotations

import dataclasses
import re
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_shape
from repro.configs.base import (InputShape, ModelConfig, OptimizerConfig,
                                ParallelConfig, RLConfig)
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16
from repro.launch.specs import (decode_state_structs, decode_token_struct,
                                prefill_batch_structs, resolve_for_shape,
                                train_batch_structs)
from repro.launch.workload import bytes_estimate, flops_estimate
from repro.models import prefill, serve_step
from repro.sharding.rules import param_shardings
from repro.train.trainer import init_train_state, make_rl_step, make_sft_step

DEFAULT_PCFG = ParallelConfig(remat="full", loss_chunk=1024, scan_layers=True)
DEFAULT_OPT = OptimizerConfig(name="muon", lr=1e-6)
DEFAULT_RL = RLConfig()

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_OP_RE = re.compile(
    r"=\s*(?P<res>\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<start>-start)?\(")
_TYPE_RE = re.compile(r"\b(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|"
                      r"pred|f8e4m3fn|f8e5m2|c64|c128)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LEGACY_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LEGACY_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def collective_stats(hlo_text: str, *, default_group: int = 2) -> dict:
    """Per-collective (op count, per-device wire bytes) from post-SPMD HLO.

    Wire-byte convention (ring algorithms, per participating device):
      all-gather        (S-1)/S * result        ≈ result
      reduce-scatter    (S-1)   * result        (operand = S * result)
      all-reduce        2(S-1)/S * result       ≈ 2 * result
      all-to-all        (S-1)/S * result        ≈ result
      collective-permute  result
    where S = replica-group size parsed from the op. This upper-bounds the
    assignment's operand-sum convention and is what a link-level roofline
    sees.
    """
    stats = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        kind = m.group("op")
        res_bytes = sum(_shape_bytes(t, d)
                        for t, d in _TYPE_RE.findall(m.group("res")))
        S = max(2, _group_size(line, default_group))
        if kind == "all-gather":
            wire = res_bytes * (S - 1) // S
        elif kind == "reduce-scatter":
            wire = res_bytes * (S - 1)
        elif kind == "all-reduce":
            wire = 2 * res_bytes * (S - 1) // S
        elif kind == "all-to-all":
            wire = res_bytes * (S - 1) // S
        else:  # collective-permute
            wire = res_bytes
        stats[kind]["count"] += 1
        stats[kind]["bytes"] += wire
    stats["total_bytes"] = sum(v["bytes"] for k, v in stats.items()
                               if isinstance(v, dict))
    stats["total_count"] = sum(v["count"] for k, v in stats.items()
                               if isinstance(v, dict))
    return stats


# ---------------------------------------------------------------------------
# Lowering
# ---------------------------------------------------------------------------


def _with_shardings(struct_tree, sharding_tree):
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        struct_tree, sharding_tree)


def lower_pair(arch: str, shape_name: str, mesh, *,
               pcfg: ParallelConfig = DEFAULT_PCFG,
               opt_cfg: OptimizerConfig = DEFAULT_OPT,
               rl_cfg: RLConfig = DEFAULT_RL,
               mode: str = "auto",
               grad_constraint: bool = False,
               tp_serving: bool = False,
               fsdp_prefer: str = "largest",
               fsdp_axes=("data", "model"),
               expert_parallel: bool = False):
    """Lower the step for (arch, shape) on mesh. Returns (lowered, meta).

    §Perf levers (beyond-paper; baselines keep all False):
      grad_constraint  pin gradient shardings to the param layout
                       (reduce-scatter instead of all-reduce)
      opt_cfg.layer_reshard_ns  Dion-style Muon NS resharding (§2.1.7)
      tp_serving       Megatron TP layout for decode/prefill params
    """
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    cfg, meta = resolve_for_shape(cfg, shape)
    cfg = dataclasses.replace(cfg, dtype="bfloat16")
    if expert_parallel:
        pcfg = dataclasses.replace(pcfg, expert_parallel=True)
    meta.update(arch=arch, shape=shape_name, kind=shape.kind,
                mesh=dict(mesh.shape), remat=pcfg.remat,
                loss_chunk=pcfg.loss_chunk, _cfg=cfg, _shape=shape)

    if shape.kind == "train":
        state_struct = jax.eval_shape(
            lambda: init_train_state(jax.random.PRNGKey(0), cfg, opt_cfg))
        # optimizer-state leaves mirror their parameter's sharding (ZeRO-3:
        # params, grads AND optimizer state all sharded)
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.sharding.rules import param_specs
        from repro.train.trainer import TrainState
        specs = param_specs(state_struct.params, mesh, prefer=fsdp_prefer,
                            fsdp_axes=fsdp_axes,
                            expert_sharding=expert_parallel)
        mirror = lambda: jax.tree_util.tree_map(
            lambda sp: NamedSharding(mesh, sp), specs)
        state_shardings = TrainState(
            params=mirror(),
            opt_state=type(state_struct.opt_state)(
                momentum=mirror(), adam_m=mirror(), adam_v=mirror(),
                count=NamedSharding(mesh, P())),
            step=NamedSharding(mesh, P()))
        state_struct = _with_shardings(state_struct, state_shardings)
        rl = mode in ("auto", "rl")
        batch = train_batch_structs(cfg, shape, mesh, rl=rl)
        grad_specs = specs if grad_constraint else None
        if rl:
            step = make_rl_step(cfg, opt_cfg, rl_cfg, pcfg, jit=False,
                                grad_specs=grad_specs)
        else:
            step = make_sft_step(cfg, opt_cfg, pcfg, jit=False,
                                 grad_specs=grad_specs)
        fn = jax.jit(step, donate_argnums=(0,))
        with mesh:
            lowered = fn.lower(state_struct, batch)
        meta["step"] = "rl_train" if rl else "sft_train"
        meta["tokens"] = shape.tokens
        return lowered, meta

    # inference shapes: params only (bf16)
    from repro.models import init_params
    params_struct = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    if tp_serving:
        from jax.sharding import NamedSharding
        from repro.sharding.rules import tp_param_specs
        p_shardings = jax.tree_util.tree_map(
            lambda sp: NamedSharding(mesh, sp),
            tp_param_specs(params_struct, mesh))
        meta["variant"] += "+tp"
    else:
        p_shardings = param_shardings(params_struct, mesh,
                                      prefer=fsdp_prefer,
                                      fsdp_axes=fsdp_axes,
                                      expert_sharding=expert_parallel)
    params_struct = _with_shardings(params_struct, p_shardings)

    if shape.kind == "prefill":
        batch = prefill_batch_structs(cfg, shape, mesh)
        fn = jax.jit(partial(prefill, cfg=cfg, max_seq=shape.seq_len,
                             pcfg=pcfg))
        with mesh:
            lowered = fn.lower(params_struct, batch)
        meta["step"] = "prefill"
        meta["tokens"] = shape.tokens
        return lowered, meta

    # decode
    state_structs, _ = decode_state_structs(cfg, shape, mesh)
    token = decode_token_struct(cfg, shape, mesh)
    fn = jax.jit(partial(serve_step, cfg=cfg, pcfg=pcfg))
    with mesh:
        lowered = fn.lower(params_struct, state_structs, token)
    meta["step"] = "serve_step"
    meta["tokens"] = shape.global_batch  # one token per sequence
    return lowered, meta


# ---------------------------------------------------------------------------
# Analysis
# ---------------------------------------------------------------------------


def analyze_compiled(compiled, meta: dict, *, n_chips: int) -> dict:
    """Roofline terms from the compiled artifact + analytic workload model.

    * collective term: trip-count-aware parse of the post-SPMD HLO (the
      layer scan's per-iteration collectives multiplied by L — see
      hlo_parse.py; a flat parse is recorded for reference).
    * compute/memory terms: analytic workload model (workload.py), because
      cost_analysis counts while bodies once (scan-over-layers would be
      undercounted by ~L×). cost_analysis values are recorded alongside.
    """
    from repro.launch.hlo_parse import collective_wire_bytes
    cost = compiled.cost_analysis() or {}
    flops_ca = float(cost.get("flops", 0.0))          # per-device, body-once
    bytes_ca = float(cost.get("bytes accessed", 0.0))
    hlo_text = compiled.as_text()
    coll = collective_wire_bytes(hlo_text)
    coll_flat = collective_stats(hlo_text)

    cfg = meta["_cfg"]
    shape = meta["_shape"]
    fl = flops_estimate(cfg, shape, kind=meta["kind"],
                        remat=meta.get("remat", "full"))
    by = bytes_estimate(cfg, shape, kind=meta["kind"],
                        remat=meta.get("remat", "full"),
                        loss_chunk=meta.get("loss_chunk", 1024))

    out = {k: v for k, v in meta.items() if not k.startswith("_")}
    out["n_chips"] = n_chips
    out["flops_global"] = fl["total"]
    out["bytes_global"] = by["total"]
    out["flops_breakdown"] = fl
    out["bytes_breakdown"] = by
    out["cost_analysis_flops_per_device"] = flops_ca
    out["cost_analysis_bytes_per_device"] = bytes_ca
    out["collective_bytes"] = coll["total_bytes"]
    out["collective_ops"] = coll["total_count"]
    out["collectives"] = {k: coll[k] for k in _COLLECTIVES}
    out["collectives_flat"] = {k: coll_flat[k] for k in _COLLECTIVES}
    out["t_compute"] = fl["total"] / (n_chips * PEAK_FLOPS_BF16)
    out["t_memory"] = by["total"] / (n_chips * HBM_BW)
    out["t_collective"] = coll["total_bytes"] / ICI_BW
    terms = {"compute": out["t_compute"], "memory": out["t_memory"],
             "collective": out["t_collective"]}
    out["bottleneck"] = max(terms, key=terms.get)
    try:
        mem = compiled.memory_analysis()
        out["bytes_per_device"] = {
            "arguments": getattr(mem, "argument_size_in_bytes", None),
            "outputs": getattr(mem, "output_size_in_bytes", None),
            "temps": getattr(mem, "temp_size_in_bytes", None),
        }
    except Exception as e:  # memory analysis can be backend-dependent
        out["bytes_per_device"] = {"error": str(e)}
    return out


def model_flops(cfg: ModelConfig, tokens: int, kind: str) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE); bwd included only
    for training (train = 3x forward's 2ND)."""
    n_active = cfg.param_counts()["active"]
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_active * tokens


def run_pair(arch: str, shape_name: str, mesh, **kw) -> dict:
    lowered, meta = lower_pair(arch, shape_name, mesh, **kw)
    compiled = lowered.compile()
    n_chips = int(np.prod(list(mesh.shape.values())))
    out = analyze_compiled(compiled, meta, n_chips=n_chips)
    mf = model_flops(meta["_cfg"], meta["tokens"], meta["kind"])
    out["model_flops"] = mf
    out["useful_frac"] = (mf / out["flops_global"]
                          if out["flops_global"] else 0.0)
    return out
