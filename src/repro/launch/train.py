"""Training driver: SFT or end-to-end RL on any assigned arch (CPU-runnable
on reduced configs; the same step functions lower on the production mesh).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch minitron-4b:reduced \
      --mode sft --steps 20
  PYTHONPATH=src python -m repro.launch.train --arch minitron-4b:reduced \
      --mode rl --steps 5 --env math --async-level 8
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np


def run_sft(args) -> dict:
    from repro.configs import get_config
    from repro.configs.base import OptimizerConfig, ParallelConfig
    from repro.data import TOKENIZER, pack_documents, synthetic_reasoning_docs
    from repro.train import Trainer

    cfg = dataclasses.replace(get_config(args.arch),
                              vocab_size=TOKENIZER.vocab_size)
    pcfg = ParallelConfig(remat=args.remat, loss_chunk=0)
    opt = OptimizerConfig(name=args.optimizer, lr=args.lr,
                          schedule="linear_warmup", warmup_steps=5,
                          total_steps=args.steps)
    trainer = Trainer(jax.random.PRNGKey(args.seed), cfg, opt, pcfg=pcfg,
                      dtype=jnp.float32, mode="sft")
    losses = []
    for step in range(args.steps):
        docs = list(synthetic_reasoning_docs(args.batch * 2,
                                             seed=args.seed + step))
        batch = pack_documents(docs, seq_len=args.seq_len,
                               num_rows=args.batch).as_dict()
        batch.pop("positions")      # packed positions are optional
        batch.pop("segment_ids")
        t0 = time.time()
        m = trainer.step(batch)
        losses.append(m["lm_loss"])
        if step % max(1, args.steps // 10) == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss={m['lm_loss']:.4f} "
                  f"grad_norm={m['grad_norm']:.3f} ({time.time()-t0:.2f}s)",
                  flush=True)
    assert losses[-1] < losses[0], "SFT loss did not improve"
    print(f"SFT: loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    return {"first_loss": losses[0], "last_loss": losses[-1]}


def run_rl(args) -> dict:
    from repro.configs import get_config
    from repro.configs.base import (OptimizerConfig, ParallelConfig, RLConfig)
    from repro.core import AsyncRLRunner, Orchestrator
    from repro.data import TOKENIZER
    from repro.envs import load_logic_env, load_math_env
    from repro.inference import InferenceEngine, InferencePool
    from repro.train import Trainer

    cfg = dataclasses.replace(get_config(args.arch),
                              vocab_size=TOKENIZER.vocab_size)
    pcfg = ParallelConfig(remat="none", loss_chunk=0)
    opt = OptimizerConfig(name=args.optimizer, lr=args.lr,
                          schedule="constant")
    rl = RLConfig(batch_prompts=args.batch, group_size=args.group_size,
                  algorithm=args.algorithm, async_level=args.async_level)
    trainer = Trainer(jax.random.PRNGKey(args.seed), cfg, opt, rl, pcfg,
                      dtype=jnp.float32, mode="rl")
    engines = [InferenceEngine(trainer.params, cfg, num_slots=args.slots,
                               max_seq=args.seq_len, pcfg=pcfg, seed=i)
               for i in range(args.engines)]
    pool = InferencePool(engines)
    load_env = {"math": load_math_env, "logic": load_logic_env}[args.env]
    env = load_env(n=args.problems, seed=args.seed,
                   max_new_tokens=args.max_new_tokens)
    orch = Orchestrator(env, pool, rl, max_new_tokens=args.max_new_tokens)
    runner = AsyncRLRunner(trainer, orch)

    def on_step(step, m, r):
        recent = orch.stats.rewards[-rl.batch_prompts * rl.group_size:]
        print(f"step {step:3d} rl_loss={m['rl_loss']:+.4f} "
              f"reward={np.mean(recent):.3f} "
              f"masked={m.get('masked_frac', 0.0):.3f} "
              f"groups={orch.stats.groups_completed} "
              f"qdepth={r.stats.queue_depth[-1] if r.stats.queue_depth else 0} "
              f"ahead={r.stats.trainer_ahead[-1]} "
              f"overlap_ticks={r.stats.overlap_ticks}", flush=True)

    out = asyncio.run(runner.run(args.steps, on_step=on_step))
    s = runner.stats
    print(f"rl done: async_level={s.async_level} steps={s.steps} "
          f"pushed_versions={out['pushed_versions']} "
          f"mean_reward={out['mean_reward']:.3f} "
          f"overlap_ticks={s.overlap_ticks} "
          f"bubble_fraction={s.bubble_fraction:.3f}", flush=True)
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="minitron-4b:reduced")
    p.add_argument("--mode", default="sft", choices=["sft", "rl"])
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--optimizer", default="muon", choices=["muon", "adamw"])
    p.add_argument("--remat", default="none",
                   choices=["full", "selective", "none"])
    p.add_argument("--seed", type=int, default=0)
    # rl
    p.add_argument("--env", default="math", choices=["math", "logic"])
    p.add_argument("--algorithm", default="icepop",
                   choices=["icepop", "cispo", "gspo"])
    p.add_argument("--group-size", type=int, default=4)
    p.add_argument("--async-level", type=int, default=8,
                   help="trainer may run this many steps ahead of rollout "
                        "generation (0 = strictly sequential loop)")
    p.add_argument("--engines", type=int, default=2)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--problems", type=int, default=32)
    p.add_argument("--max-new-tokens", type=int, default=8)
    args = p.parse_args()
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    if args.mode == "sft":
        run_sft(args)
    else:
        run_rl(args)


if __name__ == "__main__":
    main()
