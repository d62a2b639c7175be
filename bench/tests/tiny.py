"""A benchmark cell cut to what a CPU test run can hold: its configuration
at the program's reduced sizes (float32) behind a 4-slot engine, with its
traffic scaled down to match. Everything else is the harness as a chip run
drives it."""
from __future__ import annotations

import time

import jax

from bench import harness

ENGINE = {"slots": 4, "max_seq": 256, "kv_block_size": 16,
          "num_kv_blocks": 64, "min_prefill_bucket": 8,
          "prefix_cache": True, "chunk_prefill": 0, "spec_draft": 0}
ROLLOUT = {"group_size": 4, "groups": 16, "greedy_every": 2,
           "prompt_len": {"dist": "lognormal", "median": 32, "sigma": 0.5,
                          "min": 16, "max": 64},
           "max_new": {"dist": "pareto", "alpha": 1.2, "min": 16,
                       "max": 40},
           "lead_in": {"prompt_len": 16, "max_new": 6},
           "check": {"requests": 3, "greedy_groups": 1}}
AGENT = {"rate_per_s": 4.0, "sessions": 64, "max_open_sessions": 4,
         "system_len": 32, "user_len": {"dist": "uniform", "min": 8,
                                        "max": 24},
         "tool_len": {"dist": "uniform", "min": 8, "max": 24},
         "reply_len": {"dist": "uniform", "min": 4, "max": 12},
         "tool_delay_s": {"dist": "uniform", "min": 0.05, "max": 0.2,
                          "float": True},
         "extend_rows": [4, 2, 1], "warmup_s": 1.0,
         "check": {"sessions": 3, "greedy_sessions": 1}}


def cell(workload: str):
    c = harness.load_cell(workload)
    cfg = harness.model_config(c.config).reduced()
    c.config = dict(
        c.config, dtype="float32",
        num_hidden_layers=cfg.num_layers, hidden_size=cfg.d_model,
        intermediate_size=cfg.d_ff, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
        vocab_size=cfg.vocab_size, engine=ENGINE)
    small = ROLLOUT if c.traffic["generator"] == "backlog_groups" else AGENT
    c.traffic = dict(c.traffic, **small)
    return c


def run(workload: str, seed: int, seconds: float = 1.5, checker=None):
    """One run as ``bench/run.py`` makes it, past the chip gate."""
    c = cell(workload)
    return harness.run_cell(
        c, seed=seed, seconds=seconds, trace=False,
        t_start=time.perf_counter(), devices=jax.devices()[:1],
        peaks=harness.load_peaks("TPU v5 lite"), log=harness.CompileLog(),
        checker=checker)
