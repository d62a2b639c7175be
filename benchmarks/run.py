"""Benchmark aggregator — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  fig3   async off-policy overlap simulation (>2x claim, §3.3)
  fig4   continuous batching occupancy on the real engine (§2.1.3)
  sessions multi-turn KV reuse vs re-prefill on the real engine (§2.2.1)
  group  group-shared prefill: one prompt forked to a GRPO group (§2.1)
  paged  paged KV cache: block-pool capacity + COW forks vs dense rows
  hybrid hybrid sessions: paged attention KV + pooled SSM state rows
  sharded mesh-parallel engine: per-shard KV bytes, stream parity (§2.1)
  spec   self-drafting speculative decoding: multi-token ticks, parity-gated
  slo    chunked prefill vs monolithic under mixed open-loop traffic (p99 ITL)
  prefix automatic prefix caching: shared-system-prompt traffic, parity-gated
  fig5   grouped-GEMM saturation vs experts (§2.1.8)
  fig10  IcePop vs GSPO stability under staleness (§3.3)
  tab    multi-client scaling (§2.1.4) + distributed Muon (§2.1.7)
  actmem activation-memory formula validation (§2.1.6)
  kernels Pallas kernel micro-bench (interpret mode)
  roofline per-pair dominant terms from the dry-run artifacts
"""
from __future__ import annotations

import sys
import time
import traceback

MODULES = [
    ("fig3_async_overlap", "benchmarks.fig3_async_overlap"),
    ("fig4_continuous_batching", "benchmarks.fig4_continuous_batching"),
    ("fig_multiturn_sessions", "benchmarks.fig_multiturn_sessions"),
    ("fig_group_prefill", "benchmarks.fig_group_prefill"),
    ("fig_paged_kv", "benchmarks.fig_paged_kv"),
    ("fig_hybrid_sessions", "benchmarks.fig_hybrid_sessions"),
    ("fig_sharded_engine", "benchmarks.fig_sharded_engine"),
    ("fig_speculative", "benchmarks.fig_speculative"),
    ("fig_serving_slo", "benchmarks.fig_serving_slo"),
    ("fig_prefix_cache", "benchmarks.fig_prefix_cache"),
    ("fig5_grouped_gemm", "benchmarks.fig5_grouped_gemm"),
    ("fig10_stability", "benchmarks.fig10_stability"),
    ("tab_scaling", "benchmarks.tab_scaling"),
    ("act_memory", "benchmarks.act_memory"),
    ("bench_kernels", "benchmarks.bench_kernels"),
    ("roofline_table", "benchmarks.roofline_table"),
    ("perf_hillclimb", "benchmarks.perf_hillclimb"),
]


def main() -> None:
    import importlib

    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    failures = []
    print("name,us_per_call,derived")
    for tag, modname in MODULES:
        t0 = time.time()
        try:
            mod = importlib.import_module(modname)
            for name, us, derived in mod.main():
                print(f"{name},{us:.1f},{derived}", flush=True)
            print(f"_section_{tag}_elapsed,{(time.time()-t0)*1e6:.0f},ok",
                  flush=True)
        except Exception:
            failures.append(tag)
            print(f"_section_{tag}_elapsed,0,FAILED", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(f"benchmark sections failed: {failures}")


if __name__ == "__main__":
    main()
