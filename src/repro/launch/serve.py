"""Serving driver: continuous-batching engine over batched requests.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b:reduced \
      --requests 24 --slots 8

Sharded serving (mesh-parallel engines): ``--mesh dp,tp[,ep]`` partitions
the visible devices into ``dp`` disjoint engine shards of ``tp*ep``
devices each — engines stay independent (the paper's multi-client
topology: no inter-engine collectives), but each one lays its paged KV
pool out head-sharded over "model" and its MoE expert stacks over
"expert" (``decode_state_specs`` / ``serve_param_specs``). On CPU, test
with XLA_FLAGS=--xla_force_host_platform_device_count=8:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b:reduced \
      --requests 24 --slots 8 --mesh 2,4
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding


def is_reduced(arch: str) -> bool:
    return arch.partition(":")[2] == "reduced"


def serving_config(arch: str):
    """The served model config. ``:reduced`` archs are test-sized models
    over the byte tokenizer's vocabulary; every other arch keeps its
    published vocabulary."""
    from repro.configs import get_config
    from repro.data import TOKENIZER
    cfg = get_config(arch)
    if is_reduced(arch):
        cfg = dataclasses.replace(cfg, vocab_size=TOKENIZER.vocab_size)
    return cfg


def init_serving_params(cfg, seed: int, *, mesh=None, dtype=None):
    """Random params, made on the device by one jitted init in ``dtype``
    (default: the config's) — never a float32 copy, never a host round
    trip. With a ``mesh`` every leaf is created directly in the engine's
    serving layout (``serve_param_specs``), so a model larger than one
    device never has to fit on one."""
    from repro.models import init_params
    from repro.sharding.rules import serve_param_specs
    init = functools.partial(init_params, cfg=cfg, dtype=dtype)
    key = jax.random.PRNGKey(seed)
    if mesh is None:
        return jax.jit(init)(key)
    shapes = jax.eval_shape(init, key)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), serve_param_specs(shapes, mesh, cfg))
    return jax.jit(init, out_shardings=shardings)(key)


def build_pool(arch: str, *, slots: int, max_seq: int, seed: int = 0,
               engines: int = 1, mesh=None, **engine_kw):
    """The serving stack for ``arch``: ``engines`` independent engines
    behind one ``InferencePool`` — or, with ``mesh=(dp, tp[, ep])``, dp
    engines each spanning its own tp x ep device mesh. Params are bf16 at
    published widths, except ``:reduced`` archs, which serve float32.
    Returns (cfg, pool)."""
    from repro.configs.base import ParallelConfig
    from repro.inference import InferenceEngine, InferencePool
    from repro.launch.mesh import make_engine_meshes

    cfg = serving_config(arch)
    dtype = jnp.float32 if is_reduced(arch) else None
    pcfg = ParallelConfig(remat="none", loss_chunk=0)
    if mesh is None:      # unsharded engines share one copy of the params
        placed = [(init_serving_params(cfg, seed, dtype=dtype), None)] \
            * engines
    else:
        placed = [(init_serving_params(cfg, seed, mesh=m, dtype=dtype), m)
                  for m in make_engine_meshes(*mesh)]
    built = [InferenceEngine(params, cfg, num_slots=slots, max_seq=max_seq,
                             pcfg=pcfg, seed=i, mesh=m, **engine_kw)
             for i, (params, m) in enumerate(placed)]
    return cfg, InferencePool(built)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="yi-9b:reduced")
    p.add_argument("--requests", type=int, default=24)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--engines", type=int, default=1)
    p.add_argument("--mesh", default=None,
                   help="dp,tp[,ep]: engines as mesh shards — dp "
                        "independent engines, each spanning tp (model) "
                        "x ep (expert) devices. Overrides --engines.")
    p.add_argument("--max-new-tokens", type=int, default=24)
    p.add_argument("--max-seq", type=int, default=128)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--spec-draft", type=int, default=0,
                   help="self-drafting speculative decoding: draft up to "
                        "k tokens per slot per round (0 = off; recurrent "
                        "and ring-cache families stay off regardless)")
    p.add_argument("--spec-ngram", type=int, default=3,
                   help="longest n-gram the prompt-lookup drafter matches")
    p.add_argument("--chunk-prefill", type=int, default=0,
                   help="chunked prefill: stream prompts longer than this "
                        "many tokens in chunk-sized no-sample extends "
                        "interleaved with decode ticks (0 = monolithic "
                        "prefill; unsupported layouts stay monolithic)")
    p.add_argument("--prefill-budget", default="0",
                   help="SLO scheduler: max chunk+speculation tokens per "
                        "engine tick (0 = unbounded). Either one int, or "
                        "'I,R' for per-class pools (interactive,rollout); "
                        "the engine-wide total is the sum")
    p.add_argument("--promote-after", type=int, default=64,
                   help="promote a starved rollout-class request to "
                        "interactive priority after this many ticks "
                        "queued (0 = never)")
    p.add_argument("--promote-after-ms", type=float, default=0.0,
                   help="wall-clock companion to --promote-after: promote "
                        "a queued rollout-class request after this many "
                        "milliseconds (0 = never; breaks replayability)")
    p.add_argument("--prefix-cache", action="store_true",
                   help="automatic prefix caching: content-address full KV "
                        "blocks so unrelated requests sharing a prompt "
                        "prefix skip its prefill (unsupported layouts "
                        "stay off)")
    args = p.parse_args()

    if "," in args.prefill_budget:
        inter, roll = (int(x) for x in args.prefill_budget.split(","))
        prefill_budget = {"interactive": inter, "rollout": roll}
    else:
        prefill_budget = int(args.prefill_budget)

    from repro.common.compile_cache import use_compile_cache
    from repro.data import TOKENIZER
    use_compile_cache()
    mesh = None
    if args.mesh is not None:
        mesh = tuple(int(f) for f in args.mesh.split(","))
        if not 2 <= len(mesh) <= 3:
            raise SystemExit("--mesh expects dp,tp or dp,tp,ep")
    _, pool = build_pool(args.arch, slots=args.slots, max_seq=args.max_seq,
                         seed=args.seed, engines=args.engines, mesh=mesh,
                         spec_draft=args.spec_draft,
                         spec_ngram=args.spec_ngram,
                         chunk_prefill=args.chunk_prefill,
                         prefill_token_budget=prefill_budget,
                         promote_after=args.promote_after,
                         promote_after_ms=args.promote_after_ms,
                         prefix_cache=args.prefix_cache)
    if mesh is not None:
        per = int(np.prod(mesh[1:]))
        print(f"mesh serving: {mesh[0]} engine shard(s) x {per} device(s) "
              f"each ({len(jax.devices()) - mesh[0] * per} idle)")

    rng = np.random.RandomState(args.seed)
    t0 = time.time()
    for i in range(args.requests):
        prompt = TOKENIZER.encode(f"request {i}: hello")
        pool.submit_request(prompt,
                            max_new_tokens=int(rng.randint(
                                4, args.max_new_tokens)),
                            temperature=1.0, problem_id=f"req-{i}")
    done = []
    while not pool.idle:
        pool.step()
        done.extend(pool.drain_requests())
    done.extend(pool.drain_requests())
    dt = time.time() - t0
    stats = pool.stats()
    tokens = stats["tokens"]
    occ = [o for e in stats["occupancy"] for o in e]
    print(f"served {len(done)} requests, {tokens} tokens in {dt:.1f}s "
          f"({tokens / dt:.1f} tok/s)")
    print(f"decode steps per engine: {stats['decode_steps']}")
    print(f"prefill batches per engine: {stats['prefill_batches']} "
          f"({stats['prefill_requests']} requests, "
          f"{stats['prefill_traces']} compiled bucket shapes)")
    if any(stats["extend_requests"]):
        print(f"session extends per engine: {stats['extends']} "
              f"({sum(stats['extend_requests'])} turns, "
              f"{stats['prefill_tokens_saved']} prefill tokens saved, "
              f"{stats['session_evictions']} evictions / "
              f"{stats['session_fallbacks']} fallbacks)")
    if stats["spec_rounds"]:
        drafted = stats["spec_drafted_tokens"]
        accepted = stats["spec_accepted_tokens"]
        print(f"speculative decode: {stats['spec_rounds']} verify rounds, "
              f"{stats['spec_committed_tokens']} tokens committed "
              f"({accepted}/{drafted} drafts accepted, "
              f"{accepted / max(1, drafted):.0%} acceptance, "
              f"{stats['spec_saved_ticks']} decode ticks skipped)")
    if stats["chunked_admissions"]:
        print(f"chunked prefill: {stats['chunked_admissions']} admissions "
              f"in {stats['prefill_chunks']} chunk dispatches "
              f"({stats['chunk_tokens']} chunk tokens, "
              f"{stats['sched_promotions']} deadline promotions, "
              f"{stats['sched_budget_deferrals']} budget deferrals)")
    if stats["prefix_cache_hits"] or stats["prefix_cache_misses"]:
        looked = stats["prefix_cache_hits"] + stats["prefix_cache_misses"]
        print(f"prefix cache: {stats['prefix_cache_hits']}/{looked} "
              f"admissions hit ({stats['prefix_cache_hit_tokens']} prompt "
              f"tokens served from cache; {stats['prefix_cache_cached_blocks']}"
              f" blocks cached, {stats['prefix_cache_retired']} retired / "
              f"{stats['prefix_cache_reclaimed']} reclaimed / "
              f"{stats['prefix_cache_swept']} swept stale)")
    lat = stats["latency"]
    if lat["ttft_n"]:
        print(f"latency (window of {lat['ttft_n']} requests): "
              f"TTFT p50 {lat['ttft_p50'] * 1e3:.1f}ms / "
              f"p99 {lat['ttft_p99'] * 1e3:.1f}ms; "
              f"ITL p50 {lat['itl_p50'] * 1e3:.1f}ms / "
              f"p99 {lat['itl_p99'] * 1e3:.1f}ms "
              f"({lat['itl_n']} inter-token gaps)")
    if stats["kv_blocks_total"]:
        print(f"paged KV: peak {stats['kv_blocks_peak']}"
              f"/{stats['kv_blocks_total']} blocks "
              f"({stats['kv_bytes']} pool bytes, "
              f"{stats['cow_forks']} COW copies, "
              f"{stats['blocks_freed_on_evict']} blocks evicted, "
              f"{stats['kv_blocks_in_use']} still in use)")
    if stats["pooled_state_bytes"]:
        print(f"cache layout: {stats['pageable_kv_bytes']} pageable KV bytes, "
              f"{stats['pooled_state_bytes']} pooled state-row bytes "
              f"({stats['parked_state_bytes']} parked)")
    if any(stats["mesh_shapes"]):
        for i, (shape, per_shard) in enumerate(zip(
                stats["mesh_shapes"], stats["kv_bytes_per_shard"])):
            print(f"engine {i} mesh [{shape}]: "
                  f"{per_shard} KV bytes per device shard")
    print(f"mean slot occupancy: {np.mean(occ):.2f}/{args.slots} "
          f"(continuous batching keeps slots saturated)")
    for r in done[:3]:
        # the byte tokenizer can only render the reduced archs' vocabulary
        out = (TOKENIZER.decode(r.completion) if is_reduced(args.arch)
               else r.completion[:8])
        print(f"  {r.problem_id}: {len(r.completion)} tokens "
              f"({r.finish_reason}) -> {out!r}")


if __name__ == "__main__":
    main()
