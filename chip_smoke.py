"""Chip smoke test: the serving engine's main path, run on TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # one host with four chips

One chip runs two phases:

  kernels  every Pallas kernel through ``repro.kernels.ops`` at main-path
           widths, compiled natively (``tpu_custom_call`` in the compiled
           program) and checked against ``repro.kernels.ref``;
  serve    minitron-4b at its published widths (32 layers, d_model 3072,
           24/8 heads, vocab 256000, bf16 params made on the device from
           ``--seed``) behind ``InferencePool``: single requests, one GRPO
           group and a two-turn session, twice over (the first pass
           compiles, the second runs warm), with the engine's first tokens
           checked against a plain ``models.forward`` on the chip.

``--four-chips`` runs only the sharded engine and what it is compared
with: qwen2-moe-a2.7b cut to 2 layers, served on mesh (1, 4) and on one
chip with the same greedy requests; then the full 24-layer model, created
directly in its (1, 4) layout, answering a few requests.

Every phase raises on a failed check. The last line of stdout is one JSON
object, printed only when every phase passed:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from collections import defaultdict
from pathlib import Path

# bf16 tolerance of tests/test_kernels.py (ATOL[jnp.bfloat16]); the f32 SSD
# scan is held to it too, since the TPU multiplies f32 operands in bf16
# passes at the default matmul precision
KERNEL_TOL = 5e-2
# engine vs plain forward, in nats: both run bf16 weights and activations at
# the TPU's default matmul precision, but fuse and order the 32-layer stack
# differently. bf16 keeps 8 significant bits (a relative step of 2^-8), so
# logits of magnitude ~10 may move by ~0.04 between the two programs; 0.1
# leaves a margin of 2.5x. A greedy token may differ from the reference
# argmax only where the reference's top two are within the same 0.1.
LOGPROB_TOL = 0.1
FINISHED_OK = ("length", "eos")


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.compiles, self.cache_hits


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", -1)


def _report(name, **fields):
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{name}] {body}", flush=True)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def kernel_cases(seed, *, flash=(1, 2048, 24, 8, 128),
                 paged=(8, 24, 8, 128, 16, 64), gmm=(60, 128, 2048, 1408),
                 ssd=(1, 1024, 32, 64, 128, 256)):
    """(name, fn, args, ref_fn) per kernel. Defaults are main-path widths:
    attention at minitron-4b heads (24 q / 8 kv, head_dim 128; decode
    through 8 slots x 64 blocks of 16), grouped matmul at qwen2-moe expert
    widths (60 experts, 2048 -> 1408), SSD scan at mamba2-370m (32 heads of
    64, state 128, chunk 256)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    bf16 = jnp.bfloat16

    def rand(shape, dtype=bf16, scale=1.0):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    B, S, Hq, Hkv, hd = flash
    flash_args = (rand((B, S, Hq, hd)), rand((B, S, Hkv, hd)),
                  rand((B, S, Hkv, hd)))

    B, Hq, Hkv, hd, bs, nblk = paged
    num_blocks = B * nblk
    tables = jax.random.permutation(next(ks), num_blocks).reshape(B, nblk)
    pos = jax.random.randint(next(ks), (B,), 0, nblk * bs)
    paged_args = (rand((B, 1, Hq, hd)), rand((num_blocks, bs, Hkv, hd)),
                  rand((num_blocks, bs, Hkv, hd)), tables.astype(jnp.int32),
                  pos.astype(jnp.int32))

    E, C, d, f = gmm
    sizes = jax.random.randint(next(ks), (E,), 0, C + 1).astype(jnp.int32)
    gmm_args = (rand((E, C, d)), rand((E, d, f), scale=d ** -0.5), sizes)

    B, S, nh, hd, n, chunk = ssd
    f32 = jnp.float32
    ssd_args = (rand((B, S, nh, hd), f32),
                jax.nn.softplus(rand((B, S, nh), f32)) * 0.1,
                -jnp.abs(rand((B, S, nh), f32)) * 0.02,
                rand((B, S, nh, n), f32, n ** -0.5),
                rand((B, S, nh, n), f32, n ** -0.5),
                jnp.zeros((B, nh, hd, n), f32))

    return [
        ("flash_attention", lambda q, k, v: ops.flash_attention(q, k, v),
         flash_args, lambda q, k, v: ref.flash_attention_ref(q, k, v)),
        ("paged_attention", ops.paged_attention, paged_args,
         ref.paged_attention_ref),
        ("grouped_matmul", ops.grouped_matmul, gmm_args,
         ref.grouped_matmul_ref),
        ("ssd_scan", lambda *a: ops.ssd_scan(*a, chunk=chunk), ssd_args,
         ref.ssd_scan_ref),
    ]


def phase_kernels(seed, **sizes):
    """Compile each kernel, assert it compiled natively, run it, and hold
    it to the pure-jnp oracle (run at full f32 matmul precision)."""
    import jax
    import numpy as np

    for name, fn, args, ref_fn in kernel_cases(seed, **sizes):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        if "tpu_custom_call" not in compiled.as_text():
            raise AssertionError(f"{name}: no tpu_custom_call in the "
                                 f"compiled program (kernel interpreted)")
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        run_s = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            exp = jax.jit(ref_fn)(*args)
        errs = []
        for o, e in zip(jax.tree_util.tree_leaves(out),
                        jax.tree_util.tree_leaves(exp)):
            o = np.asarray(o, np.float32)
            e = np.asarray(e, np.float32)
            assert np.isfinite(o).all(), f"{name}: non-finite output"
            np.testing.assert_allclose(o, e, atol=KERNEL_TOL, rtol=KERNEL_TOL,
                                       err_msg=name)
            errs.append(float(np.abs(o - e).max()))
        _report("kernel", kernel=name, tpu_custom_call=True,
                compile_s=f"{compile_s:.3f}", first_run_s=f"{run_s:.4f}",
                max_abs_err=f"{max(errs):.3g}",
                shapes=[tuple(a.shape) for a in args])


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def _run_pool(pool, max_steps=10_000):
    done = []
    for _ in range(max_steps):
        if pool.idle:
            break
        pool.step()
        done.extend(pool.drain_requests())
    else:
        raise AssertionError("pool did not drain")
    done.extend(pool.drain_requests())
    for eng in pool.engines:
        eng.assert_kv_consistent()
    return done


def _check_finished(reqs, vocab):
    import numpy as np
    for r in reqs:
        assert r.finished and r.finish_reason in FINISHED_OK, \
            (r.request_id, r.finish_reason)
        toks = np.asarray(r.completion)
        lps = np.asarray(r.logprobs)
        assert len(toks) and ((toks >= 0) & (toks < vocab)).all(), \
            (r.request_id, toks)
        assert np.isfinite(lps).all() and (lps <= 1e-6).all(), \
            (r.request_id, lps)


def serve_traffic(pool, cfg, seed, *, new_tokens=16):
    """Single requests (prompt lengths in the 16- and 32-token buckets,
    greedy and temperature 1), one G=4 group at temperature 1, and a
    two-turn greedy session. Returns (all finished requests, the first
    prefill batch: requests + their prompts)."""
    import numpy as np
    rng = np.random.default_rng(seed)

    def prompt(n):
        return rng.integers(2, cfg.vocab_size, n).astype(np.int32)

    sid = pool.open_session()
    assert sid is not None, "engine cannot host sessions"
    first = [(pool.submit_request(p, max_new_tokens=new_tokens,
                                  temperature=t), p)
             for p, t in ((prompt(20), 0.0), (prompt(31), 0.0),
                          (prompt(27), 1.0))]
    p = prompt(13)
    first.append((pool.submit_request(p, max_new_tokens=new_tokens,
                                      temperature=0.0, session=sid), p))
    group = pool.submit_group_request(prompt(24), 4,
                                      max_new_tokens=new_tokens,
                                      temperature=1.0)
    done = _run_pool(pool)
    turn2 = pool.submit_request(prompt(6), max_new_tokens=new_tokens,
                                temperature=0.0, session=sid)
    done += _run_pool(pool)
    pool.close_session(sid)
    reqs = [r for r, _ in first] + group + [turn2]
    assert all(r.finished for r in reqs) and len(done) == len(reqs), \
        (len(done), len(reqs))
    _check_finished(reqs, cfg.vocab_size)
    return reqs, first


def check_first_tokens(params, cfg, first):
    """Each request's first token and logprob against a plain forward of
    the same prompts (right-padded into one batch: causal attention keeps
    the padding out of every real position)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.base import ParallelConfig
    from repro.models import forward

    width = max(len(p) for _, p in first)
    toks = np.zeros((len(first), width), np.int32)
    for i, (_, p) in enumerate(first):
        toks[i, :len(p)] = p
    pcfg = ParallelConfig(remat="none", loss_chunk=0)
    logits, _ = jax.jit(forward, static_argnums=(2, 3))(
        params, {"tokens": jnp.asarray(toks)}, cfg, pcfg)
    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    worst_lp, worst_gap = 0.0, 0.0
    for i, (req, p) in enumerate(first):
        row = logp[i, len(p) - 1]
        tok, lp = req.completion[0], req.logprobs[0]
        lp_diff = abs(lp - row[tok])
        assert lp_diff <= LOGPROB_TOL, (req.request_id, lp, row[tok])
        worst_lp = max(worst_lp, lp_diff)
        if req.temperature <= 0:
            gap = row.max() - row[tok]   # 0 when it is the reference argmax
            assert gap <= LOGPROB_TOL, (req.request_id, tok, row.argmax())
            worst_gap = max(worst_gap, gap)
    return worst_lp, worst_gap, int(toks.size)


def phase_serve(seed, log, *, arch="minitron-4b", slots=8, max_seq=1024,
                new_tokens=16):
    import jax
    from repro.launch.serve import build_pool

    dev = jax.devices()[0]
    t0 = time.perf_counter()
    cfg, pool = build_pool(arch, slots=slots, max_seq=max_seq, seed=seed)
    params = jax.block_until_ready(pool.engines[0].params)
    leaves = jax.tree_util.tree_leaves(params)
    dtypes = sorted({str(x.dtype) for x in leaves})
    _report("serve-init", arch=arch, layers=cfg.num_layers,
            d_model=cfg.d_model, heads=f"{cfg.num_heads}/{cfg.num_kv_heads}",
            head_dim=cfg.resolved_head_dim, vocab=cfg.vocab_size,
            param_dtypes=",".join(dtypes),
            param_bytes=sum(x.nbytes for x in leaves),
            kv_pool_bytes=pool.engines[0].stats.kv_bytes,
            slots=slots, max_seq=max_seq,
            init_s=f"{time.perf_counter() - t0:.2f}",
            peak_bytes=_peak_bytes(dev))
    for name in ("cold", "warm"):
        c0 = log.snapshot()
        t0 = time.perf_counter()
        reqs, first = serve_traffic(pool, cfg, seed, new_tokens=new_tokens)
        wall = time.perf_counter() - t0
        c1 = log.snapshot()
        lp_err, gap, ref_tokens = check_first_tokens(params, cfg, first)
        _report(f"serve-{name}", requests=len(reqs),
                tokens=sum(len(r.completion) for r in reqs),
                finish=",".join(sorted({r.finish_reason for r in reqs})),
                wall_s=f"{wall:.2f}",
                compile_s=f"{c1[0] - c0[0]:.2f}", compiles=c1[1] - c0[1],
                cache_hits=c1[2] - c0[2],
                first_token_max_lp_diff=f"{lp_err:.4g}",
                greedy_max_gap_to_ref_argmax=f"{gap:.4g}",
                ref_forward_tokens=ref_tokens,
                kv_consistent=True, peak_bytes=_peak_bytes(dev))
    stats = pool.stats()
    _report("serve-stats", decode_steps=stats["decode_steps"],
            prefill_traces=stats["prefill_traces"],
            kv_blocks_peak=stats["kv_blocks_peak"],
            kv_blocks_total=stats["kv_blocks_total"],
            cow_forks=stats["cow_forks"],
            extend_requests=stats["extend_requests"])


# ---------------------------------------------------------------------------
# four chips: the sharded engine
# ---------------------------------------------------------------------------


def _bytes_per_device(tree):
    import jax
    out = defaultdict(int)
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] += shard.data.nbytes
    return dict(sorted(out.items()))


def _greedy_streams(params, cfg, mesh, seed, *, slots, max_seq, new_tokens):
    import numpy as np
    from repro.configs.base import ParallelConfig
    from repro.inference import InferenceEngine, InferencePool

    eng = InferenceEngine(params, cfg, num_slots=slots, max_seq=max_seq,
                          pcfg=ParallelConfig(remat="none", loss_chunk=0),
                          seed=seed, mesh=mesh)
    pool = InferencePool([eng])
    rng = np.random.default_rng(seed)
    reqs = [pool.submit_request(
        rng.integers(2, cfg.vocab_size, n).astype(np.int32),
        max_new_tokens=new_tokens, temperature=0.0) for n in (9, 17, 30, 22)]
    reqs += pool.submit_group_request(
        rng.integers(2, cfg.vocab_size, 19).astype(np.int32), 4,
        max_new_tokens=new_tokens, temperature=0.0)
    _run_pool(pool)
    _check_finished(reqs, cfg.vocab_size)
    per_dev = _bytes_per_device(eng.params)
    return [(list(r.completion), list(r.logprobs)) for r in reqs], per_dev


def compare_streams(base, other):
    """Tokens agree up to the first near-tie; logprobs within LOGPROB_TOL.
    At a divergence both engines took their own argmax, so their logprobs
    there are the two top logprobs: a gap within the tolerance is a tie
    that rounding may break either way. Returns (tokens compared, max
    logprob difference, streams that diverged at a near-tie)."""
    compared, worst, ties = 0, 0.0, 0
    for (ta, la), (tb, lb) in zip(base, other):
        for j in range(min(len(ta), len(tb))):
            diff = abs(la[j] - lb[j])
            assert diff <= LOGPROB_TOL, (j, ta[j], tb[j], la[j], lb[j])
            worst = max(worst, diff)
            compared += 1
            if ta[j] != tb[j]:
                ties += 1
                break
        else:
            assert len(ta) == len(tb), (ta, tb)
    return compared, worst, ties


def phase_four_chips(seed, *, arch="qwen2-moe-a2.7b", cut_layers=2,
                     slots=8, max_seq=256, new_tokens=12):
    import jax
    from repro.configs import get_config
    from repro.launch.mesh import make_engine_meshes
    from repro.launch.serve import build_pool, init_serving_params

    devices = jax.devices()
    assert len(devices) >= 4, f"need 4 devices, have {len(devices)}"

    # (a) sharded vs one chip, same greedy requests, depth cut to 2 layers
    cfg = dataclasses.replace(get_config(arch), num_layers=cut_layers)
    params = init_serving_params(cfg, seed)
    t0 = time.perf_counter()
    one, _ = _greedy_streams(params, cfg, None, seed, slots=slots,
                             max_seq=max_seq, new_tokens=new_tokens)
    t1 = time.perf_counter()
    mesh = make_engine_meshes(1, 4)[0]
    sharded, per_dev = _greedy_streams(params, cfg, mesh, seed, slots=slots,
                                       max_seq=max_seq,
                                       new_tokens=new_tokens)
    t2 = time.perf_counter()
    compared, worst, ties = compare_streams(one, sharded)
    _report("four-chips-a", arch=arch, layers=cut_layers,
            mesh=dict(mesh.shape), streams=len(one),
            tokens_compared=compared, max_lp_diff=f"{worst:.4g}",
            diverged_at_near_tie=ties, one_chip_s=f"{t1 - t0:.2f}",
            sharded_s=f"{t2 - t1:.2f}", param_bytes_per_device=per_dev)
    del params, one, sharded
    gc.collect()

    # (b) the full model, created directly in its (1, 4) serving layout
    t0 = time.perf_counter()
    cfg, pool = build_pool(arch, slots=slots, max_seq=max_seq, seed=seed,
                           mesh=(1, 4))
    eng = pool.engines[0]
    per_dev = _bytes_per_device(eng.params)
    state_dev = _bytes_per_device(eng.state)
    total = sum(x.nbytes for x in jax.tree_util.tree_leaves(eng.params))
    _report("four-chips-b-init", arch=arch, layers=cfg.num_layers,
            param_bytes_total=total, param_bytes_per_device=per_dev,
            kv_state_bytes_per_device=state_dev,
            init_s=f"{time.perf_counter() - t0:.2f}")
    mesh_ids = sorted(d.id for d in eng.mesh.devices.flat)
    assert sorted(per_dev) == mesh_ids, (per_dev, mesh_ids)
    assert max(per_dev.values()) < total, "params not sharded"
    assert max(per_dev.values()) <= 1.05 * min(per_dev.values()), per_dev
    import numpy as np
    rng = np.random.default_rng(seed + 1)
    reqs = [pool.submit_request(
        rng.integers(2, cfg.vocab_size, n).astype(np.int32),
        max_new_tokens=new_tokens, temperature=t)
        for n, t in ((12, 0.0), (25, 1.0), (31, 0.0))]
    t0 = time.perf_counter()
    _run_pool(pool)
    _check_finished(reqs, cfg.vocab_size)
    _report("four-chips-b-serve", requests=len(reqs),
            tokens=sum(len(r.completion) for r in reqs),
            finish=",".join(sorted({r.finish_reason for r in reqs})),
            wall_s=f"{time.perf_counter() - t0:.2f}", kv_consistent=True,
            peak_bytes_per_device={d.id: _peak_bytes(d)
                                   for d in eng.mesh.devices.flat})


# ---------------------------------------------------------------------------


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--four-chips", action="store_true",
                   help="run only the sharded engine on a (1, 4) mesh and "
                        "what it is compared with")
    args = p.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform}")

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.common.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    log = CompileLog()
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(args.seed)
    else:
        phase_kernels(args.seed)
        phase_serve(args.seed, log)
    secs, compiles, hits = log.snapshot()
    _report("total", wall_s=f"{time.perf_counter() - t0:.2f}",
            compile_s=f"{secs:.2f}", compiles=compiles, cache_hits=hits,
            peak_bytes=_peak_bytes(dev))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
