"""Chunked prefill + SLO-aware scheduler (§2.1.3 serving tail latency).

The contract under test: splitting a long prompt into fixed-size
no-sample extend chunks interleaved with decode ticks must be INVISIBLE
in the streams — byte-identical to the ``HostReferenceEngine`` oracle
(chunking decisions are shared deterministic host logic; mid chunks
consume no RNG, only the final sampling chunk splits the key) and, at
temperature 0, token-identical to monolithic prefill. Around that core:
the scheduler's class priorities and deadline promotion, the per-tick
prefill token budget (shared with speculative drafts), admission under
block-pool pressure with no deadlock and zero leaked blocks on every
terminal path (including cancel mid-chunk), the per-request latency
accounting, and the per-family chunkability gate.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import TOKENIZER
from repro.inference import (GroupRequest, HostReferenceEngine,
                             InferenceEngine, InferencePool, Request)
from repro.inference.cache_layout import CacheLayout
from repro.models import init_params


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_config("minitron-4b:reduced"),
                              vocab_size=TOKENIZER.vocab_size, num_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _req(i, plen, max_new=5, temp=0.0, session_id=None):
    return Request(request_id=i, problem_id=f"p{i}",
                   prompt_tokens=(np.arange(plen) % 50 + 10).astype(np.int32),
                   max_new_tokens=max_new, temperature=temp,
                   session_id=session_id)


def _drain(eng, *, update_at=None, new_params=None, max_steps=5000):
    pushed = update_at is None
    steps = 0
    while not eng.idle:
        eng.step()
        steps += 1
        assert steps < max_steps, "engine stalled (scheduler deadlock?)"
        if not pushed and eng.stats.decode_steps >= update_at:
            eng.update_weights(new_params, 1)
            pushed = True
    assert pushed
    return {r.request_id: r for r in eng.drain_completed()}


def _streams(done):
    return [(tuple(done[i].completion), tuple(done[i].logprobs),
             tuple(done[i].versions), done[i].finish_reason)
            for i in sorted(done)]


# ------------------------------------------------------------------ parity


@pytest.mark.parametrize("temp_mode", ["zero", "mixed"])
def test_chunked_matches_host_reference(setup, temp_mode):
    """Fused chunked == host-reference chunked, byte-identical, including
    across an in-flight update_weights (mixed temps exercise the RNG
    schedule: one split per final chunk, none for mid chunks)."""
    cfg, params = setup
    p2 = jax.tree_util.tree_map(lambda x: x * 1.01, params)

    def run(cls):
        eng = cls(params, cfg, num_slots=3, max_seq=128, seed=7,
                  chunk_prefill=8)
        for i in range(6):
            temp = 0.0 if temp_mode == "zero" else 0.6 + 0.2 * (i % 3)
            eng.submit(_req(i, plen=6 + 11 * i, temp=temp))
        done = _drain(eng, update_at=2, new_params=p2)
        assert len(done) == 6
        return eng, _streams(done)

    eng_f, fused = run(InferenceEngine)
    eng_h, host = run(HostReferenceEngine)
    assert eng_f.stats.chunked_admissions > 0
    assert eng_f.stats.chunked_admissions == eng_h.stats.chunked_admissions
    assert eng_f.stats.prefill_chunks == eng_h.stats.prefill_chunks
    for a, b in zip(fused, host):
        assert a[0] == b[0] and a[2] == b[2] and a[3] == b[3]
        np.testing.assert_allclose(a[1], b[1], atol=1e-5)
    assert eng_f.stats.kv_blocks_in_use == 0
    eng_f.assert_kv_consistent()


def test_chunked_equals_unchunked_greedy(setup):
    """Chunking must not change greedy streams: tokens, versions and
    finish reasons exact; logprobs at float32 tolerance (the final chunk
    samples through a different dispatch bucket than monolithic
    prefill, which re-associates reductions)."""
    cfg, params = setup

    def run(chunk):
        eng = InferenceEngine(params, cfg, num_slots=3, max_seq=128,
                              seed=7, chunk_prefill=chunk)
        for i in range(6):
            eng.submit(_req(i, plen=6 + 11 * i))
        return eng, _streams(_drain(eng))

    eng_c, chunked = run(8)
    eng_u, mono = run(0)
    assert eng_c.stats.chunked_admissions > 0
    assert eng_u.stats.chunked_admissions == 0
    for a, b in zip(chunked, mono):
        assert a[0] == b[0] and a[2] == b[2] and a[3] == b[3]
        np.testing.assert_allclose(a[1], b[1], atol=1e-5)


def test_chunked_ssm_family():
    """Recurrent families ARE chunkable (the pad-masked extend scan
    passes state through pad tokens exactly): fused chunked mamba must
    match the host oracle and the unchunked greedy stream."""
    cfg = dataclasses.replace(get_config("mamba2-370m:reduced"),
                              vocab_size=TOKENIZER.vocab_size)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)

    def run(cls, chunk):
        eng = cls(params, cfg, num_slots=2, max_seq=128, seed=3,
                  chunk_prefill=chunk)
        assert eng.layout.supports_chunked_prefill
        for i in range(4):
            eng.submit(_req(i, plen=9 + 13 * i))
        return eng, _streams(_drain(eng))

    eng_f, fused = run(InferenceEngine, 8)
    _, host = run(HostReferenceEngine, 8)
    _, mono = run(InferenceEngine, 0)
    assert eng_f.stats.chunked_admissions > 0
    for a, b in zip(fused, host):
        assert a[0] == b[0] and a[3] == b[3]
        np.testing.assert_allclose(a[1], b[1], atol=1e-5)
    for a, b in zip(fused, mono):
        assert a[0] == b[0] and a[3] == b[3]
        # chunk boundaries re-enter the recurrent scan per segment, which
        # reassociates the float32 state accumulation vs one monolithic
        # scan — greedy tokens are identical, logprobs drift ~0.3%
        np.testing.assert_allclose(a[1], b[1], rtol=1e-2)


def test_chunked_session_resident_extend(setup):
    """A long next-turn delta on a RESIDENT session streams in chunks
    from the parked cache (base = cached prefix) and must reproduce the
    monolithic extend stream; the cached prefix is still not re-run."""
    cfg, params = setup

    def run(chunk):
        eng = InferenceEngine(params, cfg, num_slots=2, max_seq=256,
                              seed=5, chunk_prefill=chunk)
        eng.open_session(0)
        eng.submit(_req(0, plen=10, session_id=0))
        first = _drain(eng)
        eng.submit(Request(request_id=1, problem_id="t1",
                           prompt_tokens=(np.arange(40) % 37 + 20
                                          ).astype(np.int32),
                           max_new_tokens=5, temperature=0.0, session_id=0))
        second = _drain(eng)
        return eng, _streams(first) + _streams(second)

    eng_c, chunked = run(8)
    eng_u, mono = run(0)
    assert eng_c.stats.chunked_admissions >= 1
    assert eng_c.stats.prefill_tokens_saved > 0  # prefix NOT re-prefilled
    for a, b in zip(chunked, mono):
        assert a[0] == b[0] and a[3] == b[3]
        np.testing.assert_allclose(a[1], b[1], atol=1e-5)


# ------------------------------------------------------ scheduler semantics


def test_interactive_class_jumps_queue(setup):
    """With one slot held, a later interactive arrival must be admitted
    before an earlier rollout-class request (stable two-class
    partition); with no scheduler pressure the rollout still runs."""
    cfg, params = setup
    eng = InferenceEngine(params, cfg, num_slots=1, max_seq=64, seed=0,
                          promote_after=0)
    hold = _req(0, plen=4, max_new=12)
    eng.submit(hold)
    roll = _req(1, plen=4, max_new=3)
    roll.sched_class = "rollout"
    eng.submit(roll)
    inter = _req(2, plen=4, max_new=3)
    inter.sched_class = "interactive"
    eng.submit(inter)
    done = _drain(eng)
    assert len(done) == 3
    assert done[2].token_ts[0] < done[1].token_ts[0]


def test_deadline_promotion_unstarves_rollouts(setup):
    """An aged rollout request is promoted to interactive priority after
    promote_after ticks, so a later interactive arrival can no longer
    jump it (sticky, counted once in stats)."""
    cfg, params = setup
    eng = InferenceEngine(params, cfg, num_slots=1, max_seq=64, seed=0,
                          promote_after=3)
    eng.submit(_req(0, plen=4, max_new=12))
    roll = _req(1, plen=4, max_new=3)
    roll.sched_class = "rollout"
    eng.submit(roll)
    for _ in range(6):        # age the rollout past the deadline
        eng.step()
    inter = _req(2, plen=4, max_new=3)
    inter.sched_class = "interactive"
    eng.submit(inter)
    done = _drain(eng)
    assert eng.stats.sched_promotions == 1
    assert done[1].token_ts[0] < done[2].token_ts[0]


def test_prefill_budget_paces_chunks_and_caps_spec(setup):
    """A per-tick token budget defers chunk writes (counted) and caps
    speculative draft length — without changing the greedy streams."""
    cfg, params = setup

    def run(budget, spec):
        eng = InferenceEngine(params, cfg, num_slots=4, max_seq=256,
                              seed=9, chunk_prefill=8, spec_draft=spec,
                              prefill_token_budget=budget)
        rng = np.random.default_rng(4)
        for i in range(4):
            base = rng.integers(5, 30, 3).astype(np.int32)
            eng.submit(Request(
                request_id=i, problem_id=f"p{i}",
                prompt_tokens=np.tile(base, 14),  # 42 tokens, periodic
                max_new_tokens=8, temperature=0.0))
        return eng, _streams(_drain(eng))

    eng_b, budgeted = run(budget=8, spec=4)
    eng_f, free = run(budget=0, spec=4)
    assert eng_b.stats.sched_budget_deferrals > 0
    assert eng_b.stats.chunked_admissions > 0
    assert eng_f.stats.sched_budget_deferrals == 0
    for a, b in zip(budgeted, free):
        assert a[0] == b[0] and a[3] == b[3]
        np.testing.assert_allclose(a[1], b[1], atol=1e-5)
    assert eng_b.stats.kv_blocks_in_use == 0


# --------------------------------------------- pressure, cancel, leak paths


def test_mixed_queue_under_block_pressure(setup):
    """Chunked prefills + session extends + group forks against a block
    pool sized for ~half the slots: every request must reach a terminal
    state (no deadlock, no starvation — overflow is a legal outcome
    under pressure), with zero blocks in use after the drain."""
    cfg, params = setup
    probe = InferenceEngine(params, cfg, num_slots=4, max_seq=128, seed=0)
    bpr = probe._blocks_per_row
    eng = InferenceEngine(params, cfg, num_slots=4, max_seq=128, seed=0,
                          chunk_prefill=8,
                          num_kv_blocks=2 * bpr + bpr // 2)
    eng.open_session(0)
    rid = 0
    for plen in (50, 70, 40):          # chunked long prompts
        eng.submit(_req(rid, plen=plen, max_new=4))
        rid += 1
    eng.submit_group(GroupRequest(0, "g", np.arange(10, 22, dtype=np.int32),
                                  members=[Request(rid + j, "g",
                                                   np.arange(10, 22,
                                                             dtype=np.int32),
                                                   4, group_id=0)
                                           for j in range(3)]))
    rid += 3
    eng.submit(_req(rid, plen=30, max_new=4, session_id=0))
    first_turn = rid
    rid += 1
    steps, submitted_turn2 = 0, False
    while not eng.idle or not submitted_turn2:
        eng.step()
        steps += 1
        assert steps < 5000, "mixed queue deadlocked"
        for r in eng.drain_completed():
            if r.request_id == first_turn and not submitted_turn2:
                eng.submit(_req(rid, plen=40, max_new=4, session_id=0))
                submitted_turn2 = True
    done = eng.drain_completed()
    eng.close_session(0)
    st = eng.stats
    assert st.chunked_admissions > 0
    assert st.group_fork_requests == 3
    for r in done:
        assert r.finished and r.finish_reason in ("length", "eos", "overflow")
    assert st.kv_blocks_in_use == 0
    eng.assert_kv_consistent()


def test_cancel_all_phases(setup):
    """Cancel must release every resource on all three paths: queued
    (never admitted), mid-chunk (partial prompt written), and actively
    decoding — finish_reason 'cancelled', zero blocks leaked."""
    cfg, params = setup
    eng = InferenceEngine(params, cfg, num_slots=1, max_seq=128, seed=2,
                          chunk_prefill=8)
    # queued: one slot, second request never admitted
    eng.submit(_req(0, plen=4, max_new=6))
    eng.submit(_req(1, plen=4, max_new=6))
    eng.step()
    assert eng.cancel(1)
    # mid-chunk: long prompt starts chunking once the slot frees
    eng.submit(_req(2, plen=60, max_new=6))
    while 2 not in {cs.req.request_id for cs in eng._chunking.values()}:
        eng.step()
    assert eng.cancel(2)
    assert not eng._chunking
    # actively decoding
    req3 = _req(3, plen=4, max_new=20)
    eng.submit(req3)
    while not req3.completion:
        eng.step()
    assert eng.cancel(3)
    assert not eng.cancel(99)          # unknown id
    done = {r.request_id: r for r in eng.drain_completed()}
    while not eng.idle:
        eng.step()
    done.update({r.request_id: r for r in eng.drain_completed()})
    assert done[0].finish_reason in ("length", "eos")
    for rid in (1, 2, 3):
        assert done[rid].finish_reason == "cancelled", rid
    assert eng.stats.cancelled == 3
    assert eng.stats.kv_blocks_in_use == 0
    eng.assert_kv_consistent()


# ------------------------------------------------------- stats and gating


def test_latency_accounting_and_windows(setup):
    """Per-request TTFT/ITL stamps feed the engine windows; snapshot()
    reports percentiles, reset_window() starts a fresh window, and the
    pool aggregates across engines."""
    cfg, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2, max_seq=64, seed=1)
    pool = InferencePool([eng])
    reqs = [pool.submit_request(np.arange(10, 16, dtype=np.int32),
                                max_new_tokens=4, temperature=0.0,
                                problem_id=f"p{i}") for i in range(4)]
    while not pool.idle:
        pool.step()
    pool.drain_requests()
    for r in reqs:
        assert r.token_ts[0] >= r.submit_ts > 0.0
        assert len(r.token_ts) == len(r.completion)
    snap = eng.stats.snapshot()
    assert snap["ttft_n"] == 4 and snap["itl_n"] > 0
    assert snap["ttft_p99"] >= snap["ttft_p50"] > 0.0
    assert pool.stats()["latency"]["ttft_n"] == 4
    pool.reset_latency_windows()
    assert eng.stats.snapshot()["ttft_n"] == 0


def test_chunkability_gate_per_layout(setup):
    """The layout gate: attention and recurrent layouts chunk; ring
    caches, encoder-decoder cross-KV and meta-token prefixes do not —
    and a gated engine silently falls back to monolithic prefill."""
    cfg, _ = setup
    assert CacheLayout.from_config(cfg, 64).supports_chunked_prefill
    assert CacheLayout.from_config(
        get_config("mamba2-370m:reduced"), 64).supports_chunked_prefill
    ring_cfg = cfg.with_sliding_window(256)
    assert CacheLayout.from_config(ring_cfg, 64).ring
    assert not CacheLayout.from_config(ring_cfg, 64).supports_chunked_prefill
    assert not CacheLayout.from_config(
        get_config("whisper-large-v3:reduced"), 64).supports_chunked_prefill
    assert not CacheLayout.from_config(
        get_config("hymba-1.5b:reduced"), 64).supports_chunked_prefill


def test_ring_layout_falls_back_to_monolithic(setup):
    """chunk_prefill on an unchunkable (ring) layout is ignored: the
    engine admits monolithically and still completes everything."""
    cfg, _ = setup
    ring_cfg = dataclasses.replace(cfg.with_sliding_window(256))
    params = init_params(jax.random.PRNGKey(0), ring_cfg, dtype=jnp.float32)
    eng = InferenceEngine(params, ring_cfg, num_slots=2, max_seq=64,
                          seed=0, chunk_prefill=8)
    assert not eng._chunk_enabled
    for i in range(3):
        eng.submit(_req(i, plen=20 + 7 * i, max_new=4))
    done = _drain(eng)
    assert len(done) == 3
    assert eng.stats.chunked_admissions == 0
