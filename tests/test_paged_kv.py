"""Paged KV cache: the refcounting block allocator + engine integration.

The block pool replaces the dense per-slot cache as the engine's KV
memory: admission claims ``ceil(tokens/block_size)`` blocks, group forks
*share* the prompt's full blocks copy-on-write, parked sessions hold only
the blocks they filled, and every early-exit path (finish, overflow,
eviction, ``close_session``) must return its references. Stream parity
with the unpaged ``HostReferenceEngine`` is covered by the existing
engine/session/group suites (which now run the fused engine paged); this
file tests the allocator semantics themselves.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import TOKENIZER
from repro.inference import GroupRequest, InferenceEngine, Request
from repro.inference.engine import BlockAllocator
from repro.models import (init_paged_state, init_params, paged_sample_step,
                          prefill, sample_step)
from tests.utils import given, settings, st

BS = 8  # block size used throughout (divides every max_seq below)


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_config("minitron-4b:reduced"),
                              vocab_size=TOKENIZER.vocab_size, num_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


# allocator-integration tests run for every layout with a pageable
# attention_kv kind: dense attention AND hybrid (whose SSM state stays in
# pooled per-slot rows while its attention K/V pages through the pool).
# Pure-SSM layouts have no pageable kind (covered by the gating test
# below). hymba's reduced sliding window is 64, so these use max_seq=128
# to stay on the non-ring layout; its meta-token prefix occupies
# ``cfg.num_meta_tokens`` leading cache entries, which the block math
# accounts for via ``_cache_len``.
PAGEABLE_FAMILIES = ["minitron-4b:reduced", "hymba-1.5b:reduced"]


@pytest.fixture(scope="module", params=PAGEABLE_FAMILIES)
def fam_setup(request):
    cfg = dataclasses.replace(get_config(request.param),
                              vocab_size=TOKENIZER.vocab_size, num_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _cache_len(cfg, plen):
    """Cache entries a prompt occupies: meta-token prefix + prompt."""
    return cfg.num_meta_tokens + plen


def _req(i, prompt, max_new=4, sid=None, temp=1.0):
    return Request(request_id=i, problem_id=f"p{i}",
                   prompt_tokens=np.asarray(prompt, np.int32),
                   max_new_tokens=max_new, session_id=sid, temperature=temp)


def _prompt(n, seed=0):
    return ((np.arange(n, dtype=np.int32) * (seed + 3)) % 50) + 10


# ---------------------------------------------------------------- allocator


def test_allocator_refcounts():
    a = BlockAllocator(8)
    ids = a.alloc(3)
    assert ids is not None and a.in_use == 3
    a.incref(ids)                      # shared by a second owner
    assert a.free(ids) == 0            # first owner drops: nothing freed
    assert a.in_use == 3
    assert a.free(ids) == 3            # last owner drops: all freed
    assert a.in_use == 0 and a.free_blocks == 8
    assert a.alloc(9) is None          # all-or-nothing
    assert a.peak == 3


def test_allocator_double_free_asserts():
    a = BlockAllocator(4)
    ids = a.alloc(2)
    a.free(ids)
    with pytest.raises(AssertionError):
        a.free(ids)


# ------------------------------------------------------- COW fork + diverge


def test_cow_fork_shares_prompt_blocks_then_diverges(fam_setup):
    """A group fork must leave the prompt's full blocks shared (refcount =
    G) with one private tail block per member — and the members' decode
    writes must never corrupt the shared prefix: every member stream must
    match the per-member-admission baseline. For hybrids the fork also
    copies each member's pooled SSM state row; only attention K/V shares
    copy-on-write."""
    cfg, params = fam_setup
    plen = 20                       # prefix + 20 leaves a partial tail block
    G = 4
    prompt = _prompt(plen)
    full = _cache_len(cfg, plen) // BS
    assert _cache_len(cfg, plen) % BS, "test needs a partial tail block"

    def run(use_group):
        eng = InferenceEngine(params, cfg, num_slots=G, max_seq=128, seed=7,
                              kv_block_size=BS)
        members = [_req(i, prompt, max_new=6) for i in range(G)]
        if use_group:
            eng.submit_group(GroupRequest(0, "p0", prompt, members=members))
            eng._admit()                        # fork, don't decode yet
            shared_refs = [eng.allocator.refcount(b)
                           for b in eng._slot_blocks[0][:full]]
            tail_refs = [eng.allocator.refcount(eng._slot_blocks[s][-1])
                         for s in range(G)]
            assert shared_refs == [G] * full
            assert tail_refs == [1] * G
            # unique in-use blocks: shared fulls once + G private tails
            assert eng.allocator.in_use == full + G
        else:
            for r in members:
                eng.submit(r)
        eng.run_until_idle()
        done = {r.request_id: r for r in eng.drain_completed()}
        return [(tuple(done[i].completion), tuple(done[i].logprobs))
                for i in sorted(done)], eng

    forked, eng_f = run(True)
    baseline, _ = run(False)
    for (fc, fl), (bc, bl) in zip(forked, baseline):
        assert fc == bc                        # tokens always exact
        if cfg.ssm is None:
            assert fl == bl                    # attention: bitwise
        else:                                  # recurrent: reassociation
            np.testing.assert_allclose(fl, bl, rtol=2e-4, atol=2e-4)
    assert len({c for c, _ in forked}) > 1, "members should diverge"
    assert eng_f.stats.cow_forks == G          # one private tail per member
    assert eng_f.allocator.in_use == 0         # everything reclaimed


def test_cow_fork_block_aligned_prompt_shares_everything(setup):
    """Prompt length a multiple of block_size: no tail to privatize at
    fork time — the first decode write crosses into a fresh block each
    member allocates on demand."""
    cfg, params = setup
    G, plen = 3, 16                             # exactly 2 blocks
    eng = InferenceEngine(params, cfg, num_slots=4, max_seq=64, seed=3,
                          kv_block_size=BS)
    eng.submit_group(GroupRequest(0, "p0", _prompt(plen),
                                  members=[_req(i, _prompt(plen), max_new=3)
                                           for i in range(G)]))
    eng._admit()
    assert eng.stats.cow_forks == 0
    assert eng.allocator.in_use == plen // BS   # all shared, zero copies
    eng.run_until_idle()
    assert eng.allocator.in_use == 0


# -------------------------------------------------- refcount drop on finish


def test_refcount_drops_as_members_finish(fam_setup):
    """Members finishing at different times must decref the shared blocks
    one by one; the blocks free only when the LAST member drops them."""
    cfg, params = fam_setup
    G, plen = 3, 20
    eng = InferenceEngine(params, cfg, num_slots=G, max_seq=128, seed=1,
                          kv_block_size=BS)
    members = [_req(i, _prompt(plen), max_new=2 + 4 * i) for i in range(G)]
    eng.submit_group(GroupRequest(0, "p0", _prompt(plen), members=members))
    eng._admit()
    shared = list(eng._slot_blocks[0][:_cache_len(cfg, plen) // BS])
    assert all(eng.allocator.refcount(b) == G for b in shared)
    seen_refs = set()
    while not eng.idle:
        eng.step()
        seen_refs.add(tuple(eng.allocator.refcount(b) for b in shared))
    # refcounts stepped down as each member finished, and ended at zero
    assert any(r and max(r) < G for r in seen_refs)
    assert all(eng.allocator.refcount(b) == 0 for b in shared)
    assert eng.allocator.in_use == 0


# ------------------------------------------------- exhaustion backpressure


def test_allocator_exhaustion_backpressure(setup):
    """With slots for everyone but blocks for one request at a time, the
    queue must WAIT (decode drains the pool) rather than crash — and all
    requests must still complete."""
    cfg, params = setup
    # 5 blocks of 8 = 40 token capacity; each request needs 4 blocks
    eng = InferenceEngine(params, cfg, num_slots=4, max_seq=64, seed=5,
                          kv_block_size=BS, num_kv_blocks=5)
    for i in range(3):
        eng.submit(_req(i, _prompt(28, seed=i), max_new=4))
    eng.run_until_idle()
    done = eng.drain_completed()
    assert len(done) == 3
    assert all(r.finish_reason in ("eos", "length") for r in done)
    # never more than one resident request's worth of blocks
    assert eng.stats.kv_blocks_peak <= 5
    assert eng.allocator.in_use == 0
    # occupancy never exceeded what the pool could hold (1 request)
    assert max(eng.stats.occupancy_trace) == 1


def test_pool_impossible_prompt_overflows_gracefully(setup):
    """A prompt needing more blocks than the whole pool can never be
    admitted — it must finish as an overflow instead of deadlocking the
    queue behind it."""
    cfg, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2, max_seq=64, seed=2,
                          kv_block_size=BS, num_kv_blocks=3)
    eng.submit(_req(0, _prompt(30), max_new=4))       # needs 4 of 3 blocks
    eng.submit(_req(1, _prompt(10, seed=1), max_new=3))
    eng.run_until_idle()
    done = {r.request_id: r for r in eng.drain_completed()}
    assert done[0].finish_reason == "overflow" and not done[0].completion
    assert done[1].finish_reason in ("eos", "length")
    assert eng.allocator.in_use == 0


def test_decode_growth_exhaustion_finishes_overflow(setup):
    """A request whose decode growth exhausts the pool mid-stream (nothing
    parked left to evict) finishes gracefully with reason "overflow" and
    returns every block."""
    cfg, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2, max_seq=64, seed=4,
                          kv_block_size=BS, num_kv_blocks=2)
    # prompt fills block 0 partially; generation must cross into block 2
    # eventually -> allocator runs dry at the third block
    eng.submit(_req(0, _prompt(6), max_new=30))
    eng.run_until_idle()
    (r,) = eng.drain_completed()
    assert r.finish_reason in ("overflow", "eos")
    if r.finish_reason == "overflow":
        assert len(r.completion) >= 1             # banked what it generated
    assert eng.allocator.in_use == 0


# -------------------------------------------------- eviction / reclamation


def test_eviction_frees_exactly_the_parked_sessions_blocks(fam_setup):
    """LRU-evicting a parked session must return precisely the blocks that
    session filled — no more (other parked sessions keep theirs), no
    fewer (leak). Hybrid parked sessions additionally hold a pooled state
    row, which eviction releases with the slot."""
    cfg, params = fam_setup
    eng = InferenceEngine(params, cfg, num_slots=2, max_seq=128, seed=9,
                          kv_block_size=BS)
    for sid, plen in ((0, 12), (1, 20)):
        eng.open_session(sid)
        eng.submit(_req(sid, _prompt(plen, seed=sid), max_new=3, sid=sid))
        eng.run_until_idle()
    eng.drain_completed()
    held = {sid: len(eng._slot_blocks[eng.sessions[sid].slot])
            for sid in (0, 1)}
    in_use_before = eng.allocator.in_use
    assert in_use_before == sum(held.values())
    # two fresh prompts need both slots -> both sessions evict (LRU first)
    before_evicted = eng.stats.blocks_freed_on_evict
    eng.submit(_req(100, _prompt(10, seed=3), max_new=3))
    eng.step()
    assert eng.stats.session_evictions == 1
    assert eng.stats.blocks_freed_on_evict - before_evicted == held[0]
    assert eng.sessions[0].slot is None and eng.sessions[1].slot is not None
    eng.run_until_idle()
    eng.close_session(0)
    eng.close_session(1)
    assert eng.allocator.in_use == 0


def test_close_session_returns_parked_blocks(fam_setup):
    cfg, params = fam_setup
    eng = InferenceEngine(params, cfg, num_slots=2, max_seq=128, seed=8,
                          kv_block_size=BS)
    eng.open_session(0)
    eng.submit(_req(0, _prompt(12), max_new=3, sid=0))
    eng.run_until_idle()
    eng.drain_completed()
    assert eng.allocator.in_use > 0               # parked residency
    assert eng.stats.parked_state_bytes == (eng._state_row_bytes
                                            if cfg.ssm is not None else 0)
    eng.close_session(0)
    assert eng.allocator.in_use == 0


def test_parked_session_capacity_exceeds_slot_count(fam_setup):
    """The capacity win: with the pool sized to the dense budget of
    ``num_slots`` rows, short parked sessions are bounded by *blocks*,
    not rows — more sessions than a dense engine could keep resident can
    park simultaneously, and their second turns all extend (no
    fallbacks). Hybrids page only their attention K/V; the SSM state rows
    are O(1)-sized and don't grow the per-session block footprint."""
    cfg, params = fam_setup
    eng = InferenceEngine(params, cfg, num_slots=8, max_seq=128, seed=6,
                          kv_block_size=BS)
    n_sessions = 8
    for sid in range(n_sessions):
        eng.open_session(sid)
        eng.submit(_req(sid, _prompt(9, seed=sid), max_new=3, sid=sid))
    eng.run_until_idle()
    eng.drain_completed()
    parked = sum(1 for s in eng.sessions.values() if s.slot is not None)
    assert parked == n_sessions
    # dense residency cost would be n_sessions * max_seq tokens; paged
    # residency is only the filled blocks (prefix + prompt + decode)
    per = -(-_cache_len(cfg, 9 + 3) // BS) + 1
    assert eng.allocator.in_use <= n_sessions * per
    assert eng.allocator.in_use * BS * 2 <= n_sessions * 128
    for sid in range(n_sessions):
        eng.submit(_req(100 + sid, _prompt(5, seed=sid + 1), max_new=3,
                        sid=sid))
    eng.run_until_idle()
    assert eng.stats.extend_requests == n_sessions   # all turns extended
    assert eng.stats.session_fallbacks == 0
    for sid in range(n_sessions):
        eng.close_session(sid)
    assert eng.allocator.in_use == 0


def test_decode_to_cache_edge_overflows_in_parity(setup):
    """Regression: a request whose generation reaches ``max_seq`` must
    overflow-finish BEFORE the write would clamp — identically on the
    paged engine and the dense reference (the two clamp targets differ,
    so letting the write happen silently corrupts the cache AND breaks
    stream parity)."""
    from repro.inference import HostReferenceEngine
    cfg, params = setup

    def run(cls):
        eng = cls(params, cfg, num_slots=2, max_seq=32, seed=21,
                  kv_block_size=BS)
        eng.submit(_req(0, _prompt(28), max_new=10))
        eng.submit(_req(1, _prompt(5, seed=2), max_new=4))
        eng.run_until_idle()
        done = {r.request_id: r for r in eng.drain_completed()}
        return [(i, tuple(done[i].completion), tuple(done[i].logprobs),
                 done[i].finish_reason) for i in sorted(done)]

    paged = run(InferenceEngine)
    ref = run(HostReferenceEngine)
    assert paged == ref
    # prefill token + 4 decode writes (pos 28..31), then the row is full
    assert paged[0][3] == "overflow" and len(paged[0][1]) == 5


def test_group_overflow_and_unpaged_family_gating(setup):
    """Overflowing group prompts allocate nothing; a pure-SSM layout has
    no pageable layer kind, so ``CacheLayout`` resolves it unpaged (no
    allocator) and it still drains cleanly."""
    cfg, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2, max_seq=32, seed=0,
                          kv_block_size=BS)
    eng.submit_group(GroupRequest(0, "p0", _prompt(40),
                                  members=[_req(i, _prompt(40))
                                           for i in range(2)]))
    eng.run_until_idle()
    done = eng.drain_completed()
    assert [r.finish_reason for r in done] == ["overflow", "overflow"]
    assert eng.allocator.in_use == 0

    ssm_cfg = dataclasses.replace(get_config("mamba2-370m:reduced"),
                                  vocab_size=TOKENIZER.vocab_size,
                                  num_layers=2)
    ssm_params = init_params(jax.random.PRNGKey(0), ssm_cfg,
                             dtype=jnp.float32)
    ssm_eng = InferenceEngine(ssm_params, ssm_cfg, num_slots=2, max_seq=32,
                              seed=0)
    assert not ssm_eng.paged and ssm_eng.allocator is None
    ssm_eng.submit(_req(0, _prompt(6), max_new=3))
    ssm_eng.run_until_idle()
    assert len(ssm_eng.drain_completed()) == 1


# ------------------------------------------- speculative claim-then-release


def _allocator_snapshot(a):
    """The observable allocator state a rolled-back claim must restore:
    the free-list SET (claim/release may reorder the list — the ids are
    interchangeable), every block's refcount, and the in-use count."""
    return (frozenset(a._free), tuple(int(a._ref[b])
                                      for b in range(a.num_blocks)), a.in_use)


@settings(max_examples=10, deadline=None)
@given(ops=st.lists(st.sampled_from(
    [(k, j) for k in range(1, 6) for j in range(k + 1)]),
    min_size=1, max_size=12),
    shared=st.integers(0, 3))
def test_allocator_spec_claim_release_property(ops, shared):
    """Property: a speculative round claims the worst case (1 + k blocks)
    up front and releases the rejected tail (j blocks) after verification.
    Any interleaving of such rounds — on a pool that also holds COW-shared
    blocks — must keep refcounts exact, never double-free, and a full
    release must restore the allocator to its pre-claim state (free-list
    set + refcounts + in_use)."""
    a = BlockAllocator(16)
    base = a.alloc(shared)          # long-lived blocks, shared once (COW)
    if base:
        a.incref(base)
    committed = []
    for k, j in ops:
        before = _allocator_snapshot(a)
        ids = a.alloc(k)
        if ids is None:             # backpressure must leave state intact
            assert k > a.free_blocks
            assert _allocator_snapshot(a) == before
            continue
        assert all(a.refcount(b) == 1 for b in ids)
        a.free(ids[k - j:])         # reject the tail: j blocks roll back
        del ids[k - j:]
        if not ids:                 # fully-rejected round: exact restore
            assert _allocator_snapshot(a) == before
        committed.append(ids)
    # teardown: every committed prefix and both shared refs must drain
    for ids in committed:
        a.free(ids)
    if base:
        assert a.free(base) == 0 and a.free(base) == shared
    assert a.in_use == 0 and a.free_blocks == a.num_blocks
    assert all(a.refcount(b) == 0 for b in range(a.num_blocks))
    with pytest.raises(AssertionError):     # rolled-back ids are dead
        a.free([0])


@settings(max_examples=4, deadline=None)
@given(plen=st.integers(6, 18), max_new=st.integers(4, 14),
       draft=st.sampled_from([2, 4, 7]))
def test_spec_workload_never_leaks_blocks(setup, plen, max_new, draft):
    """Engine-level leak gate: randomized speculative workloads (looping
    prompts -> high draft acceptance, varying rollback lengths) must end
    every drain with zero blocks in use — ``run_until_idle`` asserts pool
    consistency on every idle transition."""
    cfg, params = setup
    eng = InferenceEngine(params, cfg, num_slots=3, max_seq=128, seed=13,
                          kv_block_size=BS, spec_draft=draft)
    assert eng._spec_enabled
    for i in range(5):
        prompt = np.tile(_prompt(4, seed=i), 6)[:plen]   # n-gram loops
        # greedy: random-init argmax streams repeat heavily, so the
        # drafter reliably finds matches (temp-1.0 draws over a 50k
        # vocab rarely repeat a token, leaving nothing to draft)
        eng.submit(_req(i, prompt, max_new=max_new + i % 3, temp=0.0))
    eng.run_until_idle()
    done = eng.drain_completed()
    assert len(done) == 5
    assert eng.stats.spec_rounds > 0, "workload must actually speculate"
    assert eng.allocator.in_use == 0
    assert eng.stats.kv_blocks_in_use == 0


# ------------------------------------------------ model-level paged decode


@pytest.mark.parametrize("arch", PAGEABLE_FAMILIES + ["qwen2-moe-a2.7b:reduced"])
def test_paged_tick_matches_dense_rows_and_spares_unowned_blocks(arch):
    """``paged_sample_step`` carries the stacked pool through its layer
    scan and writes each layer's rows in place. Over a shuffled block
    table with one inactive row, a few ticks must give the active rows
    the tokens and logprobs of ``sample_step`` on the equivalent dense
    rows, leave their blocks holding the dense rows' K/V, and leave every
    block that no active row owns — the inactive row's and the spares —
    bit-for-bit unchanged: the masked write drops, it never lands."""
    cfg = dataclasses.replace(get_config(arch), num_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    B, max_seq, ticks = 4, 128, 3
    mb = max_seq // BS
    lens = np.array([5, 17, 9, 30], np.int32)
    toks = np.zeros((B, int(lens.max())), np.int32)
    for b, n in enumerate(lens):
        toks[b, :n] = _prompt(int(n), seed=b)
    _, dense = prefill(params, {"tokens": jnp.asarray(toks),
                                "prompt_lens": jnp.asarray(lens)},
                       cfg, max_seq=max_seq)
    active = jnp.asarray([True, True, False, True])
    temps = jnp.asarray([1.0, 0.0, 1.0, 1.0], jnp.float32)

    nb = B * mb + 5                        # five spare blocks nobody owns
    rs = np.random.RandomState(0)
    table = rs.permutation(nb)[:B * mb].reshape(B, mb).astype(np.int32)
    paged = init_paged_state(cfg, B, nb, BS, mb, dtype=jnp.float32)
    paged["pos"] = dense["pos"]
    paged["block_tables"] = jnp.asarray(table)
    for key in ("k", "v"):
        # garbage everywhere, then each row's dense K/V in its blocks
        pool = rs.standard_normal(paged[key].shape).astype(np.float32)
        rows = np.asarray(dense[key])
        L, _, _, H, hd = rows.shape
        pool[:, table] = rows.reshape(L, B, mb, BS, H, hd)
        paged[key] = jnp.asarray(pool)
    for key in dense:
        if key not in ("pos", "k", "v"):
            paged[key] = dense[key]
    owned = np.zeros(nb, bool)
    owned[table[np.asarray(active)]] = True
    before = {key: np.asarray(paged[key]) for key in ("k", "v")}

    dense_step = jax.jit(lambda s, t, r: sample_step(
        params, s, t, temps, r, cfg, active=active))
    paged_step = jax.jit(lambda s, t, r: paged_sample_step(
        params, s, t, active, temps, r, cfg))
    token = jnp.asarray(toks[np.arange(B), lens - 1])
    rng_d = rng_p = jax.random.PRNGKey(3)
    act = np.asarray(active)
    for _ in range(ticks):
        t_d, lp_d, dense, rng_d = dense_step(dense, token, rng_d)
        t_p, lp_p, paged, rng_p = paged_step(paged, token, rng_p)
        np.testing.assert_array_equal(np.asarray(t_p)[act],
                                      np.asarray(t_d)[act])
        np.testing.assert_array_equal(np.asarray(lp_p)[act],
                                      np.asarray(lp_d)[act])
        token = jnp.where(active, t_d, token)
    np.testing.assert_array_equal(np.asarray(paged["pos"]),
                                  np.asarray(dense["pos"]))
    end = np.asarray(dense["pos"])
    for key in ("k", "v"):
        pool = np.asarray(paged[key])
        np.testing.assert_array_equal(pool[:, ~owned], before[key][:, ~owned])
        rows = pool[:, table].reshape(np.asarray(dense[key]).shape)
        for b in np.flatnonzero(act):
            np.testing.assert_array_equal(rows[:, b, :end[b]],
                                          np.asarray(dense[key])[:, b, :end[b]])
    for key in dense:
        if key.startswith("ssm"):          # per-row caches stay scanned
            np.testing.assert_array_equal(np.asarray(paged[key]),
                                          np.asarray(dense[key]))
