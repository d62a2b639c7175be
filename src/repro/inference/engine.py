"""Continuous-batching inference engine with in-flight weight updates (§2.1.3).

The engine is the JAX analogue of one vLLM server in the paper's pool:

  * a fixed number of decode *slots* (static shapes — the TPU formulation of
    continuous batching). Each decode tick advances every occupied slot by
    one token via a single jitted dispatch.
  * whenever a slot finishes (EOS / max tokens) it is released and immediately
    refilled from the pending queue — the pool stays saturated, no
    synchronous batch boundary (Fig. 4).
  * ``update_weights`` swaps the policy **between** decode ticks; running
    requests keep their KV cache and continue under the new policy, so one
    trajectory may span multiple policies. Every generated token is stamped
    with the policy version that produced it; the stamp flows into the
    max_off_policy_steps filter and the Fig. 4 trace.

Device-resident hot path
------------------------
One decode tick is a *single* fused device dispatch (``sample_step``):
temperature-scaled categorical sampling, logprob gather, and EOS/max-token
finished-flag tracking all run inside the jit. Per-slot temperature, active
mask, generated-token counts and the RNG key live on device; the host reads
back one small ``(tokens, logprobs, finished)`` bundle per tick instead of
N Python scalars.

Admission is *bucketed batched prefill*: pending prompts are right-padded to
power-of-two length buckets and prefilled up to ``num_slots`` at a time in
one jitted call (``prefill_sample``), then scattered into the slot state in
one more jitted call — so admission compiles O(num_length_buckets ×
num_row_buckets) traces total instead of one trace per unique prompt
length. Recurrent-state (SSM/hybrid) rows bucket identically: the model's
pad-masked scan (``models.ssm.ssm_apply`` with ``seq_lens``) passes the
state through pad tokens exactly, so right padding is sound for every
family.

Cache layout (per-layer-kind state composition)
-----------------------------------------------
What the decode state looks like — and what the engine may do with it —
is declared per layer kind by ``cache_layout.CacheLayout``: linear
attention K/V is pageable through the block pool; a window-sized ring
cache is not (and cannot park); recurrent SSM state is a tiny fixed-size
per-slot *state row* (fork = copy one row, park = keep the row — never a
pinned ``max_seq`` dense cache); cross-attention K/V is a fixed-length
dense row. The engine composes these per config instead of branching on
the family: a hybrid pages its attention layers through the shared
``BlockAllocator`` while its SSM state rides the per-slot state rows
through the same gather/scatter/fork dispatches.

Engine sessions (multi-turn KV reuse)
-------------------------------------
Agentic multi-turn rollouts (§2.2.1) would otherwise re-prefill the whole
conversation every turn — O(T·context) prefill FLOPs for a T-turn tool-use
trajectory. A *session* keeps the conversation's slot and device-resident
KV cache alive across turns: when a turn finishes, the slot *parks*
(inactive but not freed); the next turn submits only the **new** tokens
(tool result + turn delimiters), which are admitted through a bucketed
``extend`` prefill that writes into the existing cache at the session's
current position and resumes decoding. One conversation = one cache.

Parked sessions are reclaimable: when fresh prompts need slots, the
least-recently-used parked session is evicted — it keeps its token
history host-side, and its next turn transparently falls back to a full
re-prefill (the pre-session behaviour). Prompts or turns that would grow
past ``max_seq`` finish gracefully with ``finish_reason="overflow"``
instead of crashing the pump loop.

Group-shared prefill (GRPO groups)
----------------------------------
Group-based RL samples ``group_size`` (G) rollouts of the *same* prompt
per problem to form the shared-baseline advantage (§2.1) — yet admitted
independently, every member re-prefills the identical prompt, wasting
(G−1)/G of admission FLOPs on the dominant rollout path. A
``GroupRequest`` admits the whole group as a unit: the shared prompt is
prefilled ONCE as a single row through the bucketed prefill machinery,
the first token of every member is sampled from the broadcast logits
(byte-identical to a G-row batched prefill — see
``models.prefill_fork_sample``), and the resulting KV-cache row is forked
into the G member slots with one jitted broadcast→scatter (no host round
trip). Each member then decodes independently like any other slot. When
fewer than G slots are free the group is admitted *partially*: the
available slots are forked now, and the remainder re-forks (one more
1-row prefill) as slots free up — never a per-member prefill, never a
deadlock.

Paged KV cache (block pool + block tables)
------------------------------------------
For layouts with pageable attention K/V (dense, MoE, hybrid — anything
but a pure-SSM or ring cache) the dense per-slot cache is replaced by the
vLLM memory architecture: one shared K/V pool of ``num_kv_blocks`` blocks
(``kv_block_size`` tokens each) plus a per-slot block table. A
refcounting ``BlockAllocator`` makes blocks the unit of admission
(``ceil(prompt/bs)`` claimed before a slot is taken — pool-dry requests
*wait*, backpressure instead of a crash), of sharing (a group fork
increfs the prompt's full blocks into every member table copy-on-write;
only the partial tail block is materialized per member, so fork cost is
O(1) in prompt length), and of residency (a parked session holds only the
blocks it filled, so session capacity is real token usage — not
``num_slots x max_seq``). Every terminal path — finish, overflow,
eviction, ``close_session``, stale-cache release — returns its block
references, and ``run_until_idle`` asserts the pool leak-free at every
drain. Decode reads K/V through the table (``models.paged_sample_step``
-> Pallas ``kernels/paged_attention.py``, XLA gather fallback off
``use_pallas``); prefill/extend keep their dense math and convert at the
scatter/gather boundary, which keeps the streams bitwise-comparable.

Speculative decoding (self-drafting draft-and-verify)
-----------------------------------------------------
Decode is otherwise one token per fused dispatch; at small active-param
counts the tick is memory-bound and the hardware idles between one-token
readbacks. With ``spec_draft=k`` the engine adds a draft-and-verify round
before each tick: a prompt-lookup drafter scans the slot's own token
history (session history + prompt + completion so far) for the longest
n-gram match ending at the current suffix — the *earliest* occurrence,
so the continuation copied is long — and proposes up to k candidate
tokens for free (no draft model; agentic multi-turn rollouts are full of
repeated tool-output spans). Verification is ONE bucketed extend-path
dispatch over the drafted slots: each row's block is ``[t0, d1..dk]``
(the pending sampled token then the candidates, right-padded to a fixed
power-of-two bucket so verify compiles O(row-bucket) traces), and the
model samples at EVERY block offset — offset j's sample is the token the
sequential decode would have produced at position ``start+j+1``, so the
longest prefix of samples matching the drafts commits in bulk, plus the
first mismatching sample as a free bonus/correction token. Rejected
tails roll back by construction: dense rows just rewind ``pos`` (the
``k_idx <= pos`` mask hides the dead K/V), paged rows additionally drop
the tail block refs claimed for the round (claim-then-release on the
``BlockAllocator``). Families whose state cannot rewind — recurrent SSM
scan state, ring caches — gate speculation off via
``CacheLayout.supports_speculation``. The RNG discipline extends
unchanged: one split per verify dispatch, sampling on the identical
[R, S, V] block shape in the fused and host-reference paths, and the
draft/eligibility decisions are deterministic host logic — so the
byte-identical-streams contract survives speculation. (One documented
edge: under extreme pool pressure a paged engine may skip a slot's round
that the unpaged oracle runs — default pool sizing makes reservation
infallible, which is what the parity suites pin.)

Chunked prefill + SLO-aware scheduler
-------------------------------------
A monolithic long-prompt prefill dispatch stalls every decoding slot
behind it — the dominant p99 inter-token-latency failure mode under the
paper's mixed agentic traffic (long tool-output prompts interleaved with
short continuations). With ``chunk_prefill=c`` a prompt longer than ``c``
is admitted *chunked*: the slot is claimed up front, but the prompt
streams in as ``c``-token **no-sample extend chunks** across successive
``step()`` calls — a chunk is an extend with ``max_new_tokens=0`` (the
``models.extend`` S==0/pad-masked machinery), so it consumes no RNG and
discards its logits; only the FINAL chunk goes through the normal
sampling extend and consumes the admission's single RNG split. Decode
ticks run between chunks, so resident streams keep their inter-token
cadence while the long prompt trickles in. Long resident-session deltas
chunk the same way. ``CacheLayout.supports_chunked_prefill`` gates the
path: recurrent families ride the pad-masked extend; rings,
encoder-decoder cross-KV, VLM patch injection and meta-token prefixes
cannot be rebuilt positionally by extend and stay monolithic.

Scheduling is SLO-aware: every request carries a ``sched_class``
(``"interactive"`` outranks ``"rollout"``), the pending queue is a
stable two-class partition (FIFO within class — single-class traffic is
byte-identical to plain FIFO), and a rollout older than
``promote_after`` steps is promoted so interactive floods cannot starve
batch work. ``prefill_token_budget`` caps the *ride-along* tokens per
step — chunk writes first, then speculative drafts (a spec round that
commits k tokens counts k against the budget) — which bounds how much
prefill work any one tick can stall decode by. Admission control under
block-pool pressure reserves only the blocks the CURRENT chunk covers
(not the whole prompt up front); a chunked admission the pool cannot
feed waits, and a provable mutual-starvation cycle (nothing decoding,
nothing evictable, every chunking slot starved) sacrifices the youngest
chunked admission with ``finish_reason="overflow"`` instead of
deadlocking. Every chunking/scheduling decision is deterministic host
logic in this class, so ``HostReferenceEngine`` inherits it and the
byte-identical-streams contract survives chunking — and at temperature
<= 0 (greedy is RNG-schedule-invariant by the sampling contract) a
chunked run must also reproduce the unchunked run's token streams.
``EngineStats`` additionally keeps per-request latency windows (TTFT =
submit to first token, ITL = gaps between tokens) with a
``snapshot()/reset_window()`` pair for steady-state SLO measurement
(``launch/loadgen.py`` is the open-loop traffic harness that reads
them).

``HostReferenceEngine`` (repro.inference.reference) keeps the pre-fusion
host path alive as the parity oracle and Fig. 4 baseline: same scheduling
and RNG discipline, but eager host-side sampling with per-token scalar
syncs — and *unpaged* dense rows, so it also oracles the paged memory
paths. Under a fixed seed the two engines must produce identical
token/logprob/version streams — and a session-extend run must reproduce
the full-re-prefill run's streams exactly (same one-split-per-admission,
one-split-per-tick RNG discipline). The same oracle covers the group
fork (host-side row broadcast + eager scatter).
"""
from __future__ import annotations

import contextlib
import functools
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig
from repro.inference.cache_layout import CacheLayout
from repro.models import (extend, extend_sample, extend_verify_sample,
                          fork_decode_rows, init_decode_state,
                          init_paged_state, paged_gather_rows,
                          paged_sample_step, paged_write_rows,
                          prefill_fork_sample, prefill_sample, sample_step)
from repro.sharding.context import serve_mesh_context
from repro.sharding.rules import (decode_state_specs, serve_param_specs,
                                  token_spec)

DEFAULT_PCFG = ParallelConfig(remat="none", loss_chunk=0)


@dataclass
class Request:
    """One rollout request (a member of a group)."""

    request_id: int
    problem_id: str
    prompt_tokens: np.ndarray
    max_new_tokens: int
    temperature: float = 1.0
    group_id: int = 0
    # multi-turn: the engine session this turn continues. For a session's
    # first turn prompt_tokens is the full prompt; for later turns it is
    # only the *delta* (tool result + turn delimiters).
    session_id: Optional[int] = None
    # SLO scheduler class: "interactive" admits/advances ahead of
    # "rollout" batch work; an aged rollout is promoted (deadline
    # promotion) so the interactive class can never starve it out
    sched_class: str = "rollout"
    # filled during generation
    completion: List[int] = field(default_factory=list)
    logprobs: List[float] = field(default_factory=list)
    versions: List[int] = field(default_factory=list)
    finished: bool = False
    finish_reason: str = ""
    # latency accounting (engine-stamped perf_counter seconds): submit
    # time, first-token time, and one stamp per generated token
    submit_ts: float = 0.0
    first_token_ts: float = 0.0
    last_token_ts: float = 0.0
    token_ts: List[float] = field(default_factory=list)
    # engine step at submission — the deadline-promotion age reference
    submit_step: int = 0
    promoted: bool = False


@dataclass
class GroupRequest:
    """A GRPO group admitted as a unit: ``group_size`` rollouts of one
    shared prompt. The prompt is prefilled once and the KV cache forked
    to every member slot; ``members`` holds the not-yet-admitted member
    ``Request`` objects (each carrying the full prompt, so history and
    fallback accounting are per-member as usual) and is drained as slots
    become available (partial admission)."""

    group_req_id: int
    problem_id: str
    prompt_tokens: np.ndarray
    members: List[Request] = field(default_factory=list)

    @property
    def group_size(self) -> int:
        return len(self.members)


@dataclass
class EngineSession:
    """One multi-turn conversation pinned to (at most) one slot.

    Invariant while parked: the device cache row holds K/V for
    ``tokens[:-1]`` at positions ``0..len(tokens)-2`` — the final token of
    the last turn was sampled but never fed through the model, so the next
    turn's extend block re-feeds it as its first token.
    """

    session_id: int
    tokens: np.ndarray           # full conversation history (host fallback)
    slot: Optional[int] = None   # resident slot (parked or active)
    last_use: int = 0            # admission counter, LRU eviction key
    # policy version the cache prefix was (re)built under. A weight update
    # between turns leaves parked caches stale; the version check makes
    # the next turn fall back to a full re-prefill under the new policy —
    # the analogue of vLLM's reset_prefix_cache on update_weights. (A turn
    # *actively decoding* across an update keeps its cache: the PR-1
    # in-flight contract.)
    cache_version: int = -1


@dataclass
class EngineStats:
    decode_steps: int = 0
    tokens_generated: int = 0
    weight_updates: int = 0
    prefills: int = 0            # bucketed prefill calls (batches)
    prefill_requests: int = 0    # requests admitted across all batches
    prefill_traces: int = 0      # compiled (rows, bucket_len) shapes
    decode_traces: int = 0       # compiled decode-tick shapes (expect 1)
    extends: int = 0             # bucketed session-extend calls (batches)
    extend_requests: int = 0     # turns admitted via extend
    extend_traces: int = 0       # compiled (rows, bucket_len) extend shapes
    # speculative decoding (self-drafting draft-and-verify; 0 when off)
    spec_rounds: int = 0         # verify dispatches (one per spec round)
    spec_drafted_tokens: int = 0  # candidate tokens the drafter proposed
    spec_accepted_tokens: int = 0  # drafted tokens verify agreed with
    spec_rejected_tokens: int = 0  # drafted tokens verify refuted
    spec_committed_tokens: int = 0  # tokens committed by verify rounds
    spec_saved_ticks: int = 0    # decode ticks skipped (round covered all)
    spec_verify_traces: int = 0  # compiled verify shapes (O(row buckets))
    prefill_tokens: int = 0      # prompt tokens run through prefill+extend
    prefill_tokens_saved: int = 0  # cached tokens extends did NOT re-prefill
    session_evictions: int = 0   # parked sessions evicted under slot pressure
    session_fallbacks: int = 0   # evicted sessions fully re-prefilled
    overflows: int = 0           # requests finished with reason "overflow"
    group_prefills: int = 0      # group-fork dispatches (1-row prefill+fork)
    group_fork_requests: int = 0  # members admitted via a cache fork
    group_prefill_traces: int = 0  # compiled group-fork shapes
    group_partial_admissions: int = 0  # forks that admitted < the remainder
    group_prefill_tokens_saved: int = 0  # prompt tokens members did NOT re-prefill
    # paged KV-cache memory accounting (zero when the config is unpaged)
    kv_blocks_total: int = 0     # block-pool size
    kv_blocks_in_use: int = 0    # unique blocks off the free list
    kv_blocks_peak: int = 0      # high-water mark of kv_blocks_in_use
    kv_bytes: int = 0            # persistent K/V cache bytes (pool or dense)
    # per-layout memory accounting (cache_layout.CacheLayout classes)
    pageable_kv_bytes: int = 0   # K/V bytes in the shared block pool
    pooled_state_bytes: int = 0  # per-slot state-row bytes (SSM/cross), total
    parked_state_bytes: int = 0  # state-row bytes held by parked sessions
    # sharded-engine accounting (empty/equal-to-kv_bytes when unsharded)
    mesh_shape: str = ""         # "data=2,model=4" for a meshed engine
    kv_bytes_per_shard: int = 0  # K/V bytes resident per device shard
    cow_forks: int = 0           # copy-on-write private-block materializations
    blocks_freed_on_evict: int = 0  # blocks reclaimed by parked-session eviction
    # automatic prefix caching (all zero when prefix_cache=False)
    prefix_cache_hits: int = 0   # admissions that claimed >=1 cached block
    prefix_cache_misses: int = 0  # cacheable admissions with no usable prefix
    prefix_cache_hit_tokens: int = 0  # prompt tokens served from cached blocks
    prefix_cache_cached_blocks: int = 0  # gauge: retired blocks claimable now
    prefix_cache_retired: int = 0  # blocks ever retired into the cache
    prefix_cache_reclaimed: int = 0  # cached blocks recycled for fresh allocs
    prefix_cache_swept: int = 0  # stale-version mappings dropped on update
    # chunked prefill + SLO scheduler (all zero when chunk_prefill=0)
    chunked_admissions: int = 0  # requests admitted via chunked prefill
    prefill_chunks: int = 0      # no-sample chunk-write dispatches
    chunk_tokens: int = 0        # prompt tokens streamed through chunk writes
    chunk_traces: int = 0        # compiled (rows, bucket) chunk-write shapes
    sched_promotions: int = 0    # rollout -> interactive deadline promotions
    sched_budget_deferrals: int = 0  # chunk advances deferred by the budget
    cancelled: int = 0           # requests finished with reason "cancelled"
    # per-step occupancy trace for the Fig. 4 / utilization benchmark
    occupancy_trace: List[int] = field(default_factory=list)
    # latency measurement windows (seconds): TTFT = submit -> first token,
    # ITL = gap between consecutive tokens of one request. Windowed so
    # steady-state SLO measurement can drop warmup/compile samples.
    ttft_window: List[float] = field(default_factory=list)
    itl_window: List[float] = field(default_factory=list)

    def snapshot(self) -> dict:
        """p50/p99 latency summary over the current measurement window."""
        return latency_snapshot(self.ttft_window, self.itl_window)

    def reset_window(self) -> None:
        """Start a fresh measurement window (counters are untouched —
        only the TTFT/ITL sample windows clear)."""
        self.ttft_window.clear()
        self.itl_window.clear()


def _percentile(xs: List[float], q: float) -> float:
    if not xs:
        return 0.0
    return float(np.percentile(np.asarray(xs, np.float64), q))


def latency_snapshot(ttft: List[float], itl: List[float]) -> dict:
    """p50/p99 TTFT and inter-token-latency summary of raw sample windows
    (shared by ``EngineStats.snapshot`` and the pool-level aggregation)."""
    return {
        "ttft_n": len(ttft), "itl_n": len(itl),
        "ttft_p50": _percentile(ttft, 50),
        "ttft_p99": _percentile(ttft, 99),
        "itl_p50": _percentile(itl, 50),
        "itl_p99": _percentile(itl, 99),
    }


@dataclass
class _ChunkedPrefill:
    """An in-flight chunked admission: one claimed slot streaming its
    prompt in through no-sample extend chunks across successive steps.
    While chunking, ``slots[slot]`` stays None — the decode tick, the
    overflow guards and fresh admission all ignore the slot — and the
    engine's ``_chunking`` map is the residency truth (free-slot scans,
    eviction, ``idle`` and the KV leak gate all consult it)."""

    req: Request
    tokens: np.ndarray       # full block to stream: prompt, or [last]+delta
    base: int                # cache position tokens[0] writes at
    written: int = 0         # tokens of the block already in the cache
    resident: bool = False   # continues a resident session (extend-style)
    submit_step: int = 0     # scheduler age / FIFO key
    start_version: int = 0   # policy version when the admission began


def _pow2_bucket(n: int, floor: int = 1) -> int:
    """Smallest power-of-two >= n (and >= floor)."""
    b = max(int(floor), 1)
    while b < n:
        b <<= 1
    return b


class BlockAllocator:
    """Refcounting free-list allocator over the engine's KV block pool.

    Blocks are the unit of both residency and sharing: a group fork
    increfs the shared prompt's full blocks into every member's table
    (copy-on-write), and a block returns to the free list only when its
    last reference drops (finish, eviction, ``close_session``, overflow).
    ``in_use`` counts *unique* blocks off the free list — the truth the
    engine's KV stats and teardown leak assertions are written against.

    Automatic prefix caching rides on top: a full block may be
    *published* under a content-address node (an interned chained hash of
    ``(parent node, block token ids, weights version)`` — interning makes
    the chain collision-free by construction, strictly stronger than a
    real hash). When a published block's last reference drops it is
    *retired* into an LRU of zero-refcount-but-cached blocks instead of
    returning to the free list; ``alloc`` reclaims from the LRU's oldest
    end once the free list runs dry (unpublishing the victim — a
    reclaimed block is never served as a hit again). Cache capacity is
    therefore exactly the pool's idle space, and the leak invariant
    extends to ``in_use + cached + free == total``."""

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, -1, -1))   # pop() -> low ids
        self._ref = np.zeros((num_blocks,), np.int32)
        self.in_use = 0
        self.peak = 0
        # -- prefix-cache state (inert until publish() is ever called) --
        # interned chain nodes: (parent_node, token_tuple, version) -> id
        self._node_ids: Dict[tuple, int] = {}
        self._node_version: Dict[int, int] = {}
        self._node_block: Dict[int, int] = {}     # node -> published block
        self._block_node: Dict[int, int] = {}     # published block -> node
        # zero-refcount published blocks, insertion order = retire order
        # (oldest first — the reclaim end); block -> node
        self._retired: "OrderedDict[int, int]" = OrderedDict()
        self.retired_total = 0      # blocks ever retired into the cache
        self.reclaimed_total = 0    # cached blocks recycled by alloc()

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def cached(self) -> int:
        """Zero-refcount blocks held in the prefix cache (claimable)."""
        return len(self._retired)

    def alloc(self, n: int) -> Optional[List[int]]:
        """All-or-nothing allocation; ``None`` means backpressure (the
        caller leaves its request queued and retries after frees). The
        free list is preferred; once dry, cached (retired) blocks are
        reclaimed oldest-retired-first and unpublished."""
        if n > len(self._free) + len(self._retired):
            return None
        ids = []
        for _ in range(n):
            if self._free:
                b = self._free.pop()
            else:
                b, node = self._retired.popitem(last=False)  # oldest
                del self._block_node[b]
                del self._node_block[node]
                self.reclaimed_total += 1
            ids.append(b)
            self._ref[b] = 1
        self.in_use += n
        self.peak = max(self.peak, self.in_use)
        return ids

    def incref(self, ids) -> None:
        for b in ids:
            assert self._ref[b] > 0, f"incref of free block {b}"
            self._ref[b] += 1

    def refcount(self, block: int) -> int:
        return int(self._ref[block])

    def free(self, ids) -> int:
        """Drop one reference per id; returns how many blocks dropped to
        refcount zero (left ``in_use``). A published block *retires* into
        the prefix cache instead of rejoining the free list — eviction,
        finish and close_session all retire rather than discard."""
        freed = 0
        for b in ids:
            assert self._ref[b] > 0, f"double free of block {b}"
            self._ref[b] -= 1
            if self._ref[b] == 0:
                if b in self._block_node:
                    self._retired[b] = self._block_node[b]
                    self.retired_total += 1
                else:
                    self._free.append(b)
                freed += 1
        self.in_use -= freed
        return freed

    # ------------------------------------------------- prefix-cache ops

    def intern_node(self, parent: int, tokens: tuple, version: int) -> int:
        """Content-address one full block: the collision-free realization
        of the chained hash ``(parent_hash, block_token_ids,
        weights_version)``. ``parent=-1`` roots a chain."""
        key = (parent, tokens, version)
        node = self._node_ids.get(key)
        if node is None:
            node = len(self._node_ids)
            self._node_ids[key] = node
            self._node_version[node] = version
        return node

    def lookup(self, node: int) -> Optional[int]:
        """Block currently published under ``node`` (live or retired)."""
        return self._node_block.get(node)

    def claim(self, node: int) -> Optional[int]:
        """Claim the block published under ``node`` as a prefix-cache
        hit: a retired block revives (refcount 0 -> 1, back in use), a
        live one gains a reference. None on miss."""
        b = self._node_block.get(node)
        if b is None:
            return None
        if b in self._retired:
            del self._retired[b]
            self._ref[b] = 1
            self.in_use += 1
            self.peak = max(self.peak, self.in_use)
        else:
            self._ref[b] += 1
        return b

    def publish(self, block: int, node: int) -> bool:
        """Publish a full in-use block under its chain node. First
        publisher wins: a concurrent duplicate (two requests prefilled
        the same content before either published) keeps the existing
        mapping and the duplicate block stays anonymous — it frees
        normally instead of retiring."""
        assert self._ref[block] > 0, f"publish of free block {block}"
        if node in self._node_block or block in self._block_node:
            return False
        self._node_block[node] = block
        self._block_node[block] = node
        return True

    def sweep_stale(self, version: int) -> int:
        """Drop every published mapping whose node was interned under an
        older weights version (the version in the chain key already makes
        them unreachable — this reclaims the bytes). Stale *retired*
        blocks return to the free list; stale live blocks just lose their
        mapping and free normally when their refs drop."""
        stale = [(b, n) for b, n in self._block_node.items()
                 if self._node_version[n] != version]
        for b, node in stale:
            del self._block_node[b]
            del self._node_block[node]
            if b in self._retired:
                del self._retired[b]
                self._free.append(b)
        return len(stale)

    def assert_cache_consistent(self) -> None:
        """The extended leak gate: every pool block is exactly one of
        in-use, cached (retired), or free."""
        assert self.in_use + len(self._retired) + len(self._free) \
            == self.num_blocks, (
            f"block pool leak: {self.in_use} in use + "
            f"{len(self._retired)} cached + {len(self._free)} free "
            f"!= {self.num_blocks} total")
        for b in self._retired:
            assert self._ref[b] == 0, f"retired block {b} has refs"
            assert b in self._block_node, f"retired block {b} unpublished"


class InferenceEngine:
    """Slot-based continuous-batching engine over one model *shard set*.

    With ``mesh=None`` (default) the engine is single-device, exactly as
    before. With a ``mesh`` the engine IS that mesh: params take the
    bitwise-safe serving layout (``sharding.rules.serve_param_specs`` —
    column-parallel q/k/v over "model", MoE expert stacks over
    "expert"/"model"), the K/V pool (or dense cache) shards its KV-head
    dim over "model", block tables and per-slot bookkeeping shard slots
    over "data" (``decode_state_specs(paged=..., shard_heads=True)``), and
    every jitted path — fused tick, bucketed prefill, extend, group fork,
    scatters — dispatches as a sharded computation with donated state.
    Token/logprob/version streams stay byte-identical to the unsharded
    ``HostReferenceEngine`` on ANY mesh: the layout only uses sharding
    that preserves float-reduction order (heads/experts are batch/gather
    dims; the attention output is gathered before the ``wo`` contraction
    — see ``models.attention._serve_gather_heads``).
    """

    def __init__(self, params, cfg: ModelConfig, *, num_slots: int = 8,
                 max_seq: int = 512, eos_id: int = 1,
                 pcfg: ParallelConfig = DEFAULT_PCFG, seed: int = 0,
                 policy_version: int = 0, min_prefill_bucket: int = 8,
                 kv_block_size: int = 16,
                 num_kv_blocks: Optional[int] = None,
                 spec_draft: int = 0, spec_ngram: int = 3,
                 chunk_prefill: int = 0,
                 prefill_token_budget: Union[int, Dict[str, int]] = 0,
                 promote_after: int = 64, promote_after_ms: float = 0.0,
                 prefix_cache: bool = False,
                 mesh: Optional[Mesh] = None):
        self.mesh = mesh
        self.params = params
        self.cfg = cfg
        self.pcfg = pcfg
        self.num_slots = num_slots
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.policy_version = policy_version
        self.stats = EngineStats()
        self._min_bucket = min(min_prefill_bucket, max_seq)
        # per-layer-kind cache layout: what is pageable through the block
        # pool, what stays a compact per-slot state row, and what the
        # engine may therefore do (page, park sessions). This is the ONE
        # place family structure is inspected — every admission / fork /
        # park / evict path composes off the layout object.
        self.layout = CacheLayout.from_config(
            cfg, max_seq, allow_paging=self._supports_paging())
        self.supports_sessions = self.layout.supports_sessions
        self.paged = self.layout.paged
        # self-drafting speculative decoding (off at spec_draft=0). The
        # layout gates it: families whose state cannot roll back a
        # rejected tail (recurrent SSM scan state, ring caches) stay on
        # plain one-token ticks regardless of the knob.
        self.spec_draft = int(spec_draft)
        self.spec_ngram = max(1, int(spec_ngram))
        self._spec_enabled = (self.spec_draft > 0
                              and self.layout.supports_speculation)
        # fixed verify bucket [t0, d1..dk] -> one power-of-two length, so
        # the verify path compiles O(row-bucket) traces total
        self._spec_bucket = _pow2_bucket(1 + self.spec_draft, 2)
        # chunked prefill + SLO scheduler (off at chunk_prefill=0). The
        # layout gates chunkability; the knobs are deterministic host
        # state shared with the reference engine, so chunking decisions
        # cannot perturb the parity contract.
        self.chunk_prefill = max(0, int(chunk_prefill))
        # prefill_token_budget: an int is the legacy engine-wide budget
        # (one pool both classes draw from); a {"interactive": a,
        # "rollout": b} dict gives each scheduler class its own per-tick
        # pool, so rollout chunk floods cannot starve interactive first
        # tokens. The engine-wide total stays the sum.
        if isinstance(prefill_token_budget, dict):
            self._budget_classes: Optional[Dict[int, int]] = {
                0: max(0, int(prefill_token_budget.get("interactive", 0))),
                1: max(0, int(prefill_token_budget.get("rollout", 0)))}
            self.prefill_token_budget = sum(self._budget_classes.values())
        else:
            self._budget_classes = None
            self.prefill_token_budget = max(0, int(prefill_token_budget))
        self.promote_after = max(0, int(promote_after))
        # wall-clock deadline promotion (0 = off). NOT parity-safe across
        # engines of different speeds — a fused run and the host oracle
        # see different elapsed times — so parity suites leave it off;
        # step-age promote_after stays the deterministic knob.
        self.promote_after_ms = max(0.0, float(promote_after_ms))
        self._chunk_enabled = (self.chunk_prefill > 0
                               and self.layout.supports_chunked_prefill)
        # slot -> in-flight chunked admission (see _ChunkedPrefill)
        self._chunking: Dict[int, _ChunkedPrefill] = {}
        # per-step remaining budget: class -> tokens (None = unbudgeted)
        self._budget_left: Optional[Dict[int, int]] = None
        self._step_count = 0
        # automatic prefix caching: full blocks become content-addressed
        # and shared across unrelated requests. Gated by the layout (all
        # growing state pageable, no meta prefix) — note the gate is
        # paging-capability, not self.paged: the unpaged reference engine
        # mirrors every cache/allocator decision host-side (``_kvacct``)
        # so both engines claim the same prefixes in lockstep while the
        # reference never skips compute.
        self.prefix_cache = bool(prefix_cache) \
            and self.layout.supports_prefix_cache
        # host KV block accounting active? True for paged engines, and
        # for the unpaged reference when prefix caching needs its shadow
        # allocator. Device block ops stay gated on self.paged.
        self._kvacct = self.paged or (self.prefix_cache
                                      and self._shadow_kv_accounting())
        # meta-token prefix: cache entries (and _slot_len / block / bucket
        # accounting) include the n_prefix prepended slots prefill writes
        # before the text tokens
        self.n_prefix = self.layout.n_prefix
        # The block size is rounded down to a power-of-two divisor of
        # max_seq so blocks_per_row * block_size == max_seq exactly — the
        # linearized (gathered) cache then has the dense cache's shape,
        # which is what makes paged-vs-dense stream parity *bitwise*.
        bs = max(1, min(int(kv_block_size), max_seq))
        while max_seq % bs:
            bs >>= 1
        self.kv_block_size = bs
        if self.prefix_cache and self.chunk_prefill:
            # chunk boundaries land on block boundaries, so a mid-chunk
            # completion leaves behind fully-written (publishable) blocks
            # — the same rounding on both engines (deterministic host
            # config, shared with the reference)
            self.chunk_prefill = -(-self.chunk_prefill // bs) * bs

        # cache dtype follows the served params dtype
        cache_dtype = jax.tree_util.tree_leaves(params)[0].dtype
        if self._kvacct:
            self._blocks_per_row = max_seq // bs
            if num_kv_blocks is None:
                # default: byte parity with the dense layout — existing
                # workloads can never exhaust the pool (each slot's table
                # holds at most blocks_per_row blocks), they just stop
                # pinning full-length rows for short requests
                num_kv_blocks = num_slots * self._blocks_per_row
            self.allocator: Optional[BlockAllocator] = \
                BlockAllocator(num_kv_blocks)
            # host truth for every slot's block table; on a paged engine
            # the device table is a mirror updated by scatters and
            # _flush_table_updates (the unpaged reference keeps only the
            # host truth — its shadow allocator mirrors the fused
            # engine's cache decisions without any device pool)
            self._slot_blocks: List[List[int]] = \
                [[] for _ in range(num_slots)]
            self._table_dirty: List[tuple] = []
            self.stats.kv_blocks_total = num_kv_blocks
        else:
            self.allocator = None
        if self.paged:
            make_state = functools.partial(
                init_paged_state, cfg, num_slots, num_kv_blocks, bs,
                self._blocks_per_row, cache_dtype)
        else:
            make_state = functools.partial(init_decode_state, cfg, num_slots,
                                           max_seq, cache_dtype)
        # a meshed engine creates its cache state directly in its layout
        # (one jitted init) — never whole on one device first
        self._state_shardings = None
        if mesh is None:
            self.state = make_state()
        else:
            specs = decode_state_specs(cfg, mesh, batch=num_slots,
                                       paged=self.paged, shard_heads=True)
            self._state_shardings = {
                k: NamedSharding(mesh, specs[k])
                for k in jax.eval_shape(make_state)}
            self.state = jax.jit(make_state,
                                 out_shardings=self._state_shardings)()
        # prefix-cache per-slot publication bookkeeping: the token ids
        # written at cache positions [0, _slot_len), the chain nodes
        # already published for the slot's leading full blocks, and the
        # weights version the residency began under (a mid-flight weight
        # update makes later blocks mixed-version: publication stops)
        self._slot_toks: List[List[int]] = [[] for _ in range(num_slots)]
        self._slot_nodes: List[List[int]] = [[] for _ in range(num_slots)]
        self._slot_pubver = np.full((num_slots,), policy_version, np.int64)
        # logical K/V entries written per slot == the next decode write
        # position. Tracked for EVERY engine (incl. the host reference):
        # it drives the paged block-boundary allocs AND the shared
        # cache-full overflow guard, which must fire identically on both
        # engines for the parity contract to survive the max_seq edge
        self._slot_len = np.zeros((num_slots,), np.int64)
        if "k" in self.state:
            self.stats.kv_bytes = int(self.state["k"].nbytes
                                      + self.state["v"].nbytes)
        # per-layout byte accounting: pool bytes vs compact state-row bytes
        self.stats.pageable_kv_bytes = self.layout.pageable_kv_bytes(
            self.state)
        self._state_row_bytes = self.layout.state_row_bytes(self.state)
        self.stats.pooled_state_bytes = self._state_row_bytes * num_slots
        self.slots: List[Optional[Request]] = [None] * num_slots
        self.pending: Deque[Union[Request, GroupRequest]] = deque()
        self.completed: List[Request] = []
        self.sessions: Dict[int, EngineSession] = {}
        # session owning each slot (active OR parked); a slot is free for
        # fresh admission only when both slots[i] and _slot_session[i] are
        # None
        self._slot_session: List[Optional[int]] = [None] * num_slots
        self._use_counter = 0

        # device-resident slot bookkeeping (read back once per tick)
        self._last_token = jnp.zeros((num_slots,), jnp.int32)
        self._active = jnp.zeros((num_slots,), jnp.bool_)
        self._temps = jnp.ones((num_slots,), jnp.float32)
        self._gen = jnp.zeros((num_slots,), jnp.int32)
        self._max_new = jnp.ones((num_slots,), jnp.int32)
        self._rng = jax.random.PRNGKey(seed)

        # mesh placement: lay out params and slot bookkeeping across the
        # engine's shard set (the cache state already is). Donation through
        # the jitted paths requires stable layouts, so the impls re-constrain
        # their state outputs to these same shardings (_constrain_state).
        self._param_shardings = None
        self._slot_sharding = None
        if mesh is not None:
            self._param_shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s),
                serve_param_specs(params, mesh, cfg))
            self.params = jax.device_put(params, self._param_shardings)
            self._slot_sharding = NamedSharding(
                mesh, token_spec(mesh, num_slots))
            (self._last_token, self._active, self._temps, self._gen,
             self._max_new) = jax.device_put(
                (self._last_token, self._active, self._temps, self._gen,
                 self._max_new), self._slot_sharding)
            self._rng = jax.device_put(self._rng, NamedSharding(mesh, P()))
            self.stats.mesh_shape = ",".join(
                f"{a}={n}" for a, n in mesh.shape.items())
        if "k" in self.state:
            per_shard = self.state["k"].nbytes + self.state["v"].nbytes
            if mesh is not None:
                shard = self._state_shardings["k"].shard_shape(
                    self.state["k"].shape)
                per_shard = 2 * int(np.prod(shard)
                                    * self.state["k"].dtype.itemsize)
            self.stats.kv_bytes_per_shard = per_shard

        # the slot state is donated through the tick/scatter so XLA updates
        # the decode caches in place instead of copying them every dispatch
        self._tick_fn = jax.jit(self._tick_impl, donate_argnums=(1,))
        self._prefill_fn = jax.jit(self._prefill_impl)
        # extend must not donate the slot state: it only *reads* row
        # copies; the follow-up scatter (which does donate) writes them
        # back
        self._extend_fn = jax.jit(self._extend_impl)
        # verify reads row copies exactly like extend; the follow-up
        # commit scatter (donated) writes the accepted prefix back
        self._verify_fn = jax.jit(self._verify_impl)
        # chunk writes read row copies exactly like extend (no sampling,
        # no RNG); the follow-up scatter writes the advanced rows back
        self._chunk_fn = jax.jit(self._chunk_impl)
        self._scatter_fn = jax.jit(self._scatter_impl, donate_argnums=(0,))
        self._group_prefill_fn = jax.jit(self._group_prefill_impl)
        self._fork_scatter_fn = jax.jit(self._fork_scatter_impl,
                                        donate_argnums=(0,))
        if self.paged:
            self._paged_scatter_fn = jax.jit(self._paged_scatter_impl,
                                             donate_argnums=(0,))
            self._paged_fork_scatter_fn = jax.jit(
                self._paged_fork_scatter_impl, donate_argnums=(0,))
            # COW block copy: donated in-place pool update (one block's
            # K/V moves, not a fresh O(pool) buffer pair per copy)
            def _copy_block(k, v, dst, src):
                out = (k.at[:, dst].set(k[:, src]),
                       v.at[:, dst].set(v[:, src]))
                if self._state_shardings is not None:
                    out = tuple(jax.lax.with_sharding_constraint(
                        x, self._state_shardings[n])
                        for x, n in zip(out, ("k", "v")))
                return out
            self._copy_block_fn = jax.jit(_copy_block, donate_argnums=(0, 1))

    def _dispatch_ctx(self):
        """Context for every jitted dispatch: a meshed engine traces and
        runs under its serve mesh (model code reads it to apply the
        serving TP contract); an unsharded engine is a no-op."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return serve_mesh_context(self.mesh)

    def _constrain_state(self, state: dict) -> dict:
        """Re-pin a jit-produced slot state to the engine layout so donated
        buffers keep stable shardings across dispatches."""
        if self._state_shardings is None:
            return state
        return {k: jax.lax.with_sharding_constraint(
            v, self._state_shardings[k]) for k, v in state.items()}

    def _supports_paging(self) -> bool:
        """Class-level paging opt-in. ``HostReferenceEngine`` returns
        False: it stays the *unpaged* parity oracle, so every paged fast
        path is gated by byte-identical streams against dense rows."""
        return True

    def _shadow_kv_accounting(self) -> bool:
        """Whether an *unpaged* engine should still run the full host
        block-accounting (allocator, slot tables, prefix cache) as a
        shadow. The reference engine opts in: prefix-cache hit decisions
        depend on the complete allocator dynamics (refcounts, COW,
        eviction, retire/reclaim order), so the oracle replays them
        exactly — while never skipping compute."""
        return False

    # ------------------------------------------------------------------ api

    def submit(self, req: Request) -> None:
        req.submit_ts = time.perf_counter()
        req.submit_step = self._step_count
        self.pending.append(req)

    def submit_group(self, greq: GroupRequest) -> None:
        """Admit a GRPO group as a unit: the shared prompt is prefilled
        once and the KV cache forked to every member slot (partial
        admission under slot pressure — see ``_admit_group``)."""
        assert greq.members, "group must have at least one member"
        now = time.perf_counter()
        for m in greq.members:
            m.submit_ts = now
            m.submit_step = self._step_count
        self.pending.append(greq)

    def cancel(self, request_id: int) -> bool:
        """Cancel a plain (ungrouped) request on whichever path it is on:
        still queued (removed), mid-chunk (chunk state and every reserved
        block reclaimed), or actively decoding (slot freed; tokens already
        generated stay banked on the request). A session turn cancelled
        after its cache was touched drops the session's residency — the
        partial-turn K/V is inconsistent with the un-updated history, so
        the next turn transparently re-prefills. Group members are not
        cancellable (the fork shares their admission). Returns True when
        the request was found; it then surfaces via ``drain_completed``
        with ``finish_reason="cancelled"``."""
        for g in list(self.pending):
            if isinstance(g, GroupRequest) or g.request_id != request_id:
                continue
            self.pending.remove(g)
            g.finished = True
            g.finish_reason = "cancelled"
            self.completed.append(g)
            self.stats.cancelled += 1
            return True
        for slot, cs in list(self._chunking.items()):
            if cs.req.request_id == request_id:
                self._abort_chunk(slot, "cancelled")
                return True
        for i, req in enumerate(self.slots):
            if req is None or req.request_id != request_id:
                continue
            req.finished = True
            req.finish_reason = "cancelled"
            self.completed.append(req)
            self.stats.cancelled += 1
            self.slots[i] = None
            sess = self._session_of(req)
            if sess is not None and sess.slot == i:
                sess.slot = None   # partial-turn KV: drop residency
            self._slot_session[i] = None
            if self._kvacct:
                self._free_slot_blocks(i)
                self._sync_kv_stats()
            self._active = self._active.at[i].set(False)
            if self._slot_sharding is not None:
                self._active = jax.device_put(self._active,
                                              self._slot_sharding)
            return True
        return False

    def open_session(self, session_id: int) -> None:
        """Register a multi-turn session. Turns are submitted as Requests
        carrying ``session_id``; completed turns park their slot + KV cache
        for the next turn's extend."""
        assert self.supports_sessions, "engine config cannot host sessions"
        self.sessions[session_id] = EngineSession(
            session_id=session_id, tokens=np.zeros((0,), np.int32),
            last_use=self._next_use())

    def close_session(self, session_id: int) -> None:
        """Drop a session. A parked slot is freed immediately — including
        its KV blocks — while a slot with the turn still decoding is
        released (and its blocks reclaimed) by the normal finish path
        (the session is gone from the table, so it will not re-park)."""
        sess = self.sessions.pop(session_id, None)
        if sess is not None and sess.slot is not None \
                and self.slots[sess.slot] is None \
                and sess.slot not in self._chunking:
            self._slot_session[sess.slot] = None
            if self._kvacct:
                self._free_slot_blocks(sess.slot)
                self._sync_kv_stats()

    def relay_weights(self, params):
        """Stage an in-flight policy update: reshard trainer param shards
        directly into this engine's serving layout. ``jax.device_put`` on
        already-committed device arrays is a device-to-device transfer
        dispatched asynchronously — the params are NEVER gathered to host
        on this path (the relay the paper's trainer→inference weight
        broadcast performs over NCCL). Returns the placed tree;
        ``commit_weights`` installs it. Unsharded engines pass the tree
        through untouched."""
        if self.mesh is None:
            return params
        return jax.device_put(params, self._param_shardings)

    def commit_weights(self, placed, version: int) -> None:
        """Install a ``relay_weights`` result: takes effect at the next
        decode tick; occupied slots keep their caches and continue
        generating."""
        self.params = placed
        self.policy_version = version
        self.stats.weight_updates += 1
        if self.prefix_cache:
            # the version in the chain key already makes stale entries
            # unreachable; the sweep reclaims their bytes immediately
            # (deterministic host logic — the reference sweeps in
            # lockstep, so cache decisions stay identical)
            self.stats.prefix_cache_swept += \
                self.allocator.sweep_stale(version)

    def update_weights(self, params, version: int) -> None:
        """In-flight policy update (relay + commit in one call)."""
        self.commit_weights(self.relay_weights(params), version)

    @property
    def num_active(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def pending_units(self) -> int:
        """Pending work in *member* units: a queued GroupRequest counts as
        its remaining group size, not 1 — without this a G=16 group looks
        as cheap as a single request to the pool's least-loaded dispatch."""
        return sum(g.group_size if isinstance(g, GroupRequest) else 1
                   for g in self.pending)

    @property
    def load(self) -> int:
        """Work queued on this engine (pool dispatch key): live requests
        plus open sessions — each session is an ongoing conversation whose
        turns are all pinned here, and parked slots are otherwise invisible
        (slots[i] is None), so without this term a session-saturated engine
        reports load 0 and keeps winning ``open_session`` ties."""
        return (self.num_active + self.pending_units + len(self.sessions)
                + len(self._chunking))

    @property
    def idle(self) -> bool:
        return (self.num_active == 0 and not self.pending
                and not self._chunking)

    def drain_completed(self) -> List[Request]:
        done, self.completed = self.completed, []
        return done

    # --------------------------------------------------- jitted device path

    def _build_prefill_batch(self, tokens, prompt_lens) -> dict:
        """Model input batch for a prompt row bucket, including the
        family-specific stub modalities (shared with the reference
        engine so both prefill paths see identical inputs)."""
        R = tokens.shape[0]
        batch = {"tokens": tokens, "prompt_lens": prompt_lens}
        if self.cfg.family == "vlm":
            batch["patch_embeds"] = jnp.zeros(
                (R, self.cfg.num_image_tokens, self.cfg.d_model))
        if self.cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (R, self.cfg.encoder_seq_len, self.cfg.d_model))
        return batch

    def _prefill_impl(self, params, tokens, prompt_lens, temps, rng):
        """Fused bucketed prefill + first-token sampling (one dispatch)."""
        self.stats.prefill_traces += 1   # python side effect: trace-time only
        batch = self._build_prefill_batch(tokens, prompt_lens)
        return prefill_sample(params, batch, temps, rng, self.cfg,
                              self.max_seq, self.pcfg)

    def _extend_impl(self, params, state, gather_idx, tokens, ext_lens,
                     start_pos, temps, rng):
        """Fused bucketed session extend + first-token sampling: gather the
        pinned slot rows (linearizing each row's pool blocks through its
        block table when paged), run the new-token block against their
        caches with the *unchanged* dense extend math, and sample (one
        dispatch). Padded rows gather slot 0 and are dropped by the
        follow-up scatter."""
        self.stats.extend_traces += 1   # python side effect: trace-time only
        if self.paged:
            rows = paged_gather_rows(state, gather_idx)
        else:
            rows = {k: (v[gather_idx] if k == "pos" else v[:, gather_idx])
                    for k, v in state.items()}
        batch = {"tokens": tokens, "prompt_lens": ext_lens}
        return extend_sample(params, rows, batch, start_pos, temps, rng,
                             self.cfg, self.pcfg)

    def _verify_impl(self, params, state, gather_idx, tokens, ext_lens,
                     start_pos, temps, rng):
        """Fused speculative verification: the extend dispatch, but sampled
        at EVERY block offset (``extend_verify_sample``) — offset j's
        sample is what a sequential decode would have produced at position
        ``start_pos + j + 1``, which is what accept/reject compares the
        drafts against. One dispatch per speculation round; the verify
        bucket length is fixed, so this compiles one trace per row bucket."""
        self.stats.spec_verify_traces += 1  # python side effect: trace-time
        if self.paged:
            rows = paged_gather_rows(state, gather_idx)
        else:
            rows = {k: (v[gather_idx] if k == "pos" else v[:, gather_idx])
                    for k, v in state.items()}
        batch = {"tokens": tokens, "prompt_lens": ext_lens}
        return extend_verify_sample(params, rows, batch, start_pos, temps,
                                    rng, self.cfg, self.pcfg)

    def _chunk_impl(self, params, state, gather_idx, tokens, ext_lens,
                    start_pos):
        """One mid-prompt chunk of a chunked prefill: the bucketed extend
        dispatch with NO sampling — the chunk's logits are discarded, only
        the K/V (and recurrent state) writes matter. Takes no RNG, so the
        per-request RNG schedule is identical to monolithic admission: the
        one sampling split happens at the final chunk (``_extend_exec``)."""
        self.stats.chunk_traces += 1   # python side effect: trace-time only
        if self.paged:
            rows = paged_gather_rows(state, gather_idx)
        else:
            rows = {k: (v[gather_idx] if k == "pos" else v[:, gather_idx])
                    for k, v in state.items()}
        batch = {"tokens": tokens, "prompt_lens": ext_lens}
        _, st = extend(params, rows, batch, start_pos, self.cfg, self.pcfg)
        return st

    def _group_prefill_impl(self, params, tokens, prompt_lens, temps, rng):
        """Fused group-shared prefill: run the ONE shared-prompt row through
        the bucketed prefill and sample every member's first token from the
        broadcast logits (one dispatch). ``temps`` is [R] — the row bucket
        an equivalent per-member admission would have used."""
        self.stats.group_prefill_traces += 1  # python side effect: trace-time
        batch = self._build_prefill_batch(tokens, prompt_lens)
        return prefill_fork_sample(params, batch, temps, rng, self.cfg,
                                   self.max_seq, self.pcfg)

    def _fork_scatter_impl(self, state, last_token, active, temps, gen,
                           max_new, st, slot_idx, toks, row_temps,
                           row_max_new, row_active, row_gen):
        """Fork the single prefilled row into every member slot: broadcast
        the row (lazy under jit — a gather→broadcast, no materialized
        [L, R, S_max, ...] copy) and reuse the bucketed-prefill scatter.
        One dispatch, no host round trip; padded rows drop as usual."""
        st_rows = fork_decode_rows(st, slot_idx.shape[0])
        return self._scatter_impl(state, last_token, active, temps, gen,
                                  max_new, st_rows, slot_idx, toks,
                                  row_temps, row_max_new, row_active,
                                  row_gen)

    def _tick_impl(self, params, state, token, active, temps, gen, max_new,
                   rng):
        """Fused decode tick: serve + sample + finished-flag tracking.
        Paged engines read K/V through the block table and mask inactive
        rows' writes (a shared pool cannot tolerate parked-row drift
        writes the way exclusively-owned dense rows can); both paths also
        freeze inactive rows' recurrent SSM state, which — unlike dense
        K/V drift — could never be overwritten back. The RNG split and
        sampling math are identical either way."""
        self.stats.decode_traces += 1    # python side effect: trace-time only
        if self.paged:
            toks, lps, new_state, rng = paged_sample_step(
                params, state, token, active, temps, rng, self.cfg,
                self.pcfg)
        else:
            toks, lps, new_state, rng = sample_step(
                params, state, token, temps, rng, self.cfg, self.pcfg,
                active=active)
        count = gen + active.astype(jnp.int32)
        finished = active & ((toks == self.eos_id) | (count >= max_new))
        new_token = jnp.where(active, toks, token)
        return (toks, lps, finished, new_token, active & ~finished, count,
                self._constrain_state(new_state), rng)

    def _scatter_impl(self, state, last_token, active, temps, gen, max_new,
                      st, slot_idx, toks, row_temps, row_max_new, row_active,
                      row_gen):
        """Scatter a prefilled row bucket into the slot state in one
        dispatch. Padded rows carry slot_idx == num_slots (out of bounds)
        and are dropped by the scatter. ``row_gen`` seeds the device
        generated-token counter: 1 for admission scatters (the sampled
        first token), ``len(completion)`` for a speculative commit."""
        new_state = dict(state)
        for key, val in st.items():
            if key == "pos":
                new_state["pos"] = state["pos"].at[slot_idx].set(
                    val.astype(state["pos"].dtype), mode="drop")
            else:
                # cache tensors are [L, B, ...] -> batch axis 1
                new_state[key] = state[key].at[:, slot_idx].set(
                    val.astype(state[key].dtype), mode="drop")
        last_token = last_token.at[slot_idx].set(toks, mode="drop")
        active = active.at[slot_idx].set(row_active, mode="drop")
        temps = temps.at[slot_idx].set(row_temps, mode="drop")
        gen = gen.at[slot_idx].set(row_gen, mode="drop")
        max_new = max_new.at[slot_idx].set(row_max_new, mode="drop")
        return (self._constrain_state(new_state), last_token, active, temps,
                gen, max_new)

    def _paged_scatter_impl(self, state, last_token, active, temps, gen,
                            max_new, st, slot_idx, toks, row_temps,
                            row_max_new, row_active, row_gen, src_pos,
                            blk_pos, off_pos, new_tables):
        """Paged scatter: copy row positions ``src_pos`` of the dense
        prefill/extend product into pool blocks ``(blk_pos, off_pos)``
        (host-computed from the allocator's tables; out-of-bounds block
        ids drop — padded rows, unallocated tails, and blocks a row only
        *shares*), and install each row's block table. One dispatch, same
        bookkeeping as the dense scatter."""
        new_state = paged_write_rows(state, st, slot_idx, src_pos, blk_pos,
                                     off_pos, new_tables)
        last_token = last_token.at[slot_idx].set(toks, mode="drop")
        active = active.at[slot_idx].set(row_active, mode="drop")
        temps = temps.at[slot_idx].set(row_temps, mode="drop")
        gen = gen.at[slot_idx].set(row_gen, mode="drop")
        max_new = max_new.at[slot_idx].set(row_max_new, mode="drop")
        return (self._constrain_state(new_state), last_token, active, temps,
                gen, max_new)

    def _paged_fork_scatter_impl(self, state, last_token, active, temps,
                                 gen, max_new, st, slot_idx, toks,
                                 row_temps, row_max_new, row_active,
                                 row_gen, src_pos, blk_pos, off_pos,
                                 new_tables):
        """Copy-on-write group fork: broadcast the single prefilled row
        (lazy under jit) and scatter it *once* into the shared prompt
        blocks via member 0's coordinates; members >0 write only their
        private tail block (every other position carries an out-of-bounds
        block id). The pool write cost is therefore O(prompt + G·tail) —
        the prompt lands once like any single admission and each member
        adds at most one block — instead of the dense fork's O(G·max_seq)
        row broadcast."""
        st_rows = fork_decode_rows(st, slot_idx.shape[0])
        return self._paged_scatter_impl(state, last_token, active, temps,
                                        gen, max_new, st_rows, slot_idx,
                                        toks, row_temps, row_max_new,
                                        row_active, row_gen, src_pos,
                                        blk_pos, off_pos, new_tables)

    # -------------------------------------------- overridable execution ops
    # (HostReferenceEngine swaps these for the pre-fusion host path while
    # inheriting identical scheduling and RNG discipline)

    def _prefill_exec(self, tokens, prompt_lens, temps):
        """Run one bucketed prefill. Returns (tokens, logprobs, row state);
        consumes exactly one split of the engine RNG."""
        with self._dispatch_ctx():
            toks, lps, st, self._rng = self._prefill_fn(
                self.params, jnp.asarray(tokens), jnp.asarray(prompt_lens),
                jnp.asarray(temps), self._rng)
        return toks, lps, st

    def _extend_exec(self, gather_idx, tokens, ext_lens, start_pos, temps):
        """Run one bucketed session extend. Returns (tokens, logprobs, row
        state); consumes exactly one split of the engine RNG — the same
        discipline as a prefill batch, so an extend turn and a
        re-prefilled turn keep the RNG streams aligned."""
        with self._dispatch_ctx():
            toks, lps, st, self._rng = self._extend_fn(
                self.params, self.state, jnp.asarray(gather_idx),
                jnp.asarray(tokens), jnp.asarray(ext_lens),
                jnp.asarray(start_pos), jnp.asarray(temps), self._rng)
        return toks, lps, st

    def _verify_exec(self, gather_idx, tokens, ext_lens, start_pos, temps):
        """Run one speculative verification round. Returns (tokens [R, S],
        logprobs [R, S], row state); consumes exactly one split of the
        engine RNG — and samples on the [R, S, V] block shape, which the
        host reference mirrors exactly (categorical's gumbel bits depend
        on the draw shape, so the shapes must agree for byte parity)."""
        with self._dispatch_ctx():
            toks, lps, st, self._rng = self._verify_fn(
                self.params, self.state, jnp.asarray(gather_idx),
                jnp.asarray(tokens), jnp.asarray(ext_lens),
                jnp.asarray(start_pos), jnp.asarray(temps), self._rng)
        return toks, lps, st

    def _chunk_exec(self, gather_idx, tokens, ext_lens, start_pos):
        """Run one no-sample prefill chunk. Returns the row state for the
        follow-up scatter; consumes NO engine RNG — mid chunks are pure
        cache writes, keeping the sampling RNG schedule identical to an
        unchunked admission of the same request sequence."""
        with self._dispatch_ctx():
            st = self._chunk_fn(
                self.params, self.state, jnp.asarray(gather_idx),
                jnp.asarray(tokens), jnp.asarray(ext_lens),
                jnp.asarray(start_pos))
        return st

    def _group_prefill_exec(self, tokens, prompt_lens, temps):
        """Run one group-shared prefill (single prompt row, member-bucket
        ``temps``). Returns (tokens [R], logprobs [R], single-row state);
        consumes exactly one split of the engine RNG — the same discipline
        as a per-member prefill batch, which is what keeps fork and
        independent admission on identical RNG streams."""
        with self._dispatch_ctx():
            toks, lps, st, self._rng = self._group_prefill_fn(
                self.params, jnp.asarray(tokens), jnp.asarray(prompt_lens),
                jnp.asarray(temps), self._rng)
        return toks, lps, st

    def _fork_scatter_exec(self, st, slot_idx, toks, row_temps, row_max_new,
                           row_active, paged_coords=None) -> None:
        fn = self._fork_scatter_fn if paged_coords is None \
            else self._paged_fork_scatter_fn
        extra = () if paged_coords is None \
            else tuple(jnp.asarray(c) for c in paged_coords)
        row_gen = np.ones((len(np.asarray(slot_idx)),), np.int32)
        with self._dispatch_ctx():
            (self.state, self._last_token, self._active, self._temps,
             self._gen, self._max_new) = fn(
                self.state, self._last_token, self._active, self._temps,
                self._gen, self._max_new, st, jnp.asarray(slot_idx),
                jnp.asarray(toks), jnp.asarray(row_temps),
                jnp.asarray(row_max_new), jnp.asarray(row_active),
                jnp.asarray(row_gen), *extra)

    def _scatter_exec(self, st, slot_idx, toks, row_temps, row_max_new,
                      row_active, paged_coords=None, row_gen=None) -> None:
        fn = self._scatter_fn if paged_coords is None \
            else self._paged_scatter_fn
        extra = () if paged_coords is None \
            else tuple(jnp.asarray(c) for c in paged_coords)
        if row_gen is None:   # admission: the sampled first token counts 1
            row_gen = np.ones((len(np.asarray(slot_idx)),), np.int32)
        with self._dispatch_ctx():
            (self.state, self._last_token, self._active, self._temps,
             self._gen, self._max_new) = fn(
                self.state, self._last_token, self._active, self._temps,
                self._gen, self._max_new, st, jnp.asarray(slot_idx),
                jnp.asarray(toks), jnp.asarray(row_temps),
                jnp.asarray(row_max_new), jnp.asarray(row_active),
                jnp.asarray(row_gen), *extra)

    def _decode_exec(self):
        """One fused decode tick; a single small host readback."""
        with self._dispatch_ctx():
            (toks, lps, fin, self._last_token, self._active, self._gen,
             self.state, self._rng) = self._tick_fn(
                self.params, self.state, self._last_token, self._active,
                self._temps, self._gen, self._max_new, self._rng)
        return jax.device_get((toks, lps, fin))

    # ------------------------------------------------------------ internals

    def _next_use(self) -> int:
        self._use_counter += 1
        return self._use_counter

    # ------------------------------------------------- paged-KV bookkeeping

    def _blocks_for(self, tokens: int) -> int:
        """Blocks needed to hold ``tokens`` K/V entries."""
        return -(-tokens // self.kv_block_size)

    def _free_slot_blocks(self, slot: int, evicted: bool = False) -> None:
        """Return a slot's block references to the allocator (shared blocks
        only free when the last referencing member drops them; published
        full blocks *retire* into the prefix cache instead of freeing)."""
        n = self.allocator.free(self._slot_blocks[slot])
        self._slot_blocks[slot] = []
        self._slot_len[slot] = 0
        self._slot_toks[slot] = []
        self._slot_nodes[slot] = []
        if evicted:
            self.stats.blocks_freed_on_evict += n

    def _alloc_evicting(self, n: int, protect=()) -> Optional[List[int]]:
        """Allocate ``n`` blocks, LRU-evicting parked sessions for their
        blocks when the free list runs short (the eviction also frees the
        slot — fine, eviction is eviction). ``protect`` names session ids
        that must survive: the sessions an in-flight extend run is about
        to re-activate. Returns None when the pool cannot satisfy the
        request even with every unprotected parked session gone — the
        caller leaves its work queued (admission backpressure) and the
        queue drains as decoding frees blocks."""
        while True:
            ids = self.allocator.alloc(n)
            if ids is not None:
                return ids
            if self._evict_lru_parked(protect) is None:
                return None

    def _cow_block(self, slot: int, li: int, protect=()) -> bool:
        """Copy-on-write: give ``slot`` a private copy of its logical
        block ``li`` before writing into it. Triggered when a write would
        land in a block whose refcount is >1 (shared via a group fork).
        Copies one block's K/V pool-to-pool (O(block_size), independent
        of how long the shared prefix is), drops the shared reference,
        and queues the device-table fixup."""
        old = self._slot_blocks[slot][li]
        ids = self._alloc_evicting(1, protect)
        if ids is None:
            return False
        new = ids[0]
        if self.paged:   # device copy; the shadow oracle is bookkeeping-only
            self.state["k"], self.state["v"] = self._copy_block_fn(
                self.state["k"], self.state["v"], jnp.int32(new),
                jnp.int32(old))
        self.allocator.free([old])
        self._slot_blocks[slot][li] = new
        self._table_dirty.append((slot, li, new))
        self.stats.cow_forks += 1
        return True

    def _flush_table_updates(self) -> None:
        """Push queued host-table changes (decode-growth allocations, COW
        swaps) to the device block table in one dispatch. The unpaged
        shadow oracle has no device table: it just drops the queue."""
        if not self._kvacct or not self._table_dirty:
            return
        if not self.paged:
            self._table_dirty.clear()
            return
        rows = np.array([t[0] for t in self._table_dirty], np.int32)
        cols = np.array([t[1] for t in self._table_dirty], np.int32)
        vals = np.array([t[2] for t in self._table_dirty], np.int32)
        tables = self.state["block_tables"].at[rows, cols].set(vals)
        if self._state_shardings is not None:
            # eager scatter output layout is XLA's choice; re-pin so the
            # donated jit paths keep seeing the engine layout
            tables = jax.device_put(
                tables, self._state_shardings["block_tables"])
        self.state["block_tables"] = tables
        self._table_dirty.clear()

    def _build_scatter_coords(self, slot_idx, S_write: int, row_starts):
        """Host-side physical coordinates for a paged scatter: for bucket
        row r and offset j, position ``row_starts[r] + j`` of the dense
        row goes to ``(blk[r,j], off[r,j])`` per the slot's block table —
        or to the out-of-bounds sentinel (dropped) for padded rows and
        positions past the row's allocation."""
        sent = self.allocator.num_blocks
        R = len(slot_idx)
        bs = self.kv_block_size
        offsets = np.arange(S_write, dtype=np.int32)
        src = np.asarray(row_starts, np.int32)[:, None] + offsets[None, :]
        blk = np.full((R, S_write), sent, np.int32)
        off = np.zeros((R, S_write), np.int32)
        tables = np.zeros((R, self._blocks_per_row), np.int32)
        for r in range(R):
            s = int(slot_idx[r])
            if s >= self.num_slots:
                continue
            blocks = self._slot_blocks[s]
            tables[r, :len(blocks)] = blocks
            # sentinel-padded lookup table: positions past the slot's
            # allocation resolve to the out-of-bounds id and drop
            lut = np.full((self._blocks_per_row + 1,), sent, np.int64)
            lut[:len(blocks)] = blocks
            li = np.minimum(src[r] // bs, self._blocks_per_row)
            blk[r] = lut[li]
            off[r] = src[r] % bs
        return src, blk, off, tables

    def _build_fork_coords(self, slot_idx, S_write: int, k: int,
                           shared: List[int], tails: List[int]):
        """Coordinates for the copy-on-write group fork: member 0 writes
        the shared full blocks (once, for everyone — they are the same
        physical blocks in every member's table) plus its tail; members
        1..k-1 write *only* their private tail block."""
        sent = self.allocator.num_blocks
        R = len(slot_idx)
        bs = self.kv_block_size
        src = np.broadcast_to(np.arange(S_write, dtype=np.int32),
                              (R, S_write)).copy()
        blk = np.full((R, S_write), sent, np.int32)
        off = src % bs
        tables = np.zeros((R, self._blocks_per_row), np.int32)
        li = src[0] // bs
        for r in range(min(k, R)):
            s = int(slot_idx[r])
            blocks = self._slot_blocks[s]
            tables[r, :len(blocks)] = blocks
            lut = np.full((self._blocks_per_row + 1,), sent, np.int64)
            if r == 0:
                lut[:len(shared)] = shared        # prompt lands ONCE
            if tails:
                lut[len(shared)] = tails[r]       # private COW tail
            blk[r] = lut[np.minimum(li, self._blocks_per_row)]
        return src, blk, off, tables

    def _ensure_decode_blocks(self) -> None:
        """Pre-tick invariant: every active slot's next K/V write position
        lands in an allocated block it owns exclusively. Crossing a block
        boundary allocates (LRU-evicting parked sessions when the free
        list is short); a shared block is copy-on-write'd. A slot the
        pool genuinely cannot serve finishes gracefully with
        ``finish_reason="overflow"`` instead of crashing the pump loop."""
        if not self._kvacct:
            return
        bs = self.kv_block_size
        starved = []
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            # _overflow_full_slots ran first, so the write is in range
            li = int(self._slot_len[i]) // bs
            blocks = self._slot_blocks[i]
            if li == len(blocks):
                ids = self._alloc_evicting(1)
                if ids is None:
                    starved.append(i)
                    continue
                blocks.append(ids[0])
                self._table_dirty.append((i, li, ids[0]))
            elif self.allocator.refcount(blocks[li]) > 1:
                if not self._cow_block(i, li):
                    starved.append(i)
        for i in starved:
            self._finish_starved(i)

    def _overflow_full_slots(self) -> None:
        """Cache-full guard, shared by paged AND dense engines: a slot
        whose next K/V write position has reached ``max_seq`` finishes
        with ``finish_reason="overflow"`` *before* the tick. Without
        this the dense write clamps to position max_seq-1 and the paged
        write would clamp to a different slot of the last block — both
        silently corrupt the cache (and, post-fork, possibly a SHARED
        block), and the two clamp targets differ, so the guard is also
        what keeps the parity contract intact at the max_seq edge."""
        for i, req in enumerate(self.slots):
            if req is not None and int(self._slot_len[i]) >= self.max_seq:
                self._finish_starved(i)

    def _finish_starved(self, slot: int) -> None:
        """Graceful overflow finish for an actively-decoding request whose
        cache row is full or whose pool ran dry: bank what it generated,
        release the slot, and reclaim its blocks unless a session parks
        them."""
        req = self.slots[slot]
        req.finished = True
        req.finish_reason = "overflow"
        self.stats.overflows += 1
        self._finish(req)
        self.slots[slot] = None
        sess = self._session_of(req)
        if sess is None or sess.slot != slot:
            self._slot_session[slot] = None
            if self._kvacct:
                self._free_slot_blocks(slot)
        self._active = self._active.at[slot].set(False)
        if self._slot_sharding is not None:
            self._active = jax.device_put(self._active, self._slot_sharding)

    def _sync_kv_stats(self) -> None:
        if self._kvacct:
            self.stats.kv_blocks_in_use = self.allocator.in_use
            self.stats.kv_blocks_peak = self.allocator.peak
            self.stats.prefix_cache_cached_blocks = self.allocator.cached
            self.stats.prefix_cache_retired = self.allocator.retired_total
            self.stats.prefix_cache_reclaimed = \
                self.allocator.reclaimed_total
        if self._state_row_bytes:
            parked = sum(1 for i in range(self.num_slots)
                         if self.slots[i] is None
                         and self._slot_session[i] is not None)
            self.stats.parked_state_bytes = parked * self._state_row_bytes

    def assert_kv_consistent(self) -> None:
        """Block-leak gate (runs at every ``run_until_idle`` teardown):
        each in-use pool block must be reachable from an occupied or
        parked slot, and freed slots must hold no blocks — so with no
        resident sessions, ``in_use == 0``. With prefix caching the gate
        extends: every pool block is exactly one of in-use, cached
        (retired into the prefix cache), or free."""
        if not self._kvacct:
            return
        self.allocator.assert_cache_consistent()
        held = set()
        for i in range(self.num_slots):
            if (self.slots[i] is not None
                    or self._slot_session[i] is not None
                    or i in self._chunking):
                held.update(self._slot_blocks[i])
            else:
                assert not self._slot_blocks[i], \
                    f"freed slot {i} still holds blocks {self._slot_blocks[i]}"
        assert self.allocator.in_use == len(held), (
            f"KV block leak: {self.allocator.in_use} blocks in use, "
            f"{len(held)} reachable from slots/sessions")
        self._sync_kv_stats()

    def _session_of(self, req: Request) -> Optional[EngineSession]:
        if req.session_id is None:
            return None
        return self.sessions.get(req.session_id)

    def _required_len(self, req: Request) -> int:
        """Total cache entries this request implies (meta-token prefix +
        history + new tokens) — the same bound a full re-prefill of the
        conversation would have to satisfy."""
        sess = self._session_of(req)
        hist = len(sess.tokens) if sess is not None else 0
        return self.n_prefix + hist + len(req.prompt_tokens)

    def _is_resident_extend(self, req) -> bool:
        """True when the request continues a session whose slot + KV cache
        are still resident (parked) AND still built under the current
        policy — the extend fast path. A stale cache (weight update since
        the prefix was built) forces the full-re-prefill fallback so fresh
        turns sample against self-consistent new-policy KV. Accepts a
        GroupRequest (always False): the extend-run batching loop walks
        the pending queue past the head, where groups may sit."""
        if isinstance(req, GroupRequest):
            return False
        sess = self._session_of(req)
        return (sess is not None and len(sess.tokens) > 0
                and sess.slot is not None
                and self.slots[sess.slot] is None
                and sess.slot not in self._chunking
                and sess.cache_version == self.policy_version)

    def _overflow_head(self) -> bool:
        """Finish the head request with ``finish_reason="overflow"`` if its
        conversation would not fit in ``max_seq`` — or, when paged, if its
        prompt alone needs more blocks than the whole pool holds (it could
        never be admitted; waiting would deadlock the queue). Graceful:
        the pump loop keeps running, the client surfaces a masked
        rollout."""
        req = self.pending[0]
        fits = self._required_len(req) <= self.max_seq
        if fits and self._kvacct:
            fits = (self._blocks_for(self._required_len(req))
                    <= self.allocator.num_blocks)
        if fits:
            return False
        self.pending.popleft()
        req.finished = True
        req.finish_reason = "overflow"
        # no _finish(): the turn produced nothing, session history is
        # untouched (its cache stays consistent for a later, shorter turn)
        self.completed.append(req)
        self.stats.overflows += 1
        return True

    def _evict_lru_parked(self, protect=()) -> Optional[int]:
        """Reclaim the least-recently-used parked session's slot — and,
        when paged, its KV blocks. The evicted session keeps its
        host-side token history; its next turn transparently falls back
        to a full re-prefill. ``protect`` shields sessions an in-flight
        extend run is about to re-activate."""
        parked = [(sess.last_use, sid) for sid, sess in self.sessions.items()
                  if sess.slot is not None and self.slots[sess.slot] is None
                  and sess.slot not in self._chunking   # mid-chunk resident
                  and sid not in protect]
        if not parked:
            return None
        _, sid = min(parked)
        sess = self.sessions[sid]
        slot, sess.slot = sess.slot, None
        self._slot_session[slot] = None
        if self._kvacct:
            # published full blocks retire into the prefix cache here
            # instead of freeing — an evicted conversation's prefix is
            # exactly the kind of content the next request re-sends
            self._free_slot_blocks(slot, evicted=True)
        self.stats.session_evictions += 1
        return slot

    def _effective_prompt(self, req: Request) -> np.ndarray:
        """Tokens a fresh prefill of this request must process: the raw
        prompt, or — for an evicted session's turn — the full conversation
        history plus the delta (fallback re-prefill)."""
        p = np.asarray(req.prompt_tokens, np.int32)
        sess = self._session_of(req)
        if sess is None or not len(sess.tokens):
            return p
        return np.concatenate([sess.tokens, p])

    def _admit(self) -> None:
        """Fill slots from the pending queue, strictly FIFO in type runs:
        session-extend turns re-activate their parked slot via a bucketed
        extend (no free slot needed); a GroupRequest prefills its shared
        prompt once and forks the cache to every member slot; everything
        else — fresh prompts, first session turns, evicted-session
        fallbacks — goes through the bucketed batched prefill, evicting
        LRU parked sessions when free slots run out. Requests that finish
        at their first token free their slot immediately, so keep
        admitting until slots or queue run out. Under the SLO scheduler,
        the queue is first stably partitioned by request class
        (``_schedule_pending``); long prompts — fresh or resident-delta —
        divert to the chunked-prefill path when chunking is enabled."""
        self._schedule_pending()
        while self.pending:
            if isinstance(self.pending[0], GroupRequest):
                if not self._admit_group():
                    return
                continue
            if self._overflow_head():
                continue
            if self._is_resident_extend(self.pending[0]):
                head = self.pending[0]
                if (self._chunk_enabled
                        and 1 + len(head.prompt_tokens) > self.chunk_prefill):
                    # long resident delta: stream it in chunks instead of
                    # one monolithic extend dispatch
                    if not self._admit_chunked_resident(head):
                        return
                    continue
                if not self._admit_extend_run():
                    return
                continue
            if not self._admit_prefill_run():
                return

    def _sched_priority(self, req: Request) -> int:
        """0 = high (interactive, or a rollout promoted past its deadline),
        1 = normal. Promotion is sticky and counted once per request.
        Two deadlines promote: step age (``promote_after``, deterministic
        — the parity-safe default) and wall-clock age
        (``promote_after_ms`` against the ``submit_ts`` stamp, for real
        latency SLOs where a step is not a unit of time)."""
        if req.sched_class == "interactive" or req.promoted:
            return 0
        aged = (self.promote_after > 0
                and self._step_count - req.submit_step >= self.promote_after)
        if not aged and self.promote_after_ms > 0 and req.submit_ts > 0:
            aged = (time.perf_counter() - req.submit_ts) * 1e3 \
                >= self.promote_after_ms
        if aged:
            req.promoted = True
            self.stats.sched_promotions += 1
            return 0
        return 1

    # ------------------------------------------- per-class prefill budget

    def _budget_class(self, req: Request) -> int:
        """Which per-tick budget pool a request draws from: promoted
        rollouts spend from the interactive pool — promotion exists to
        let aged work cut the line, budget included."""
        return self._sched_priority(req)

    def _budget_for(self, req: Request) -> Optional[int]:
        """Remaining prefill-token budget for ``req`` this tick (None =
        unbudgeted). With an engine-wide (int) budget both classes share
        pool 0."""
        if self._budget_left is None:
            return None
        if self._budget_classes is None:
            return self._budget_left[0]
        return self._budget_left[self._budget_class(req)]

    def _budget_take(self, req: Request, n: int) -> None:
        if self._budget_left is None or n <= 0:
            return
        c = 0 if self._budget_classes is None else self._budget_class(req)
        self._budget_left[c] = max(0, self._budget_left[c] - n)

    def _schedule_pending(self) -> None:
        """Stable two-class partition of the pending queue: interactive
        (and deadline-promoted rollout) work moves ahead of unpromoted
        rollout work, FIFO *within* each class. Identity when every
        queued unit shares one class — the single-tenant RL rollout path
        keeps its exact FIFO order (and admission-run batching)."""
        if len(self.pending) < 2:
            return
        pri = [(g, self._sched_priority(
                    g.members[0] if isinstance(g, GroupRequest) else g))
               for g in self.pending]
        if all(p == pri[0][1] for _, p in pri):
            return
        hi = [g for g, p in pri if p == 0]
        lo = [g for g, p in pri if p == 1]
        self.pending = deque(hi + lo)

    # --------------------------------------------- automatic prefix caching

    def _match_cached_prefix(self, prompt: np.ndarray) -> List[int]:
        """Walk the prompt's chained block hashes against the published
        map and return the leading run of cached chain nodes. Capped at
        ``(len(prompt)-1) // block_size`` blocks so the admission dispatch
        always has at least one uncached token to feed (the model needs a
        real forward to sample the first output token). Deterministic
        host logic shared verbatim with the reference engine — both
        engines see the same allocator state, so they match (and claim)
        identical prefixes in lockstep."""
        bs = self.kv_block_size
        nodes: List[int] = []
        parent = -1
        for j in range((len(prompt) - 1) // bs):
            node = self.allocator.intern_node(
                parent, tuple(int(t) for t in prompt[j * bs:(j + 1) * bs]),
                self.policy_version)
            if self.allocator.lookup(node) is None:
                break
            nodes.append(node)
            parent = node
        return nodes

    def _admit_cached(self, req: Request, prompt: np.ndarray, slot: int,
                      nodes: List[int]) -> bool:
        """Admit one prefix-cache-hit request: claim the cached leading
        blocks by refcount bump (zero recompute, zero new KV bytes for
        the prefix), allocate blocks for the uncached suffix, and run a
        single-row extend over the suffix at ``start_pos = cached_len``
        — the same dispatch shape PR 2's session-extend parity test pins
        bitwise against a full re-prefill. A suffix longer than the
        chunk threshold streams through the chunked path from the cached
        base instead. Returns False on pool backpressure (claim released
        — retired blocks return to the cache unharmed; head waits)."""
        bs = self.kv_block_size
        claimed: List[int] = []
        for node in nodes:
            b = self.allocator.claim(node)
            assert b is not None, "matched node vanished within admission"
            claimed.append(b)
        c = len(claimed) * bs
        suffix = prompt[c:]
        # attach the claim before any further allocation: _alloc_evicting
        # may evict parked sessions, and the claim must be reachable (and
        # releasable through _free_slot_blocks on every failure path)
        self._slot_blocks[slot] = claimed
        self._slot_toks[slot] = [int(t) for t in prompt[:c]]
        self._slot_nodes[slot] = list(nodes)
        self._slot_pubver[slot] = self.policy_version
        if self.paged:
            # the hit dispatch (extend or first chunk) GATHERS the slot's
            # pages before any scatter installs a table: the device table
            # must hold the claimed blocks up front
            for j, b in enumerate(claimed):
                self._table_dirty.append((slot, j, b))
            self._flush_table_updates()
        # the unpaged oracle recomputes the claimed prefix K/V into its
        # dense row here (no RNG) — the fused engine's blocks already
        # hold it, so this is a no-op for us
        self._restore_cached_prefix(slot, prompt, c)
        if self._chunk_enabled and len(suffix) > self.chunk_prefill:
            # long uncached suffix: stream it in chunks from the cached
            # base (c is block-aligned, so the first chunk's boundary
            # block is freshly allocated — no COW against the claim)
            if not self._start_chunk(req, suffix, slot, base=c):
                self._free_slot_blocks(slot)
                return False
        else:
            need = self._blocks_for(c + len(suffix)) - len(claimed)
            blocks = self._alloc_evicting(need) if need > 0 else []
            if blocks is None:
                self._free_slot_blocks(slot)
                return False
            self._slot_blocks[slot] = claimed + blocks
            self._slot_len[slot] = c + len(suffix)
            if self.paged:
                for j, b in enumerate(blocks):
                    self._table_dirty.append((slot, len(claimed) + j, b))
                self._flush_table_updates()
            tok, lp = self._cached_admit_exec(slot, prompt, c, req)
            sess = self._session_of(req)
            if sess is not None:
                if len(sess.tokens):
                    self.stats.session_fallbacks += 1
                sess.slot = slot
                sess.last_use = self._next_use()
                sess.cache_version = self.policy_version
                self._slot_session[slot] = req.session_id
            finished = (tok == self.eos_id) or (req.max_new_tokens <= 1)
            self._record(req, tok, lp, finished)
            self._publish_slot_blocks(slot)
            if finished:
                self._finish(req)
                if self._slot_session[slot] is None:
                    # write-then-free, as everywhere: the suffix scatter
                    # is already enqueued when the blocks recycle
                    self._free_slot_blocks(slot)
            else:
                self.slots[slot] = req
            self.stats.prefills += 1
            self.stats.prefill_requests += 1
            self.stats.prefill_tokens += len(suffix)
        self.stats.prefix_cache_hits += 1
        self.stats.prefix_cache_hit_tokens += c
        self.stats.prefill_tokens_saved += c
        return True

    def _restore_cached_prefix(self, slot: int, prompt: np.ndarray,
                               c: int) -> None:
        """Hook for the unpaged oracle: recompute a claimed prefix's K/V
        into the dense slot row (see ``HostReferenceEngine``). The fused
        engine's claimed blocks already hold the bytes — no-op here."""

    def _cached_admit_exec(self, slot: int, prompt: np.ndarray, c: int,
                           req: Request) -> Tuple[int, float]:
        """Device half of a cache-hit admission: one single-row extend
        over the uncached suffix against the (claimed, or — oracle —
        restored) prefix KV, sampling the first token. One RNG split:
        exactly the split a full prefill of this prompt would have
        consumed, so hit admissions keep both engines' RNG schedules in
        lockstep. PR 2's extend-vs-reprefill test pins this dispatch
        shape to bitwise-equal logits against a monolithic prefill."""
        suffix = prompt[c:]
        S_b = self._extend_bucket(len(suffix), c)
        tokens = np.zeros((1, S_b), np.int32)
        tokens[0, :len(suffix)] = suffix
        ext_lens = np.array([len(suffix)], np.int32)
        start_pos = np.array([c], np.int32)
        temps = np.array([req.temperature], np.float32)
        maxnew = np.array([max(1, req.max_new_tokens)], np.int32)
        gather_idx = np.array([slot], np.int32)
        slot_idx = np.array([slot], np.int32)
        toks, lps, st = self._extend_exec(gather_idx, tokens, ext_lens,
                                          start_pos, temps)
        toks_h, lps_h = jax.device_get((toks, lps))
        tok, lp = int(toks_h[0]), float(lps_h[0])
        finished = (tok == self.eos_id) or (req.max_new_tokens <= 1)
        row_active = np.array([not finished], bool)
        if self.paged:
            coords = self._build_scatter_coords(slot_idx, S_b, start_pos)
            self._scatter_exec(st, slot_idx, toks, temps, maxnew,
                               row_active, paged_coords=coords)
        else:
            self._scatter_exec(st, slot_idx, toks, temps, maxnew,
                               row_active)
        return tok, lp

    def _publish_slot_blocks(self, slot: int) -> None:
        """Publish the slot's newly-filled full blocks under their chain
        nodes (first publisher wins — a duplicate stays anonymous and
        frees normally). Publication stops the moment the policy version
        moves past the version the residency was admitted under: KV
        written after a weight update would extend an old-version chain
        with mixed-version content."""
        if not self.prefix_cache:
            return
        if int(self._slot_pubver[slot]) != self.policy_version:
            return
        bs = self.kv_block_size
        toks = self._slot_toks[slot]
        nodes = self._slot_nodes[slot]
        blocks = self._slot_blocks[slot]
        nfull = min(len(toks) // bs, len(blocks))
        while len(nodes) < nfull:
            j = len(nodes)
            parent = nodes[-1] if nodes else -1
            node = self.allocator.intern_node(
                parent, tuple(toks[j * bs:(j + 1) * bs]),
                self.policy_version)
            self.allocator.publish(blocks[j], node)
            nodes.append(node)

    def _admit_prefill_run(self) -> bool:
        """Admit the head run of prefill-type requests. Returns False when
        no progress is possible (every slot active)."""
        want = 0                      # head run length (no queue mutation)
        for req in self.pending:
            if (want >= self.num_slots or isinstance(req, GroupRequest)
                    or self._is_resident_extend(req)):
                break
            if self._required_len(req) > self.max_seq:
                continue              # overflow-doomed: never takes a slot
            # a session going the prefill path with a parked-but-unusable
            # slot (stale cache version) releases that slot — and its now
            # dead-policy KV blocks — up front; the fallback re-prefill
            # will claim a slot and fresh blocks like any new prompt
            sess = self._session_of(req)
            if (sess is not None and sess.slot is not None
                    and self.slots[sess.slot] is None
                    and sess.slot not in self._chunking):
                self._slot_session[sess.slot] = None
                if self._kvacct:
                    self._free_slot_blocks(sess.slot)
                sess.slot = None
            want += 1
        free = [i for i in range(self.num_slots)
                if self.slots[i] is None and self._slot_session[i] is None
                and i not in self._chunking]
        while len(free) < want:
            slot = self._evict_lru_parked()
            if slot is None:
                break
            free.append(slot)
        if not free:
            return False
        reqs: List[Request] = []
        prompts: List[np.ndarray] = []
        slot_ids: List[int] = []
        block_lists: List[List[int]] = []
        used = 0
        progress = False
        while (self.pending and used < len(free)
               and not isinstance(self.pending[0], GroupRequest)
               and not self._is_resident_extend(self.pending[0])):
            if self._overflow_head():
                progress = True
                continue
            prompt = self._effective_prompt(self.pending[0])
            nodes = (self._match_cached_prefix(prompt)
                     if self.prefix_cache else [])
            if nodes:
                # prefix-cache hit: the head admits through its own
                # single-row dispatch (claim cached blocks, compute only
                # the uncached suffix). Flush the dense batch accumulated
                # so far first — FIFO dispatch order is part of the
                # parity contract — and let the next run (same _admit
                # pass) take the hit with a clean accumulator.
                if reqs:
                    break
                if not self._admit_cached(self.pending[0], prompt,
                                          free[used], nodes):
                    break             # block backpressure: head waits
                self.pending.popleft()
                used += 1
                progress = True
                continue
            if self._chunk_enabled and len(prompt) > self.chunk_prefill:
                # long prompt: claim the slot now and stream the tokens in
                # chunk-sized no-sample extends across the next steps —
                # only the blocks the FIRST chunk covers are reserved
                if not self._start_chunk(self.pending[0], prompt,
                                         free[used]):
                    break             # block backpressure: head waits
                if self.prefix_cache:
                    self.stats.prefix_cache_misses += 1
                self.pending.popleft()
                used += 1
                progress = True
                continue
            if self._kvacct:
                # admission is gated on real KV capacity, not slot count:
                # the prompt's blocks are claimed here (evicting parked
                # LRU sessions if the free list is short) and the request
                # WAITS at the queue head when the pool cannot serve it
                # yet — backpressure, not a crash
                blocks = self._alloc_evicting(
                    self._blocks_for(self.n_prefix + len(prompt)))
                if blocks is None:
                    break
                block_lists.append(blocks)
            if self.prefix_cache:
                self.stats.prefix_cache_misses += 1
            reqs.append(self.pending.popleft())
            prompts.append(prompt)
            slot_ids.append(free[used])
            used += 1
        if reqs:
            self._admit_batch(reqs, prompts, slot_ids, block_lists)
            progress = True
        return progress

    def _admit_extend_run(self) -> bool:
        """Admit the head run of resident-session extend turns that share
        one length bucket, as a single fused extend dispatch. Returns
        False when no turn could be admitted (paged pool exhausted — the
        head waits for blocks; backpressure, not a crash)."""
        head = self.pending[0]
        head_sess = self.sessions[head.session_id]
        # cache coordinates include the meta-token prefix
        S_b = self._extend_bucket(1 + len(head.prompt_tokens),
                                  self.n_prefix + len(head_sess.tokens) - 1)
        reqs: List[Request] = []
        seen = set()
        progress = False
        while self.pending and len(reqs) < self.num_slots:
            req = self.pending[0]
            if not self._is_resident_extend(req) or req.session_id in seen:
                break
            if self._overflow_head():
                progress = True
                continue
            sess = self.sessions[req.session_id]
            pos = self.n_prefix + len(sess.tokens) - 1
            if 1 + len(req.prompt_tokens) > S_b or pos + S_b > self.max_seq:
                break
            if self._kvacct and not self._reserve_extend_blocks(
                    sess, pos, 1 + len(req.prompt_tokens),
                    protect=seen | {req.session_id}):
                break
            self.pending.popleft()
            reqs.append(req)
            seen.add(req.session_id)
        if reqs:
            self._admit_extend(reqs, S_b)
        return bool(reqs) or progress

    def _reserve_extend_blocks(self, sess: EngineSession, start: int,
                               ext_len: int, protect=()) -> bool:
        """Session-extend wrapper over ``_reserve_slot_blocks``."""
        return self._reserve_slot_blocks(sess.slot, start, ext_len, protect)

    def _reserve_slot_blocks(self, slot: int, start: int, ext_len: int,
                             protect=()) -> bool:
        """Grow a slot's block list to cover a multi-token write region
        [start, start+ext_len) — a session-extend block or a speculative
        verify block — and copy-on-write the boundary block if it is
        shared (a group-forked member whose first write lands in a block
        its siblings still reference). ``protect`` keeps the caller's own
        sessions out of the eviction pool. On failure blocks already
        grown stay attached to the slot (owned, reachable, reused by the
        next attempt — never leaked)."""
        blocks = self._slot_blocks[slot]
        need = self._blocks_for(start + ext_len) - len(blocks)
        if need > 0:
            ids = self._alloc_evicting(need, protect)
            if ids is None:
                return False
            blocks.extend(ids)
        li = start // self.kv_block_size
        if li < len(blocks) and self.allocator.refcount(blocks[li]) > 1:
            if not self._cow_block(slot, li, protect):
                return False
        return True

    def _extend_bucket(self, ext_len: int, pos: int) -> int:
        """Power-of-two extend bucket, capped so the block write at ``pos``
        cannot be clamp-shifted into the live cache prefix. The overflow
        check guarantees ``pos + ext_len <= max_seq``, so the cap never
        truncates the block itself."""
        return min(_pow2_bucket(ext_len, self._min_bucket),
                   self.max_seq - pos)

    def _admit_group(self) -> bool:
        """Admit (part of) the head GroupRequest via the shared-prefill
        fork. Returns False when no progress is possible (no free slot,
        nothing evictable). Partial admission: fork into however many
        slots are free now; the remainder stays queued at the head and
        re-forks (one more 1-row prefill, never per-member prefills) as
        slots free up — first-token finishes can free slots within this
        same ``_admit`` pass."""
        greq = self.pending[0]
        plen = len(greq.prompt_tokens)
        # block math over cache entries: the meta prefix lands in the
        # shared blocks ahead of the prompt tokens
        full, tail = divmod(self.n_prefix + plen, self.kv_block_size)
        doomed = self.n_prefix + plen > self.max_seq
        if not doomed and self._kvacct:
            # one member needs the shared full blocks plus (maybe) a tail
            # block; if even that exceeds the whole pool, waiting would
            # deadlock the queue
            doomed = full + (1 if tail else 0) > self.allocator.num_blocks
        if doomed:
            # shared prompt can never fit: every member overflows, exactly
            # as each would have independently
            self.pending.popleft()
            for req in greq.members:
                req.finished = True
                req.finish_reason = "overflow"
                self.completed.append(req)
                self.stats.overflows += 1
            greq.members = []
            return True
        free = [i for i in range(self.num_slots)
                if self.slots[i] is None and self._slot_session[i] is None
                and i not in self._chunking]
        while len(free) < len(greq.members):
            slot = self._evict_lru_parked()
            if slot is None:
                break
            free.append(slot)
        if not free:
            return False
        k = min(len(free), len(greq.members))
        shared: List[int] = []
        tails: List[int] = []
        if self._kvacct:
            # claim the shared prompt blocks once, then one private tail
            # block per member (copy-on-write: members share the full
            # blocks via refcounts and own only the partial tail they
            # will immediately write into). Under block pressure the
            # member count shrinks — partial admission by capacity, same
            # re-fork contract as partial admission by slots.
            shared = self._alloc_evicting(full)
            if shared is None:
                return False
            while k > 0 and tail:
                tails = self._alloc_evicting(k)
                if tails is not None:
                    break
                k -= 1
            if k == 0 or (tail and tails is None):
                self.allocator.free(shared)
                return False
        if k < len(greq.members):
            self.stats.group_partial_admissions += 1
        members, greq.members = greq.members[:k], greq.members[k:]
        if not greq.members:
            self.pending.popleft()
        self._admit_group_fork(greq, members, free[:k], shared, tails)
        return True

    def _admit_group_fork(self, greq: "GroupRequest", members: List[Request],
                          slot_ids: List[int], shared: List[int],
                          tails: List[int]) -> None:
        """One shared-prefill fork dispatch: prefill the group prompt as a
        single bucketed row, sample every member's first token from the
        broadcast logits (byte-identical to a per-member prefill batch —
        see ``models.prefill_fork_sample``), and fork the cache row into
        the member slots with one jitted broadcast→scatter.

        Paged engines fork **copy-on-write**: every member's block table
        references the same physical ``shared`` full blocks (refcounted),
        and only the partial tail block — the one a member's first decode
        write lands in — is materialized per member. Fork cost is
        O(prompt + G·block_size) pool writes instead of the dense fork's
        G× row broadcast: independent of prompt length per member."""
        k = len(members)
        prompt = np.asarray(greq.prompt_tokens, np.int32)
        plen = len(prompt)
        S_b = min(_pow2_bucket(plen, self._min_bucket),
                  self.max_seq - self.n_prefix)
        tokens = np.zeros((1, S_b), np.int32)
        tokens[0, :plen] = prompt
        plens = np.full((1,), plen, np.int32)
        R = _pow2_bucket(k)           # member-row bucket, NOT the prompt row
        temps = np.ones((R,), np.float32)
        maxnew = np.ones((R,), np.int32)
        for r, req in enumerate(members):
            temps[r] = req.temperature
            maxnew[r] = max(1, req.max_new_tokens)
        for r in range(k):
            self._slot_len[slot_ids[r]] = self.n_prefix + plen
            if self.prefix_cache:
                self._slot_toks[slot_ids[r]] = [int(t) for t in prompt]
                self._slot_nodes[slot_ids[r]] = []
                self._slot_pubver[slot_ids[r]] = self.policy_version
        if self._kvacct:
            for r in range(k):
                if r:
                    self.allocator.incref(shared)
                self._slot_blocks[slot_ids[r]] = \
                    shared + ([tails[r]] if tails else [])
            if tails:
                self.stats.cow_forks += k
        toks, lps, st = self._group_prefill_exec(tokens, plens, temps)
        toks_h, lps_h = jax.device_get((toks, lps))

        slot_idx = np.full((R,), self.num_slots, np.int32)  # OOB rows drop
        slot_idx[:k] = slot_ids
        row_active = np.zeros((R,), bool)
        for r, req in enumerate(members):
            sess = self._session_of(req)
            if sess is not None:
                # the fork establishes session residency for every member
                # at once (a group of multi-turn rollouts): the member slot
                # parks for its turn-2 extend exactly as a prefilled first
                # turn would
                sess.slot = slot_ids[r]
                sess.last_use = self._next_use()
                sess.cache_version = self.policy_version
                self._slot_session[slot_ids[r]] = req.session_id
            tok, lp = int(toks_h[r]), float(lps_h[r])
            finished = (tok == self.eos_id) or (req.max_new_tokens <= 1)
            self._record(req, tok, lp, finished)
            if finished:
                self._finish(req)
            else:
                self.slots[slot_ids[r]] = req
                row_active[r] = True
        if self.paged:
            coords = self._build_fork_coords(slot_idx, self.n_prefix + S_b,
                                             k, shared, tails)
            self._fork_scatter_exec(st, slot_idx, toks, temps, maxnew,
                                    row_active, paged_coords=coords)
        else:
            self._fork_scatter_exec(st, slot_idx, toks, temps, maxnew,
                                    row_active)
        if self._kvacct:
            # publish the shared full prompt blocks (first member wins,
            # siblings' publishes are first-wins no-ops on the same
            # physical blocks), THEN release first-token finishes with
            # no session to park for — write then publish then free
            # keeps dispatch order sound: a later admission can only
            # recycle a block after this fork scatter is enqueued
            for r in range(k):
                self._publish_slot_blocks(slot_ids[r])
            for r, req in enumerate(members):
                if req.finished and self.slots[slot_ids[r]] is None \
                        and self._slot_session[slot_ids[r]] is None:
                    self._free_slot_blocks(slot_ids[r])
        self.stats.group_prefills += 1
        self.stats.group_fork_requests += k
        self.stats.prefill_tokens += plen               # prefilled ONCE
        self.stats.group_prefill_tokens_saved += (k - 1) * plen

    def _admit_batch(self, reqs: List[Request], prompts: List[np.ndarray],
                     slot_ids: List[int],
                     block_lists: Optional[List[List[int]]] = None) -> None:
        n = len(reqs)
        lens = [len(p) for p in prompts]
        maxlen = max(lens)
        assert self.n_prefix + maxlen <= self.max_seq, \
            f"prompt ({maxlen} tokens + {self.n_prefix} prefix) exceeds " \
            f"max_seq={self.max_seq}"
        # bucket cap leaves room for the meta-token prefix the prefill
        # prepends to every cache row
        S_b = min(_pow2_bucket(maxlen, self._min_bucket),
                  self.max_seq - self.n_prefix)
        R = _pow2_bucket(n)
        tokens = np.zeros((R, S_b), np.int32)
        plens = np.ones((R,), np.int32)
        temps = np.ones((R,), np.float32)
        maxnew = np.ones((R,), np.int32)
        for r, req in enumerate(reqs):
            p = prompts[r]
            tokens[r, :len(p)] = p
            plens[r] = len(p)
            temps[r] = req.temperature
            maxnew[r] = max(1, req.max_new_tokens)
            self._slot_len[slot_ids[r]] = self.n_prefix + len(p)
            if self.prefix_cache:
                self._slot_toks[slot_ids[r]] = [int(t) for t in p]
                self._slot_nodes[slot_ids[r]] = []
                self._slot_pubver[slot_ids[r]] = self.policy_version
            if self._kvacct:
                assert not self._slot_blocks[slot_ids[r]], \
                    f"slot {slot_ids[r]} re-admitted while holding blocks"
                self._slot_blocks[slot_ids[r]] = block_lists[r]
        toks, lps, st = self._prefill_exec(tokens, plens, temps)
        toks_h, lps_h = jax.device_get((toks, lps))

        slot_idx = np.full((R,), self.num_slots, np.int32)  # OOB rows drop
        slot_idx[:n] = slot_ids
        row_active = np.zeros((R,), bool)
        for r, req in enumerate(reqs):
            sess = self._session_of(req)
            if sess is not None:
                if len(sess.tokens):
                    self.stats.session_fallbacks += 1
                sess.slot = slot_ids[r]
                sess.last_use = self._next_use()
                sess.cache_version = self.policy_version
                self._slot_session[slot_ids[r]] = req.session_id
            tok, lp = int(toks_h[r]), float(lps_h[r])
            finished = (tok == self.eos_id) or (req.max_new_tokens <= 1)
            self._record(req, tok, lp, finished)
            if finished:
                self._finish(req)
            else:
                self.slots[slot_ids[r]] = req
                row_active[r] = True
        if self.paged:
            # the dense prefill rows carry [0, n_prefix + plen) cache
            # entries (meta prefix first): scatter the whole region
            coords = self._build_scatter_coords(
                slot_idx, self.n_prefix + S_b, np.zeros((R,), np.int32))
            self._scatter_exec(st, slot_idx, toks, temps, maxnew,
                               row_active, paged_coords=coords)
        else:
            self._scatter_exec(st, slot_idx, toks, temps, maxnew, row_active)
        if self._kvacct:
            # publish full prompt blocks, then reclaim first-token
            # finishes with no session to park for (write then publish
            # then free keeps dispatch order sound for any admission
            # that recycles the block)
            for r, req in enumerate(reqs):
                self._publish_slot_blocks(slot_ids[r])
                if req.finished and self.slots[slot_ids[r]] is None \
                        and self._slot_session[slot_ids[r]] is None:
                    self._free_slot_blocks(slot_ids[r])
        self.stats.prefills += 1
        self.stats.prefill_requests += n
        self.stats.prefill_tokens += int(sum(lens))

    def _admit_extend(self, reqs: List[Request], S_b: int) -> None:
        """One fused extend dispatch: gather the pinned slot rows, run each
        session's new-token block ([last history token] + delta) against
        its cache at the session's position, sample the first token of the
        turn, and scatter the advanced rows back."""
        n = len(reqs)
        R = _pow2_bucket(n)
        tokens = np.zeros((R, S_b), np.int32)
        ext_lens = np.ones((R,), np.int32)
        start_pos = np.zeros((R,), np.int32)
        temps = np.ones((R,), np.float32)
        maxnew = np.ones((R,), np.int32)
        gather_idx = np.zeros((R,), np.int32)   # pad rows gather slot 0
        slot_idx = np.full((R,), self.num_slots, np.int32)  # OOB rows drop
        for r, req in enumerate(reqs):
            sess = self.sessions[req.session_id]
            block = np.concatenate([
                sess.tokens[-1:], np.asarray(req.prompt_tokens, np.int32)])
            tokens[r, :len(block)] = block
            ext_lens[r] = len(block)
            start_pos[r] = self.n_prefix + len(sess.tokens) - 1
            temps[r] = req.temperature
            maxnew[r] = max(1, req.max_new_tokens)
            gather_idx[r] = sess.slot
            slot_idx[r] = sess.slot
            sess.last_use = self._next_use()
            if self.prefix_cache:
                self._slot_toks[sess.slot].extend(int(t) for t in block)
            self._slot_len[sess.slot] = int(start_pos[r] + ext_lens[r])
        toks, lps, st = self._extend_exec(gather_idx, tokens, ext_lens,
                                          start_pos, temps)
        toks_h, lps_h = jax.device_get((toks, lps))

        row_active = np.zeros((R,), bool)
        for r, req in enumerate(reqs):
            tok, lp = int(toks_h[r]), float(lps_h[r])
            finished = (tok == self.eos_id) or (req.max_new_tokens <= 1)
            self._record(req, tok, lp, finished)
            if finished:
                self._finish(req)
            else:
                self.slots[self.sessions[req.session_id].slot] = req
                row_active[r] = True
            # a full re-prefill would have re-processed the whole cached
            # *text* prefix on top of the block (the meta-token prefix is
            # not a prefilled token — exclude it from the savings)
            self.stats.prefill_tokens_saved += \
                int(start_pos[r]) - self.n_prefix
        if self.paged:
            coords = self._build_scatter_coords(slot_idx, S_b, start_pos)
            self._scatter_exec(st, slot_idx, toks, temps, maxnew,
                               row_active, paged_coords=coords)
        else:
            self._scatter_exec(st, slot_idx, toks, temps, maxnew, row_active)
        if self.prefix_cache:
            for req in reqs:
                self._publish_slot_blocks(self.sessions[req.session_id].slot)
        self.stats.extends += 1
        self.stats.extend_requests += n
        self.stats.prefill_tokens += int(ext_lens[:n].sum())

    # ------------------------------------------------------- chunked prefill

    def _start_chunk(self, req: Request, tokens: np.ndarray, slot: int,
                     base: int = 0, resident: bool = False) -> bool:
        """Claim ``slot`` for a chunked prefill of ``tokens`` (cache
        positions [base, base+len)). Reserves only the blocks the FIRST
        chunk covers — the admission-control half of the SLO story: a
        long prompt no longer has to find its whole block footprint free
        at once. Returns False (head waits, backpressure) when even the
        first chunk's blocks cannot be claimed."""
        first = min(self.chunk_prefill, len(tokens))
        if self._kvacct:
            protect = {req.session_id} if req.session_id is not None else ()
            if not self._reserve_slot_blocks(slot, base, first,
                                             protect=protect):
                return False
        self._chunking[slot] = _ChunkedPrefill(
            req=req, tokens=np.asarray(tokens, np.int32), base=base,
            resident=resident, submit_step=req.submit_step,
            start_version=self.policy_version)
        if self.prefix_cache and not resident:
            self._slot_pubver[slot] = self.policy_version
        self._slot_len[slot] = base
        self.stats.chunked_admissions += 1
        return True

    def _admit_chunked_resident(self, req: Request) -> bool:
        """Divert a long resident-session delta to the chunked path: the
        parked slot keeps its cache and the [last history token] + delta
        block streams in chunks from the session's position."""
        sess = self.sessions[req.session_id]
        tokens = np.concatenate([
            sess.tokens[-1:], np.asarray(req.prompt_tokens, np.int32)])
        base = self.n_prefix + len(sess.tokens) - 1
        if not self._start_chunk(req, tokens, sess.slot, base=base,
                                 resident=True):
            return False
        self.pending.popleft()
        sess.last_use = self._next_use()
        return True

    def _advance_chunks(self) -> None:
        """Advance every in-flight chunked prefill by (up to) one chunk,
        highest scheduling priority first, within this tick's chunk-token
        budget. Mid chunks dispatch as no-sample extends; a request's
        last chunk goes through the sampling extend and activates (or
        finishes) the slot. Block reservation is per-chunk; when every
        chunking slot is starved for blocks AND nothing is decoding (so
        no blocks will ever come back), the youngest chunking request is
        sacrificed with ``finish_reason="overflow"`` to break the
        deadlock."""
        while self._chunking:
            order = sorted(
                self._chunking,
                key=lambda s: (self._sched_priority(self._chunking[s].req),
                               self._chunking[s].submit_step, s))
            protect = {cs.req.session_id
                       for cs in self._chunking.values()
                       if cs.req.session_id is not None}
            mid_rows: List[Tuple[int, int]] = []
            fin_rows: List[Tuple[int, int]] = []
            starved: List[int] = []
            for slot in order:
                cs = self._chunking[slot]
                remaining = len(cs.tokens) - cs.written
                take = min(self.chunk_prefill, remaining)
                b = self._budget_for(cs.req)
                if b is not None:
                    if b <= 0:
                        self.stats.sched_budget_deferrals += 1
                        continue
                    take = min(take, b)
                if self._kvacct and not self._reserve_slot_blocks(
                        slot, cs.base + cs.written, take, protect=protect):
                    starved.append(slot)
                    continue
                self._budget_take(cs.req, take)
                if cs.written + take == len(cs.tokens):
                    fin_rows.append((slot, take))
                else:
                    mid_rows.append((slot, take))
            if (starved and not mid_rows and not fin_rows
                    and self.num_active == 0):
                victim = max(starved,
                             key=lambda s: (self._chunking[s].submit_step,
                                            s))
                self._abort_chunk(victim, "overflow")
                continue   # retry with the sacrificed request's blocks
            for S_b, rows in self._bucket_chunk_rows(mid_rows):
                self._chunk_write(rows, S_b)
            for S_b, rows in self._bucket_chunk_rows(fin_rows):
                self._finish_chunk(rows, S_b)
            return

    def _bucket_chunk_rows(self, rows: List[Tuple[int, int]]):
        """Group (slot, take) chunk rows by their extend bucket so each
        group is one fused dispatch (deterministic ascending order)."""
        groups: Dict[int, List[Tuple[int, int]]] = {}
        for slot, take in rows:
            cs = self._chunking[slot]
            S_b = self._extend_bucket(take, cs.base + cs.written)
            groups.setdefault(S_b, []).append((slot, take))
        return sorted(groups.items())

    def _chunk_write(self, rows: List[Tuple[int, int]], S_b: int) -> None:
        """One fused mid-chunk dispatch: write each row's next chunk of
        prompt K/V (no sampling, no RNG), scatter the advanced rows back
        with inert sampling fields, and leave every row inactive."""
        n = len(rows)
        R = _pow2_bucket(n)
        tokens = np.zeros((R, S_b), np.int32)
        ext_lens = np.ones((R,), np.int32)
        start_pos = np.zeros((R,), np.int32)
        gather_idx = np.zeros((R,), np.int32)   # pad rows gather slot 0
        slot_idx = np.full((R,), self.num_slots, np.int32)  # OOB rows drop
        for r, (slot, take) in enumerate(rows):
            cs = self._chunking[slot]
            tokens[r, :take] = cs.tokens[cs.written:cs.written + take]
            ext_lens[r] = take
            start_pos[r] = cs.base + cs.written
            gather_idx[r] = slot
            slot_idx[r] = slot
        st = self._chunk_exec(gather_idx, tokens, ext_lens, start_pos)
        zeros_i = np.zeros((R,), np.int32)
        ones_f = np.ones((R,), np.float32)
        ones_i = np.ones((R,), np.int32)
        row_active = np.zeros((R,), bool)
        if self.paged:
            coords = self._build_scatter_coords(slot_idx, S_b, start_pos)
            self._scatter_exec(st, slot_idx, zeros_i, ones_f, ones_i,
                               row_active, paged_coords=coords,
                               row_gen=zeros_i)
        else:
            self._scatter_exec(st, slot_idx, zeros_i, ones_f, ones_i,
                               row_active, row_gen=zeros_i)
        if self._kvacct:
            # the paged scatter installed each row's full table from host
            # truth (same stale-write hazard as the speculation round)
            covered = {slot for slot, _ in rows}
            self._table_dirty = [t for t in self._table_dirty
                                 if t[0] not in covered]
        for slot, take in rows:
            cs = self._chunking[slot]
            if self.prefix_cache:
                self._slot_toks[slot].extend(
                    int(t) for t in cs.tokens[cs.written:cs.written + take])
            cs.written += take
            self._slot_len[slot] = cs.base + cs.written
            # mid-chunk completions leave behind fully-written blocks —
            # publish them now (chunk size is block-aligned under prefix
            # caching, so every mid chunk ends on a block boundary)
            self._publish_slot_blocks(slot)
            self.stats.chunk_tokens += take
            self.stats.prefill_tokens += take
        self.stats.prefill_chunks += 1

    def _finish_chunk(self, rows: List[Tuple[int, int]], S_b: int) -> None:
        """One fused final-chunk dispatch: the LAST chunk of each row's
        prompt runs through the sampling extend (one RNG split — the
        same split a monolithic admission would have consumed), the
        first token records, and the slot activates (or finishes).
        Session bookkeeping mirrors ``_admit_batch``/``_admit_extend``:
        a fresh chunked prompt stamps ``cache_version`` with the policy
        version AT ADMISSION — if weights updated mid-chunk the cache is
        mixed-policy and the next turn must fall back to a re-prefill."""
        n = len(rows)
        R = _pow2_bucket(n)
        tokens = np.zeros((R, S_b), np.int32)
        ext_lens = np.ones((R,), np.int32)
        start_pos = np.zeros((R,), np.int32)
        temps = np.ones((R,), np.float32)
        maxnew = np.ones((R,), np.int32)
        gather_idx = np.zeros((R,), np.int32)   # pad rows gather slot 0
        slot_idx = np.full((R,), self.num_slots, np.int32)  # OOB rows drop
        for r, (slot, take) in enumerate(rows):
            cs = self._chunking[slot]
            req = cs.req
            tokens[r, :take] = cs.tokens[cs.written:cs.written + take]
            ext_lens[r] = take
            start_pos[r] = cs.base + cs.written
            temps[r] = req.temperature
            maxnew[r] = max(1, req.max_new_tokens)
            gather_idx[r] = slot
            slot_idx[r] = slot
        toks, lps, st = self._extend_exec(gather_idx, tokens, ext_lens,
                                          start_pos, temps)
        toks_h, lps_h = jax.device_get((toks, lps))

        row_active = np.zeros((R,), bool)
        deferred_free: List[int] = []
        for r, (slot, take) in enumerate(rows):
            cs = self._chunking.pop(slot)
            req = cs.req
            if self.prefix_cache:
                self._slot_toks[slot].extend(
                    int(t) for t in cs.tokens[cs.written:cs.written + take])
            cs.written += take
            self._slot_len[slot] = cs.base + cs.written
            self.stats.chunk_tokens += take
            self.stats.prefill_tokens += take
            sess = self._session_of(req)
            if sess is None:
                # session closed (or none): no residency to maintain
                self._slot_session[slot] = None
            elif cs.resident:
                sess.last_use = self._next_use()
                self.stats.prefill_tokens_saved += cs.base - self.n_prefix
            else:
                if len(sess.tokens):
                    self.stats.session_fallbacks += 1
                sess.slot = slot
                sess.last_use = self._next_use()
                sess.cache_version = cs.start_version
                self._slot_session[slot] = req.session_id
            tok, lp = int(toks_h[r]), float(lps_h[r])
            finished = (tok == self.eos_id) or (req.max_new_tokens <= 1)
            self._record(req, tok, lp, finished)
            if finished:
                self._finish(req)
                if self._kvacct and self._slot_session[slot] is None:
                    deferred_free.append(slot)
            else:
                self.slots[slot] = req
                row_active[r] = True
        if self.paged:
            coords = self._build_scatter_coords(slot_idx, S_b, start_pos)
            self._scatter_exec(st, slot_idx, toks, temps, maxnew,
                               row_active, paged_coords=coords)
        else:
            self._scatter_exec(st, slot_idx, toks, temps, maxnew,
                               row_active)
        if self._kvacct:
            for slot, _ in rows:       # publish before any free
                self._publish_slot_blocks(slot)
            for slot in deferred_free:   # write-then-free, as everywhere
                self._free_slot_blocks(slot)
            covered = {slot for slot, _ in rows}
            self._table_dirty = [t for t in self._table_dirty
                                 if t[0] not in covered]
        self.stats.prefill_chunks += 1

    def _abort_chunk(self, slot: int, reason: str) -> None:
        """Tear down an in-flight chunked prefill on a terminal path
        (overflow sacrifice, cancel): the request finishes with
        ``reason`` and zero tokens, the session — if any — loses its
        residency (the partially-written KV is inconsistent with the
        un-updated history), and every reserved block returns to the
        pool."""
        cs = self._chunking.pop(slot)
        req = cs.req
        req.finished = True
        req.finish_reason = reason
        # no _finish(): nothing was generated; session history untouched
        self.completed.append(req)
        if reason == "cancelled":
            self.stats.cancelled += 1
        else:
            self.stats.overflows += 1
        sess = self._session_of(req)
        if sess is not None and sess.slot == slot:
            sess.slot = None
        self._slot_session[slot] = None
        if self._kvacct:
            self._table_dirty = [t for t in self._table_dirty
                                 if t[0] != slot]
            self._free_slot_blocks(slot)
            self._sync_kv_stats()
        self._slot_len[slot] = 0

    def _finish(self, req: Request) -> None:
        """Bank a completed request and update its session: the turn's
        tokens join the host-side history and the slot parks (it is NOT
        freed — the KV cache stays resident for the next turn)."""
        self.completed.append(req)
        sess = self._session_of(req)
        if sess is not None:
            sess.tokens = np.concatenate([
                sess.tokens, np.asarray(req.prompt_tokens, np.int32),
                np.asarray(req.completion, np.int32)])
            sess.last_use = self._next_use()

    def _record(self, req: Request, tok: int, lp: float,
                finished: bool) -> None:
        now = time.perf_counter()
        if not req.completion:
            req.first_token_ts = now
            self.stats.ttft_window.append(now - req.submit_ts)
        else:
            self.stats.itl_window.append(now - req.last_token_ts)
        req.last_token_ts = now
        req.token_ts.append(now)
        req.completion.append(tok)
        req.logprobs.append(lp)
        req.versions.append(self.policy_version)
        self.stats.tokens_generated += 1
        if finished:
            req.finished = True
            req.finish_reason = "eos" if tok == self.eos_id else "length"

    # ------------------------------------------- speculative decoding round

    def _draft_tokens(self, req: Request, k: int) -> np.ndarray:
        """Prompt-lookup drafter: propose up to ``k`` continuation tokens
        from the request's own token history (session history + prompt +
        completion so far). Finds the longest n-gram (n <= spec_ngram)
        ending the history at its EARLIEST other occurrence — the earliest
        match has the longest continuation ahead of it, where the most
        recent match sits near the end of the history and proposes ~1
        token. Pure deterministic host logic: the fused engine and the
        host reference draft identically, which is half the speculative
        parity contract (the shared verify RNG discipline is the other)."""
        parts = [np.asarray(req.prompt_tokens, np.int32)]
        sess = self._session_of(req)
        if sess is not None and len(sess.tokens):
            parts.insert(0, sess.tokens)
        if req.completion:
            parts.append(np.asarray(req.completion, np.int32))
        hist = np.concatenate(parts)
        L = len(hist)
        for n in range(min(self.spec_ngram, L - 1), 0, -1):
            pat = hist[-n:]
            win = hist[:-1]              # exclude the trailing occurrence
            if len(win) < n:
                continue
            view = np.lib.stride_tricks.sliding_window_view(win, n)
            m = np.nonzero((view == pat).all(axis=1))[0]
            if len(m):
                p = int(m[0])
                return hist[p + n:p + n + k].astype(np.int32)
        return np.zeros((0,), np.int32)

    def _speculate(self) -> Tuple[set, int]:
        """One self-drafting speculation round before the decode tick:
        draft candidates per active slot, verify them all in a single
        bucketed extend dispatch sampled at every offset, commit the
        longest accepted prefix (plus the mismatch sample as the free
        bonus/correction token) in bulk, and roll the rejected tail back
        — a ``pos`` rewind on dense rows, plus dropping the tail block
        refs on paged rows (claim-then-release). Every decision feeding
        the dispatch (eligibility, drafts, batch shape) is deterministic
        host logic shared with ``HostReferenceEngine``, so both engines
        consume the verify RNG split — or skip it — in lockstep.

        Returns (slots that went through this round, tokens committed):
        ``step`` skips the decode tick entirely when the round covered
        every active slot — the bonus token already chains each stream
        (the next dispatch feeds ``completion[-1]``), so the tick would
        spend a whole dispatch on work the next round re-derives."""
        if not self._spec_enabled:
            return set(), 0
        S_b = self._spec_bucket
        rows = []                                     # (slot, req, draft)
        pre_blocks: Dict[int, int] = {}
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            start = int(self._slot_len[i])
            # the fixed bucket must respect the extend write contract
            # (start + S_b <= max_seq for every row of the batch)
            if start + S_b > self.max_seq:
                continue
            # never draft past max_new: room leaves space for the round's
            # final (bonus/correction) token
            room = max(1, req.max_new_tokens) - len(req.completion) - 1
            k_r = min(self.spec_draft, room)
            # the SLO token budget: a spec round commits up to k+1 tokens,
            # so cap drafts at budget-1 — chunk writes claimed the budget
            # first this tick, keeping chunked-prefill progress ahead of
            # hot speculation
            b = self._budget_for(req)
            if b is not None:
                k_r = min(k_r, b - 1)
            if k_r < 1:
                continue
            draft = self._draft_tokens(req, k_r)
            if not len(draft):
                continue
            if self._kvacct:
                pre = len(self._slot_blocks[i])
                if not self._reserve_slot_blocks(i, start, 1 + len(draft)):
                    # claim-then-release: restore the exact pre-round
                    # block list and skip this slot's round (pool
                    # backpressure — unreachable at default pool sizing,
                    # where every table fits blocks_per_row)
                    blocks = self._slot_blocks[i]
                    if len(blocks) > pre:
                        self.allocator.free(blocks[pre:])
                        del blocks[pre:]
                    continue
                pre_blocks[i] = pre
            rows.append((i, req, draft))
        if not rows:
            return set(), 0
        n = len(rows)
        R = _pow2_bucket(n)
        tokens = np.zeros((R, S_b), np.int32)
        ext_lens = np.ones((R,), np.int32)
        start_pos = np.zeros((R,), np.int32)
        temps = np.ones((R,), np.float32)
        gather_idx = np.zeros((R,), np.int32)   # pad rows gather slot 0
        slot_idx = np.full((R,), self.num_slots, np.int32)  # OOB rows drop
        for r, (i, req, draft) in enumerate(rows):
            # t0 = the pending last sampled token: recorded host-side in
            # both engines but never yet fed through the model
            tokens[r, 0] = req.completion[-1]
            tokens[r, 1:1 + len(draft)] = draft
            ext_lens[r] = 1 + len(draft)
            start_pos[r] = self._slot_len[i]
            temps[r] = req.temperature
            gather_idx[r] = i
            slot_idx[r] = i
            self.stats.spec_drafted_tokens += len(draft)
        toks, lps, st = self._verify_exec(gather_idx, tokens, ext_lens,
                                          start_pos, temps)
        toks_h, lps_h = jax.device_get((toks, lps))
        self.stats.spec_rounds += 1

        row_active = np.zeros((R,), bool)
        row_last = np.zeros((R,), np.int32)
        row_maxnew = np.ones((R,), np.int32)
        row_gen = np.zeros((R,), np.int32)
        row_pos = np.zeros((R,), np.int32)
        deferred_free: List[int] = []
        committed_total = 0
        for r, (i, req, draft) in enumerate(rows):
            start = int(start_pos[r])
            k_r = len(draft)
            samp = toks_h[r]
            # the sample at offset j IS what a sequential decode would
            # have produced at position start+j+1: draft j is accepted
            # exactly when they agree
            m = 0
            while m < k_r and int(samp[m]) == int(draft[m]):
                m += 1
            committed = 0
            for j in range(m + 1):
                tok = int(samp[j])
                finished = (tok == self.eos_id) or (
                    len(req.completion) + 1 >= max(1, req.max_new_tokens))
                self._record(req, tok, float(lps_h[r][j]), finished)
                committed += 1
                if finished:
                    break
            self.stats.spec_accepted_tokens += min(committed, m)
            self.stats.spec_rejected_tokens += k_r - m
            self.stats.spec_committed_tokens += committed
            committed_total += committed
            self._budget_take(req, committed)
            if self.prefix_cache:
                # the round's fed (KV-committed) tokens: t0 plus the
                # accepted draft prefix — exactly tokens[r, :committed]
                self._slot_toks[i].extend(
                    int(tokens[r, j]) for j in range(committed))
            new_len = start + committed
            self._slot_len[i] = new_len
            row_pos[r] = new_len
            row_last[r] = int(samp[committed - 1])
            row_gen[r] = len(req.completion)
            row_maxnew[r] = max(1, req.max_new_tokens)
            row_active[r] = not req.finished
            if self._kvacct:
                # roll back the rejected tail BEFORE building scatter
                # coords: positions past the kept blocks resolve to the
                # out-of-bounds sentinel and their pool writes drop
                keep = max(self._blocks_for(new_len), pre_blocks[i])
                blocks = self._slot_blocks[i]
                if keep < len(blocks):
                    self.allocator.free(blocks[keep:])
                    del blocks[keep:]
            if req.finished:
                self._finish(req)
                self.slots[i] = None
                sess = self._session_of(req)
                if sess is None or sess.slot != i:
                    self._slot_session[i] = None
                    if self._kvacct:
                        # write-then-free: the commit scatter below still
                        # writes this slot's accepted K/V region
                        deferred_free.append(i)
        # the verify rows advanced pos to start + ext_lens; the commit
        # rewinds it to start + committed. On dense rows this rewind IS
        # the rollback: the k_idx <= pos mask hides the dead tail K/V
        st = dict(st)
        st["pos"] = jnp.asarray(row_pos)
        covered = {i for i, _, _ in rows}
        if self.paged:
            coords = self._build_scatter_coords(slot_idx, S_b, start_pos)
            self._scatter_exec(st, slot_idx, row_last, temps, row_maxnew,
                               row_active, paged_coords=coords,
                               row_gen=row_gen)
        else:
            self._scatter_exec(st, slot_idx, row_last, temps, row_maxnew,
                               row_active, row_gen=row_gen)
        if self._kvacct:
            for i in sorted(covered):  # publish committed full blocks
                self._publish_slot_blocks(i)
            for i in deferred_free:
                self._free_slot_blocks(i)
            # the scatter installed each row's FULL table from host truth
            # (post-rollback), so dirty entries queued for these slots
            # during reservation/COW are redundant — and must not outlive
            # the round: a skipped tick defers the next flush, by which
            # time the slot may have been reassigned (stale-write hazard)
            self._table_dirty = [t for t in self._table_dirty
                                 if t[0] not in covered]
        return covered, committed_total

    # ----------------------------------------------------------------- step

    def step(self) -> int:
        """One engine iteration: admit pending, run one speculation round
        (when enabled), ensure every active slot's next K/V write has an
        exclusively-owned block (paged), decode one token for every
        occupied slot in a single fused dispatch. When the speculation
        round covered EVERY active slot, the decode tick is skipped — each
        covered stream already advanced by the round's committed tokens
        and chains through its bonus token, so the tick would burn a
        dispatch re-deriving the next round's t0 sample. Returns tokens
        generated this step (verify commits + decode tick).

        With chunked prefill enabled, in-flight chunked prompts advance
        by one chunk right after admission — chunk-tokens ride along
        with the decode tick instead of monopolizing it — and the
        per-tick token budget (when set) is claimed by chunk writes
        first, speculation rounds second."""
        self._step_count += 1
        if self._budget_classes is not None:
            self._budget_left = dict(self._budget_classes)
        elif self.prefill_token_budget > 0:
            self._budget_left = {0: self.prefill_token_budget}
        else:
            self._budget_left = None
        self._admit()
        self._advance_chunks()
        self._overflow_full_slots()
        covered, spec_tokens = self._speculate()
        # a verify commit can land a slot exactly at max_seq: overflow it
        # before the tick (same guard, same reason — the tick's write
        # would clamp and corrupt the cache)
        self._overflow_full_slots()
        active = [i for i, r in enumerate(self.slots) if r is not None]
        self.stats.occupancy_trace.append(len(active))
        if not active:
            self._sync_kv_stats()
            return spec_tokens
        if covered and all(i in covered for i in active):
            # multi-token step: every active stream committed through the
            # verify round (the skip decision is shared deterministic
            # host logic, so the reference engine skips — and preserves
            # the RNG split sequence — in lockstep)
            self.stats.spec_saved_ticks += 1
            self._sync_kv_stats()
            return spec_tokens
        self._ensure_decode_blocks()
        # pool starvation may have overflow-finished slots: re-derive the
        # tick's participant list after the block guarantee
        active = [i for i, r in enumerate(self.slots) if r is not None]
        if not active:
            self._sync_kv_stats()
            return spec_tokens
        self._flush_table_updates()
        toks_h, lps_h, fin_h = self._decode_exec()
        for i in active:
            req = self.slots[i]
            if self.prefix_cache:
                # the tick fed the previous sample (completion[-1] before
                # this _record): that's the token whose K/V it wrote
                self._slot_toks[i].append(int(req.completion[-1]))
            self._slot_len[i] += 1          # this tick wrote K/V at wpos
            self._record(req, int(toks_h[i]), float(lps_h[i]), bool(fin_h[i]))
            self._publish_slot_blocks(i)    # tail block may just have filled
            if req.finished:
                self._finish(req)
                self.slots[i] = None
                sess = self._session_of(req)
                if sess is None or sess.slot != i:
                    # no live session to park for -> free the slot (and,
                    # when paged, return its KV blocks to the pool —
                    # published full blocks retire into the prefix cache)
                    self._slot_session[i] = None
                    if self._kvacct:
                        self._free_slot_blocks(i)
        self.stats.decode_steps += 1
        self._sync_kv_stats()
        return spec_tokens + len(active)

    def run_until_idle(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if self.idle:
                # engine teardown gate shared by every test/benchmark
                # drain: no block may leak past the work that owned it
                self.assert_kv_consistent()
                return
            self.step()
        raise RuntimeError("engine did not drain")
