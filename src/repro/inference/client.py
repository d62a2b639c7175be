"""Multi-client inference pool (§2.1.4).

The paper found vLLM's built-in multi-node data parallelism plateaued; the
fix was one *entirely independent* server per node with a multi-client on
the orchestrator. This module reproduces that topology: ``InferencePool``
owns N independent ``InferenceEngine`` replicas and dispatches whole
*rollout groups* to the least-loaded engine (pending + active requests) —
long-tailed rollout lengths make blind round-robin pile work onto whichever
engine drew the stragglers. A group's rollouts share a prompt, so keeping
them on one engine maximizes prefix reuse, exactly the paper's
engine-affinity argument — and with group-shared prefill the affinity is
literal: the group is submitted as one ``GroupRequest``, its prompt is
prefilled once, and the KV cache is forked to every member slot. There is
no inter-engine synchronization; weight updates are pushed to each engine
independently (in-flight).

Multi-turn *sessions* are engine-pinned by construction: ``open_session``
picks the least-loaded engine once, and every turn of that conversation is
dispatched to it — the turn's KV cache lives in that engine's slot state,
so there is nothing to migrate (the strongest form of the engine-affinity
argument).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core.rollouts import Rollout, RolloutGroup
from . import tracing
from .engine import (GroupRequest, InferenceEngine, Request,
                     latency_snapshot)


class InferencePool:
    """Least-loaded multi-client over independent engines."""

    def __init__(self, engines: Sequence[InferenceEngine]):
        assert engines, "need at least one engine"
        self.engines = list(engines)
        self._policy_version = self.engines[0].policy_version
        self._next_request_id = 0
        self._next_group_id = 0
        self._next_session_id = 0
        # group_id -> (problem_id, expected, [finished Requests])
        self._groups: Dict[int, tuple] = {}
        self._ungrouped: List[Request] = []
        self._session_engine: Dict[int, InferenceEngine] = {}

    def _pick_engine(self) -> InferenceEngine:
        """Least-loaded dispatch; ties break to the earliest engine."""
        return min(self.engines, key=lambda e: e.load)

    def _make_group_request(self, prompt_tokens: np.ndarray, group_size: int,
                            *, problem_id: str, group_id: int,
                            max_new_tokens: int, temperature: float,
                            sessions: Optional[Sequence[int]] = None,
                            sched_class: str = "rollout") -> GroupRequest:
        prompt = np.asarray(prompt_tokens, np.int32)
        members = []
        for i in range(group_size):
            members.append(Request(
                request_id=self._next_request_id, problem_id=problem_id,
                prompt_tokens=prompt, max_new_tokens=max_new_tokens,
                temperature=temperature, group_id=group_id,
                session_id=sessions[i] if sessions else None,
                sched_class=sched_class))
            self._next_request_id += 1
        return GroupRequest(group_req_id=group_id, problem_id=problem_id,
                            prompt_tokens=prompt, members=members)

    # ------------------------------------------------------------------ api

    def submit_group(self, problem_id: str, prompt_tokens: np.ndarray,
                     group_size: int, *, max_new_tokens: int = 64,
                     temperature: float = 1.0,
                     sched_class: str = "rollout") -> int:
        """Submit one prompt × group_size rollouts to a single engine
        (least-loaded across groups). The group is admitted as a
        ``GroupRequest``: the shared prompt is prefilled once and the KV
        cache forked to every member slot — the strongest form of the
        prefix-affinity argument that already kept groups together."""
        gid = self._next_group_id
        self._next_group_id += 1
        greq = self._make_group_request(
            prompt_tokens, group_size, problem_id=problem_id, group_id=gid,
            max_new_tokens=max_new_tokens, temperature=temperature,
            sched_class=sched_class)
        self._pick_engine().submit_group(greq)
        self._groups[gid] = (problem_id, group_size, [])
        return gid

    def submit_group_request(self, prompt_tokens: np.ndarray,
                             group_size: int, *, max_new_tokens: int = 64,
                             temperature: float = 1.0, problem_id: str = "",
                             sessions: Optional[Sequence[int]] = None,
                             sched_class: str = "rollout"
                             ) -> List[Request]:
        """Group-shared-prefill variant of ``submit_request``: one
        GroupRequest whose members surface individually via
        ``drain_requests`` (the asyncio client resolves one future per
        member). When ``sessions`` is given (one id per member, all opened
        via ``open_group_sessions`` so they share an engine) the fork
        seeds every member's session residency."""
        if sessions is not None:
            assert len(sessions) == group_size, "one session per member"
            eng = self._session_engine[sessions[0]]
        else:
            eng = self._pick_engine()
        greq = self._make_group_request(
            prompt_tokens, group_size, problem_id=problem_id, group_id=-1,
            max_new_tokens=max_new_tokens, temperature=temperature,
            sessions=sessions, sched_class=sched_class)
        eng.submit_group(greq)
        return list(greq.members)

    def open_session(self) -> Optional[int]:
        """Open a multi-turn session pinned to the least-loaded engine.
        Returns None when the engine config cannot host sessions (the
        caller falls back to full-context turns)."""
        eng = self._pick_engine()
        if not eng.supports_sessions:
            return None
        sid = self._next_session_id
        self._next_session_id += 1
        eng.open_session(sid)
        self._session_engine[sid] = eng
        return sid

    def open_group_sessions(self, group_size: int) -> Optional[List[int]]:
        """Open ``group_size`` multi-turn sessions pinned to ONE engine —
        a GRPO group of agentic rollouts. Sharing an engine is what lets
        ``submit_group_request(..., sessions=...)`` fork the shared first
        turn into every member's session. Returns None when the chosen
        engine cannot host sessions (callers fall back per member)."""
        eng = self._pick_engine()
        if not eng.supports_sessions:
            return None
        sids = []
        for _ in range(group_size):
            sid = self._next_session_id
            self._next_session_id += 1
            eng.open_session(sid)
            self._session_engine[sid] = eng
            sids.append(sid)
        return sids

    def close_session(self, session_id: int) -> None:
        eng = self._session_engine.pop(session_id, None)
        if eng is not None:
            eng.close_session(session_id)

    def submit_request(self, prompt_tokens: np.ndarray, *,
                       max_new_tokens: int = 64, temperature: float = 1.0,
                       problem_id: str = "",
                       session: Optional[int] = None,
                       sched_class: str = "rollout") -> Request:
        """Submit a single ungrouped request (least-loaded, or pinned to
        its session's engine). Used by the asyncio rollout client;
        completion surfaces via drain_requests. ``sched_class``
        ("interactive" | "rollout") feeds the engines' SLO scheduler:
        interactive work is admitted and chunk-scheduled ahead of
        unpromoted rollout work."""
        req = Request(
            request_id=self._next_request_id, problem_id=problem_id,
            prompt_tokens=np.asarray(prompt_tokens, np.int32),
            max_new_tokens=max_new_tokens, temperature=temperature,
            group_id=-1, session_id=session, sched_class=sched_class)
        self._next_request_id += 1
        eng = (self._session_engine[session] if session is not None
               else self._pick_engine())
        eng.submit(req)
        return req

    def cancel(self, request_id: int) -> bool:
        """Cancel an ungrouped request wherever it lives (queued, mid
        chunked-prefill, or decoding). True when some engine found it."""
        return any(eng.cancel(request_id) for eng in self.engines)

    def latency_snapshot(self) -> dict:
        """Pool-level TTFT/ITL percentiles over the engines' current
        measurement windows (seconds; since the last reset)."""
        ttft = [x for e in self.engines for x in e.stats.ttft_window]
        itl = [x for e in self.engines for x in e.stats.itl_window]
        return latency_snapshot(ttft, itl)

    def reset_latency_windows(self) -> None:
        """Start a fresh steady-state measurement window on every engine
        (drop warmup/compile-skewed samples)."""
        for eng in self.engines:
            eng.stats.reset_window()

    def _collect(self) -> None:
        for eng in self.engines:
            for req in eng.drain_completed():
                if req.group_id < 0:
                    self._ungrouped.append(req)
                else:
                    self._groups[req.group_id][2].append(req)

    def drain_requests(self) -> List[Request]:
        """Finished ungrouped requests (group requests stay internal)."""
        self._collect()
        out, self._ungrouped = self._ungrouped, []
        return out

    def step(self) -> int:
        """Advance every engine one decode step. Returns tokens generated."""
        return sum(eng.step() for eng in self.engines)

    def update_weights(self, params, version: int) -> None:
        """Push a policy update to every engine, relay-then-commit.

        Phase 1 DISPATCHES every engine's reshard (``relay_weights`` is an
        async device-to-device ``device_put`` into each engine's serving
        layout — no host gather, no blocking), so the transfers overlap
        instead of running as the old sequential per-engine loop. Phase 2
        commits them all, then bumps ONE pool-level version counter: an
        engine can never observe a torn pool version (some engines on v+1
        while ``policy_version`` still reads an older engine's v)."""
        placed = [eng.relay_weights(params) for eng in self.engines]
        for eng, p in zip(self.engines, placed):
            eng.commit_weights(p, version)
        self._policy_version = version

    @property
    def idle(self) -> bool:
        return all(e.idle for e in self.engines)

    @property
    def policy_version(self) -> int:
        return self._policy_version

    def drain_groups(self) -> List[RolloutGroup]:
        """Collect completed requests and return any fully-finished groups."""
        self._collect()
        finished = []
        for gid in list(self._groups):
            pid, size, done = self._groups[gid]
            if len(done) == size:
                finished.append(RolloutGroup(pid, [
                    _to_rollout(r) for r in done]))
                del self._groups[gid]
        return finished

    def stats(self) -> dict:
        return {
            "engines": len(self.engines),
            "decode_steps": [e.stats.decode_steps for e in self.engines],
            "tokens": sum(e.stats.tokens_generated for e in self.engines),
            "weight_updates": [e.stats.weight_updates for e in self.engines],
            "occupancy": [e.stats.occupancy_trace for e in self.engines],
            "prefill_batches": [e.stats.prefills for e in self.engines],
            "prefill_requests": [e.stats.prefill_requests
                                 for e in self.engines],
            "prefill_traces": [e.stats.prefill_traces for e in self.engines],
            "extends": [e.stats.extends for e in self.engines],
            "extend_requests": [e.stats.extend_requests
                                for e in self.engines],
            "prefill_tokens": sum(e.stats.prefill_tokens
                                  for e in self.engines),
            "prefill_tokens_saved": sum(e.stats.prefill_tokens_saved
                                        for e in self.engines),
            "session_evictions": sum(e.stats.session_evictions
                                     for e in self.engines),
            "session_fallbacks": sum(e.stats.session_fallbacks
                                     for e in self.engines),
            "overflows": sum(e.stats.overflows for e in self.engines),
            "group_prefills": sum(e.stats.group_prefills
                                  for e in self.engines),
            "group_fork_requests": sum(e.stats.group_fork_requests
                                       for e in self.engines),
            "group_partial_admissions": sum(e.stats.group_partial_admissions
                                            for e in self.engines),
            "group_prefill_tokens_saved": sum(
                e.stats.group_prefill_tokens_saved for e in self.engines),
            "kv_blocks_total": sum(e.stats.kv_blocks_total
                                   for e in self.engines),
            "kv_blocks_in_use": sum(e.stats.kv_blocks_in_use
                                    for e in self.engines),
            "kv_blocks_peak": sum(e.stats.kv_blocks_peak
                                  for e in self.engines),
            "kv_bytes": sum(e.stats.kv_bytes for e in self.engines),
            "pageable_kv_bytes": sum(e.stats.pageable_kv_bytes
                                     for e in self.engines),
            "pooled_state_bytes": sum(e.stats.pooled_state_bytes
                                      for e in self.engines),
            "parked_state_bytes": sum(e.stats.parked_state_bytes
                                      for e in self.engines),
            "mesh_shapes": [e.stats.mesh_shape for e in self.engines],
            "kv_bytes_per_shard": [e.stats.kv_bytes_per_shard
                                   for e in self.engines],
            "cow_forks": sum(e.stats.cow_forks for e in self.engines),
            "blocks_freed_on_evict": sum(e.stats.blocks_freed_on_evict
                                         for e in self.engines),
            # speculative decoding (all zero when spec_draft=0)
            "spec_rounds": sum(e.stats.spec_rounds for e in self.engines),
            "spec_drafted_tokens": sum(e.stats.spec_drafted_tokens
                                       for e in self.engines),
            "spec_accepted_tokens": sum(e.stats.spec_accepted_tokens
                                        for e in self.engines),
            "spec_rejected_tokens": sum(e.stats.spec_rejected_tokens
                                        for e in self.engines),
            "spec_committed_tokens": sum(e.stats.spec_committed_tokens
                                         for e in self.engines),
            "spec_saved_ticks": sum(e.stats.spec_saved_ticks
                                    for e in self.engines),
            # chunked prefill + SLO scheduler (zero when chunk_prefill=0)
            "chunked_admissions": sum(e.stats.chunked_admissions
                                      for e in self.engines),
            "prefill_chunks": sum(e.stats.prefill_chunks
                                  for e in self.engines),
            "chunk_tokens": sum(e.stats.chunk_tokens for e in self.engines),
            "sched_promotions": sum(e.stats.sched_promotions
                                    for e in self.engines),
            "sched_budget_deferrals": sum(e.stats.sched_budget_deferrals
                                          for e in self.engines),
            "cancelled": sum(e.stats.cancelled for e in self.engines),
            # automatic prefix caching (all zero when prefix_cache=False)
            "prefix_cache_hits": sum(e.stats.prefix_cache_hits
                                     for e in self.engines),
            "prefix_cache_misses": sum(e.stats.prefix_cache_misses
                                       for e in self.engines),
            "prefix_cache_hit_tokens": sum(e.stats.prefix_cache_hit_tokens
                                           for e in self.engines),
            "prefix_cache_cached_blocks": sum(
                e.stats.prefix_cache_cached_blocks for e in self.engines),
            "prefix_cache_retired": sum(e.stats.prefix_cache_retired
                                        for e in self.engines),
            "prefix_cache_reclaimed": sum(e.stats.prefix_cache_reclaimed
                                          for e in self.engines),
            "prefix_cache_swept": sum(e.stats.prefix_cache_swept
                                      for e in self.engines),
            # host seconds per phase of the engines' steps, summed
            "phase_s": {p: sum(getattr(e.stats, p + "_s")
                               for e in self.engines)
                        for p in tracing.PHASES},
            "latency": self.latency_snapshot(),
        }


def _to_rollout(req: Request) -> Rollout:
    return Rollout(
        problem_id=req.problem_id,
        prompt_tokens=np.asarray(req.prompt_tokens, np.int32),
        completion_tokens=np.asarray(req.completion, np.int32),
        infer_logprobs=np.asarray(req.logprobs, np.float32),
        policy_versions=np.asarray(req.versions, np.int32),
        info={"finish_reason": req.finish_reason},
    )
