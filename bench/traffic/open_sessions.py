"""Agentic multi-turn sessions: sessions arrive open-loop (Poisson, at
``rate_per_s``); inside a session turns chain closed-loop. A session opens
with one of ``system_prompts`` shared system prompts and a user prompt;
each later turn appends a tool output and asks for the next reply, due
``tool_delay_s`` after the previous reply's last token. At most
``max_open_sessions`` sessions are open at once (the environments a
rollout worker runs); a session that arrives when all are busy waits, and
its first turn stays due from its arrival.

A session ends after its drawn number of turns, or earlier when the next
turn would not fit the engine's ``max_seq`` (its tool output and reply, or
its extend bucket). Sizes, gaps and delays come from fixed sets drawn by
quantile; the seed orders them and draws the token ids. Every
``greedy_every``-th session decodes greedily, for the check.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from bench.check import Sequence
from bench.draw import pow2_bucket, quantiles
from bench.harness import Item


@dataclass
class Session:
    index: int
    arrival: float
    system: int
    user_len: int
    turns: int
    tool_lens: List[int]
    reply_lens: List[int]
    delays: List[float]
    greedy: bool
    sid: Optional[int] = None
    tokens: list = field(default_factory=list)   # conversation so far
    served: list = field(default_factory=list)   # indices of served tokens
    logprobs: list = field(default_factory=list)
    done_turns: int = 0
    next_due: Optional[float] = None
    in_flight: Optional[Item] = None
    closed_at: Optional[float] = None


class OpenSessions:
    def __init__(self, mix: dict, *, seed: int, vocab: int, engine: dict):
        self.mix = mix
        self.vocab = vocab
        self.max_seq = int(engine["max_seq"])
        self.min_bucket = int(engine["min_prefill_bucket"])
        self.rng = np.random.default_rng(seed)
        n = int(mix["sessions"])
        t_max = int(mix["turns"]["max"])
        rng = self.rng
        gaps = rng.permutation(quantiles(
            {"dist": "exponential", "mean": 1.0 / mix["rate_per_s"],
             "float": True}, n))
        users = rng.permutation(quantiles(mix["user_len"], n))
        turns = rng.permutation(quantiles(mix["turns"], n))
        tools = rng.permutation(quantiles(mix["tool_len"], n * t_max))
        replies = rng.permutation(quantiles(mix["reply_len"], n * t_max))
        delays = rng.permutation(quantiles(mix["tool_delay_s"], n * t_max))
        systems = rng.permutation(np.arange(n) % int(mix["system_prompts"]))
        self.system_prompts = [self.prompt(int(mix["system_len"]))
                               for _ in range(int(mix["system_prompts"]))]
        self.gaps = gaps
        self.sessions = [Session(
            index=i, arrival=0.0, system=int(systems[i]),
            user_len=int(users[i]), turns=int(turns[i]),
            tool_lens=[int(x) for x in tools[i * t_max:(i + 1) * t_max]],
            reply_lens=[int(x) for x in replies[i * t_max:(i + 1) * t_max]],
            delays=[float(x) for x in delays[i * t_max:(i + 1) * t_max]],
            greedy=i % int(mix["greedy_every"]) == 0) for i in range(n)]
        self.arrived = 0
        self.waiting: deque = deque()
        self.open: dict = {}
        self.items: list = []
        self.clock0 = None
        self.warmup_limit_s = float(mix["warmup_s"]) + 60.0

    def prompt(self, n: int) -> np.ndarray:
        return self.rng.integers(2, self.vocab, n).astype(np.int32)

    # ------------------------------------------------------------ warm-up

    def warm_shapes(self, pool) -> None:
        """Every dispatch the mix can make, once, before the window:
        prefix-cache misses batched 4, 2 and 1 at a time (which also caches
        the system prompts), a hit for every user-prompt bucket, and
        session extends of every tool-output bucket for 8, 4, 2 and 1
        sessions at once, each followed by one tick."""
        sys_len, mb = int(self.mix["system_len"]), self.min_bucket
        u = self.mix["user_len"]
        first_len = sys_len + int(u["max"])
        batches = [[np.concatenate([p, self.prompt(int(u["max"]))])
                    for p in self.system_prompts]]
        batches += [[self.prompt(first_len) for _ in range(k)]
                    for k in (2, 1)]
        for batch in batches:
            for p in batch:
                pool.submit_request(p, max_new_tokens=2, temperature=1.0)
            _serve_all(pool)
        for n in sorted({pow2_bucket(k, mb)
                         for k in range(u["min"], u["max"] + 1)}):
            pool.submit_request(np.concatenate([self.system_prompts[0],
                                                self.prompt(n)]),
                                max_new_tokens=2, temperature=1.0)
            _serve_all(pool)
        tl = self.mix["tool_len"]
        buckets = sorted({pow2_bucket(1 + k, mb)
                          for k in range(tl["min"], tl["max"] + 1)})
        rows = self.mix["extend_rows"]
        for b in buckets:
            sids = [pool.open_session() for _ in range(max(rows))]
            for sid in sids:
                pool.submit_request(np.concatenate(
                    [self.system_prompts[0], self.prompt(int(u["min"]))]),
                    max_new_tokens=1, temperature=1.0, session=sid)
            _serve_all(pool)
            uses = {sid: 0 for sid in sids}
            for r in rows:
                chosen = sorted(sids, key=lambda s: uses[s])[:r]
                for sid in chosen:
                    uses[sid] += 1
                    pool.submit_request(self.prompt(b - 1), max_new_tokens=2,
                                        temperature=1.0, session=sid)
                _serve_all(pool)
            for sid in sids:
                pool.close_session(sid)

    # ------------------------------------------------------------ traffic

    def start(self, pool, now: float) -> None:
        self.clock0 = now
        t = now
        for s, gap in zip(self.sessions, self.gaps):
            t += float(gap)
            s.arrival = t
        self.release(pool, now)

    def ready(self) -> bool:
        return (self.clock0 is not None and time.perf_counter()
                >= self.clock0 + float(self.mix["warmup_s"]))

    def _submit(self, pool, s: Session, prompt, due: float, now: float):
        reply = s.reply_lens[s.done_turns]
        temp = 0.0 if s.greedy else float(self.mix["temperature"])
        req = pool.submit_request(prompt, max_new_tokens=reply,
                                  temperature=temp, session=s.sid)
        ctx = len(s.tokens) + len(prompt)
        s.tokens.extend(int(t) for t in prompt)
        item = Item(req=req, due=due, released=now, ctx=ctx,
                    kind="greedy" if s.greedy else "sampled", key=s.index)
        s.in_flight, s.next_due = item, None
        self.items.append(item)

    def release(self, pool, now: float) -> None:
        while (self.arrived < len(self.sessions)
               and self.sessions[self.arrived].arrival <= now):
            self.waiting.append(self.sessions[self.arrived])
            self.arrived += 1
        while self.waiting and len(self.open) < int(
                self.mix["max_open_sessions"]):
            s = self.waiting.popleft()
            s.sid = pool.open_session()
            if s.sid is None:
                raise RuntimeError("the engine cannot host sessions")
            self.open[s.sid] = s
            prompt = np.concatenate([self.system_prompts[s.system],
                                     self.prompt(s.user_len)])
            self._submit(pool, s, prompt, s.arrival, now)
        for s in list(self.open.values()):
            if s.next_due is not None and s.next_due <= now:
                tool = self.prompt(s.tool_lens[s.done_turns])
                self._submit(pool, s, tool, s.next_due, now)

    def _fits(self, s: Session) -> bool:
        """The next turn's tool output and reply fit ``max_seq``, and so
        does the power-of-two extend it is dispatched as (the engine would
        otherwise cut the bucket to an odd length)."""
        j = s.done_turns
        tool, reply = s.tool_lens[j], s.reply_lens[j]
        hist = len(s.tokens)
        return (hist + tool + reply <= self.max_seq
                and hist - 1 + pow2_bucket(1 + tool, self.min_bucket)
                <= self.max_seq)

    def collect(self, pool, done) -> None:
        by_req = {id(s.in_flight.req): s for s in self.open.values()
                  if s.in_flight is not None}
        for req in done:
            s = by_req.get(id(req))
            if s is None:
                continue
            start = len(s.tokens)
            s.tokens.extend(int(t) for t in req.completion)
            s.served.extend(range(start, len(s.tokens)))
            s.logprobs.extend(float(x) for x in req.logprobs)
            s.done_turns += 1
            s.in_flight = None
            ok = req.finish_reason in ("length", "eos")
            if ok and s.done_turns < s.turns and self._fits(s):
                s.next_due = req.last_token_ts + s.delays[s.done_turns]
            else:
                s.closed_at = req.last_token_ts
                del self.open[s.sid]
                pool.close_session(s.sid)

    def next_due(self):
        dues = [s.next_due for s in self.open.values()
                if s.next_due is not None]
        if self.arrived < len(self.sessions):
            dues.append(self.sessions[self.arrived].arrival)
        return min(dues) if dues else None

    # -------------------------------------------------------------- check

    def _finished(self, t0: float, greedy=None):
        return [s for s in self.sessions
                if s.closed_at is not None and s.closed_at >= t0 and s.served
                and (greedy is None or s.greedy == greedy)]

    def unserved(self, t1: float) -> list:
        """Turns due before ``t1`` that were never submitted: sessions still
        waiting for an environment, and next turns not yet released."""
        due = [(s, s.arrival) for s in self.waiting if s.arrival < t1]
        due += [(s, s.next_due) for s in self.open.values()
                if s.next_due is not None and s.next_due < t1]
        return [Item(req=None, due=d, released=float("inf"), ctx=0,
                     kind="greedy" if s.greedy else "sampled", key=s.index)
                for s, d in due]

    def settled(self, t0: float, t1: float) -> bool:
        due = [it for it in self.items if t0 <= it.due < t1]
        firsts = all(it.req.token_ts for it in due) and not self.unserved(t1)
        greedy = len(self._finished(t0, True)) >= int(
            self.mix["check"]["greedy_sessions"])
        return firsts and greedy

    def check_sample(self, t0: float, t1: float):
        """Closed sessions, drawn from the seed: up to ``greedy_sessions``
        greedy ones, the longest sampled one and more sampled ones up to
        ``sessions`` in all."""
        chk = self.mix["check"]
        rng = np.random.default_rng(self.rng.integers(2 ** 32))
        greedy = self._finished(t0, True)
        picked = [greedy[j] for j in rng.permutation(len(greedy))[
            :int(chk["greedy_sessions"])]]
        sampled = self._finished(t0, False)
        if sampled:
            longest = max(sampled, key=lambda s: len(s.served))
            picked.append(longest)
            rest = [s for s in sampled if s is not longest]
            for j in rng.permutation(len(rest)):
                if len(picked) >= int(chk["sessions"]):
                    break
                picked.append(rest[j])
        return [Sequence(tokens=np.asarray(s.tokens, np.int32),
                         served=np.asarray(s.served, np.int64),
                         logprobs=np.asarray(s.logprobs, np.float64),
                         greedy=np.full(len(s.served), s.greedy))
                for s in picked]


def _serve_all(pool, max_steps: int = 10_000) -> None:
    for _ in range(max_steps):
        if pool.idle:
            pool.drain_requests()
            return
        pool.step()
    raise RuntimeError("warm-up traffic did not drain")


def make(mix: dict, *, seed: int, vocab: int, engine: dict) -> OpenSessions:
    return OpenSessions(mix, seed=seed, vocab=vocab, engine=engine)
