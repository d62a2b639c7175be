"""Offline GRPO generation: a backlog of groups, each one prompt sampled
``group_size`` times, submitted so that at least ``queued_groups`` groups
wait beyond what the slots hold. Every group is due when it is submitted.

Sizes come from a fixed set drawn by quantile from the mix's distributions
(``groups`` of them); the seed orders them and draws the token ids. A lead-in
group of fixed size fills the slots first; the window opens once it has
finished and the backlog has taken its slots. Every ``greedy_every``-th
group decodes greedily, so that the check has greedy streams to compare.
"""
from __future__ import annotations


import numpy as np

from bench.draw import pow2_bucket, quantiles
from bench.harness import Item
from bench.check import Sequence


class BacklogGroups:
    def __init__(self, mix: dict, *, seed: int, vocab: int, engine: dict):
        self.mix = mix
        self.G = int(mix["group_size"])
        self.slots = int(engine["slots"])
        self.max_seq = int(engine["max_seq"])
        self.min_bucket = int(engine["min_prefill_bucket"])
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        n = int(mix["groups"])
        plens = self.rng.permutation(quantiles(mix["prompt_len"], n))
        news = self.rng.permutation(quantiles(mix["max_new"], n))
        self.sizes = [(int(p), int(min(m, self.max_seq - p)))
                      for p, m in zip(plens, news)]
        self.next = 0
        self.items: list = []
        self.unfinished = 0
        self.lead: list = []
        self.warmup_limit_s = float(mix["warmup_limit_s"])

    # ------------------------------------------------------------ warm-up

    def prompt(self, n: int) -> np.ndarray:
        return self.rng.integers(2, self.vocab, n).astype(np.int32)

    def warm_shapes(self, pool) -> None:
        """One fork for every prompt bucket the mix can draw and every member
        bucket a (partial) admission can take, each followed by one tick."""
        lo, hi = self.mix["prompt_len"]["min"], self.mix["prompt_len"]["max"]
        lengths = sorted({min(pow2_bucket(n, self.min_bucket), hi)
                          for n in range(lo, hi + 1)})
        members = sorted({pow2_bucket(k, 1) for k in range(1, self.G + 1)})
        for n in lengths:
            for k in members:
                pool.submit_group_request(self.prompt(n), k,
                                          max_new_tokens=2, temperature=1.0)
                _serve_all(pool)

    # ------------------------------------------------------------ traffic

    def _submit(self, pool, plen: int, max_new: int, greedy: bool, now,
                key: int):
        temp = 0.0 if greedy else float(self.mix["temperature"])
        reqs = pool.submit_group_request(self.prompt(plen), self.G,
                                         max_new_tokens=max_new,
                                         temperature=temp)
        items = [Item(req=r, due=now, released=now, ctx=plen,
                      kind="greedy" if greedy else "sampled", key=key)
                 for r in reqs]
        self.items.extend(items)
        self.unfinished += self.G
        return items

    def start(self, pool, now: float) -> None:
        lead = self.mix["lead_in"]
        self.lead = self._submit(pool, int(lead["prompt_len"]),
                                 int(lead["max_new"]), False, now, -1)
        self.release(pool, now)

    def ready(self) -> bool:
        return all(it.req.finished for it in self.lead)

    def release(self, pool, now: float) -> None:
        want = self.slots + int(self.mix["queued_groups"]) * self.G
        while self.unfinished < want:
            i = self.next % len(self.sizes)
            plen, max_new = self.sizes[i]
            greedy = i % int(self.mix["greedy_every"]) == 0
            self._submit(pool, plen, max_new, greedy, now, self.next)
            self.next += 1

    def collect(self, pool, done) -> None:
        self.unfinished -= len(done)

    def next_due(self):
        return None

    # -------------------------------------------------------------- check

    def _finished(self, t0: float, kind=None):
        return [it for it in self.items
                if it.req.finished and it.req.finish_reason in ("length", "eos")
                and it.req.token_ts and it.req.token_ts[-1] >= t0
                and it not in self.lead and (kind is None or it.kind == kind)]

    def unserved(self, t1: float) -> list:
        return []       # a group is due when it is submitted

    def settled(self, t0: float, t1: float) -> bool:
        groups = {it.key for it in self._finished(t0, "greedy")}
        return len(groups) >= int(self.mix["check"]["greedy_groups"])

    def check_sample(self, t0: float, t1: float):
        """Finished requests, drawn from the seed: one member of each of up
        to ``greedy_groups`` greedy groups, then the longest sampled request
        and more sampled ones up to ``requests`` in all."""
        chk = self.mix["check"]
        rng = np.random.default_rng(self.rng.integers(2 ** 32))
        picked, seen = [], set()
        greedy = self._finished(t0, "greedy")
        for j in rng.permutation(len(greedy)):
            it = greedy[j]
            if it.key not in seen:
                seen.add(it.key)
                picked.append(it)
            if len(seen) >= int(chk["greedy_groups"]):
                break
        sampled = self._finished(t0, "sampled")
        if sampled:
            longest = max(sampled, key=lambda it: len(it.req.completion))
            picked.append(longest)
            rest = [it for it in sampled if it is not longest]
            for j in rng.permutation(len(rest)):
                if len(picked) >= int(chk["requests"]):
                    break
                picked.append(rest[j])
        return [Sequence.of_request(it.req) for it in picked]


def _serve_all(pool, max_steps: int = 10_000) -> None:
    for _ in range(max_steps):
        if pool.idle:
            pool.drain_requests()
            return
        pool.step()
    raise RuntimeError("warm-up traffic did not drain")


def make(mix: dict, *, seed: int, vocab: int, engine: dict) -> BacklogGroups:
    return BacklogGroups(mix, seed=seed, vocab=vocab, engine=engine)
