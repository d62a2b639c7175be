"""A stand-in for ``InferencePool`` with no model: each step serves one
token to every running request, so traffic generators can be tested
without JAX."""
from __future__ import annotations

import itertools

import numpy as np


class FakeRequest:
    def __init__(self, prompt, max_new, temperature, session=None):
        self.prompt_tokens = np.asarray(prompt, np.int32)
        self.max_new_tokens = max_new
        self.temperature = temperature
        self.session_id = session
        self.completion, self.logprobs, self.token_ts = [], [], []
        self.finished, self.finish_reason = False, ""
        self.first_token_ts = self.last_token_ts = 0.0


class FakePool:
    def __init__(self, clock):
        self.clock = clock            # callable: the current second
        self.running, self.done = [], []
        self.sessions = {}
        self.closed = []
        self.submitted = []
        self._sid = itertools.count()

    def submit_group_request(self, prompt, group_size, *, max_new_tokens,
                             temperature):
        reqs = [FakeRequest(prompt, max_new_tokens, temperature)
                for _ in range(group_size)]
        self.running += reqs
        self.submitted.append(("group", reqs))
        return reqs

    def submit_request(self, prompt, *, max_new_tokens, temperature,
                       session=None):
        req = FakeRequest(prompt, max_new_tokens, temperature, session)
        self.running.append(req)
        self.submitted.append(("request", [req]))
        return req

    def open_session(self):
        sid = next(self._sid)
        self.sessions[sid] = True
        return sid

    def close_session(self, sid):
        self.closed.append(sid)
        del self.sessions[sid]

    @property
    def idle(self):
        return not self.running

    def step(self):
        now = self.clock()
        for r in self.running:
            r.completion.append(7)
            r.logprobs.append(-1.0)
            r.token_ts.append(now)
            r.last_token_ts = now
            if len(r.completion) >= r.max_new_tokens:
                r.finished, r.finish_reason = True, "length"
        self.done += [r for r in self.running if r.finished]
        self.running = [r for r in self.running if not r.finished]
        return len(self.running)

    def drain_requests(self):
        out, self.done = self.done, []
        return out
