"""Prompt tokens the engine ran through prefill or extend in the window,
per turn it admitted: session residency and prefix-cache hits lower it."""


def compute(rec):
    c = rec.counters
    turns = c["prefill_requests"] + c["extend_requests"] \
        + c["group_fork_requests"]
    if not turns:
        return None
    return c["prefill_tokens"] / turns
