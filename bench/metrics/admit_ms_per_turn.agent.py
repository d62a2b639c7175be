"""Milliseconds the engine spent in admission (``admit_s``: prefills,
session extends and group forks with their readbacks) per turn it admitted
in the window; nothing to read where the program keeps no phase
counters."""


def compute(rec):
    c = rec.counters
    turns = c["prefill_requests"] + c["extend_requests"] \
        + c["group_fork_requests"]
    if "admit_s" not in c or not turns:
        return None
    return 1e3 * c["admit_s"] / turns
