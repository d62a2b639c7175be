"""Rollout tokens per second: every served token whose engine timestamp
falls in the window, from finished and unfinished requests alike, over the
window's seconds."""


def compute(rec):
    n = sum(1 for it in rec.items if not it.refused
            for ts in it.req.token_ts if rec.in_window(ts))
    return n / rec.seconds
