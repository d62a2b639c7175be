"""95th percentile of every gap between consecutive tokens of one request,
both inside the window."""
from bench.harness import percentile


def compute(rec):
    gaps = []
    for it in rec.items:
        if it.refused:
            continue
        ts = [t for t in it.req.token_ts if rec.in_window(t)]
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    if not gaps:
        return None
    return 1e3 * percentile(gaps, 95)
