"""The decode ticks' least time over the window's seconds. A tick's least
time is the larger of its FLOPs over the cell's chips' summed peak and its
bytes over their summed bandwidth (``bench/work.py``): every weight it uses
once, and the K/V of each request's live positions. The requests a tick
served, and their live lengths, come from the harness's record: a
request's token ``j >= 1`` stamped inside a ``pool.step()`` came from that
step's tick."""
import bisect

from bench import work


def compute(rec):
    if not rec.steps:
        return None
    starts = [a for a, _ in rec.steps]
    live = [[] for _ in rec.steps]
    for it in rec.items:
        if it.refused:
            continue
        for j, ts in enumerate(it.req.token_ts):
            if j == 0 or not rec.in_window(ts):
                continue
            k = bisect.bisect_right(starts, ts) - 1
            if k >= 0 and ts <= rec.steps[k][1]:
                live[k].append(it.ctx + j)
    least = sum(work.least_seconds(rec.model, lv, rec.peaks, rec.chips)
                for lv in live if lv)
    return 100.0 * least / rec.seconds
