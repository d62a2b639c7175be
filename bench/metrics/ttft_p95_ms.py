"""95th percentile, over every request due in the window, of its first
token's timestamp minus the time it was due (not when it was submitted).
A request refused, failed without a token, or still without one when the
harness stops waiting counts as infinitely late."""
import math

from bench.harness import FINISHED_OK, percentile


def compute(rec):
    due = rec.due_in_window()
    if not due:
        return None
    lat = []
    for it in due:
        if it.refused or not it.req.token_ts or (
                it.req.finished and it.req.finish_reason not in FINISHED_OK):
            lat.append(math.inf)
        else:
            lat.append(it.req.token_ts[0] - it.due)
    return 1e3 * percentile(lat, 95)
