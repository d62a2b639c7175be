"""Model FLOPs of every token served in the window (``bench/work.py``, at
each token's live length) per second, over the cell's chips times the
bf16 peak."""
from bench import work


def compute(rec):
    flops = 0.0
    for it in rec.items:
        if it.refused:
            continue
        for j, ts in enumerate(it.req.token_ts):
            if rec.in_window(ts):
                flops += work.token_flops(rec.model, it.ctx + j)
    peak = rec.chips * rec.peaks["bf16_flops_per_s"]
    return 100.0 * flops / rec.seconds / peak
