"""Mean share of the engine's slots decoding, over the window's ticks
(the engine's own per-tick occupancy trace)."""


def compute(rec):
    if not rec.occupancy:
        return None
    return 100.0 * sum(rec.occupancy) / (len(rec.occupancy) * rec.slots)
