"""Share of the traced window in which no operation ran on the device
(mean over the cell's chips)."""


def compute(rec):
    if rec.trace is None:
        return None
    return 100.0 * rec.trace.idle_share
