"""Host milliseconds of one engine step that are not spent waiting on the
device: the engine's own ``step_s`` less its blocking readbacks
(``readback_s``), over the steps in the window (one occupancy entry per
``step()``). Admission, drafting, block-table fixups, the tick's dispatch
and the per-slot record loop; nothing to read where the program keeps no
phase counters."""


def compute(rec):
    c = rec.counters
    if "step_s" not in c or not rec.occupancy:
        return None
    return 1e3 * (c["step_s"] - c["readback_s"]) / len(rec.occupancy)
