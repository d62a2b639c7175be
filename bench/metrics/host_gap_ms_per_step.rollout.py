"""Device-idle time inside the harness's span around each ``pool.step()``,
per step, from the trace: the host work of one engine step that the device
waits for (admission, scheduling, the per-tick readback)."""


def compute(rec):
    t = rec.trace
    if t is None or not t.step_idle_s:
        return None
    return 1e3 * sum(t.step_idle_s) / len(t.step_idle_s)
