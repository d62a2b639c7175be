"""The trace reduction: busy union, idle share, idle gaps attributed to
the harness's spans, on hand-built events and on a trace recorded on the
CPU backend."""
import time

import pytest

from bench import trace


def test_union_and_complement():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert trace.complement([(0, 4), (5, 6)], 0, 10) == [(4, 5), (6, 10)]
    assert trace.complement([(1, 2)], 0, 1.5) == [(0, 1)]


def test_reduce_hand_built_events():
    host = [("bench.window", 0.0, 10.0),
            ("pool.step", 0.0, 4.0), ("drain", 4.0, 5.0),
            ("tool_wait", 5.0, 8.0), ("pool.step", 8.0, 10.0)]
    dev0 = [("tick", 1.0, 3.0), ("tick", 2.0, 3.5), ("fork", 8.5, 9.5)]
    dev1 = [("tick", 0.0, 4.0), ("fork", 8.0, 10.0)]
    s = trace.reduce_events(host, [dev0, dev1])
    # busy: device 0 2.5 + 1 s, device 1 4 + 2 s; mean over the two
    assert s.busy_s == pytest.approx((3.5 + 6.0) / 2)
    assert s.window_s == 10.0
    assert s.idle_share == pytest.approx(1 - 4.75 / 10)
    # device 0 idles 0-1 and 3.5-4 in step one, 8-8.5 and 9.5-10 in step
    # two; device 1 never inside a step
    assert s.step_idle_s == pytest.approx([1.5 / 2, 1.0 / 2])
    assert s.idle_by_span["drain"] == pytest.approx(1.0)
    assert s.idle_by_span["tool_wait"] == pytest.approx(3.0)
    # operation time is summed per operation (overlaps count twice)
    assert s.op_seconds["tick"] == pytest.approx((2.0 + 1.5 + 4.0) / 2)
    b = s.breakdown()
    assert b["idle_gaps"][0] == ["tool_wait", pytest.approx(3.0)]
    assert [n for n, _ in b["device_ops"]] == ["tick", "fork"]


def test_collective_seconds_from_hlo_names():
    host = [("bench.window", 0.0, 10.0)]
    ops = [("jit_step:all-gather.3", 0.0, 1.0),
           ("jit_step:all-reduce-start.1", 1.0, 1.5),
           ("jit_step:all-reduce-done.1", 2.0, 2.25),
           ("reduce-scatter", 3.0, 3.5), ("all-to-all.7", 4.0, 4.125),
           ("jit_step:collective-permute-done.2", 5.0, 5.0625),
           ("jit_step:fusion.2", 6.0, 8.5), ("copy-start.1", 8.5, 9.0),
           ("jit_step:all-gather-fusion.1", 9.0, 9.5)]
    s = trace.reduce_events(host, [ops, ops[:1]])
    # device 0: 1 + 0.5 + 0.25 + 0.5 + 0.125 + 0.0625; device 1: 1
    assert s.collective_s == pytest.approx((2.4375 + 1.0) / 2)
    # the breakdown is as it was: the top operations by name
    assert s.breakdown()["device_ops"][0][0] == "jit_step:fusion.2"


def test_reduce_clips_to_the_window_and_needs_one():
    host = [("bench.window", 2.0, 4.0)]
    s = trace.reduce_events(host, [[("op", 0.0, 3.0), ("op", 3.5, 9.0)]])
    assert s.busy_s == pytest.approx(1.5) and s.window_s == 2.0
    assert s.idle_by_span == {"other": pytest.approx(0.5)}
    with pytest.raises(ValueError):
        trace.reduce_events([], [[("op", 0.0, 1.0)]])


def test_op_names_carry_their_program():
    mods = [(0, 100, "jit__tick_impl(42)")]
    assert trace.op_name("%while.2 = (s32[]) while(%t)", 50, mods, [0]) \
        == "jit__tick_impl:while.2"
    assert trace.op_name("%copy.8 = bf16[4] copy(%x)", 150, mods, [0]) \
        == "copy.8"


def cpu_ops(plane, line):
    return plane == "/host:CPU" and line.startswith("tf_XLAPjRtCpuClient")


def test_trace_recorded_on_cpu():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    tr = trace.Tracer(is_op_line=cpu_ops)
    tr.start()
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("pool.step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("tool_wait"):
                time.sleep(0.05)
    tr.stop()
    s = tr.read()
    assert 0.15 <= s.window_s < 5.0
    assert 0 < s.busy_s < s.window_s
    assert s.devices == 1 and len(s.step_idle_s) == 3
    # the sleeps are idle time, and the harness knows what it was doing
    assert s.idle_by_span["tool_wait"] >= 0.14
    assert s.idle_share > 0.14 / s.window_s
    assert any("dot" in name or "fusion" in name for name in s.op_seconds)
