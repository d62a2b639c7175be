"""The engine-phase reduction (``bench/phases.py``) and the readers of the
engine's phase counters: device idle inside ``pool.step`` put down to the
innermost program span, on hand-built events and on a trace recorded on
the CPU backend while a tiny engine serves."""
import glob
import json
import os

import numpy as np
import pytest

from bench import harness, phases, trace
from bench.harness import BENCH_DIR, load_module
from bench.tests import tiny
from bench.tests.test_bench_trace import cpu_ops
from repro.inference import tracing


def reader(name):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))


# window 0-10; two steps. Step one: engine.step 0.5-3.5 holds admit 0.5-2.0
# (with a readback 1.0-1.8 inside it), a gc 2.5-2.7 and the tick 3.0-3.2;
# step two: engine.step 6.5-9.5 holds record 8.0-9.0.
HOST = [("bench.window", 0.0, 10.0),
        ("pool.step", 0.0, 4.0), ("drain", 4.0, 5.0),
        ("tool_wait", 5.0, 6.0), ("pool.step", 6.0, 10.0),
        ("engine.step", 0.5, 3.5), ("engine.admit", 0.5, 2.0),
        ("engine.readback", 1.0, 1.8), ("gc", 2.5, 2.7),
        ("engine.tick", 3.0, 3.2),
        ("engine.step", 6.5, 9.5), ("engine.record", 8.0, 9.0)]
# busy 1.2-1.5 (inside the readback) and 2.2-2.4 in step one, 7.0-8.5 in
# step two
DEV = [("op", 1.2, 1.5), ("op", 2.2, 2.4), ("op", 7.0, 8.5)]


def test_innermost_tiles_nested_spans():
    pieces = phases.innermost([n for n in HOST if phases.is_program_span(
        n[0])])
    assert pieces[:6] == [("engine.admit", 0.5, 1.0),
                          ("engine.readback", 1.0, 1.8),
                          ("engine.admit", 1.8, 2.0),
                          ("engine.step", 2.0, 2.5), ("gc", 2.5, 2.7),
                          ("engine.step", 2.7, 3.0)]
    # two spans opened together: the shorter is the inner one
    assert phases.innermost([("engine.step", 0, 4), ("engine.admit", 0, 1)]
                            ) == [("engine.admit", 0, 1),
                                  ("engine.step", 1, 4)]


def test_idle_in_step_puts_idle_down_to_the_innermost_span():
    idle = phases.idle_in_step(HOST, [DEV])
    expect = {"pool.step:other": 0.5 + 0.5 + 0.5 + 0.5,
              "engine.admit": 0.5 + 0.2,
              "engine.readback": 0.8 - 0.3,
              "engine.step": (0.2 + 0.1 + 0.3 + 0.3) + (0.5 + 0.5),
              "gc": 0.2, "engine.tick": 0.2, "engine.record": 0.5}
    assert idle == pytest.approx(expect)
    s = trace.reduce_events([h for h in HOST if not phases.is_program_span(
        h[0])], [DEV])
    assert sum(idle.values()) == pytest.approx(sum(s.step_idle_s))


def test_idle_inside_a_running_program():
    # a program runs 1.0-2.4: the idle inside it is a stall of the device,
    # not time between programs
    inside = phases.inside_programs(HOST, [DEV], [[(1.0, 2.4)]])
    assert inside == pytest.approx({"engine.readback": 0.2 + 0.3,
                                    "engine.admit": 0.2,
                                    "engine.step": 0.2})


def test_idle_in_step_sums_to_step_idle_on_the_harness_cases():
    # test_bench_trace's hand-built case: no program spans, two devices
    host = [("bench.window", 0.0, 10.0),
            ("pool.step", 0.0, 4.0), ("drain", 4.0, 5.0),
            ("tool_wait", 5.0, 8.0), ("pool.step", 8.0, 10.0)]
    dev0 = [("tick", 1.0, 3.0), ("tick", 2.0, 3.5), ("fork", 8.5, 9.5)]
    dev1 = [("tick", 0.0, 4.0), ("fork", 8.0, 10.0)]
    idle = phases.idle_in_step(host, [dev0, dev1])
    s = trace.reduce_events(host, [dev0, dev1])
    assert idle == {"pool.step:other": pytest.approx(sum(s.step_idle_s))}
    assert s.step_idle_s == pytest.approx([1.5 / 2, 1.0 / 2])
    with pytest.raises(ValueError):
        phases.idle_in_step([], [dev0])


def test_spans_recorded_on_cpu_nest_inside_the_step(monkeypatch):
    """A tiny engine serves inside ``bench.window`` / ``pool.step`` with the
    switch on: its phase spans land on the profiler's clock, inside the
    harness's step spans."""
    c = tiny.cell("mistral-7b-v0.1.rollout")
    _, pool = harness.build_pool(c.config, seed=3)
    for i in range(3):
        pool.submit_request(np.arange(10, 20 + i, dtype=np.int32),
                            max_new_tokens=5)
    pool.step()                       # compile outside the trace
    tr = phases.PhaseTracer(is_op_line=cpu_ops)
    tr.start()
    assert tracing.enabled()
    import jax
    with jax.profiler.TraceAnnotation("bench.window"):
        while not pool.idle:
            with jax.profiler.TraceAnnotation("pool.step"):
                pool.step()
    tr.stop()
    assert not tracing.enabled()
    path, = glob.glob(os.path.join(tr.dir, "**", "*.xplane.pb"),
                      recursive=True)
    r = phases.read(path, cpu_ops)
    steps = [(a, b) for n, a, b in r["host"] if n == "pool.step"]
    spans = [(n, a, b) for n, a, b in r["host"]
             if n.startswith("engine.")]
    names = {n for n, _, _ in spans}
    assert {"engine.step", "engine.admit", "engine.tick",
            "engine.readback", "engine.record"} <= names
    for n, a, b in spans:
        assert any(s0 <= a and b <= s1 for s0, s1 in steps), n
    assert len(steps) == sum(1 for n, _, _ in spans if n == "engine.step")
    idle = r["idle_in_step"]
    assert sum(idle.values()) == pytest.approx(
        sum(r["summary"].step_idle_s))
    assert r["summary"].step_idle_s == pytest.approx(
        trace.read_xplane(path, cpu_ops).step_idle_s)
    tr.read()                         # prints the phases line, cleans up
    assert not os.path.exists(tr.dir)


def test_window_reports_phase_deltas(monkeypatch, capsys):
    monkeypatch.setattr(harness, "drive", harness.drive)
    phases.watch_steps(harness)
    result = tiny.run("mistral-7b-v0.1.rollout", 2 ** 31 + 11,
                      checker=lambda *a: {"correct": True, "compared": {}})
    assert result["correct"]
    line = next(ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("phases: ") and "engine_phase_s" in ln)
    p = json.loads(line[len("phases: "):])["engine_phase_s"]
    assert set(p) == set(tracing.PHASES)
    assert p["step"] > 0 and p["step"] >= p["admit"] + p["tick"]


def counters(**over):
    c = {"prefill_tokens": 900, "prefill_requests": 2, "extend_requests": 7,
         "group_fork_requests": 1, "step_s": 0.5, "admit_s": 0.12,
         "readback_s": 0.1, "tick_s": 0.05}
    c.update(over)
    return c


def record(**over):
    rec = harness.Record(cell="c", t0=10.0, t1=20.0, items=[], steps=[],
                         counters=counters(), occupancy=[16] * 40, slots=16,
                         chips=1, peaks={}, model={}, setup_s=1.0)
    for k, v in over.items():
        setattr(rec, k, v)
    return rec


@pytest.mark.parametrize("name,value", [
    ("step_host_ms.rollout", 1e3 * 0.4 / 40),
    ("step_host_ms.agent", 1e3 * 0.4 / 40),
    ("admit_ms_per_turn.agent", 1e3 * 0.12 / 10)])
def test_phase_readers(name, value):
    assert reader(name).compute(record()) == pytest.approx(value)


@pytest.mark.parametrize("name", ["step_host_ms.rollout",
                                  "step_host_ms.agent",
                                  "admit_ms_per_turn.agent"])
def test_phase_readers_with_nothing_to_read(name):
    # no steps or no turns in the window
    empty = record(occupancy=[], counters=counters(
        prefill_requests=0, extend_requests=0, group_fork_requests=0))
    assert reader(name).compute(empty) is None
    # a program with no phase counters (the metric's source is absent)
    old = {k: v for k, v in counters().items() if not k.endswith("_s")}
    assert reader(name).compute(record(counters=old)) is None


def test_command_is_the_run_command_with_phases(monkeypatch):
    from bench import run
    seen = []

    def main(argv=None):
        seen.append(argv)
        return 0
    monkeypatch.setattr(run, "main", main)
    monkeypatch.setattr(trace, "Tracer", trace.Tracer)
    monkeypatch.setattr(harness, "drive", harness.drive)
    assert phases.main(["--workload", "x"]) == 0
    assert seen == [["--workload", "x"]]
    assert trace.Tracer is phases.PhaseTracer
    assert harness.drive.__name__ == "watched"
