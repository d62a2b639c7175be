"""One run of one benchmark cell with the engine's own phase spans on.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The run is ``bench/run.py``'s, with these additions. A traced run switches
``repro.inference.tracing`` on for the window, so that every phase of
``InferenceEngine.step`` (``engine.admit``, ``engine.readback``, ...) and
every garbage collection (``gc``) is a span on the profiler's clock, and
prints a ``phases:`` line: the device idle inside ``pool.step`` put down
to the innermost program span open at each instant (``idle_in_step``),
the part of it that fell while an XLA program ran (``inside_programs``),
each span's total seconds (``span_s``) and the names of the stats the
device's operations carry (``op_stats``: on a TPU v5e none carries a
``jax.named_scope`` path, so device work is known only by its HLO
instruction). Every run prints the window deltas of the engine's phase
counters (``engine_phase_s``) and the phase split of each engine step
over ``LONG_STEP_S``. The result line is ``bench/run.py``'s.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import heapq  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace  # noqa: E402

OTHER = "pool.step:other"   # idle in a step under no program span
LONG_STEP_S = 1.0

Span = Tuple[str, float, float]


def is_program_span(name: str) -> bool:
    return name.startswith("engine.") or name == "gc"


def innermost(spans: List[Span]) -> List[Span]:
    """Tile the time that ``spans`` cover into (name, start, end) pieces,
    each named after the innermost span open there: the one opened last
    (of two opened together, the shorter)."""
    bounds = sorted({t for _, a, b in spans for t in (a, b)})
    order = sorted(spans, key=lambda s: s[1])
    heap: list = []
    k = 0
    out: List[Span] = []
    for t0, t1 in zip(bounds, bounds[1:]):
        while k < len(order) and order[k][1] <= t0:
            n, a, b = order[k]
            heapq.heappush(heap, (-a, b, n))
            k += 1
        while heap and heap[0][1] <= t0:
            heapq.heappop(heap)
        if heap:
            name = heap[0][2]
            if out and out[-1][0] == name and out[-1][2] == t0:
                out[-1] = (name, out[-1][1], t1)
            else:
                out.append((name, t0, t1))
    return out


def intersect(xs, ys) -> List[Tuple[float, float]]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_in_step(host: List[Span], devices: List[List[Span]]
                 ) -> Dict[str, float]:
    """Device-idle seconds inside the harness's ``pool.step`` spans, put
    down to the innermost ``engine.*`` or ``gc`` span open at each instant,
    and to ``pool.step:other`` where none is; mean over the devices, so
    the values sum to ``sum(reduce_events(...).step_idle_s)``."""
    windows = [(a, b) for n, a, b in host if n == trace.WINDOW]
    if not windows or not devices:
        raise ValueError("trace holds no window span or no device")
    lo, hi = windows[0]
    steps = trace.union([(max(a, lo), min(b, hi)) for n, a, b in host
                         if n == "pool.step" and b > lo and a < hi])
    pieces = innermost([(n, a, b) for n, a, b in host
                        if is_program_span(n) and b > lo and a < hi])
    starts = [a for _, a, _ in pieces]
    out: Dict[str, float] = defaultdict(float)
    for evs in devices:
        busy = trace.union([(max(a, lo), min(b, hi)) for _, a, b in evs
                            if b > lo and a < hi])
        for g0, g1 in intersect(trace.complement(busy, lo, hi), steps):
            left = g1 - g0
            k = max(0, bisect.bisect_right(starts, g0) - 1)
            while k < len(pieces) and pieces[k][1] < g1:
                ov = trace.overlap(g0, g1, pieces[k][1], pieces[k][2])
                if ov > 0:
                    out[pieces[k][0]] += ov
                    left -= ov
                k += 1
            if left > 1e-12:
                out[OTHER] += left
    nd = len(devices)
    return {k: v / nd for k, v in out.items()}


def inside_programs(host: List[Span], devices: List[List[Span]],
                    programs: List[List[Tuple[float, float]]]
                    ) -> Dict[str, float]:
    """The part of ``idle_in_step`` that falls while an XLA program runs
    on the device (its ``XLA Modules`` span): the device stalls inside a
    program, where the rest is time between programs."""
    lo = min((a for _, a, _ in host), default=0.0)
    hi = max((b for _, _, b in host), default=0.0)
    outside = [[("", a, b) for a, b in trace.complement(
        trace.union(list(progs)), lo, hi)] for progs in programs]
    return idle_in_step(host, [d + o for d, o in zip(devices, outside)])


def top(d: Dict[str, float], n: int = trace.TOP) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def read(path: str, is_op_line=trace.tpu_op_lines) -> dict:
    """Reduce one ``.xplane.pb``: the harness's summary (exactly what
    ``trace.read_xplane`` gives), the host spans, ``idle_in_step`` and the
    names of the stats on the device's operations."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host: List[Span] = []
    devices, programs = [], []
    stat_names = set()
    for plane in data.planes:
        modules = []
        for line in plane.lines:
            if line.name == trace.MODULES:
                modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name) for e in line.events)
        starts = [m[0] for m in modules]
        dev, found = [], False
        for line in plane.lines:
            if is_op_line(plane.name, line.name):
                found = True
                for e in line.events:
                    a = e.start_ns * 1e-9
                    b = (e.start_ns + e.duration_ns) * 1e-9
                    dev.append((trace.op_name(e.name, e.start_ns, modules,
                                              starts), a, b))
                    stat_names.update(k for k, _ in e.stats)
            elif plane.name == "/host:CPU":
                host.extend((e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events
                            if e.name == trace.WINDOW or e.name in trace.SPANS
                            or is_program_span(e.name))
        if found:
            devices.append(dev)
            programs.append([(a * 1e-9, b * 1e-9) for a, b, _ in modules])
    summary = trace.reduce_events(
        [s for s in host if not is_program_span(s[0])], devices)
    return {"summary": summary, "host": host,
            "idle_in_step": idle_in_step(host, devices),
            "inside_programs": inside_programs(host, devices, programs),
            "op_stats": sorted(stat_names)}


class PhaseTracer(trace.Tracer):
    """``trace.Tracer`` with the engine's spans switched on while it
    records, and this module's splits printed when it is read."""

    def start(self) -> None:
        from repro.inference import tracing
        tracing.enable(True)
        super().start()

    def stop(self) -> None:
        from repro.inference import tracing
        super().stop()
        tracing.enable(False)

    def read(self):
        import glob
        import os
        import shutil
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if len(paths) != 1:
                raise ValueError(f"expected one trace file, found {paths}")
            size = os.path.getsize(paths[0])
            print(f"trace: {size} bytes", flush=True)
            r = read(paths[0], self.is_op_line)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        s = r["summary"]
        idle = r["idle_in_step"]
        span_s: Dict[str, float] = defaultdict(float)
        for n, a, b in r["host"]:
            if is_program_span(n):
                span_s[n] += b - a
        print("phases: " + json.dumps({
            "trace_bytes": size, "step_idle_s": sum(s.step_idle_s),
            "idle_in_step": top(idle, 16),
            "idle_in_step_named_share": (
                sum(v for k, v in idle.items()
                    if k not in (OTHER, "engine.step"))
                / max(sum(idle.values()), 1e-12)),
            "inside_programs": top(r["inside_programs"], 16),
            "span_s": top(span_s, 16),
            "op_stats": r["op_stats"]}), flush=True)
        return s


def phase_seconds(pool) -> Dict[str, float]:
    from repro.inference.tracing import PHASES
    return {p: sum(getattr(e.stats, p + "_s") for e in pool.engines)
            for p in PHASES}


def watch_steps(harness) -> None:
    """Wrap the harness's ``drive`` so that the measured window reports its
    phase counters' deltas and the phase split of every long step."""
    drive = harness.drive

    def watched(pool, traffic, until, spans, steps=None, stop=None):
        if steps is None:
            return drive(pool, traffic, until, spans, steps, stop)
        inner = pool.step
        long_steps = []

        def step():
            p0, t = phase_seconds(pool), time.perf_counter()
            n = inner()
            dt = time.perf_counter() - t
            if dt > LONG_STEP_S:
                p1 = phase_seconds(pool)
                long_steps.append({"s": dt, "phase_s": {
                    k: p1[k] - p0[k] for k in p1}})
            return n

        c0 = phase_seconds(pool)
        pool.step = step
        try:
            drive(pool, traffic, until, spans, steps, stop)
        finally:
            del pool.step
        c1 = phase_seconds(pool)
        print("phases: " + json.dumps({
            "engine_phase_s": {k: c1[k] - c0[k] for k in c1},
            "long_steps": long_steps}), flush=True)

    harness.drive = watched


def main(argv=None) -> int:
    from bench import run
    from bench import harness
    run.T_START = T_START
    trace.Tracer = PhaseTracer
    watch_steps(harness)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
