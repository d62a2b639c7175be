"""Reduce a profiler trace of the measured window to device busy time, idle
gaps attributed to the harness's host spans, and the operations that took
the most device time.

The harness wraps its window in a ``bench.window`` annotation and each call
into the program in a span of its own (``pool.step``, ``release``, ``drain``,
``tool_wait``); all of them land on the profiler's clock with the device's
operations. On a TPU every chip is a plane ``/device:TPU:<n>`` whose
``XLA Ops`` line holds the operations that ran on it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Tuple

WINDOW = "bench.window"
SPANS = ("pool.step", "release", "drain", "tool_wait")
MODULES = "XLA Modules"
TOP = 10
# an HLO instruction name of a collective, such as ``all-gather.3`` or
# ``all-reduce-start.1``
COLLECTIVE = re.compile(r"(all-gather|all-reduce|reduce-scatter|all-to-all"
                        r"|collective-permute)(-start|-done)?(\.\d+)?$")


def tpu_op_lines(plane_name: str, line_name: str) -> bool:
    return (plane_name.startswith("/device:TPU:")
            and line_name == "XLA Ops")


def op_name(name: str, start_ns: float, modules, starts) -> str:
    """``<program>:<instruction>``: the XLA program the operation ran in
    (from the plane's ``XLA Modules`` line) and the instruction's name
    without its HLO text."""
    short = name.split(" = ", 1)[0].strip().lstrip("%")
    k = bisect.bisect_right(starts, start_ns) - 1
    if k >= 0 and start_ns < modules[k][1]:
        short = f"{modules[k][2].split('(', 1)[0]}:{short}"
    return short[:96]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def complement(busy, lo, hi) -> List[Tuple[float, float]]:
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(a, b) for a, b in gaps if b > a]


@dataclass
class Summary:
    """Times in seconds. ``busy_s`` is averaged over the devices traced."""
    window_s: float
    busy_s: float
    devices: int
    op_seconds: Dict[str, float]          # op name -> seconds (device mean)
    idle_by_span: Dict[str, float]        # host span -> idle seconds
    step_idle_s: List[float] = field(default_factory=list)  # per pool.step

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    @property
    def collective_s(self) -> float:
        """Seconds (device mean) of the operations that exchange data
        between chips: all-gather, all-reduce, reduce-scatter, all-to-all
        and collective-permute, and the start and done halves of each."""
        return sum(v for k, v in self.op_seconds.items()
                   if COLLECTIVE.match(k.rsplit(":", 1)[-1]))

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def reduce_events(host: List[Tuple[str, float, float]],
                  devices: List[List[Tuple[str, float, float]]]) -> Summary:
    """``host``: (name, start, end) host spans, one named ``bench.window``;
    ``devices``: per device, (op name, start, end). Any one time unit."""
    windows = [(a, b) for n, a, b in host if n == WINDOW]
    if not windows or not devices:
        raise ValueError("trace holds no window span or no device")
    lo, hi = windows[0]
    spans = [(n, a, b) for n, a, b in host if n in SPANS and b > lo and a < hi]
    steps = [(a, b) for n, a, b in spans if n == "pool.step"]
    busy_total = 0.0
    ops: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    step_idle = [0.0] * len(steps)
    for evs in devices:
        clipped = [(n, max(a, lo), min(b, hi)) for n, a, b in evs
                   if b > lo and a < hi]
        for n, a, b in clipped:
            ops[n] += b - a
        busy = union([(a, b) for _, a, b in clipped])
        busy_total += sum(b - a for a, b in busy)
        for g0, g1 in complement(busy, lo, hi):
            left = g1 - g0
            for n, a, b in spans:
                ov = overlap(g0, g1, a, b)
                if ov > 0:
                    idle[n] += ov
                    left -= ov
            if left > 1e-12:
                idle["other"] += left
            for i, (a, b) in enumerate(steps):
                step_idle[i] += overlap(g0, g1, a, b)
    nd = len(devices)
    return Summary(window_s=hi - lo, busy_s=busy_total / nd, devices=nd,
                   op_seconds={k: v / nd for k, v in ops.items()},
                   idle_by_span={k: v / nd for k, v in idle.items()},
                   step_idle_s=[s / nd for s in step_idle])


def read_xplane(path: str,
                is_op_line: Callable[[str, str], bool] = tpu_op_lines
                ) -> Summary:
    """Summarise one ``.xplane.pb`` (times in the file are nanoseconds)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host, devices = [], []
    for plane in data.planes:
        dev: List[Tuple[str, float, float]] = []
        found = False
        modules = []
        for line in plane.lines:
            if line.name == MODULES:
                modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name) for e in line.events)
        starts = [m[0] for m in modules]
        for line in plane.lines:
            if is_op_line(plane.name, line.name):
                found = True
                dev.extend((op_name(e.name, e.start_ns, modules, starts),
                            e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
                           for e in line.events)
            elif plane.name == "/host:CPU":
                host.extend((e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events
                            if e.name == WINDOW or e.name in SPANS)
        if found:
            devices.append(dev)
    print("trace: planes " + "; ".join(
        f"{p.name} [{', '.join(sorted({ln.name for ln in p.lines}))[:160]}]"
        for p in data.planes)[:1500], flush=True)
    return reduce_events(host, devices)


class Tracer:
    """The JAX profiler around the measured window, writing under the run's
    own temporary directory and removing it once the trace is read."""

    def __init__(self, is_op_line=tpu_op_lines):
        self.is_op_line = is_op_line
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def start(self) -> None:
        import jax
        jax.profiler.start_trace(self.dir)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def read(self) -> Summary:
        """Reduce the trace (after the window's requests are served out:
        reading a trace of tens of seconds takes seconds)."""
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if len(paths) != 1:
                raise ValueError(f"expected one trace file, found {paths}")
            print(f"trace: {os.path.getsize(paths[0])} bytes", flush=True)
            return read_xplane(paths[0], self.is_op_line)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
