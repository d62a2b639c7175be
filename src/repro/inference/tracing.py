"""Engine phase timing.

Every phase of ``InferenceEngine.step`` adds its ``time.perf_counter``
seconds to the ``EngineStats`` field ``<phase>_s``, always. While the module
switch is on, each phase is also a ``jax.profiler.TraceAnnotation`` span
named ``engine.<phase>``, and each garbage collection a span named ``gc``,
so that a profiler trace shows on one clock what the host was doing while
the device idled::

    tracing.enable(True)          # around a profiler trace
    with tracing.phase(engine.stats, "admit"):
        ...

With the switch off no annotation is built and no collector hook is
installed: a phase costs two clock reads and one add.
"""
from __future__ import annotations

import gc
import time

import jax

# the phases of one engine step; readbacks nest in whichever phase waits
PHASES = ("step", "admit", "chunks", "speculate", "blocks", "tick",
          "readback", "record")

_on = False
_gc_open: list = []


def enabled() -> bool:
    return _on


def enable(on: bool) -> None:
    """Switch the profiler spans (and the collector hook) on or off."""
    global _on
    on = bool(on)
    if on == _on:
        return
    _on = on
    if on:
        gc.callbacks.append(_gc_span)
    else:
        gc.callbacks.remove(_gc_span)
        while _gc_open:
            _gc_open.pop().__exit__(None, None, None)


def _gc_span(stage: str, info: dict) -> None:
    if stage == "start":
        span = jax.profiler.TraceAnnotation("gc")
        span.__enter__()
        _gc_open.append(span)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


class phase:
    """``with phase(stats, name):`` adds the block's seconds to
    ``stats.<name>_s`` and, while the switch is on, spans it as
    ``engine.<name>``."""
    __slots__ = ("stats", "name", "t0", "span")

    def __init__(self, stats, name: str):
        self.stats = stats
        self.name = name
        self.span = None

    def __enter__(self):
        if _on:
            self.span = jax.profiler.TraceAnnotation("engine." + self.name)
            self.span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        key = self.name + "_s"
        setattr(self.stats, key, getattr(self.stats, key) + dt)
        if self.span is not None:
            self.span.__exit__(*exc)
        return False
