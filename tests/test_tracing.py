"""Engine phase counters and profiler spans (``repro.inference.tracing``)."""
import dataclasses
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import TOKENIZER
from repro.inference import InferenceEngine, InferencePool, Request, tracing
from repro.models import init_params


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_config("minitron-4b:reduced"),
                              vocab_size=TOKENIZER.vocab_size, num_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _serve(cfg, params, n=5):
    eng = InferenceEngine(params, cfg, num_slots=2, max_seq=64, seed=0,
                          kv_block_size=8, num_kv_blocks=32)
    for i in range(n):
        eng.submit(Request(request_id=i, problem_id=f"p{i}",
                           prompt_tokens=np.arange(10, 16, dtype=np.int32),
                           max_new_tokens=4 + i % 3))
    eng.run_until_idle()
    assert len(eng.drain_completed()) == n
    return eng


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records the names
    of the spans opened."""
    names: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Recorder.names.append(self.name)

    def __exit__(self, *exc):
        return False


def test_switch_off_builds_no_annotation_and_still_counts(setup,
                                                          monkeypatch):
    def refuse(name):
        raise AssertionError(f"annotation {name!r} built with tracing off")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert not tracing.enabled()
    assert tracing._gc_span not in gc.callbacks
    eng = _serve(*setup)
    gc.collect()                     # no collector hook: nothing raises
    s = eng.stats
    for p in tracing.PHASES:
        assert getattr(s, p + "_s") >= 0.0
    for p in ("step", "admit", "blocks", "tick", "readback", "record"):
        assert getattr(s, p + "_s") > 0.0, p
    assert s.step_s >= s.admit_s + s.blocks_s + s.tick_s + s.record_s
    assert s.step_s >= s.readback_s


def test_switch_on_spans_every_phase_and_collection(setup, monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    _Recorder.names = []
    tracing.enable(True)
    try:
        assert tracing.enabled() and tracing._gc_span in gc.callbacks
        _serve(*setup, n=2)
        gc.collect()
    finally:
        tracing.enable(False)
    assert tracing._gc_span not in gc.callbacks and not tracing._gc_open
    names = set(_Recorder.names)
    assert {"engine.step", "engine.admit", "engine.blocks", "engine.tick",
            "engine.readback", "engine.record", "gc"} <= names
    assert all(n == "gc" or n.startswith("engine.") for n in names)
    # switching off twice, or on twice, leaves one hook at most
    tracing.enable(False)
    assert tracing._gc_span not in gc.callbacks


def test_pool_exports_phase_seconds(setup):
    cfg, params = setup
    pool = InferencePool([InferenceEngine(params, cfg, num_slots=2,
                                          max_seq=64, seed=0)])
    pool.submit_request(np.arange(10, 15, dtype=np.int32), max_new_tokens=3)
    while not pool.idle:
        pool.step()
    phase_s = pool.stats()["phase_s"]
    assert set(phase_s) == set(tracing.PHASES)
    assert phase_s["step"] >= phase_s["admit"] > 0.0
    assert phase_s["step"] == pytest.approx(pool.engines[0].stats.step_s)
