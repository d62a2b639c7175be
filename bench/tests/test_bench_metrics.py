"""Every metric reader on a hand-built run record."""
import math

import pytest

from bench import work
from bench.harness import (BENCH_DIR, ROOT, Item, Record, load_json,
                           load_module, load_peaks)
from bench.tests.fake_pool import FakeRequest
from bench.trace import Summary

MODEL = load_json(BENCH_DIR / "configs" / "mistral-7b-v0.1.json")
PEAKS = load_peaks("TPU v5 lite")
BENCH = load_json(ROOT / "BENCHMARK.json")
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
METRICS += ["ttft_p95_ms"]   # read by the knee sweep; no cell bounds it yet


def req(token_ts, finished=True, reason="length"):
    r = FakeRequest([5] * 4, len(token_ts), 1.0)
    r.token_ts = list(token_ts)
    r.completion = [7] * len(token_ts)
    r.finished, r.finish_reason = finished, reason if finished else ""
    return r


def record(**over):
    items = [
        # due before the window: counts for tokens, not for latency
        Item(req([9.5, 10.5, 11.0, 11.5]), due=9.0, released=9.0, ctx=100),
        Item(req([12.5, 13.0, 13.5]), due=12.0, released=12.2, ctx=50),
        Item(req([16.0, 19.0]), due=15.0, released=15.05, ctx=10),
    ]
    rec = Record(cell="c", t0=10.0, t1=20.0, items=items,
                 steps=[(10.4, 10.6), (10.9, 11.6), (12.9, 13.6)],
                 counters={"prefill_tokens": 900, "prefill_requests": 2,
                           "extend_requests": 7, "group_fork_requests": 0,
                           "step_s": 0.1, "readback_s": 0.06,
                           "admit_s": 0.18},
                 occupancy=[16, 16, 8, 0], slots=16, chips=1, peaks=PEAKS,
                 model=MODEL, setup_s=42.5,
                 trace=Summary(window_s=10.0, busy_s=9.0, devices=1,
                               op_seconds={}, idle_by_span={},
                               step_idle_s=[0.002, 0.004]))
    for k, v in over.items():
        setattr(rec, k, v)
    return rec


def expected():
    flops = sum(work.token_flops(MODEL, n)
                for n in (101, 102, 103, 50, 51, 52, 10, 11))
    least = sum(work.least_seconds(MODEL, lv, PEAKS, 1)
                for lv in ([101], [102, 103], [51, 52]))
    return {
        "rollout_tok_s": 8 / 10.0,
        "ttft_p95_ms": 1000.0,           # [0.5 s, 1.0 s]: nearest rank
        "itl_p95_ms": 3000.0,            # gaps 0.5 x4 and 3.0
        "setup_s": 42.5,
        "release_late_p95_ms.agent": 200.0,
        "slot_occupancy.rollout": 100.0 * 40 / 64,
        "prefill_tokens_per_turn.agent": 100.0,
        "host_gap_ms_per_step.rollout": 3.0,
        "step_mfu.rollout": 100.0 * flops / 10.0 / 197e12,
        "step_roofline.rollout": 100.0 * least / 10.0,
        "idle_share.rollout": 10.0,
        "idle_share.agent": 10.0,
        # 0.1 s in step() less 0.06 s of readbacks, over 4 steps
        "step_host_ms.rollout": 10.0,
        "step_host_ms.agent": 10.0,
        # 0.18 s of admission over 2 prefills and 7 extends
        "admit_ms_per_turn.agent": 20.0,
    }


def reader(name):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))


@pytest.mark.parametrize("name", METRICS)
def test_reader_on_hand_built_record(name):
    assert reader(name).compute(record()) == pytest.approx(expected()[name])


@pytest.mark.parametrize("name", ["host_gap_ms_per_step.rollout",
                                  "idle_share.rollout", "idle_share.agent"])
def test_trace_readers_return_nothing_without_a_trace(name):
    assert reader(name).compute(record(trace=None)) is None


def test_missing_first_tokens_count_as_infinitely_late():
    rec = record()
    rec.items.append(Item(req([], finished=False), due=19.5, released=19.6,
                          ctx=10))
    rec.items.append(Item(req([19.9], reason="overflow"), due=18.0,
                          released=18.0, ctx=10))
    assert math.isinf(reader("ttft_p95_ms").compute(rec))


def test_readers_with_nothing_to_read():
    empty = record(items=[], steps=[], occupancy=[],
                   counters=dict.fromkeys(record().counters, 0))
    for name in ("ttft_p95_ms", "itl_p95_ms", "release_late_p95_ms.agent",
                 "slot_occupancy.rollout", "prefill_tokens_per_turn.agent",
                 "step_roofline.rollout"):
        assert reader(name).compute(empty) is None, name


def test_shares_of_peak_stay_under_the_roofline():
    # 16 requests served by ticks that each take exactly their least time,
    # back to back: the roofline share is then 100% and no more
    ctx, n = 499, 200
    bounds = [10.0]
    for k in range(1, n + 1):
        bounds.append(bounds[-1] + work.least_seconds(MODEL, [ctx + k] * 16,
                                                      PEAKS, 1))
    steps = list(zip(bounds[:-1], bounds[1:]))
    stamps = [9.0] + [(a + b) / 2 for a, b in steps]
    items = [Item(req(stamps), due=0.0, released=0.0, ctx=ctx)
             for _ in range(16)]
    rec = record(items=items, steps=steps, t1=bounds[-1])
    assert reader("step_roofline.rollout").compute(rec) == pytest.approx(
        100.0)
    assert reader("step_mfu.rollout").compute(rec) < 100.0


@pytest.mark.parametrize("name", ["step_roofline.rollout", "step_mfu.rollout"])
def test_shares_of_peak_count_the_cells_chips(name):
    # the same work on four chips is a quarter of their summed peak
    one = reader(name).compute(record())
    assert reader(name).compute(record(chips=4)) == pytest.approx(one / 4)
