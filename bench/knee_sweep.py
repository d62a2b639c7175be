"""Find the knee of an open-loop cell: the highest arrival rate it sustains.

    python3 bench/knee_sweep.py --workload mistral-7b-v0.1.agent --seed 7 \\
        --rates 1.5,2,2.5,3,3.5,4 --seconds 30

Not part of a benchmark run. One process builds the cell's pool once, warms
its shapes, then offers the traffic at each rate in turn: the mix's own
warm-up, then ``--seconds`` measured, then the sessions in flight are
served out and closed. For each rate it prints one JSON line: the backlog
(sessions arrived and waiting for an environment) at the window's start,
end and peak and its mean over each half of the window, the turns due,
and the window's latency tails. The knee is the highest rate below which
no rate keeps a backlog (a mean of more than one waiting session over the
window's second half); the cell runs at four fifths of it (``rate_per_s``
in its traffic file).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def sweep(cell, pool, cfg, rates, seconds, seed, log):
    from bench import harness
    rows = []
    for rate in rates:
        mix = dict(cell.traffic, rate_per_s=rate)
        traffic = cell.generator().make(mix, seed=seed, vocab=cfg.vocab_size,
                                        engine=cell.config["engine"])
        spans = harness.Spans(False)
        start = time.perf_counter()
        traffic.start(pool, start)
        harness.drive(pool, traffic, start + traffic.warmup_limit_s, spans,
                      stop=traffic.ready)
        backlog = []
        t0 = time.perf_counter()
        c0 = log.compiles

        def sample():
            backlog.append(len(traffic.waiting))
            return False
        harness.drive(pool, traffic, t0 + seconds, spans, [], stop=sample)
        t1 = time.perf_counter()
        rec = harness.Record(
            cell=cell.name, t0=t0, t1=t1,
            items=list(traffic.items) + traffic.unserved(t1),
            steps=[], counters={}, occupancy=[], slots=0, chips=1, peaks={},
            model=cell.config, setup_s=0.0)
        half = len(backlog) // 2
        row = {"rate_per_s": rate, "backlog_start": backlog[0],
               "backlog_end": backlog[-1], "backlog_max": max(backlog),
               "backlog_mean_first_half": sum(backlog[:half]) / max(1, half),
               "backlog_mean_second_half": sum(backlog[half:])
               / max(1, len(backlog) - half),
               "turns_due": len(rec.due_in_window()),
               "compiles_in_window": log.compiles - c0}
        for name in ("ttft_p95_ms", "itl_p95_ms"):
            row[name] = cell.reader(name).compute(rec)
        print(json.dumps(row), flush=True)
        rows.append(row)
        # serve out what is in flight, then close every session
        traffic.waiting.clear()
        traffic.arrived = len(traffic.sessions)
        for s in traffic.open.values():
            s.next_due = None
        while not pool.idle:
            pool.step()
        pool.drain_requests()
        for sid in list(traffic.open):
            pool.close_session(sid)
    return rows


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    cell = harness.load_cell(args.workload)
    harness.check_devices(cell.chips)
    harness.use_checkout_compile_cache()
    log = harness.CompileLog()
    cfg, pool = harness.build_pool(cell.config, args.seed)
    warm = cell.generator().make(cell.traffic, seed=args.seed,
                                 vocab=cfg.vocab_size,
                                 engine=cell.config["engine"])
    warm.warm_shapes(pool)
    harness.warm_table_updates(pool)
    print(json.dumps({"setup_s": time.perf_counter() - T_START,
                      "compiles": log.compiles, "cache_hits": log.cache_hits,
                      "compile_s": log.seconds}), flush=True)
    rates = [float(r) for r in args.rates.split(",")]
    rows = sweep(cell, pool, cfg, rates, args.seconds, args.seed, log)
    knee = None
    for r in sorted(rows, key=lambda r: r["rate_per_s"]):
        if r["backlog_mean_second_half"] > 1.0:
            break           # sessions queue for an environment: past the knee
        knee = r["rate_per_s"]
    print(json.dumps({"knee_rate_per_s": knee, "cell_rate_per_s":
                      None if knee is None else round(0.8 * knee, 3)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
