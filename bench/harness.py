"""One run of one benchmark cell, from set-up to the result line.

Everything that belongs to one configuration, traffic mix or metric lives in
a file of its own, found by the name ``BENCHMARK.json`` gives it:

  bench/configs/<config>.json    model, chips, mesh and engine geometry
  bench/traffic/<mix>.json       the mix's parameters; ``"generator"``
                                 names bench/traffic/<generator>.py
  bench/metrics/<metric>.py      ``compute(record) -> float | None``

The program is driven only through its serving entry points:
``repro.launch.serve.build_pool`` and the ``InferencePool`` it returns.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import sys
import time
from contextlib import contextmanager
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
FINISHED_OK = ("length", "eos")
# after the window closes the harness keeps serving until every request due
# in the window has its first token (and the check has its greedy streams),
# for at most this long; a request still without one then counts as missing
DRAIN_LIMIT_S = 60.0


class BenchError(RuntimeError):
    """A run that cannot give a result (no chip, unknown device, a compile
    inside the window): the command exits non-zero and prints no result."""


# ---------------------------------------------------------------- the cell


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a benchmark file by path (metric and generator files are named
    after the entries of ``BENCHMARK.json``, dots and dashes included)."""
    if not path.is_file():
        raise BenchError(f"no file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict           # bench/configs/<config>.json
    traffic: dict          # bench/traffic/<mix>.json
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path = ROOT

    def generator(self):
        kind = self.traffic["generator"]
        return load_module(self.root / "bench" / "traffic" / f"{kind}.py",
                           f"bench_traffic_{kind}")

    def reader(self, metric: str):
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py",
                           "bench_metric_" + metric.replace(".", "_")
                           .replace("-", "_"))


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, workload)], root=root)


def load_peaks(device_kind: str, root: Path = ROOT) -> dict:
    table = load_json(root / "bench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json (have {sorted(table['devices'])})")
    return dict(table["devices"][device_kind], source=table["source"])


# ------------------------------------------------------------ the device


def check_devices(chips: int):
    """The chip gate: platform, kind and count first, then refuse anything
    but a TPU with its peaks known and at least ``chips`` devices."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {dev.platform}")
    if len(devices) < chips:
        raise BenchError(f"cell needs {chips} chips; JAX found "
                         f"{len(devices)}")
    return devices[:chips], load_peaks(dev.device_kind)


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", -1)
               for d in devices)


def use_checkout_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, for every
    program however short its compile, so that only a cell's first run in a
    checkout compiles. The program's own ``use_compile_cache`` reads the
    directory from the environment."""
    import jax
    path = str(root / ".jax_cache")
    # JAX read the environment when it was imported: set both
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    # the directory is the checkout's own: no size limit, so no eviction
    # (a size limit from the environment turns on JAX's LRU eviction, which
    # failed every write on the chip's host and left the cache empty)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.common.compile_cache import use_compile_cache
    return use_compile_cache()


# ---------------------------------------------------------------- the pool

# configuration-file keys (the names of the model's published config) and
# the program's ModelConfig fields they set
MODEL_KEYS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
    "sliding_window": "sliding_window",
    "tie_word_embeddings": "tie_embeddings",
}


# the same for an expert layer (``num_experts`` in the file): the program's
# MoEConfig fields; a non-zero shared expert width makes one shared expert
EXPERT_KEYS = {
    "num_experts": "num_experts", "num_experts_per_tok": "top_k",
    "moe_intermediate_size": "expert_d_ff",
    "shared_expert_intermediate_size": "shared_d_ff",
    "norm_topk_prob": "norm_topk_prob",
}
# published keys that change the maths and have no program field: a file
# may give them only with the value the program computes
FIXED_KEYS = {"hidden_act": "silu", "decoder_sparse_step": 1,
              "mlp_only_layers": []}


def _program_fields(config: dict):
    """The file's ``"program"`` object split into ModelConfig and MoEConfig
    fields: what the published configuration does not name, set by the
    program's own field names. A name of neither, or one that the
    published keys already set, is refused."""
    from dataclasses import fields

    from repro.configs.base import ModelConfig, MoEConfig
    taken = set(MODEL_KEYS.values()) | set(EXPERT_KEYS.values()) | {
        "name", "family", "source", "dtype", "moe", "num_shared_experts"}
    model_fields = {f.name for f in fields(ModelConfig)}
    expert_fields = {f.name for f in fields(MoEConfig)}
    top, moe = {}, {}
    for key, value in config.get("program", {}).items():
        if key in taken:
            raise BenchError(f"{config['name']}: program field {key!r} is "
                             "set from the published keys")
        if key in model_fields:
            top[key] = value
        elif key in expert_fields and config.get("num_experts"):
            moe[key] = value
        elif key in expert_fields:
            raise BenchError(f"{config['name']}: program field {key!r} is "
                             "an expert field, and the file has no experts")
        else:
            raise BenchError(f"{config['name']}: the program has no field "
                             f"{key!r} to set")
    return top, moe


def model_config(config: dict):
    """The program's ModelConfig of a configuration file, every size taken
    from the file: the file holds the configuration as it is run."""
    from repro.configs.base import ModelConfig, MoEConfig
    for key, value in FIXED_KEYS.items():
        if key in config and config[key] != value:
            raise BenchError(f"{config['name']}: {key}={config[key]!r}, but "
                             f"the program computes only {value!r}")
    top, moe_fields = _program_fields(config)
    moe = None
    if config.get("num_experts"):
        missing = [key for key in EXPERT_KEYS if key not in config]
        if missing:
            raise BenchError(f"{config['name']}: an expert configuration "
                             f"needs {missing} (a shared width of 0 for none)")
        moe_fields.update({attr: config[key]
                           for key, attr in EXPERT_KEYS.items()})
        moe = MoEConfig(num_shared_experts=int(bool(moe_fields["shared_d_ff"])),
                        **moe_fields)
    return ModelConfig(
        name=config["name"], family="moe" if moe else "dense",
        source=config["source"], dtype=config["dtype"], moe=moe, **top,
        **{attr: config[key] for key, attr in MODEL_KEYS.items()})


def _expert_shapes(config: dict) -> dict:
    """Global shapes of one configuration's served expert stacks, layers
    first: [L, E, d, f] and the like."""
    L, d, E = (config["num_hidden_layers"], config["hidden_size"],
               config["num_experts"])
    f = config["moe_intermediate_size"]
    shapes = {("router",): (L, d, E), ("w_gate",): (L, E, d, f),
              ("w_up",): (L, E, d, f), ("w_down",): (L, E, f, d)}
    sf = config["shared_expert_intermediate_size"]
    if sf:
        shapes.update({("shared", "w_gate"): (L, d, sf),
                       ("shared", "w_up"): (L, d, sf),
                       ("shared", "w_down"): (L, sf, d),
                       ("shared_gate",): (L, d, 1)})
    return shapes


def check_config(config: dict, cfg, params) -> None:
    """Refuse a run whose program does not run what the file says: every
    published key, every ``"program"`` field, the served dtype and, for an
    expert layer, the served expert stacks' global shapes."""
    name = config["name"]
    pairs = [(key, config[key], attr, getattr(cfg, attr))
             for key, attr in MODEL_KEYS.items()]
    if config.get("num_experts"):
        pairs += [(key, config[key], attr, getattr(cfg.moe, attr))
                  for key, attr in EXPERT_KEYS.items()]
    for key, value in config.get("program", {}).items():
        held = cfg.moe if hasattr(cfg.moe, key) else cfg
        pairs.append((f"program.{key}", value, key, getattr(held, key)))
    for key, want, attr, runs in pairs:
        if runs != want:
            raise BenchError(f"{name}: {key}={want} but the program runs "
                             f"{attr}={runs}")
    served = str(params["embed"].dtype)
    if served != config["dtype"]:
        raise BenchError(f"{name}: dtype={config['dtype']} but the "
                         f"program serves {served}")
    if config.get("num_experts"):
        for path, want in _expert_shapes(config).items():
            leaf = params["layers"]["moe"]
            for p in path:
                leaf = leaf[p]
            if tuple(leaf.shape) != want:
                raise BenchError(f"{name}: the program serves moe."
                                 f"{'.'.join(path)} of shape "
                                 f"{tuple(leaf.shape)}, the file says {want}")


def build_pool(config: dict, seed: int):
    """The serving stack of ``config`` as ``repro.launch.serve.build_pool``
    assembles it (one engine per mesh, params made on the device from
    ``seed`` by the program's own init), for a model given by its sizes
    rather than by a registry name. Returns (cfg, pool)."""
    from repro.configs.base import ParallelConfig
    from repro.inference import InferenceEngine, InferencePool
    from repro.launch.mesh import make_engine_meshes
    from repro.launch.serve import init_serving_params
    cfg = model_config(config)
    eng = config["engine"]
    meshes = (make_engine_meshes(*config["mesh"]) if config.get("mesh")
              else [None])
    engines = [InferenceEngine(
        init_serving_params(cfg, seed, mesh=m), cfg, num_slots=eng["slots"],
        max_seq=eng["max_seq"], pcfg=ParallelConfig(remat="none",
                                                     loss_chunk=0),
        seed=i, mesh=m, kv_block_size=eng["kv_block_size"],
        num_kv_blocks=eng["num_kv_blocks"],
        min_prefill_bucket=eng["min_prefill_bucket"],
        prefix_cache=eng["prefix_cache"], chunk_prefill=eng["chunk_prefill"],
        spec_draft=eng["spec_draft"]) for i, m in enumerate(meshes)]
    check_config(config, cfg, engines[0].params)
    return cfg, InferencePool(engines)


def warm_table_updates(pool) -> None:
    """Compile, before the window, the eager block-table update the engine
    issues between dispatches for every count of entries it can carry: one
    per slot crossing a block boundary (or copied on write) in a tick, and
    up to a row's blocks when a prefix-cache hit attaches its blocks. The
    update is repeated on a throwaway result; the engine's table is left as
    it was."""
    import jax
    for eng in pool.engines:
        tables = eng.state["block_tables"]
        slots, per_row = tables.shape
        for n in range(1, slots + per_row + 1):
            idx = np.arange(n, dtype=np.int32)
            rows, cols, vals = idx % slots, idx % per_row, idx
            jax.block_until_ready(tables.at[rows, cols].set(vals))


# ------------------------------------------------------------- the record


@dataclass
class Item:
    """One request the traffic sent: a group member or a session turn."""
    req: object              # the engine's Request (token stamps, reasons)
    due: float               # perf_counter second it was due
    released: float          # perf_counter second it was submitted
    ctx: int                 # cache entries before its first served token
    kind: str = "sampled"    # or "greedy"
    key: int = -1            # the group or session it belongs to

    @property
    def refused(self) -> bool:
        return self.req is None


@dataclass
class Record:
    """What a metric reader reads: the window's clock, every request sent,
    the harness's step spans, the engine counters' deltas over the window,
    the trace summary (traced runs) and the analytic work of the model."""
    cell: str
    t0: float
    t1: float
    items: List[Item]
    steps: List[tuple]                 # (start, end) of each pool.step()
    counters: Dict[str, float]
    occupancy: List[int]               # active slots per tick in the window
    slots: int
    chips: int
    peaks: dict
    model: dict                        # the configuration file
    setup_s: float
    trace: Optional[object] = None     # bench.trace.Summary

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def in_window(self, ts: float) -> bool:
        return self.t0 <= ts < self.t1

    def due_in_window(self) -> List[Item]:
        return [it for it in self.items if self.in_window(it.due)]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile over every value (``inf`` counts as a value
    above all others)."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    k = max(0, math.ceil(q / 100.0 * len(xs)) - 1)
    return float(xs[k])


def read_counters(pool) -> Dict[str, float]:
    """Every counter of the engines' ``EngineStats``, summed over engines."""
    out: Dict[str, float] = {}
    for e in pool.engines:
        for k, v in vars(e.stats).items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[k] = out.get(k, 0) + v
    return out


def occupancy_len(pool) -> List[int]:
    return [len(e.stats.occupancy_trace) for e in pool.engines]


# --------------------------------------------------------------- the loop


class Spans:
    """Host spans around the harness's calls into each layer. In a traced
    run each span is also a ``TraceAnnotation`` on the profiler's clock."""

    def __init__(self, traced: bool):
        self.traced = traced

    @contextmanager
    def __call__(self, name: str):
        if self.traced:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield


def drive(pool, traffic, until, spans: Spans, steps: Optional[list] = None,
          stop=None):
    """Serve the traffic until the perf_counter second ``until`` (or until
    ``stop()`` says so): release what is due, advance the engines one step,
    hand finished requests back to the traffic; when nothing is in flight,
    wait for the next request to come due."""
    while True:
        now = time.perf_counter()
        if now >= until or (stop is not None and stop()):
            return
        with spans("release"):
            traffic.release(pool, now)
        if pool.idle:
            nxt = traffic.next_due()
            wait = (until if nxt is None else min(nxt, until)) - now
            if wait > 0:
                with spans("tool_wait"):
                    time.sleep(min(wait, 0.05))
            continue
        t = time.perf_counter()
        with spans("pool.step"):
            pool.step()
        if steps is not None:
            steps.append((t, time.perf_counter()))
        with spans("drain"):
            traffic.collect(pool, pool.drain_requests())


# ------------------------------------------------------------ one run


def metric_value(cell: Cell, entry: dict, record: Record):
    value = cell.reader(entry["name"]).compute(record)
    if value is None:
        return None
    return {"value": float(value), "unit": entry["unit"]}


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, devices, peaks: dict, log: CompileLog,
             checker=None) -> dict:
    """Set up, warm, measure ``seconds``, check. Returns the result object
    (the last line the command prints)."""
    import jax
    traffic_mod = cell.generator()
    cfg, pool = build_pool(cell.config, seed)
    jax.block_until_ready(pool.engines[0].params)
    traffic = traffic_mod.make(cell.traffic, seed=seed,
                               vocab=cfg.vocab_size,
                               engine=cell.config["engine"])
    traffic.warm_shapes(pool)
    warm_table_updates(pool)
    spans = Spans(traced=False)
    start = time.perf_counter()
    traffic.start(pool, start)
    drive(pool, traffic, start + traffic.warmup_limit_s, spans,
          stop=traffic.ready)
    if not traffic.ready():
        raise BenchError("traffic did not reach its steady state in "
                         f"{traffic.warmup_limit_s} s of warm-up")
    compiles0 = log.compiles
    tracer = None
    if trace:
        from bench.trace import Tracer
        tracer = Tracer()
        tracer.start()
        spans = Spans(traced=True)
    c0, occ0 = read_counters(pool), occupancy_len(pool)
    steps: list = []
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    t1 = t0 + seconds
    if tracer is not None:
        with jax.profiler.TraceAnnotation("bench.window"):
            drive(pool, traffic, t1, spans, steps)
        tracer.stop()
    else:
        drive(pool, traffic, t1, spans, steps)
    c1 = read_counters(pool)
    occupancy = [o for e, n in zip(pool.engines, occ0)
                 for o in e.stats.occupancy_trace[n:]]
    # served until every request due in the window has its first token and
    # the check has finished streams of each kind it samples
    drive(pool, traffic, time.perf_counter() + DRAIN_LIMIT_S, Spans(False),
          stop=lambda: traffic.settled(t0, t1))
    trace_summary = tracer.read() if tracer is not None else None
    in_window = log.compiles - compiles0
    if in_window:
        raise BenchError(f"{in_window} compile(s) inside the measured "
                         f"window: the warm-up missed a shape")
    record = Record(
        cell=cell.name, t0=t0, t1=t1,
        items=list(traffic.items) + traffic.unserved(t1),
        steps=steps,
        counters={k: c1[k] - c0[k] for k in c0}, occupancy=occupancy,
        slots=cell.config["engine"]["slots"] * len(pool.engines),
        chips=cell.chips, peaks=peaks, model=cell.config, setup_s=setup_s,
        trace=trace_summary)
    mem_peak = peak_bytes(devices)
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for entry in entries:
        m = metric_value(cell, entry, record)
        if m is not None:
            metrics[entry["name"]] = m
    due = record.due_in_window()
    failed = sum(1 for it in due if it.refused or (
        it.req.finished and it.req.finish_reason not in FINISHED_OK))
    sample = traffic.check_sample(t0, t1)
    notes = {"setup_s": setup_s, "window_s": record.seconds,
             "compiles": log.compiles, "compile_s": round(log.seconds, 3),
             "cache_hits": log.cache_hits,
             "release_late_max_ms": 1e3 * max(
                 [it.released - it.due for it in due if not it.refused],
                 default=0.0),
             "unserved": sum(1 for it in due if it.refused),
             "due_in_window": len(due), "tokens_in_window": sum(
                 1 for it in record.items if not it.refused
                 for ts in it.req.token_ts if record.in_window(ts)),
             "window_steps": len(steps),
             # for a run that reads far off: where its steps went
             "step_ms_p50_p99_max": [round(1e3 * percentile(
                 [b - a for a, b in steps], q), 3) for q in (50, 99, 100)],
             "between_steps_ms_max": round(1e3 * max(
                 [b[0] - a[1] for a, b in zip(steps, steps[1:])],
                 default=0.0), 3)}
    print("run: " + json.dumps(notes), flush=True)
    # free the program's weights and caches before the reference takes the
    # chip (its compiled programs may still hold the engine alive)
    for eng in pool.engines:
        for x in jax.tree_util.tree_leaves((eng.params, eng.state)):
            x.delete()
    del pool, traffic
    gc.collect()
    t_check = time.perf_counter()
    check = (checker(cell.config, seed, sample) if checker
             else default_checker(cell.config, seed, sample, cell.root))
    print(f"check: {time.perf_counter() - t_check:.1f} s over "
          f"{len(sample)} sequences", flush=True)
    result = {
        "correct": bool(check["correct"]),
        "attempted": len(due),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices),
                   "memory_peak_bytes": int(mem_peak)},
    }
    if trace_summary is not None:
        result["device"]["busy_s"] = trace_summary.busy_s
        result["device"]["window_s"] = trace_summary.window_s
        result["breakdown"] = trace_summary.breakdown()
    result["check"] = check["compared"]
    return result


def default_checker(config: dict, seed: int, sample,
                    root: Path = ROOT) -> dict:
    from bench.check import check_sample
    return check_sample(config, seed, sample, root=root)
