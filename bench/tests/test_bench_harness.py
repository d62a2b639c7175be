"""The harness finds cells, configurations, traffic and metrics by name,
refuses what is not a TPU it knows, and a new cell needs only new files and
a new ``BENCHMARK.json`` entry."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness
from bench.harness import BENCH_DIR, ROOT, BenchError, load_cell, load_json

BENCH = load_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_file_keeps_to_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"] and BENCH["command"][1] == "bench/run.py"
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = {w["name"]: w for w in BENCH["workloads"]}
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
        assert any(w["config"] == c["name"] for w in cells.values())
        cfg = load_json(ROOT / c["file"])
        assert cfg["reduced"] == c["reduced"] and cfg["name"] == c["name"]
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank", "_size")), key
    pairs = set()
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (BENCH_DIR / "traffic" / f"{w['traffic']}.json").is_file()
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    names = set()
    for kind in ("end_to_end", "per_layer"):
        for m in BENCH[kind]:
            assert NAME.match(m["name"]) and m["name"] not in names
            names.add(m["name"])
            assert UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
            assert (BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
            assert set(m.get("workloads", cells)) <= set(cells)
            if kind == "end_to_end":
                assert m["source"] in ("host_clock", "device_trace")
                assert 0.01 <= m["bound"] <= 0.25
            else:
                assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
                movers = next(e for e in BENCH["end_to_end"]
                              if e["name"] == m["moves"])
                assert set(m["workloads"]) <= set(movers.get("workloads",
                                                             cells))
    for cell in cells:
        e2e = [m["name"] for m in BENCH["end_to_end"]
               if cell in m.get("workloads", [cell])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(cell in m.get("workloads", [cell])
                   for m in BENCH["per_layer"])


@pytest.mark.parametrize("workload", sorted(w["name"]
                                            for w in BENCH["workloads"]))
def test_cells_found_by_name(workload):
    cell = load_cell(workload)
    w = next(x for x in BENCH["workloads"] if x["name"] == workload)
    assert cell.config["name"] == w["config"]
    assert cell.traffic == load_json(BENCH_DIR / "traffic"
                                     / f"{w['traffic']}.json")
    assert hasattr(cell.generator(), "make")
    assert {"setup_s"} <= {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cell.reader(m["name"]).compute)


def test_unknown_names_are_refused():
    with pytest.raises(BenchError):
        load_cell("no-such-cell")
    with pytest.raises(BenchError):
        harness.load_peaks("TPU v9 imaginary")
    peaks = harness.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in peaks["source"]


def test_cpu_platform_is_refused(capsys):
    with pytest.raises(BenchError, match="needs a TPU"):
        harness.check_devices(1)
    assert "platform=cpu" in capsys.readouterr().out


def test_config_file_must_hold_what_the_program_runs():
    config = load_cell("mistral-7b-v0.1.rollout").config
    cfg = harness.model_config(config)
    params = {"embed": np.zeros((1, 1), "float32")}
    with pytest.raises(BenchError, match="dtype"):
        harness.check_config(config, cfg, params)
    import ml_dtypes
    params = {"embed": np.zeros((1, 1), ml_dtypes.bfloat16)}
    harness.check_config(config, cfg, params)
    with pytest.raises(BenchError, match="hidden_size"):
        harness.check_config(dict(config, hidden_size=2048), cfg, params)


def run_command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mistral-7b-v0.1.rollout",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_without_a_tpu_exits_nonzero_with_no_result():
    out = run_command(ROOT)
    assert out.returncode != 0
    assert "platform=cpu" in out.stdout
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_command_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = run_command(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_new_cell_from_new_files_and_an_entry(tmp_path):
    """A later change adds a traffic mix, a metric and a cell by adding
    files and entries; no file the benchmark has is edited."""
    shutil.copytree(BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mix = dict(load_json(BENCH_DIR / "traffic" / "rollout.json"),
               group_size=8, queued_groups=2)
    (tmp_path / "bench" / "traffic" / "rollout_g8.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "ticks_per_s.rollout_g8.py"
     ).write_text("def compute(rec):\n"
                  "    return len(rec.occupancy) / rec.seconds\n")
    bench["workloads"].append({"name": "mistral-7b-v0.1.rollout_g8",
                               "config": "mistral-7b-v0.1",
                               "traffic": "rollout_g8", "chips": 1,
                               "why": "groups of 8"})
    bench["per_layer"].append({"name": "ticks_per_s.rollout_g8",
                               "unit": "1/s", "better": "higher",
                               "source": "program_counter",
                               "layer": "engine dispatch",
                               "moves": "rollout_tok_s",
                               "workloads": ["mistral-7b-v0.1.rollout_g8"]})
    for m in bench["end_to_end"]:
        if m["name"] == "rollout_tok_s":
            m["workloads"].append("mistral-7b-v0.1.rollout_g8")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = load_cell("mistral-7b-v0.1.rollout_g8", root=tmp_path)
    assert cell.traffic["group_size"] == 8
    assert [m["name"] for m in cell.per_layer] == ["ticks_per_s.rollout_g8"]
    assert {m["name"] for m in cell.end_to_end} == {"rollout_tok_s",
                                                    "setup_s"}
    gen = cell.generator().make(cell.traffic, seed=1, vocab=1000,
                                engine=cell.config["engine"])
    assert gen.G == 8
    rec = harness.Record(cell="x", t0=0.0, t1=2.0, items=[], steps=[],
                         counters={}, occupancy=[3, 3, 3], slots=16,
                         chips=1, peaks={}, model={}, setup_s=0.0)
    assert cell.reader("ticks_per_s.rollout_g8").compute(rec) == 1.5


# a small expert configuration, at the sizes of qwen2-moe-a2.7b:reduced, as
# a later change would add it: its file, its own copy of the reference, and
# entries in BENCHMARK.json
TINY_MOE = {
    "name": "tiny-moe",
    "source": "https://huggingface.co/Qwen/Qwen1.5-MoE-A2.7B",
    "chips": 1, "mesh": None, "dtype": "float32",
    "num_hidden_layers": 2, "hidden_size": 256, "intermediate_size": 512,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 64,
    "vocab_size": 512, "hidden_act": "silu", "rms_norm_eps": 1e-05,
    "rope_theta": 1000000.0, "sliding_window": 0,
    "tie_word_embeddings": False, "num_experts": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 128, "shared_expert_intermediate_size": 128,
    "norm_topk_prob": False, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "program": {"qk_norm": False},
    "reference": "bench/references/tiny-moe.py",
    "reduced": [], "engine": {
        "slots": 4, "max_seq": 128, "kv_block_size": 16, "num_kv_blocks": 32,
        "min_prefill_bucket": 8, "prefix_cache": True, "chunk_prefill": 0,
        "spec_draft": 0},
    "check": {"logprob_err": 0.2, "greedy_gap": 0.2},
}


def expert_checkout(tmp_path):
    shutil.copytree(BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench" / "configs" / "tiny-moe.json").write_text(
        json.dumps(TINY_MOE))
    (tmp_path / "bench" / "references").mkdir()
    shutil.copy(BENCH_DIR / "reference.py",
                tmp_path / "bench" / "references" / "tiny-moe.py")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-moe",
                             "source": TINY_MOE["source"],
                             "file": "bench/configs/tiny-moe.json",
                             "reduced": [], "why": "sparse experts"})
    bench["workloads"].append({"name": "tiny-moe.rollout",
                               "config": "tiny-moe", "traffic": "rollout",
                               "chips": 1, "why": "expert decode"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("rollout_tok_s", "step_roofline.rollout"):
            m["workloads"].append("tiny-moe.rollout")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_expert_cell_from_new_files_and_entries(tmp_path):
    """A later change adds an expert configuration, its own reference and a
    cell by new files and entries alone: the harness builds and serves it,
    and the check goes through the reference the file names."""
    from bench.check import Sequence, check_sample, reference_module
    expert_checkout(tmp_path)
    cell = load_cell("tiny-moe.rollout", root=tmp_path)
    assert {m["name"] for m in cell.end_to_end} == {"rollout_tok_s",
                                                    "setup_s"}
    assert [m["name"] for m in cell.per_layer] == ["step_roofline.rollout"]
    cfg = harness.model_config(cell.config)
    assert cfg.family == "moe" and not cfg.qk_norm
    assert (cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.expert_d_ff,
            cfg.moe.shared_d_ff, cfg.moe.num_shared_experts,
            cfg.moe.norm_topk_prob) == (4, 2, 128, 128, 1, False)
    seed = 2 ** 31 + 9
    cfg, pool = harness.build_pool(cell.config, seed)
    params = pool.engines[0].params
    prompts = np.random.default_rng(seed).integers(2, 512, (3, 12))
    reqs = [pool.submit_request(p.astype(np.int32), max_new_tokens=10,
                                temperature=0.0) for p in prompts]
    for _ in range(100):
        if pool.idle:
            break
        pool.step()
    assert all(r.finished and len(r.completion) == 10 for r in reqs)
    ref = reference_module(cell.config, tmp_path)
    assert ref.__file__ == str(tmp_path / "bench" / "references"
                               / "tiny-moe.py")
    out = check_sample(cell.config, seed, [Sequence.of_request(r)
                                           for r in reqs], root=tmp_path)
    assert out["correct"] is True, out
    assert out["compared"]["greedy_tokens"] == 30
    # what the file says and the program does not run is refused
    bad = dict(cell.config, moe_intermediate_size=64)
    with pytest.raises(BenchError, match="moe_intermediate_size"):
        harness.check_config(bad, cfg, params)
    with pytest.raises(BenchError, match="moe.w_gate of shape"):
        harness.check_config(bad, harness.model_config(bad), params)
    with pytest.raises(BenchError, match="no_such_field"):
        harness.model_config(dict(cell.config,
                                  program={"no_such_field": True}))


@pytest.mark.parametrize("change,named", [
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"mlp_only_layers": [0]}, "mlp_only_layers"),
    # a field that a published key sets is not set again under "program"
    ({"program": {"d_model": 128}}, "d_model"),
    ({"program": {"norm_topk_prob": True}}, "norm_topk_prob"),
    # an expert field in a file without experts
    ({"num_experts": 0, "program": {"router_noise": 0.1}}, "no experts"),
    ({"norm_topk_prob": None}, "norm_topk_prob"),        # left out
])
def test_expert_files_the_program_cannot_run_are_refused(change, named):
    config = dict(TINY_MOE, **change)
    config = {k: v for k, v in config.items()
              if k not in change or v is not None}
    with pytest.raises(BenchError, match=named):
        harness.model_config(config)


def test_compile_cache_sits_inside_the_checkout(tmp_path):
    """Whatever the environment named, the cache is the checkout's own, at
    a fixed path, and every program goes into it."""
    code = ("import jax, pathlib, sys; from bench import harness; "
            "d = harness.use_checkout_compile_cache(pathlib.Path(sys.argv[1]));"
            " print(d); print(jax.config.jax_compilation_cache_dir); "
            "print(jax.config.jax_persistent_cache_min_compile_time_secs); "
            "print(jax.config.jax_compilation_cache_max_size)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "elsewhere"),
               JAX_COMPILATION_CACHE_MAX_SIZE="1000",
               PYTHONPATH=f"{ROOT}:{ROOT / 'src'}")
    out = subprocess.run([sys.executable, "-c", code, str(tmp_path)],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    want = str(tmp_path / ".jax_cache")
    assert out.stdout.split() == [want, want, "0", "-1"], out.stderr
