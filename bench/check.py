"""The comparison that decides ``correct``.

After the window the traffic hands over a sample of the sequences it
finished (prompts with every token the program served in them, the
logprobs it reported, and which tokens were greedy). The plain reference
runs once over each whole sequence; every served token is then held to it:

  logprob_err  the widest gap between the logprob the program reported
               for a served token and the reference's log-probability of
               that token (every served token, sampled or greedy);
  greedy_gap   the widest gap by which a greedy token lies below the
               reference's best token at its position.

The limits live in the configuration file under ``check``; PERF.md gives
the readings each was set from.

The reference is ``bench/reference.py`` unless the configuration file names
its own copy under ``"reference"`` (a path under ``bench/``), a module with
the same ``Weights``, ``hidden_states`` and ``head_readings``.
"""
from __future__ import annotations

import gc
from dataclasses import dataclass
from pathlib import Path, PurePosixPath
from typing import List

import numpy as np

from bench.harness import ROOT, BenchError, load_module


@dataclass
class Sequence:
    tokens: np.ndarray      # [n] int32: prompt, served tokens, later turns
    served: np.ndarray      # indices into tokens of the tokens served
    logprobs: np.ndarray    # the program's logprob of each served token
    greedy: np.ndarray      # bool per served token

    @classmethod
    def of_request(cls, req) -> "Sequence":
        prompt = np.asarray(req.prompt_tokens, np.int32)
        comp = np.asarray(req.completion, np.int32)
        return cls(tokens=np.concatenate([prompt, comp]),
                   served=np.arange(len(prompt), len(prompt) + len(comp)),
                   logprobs=np.asarray(req.logprobs, np.float64),
                   greedy=np.full(len(comp), req.temperature <= 0))


def reference_module(model: dict, root: Path = ROOT):
    """The configuration's plain reference: the module its file names under
    ``"reference"``, or ``bench/reference.py``."""
    path = model.get("reference")
    if path is None:
        from bench import reference
        return reference
    parts = PurePosixPath(path).parts
    if parts[:1] != ("bench",) or ".." in parts:
        raise BenchError(f"{model['name']}: reference {path!r} does not lie "
                         "under bench/")
    return load_module(root / path, "bench_reference_" + "_".join(
        parts[1:]).removesuffix(".py").replace(".", "_").replace("-", "_"))


def readings(model: dict, seed: int, sample: List[Sequence],
             control: bool = False, root: Path = ROOT) -> dict:
    """The reference's readings of every served token of ``sample`` (and,
    with ``control``, the fp8 control's at the same positions)."""
    import jax.numpy as jnp
    ref = reference_module(model, root)
    if not sample:
        raise ValueError("no finished sequence to check")
    # rows padded to the engine's max_seq, so that the reference compiles
    # one program per cell and later runs find it in the cache; the last
    # token of a sequence is never an input
    width = max([model["engine"]["max_seq"]] + [len(s.tokens) for s in sample])
    rows = np.zeros((len(sample), width - 1), np.int32)
    for i, s in enumerate(sample):
        rows[i, :len(s.tokens) - 1] = s.tokens[:-1]
    weights = ref.Weights(model, seed)
    quants = (None, "fp8") if control else (None,)
    hs = ref.hidden_states(weights, rows, quants)
    at_r = np.concatenate([np.full(len(s.served), i)
                           for i, s in enumerate(sample)])
    at_p = np.concatenate([s.served - 1 for s in sample])
    toks = np.concatenate([s.tokens[s.served] for s in sample])
    picked = [h[jnp.asarray(at_r), jnp.asarray(at_p)] for h in hs]
    del hs
    gc.collect()
    head = weights.head()
    out = ref.head_readings(head, toks, picked[0],
                            picked[1] if control else None)
    out["served_lp"] = np.concatenate([s.logprobs for s in sample])
    out["greedy"] = np.concatenate([s.greedy for s in sample])
    out["tokens"] = toks
    return out


def numbers(r: dict, vocab: int, control: bool = False) -> dict:
    """The numbers compared, from ``readings``: the program's, or with
    ``control`` the control's, which then stands in the program's place."""
    greedy = r["greedy"].astype(bool)
    sound = bool(np.isfinite(r["served_lp"]).all()
                 and ((r["tokens"] >= 0) & (r["tokens"] < vocab)).all())
    lp, gap = ((r["control_lp"], r["control_gap"]) if control
               else (r["served_lp"], r["gap"]))
    return {"logprob_err": float(np.max(np.abs(lp - r["lp"]))),
            "greedy_gap": float(np.max(gap[greedy], initial=0.0)),
            "greedy_tokens": int(greedy.sum()),
            "tokens": int(len(r["lp"])), "sound": sound}


def judge(n: dict, limits: dict) -> dict:
    """``correct`` and each number beside its limit."""
    compared = {k: {"value": n[k], "limit": limits[k]}
                for k in ("logprob_err", "greedy_gap")}
    compared["tokens"] = n["tokens"]
    compared["greedy_tokens"] = n["greedy_tokens"]
    correct = (n["sound"] and n["greedy_tokens"] > 0
               and all(compared[k]["value"] <= limits[k]
                       for k in ("logprob_err", "greedy_gap")))
    return {"correct": correct, "compared": compared}


def check_sample(model: dict, seed: int, sample: List[Sequence],
                 control: bool = False, root: Path = ROOT) -> dict:
    """The comparison that decides ``correct``. With ``control`` the fp8
    control takes the program's place, at the same served tokens; it has to
    come out not correct."""
    try:
        r = readings(model, seed, sample, control=control, root=root)
    except ValueError as e:
        return {"correct": False, "compared": {"error": str(e)}}
    return judge(numbers(r, model["vocab_size"], control), model["check"])
