"""Analytic work of one decode tick, from the configuration's sizes alone.

A tick feeds one token to each active request and attends over that
request's live positions. Its least work is what any implementation must
do: read every weight once, read the K/V of the live positions (not of
``max_seq``), write one position of K/V per request, and multiply. So the
shares of peak built on it (``step_mfu``, ``step_roofline``) cannot pass
100% whatever implements the tick.
"""
from __future__ import annotations

from typing import Iterable, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(model: dict) -> dict:
    if model.get("num_experts"):
        raise NotImplementedError(
            f"{model['name']}: decode work of expert layers is not counted "
            "yet")
    d = model["hidden_size"]
    hd = model["head_dim"]
    q, kv = model["num_attention_heads"] * hd, model["num_key_value_heads"] * hd
    layer = d * q + 2 * d * kv + q * d + 3 * d * model["intermediate_size"]
    layer_norms = 2 * d
    head = d * model["vocab_size"]
    return {"layers": model["num_hidden_layers"], "layer_params": layer,
            "norm_params": model["num_hidden_layers"] * layer_norms + d,
            "head_params": head, "q_dim": q, "kv_dim": kv,
            "bytes": DTYPE_BYTES[model["dtype"]]}


def weight_bytes(model: dict) -> int:
    """Every weight a tick must read: the layers, the norms and the output
    head (the embedding is read one row per request)."""
    m = dims(model)
    return m["bytes"] * (m["layers"] * m["layer_params"] + m["norm_params"]
                         + m["head_params"])


def kv_bytes_per_position(model: dict) -> int:
    m = dims(model)
    return 2 * m["layers"] * m["kv_dim"] * m["bytes"]


def token_flops(model: dict, live: int) -> float:
    """Multiply-adds x 2 to produce one token whose query attends over
    ``live`` positions."""
    m = dims(model)
    matmul = 2.0 * (m["layers"] * m["layer_params"] + m["head_params"])
    attn = 4.0 * m["layers"] * m["q_dim"] * live
    return matmul + attn


def tick(model: dict, live: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one tick over requests with these live lengths."""
    live = list(live)
    m = dims(model)
    kvp = kv_bytes_per_position(model)
    flops = sum(token_flops(model, n) for n in live)
    nbytes = (weight_bytes(model) + kvp * sum(live) + kvp * len(live)
              + m["bytes"] * len(live) * (model["hidden_size"]))
    return flops, float(nbytes)


def least_seconds(model: dict, live: Iterable[int], peaks: dict) -> float:
    flops, nbytes = tick(model, live)
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
