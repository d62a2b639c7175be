"""Analytic work of one decode tick, from the configuration's sizes alone.

A tick feeds one token to each active request and attends over that
request's live positions. Its least work is what any implementation must
do: read every weight it uses once, read the K/V of the live positions (not
of ``max_seq``), write one position of K/V per request, and multiply. So
the shares of peak built on it (``step_mfu``, ``step_roofline``) cannot
pass 100% whatever implements the tick.

An expert layer (``num_experts`` in the file) computes, per token, its
attention, the router (``d x E``, float32), ``num_experts_per_tok`` routed
experts of ``3 d f`` each, and the shared expert with its sigmoid gate
(``3 d f_shared + d``). A tick reads the routed experts its tokens reach.
"""
from __future__ import annotations

from typing import Iterable, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
ROUTER_BYTES = 4            # the router's weights are float32


def dims(model: dict) -> dict:
    """Parameter counts of one layer. ``layer_params`` is what every token
    reads in the served dtype (attention, and the dense MLP or the shared
    expert with its gate); ``router_params`` and ``expert_params`` (one
    routed expert) are 0 for a dense layer."""
    d = model["hidden_size"]
    hd = model["head_dim"]
    q, kv = model["num_attention_heads"] * hd, model["num_key_value_heads"] * hd
    layer = d * q + 2 * d * kv + q * d
    E = model.get("num_experts", 0)
    if E:
        sf = model["shared_expert_intermediate_size"]
        layer += 3 * d * sf + (d if sf else 0)
        top_k, router = model["num_experts_per_tok"], d * E
        expert = 3 * d * model["moe_intermediate_size"]
    else:
        layer += 3 * d * model["intermediate_size"]
        top_k = router = expert = 0
    layer_norms = 2 * d
    head = d * model["vocab_size"]
    return {"layers": model["num_hidden_layers"], "layer_params": layer,
            "experts": E, "top_k": top_k, "router_params": router,
            "expert_params": expert,
            "norm_params": model["num_hidden_layers"] * layer_norms + d,
            "head_params": head, "q_dim": q, "kv_dim": kv,
            "bytes": DTYPE_BYTES[model["dtype"]]}


def experts_reached(model: dict, tokens: int) -> float:
    """Routed experts of one layer that ``tokens`` tokens reach, each sent to
    ``k`` of ``E``: ``E (1 - (1 - k/E)^T)``. This is the expectation under
    uniform routing, not a strict bound: a router that sends the tokens to
    fewer experts reads fewer."""
    m = dims(model)
    if not m["experts"]:
        return 0.0
    return m["experts"] * (1.0 - (1.0 - m["top_k"] / m["experts"]) ** tokens)


def weight_bytes(model: dict, tokens: int) -> float:
    """Every weight a tick of ``tokens`` requests must read: the layers (of
    an expert layer, the router and the routed experts its tokens reach),
    the norms and the output head (the embedding is read one row per
    request)."""
    m = dims(model)
    nbytes = m["bytes"] * (m["layers"] * m["layer_params"] + m["norm_params"]
                           + m["head_params"])
    if m["experts"]:
        nbytes += m["layers"] * (
            ROUTER_BYTES * m["router_params"]
            + m["bytes"] * m["expert_params"] * experts_reached(model, tokens))
    return nbytes


def kv_bytes_per_position(model: dict) -> int:
    m = dims(model)
    return 2 * m["layers"] * m["kv_dim"] * m["bytes"]


def token_flops(model: dict, live: int) -> float:
    """Multiply-adds x 2 to produce one token whose query attends over
    ``live`` positions."""
    m = dims(model)
    per_layer = (m["layer_params"] + m["router_params"]
                 + m["top_k"] * m["expert_params"])
    matmul = 2.0 * (m["layers"] * per_layer + m["head_params"])
    attn = 4.0 * m["layers"] * m["q_dim"] * live
    return matmul + attn


def tick(model: dict, live: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one tick over requests with these live lengths."""
    live = list(live)
    m = dims(model)
    kvp = kv_bytes_per_position(model)
    flops = sum(token_flops(model, n) for n in live)
    nbytes = (weight_bytes(model, len(live)) + kvp * sum(live)
              + kvp * len(live)
              + m["bytes"] * len(live) * (model["hidden_size"]))
    return flops, float(nbytes)


def least_seconds(model: dict, live: Iterable[int], peaks: dict,
                  chips: int) -> float:
    """The least time of one tick on ``chips`` chips: all its FLOPs over the
    chips' summed peak, or all its bytes over their summed bandwidth,
    whichever is longer. However the work is laid out across the chips,
    none can finish sooner."""
    flops, nbytes = tick(model, live)
    return max(flops / (chips * peaks["bf16_flops_per_s"]),
               nbytes / (chips * peaks["hbm_bytes_per_s"]))
