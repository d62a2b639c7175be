"""The plain reference: the configuration's forward pass in float32
``jax.numpy`` at the highest matmul precision, with no cache, batching
across requests, kernels or sharding. It imports nothing of the program.

Weights are not taken from the program. They are made again from the seed,
layer by layer, by the same draws the program's init makes (every matrix
``normal(key, shape) * fan_in ** -0.5`` in float32, rounded to the served
dtype; norm weights one; the router in float32), so both sides hold the
same numbers while neither reads the other's.

Only what the program computes is followed: a pre-norm decoder block with
RMSNorm, grouped-query attention with rotary positions over the whole
head, and a SwiGLU MLP or a mixture of SwiGLU experts (softmax router, top
k, optional shared experts behind a sigmoid gate). Where that departs from
the published model the configuration file says so.

A ``quant`` of ``"fp8"`` computes the same forward with every matmul's
operands rounded to float8 e4m3 (weights per output column, activations
per row, each scaled to the format's range): the control, one precision
below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


class Weights:
    """The program's weights, drawn again from ``seed`` one piece at a
    time and returned in float32."""

    def __init__(self, model: dict, seed: int):
        self.m = model
        self.dtype = DTYPES[model["dtype"]]
        self.d = model["hidden_size"]
        self.hd = model["head_dim"]
        self.q = model["num_attention_heads"] * self.hd
        self.kv = model["num_key_value_heads"] * self.hd
        ks = jax.random.split(jax.random.PRNGKey(seed), 6)
        self.ks = ks
        self.layer_keys = jax.random.split(ks[1], model["num_hidden_layers"])
        self._layer = jax.jit(self._make_layer)

    def embed(self) -> jax.Array:
        m = self.m
        return _normal(self.ks[0], (m["vocab_size"], self.d), self.d ** -0.5,
                       self.dtype)

    def head(self) -> jax.Array:
        m = self.m
        if m.get("tie_word_embeddings"):
            return self.embed().astype(jnp.float32).T
        return _normal(self.ks[2], (self.d, m["vocab_size"]),
                       self.d ** -0.5, self.dtype).astype(jnp.float32)

    def layer(self, i: int) -> dict:
        return self._layer(self.layer_keys[i])

    def _make_layer(self, key) -> dict:
        d, q, kv, dt = self.d, self.q, self.kv, self.dtype
        f32 = jnp.float32
        ks = jax.random.split(key, 6)
        a = jax.random.split(ks[0], 4)
        w = {"wq": _normal(a[0], (d, q), d ** -0.5, dt),
             "wk": _normal(a[1], (d, kv), d ** -0.5, dt),
             "wv": _normal(a[2], (d, kv), d ** -0.5, dt),
             "wo": _normal(a[3], (q, d), q ** -0.5, dt)}
        E = self.m.get("num_experts", 0)
        if E:
            f = self.m["moe_intermediate_size"]
            mk = jax.random.split(ks[3], 6)

            def experts(k, i, o, scale):
                kk = jax.random.split(k, E)
                return jnp.stack([_normal(kk[e], (i, o), scale, dt)
                                  for e in range(E)])
            w["router"] = _normal(mk[0], (d, E), d ** -0.5, f32)
            w["e_gate"] = experts(mk[1], d, f, d ** -0.5)
            w["e_up"] = experts(mk[2], d, f, d ** -0.5)
            w["e_down"] = experts(mk[3], f, d, f ** -0.5)
            sf = self.m.get("shared_expert_intermediate_size", 0)
            if sf:
                k1, k2, k3 = jax.random.split(mk[4], 3)
                w["s_gate"] = _normal(k1, (d, sf), d ** -0.5, dt)
                w["s_up"] = _normal(k2, (d, sf), d ** -0.5, dt)
                w["s_down"] = _normal(k3, (sf, d), sf ** -0.5, dt)
                w["s_router"] = _normal(mk[5], (d, 1), d ** -0.5, dt)
        else:
            f = self.m["intermediate_size"]
            mk = jax.random.split(ks[4], 3)
            w["w_gate"] = _normal(mk[0], (d, f), d ** -0.5, dt)
            w["w_up"] = _normal(mk[1], (d, f), d ** -0.5, dt)
            w["w_down"] = _normal(mk[2], (f, d), f ** -0.5, dt)
        return {k: v.astype(f32) for k, v in w.items()}


# ------------------------------------------------------------- precision


def fp8_columns(w):
    """Round a weight to float8 e4m3 with one scale per output column."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True),
                        1e-30) / FP8_MAX
    return (w / scale).astype(FP8).astype(jnp.float32) * scale


def fp8_rows(x):
    """Round activations to float8 e4m3 with one scale per row."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                        1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def make_matmul(quant: Optional[str]):
    if quant is None:
        return lambda x, w: x @ w
    if quant == "fp8":
        return lambda x, w: fp8_rows(x) @ fp8_columns(w)
    raise ValueError(f"unknown precision {quant!r}")


# --------------------------------------------------------------- forward


def rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, positions, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(w, h, positions, m, mm):
    B, S, _ = h.shape
    H, Hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    q = mm(h, w["wq"]).reshape(B, S, H, hd)
    k = mm(h, w["wk"]).reshape(B, S, Hkv, hd)
    v = mm(h, w["wv"]).reshape(B, S, Hkv, hd)
    q, k = rope(q, positions, m["rope_theta"]), rope(k, positions,
                                                      m["rope_theta"])
    g = H // Hkv
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, H * hd)
    return mm(o, w["wo"])


def swiglu(h, gate, up, down, mm):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


def experts(w, h, m, mm):
    """Every routed expert on every token, weighted by the router's top-k
    probabilities (zero for the experts a token was not sent to)."""
    probs = jax.nn.softmax(h @ w["router"], axis=-1)
    top, idx = jax.lax.top_k(probs, m["num_experts_per_tok"])
    if m.get("norm_topk_prob"):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    comb = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1]) * top[..., None],
                   axis=-2)                                  # [..., E]
    y = jnp.zeros_like(h)
    for e in range(probs.shape[-1]):
        ye = swiglu(h, w["e_gate"][e], w["e_up"][e], w["e_down"][e], mm)
        y = y + comb[..., e:e + 1] * ye
    if "s_gate" in w:
        shared = swiglu(h, w["s_gate"], w["s_up"], w["s_down"], mm)
        y = y + jax.nn.sigmoid(h @ w["s_router"]) * shared
    return y


def layer_apply(w, x, positions, m, quant=None):
    mm = make_matmul(quant)
    eps = m["rms_norm_eps"]
    x = x + attention(w, rmsnorm(x, eps), positions, m, mm)
    h = rmsnorm(x, eps)
    if "router" in w:
        return x + experts(w, h, m, mm)
    return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"], mm)


def hidden_states(weights: Weights, tokens: np.ndarray, quants=(None,)):
    """Final-normed hidden states [N, S, d] of right-padded token rows, one
    stream per entry of ``quants``, layer by layer."""
    m = weights.m
    if m.get("sliding_window") and tokens.shape[1] > m["sliding_window"]:
        raise NotImplementedError("rows longer than the sliding window")
    with jax.default_matmul_precision("highest"):
        emb = weights.embed()
        x0 = jnp.asarray(emb[jnp.asarray(tokens)], jnp.float32)
        del emb
        pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        steps = [jax.jit(functools.partial(layer_apply, m=m, quant=q))
                 for q in quants]
        xs = [x0 for _ in quants]
        for i in range(m["num_hidden_layers"]):
            w = weights.layer(i)
            xs = [step(w, x, pos) for step, x in zip(steps, xs)]
            del w
        return [rmsnorm(x, m["rms_norm_eps"]) for x in xs]


def head_readings(head, tokens, h, h_control=None, block=512) -> dict:
    """At rows ``h`` [M, d] and the tokens served there: the reference's
    log-probability of each token (``lp``) and how far it lies below the
    row's best (``gap``). With ``h_control``, the control's log-probability
    of the same tokens (``control_lp``) and the reference's gap of the
    token the control puts first (``control_gap``). ``block`` rows at a
    time, so the [rows, vocab] logits never exist whole."""
    head_q = fp8_columns(head) if h_control is not None else None

    # the head goes in as an argument: a closed-over array would be baked
    # into the program as a constant
    @jax.jit
    def one(head, head_q, hb, tb, cb):
        logp = jax.nn.log_softmax(hb @ head, axis=-1)
        best = logp.max(axis=-1)
        lp = jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]
        out = {"lp": lp, "gap": best - lp}
        if cb is not None:
            clogp = jax.nn.log_softmax(fp8_rows(cb) @ head_q, axis=-1)
            top = jnp.argmax(clogp, axis=-1)
            out["control_lp"] = jnp.take_along_axis(
                clogp, tb[:, None], axis=-1)[:, 0]
            out["control_gap"] = best - jnp.take_along_axis(
                logp, top[:, None], axis=-1)[:, 0]
        return out

    def padded(x, n):           # every block the same shape: one program
        return jnp.pad(x, [(0, block - n)] + [(0, 0)] * (x.ndim - 1))

    parts = []
    with jax.default_matmul_precision("highest"):
        for s in range(0, h.shape[0], block):
            n = min(block, h.shape[0] - s)
            cb = (None if h_control is None
                  else padded(h_control[s:s + n], n))
            out = jax.device_get(one(
                head, head_q, padded(h[s:s + n], n),
                padded(jnp.asarray(tokens[s:s + n]), n), cb))
            parts.append({k: v[:n] for k, v in out.items()})
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
