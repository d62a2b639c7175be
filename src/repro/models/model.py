"""Model assembly: init / forward / loss / prefill / decode for all families.

Families (from the assigned architectures):
  dense   — pre-norm GQA transformer (llama-like), optional SWA
  moe     — dense attention + MoE FFN (+ optional shared experts)
  ssm     — Mamba-2 (SSD) mixer blocks, attention-free
  hybrid  — hymba: attention ∥ SSM heads in parallel, learned meta tokens
  vlm     — dense LM backbone consuming stubbed patch embeddings
  audio   — whisper enc-dec backbone consuming stubbed frame embeddings

Everything is pure-functional: ``init_params(key, cfg)`` builds a pytree of
arrays; apply fns are jit/pjit-compatible with only `cfg`/`pcfg` static.
Layer stacks are ``lax.scan`` over stacked per-layer params with configurable
``jax.checkpoint`` (full activation checkpointing by default, as the paper
trained with).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ParallelConfig
from .attention import (attn_apply, attn_decode_apply, attn_extend_apply,
                        attn_init, attn_paged_decode_apply, cross_attn_apply,
                        cross_attn_kv)
from .layers import (embed_init, mlp_apply, mlp_init, rmsnorm, rmsnorm_init,
                     sinusoidal_positions)
from .moe import moe_apply, moe_decode_apply, moe_init
from .ssm import init_ssm_state, ssm_apply, ssm_decode_step, ssm_init

DEFAULT_PARALLEL = ParallelConfig()


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _decoder_layer_init(key, cfg: ModelConfig, dtype):
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    p = {"ln1": rmsnorm_init(d, dtype)}
    if cfg.uses_attention:
        p["attn"] = attn_init(ks[0], cfg, dtype)
    if cfg.ssm is not None:
        p["ssm"] = ssm_init(ks[1], cfg, dtype)
    if cfg.parallel_ssm:
        p["attn_out_norm"] = rmsnorm_init(d, dtype)
        p["ssm_out_norm"] = rmsnorm_init(d, dtype)
    if cfg.is_encoder_decoder:
        p["ln_cross"] = rmsnorm_init(d, dtype)
        p["cross"] = attn_init(ks[2], cfg, dtype)
    if cfg.moe is not None:
        p["ln2"] = rmsnorm_init(d, dtype)
        p["moe"] = moe_init(ks[3], cfg, dtype)
    elif cfg.d_ff:
        p["ln2"] = rmsnorm_init(d, dtype)
        p["mlp"] = mlp_init(ks[4], d, cfg.d_ff, dtype)
    return p


def _encoder_layer_init(key, cfg: ModelConfig, dtype):
    d = cfg.d_model
    k1, k2 = jax.random.split(key)
    return {
        "ln1": rmsnorm_init(d, dtype),
        "attn": attn_init(k1, cfg, dtype),
        "ln2": rmsnorm_init(d, dtype),
        "mlp": mlp_init(k2, d, cfg.d_ff, dtype),
    }


def _stack_init(key, n, init_fn):
    # one layer at a time (same values as a vmap over the keys): a jitted
    # init then holds one layer's float32 draws in temporaries, not all of
    # them — what lets a full-depth model be created on the device(s) it
    # is served from
    return jax.lax.map(init_fn, jax.random.split(key, n))


def init_params(key, cfg: ModelConfig, dtype=None):
    """Build the parameter pytree. Layer params are stacked on a leading [L]."""
    dtype = jnp.dtype(dtype or cfg.dtype)
    ks = jax.random.split(key, 6)
    p = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "layers": _stack_init(ks[1], cfg.num_layers,
                              lambda k: _decoder_layer_init(k, cfg, dtype)),
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        d = cfg.d_model
        p["lm_head"] = (jax.random.normal(ks[2], (d, cfg.vocab_size),
                                          jnp.float32) * d ** -0.5).astype(dtype)
    if cfg.num_meta_tokens:
        p["meta_tokens"] = (jax.random.normal(
            ks[3], (cfg.num_meta_tokens, cfg.d_model), jnp.float32)
            * cfg.d_model ** -0.5).astype(dtype)
    if cfg.is_encoder_decoder:
        p["encoder"] = {
            "layers": _stack_init(ks[4], cfg.num_encoder_layers,
                                  lambda k: _encoder_layer_init(k, cfg, dtype)),
            "final_norm": rmsnorm_init(cfg.d_model, dtype),
        }
    return p


# ---------------------------------------------------------------------------
# Full-sequence forward (training / prefill)
# ---------------------------------------------------------------------------


def _decoder_layer_apply(lp, x, positions, cfg, pcfg, enc_out=None):
    """One decoder layer, full-sequence. Returns (x, aux)."""
    aux = {}
    h = rmsnorm(x, lp["ln1"], cfg.rms_eps)
    if cfg.family == "ssm":
        out, _ = ssm_apply(lp["ssm"], h, cfg)
        x = x + out
    else:
        attn_out, _ = attn_apply(lp["attn"], h, positions, cfg,
                                 use_pallas=pcfg.use_pallas,
                                 context_parallel=pcfg.context_parallel > 1)
        if cfg.parallel_ssm:
            ssm_out, _ = ssm_apply(lp["ssm"], h, cfg)
            attn_out = 0.5 * (
                rmsnorm(attn_out, lp["attn_out_norm"], cfg.rms_eps)
                + rmsnorm(ssm_out, lp["ssm_out_norm"], cfg.rms_eps))
        x = x + attn_out
    if enc_out is not None:
        h = rmsnorm(x, lp["ln_cross"], cfg.rms_eps)
        k, v = cross_attn_kv(lp["cross"], enc_out, cfg)
        x = x + cross_attn_apply(lp["cross"], h, k, v, cfg)
    if cfg.moe is not None:
        h = rmsnorm(x, lp["ln2"], cfg.rms_eps)
        out, aux = moe_apply(lp["moe"], h, cfg, use_pallas=pcfg.use_pallas,
                             expert_parallel=pcfg.expert_parallel)
        x = x + out
    elif cfg.d_ff:
        x = x + mlp_apply(lp["mlp"], rmsnorm(x, lp["ln2"], cfg.rms_eps))
    return x, aux


def _maybe_remat(fn, pcfg):
    if pcfg.remat == "full":
        return jax.checkpoint(fn, prevent_cse=False)
    if pcfg.remat == "selective":
        return jax.checkpoint(
            fn, prevent_cse=False,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


def _gather_weights(lp):
    """FSDP gather-at-use (§Perf H5): replicate this layer's weight slices
    for the duration of the layer — GSPMD lowers the constraint to per-layer
    weight all-gathers (and weight-grad reduce-scatters in the transpose),
    keeping activations collective-free.

    MoE expert stacks (per-layer ndim 3: [E, d, f]) are NOT gathered — they
    stay expert-sharded and the dispatch buffer moves to them instead
    (expert parallelism, §2.1.8); gathering 128 experts per layer would be
    ~50x the dense-weight traffic."""
    from jax.sharding import PartitionSpec as P
    return jax.tree_util.tree_map(
        lambda w: (w if w.ndim >= 3
                   else jax.lax.with_sharding_constraint(w, P())), lp)


def _scan_layers(layers, x, layer_fn, pcfg):
    if pcfg.fsdp_gather_weights:
        inner = layer_fn
        layer_fn = lambda lp, y: inner(_gather_weights(lp), y)
    layer_fn = _maybe_remat(layer_fn, pcfg)
    if pcfg.scan_layers:
        def body(carry, lp):
            y, aux = layer_fn(lp, carry)
            return y, aux
        x, auxs = jax.lax.scan(body, x, layers)
        aux = {k: jnp.mean(v) for k, v in auxs.items()} if auxs else {}
        # aux losses must *sum* over layers; means are for metrics
        if "moe_aux_loss" in auxs:
            aux["moe_aux_loss"] = jnp.sum(auxs["moe_aux_loss"])
        return x, aux
    # unrolled python loop (debug / small models)
    n = jax.tree_util.tree_leaves(layers)[0].shape[0]
    aux_acc = {}
    for i in range(n):
        lp = jax.tree_util.tree_map(lambda a: a[i], layers)
        x, aux = layer_fn(lp, x)
        for k, v in aux.items():
            aux_acc.setdefault(k, []).append(v)
    aux = {k: (jnp.sum(jnp.stack(v)) if k == "moe_aux_loss"
               else jnp.mean(jnp.stack(v))) for k, v in aux_acc.items()}
    return x, aux


def encode(params, frames, cfg: ModelConfig, pcfg=DEFAULT_PARALLEL):
    """Whisper encoder over stubbed frame embeddings [B, T, d]."""
    B, T, d = frames.shape
    pos = sinusoidal_positions(jnp.arange(T), d)[None].astype(frames.dtype)
    x = frames + pos

    def layer_fn(lp, x):
        h = rmsnorm(x, lp["ln1"], cfg.rms_eps)
        out, _ = attn_apply(lp["attn"], h, jnp.zeros((B, T), jnp.int32), cfg,
                            causal=False)
        x = x + out
        x = x + mlp_apply(lp["mlp"], rmsnorm(x, lp["ln2"], cfg.rms_eps))
        return x, {}

    x, _ = _scan_layers(params["encoder"]["layers"], x, layer_fn, pcfg)
    return rmsnorm(x, params["encoder"]["final_norm"], cfg.rms_eps)


def embed_inputs(params, batch, cfg: ModelConfig):
    """Token embedding + family-specific input fusion.

    Returns (x [B, S_eff, d], positions [B, S_eff], n_prefix) where n_prefix
    counts prepended non-text slots (meta tokens) that are dropped from the
    output hidden states.
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = params["embed"][tokens]
    if cfg.family == "vlm" and "patch_embeds" in batch:
        # first num_image_tokens positions are image-patch slots (carve-out
        # stub): overwrite their embeddings with the projector outputs.
        pe = batch["patch_embeds"].astype(x.dtype)
        n_img = pe.shape[1]
        assert S >= n_img, (
            f"VLM prompt ({S} tokens) must cover the {n_img} image slots")
        x = jnp.concatenate([pe, x[:, n_img:]], axis=1)
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    n_prefix = 0
    if cfg.num_meta_tokens:
        n_prefix = cfg.num_meta_tokens
        meta = jnp.broadcast_to(params["meta_tokens"][None],
                                (B, n_prefix, cfg.d_model)).astype(x.dtype)
        x = jnp.concatenate([meta, x], axis=1)
        meta_pos = jnp.broadcast_to(
            jnp.arange(n_prefix, dtype=jnp.int32)[None], (B, n_prefix))
        positions = jnp.concatenate([meta_pos, positions + n_prefix], axis=1)
    if cfg.rope_theta == 0.0:  # whisper: sinusoidal absolute positions
        x = x + sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)
    return x, positions, n_prefix


def forward_hidden(params, batch, cfg: ModelConfig, pcfg=DEFAULT_PARALLEL):
    """Full-sequence decoder forward. Returns (hidden [B,S,d], aux)."""
    x, positions, n_prefix = embed_inputs(params, batch, cfg)
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = encode(params, batch["frames"], cfg, pcfg)

    def layer_fn(lp, x):
        return _decoder_layer_apply(lp, x, positions, cfg, pcfg, enc_out)

    x, aux = _scan_layers(params["layers"], x, layer_fn, pcfg)
    if n_prefix:
        x = x[:, n_prefix:]
    return rmsnorm(x, params["final_norm"], cfg.rms_eps), aux


def head_weights(params, cfg: ModelConfig):
    """[d, V] unembedding matrix (tied or untied)."""
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


def forward(params, batch, cfg: ModelConfig, pcfg=DEFAULT_PARALLEL):
    """Full logits [B, S, V] — small-model paths (tests, toy RL)."""
    hidden, aux = forward_hidden(params, batch, cfg, pcfg)
    logits = (hidden @ head_weights(params, cfg)).astype(jnp.float32)
    return logits, aux


# ---------------------------------------------------------------------------
# Chunked vocab loss (the [B,S,V] logits tensor is never materialized)
# ---------------------------------------------------------------------------


def chunked_token_nll(hidden, head_w, labels, chunk: int):
    """Per-token negative log-likelihood [B, S], computed over S-chunks so the
    live logits buffer is [B, chunk, V] instead of [B, S, V]."""
    B, S, d = hidden.shape
    if chunk <= 0 or S <= chunk:
        logits = (hidden @ head_w).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return lse - tgt
    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))

    def one(i):
        h = jax.lax.dynamic_slice_in_dim(hidden, i * chunk, chunk, axis=1)
        lab = jax.lax.dynamic_slice_in_dim(labels, i * chunk, chunk, axis=1)
        logits = (h @ head_w).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        return lse - tgt  # [B, chunk]

    nll = jax.lax.map(one, jnp.arange(nc))           # [nc, B, chunk]
    nll = nll.transpose(1, 0, 2).reshape(B, nc * chunk)
    return nll[:, :S]


def token_logprobs(params, batch, cfg: ModelConfig, pcfg=DEFAULT_PARALLEL):
    """Per-token log p(labels) [B, S] plus aux — used by both SFT and RL."""
    hidden, aux = forward_hidden(params, batch, cfg, pcfg)
    nll = chunked_token_nll(hidden, head_weights(params, cfg),
                            batch["labels"], pcfg.loss_chunk)
    return -nll, aux


def lm_loss(params, batch, cfg: ModelConfig, pcfg=DEFAULT_PARALLEL):
    """Masked mean cross-entropy. batch: tokens, labels, loss_mask."""
    logp, aux = token_logprobs(params, batch, cfg, pcfg)
    mask = batch["loss_mask"].astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = -(logp * mask).sum() / denom
    metrics = {"lm_loss": loss, **aux}
    if "moe_aux_loss" in aux:
        loss = loss + aux["moe_aux_loss"]
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# Decode (serving): one token in, one token out, static-shape caches
# ---------------------------------------------------------------------------


def init_decode_state(cfg: ModelConfig, batch: int, max_seq: int, dtype=None):
    """Static-shape decode caches, stacked over layers on dim 0."""
    dtype = jnp.dtype(dtype or cfg.dtype)
    L, hd = cfg.num_layers, cfg.resolved_head_dim
    state = {"pos": jnp.zeros((batch,), jnp.int32)}
    if cfg.uses_attention:
        kv_shape = (L, batch, max_seq, cfg.num_kv_heads, hd)
        state["k"] = jnp.zeros(kv_shape, dtype)
        state["v"] = jnp.zeros(kv_shape, dtype)
    if cfg.ssm is not None:
        s = cfg.ssm
        one = init_ssm_state(cfg, batch, dtype)
        state["ssm_conv"] = jnp.broadcast_to(one["conv"][None],
                                             (L,) + one["conv"].shape).copy()
        state["ssm_h"] = jnp.broadcast_to(one["ssm"][None],
                                          (L,) + one["ssm"].shape).copy()
    if cfg.is_encoder_decoder:
        T = cfg.encoder_seq_len
        state["cross_k"] = jnp.zeros((L, batch, T, cfg.num_kv_heads, hd), dtype)
        state["cross_v"] = jnp.zeros((L, batch, T, cfg.num_kv_heads, hd), dtype)
    return state


def _decoder_layer_decode(lp, x, pos, caches, cfg):
    """One layer, one token. caches: per-layer slice dict. Returns (x, caches)."""
    new = dict(caches)
    h = rmsnorm(x, lp["ln1"], cfg.rms_eps)
    if cfg.family == "ssm":
        out, st = ssm_decode_step(lp["ssm"], h,
                                  {"conv": caches["ssm_conv"],
                                   "ssm": caches["ssm_h"]}, cfg)
        new["ssm_conv"], new["ssm_h"] = st["conv"], st["ssm"]
        x = x + out
    else:
        attn_out, k, v = attn_decode_apply(lp["attn"], h, caches["k"],
                                           caches["v"], pos, cfg)
        new["k"], new["v"] = k, v
        if cfg.parallel_ssm:
            ssm_out, st = ssm_decode_step(lp["ssm"], h,
                                          {"conv": caches["ssm_conv"],
                                           "ssm": caches["ssm_h"]}, cfg)
            new["ssm_conv"], new["ssm_h"] = st["conv"], st["ssm"]
            attn_out = 0.5 * (
                rmsnorm(attn_out, lp["attn_out_norm"], cfg.rms_eps)
                + rmsnorm(ssm_out, lp["ssm_out_norm"], cfg.rms_eps))
        x = x + attn_out
    if cfg.is_encoder_decoder:
        h = rmsnorm(x, lp["ln_cross"], cfg.rms_eps)
        x = x + cross_attn_apply(lp["cross"], h, caches["cross_k"],
                                 caches["cross_v"], cfg)
    if cfg.moe is not None:
        h = rmsnorm(x, lp["ln2"], cfg.rms_eps)
        x = x + moe_decode_apply(lp["moe"], h, cfg)
    elif cfg.d_ff:
        x = x + mlp_apply(lp["mlp"], rmsnorm(x, lp["ln2"], cfg.rms_eps))
    return x, new


_CACHE_KEYS = ("k", "v", "ssm_conv", "ssm_h", "cross_k", "cross_v")

# recurrent per-row state: must be frozen (not drift-overwritten) for
# inactive rows — see ``serve_step``'s ``active`` contract
_RECURRENT_KEYS = ("ssm_conv", "ssm_h")


def _freeze_inactive_recurrent(new_caches, old_caches, active):
    """Keep inactive rows' recurrent state bitwise unchanged.

    Caches are ``[L, B, ...]`` (row axis 1). ``jnp.where(True, a, b)``
    selects ``a``'s bits exactly, so an all-active mask is an identity —
    which is what keeps the masked path on the byte-parity contract."""
    if active is None:
        return new_caches
    out = dict(new_caches)
    for key in _RECURRENT_KEYS:
        if key in out:
            keep = active.reshape((1, -1) + (1,) * (out[key].ndim - 2))
            out[key] = jnp.where(keep, out[key], old_caches[key])
    return out


def serve_step(params, state, token, cfg: ModelConfig, pcfg=DEFAULT_PARALLEL,
               active=None):
    """One decode step. token: [B] int32. Returns (logits [B,V], new state).

    `state["pos"]` is the *text* position (number of tokens already in the
    cache, including any meta-token prefix handled by prefill).

    ``active`` ([B] bool, optional) freezes the *recurrent* state of
    inactive rows: a parked or empty slot keeps ticking garbage tokens,
    which dense K/V tolerates (the decode mask never reads above ``pos``
    and extend overwrites the drift) but a scan state folds in
    irreversibly. With the mask, inactive rows keep their ssm_conv/ssm_h
    bits unchanged; attention-only families have no such keys and the
    mask is a no-op. ``pos`` still advances for every row, mirroring the
    dense drift semantics."""
    B = token.shape[0]
    pos = state["pos"]
    x = params["embed"][token][:, None, :]
    if cfg.rope_theta == 0.0:
        x = x + sinusoidal_positions(pos[:, None], cfg.d_model).astype(x.dtype)

    per_layer = {k: state[k] for k in _CACHE_KEYS if k in state}

    def body(x, inp):
        lp, caches = inp
        x, new = _decoder_layer_decode(lp, x, pos, caches, cfg)
        return x, new

    x, new_caches = jax.lax.scan(body, x, (params["layers"], per_layer))
    new_caches = _freeze_inactive_recurrent(new_caches, per_layer, active)
    x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
    logits = (x[:, 0] @ head_weights(params, cfg)).astype(jnp.float32)
    new_state = dict(state)
    new_state.update(new_caches)
    new_state["pos"] = pos + 1
    return logits, new_state


def prefill(params, batch, cfg: ModelConfig, max_seq: int,
            pcfg=DEFAULT_PARALLEL, dtype=None):
    """Run the prompt through the model, filling decode caches.

    Returns (logits_last [B,V], state). Prompt length S must be <= max_seq.

    For *right-padded* prompt batches (the engine's bucketed prefill) pass
    ``batch["prompt_lens"]`` [B]: the last-token logits are gathered per row
    at ``prompt_lens - 1`` and ``state["pos"]`` is set per row, so decode
    overwrites the padded cache tail and the decode attention mask
    (``k_idx <= pos``) never reads it. Recurrent (SSM/hybrid) layers are
    pad-masked instead: ``ssm_apply`` receives the per-row valid lengths
    and forces dt to 0 at pad positions, so pads pass the scan state
    through exactly and the conv state window ends at each row's last
    valid token — right padding is sound for every family.
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    prompt_lens = batch.get("prompt_lens")
    x, positions, n_prefix = embed_inputs(params, batch, cfg)
    enc_out = encode(params, batch["frames"], cfg, pcfg) \
        if cfg.is_encoder_decoder else None
    # cache dtype follows the params dtype unless overridden (fp32 tests get
    # fp32 caches; bf16 production params get bf16 caches)
    state = init_decode_state(cfg, B, max_seq,
                              dtype or params["embed"].dtype)

    layers = params["layers"]
    L = cfg.num_layers
    # SSM valid lengths include the meta-token prefix (meta rows are real
    # scan inputs; only right-pad tail positions must be masked out)
    ssm_lens = None if prompt_lens is None else \
        prompt_lens.astype(jnp.int32) + n_prefix

    def body(x, inp):
        lp, caches = inp
        new = dict(caches)
        h = rmsnorm(x, lp["ln1"], cfg.rms_eps)
        if cfg.family == "ssm":
            out, st = ssm_apply(lp["ssm"], h, cfg, seq_lens=ssm_lens)
            new["ssm_conv"], new["ssm_h"] = st["conv"], st["ssm"]
            x = x + out
        else:
            attn_out, (k, v) = attn_apply(lp["attn"], h, positions, cfg,
                                          use_pallas=pcfg.use_pallas)
            W = caches["k"].shape[1]
            if W < k.shape[1]:
                # ring cache (W == sliding_window): keep the last W tokens
                # at slots (position % W)
                tail_pos = jnp.arange(k.shape[1] - W, k.shape[1])
                slots = tail_pos % W
                new["k"] = caches["k"].at[:, slots].set(
                    k[:, -W:].astype(caches["k"].dtype))
                new["v"] = caches["v"].at[:, slots].set(
                    v[:, -W:].astype(caches["v"].dtype))
            else:
                new["k"] = jax.lax.dynamic_update_slice_in_dim(
                    caches["k"], k.astype(caches["k"].dtype), 0, axis=1)
                new["v"] = jax.lax.dynamic_update_slice_in_dim(
                    caches["v"], v.astype(caches["v"].dtype), 0, axis=1)
            if cfg.parallel_ssm:
                ssm_out, st = ssm_apply(lp["ssm"], h, cfg, seq_lens=ssm_lens)
                new["ssm_conv"], new["ssm_h"] = st["conv"], st["ssm"]
                attn_out = 0.5 * (
                    rmsnorm(attn_out, lp["attn_out_norm"], cfg.rms_eps)
                    + rmsnorm(ssm_out, lp["ssm_out_norm"], cfg.rms_eps))
            x = x + attn_out
        if cfg.is_encoder_decoder:
            hh = rmsnorm(x, lp["ln_cross"], cfg.rms_eps)
            ck, cv = cross_attn_kv(lp["cross"], enc_out, cfg)
            new["cross_k"] = ck.astype(caches["cross_k"].dtype)
            new["cross_v"] = cv.astype(caches["cross_v"].dtype)
            x = x + cross_attn_apply(lp["cross"], hh, ck, cv, cfg)
        if cfg.moe is not None:
            hh = rmsnorm(x, lp["ln2"], cfg.rms_eps)
            out, _ = moe_apply(lp["moe"], hh, cfg, use_pallas=pcfg.use_pallas,
                               expert_parallel=pcfg.expert_parallel)
            x = x + out
        elif cfg.d_ff:
            x = x + mlp_apply(lp["mlp"], rmsnorm(x, lp["ln2"], cfg.rms_eps))
        return x, new

    per_layer = {k: state[k] for k in _CACHE_KEYS if k in state}
    x, new_caches = jax.lax.scan(body, x, (layers, per_layer))
    if prompt_lens is None:
        x_last = x[:, -1]
        pos = jnp.full((B,), S + n_prefix, jnp.int32)
    else:
        last_idx = jnp.clip(prompt_lens - 1, 0, S - 1) + n_prefix
        x_last = x[jnp.arange(B), last_idx]
        pos = prompt_lens.astype(jnp.int32) + n_prefix
    x_last = rmsnorm(x_last, params["final_norm"], cfg.rms_eps)
    logits = (x_last @ head_weights(params, cfg)).astype(jnp.float32)
    state.update(new_caches)
    state["pos"] = pos
    return logits, state


def _decoder_layer_extend(lp, x, positions, caches, cfg, pcfg, ext_lens=None):
    """One layer over a block of new tokens continuing an existing cache.

    The multi-token sibling of ``_decoder_layer_decode``: K/V for the block
    are written into the caches at ``positions`` and each token attends
    over the full cache prefix. Recurrent (SSM/hybrid) layers continue
    their per-row scan state through ``ssm_apply`` with ``ext_lens`` as the
    pad mask — right-padded extend blocks pass the state through pads
    exactly, same contract as prefill.
    """
    new = dict(caches)
    h = rmsnorm(x, lp["ln1"], cfg.rms_eps)
    if cfg.family == "ssm":
        out, st = ssm_apply(lp["ssm"], h, cfg,
                            state={"conv": caches["ssm_conv"],
                                   "ssm": caches["ssm_h"]},
                            seq_lens=ext_lens)
        new["ssm_conv"], new["ssm_h"] = st["conv"], st["ssm"]
        x = x + out
    else:
        attn_out, k_cache, v_cache = attn_extend_apply(
            lp["attn"], h, caches["k"], caches["v"], positions, cfg)
        new["k"], new["v"] = k_cache, v_cache
        if cfg.parallel_ssm:
            ssm_out, st = ssm_apply(lp["ssm"], h, cfg,
                                    state={"conv": caches["ssm_conv"],
                                           "ssm": caches["ssm_h"]},
                                    seq_lens=ext_lens)
            new["ssm_conv"], new["ssm_h"] = st["conv"], st["ssm"]
            attn_out = 0.5 * (
                rmsnorm(attn_out, lp["attn_out_norm"], cfg.rms_eps)
                + rmsnorm(ssm_out, lp["ssm_out_norm"], cfg.rms_eps))
        x = x + attn_out
    if cfg.is_encoder_decoder:
        h = rmsnorm(x, lp["ln_cross"], cfg.rms_eps)
        x = x + cross_attn_apply(lp["cross"], h, caches["cross_k"],
                                 caches["cross_v"], cfg)
    if cfg.moe is not None:
        h = rmsnorm(x, lp["ln2"], cfg.rms_eps)
        out, _ = moe_apply(lp["moe"], h, cfg, use_pallas=pcfg.use_pallas,
                           expert_parallel=pcfg.expert_parallel)
        x = x + out
    elif cfg.d_ff:
        x = x + mlp_apply(lp["mlp"], rmsnorm(x, lp["ln2"], cfg.rms_eps))
    return x, new


def extend(params, state, batch, start_pos, cfg: ModelConfig,
           pcfg=DEFAULT_PARALLEL):
    """Continuation prefill: run a block of *new* tokens against existing
    per-row decode caches (engine sessions — §2.2.1 multi-turn rollouts).

    state: decode-state rows (caches [L, R, S_max, ...], "pos" ignored in
    favour of ``start_pos``); batch["tokens"]: right-padded [R, S_b] block
    of new tokens with batch["prompt_lens"] [R] valid lengths; start_pos
    [R]: cache position of each row's first new token. Returns
    (logits_last [R, V], new state rows) with the same right-padding
    contract as ``prefill``: logits gathered at ``prompt_lens - 1``,
    ``pos`` advanced by ``prompt_lens``, padded-tail cache writes land
    above ``pos`` and are never read before decode overwrites them.
    Recurrent (SSM/hybrid) rows continue their scan state with pads
    masked out, so the same bucketing is sound for every family.
    Callers must guarantee ``start_pos + S_b <= S_max``.

    A zero-length delta (``S_b == 0`` — e.g. ``max_new_tokens=0`` turns,
    or a chunked-prefill boundary chunk) is a bit-exact no-op: caches are
    returned untouched and ``pos`` stays at ``start_pos`` (``ext_lens``
    must be all zeros). Both speculative verification and chunked prefill
    lean on this guarantee.
    """
    tokens = batch["tokens"]
    ext_lens = batch["prompt_lens"]
    R, S = tokens.shape
    start = start_pos.astype(jnp.int32)
    if S == 0:  # zero-length delta: bit-exact no-op on caches and pos
        new_state = dict(state)
        new_state["pos"] = start + ext_lens.astype(jnp.int32)
        logits = jnp.zeros((R, head_weights(params, cfg).shape[-1]),
                           dtype=jnp.float32)
        return logits, new_state
    x, new_caches = _extend_hidden(params, state, tokens, ext_lens, start,
                                   cfg, pcfg)
    last_idx = jnp.clip(ext_lens - 1, 0, S - 1)
    x_last = x[jnp.arange(R), last_idx]
    x_last = rmsnorm(x_last, params["final_norm"], cfg.rms_eps)
    logits = (x_last @ head_weights(params, cfg)).astype(jnp.float32)
    new_state = dict(state)
    new_state.update(new_caches)
    new_state["pos"] = start + ext_lens.astype(jnp.int32)
    return logits, new_state


def _extend_hidden(params, state, tokens, ext_lens, start, cfg, pcfg):
    """Shared extend trunk: embed + layer scan over a [R, S] token block.

    Returns the final hidden states ``x`` [R, S, D] (pre final-norm) and
    the updated per-layer caches. ``extend`` reads only the last valid
    position; ``extend_verify`` reads every position (speculative
    verification needs logits at each candidate offset).
    """
    R, S = tokens.shape
    positions = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    x = params["embed"][tokens]
    if cfg.rope_theta == 0.0:  # whisper: sinusoidal absolute positions
        x = x + sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)

    def body(x, inp):
        lp, caches = inp
        return _decoder_layer_extend(lp, x, positions, caches, cfg, pcfg,
                                     ext_lens=ext_lens.astype(jnp.int32))

    per_layer = {k: state[k] for k in _CACHE_KEYS if k in state}
    x, new_caches = jax.lax.scan(body, x, (params["layers"], per_layer))
    return x, new_caches


def extend_verify(params, state, batch, start_pos, cfg: ModelConfig,
                  pcfg=DEFAULT_PARALLEL):
    """Multi-position verify forward: ``extend``, but with logits at EVERY
    block offset instead of only the last valid one.

    This is the speculative-decoding verification primitive: the block is
    ``[t0, d1..dk]`` (the pending sampled token followed by drafted
    candidates, right-padded to the bucket), and ``logits[:, j]`` predicts
    the token at cache position ``start_pos + j + 1`` — so offset ``j``
    verifies draft ``d_{j+1}`` and the first mismatch offset yields the
    bonus/correction token for free. Cache writes at rejected offsets land
    above the rolled-back ``pos`` and are masked by the decode/extend
    ``k_idx <= pos`` invariant until overwritten (dense rows) or dropped
    with their block refs (paged rows). Returns
    (logits [R, S, V] f32, new state rows with ``pos = start + ext_lens``).
    """
    tokens = batch["tokens"]
    ext_lens = batch["prompt_lens"]
    start = start_pos.astype(jnp.int32)
    x, new_caches = _extend_hidden(params, state, tokens, ext_lens, start,
                                   cfg, pcfg)
    x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
    logits = (x @ head_weights(params, cfg)).astype(jnp.float32)
    new_state = dict(state)
    new_state.update(new_caches)
    new_state["pos"] = start + ext_lens.astype(jnp.int32)
    return logits, new_state


# ---------------------------------------------------------------------------
# Fused sampling (device-resident decode hot path)
# ---------------------------------------------------------------------------


def _sample_logits_core(key, logits, temps):
    scaled = logits / jnp.maximum(temps[:, None], 1e-4)
    toks = jax.random.categorical(key, scaled, axis=-1)
    # temperature <= 0 is exact greedy decode: argmax is RNG-independent,
    # so a greedy stream is invariant to HOW MANY dispatches consumed the
    # key sequence (a speculating engine splits per verify round; sampling
    # a near-tie through the clamped categorical would let those extra
    # splits flip tokens the baseline tick would not)
    toks = jnp.where(temps <= 0, jnp.argmax(logits, axis=-1), toks)
    logp = jax.nn.log_softmax(logits, axis=-1)
    lps = jnp.take_along_axis(logp, toks[:, None], axis=-1)[:, 0]
    return toks.astype(jnp.int32), lps


def sample_logits(key, logits, temps):
    """Temperature-scaled categorical sampling + logprob gather, batched.

    logits: [B, V] f32; temps: [B]. Returns (tokens [B] i32, logprobs [B]
    f32) where logprobs are log-softmax of the *unscaled* logits at the
    sampled token (the trainer-consistency convention the engine records).
    ``temps <= 0`` rows decode exact greedy (argmax, no RNG): the stream
    is then independent of the dispatch/RNG-split schedule, which is what
    lets a speculating engine match a plain one byte-for-byte at temp 0.

    Under a serving mesh the draw runs inside a fully-replicated
    ``shard_map``: the categorical's gumbel bits are NOT partition-
    invariant (the threefry lowering emits different bits depending on how
    GSPMD shards the [B, V] draw — measured on multi-axis meshes even a
    replication *constraint* on the logits is not enough, because the
    partitioner may still shard the bit-generator op itself). Inside the
    shard_map every device runs the exact single-device sampling program
    on a full copy, so token/logprob streams stay byte-identical to the
    unsharded oracle.
    """
    from repro.sharding.context import current_serve_mesh
    mesh = current_serve_mesh()
    if mesh is None:
        return _sample_logits_core(key, logits, temps)
    from jax.sharding import PartitionSpec as P
    fn = jax.shard_map(_sample_logits_core, mesh=mesh,
                       in_specs=(P(), P(), P()), out_specs=(P(), P()),
                       check_vma=False)
    return fn(key, logits, temps)


def sample_step(params, state, token, temps, rng, cfg: ModelConfig,
                pcfg=DEFAULT_PARALLEL, active=None):
    """One fused decode tick: serve_step + on-device sampling.

    Consumes one split of `rng` per call (the engine's RNG discipline —
    the host-path reference engine performs the identical split sequence,
    which is what makes per-token parity checkable). ``active`` freezes
    inactive rows' recurrent state (see ``serve_step``). Returns
    (tokens [B], logprobs [B], new_state, new_rng).
    """
    rng, k = jax.random.split(rng)
    logits, new_state = serve_step(params, state, token, cfg, pcfg,
                                   active=active)
    toks, lps = sample_logits(k, logits, temps)
    return toks, lps, new_state, rng


def prefill_sample(params, batch, temps, rng, cfg: ModelConfig, max_seq: int,
                   pcfg=DEFAULT_PARALLEL):
    """Bucketed batched prefill + fused first-token sampling.

    batch["tokens"] is a right-padded [R, S_bucket] row batch with
    batch["prompt_lens"]; one RNG split covers the whole bucket. Returns
    (tokens [R], logprobs [R], state, new_rng).
    """
    rng, k = jax.random.split(rng)
    logits, state = prefill(params, batch, cfg, max_seq=max_seq, pcfg=pcfg)
    toks, lps = sample_logits(k, logits, temps)
    return toks, lps, state, rng


def fork_decode_rows(state, num_rows: int):
    """Fork one prefilled decode-state row into ``num_rows`` identical rows.

    ``state`` is a single-row decode state (caches ``[L, 1, S_max, ...]``,
    ``pos`` ``[1]``) as produced by a 1-row ``prefill``; the result has the
    same tree with the row axis broadcast to ``num_rows``. This is the
    group-shared-prefill cache fork (GRPO groups sample ``group_size``
    rollouts of one prompt): the shared prompt's K/V prefix is computed
    once and every member slot receives a bitwise copy.

    The fork is ``prompt_lens``-aware by construction: a right-padded
    bucketed prefill leaves garbage K/V above ``pos`` in the source row,
    and the fork copies it verbatim — sound for the same reason right
    padding itself is sound (the decode/extend masks ``k_idx <= pos``
    never read above the row's logical position, and each member's decode
    overwrites its own padded tail in place). Broadcasts are lazy under
    jit, so inside a jitted scatter this lowers to a gather→broadcast
    with no materialized [L, G, S_max, ...] intermediate on host.
    """
    def bcast(key, val):
        if key == "pos":
            return jnp.broadcast_to(val[:1], (num_rows,))
        # cache tensors are [L, B, ...] -> row axis 1
        return jnp.broadcast_to(val[:, :1],
                                val.shape[:1] + (num_rows,) + val.shape[2:])
    return {k: bcast(k, v) for k, v in state.items()}


def prefill_fork_sample(params, batch, temps, rng, cfg: ModelConfig,
                        max_seq: int, pcfg=DEFAULT_PARALLEL):
    """Group-shared prefill + fused first-token sampling for all members.

    ``batch`` holds ONE row — the group's shared prompt, right-padded to
    its length bucket with ``prompt_lens`` — run through the same
    ``prefill`` machinery as ``prefill_sample``. ``temps`` is ``[R]``
    where ``R`` is the row bucket an equivalent per-member admission
    would have used (pow2 of the member count): the single row of logits
    is broadcast to ``[R, V]`` before sampling, so member ``r`` draws
    against the identical logits and the identical slice of the
    ``[R, V]`` gumbel noise that row ``r`` of a batched ``prefill_sample``
    over R copies of the prompt would have seen — byte-identical streams,
    at 1/G of the prefill FLOPs. One RNG split per call (the engine's
    one-split-per-admission discipline).

    Returns (tokens [R], logprobs [R], single-row state, new_rng); the
    caller forks the state into member slots (``fork_decode_rows``).
    """
    rng, k = jax.random.split(rng)
    logits, state = prefill(params, batch, cfg, max_seq=max_seq, pcfg=pcfg)
    R = temps.shape[0]
    logits_b = jnp.broadcast_to(logits[0], (R, logits.shape[-1]))
    toks, lps = sample_logits(k, logits_b, temps)
    return toks, lps, state, rng


# ---------------------------------------------------------------------------
# Paged KV cache (block-pool decode state — the vLLM memory architecture)
# ---------------------------------------------------------------------------


_PAGED_POOL_KEYS = ("k", "v")


def init_paged_state(cfg: ModelConfig, batch: int, num_blocks: int,
                     block_size: int, blocks_per_row: int, dtype=None):
    """Block-pool decode state: one shared K/V pool plus per-row block
    tables, instead of a dense ``[L, batch, max_seq, ...]`` row per slot.

    ``k``/``v`` are ``[L, num_blocks, block_size, kv_heads, hd]`` pools;
    ``block_tables`` ``[batch, blocks_per_row]`` maps each row's logical
    block index to a physical pool block (the allocator on the host is the
    source of truth; unallocated entries hold 0 — a valid id whose reads
    are always masked by ``k_idx <= pos``). Per-layer state that is NOT a
    growing KV sequence stays dense per-row: cross-attention caches are
    fixed ``encoder_seq_len`` length, and recurrent SSM state (hybrid
    families) is a tiny fixed-size row — paging buys neither anything.
    Requires ``cfg.uses_attention`` (a pure-SSM family has no KV to page;
    the engine's layout keeps it on dense state rows).
    """
    assert cfg.uses_attention, "paged state requires attention layers"
    dtype = jnp.dtype(dtype or cfg.dtype)
    L, hd = cfg.num_layers, cfg.resolved_head_dim
    pool_shape = (L, num_blocks, block_size, cfg.num_kv_heads, hd)
    state = {
        "pos": jnp.zeros((batch,), jnp.int32),
        "k": jnp.zeros(pool_shape, dtype),
        "v": jnp.zeros(pool_shape, dtype),
        "block_tables": jnp.zeros((batch, blocks_per_row), jnp.int32),
    }
    if cfg.ssm is not None:
        one = init_ssm_state(cfg, batch, dtype)
        state["ssm_conv"] = jnp.broadcast_to(one["conv"][None],
                                             (L,) + one["conv"].shape).copy()
        state["ssm_h"] = jnp.broadcast_to(one["ssm"][None],
                                          (L,) + one["ssm"].shape).copy()
    if cfg.is_encoder_decoder:
        T = cfg.encoder_seq_len
        state["cross_k"] = jnp.zeros((L, batch, T, cfg.num_kv_heads, hd),
                                     dtype)
        state["cross_v"] = jnp.zeros((L, batch, T, cfg.num_kv_heads, hd),
                                     dtype)
    return state


def paged_gather_rows(state, gather_idx):
    """Linearize ``gather_idx`` rows of a paged state into dense decode
    rows (caches ``[L, R, blocks_per_row·bs, ...]``) — the bridge that
    lets the continuation ``extend`` path run its *unchanged* dense math
    against a paged cache. Entries past a row's allocation gather block 0
    garbage; the extend mask (``k_idx <= q_pos``) never reads it. Non-pool
    per-row caches (SSM state rows, cross-attention KV) gather straight
    through on the row axis."""
    table = state["block_tables"][gather_idx]          # [R, blocks_per_row]
    R, mb = table.shape
    rows = {"pos": state["pos"][gather_idx]}
    for key in _PAGED_POOL_KEYS:
        g = state[key][:, table]                       # [L, R, mb, bs, H, hd]
        rows[key] = g.reshape(g.shape[0], R, mb * g.shape[3], *g.shape[4:])
    for key in state:
        if key in _PAGED_POOL_KEYS or key in ("pos", "block_tables"):
            continue
        rows[key] = state[key][:, gather_idx]
    return rows


def paged_write_rows(state, rows, slot_idx, src_pos, blk_pos, off_pos,
                     new_tables):
    """Scatter dense decode rows (a prefill/extend/fork product) into the
    block pool. ``src_pos`` [R, S] names the row positions to copy;
    ``blk_pos``/``off_pos`` [R, S] their physical destination (block id,
    in-block offset) — an out-of-bounds block id drops the write, which
    is how padded bucket rows, unallocated tails, and COW-shared blocks a
    row must not touch are all expressed. ``new_tables`` [R, blocks_per
    _row] replaces each admitted row's device block table (the host
    allocator's view). Returns the updated state."""
    new = dict(state)
    new["pos"] = state["pos"].at[slot_idx].set(
        rows["pos"].astype(state["pos"].dtype), mode="drop")
    new["block_tables"] = state["block_tables"].at[slot_idx].set(
        new_tables.astype(state["block_tables"].dtype), mode="drop")
    idx = src_pos[None, :, :, None, None]
    for key in _PAGED_POOL_KEYS:
        vals = jnp.take_along_axis(rows[key], idx, axis=2)  # [L, R, S, H, hd]
        new[key] = state[key].at[:, blk_pos, off_pos].set(
            vals.astype(state[key].dtype), mode="drop")
    for key in state:
        if key in _PAGED_POOL_KEYS or key in ("pos", "block_tables"):
            continue
        new[key] = state[key].at[:, slot_idx].set(
            rows[key].astype(state[key].dtype), mode="drop")
    return new


def _decoder_layer_paged_decode(lp, x, pos, caches, table, write_block,
                                write_off, cfg, pcfg):
    """One layer, one token, against the block pool. The paged sibling of
    ``_decoder_layer_decode``; hybrid layers run their SSM mixer against
    the dense per-row state rows alongside the paged attention."""
    new = dict(caches)
    h = rmsnorm(x, lp["ln1"], cfg.rms_eps)
    attn_out, kp, vp = attn_paged_decode_apply(
        lp["attn"], h, caches["k"], caches["v"], table, pos,
        write_block, write_off, cfg, use_pallas=pcfg.use_pallas)
    new["k"], new["v"] = kp, vp
    if cfg.parallel_ssm:
        ssm_out, st = ssm_decode_step(lp["ssm"], h,
                                      {"conv": caches["ssm_conv"],
                                       "ssm": caches["ssm_h"]}, cfg)
        new["ssm_conv"], new["ssm_h"] = st["conv"], st["ssm"]
        attn_out = 0.5 * (
            rmsnorm(attn_out, lp["attn_out_norm"], cfg.rms_eps)
            + rmsnorm(ssm_out, lp["ssm_out_norm"], cfg.rms_eps))
    x = x + attn_out
    if cfg.is_encoder_decoder:
        h = rmsnorm(x, lp["ln_cross"], cfg.rms_eps)
        x = x + cross_attn_apply(lp["cross"], h, caches["cross_k"],
                                 caches["cross_v"], cfg)
    if cfg.moe is not None:
        h = rmsnorm(x, lp["ln2"], cfg.rms_eps)
        x = x + moe_decode_apply(lp["moe"], h, cfg)
    elif cfg.d_ff:
        x = x + mlp_apply(lp["mlp"], rmsnorm(x, lp["ln2"], cfg.rms_eps))
    return x, new


def paged_serve_step(params, state, token, active, cfg: ModelConfig,
                     pcfg=DEFAULT_PARALLEL):
    """One decode step against the paged state. token/active: [B].

    ``active`` masks the K/V write: inactive rows (empty or parked slots)
    route their write to an out-of-bounds block id so they can never
    corrupt pool blocks owned — or, after a copy-on-write group fork,
    *shared* — by live rows. (The dense path tolerates parked-row drift
    writes because each row owns its cache exclusively; a shared pool
    does not have that luxury.) ``active`` also freezes inactive rows'
    recurrent SSM state (hybrid families) — see ``serve_step``. ``pos``
    still advances for every row, mirroring the dense drift semantics."""
    B = token.shape[0]
    pos = state["pos"]
    table = state["block_tables"]
    nb, bs = state["k"].shape[1], state["k"].shape[2]
    blk_log = jnp.minimum(pos // bs, table.shape[1] - 1)
    # rows past the table's capacity drop their write too (the engine
    # overflow-finishes them before this can happen; the mask keeps a
    # clamped write from ever corrupting the last — possibly shared —
    # block even if a caller drives the state directly)
    writable = active & (pos < table.shape[1] * bs)
    write_block = jnp.where(writable, table[jnp.arange(B), blk_log], nb)
    write_off = pos % bs
    x = params["embed"][token][:, None, :]
    if cfg.rope_theta == 0.0:
        x = x + sinusoidal_positions(pos[:, None], cfg.d_model).astype(x.dtype)

    per_layer = {k: state[k] for k in _CACHE_KEYS if k in state}

    def body(x, inp):
        lp, caches = inp
        x, new = _decoder_layer_paged_decode(
            lp, x, pos, caches, table, write_block, write_off, cfg, pcfg)
        return x, new

    x, new_caches = jax.lax.scan(body, x, (params["layers"], per_layer))
    new_caches = _freeze_inactive_recurrent(new_caches, per_layer, active)
    x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
    logits = (x[:, 0] @ head_weights(params, cfg)).astype(jnp.float32)
    new_state = dict(state)
    new_state.update(new_caches)
    new_state["pos"] = pos + 1
    return logits, new_state


def paged_sample_step(params, state, token, active, temps, rng,
                      cfg: ModelConfig, pcfg=DEFAULT_PARALLEL):
    """Fused paged decode tick: ``paged_serve_step`` + on-device sampling.
    Same one-split-per-tick RNG discipline as ``sample_step`` — which is
    what keeps a paged engine and the unpaged reference oracle on
    byte-identical token/logprob streams."""
    rng, k = jax.random.split(rng)
    logits, new_state = paged_serve_step(params, state, token, active, cfg,
                                         pcfg)
    toks, lps = sample_logits(k, logits, temps)
    return toks, lps, new_state, rng


def extend_sample(params, state, batch, start_pos, temps, rng,
                  cfg: ModelConfig, pcfg=DEFAULT_PARALLEL):
    """Bucketed session extend + fused first-token sampling.

    The continuation sibling of ``prefill_sample``: one RNG split covers
    the whole bucket (the same split discipline, so a session-extend turn
    and a full-re-prefill turn consume the engine RNG identically —
    what makes stream parity checkable). Returns
    (tokens [R], logprobs [R], new state rows, new_rng).
    """
    rng, k = jax.random.split(rng)
    logits, new_state = extend(params, state, batch, start_pos, cfg, pcfg)
    toks, lps = sample_logits(k, logits, temps)
    return toks, lps, new_state, rng


def _sample_logits_block_core(key, logits, temps):
    scaled = logits / jnp.maximum(temps[:, None, None], 1e-4)
    toks = jax.random.categorical(key, scaled, axis=-1)
    # same greedy contract as _sample_logits_core, per row of the block
    toks = jnp.where(temps[:, None] <= 0, jnp.argmax(logits, axis=-1), toks)
    logp = jax.nn.log_softmax(logits, axis=-1)
    lps = jnp.take_along_axis(logp, toks[..., None], axis=-1)[..., 0]
    return toks.astype(jnp.int32), lps


def sample_logits_block(key, logits, temps):
    """``sample_logits`` over a [R, S, V] block of per-position logits.

    One categorical draw covers the whole block (logits [R, S, V], temps
    [R]); returns (tokens [R, S] i32, logprobs [R, S] f32) with the same
    unscaled-log-softmax logprob convention. The gumbel bits depend on
    the draw's array SHAPE, so fused and host-reference speculative
    verification must both sample on the identical [R, S, V] block — and,
    like ``sample_logits``, under a serving mesh the draw runs inside a
    fully-replicated ``shard_map`` so the bits are partition-invariant.
    """
    from repro.sharding.context import current_serve_mesh
    mesh = current_serve_mesh()
    if mesh is None:
        return _sample_logits_block_core(key, logits, temps)
    from jax.sharding import PartitionSpec as P
    fn = jax.shard_map(_sample_logits_block_core, mesh=mesh,
                       in_specs=(P(), P(), P()), out_specs=(P(), P()),
                       check_vma=False)
    return fn(key, logits, temps)


def extend_verify_sample(params, state, batch, start_pos, temps, rng,
                         cfg: ModelConfig, pcfg=DEFAULT_PARALLEL):
    """Speculative verification: ``extend_verify`` + one block draw.

    One RNG split covers the whole [R, S] verify block — the same
    one-split-per-dispatch discipline as every other fused entry point,
    so a speculating engine and the host reference consume the RNG
    identically. ``toks[:, j]`` is the token the model samples at cache
    position ``start_pos + j + 1``: the acceptance rule commits the
    longest prefix where ``toks[:, j]`` equals the drafted token at block
    offset ``j + 1``, plus ``toks[:, m]`` at the first mismatch as the
    bonus/correction token. Returns
    (tokens [R, S], logprobs [R, S], new state rows, new_rng).
    """
    rng, k = jax.random.split(rng)
    logits, new_state = extend_verify(params, state, batch, start_pos, cfg,
                                      pcfg)
    toks, lps = sample_logits_block(k, logits, temps)
    return toks, lps, new_state, rng
