"""The plain reference against the program at a small size on the CPU:
the same weights drawn again from the seed, and the same logits from its
plain forward pass as from ``models.forward`` (dense and expert layers)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from repro.configs import get_config
from repro.models import forward, init_params


def model_dict(cfg):
    m = {"name": cfg.name, "dtype": "float32",
         "num_hidden_layers": cfg.num_layers, "hidden_size": cfg.d_model,
         "num_attention_heads": cfg.num_heads,
         "num_key_value_heads": cfg.num_kv_heads,
         "head_dim": cfg.resolved_head_dim, "intermediate_size": cfg.d_ff,
         "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_theta,
         "rms_norm_eps": cfg.rms_eps,
         "tie_word_embeddings": cfg.tie_embeddings}
    if cfg.moe is not None:
        mo = cfg.moe
        m.update(num_experts=mo.num_experts, num_experts_per_tok=mo.top_k,
                 moe_intermediate_size=mo.expert_d_ff,
                 shared_expert_intermediate_size=mo.shared_d_ff
                 or mo.expert_d_ff * mo.num_shared_experts,
                 norm_topk_prob=mo.norm_topk_prob)
    return m


ARCHS = ["minitron-4b:reduced", "qwen2-moe-a2.7b:reduced"]
SEED = 2 ** 31 + 77          # seeds wider than 32 signed bits


def program(arch):
    cfg = get_config(arch)
    params = init_params(jax.random.PRNGKey(SEED), cfg, jnp.float32)
    return cfg, params


@pytest.mark.parametrize("arch", ARCHS)
def test_weights_drawn_again_equal_the_programs(arch):
    cfg, params = program(arch)
    w = reference.Weights(model_dict(cfg), SEED)
    np.testing.assert_array_equal(w.embed(), params["embed"])
    np.testing.assert_array_equal(w.head(), params["lm_head"])
    names = {"wq": ("attn", "wq"), "wk": ("attn", "wk"),
             "wv": ("attn", "wv"), "wo": ("attn", "wo"),
             "w_gate": ("mlp", "w_gate"), "w_up": ("mlp", "w_up"),
             "w_down": ("mlp", "w_down"), "router": ("moe", "router"),
             "e_gate": ("moe", "w_gate"), "e_up": ("moe", "w_up"),
             "e_down": ("moe", "w_down"),
             "s_gate": ("moe", "shared", "w_gate"),
             "s_up": ("moe", "shared", "w_up"),
             "s_down": ("moe", "shared", "w_down"),
             "s_router": ("moe", "shared_gate")}
    for i in range(cfg.num_layers):
        layer = w.layer(i)
        assert layer
        for key, path in names.items():
            if key not in layer:
                continue
            leaf = params["layers"]
            for p in path:
                leaf = leaf[p]
            np.testing.assert_array_equal(layer[key], leaf[i], err_msg=key)


@pytest.mark.parametrize("arch", ARCHS)
def test_reference_logits_match_the_program_forward(arch):
    """Both sides in float32 at the highest matmul precision; they differ
    only in the order of additions, so 1e-4 on log-probabilities of order
    one is a margin of about a hundred float32 steps. The expert layer is
    compared on eight tokens per row, where the program's per-expert
    capacity (at least 8 rows) drops nothing."""
    cfg, params = program(arch)
    m = model_dict(cfg)
    S = 8 if cfg.moe is not None else 24
    toks = np.random.default_rng(0).integers(2, cfg.vocab_size, (2, S))
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, {"tokens": jnp.asarray(toks)}, cfg)
        want = jax.nn.log_softmax(logits, axis=-1)
    h = reference.hidden_states(reference.Weights(m, SEED), toks)[0]
    rows = np.asarray(h).reshape(-1, cfg.d_model)
    nxt = toks.reshape(-1)
    got = reference.head_readings(reference.Weights(m, SEED).head(), nxt,
                                  jnp.asarray(rows))
    ref_lp = np.take_along_axis(np.asarray(want).reshape(-1, cfg.vocab_size),
                                nxt[:, None], axis=1)[:, 0]
    np.testing.assert_allclose(got["lp"], ref_lp, atol=1e-4)
    best = np.asarray(want).reshape(-1, cfg.vocab_size).max(axis=1)
    np.testing.assert_allclose(got["gap"], best - ref_lp, atol=1e-4)


def test_fp8_control_departs_from_the_reference():
    cfg, _ = program("minitron-4b:reduced")
    m = model_dict(cfg)
    toks = np.random.default_rng(1).integers(2, cfg.vocab_size, (2, 32))
    w = reference.Weights(m, SEED)
    h, hc = reference.hidden_states(w, toks, quants=(None, "fp8"))
    rows = np.asarray(h).reshape(-1, cfg.d_model)
    crow = np.asarray(hc).reshape(-1, cfg.d_model)
    r = reference.head_readings(w.head(), toks.reshape(-1),
                                jnp.asarray(rows), jnp.asarray(crow))
    err = np.abs(r["control_lp"] - r["lp"])
    assert err.max() > 0.05 and (r["control_gap"] >= 0).all()
    x = jnp.linspace(-3.0, 3.0, 64).reshape(8, 8)
    q = reference.fp8_rows(x)
    assert not np.allclose(q, x) and np.allclose(q, x, rtol=0.07)
