"""The comparison that decides ``correct``, on the agent cell cut to a CPU
test's size: whole conversations (prefix-cache hits, session extends and
decode ticks) are held to the reference; a reply token altered where the
extend produces it comes out not correct."""
import repro.inference.engine as engine_mod
from bench.tests import tiny

W = "mistral-7b-v0.1.agent"


def test_sound_sessions_are_correct():
    result = tiny.run(W, 2 ** 31 + 9, seconds=3.0)
    assert result["correct"] is True, result["check"]
    assert result["check"]["greedy_tokens"] > 0
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"]["itl_p95_ms"]["value"] > 0


def test_token_altered_at_extend_is_not_correct(monkeypatch):
    real = engine_mod.extend_sample

    def altered(params, state, batch, start_pos, temps, rng, cfg, pcfg):
        toks, lps, st, rng = real(params, state, batch, start_pos, temps,
                                  rng, cfg, pcfg)
        return (toks + 1) % cfg.vocab_size, lps, st, rng

    monkeypatch.setattr(engine_mod, "extend_sample", altered)
    result = tiny.run(W, 12, seconds=3.0)
    assert result["correct"] is False, result["check"]
