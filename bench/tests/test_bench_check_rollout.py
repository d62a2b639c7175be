"""The comparison that decides ``correct``, on the rollout cell cut to a
CPU test's size: a sound run passes; the fp8 control fails the cell's
limits; and a run whose timed path is broken underneath comes out not
correct, for each fault the cell can have."""
import pytest

import repro.inference.engine as engine_mod
from bench.check import check_sample
from bench.tests import tiny

W = "mistral-7b-v0.1.rollout"


def test_sound_run_is_correct_and_the_control_is_not():
    seen = {}

    def checker(config, seed, sample):
        # the control: the reference in float8 in the program's place, read
        # at the same tokens and judged by the same comparison
        seen["control"] = check_sample(config, seed, sample, control=True)
        return check_sample(config, seed, sample)

    result = tiny.run(W, 2 ** 31 + 5, checker=checker)
    assert result["correct"] is True, result["check"]
    assert result["failed"] == 0 and result["metrics"]["rollout_tok_s"]
    assert result["check"]["greedy_tokens"] > 0
    assert seen["control"]["correct"] is False, seen["control"]


def broken_tick(fault):
    real = engine_mod.paged_sample_step

    def tick(params, state, token, active, temps, rng, cfg, pcfg):
        toks, lps, new_state, rng = real(params, state, token, active, temps,
                                         rng, cfg, pcfg)
        if fault == "state_unchanged":
            return toks, lps, state, rng
        if fault == "token_altered":
            return (toks + 1) % cfg.vocab_size, lps, new_state, rng
        raise ValueError(fault)
    return tick


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
def test_broken_decode_tick_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(engine_mod, "paged_sample_step", broken_tick(fault))
    result = tiny.run(W, 11)
    assert result["correct"] is False, result["check"]
