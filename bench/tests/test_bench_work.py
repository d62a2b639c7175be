"""``bench/work.py`` against counts made by hand for the mistral-7b-v0.1
configuration (16 layers) and for a small expert configuration."""
import pytest

from bench import work
from bench.harness import BENCH_DIR, load_json, load_peaks

MODEL = load_json(BENCH_DIR / "configs" / "mistral-7b-v0.1.json")
# one layer: q and o 4096x4096, k and v 4096x1024, three 4096x14336 MLP
LAYER = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
HEAD = 4096 * 32000
NORMS = 16 * 2 * 4096 + 4096


def test_layer_and_weight_bytes():
    assert LAYER == 218_103_808
    # a dense tick reads every weight, however many requests it serves
    for tokens in (1, 32):
        assert work.weight_bytes(MODEL, tokens) \
            == 2 * (16 * LAYER + HEAD + NORMS) == 7_241_736_192


def test_kv_bytes_per_position():
    # K and V, 16 layers, 8 heads of 128, two bytes
    assert work.kv_bytes_per_position(MODEL) == 2 * 16 * 8 * 128 * 2 \
        == 64 * 1024


@pytest.mark.parametrize("live", [1, 300, 1024])
def test_token_flops(live):
    matmul = 2 * (16 * LAYER + HEAD)
    attn = 4 * 16 * 4096 * live          # q.k and p.v over 32 heads of 128
    assert work.token_flops(MODEL, live) == matmul + attn


def test_tick_and_least_time():
    live = [100, 200, 700]
    flops, nbytes = work.tick(MODEL, live)
    assert flops == sum(work.token_flops(MODEL, n) for n in live)
    assert nbytes == (7_241_736_192 + 65_536 * 1000 + 65_536 * 3
                      + 2 * 3 * 4096)
    peaks = load_peaks("TPU v5 lite")
    # a decode tick is bound by bytes: 7.3 GB at 819 GB/s is about 9 ms
    t = work.least_seconds(MODEL, live, peaks, 1)
    assert t == pytest.approx(nbytes / 819e9)
    assert flops / 197e12 < t


# the sizes of qwen2-moe-a2.7b:reduced, in bfloat16: 2 layers, d 256, 4 heads
# of 64, 4 routed experts of width 128 (top 2), a shared expert of 128
MOE = {"name": "tiny-moe", "dtype": "bfloat16", "num_hidden_layers": 2,
       "hidden_size": 256, "num_attention_heads": 4,
       "num_key_value_heads": 4, "head_dim": 64, "intermediate_size": 512,
       "vocab_size": 512, "num_experts": 4, "num_experts_per_tok": 2,
       "moe_intermediate_size": 128, "shared_expert_intermediate_size": 128,
       "norm_topk_prob": False}
ATTN = 4 * 256 * 256                 # q, k, v and o, each 256x256
SHARED = 3 * 256 * 128 + 256         # the shared expert and its gate
ROUTER = 256 * 4                     # float32
EXPERT = 3 * 256 * 128               # one routed expert
MOE_HEAD, MOE_NORMS = 256 * 512, 2 * 2 * 256 + 256


def test_expert_dims():
    m = work.dims(MOE)
    assert (m["layer_params"], m["router_params"], m["expert_params"]) \
        == (ATTN + SHARED, ROUTER, EXPERT) == (360_704, 1_024, 98_304)
    assert (m["experts"], m["top_k"]) == (4, 2)
    # the intermediate_size of the published config is no expert's width
    assert work.dims(dict(MOE, intermediate_size=1)) == m


@pytest.mark.parametrize("live", [1, 300])
def test_expert_token_flops(live):
    # attention, the shared expert, the router and two of the four experts;
    # q.k and p.v over 4 heads of 64
    matmul = 2 * (2 * (ATTN + SHARED + ROUTER + 2 * EXPERT) + MOE_HEAD)
    assert matmul == 2_495_488
    assert work.token_flops(MOE, live) == matmul + 4 * 2 * 256 * live


@pytest.mark.parametrize("tokens,reached", [(1, 2.0), (2, 3.0), (3, 3.5)])
def test_expert_weight_bytes(tokens, reached):
    # T tokens each sent to 2 of 4 experts reach 4 (1 - (1/2)^T) of them
    assert work.experts_reached(MOE, tokens) == reached
    dense = 2 * (2 * (ATTN + SHARED) + MOE_NORMS + MOE_HEAD)
    routed = 2 * (4 * ROUTER + 2 * EXPERT * reached)
    assert work.weight_bytes(MOE, tokens) == dense + routed
    assert dense == 1_707_520


def test_published_expert_layer_reaches_nearly_every_expert():
    # 60 experts, top 4: 64 tokens reach 59.3 of them
    qwen = dict(MOE, num_experts=60, num_experts_per_tok=4)
    assert work.experts_reached(qwen, 64) == pytest.approx(59.27, abs=0.01)


def test_expert_tick_and_least_time_on_four_chips():
    live = [10, 20]
    flops, nbytes = work.tick(MOE, live)
    assert flops == 2 * 2_495_488 + 4 * 2 * 256 * 30 == 5_052_416
    # weights for two tokens, K/V of 30 live positions and 2 written (2 x
    # 2 layers x 256 x 2 bytes a position), and each token's hidden state
    assert nbytes == 2_895_360 + 2_048 * 30 + 2_048 * 2 + 2 * 2 * 256 \
        == 2_961_920
    peaks = load_peaks("TPU v5 lite")
    one = work.least_seconds(MOE, live, peaks, 1)
    four = work.least_seconds(MOE, live, peaks, 4)
    assert four == pytest.approx(nbytes / (4 * 819e9)) == pytest.approx(
        one / 4)
    assert flops / (4 * 197e12) < four
