"""``bench/work.py`` against counts made by hand for the mistral-7b-v0.1
configuration (16 layers)."""
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
    assert work.weight_bytes(MODEL) == 2 * (16 * LAYER + HEAD + NORMS) \
        == 7_241_736_192


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
    t = work.least_seconds(MODEL, live, peaks)
    assert t == pytest.approx(nbytes / 819e9)
    assert flops / 197e12 < t


def test_expert_layers_are_refused_until_counted():
    with pytest.raises(NotImplementedError):
        work.dims(dict(MODEL, num_experts=60))
