"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX, and it compiles for a chip it is
only told about (``topologies.get_topology_desc``). Nothing runs here:
a passing test says the chip's compiler accepts the program at real
widths — each Pallas kernel natively (``tpu_custom_call``), not in the
interpreter the other kernel tests use — and how much device memory the
paged decode step needs. Interpret mode cannot catch a block shape that
breaks the (8, 128) tiling rule; these compiles do.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a test worker that is not given this
file must not try.
"""
import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import ParallelConfig
from repro.kernels import flash_attention as fa
from repro.kernels import grouped_matmul as gmm
from repro.kernels import paged_attention as pa
from repro.kernels import ssd_scan as ssd
from repro.models import init_params, init_paged_state, paged_sample_step

HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs on disk
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with JAX's persistent compilation cache off: a
    compile for a described chip would be written to it but could not be
    read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_native(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("window", [0, 1024])
def test_flash_attention_compiles_natively(one_chip, window):
    """minitron-4b heads: 24 query / 8 KV heads of 128, 2048 tokens."""
    B, S, Hq, Hkv, hd = 1, 2048, 24, 8, 128
    _compile_native(
        functools.partial(fa.flash_attention, window=window, interpret=False),
        _spec(one_chip, (B, S, Hq, hd)), _spec(one_chip, (B, S, Hkv, hd)),
        _spec(one_chip, (B, S, Hkv, hd)))


def test_paged_attention_compiles_natively(one_chip):
    """minitron-4b decode: 8 slots reading 64 blocks of 16 through the
    block table (max_seq 1024)."""
    B, Hq, Hkv, hd, bs, nblk = 8, 24, 8, 128, 16, 64
    _compile_native(
        functools.partial(pa.paged_attention, interpret=False),
        _spec(one_chip, (B, 1, Hq, hd)),
        _spec(one_chip, (B * nblk, bs, Hkv, hd)),
        _spec(one_chip, (B * nblk, bs, Hkv, hd)),
        _spec(one_chip, (B, nblk), jnp.int32), _spec(one_chip, (B,), jnp.int32))


def test_grouped_matmul_compiles_natively(one_chip):
    """qwen2-moe expert widths: 60 experts, d_model 2048 -> 1408."""
    E, C, d, f = 60, 128, 2048, 1408
    _compile_native(
        functools.partial(gmm.grouped_matmul, interpret=False),
        _spec(one_chip, (E, C, d)), _spec(one_chip, (E, d, f)),
        _spec(one_chip, (E,), jnp.int32))


def test_ssd_scan_compiles_natively(one_chip):
    """mamba2-370m: 32 heads of 64, state 128, chunk 256 (the model feeds
    the scan float32)."""
    B, S, nh, hd, n = 1, 1024, 32, 64, 128
    f32 = jnp.float32
    _compile_native(
        functools.partial(ssd.ssd_scan, chunk=256, interpret=False),
        _spec(one_chip, (B, S, nh, hd), f32), _spec(one_chip, (B, S, nh), f32),
        _spec(one_chip, (B, S, nh), f32), _spec(one_chip, (B, S, nh, n), f32),
        _spec(one_chip, (B, S, nh, n), f32),
        _spec(one_chip, (B, nh, hd, n), f32))


@pytest.fixture(scope="module")
def paged_tick(one_chip):
    """The engine's fused paged decode tick for minitron-4b at full width
    (bf16, vocab 256000), cut to 2 layers, with 8 slots and max_seq 1024
    (512 blocks of 16), its state donated as the engine donates it.
    Returns (compiled, pool shape)."""
    cfg = dataclasses.replace(get_config("minitron-4b"), num_layers=2)
    slots, max_seq, bs = 8, 1024, 16

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: _spec(one_chip, s.shape, s.dtype), tree)

    params = on_chip(jax.eval_shape(
        functools.partial(init_params, cfg=cfg), jax.random.PRNGKey(0)))
    state = on_chip(jax.eval_shape(functools.partial(
        init_paged_state, cfg, slots, slots * max_seq // bs, bs,
        max_seq // bs)))
    step = functools.partial(paged_sample_step, cfg=cfg,
                             pcfg=ParallelConfig(remat="none", loss_chunk=0))
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, state, _spec(one_chip, (slots,), jnp.int32),
        _spec(one_chip, (slots,), jnp.bool_),
        _spec(one_chip, (slots,), jnp.float32),
        _spec(one_chip, (2,), jnp.uint32)).compile()
    return compiled, state["k"].shape


def test_paged_decode_tick_fits_one_chip(paged_tick):
    """The tick compiles for the chip and needs less than its 16 GiB."""
    compiled, _ = paged_tick
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 0 < need < HBM_BYTES, need
    # the params alone are ~3.3 GB at this cut: the arguments were sized
    # at full width, not at a toy width
    assert mem.argument_size_in_bytes > 3 * 10**9


def test_paged_decode_tick_updates_pool_in_place(paged_tick):
    """The K/V pools go through the layer scan as carried state, so the
    donated pool is written in place: the tick's scratch memory is less
    than one pool, and no ``copy`` or ``dynamic-update-slice`` produces a
    pool-shaped buffer (a pool fed to the scan as input and taken back as
    output costs a second pool and a copy of each into the output)."""
    compiled, pool_shape = paged_tick
    pool_bytes = math.prod(pool_shape) * jnp.dtype(jnp.bfloat16).itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes, (temp, pool_bytes)
    pool_type = "bf16[" + ",".join(map(str, pool_shape)) + "]"
    copies = [line.strip()[:160]
              for line in compiled.as_text().splitlines()
              if re.search(r"=\s*" + re.escape(pool_type), line)
              and re.search(r"\b(copy|copy-done|dynamic-update-slice)\(|"
                            r"dynamic-update-slice_fusion", line)]
    assert not copies, copies
