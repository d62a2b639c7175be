"""Property tests: paged (block-table) attention reads vs dense decode.

The paged engine's parity contract rests on the block-table read path
producing the dense path's numbers — bitwise for the XLA gather fallback
(same shapes, same unmasked values, exact-zero masked contributions),
numerically for the Pallas kernel. Sweeps cover block-boundary-straddling
positions, GQA head mappings, sliding windows, and *shuffled* block
tables (physical placement must not matter).
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import paged_attention
from repro.models.attention import (attention_decode, attention_direct,
                                    attention_paged_decode)
from tests.utils import given, settings, st


def _paged_case(seed, B, Hq, Hkv, hd, bs, max_blocks, positions):
    """Build a dense cache, shatter it into a shuffled block pool, and
    return (q, dense k/v, pool k/v, tables, pos)."""
    rng = np.random.RandomState(seed)
    S = max_blocks * bs
    k_dense = rng.randn(B, S, Hkv, hd).astype(np.float32)
    v_dense = rng.randn(B, S, Hkv, hd).astype(np.float32)
    q = rng.randn(B, 1, Hq, hd).astype(np.float32)
    # one pool block per (row, logical block), physically shuffled, plus
    # spare blocks full of garbage that must never influence the output
    n_pool = B * max_blocks + 4
    perm = rng.permutation(n_pool)
    k_pool = rng.randn(n_pool, bs, Hkv, hd).astype(np.float32) * 100.0
    v_pool = rng.randn(n_pool, bs, Hkv, hd).astype(np.float32) * 100.0
    tables = np.zeros((B, max_blocks), np.int32)
    for b in range(B):
        for i in range(max_blocks):
            blk = int(perm[b * max_blocks + i])
            tables[b, i] = blk
            k_pool[blk] = k_dense[b, i * bs:(i + 1) * bs]
            v_pool[blk] = v_dense[b, i * bs:(i + 1) * bs]
    pos = np.asarray(positions, np.int32)
    return (jnp.asarray(q), jnp.asarray(k_dense), jnp.asarray(v_dense),
            jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(tables),
            jnp.asarray(pos))


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([1, 2, 4]),
       st.sampled_from([1, 2, 4]), st.sampled_from([2, 4, 8]),
       st.sampled_from([0, 5, 8]))
def test_paged_read_matches_dense(seed, group, Hkv, bs, window):
    """Gather fallback is bitwise-identical to dense decode; the Pallas
    kernel matches to float tolerance — across random positions incl.
    block-boundary straddles and sliding windows."""
    rng = np.random.RandomState(seed ^ 0x5EED)
    B, hd, max_blocks = 3, 16, 4
    S = max_blocks * bs
    # straddle the boundary on purpose: one row just below, one exactly
    # on, one random
    positions = [bs - 1, min(bs, S - 1), int(rng.randint(0, S))]
    q, k_d, v_d, k_p, v_p, tables, pos = _paged_case(
        seed, B, Hkv * group, Hkv, hd, bs, max_blocks, positions)

    ref = attention_decode(q, k_d, v_d, pos, window=window)
    via_gather = attention_paged_decode(q, k_p, v_p, tables, pos,
                                        window=window)
    np.testing.assert_array_equal(np.asarray(via_gather), np.asarray(ref))

    via_kernel = paged_attention(q, k_p, v_p, tables, pos, window=window,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(via_kernel), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_read_matches_full_prefix_attention():
    """Cross-check against full-sequence attention: decoding token at
    ``pos`` through the block table equals the last row of a causal
    ``attention_direct`` over the prefix [0..pos]."""
    B, Hq, Hkv, hd, bs, max_blocks = 2, 4, 2, 8, 4, 3
    for pos_v in (3, 4, 7, 11):                    # straddles both edges
        q, k_d, v_d, k_p, v_p, tables, pos = _paged_case(
            pos_v, B, Hq, Hkv, hd, bs, max_blocks, [pos_v] * B)
        paged = attention_paged_decode(q, k_p, v_p, tables, pos)
        full = attention_direct(q, k_d[:, :pos_v + 1], v_d[:, :pos_v + 1],
                                causal=True, q_offset=pos_v)
        np.testing.assert_allclose(np.asarray(paged), np.asarray(full),
                                   rtol=2e-5, atol=2e-5)


def test_paged_kernel_spare_blocks_are_inert():
    """Rewriting the *unreferenced* spare pool blocks must not change the
    output (no out-of-table reads)."""
    B, Hq, Hkv, hd, bs, max_blocks = 2, 4, 2, 8, 4, 3
    q, _, _, k_p, v_p, tables, pos = _paged_case(
        42, B, Hq, Hkv, hd, bs, max_blocks, [5, 9])
    used = set(np.asarray(tables).ravel().tolist())
    spare = [i for i in range(k_p.shape[0]) if i not in used]
    out1 = paged_attention(q, k_p, v_p, tables, pos, interpret=True)
    k_p2 = k_p.at[jnp.asarray(spare)].set(1e6)
    v_p2 = v_p.at[jnp.asarray(spare)].set(-1e6)
    out2 = paged_attention(q, k_p2, v_p2, tables, pos, interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    out3 = attention_paged_decode(q, k_p2, v_p2, tables, pos)
    np.testing.assert_allclose(np.asarray(out3), np.asarray(out1),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_ref_matches_dense_decode():
    """The naive oracle ``kernels.ref.paged_attention_ref`` (gather the
    table, full softmax over keys <= pos) agrees with dense decode on a
    shuffled pool — it is what the Pallas kernel is held to on the chip."""
    from repro.kernels.ref import paged_attention_ref
    B, Hq, Hkv, hd, bs, max_blocks = 3, 8, 2, 16, 4, 5
    q, k_d, v_d, k_p, v_p, tables, pos = _paged_case(
        7, B, Hq, Hkv, hd, bs, max_blocks, [0, 9, 19])
    ref = attention_decode(q, k_d, v_d, pos)
    np.testing.assert_allclose(
        np.asarray(paged_attention_ref(q, k_p, v_p, tables, pos)),
        np.asarray(ref), rtol=2e-5, atol=2e-5)
