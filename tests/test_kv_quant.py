"""Int8 paged-KV cache (engine kv_quant): quantization bounds, kernel ≡
scatter parity, and attention over the quantized cache ≡ reference over the
SAME dequantized values, on the plain walk and its edges. The walk's other
int8 tests are test_kv_quant_shared_head.py, test_kv_quant_packed_tile.py and
test_kv_quant_engine.py (end-to-end engine decode, ring prefill, TP).

The contract: per-token-per-head scales are written once at append time
and never requantized (the page RMW copies existing int8 rows verbatim),
so cached values are bit-stable and the only error is the one-time row
rounding, bounded by amax/254 per element.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paged_walk_cases import PAGE_SIZE, SHAPES, assert_matches_reference, walk_case

from finchat_tpu.engine.kv_cache import (
    PagedKVCache,
    gather_kv_q8,
    quantize_kv_rows,
    scale_rows,
    scatter_kv_chunk_q8,
)
from finchat_tpu.models.llama import PRESETS
from finchat_tpu.ops.refs import mha_reference

CONFIG = PRESETS["tiny"]  # n_kv_heads=2, head_dim=32


def test_quantize_kv_rows_error_bound():
    x = jax.random.normal(jax.random.key(0), (3, 5, 2 * 32), jnp.float32)
    q, s = quantize_kv_rows(x, n_kv=2)
    assert q.dtype == jnp.int8 and s.shape == (3, 5, 2)
    deq = (q.reshape(3, 5, 2, 32).astype(jnp.float32) * s[..., None]).reshape(x.shape)
    err = jnp.abs(deq - x)
    bound = jnp.repeat(s, 32, axis=-1) / 2 + 1e-6  # half a step per element
    assert bool((err <= bound).all())


def test_scale_rows_padding():
    assert scale_rows(2) == 8 and scale_rows(8) == 8 and scale_rows(9) == 16


def _fresh_cache(n_pages=8, page_size=8):
    cache = PagedKVCache.create(CONFIG, n_pages, page_size, kv_quant="int8")
    return cache


def test_scatter_gather_roundtrip():
    """scatter_kv_chunk_q8 → gather_kv_q8 reproduces the written rows to
    quantization tolerance, in the right positions."""
    page_size = 8
    cache = _fresh_cache()
    B, C, Hkv, hd = 2, 6, CONFIG.n_kv_heads, CONFIG.head_dim
    k_new = jax.random.normal(jax.random.key(1), (B, C, Hkv, hd), jnp.float32)
    v_new = jax.random.normal(jax.random.key(2), (B, C, Hkv, hd), jnp.float32)
    page_table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    start_pos = jnp.asarray([0, 5], jnp.int32)
    n_valid = jnp.asarray([6, 4], jnp.int32)  # slot 1: 2 padding lanes

    kp, vp, ks, vs = scatter_kv_chunk_q8(
        cache.k_pages, cache.v_pages, cache.k_scales, cache.v_scales,
        k_new, v_new, page_table, start_pos, n_valid, page_size,
        jnp.int32(0), Hkv,
    )
    k_all, v_all = gather_kv_q8(
        kp, vp, ks, vs, page_table, page_size, jnp.int32(0), Hkv,
        dtype=jnp.float32,
    )
    for b in range(B):
        for i in range(int(n_valid[b])):
            pos = int(start_pos[b]) + i
            for src, got in ((k_new, k_all), (v_new, v_all)):
                want = np.asarray(src[b, i])
                have = np.asarray(got[b, pos])
                amax = np.abs(want).max(axis=-1, keepdims=True)
                assert np.all(np.abs(have - want) <= amax / 127 + 1e-6), (b, i)


def test_append_kernel_matches_scatter():
    """The in-place quantizing append (interpret mode) must write exactly
    what the XLA scatter writes for the same single token: same int8 rows,
    same scales."""
    from finchat_tpu.ops.kv_append import paged_kv_append_q8

    page_size = 8
    Hkv, hd = CONFIG.n_kv_heads, CONFIG.head_dim
    B = 2
    k_row = jax.random.normal(jax.random.key(3), (B, 1, Hkv, hd), jnp.bfloat16)
    v_row = jax.random.normal(jax.random.key(4), (B, 1, Hkv, hd), jnp.bfloat16)
    page_table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([3, 9], jnp.int32)
    n_valid = jnp.asarray([1, 1], jnp.int32)

    ca = _fresh_cache()
    kv_new = jnp.concatenate(
        [k_row.reshape(B, 1, -1), v_row.reshape(B, 1, -1)], axis=-1
    )
    ka, va, ksa, vsa = paged_kv_append_q8(
        kv_new, ca.k_pages, ca.v_pages, ca.k_scales, ca.v_scales,
        page_table, pos, n_valid, jnp.zeros((1,), jnp.int32),
        page_size=page_size, n_kv=Hkv, interpret=True,
    )

    cb = _fresh_cache()
    kb, vb, ksb, vsb = scatter_kv_chunk_q8(
        cb.k_pages, cb.v_pages, cb.k_scales, cb.v_scales,
        k_row, v_row, page_table, pos, n_valid, page_size, jnp.int32(0), Hkv,
    )
    np.testing.assert_array_equal(np.asarray(ka), np.asarray(kb))
    np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
    np.testing.assert_allclose(np.asarray(ksa), np.asarray(ksb), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(vsa), np.asarray(vsb), rtol=1e-6)


def test_trash_redirect_append_q8():
    """n_valid == 0 lanes must write page 0 (trash), even at an
    out-of-range position (the verify-step padding case)."""
    from finchat_tpu.ops.kv_append import paged_kv_append_q8

    page_size = 8
    Hkv, hd = CONFIG.n_kv_heads, CONFIG.head_dim
    ca = _fresh_cache()
    kv_new = jnp.ones((1, 1, 2 * Hkv * hd), jnp.bfloat16)
    page_table = jnp.asarray([[1, 2]], jnp.int32)
    ka, va, ksa, vsa = paged_kv_append_q8(
        kv_new, ca.k_pages, ca.v_pages, ca.k_scales, ca.v_scales,
        page_table, jnp.asarray([100], jnp.int32), jnp.asarray([0], jnp.int32),
        jnp.zeros((1,), jnp.int32), page_size=page_size, n_kv=Hkv, interpret=True,
    )
    assert int(jnp.abs(ka[:, 1:].astype(jnp.int32)).sum()) == 0  # real pages untouched
    assert int(jnp.abs(va[:, 1:].astype(jnp.int32)).sum()) == 0


@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
def test_paged_attention_q8_matches_dequantized_reference(backend):
    """Attention over the int8 cache must equal mha_reference over the SAME
    dequantized K/V — both kernels and the gather path see identical
    semantic values, so the only tolerance is fp accumulation order."""
    from finchat_tpu.ops.dispatch import paged_attention

    page_size = 8
    Hkv, hd, H = CONFIG.n_kv_heads, CONFIG.head_dim, CONFIG.n_heads
    B, C = 2, 1
    cache = _fresh_cache(n_pages=8)
    T = 14
    k_ctx = jax.random.normal(jax.random.key(5), (B, T, Hkv, hd), jnp.float32)
    v_ctx = jax.random.normal(jax.random.key(6), (B, T, Hkv, hd), jnp.float32)
    page_table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    kp, vp, ks, vs = scatter_kv_chunk_q8(
        cache.k_pages, cache.v_pages, cache.k_scales, cache.v_scales,
        k_ctx, v_ctx, page_table, jnp.zeros((B,), jnp.int32),
        jnp.full((B,), T, jnp.int32), page_size, jnp.int32(0), Hkv,
    )
    q = jax.random.normal(jax.random.key(7), (B, C, H, hd), jnp.float32)
    q_offset = jnp.full((B,), T - 1, jnp.int32)
    kv_len = jnp.full((B,), T, jnp.int32)

    got = paged_attention(
        q, kp, vp, page_table, q_offset, kv_len, jnp.zeros((1,), jnp.int32),
        page_size=page_size, n_kv=Hkv, backend=backend,
        k_scales=ks, v_scales=vs,
    )
    # the oracle sees the SAME dequantized values
    k_deq, v_deq = gather_kv_q8(
        kp, vp, ks, vs, page_table, page_size, jnp.int32(0), Hkv,
        dtype=jnp.float32,
    )
    want = mha_reference(q, k_deq, v_deq, causal=True, q_offset=q_offset, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("group,C", SHAPES)
def test_paged_walk_edges_q8_match_dequantized_reference(group, C):
    """The int8 walk on the float walk's cases (paged_walk_cases): every
    edge of the walk as a row, dead table entries on a page whose scales
    are NaN, the oracle over the same dequantized values."""
    from finchat_tpu.ops.paged_attention import paged_flash_attention_q8

    q, sources, table, q_offset, kv_len, layer, k_deq, v_deq = walk_case(
        group, C, quantized=True)
    out = paged_flash_attention_q8(
        q, *sources, table, q_offset, kv_len, layer,
        page_size=PAGE_SIZE, n_kv=2, interpret=True,
    )
    want = mha_reference(q, k_deq, v_deq, causal=True, q_offset=q_offset, kv_len=kv_len)
    assert_matches_reference(out, want, atol=1e-4, rtol=1e-4)
