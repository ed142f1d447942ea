"""Pallas kernels vs jnp reference oracles (SURVEY §4.2): flash attention, the
plain paged walk and its edges, the KV append — in float and, at TPU-tileable
shapes, in int8. The walk's other tests are test_paged_walk_shared_head.py (rows
on one shared head), test_paged_walk_packed_tile.py (several KV heads a softmax
tile) and test_paged_walk_engine.py (the engine's steps through the kernels).

Runs in interpret mode on the CPU test mesh. Under ``FINCHAT_TESTS_TPU=1``
(see conftest.py) the same matrix runs ON-CHIP with ``interpret=False`` —
Mosaic-lowered kernels asserted against the jnp oracles on real hardware.
On-chip fp32 tolerances are looser because TPU fp32 dots lower to bf16
multi-pass matmuls in both the kernel and the oracle, but not identically.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paged_walk_cases import (
    ATOL,
    INTERPRET,
    PAGE_SIZE,
    RTOL,
    SHAPES,
    assert_matches_reference,
    pallas_eqn,
    walk_case,
)

from finchat_tpu.engine.kv_cache import gather_kv, scatter_kv_chunk
from finchat_tpu.ops.flash_attention import flash_attention
from finchat_tpu.ops.paged_attention import paged_flash_attention
from finchat_tpu.ops.refs import mha_reference


def _rand_qkv(key, B, Sq, Sk, H, Hkv, D, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Sq, H, D), dtype)
    k = jax.random.normal(kk, (B, Sk, Hkv, D), dtype)
    v = jax.random.normal(kv, (B, Sk, Hkv, D), dtype)
    return q, k, v


@pytest.mark.parametrize(
    "B,Sq,Sk,H,Hkv,D",
    [
        (1, 128, 128, 4, 4, 64),  # MHA, square
        (2, 64, 256, 8, 2, 64),  # GQA, kv longer than q
        (1, 256, 512, 4, 1, 128),  # MQA
    ],
)
def test_flash_matches_reference_causal(B, Sq, Sk, H, Hkv, D):
    q, k, v = _rand_qkv(jax.random.key(0), B, Sq, Sk, H, Hkv, D)
    out = flash_attention(q, k, v, causal=True, interpret=INTERPRET)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=ATOL, rtol=RTOL)


def test_flash_q_offset_and_kv_len():
    """Chunked-prefill semantics: q chunk sits at an offset inside a padded
    KV axis whose valid length differs per batch element."""
    B, Sq, Sk, H, Hkv, D = 2, 64, 256, 4, 2, 64
    q, k, v = _rand_qkv(jax.random.key(1), B, Sq, Sk, H, Hkv, D)
    q_offset = jnp.array([32, 100], jnp.int32)
    kv_len = jnp.array([96, 164], jnp.int32)  # q_offset + Sq
    out = flash_attention(q, k, v, q_offset=q_offset, kv_len=kv_len, interpret=INTERPRET)
    ref = mha_reference(q, k, v, causal=True, q_offset=q_offset, kv_len=kv_len)
    np.testing.assert_allclose(out, ref, atol=ATOL, rtol=RTOL)


def test_flash_non_causal():
    B, Sq, Sk, H, Hkv, D = 1, 128, 128, 4, 4, 64
    q, k, v = _rand_qkv(jax.random.key(2), B, Sq, Sk, H, Hkv, D)
    out = flash_attention(q, k, v, causal=False, interpret=INTERPRET)
    ref = mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, atol=ATOL, rtol=RTOL)


def test_flash_bf16_tolerance():
    B, Sq, Sk, H, Hkv, D = 1, 128, 128, 8, 4, 64
    q, k, v = _rand_qkv(jax.random.key(3), B, Sq, Sk, H, Hkv, D, jnp.bfloat16)
    out = flash_attention(q, k, v, interpret=INTERPRET)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), atol=2e-2, rtol=2e-2
    )


# ---------------------------------------------------------------------------
# paged decode/prefill kernel (token-major cache [L, P, PS, Hkv*D])
# ---------------------------------------------------------------------------


def _build_paged_case(key, B, H, Hkv, D, page_size, max_pages, ctx_lens, C,
                      n_layers=2, layer=1):
    """Scatter per-sequence KV into shuffled physical pages of one layer;
    return the paged arrays, the q chunk, and dense KV for the oracle."""
    num_phys = 1 + B * max_pages  # page 0 = trash
    k_pages = jnp.zeros((n_layers, num_phys, page_size, Hkv * D), jnp.float32)
    v_pages = jnp.zeros_like(k_pages)

    # shuffled physical page assignment, like a real allocator under churn
    perm = np.random.RandomState(0).permutation(num_phys - 1) + 1
    page_table = np.zeros((B, max_pages), np.int32)
    dense_max = max_pages * page_size
    k_dense = np.zeros((B, dense_max, Hkv, D), np.float32)
    v_dense = np.zeros_like(k_dense)

    next_phys = 0
    rng = np.random.RandomState(1)
    for b in range(B):
        n_pages = -(-ctx_lens[b] // page_size) if ctx_lens[b] else 0
        for p in range(n_pages):
            page_table[b, p] = perm[next_phys]
            next_phys += 1
        kb = rng.randn(ctx_lens[b], Hkv, D).astype(np.float32)
        vb = rng.randn(ctx_lens[b], Hkv, D).astype(np.float32)
        k_dense[b, : ctx_lens[b]] = kb
        v_dense[b, : ctx_lens[b]] = vb
        for t in range(ctx_lens[b]):
            phys, off = page_table[b, t // page_size], t % page_size
            k_pages = k_pages.at[layer, phys, off].set(kb[t].reshape(-1))
            v_pages = v_pages.at[layer, phys, off].set(vb[t].reshape(-1))

    q = jax.random.normal(key, (B, C, H, D), jnp.float32)
    return q, k_pages, v_pages, jnp.asarray(page_table), jnp.asarray(k_dense), jnp.asarray(v_dense)


def test_paged_decode_matches_reference():
    """C=1 decode: ragged context lengths, shuffled pages, one inactive slot."""
    B, H, Hkv, D, page_size, max_pages = 4, 8, 2, 64, 16, 8
    ctx_lens = [37, 128, 5, 0]  # slot 3 inactive
    q, k_pages, v_pages, page_table, k_dense, v_dense = _build_paged_case(
        jax.random.key(4), B, H, Hkv, D, page_size, max_pages, ctx_lens, C=1
    )
    kv_len = jnp.asarray(ctx_lens, jnp.int32)
    q_offset = jnp.maximum(kv_len - 1, 0)  # decode: q is the last cached token

    out = paged_flash_attention(
        q, k_pages, v_pages, page_table, q_offset, kv_len, jnp.asarray([1]),
        page_size=page_size, n_kv=Hkv, interpret=INTERPRET,
    )
    ref = mha_reference(q, k_dense, v_dense, causal=True, q_offset=q_offset, kv_len=kv_len)
    # inactive slot must be exactly zero (fully masked)
    np.testing.assert_array_equal(np.asarray(out[3]), 0.0)
    np.testing.assert_allclose(out[:3], ref[:3], atol=ATOL, rtol=RTOL)


@pytest.mark.parametrize("C", [32, 3])
def test_paged_prefill_chunk_matches_reference(C):
    """C>1 chunked prefill at an offset: chunk KV already scattered. C=3 is
    a spec-verify block (1 + 2 drafts): not a whole sublane tile, so the
    wrapper pads it (Mosaic refused the unpadded block's shape cast)."""
    B, H, Hkv, D, page_size, max_pages = 2, 4, 4, 64, 16, 8
    ctx_lens = [64, 96]  # total cached INCLUDING the current chunk
    q, k_pages, v_pages, page_table, k_dense, v_dense = _build_paged_case(
        jax.random.key(5), B, H, Hkv, D, page_size, max_pages, ctx_lens, C=C
    )
    kv_len = jnp.asarray(ctx_lens, jnp.int32)
    q_offset = kv_len - C

    out = paged_flash_attention(
        q, k_pages, v_pages, page_table, q_offset, kv_len, jnp.asarray([1]),
        page_size=page_size, n_kv=Hkv, interpret=INTERPRET,
    )
    ref = mha_reference(q, k_dense, v_dense, causal=True, q_offset=q_offset, kv_len=kv_len)
    np.testing.assert_allclose(out, ref, atol=ATOL, rtol=RTOL)


@pytest.mark.parametrize("group,C", SHAPES)
def test_paged_walk_edges_match_reference(group, C):
    """Every edge of the walk as a row of one call (paged_walk_cases), under
    a table far wider than most rows whose dead entries point at a NaN page:
    a dead page read fails, the empty row is exactly zero."""
    q, sources, table, q_offset, kv_len, layer, k_dense, v_dense = walk_case(group, C)
    out = paged_flash_attention(
        q, *sources, table, q_offset, kv_len, layer,
        page_size=PAGE_SIZE, n_kv=2, interpret=INTERPRET,
    )
    ref = mha_reference(q, k_dense, v_dense, causal=True, q_offset=q_offset, kv_len=kv_len)
    assert_matches_reference(out, ref, atol=ATOL, rtol=RTOL)


def _pallas_grid(jaxpr):
    """The grid of the first ``pallas_call`` in a (nested) jaxpr."""
    return tuple(pallas_eqn(jaxpr).params["grid_mapping"].grid)


def test_paged_grid_does_not_follow_the_table_width():
    """The walk is as long as the row: the page table's width is not a grid
    axis, so a wider table adds no grid step."""
    def grid(width):
        q, sources, table, q_offset, kv_len, layer, *_ = walk_case(
            4, 1, contexts=[5, 40], width=width)
        return _pallas_grid(jax.make_jaxpr(
            lambda *args: paged_flash_attention(
                *args, page_size=PAGE_SIZE, n_kv=2, interpret=True)
        )(q, *sources, table, q_offset, kv_len, layer).jaxpr)

    assert grid(8) == grid(128) == (2, 1)  # (rows, query blocks)


def test_paged_kernel_agrees_with_scatter_gather_path():
    """End-to-end consistency with the engine's jnp path: scatter a chunk via
    scatter_kv_chunk, then paged kernel == gather_kv + mha_reference."""
    B, H, Hkv, D, page_size, max_pages = 2, 4, 2, 64, 16, 4
    L = 3
    num_phys = 1 + B * max_pages
    key = jax.random.key(6)
    kk, kv_, kq = jax.random.split(key, 3)

    k_pages = jnp.zeros((L, num_phys, page_size, Hkv * D), jnp.float32)
    v_pages = jnp.zeros_like(k_pages)
    page_table = jnp.asarray(
        [[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32
    )
    C = 16
    start_pos = jnp.array([0, 24], jnp.int32)
    n_valid = jnp.array([16, 9], jnp.int32)
    layer = jnp.int32(2)

    k_new = jax.random.normal(kk, (B, C, Hkv, D), jnp.float32)
    v_new = jax.random.normal(kv_, (B, C, Hkv, D), jnp.float32)
    k_pages, v_pages = scatter_kv_chunk(
        k_pages, v_pages, k_new, v_new, page_table, start_pos, n_valid,
        page_size, layer,
    )

    q = jax.random.normal(kq, (B, C, H, D), jnp.float32)
    kv_len = start_pos + n_valid

    out = paged_flash_attention(
        q, k_pages, v_pages, page_table, start_pos, kv_len, layer[None],
        page_size=page_size, n_kv=Hkv, interpret=INTERPRET,
    )
    k_dense, v_dense = gather_kv(k_pages, v_pages, page_table, page_size, layer, Hkv)
    ref = mha_reference(q, k_dense, v_dense, causal=True, q_offset=start_pos, kv_len=kv_len)
    # rows beyond n_valid are padding; compare valid rows only
    for b in range(B):
        nv = int(n_valid[b])
        np.testing.assert_allclose(out[b, :nv], ref[b, :nv], atol=ATOL, rtol=RTOL)


def test_kv_append_matches_scatter():
    """The in-place decode append kernel == scatter_kv_chunk for C=1, incl.
    the inactive-slot trash redirect and untouched other layers/pages."""
    from finchat_tpu.ops.kv_append import paged_kv_append

    B, Hkv, D, page_size, max_pages, L = 4, 2, 64, 16, 4, 3
    num_phys = 1 + B * max_pages
    rng = np.random.RandomState(7)
    k_pages = jnp.asarray(rng.randn(L, num_phys, page_size, Hkv * D), jnp.float32)
    v_pages = jnp.asarray(rng.randn(L, num_phys, page_size, Hkv * D), jnp.float32)
    page_table = jnp.asarray(
        [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12], [13, 14, 15, 16]], jnp.int32)
    pos = jnp.asarray([13, 37, 0, 63], jnp.int32)
    n_valid = jnp.asarray([1, 1, 0, 1], jnp.int32)
    layer = jnp.asarray([1], jnp.int32)
    k_new = jnp.asarray(rng.randn(B, 1, Hkv, D), jnp.float32)
    v_new = jnp.asarray(rng.randn(B, 1, Hkv, D), jnp.float32)

    want_k, want_v = scatter_kv_chunk(
        k_pages, v_pages, k_new, v_new, page_table, pos, n_valid,
        page_size, jnp.int32(1),
    )

    kv_new = jnp.concatenate(
        [k_new.reshape(B, 1, -1), v_new.reshape(B, 1, -1)], axis=-1)
    got_k, got_v = paged_kv_append(
        kv_new, k_pages, v_pages, page_table, pos, n_valid, layer,
        page_size=page_size, interpret=INTERPRET,
    )
    # trash page contents may differ (scatter drops padding writes there);
    # compare everything but physical page 0
    np.testing.assert_allclose(np.asarray(got_k)[:, 1:], np.asarray(want_k)[:, 1:], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got_v)[:, 1:], np.asarray(want_v)[:, 1:], rtol=1e-6)


# --- int8-KV (q8) kernels -------------------------------------------------
# test_kv_quant.py pins these kernels in interpret mode with tiny shapes;
# these two nodes use TPU-tileable shapes (row width 128 lanes, page 128
# so each page's fp32 scale block [pad8(Hkv)=8, 128] is exactly one tile)
# and follow this file's INTERPRET switch, so an on-chip run extends Mosaic
# coverage to the quantizing append and int8 paged attention that kv_quant
# serving uses.


_Q8_HKV, _Q8_HD, _Q8_PAGE = 2, 64, 128


def _q8_cache(n_pages):
    L = 1
    width = _Q8_HKV * _Q8_HD
    k_pages = jnp.zeros((L, n_pages, _Q8_PAGE, width), jnp.int8)
    v_pages = jnp.zeros_like(k_pages)
    sshape = (L, n_pages, 8, _Q8_PAGE)  # pad8(Hkv=2) = 8 scale rows
    return k_pages, v_pages, jnp.zeros(sshape, jnp.float32), jnp.zeros(sshape, jnp.float32)


def test_kv_append_q8_matches_scatter():
    """In-place quantizing append kernel == XLA q8 scatter for the same
    tokens: identical int8 rows and scales (interpret), within one int8
    step / fp32 scale tolerance on-chip where Mosaic and XLA may round
    the quantization division differently."""
    from finchat_tpu.engine.kv_cache import scatter_kv_chunk_q8
    from finchat_tpu.ops.kv_append import paged_kv_append_q8

    B = 2
    k_row = jax.random.normal(jax.random.key(3), (B, 1, _Q8_HKV, _Q8_HD), jnp.bfloat16)
    v_row = jax.random.normal(jax.random.key(4), (B, 1, _Q8_HKV, _Q8_HD), jnp.bfloat16)
    page_table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([3, 140], jnp.int32)  # second lands on page 2 of the row
    n_valid = jnp.asarray([1, 1], jnp.int32)
    layer = jnp.zeros((1,), jnp.int32)

    ka, va, ksa, vsa = paged_kv_append_q8(
        jnp.concatenate([k_row.reshape(B, 1, -1), v_row.reshape(B, 1, -1)], axis=-1),
        *_q8_cache(5), page_table, pos, n_valid, layer,
        page_size=_Q8_PAGE, n_kv=_Q8_HKV, interpret=INTERPRET,
    )
    kb, vb, ksb, vsb = scatter_kv_chunk_q8(
        *_q8_cache(5), k_row, v_row, page_table, pos, n_valid,
        _Q8_PAGE, jnp.int32(0), _Q8_HKV,
    )
    if INTERPRET:
        np.testing.assert_array_equal(np.asarray(ka), np.asarray(kb))
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
    else:
        np.testing.assert_allclose(
            np.asarray(ka, np.int32), np.asarray(kb, np.int32), atol=1)
        np.testing.assert_allclose(
            np.asarray(va, np.int32), np.asarray(vb, np.int32), atol=1)
    np.testing.assert_allclose(np.asarray(ksa), np.asarray(ksb), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(vsa), np.asarray(vsb), rtol=1e-5)


def test_paged_attention_q8_matches_dequantized_reference():
    """int8 paged attention == mha_reference over the SAME dequantized
    K/V (both sides see identical semantic values; tolerance is fp
    accumulation order only)."""
    from finchat_tpu.engine.kv_cache import gather_kv_q8, scatter_kv_chunk_q8
    from finchat_tpu.ops.dispatch import paged_attention

    B, C, H, T = 2, 1, 4, 200
    kp, vp, ks, vs = scatter_kv_chunk_q8(
        *_q8_cache(5),
        jax.random.normal(jax.random.key(5), (B, T, _Q8_HKV, _Q8_HD), jnp.float32),
        jax.random.normal(jax.random.key(6), (B, T, _Q8_HKV, _Q8_HD), jnp.float32),
        jnp.asarray([[1, 2], [3, 4]], jnp.int32), jnp.zeros((B,), jnp.int32),
        jnp.full((B,), T, jnp.int32), _Q8_PAGE, jnp.int32(0), _Q8_HKV,
    )
    q = jax.random.normal(jax.random.key(7), (B, C, H, _Q8_HD), jnp.float32)
    q_offset = jnp.full((B,), T - 1, jnp.int32)
    kv_len = jnp.full((B,), T, jnp.int32)
    page_table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)

    got = paged_attention(
        q, kp, vp, page_table, q_offset, kv_len, jnp.zeros((1,), jnp.int32),
        page_size=_Q8_PAGE, n_kv=_Q8_HKV,
        backend="pallas-interpret" if INTERPRET else "pallas",
        k_scales=ks, v_scales=vs,
    )
    k_deq, v_deq = gather_kv_q8(
        kp, vp, ks, vs, page_table, _Q8_PAGE, jnp.int32(0), _Q8_HKV,
        dtype=jnp.float32,
    )
    want = mha_reference(q, k_deq, v_deq, causal=True, q_offset=q_offset, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2, rtol=2e-2)
