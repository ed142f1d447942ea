"""Pallas kernels vs jnp reference oracles (SURVEY §4.2).

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
    DEAD_PAGE,
    PACKED_CASES,
    PACKED_SHAPES,
    PAGE_SIZE,
    SHAPES,
    SHARED_CASES,
    assert_matches_reference,
    walk_case,
)

from finchat_tpu.engine.kv_cache import gather_kv, scatter_kv_chunk
from finchat_tpu.ops.flash_attention import flash_attention
from finchat_tpu.ops.paged_attention import (
    _block_diagonal,
    _heads_per_tile,
    paged_flash_attention,
    paged_flash_attention_q8,
    shared_head,
)
from finchat_tpu.ops.refs import mha_reference

INTERPRET = jax.default_backend() != "tpu"
ATOL = RTOL = 2e-5 if INTERPRET else 2e-2


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


@pytest.mark.parametrize("name", SHARED_CASES)
def test_shared_head_is_read_off_the_page_tables(name):
    """Which rows and how many leading pages the decode pass takes, from the
    page tables, the contexts and the active mask alone."""
    contexts, heads, rows, pages = SHARED_CASES[name]
    _, _, table, _, kv_len, *_ = walk_case(4, 1, contexts=contexts, heads=heads)
    member, head = shared_head(table, kv_len, PAGE_SIZE, kv_len > 0)
    assert tuple(np.flatnonzero(np.asarray(member))) == rows
    assert int(head[0]) == pages
    if rows:
        assert int(head[1]) in rows


def test_shared_head_leaves_out_rows_that_are_not_active():
    """A slot that is not decoding keeps its old table row: it is no member,
    and its short context does not cut the others' run."""
    contexts, heads, rows, pages = SHARED_CASES["a_short_member"]
    _, _, table, _, kv_len, *_ = walk_case(4, 1, contexts=contexts, heads=heads)
    member, head = shared_head(table, kv_len, PAGE_SIZE, jnp.asarray([True, True, False]))
    assert (member.tolist(), int(head[0])) == ([1, 1, 0], 5)


@pytest.mark.parametrize("group", [1, 4, 5, 8])
@pytest.mark.parametrize("name", SHARED_CASES)
def test_paged_decode_with_a_shared_head_matches_reference(name, group):
    """Rows holding the same physical pages at the head of their tables: the
    pass that reads those pages once for all of them, then each row's own
    walk behind them, against the dense oracle; every dead table entry is on
    the NaN page."""
    contexts, heads, *_ = SHARED_CASES[name]
    q, sources, table, q_offset, kv_len, layer, k_dense, v_dense = walk_case(
        group, 1, contexts=contexts, heads=heads)
    out = paged_flash_attention(
        q, *sources, table, q_offset, kv_len, layer,
        page_size=PAGE_SIZE, n_kv=2, interpret=INTERPRET,
    )
    ref = mha_reference(q, k_dense, v_dense, causal=True, q_offset=q_offset, kv_len=kv_len)
    assert_matches_reference(out, ref, contexts, atol=ATOL, rtol=RTOL)


@pytest.mark.parametrize("name", ["all_rows", "two_heads", "a_long_head"])
def test_paged_decode_reads_a_shared_head_through_one_row_alone(name):
    """A member's own walk starts behind the shared pages: with every member
    but the leading one pointing its head columns at the NaN page, the result
    is still the oracle's."""
    contexts, heads, rows, pages = SHARED_CASES[name]
    q, sources, table, q_offset, kv_len, layer, k_dense, v_dense = walk_case(
        4, 1, contexts=contexts, heads=heads)
    member, head = shared_head(table, kv_len, PAGE_SIZE)
    lead = int(head[1])
    table = np.array(table)
    table[[b for b in rows if b != lead], :pages] = DEAD_PAGE
    out = paged_flash_attention(
        q, *sources, jnp.asarray(table), q_offset, kv_len, layer, (member, head),
        page_size=PAGE_SIZE, n_kv=2, interpret=INTERPRET,
    )
    ref = mha_reference(q, k_dense, v_dense, causal=True, q_offset=q_offset, kv_len=kv_len)
    assert_matches_reference(out, ref, contexts, atol=ATOL, rtol=RTOL)


@pytest.mark.parametrize("name", PACKED_CASES)
@pytest.mark.parametrize("group,n_kv", PACKED_SHAPES)
def test_paged_decode_with_heads_sharing_a_tile_matches_reference(group, n_kv, name):
    """One or two query heads a KV head: the heads of a tile take one block
    update together, off block-diagonal queries (a last tile with fewer heads
    than fit, rows that are no member of the shared head, inactive slots, a
    head longer than a block), against the dense oracle."""
    assert _heads_per_tile(group, 1) == 8 // group
    contexts, heads, *_ = SHARED_CASES[name]
    q, sources, table, q_offset, kv_len, layer, k_dense, v_dense = walk_case(
        group, 1, contexts=contexts, heads=heads, n_kv=n_kv)
    out = paged_flash_attention(
        q, *sources, table, q_offset, kv_len, layer,
        page_size=PAGE_SIZE, n_kv=n_kv, interpret=INTERPRET,
    )
    ref = mha_reference(q, k_dense, v_dense, causal=True, q_offset=q_offset, kv_len=kv_len)
    assert_matches_reference(out, ref, contexts, atol=ATOL, rtol=RTOL)


@pytest.mark.parametrize("group,n_kv", PACKED_SHAPES)
def test_paged_decode_of_one_row_with_heads_sharing_a_tile_matches_reference(group, n_kv):
    """A batch of one row has no shared-head pass: the tiles alone, every
    edge of the walk in turn."""
    for context in (1, PAGE_SIZE, 8 * PAGE_SIZE + 1, 13 * PAGE_SIZE + 5):
        q, sources, table, q_offset, kv_len, layer, k_dense, v_dense = walk_case(
            group, 1, contexts=[context], n_kv=n_kv)
        out = paged_flash_attention(
            q, *sources, table, q_offset, kv_len, layer,
            page_size=PAGE_SIZE, n_kv=n_kv, interpret=INTERPRET,
        )
        ref = mha_reference(q, k_dense, v_dense, causal=True, q_offset=q_offset, kv_len=kv_len)
        assert_matches_reference(out, ref, [context], atol=ATOL, rtol=RTOL)


@pytest.mark.parametrize("group,block_q,heads", [
    (1, 1, 8), (2, 1, 4),  # fewer than half a tile: as many heads as fill it
    (4, 1, 1),  # half a tile (Mistral, Mixtral): a head a tile
    (5, 1, 1),  # Falcon-H1: 5 rows do not divide 8
    (8, 1, 1), (16, 1, 1),
    (1, 8, 1), (1, 128, 1), (4, 128, 1),  # a verify block, prefill: tiles of their own
])
def test_heads_share_a_tile_only_where_their_rows_leave_most_of_it_empty(group, block_q, heads):
    assert _heads_per_tile(group, block_q) == heads


@pytest.mark.parametrize("group,n_kv", PACKED_SHAPES)
def test_block_diagonal_queries_give_each_row_its_own_heads_logits(group, n_kv):
    """Row ``i * group + g`` of a tile holds query head ``g`` of the tile's
    ``i``-th KV head at that head's lanes and zeros elsewhere: its product
    with the tile's lanes of a K row is that head's logit, exactly."""
    pack, D, B = 8 // group, 16, 3
    rng = np.random.RandomState(n_kv)
    q = rng.randn(B, 1, n_kv * group, D).astype(np.float32)
    k = rng.randn(n_kv * D).astype(np.float32)  # one token's K row, heads fused
    tiles = np.asarray(_block_diagonal(jnp.asarray(q), n_kv, pack))
    n_tiles = -(-n_kv // pack)
    assert tiles.shape == (B, n_tiles, 8, pack * D)
    k_pad = np.pad(k, (0, n_tiles * pack * D - k.size)).reshape(n_tiles, pack * D)
    logits = np.einsum("btrl,tl->btr", tiles, k_pad).reshape(B, n_tiles * 8)
    want = np.einsum("bhd,hd->bh", q[:, 0].reshape(B, n_kv * group, D),
                     np.repeat(k.reshape(n_kv, D), group, axis=0))
    np.testing.assert_allclose(logits[:, :n_kv * group], want, rtol=1e-6, atol=1e-6)
    assert not logits[:, n_kv * group:].any()  # the rows of heads that are not there
    assert (tiles != 0).sum() == q.size  # nothing but each row's own head


def _pallas_eqn(jaxpr):
    """The first ``pallas_call`` equation in a (nested) jaxpr."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns") and (found := _pallas_eqn(inner)) is not None:
                return found
    return None


# the four cells' decode shapes (perfbench/configs: heads / KV heads of 128,
# pages of 128, 16 rows, a table of 128), their verify block and their
# 256-token prefill chunk: (heads, KV heads, C, int8) -> pages a block, KV
# heads a tile
BLOCK_TABLE = [
    (32, 8, 1, False, 4, 1), (32, 8, 3, False, 4, 1), (32, 8, 256, False, 2, 1),
    (32, 8, 1, True, 4, 1),
    (20, 4, 1, False, 4, 1), (20, 4, 256, False, 1, 1),
    # a K block is bounded by its bytes (2 MiB): 256 tokens of 7.5 KiB. With
    # the heads of a tile together that measured FASTER than 512 tokens under
    # a larger VMEM limit (PERF.md section 6, PR 33)
    (30, 30, 1, False, 2, 8), (30, 30, 3, False, 2, 1), (30, 30, 256, False, 1, 1),
    (30, 30, 1, True, 1, 8),
]


@pytest.mark.parametrize("heads,n_kv,C,quantized,pages,pack", BLOCK_TABLE)
def test_block_and_tile_follow_from_the_cells_static_shapes(heads, n_kv, C, quantized,
                                                            pages, pack):
    """Pages a block (the K and V buffers in the kernel's scratch) and KV
    heads a tile (the width of the softmax state) at the shapes the
    benchmark's cells run, from a trace alone: nothing is compiled or run."""
    from finchat_tpu.engine.kv_cache import scale_rows

    rows, page, width, D = 16, 128, 128, 128
    S = jax.ShapeDtypeStruct
    cache = S((3, 1600, page, n_kv * D), jnp.int8 if quantized else jnp.bfloat16)
    sources = (cache, cache)
    if quantized:
        sources += (S((3, 1600, scale_rows(n_kv), page), jnp.float32),) * 2
    kernel = paged_flash_attention_q8 if quantized else paged_flash_attention
    eqn = _pallas_eqn(jax.make_jaxpr(
        lambda *args: kernel(*args, page_size=page, n_kv=n_kv))(
            S((rows, C, heads, D), jnp.bfloat16), *sources, S((rows, width), jnp.int32),
            S((rows,), jnp.int32), S((rows,), jnp.int32), S((1,), jnp.int32)).jaxpr)
    shapes = [tuple(v.aval.shape) for v in eqn.params["jaxpr"].invars]
    buffers = [s for s in shapes if len(s) == 4 and s[0] == 2 and s[2:] == (page, n_kv * D)]
    assert len(buffers) == 2 and buffers[0][1] == pages, buffers
    assert _heads_per_tile(heads // n_kv, 1 if C == 1 else 8) == pack
    if pack > 1:  # a row's acc is as wide as its tile's lanes, 8 rows a tile
        assert (-(-n_kv // pack) * 8, pack * D) in shapes


def _pallas_grid(jaxpr):
    """The grid of the first ``pallas_call`` in a (nested) jaxpr."""
    return tuple(_pallas_eqn(jaxpr).params["grid_mapping"].grid)


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


def test_engine_end_to_end_pallas_backend():
    """The engine's chunked prefill + decode must produce identical greedy
    tokens whether attention runs through the jnp reference path or the
    Pallas kernels (interpret mode on the CPU test mesh)."""
    from finchat_tpu.engine.engine import InferenceEngine, commit_first_token
    from finchat_tpu.engine.kv_cache import PageAllocator, pages_needed
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.utils.config import EngineConfig

    config = PRESETS["tiny"]
    engine_cfg = EngineConfig(
        max_seqs=2, page_size=8, num_pages=32, max_seq_len=64, prefill_chunk=8
    )
    params = init_params(config, jax.random.key(0))
    prompt = [3, 7, 11, 200, 42, 9, 13, 55, 21, 8]  # 2 chunks
    n_new = 6

    def run(backend):
        eng = InferenceEngine(config, params, engine_cfg, attn_backend=backend)
        alloc = PageAllocator(engine_cfg.num_pages)
        pages = alloc.allocate("s", pages_needed(len(prompt) + n_new, eng.page_size))
        eng.set_page_table_row(0, pages)
        logits = eng.prefill(0, prompt)
        eng.state, tok = commit_first_token(
            eng.state, jnp.int32(0), logits,
            jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0),
        )
        out = [int(tok)]
        B = engine_cfg.max_seqs
        active = jnp.zeros((B,), bool).at[0].set(True)
        zeros, ones, zk = jnp.zeros((B,)), jnp.ones((B,)), jnp.zeros((B,), jnp.int32)
        for _ in range(n_new - 1):
            out.append(int(eng.decode(active, zeros, ones, zk)[0]))
        return out

    assert run("ref") == run("pallas-interpret")


def _decode_logits_on_both_backends(engine, state, active):
    """``decode_step`` from one state on ``ref`` and on the kernels
    (interpret mode here); the step donates its state, so each gets a copy."""
    from finchat_tpu.engine.engine import decode_step

    B = active.shape[0]
    zeros, ones, zk = jnp.zeros((B,)), jnp.ones((B,)), jnp.zeros((B,), jnp.int32)

    def logits(backend):
        _, _, out, _ = decode_step(
            engine.params, jax.tree.map(jnp.copy, state), active, zeros, ones, zk,
            config=engine.config, page_size=engine.page_size,
            attn_backend=backend, return_logits=True)
        return np.asarray(out)[np.asarray(active)]

    return logits("ref"), logits("pallas" if not INTERPRET else "pallas-interpret")


def test_decode_step_over_a_scheduler_built_shared_head_matches_ref():
    """Three rows admitted on ONE prefix entry, as the scheduler lays them
    out (the same four physical pages at the head of each page table): the
    decode step through the kernels, shared-head pass engaged, gives the
    ``ref`` backend's logits."""
    import asyncio
    import dataclasses

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.utils.config import EngineConfig

    page, head = 8, list(range(1, 33))  # four whole pages
    config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
    engine = InferenceEngine(
        config, init_params(config, jax.random.key(0)),
        EngineConfig(max_seqs=4, page_size=page, num_pages=128, max_seq_len=256,
                     prefill_chunk=16, session_cache=False, mixed_step=False),
        attn_backend="ref")
    caught = []
    decode = engine.decode

    def spy(active, *args, **kw):
        if not caught and int(np.sum(np.asarray(active))) == 3:
            caught.append((jax.tree.map(jnp.copy, engine.state), jnp.asarray(active)))
        return decode(active, *args, **kw)

    engine.decode = spy

    async def drain(handle):
        while (await handle.events.get())["type"] == "token":
            pass

    async def go():
        sched = ContinuousBatchingScheduler(engine, eos_id=-1)
        assert sched.register_prefix(head + [99]) == len(head)
        await sched.start()
        try:
            sampling = SamplingParams(temperature=0.0, max_new_tokens=8)
            handles = [await sched.submit(f"r{i}", head + tail, sampling)
                       for i, tail in enumerate([[40, 41, 42], [50] * 9, [60, 61]])]
            await asyncio.wait_for(asyncio.gather(*map(drain, handles)), timeout=240)
        finally:
            await sched.stop()

    asyncio.run(go())
    assert caught, "the three rows never decoded together"
    state, active = caught[0]
    member, shared = shared_head(
        state.page_table, state.context_lens + active, page, active)
    assert (int(member.sum()), int(shared[0])) == (3, 4)
    want, got = _decode_logits_on_both_backends(engine, state, active)
    np.testing.assert_allclose(got, want, atol=1e-4 if INTERPRET else 5e-2, rtol=1e-4)


def test_decode_step_with_a_gapped_row_in_the_shared_head_matches_ref():
    """Bounded KV: a row whose policy evicted pages behind the pinned sink
    keeps the sink at the head of its (compacted) page list. The sink pages
    it shares with two unbounded rows are read in the shared pass, its window
    behind them at compacted positions, as ``ref`` reads them."""
    import dataclasses

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.utils.config import EngineConfig

    page = 8
    config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
    engine = InferenceEngine(
        config, init_params(config, jax.random.key(0)),
        EngineConfig(max_seqs=4, page_size=page, num_pages=64, max_seq_len=128,
                     prefill_chunk=16, session_cache=False, mixed_step=False),
        attn_backend="ref")
    state = engine.state
    table = np.zeros(state.page_table.shape, np.int32)
    table[0, :7] = [3, 4, 5, 10, 11, 12, 13]
    table[1, :5] = [3, 4, 5, 20, 21]
    table[3, :6] = [3, 4, 5, 30, 31, 32]  # slot 2 stays inactive
    contexts = np.array([50, 36, 0, 41 + 3 * page], np.int32)  # absolute
    gaps = np.array([0, 0, 0, 3 * page], np.int32)  # three pages evicted
    pools = [jax.random.normal(jax.random.key(i), pool.shape, pool.dtype)
             for i, pool in enumerate((state.k_pages, state.v_pages))]
    state = dataclasses.replace(
        state, k_pages=pools[0], v_pages=pools[1], page_table=jnp.asarray(table),
        context_lens=jnp.asarray(contexts), kv_gaps=jnp.asarray(gaps),
        last_tokens=jnp.asarray([5, 6, 0, 7], jnp.int32))
    active = jnp.asarray([True, True, False, True])
    member, shared = shared_head(
        state.page_table, state.context_lens - state.kv_gaps + active, page, active)
    assert (member.tolist(), int(shared[0])) == ([1, 1, 0, 1], 3)
    want, got = _decode_logits_on_both_backends(engine, state, active)
    np.testing.assert_allclose(got, want, atol=1e-4 if INTERPRET else 5e-2, rtol=1e-4)


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
