"""The paged walk's decode kernel where several KV heads' query rows share one
8-row softmax tile (``_heads_per_tile``: one or two query heads a KV head):
the tiles against the dense oracle, the block-diagonal queries, and the block
and tile the benchmark's cells get from their static shapes. Interpret mode
here, on the chip under ``FINCHAT_TESTS_TPU=1`` (tests/test_pallas_attention.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paged_walk_cases import (
    ATOL,
    INTERPRET,
    PACKED_CASES,
    PACKED_SHAPES,
    PAGE_SIZE,
    RTOL,
    SHARED_CASES,
    SHARED_POOL,
    assert_matches_reference,
    pallas_eqn,
    walk_case,
)

from finchat_tpu.ops.paged_attention import (
    _block_diagonal,
    _heads_per_tile,
    paged_flash_attention,
    paged_flash_attention_q8,
)
from finchat_tpu.ops.refs import mha_reference


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
        group, 1, contexts=contexts, heads=heads, n_kv=n_kv, pool=SHARED_POOL)
    out = paged_flash_attention(
        q, *sources, table, q_offset, kv_len, layer,
        page_size=PAGE_SIZE, n_kv=n_kv, interpret=INTERPRET,
    )
    ref = mha_reference(q, k_dense, v_dense, causal=True, q_offset=q_offset, kv_len=kv_len)
    assert_matches_reference(out, ref, contexts, atol=ATOL, rtol=RTOL)


@pytest.mark.parametrize("group,n_kv", PACKED_SHAPES)
def test_paged_decode_of_one_row_with_heads_sharing_a_tile_matches_reference(group, n_kv):
    """A batch of one row has no shared-head pass: the tiles alone, every
    edge of the walk in turn (one pool's size, so one compiled program)."""
    for context in (1, PAGE_SIZE, 8 * PAGE_SIZE + 1, 13 * PAGE_SIZE + 5):
        q, sources, table, q_offset, kv_len, layer, k_dense, v_dense = walk_case(
            group, 1, contexts=[context], n_kv=n_kv, pool=2 + 14)  # the longest holds 14 pages
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
    eqn = pallas_eqn(jax.make_jaxpr(
        lambda *args: kernel(*args, page_size=page, n_kv=n_kv))(
            S((rows, C, heads, D), jnp.bfloat16), *sources, S((rows, width), jnp.int32),
            S((rows,), jnp.int32), S((rows,), jnp.int32), S((1,), jnp.int32)).jaxpr)
    shapes = [tuple(v.aval.shape) for v in eqn.params["jaxpr"].invars]
    buffers = [s for s in shapes if len(s) == 4 and s[0] == 2 and s[2:] == (page, n_kv * D)]
    assert len(buffers) == 2 and buffers[0][1] == pages, buffers
    assert _heads_per_tile(heads // n_kv, 1 if C == 1 else 8) == pack
    if pack > 1:  # a row's acc is as wide as its tile's lanes, 8 rows a tile
        assert (-(-n_kv // pack) * 8, pack * D) in shapes
