"""The int8 paged walk where several KV heads' query rows share one softmax
tile: the float walk's cases (tests/paged_walk_cases.py,
tests/test_paged_walk_packed_tile.py) over the quantized cache, against the
reference over the same dequantized values.
"""

import pytest

from paged_walk_cases import (
    PACKED_CASES,
    PACKED_SHAPES,
    PAGE_SIZE,
    SHARED_CASES,
    SHARED_POOL,
    assert_matches_reference,
    walk_case,
)

from finchat_tpu.ops.refs import mha_reference


@pytest.mark.parametrize("name", PACKED_CASES)
@pytest.mark.parametrize("group,n_kv", PACKED_SHAPES)
def test_paged_decode_q8_with_heads_sharing_a_tile_matches_dequantized_reference(
        group, n_kv, name):
    """One or two query heads a KV head over the int8 cache: the heads of a
    tile take one block update together, each row under its own head's
    per-token scales (a row of the scale block a head)."""
    from finchat_tpu.ops.paged_attention import paged_flash_attention_q8

    contexts, heads, *_ = SHARED_CASES[name]
    q, sources, table, q_offset, kv_len, layer, k_deq, v_deq = walk_case(
        group, 1, quantized=True, contexts=contexts, heads=heads, n_kv=n_kv, pool=SHARED_POOL)
    out = paged_flash_attention_q8(
        q, *sources, table, q_offset, kv_len, layer,
        page_size=PAGE_SIZE, n_kv=n_kv, interpret=True,
    )
    want = mha_reference(q, k_deq, v_deq, causal=True, q_offset=q_offset, kv_len=kv_len)
    assert_matches_reference(out, want, contexts, atol=1e-4, rtol=1e-4)
