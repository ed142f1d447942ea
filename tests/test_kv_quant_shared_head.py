"""The int8 paged walk over rows that hold the same physical pages at the head
of their tables: the float walk's shared-head cases (tests/paged_walk_cases.py,
tests/test_paged_walk_shared_head.py) over the quantized cache, against the
reference over the same dequantized values.
"""

import pytest

from paged_walk_cases import (
    PAGE_SIZE,
    SHARED_CASES,
    SHARED_POOL,
    assert_matches_reference,
    walk_case,
)

from finchat_tpu.ops.refs import mha_reference


@pytest.mark.parametrize("group", [4, 5])
@pytest.mark.parametrize("name", SHARED_CASES)
def test_paged_decode_q8_with_a_shared_head_matches_dequantized_reference(name, group):
    """The int8 walk on the float walk's shared-head cases: the scales of the
    shared pages ride the first pass's copies as they ride a row's own."""
    from finchat_tpu.ops.paged_attention import paged_flash_attention_q8

    contexts, heads, *_ = SHARED_CASES[name]
    q, sources, table, q_offset, kv_len, layer, k_deq, v_deq = walk_case(
        group, 1, quantized=True, contexts=contexts, heads=heads, pool=SHARED_POOL)
    out = paged_flash_attention_q8(
        q, *sources, table, q_offset, kv_len, layer,
        page_size=PAGE_SIZE, n_kv=2, interpret=True,
    )
    want = mha_reference(q, k_deq, v_deq, causal=True, q_offset=q_offset, kv_len=kv_len)
    assert_matches_reference(out, want, contexts, atol=1e-4, rtol=1e-4)
