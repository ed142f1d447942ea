"""The paged walk over rows that hold the same physical pages at the head of
their tables (``ops.paged_attention.shared_head``): which rows and pages the
decode pass takes, and the pass that reads them once for all of its rows
against the dense oracle (tests/paged_walk_cases.py has the cases). Interpret
mode here, on the chip under ``FINCHAT_TESTS_TPU=1`` (tests/test_pallas_attention.py).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from paged_walk_cases import (
    ATOL,
    DEAD_PAGE,
    INTERPRET,
    PAGE_SIZE,
    RTOL,
    SHARED_CASES,
    SHARED_POOL,
    assert_matches_reference,
    walk_case,
)

from finchat_tpu.ops.paged_attention import paged_flash_attention, shared_head
from finchat_tpu.ops.refs import mha_reference


@pytest.mark.parametrize("name", SHARED_CASES)
def test_shared_head_is_read_off_the_page_tables(name):
    """Which rows and how many leading pages the decode pass takes, from the
    page tables, the contexts and the active mask alone."""
    contexts, heads, rows, pages = SHARED_CASES[name]
    _, _, table, _, kv_len, *_ = walk_case(4, 1, contexts=contexts, heads=heads, pool=SHARED_POOL)
    member, head = shared_head(table, kv_len, PAGE_SIZE, kv_len > 0)
    assert tuple(np.flatnonzero(np.asarray(member))) == rows
    assert int(head[0]) == pages
    if rows:
        assert int(head[1]) in rows


def test_shared_head_leaves_out_rows_that_are_not_active():
    """A slot that is not decoding keeps its old table row: it is no member,
    and its short context does not cut the others' run."""
    contexts, heads, rows, pages = SHARED_CASES["a_short_member"]
    _, _, table, _, kv_len, *_ = walk_case(4, 1, contexts=contexts, heads=heads, pool=SHARED_POOL)
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
        group, 1, contexts=contexts, heads=heads, pool=SHARED_POOL)
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
        4, 1, contexts=contexts, heads=heads, pool=SHARED_POOL)
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
