"""The paged kernels where a head's keys are WIDER than its values (192 over
128: K and V arrays of two widths, a head's keys cut as the two lane tiles that
hold them) and where the softmax has a SINK (ops/paged_attention.py
``_key_lanes`` / ``_pair_queries`` / ``sink``; ops/ragged_paged_attention.py):
interpreted Pallas against ``mha_reference`` over the gathered pages, in
tests/paged_walk_cases.py's style — pages the walk must not read are poisoned.

One compile a case (interpret mode: most of a case's time), small tables.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finchat_tpu.engine.kv_cache import gather_kv
from finchat_tpu.ops.dispatch import paged_attention, ragged_paged_attention
from finchat_tpu.ops.paged_attention import (
    _key_lanes,
    _pages_per_block,
    _pair_queries,
    paged_flash_attention,
    shared_head,
)
from finchat_tpu.ops.ragged_paged_attention import ragged_flash_attention
from finchat_tpu.ops.refs import mha_reference

INTERPRET = jax.default_backend() != "tpu"
ATOL = RTOL = 3e-5 if INTERPRET else 2e-2
DK, DV, PS, LAYER = 192, 128, 16, 1


def _pools(contexts, n_kv, width, seed=0, shared=0, sharing=()):
    """``(k_pages, v_pages, page_table, k_dense, v_dense)``: rows of
    ``contexts`` tokens over pages of ``PS`` in shuffled physical pages of
    layer 1 of 2, the first ``shared`` pages of the rows ``sharing`` row 0's; the
    trash page and every page no row holds are NaN; a row without a token has a
    table row of zeros."""
    rng = np.random.RandomState(seed)
    B = len(contexts)
    live = [-(-n // PS) for n in contexts]
    n_phys = 2 + sum(live)
    phys = rng.permutation(np.arange(2, n_phys))
    table = np.full((B, width), 1, np.int32)
    k_pages = np.full((2, n_phys, PS, n_kv * DK), np.nan, np.float32)
    v_pages = np.full((2, n_phys, PS, n_kv * DV), np.nan, np.float32)
    used = 0
    for b, n in enumerate(contexts):
        table[b, :live[b]] = phys[used:used + live[b]]
        used += live[b]
        if not n:
            table[b] = 0
        if b in sharing:
            table[b, :shared] = table[0, :shared]
        for p in range(live[b]):
            if b in sharing and p < shared:
                continue
            k_pages[LAYER, table[b, p]] = rng.randn(PS, n_kv * DK)
            v_pages[LAYER, table[b, p]] = rng.randn(PS, n_kv * DV)
    k_pages, v_pages, table = jnp.asarray(k_pages), jnp.asarray(v_pages), jnp.asarray(table)
    k_dense, v_dense = gather_kv(k_pages, v_pages, table, PS, jnp.int32(LAYER), n_kv)
    assert k_dense.shape[-1] == DK and v_dense.shape[-1] == DV
    # (what lies beyond a row's tokens is masked in the oracle: make it finite there)
    return k_pages, v_pages, table, jnp.nan_to_num(k_dense), jnp.nan_to_num(v_dense)


def _queries(B, C, H, seed=1):
    return jnp.asarray(np.random.RandomState(seed).randn(B, C, H, DK), jnp.float32)


def test_a_heads_keys_are_the_two_lane_tiles_that_hold_them():
    """Two heads of 192 are three lane tiles: head 0's keys lead tiles 0-1,
    head 1's trail tiles 1-2; the queries carry zeros at the neighbour's lanes,
    so the product over the two tiles is the head's own."""
    assert [_key_lanes(h, 192, 256) for h in range(4)] == [0, 128, 384, 512]
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(3, 8, DK), jnp.float32)  # 8 query heads over 4 KV heads
    k = rng.randn(4 * DK).astype(np.float32)
    paired = np.asarray(_pair_queries(q, 4))
    assert paired.shape == (3, 8, 256)
    for head in range(8):
        kv = head // 2
        lanes = k[_key_lanes(kv, 192, 256):][:256]
        np.testing.assert_allclose(paired[:, head] @ lanes,
                                   np.asarray(q)[:, head] @ k[kv * DK:(kv + 1) * DK], rtol=1e-5)


def test_the_block_budgets_k_and_v_apart():
    """A token's K and V bytes are counted as wide as each is: at 8 heads of
    192 over 128 a 512-token block of both, double-buffered, is 5 MiB and
    fits; the same call counted at 2 x the K width would be said to hold 6."""
    assert _pages_per_block(128, 8, 8 * 192, 2, 256, v_width=8 * 128) == 4
    # an accepted shape is counted as it was: one width serves both
    assert _pages_per_block(128, 4, 1024, 2, 128) == _pages_per_block(
        128, 4, 1024, 2, 128, v_width=1024) == 4
    # a window's table of 3 columns at 8 heads of 192 / 128 is ONE block
    assert _pages_per_block(128, 8, 1536, 2, 3, 1 << 20, whole_table=True, v_width=1024) == 3


@pytest.mark.parametrize("group", [16, 8], ids=["16-rows-a-head", "8-rows-a-head"])
def test_the_decode_walk_with_a_shared_head_at_keys_of_192_over_values_of_128(group):
    """The full layers' one-token call: program 0's stacked pass over the
    shared pages (16 query rows a K/V head are two sublane tiles a sequence),
    then each row's own walk; one row shares nothing, one has no token."""
    n_kv, contexts = 2, [5 * PS + 3, 7 * PS, 0, 4 * PS + 1, 2 * PS + 5]
    k_pages, v_pages, table, k_dense, v_dense = _pools(contexts, n_kv, 8, shared=3,
                                                       sharing=(1, 3))
    kv_len = jnp.asarray(contexts, jnp.int32)
    q = _queries(len(contexts), 1, n_kv * group)
    member, head = shared_head(table, kv_len, PS, kv_len > 0)
    assert [int(m) for m in member] == [1, 1, 0, 1, 0] and int(head[0]) == 3
    want = mha_reference(q, k_dense, v_dense, q_offset=jnp.maximum(kv_len - 1, 0), kv_len=kv_len)
    got = paged_flash_attention(q, k_pages, v_pages, table, jnp.maximum(kv_len - 1, 0), kv_len,
                                jnp.asarray([LAYER]), page_size=PS, n_kv=n_kv,
                                interpret=INTERPRET)
    assert got.shape == (len(contexts), 1, n_kv * group, DV) and np.isfinite(np.asarray(got)).all()
    live = np.asarray(kv_len) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], atol=ATOL, rtol=RTOL)
    np.testing.assert_array_equal(np.asarray(got)[~live], 0.0)


@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("C", [1, 8], ids=["decode", "chunk"])
def test_the_window_form_with_and_without_a_sink(C, sink):
    """A window layer's call over a bounded table (window / page + 2 columns):
    one token a row (no stacked pass, live pages alone copied) and a chunk;
    the sink starts a row's sum at (b, 1, 0)."""
    n_kv, group, window = 4, 4, 2 * PS
    contexts = [3 * PS + 5, 2 * PS, PS - 2, 4 * PS]  # tokens in the (compacted) table
    k_pages, v_pages, table, k_dense, v_dense = _pools(contexts, n_kv, 4, seed=2)
    kv_len = jnp.asarray(contexts, jnp.int32)
    q = _queries(len(contexts), C, n_kv * group, seed=3)
    sinks = jnp.asarray(np.random.RandomState(4).randn(n_kv * group), jnp.float32) if sink else None
    kw = {"sink": sinks} if sink else {}
    want = mha_reference(q, k_dense, v_dense, q_offset=kv_len - C, kv_len=kv_len, window=window,
                         **kw)
    shared = (jnp.zeros((len(contexts),), jnp.int32), jnp.zeros((2,), jnp.int32))
    got = paged_attention(q, k_pages, v_pages, table, kv_len - C, kv_len, jnp.asarray([LAYER]),
                          page_size=PS, n_kv=n_kv, backend="pallas-interpret", shared=shared,
                          window=window, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL, rtol=RTOL)
    if sink:  # the sink takes probability: without it the output is another
        plain = mha_reference(q, k_dense, v_dense, q_offset=kv_len - C, kv_len=kv_len,
                              window=window)
        assert np.abs(np.asarray(plain) - np.asarray(want)).max() > 1e-2


def test_the_sink_is_one_more_logit_in_the_sum_and_gives_no_value():
    """``mha_reference``'s sink against the equation written out: m = max(max
    s, b); p = exp(s - m) / (sum exp(s - m) + exp(b - m))."""
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(1, 6, 2, d), jnp.float32) for d in (DK, DK, DV))
    b = jnp.asarray([0.7, -1.3], jnp.float32)
    got = np.asarray(mha_reference(q, k, v, sink=b))
    s = np.einsum("qhd,khd->hqk", np.asarray(q[0]), np.asarray(k[0])) / np.sqrt(DK)
    s = np.where(np.tril(np.ones((6, 6), bool))[None], s, -np.inf)
    m = np.maximum(s.max(-1), np.asarray(b)[:, None])
    p = np.exp(s - m[..., None])
    p = p / (p.sum(-1) + np.exp(np.asarray(b)[:, None] - m))[..., None]
    np.testing.assert_allclose(got[0], np.einsum("hqk,khd->qhd", p, np.asarray(v[0])), atol=1e-5)
    # a sink far below every score is no sink; far above, it takes everything
    np.testing.assert_allclose(np.asarray(mha_reference(q, k, v, sink=b - 1e4)),
                               np.asarray(mha_reference(q, k, v)), atol=1e-6)
    assert np.abs(np.asarray(mha_reference(q, k, v, sink=b + 1e4))).max() < 1e-6


@pytest.mark.parametrize("window", [0, 2 * PS], ids=["full", "window-sink"])
def test_a_ragged_round_at_keys_of_192_over_values_of_128(window):
    """One packed buffer: a decode row, a prompt's later chunk, a padding
    span; the window layers' form with its sink, the full layers' without."""
    n_kv, group = (4, 4) if window else (2, 8)
    H = n_kv * group
    contexts = [2 * PS + 3, 4 * PS + 1, PS]
    k_pages, v_pages, table, _k, _v = _pools(contexts, n_kv, 5, seed=6)
    table = jnp.where(table == 1, 0, table)  # (the reference backend gathers whole tables)
    k_pages, v_pages = jnp.nan_to_num(k_pages), jnp.nan_to_num(v_pages)
    q_lens = [1, 9, 4]
    tok_row = np.concatenate([np.full(n, r) for r, n in enumerate(q_lens)] + [np.full(2, 3)])
    tok_pos = np.concatenate([np.arange(c - n, c) for c, n in zip(contexts, q_lens)] + [[0, 0]])
    q = jnp.asarray(np.random.RandomState(7).randn(len(tok_row), H, DK), jnp.float32)
    kw = dict(page_size=PS, n_kv=n_kv)
    if window:
        kw.update(window=window,
                  sink=jnp.asarray(np.random.RandomState(8).randn(H), jnp.float32))
    args = (q, k_pages, v_pages, table, jnp.asarray(tok_row, jnp.int32),
            jnp.asarray(tok_pos, jnp.int32), jnp.asarray(contexts, jnp.int32), jnp.asarray([LAYER]))
    want = ragged_paged_attention(*args, backend="ref", **kw)
    got = ragged_flash_attention(*args, interpret=INTERPRET, **kw)
    assert got.shape == (len(tok_row), H, DV)
    np.testing.assert_allclose(np.asarray(got)[:-2], np.asarray(want)[:-2], atol=ATOL, rtol=RTOL)


def test_thirty_two_rows_of_sixteen_query_heads_take_the_stacked_pass_in_two_chunks():
    """The cell's decode batch: 32 sequences x 16 query rows a K/V head are 512
    stacked rows a tile, taken 256 at a time (``STACKED_ROWS``) so that the
    rows' own walks keep blocks of 512 tokens; half the rows share a head of 3
    pages, every fourth has no token."""
    from finchat_tpu.ops.paged_attention import STACKED_ROWS

    n_kv, group, B = 2, 16, 32
    assert B * group == 2 * STACKED_ROWS
    contexts = [0 if b % 4 == 3 else 3 * PS + 1 + 5 * b for b in range(B)]
    sharing = tuple(b for b in range(1, B) if b % 2 == 0 and contexts[b])
    k_pages, v_pages, table, k_dense, v_dense = _pools(contexts, n_kv, 16, shared=3,
                                                       sharing=sharing, seed=9)
    kv_len = jnp.asarray(contexts, jnp.int32)
    q = _queries(B, 1, n_kv * group, seed=10)
    member, head = shared_head(table, kv_len, PS, kv_len > 0)
    assert int(head[0]) == 3 and int(member.sum()) == len(sharing) + 1
    want = mha_reference(q, k_dense, v_dense, q_offset=jnp.maximum(kv_len - 1, 0), kv_len=kv_len)
    got = paged_flash_attention(q, k_pages, v_pages, table, jnp.maximum(kv_len - 1, 0), kv_len,
                                jnp.asarray([LAYER]), page_size=PS, n_kv=n_kv,
                                interpret=INTERPRET)
    live = np.asarray(kv_len) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], atol=ATOL, rtol=RTOL)
    np.testing.assert_array_equal(np.asarray(got)[~live], 0.0)
