"""The one-token append (ops/kv_append.py): a row's SLAB — the packed tile that
holds its token, 16 rows of bfloat16, 8 of float32 — read, patched and written
for all rows at once by one program; in interpret mode against a plain scatter,
BIT for bit over the whole pool.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finchat_tpu.ops.kv_append import TRASH_PAGE, paged_kv_append, slab_rows

INTERPRET = jax.default_backend() != "tpu"
L, LAYER, COLUMNS = 3, 1, 3  # the pool's depth, the layer written, a row's table columns


def _case(dtype, page_size, widths, pos, n_valid, seed=0):
    """A pool of two arrays ``[L, 1 + B * COLUMNS, page_size, width]`` of random
    values, each row's table its own pages, and what a scatter of the VALID
    rows' tokens leaves of it (numpy, the bits of ``dtype``)."""
    rng = np.random.RandomState(seed)
    B = len(pos)
    n_phys = 1 + B * COLUMNS
    pools = [jnp.asarray(rng.randn(L, n_phys, page_size, w), dtype) for w in widths]
    table = rng.permutation(np.arange(1, n_phys)).reshape(B, COLUMNS).astype(np.int32)
    new = jnp.asarray(rng.randn(B, 1, sum(widths)), dtype)
    want = [np.array(p) for p in pools]
    for b in range(B):
        if n_valid[b]:
            page, off = table[b, pos[b] // page_size], pos[b] % page_size
            want[0][LAYER, page, off] = np.asarray(new[b, 0, :widths[0]])
            want[1][LAYER, page, off] = np.asarray(new[b, 0, widths[0]:])
    return pools, jnp.asarray(table), new, want


def _append(pools, table, new, pos, n_valid, page_size):
    got = paged_kv_append(new, *pools, table, jnp.asarray(pos, jnp.int32),
                          jnp.asarray(n_valid, jnp.int32), jnp.asarray([LAYER], jnp.int32),
                          page_size=page_size, interpret=INTERPRET)
    return [np.array(g) for g in got]


def _bits(x):
    return x.view(np.uint16 if x.dtype.itemsize == 2 else np.uint32)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "float32"])
@pytest.mark.parametrize("offset", [0, 15, 16, 127, 128], ids=lambda o: f"at_{o}")
def test_the_append_equals_a_scatter_bit_for_bit_over_the_whole_pool(offset, dtype):
    """Every row valid, row 0 at the offset (128: a page's first token, the
    table's second column), the others spread over slabs and pages."""
    pos = [offset, 37, 2 * 128 + 77, 128 + 16]
    pools, table, new, want = _case(dtype, 128, (256, 256), pos, [1] * 4)
    got = _append(pools, table, new, pos, [1] * 4, 128)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "float32"])
def test_an_inactive_row_writes_the_trash_page_and_nothing_else(dtype):
    """Rows 1 and 3 are inactive (row 3 at a position past its table, as a
    trash-redirected verify position at the length limit): the pool is the
    scatter of rows 0 and 2 but for the trash page's two token rows."""
    page_size, pos, n_valid = 128, [130, 5, 383, COLUMNS * 128 + 9], [1, 0, 1, 0]
    pools, table, new, want = _case(dtype, page_size, (256, 256), pos, n_valid)
    got = _append(pools, table, new, pos, n_valid, page_size)
    touched = np.zeros((L, pools[0].shape[1], page_size), bool)
    touched[LAYER, TRASH_PAGE, [5, 9]] = True
    for g, w in zip(got, want):
        same = (_bits(g) == _bits(w)).all(axis=-1)
        assert same[~touched].all()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "float32"])
def test_two_widths_a_latent_row_beside_an_index_key(dtype):
    pos, n_valid = [0, 127, 200, 31, 16], [1, 1, 1, 0, 1]
    pools, table, new, want = _case(dtype, 128, (640, 128), pos, n_valid)
    got = _append(pools, table, new, pos, n_valid, 128)
    for g, w in zip(got, want):  # (every page but the trash page)
        np.testing.assert_array_equal(_bits(g)[:, 1:], _bits(w)[:, 1:])


@pytest.mark.parametrize("dtype, page_size", [(jnp.bfloat16, 8), (jnp.float32, 4),
                                              (jnp.bfloat16, 16), (jnp.float32, 16)],
                         ids=["bf16_page_8", "float32_page_4", "bf16_page_16", "float32_page_16"])
def test_a_page_smaller_than_a_slab_moves_whole(dtype, page_size):
    """The slab is the packed tile or the page, whichever is smaller."""
    slab = slab_rows(page_size, jnp.dtype(dtype).itemsize)
    assert slab == min(page_size, 16 if dtype == jnp.bfloat16 else 8)
    pos = [0, page_size - 1, page_size, 3 * page_size - 1]
    pools, table, new, want = _case(dtype, page_size, (128, 128), pos, [1] * 4)
    got = _append(pools, table, new, pos, [1] * 4, page_size)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("C", [2, 3])
def test_a_chunk_through_inplace_append_equals_the_scatter(C):
    """The speculative verify step's path (``inplace_append``): one append a
    chunk position, token ``i`` of a row valid iff ``i < n_valid``; the cache
    equals the prefill scatter's outside the trash page."""
    from finchat_tpu.engine.engine import _paged_attention_fn

    B, n_kv, hd, page_size = 3, 2, 32, 16
    rng = np.random.RandomState(3)
    n_phys = 1 + B * COLUMNS

    def cache():
        r = np.random.RandomState(4)
        return (jnp.asarray(r.randn(L, n_phys, page_size, n_kv * hd), jnp.float32),
                jnp.asarray(r.randn(L, n_phys, page_size, n_kv * hd), jnp.float32), None, None)

    table = jnp.asarray(np.arange(1, n_phys).reshape(B, COLUMNS), jnp.int32)
    start = jnp.asarray([14, 7, 30], jnp.int32)  # row 0's chunk crosses a page
    n_valid = jnp.asarray([C, 1, 0], jnp.int32)
    q = jnp.asarray(rng.randn(B, C, 2 * n_kv, hd), jnp.float32)
    k, v = (jnp.asarray(rng.randn(B, C, n_kv, hd), jnp.float32) for _ in range(2))
    got = {}
    for backend, inplace in (("pallas-interpret", True), ("ref", False)):
        attention = _paged_attention_fn(table, start, n_valid, page_size, n_kv, backend,
                                        inplace_append=inplace)
        got[backend] = attention(q, k, v, cache(), jnp.asarray(LAYER, jnp.int32))[1]
    for a, b in zip(got["pallas-interpret"][:2], got["ref"][:2]):
        np.testing.assert_array_equal(np.asarray(a)[:, 1:], np.asarray(b)[:, 1:])
