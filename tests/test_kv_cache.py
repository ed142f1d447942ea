"""Page allocator invariants (SURVEY §5.2: a KV page never owned by two
sequences; double-free detection) and scatter/gather correctness."""

import jax.numpy as jnp
import pytest

from finchat_tpu.engine.kv_cache import (
    PageAllocationError,
    PageAllocator,
    gather_kv,
    pages_needed,
    scatter_kv_chunk,
)


def test_pages_needed():
    assert pages_needed(1, 8) == 1
    assert pages_needed(8, 8) == 1
    assert pages_needed(9, 8) == 2
    assert pages_needed(0, 8) == 1


def test_allocator_never_hands_out_trash_page():
    alloc = PageAllocator(8)
    pages = alloc.allocate("s", 7)
    assert 0 not in pages
    assert sorted(pages) == list(range(1, 8))


def test_allocator_exhaustion():
    alloc = PageAllocator(4)
    alloc.allocate("a", 3)
    assert not alloc.can_allocate(1)
    with pytest.raises(PageAllocationError):
        alloc.allocate("b", 1)


def test_double_free_raises():
    alloc = PageAllocator(8)
    pages = alloc.allocate("a", 2)
    alloc.free("a", pages)
    with pytest.raises(PageAllocationError):
        alloc.free("a", pages)


def test_foreign_free_raises():
    alloc = PageAllocator(8)
    pages = alloc.allocate("a", 2)
    with pytest.raises(PageAllocationError):
        alloc.free("b", pages)


def test_free_then_realloc_keeps_invariants():
    alloc = PageAllocator(16)
    a = alloc.allocate("a", 5)
    b = alloc.allocate("b", 5)
    alloc.free("a", a)
    c = alloc.allocate("c", 8)
    alloc.check_invariants()
    assert set(c).isdisjoint(b)


def test_scatter_gather_roundtrip():
    L, P, ps, Hkv, hd = 2, 6, 4, 2, 8
    k_pages = jnp.zeros((L, P, ps, Hkv * hd))
    v_pages = jnp.zeros((L, P, ps, Hkv * hd))
    B, C = 1, 6
    k_new = jnp.arange(B * C * Hkv * hd, dtype=jnp.float32).reshape(B, C, Hkv, hd)
    v_new = -k_new
    page_table = jnp.asarray([[2, 4, 0]], jnp.int32)  # logical pages 0,1 -> phys 2,4
    # write 6 tokens starting at absolute position 2 into layer 1: positions
    # 2,3 in page 2, positions 4..7 in page 4
    k_pages, v_pages = scatter_kv_chunk(
        k_pages, v_pages, k_new, v_new, page_table,
        start_pos=jnp.asarray([2]), n_valid=jnp.asarray([6]), page_size=ps,
        layer=jnp.int32(1),
    )
    k_all, v_all = gather_kv(k_pages, v_pages, page_table, ps, jnp.int32(1), Hkv)
    assert k_all.shape == (B, 3 * ps, Hkv, hd)
    # gathered positions 2..7 must equal the chunk in order
    assert jnp.array_equal(k_all[0, 2:8], k_new[0])
    assert jnp.array_equal(v_all[0, 2:8], v_new[0])
    # trash page (phys 0) is untouched territory for this row's logical page 2
    assert jnp.array_equal(k_all[0, 8:], jnp.zeros((ps, Hkv, hd)))
    # the other layer is untouched
    assert float(jnp.abs(k_pages[0]).sum()) == 0.0


def test_scatter_padding_goes_to_trash():
    L, P, ps, Hkv, hd = 1, 4, 4, 1, 2
    k_pages = jnp.zeros((L, P, ps, Hkv * hd))
    v_pages = jnp.zeros((L, P, ps, Hkv * hd))
    k_new = jnp.ones((1, 4, Hkv, hd))
    page_table = jnp.asarray([[1, 2]], jnp.int32)
    k_pages, v_pages = scatter_kv_chunk(
        k_pages, v_pages, k_new, k_new, page_table,
        start_pos=jnp.asarray([0]), n_valid=jnp.asarray([2]), page_size=ps,
        layer=jnp.int32(0),
    )
    # only 2 valid tokens written to page 1; padding went to trash page 0
    assert float(k_pages[0, 1, :2].sum()) == 2 * Hkv * hd
    assert float(k_pages[0, 1, 2:].sum()) == 0.0
    assert float(k_pages[0, 2].sum()) == 0.0


def test_page_hbm_bytes_matches_real_allocation():
    """page_hbm_bytes (the no-alloc sizing helper harnesses use to fit a
    KV pool to an HBM budget) must mirror PagedKVCache.create exactly,
    for both the native-dtype and int8 layouts."""
    from finchat_tpu.engine.kv_cache import PagedKVCache, page_hbm_bytes
    from finchat_tpu.models.llama import PRESETS

    config = PRESETS["mini"]
    for kv_quant in ("", "int8"):
        cache = PagedKVCache.create(config, num_pages=6, page_size=16,
                                    kv_quant=kv_quant)
        per_page = page_hbm_bytes(config, 16, kv_quant)
        expected = per_page * 6
        if not kv_quant:
            # the no-quant layout carries (1,1,1,1) scale placeholders
            expected += cache.k_scales.nbytes + cache.v_scales.nbytes
        assert cache.hbm_bytes() == expected


@pytest.mark.parametrize("n_pages", [1, 3, 4, 5])
def test_the_offloads_take_is_shaped_by_a_power_of_two_and_returns_the_pages_asked_for(n_pages):
    """``gather_pages_host`` pads its page count to a power of two (the trash
    page repeated) so that the eager take meets a handful of shapes, and hands
    back exactly the pages asked for, in their order, as arrays of their own."""
    import numpy as np

    from finchat_tpu.engine.kv_cache import gather_bucket, gather_pages_host

    assert [gather_bucket(n) for n in (1, 2, 3, 4, 5, 64, 65, 128)] == [1, 2, 4, 4, 8, 64, 128, 128]
    k = jnp.arange(2 * 9 * 4 * 6, dtype=jnp.float32).reshape(2, 9, 4, 6)
    v = -jnp.arange(2 * 9 * 4 * 3, dtype=jnp.float32).reshape(2, 9, 4, 3)  # another width
    scales = jnp.zeros((1, 1, 1, 1), jnp.float32)
    ids = [7, 2, 5, 1, 8][:n_pages]
    got_k, got_v, ks, vs = gather_pages_host(k, v, scales, scales, ids)
    assert ks is None and vs is None
    assert np.array_equal(got_k, np.asarray(k)[:, ids]) and got_k.shape == (2, n_pages, 4, 6)
    assert np.array_equal(got_v, np.asarray(v)[:, ids]) and got_v.shape == (2, n_pages, 4, 3)
    held = got_k if got_k.base is None else got_k.base  # what the snapshot keeps alive
    assert got_k.flags["C_CONTIGUOUS"] and held.nbytes == got_k.nbytes  # not the padded buffer
