"""A sliding-window layer's one-token walk (ops/paged_attention.py at ``window``
> 0, C = 1): the row's bounded page list in the fewest blocks that fit — ONE
block at a table of 6 columns, two of 9 pages at 18 — chained across the rows,
with no stacked shared-head pass traced; against ``mha_reference`` under the
same window, in interpret mode on ``paged_walk_cases``' poisoned pools (a dead
page read is NaN, not a slower test).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finchat_tpu.ops import paged_attention as pa
from finchat_tpu.ops.refs import mha_reference
from tests.paged_walk_cases import (
    ATOL,
    INTERPRET,
    PAGE_SIZE,
    RTOL,
    assert_matches_reference,
    pallas_eqn,
    walk_case,
)

P = PAGE_SIZE
# name: (table columns, contexts in the table's compacted coordinates); the window is what a
# table of that width serves (``window_pages_per_row``: window / page + 2 columns)
CASES = {
    # Phi-4-flash's table: 5 of 6 pages live, a row on all 6, one token, a row ending on a page
    "5_of_6": (6, [4 * P + 37, 5 * P + 3, 1, 4 * P + 1, 5 * P]),
    # Trinity-Mini's: 17 of 18 (two blocks, the second short a page), the whole table
    "17_of_18": (18, [16 * P + 9, 17 * P + 60, 16 * P + 1, 18 * P, 40]),
    # a row on a page boundary: 16 whole pages, and one token into the 17th
    "16_of_18": (18, [16 * P, 16 * P + 1, 15 * P + 63, 9 * P, 9 * P + 1]),
    # dead rows (no token, a table row of zeros) first, between and last
    "dead_rows": (6, [0, 4 * P + 5, 0, 5 * P + 11, 0]),
}


def _window(width):
    return (width - 2) * P


@pytest.fixture
def small_buffers(monkeypatch):
    """At the tests' float32 rows of 64 columns a table of 18 pages of 64 is 1.1 MiB of
    K and V blocks: a budget of 1 MiB cuts it in two as 8 MiB cuts Trinity-Mini's 9 MiB."""
    monkeypatch.setattr(pa, "KV_BUFFER_BYTES", 1 << 20)


@pytest.mark.parametrize("group", [4, 8], ids=["4_a_kv_head", "8_a_kv_head"])
@pytest.mark.parametrize("name", list(CASES))
def test_a_window_walk_equals_the_reference_under_the_same_window(name, group, small_buffers):
    width, contexts = CASES[name]
    q, sources, table, q_offset, kv_len, layer, k_dense, v_dense = walk_case(
        group, 1, contexts=contexts, width=width, pool=2 + 5 * 18,
        heads=[((), 0)] if 0 in contexts else ())
    B = len(contexts)
    out = pa.paged_flash_attention(
        q, *sources, table, q_offset, kv_len, layer,
        (jnp.zeros((B,), jnp.int32), jnp.zeros((2,), jnp.int32)),  # the engine's: no page
        page_size=P, n_kv=2, window=_window(width), interpret=INTERPRET)
    ref = mha_reference(q, k_dense, v_dense, causal=True, q_offset=q_offset, kv_len=kv_len,
                        window=_window(width))
    assert_matches_reference(out, ref, contexts, ATOL, RTOL)
    # the window binds: without it the rows past it read otherwise
    if max(contexts) > _window(width) + 1:
        free = mha_reference(q, k_dense, v_dense, causal=True, q_offset=q_offset, kv_len=kv_len)
        assert not np.allclose(np.asarray(ref), np.asarray(free), atol=1e-3)


@pytest.mark.parametrize("width, blocks", [(6, 1), (18, 2)])
def test_a_window_call_traces_no_stacked_pass_and_the_fewest_blocks(width, blocks, small_buffers):
    """No stacked queries, no second m / l / acc: the scratch is a row's own
    state, the chain's slot word, and K and V buffers of ceil(width / blocks)
    pages a slot."""
    q, sources, table, q_offset, kv_len, layer, *_ = walk_case(
        4, 1, contexts=[3 * P, 2 * P + 1], width=width)
    jaxpr = jax.make_jaxpr(lambda *a: pa.paged_flash_attention(
        *a, page_size=P, n_kv=2, window=_window(width), interpret=True))(
        q, *sources, table, q_offset, kv_len, layer)
    eqn = pallas_eqn(jaxpr.jaxpr)
    mapping = eqn.params["grid_mapping"]
    assert mapping.num_index_operands == 4  # layer, table, q_offset, kv_len: no member, no head
    assert mapping.num_inputs == 1 + 2  # the query block, K, V: no stacked queries
    scratch = [v.aval.shape for v in eqn.params["jaxpr"].invars[-mapping.num_scratch_operands:]]
    pages = -(-width // blocks)
    assert scratch == [(8, 128), (8, 128), (8, 32), (1,),
                       (2, pages, P, 64), (2, pages, P, 64), (2, 2)]


# the eight accepted decode shapes' calls (rows, query heads, KV heads, table columns, window):
# six as they were, the two window calls their table in one and two blocks
@pytest.mark.parametrize("rows, heads, n_kv, columns, window, pages", [
    (16, 32, 8, 128, 0, 4),  # Mixtral, Mistral, Granite
    (16, 20, 4, 128, 0, 4),  # Falcon-H1
    (16, 30, 30, 128, 0, 2),  # Olmo-Hybrid
    (32, 40, 10, 128, 0, 4),  # Phi-4-flash's full layer and its seven cross layers
    (32, 32, 4, 128, 0, 4),  # Trinity-Mini's full layer
    (32, 40, 10, 6, 512, 6),  # Phi-4-flash's window layers: ONE block a row
    (32, 32, 4, 18, 2048, 9),  # Trinity-Mini's: two
], ids=["32x8", "20x4", "30x30", "phi4-full", "trinity-full", "phi4-window", "trinity-window"])
def test_pages_per_block_of_the_accepted_decode_shapes(rows, heads, n_kv, columns, window, pages):
    def struct(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype)

    pool = struct(1, 8, 128, n_kv * 128)
    jaxpr = jax.make_jaxpr(lambda *a: pa.paged_flash_attention(
        *a, page_size=128, n_kv=n_kv, window=window, interpret=True))(
        struct(rows, 1, heads, 128), pool, pool, struct(rows, columns, dtype=jnp.int32),
        struct(rows, dtype=jnp.int32), struct(rows, dtype=jnp.int32), struct(1, dtype=jnp.int32))
    eqn = pallas_eqn(jaxpr.jaxpr)
    k_buffer = eqn.params["jaxpr"].invars[-3].aval.shape
    assert k_buffer == (2, pages, 128, n_kv * 128)


def test_the_latent_and_index_blocks_are_what_they_were():
    """DeepSeek's two walks size their blocks themselves (1,024 and 2,048
    tokens): the eighth accepted decode shape."""
    assert (pa.LATENT_BLOCK_TOKENS // 128, pa.INDEX_BLOCK_TOKENS // 128) == (8, 16)
