"""The one-token latent attention's WALK form (PR 41): ``ops/paged_attention.py``
``paged_latent_attention`` — the paged kernel's walk over the row's pages under
the selection's mask — in interpret mode, against ``attend_reference`` over the
gather form's rows, at a size a test holds (4 heads over a latent of 32 + 8 in
rows of 128, 4 index heads of 16, pages of 16).

FORM      which form a call takes is read off the call (``decode_form``)
CONTEXTS  under, at and over ``index_topk``; a partly filled last page
TIES      exact ties at the k-th score: the tokens the stable sort keeps
SHARED    a shared head with a row that is no member; a dead row
BLOCKS    several blocks a walk, several tiles of stacked rows
PACKED    ``packed_attention``'s one-token rows through the same form
ENGINE    the decode step on the kernel backend; the counter's ``form``
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finchat_tpu.ops import latent_attention as la
from finchat_tpu.ops import paged_attention as pa
from finchat_tpu.utils.metrics import METRICS

PAGE, HEADS, LATENT, ROPE, ROW, DI = 16, 4, 32, 8, 128, 16
SHAPE = la.LatentShape(LATENT, 24, 0.2)
KW = dict(page_size=PAGE, shape=SHAPE)
# rows 0 and 1 hold the same two pages at the head of their tables; row 2 none
TABLE = jnp.asarray([[3, 5, 7, 2, 9, 11, 17, 0], [3, 5, 6, 8, 10, 12, 13, 0],
                     [14, 15, 16, 18, 19, 0, 0, 0]], jnp.int32)


def _pool(seed=0, layers=2, pages=20):
    ks = jax.random.split(jax.random.key(seed), 2)
    rows = jax.random.normal(ks[0], (layers, pages, PAGE, ROW), jnp.float32)
    return rows.at[..., LATENT + ROPE:].set(0), jax.random.normal(ks[1], (layers, pages, PAGE, DI))


def _queries(seed=3, rows=3):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (rows, HEADS, LATENT + ROPE)),
            jax.random.normal(ks[1], (rows, HEADS, DI)), jax.random.normal(ks[2], (rows, HEADS)))


def _both_forms(q, iq, iw, rows, keys, table, kv_len, live=None, shape=SHAPE, layer=1):
    live = jnp.ones((len(kv_len),), bool) if live is None else jnp.asarray(live)
    args = (q, iq, iw, rows, keys, jnp.int32(layer), table, jnp.asarray(kv_len), live)
    return (la.decode_attention(*args, page_size=PAGE, shape=shape, backend="pallas-interpret"),
            la.decode_attention(*args, page_size=PAGE, shape=shape))


def _dense(q, iq, iw, rows, keys, table, kv_len, live, shape=SHAPE, layer=1):
    """``attend_reference`` over each row's pages under ``select``'s mask."""
    J = table.shape[1] * PAGE
    flat = rows[layer][table].reshape(len(kv_len), J, ROW)
    scores = la.index_scores(iq[:, None], iw[:, None], keys[layer][table].reshape(len(kv_len), J, DI))
    allowed = (jnp.arange(J)[None] < jnp.asarray(kv_len)[:, None]) & jnp.asarray(live)[:, None]
    mask = la.select(scores, allowed[:, None], shape.topk)
    return la.attend_reference(q[:, None], flat, mask, shape)[:, 0]


# --- FORM ------------------------------------------------------------------------

@pytest.mark.parametrize("backend, context, topk, form", [
    ("ref", 16384, 2048, "gather"), ("pallas", 16384, 2048, "walk"),
    ("pallas-interpret", 128, 24, "walk"), ("pallas", 32768, 2048, "gather"),
    ("pallas", 16384, 0, "walk"), ("pallas", 1024, 2048, "walk"), ("ref", 128, 0, "gather"),
])
def test_the_form_is_read_off_the_backend_and_the_tables_width_in_selections(
        backend, context, topk, form):
    assert la.decode_form(backend, context, topk) == form
    assert la.WALK_MAX_CONTEXTS * 2048 >= 16384  # the benchmark's cell walks


# --- CONTEXTS --------------------------------------------------------------------

@pytest.mark.parametrize("kv_len", [
    [10, 23, 5],  # all under index_topk: dense attention over what is there
    [24, 25, 24],  # at it, and one past
    [78, 79, 40], [100, 112, 80],  # over it; 100 = 6 pages and a quarter
    [97, 33, 17],  # a last page that holds ONE token
], ids=["under", "at", "over", "over-far", "one-token-page"])
def test_the_walk_equals_the_gather_form_and_the_dense_reference(kv_len):
    rows, keys = _pool()
    q, iq, iw = _queries()
    (got, selected), (want, n) = _both_forms(q, iq, iw, rows, keys, TABLE, kv_len)
    assert int(selected) == int(n) == sum(min(24, n) for n in kv_len)
    assert jnp.abs(got - want).max() < 1e-5
    assert jnp.abs(got - _dense(q, iq, iw, rows, keys, TABLE, kv_len, [True] * 3)).max() < 1e-5


def test_without_an_indexer_the_walk_is_dense_attention_over_the_rows_tokens():
    rows, keys = _pool(seed=2)
    q, _iq, _iw = _queries(seed=5)
    shape = la.LatentShape(LATENT, 0, 0.2)
    kv = jnp.asarray([100, 47, 80])
    got, selected = la.decode_attention(q, None, None, rows, keys, jnp.int32(0), TABLE, kv,
                                        jnp.ones((3,), bool), page_size=PAGE, shape=shape,
                                        backend="pallas-interpret")
    flat = rows[0][TABLE].reshape(3, -1, ROW)
    want = la.attend_reference(q[:, None], flat, (jnp.arange(128)[None] < kv[:, None])[:, None],
                               shape)[:, 0]
    assert int(selected) == 227 and jnp.abs(got - want).max() < 1e-5


# --- TIES ------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_of_tokens_tied_at_the_kth_score_the_walk_keeps_what_the_stable_sort_keeps(seed):
    """Index queries and keys of small whole numbers under four heads: the
    ReLU zeroes all four often and equal sums abound, so the k-th score is
    shared by tokens on both sides of the cut. The latent rows are random: a
    different tied token kept would move the output by a tenth."""
    rows, _keys = _pool(seed=seed)
    ks = jax.random.split(jax.random.key(10 + seed), 3)
    keys = jnp.round(jax.random.normal(ks[0], (2, 20, PAGE, DI)))
    q, _iq, _iw = _queries(seed=seed)
    iq = jnp.round(jax.random.normal(ks[1], (3, HEADS, DI)))
    iw = jnp.abs(jnp.round(jax.random.normal(ks[2], (3, HEADS)) * 2)) / 2
    kv_len = [100, 112, 80]
    J = TABLE.shape[1] * PAGE
    scores = la.index_scores(iq[:, None], iw[:, None], keys[1][TABLE].reshape(3, J, DI))[:, 0]
    masked = jnp.where(jnp.arange(J)[None] < jnp.asarray(kv_len)[:, None], scores, -jnp.inf)
    kth = jnp.sort(masked, axis=-1)[:, -24]
    assert ((masked == kth[:, None]).sum(-1) > 1).any()  # the cut does fall among ties
    (got, selected), (want, _n) = _both_forms(q, iq, iw, rows, keys, TABLE, kv_len)
    assert int(selected) == 3 * 24 and jnp.abs(got - want).max() < 1e-5


# --- SHARED ----------------------------------------------------------------------

def test_a_shared_head_is_read_for_its_members_and_a_row_outside_it_walks_alone():
    rows, keys = _pool(seed=3)
    q, iq, iw = _queries(seed=4)
    kv_len = [100, 112, 70]
    member, head = pa.shared_head(TABLE, jnp.asarray(kv_len), PAGE, jnp.ones((3,), bool))
    assert [int(m) for m in member] == [1, 1, 0] and [int(h) for h in head] == [2, 0]
    (got, _s), (want, _n) = _both_forms(q, iq, iw, rows, keys, TABLE, kv_len)
    assert jnp.abs(got - want).max() < 1e-5
    # the head handed in (the decode step reads it once, outside the layers) is the same call
    args = (q, iq, iw, rows, keys, jnp.int32(1), TABLE, jnp.asarray(kv_len), jnp.ones((3,), bool))
    handed, _ = la.decode_attention(*args, backend="pallas-interpret", shared=(member, head), **KW)
    assert (handed == got).all()
    # no head at all (every row its own pages): the same program, every walk from column 0
    alone = TABLE.at[1, :2].set(jnp.asarray([1, 4]))
    (got, _s), (want, _n) = _both_forms(q, iq, iw, rows, keys, alone, kv_len)
    assert jnp.abs(got - want).max() < 1e-5


@pytest.mark.parametrize("live", [[True, False, True], [False, True, True], [False, False, False]])
def test_a_dead_row_gives_zeros_selects_nothing_and_leaves_the_others_as_they_were(live):
    rows, keys = _pool(seed=5)
    q, iq, iw = _queries(seed=6)
    kv_len = [100, 112, 33]
    (got, selected), (want, n) = _both_forms(q, iq, iw, rows, keys, TABLE, kv_len, live)
    assert int(selected) == int(n) == 24 * sum(live)
    assert jnp.abs(got - want).max() < 1e-5
    for b, alive in enumerate(live):
        assert alive or not got[b].any()


def test_one_row_alone_walks_without_the_stacked_pass():
    rows, keys = _pool(seed=6)
    q, iq, iw = _queries(seed=7, rows=1)
    (got, selected), (want, _n) = _both_forms(q, iq, iw, rows, keys, TABLE[:1], [90])
    assert int(selected) == 24 and jnp.abs(got - want).max() < 1e-5


# --- BLOCKS ----------------------------------------------------------------------

@pytest.mark.parametrize("block_tokens, tile_bytes", [(32, 1 << 19), (16, 1 << 19), (48, 1 << 19),
                                                      (128, 8 * 128 * 4), (32, 8 * 32 * 4)])
def test_blocks_of_any_size_and_tiles_of_stacked_rows_give_the_same_values(
        block_tokens, tile_bytes, monkeypatch):
    """Two pages a block (four blocks a walk and a partial last one), one
    page, three (which does not divide the table); a logit tile that holds
    ONE sequence's stacked rows, so the shared head's pass takes a tile a
    sequence, each under its own mask."""
    monkeypatch.setattr(pa, "LATENT_BLOCK_TOKENS", block_tokens)
    monkeypatch.setattr(pa, "LATENT_TILE_BYTES", tile_bytes)
    jax.clear_caches()
    rows, keys = _pool(seed=7)
    q, iq, iw = _queries(seed=8)
    table = TABLE.at[2, :3].set(jnp.asarray([3, 5, 7]))  # row 2 a member too, as far as row 0
    kv_len = [100, 112, 77]
    assert [int(h) for h in pa.shared_head(table, jnp.asarray(kv_len), PAGE)[1]] == [2, 0]
    (got, _s), (want, _n) = _both_forms(q, iq, iw, rows, keys, table, kv_len)
    jax.clear_caches()
    assert jnp.abs(got - want).max() < 1e-5


# --- PACKED ----------------------------------------------------------------------

def test_a_ragged_rounds_one_token_rows_take_the_walk_and_its_chunk_rows_the_mask_form():
    rows, keys = _pool(seed=8)
    T, width = 16, 8
    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], (T, HEADS, LATENT + ROPE))
    iq, iw = jax.random.normal(ks[1], (T, HEADS, DI)), jax.random.normal(ks[2], (T, HEADS))
    # row 0: one token at position 99; row 1: a chunk of 6 from 40; row 2: one at 60; row 3: none
    table = jnp.concatenate([TABLE, jnp.zeros((1, 8), jnp.int32)])
    q_start, start = jnp.asarray([0, 1, 7, 8]), jnp.asarray([99, 40, 60, 0])
    n_valid = jnp.asarray([1, 6, 1, 0])
    args = (q, iq, iw, rows, keys, jnp.int32(1), table, q_start, start, n_valid)
    got, selected = la.packed_attention(*args, width=width, backend="pallas-interpret", **KW)
    want, n = la.packed_attention(*args, width=width, **KW)
    assert int(selected) == int(n) == 24 * 8
    assert jnp.abs(got - want)[:8].max() < 1e-5


# --- ENGINE ----------------------------------------------------------------------

def _engine(backend, max_seq_len):
    from tests.test_deepseek_v32 import CONFIG, PARAMS
    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.utils.config import EngineConfig

    cfg = EngineConfig(max_seqs=4, page_size=PAGE, num_pages=64, max_seq_len=max_seq_len,
                       prefill_chunk=12)
    return InferenceEngine(CONFIG, PARAMS, cfg, attn_backend=backend)


def test_the_decode_step_on_the_kernel_backend_walks_and_equals_the_reference():
    """A table of 128 tokens is 5.3 selections of 24: the walk. 70 tokens
    prefilled, then decode across a page boundary against the float32
    reference, every context past ``index_topk``."""
    from tests.test_deepseek_v32 import TOL, _decode, _reference, _tokens

    engine = _engine("pallas-interpret", 128)
    assert engine.latent_form == "walk" and _engine("ref", 128).latent_form == "gather"
    tokens = _tokens(82)
    want = _reference(tokens, list(range(69, 82)))
    engine.set_page_table_row(1, list(range(1, 9)))
    assert np.abs(np.asarray(engine.prefill(1, tokens[:70])) - want[0]).max() < TOL
    for i, token in enumerate(tokens[70:]):
        assert np.abs(_decode(engine, {1: token})[1] - want[1 + i]).max() < TOL
    assert int(engine.moe_experts[2]) == 3 * 24


@pytest.mark.parametrize("max_seq_len, form", [(128, "walk"), (256, "gather")])
def test_the_scheduler_counts_a_layer_a_step_under_the_form_the_steps_took(max_seq_len, form):
    """A table of 256 tokens is 10.7 selections: past ``WALK_MAX_CONTEXTS``,
    the gather form, on the same backend. The other label does not move."""
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from tests.test_deepseek_v32 import _run, _tokens

    other = {"walk": "gather", "gather": "walk"}[form]
    name = "finchat_latent_attention_calls_total"
    sched = ContinuousBatchingScheduler(_engine("pallas-interpret", max_seq_len), eos_id=-1)
    before = {f: METRICS.get(name, labels={"form": f}) for f in (form, other)}
    steps = METRICS.get("finchat_dsa_row_layer_steps_total")
    [(_handle, tokens)] = _run(sched, _tokens(40, seed=4), n_new=4)
    steps = METRICS.get("finchat_dsa_row_layer_steps_total") - steps  # one live row
    assert len(tokens) == 4 and steps >= 3 * 3
    assert METRICS.get(name, labels={"form": form}) - before[form] == steps
    assert METRICS.get(name, labels={"form": other}) == before[other]
