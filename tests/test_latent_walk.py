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
HEAD      the two forms a call takes the shared head in (PR 56) have a file of
          their own, ``tests/test_latent_walk_head.py`` (a file is one worker's
          work under the driver's ``--dist loadfile``)
PACKED    ``packed_attention``'s one-token rows through the same form
ENGINE    the decode step on the kernel backend; the counter's ``form``
INDEX     the indexer's scores by the same walk (PR 43): ``paged_index_scores``
          against ``index_scores`` below each row's length, the selection it
          leads to against the ``ref`` form's
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

def _ragged_round():
    """row 0: one token at position 99; row 1: a chunk of 6 from 40; row 2: one at 60; row 3: none"""
    rows, keys = _pool(seed=8)
    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], (16, HEADS, LATENT + ROPE))
    iq, iw = jax.random.normal(ks[1], (16, HEADS, DI)), jax.random.normal(ks[2], (16, HEADS))
    table = jnp.concatenate([TABLE, jnp.zeros((1, 8), jnp.int32)])
    return (q, iq, iw, rows, keys, jnp.int32(1), table, jnp.asarray([0, 1, 7, 8]),
            jnp.asarray([99, 40, 60, 0]), jnp.asarray([1, 6, 1, 0]))


def test_a_ragged_rounds_one_token_rows_take_the_walk_and_its_chunk_rows_the_mask_form():
    args = _ragged_round()
    got, selected = la.packed_attention(*args, width=8, backend="pallas-interpret", **KW)
    want, n = la.packed_attention(*args, width=8, **KW)
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
    index = "finchat_dsa_index_calls_total"
    walked, staged = (METRICS.get(index, labels={"form": f}) for f in ("walk", "staged"))
    steps = METRICS.get("finchat_dsa_row_layer_steps_total")
    [(_handle, tokens)] = _run(sched, _tokens(40, seed=4), n_new=4)
    steps = METRICS.get("finchat_dsa_row_layer_steps_total") - steps  # one live row
    assert len(tokens) == 4 and steps >= 3 * 3
    assert METRICS.get(name, labels={"form": form}) - before[form] == steps
    assert METRICS.get(name, labels={"form": other}) == before[other]
    # the indexer walks on a kernel backend whatever the table's width; ``staged`` does not move
    assert sched.engine.index_form == "walk"
    assert METRICS.get(index, labels={"form": "walk"}) - walked == steps
    assert METRICS.get(index, labels={"form": "staged"}) == staged


# --- INDEX -----------------------------------------------------------------------

EPS = 2.0 ** -23  # a float32 sum's relative rounding a term


def _index_inputs(seed, rows=3, heads=HEADS, width=DI, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (rows, heads, width)).astype(dtype),
            jax.random.normal(ks[1], (rows, heads)),
            jax.random.normal(ks[2], (2, 20, PAGE, width)).astype(dtype))


def _staged_scores(iq, iw, keys, table, layer=1):
    staged = keys[layer][table].reshape(len(table), -1, keys.shape[-1])
    return la.index_scores(iq[:, None], iw[:, None], staged)[:, 0], staged


def _rounding(iq, iw, staged):
    """How far two float32 evaluations of ``sum_h w ReLU(q . k)`` may lie apart
    for a summation order's sake: each of the ``heads + width`` additions a
    score goes through rounds by at most ``EPS`` of what it has summed, and
    that is at most the sum of the terms' magnitudes (the stored values'
    products are exact in float32 where they are bf16, and rounded alike in
    both where they are float32) — the largest over the tokens."""
    heads, width = iq.shape[1:]
    size = jnp.einsum("bhd,bjd,bh->bj", *(jnp.abs(a.astype(jnp.float32))
                                            for a in (iq, staged, iw)))
    return float((heads + width) * EPS * size.max())


def _walk_scores(iq, iw, keys, table, kv_len, layer=1, **kw):
    return pa.paged_index_scores(iq, iw, keys, table, jnp.asarray(kv_len, jnp.int32),
                                 jnp.asarray([layer]), page_size=PAGE, interpret=True, **kw)


def _below(kv_len, J=128):
    return jnp.arange(J)[None] < jnp.asarray(kv_len)[:, None]


@pytest.mark.parametrize("heads, width, dtype", [(HEADS, DI, jnp.float32), (64, 128, jnp.bfloat16)],
                         ids=["4x16-float32", "64x128-bf16"])
@pytest.mark.parametrize("kv_len", [
    [100, 112, 70],  # rows that end in mid-page, and (blocks of two pages) in mid-block
    [97, 33, 17],  # a last page of ONE token; rows shorter than the members' shared head + 1
    [128, 96, 64],  # whole pages, whole blocks, a full table
    [5, 31, 1],  # no page whole: no head is shared
], ids=["ragged", "one-token-page", "whole", "short"])
def test_the_index_walk_scores_every_token_below_a_rows_length_as_index_scores_does(
        kv_len, heads, width, dtype, monkeypatch):
    """At the tests' width and at the cell's (64 index heads over keys of 128,
    bf16): the walk's float32 head sum may associate otherwise than XLA's, no
    more (``_rounding``; the values themselves differ by a few 1e-6 of
    scores up to about 30)."""
    monkeypatch.setattr(pa, "INDEX_BLOCK_TOKENS", 2 * PAGE)
    jax.clear_caches()
    iq, iw, keys = _index_inputs(11, heads=heads, width=width, dtype=dtype)
    want, staged = _staged_scores(iq, iw, keys, TABLE)
    got = _walk_scores(iq, iw, keys, TABLE, kv_len)
    jax.clear_caches()
    assert got.shape == want.shape == (3, 128) and got.dtype == jnp.float32
    far = jnp.abs(jnp.where(_below(kv_len), got - want, 0)).max()
    assert far <= _rounding(iq, iw, staged) < 1e-3 * jnp.abs(want).max()


def test_the_index_walk_scores_a_shared_head_once_and_a_row_outside_it_from_column_0():
    """Rows 0 and 1 share two pages (the stacked pass writes EVERY row's
    scores of those columns, row 2's too: its own walk from column 0 puts its
    own keys' there); handed the head or finding it, the same scores; with a
    member as far as the head and no further, and a row outside that is
    shorter than the head."""
    iq, iw, keys = _index_inputs(12)
    want, staged = _staged_scores(iq, iw, keys, TABLE)
    tol = _rounding(iq, iw, staged)
    for kv_len in ([100, 112, 70], [32, 112, 20], [100, 112, 0]):
        kv = jnp.asarray(kv_len)
        member, head = pa.shared_head(TABLE, kv, PAGE, kv > 0)
        assert [int(m) for m in member] == [1, 1, 0] and [int(h) for h in head] == [2, 0]
        got = _walk_scores(iq, iw, keys, TABLE, kv_len)
        assert jnp.abs(jnp.where(_below(kv_len), got - want, 0)).max() <= tol
        handed = _walk_scores(iq, iw, keys, TABLE, kv_len, shared=(member, head))
        assert (jnp.where(_below(kv_len), handed - got, 0) == 0).all()


def test_the_index_walk_of_one_row_alone_has_no_stacked_pass():
    iq, iw, keys = _index_inputs(13, rows=1)
    want, staged = _staged_scores(iq, iw, keys, TABLE[:1])
    got = _walk_scores(iq, iw, keys, TABLE[:1], [90])
    assert jnp.abs(jnp.where(_below([90]), got - want, 0)).max() <= _rounding(iq, iw, staged)


@pytest.mark.parametrize("kv_len", [[100, 0, 70], [0, 112, 70], [0, 0, 0]])
def test_a_dead_row_among_live_ones_is_not_walked_and_the_others_score_as_they_did(kv_len):
    iq, iw, keys = _index_inputs(14)
    want, staged = _staged_scores(iq, iw, keys, TABLE)
    got = _walk_scores(iq, iw, keys, TABLE, kv_len)
    assert jnp.abs(jnp.where(_below(kv_len), got - want, 0)).max() <= _rounding(iq, iw, staged)


@pytest.mark.parametrize("tile_bytes", [1 << 20, HEADS * 2 * 4], ids=["one-tile", "a-tile-a-row"])
def test_index_blocks_of_several_sizes_give_the_same_scores(tile_bytes, monkeypatch):
    """One page a block, two, three (which does not divide the table) and the
    whole table in one; the stacked pass in one tile of all rows, and in a
    tile a sequence. A token's score does not depend on its block."""
    monkeypatch.setattr(pa, "LATENT_TILE_BYTES", tile_bytes)
    iq, iw, keys = _index_inputs(15)
    table = TABLE.at[2, :3].set(jnp.asarray([3, 5, 7]))  # row 2 a member too, as far as row 0
    kv_len = [100, 112, 77]
    want, staged = _staged_scores(iq, iw, keys, table)
    seen = []
    for block_tokens in (PAGE, 2 * PAGE, 3 * PAGE, 8 * PAGE):
        monkeypatch.setattr(pa, "INDEX_BLOCK_TOKENS", block_tokens)
        jax.clear_caches()
        seen.append(jnp.where(_below(kv_len), _walk_scores(iq, iw, keys, table, kv_len), 0))
    jax.clear_caches()
    tol = _rounding(iq, iw, staged)
    assert all(jnp.abs(got - jnp.where(_below(kv_len), want, 0)).max() <= tol for got in seen)
    assert all(jnp.abs(got - seen[0]).max() <= tol for got in seen[1:])


@pytest.mark.parametrize("poison", [jnp.nan, jnp.inf, -jnp.inf, 1e30])
def test_what_the_walk_leaves_beyond_a_rows_length_never_reaches_the_selection(
        poison, monkeypatch):
    """The walk fills nothing beyond a row's last live page (and nothing of a
    dead row): whatever stands there — here NaN, infinities, a huge score
    written over the tail of the walk's output — the selection and the
    attention over it are those of the ``ref`` form."""
    walk = pa.paged_index_scores

    def poisoned(idx_q, idx_w, pages, table, kv_len, *args, **kw):
        scores = walk(idx_q, idx_w, pages, table, kv_len, *args, **kw)
        return jnp.where(_below(kv_len, scores.shape[1]), scores, poison)

    monkeypatch.setattr(pa, "paged_index_scores", poisoned)
    rows, keys = _pool(seed=9)
    q, iq, iw = _queries(seed=10)
    (got, selected), (want, n) = _both_forms(q, iq, iw, rows, keys, TABLE, [100, 112, 33],
                                             [True, False, True])
    assert int(selected) == int(n) == 2 * 24 and jnp.abs(got - want).max() < 1e-5
    assert not got[1].any()


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_the_walks_scores_select_the_tokens_the_ref_forms_scores_select(seed):
    """The SAME 24 tokens a row, where no score lies within the rounding of
    the k-th (none does at these seeds: the gap at the cut is a thousand
    roundings and more); then the whole call, on both backends."""
    rows, keys = _pool(seed=seed)
    q, iq, iw = _queries(seed=20 + seed)
    kv_len = [100, 112, 80]
    want, staged = _staged_scores(iq, iw, keys, TABLE)
    got = _walk_scores(iq, iw, keys, TABLE, kv_len)
    allowed = _below(kv_len)
    ranked = jnp.sort(jnp.where(allowed, want, -jnp.inf), axis=-1)
    assert (ranked[:, -24] - ranked[:, -25]).min() > 2 * _rounding(iq, iw, staged)
    kept = la.select(got, allowed, 24)
    assert (kept == la.select(want, allowed, 24)).all() and int(kept.sum()) == 3 * 24
    (out, selected), (ref, n) = _both_forms(q, iq, iw, rows, keys, TABLE, kv_len)
    assert int(selected) == int(n) == 3 * 24 and jnp.abs(out - ref).max() < 1e-5


@pytest.mark.parametrize("backend, packed, form", [
    ("ref", False, "staged"), ("pallas", False, "walk"), ("pallas-interpret", False, "walk"),
    ("pallas", True, "staged"), ("ref", True, "staged")])
def test_the_indexers_form_is_read_off_the_backend_and_the_rows_packing(backend, packed, form):
    assert la.index_form(backend, packed) == form


def test_a_ragged_rounds_one_token_rows_keep_the_staged_indexer(monkeypatch):
    """``packed_attention`` (and the chunk form beside it) computes what it
    computed: on a kernel backend its one-token rows walk their LATENT pages
    and stage their index keys."""
    def refuse(*args, **kw):
        raise AssertionError("a ragged round's rows took the indexer's walk")

    monkeypatch.setattr(pa, "paged_index_scores", refuse)
    args = _ragged_round()
    got, selected = la.packed_attention(*args, width=8, backend="pallas-interpret", **KW)
    want, n = la.packed_attention(*args, width=8, **KW)
    assert int(selected) == int(n) and jnp.abs(got - want)[:8].max() < 1e-5
    q, iq, iw, rows, keys = args[:5]
    with pytest.raises(AssertionError, match="took the indexer's walk"):
        _both_forms(q[:3], iq[:3], iw[:3], rows, keys, TABLE, [100, 112, 80])
