"""The two forms a walked latent call takes the batch's shared head in (PR 56;
``ops/paged_attention.py`` ``latent_head_form``): ``folded`` — the head's pages
resident in VMEM, the stacked pass's units dealt out over the rows' own walks,
the call's blocks through one ring — and ``stacked``, the pass as program 0's
prologue. In interpret mode at ``tests/test_latent_walk.py``'s size, each form
against every row walking alone (a call of one row: no batch, no head).

FORMS   members and a row outside the set; all members; a head of no page; a
        dead row; a member with no page of its own; with the log-sum-exp and
        without; blocks of one, two and three pages; a head longer than the
        resident buffer; one row alone
RULE    the form is read off the query rows and the row's width, nothing else
ENGINE  the engine's word, the dispatch annotation, the counter
"""

import jax
import jax.numpy as jnp
import pytest

from finchat_tpu.ops import latent_attention as la
from finchat_tpu.ops import paged_attention as pa
from finchat_tpu.utils.metrics import METRICS
from tests.test_latent_walk import HEADS, LATENT, PAGE, ROW, TABLE, _engine, _pool, _queries

# --- FORMS -----------------------------------------------------------------------

#: ``LATENT_MXU_SHARE`` that makes ``latent_head_form`` say each form whatever the shapes
FORMS = {"folded": 1e9, "stacked": 0.0}
SHARED_3 = TABLE.at[2, :3].set(jnp.asarray([3, 5, 7]))  # row 2 a member too, as far as row 0
NO_HEAD = TABLE.at[1, :2].set(jnp.asarray([1, 4]))  # every row on pages of its own


@pytest.fixture
def head_form(request, monkeypatch):
    """The form a walked call takes the head in, forced for the test: the rule
    reads ``LATENT_MXU_SHARE`` while a call is traced."""
    monkeypatch.setattr(pa, "LATENT_MXU_SHARE", FORMS[request.param])
    jax.clear_caches()
    assert pa.latent_head_form(3, HEADS, ROW, LATENT, 4) == request.param
    yield request.param
    jax.clear_caches()


def _walked(q, rows, table, kv_len, with_lse, layer=1):
    """``paged_latent_attention`` over every token below a row's length:
    ``(values, log-sum-exp or None)``."""
    kv = jnp.asarray(kv_len)
    keep = jnp.arange(table.shape[1] * PAGE)[None] < kv[:, None]
    got = pa.paged_latent_attention(
        la._pad_q(q, ROW), rows, keep, table, kv, jnp.asarray([layer]), None, page_size=PAGE,
        value_width=LATENT, scale=0.2, interpret=True, with_lse=with_lse)
    return got if with_lse else (got, None)


def _each_alone(q, rows, table, kv_len, with_lse):
    """Every row as a call of its own: no batch, no head, the walk from column 0."""
    alone = [_walked(q[b:b + 1], rows, table[b:b + 1], kv_len[b:b + 1], with_lse)
             for b in range(len(kv_len))]
    return (jnp.concatenate([a[0] for a in alone]),
            jnp.concatenate([a[1] for a in alone]) if with_lse else None)


def _same(got, want):
    values, lse = got
    assert jnp.abs(values - want[0]).max() < 1e-5
    assert lse is None or jnp.abs(lse - want[1]).max() < 1e-5


@pytest.mark.parametrize("with_lse", [False, True], ids=["values", "with-lse"])
@pytest.mark.parametrize("table, kv_len", [
    (TABLE, [100, 112, 70]),  # rows 0 and 1 on two shared pages, row 2 outside the set
    (SHARED_3, [100, 112, 77]),  # all three members
    (NO_HEAD, [100, 112, 70]),  # a head of no page: every walk from column 0
    (SHARED_3, [100, 0, 77]),  # a dead row between two members
    (SHARED_3, [32, 112, 40]),  # a member whose own walk holds NO page: the head is all it has
], ids=["members-and-an-outsider", "all-members", "no-head", "a-dead-row", "a-member-of-no-own-page"])
@pytest.mark.parametrize("head_form", list(FORMS), indirect=True)
def test_either_form_of_the_head_gives_what_every_row_walking_alone_gives(
        head_form, table, kv_len, with_lse):
    rows, _keys = _pool(seed=11)
    q, _iq, _iw = _queries(seed=12)
    got = _walked(q, rows, table, kv_len, with_lse)
    _same(got, _each_alone(q, rows, table, kv_len, with_lse))
    for b, n in enumerate(kv_len):
        assert n or not got[0][b].any()


@pytest.mark.parametrize("block_tokens", [16, 32, 48])
@pytest.mark.parametrize("head_form", list(FORMS), indirect=True)
def test_either_form_of_the_head_at_blocks_of_several_sizes(head_form, block_tokens, monkeypatch):
    """One page a block (the head two blocks, a tile's rows two units each),
    two, three (a partial block of the head and of every row), with the
    log-sum-exp; the stacked rows ONE sequence a tile, so that a later tile's
    units ride the rows before it."""
    monkeypatch.setattr(pa, "LATENT_BLOCK_TOKENS", block_tokens)
    monkeypatch.setattr(pa, "LATENT_TILE_BYTES", 8 * block_tokens * 4)
    jax.clear_caches()
    rows, _keys = _pool(seed=13)
    q, _iq, _iw = _queries(seed=14)
    kv_len = [100, 112, 77]
    _same(_walked(q, rows, SHARED_3, kv_len, True), _each_alone(q, rows, SHARED_3, kv_len, True))


def test_a_head_longer_than_the_resident_buffer_is_folded_as_far_as_the_buffer_goes(monkeypatch):
    """``LATENT_HEAD_TOKENS`` of one page under a head of two: the buffer holds
    the first, the second is every member's own."""
    monkeypatch.setattr(pa, "LATENT_MXU_SHARE", FORMS["folded"])
    monkeypatch.setattr(pa, "LATENT_HEAD_TOKENS", PAGE)
    monkeypatch.setattr(pa, "LATENT_BLOCK_TOKENS", PAGE)
    jax.clear_caches()
    rows, _keys = _pool(seed=15)
    q, _iq, _iw = _queries(seed=16)
    kv_len = [100, 112, 77]
    _same(_walked(q, rows, SHARED_3, kv_len, True), _each_alone(q, rows, SHARED_3, kv_len, True))
    jax.clear_caches()


def test_one_row_alone_takes_no_head_in_either_form():
    assert pa.latent_head_form(1, 64, 640, 512, 2) == "none"
    rows, _keys = _pool(seed=17)
    q, _iq, _iw = _queries(seed=18, rows=1)
    values, _lse = _walked(q, rows, TABLE[:1], [90], True)
    want = la.attend_reference(q[:, None], rows[1][TABLE[:1]].reshape(1, -1, ROW),
                               (jnp.arange(128)[None] < 90)[:, None],
                               la.LatentShape(LATENT, 0, 0.2))[:, 0]
    assert jnp.abs(values - want).max() < 1e-5


# --- RULE ------------------------------------------------------------------------

@pytest.mark.parametrize("rows, heads, row_width, value_width, itemsize, form", [
    (32, 64, 640, 512, 2, "folded"),  # JoyAI-LLM-Flash: a token and its draft, 2 x 32 heads
    (32, 32, 640, 512, 2, "folded"),  # Kimi-Linear
    (16, 128, 640, 512, 2, "stacked"),  # DeepSeek-V3.2: the row's own walk is MXU-bound
    (2, 128, 640, 512, 2, "stacked"), (2, 64, 640, 512, 2, "folded"),  # whatever the rows, from 2
    (16, 128, 640, 512, 4, "folded"),  # twice the bytes a token: the copy is the longer again
    (16, 128, 1280, 128, 2, "folded"),  # a wide key under a narrow value: half the FLOP a byte
    (1, 64, 640, 512, 2, "none"), (1, 128, 640, 512, 2, "none"),
])
def test_the_heads_form_is_read_off_the_query_rows_and_the_rows_width_and_nothing_else(
        rows, heads, row_width, value_width, itemsize, form):
    import inspect

    assert pa.latent_head_form(rows, heads, row_width, value_width, itemsize) == form
    assert list(inspect.signature(pa.latent_head_form).parameters) == [
        "rows", "heads", "row_width", "value_width", "itemsize"]
    # the crossover: a block update at LATENT_MXU_SHARE of the peak as long as its copy
    at = pa.LATENT_MXU_SHARE * pa.RIDGE_FLOP_PER_BYTE * 640 * 2 / (2 * (640 + 512))
    assert 64 < at < 128 and pa.RIDGE_FLOP_PER_BYTE == 197e12 / 819e9


# --- ENGINE ----------------------------------------------------------------------

@pytest.mark.parametrize("max_seq_len, head_form", [(128, "folded"), (256, "none")])
def test_the_engine_says_the_heads_form_on_the_annotation_and_the_counter(max_seq_len, head_form):
    """The walk's rule at the test's widths (4 heads over float32 rows: far
    under the ridge) is ``folded``; the gather form takes no head. The word
    rides the decode dispatch's annotation beside ``prefix_rows`` and labels
    ``finchat_latent_head_walks_total``, a layer a delivered step."""
    from types import SimpleNamespace

    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from tests.test_deepseek_v32 import CONFIG, _run, _tokens

    engine = _engine("pallas-interpret", max_seq_len)
    assert engine.head_form == head_form
    if head_form != "none":
        assert engine.head_form == pa.latent_head_form(
            4, CONFIG.n_heads, CONFIG.latent_row, CONFIG.kv_lora_rank, 4)
    sched = ContinuousBatchingScheduler(engine, eos_id=-1)
    name, labels = "finchat_latent_head_walks_total", {"form": head_form}
    before, steps = METRICS.get(name, labels=labels), METRICS.get("finchat_dsa_row_layer_steps_total")
    _run(sched, _tokens(40, seed=5), n_new=3)
    steps = METRICS.get("finchat_dsa_row_layer_steps_total") - steps  # one live row
    assert steps > 0 and METRICS.get(name, labels=labels) - before == steps
    notes = []
    sched._phases = SimpleNamespace(note=lambda **numbers: notes.append(numbers))
    sched._trace_dispatch("decode", [(0, "t", "decode", None, 40)])
    assert notes[0]["head_form"] == head_form and "prefix_rows" in notes[0]
