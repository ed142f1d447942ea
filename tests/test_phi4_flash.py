"""``phi4flash`` (PR 42) on the program's block under a ``layer_plan``, at a
size a test holds: Mamba-1 layers, sliding-window layers on a bounded page list
of their own, ONE full-attention layer whose pages the cross layers read,
gated memory units, differential attention. Everything against the plain
reference of ``perfbench/models/phi4flash.py`` (float32, token by token, pair
by pair, no pages).

FORWARD  the cache-less forward; the pairing against four plain softmaxes; the
         memory a GMU reads; a malformed plan
SPLIT / RAGGED  prefill then decode and the packed round, past the window's
         edge, float32 tightly and bfloat16; the benchmark's own two paths
WINDOW   a row's page count stays at its bound and freed pages go back; a
         dropped page, a page kept too long and a mask off by one each fail
HEADS    a row admitted from a shared head: full pages, window pages, state
POOLS / REFUSED  the pools' depth by kind; what is refused at load
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import tiny_models

from finchat_tpu.engine.engine import InferenceEngine, window_pool_pages
from finchat_tpu.engine.kv_cache import (
    PageAllocationError,
    PagedKVCache,
    WindowPager,
    page_hbm_bytes,
    window_pages_per_row,
)
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
from finchat_tpu.models import sambay
from finchat_tpu.models.llama import (
    CROSS,
    FULL,
    GMU,
    MAMBA1,
    WINDOW,
    forward_full,
    init_params,
    n_params,
)
from finchat_tpu.utils.config import EngineConfig
from finchat_tpu.utils.metrics import METRICS
from perfbench.models import phi4flash

FILE = tiny_models.FILES["phi4_flash"]
CONFIG, PARAMS = tiny_models.build("phi4_flash")
PAGE, CHUNK, SLOTS, W = 4, 8, 4, 8
TOL = 2e-4  # float32 against float32; the logits' spread is about 0.5
BOUND = window_pages_per_row(W, PAGE)


def _tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 211, size=n)]


def _reference(tokens, positions, file=FILE, params=PARAMS, **kw):
    want, margins = phi4flash.reference_logits(params, tokens, file, positions=positions, **kw)
    assert np.isinf(np.asarray(margins)).all()  # nothing routes
    return np.asarray(want)


def _engine(attn_backend="ref", config=CONFIG, params=PARAMS, **options) -> InferenceEngine:
    cfg = EngineConfig(max_seqs=SLOTS, page_size=PAGE, num_pages=128, max_seq_len=256,
                       prefill_chunk=CHUNK, **options)
    return InferenceEngine(config, params, cfg, attn_backend=attn_backend)


def _decode(engine, slot_tokens: dict[int, int]) -> np.ndarray:
    active = np.zeros((SLOTS,), bool)
    for slot, token in slot_tokens.items():
        engine.set_last_token(slot, token)
        active[slot] = True
    _, logits = engine.decode(jnp.asarray(active), jnp.zeros((SLOTS,)), jnp.ones((SLOTS,)),
                              jnp.zeros((SLOTS,), jnp.int32), return_logits=True)
    return np.asarray(logits, np.float32)


def _split(engine, tokens, prompt_len, slot=2, pages=None):
    """``engine.prefill`` then a ``decode`` a token: the logits from the
    prompt's last position on."""
    engine.set_page_table_row(slot, pages or list(range(5, 5 + -(-len(tokens) // PAGE))))
    got = [np.asarray(engine.prefill(slot, tokens[:prompt_len]), np.float32)]
    return np.stack(got + [_decode(engine, {slot: t})[slot] for t in tokens[prompt_len:]])


# --- FORWARD ---------------------------------------------------------------------

def test_param_count_and_config():
    assert CONFIG.layer_plan == (((MAMBA1, WINDOW), 2), ((MAMBA1, FULL), 1), ((GMU, CROSS), 2))
    assert (CONFIG.n_heads, CONFIG.n_kv_heads, CONFIG.head_dim) == (8, 2, 16)  # the kernel's
    assert (CONFIG.n_attn_layers, CONFIG.n_window_layers, CONFIG.n_state_layers) == (1, 2, 3)
    assert CONFIG.state_shape == (1, 4, 128) and CONFIG.conv_shape == (3, 128)
    leaves = sum(x.size for x in jax.tree.leaves(PARAMS))
    assert leaves == n_params(CONFIG) == phi4flash.param_counts(FILE)["total"]
    layers = PARAMS["layers"]
    assert layers["attn_q"].shape[0] == 5 and layers["attn_k"].shape[0] == 3  # cross: W_q, W_o only
    assert layers["m1_in"].shape[0] == 3 and layers["gmu_in"].shape[0] == 2
    assert layers["m1_A_log"].shape == (3, 4, 128)  # [N, E]: the channels along the lanes


def test_the_forward_without_a_cache_equals_the_reference_past_the_windows_edge():
    tokens = _tokens(37, seed=1)
    got = forward_full(PARAMS, jnp.asarray(tokens)[None], jnp.arange(37)[None], config=CONFIG,
                       attn_backend="ref")[0]
    np.testing.assert_allclose(np.asarray(got), _reference(tokens, list(range(37))), atol=TOL)


def test_the_differential_pairing_equals_four_plain_softmaxes_a_key_pair():
    """One attention layer by hand: pair p's q1 and q2 against the key pair
    p // 2, one softmax each — four a key pair — subtracted, normed, scaled."""
    c, T = CONFIG, 11
    rng = np.random.RandomState(3)
    lp = {name: jnp.asarray(rng.normal(size=leaf.shape[1:]), jnp.float32) * 0.3
          for name, leaf in PARAMS["layers"].items() if name.startswith("attn_")}
    h = jnp.asarray(rng.normal(size=(1, T, c.dim)), jnp.float32)
    from finchat_tpu.models.llama import make_causal_attention

    got, _cache = sambay.attention(h, lp, c, make_causal_attention("ref", c.attention_scale, c),
                                   None, jnp.int32(0), FULL, 5)
    hd = 8
    x = np.asarray(h[0], np.float64)
    q = (x @ np.asarray(lp["attn_q"]) + np.asarray(lp["attn_q_b"])).reshape(T, 8, hd)
    k = (x @ np.asarray(lp["attn_k"]) + np.asarray(lp["attn_k_b"])).reshape(T, 4, hd)
    v = (x @ np.asarray(lp["attn_v"]) + np.asarray(lp["attn_v_b"])).reshape(T, 4, hd)
    causal = np.tril(np.ones((T, T), bool))

    def softmax(q_i, k_i):
        s = np.where(causal, q_i @ k_i.T / np.sqrt(hd), -np.inf)
        e = np.exp(s - s.max(-1, keepdims=True))
        return e / e.sum(-1, keepdims=True)

    lq1, lk1, lq2, lk2 = np.asarray(lp["attn_lam"], np.float64)
    lam_init = 0.8 - 0.6 * np.exp(-0.3 * 5)
    lam = np.exp(lq1 @ lk1) - np.exp(lq2 @ lk2) + lam_init
    outs = []
    for p in range(4):
        j = p // 2
        o = (softmax(q[:, 2 * p], k[:, 2 * j]) - lam * softmax(q[:, 2 * p + 1], k[:, 2 * j + 1])) \
            @ np.concatenate([v[:, 2 * j], v[:, 2 * j + 1]], axis=-1)
        o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-5) * np.asarray(lp["attn_subln"])
        outs.append(o * (1 - lam_init))
    want = np.concatenate(outs, axis=-1) @ np.asarray(lp["attn_o"]) + np.asarray(lp["attn_o_b"])
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-4)


def test_a_gmu_reads_the_last_mamba_layers_y_of_the_same_token(monkeypatch):
    tokens = _tokens(19, seed=2)
    want = _reference(tokens, list(range(19)))
    real = sambay.gmu

    def forward():
        return np.asarray(forward_full(PARAMS, jnp.asarray(tokens)[None], jnp.arange(19)[None],
                                       config=CONFIG, attn_backend="ref")[0])

    np.testing.assert_allclose(forward(), want, atol=TOL)
    # the memory of the token BEFORE: not the reference
    monkeypatch.setattr(sambay, "gmu", lambda h, m, lp, qm=None: real(
        h, jnp.roll(m, 1, axis=1), lp, qm))
    jax.clear_caches()
    assert np.abs(forward() - want).max() > 20 * TOL
    # and it is the LAST mamba1 layer's (layer 4 of 10), not an earlier one's
    monkeypatch.setattr(sambay, "gmu", real)
    jax.clear_caches()
    other = jax.tree.map(lambda x: x, PARAMS)
    other["layers"] = dict(other["layers"], m1_D=PARAMS["layers"]["m1_D"].at[2].mul(2.0))
    moved = np.asarray(forward_full(other, jnp.asarray(tokens)[None], jnp.arange(19)[None],
                                    config=CONFIG, attn_backend="ref")[0])
    assert np.abs(moved - want).max() > 20 * TOL


@pytest.mark.parametrize("fields,said", [
    (dict(layer_plan=(((MAMBA1, "ring"), 5),)), "kinds are"),
    (dict(layer_plan=(((MAMBA1, WINDOW), 4),)), "names 8 layers"),
    (dict(layer_plan=(((MAMBA1, WINDOW), 1), ((GMU, CROSS), 4))), "reads what a"),
    (dict(layer_plan=(((MAMBA1, WINDOW), 0), ((MAMBA1, FULL), 5))), "repeats >= 1"),
    (dict(window=0), "window and"),
    (dict(m1_inner=0), "m1_inner and"),
    (dict(layer_pattern=(FULL,)), "is not combined with a layer_pattern"),
    (dict(layer_plan=()), "are a layer_plan's"),
    (dict(layer_plan=(), window=0, m1_inner=0, layer_pattern=(WINDOW, FULL)),
     "are a layer_plan's"),
    (dict(n_heads=6, n_kv_heads=2, head_dim=5), "pairs heads"),
])
def test_plans_that_do_not_hold_together_are_refused(fields, said):
    with pytest.raises(ValueError, match=said):
        dataclasses.replace(CONFIG, **fields)


# --- SPLIT / RAGGED --------------------------------------------------------------

@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
@pytest.mark.parametrize("prompt_len", [7, 29])
def test_prefill_in_chunks_then_decode_past_the_windows_edge(prompt_len, backend):
    tokens = _tokens(prompt_len + 14, seed=prompt_len)
    want = _reference(tokens, list(range(prompt_len - 1, len(tokens))))
    np.testing.assert_allclose(_split(_engine(backend), tokens, prompt_len), want, atol=TOL)


def test_in_bfloat16_the_program_stays_near_the_reference():
    config = dataclasses.replace(CONFIG, dtype=jnp.bfloat16)
    params = init_params(config, jax.random.key(0))
    tokens = _tokens(40, seed=9)
    want, _ = phi4flash.reference_logits(params, tokens, FILE, positions=list(range(28, 40)))
    got = _split(_engine(config=config, params=params), tokens, 29)
    rel = np.sqrt(((got - np.asarray(want)) ** 2).mean(-1)) / np.asarray(want).std(-1)
    assert rel.max() < 0.08, rel


def test_ragged_round_with_rows_at_both_ends_of_the_buffer():
    """One packed buffer: a decode row past its window's edge, a prompt's
    first chunk, another prompt's third chunk (its window reaches back over
    two earlier ones), and a decode row at the buffer's last token; the next
    decode step of all four slots still equals the reference."""
    seqs = {0: _tokens(23, 1), 1: _tokens(CHUNK + 1, 2), 2: _tokens(3 * CHUNK + 1, 3),
            3: _tokens(10, 4)}
    engine = _engine(mixed_step=True)
    for slot in range(SLOTS):
        engine.set_page_table_row(slot, list(range(1 + 8 * slot, 9 + 8 * slot)))
    engine.prefill(0, seqs[0][:-2])
    engine.prefill(3, seqs[3][:-2])
    engine.prefill(2, seqs[2][:2 * CHUNK])
    engine.set_last_token(0, seqs[0][-2])
    engine.set_last_token(3, seqs[3][-2])
    packed = [0] + seqs[1][:CHUNK] + seqs[2][2 * CHUNK:3 * CHUNK] + [0]
    tok_row = [0] + [1] * CHUNK + [2] * CHUNK + [3]
    dev = np.asarray([True, False, False, True])
    zeros_i = jnp.zeros((SLOTS,), jnp.int32)
    _e, _n, row_logits = engine.ragged_round(
        jnp.asarray(packed, jnp.int32), jnp.asarray(tok_row, jnp.int32),
        jnp.arange(SLOTS, dtype=jnp.int32), jnp.asarray([0, 0, 2 * CHUNK, 0], jnp.int32),
        jnp.asarray([1, CHUNK, CHUNK, 1], jnp.int32), jnp.asarray(dev), jnp.asarray(dev), zeros_i,
        jnp.zeros((SLOTS,)), jnp.ones((SLOTS,)), zeros_i)
    row_logits = np.asarray(row_logits)
    after = _decode(engine, {slot: seqs[slot][-1] for slot in range(SLOTS)})
    for slot, seq in seqs.items():
        want = _reference(seq, [len(seq) - 2, len(seq) - 1])
        np.testing.assert_allclose(row_logits[slot], want[0], atol=TOL, err_msg=f"row {slot}")
        np.testing.assert_allclose(after[slot], want[1], atol=TOL, err_msg=f"slot {slot}")


@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
def test_the_benchmarks_own_logits_paths_agree_and_give_their_slots_back_clean(backend):
    from finchat_tpu.engine.kv_cache import PageAllocator
    from perfbench import correct

    class Sched:
        engine = _engine(backend, mixed_step=True)
        free_slots = [0, 1, 2, 3]
        allocator = PageAllocator(128)

    n_prompt = CHUNK * 3 // 2
    tokens = _tokens(n_prompt + 9, seed=5)
    prompt, forced = tokens[:n_prompt], tokens[n_prompt:]
    want = _reference(tokens, list(range(len(prompt) - 1, len(tokens))))
    for i, got in correct._ragged_path_logits(Sched, prompt, forced):
        np.testing.assert_allclose(got[:211], want[i], atol=TOL)
    for got, w in zip(correct._split_path_logits(Sched, prompt, forced), want):
        np.testing.assert_allclose(got, w, atol=TOL)
    assert float(jnp.abs(Sched.engine.state.ssm_state).max()) == 0.0
    assert Sched.engine.window_pager.pages_in_use == 0  # every window page went back
    assert not np.asarray(Sched.engine.state.win_table).any()


# --- WINDOW ----------------------------------------------------------------------

def test_a_rows_window_pages_stay_at_the_bound_and_freed_pages_go_back():
    engine = _engine()
    pager = engine.window_pager
    freed0 = METRICS.get("finchat_window_pages_freed_total")
    tokens = _tokens(90, seed=6)
    engine.set_page_table_row(1, list(range(1, 30)))
    engine.prefill(1, tokens[:21])
    held = []
    for t in tokens[21:]:
        _decode(engine, {1: t})
        held.append(len(pager.pages_of(1)))
        assert pager.pages_in_use == held[-1] <= BOUND - 1  # a decoding row never needs the last
    assert max(held) == W // PAGE + 1 and min(held) >= W // PAGE
    # 90 tokens are 23 pages in the full layer, three in each window layer
    assert METRICS.get("finchat_window_pages_freed_total") - freed0 == 23 - held[-1]
    assert METRICS.get("finchat_window_kv_bytes") == held[-1] * page_hbm_bytes(
        CONFIG, PAGE, kind="window")
    assert int(engine.state.win_gaps[1]) == (89 - W + 1) // PAGE * PAGE
    engine.reset_slot(1)
    assert pager.pages_in_use == 0 and METRICS.get("finchat_window_kv_bytes") == 0
    pager.allocator.check_invariants()


def test_a_chunk_that_starts_inside_a_page_is_cut_to_the_bound():
    pager = WindowPager(33, SLOTS, W, PAGE)
    assert pager.room(0) == 16 and pager.room(16) == 8 and pager.room(18) == 6
    pager.advance(0, 18, 6)
    with pytest.raises(PageAllocationError, match="spans"):
        pager.advance(1, 18, 7)
    with pytest.raises(ValueError, match="whole number"):
        WindowPager(33, SLOTS, 6, PAGE)
    with pytest.raises(ValueError, match="cannot hold"):
        WindowPager(8, SLOTS, W, PAGE)


@pytest.mark.parametrize("fault", ["a dropped page", "a page kept too long",
                                   "a mask one token short", "a mask one token long"])
def test_each_fault_at_the_windows_edge_fails(fault, monkeypatch):
    tokens = _tokens(45, seed=8)
    want = _reference(tokens, list(range(28, 45)))
    if fault == "a dropped page":  # the oldest page of the window goes back a page early
        monkeypatch.setattr(WindowPager, "_lowest",
                            lambda self, start: max(start - W + 1 + PAGE, 0) // PAGE)
    elif fault == "a page kept too long":  # ... or a page late: the bound is passed
        monkeypatch.setattr(WindowPager, "_lowest",
                            lambda self, start: max(start - W + 1 - 2 * PAGE, 0) // PAGE)
        with pytest.raises(PageAllocationError, match="spans"):
            _split(_engine(), tokens, 29)
        return
    else:  # the reference with the window the faulty mask would realise
        file = dict(FILE, sliding_window=W + (-1 if "short" in fault else 1))
        want = _reference(tokens, list(range(28, 45)), file=file)
    got = _split(_engine(), tokens, 29)
    assert np.abs(got - want).max() > 20 * TOL


# --- HEADS -----------------------------------------------------------------------

HEAD = _tokens(5 * PAGE, seed=11)  # a shared head of five whole pages: 20 tokens, 2.5 windows


def test_a_head_keeps_its_trailing_window_pages_and_a_row_reads_them_without_a_copy():
    engine = _engine()
    pager = engine.window_pager
    tail = _tokens(13, seed=12)
    alone = _split(_engine(), HEAD + tail, len(HEAD) + 7)
    # the head, prefilled once in slot 0; detaching it takes the window pages along
    head_pages = [1, 2, 3, 4, 5]
    engine.set_page_table_row(0, head_pages)
    engine.prefill(0, HEAD)
    snap = engine.detach_head(0)
    engine.reset_slot(0)
    head = snap[2]
    assert head.first == (len(HEAD) - W + 1) // PAGE and len(head.pages) == 2
    assert pager.pages_in_use == 2 and pager.pages_of(0) == []
    # two rows admitted from it: full pages and window pages by reference, the state copied
    for slot in (1, 3):
        engine.set_page_table_row(slot, head_pages + list(range(10 * slot, 10 * slot + 6)))
        engine.set_context_lens_rows({slot: len(HEAD)})
    engine.ssm_admit({1: snap, 3: snap})
    assert pager.pages_of(1) == pager.pages_of(3) == head.pages and pager.pages_in_use == 2
    for slot in (1, 3):
        logits = engine.prefill_rows(
            jnp.asarray([tail[:7] + [0]], jnp.int32), jnp.asarray([slot], jnp.int32),
            jnp.asarray([len(HEAD)], jnp.int32), jnp.asarray([7], jnp.int32))
        np.testing.assert_allclose(np.asarray(logits[0]), alone[0], atol=TOL)
    for i, t in enumerate(tail[7:]):
        got = _decode(engine, {1: t, 3: t})
        np.testing.assert_allclose(got[1], alone[1 + i], atol=TOL)
        np.testing.assert_allclose(got[3], alone[1 + i], atol=TOL)
    # both rows slid past the head's pages; the head still holds them
    assert not set(pager.pages_of(1)) & set(head.pages)
    assert set(head.pages) <= set(pager.allocator._owner)
    engine.reset_slots([1, 3])
    assert pager.pages_in_use == 2
    engine.release_snapshot(snap)
    assert pager.pages_in_use == 0
    pager.allocator.check_invariants()


def test_a_snapshot_of_a_decoding_row_changes_nothing():
    """``ssm_snapshot`` is a READ (``perfbench/state_control.py`` takes it of
    live slots and releases nothing): the row keeps its window pages, the
    allocator its count, and the row's next logits are what an undisturbed
    engine's are. The hand-over of pages is ``detach_head``'s alone."""
    tokens = _tokens(30, seed=21)
    engines = _engine(), _engine()
    for engine in engines:
        engine.set_page_table_row(1, list(range(1, 10)))
        engine.prefill(1, tokens[:21])
        _decode(engine, {1: tokens[21]})
    disturbed, quiet = engines
    pager = disturbed.window_pager
    held, in_use = pager.pages_of(1), pager.pages_in_use
    table = np.asarray(disturbed.state.win_table).copy()
    snap = disturbed.ssm_snapshot(1)
    assert len(snap) == 2  # the state and the conv tail: no page changes hands
    assert pager.pages_of(1) == held and held and pager.pages_in_use == in_use
    np.testing.assert_array_equal(np.asarray(disturbed.state.win_table), table)
    for t in tokens[22:]:
        np.testing.assert_array_equal(_decode(disturbed, {1: t})[1], _decode(quiet, {1: t})[1])
    # ... and restoring a bare snapshot (warm-up's round trip) leaves the lists alone too
    held = pager.pages_of(1)
    disturbed.ssm_restore(1, disturbed.ssm_snapshot(1))
    assert pager.pages_of(1) == held


def test_a_retired_heads_pages_wait_for_the_last_row_that_reads_them():
    pager = WindowPager(33, SLOTS, W, PAGE)
    pager.advance(0, 0, 8)
    pager.advance(0, 8, 8)
    head = pager.detach_head(0, 16)
    assert len(head.pages) == 2 and pager.pages_in_use == 2
    tight = WindowPager(SLOTS * BOUND + 1 + BOUND, SLOTS, W, PAGE)  # one head's room, no more
    tight.advance(0, 0, 8)
    assert tight.room_for_head() and tight.detach_head(0, 8).pages and not tight.room_for_head()
    pager.share(1, head)
    pager.release_head(head)
    assert pager.pages_in_use == 2  # the row still reads them
    assert pager.advance(1, 16, 1) == 0 and pager.advance(1, 24, 1) == 2
    assert pager.room_for_head()
    assert pager.pages_in_use == 3  # its own three; the head's two went back
    pager.release(1)
    assert pager.pages_in_use == 0
    pager.allocator.check_invariants()


def _scheduler(**options):
    return ContinuousBatchingScheduler(_engine(**options), eos_id=-1)


async def _stream(sched, prompt, n_new=11):
    handle = await sched.submit("seq", prompt, SamplingParams(temperature=0.0, max_new_tokens=n_new),
                                trace_id="t-1")
    tokens = []
    while True:
        event = await asyncio.wait_for(handle.events.get(), timeout=120)
        if event["type"] == "token":
            tokens.append(event["token_id"])
        elif event["type"] == "done":
            return handle, tokens
        else:
            raise AssertionError(event)


def _run(sched, prompt, **kw):
    async def go():
        await sched.start()
        try:
            got = await _stream(sched, prompt, **kw)
            await asyncio.sleep(0.05)
            return got
        finally:
            await sched.stop()
    return asyncio.run(go())


@pytest.mark.parametrize("mixed", [False, True])
def test_a_row_admitted_from_a_head_streams_what_the_whole_row_streams(mixed):
    prompt = HEAD + _tokens(13, seed=12)
    _handle, whole = _run(_scheduler(mixed_step=mixed), prompt)
    sched = _scheduler(mixed_step=mixed)
    pager = sched.engine.window_pager
    assert sched.register_prefix(HEAD + [1, 2, 3]) == len(HEAD)
    snap = sched._prefixes[0].ssm_snap
    assert snap[0].shape == (3, 1, 4, 128) and len(snap[2].pages) == 2
    assert pager.pages_in_use == 2  # the head's slot went back; its window pages stay
    handle, resumed = _run(sched, prompt)
    assert handle.shared_len == len(HEAD) and handle.span.state_restored_tokens == len(HEAD)
    assert resumed == whole and len(whole) == 11
    assert pager.pages_in_use == 2
    sched.retire_prefixes()
    assert pager.pages_in_use == 0 and not sched._prefixes


# --- POOLS / REFUSED -------------------------------------------------------------

def test_the_pools_have_their_kinds_depths_and_the_cross_layers_own_nothing():
    engine = _engine()
    state = engine.state
    assert state.k_pages.shape == (1, 128, PAGE, 32)  # ONE layer owns full pages
    n_win = window_pool_pages(CONFIG, engine.engine_cfg)
    assert n_win == (SLOTS + 4) * BOUND + 1
    assert state.win_k_pages.shape == state.win_v_pages.shape == (2, n_win, PAGE, 32)
    assert state.win_table.shape == (SLOTS, BOUND) and state.ssm_state.shape == (3, SLOTS, 1, 4, 128)
    for kind, pool in (("full", PagedKVCache.create(CONFIG, 7, PAGE)),
                       ("window", PagedKVCache.create_window(CONFIG, 7, PAGE))):
        assert pool.hbm_bytes() - 8 == 7 * page_hbm_bytes(CONFIG, PAGE, kind=kind)
    assert METRICS.get("finchat_window_kv_pool_bytes") == n_win * page_hbm_bytes(
        CONFIG, PAGE, kind="window")
    # the cross layers keep nothing: the full layer's pages have three readers a step
    assert CONFIG.cache_readers == 3
    engine.set_page_table_row(0, [1, 2, 3])
    engine.prefill(0, _tokens(5))
    # ... and what they read is the full layer's pages: without them they answer otherwise
    before = _decode(engine, {0: 7})[0]
    engine2 = _engine()
    engine2.set_page_table_row(0, [1, 2, 3])
    engine2.prefill(0, _tokens(5))
    engine2.state = dataclasses.replace(engine2.state, k_pages=jnp.zeros_like(state.k_pages))
    assert np.abs(_decode(engine2, {0: 7})[0] - before).max() > 20 * TOL


@pytest.mark.parametrize("options,named", [
    (dict(kv_sink_pages=1, kv_window_pages=8), "kv_sink_pages"),
    (dict(spec_tokens=2), "engine.spec_tokens"),
])
def test_engine_options_that_would_not_carry_the_state_are_refused_by_name(options, named):
    with pytest.raises(ValueError, match=named):
        _engine(**options)


def test_bounded_kv_is_refused_with_window_layers_by_what_each_bounds():
    with pytest.raises(ValueError, match="EVERY layer.*bounded page list of their own"):
        _engine(kv_sink_pages=1, kv_window_pages=8)
    with pytest.raises(ValueError, match="at most two pages"):
        InferenceEngine(CONFIG, PARAMS, EngineConfig(
            max_seqs=SLOTS, page_size=PAGE, num_pages=64, max_seq_len=256, prefill_chunk=12),
            attn_backend="ref")
    with pytest.raises(ValueError, match="kv_quant"):
        _engine(kv_quant="int8")


def test_chip_smokes_window_case_at_a_small_size():
    """``chip_smoke.check_decode_at_cell_shape`` with a window, as it runs on
    the chip for the cell's sliding layers: a table as wide as a row's bound,
    contexts compacted into it, a NaN trash page under the dead entries."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    import chip_smoke

    error = chip_smoke.check_decode_at_cell_shape(
        "pallas-interpret", rows=4, n_heads=8, n_kv=2, head_dim=16, page_size=8, width=4,
        contexts=(17, 30), pool_pages=20, window=16)
    assert error < chip_smoke.KERNEL_ATOL + chip_smoke.KERNEL_RTOL * 4


@pytest.mark.parametrize("window", [0, 16])
def test_the_ragged_kernel_at_a_prompt_rounds_query_block_equals_its_reference(window):
    """A model with a ``layer_plan`` takes query blocks of 64 tokens in a
    round of 2,048 tokens or more (``_ragged_round_math``): the kernel at that
    block — rows of 70, 1, 64 and 3 tokens, a window or none — against the
    reference that attends a token at a time."""
    from finchat_tpu.ops.ragged_paged_attention import (
        ragged_flash_attention,
        ragged_paged_attention_ref,
    )

    rng = np.random.RandomState(4)
    H, Hkv, D, PS, P, MP = 4, 2, 16, 8, 64, 16
    lens, ctx = [70, 1, 64, 3], [9, 40, 0, 77]
    T = 160
    q = jnp.asarray(rng.normal(size=(T, H, D)), jnp.float32)
    k_pages, v_pages = (jnp.asarray(rng.normal(size=(1, P, PS, Hkv * D)), jnp.float32)
                        for _ in range(2))
    table = rng.permutation(np.arange(1, P))[:4 * MP - 8].reshape(4, MP - 2)
    table = np.concatenate([table, np.zeros((4, 2), np.int64)], axis=1).astype(np.int32)
    tok_row = np.concatenate([np.full(n, r) for r, n in enumerate(lens)]
                             + [np.full(T - sum(lens), 4)]).astype(np.int32)
    tok_pos = np.concatenate([c + np.arange(n) for c, n in zip(ctx, lens)]
                             + [np.zeros(T - sum(lens))]).astype(np.int32)
    kv_len = np.asarray([c + n for c, n in zip(ctx, lens)], np.int32)
    args = (q, k_pages, v_pages, jnp.asarray(table), jnp.asarray(tok_row), jnp.asarray(tok_pos),
            jnp.asarray(kv_len), jnp.asarray([0], jnp.int32))
    kw = dict(page_size=PS, n_kv=Hkv, scale=0.3, **({"window": window} if window else {}))
    want = ragged_paged_attention_ref(*args, **kw)
    got = ragged_flash_attention(*args, **kw, block_q=64, interpret=True)
    live = tok_row < 4
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], atol=2e-5)
    if window:  # and the window bites: without it the longest row reads otherwise
        loose = ragged_paged_attention_ref(*args, page_size=PS, n_kv=Hkv, scale=0.3)
        assert np.abs(np.asarray(loose) - np.asarray(want))[live].max() > 1e-2


@pytest.mark.parametrize("heads, want", [(40, 64), (32, 128), (30, 128), (20, 128)])
def test_the_chunk_forms_query_block_is_halved_only_where_it_would_not_fit(heads, want):
    """The paged kernel's fit rule reads the call's shapes: this model's 40
    kernel heads of 128 take a block of 64 (128 asks 13.1 MiB of VMEM before a
    page is copied); the accepted configurations' 32, 30 and 20 heads of 128
    keep the 128 they compiled with."""
    from finchat_tpu.ops.paged_attention import _fit_block

    assert _fit_block(128, heads, 128, 2) == want
    assert _fit_block(8, heads, 128, 2) == 8  # (a one-token call's block is never cut)


def test_the_page_lists_the_device_sees_are_copies_of_the_hosts():
    """The pager changes its table in place; a device array made straight
    from it may alias the host buffer (the CPU backend does) and a dispatched
    step would then read a LATER list: the first stream of a process decoded
    garbage one run in two before the upload copied."""
    engine = _engine()
    engine.set_page_table_row(0, list(range(1, 9)))
    engine.prefill(0, _tokens(11))
    seen = np.asarray(engine.state.win_table).copy()
    engine.window_pager.table[:] = 99
    engine.window_pager.gaps[:] = 99
    assert (np.asarray(engine.state.win_table) == seen).all() and seen.max() < 99
    assert int(np.asarray(engine.state.win_gaps).max()) < 99
