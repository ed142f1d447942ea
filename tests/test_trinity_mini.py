"""``afmoe`` (Trinity-Mini, PR 47) on the llama block under a ``layer_pattern``
with sliding-window layers, at a size a test holds: ONE leading dense window
layer, two periods of three window layers and a full one; a q/k norm a head,
the window layers rotated and the full ones not, a gated attention output, a
norm on each sub-block's input AND output; 32 routed experts at 4 a token, all
held, beside a shared one. Everything against the plain reference of
``perfbench/models/afmoe.py`` (float32, a head group and an expert at a time,
no pages).

FORWARD  the cache-less forward past the window's edge; each of the four
         pieces faulted in the reference; the counts
SPLIT / RAGGED  prefill then decode and the packed round past more than three
         windows, so that pages slide in the leading layer and the scanned
         ones; the benchmark's own two paths; the experts counted a step
HEADS    a row admitted from a shared head: full pages and window pages by
         reference, no state; through the scheduler too
POOLS / REFUSED  the pools' depth by kind; what is refused at load
"""

import asyncio
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import tiny_models

from finchat_tpu.engine.engine import InferenceEngine, window_pool_pages
from finchat_tpu.engine.kv_cache import page_hbm_bytes, window_pages_per_row
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
from finchat_tpu.models.llama import (
    FULL,
    PRESETS,
    WINDOW,
    LlamaConfig,
    forward_full,
    n_params,
)
from finchat_tpu.utils.config import EngineConfig
from finchat_tpu.utils.metrics import METRICS
from perfbench.models import afmoe

ROOT = Path(__file__).resolve().parents[1]
FILE = tiny_models.FILES["trinity_mini"]
CONFIG, _DRAWN = tiny_models.build("trinity_mini")
PAGE, CHUNK, SLOTS = tiny_models.SHAPES["trinity_mini"]
W = FILE["sliding_window"]
TOL = 3e-4  # float32 against float32; the logits' spread is about 1
BOUND = window_pages_per_row(W, PAGE)
VOCAB = FILE["vocab_size"]


def _with_norms_that_matter(params):
    """The drawn tree with every norm weight moved off 1, so that a norm left
    out, put on the wrong side or given another layer's weight shows."""
    rng = np.random.RandomState(7)

    def moved(stack):
        return {name: leaf * (1.0 + 0.3 * jnp.asarray(rng.standard_normal(leaf.shape), leaf.dtype))
                if name.startswith("ln_") or name.endswith("_norm") else leaf
                for name, leaf in stack.items()}

    return dict(params, layers=moved(params["layers"]), dense_layers=moved(params["dense_layers"]))


PARAMS = _with_norms_that_matter(_DRAWN)


def _tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, VOCAB, size=n)]


def _reference(tokens, positions, file=FILE, params=PARAMS, **kw):
    want, margins = afmoe.reference_logits(params, tokens, file, positions=positions, **kw)
    assert np.isfinite(np.asarray(margins)).all()  # every layer behind the first routes
    return np.asarray(want)


def _engine(attn_backend="ref", config=CONFIG, params=PARAMS, **options) -> InferenceEngine:
    cfg = EngineConfig(max_seqs=SLOTS, page_size=PAGE, num_pages=160, max_seq_len=256,
                       **{"prefill_chunk": CHUNK, **options})
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
    c = CONFIG
    assert c.layer_pattern == (WINDOW, WINDOW, WINDOW, FULL) and c.leading_kinds == (WINDOW,)
    assert (c.n_attn_layers, c.n_window_layers, c.n_kv_layers, c.n_state_layers) == (2, 7, 9, 0)
    assert c.moe_sparse and not c.has_state and c.cache_readers == 1
    assert c.rope_kinds == (WINDOW,) and c.embedding_multiplier == 8.0
    # the adapter reads the published keys into the preset written by hand
    assert c == dataclasses.replace(PRESETS["trinity-tiny"], dtype=jnp.float32)
    leaves = sum(x.size for x in jax.tree.leaves(PARAMS))
    assert leaves == n_params(c) == afmoe.param_counts(FILE)["total"]
    layers, dense = PARAMS["layers"], PARAMS["dense_layers"]
    # one stack of attention leaves for both kinds of layer, the leading layer's apart
    for name in ("attn_q", "attn_k", "attn_v", "attn_o", "attn_gate", "attn_q_norm", "ln_attn_out",
                 "ln_mlp_out", "moe_in", "router_bias"):
        assert layers[name].shape[0] == 8, name
    assert dense["attn_gate"].shape == (1, 64, 128) and dense["mlp_gate"].shape == (1, 64, 96)
    assert layers["attn_q_norm"].shape == layers["attn_k_norm"].shape == (8, 16)  # a HEAD wide
    assert layers["moe_in"].shape == (8, 32, 64, 64) and "mlp_gate" not in layers


def test_the_published_widths_count_what_the_issue_counts():
    """4,241.5 M parameters at the cut (1 dense + 4 routed layers), 26.1 B
    uncut; analytic, the adapter's and the tree's shapes, nothing drawn."""
    from finchat_tpu.models.llama import init_params

    file = json.loads((ROOT / "perfbench/configs/trinity-mini.json").read_text())
    c = afmoe.program_config(file)
    tree = jax.eval_shape(lambda: init_params(c, jax.random.key(0)))
    total = sum(x.size for x in jax.tree.leaves(tree))
    assert total == n_params(c) == afmoe.param_counts(file)["total"] == 4_241_534_720
    assert c.leading_kinds == (WINDOW,) and c.layer_pattern == (WINDOW, WINDOW, WINDOW, FULL)
    assert (c.n_attn_layers, c.n_window_layers) == (1, 4)
    cut = file["reduced"]
    uncut = dict(file, num_hidden_layers=cut["num_hidden_layers"]["from"],
                 num_dense_layers=cut["num_dense_layers"]["from"],
                 layer_types=cut["layer_types"]["from"])
    whole = afmoe.program_config(uncut)
    assert n_params(whole) == afmoe.param_counts(uncut)["total"] == n_params(PRESETS["trinity-mini"])
    assert round(n_params(whole) / 1e9, 1) == 26.1
    assert (whole.n_attn_layers, whole.n_window_layers) == (8, 24)
    assert whole.leading_kinds == (WINDOW, WINDOW) and len(whole.layer_pattern) == 30


def test_the_forward_without_a_cache_equals_the_reference_past_the_windows_edge():
    tokens = _tokens(37, seed=1)
    got = forward_full(PARAMS, jnp.asarray(tokens)[None], jnp.arange(37)[None], config=CONFIG,
                       attn_backend="ref")[0]
    np.testing.assert_allclose(np.asarray(got), _reference(tokens, list(range(37))), atol=TOL)


@pytest.mark.parametrize("fault", afmoe.FAULTS + ("window_off",))
def test_the_reference_with_a_piece_faulted_differs_from_the_program(fault):
    """Rotation on the full layers, no norm a head, no gate, no output norm,
    no window: each moves the logits by far more than the tolerance, so the
    comparison holds the program to each piece."""
    tokens = _tokens(37, seed=1)
    got = np.asarray(forward_full(PARAMS, jnp.asarray(tokens)[None], jnp.arange(37)[None],
                                  config=CONFIG, attn_backend="ref")[0])
    kw = {"window_off": True} if fault == "window_off" else {"fault": fault}
    assert np.abs(_reference(tokens, list(range(37)), **kw) - got).max() > 100 * TOL
    # (window_control.py's second control is the model unchanged: no cross layer here)
    np.testing.assert_allclose(_reference(tokens, [36], cross_own=True), got[36:], atol=TOL)


def test_the_seeded_selection_bias_is_small_and_still_moves_picks():
    """``expert_bias`` is drawn at a tenth of the scores' spread (it balances
    load in the trained model; drawn large it herds every row onto the same
    experts): a fault in it still shows — without it a third of the tokens
    pick otherwise in some layer and the logits move."""
    bias = np.asarray(PARAMS["layers"]["router_bias"])
    assert CONFIG.moe_bias_init_std == 0.02 and 0.015 < bias.std() < 0.025
    tokens = _tokens(37, seed=1)
    unbiased = dict(PARAMS, layers=dict(PARAMS["layers"], router_bias=jnp.zeros_like(bias)))
    with_bias = _reference(tokens, list(range(37)))
    without = _reference(tokens, list(range(37)), params=unbiased)
    moved = np.abs(with_bias - without).max(axis=-1)
    assert (moved > 100 * TOL).mean() > 0.3, moved
    # an accepted configuration's draw is what it was
    assert PRESETS["moe-tiny"].moe_bias_init_std == LlamaConfig().moe_bias_init_std == 0.1


def test_the_routing_margin_is_the_gap_to_the_ninth_choice():
    tokens = _tokens(12, seed=3)
    _want, margins = afmoe.reference_logits(PARAMS, tokens, FILE, positions=list(range(12)))
    x = jnp.asarray(np.random.RandomState(0).standard_normal((5, 64)), jnp.float32)
    router, bias = PARAMS["layers"]["router"][0], PARAMS["layers"]["router_bias"][0]
    picks, gates, margin = afmoe._route(x, router, bias, top_k=4, gate_scale=2.826, norm=True)
    choice = np.sort(np.asarray(jax.nn.sigmoid(x @ router) + bias), axis=-1)[:, ::-1]
    np.testing.assert_allclose(np.asarray(margin), (choice[:, 3] - choice[:, 4])
                               / (afmoe.MARGIN_UNIT * choice.std(-1)), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.826, rtol=1e-5)
    assert np.asarray(picks).shape == (5, 4) and (np.asarray(margins) > 0).all()


# --- SPLIT / RAGGED --------------------------------------------------------------

@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
@pytest.mark.parametrize("prompt_len", [7, 29])
def test_prefill_in_chunks_then_decode_past_three_windows(prompt_len, backend):
    tokens = _tokens(prompt_len + 14, seed=prompt_len)
    want = _reference(tokens, list(range(prompt_len - 1, len(tokens))))
    engine = _engine(backend)
    np.testing.assert_allclose(_split(engine, tokens, prompt_len), want, atol=TOL)
    if prompt_len > 3 * W:  # pages slid out of the leading layer's list and the scanned ones'
        assert int(engine.state.win_gaps[2]) == (len(tokens) - 1 - W + 1) // PAGE * PAGE
        assert len(engine.window_pager.pages_of(2)) <= BOUND - 1


def test_the_decode_step_counts_the_experts_its_rows_touched_in_the_routed_layers():
    engine = _engine()
    tokens = _tokens(12, seed=2)
    engine.set_page_table_row(1, [1, 2, 3, 4])
    engine.prefill(1, tokens[:11])
    _decode(engine, {1: tokens[11]})
    touched, read = (int(n) for n in np.asarray(engine.moe_experts))
    # one row: 4 picks in each of the 8 routed layers; the leading dense layer counts none;
    # the reference backend's dense dispatch reads every held expert
    assert touched == 8 * 4 and read == 8 * 32
    kernel = _engine("pallas-interpret")
    kernel.set_page_table_row(1, [1, 2, 3, 4])
    kernel.prefill(1, tokens[:11])
    _decode(kernel, {1: tokens[11]})
    assert [int(n) for n in np.asarray(kernel.moe_experts)] == [32, 32]  # the touched pass


def test_in_bfloat16_the_program_stays_near_the_reference():
    """At this size (hidden 64, eight routed layers of 32 small experts) bfloat16
    flips a pick at most positions, each a tenth to a quarter of the logits'
    spread: the level says only that nothing is wrong by a whole piece — a
    reference without the gate reads several times it."""
    from finchat_tpu.models.llama import init_params

    config = dataclasses.replace(CONFIG, dtype=jnp.bfloat16)
    params = init_params(config, jax.random.key(0))
    tokens = _tokens(40, seed=9)
    positions = list(range(28, 40))
    want, _margins = afmoe.reference_logits(params, tokens, FILE, positions=positions)
    wrong, _ = afmoe.reference_logits(params, tokens, FILE, positions=positions, fault="no_gate")
    got = _split(_engine(config=config, params=params), tokens, 29)

    def rel(want):
        return np.sqrt(((got - np.asarray(want)) ** 2).mean(-1)) / np.asarray(want).std(-1)

    assert np.median(rel(want)) < 0.25 and np.median(rel(wrong)) > 3 * np.median(rel(want))


def test_ragged_round_with_rows_at_both_ends_of_the_buffer():
    """One packed buffer: a decode row past its window's edge, a prompt's
    first chunk, another prompt's fourth chunk (its window reaches back over
    earlier ones, three windows in), and a decode row at the buffer's last
    token; the next decode step of all four slots still equals the reference."""
    seqs = {0: _tokens(27, 1), 1: _tokens(CHUNK + 1, 2), 2: _tokens(4 * CHUNK + 1, 3),
            3: _tokens(10, 4)}
    engine = _engine(mixed_step=True)
    for slot in range(SLOTS):
        engine.set_page_table_row(slot, list(range(1 + 10 * slot, 11 + 10 * slot)))
    engine.prefill(0, seqs[0][:-2])
    engine.prefill(3, seqs[3][:-2])
    engine.prefill(2, seqs[2][:3 * CHUNK])
    engine.set_last_token(0, seqs[0][-2])
    engine.set_last_token(3, seqs[3][-2])
    packed = [0] + seqs[1][:CHUNK] + seqs[2][3 * CHUNK:4 * CHUNK] + [0]
    tok_row = [0] + [1] * CHUNK + [2] * CHUNK + [3]
    dev = np.asarray([True, False, False, True])
    zeros_i = jnp.zeros((SLOTS,), jnp.int32)
    _e, _n, row_logits = engine.ragged_round(
        jnp.asarray(packed, jnp.int32), jnp.asarray(tok_row, jnp.int32),
        jnp.arange(SLOTS, dtype=jnp.int32), jnp.asarray([0, 0, 3 * CHUNK, 0], jnp.int32),
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
    """``correct.py``'s two paths as ``window_control.py`` drives them: a
    prompt of more than three windows in ``prefill_chunk`` pieces, so that the
    window pages slide more than once before the first compared position."""
    from finchat_tpu.engine.kv_cache import PageAllocator
    from perfbench import correct
    from perfbench.sparse_control import ragged_path_logits

    class Sched:
        engine = _engine(backend, mixed_step=True)
        free_slots = [0, 1, 2, 3]
        allocator = PageAllocator(160)

    n_prompt = 3 * W + 5
    tokens = _tokens(n_prompt + 9, seed=5)
    prompt, forced = tokens[:n_prompt], tokens[n_prompt:]
    want = _reference(tokens, list(range(len(prompt) - 1, len(tokens))))
    for i, got in ragged_path_logits(Sched, prompt, forced):
        np.testing.assert_allclose(got[:VOCAB], want[i], atol=TOL)
    for got, w in zip(correct._split_path_logits(Sched, prompt, forced), want):
        np.testing.assert_allclose(got, w, atol=TOL)
    assert Sched.engine.window_pager.pages_in_use == 0  # every window page went back
    assert not np.asarray(Sched.engine.state.win_table).any()


def test_a_rows_window_pages_stay_at_the_bound_in_all_seven_window_layers():
    engine = _engine()
    pager = engine.window_pager
    freed0 = METRICS.get("finchat_window_pages_freed_total")
    tokens = _tokens(60, seed=6)
    engine.set_page_table_row(1, list(range(1, 20)))
    engine.prefill(1, tokens[:21])
    held = []
    for t in tokens[21:]:
        _decode(engine, {1: t})
        held.append(len(pager.pages_of(1)))
        assert pager.pages_in_use == held[-1] <= BOUND - 1
    assert max(held) == W // PAGE + 1 and min(held) >= W // PAGE
    assert METRICS.get("finchat_window_pages_freed_total") - freed0 == 15 - held[-1]
    # a page of the second pool is seven layers deep: the leading layer's and the scan's six
    assert page_hbm_bytes(CONFIG, PAGE, kind="window") == 7 * PAGE * 2 * 32 * 4
    assert METRICS.get("finchat_window_kv_bytes") == held[-1] * page_hbm_bytes(
        CONFIG, PAGE, kind="window")
    engine.reset_slot(1)
    assert pager.pages_in_use == 0 and METRICS.get("finchat_window_kv_bytes") == 0
    pager.allocator.check_invariants()


# --- HEADS -----------------------------------------------------------------------

HEAD = _tokens(7 * PAGE, seed=11)  # a shared head of seven whole pages: 28 tokens, 3.5 windows


def test_a_head_keeps_its_trailing_window_pages_and_a_row_reads_them_without_a_copy():
    engine = _engine()
    pager = engine.window_pager
    tail = _tokens(13, seed=12)
    alone = _split(_engine(), HEAD + tail, len(HEAD) + 7)
    head_pages = [1, 2, 3, 4, 5, 6, 7]
    engine.set_page_table_row(0, head_pages)
    engine.prefill(0, HEAD)
    snap = engine.detach_head(0)
    engine.reset_slot(0)
    assert snap[:2] == (None, None)  # no recurrent state: the window pages alone
    head = snap[2]
    assert head.first == (len(HEAD) - W + 1) // PAGE and len(head.pages) == 2
    assert pager.pages_in_use == 2 and pager.pages_of(0) == []
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
    assert not set(pager.pages_of(1)) & set(head.pages)  # both rows slid past the head's pages
    engine.reset_slots([1, 3])
    assert pager.pages_in_use == 2
    # a cold admission gives back what the slot's last row referenced
    engine.ssm_admit({1: snap})
    engine.ssm_admit({1: None})
    assert pager.pages_of(1) == []
    engine.release_snapshot(snap)
    assert pager.pages_in_use == 0
    pager.allocator.check_invariants()
    assert engine.ssm_snapshot(0) is None  # a READ of a model without state: nothing


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
    steps0 = METRICS.get("finchat_moe_layer_steps_total")
    touched0 = METRICS.get("finchat_moe_experts_touched_total")
    _handle, whole = _run(_scheduler(mixed_step=mixed), prompt)
    # the scheduler books the experts a decode step touched in the 8 routed layers
    steps = METRICS.get("finchat_moe_layer_steps_total") - steps0
    assert steps > 0 and steps % 8 == 0
    assert METRICS.get("finchat_moe_experts_touched_total") - touched0 == steps * 4
    sched = _scheduler(mixed_step=mixed)
    pager = sched.engine.window_pager
    assert sched.has_ssm  # window pages are per-row memory a head keeps, state or none
    assert sched.register_prefix(HEAD + [1, 2, 3]) == len(HEAD)
    snap = sched._prefixes[0].ssm_snap
    assert snap[0] is None and len(snap[2].pages) == 2
    assert pager.pages_in_use == 2  # the head's slot went back; its window pages stay
    handle, resumed = _run(sched, prompt)
    assert handle.shared_len == len(HEAD) and handle.span.state_restored_tokens == len(HEAD)
    assert resumed == whole and len(whole) == 11
    assert pager.pages_in_use == 2
    # a prompt that shares only part of the head recomputes: the head's window pages are its END's
    partial = HEAD[:4 * PAGE] + _tokens(9, seed=13)
    handle, _ = _run(sched, partial)
    assert handle.shared_len == 0
    sched.retire_prefixes()
    assert pager.pages_in_use == 0 and not sched._prefixes


def test_a_dispatch_notes_what_one_window_layers_walk_reads():
    noted = []

    class Phases:
        def note(self, **stats):
            noted.append(stats)

    sched = _scheduler()
    sched._phases = Phases()
    sched._trace_dispatch("decode", [(0, "t", "decode", None, 5), (1, "t", "decode", None, 40)])
    assert noted[-1]["window_kv_tokens"] == 5 + W and noted[-1]["kv_tokens"] == 45


# --- POOLS / REFUSED -------------------------------------------------------------

def test_the_pools_have_their_kinds_depths_and_the_leading_layer_comes_first():
    engine = _engine()
    state = engine.state
    assert state.k_pages.shape == (2, 160, PAGE, 32)  # the two full layers own full pages
    n_win = window_pool_pages(CONFIG, engine.engine_cfg)
    assert n_win == (SLOTS + 4) * BOUND + 1
    assert state.win_k_pages.shape == state.win_v_pages.shape == (7, n_win, PAGE, 32)
    assert state.win_table.shape == (SLOTS, BOUND) and state.ssm_state.shape == (1, 1, 1, 1, 1)
    # the leading dense layer writes index 0 of the WINDOW pool and nothing of the full pool
    engine.set_page_table_row(0, [1, 2, 3])
    engine.prefill(0, _tokens(3))
    page = engine.window_pager.pages_of(0)[0]
    win_k = np.asarray(engine.state.win_k_pages)
    assert np.abs(win_k[:, page, :3]).max(axis=(1, 2)).min() > 0  # all seven layers wrote
    assert np.abs(np.asarray(engine.state.k_pages)[:, 1, :3]).max(axis=(1, 2)).min() > 0
    assert not np.asarray(engine.state.k_pages)[:, 2:].any()


@pytest.mark.parametrize("options,named", [
    (dict(kv_sink_pages=1, kv_window_pages=8), "EVERY layer.*bounded page list of their own"),
    (dict(spec_tokens=2), r"a model with window pages.*engine.spec_tokens \(verify_step"),
    (dict(kv_quant="int8"), "engine.kv_quant has no sliding-window form"),
    (dict(prefill_chunk=12), r"at most two pages.*= 4 pages a window layer"),
])
def test_engine_options_that_would_not_carry_the_window_are_refused_by_name(options, named):
    with pytest.raises(ValueError, match=named):
        _engine(**options)


def test_a_mesh_and_quantized_weights_are_refused_by_name():
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2, 1), ("data", "model"))
    cfg = EngineConfig(max_seqs=SLOTS, page_size=PAGE, num_pages=64, max_seq_len=256,
                       prefill_chunk=CHUNK)
    with pytest.raises(ValueError, match=r"window pages.*mesh\.\* > 1 \(the window pool"):
        InferenceEngine(CONFIG, PARAMS, cfg, mesh=mesh, attn_backend="ref")
    with pytest.raises(ValueError, match="model.quant='int8' is not supported for a model with "
                                         "fused-GLU expert stacks"):
        InferenceEngine(CONFIG, PARAMS, cfg, attn_backend="ref", quant="int8")


@pytest.mark.parametrize("option,value", [("fleet.replicas", 2), ("pod.host_id", "a")])
def test_the_tiers_that_hold_no_window_pages_are_refused_at_load(option, value):
    from finchat_tpu.serve.app import make_engine_replica
    from finchat_tpu.utils.config import load_config

    cfg = load_config(None, {option: value})
    with pytest.raises(ValueError, match=f"sliding-window layers.*one engine.*{option}"):
        make_engine_replica(cfg, (CONFIG, PARAMS, None, None))


def test_the_warm_fabric_is_refused_and_the_session_tier_is_not_built():
    engine = _engine(session_cache=True, session_cache_bytes=1 << 20)
    with pytest.raises(ValueError, match="fabric.path.*sliding-window"):
        ContinuousBatchingScheduler(engine, eos_id=-1, fabric=object())
    sched = ContinuousBatchingScheduler(engine, eos_id=-1)
    assert sched.session_cache is None and sched._ssm_session_fallback


@pytest.mark.parametrize("fields,said", [
    (dict(window=0), "window and 'sliding_attention' layers"),
    (dict(layer_pattern=(FULL,), leading_kinds=(FULL,)), "window and 'sliding_attention' layers"),
    (dict(leading_kinds=(WINDOW, WINDOW)), "leading_kinds names each leading dense layer"),
    (dict(leading_kinds=("mamba",)), "leading_kinds names each leading dense layer"),
    (dict(qk_norm=True), "one or the other"),
    (dict(norm_after=True), "one or the other"),
    (dict(rope_kinds=("mamba",)), "rope_kinds names the rotated kinds"),
    (dict(rope_theta=None), "rope_kinds names the rotated kinds"),
    (dict(n_layers=8), "whole number of periods"),
    (dict(ssm_heads=4, ssm_head_dim=16, ssm_state=8), "no mixer, linear or latent attention"),
])
def test_patterns_with_window_layers_that_do_not_hold_together_are_refused(fields, said):
    with pytest.raises(ValueError, match=said):
        dataclasses.replace(CONFIG, **fields)


def test_an_older_pattern_emits_the_tree_and_the_state_it_always_did():
    """Every new piece is data whose absent value emits nothing: the seven
    accepted files' parameter trees and state leaves (the test of that name in
    tests/perfbench holds their files; this holds the presets)."""
    from finchat_tpu.engine.engine import create_state
    from finchat_tpu.models.llama import init_params

    for name in ("tiny", "moe-tiny"):
        c = PRESETS[name]
        assert not (c.window or c.leading_kinds or c.rope_kinds or c.qk_head_norm
                    or c.attn_gate or c.norm_both)
        tree = jax.eval_shape(lambda c=c: init_params(c, jax.random.key(0)))
        assert not [leaf for leaf in tree["layers"]
                    if leaf in ("attn_gate", "ln_attn_out", "ln_mlp_out", "attn_q_norm")]
        cfg = EngineConfig(max_seqs=2, page_size=8, num_pages=16, max_seq_len=64)
        state = jax.eval_shape(lambda c=c: create_state(c, cfg, 8))
        assert state.win_k_pages is None and len(jax.tree.leaves(state)) == 11
    assert LlamaConfig().n_of(FULL) == 2 and LlamaConfig().n_of(WINDOW) == 0
