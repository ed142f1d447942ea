"""``mimo_v2_flash`` (PR 57) on the served path at a size a test holds — the
engine's steps, the scheduler and a shared head over pools of two page widths
(the full layers' 2 K/V heads, the sliding layers' 4; keys of 192 over values
of 128), against the plain reference (tests/test_mimo_v2_flash.py holds the
block itself and the split path).

RAGGED   the packed round with rows at both ends of the buffer; the
         benchmark's own two logits paths, which give their slots back clean
HEADS    a row admitted from a shared head: full pages and the head's ONE
         trailing window page by reference; through the scheduler too
BOUND    a window of ONE page: three pages a row, a chunk that starts
         mid-page is cut to what fits
REFUSED  what is refused at load beside per-kind shapes; what an older
         pattern emits
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from finchat_tpu.engine.engine import NOT_CARRIED, InferenceEngine, create_state
from finchat_tpu.engine.kv_cache import PageAllocationError, WindowPager, window_pages_per_row
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
from finchat_tpu.models.llama import FULL, PRESETS, WINDOW, LlamaConfig, init_params
from finchat_tpu.utils.config import EngineConfig
from finchat_tpu.utils.metrics import METRICS

from test_mimo_v2_flash import (  # the block's own file: the same tiny model and drivers
    CHUNK,
    CONFIG,
    PAGE,
    PARAMS,
    SLOTS,
    TOL,
    VOCAB,
    W,
    _decode,
    _engine,
    _reference,
    _split,
    _tokens,
)

BOUND = window_pages_per_row(W, PAGE)


# --- RAGGED ----------------------------------------------------------------------

def test_ragged_round_with_rows_at_both_ends_of_the_buffer():
    """One packed buffer: a decode row past its window's edge, a prompt's
    first chunk, another prompt's fourth chunk, and a decode row at the
    buffer's last token; the next decode step of all four slots still equals
    the reference."""
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
    tokens = _tokens(n_prompt + 5, seed=5)
    prompt, forced = tokens[:n_prompt], tokens[n_prompt:]
    want = _reference(tokens, list(range(len(prompt) - 1, len(tokens))))
    for i, got in ragged_path_logits(Sched, prompt, forced):
        np.testing.assert_allclose(got[:VOCAB], want[i], atol=TOL)
    if backend == "ref":  # (the split steps on the kernels: test_mimo_v2_flash.py)
        for got, w in zip(correct._split_path_logits(Sched, prompt, forced), want):
            np.testing.assert_allclose(got, w, atol=TOL)
    assert Sched.engine.window_pager.pages_in_use == 0  # every window page went back
    assert not np.asarray(Sched.engine.state.win_table).any()


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
    engine.release_snapshot(snap)
    assert pager.pages_in_use == 0
    pager.allocator.check_invariants()


def _scheduler(**options):
    return ContinuousBatchingScheduler(_engine(**options), eos_id=-1)


def _run(sched, prompt, n_new=11):
    async def go():
        await sched.start()
        try:
            handle = await sched.submit(
                "seq", prompt, SamplingParams(temperature=0.0, max_new_tokens=n_new),
                trace_id="t-1")
            tokens = []
            while True:
                event = await asyncio.wait_for(handle.events.get(), timeout=120)
                if event["type"] == "token":
                    tokens.append(event["token_id"])
                elif event["type"] == "done":
                    await asyncio.sleep(0.05)
                    return handle, tokens
                else:
                    raise AssertionError(event)
        finally:
            await sched.stop()
    return asyncio.run(go())


def test_a_row_admitted_from_a_head_streams_what_the_whole_row_streams():
    prompt = HEAD + _tokens(13, seed=12)
    steps0 = METRICS.get("finchat_moe_layer_steps_total")
    _handle, whole = _run(_scheduler(mixed_step=True), prompt)
    steps = METRICS.get("finchat_moe_layer_steps_total") - steps0
    assert steps > 0 and steps % 8 == 0  # the 8 routed layers; the leading layer routes nothing
    sched = _scheduler(mixed_step=True)
    pager = sched.engine.window_pager
    assert sched.has_ssm  # window pages are per-row memory a head keeps
    assert sched.register_prefix(HEAD + [1, 2, 3]) == len(HEAD)
    snap = sched._prefixes[0].ssm_snap
    assert snap[0] is None and len(snap[2].pages) == 2
    handle, resumed = _run(sched, prompt)
    assert handle.shared_len == len(HEAD) and handle.span.state_restored_tokens == len(HEAD)
    assert resumed == whole and len(whole) == 11
    sched.retire_prefixes()
    assert pager.pages_in_use == 0 and not sched._prefixes


def test_a_dispatch_notes_the_windows_tokens_and_the_pool_names_each_array():
    noted = []

    class Phases:
        def note(self, **stats):
            noted.append(stats)

    sched = _scheduler()
    sched._phases = Phases()
    sched._trace_dispatch("decode", [(0, "t", "decode", None, 5), (1, "t", "decode", None, 40)])
    assert noted[-1]["window_kv_tokens"] == 5 + W and noted[-1]["kv_tokens"] == 45
    # the pool's gauge names each array: K is wider than V
    k, v = (METRICS.get("finchat_kv_pool_bytes", labels={"array": a}) for a in ("k", "v"))
    assert k == sched.engine.state.k_pages.nbytes and v == sched.engine.state.v_pages.nbytes
    assert 2 * k == 3 * v


# --- BOUND -----------------------------------------------------------------------

@pytest.mark.parametrize("first", [0, 64, 91, 200])
def test_a_window_of_one_page_holds_three_pages_a_row_whatever_the_chunks_start(first):
    """The cell's shapes (window 128 = ONE page of 128, chunks of 256): a row's
    bound is 3 pages; a chunk that starts mid-page is cut by ``room`` to what
    fits beside the window's oldest page, and one that is not cut is refused."""
    pager = WindowPager((32 + 4) * 3 + 1, 32, 128, 128)
    assert pager.per_row == window_pages_per_row(128, 128) == 3
    if first:
        pager.advance(0, 0, min(first, pager.room(0)))
    pos = first
    while pos < 4000:
        n = min(256, pager.room(pos))
        assert 0 < n and (pos % 128 or n == 256)  # a chunk from a page's first token is whole
        pager.advance(0, pos, n)
        pos += n
        assert len(pager.pages_of(0)) <= 3
    for _ in range(300):  # a token a step: the window's page and the one being written
        pager.advance(0, pos, 1)
        pos += 1
        assert len(pager.pages_of(0)) <= 2
    assert pager.gaps[0] == (pos - 128) // 128 * 128
    with pytest.raises(PageAllocationError, match="spans 4 window pages"):
        pager.advance(1, 200, 256)


# --- REFUSED ---------------------------------------------------------------------

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
    assert "a kind's own k / v stacks" in NOT_CARRIED["window pages"]["mesh.* > 1"]


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


@pytest.mark.parametrize("name", ["tiny", "moe-tiny", "trinity-tiny"])
def test_an_older_pattern_emits_the_tree_and_the_state_it_always_did(name):
    """Every new piece is data whose absent value emits nothing: no leaf by
    kind, no sink, pages of ONE width in both arrays of both pools."""
    c = PRESETS[name]
    assert not (c.attn_kinds or c.rope_dim or c.v_head_dim) and c.value_scale == 1.0
    tree = jax.eval_shape(lambda: init_params(c, jax.random.key(0)))
    stacks = [tree["layers"], tree.get("dense_layers", {})]
    assert not [leaf for stack in stacks for leaf in stack if leaf.startswith("swa_")]
    depth = c.n_kv_layers - c.n_leading_of(FULL) - c.n_leading_of(WINDOW)
    for leaf in ("attn_q", "attn_k", "attn_v", "attn_o"):  # ONE stack for both kinds
        assert tree["layers"][leaf].shape[0] == depth
    assert tree["layers"]["attn_k"].shape == tree["layers"]["attn_v"].shape
    cfg = EngineConfig(max_seqs=2, page_size=4, num_pages=16, max_seq_len=64, prefill_chunk=8)
    state = jax.eval_shape(lambda: create_state(c, cfg, 16))
    assert state.k_pages.shape == state.v_pages.shape
    if c.window:
        assert state.win_k_pages.shape == state.win_v_pages.shape
        assert state.win_k_pages.shape[-1] == state.k_pages.shape[-1]
    else:
        assert state.win_k_pages is None and len(jax.tree.leaves(state)) == 11
    # the same values too: a default's draw is what it was
    assert dataclasses.replace(LlamaConfig(), attn_kinds=()) == LlamaConfig()
