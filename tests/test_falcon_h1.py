"""A Mamba-2 mixer beside attention in every layer (``models/ssm.py``), its
recurrent state beside the paged KV in the engine's ``DecodeState``: the
program against the plain float32 reference of ``perfbench/models/
falcon_h1.py`` at a small size, seeded random weights, through every path
that carries the state — and the options that would not carry it refused.

- FORWARD: the cache-less forward (the chunked scan) equals the reference's
  token-by-token recurrence, whatever the chunk.
- SPLIT: ``prefill`` in chunks whose ends fall inside and across the scan's
  blocks, then ``decode`` token by token.
- RAGGED: prefill rows and decode rows packed in one buffer, a row that ends
  at the buffer's first token and one that starts at its last.
- SNAPSHOT: a row admitted from a shared head's snapshot streams what the
  same row prefilled whole streams; without the state's copy it does not.
- RESET: a slot reused after ``reset_slot`` carries nothing over; without the
  zeroing it does.
- ADMISSION: a cold row admitted into a slot left dirty starts from zero
  (admission owns the state); without ``ssm_admit`` it does not.
- REFUSED / FALLBACK: each option that would rewind or move a row without its
  state raises at load by name; what falls back recomputes and counts.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import tiny_models

from finchat_tpu.engine import engine as engine_module
from finchat_tpu.engine.engine import InferenceEngine, ragged_mixed_step
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
from finchat_tpu.models.llama import LlamaConfig, forward_full, init_params, n_params
from finchat_tpu.utils.config import EngineConfig
from finchat_tpu.utils.metrics import METRICS
from perfbench.models import falcon_h1

FILE = tiny_models.FILES["falcon_h1"]
CONFIG, PARAMS = tiny_models.build("falcon_h1")
PAGE, CHUNK, SLOTS = 16, 12, 4  # a prefill chunk of 12 against scan blocks of 8
TOL = 2e-5  # float32 against float32, logits of spread 0.06


def _tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 300, size=n)]


def _reference(tokens, positions):
    want, margins = falcon_h1.reference_logits(PARAMS, tokens, FILE, positions=positions)
    assert np.isinf(np.asarray(margins)).all()  # nothing is routed
    return np.asarray(want)


def _engine(**options) -> InferenceEngine:
    cfg = EngineConfig(max_seqs=SLOTS, page_size=PAGE, num_pages=64, max_seq_len=256,
                       prefill_chunk=CHUNK, **options)
    return InferenceEngine(CONFIG, PARAMS, cfg, attn_backend="ref")


def _decode(engine, slot_tokens: dict[int, int]) -> np.ndarray:
    """One ``decode_step`` feeding ``slot_tokens``; the step's logits."""
    active = np.zeros((SLOTS,), bool)
    for slot, token in slot_tokens.items():
        engine.set_last_token(slot, token)
        active[slot] = True
    _, logits = engine.decode(jnp.asarray(active), jnp.zeros((SLOTS,)), jnp.ones((SLOTS,)),
                              jnp.zeros((SLOTS,), jnp.int32), return_logits=True)
    return np.asarray(logits)


# --- FORWARD -------------------------------------------------------------------

def test_param_count_and_config():
    assert CONFIG.head_dim == 32 != CONFIG.dim // CONFIG.n_heads
    assert sum(x.size for x in jax.tree.leaves(PARAMS)) == n_params(CONFIG) \
        == falcon_h1.param_counts(FILE)["total"]
    # a config without the mixer is the config it always was
    assert LlamaConfig().head_dim == 32 and not LlamaConfig().ssm_heads


@pytest.mark.parametrize("chunk", [1, 5, 8, 64])
def test_forward_equals_the_reference_whatever_the_scan_block(chunk):
    tokens = _tokens(37)
    c = dataclasses.replace(CONFIG, ssm_chunk=chunk)
    got = forward_full(PARAMS, jnp.asarray(tokens)[None], jnp.arange(37)[None], config=c)[0]
    np.testing.assert_allclose(np.asarray(got), _reference(tokens, list(range(37))), atol=TOL)


def test_the_recurrence_reaches_far_back():
    """Published initialisation: the state neither dies in a token nor never
    (else every comparison here would be vacuous). With attention silenced,
    changing the FIRST token moves position 20 through the state alone."""
    tokens = _tokens(21)
    c = dataclasses.replace(CONFIG, attention_out_multiplier=0.0)  # attention silenced
    pos = jnp.arange(21)[None]
    a = forward_full(PARAMS, jnp.asarray(tokens)[None], pos, config=c)[0, -1]
    b = forward_full(PARAMS, jnp.asarray([tokens[0] + 1] + tokens[1:])[None], pos, config=c)[0, -1]
    assert 1e-4 < float(jnp.abs(a - b).max()) < 0.1


# --- SPLIT ---------------------------------------------------------------------

@pytest.mark.parametrize("prompt_len", [7, 12, 29, 40])
def test_prefill_in_chunks_then_decode_token_by_token(prompt_len):
    tokens = _tokens(prompt_len + 9, seed=prompt_len)
    want = _reference(tokens, list(range(prompt_len - 1, len(tokens))))
    engine = _engine()
    engine.set_page_table_row(2, [5, 6, 7, 8])
    got = [np.asarray(engine.prefill(2, tokens[:prompt_len]))]
    got += [_decode(engine, {2: t})[2] for t in tokens[prompt_len:]]
    np.testing.assert_allclose(np.stack(got), want, atol=TOL)


def test_rows_of_one_prefill_round_keep_their_own_state():
    """Three prompts of different lengths advance together; the short ones
    ride the later rounds inert (``n_valid`` 0) and keep their state."""
    prompts = [_tokens(n, seed=n) for n in (30, 5, 17)]
    engine = _engine()
    for slot in range(3):
        engine.set_page_table_row(slot, [1 + 3 * slot, 2 + 3 * slot, 3 + 3 * slot])
    last = engine.prefill_batch(list(enumerate(prompts)))
    nxt = _decode(engine, {slot: 7 for slot in range(3)})
    for slot, prompt in enumerate(prompts):
        want = _reference(prompt + [7], [len(prompt) - 1, len(prompt)])
        np.testing.assert_allclose(np.asarray(last[slot]), want[0], atol=TOL)
        np.testing.assert_allclose(nxt[slot], want[1], atol=TOL)


# --- RAGGED --------------------------------------------------------------------

def test_ragged_round_with_rows_at_both_ends_of_the_buffer():
    """One packed buffer of exactly 34 tokens: a decode row that ends at the
    buffer's first token, a prompt's first chunk, another prompt's second
    chunk, and a decode row that starts at the buffer's last token. Each row
    starts from its own slot's state and leaves its last state there: the
    next decode step of all four slots still equals the reference."""
    seqs = {0: _tokens(21, 1), 1: _tokens(CHUNK + 1, 2), 2: _tokens(2 * CHUNK + 1, 3),
            3: _tokens(10, 4)}
    engine = _engine(mixed_step=True)
    for slot in range(SLOTS):
        engine.set_page_table_row(slot, [1 + 3 * slot, 2 + 3 * slot, 3 + 3 * slot])
    # before the round: slots 0 and 3 hold all but their last token, slot 2
    # its first chunk
    engine.prefill(0, seqs[0][:-2])
    engine.prefill(3, seqs[3][:-2])
    engine.prefill(2, seqs[2][:CHUNK])
    engine.set_last_token(0, seqs[0][-2])
    engine.set_last_token(3, seqs[3][-2])
    packed = [0] + seqs[1][:CHUNK] + seqs[2][CHUNK:2 * CHUNK] + [0]
    tok_row = [0] + [1] * CHUNK + [2] * CHUNK + [3]
    dev = np.asarray([True, False, False, True])
    zeros_i = jnp.zeros((SLOTS,), jnp.int32)
    engine.state, _e, _n, row_logits = ragged_mixed_step(
        engine.params, engine.state, jnp.asarray(packed, jnp.int32),
        jnp.asarray(tok_row, jnp.int32), jnp.arange(SLOTS, dtype=jnp.int32),
        jnp.asarray([0, 0, CHUNK, 0], jnp.int32), jnp.asarray([1, CHUNK, CHUNK, 1], jnp.int32),
        jnp.asarray(dev), jnp.asarray(dev), zeros_i,
        jnp.zeros((SLOTS,)), jnp.ones((SLOTS,)), zeros_i,
        config=CONFIG, page_size=PAGE, attn_backend="ref",
        max_row_tokens=CHUNK)
    row_logits = np.asarray(row_logits)
    after = _decode(engine, {slot: seqs[slot][-1] for slot in range(SLOTS)})
    for slot, seq in seqs.items():
        want = _reference(seq, [len(seq) - 2, len(seq) - 1])
        np.testing.assert_allclose(row_logits[slot], want[0], atol=TOL, err_msg=f"row {slot}")
        np.testing.assert_allclose(after[slot], want[1], atol=TOL, err_msg=f"slot {slot}")


def test_ragged_padding_rows_leave_the_slot_they_repeat_alone():
    """A round's padding rows carry a live row's slot with length 0: they must
    not race its state (the packing of ``perfbench/correct.py``'s check and
    of the scheduler's rounds)."""
    from perfbench import correct

    class Sched:
        engine = _engine(mixed_step=True)
        free_slots = [0, 1, 2, 3]
        allocator = engine_module.PagedKVCache and __import__(
            "finchat_tpu.engine.kv_cache", fromlist=["PageAllocator"]).PageAllocator(64)

    tokens = _tokens(CHUNK * 3 // 2 + 9, seed=5)
    prompt, forced = tokens[:CHUNK * 3 // 2], tokens[CHUNK * 3 // 2:]
    want = _reference(tokens, list(range(len(prompt) - 1, len(tokens))))
    for i, got in correct._ragged_path_logits(Sched, prompt, forced):
        np.testing.assert_allclose(got[:300], want[i], atol=TOL)
    for got, w in zip(correct._split_path_logits(Sched, prompt, forced), want):
        np.testing.assert_allclose(got, w, atol=TOL)
    # both checks gave their slots back clean
    assert float(jnp.abs(Sched.engine.state.ssm_state).max()) == 0.0
    assert float(jnp.abs(Sched.engine.state.conv_state).max()) == 0.0


# --- RESET ---------------------------------------------------------------------

@pytest.mark.parametrize("zeroed", [True, False])
def test_a_reused_slot_carries_nothing_over(zeroed, monkeypatch):
    if not zeroed:  # the control: without the zeroing the next row is wrong
        monkeypatch.setattr(engine_module, "_ssm_clear_slots", lambda s, c, keep: (s, c))
    engine = _engine()
    engine.set_page_table_row(1, [3, 4])
    engine.prefill(1, _tokens(20, seed=8))
    engine.reset_slot(1)
    held = float(jnp.abs(engine.state.ssm_state[:, 1]).max()
                 + jnp.abs(engine.state.conv_state[:, 1]).max())
    engine.set_page_table_row(1, [9, 10])
    tokens = _tokens(15, seed=9)
    got = np.asarray(engine.prefill(1, tokens))
    off = float(np.abs(got - _reference(tokens, [14])[0]).max())
    assert (held == 0.0 and off < TOL) if zeroed else (held > 0.0 and off > 100 * TOL)


# --- SNAPSHOT ------------------------------------------------------------------

HEAD = _tokens(2 * PAGE, seed=11)  # two whole pages: the shared head


def _scheduler(**options):
    engine = _engine(**options)
    return ContinuousBatchingScheduler(engine, eos_id=-1)


async def _stream(sched, prompt, n_new=6, conversation_id=None):
    handle = await sched.submit("seq", prompt, SamplingParams(temperature=0.0, max_new_tokens=n_new),
                                conversation_id=conversation_id, trace_id="t-1")
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
            return await _stream(sched, prompt, **kw)
        finally:
            await sched.stop()
    return asyncio.run(go())


def _greedy_reference(prompt, n_new):
    tokens = list(prompt)
    for _ in range(n_new):
        tokens.append(int(np.argmax(_reference(tokens, [len(tokens) - 1])[0])))
    return tokens[len(prompt):]


@pytest.mark.parametrize("mixed", [False, True])
def test_a_row_admitted_from_a_heads_snapshot_streams_what_the_whole_row_streams(mixed):
    prompt = HEAD + _tokens(13, seed=12)
    want = _greedy_reference(prompt, 6)
    _handle, whole = _run(_scheduler(mixed_step=mixed), prompt)
    assert whole == want
    sched = _scheduler(mixed_step=mixed)
    before = METRICS.get("finchat_ssm_snapshot_restores_total")
    assert sched.register_prefix(HEAD + [1, 2, 3]) == len(HEAD)
    # the head's slot went back clean
    assert float(jnp.abs(sched.engine.state.ssm_state).max()) == 0.0
    handle, resumed = _run(sched, prompt)
    assert handle.shared_len == len(HEAD) and handle.span.state_restored_tokens == len(HEAD)
    assert METRICS.get("finchat_ssm_snapshot_restores_total") == before + 1
    assert resumed == want


@pytest.mark.parametrize("state_copied", [True, False])
def test_admission_from_a_head_copies_its_state_into_the_rows_slot(state_copied, monkeypatch):
    """``_admit`` itself, the loop not running: the admitted row's next chunk
    gives the reference's logits — and, the control, does not when the pages
    are referenced but the state is left at zero."""
    prompt = HEAD + _tokens(CHUNK, seed=12)
    sched = _scheduler()
    if not state_copied:
        monkeypatch.setattr(sched.engine, "ssm_restore", lambda slot, snap: None)
    assert sched.register_prefix(HEAD + [1, 2, 3]) == len(HEAD)
    snap = sched._prefixes[0].ssm_snap
    assert float(jnp.abs(snap[0]).max()) > 0.0
    handle = asyncio.run(sched.submit(
        "seq", prompt, SamplingParams(temperature=0.0, max_new_tokens=4)))
    sched._admit()
    engine, slot = sched.engine, handle.slot
    assert slot >= 0 and handle.prefill_pos == len(HEAD)
    held = engine.ssm_snapshot(slot)
    if state_copied:
        np.testing.assert_array_equal(np.asarray(held[0]), np.asarray(snap[0]))
        np.testing.assert_array_equal(np.asarray(held[1]), np.asarray(snap[1]))
    got = np.asarray(engine.prefill_rows(
        jnp.asarray([prompt[len(HEAD):]], jnp.int32), jnp.asarray([slot], jnp.int32),
        jnp.asarray([len(HEAD)], jnp.int32), jnp.asarray([CHUNK], jnp.int32)))[0]
    off = float(np.abs(got - _reference(prompt, [len(prompt) - 1])[0]).max())
    assert off < TOL if state_copied else off > 100 * TOL
    sched._evict(handle, "error", error="test over")


@pytest.mark.parametrize("admission_clears", [True, False])
def test_a_cold_row_starts_from_zero_whatever_its_slot_was_left_with(admission_clears, monkeypatch):
    """Admission owns the state: a slot whose release-time reset did not go
    through (here: every slot left dirty) still starts a row without a head
    from zero — and, the control, does not when ``_admit`` leaves it alone."""
    sched = _scheduler()
    engine = sched.engine
    engine.state = dataclasses.replace(
        engine.state, ssm_state=jnp.ones_like(engine.state.ssm_state),
        conv_state=jnp.ones_like(engine.state.conv_state))
    if not admission_clears:
        monkeypatch.setattr(engine, "ssm_admit", lambda rows: None)
    prompt = _tokens(CHUNK, seed=17)
    handle = asyncio.run(sched.submit(
        "seq", prompt, SamplingParams(temperature=0.0, max_new_tokens=4)))
    sched._admit()
    slot = handle.slot
    held = engine.ssm_snapshot(slot)
    dirty = float(jnp.abs(held[0]).max() + jnp.abs(held[1]).max())
    got = np.asarray(engine.prefill_rows(
        jnp.asarray([prompt], jnp.int32), jnp.asarray([slot], jnp.int32),
        jnp.asarray([0], jnp.int32), jnp.asarray([CHUNK], jnp.int32)))[0]
    off = float(np.abs(got - _reference(prompt, [len(prompt) - 1])[0]).max())
    assert (dirty == 0.0 and off < TOL) if admission_clears else (dirty > 0.0 and off > 100 * TOL)
    # the other slots are not admission's to touch
    other = next(s for s in range(engine.engine_cfg.max_seqs) if s != slot)
    assert float(jnp.abs(engine.state.ssm_state[:, other]).min()) == 1.0
    sched._evict(handle, "error", error="test over")


def test_a_head_registered_while_the_loop_runs_keeps_its_snapshot_too():
    sched = _scheduler(mixed_step=True)
    prompt = HEAD + _tokens(9, seed=13)

    async def go():
        await sched.start()
        try:
            assert await sched.register_prefix_async(HEAD + [4]) == len(HEAD)
            return await _stream(sched, prompt)
        finally:
            await sched.stop()

    handle, resumed = asyncio.run(go())
    assert handle.shared_len == len(HEAD) and resumed == _greedy_reference(prompt, 6)


# --- FALLBACK ------------------------------------------------------------------

def test_what_cannot_start_from_a_snapshot_recomputes_and_counts():
    """A prompt that shares only part of a head, and a turn the session tier
    would have resumed: both are recomputed from their tokens, counted, and
    right."""
    sched = _scheduler(mixed_step=True)
    assert sched.session_cache is None  # its entries hold no state
    assert sched.register_prefix(HEAD + [1]) == len(HEAD)
    fallbacks = lambda: METRICS.get("finchat_ssm_recompute_fallbacks_total")  # noqa: E731
    before = fallbacks()
    short = HEAD[:PAGE + 3]  # one whole page of the head, then its own tokens
    handle, got = _run(sched, short)
    assert handle.shared_len == 0 and got == _greedy_reference(short, 6)
    assert fallbacks() == before + 1
    prompt = HEAD + _tokens(5, seed=14)
    _handle, turn1 = _run(sched, prompt, conversation_id="conv")
    turn2_prompt = prompt + turn1 + _tokens(4, seed=15)
    handle, turn2 = _run(sched, turn2_prompt, conversation_id="conv")
    assert handle.resumed_len == 0 and handle.shared_len == len(HEAD)
    assert turn2 == _greedy_reference(turn2_prompt, 6)
    assert fallbacks() == before + 3


def test_preempt_replay_recomputes_the_row_from_its_tokens():
    sched = _scheduler(mixed_step=True)
    prompt = _tokens(20, seed=16)

    async def go():
        await sched.start()
        try:
            handle = await sched.submit(
                "seq", prompt, SamplingParams(temperature=0.0, max_new_tokens=8))
            tokens = []
            while True:
                event = await asyncio.wait_for(handle.events.get(), timeout=120)
                if event["type"] == "token":
                    tokens.append(event["token_id"])
                    if len(tokens) == 3 and not handle.preempted:
                        sched._preempt(handle)
                elif event["type"] == "done":
                    return handle, tokens
        finally:
            await sched.stop()

    handle, tokens = asyncio.run(go())
    assert handle.preempted == 1 and tokens == _greedy_reference(prompt, 8)


def test_rebuild_device_state_starts_every_slot_from_zero():
    engine = _engine()
    engine.set_page_table_row(0, [1, 2])
    engine.prefill(0, _tokens(20, seed=17))
    assert float(jnp.abs(engine.state.ssm_state).max()) > 0.0
    shapes = jax.tree.map(lambda x: (x.shape, x.dtype), engine.state)
    engine.rebuild_device_state()
    assert jax.tree.map(lambda x: (x.shape, x.dtype), engine.state) == shapes
    assert float(jnp.abs(engine.state.ssm_state).max()) == 0.0


# --- REFUSED -------------------------------------------------------------------

@pytest.mark.parametrize("options,named", [
    ({"spec_tokens": 2}, "engine.spec_tokens"),
    ({"kv_sink_pages": 1, "kv_window_pages": 4}, "engine.kv_sink_pages"),
])
def test_engine_options_that_would_not_carry_the_state_are_refused_by_name(options, named):
    with pytest.raises(ValueError, match=named):
        _engine(**options)


def test_a_mesh_is_refused():
    from finchat_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=1, pipe=1, seq=1, expert=1, model=2),
                      devices=jax.devices()[:2])
    cfg = EngineConfig(max_seqs=SLOTS, page_size=PAGE, num_pages=64, max_seq_len=256,
                       prefill_chunk=CHUNK)
    with pytest.raises(ValueError, match="mesh"):
        InferenceEngine(CONFIG, PARAMS, cfg, mesh=mesh, attn_backend="ref")


def test_the_fabric_is_refused_by_the_scheduler(tmp_path):
    from finchat_tpu.engine.warm_fabric import WarmFabric

    fabric = WarmFabric(str(tmp_path), 1 << 20)
    with pytest.raises(ValueError, match="fabric.path"):
        ContinuousBatchingScheduler(_engine(), eos_id=-1, fabric=fabric)


@pytest.mark.parametrize("overrides,named", [
    ({"fleet.replicas": 2}, "fleet.replicas"),
    ({"fleet.replicas": 2, "fleet.roles": "prefill,decode"}, "fleet.roles"),
    ({"pod.host_id": "host-a"}, "pod.host_id"),
])
def test_app_options_that_move_rows_between_engines_are_refused_by_name(overrides, named):
    from finchat_tpu.serve.app import make_engine_replica
    from finchat_tpu.utils.config import load_config

    cfg = load_config(None, {"model.preset": "tiny", **overrides})
    with pytest.raises(ValueError, match=named):
        make_engine_replica(cfg, (CONFIG, PARAMS, None, None))


def test_a_step_that_does_not_carry_the_state_raises_instead_of_running():
    engine = _engine()
    B = SLOTS
    with pytest.raises(NotImplementedError, match="ssm_cache"):
        engine_module.verify_step(
            engine.params, engine.state, jnp.zeros((B,), bool), jnp.zeros((B, 2), jnp.int32),
            jnp.zeros((B,), jnp.int32), jnp.ones((B,)), jnp.ones((B,)),
            jnp.zeros((B,), jnp.int32), config=CONFIG, page_size=PAGE, attn_backend="ref")


# --- the block without the mixer is the block it was ----------------------------

def test_a_model_without_a_mixer_keeps_placeholders_and_its_programs():
    from finchat_tpu.models.llama import PRESETS

    c = PRESETS["tiny"]
    cfg = EngineConfig(max_seqs=2, page_size=8, num_pages=16, max_seq_len=64, prefill_chunk=8)
    engine = InferenceEngine(c, init_params(c, jax.random.key(0)), cfg, attn_backend="ref")
    assert engine.state.ssm_state.shape == (1, 1, 1, 1, 1) and engine.ssm_state_bytes == 0
    assert engine.ssm_snapshot(0) is None and engine._ragged_kw() == {}
    engine.reset_slot(0)  # no state program runs for it
    hlo = engine_module.decode_step.lower(
        engine.params, engine.state, jnp.zeros((2,), bool), jnp.ones((2,)), jnp.ones((2,)),
        jnp.zeros((2,), jnp.int32), config=c, page_size=8,
        attn_backend="ref").as_text(debug_info=True)
    assert not any(scope in hlo for scope in ("ssm_in", "ssm_conv", "ssm_scan", "ssm_out"))
