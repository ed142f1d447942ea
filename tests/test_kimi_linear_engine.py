"""Kimi-Linear's block on the SERVED paths (``tests/test_kimi_linear.py`` has the
rule): the first model that holds latent pages (2 layers) AND recurrent state
(7 layers) at once — ``prefill_step`` + ``decode_step`` and
``ragged_mixed_step`` against the reference's full forward by logits, on the
``ref`` backend and through the interpreted kernels (the vector-decay state
step, the latent walk with no selection, the touched-expert pass, the
append); a row admitted from a shared head's snapshot (latent pages + state +
conv tail) against one prefilled from the start; ``reset_slot`` and preemption
leaving neither behind; every ``NOT_CARRIED`` option refused, both kinds named.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import tiny_models

from finchat_tpu.engine import engine as engine_module
from finchat_tpu.engine.engine import NOT_CARRIED, InferenceEngine, ragged_mixed_step
from finchat_tpu.engine.kv_cache import page_hbm_bytes
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
from finchat_tpu.models import gdn, mla
from finchat_tpu.models.llama import FULL, LINEAR, forward_full, moe_mlp, n_params, rms_norm
from finchat_tpu.models.ssm import SsmRows
from finchat_tpu.ops import gdn_step
from finchat_tpu.ops import latent_attention as la
from finchat_tpu.utils.config import EngineConfig
from finchat_tpu.utils.metrics import METRICS
from perfbench.models import kimi_linear as kl

FILE = tiny_models.FILES["kimi_linear"]
CONFIG, PARAMS = tiny_models.build("kimi_linear")
PAGE, CHUNK, SLOTS = tiny_models.SHAPES["kimi_linear"]
TOL = 1e-3  # float32 against float32, logits of spread 1; a dropped term reads 0.05-1


@pytest.fixture(autouse=True)
def _wy_blocks_of_8_in_sub_blocks_of_4(monkeypatch):
    monkeypatch.setattr(gdn, "CHUNK", 8)
    monkeypatch.setattr(gdn, "SUB", 4)


def _tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 300, size=n)]


def _reference(tokens, positions, **kw):
    return np.asarray(kl.reference_logits(PARAMS, tokens, FILE, positions=positions, **kw)[0])


def _forward(tokens, config=CONFIG, params=PARAMS):
    n = len(tokens)
    return np.asarray(forward_full(params, jnp.asarray(tokens)[None], jnp.arange(n)[None],
                                   config=config, attn_backend="ref")[0])


def _engine(backend="ref", **options) -> InferenceEngine:
    cfg = EngineConfig(**{**dict(max_seqs=SLOTS, page_size=PAGE, num_pages=64, max_seq_len=256,
                                 prefill_chunk=CHUNK), **options})
    return InferenceEngine(CONFIG, PARAMS, cfg, attn_backend=backend)


def _decode(engine, slot_tokens: dict[int, int]) -> np.ndarray:
    active = np.zeros((SLOTS,), bool)
    for slot, token in slot_tokens.items():
        engine.set_last_token(slot, token)
        active[slot] = True
    _, logits = engine.decode(jnp.asarray(active), jnp.zeros((SLOTS,)), jnp.ones((SLOTS,)),
                              jnp.zeros((SLOTS,), jnp.int32), return_logits=True)
    return np.asarray(logits)


# --- SPLIT / RAGGED --------------------------------------------------------------

@pytest.mark.parametrize("prompt_len,backend", [(29, "ref"), (40, "pallas-interpret")])
def test_prefill_in_chunks_then_decode_token_by_token(prompt_len, backend):
    tokens = _tokens(prompt_len + 9, seed=prompt_len)
    want = _reference(tokens, list(range(prompt_len - 1, len(tokens))))
    engine = _engine(backend)
    engine.set_page_table_row(2, [5, 6, 7, 8])
    got = [np.asarray(engine.prefill(2, tokens[:prompt_len]))]
    got += [_decode(engine, {2: t})[2] for t in tokens[prompt_len:]]
    np.testing.assert_allclose(np.stack(got), want, atol=TOL)
    touched, read, selected = (int(n) for n in engine.moe_experts)
    assert selected == 2 * len(tokens)  # the two latent layers attend every token; KDA none
    assert 0 < touched <= 8 * 2 and read == (touched if backend != "ref" else 8 * 8)


def test_ragged_round_with_rows_at_both_ends_of_the_buffer():
    seqs = {0: _tokens(21, 1), 1: _tokens(CHUNK + 1, 2), 2: _tokens(2 * CHUNK + 1, 3),
            3: _tokens(10, 4)}
    engine = _engine(mixed_step=True)
    for slot in range(SLOTS):
        engine.set_page_table_row(slot, [1 + 3 * slot, 2 + 3 * slot, 3 + 3 * slot])
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
        **engine._ragged_kw())
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
        allocator = PageAllocator(64)

    tokens = _tokens(CHUNK * 3 // 2 + 9, seed=5)
    prompt, forced = tokens[:CHUNK * 3 // 2], tokens[CHUNK * 3 // 2:]
    want = _reference(tokens, list(range(len(prompt) - 1, len(tokens))))
    for i, got in correct._ragged_path_logits(Sched, prompt, forced):
        np.testing.assert_allclose(got[:300], want[i], atol=TOL)
    for got, w in zip(correct._split_path_logits(Sched, prompt, forced), want):
        np.testing.assert_allclose(got, w, atol=TOL)
    assert float(jnp.abs(Sched.engine.state.ssm_state).max()) == 0.0
    assert float(jnp.abs(Sched.engine.state.conv_state).max()) == 0.0


# --- SNAPSHOT / ADMISSION / RESET ---------------------------------------------------

HEAD = _tokens(2 * PAGE, seed=11)  # two whole pages: the shared head


def _scheduler(**options):
    return ContinuousBatchingScheduler(_engine(**options), eos_id=-1)


async def _stream(sched, prompt, n_new=6):
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
            await asyncio.sleep(0.05)  # the last round books at the next turn of the loop
            return got
        finally:
            await sched.stop()
    return asyncio.run(go())


@pytest.mark.parametrize("mixed", [False, True])
def test_a_row_admitted_from_a_heads_snapshot_streams_what_the_whole_row_streams(mixed):
    """Prefix reuse where 2 layers hold latent pages and 7 hold state: the row
    that starts from the head's pages AND a copy of its state and conv tail
    streams the tokens of the row that prefilled from its first token."""
    prompt = HEAD + _tokens(13, seed=12)
    _handle, whole = _run(_scheduler(mixed_step=mixed), prompt)
    sched = _scheduler(mixed_step=mixed)
    restores = METRICS.get("finchat_ssm_snapshot_restores_total")
    selected = METRICS.get("finchat_dsa_selected_tokens_total")
    steps = METRICS.get("finchat_dsa_row_layer_steps_total")
    assert sched.register_prefix(HEAD + [1, 2, 3]) == len(HEAD)
    assert float(jnp.abs(sched.engine.state.ssm_state).max()) == 0.0  # the head's slot went back clean
    snap = sched._prefixes[0].ssm_snap
    assert snap[0].shape == (7, 4, 16, 16) and snap[1].shape == (7, 3, 192)
    assert float(jnp.abs(snap[0]).max()) > 0.0
    handle, resumed = _run(sched, prompt)
    assert handle.shared_len == len(HEAD) and handle.span.state_restored_tokens == len(HEAD)
    assert METRICS.get("finchat_ssm_snapshot_restores_total") == restores + 1
    assert resumed == whole and len(whole) == 6
    # the step's count: the TWO latent layers' rows, every context token attended
    d_steps = METRICS.get("finchat_dsa_row_layer_steps_total") - steps
    d_selected = METRICS.get("finchat_dsa_selected_tokens_total") - selected
    assert d_steps > 0 and d_steps % 2 == 0
    assert len(prompt) < d_selected / d_steps <= len(prompt) + 6
    assert METRICS.get("finchat_kv_pool_bytes", labels={"array": "latent"}) \
        == sched.engine.state.k_pages.nbytes
    assert METRICS.get("finchat_ssm_state_bytes") == sched.engine.ssm_state_bytes > 0


@pytest.mark.parametrize("state_copied", [True, False])
def test_admission_from_a_head_copies_its_state_into_the_rows_slot(state_copied, monkeypatch):
    prompt = HEAD + _tokens(CHUNK, seed=12)
    sched = _scheduler()
    if not state_copied:
        monkeypatch.setattr(sched.engine, "ssm_restore", lambda slot, snap: None)
    assert sched.register_prefix(HEAD + [1, 2, 3]) == len(HEAD)
    snap = sched._prefixes[0].ssm_snap
    handle = asyncio.run(sched.submit(
        "seq", prompt, SamplingParams(temperature=0.0, max_new_tokens=4)))
    sched._admit()
    engine, slot = sched.engine, handle.slot
    assert slot >= 0 and handle.prefill_pos == len(HEAD)
    if state_copied:
        held = engine.ssm_snapshot(slot)
        np.testing.assert_array_equal(np.asarray(held[0]), np.asarray(snap[0]))
        np.testing.assert_array_equal(np.asarray(held[1]), np.asarray(snap[1]))
    got = np.asarray(engine.prefill_rows(
        jnp.asarray([prompt[len(HEAD):]], jnp.int32), jnp.asarray([slot], jnp.int32),
        jnp.asarray([len(HEAD)], jnp.int32), jnp.asarray([CHUNK], jnp.int32)))[0]
    off = float(np.abs(got - _reference(prompt, [len(prompt) - 1])[0]).max())
    assert off < TOL if state_copied else off > 20 * TOL
    sched._evict(handle, "error", error="test over")


@pytest.mark.parametrize("zeroed", [True, False])
def test_a_reset_slot_leaves_neither_pages_nor_state_behind(zeroed, monkeypatch):
    if not zeroed:  # the control: without the zeroing the next row is wrong
        monkeypatch.setattr(engine_module, "_ssm_clear_slots", lambda s, c, keep: (s, c))
    engine = _engine()
    engine.set_page_table_row(1, [3, 4])
    engine.prefill(1, _tokens(20, seed=8))
    engine.reset_slot(1)
    held = float(jnp.abs(engine.state.ssm_state[:, 1]).max()
                 + jnp.abs(engine.state.conv_state[:, 1]).max())
    assert int(engine.state.context_lens[1]) == 0 and not np.asarray(engine.state.page_table[1]).any()
    engine.set_page_table_row(1, [9, 10])
    tokens = _tokens(15, seed=9)
    off = float(np.abs(np.asarray(engine.prefill(1, tokens)) - _reference(tokens, [14])[0]).max())
    assert (held == 0.0 and off < TOL) if zeroed else (held > 0.0 and off > 20 * TOL)


def test_a_preempted_row_gives_back_pages_and_state_and_streams_its_answer_again():
    """Preemption through the scheduler: the row's latent pages go back, its
    slot's state is cleared at the next admission, and the row streams from
    its prompt (and what it had generated) again: the same answer."""
    prompt = _tokens(30, seed=21)
    _h, want = _run(_scheduler(), prompt)
    sched = _scheduler()

    async def go():
        await sched.start()
        try:
            handle = await sched.submit("seq", prompt, SamplingParams(temperature=0.0,
                                                                      max_new_tokens=6))
            tokens = []
            while True:
                event = await asyncio.wait_for(handle.events.get(), timeout=120)
                if event["type"] == "token":
                    tokens.append(event["token_id"])
                    if len(tokens) == 2:
                        sched._preempt(handle)
                elif event["type"] == "done":
                    await asyncio.sleep(0.05)
                    return tokens
                else:
                    raise AssertionError(event)
        finally:
            await sched.stop()

    got = asyncio.run(go())
    assert got == want, (got, want)
    assert sched.allocator.used_count == 0 and len(sched.free_slots) == SLOTS
    assert float(jnp.abs(sched.engine.state.ssm_state).max()) == 0.0


# --- REFUSED --------------------------------------------------------------------

OPTIONS = {
    "engine.kv_quant": ({"kv_quant": "int8"}, ""),
    "model.quant": ({}, "int8"),
    "engine.spec_tokens": ({"spec_tokens": 2}, ""),
    "engine.kv_sink_pages / engine.kv_window_pages": ({"kv_sink_pages": 1, "kv_window_pages": 4}, ""),
}


@pytest.mark.parametrize("option", sorted({o for kind in ("recurrent state", "latent pages")
                                           for o in NOT_CARRIED[kind]} - {"mesh.* > 1"}))
def test_every_option_that_either_kind_cannot_carry_is_refused_by_name(option):
    """Both rows of ``NOT_CARRIED`` apply to a model that holds latent pages
    AND recurrent state: each option of either is refused, and the refusal
    names the kind whose row it stands in."""
    engine_kw, quant = OPTIONS[option]
    kinds = [kind for kind in ("recurrent state", "latent pages") if option in NOT_CARRIED[kind]]
    cfg = EngineConfig(max_seqs=SLOTS, page_size=PAGE, num_pages=64, max_seq_len=256,
                       prefill_chunk=CHUNK, **engine_kw)
    with pytest.raises(ValueError) as refused:
        InferenceEngine(CONFIG, PARAMS, cfg, attn_backend="ref", quant=quant)
    said = str(refused.value)
    assert option.split(" / ")[0].split(".")[-1] in said
    assert any(f"a model with {kind}" in said for kind in kinds) or "kv_quant" in said


def test_a_mesh_the_fabric_and_the_disk_tier_are_refused_with_both_kinds_in_the_table(tmp_path):
    from finchat_tpu.engine.warm_fabric import WarmFabric
    from finchat_tpu.parallel.mesh import MeshSpec, build_mesh

    assert "mesh.* > 1" in NOT_CARRIED["recurrent state"] and "mesh.* > 1" in NOT_CARRIED["latent pages"]
    mesh = build_mesh(MeshSpec(data=1, pipe=1, seq=1, expert=1, model=2),
                      devices=jax.devices()[:2])
    cfg = EngineConfig(max_seqs=SLOTS, page_size=PAGE, num_pages=64, max_seq_len=256,
                       prefill_chunk=CHUNK)
    with pytest.raises(ValueError, match="mesh"):
        InferenceEngine(CONFIG, PARAMS, cfg, mesh=mesh, attn_backend="ref")
    with pytest.raises(ValueError, match="fabric.path"):
        ContinuousBatchingScheduler(_engine(), eos_id=-1,
                                    fabric=WarmFabric(str(tmp_path), 1 << 20))
    sched = _scheduler()
    assert sched.session_cache is None and sched.has_ssm  # the session tier is not built


@pytest.mark.parametrize("overrides,named", [
    ({"fleet.replicas": 2}, "fleet.replicas"),
    ({"pod.host_id": "host-a"}, "pod.host_id"),
])
def test_app_options_that_move_rows_between_engines_are_refused_by_name(overrides, named):
    from finchat_tpu.serve.app import make_engine_replica
    from finchat_tpu.utils.config import load_config

    cfg = load_config(None, {"model.preset": "tiny", **overrides})
    with pytest.raises(ValueError, match=named):
        make_engine_replica(cfg, (CONFIG, PARAMS, None, None))
