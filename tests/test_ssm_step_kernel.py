"""``ops/ssm_step.py``: the one-token Mamba-2 state update as one in-place
pass over the carried state, interpreted on the CPU (Mosaic's own rules for
the cell's shape: ``tests/test_tpu_compile.py``; values and time on the chip:
``chip_smoke.check_ssm_step_at_cell_shape``).

- KERNEL: against ``models/ssm.py``'s ``_step`` — ``y`` and the layer's new
  state to float32 round-off; a row with ``dt = 0`` and every other layer bit
  for bit; whatever the block of rows and however the heads fall into groups
  and halves; in BOTH forms (``tile_heads``: a head's ``[P, Ns]`` as the
  recurrence writes it, or pairs of heads ``[Ns, 2 P]`` where two heads make
  one lane tile — Granite's ``[128, 64, 128]``, a Nemotron-like ``[64, 64,
  128]`` at 8 groups; an odd head count at P = 64 keeps today's body); the
  state's buffer donated; Falcon-H1's call traces the program it traced
  before the pairs (a hash of its jaxpr).
- LAYOUT: ``logical -> stored -> logical`` bit for bit, the form by the static
  shapes alone.
- MIXER: the kernel is taken where the input shows that it applies (one
  token, a carried cache, the whole slot batch, a kernel backend) and nowhere
  else; a one-token update over gathered slots counts as a fallback.
- ENGINE: ``decode_step`` of the tiny Falcon-H1 config through the interpreted
  kernel against the ``ref`` backend over several tokens; and a Granite block
  whose heads ARE stored as pairs (4 heads of 64 x 128, two groups) against the
  plain reference of ``perfbench/models/granitemoehybrid.py``: chunked prefill,
  a ragged round, then decode steps, on ``ref`` and through the kernel; a
  head's snapshot is logical, and restored into another slot it is the donor's.
"""

import dataclasses
import hashlib
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_models

from finchat_tpu.engine.engine import InferenceEngine, ragged_mixed_step
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
from finchat_tpu.models import ssm
from finchat_tpu.ops import ssm_step
from finchat_tpu.utils.config import EngineConfig
from finchat_tpu.utils.metrics import METRICS
from perfbench.models import granitemoehybrid as granite
from tests.test_falcon_h1 import CONFIG, PAGE, PARAMS, SLOTS, _decode, _tokens

BASE = dict(rows=4, heads=4, groups=2, head_dim=16, state=128, layers=3, layer=1,
            inert=(), block_rows=2)
CASES = {
    "two_groups_of_two_heads": {},
    "an_inert_row": {"inert": (2,)},
    "every_row_inert": {"inert": (0, 1, 2, 3)},
    "first_layer": {"layer": 0},
    "last_layer": {"layer": 2},
    "one_layer": {"layers": 1, "layer": 0},
    "one_block": {"block_rows": 4},
    "a_row_a_block": {"block_rows": 1},
    "halves_cut_a_group": {"heads": 6, "groups": 3},
    "odd_heads": {"heads": 5, "groups": 1},
    "six_rows_in_blocks_of_three": {"rows": 6, "block_rows": 3},
    "a_row_larger_than_a_block": {"block_rows": 0},
    # two heads make one lane tile: pairs [Ns, 2 P], the state axis on sublanes
    "pairs_of_64": {"head_dim": 64, "pairs": True},
    "pairs_an_inert_row": {"head_dim": 64, "inert": (2,), "pairs": True},
    "pairs_in_groups_of_two_heads": {"heads": 8, "groups": 4, "head_dim": 64, "pairs": True},
    "pairs_halves_cut_a_group": {"heads": 12, "groups": 3, "head_dim": 64, "pairs": True},
    "pairs_a_row_a_block": {"head_dim": 64, "block_rows": 1, "pairs": True},
    "pairs_a_state_of_two_lane_tiles": {"head_dim": 64, "state": 256, "groups": 1, "pairs": True},
    "granites_heads": {"rows": 2, "heads": 128, "groups": 1, "head_dim": 64, "layers": 2,
                       "inert": (1,), "pairs": True},
    "nemotron_like_heads_in_8_groups": {"rows": 2, "heads": 64, "groups": 8, "head_dim": 64,
                                        "layers": 2, "pairs": True},
    # ... and where they cannot be paired, the body that was there
    "an_odd_head_count_at_64": {"heads": 5, "groups": 1, "head_dim": 64},
    "an_odd_head_count_a_group": {"heads": 6, "groups": 2, "head_dim": 64},
    "a_state_short_of_a_lane_tile": {"head_dim": 64, "state": 64},
}


def _inputs(rows, heads, groups, head_dim, state, layers, inert, seed=0):
    ks = jax.random.split(jax.random.key(seed), 7)
    f32 = jnp.float32
    dt = jax.nn.softplus(jax.random.normal(ks[2], (rows, heads), f32))
    for row in inert:
        dt = dt.at[row].set(0.0)
    return dict(
        ssm_state=jax.random.normal(ks[0], (layers, rows, heads, head_dim, state), f32),
        xs=jax.random.normal(ks[1], (rows, heads, head_dim), f32), dt=dt,
        A=-jnp.exp(jax.random.normal(ks[3], (heads,), f32)),
        Bm=jax.random.normal(ks[4], (rows, groups, state), f32),
        Cm=jax.random.normal(ks[5], (rows, groups, state), f32),
        D=jax.random.normal(ks[6], (heads,), f32))


def _reference(i, layer, groups):
    """``_step`` on the layer's slice, in the mixer's own shapes."""
    _layers, rows, heads, head_dim, state = i["ssm_state"].shape
    hg = heads // groups
    y, new = ssm._step(
        i["ssm_state"][layer].reshape(rows, groups, hg, head_dim, state),
        i["xs"].reshape(rows, groups, hg, head_dim), i["dt"].reshape(rows, groups, hg),
        i["A"].reshape(groups, hg), i["Bm"], i["Cm"], i["D"].reshape(groups, hg))
    return np.asarray(y).reshape(rows, heads, head_dim), np.asarray(new).reshape(
        rows, heads, head_dim, state)


@pytest.mark.parametrize("case", CASES)
def test_kernel_equals_step_and_touches_nothing_else(case, monkeypatch):
    p = {"pairs": False, **BASE, **CASES[case]}
    layer, inert, groups = min(p["layer"], p["layers"] - 1), p["inert"], p["groups"]
    heads = (p["heads"], p["head_dim"], p["state"])
    assert ssm_step.tile_heads(*heads, groups) == (2 if p["pairs"] else 1)
    row_bytes = p["heads"] * p["head_dim"] * p["state"] * 4
    monkeypatch.setattr(ssm_step, "_BLOCK_BYTES", p["block_rows"] * row_bytes)
    assert ssm_step.rows_per_block(p["rows"], row_bytes) == max(1, p["block_rows"])
    i = _inputs(p["rows"], p["heads"], groups, p["head_dim"], p["state"], p["layers"], inert)
    want_y, want_new = _reference(i, layer, groups)
    before = np.asarray(i["ssm_state"])
    # a fresh function: the block size is read when the wrapper is traced
    step = jax.jit(ssm_step.ssm_state_step.__wrapped__, static_argnames=("interpret",),
                   donate_argnums=(0,))
    stored = ssm_step.to_stored(i["ssm_state"], groups)
    assert stored.shape[2:] == ssm_step.stored_shape(*heads, groups)
    y, after = step(stored, i["xs"], i["dt"], i["A"], i["Bm"], i["Cm"], i["D"],
                    jnp.asarray([layer], jnp.int32), interpret=True)
    assert after.shape == stored.shape
    after = np.asarray(ssm_step.to_logical(after, heads, groups))
    # float32 round-off: y sums `state` products in another order than _step
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(after[layer], want_new, rtol=1e-6, atol=1e-5)
    others = [at for at in range(p["layers"]) if at != layer]
    np.testing.assert_array_equal(after[others], before[others])
    for row in inert:
        np.testing.assert_array_equal(after[layer, row], before[layer, row])
    live = [row for row in range(p["rows"]) if row not in inert]
    assert not live or not np.array_equal(after[layer, live], before[layer, live])


@pytest.mark.parametrize("head_dim", [16, 64], ids=["heads", "pairs"])
def test_the_state_is_donated_and_comes_back_in_its_own_buffer(head_dim):
    """On the CPU the interpreter copies, so the contract that can be checked
    here is the wrapper's: the state argument is donated (deleted after the
    call) and the compiled call aliases it to the output."""
    i = _inputs(4, 4, 2, head_dim, 128, 2, ())
    args = (i["xs"], i["dt"], i["A"], i["Bm"], i["Cm"], i["D"], jnp.asarray([1], jnp.int32))
    state = ssm_step.to_stored(i["ssm_state"], 2) + 0.0  # (a buffer of its own in both forms)
    lowered = ssm_step.ssm_state_step.lower(state, *args, interpret=True)
    assert "tf.aliasing_output" in lowered.as_text() or "jax.buffer_donor" in lowered.as_text()
    _y, after = ssm_step.ssm_state_step(state, *args, interpret=True)
    assert state.is_deleted() and after.shape == state.shape


def _program_hash(fn, *shapes) -> str:
    """The traced program of ``fn`` at ``shapes``, as text with file paths and
    line numbers stripped (the lowered text carries line numbers and is no
    yardstick: PERF.md section 6, PR 49)."""
    text = str(jax.make_jaxpr(fn)(*shapes))
    text = re.sub(r"[^\s\"'(]*\.py\b(:\d+)*", "", text)
    return hashlib.sha256(re.sub(r" at 0x[0-9a-f]+", "", text).encode()).hexdigest()[:16]


def test_falcon_h1s_call_traces_the_program_it_traced_before_the_pairs():
    """``[5, 16, 32, 128, 256]`` at two groups, the cell's call: the hash is
    the parent commit's (89b5090), taken there by this function. A change to
    ``_step_kernel``, ``in_place_pass`` or ``in_place_call`` moves it — then
    measure Falcon-H1's cell and take the new hash; the pairs must not."""
    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype)

    n, H, P, Ns, G = 16, 32, 128, 256, 2
    assert ssm_step.tile_heads(H, P, Ns, G) == 1 and ssm_step.stored_shape(H, P, Ns, G) == (H, P, Ns)
    assert _program_hash(
        ssm_step.ssm_state_step, shape(5, n, H, P, Ns), shape(n, H, P), shape(n, H), shape(H),
        shape(n, G, Ns), shape(n, G, Ns), shape(H), shape(1, dtype=jnp.int32)) == "5254d670d6630720"


# --- LAYOUT --------------------------------------------------------------------

LAYOUTS = {
    # (H, P, Ns, G) -> heads a tile, one slot's state as the device holds it
    "granite": ((128, 64, 128, 1), 2, (64, 128, 128)),
    "nemotron_like": ((64, 64, 128, 8), 2, (32, 128, 128)),
    "a_state_of_256": ((8, 64, 256, 2), 2, (4, 256, 128)),
    "falcon_h1": ((32, 128, 256, 2), 1, (32, 128, 256)),
    "an_odd_head_count": ((5, 64, 128, 1), 1, (5, 64, 128)),
    "an_odd_head_count_a_group": ((6, 64, 128, 2), 1, (6, 64, 128)),
    "one_pair_is_one_tile": ((2, 64, 128, 1), 1, (2, 64, 128)),  # the pipeline wants two halves
    "the_tiny_configs": ((8, 16, 16, 1), 1, (8, 16, 16)),
}


@pytest.mark.parametrize("case", LAYOUTS)
def test_the_stored_layout_follows_from_the_static_shapes_and_round_trips(case):
    (H, P, Ns, G), n, stored = LAYOUTS[case]
    assert ssm_step.tile_heads(H, P, Ns, G) == n
    assert ssm_step.stored_shape(H, P, Ns, G) == stored and np.prod(stored) == H * P * Ns
    state = jax.random.normal(jax.random.key(5), (2, 3, H, P, Ns), jnp.float32)
    there = ssm_step.to_stored(state, G)
    assert there.shape == (2, 3, *stored)
    np.testing.assert_array_equal(ssm_step.to_logical(there, (H, P, Ns), G), state)
    if n == 2:  # head 2 t + j of a pair lies in lanes [j P, (j + 1) P) of tile t, state rows down
        np.testing.assert_array_equal(there[1, 2, 3, :, P:], state[1, 2, 7].T)
    else:
        assert there is state


# --- MIXER ---------------------------------------------------------------------

def _mixer(rows: ssm.SsmRows, width: int = 1, cache: bool = True):
    """One layer's mixer over ``width`` tokens a slot from a seeded cache;
    (output, new ssm_state or None, the HLO-level text of the traced call)."""
    c, n = CONFIG, SLOTS
    lp = jax.tree.map(lambda x: x[1], PARAMS["layers"])
    ks = jax.random.split(jax.random.key(3), 3)
    h = jax.random.normal(ks[0], (n, width, c.dim), jnp.float32)
    carried = (jax.random.normal(ks[1], (c.n_layers, n, c.ssm_heads, c.ssm_head_dim,
                                         c.ssm_state), jnp.float32),
               jax.random.normal(ks[2], (c.n_layers, n, c.ssm_conv - 1, c.ssm_conv_dim),
                                 jnp.float32)) if cache else None

    def run(h, carried):
        return ssm.mixer(h, lp, c, carried, jnp.asarray(1, jnp.int32), rows)

    text = str(jax.make_jaxpr(run)(h, carried))
    out, new = run(h, carried)
    return np.asarray(out), None if new is None else np.asarray(new[0]), text


MIXER_CASES = {
    # (slots given, tokens a row, cache carried, backend) -> kernel taken, fallback counted
    "decode_step_on_a_kernel_backend": ((False, 1, True, "pallas-interpret"), True, False),
    "decode_step_on_ref": ((False, 1, True, "ref"), False, False),
    "gathered_slots_on_a_kernel_backend": ((True, 1, True, "pallas-interpret"), False, True),
    "gathered_slots_on_ref": ((True, 1, True, "ref"), False, False),
    "a_chunk_of_tokens": ((False, 3, True, "pallas-interpret"), False, False),
    "no_cache": ((False, 1, False, "pallas-interpret"), False, False),
}


@pytest.mark.parametrize("case", MIXER_CASES)
def test_mixer_takes_the_kernel_only_where_the_input_shows_it_applies(case):
    (gathered, width, cache, backend), kernel, fallback = MIXER_CASES[case]
    n_valid = jnp.asarray([width, width, 0, width], jnp.int32)  # slot 2 rides inert
    slots = jnp.arange(SLOTS, dtype=jnp.int32) if gathered else None
    counted = METRICS.get("finchat_ssm_step_fallbacks_total")
    out, state, text = _mixer(ssm.SsmRows(slots, n_valid, backend=backend), width, cache)
    assert ("pallas_call" in text) == kernel
    assert (METRICS.get("finchat_ssm_step_fallbacks_total") > counted) == fallback
    want_out, want_state, _ = _mixer(ssm.SsmRows(slots, n_valid), width, cache)
    np.testing.assert_allclose(out, want_out, atol=2e-5)
    if cache:
        np.testing.assert_allclose(state, want_state, rtol=1e-6, atol=1e-5)


# --- ENGINE --------------------------------------------------------------------

def test_decode_step_through_the_kernel_equals_the_ref_backend():
    """Two rows prefilled, then six decode steps feeding the same tokens:
    logits of every step and the recurrent state at the end, the interpreted
    kernels (attention, append and the state update) against ``ref``; a slot
    that never decodes keeps its state bit for bit."""
    prompts = {0: _tokens(21, seed=1), 3: _tokens(9, seed=2)}
    feed = [{0: a, 3: b} for a, b in zip(_tokens(6, seed=3), _tokens(6, seed=4))]
    logits, states = {}, {}
    for backend in ("ref", "pallas-interpret"):
        cfg = EngineConfig(max_seqs=SLOTS, page_size=PAGE, num_pages=64, max_seq_len=256,
                           prefill_chunk=12)
        engine = InferenceEngine(CONFIG, PARAMS, cfg, attn_backend=backend)
        for slot, prompt in prompts.items():
            engine.set_page_table_row(slot, [1 + 4 * slot + k for k in range(4)])
            engine.prefill(slot, prompt)
        engine.state = dataclasses.replace(
            engine.state, ssm_state=engine.state.ssm_state.at[:, 1].set(0.5))
        logits[backend] = np.stack([_decode(engine, step)[[0, 3]] for step in feed])
        states[backend] = np.asarray(engine.state.ssm_state)
    np.testing.assert_allclose(logits["pallas-interpret"], logits["ref"], atol=2e-5)
    np.testing.assert_allclose(states["pallas-interpret"], states["ref"], rtol=1e-5, atol=1e-5)
    assert np.abs(states["ref"][:, [0, 3]]).max() > 0.01
    assert (states["pallas-interpret"][:, 1] == 0.5).all()


# --- ENGINE, the heads stored as pairs -------------------------------------------

# Granite's block with a head of 64 x 128 (the published head, four of them in
# two groups: one pair a group), two mamba layers in front of the attention
# layer and one behind it
PAIRS_CHANGES = dict(hidden_size=128, num_hidden_layers=4,
                     layer_types=("mamba", "mamba", "attention", "mamba"),
                     mamba_n_heads=4, mamba_d_head=64, mamba_d_state=128, mamba_n_groups=2)
PAIRS_FILE = tiny_models.FILES["granite_hybrid"] | PAIRS_CHANGES
PAIRS = dataclasses.replace(granite.program_config(PAIRS_FILE), dtype=jnp.float32)
CHUNK = 12


def _pairs_params():
    config, params = tiny_models.build("granite_hybrid", **PAIRS_CHANGES)  # (built once)
    assert config == PAIRS
    return params


def _pairs_engine(backend: str) -> InferenceEngine:
    cfg = EngineConfig(max_seqs=SLOTS, page_size=PAGE, num_pages=64, max_seq_len=256,
                       prefill_chunk=CHUNK, mixed_step=True)
    return InferenceEngine(PAIRS, _pairs_params(), cfg, attn_backend=backend)


def _pairs_reference(tokens, positions):
    want, _margins = granite.reference_logits(_pairs_params(), tokens, PAIRS_FILE, positions=positions)
    return np.asarray(want)


def test_the_pairs_config_stores_pairs_and_says_so():
    assert PAIRS.state_shape == (4, 64, 128) and PAIRS.stored_state_shape == (2, 128, 128)
    assert CONFIG.stored_state_shape == CONFIG.state_shape  # Falcon-H1's tiny block: as it was
    forms = {backend: _pairs_engine(backend) for backend in ("ref", "pallas-interpret")}
    assert forms["pallas-interpret"].state.ssm_state.shape == (3, SLOTS, 2, 128, 128)
    assert forms["ref"].state.ssm_state.shape == (3, SLOTS, 2, 128, 128)  # one layout, every backend
    # the decode dispatch's annotation names the tile where a kernel takes it
    assert (forms["pallas-interpret"].state_form, forms["ref"].state_form) == ("pairs", None)
    falcon = InferenceEngine(CONFIG, PARAMS, EngineConfig(max_seqs=SLOTS, page_size=PAGE,
                                                          num_pages=8, max_seq_len=64),
                             attn_backend="pallas-interpret")
    assert falcon.state_form == "heads"
    # ... as ``state_form`` beside ``rows``, an argument only where the engine has one
    for engine, noted in ((forms["pallas-interpret"], {"state_form": "pairs"}), (forms["ref"], {})):
        sched = ContinuousBatchingScheduler(engine, eos_id=-1)
        notes = []
        sched._phases = SimpleNamespace(note=lambda **numbers: notes.append(numbers))
        sched._trace_dispatch("decode", [(0, "t", "decode", None, 40)])
        assert notes[0]["rows"] == 1 and notes[0]["kv_tokens"] == 40
        assert {k: v for k, v in notes[0].items() if k == "state_form"} == noted


@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
def test_a_decode_step_after_a_ragged_round_equals_the_reference_on_stored_pairs(backend):
    """Chunked prefill, one packed round (a decode row at each end of the
    buffer, a prompt's first chunk and another's second between them), then a
    decode step of all four slots: every path reads and writes the stored
    pairs — the chunked form and ``_step`` over gathered slots through the
    view, the decode step where the state lies — and all of it equals the
    plain reference; the kernel's step equals ``ref``'s."""
    seqs = {0: _tokens(21, 1), 1: _tokens(CHUNK + 1, 2), 2: _tokens(2 * CHUNK + 1, 3),
            3: _tokens(10, 4)}
    engine = _pairs_engine(backend)
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
    fallbacks = METRICS.get("finchat_ssm_step_fallbacks_total")
    engine.state, _e, _n, row_logits = ragged_mixed_step(
        engine.params, engine.state, jnp.asarray(packed, jnp.int32),
        jnp.asarray(tok_row, jnp.int32), jnp.arange(SLOTS, dtype=jnp.int32),
        jnp.asarray([0, 0, CHUNK, 0], jnp.int32), jnp.asarray([1, CHUNK, CHUNK, 1], jnp.int32),
        jnp.asarray(dev), jnp.asarray(dev), zeros_i,
        jnp.zeros((SLOTS,)), jnp.ones((SLOTS,)), zeros_i,
        config=PAIRS, page_size=PAGE, attn_backend=backend, **engine._ragged_kw())
    assert METRICS.get("finchat_ssm_step_fallbacks_total") == fallbacks  # rows of many tokens
    row_logits = np.asarray(row_logits)
    active = np.ones((SLOTS,), bool)
    for slot, seq in seqs.items():
        engine.set_last_token(slot, seq[-1])
    _, after = engine.decode(jnp.asarray(active), jnp.zeros((SLOTS,)), jnp.ones((SLOTS,)),
                             zeros_i, return_logits=True)
    after = np.asarray(after)
    for slot, seq in seqs.items():
        want = _pairs_reference(seq, [len(seq) - 2, len(seq) - 1])
        np.testing.assert_allclose(row_logits[slot], want[0], atol=2e-4, err_msg=f"row {slot}")
        np.testing.assert_allclose(after[slot], want[1], atol=2e-4, err_msg=f"slot {slot}")
    # ... and the state each slot ends on is the reference's, read as a snapshot
    want_state = np.asarray(granite.reference_state(_pairs_params(), seqs[2], PAIRS_FILE))
    snap = engine.ssm_snapshot(2)
    assert snap[0].shape == (3, 4, 64, 128) and snap[0].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(snap[0]), want_state, rtol=1e-4, atol=1e-5)


def test_a_snapshot_is_logical_and_restored_into_another_slot_it_is_the_donors():
    engine = _pairs_engine("pallas-interpret")
    engine.set_page_table_row(1, [3, 4])
    engine.prefill(1, _tokens(20, seed=2))
    stored = np.asarray(engine.state.ssm_state)
    assert np.abs(stored[:, 1]).max() > 0 and np.abs(stored[:, [0, 2, 3]]).max() == 0
    snap = engine.ssm_snapshot(1)
    # what ``perfbench/state_control.py`` compares with the reference's [layers, H, P, N]
    assert snap[0].shape == (3, *PAIRS.state_shape)
    np.testing.assert_array_equal(
        snap[0], ssm_step.to_logical(engine.state.ssm_state[:, 1], PAIRS.state_shape, 2))
    np.testing.assert_array_equal(snap[0], PAIRS.state_to_logical(engine.state.ssm_state[:, 1]))
    engine.ssm_restore(3, snap)
    after = np.asarray(engine.state.ssm_state)
    np.testing.assert_array_equal(after[:, 3], stored[:, 1])  # the donor's, bit for bit, as stored
    np.testing.assert_array_equal(after[:, [0, 1, 2]], stored[:, [0, 1, 2]])
    np.testing.assert_array_equal(np.asarray(engine.state.conv_state)[:, 3],
                                  np.asarray(engine.state.conv_state)[:, 1])
    again = engine.ssm_snapshot(3)
    np.testing.assert_array_equal(again[0], snap[0])
    engine.ssm_admit({3: None})  # admitted cold: from zero, whatever the layout
    assert np.abs(np.asarray(engine.state.ssm_state)[:, 3]).max() == 0


def test_one_token_over_gathered_slots_on_stored_pairs_counts_as_a_fallback():
    """The mixer over a pair-stored cache: the decode step's whole slot batch
    takes the kernel where the state lies; gathered slots take ``_step``
    through the view and count; both leave what ``ref`` leaves."""
    lp = jax.tree.map(lambda x: x[0], _pairs_params()["layers"])
    ks = jax.random.split(jax.random.key(3), 3)
    h = jax.random.normal(ks[0], (SLOTS, 1, PAIRS.dim), jnp.float32)
    n_valid = jnp.asarray([1, 1, 0, 1], jnp.int32)

    def carried():  # (the kernel's wrapper donates the state: a fresh one a call)
        return (jax.random.normal(ks[1], (3, SLOTS, *PAIRS.stored_state_shape), jnp.float32),
                jax.random.normal(ks[2], (3, SLOTS, *PAIRS.conv_shape), jnp.float32))

    def run(rows):
        out, new = ssm.mixer(h, lp, PAIRS, carried(), jnp.asarray(1, jnp.int32), rows)
        return np.asarray(out), np.asarray(new[0])

    want = run(ssm.SsmRows(None, n_valid))
    counted = METRICS.get("finchat_ssm_step_fallbacks_total")
    kernel = run(ssm.SsmRows(None, n_valid, backend="pallas-interpret"))
    assert METRICS.get("finchat_ssm_step_fallbacks_total") == counted
    gathered = run(ssm.SsmRows(jnp.arange(SLOTS, dtype=jnp.int32), n_valid,
                               backend="pallas-interpret"))
    assert METRICS.get("finchat_ssm_step_fallbacks_total") == counted + 1
    for got in (kernel, gathered):
        np.testing.assert_allclose(got[0], want[0], atol=2e-5)
        np.testing.assert_allclose(got[1], want[1], rtol=1e-6, atol=1e-5)
        np.testing.assert_array_equal(got[1][[0, 2]], np.asarray(carried()[0])[[0, 2]])
        np.testing.assert_array_equal(got[1][1, 2], np.asarray(carried()[0])[1, 2])  # the inert slot
