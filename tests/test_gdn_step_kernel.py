"""``ops/gdn_step.py``: the one-token gated-delta-rule update as one in-place
pass over the carried state, interpreted on the CPU (Mosaic's own rules for
the cell's shape: ``tests/test_tpu_compile.py``; values and time on the chip:
``chip_smoke.check_gdn_step_at_cell_shape``).

- KERNEL: against ``models/gdn.py``'s ``_step`` — ``o`` and the layer's new
  state to float32 round-off; an inert row (``g = 0, beta = 0``) and every
  other layer bit for bit; whatever the block of rows, however many heads
  share a tile and however the tiles fall into halves; the state's buffer
  donated.
- VIEWS: ``_heads`` / ``_tiles`` round-trip ``[N, H, dk, dv]`` exactly, and
  the chunked form through them equals the recurrence.
- MIXER: the kernel is taken where the input shows that it applies (one
  token, a carried cache, the whole slot batch, a kernel backend) and nowhere
  else; a one-token update over gathered slots counts as a fallback.
- ENGINE: ``decode_step`` of the tiny Olmo-Hybrid config through the
  interpreted kernel against the ``ref`` backend over several tokens.
"""

import dataclasses
import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finchat_tpu.engine.engine import InferenceEngine
from finchat_tpu.models import gdn
from finchat_tpu.models.llama import FULL, LINEAR, LlamaConfig, init_params
from finchat_tpu.models.ssm import SsmRows
from finchat_tpu.ops import gdn_step, ssm_step
from finchat_tpu.utils.config import EngineConfig
from finchat_tpu.utils.metrics import METRICS
from tests.test_olmo_hybrid import CONFIG, PAGE, PARAMS, SLOTS, TOL, _decode, _tokens

BASE = dict(rows=4, heads=6, tile_heads=2, key_dim=8, value_dim=64, layers=3, layer=1,
            inert=(), block_rows=2, beta_max=1.0)
CASES = {
    "three_tiles_of_two_heads": {},  # halves of one tile and two
    "an_inert_row": {"inert": (2,)},
    "every_row_inert": {"inert": (0, 1, 2, 3)},
    "first_layer": {"layer": 0},
    "last_layer": {"layer": 2},
    "one_layer": {"layers": 1, "layer": 0},
    "one_block": {"block_rows": 4},
    "a_row_a_block": {"block_rows": 1},
    "six_rows_in_blocks_of_three": {"rows": 6, "block_rows": 3},
    "a_row_larger_than_a_block": {"block_rows": 0},
    "beta_near_two": {"beta_max": 2.0},
    "a_head_a_tile": {"tile_heads": 1, "value_dim": 128},
    "a_head_a_tile_narrower_than_a_lane_tile": {"heads": 4, "tile_heads": 1, "value_dim": 16},
    "two_heads_meet_inside_a_lane_tile": {"heads": 10, "value_dim": 192},  # Olmo-Hybrid's values
    "four_heads_a_tile": {"heads": 8, "tile_heads": 4, "value_dim": 32},
    "seven_tiles_in_halves_of_three_and_four": {"heads": 14},
}


def _inputs(rows, heads, tile_heads, key_dim, value_dim, layers, inert, beta_max, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    f32 = jnp.float32
    g = -jax.random.uniform(ks[4], (rows, heads), f32, 0.01, 1.5)
    beta = jax.random.uniform(ks[5], (rows, heads), f32, 0.9 * beta_max, beta_max)
    for row in inert:
        g, beta = g.at[row].set(0.0), beta.at[row].set(0.0)
    by_head = jax.random.normal(ks[0], (layers, rows, heads, key_dim, value_dim), f32)
    return dict(
        by_head=by_head,
        state=jnp.stack([gdn._tiles(layer, heads // tile_heads) for layer in by_head]),
        q=gdn._l2norm(jax.random.normal(ks[1], (rows, heads, key_dim), f32)) * key_dim ** -0.5,
        k=gdn._l2norm(jax.random.normal(ks[2], (rows, heads, key_dim), f32)),
        v=jax.random.normal(ks[3], (rows, heads, value_dim), f32), g=g, beta=beta)


@pytest.mark.parametrize("case", CASES)
def test_kernel_equals_step_and_touches_nothing_else(case, monkeypatch):
    p = {**BASE, **CASES[case]}
    layer, inert, heads = p["layer"], p["inert"], p["heads"]
    row_bytes = heads * p["key_dim"] * p["value_dim"] * 4
    monkeypatch.setattr(ssm_step, "_BLOCK_BYTES", p["block_rows"] * row_bytes)
    i = _inputs(p["rows"], heads, p["tile_heads"], p["key_dim"], p["value_dim"], p["layers"],
                inert, p["beta_max"])
    assert i["state"].shape[2:] == (heads // p["tile_heads"], p["key_dim"],
                                    p["tile_heads"] * p["value_dim"])
    want_o, want_new = gdn._step(i["by_head"][layer], i["q"], i["k"], i["v"], i["g"], i["beta"])
    before = np.asarray(i["state"])
    # a fresh function: the block size is read when the wrapper is traced,
    # and jit's cache is keyed by the function it wraps
    step = jax.jit(functools.partial(gdn_step.gdn_state_step.__wrapped__, interpret=True),
                   donate_argnums=(0,))
    args = (i["q"], i["k"], i["v"], i["g"], i["beta"], jnp.asarray([layer], jnp.int32))
    steps = p["rows"] // max(1, p["block_rows"])
    assert f"grid=({steps},)" in str(jax.make_jaxpr(step)(i["state"], *args))
    o, after = step(i["state"], *args)
    after = np.asarray(after)
    # float32 round-off: the products over a head's keys are summed in another order
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gdn._heads(jnp.asarray(after[layer]), heads)),
                               np.asarray(want_new), rtol=1e-6, atol=1e-5)
    others = [at for at in range(p["layers"]) if at != layer]
    np.testing.assert_array_equal(after[others], before[others])
    for row in inert:
        np.testing.assert_array_equal(after[layer, row], before[layer, row])
    live = [row for row in range(p["rows"]) if row not in inert]
    assert not live or not np.array_equal(after[layer, live], before[layer, live])


def test_the_state_is_donated_and_comes_back_in_its_own_buffer():
    """On the CPU the interpreter copies, so the contract that can be checked
    here is the wrapper's: the state argument is donated (deleted after the
    call) and the compiled call aliases it to the output."""
    i = _inputs(4, 6, 2, 8, 64, 2, (), 1.0)
    args = (i["q"], i["k"], i["v"], i["g"], i["beta"], jnp.asarray([1], jnp.int32))
    lowered = gdn_step.gdn_state_step.lower(i["state"], *args, interpret=True)
    assert "tf.aliasing_output" in lowered.as_text() or "jax.buffer_donor" in lowered.as_text()
    state = i["state"]
    _o, after = gdn_step.gdn_state_step(state, *args, interpret=True)
    assert state.is_deleted() and after.shape == state.shape


def test_the_kernel_shares_the_mamba_kernels_pipeline():
    """ROADMAP D14: the row-block pipeline is ONE body, ``ops/ssm_step.py``'s;
    this file's kernel brings its ``advance`` and its operands and nothing of
    the DMAs, the buffers or the aliasing."""
    assert gdn_step.in_place_pass is ssm_step.in_place_pass
    assert gdn_step.in_place_call is ssm_step.in_place_call
    source = Path(gdn_step.__file__).read_text()
    for own in ("make_async_copy", "pallas_call", "input_output_aliases", "SemaphoreType"):
        assert own not in source, own


# --- VIEWS ---------------------------------------------------------------------

@pytest.mark.parametrize("heads, tiles", [(6, 3), (6, 6), (6, 1), (30, 15)])
def test_the_views_round_trip_heads_exactly(heads, tiles):
    state = jax.random.normal(jax.random.key(5), (3, heads, 8, 16), jnp.float32)
    laid = gdn._tiles(state, tiles)
    assert laid.shape == (3, tiles, 8, heads // tiles * 16)
    np.testing.assert_array_equal(np.asarray(gdn._heads(laid, heads)), np.asarray(state))
    # head h's matrix stands in tile h // n at lanes [(h % n) dv, (h % n + 1) dv)
    n = heads // tiles
    for h in (0, heads - 1):
        np.testing.assert_array_equal(
            np.asarray(laid[:, h // n, :, (h % n) * 16:(h % n + 1) * 16]), np.asarray(state[:, h]))


def test_state_shape_fills_whole_lane_tiles_where_heads_allow_it():
    def shape(heads, value_dim):
        return LlamaConfig(n_layers=4, layer_pattern=(LINEAR, LINEAR, LINEAR, FULL),
                           gdn_heads=heads, gdn_key_dim=96, gdn_value_dim=value_dim).state_shape

    assert shape(30, 192) == (15, 96, 384)  # Olmo-Hybrid: no padded lane
    assert shape(30, 128) == (30, 96, 128) and shape(32, 64) == (16, 96, 128)
    assert shape(4, 16) == (4, 96, 16)  # no divisor fills a lane tile: heads as they are
    assert shape(2, 64) == (2, 96, 64)  # two tiles at least (the kernel's halves)
    assert CONFIG.state_shape == (4, 8, 16)


# --- MIXER ---------------------------------------------------------------------

# the tiny config with values of 64: two heads a tile, so the views are no identity
PAIRED = dataclasses.replace(CONFIG, gdn_value_dim=64)
PAIRED_PARAMS = init_params(PAIRED, jax.random.key(1))


def _mixer_inputs(width: int, cache: bool = True):
    """A linear layer's leaves, ``width`` tokens a slot and a seeded cache."""
    c, n = PAIRED, SLOTS
    assert c.state_shape == (2, 8, 128)
    lp = jax.tree.map(lambda x: x[1], {k: v for k, v in PAIRED_PARAMS["layers"].items()
                                       if k.startswith("gdn_")})
    ks = jax.random.split(jax.random.key(3), 3)
    h = jax.random.normal(ks[0], (n, width, c.dim), jnp.float32)
    carried = (0.3 * jax.random.normal(ks[1], (c.n_state_layers, n, *c.state_shape), jnp.float32),
               jax.random.normal(ks[2], (c.n_state_layers, n, *c.conv_shape), jnp.float32)
               ) if cache else None
    return lp, h, carried


def _mixer(rows: SsmRows, width: int = 1, cache: bool = True):
    """One linear layer's mixer over ``width`` tokens a slot from a seeded
    cache; (output, new state leaf or None, the jaxpr's text)."""
    lp, h, carried = _mixer_inputs(width, cache)

    def run(h, carried):
        return gdn.mixer(h, lp, PAIRED, carried, jnp.asarray(1, jnp.int32), rows)

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
    out, state, text = _mixer(SsmRows(slots, n_valid, backend=backend), width, cache)
    assert ("pallas_call" in text) == kernel
    assert (METRICS.get("finchat_ssm_step_fallbacks_total") > counted) == fallback
    want_out, want_state, _ = _mixer(SsmRows(slots, n_valid), width, cache)
    np.testing.assert_allclose(out, want_out, atol=2e-5)
    if cache:
        np.testing.assert_allclose(state, want_state, rtol=1e-6, atol=1e-5)


def test_the_chunked_form_through_the_views_equals_the_recurrence():
    """Five tokens a slot through ``mixer`` at once (the WY form, the state
    read and written through ``_heads`` / ``_tiles``) against the same tokens
    one at a time (``_step`` through the same views): outputs and the leaf."""
    out, state, _ = _mixer(SsmRows(None, jnp.full((SLOTS,), 5, jnp.int32)), 5)
    lp, h, carried = _mixer_inputs(5)
    one = SsmRows(None, jnp.ones((SLOTS,), jnp.int32))
    outs = []
    for t in range(5):
        y, carried = gdn.mixer(h[:, t:t + 1], lp, PAIRED, carried, jnp.asarray(1, jnp.int32), one)
        outs.append(y)
    np.testing.assert_allclose(out, np.asarray(jnp.concatenate(outs, axis=1)), atol=2e-5)
    np.testing.assert_allclose(state, np.asarray(carried[0]), rtol=1e-5, atol=1e-5)
    assert not np.array_equal(state[1], state[0])  # the layer named, and no other


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
    # a summation order apart, through a block whose every sub-block's output is
    # normed (test_olmo_hybrid's TOL): `_step` with its keys reversed reads 2e-4 on
    # these logits and 6e-4 on these states, which reach 10
    np.testing.assert_allclose(logits["pallas-interpret"], logits["ref"], atol=TOL)
    np.testing.assert_allclose(states["pallas-interpret"], states["ref"], atol=2 * TOL)
    assert np.abs(states["ref"][:, [0, 3]]).max() > 0.01
    assert (states["pallas-interpret"][:, 1] == 0.5).all()
