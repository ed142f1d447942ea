"""``ops/ssm_step.py``: the one-token Mamba-2 state update as one in-place
pass over the carried state, interpreted on the CPU (Mosaic's own rules for
the cell's shape: ``tests/test_tpu_compile.py``; values and time on the chip:
``chip_smoke.check_ssm_step_at_cell_shape``).

- KERNEL: against ``models/ssm.py``'s ``_step`` — ``y`` and the layer's new
  state to float32 round-off; a row with ``dt = 0`` and every other layer bit
  for bit; whatever the block of rows and however the heads fall into groups
  and halves; the state's buffer donated.
- MIXER: the kernel is taken where the input shows that it applies (one
  token, a carried cache, the whole slot batch, a kernel backend) and nowhere
  else; a one-token update over gathered slots counts as a fallback.
- ENGINE: ``decode_step`` of the tiny Falcon-H1 config through the interpreted
  kernel against the ``ref`` backend over several tokens.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finchat_tpu.engine.engine import InferenceEngine
from finchat_tpu.models import ssm
from finchat_tpu.ops import ssm_step
from finchat_tpu.utils.config import EngineConfig
from finchat_tpu.utils.metrics import METRICS
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
    p = {**BASE, **CASES[case]}
    layer, inert, groups = p["layer"], p["inert"], p["groups"]
    row_bytes = p["heads"] * p["head_dim"] * p["state"] * 4
    monkeypatch.setattr(ssm_step, "_BLOCK_BYTES", p["block_rows"] * row_bytes)
    assert ssm_step.rows_per_block(p["rows"], row_bytes) == max(1, p["block_rows"])
    i = _inputs(p["rows"], p["heads"], groups, p["head_dim"], p["state"], p["layers"], inert)
    want_y, want_new = _reference(i, layer, groups)
    before = np.asarray(i["ssm_state"])
    # a fresh function: the block size is read when the wrapper is traced
    step = jax.jit(ssm_step.ssm_state_step.__wrapped__, static_argnames=("interpret",),
                   donate_argnums=(0,))
    y, after = step(i["ssm_state"], i["xs"], i["dt"], i["A"], i["Bm"], i["Cm"], i["D"],
                    jnp.asarray([layer], jnp.int32), interpret=True)
    after = np.asarray(after)
    # float32 round-off: y sums `state` products in another order than _step
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(after[layer], want_new, rtol=1e-6, atol=1e-5)
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
    i = _inputs(4, 4, 2, 16, 128, 2, ())
    args = (i["xs"], i["dt"], i["A"], i["Bm"], i["Cm"], i["D"], jnp.asarray([1], jnp.int32))
    lowered = ssm_step.ssm_state_step.lower(i["ssm_state"], *args, interpret=True)
    assert "tf.aliasing_output" in lowered.as_text() or "jax.buffer_donor" in lowered.as_text()
    state = i["ssm_state"]
    _y, after = ssm_step.ssm_state_step(state, *args, interpret=True)
    assert state.is_deleted() and after.shape == state.shape


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
