"""Layers of more than one kind (``LlamaConfig.layer_pattern``): three
gated-delta-rule layers (``models/gdn.py``) to one of full attention, the
page pool owned by the full layers alone and the recurrent state by the
linear ones — the program against the plain float32 reference of
``perfbench/models/olmo_hybrid.py`` at a small size, seeded random weights,
through every path that carries the state, and the options that would not
carry it refused.

- RULE: the chunked (WY) form and the one-token step equal the recurrence as
  it is written, over block boundaries and padding; the recurrence without
  any one of its terms does not.
- FORWARD: the cache-less forward equals the reference whatever the block;
  ``linear_allow_neg_eigval`` on and off; a dropped L2 norm or gate shows.
- SPLIT / RAGGED: ``prefill`` in chunks then ``decode``; rows of all kinds
  packed in one buffer, at both of its ends.
- CACHES: the pool has the full layers' depth, the state the linear layers'.
- SNAPSHOT / ADMISSION: a row admitted from a head's snapshot equals the row
  that computed the head itself (a control without the copy does not); a
  dirty slot is cleared on admission.
- REFUSED: each option that would rewind or move a row without its state.
- PERIOD ONE: a pattern of one layer is today's block, tree and program.
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
from finchat_tpu.engine.kv_cache import page_hbm_bytes
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
from finchat_tpu.models import gdn
from finchat_tpu.models.llama import (
    FULL,
    LINEAR,
    LlamaConfig,
    forward_full,
    init_params,
    n_params,
)
from finchat_tpu.utils.config import EngineConfig
from finchat_tpu.utils.metrics import METRICS
from perfbench.models import olmo_hybrid

FILE = tiny_models.FILES["olmo_hybrid"]
CONFIG, PARAMS = tiny_models.build("olmo_hybrid")
PAGE, CHUNK, SLOTS = 16, 12, 4  # a prefill chunk of 12 against WY blocks of 8


@pytest.fixture(autouse=True)
def _wy_blocks_of_8(monkeypatch):
    monkeypatch.setattr(gdn, "CHUNK", 8)


TOL = 1e-3  # float32 against float32, logits of spread 1: each norm on a
# sub-block's output rescales that sub-block's rounding; a dropped term reads 0.05-1


def _tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 300, size=n)]


def _reference(tokens, positions, file=FILE, params=PARAMS):
    want, margins = olmo_hybrid.reference_logits(params, tokens, file, positions=positions)
    assert np.isinf(np.asarray(margins)).all()  # nothing is routed
    return np.asarray(want)


def _forward(tokens, config=CONFIG, params=PARAMS):
    n = len(tokens)
    return np.asarray(forward_full(params, jnp.asarray(tokens)[None], jnp.arange(n)[None],
                                   config=config)[0])


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


# --- RULE ----------------------------------------------------------------------

def _rule_inputs(n=2, S=29, H=3, dk=8, dv=16, seed=0, n_valid=(29, 18)):
    """q, k, v, g, beta as the mixer hands them to the rule (L2-normed q and
    k, beta in (0, 2), padding with g = beta = 0) and a non-zero state."""
    r = np.random.RandomState(seed)
    q, k = (r.randn(n, S, H, dk).astype(np.float32) for _ in range(2))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.randn(n, S, H, dv).astype(np.float32)
    live = (np.arange(S)[None, :] < np.asarray(n_valid)[:, None])[..., None]
    g = np.where(live, -r.uniform(0.01, 1.5, (n, S, H)), 0.0).astype(np.float32)
    beta = np.where(live, r.uniform(0.0, 2.0, (n, S, H)), 0.0).astype(np.float32)
    state = (0.3 * r.randn(n, H, dk, dv)).astype(np.float32)
    return state, q, k, v, g, beta


def _recurrence(state, q, k, v, g, beta, drop=None):
    """The rule token by token, exactly as ISSUE 32 writes it, in float64;
    ``drop`` leaves one term out."""
    S_ = np.asarray(state, np.float64).copy()
    q, k, v, g, beta = (np.asarray(t, np.float64) for t in (q, k, v, g, beta))
    out = np.zeros(v.shape)
    for t in range(q.shape[1]):
        alpha = np.ones_like(g[:, t]) if drop == "decay" else np.exp(g[:, t])
        S_ = alpha[..., None, None] * S_
        seen = 0.0 if drop == "correction" else np.einsum("nhkv,nhk->nhv", S_, k[:, t])
        u = beta[:, t][..., None] * (v[:, t] - seen)
        S_ = S_ + k[:, t][..., :, None] * u[..., None, :]
        out[:, t] = np.einsum("nhkv,nhk->nhv", S_, q[:, t])
    return out, S_


@pytest.mark.parametrize("chunk", [1, 5, 8, 29, 64])
def test_the_chunked_form_equals_the_recurrence_over_block_ends_and_padding(chunk):
    state, q, k, v, g, beta = _rule_inputs()
    want_o, want_s = _recurrence(state, q, k, v, g, beta)
    o, s = gdn._chunked(*(jnp.asarray(t) for t in (state, q, k, v, g, beta)), chunk)
    np.testing.assert_allclose(np.asarray(s), want_s, atol=2e-5)
    # the second row's tokens past its 18 real ones are padding: not compared
    np.testing.assert_allclose(np.asarray(o)[0], want_o[0], atol=2e-5)
    np.testing.assert_allclose(np.asarray(o)[1, :18], want_o[1, :18], atol=2e-5)
    # and its state is the state after its last REAL token
    _, at_18 = _recurrence(state[1:], q[1:, :18], k[1:, :18], v[1:, :18], g[1:, :18],
                           beta[1:, :18])
    np.testing.assert_allclose(np.asarray(s)[1:], at_18, atol=2e-5)


@pytest.mark.parametrize("lens,T", [((1, 9, 1, 8, 0, 1), 24), ((1, 1, 17, 1, 1, 1), 24),
                                    ((8, 8, 8, 0, 0, 0), 24), ((12, 12, 12, 12, 12, 12), 72)])
def test_rows_ranked_by_length_leave_the_packed_buffer_as_the_recurrence_leaves_each(lens, T):
    """``_packed_scan`` at blocks of 8 and a width of 17: of a buffer of 24
    tokens two rows can hold more than a block, so the two longest ride the
    whole width and the others ONE block, wherever they stand in the buffer
    (a row of exactly 8 on either side of the cut, a row of none, decode rows
    around a prompt's chunk); at 72 tokens every row gets the width. Each
    row's outputs and the state left in ITS slot are the recurrence's from
    that slot's state; an empty row's slot, which repeats a live row's, and
    the slots no row names are left alone."""
    from finchat_tpu.models.ssm import SsmRows

    H, dk, dv, n, slots = 2, 4, 6, len(lens), 9
    r = np.random.RandomState(7)
    q, k = (r.randn(T, H, dk).astype(np.float32) for _ in range(2))
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.randn(T, H, dv).astype(np.float32)
    g = -r.uniform(0.01, 1.5, (T, H)).astype(np.float32)
    beta = r.uniform(0.0, 2.0, (T, H)).astype(np.float32)
    leaf = (0.3 * r.randn(2, slots, H, dk, dv)).astype(np.float32)
    # an empty row repeats a live row's slot, as the scheduler's padding rows do
    row_slot = np.where(np.asarray(lens) > 0, [7, 2, 5, 0, 8, 3], [7, 2, 5, 0, 8, 3][int(np.argmax(lens))])
    n_valid = np.asarray(lens, np.int32)
    q_start = np.cumsum(n_valid) - n_valid
    tok_row = np.repeat(np.arange(n), n_valid)
    tok_row = np.concatenate([tok_row, np.full((T - len(tok_row),), n)]).astype(np.int32)
    tok_off = (np.arange(T) - q_start[np.minimum(tok_row, n - 1)]).astype(np.int32)
    rows = SsmRows(jnp.asarray(row_slot), jnp.asarray(n_valid),
                   pack=tuple(jnp.asarray(t) for t in (q_start, tok_row, tok_off)), width=17)
    o, new = gdn._packed_scan(jnp.asarray(leaf), jnp.asarray(1), rows,
                              *(jnp.asarray(t) for t in (q, k, v, g, beta)))
    o, new, touched = np.asarray(o), np.asarray(new), set()
    for row, (start, count) in enumerate(zip(q_start, lens)):
        if not count:
            continue
        at = slice(start, start + count)
        want_o, want_s = _recurrence(leaf[1, row_slot[row]][None], *(t[at][None] for t in (q, k, v, g, beta)))
        np.testing.assert_allclose(o[at], want_o[0], atol=2e-5, err_msg=f"row {row}")
        np.testing.assert_allclose(new[1, row_slot[row]], want_s[0], atol=2e-5, err_msg=f"row {row}")
        touched.add(int(row_slot[row]))
    untouched = [slot for slot in range(slots) if slot not in touched]
    np.testing.assert_array_equal(new[1, untouched], leaf[1, untouched])
    np.testing.assert_array_equal(new[0], leaf[0])


@pytest.mark.parametrize("repeat,beta", [(0.9, 1.9), (1.0, 2.0)], ids=["near", "identical"])
def test_keys_that_repeat_under_beta_near_two_do_not_overflow_the_block_solve(repeat, beta):
    """The system a block solves is as ill-conditioned as the model allows
    (``linear_allow_neg_eigval``: beta up to 2, and keys that repeat make
    every ``k_i . k_j`` near 1): the powers of ``A`` reach 1e30 and more
    before its nilpotency ends them, the solution stays of order 1. Block
    forward substitution holds it to float32 round-off."""
    r = np.random.RandomState(7)
    n, S, H, dk, dv = 1, 128, 2, 24, 48
    base = r.randn(n, 1, H, dk)
    k = repeat * base + (1 - repeat) * r.randn(n, S, H, dk)
    k = (k / np.linalg.norm(k, axis=-1, keepdims=True)).astype(np.float32)
    q = r.randn(n, S, H, dk)
    q = (q / np.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5).astype(np.float32)
    v = r.randn(n, S, H, dv).astype(np.float32)
    g = np.zeros((n, S, H), np.float32)
    state = (0.3 * r.randn(n, H, dk, dv)).astype(np.float32)
    b = np.full((n, S, H), beta, np.float32)
    want_o, want_s = _recurrence(state, q, k, v, g, b)
    o, s = gdn._chunked(*(jnp.asarray(t) for t in (state, q, k, v, g, b)), 64)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(np.asarray(o), want_o, atol=1e-4 * np.abs(want_o).max())
    np.testing.assert_allclose(np.asarray(s), want_s, atol=1e-4 * np.abs(want_s).max())


def test_the_one_token_step_equals_the_recurrence_and_an_inert_row_keeps_its_state():
    state, q, k, v, g, beta = _rule_inputs(S=1, n_valid=(1, 0))
    want_o, want_s = _recurrence(state, q, k, v, g, beta)
    o, s = gdn._step(*(jnp.asarray(t) for t in (state, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                                beta[:, 0])))
    np.testing.assert_allclose(np.asarray(o), want_o[:, 0], atol=1e-6)
    np.testing.assert_allclose(np.asarray(s), want_s, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(s)[1], state[1])


@pytest.mark.parametrize("lens", [(5, 0, 1, 2, 3, 9), (1, 1, 1, 1, 1, 1), (0, 0, 12, 0, 0, 0)])
def test_the_conv_along_the_packed_buffer_equals_the_conv_over_its_rows(lens):
    """Rows of 0 to K tokens and more, one after another in the buffer and
    padding behind them: each row's outputs and its new tail are what
    ``causal_conv`` gives that row alone from its own tail."""
    from finchat_tpu.models.ssm import SsmRows, _to_rows, causal_conv

    K, C, T, width = 4, 6, 24, 12
    key = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(key[0], (T, C))
    tail = jax.random.normal(key[1], (len(lens), K - 1, C))
    w = jax.random.normal(key[2], (K, C))
    n_valid = jnp.asarray(lens, jnp.int32)
    q_start = jnp.cumsum(n_valid) - n_valid
    rows = SsmRows(jnp.arange(len(lens)), n_valid, pack=(q_start, None, None), width=width)
    want, want_tail = causal_conv(_to_rows(x, rows), tail, n_valid, w, None)
    got, got_tail = gdn._packed_conv(x, tail, rows, w)
    np.testing.assert_allclose(got_tail, want_tail, atol=1e-6)
    for r, n in enumerate(lens):
        np.testing.assert_allclose(_to_rows(got, rows)[r, :n], want[r, :n], atol=1e-6)


@pytest.mark.parametrize("drop", ["decay", "correction"])
def test_the_rule_without_one_of_its_terms_is_another_rule(drop):
    """The controls of the two cases above: the decay and the ``S~^T k``
    correction each move the result far beyond what those cases allow."""
    state, q, k, v, g, beta = _rule_inputs()
    o, s = gdn._chunked(*(jnp.asarray(t) for t in (state, q, k, v, g, beta)), 8)
    other_o, other_s = _recurrence(state, q, k, v, g, beta, drop=drop)
    assert np.abs(np.asarray(o)[0] - other_o[0]).max() > 0.05
    assert np.abs(np.asarray(s) - other_s).max() > 0.05


# --- FORWARD -------------------------------------------------------------------

def test_param_count_and_config():
    c = CONFIG
    assert c.layer_pattern == (LINEAR, LINEAR, LINEAR, FULL) and c.rope_theta is None
    assert (c.n_attn_layers, c.n_state_layers, c.has_state) == (2, 6, True)
    assert sum(x.size for x in jax.tree.leaves(PARAMS)) == n_params(c) \
        == olmo_hybrid.param_counts(FILE)["total"]
    shapes = {name: leaf.shape[0] for name, leaf in PARAMS["layers"].items()}
    assert {shapes[n] for n in shapes if n.startswith("attn_")} == {2}
    assert {shapes[n] for n in shapes if n.startswith("gdn_")} == {6}
    assert {shapes[n] for n in ("mlp_gate", "mlp_up", "mlp_down", "ln_attn", "ln_mlp")} == {8}
    with pytest.raises(ValueError, match="whole number of periods"):
        dataclasses.replace(c, n_layers=6)
    with pytest.raises(ValueError, match="go together"):
        dataclasses.replace(c, gdn_heads=0)


@pytest.mark.parametrize("chunk", [1, 5, 8, 64])
def test_forward_equals_the_reference_whatever_the_block(chunk, monkeypatch):
    monkeypatch.setattr(gdn, "CHUNK", chunk)
    tokens = _tokens(37)
    # a config of its own, so that no program traced at another block is served
    got = _forward(tokens, dataclasses.replace(CONFIG, max_seq_len=300 + chunk))
    np.testing.assert_allclose(got, _reference(tokens, list(range(37))), atol=TOL)


@pytest.mark.parametrize("neg_eigval", [True, False])
def test_allow_neg_eigval_doubles_beta_in_program_and_reference_alike(neg_eigval):
    tokens = _tokens(23, seed=3)
    file = dict(FILE, linear_allow_neg_eigval=neg_eigval)
    config = dataclasses.replace(CONFIG, gdn_neg_eigval=neg_eigval)
    want = _reference(tokens, list(range(23)), file=file)
    np.testing.assert_allclose(_forward(tokens, config), want, atol=TOL)
    # the factor is no rounding: the other setting is another model
    other = _forward(tokens, dataclasses.replace(CONFIG, gdn_neg_eigval=not neg_eigval))
    assert np.abs(other - want).max() > 50 * TOL


LEFT_OUT = ["l2norm", "gate", "decay", "qk_norm", "norm_after"]


@pytest.mark.parametrize("left_out", LEFT_OUT)
def test_a_program_without_one_term_of_the_layer_is_not_the_reference(left_out, monkeypatch):
    config = CONFIG
    if left_out == "l2norm":
        monkeypatch.setattr(gdn, "_l2norm", lambda x: x)
    elif left_out == "gate":
        monkeypatch.setattr(gdn, "gated_head_norm", lambda o, gate, weight, eps: o * jax.lax.rsqrt(
            jnp.mean(o * o, axis=-1, keepdims=True) + eps) * weight.astype(jnp.float32))
    elif left_out == "decay":
        gates = gdn._gates
        monkeypatch.setattr(gdn, "_gates", lambda *a: (0.0 * gates(*a)[0], gates(*a)[1]))
    else:
        config = dataclasses.replace(CONFIG, **{left_out: False})
    tokens = _tokens(21, seed=4)
    # a config of its own, so that no cached program of another case is served
    config = dataclasses.replace(config, max_seq_len=200 + LEFT_OUT.index(left_out))
    assert np.abs(_forward(tokens, config) - _reference(tokens, list(range(21)))).max() > 50 * TOL


def test_the_recurrence_reaches_far_back():
    """Published initialisation: the state does not die in a token (else
    every comparison here would be vacuous). With the full-attention layers'
    values zeroed, changing the FIRST token moves position 20 — seventeen
    tokens past the conv's reach — through the recurrent state alone."""
    layers = dict(PARAMS["layers"], attn_v=jnp.zeros_like(PARAMS["layers"]["attn_v"]))
    params = dict(PARAMS, layers=layers)
    tokens = _tokens(21)
    a = _forward(tokens, params=params)[-1]
    b = _forward([tokens[0] + 1] + tokens[1:], params=params)[-1]
    assert float(np.abs(a - b).max()) > 50 * TOL


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
    """One packed buffer of exactly 26 tokens: a decode row that ends at the
    buffer's first token, a prompt's first chunk, another prompt's second
    chunk, and a decode row that starts at the buffer's last token. Each row
    starts from its own slot's state (the linear layers') and pages (the full
    layers') and leaves its last state there: the next decode step of all
    four slots still equals the reference."""
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


def test_the_benchmarks_own_logits_paths_agree_and_give_their_slots_back_clean():
    """``perfbench/correct.py``'s two paths (the split one and the round
    packed as the scheduler packs it, padding rows repeating a live slot)."""
    from finchat_tpu.engine.kv_cache import PageAllocator
    from perfbench import correct

    class Sched:
        engine = _engine(mixed_step=True)
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


# --- CACHES --------------------------------------------------------------------

def test_the_pool_has_the_full_layers_depth_and_the_state_the_linear_layers():
    engine = _engine()
    state = engine.state
    assert state.k_pages.shape == state.v_pages.shape == (2, 64, PAGE, 4 * 16)
    assert state.ssm_state.shape == (6, SLOTS, 4, 8, 16) and state.ssm_state.dtype == jnp.float32
    assert state.conv_state.shape == (6, SLOTS, 3, 4 * (2 * 8 + 16))
    # a page costs K and V in the TWO layers that own pages, not in eight
    assert page_hbm_bytes(CONFIG, PAGE) == 2 * 2 * PAGE * 64 * 4
    assert state.k_pages.nbytes + state.v_pages.nbytes == 64 * page_hbm_bytes(CONFIG, PAGE)
    assert engine.ssm_state_bytes == 6 * SLOTS * (4 * 8 * 16 + 3 * 128) * 4
    # the benchmark's adapter counts the same bytes
    assert olmo_hybrid.kv_bytes_per_token(dict(FILE, dtype="float32")) * PAGE \
        == page_hbm_bytes(CONFIG, PAGE)
    assert SLOTS * 6 * (olmo_hybrid.ssm_state_bytes_per_row(FILE)
                        + olmo_hybrid.conv_tail_bytes_per_row(FILE)) == engine.ssm_state_bytes
    quantized = _engine(kv_quant="int8").state
    assert quantized.k_pages.shape[0] == quantized.k_scales.shape[0] == 2


def test_a_full_layer_writes_its_own_place_in_the_pool_and_nothing_else():
    """The kernels' layer index is the layer's place among its OWN kind:
    after a prefill the two full layers' pages differ (two layers wrote), the
    six linear layers' states all differ, and none is left at zero."""
    engine = _engine()
    engine.set_page_table_row(1, [3, 4])
    engine.prefill(1, _tokens(20, seed=8))
    k = np.asarray(engine.state.k_pages)[:, 3]
    assert np.abs(k[0]).max() > 0 and np.abs(k[1]).max() > 0 and np.abs(k[0] - k[1]).max() > 0
    s = np.asarray(engine.state.ssm_state)[:, 1]
    assert all(np.abs(s[i]).max() > 0 for i in range(6))
    assert len({float(np.abs(s[i]).sum()) for i in range(6)}) == 6


# --- RESET / SNAPSHOT / ADMISSION ------------------------------------------------

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
    assert (held == 0.0 and off < TOL) if zeroed else (held > 0.0 and off > 20 * TOL)


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
            return await _stream(sched, prompt, **kw)
        finally:
            await sched.stop()
    return asyncio.run(go())


@pytest.mark.parametrize("mixed", [False, True])
def test_a_row_admitted_from_a_heads_snapshot_streams_what_the_whole_row_streams(mixed):
    """Prefix reuse where most layers hold state and some hold pages: the
    row that starts from the head's pages AND state snapshot streams the
    tokens of the row that computed the head itself."""
    prompt = HEAD + _tokens(13, seed=12)
    _handle, whole = _run(_scheduler(mixed_step=mixed), prompt)
    sched = _scheduler(mixed_step=mixed)
    restores = METRICS.get("finchat_ssm_snapshot_restores_total")
    assert sched.register_prefix(HEAD + [1, 2, 3]) == len(HEAD)
    assert float(jnp.abs(sched.engine.state.ssm_state).max()) == 0.0  # the head's slot went back clean
    handle, resumed = _run(sched, prompt)
    assert handle.shared_len == len(HEAD) and handle.span.state_restored_tokens == len(HEAD)
    assert METRICS.get("finchat_ssm_snapshot_restores_total") == restores + 1
    assert resumed == whole and len(whole) == 6
    assert METRICS.get("finchat_ssm_state_bytes") == sched.engine.ssm_state_bytes > 0


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
    assert snap[0].shape == (6, 4, 8, 16) and float(jnp.abs(snap[0]).max()) > 0.0
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
    assert off < TOL if state_copied else off > 20 * TOL
    sched._evict(handle, "error", error="test over")


@pytest.mark.parametrize("admission_clears", [True, False])
def test_a_cold_row_starts_from_zero_whatever_its_slot_was_left_with(admission_clears, monkeypatch):
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
    assert (dirty == 0.0 and off < TOL) if admission_clears else (dirty > 0.0 and off > 20 * TOL)
    other = next(s for s in range(engine.engine_cfg.max_seqs) if s != slot)
    assert float(jnp.abs(engine.state.ssm_state[:, other]).min()) == 1.0
    sched._evict(handle, "error", error="test over")


def test_what_cannot_start_from_a_snapshot_recomputes_and_counts():
    """A prompt that shares only part of a head has pages to reference in the
    full layers but no state to start the linear ones from: recomputed from
    its tokens, counted; the session tier is not built."""
    sched = _scheduler(mixed_step=True)
    assert sched.session_cache is None and sched.has_ssm
    assert sched.register_prefix(HEAD + [1]) == len(HEAD)
    before = METRICS.get("finchat_ssm_recompute_fallbacks_total")
    short = HEAD[:PAGE + 3]  # one whole page of the head, then its own tokens
    handle, got = _run(sched, short)
    _h, want = _run(_scheduler(mixed_step=True), short)
    assert handle.shared_len == 0 and got == want
    assert METRICS.get("finchat_ssm_recompute_fallbacks_total") == before + 1


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

    with pytest.raises(ValueError, match="fabric.path"):
        ContinuousBatchingScheduler(_engine(), eos_id=-1,
                                    fabric=WarmFabric(str(tmp_path), 1 << 20))


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


# --- PERIOD ONE ------------------------------------------------------------------

TODAYS_TREE = {  # the "tiny" preset's stacked leaves, as every PR before this one built them
    "attn_q": (2, 128, 128), "attn_k": (2, 128, 64), "attn_v": (2, 128, 64),
    "attn_o": (2, 128, 128), "ln_attn": (2, 128), "ln_mlp": (2, 128),
    "mlp_gate": (2, 128, 256), "mlp_up": (2, 128, 256), "mlp_down": (2, 256, 128)}


def test_a_pattern_of_period_one_builds_todays_tree_and_todays_program():
    plain, one = LlamaConfig(), LlamaConfig(layer_pattern=(FULL,))
    assert not plain.has_state and plain.n_attn_layers == plain.n_layers == 2
    assert plain.n_state_layers == one.n_state_layers == 0 and one.n_attn_layers == 2
    a, b = init_params(plain, jax.random.key(3)), init_params(one, jax.random.key(3))
    assert {name: leaf.shape for name, leaf in a["layers"].items()} == TODAYS_TREE
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    cfg = EngineConfig(max_seqs=2, page_size=8, num_pages=16, max_seq_len=64, prefill_chunk=8)
    texts = []
    for c, params in ((plain, a), (one, b)):
        engine = InferenceEngine(c, params, cfg, attn_backend="ref")
        assert engine.state.k_pages.shape[0] == 2 and engine.state.ssm_state.shape == (1,) * 5
        assert engine.ssm_snapshot(0) is None and engine._ragged_kw() == {}
        texts.append(engine_module.decode_step.lower(
            engine.params, engine.state, jnp.zeros((2,), bool), jnp.ones((2,)), jnp.ones((2,)),
            jnp.zeros((2,), jnp.int32), config=c, page_size=8,
            attn_backend="ref").as_text(debug_info=True))
    assert texts[0] == texts[1]
    assert not any(scope in texts[0] for scope in ("gdn_in", "gdn_conv", "gdn_scan", "gdn_out"))
