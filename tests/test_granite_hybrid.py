"""``granitemoehybrid`` (PR 34) on the program's one block, at a size a test
holds: many small routed experts of which this process holds a range, beside
a shared expert; the Mamba-2 mixer as a KIND of the layer pattern (nine to one
with attention that is not rotated and has a softmax scale of its own); four
scalars. Everything against the plain reference of ``perfbench/models/
granitemoehybrid.py`` (float32, token by token, a loop over the held experts).

EXPERTS  the held ranges' shares and the shared expert once sum to the uncut
         layer; the grouped form equals the dense one (ties, an expert without
         a token, every pick absent); the experts-touched count by hand
FORWARD  the cache-less forward, whatever the scan's block; a program without
         one term of the layer is not the reference
SPLIT / RAGGED / CACHES / HEADS  prefill then decode, the packed round, the
         pool's and the state's depth, admission from a head's snapshot
COUNT    the decode step's count through engine and scheduler
REFUSED  what is refused at load
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import tiny_models

from finchat_tpu.engine.engine import InferenceEngine, ragged_mixed_step
from finchat_tpu.engine.kv_cache import page_hbm_bytes
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
from finchat_tpu.models import llama
from finchat_tpu.models.llama import (
    FULL,
    LINEAR,
    MAMBA,
    LlamaConfig,
    forward_full,
    init_params,
    moe_mlp,
    n_params,
)
from finchat_tpu.utils.config import EngineConfig
from finchat_tpu.utils.metrics import METRICS
from finchat_tpu.utils.tracing import TRACER
from perfbench.models import granitemoehybrid as granite

FILE = tiny_models.FILES["granite_hybrid"]
CONFIG, PARAMS = tiny_models.build("granite_hybrid")
PAGE, CHUNK, SLOTS = 16, 12, 4
TOL = 2e-4  # float32 against float32; the logits' spread is about 0.07, a dropped term reads 3e-3 or more


def _tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 300, size=n)]


def _reference(tokens, positions, file=FILE, params=PARAMS, **kw):
    want, margins = granite.reference_logits(params, tokens, file, positions=positions, **kw)
    assert np.isinf(np.asarray(margins)).all()  # every position is compared
    return np.asarray(want)


def _forward(tokens, config=CONFIG, params=PARAMS):
    n = len(tokens)
    return np.asarray(forward_full(params, jnp.asarray(tokens)[None], jnp.arange(n)[None],
                                   config=config)[0])


def _engine(attn_backend="ref", **options) -> InferenceEngine:
    cfg = EngineConfig(max_seqs=SLOTS, page_size=PAGE, num_pages=64, max_seq_len=256,
                       prefill_chunk=CHUNK, **options)
    return InferenceEngine(CONFIG, PARAMS, cfg, attn_backend=attn_backend)


def _decode(engine, slot_tokens: dict[int, int]) -> np.ndarray:
    """One ``decode_step`` feeding ``slot_tokens``; the step's logits."""
    active = np.zeros((SLOTS,), bool)
    for slot, token in slot_tokens.items():
        engine.set_last_token(slot, token)
        active[slot] = True
    _, logits = engine.decode(jnp.asarray(active), jnp.zeros((SLOTS,)), jnp.ones((SLOTS,)),
                              jnp.zeros((SLOTS,), jnp.int32), return_logits=True)
    return np.asarray(logits)


# --- EXPERTS ---------------------------------------------------------------------

def _one_layer(config=CONFIG, seed=3, **leaves):
    """A layer's expert leaves drawn for ``config`` (its layer 0) and a batch
    of normed inputs [2, 5, 64]."""
    params = init_params(dataclasses.replace(config, n_layers=len(config.layer_pattern)),
                         jax.random.key(seed))
    lp = {k: v[0] for k, v in params["layers"].items()
          if k in ("router", "moe_in", "moe_out", "shared_in", "shared_out")}
    lp.update(leaves)
    h = jax.random.normal(jax.random.key(seed + 1), (2, 5, config.dim), jnp.float32)
    return lp, h


def _by_hand(h, lp, c):
    """The sub-block as ISSUE 34 writes it, in numpy, a token at a time."""
    h, lp = np.asarray(h, np.float64), {k: np.asarray(v, np.float64) for k, v in lp.items()}
    silu = lambda a: a / (1 + np.exp(-a))  # noqa: E731

    def glu(x, w_in, w_out):
        a, b = np.split(x @ w_in, 2)
        return (silu(a) * b) @ w_out

    out = np.zeros_like(h)
    for idx in np.ndindex(h.shape[:2]):
        x = h[idx]
        r = x @ lp["router"]
        picks = np.argsort(-r, kind="stable")[:c.top_k_experts]
        g = np.exp(r[picks] - r[picks].max())
        g = g / g.sum()  # over ALL picks, held or not
        y = glu(x, lp["shared_in"], lp["shared_out"]) if "shared_in" in lp else 0.0
        for e, ge in zip(picks, g):
            if e < c.n_experts:  # HELD: the first n_experts
                y = y + ge * glu(x, lp["moe_in"][e], lp["moe_out"][e])
        out[idx] = y
    return out


@pytest.mark.parametrize("form", ["dense", "grouped"])
def test_the_held_ranges_shares_and_the_shared_expert_once_are_the_uncut_layer(form, monkeypatch):
    """The routed parts of the two chips' shares, experts [0, 6) and [6, 12),
    each with the gates normalised over ALL picks, plus the shared expert
    counted ONCE equal the layer with all 12 experts here — the reference's
    and by hand. A chip holds the FIRST six of its router: the second chip's
    router has its columns rolled, so that [6, 12) are its first six."""
    monkeypatch.setattr(llama, "MOE_DENSE_TOKENS_MAX", 0 if form == "grouped" else 10 ** 9)
    uncut = dataclasses.replace(CONFIG, n_experts=12, moe_router_width=12)
    lp, h = _one_layer(uncut)
    whole = np.asarray(moe_mlp(h, lp, uncut))
    np.testing.assert_allclose(whole, _by_hand(h, lp, uncut), atol=1e-5)
    no_shared = {**lp, "shared_out": jnp.zeros_like(lp["shared_out"])}
    shares = []
    for first in (0, 6):
        held = {**no_shared, "router": jnp.roll(lp["router"], -first, axis=-1),
                "moe_in": lp["moe_in"][first:first + 6],
                "moe_out": lp["moe_out"][first:first + 6]}
        shares.append(np.asarray(moe_mlp(h, held, CONFIG)))
        np.testing.assert_allclose(shares[-1], _by_hand(h, held, CONFIG), atol=1e-5)
    shared = np.asarray(moe_mlp(h, {**lp, "moe_out": jnp.zeros_like(lp["moe_out"])}, uncut))
    np.testing.assert_allclose(shares[0] + shares[1] + shared, whole, atol=1e-5)
    assert np.abs(shares[0]).max() > 1e-2 and np.abs(shares[1]).max() > 1e-2
    # the reference's loop over the held experts, uncut and a half, is the same layer
    s = granite._sizes(FILE)
    with jax.default_matmul_precision("highest"):
        ref = granite._experts(h.reshape(10, 64), {k: v[None] for k, v in lp.items()}, 0,
                               {**s, "held": 12}, lambda w: w, swap=False)
    np.testing.assert_allclose(np.asarray(ref).reshape(2, 5, 64), whole, atol=1e-5)


@pytest.mark.parametrize("case", ["ties", "an expert without a token", "every pick absent"])
def test_the_grouped_form_equals_the_dense_form_on_the_same_picks(case, monkeypatch):
    lp, h = _one_layer()
    router = np.array(lp["router"])
    if case == "ties":  # experts 1 and 2, 7 and 8 score alike for every token
        router[:, 2], router[:, 8] = router[:, 1], router[:, 7]
    elif case == "an expert without a token":
        router[:, 3] = 0.0
        router[0, 3] = -1e3
        h = h.at[..., 0].set(jnp.abs(h[..., 0]) + 1.0)
    else:  # the held six never among the two largest
        router[:, :6] = 0.0
        router[0, :6] = -1e3
        h = h.at[..., 0].set(jnp.abs(h[..., 0]) + 1.0)
    lp = {**lp, "router": jnp.asarray(router)}
    got = {}
    for form, limit in (("dense", 10 ** 9), ("grouped", 0)):
        monkeypatch.setattr(llama, "MOE_DENSE_TOKENS_MAX", limit)
        got[form] = np.asarray(moe_mlp(h, lp, CONFIG))
    np.testing.assert_allclose(got["grouped"], got["dense"], atol=1e-5)
    np.testing.assert_allclose(got["dense"], _by_hand(h, lp, CONFIG), atol=1e-5)
    if case == "every pick absent":  # what is left is the shared expert alone
        only_shared = {**lp, "moe_out": jnp.zeros_like(lp["moe_out"])}
        np.testing.assert_allclose(got["grouped"], np.asarray(moe_mlp(h, only_shared, CONFIG)),
                                   atol=1e-6)


def test_the_rule_that_picks_the_form_is_on_static_shapes():
    mixtral = LlamaConfig(n_experts=8, top_k_experts=2)
    assert not mixtral.moe_sparse and CONFIG.moe_sparse  # 8 / 2 against 12 / 2 (72 / 10)
    lp, _h = _one_layer()
    jaxpr = lambda n: str(jax.make_jaxpr(  # noqa: E731
        lambda h: moe_mlp(h, lp, CONFIG))(jnp.zeros((1, n, 64), jnp.float32)))
    assert "ragged_dot" not in jaxpr(llama.MOE_DENSE_TOKENS_MAX)
    assert "ragged_dot" in jaxpr(llama.MOE_DENSE_TOKENS_MAX + 1)


def test_the_experts_touched_count_equals_a_count_by_hand_and_leaves_inert_rows_out():
    lp, h = _one_layer()
    live = np.array([[True, False, True, True, False], [False] * 5])
    out, (touched, read) = moe_mlp(h, lp, CONFIG, live=jnp.asarray(live))
    assert int(read) == 6  # dense dispatch (the `ref` backend) reads every held expert
    np.testing.assert_allclose(np.asarray(out), np.asarray(moe_mlp(h, lp, CONFIG)), atol=0)
    r = np.asarray(h) @ np.asarray(lp["router"])
    picks = np.argsort(-r, axis=-1)[..., :2]
    by_hand = {int(e) for e in picks[live].ravel() if e < 6}  # held: [0, 6)
    assert int(touched) == len(by_hand) and 0 < len(by_hand) <= 6
    everyone = {int(e) for e in picks.ravel() if e < 6}
    assert int(moe_mlp(h, lp, CONFIG, live=jnp.ones((2, 5), bool))[1][0]) == len(everyone) \
        > len(by_hand)
    assert int(moe_mlp(h, lp, CONFIG, live=jnp.zeros((2, 5), bool))[1][0]) == 0


# --- FORWARD ---------------------------------------------------------------------

def test_param_count_and_config():
    assert CONFIG.layer_pattern == (MAMBA,) * 5 + (FULL,) + (MAMBA,) * 4
    assert CONFIG.rope_theta is None and CONFIG.attention_scale == 2 ** -7
    assert (CONFIG.n_attn_layers, CONFIG.n_state_layers, CONFIG.has_state) == (1, 9, True)
    assert (CONFIG.n_experts, CONFIG.moe_router_width) == (6, 12)
    assert CONFIG.state_shape == (8, 16, 16) and CONFIG.conv_shape == (3, 128 + 32)
    tree = PARAMS["layers"]
    assert {tree[k].shape[0] for k in tree if k.startswith("ssm_")} == {9}
    assert {tree[k].shape[0] for k in tree if k.startswith("attn_")} == {1}
    assert tree["moe_in"].shape == (10, 6, 64, 64) and tree["router"].shape == (10, 64, 12)
    assert tree["shared_in"].shape == (10, 64, 96) and "lm_head" not in PARAMS
    n = sum(x.size for x in jax.tree.leaves(PARAMS))
    assert n == n_params(CONFIG) == granite.param_counts(FILE)["total"]


def test_a_large_stack_is_drawn_layer_by_layer_in_place(monkeypatch):
    monkeypatch.setattr(llama, "SLICED_INIT_MIN_ELEMS", 10 * 6 * 64 * 64 - 1)  # moe_in alone
    sliced = init_params(CONFIG, jax.random.key(0))
    assert sliced["layers"]["moe_in"].shape == (10, 6, 64, 64)
    assert float(jnp.std(sliced["layers"]["moe_in"])) == pytest.approx(64 ** -0.5, rel=0.02)
    assert float(jnp.abs(sliced["layers"]["moe_in"][3] - sliced["layers"]["moe_in"][4]).max()) > 0
    monkeypatch.undo()  # the same draw with no leaf sliced (PARAMS come from ONE compiled
    whole = init_params(CONFIG, jax.random.key(0))  # initialiser: an ulp from a draw a leaf)
    np.testing.assert_array_equal(np.asarray(sliced["layers"]["moe_out"]),
                                  np.asarray(whole["layers"]["moe_out"]))


@pytest.mark.parametrize("chunk", [5, 64])
def test_forward_equals_the_reference_whatever_the_block(chunk):
    tokens = _tokens(37, seed=chunk)
    got = _forward(tokens, dataclasses.replace(CONFIG, ssm_chunk=chunk))
    np.testing.assert_allclose(got, _reference(tokens, list(range(37))), atol=TOL)
    assert np.std(got) > 0.03  # the comparison is not vacuous


def test_many_tokens_take_the_grouped_form_and_still_equal_the_reference():
    tokens = _tokens(llama.MOE_DENSE_TOKENS_MAX + 9, seed=1)
    at = [0, 77, len(tokens) - 1]
    np.testing.assert_allclose(_forward(tokens)[at], _reference(tokens, at), atol=TOL)


# a layer pattern of three, for the compile's sake: what is left out is a term
# of the layer, not of the depth
SHORT = dict(FILE, num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"])
SHORT_CONFIG = dataclasses.replace(granite.program_config(SHORT), dtype=jnp.float32)
SHORT_PARAMS = init_params(SHORT_CONFIG, jax.random.key(1))
LEFT_OUT = {
    "the residual's 0.22": {"residual_multiplier": 1.0},
    "the softmax scale 2^-7": {"attention_scale": None},
    "the embedding's 12": {"embedding_multiplier": 1.0},
    "the logits' 1 / 16": {"lm_head_multiplier": 1.0},
    "the shared expert": "shared_out",
    "the conv bias": "ssm_conv_b",
    "D x": "ssm_D",
    # a router that scores only the experts held here: every pick is held
    "the picks on absent experts": ({"moe_router_width": 6}, "router", lambda r: r[..., :6]),
}


@pytest.mark.parametrize("left_out", list(LEFT_OUT))
def test_a_program_without_one_term_of_the_layer_is_not_the_reference(left_out):
    tokens = _tokens(33, seed=7)
    want = _reference(tokens, list(range(33)), file=SHORT, params=SHORT_PARAMS)
    np.testing.assert_allclose(_forward(tokens, SHORT_CONFIG, SHORT_PARAMS), want, atol=TOL)
    change, params = LEFT_OUT[left_out], SHORT_PARAMS
    if isinstance(change, str):  # the term's own parameter, at zero
        layers = {**params["layers"], change: jnp.zeros_like(params["layers"][change])}
        params, change = {**params, "layers": layers}, {}
    elif isinstance(change, tuple):  # a field and the leaf that goes with it
        change, leaf, cut = change
        params = {**params, "layers": {**params["layers"], leaf: cut(params["layers"][leaf])}}
    got = _forward(tokens, dataclasses.replace(SHORT_CONFIG, **change), params)
    assert np.abs(got - want).max() > 10 * TOL, left_out


def test_the_gates_are_normalised_over_all_picks_not_over_the_held_ones(monkeypatch):
    """A program that renormalised over the picks it holds would still sum to
    one a token, and would not be the reference."""
    lp, h = _one_layer()
    want = _by_hand(h, lp, CONFIG)
    np.testing.assert_allclose(np.asarray(moe_mlp(h, lp, CONFIG)), want, atol=1e-5)
    real = jax.nn.one_hot

    def held_only(idx, n, dtype):  # the gate matrix's rows renormalised over the held picks
        hot = real(idx, n, dtype=dtype)
        return hot * 2.0 if hot.ndim == 4 else hot

    monkeypatch.setattr(llama.jax.nn, "one_hot", held_only)
    assert np.abs(np.asarray(moe_mlp(h, lp, CONFIG)) - want).max() > 1e-3


def test_a_flip_at_the_smallest_margin_hardly_moves_the_logits():
    """The account of the routing margin (PERF.md section 4): the 10th and the
    11th of 72 logits lie 0.06 sigma apart on average, so bfloat16 flips such
    picks all the time; a flip exchanges the SMALLEST gate's expert for its
    near-equal. Made on purpose at EVERY token of one layer, at this size
    (2 picks of 12, a gate of about 0.4: far more than 1 of 10), it still
    moves the logits by a small part of their spread."""
    tokens = _tokens(40, seed=9)
    at = list(range(40))
    want = _reference(tokens, at)
    for layer in (0, 9):
        moved = _reference(tokens, at, swap_layer=layer)
        rel = np.sqrt(np.mean((moved - want) ** 2, axis=-1)) / np.std(want, axis=-1)
        assert 0 < np.median(rel) < 0.25, (layer, np.median(rel))


# --- SPLIT / RAGGED ----------------------------------------------------------------

@pytest.mark.parametrize("prompt_len", [7, 29])
def test_prefill_in_chunks_then_decode_token_by_token(prompt_len):
    tokens = _tokens(prompt_len + 6, seed=prompt_len)
    want = _reference(tokens, list(range(prompt_len - 1, len(tokens))))
    engine = _engine()
    engine.set_page_table_row(2, [5, 6, 7, 8])
    got = [np.asarray(engine.prefill(2, tokens[:prompt_len]))]
    got += [_decode(engine, {2: t})[2] for t in tokens[prompt_len:]]
    np.testing.assert_allclose(np.stack(got), want, atol=TOL)


def test_ragged_round_with_rows_at_both_ends_of_the_buffer():
    """One packed buffer of exactly 26 tokens: a decode row that ends at the
    buffer's first token, a prompt's first chunk, another prompt's second
    chunk, and a decode row that starts at the buffer's last token; the next
    decode step of all four slots still equals the reference."""
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
    from finchat_tpu.engine.kv_cache import PageAllocator
    from perfbench import correct

    class Sched:
        engine = _engine(mixed_step=True)
        free_slots = [0, 1, 2, 3]
        allocator = PageAllocator(64)

    tokens = _tokens(CHUNK * 3 // 2 + 5, seed=5)
    prompt, forced = tokens[:CHUNK * 3 // 2], tokens[CHUNK * 3 // 2:]
    want = _reference(tokens, list(range(len(prompt) - 1, len(tokens))))
    for i, got in correct._ragged_path_logits(Sched, prompt, forced):
        np.testing.assert_allclose(got[:300], want[i], atol=TOL)
    for got, w in zip(correct._split_path_logits(Sched, prompt, forced), want):
        np.testing.assert_allclose(got, w, atol=TOL)
    assert float(jnp.abs(Sched.engine.state.ssm_state).max()) == 0.0
    assert float(jnp.abs(Sched.engine.state.conv_state).max()) == 0.0


def test_the_state_a_slot_ends_on_is_the_references_and_is_kept_in_float32():
    """What the logits cannot show in this model (PERF.md section 4) is read
    off the state itself, on both of the benchmark's paths: layer by layer
    the slot's state is the reference's after the same tokens and uses all of
    float32's mantissa; the control — the reference's state rounded to
    bfloat16 after every token — stands apart on both numbers."""
    from types import SimpleNamespace

    from finchat_tpu.engine.kv_cache import PageAllocator
    from perfbench import state_control

    sched = SimpleNamespace(engine=_engine(mixed_step=True), free_slots=[0, 1, 2, 3],
                            allocator=PageAllocator(64))
    tokens = _tokens(CHUNK * 3 // 2 + 5, seed=6)
    prompt, forced = tokens[:CHUNK * 3 // 2], tokens[CHUNK * 3 // 2:]
    want = granite.reference_state(PARAMS, tokens, FILE)
    assert want.shape == (9, *CONFIG.state_shape)
    assert float(jnp.abs(want).max(axis=(1, 2, 3)).min()) > 0  # no layer's state is empty
    states = state_control.program_states(sched, prompt, forced)
    assert set(states) == {"split", "ragged"} and "reset_slot" not in vars(sched.engine)
    for path, got in states.items():
        assert got.shape == want.shape
        assert max(granite.state_distance(got, want)) < 1e-4, path
        assert min(granite.kept_mantissa_bits(got)) == 23, path
    control = granite.reference_state(PARAMS, tokens, FILE, state_dtype=jnp.bfloat16)
    assert min(granite.state_distance(control, want)) > 5e-4
    assert max(granite.kept_mantissa_bits(control)) == 7
    assert granite.kept_mantissa_bits(jnp.asarray(want, jnp.bfloat16)) == [7] * 9  # stored in it


def test_an_engine_that_keeps_the_state_below_the_files_dtype_is_refused(monkeypatch):
    from finchat_tpu.engine import engine as engine_module

    leaves = engine_module._ssm_leaves
    monkeypatch.setattr(engine_module, "_ssm_leaves", lambda c, n: {
        k: v.astype(jnp.bfloat16) for k, v in leaves(c, n).items()})
    with pytest.raises(ValueError, match="ssm_state_dtype: the file states float32.*bfloat16"):
        granite.program_config(FILE)
    monkeypatch.undo()
    assert granite.program_config(dict(FILE, ssm_state_dtype="float32")).has_state


# --- CACHES ----------------------------------------------------------------------

def test_the_pool_has_one_layers_depth_and_the_state_nine():
    engine = _engine()
    state = engine.state
    assert state.k_pages.shape == state.v_pages.shape == (1, 64, PAGE, 2 * 16)
    assert state.ssm_state.shape == (9, SLOTS, 8, 16, 16) and state.ssm_state.dtype == jnp.float32
    assert state.conv_state.shape == (9, SLOTS, 3, 160)
    assert page_hbm_bytes(CONFIG, PAGE) == 2 * PAGE * 32 * 4  # K and V in ONE layer
    assert engine.ssm_state_bytes == 9 * SLOTS * (8 * 16 * 16 + 3 * 160) * 4
    # the benchmark's adapter counts the same bytes
    assert granite.kv_bytes_per_token(FILE) * PAGE == page_hbm_bytes(CONFIG, PAGE)
    assert SLOTS * 9 * (granite.ssm_state_bytes_per_row(FILE)
                        + granite.conv_tail_bytes_per_row(FILE)) == engine.ssm_state_bytes
    engine.set_page_table_row(1, [3, 4])
    engine.prefill(1, _tokens(20, seed=2))
    per_layer = np.abs(np.asarray(engine.state.ssm_state[:, 1])).reshape(9, -1).max(axis=1)
    assert (per_layer > 0).all() and len({float(v) for v in per_layer}) == 9
    assert float(jnp.abs(engine.state.ssm_state[:, 0]).max()) == 0.0


# --- HEADS -----------------------------------------------------------------------

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
    prompt = HEAD + _tokens(13, seed=12)
    _handle, whole = _run(_scheduler(mixed_step=mixed), prompt)
    sched = _scheduler(mixed_step=mixed)
    restores = METRICS.get("finchat_ssm_snapshot_restores_total")
    assert sched.register_prefix(HEAD + [1, 2, 3]) == len(HEAD)
    snap = sched._prefixes[0].ssm_snap
    assert snap[0].shape == (9, 8, 16, 16) and float(jnp.abs(snap[0]).max()) > 0.0
    assert float(jnp.abs(sched.engine.state.ssm_state).max()) == 0.0  # the head's slot went back clean
    handle, resumed = _run(sched, prompt)
    assert handle.shared_len == len(HEAD) and handle.span.state_restored_tokens == len(HEAD)
    assert METRICS.get("finchat_ssm_snapshot_restores_total") == restores + 1
    assert resumed == whole and len(whole) == 6
    assert METRICS.get("finchat_ssm_state_bytes") == sched.engine.ssm_state_bytes > 0


# --- COUNT -----------------------------------------------------------------------

def test_the_decode_step_counts_the_held_experts_of_live_rows_over_the_layers():
    engine = _engine()
    for slot in range(SLOTS):
        engine.set_page_table_row(slot, [1 + 2 * slot, 2 + 2 * slot])
        engine.prefill(slot, _tokens(9 + slot, seed=slot))
    snapshot = jax.tree.map(jnp.copy, engine.state)

    def count(slot_tokens, others=0):
        engine.state = jax.tree.map(jnp.copy, snapshot)
        for slot in range(SLOTS):
            engine.set_last_token(slot, others)
        _decode(engine, slot_tokens)
        touched, read = (int(count) for count in engine.moe_experts)
        assert read == 10 * 6  # the `ref` backend: dense dispatch over the 6 held, ten layers
        return touched

    one = count({2: 5})
    assert 0 <= one <= 10 * 2  # ten layers, two picks a token, half of them held on average
    assert count({2: 5}, others=123) == one  # what an inert row would have picked is not counted
    assert one <= count({0: 9, 1: 8, 2: 5, 3: 7}) <= 10 * 6
    assert count({}) == 0
    dense = InferenceEngine(llama.PRESETS["moe-tiny"], init_params(llama.PRESETS["moe-tiny"],
                            jax.random.key(0)), EngineConfig(max_seqs=2, page_size=8, num_pages=8,
                            max_seq_len=64, prefill_chunk=8), attn_backend="ref")
    dense.decode(jnp.zeros((2,), bool), jnp.zeros((2,)), jnp.ones((2,)), jnp.zeros((2,), jnp.int32))
    assert dense.moe_experts is None  # emitted only where the model routes sparsely


def test_the_scheduler_books_the_count_on_deliver_and_on_the_rounds_event():
    TRACER.configure(enabled=True)
    TRACER.clear()
    sched = _scheduler()
    before = {name: METRICS.get(name) for name in
              ("finchat_moe_experts_touched_total", "finchat_moe_layer_steps_total")}
    _handle, tokens = _run(sched, _tokens(15, seed=4), n_new=6)
    steps = METRICS.get("finchat_moe_layer_steps_total") - before["finchat_moe_layer_steps_total"]
    touched = (METRICS.get("finchat_moe_experts_touched_total")
               - before["finchat_moe_experts_touched_total"])
    # the first token is the prefill's; the step in flight when the answer
    # reaches its length is delivered to nobody, and counted: it ran
    assert steps in (10 * (len(tokens) - 1), 10 * len(tokens))
    assert 0 < touched <= 2 * steps
    noted = [args["experts_touched"] for _ts, _tid, name, _dur, _track, args in TRACER.snapshot()
             if name == "round" and "experts_touched" in (args or {})]
    assert 10 * len(noted) == steps and sum(noted) == touched
    TRACER.configure(enabled=False)
    TRACER.clear()


@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
def test_the_scheduler_books_the_experts_a_steps_form_read_beside_those_touched(backend):
    """``finchat_moe_experts_read_total``: through the touched pass (a kernel
    backend) a step read what it touched; through dense dispatch (``ref``)
    every held expert, 6 a layer a step. The `round` event carries both."""
    TRACER.configure(enabled=True)
    TRACER.clear()
    sched = _scheduler(attn_backend=backend)
    names = ("finchat_moe_experts_touched_total", "finchat_moe_experts_read_total",
             "finchat_moe_layer_steps_total")
    before = {name: METRICS.get(name) for name in names}
    _run(sched, _tokens(15, seed=4), n_new=4)
    touched, read, steps = (METRICS.get(name) - before[name] for name in names)
    assert steps > 0 and 0 < touched <= 2 * steps
    assert read == (touched if backend == "pallas-interpret" else 6 * steps)
    noted = [(args["experts_touched"], args["experts_read"])
             for _ts, _tid, name, _dur, _track, args in TRACER.snapshot()
             if name == "round" and "experts_read" in (args or {})]
    assert 10 * len(noted) == steps
    assert (sum(t for t, _r in noted), sum(r for _t, r in noted)) == (touched, read)
    TRACER.configure(enabled=False)
    TRACER.clear()


def test_the_read_counter_is_absent_for_a_model_that_does_not_route_sparsely():
    dense = llama.PRESETS["moe-tiny"]
    engine = InferenceEngine(dense, init_params(dense, jax.random.key(0)),
                             EngineConfig(max_seqs=2, page_size=8, num_pages=8, max_seq_len=64,
                                          prefill_chunk=8), attn_backend="ref")
    view = METRICS.labeled(replica="not-sparse")
    ContinuousBatchingScheduler(engine, eos_id=-1, metrics=view)
    sparse = METRICS.labeled(replica="sparse")
    ContinuousBatchingScheduler(_engine(), eos_id=-1, metrics=sparse)
    rendered = METRICS.render_prometheus()
    for name in ("finchat_moe_experts_read_total", "finchat_moe_experts_touched_total"):
        assert f'{name}{{replica="sparse"}} 0' in rendered
        assert f'{name}{{replica="not-sparse"}}' not in rendered


# --- REFUSED ---------------------------------------------------------------------

@pytest.mark.parametrize("options,named", [
    ({"spec_tokens": 2}, "engine.spec_tokens"),
    ({"kv_sink_pages": 1, "kv_window_pages": 4}, "engine.kv_sink_pages"),
])
def test_engine_options_that_would_not_carry_the_state_are_refused_by_name(options, named):
    with pytest.raises(ValueError, match=named):
        _engine(**options)


STATELESS = LlamaConfig(n_experts=6, top_k_experts=2, moe_router_width=12, moe_fused_glu=True,
                        hidden_dim=32, dtype=jnp.float32)  # a held range, no recurrent state


def test_a_mesh_with_an_expert_axis_and_a_held_range_are_one_too_many():
    from finchat_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=1, pipe=1, seq=1, expert=2, model=1),
                      devices=jax.devices()[:2])
    cfg = EngineConfig(max_seqs=2, page_size=8, num_pages=8, max_seq_len=64, prefill_chunk=8)
    params = jax.eval_shape(lambda: init_params(STATELESS, jax.random.key(0)))
    with pytest.raises(ValueError, match="one or the other says where experts live"):
        InferenceEngine(STATELESS, params, cfg, mesh=mesh, attn_backend="ref")
    with pytest.raises(ValueError, match="mesh"):  # and PR 27's lines, for a model with state
        InferenceEngine(CONFIG, PARAMS, dataclasses.replace(cfg, max_seqs=SLOTS), mesh=mesh,
                        attn_backend="ref")


def test_quantized_weights_are_refused_for_the_grouped_stacks():
    cfg = EngineConfig(max_seqs=2, page_size=8, num_pages=8, max_seq_len=64, prefill_chunk=8)
    params = jax.eval_shape(lambda: init_params(STATELESS, jax.random.key(0)))
    with pytest.raises(ValueError, match="model.quant='int8'"):
        InferenceEngine(STATELESS, params, cfg, attn_backend="ref", quant="int8")


@pytest.mark.parametrize("fields,said", [
    ({"layer_pattern": (MAMBA, FULL), "n_layers": 2}, "ssm_heads and 'mamba' layers go together"),
    ({"layer_pattern": (FULL,), "ssm_heads": 2, "ssm_head_dim": 8, "ssm_state": 8},
     "ssm_heads and 'mamba' layers go together"),
    ({"layer_pattern": (MAMBA, LINEAR), "n_layers": 2, "ssm_heads": 2, "gdn_heads": 2},
     "one shape"),
    ({"n_experts": 6, "moe_router_width": 4, "moe_fused_glu": True},
     "are not among the router's 4"),
    ({"n_experts": 6, "moe_shared_dim": 8}, "fused GLU layout"),
    ({"layer_pattern": (MAMBA, "sliding"), "n_layers": 2, "ssm_heads": 2}, "kinds are"),
])
def test_configs_that_do_not_hold_together_are_refused(fields, said):
    with pytest.raises(ValueError, match=said):
        LlamaConfig(**fields)


def test_the_mixer_in_every_layer_is_still_ssm_heads_without_a_pattern():
    falcon = LlamaConfig(ssm_heads=2, ssm_head_dim=8, ssm_state=8)
    assert falcon.n_state_layers == falcon.n_layers == falcon.n_attn_layers and falcon.has_state
    tree = jax.eval_shape(lambda: init_params(falcon, jax.random.key(0)))["layers"]
    assert tree["ssm_in"].shape[0] == tree["attn_q"].shape[0] == falcon.n_layers
