"""``mimo_v2_flash`` (MiMo-V2-Flash, PR 57) on the llama block, at a size a test
holds: attention whose shape is a KIND's — ONE leading dense full layer, two
periods of three sliding layers and a full one; 16 query heads over 2 K/V heads
(rotated at 5e6) in the full layers and 4 (at 1e4, a window of 8 tokens, a SINK
in the softmax) in the sliding ones; keys of 192 over values of 128, the first
64 dims of a head rotated, v scaled by 0.707; 4 of 16 routed experts held, no
shared expert. Everything against the plain reference of
``perfbench/models/mimo_v2_flash.py`` (float32, a block of queries and an expert
at a time, no pages).

FORWARD  the cache-less forward past the window's edge; each of the five
         pieces faulted in the reference and the int8 control; the counts; the
         shares of a routed layer
SPLIT    prefill then decode through BOTH pools, past the window and past a
         page boundary, on the reference backend and the interpreted kernels
POOLS    the two pools' widths and depths by kind; what the tree holds by kind
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import tiny_models

from finchat_tpu.engine.engine import InferenceEngine
from finchat_tpu.engine.kv_cache import page_hbm_bytes, window_pages_per_row
from finchat_tpu.models.llama import (
    FULL,
    PRESETS,
    WINDOW,
    AttnKind,
    LlamaConfig,
    forward_full,
    init_params,
    n_params,
    rope,
)
from finchat_tpu.utils.config import EngineConfig
from perfbench.models import mimo_v2_flash

ROOT = Path(__file__).resolve().parents[1]
FILE = tiny_models.FILES["mimo_v2_flash"]
CONFIG, PARAMS = tiny_models.build("mimo_v2_flash")
PAGE, CHUNK, SLOTS = tiny_models.SHAPES["mimo_v2_flash"]
W = FILE["sliding_window"]
TOL = 3e-4  # float32 against float32; the logits' spread is about 1
VOCAB = FILE["vocab_size"]


def _tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, VOCAB, size=n)]


def _reference(tokens, positions, file=FILE, params=PARAMS, **kw):
    want, margins = mimo_v2_flash.reference_logits(params, tokens, file, positions=positions, **kw)
    assert np.isfinite(np.asarray(margins)).all()  # every layer behind the first routes
    return np.asarray(want)


def _program(tokens):
    n = len(tokens)
    return np.asarray(forward_full(PARAMS, jnp.asarray(tokens)[None], jnp.arange(n)[None],
                                   config=CONFIG, attn_backend="ref")[0])


def _engine(attn_backend="ref", **options) -> InferenceEngine:
    cfg = EngineConfig(max_seqs=SLOTS, page_size=PAGE, num_pages=160, max_seq_len=256,
                       **{"prefill_chunk": CHUNK, **options})
    return InferenceEngine(CONFIG, PARAMS, cfg, attn_backend=attn_backend)


def _decode(engine, slot_tokens: dict[int, int]) -> np.ndarray:
    active = np.zeros((SLOTS,), bool)
    for slot, token in slot_tokens.items():
        engine.set_last_token(slot, token)
        active[slot] = True
    _, logits = engine.decode(jnp.asarray(active), jnp.zeros((SLOTS,)), jnp.ones((SLOTS,)),
                              jnp.zeros((SLOTS,), jnp.int32), return_logits=True)
    return np.asarray(logits, np.float32)


def _split(engine, tokens, prompt_len, slot=2):
    """``engine.prefill`` then a ``decode`` a token: the logits from the
    prompt's last position on."""
    engine.set_page_table_row(slot, list(range(5, 5 + -(-len(tokens) // PAGE))))
    got = [np.asarray(engine.prefill(slot, tokens[:prompt_len]), np.float32)]
    return np.stack(got + [_decode(engine, {slot: t})[slot] for t in tokens[prompt_len:]])


# --- FORWARD ---------------------------------------------------------------------

def test_param_count_and_config():
    c = CONFIG
    assert c.layer_pattern == (WINDOW, WINDOW, WINDOW, FULL) and c.leading_kinds == (FULL,)
    assert (c.n_attn_layers, c.n_window_layers, c.n_kv_layers, c.n_state_layers) == (3, 6, 9, 0)
    assert c.moe_sparse and not c.has_state and c.cache_readers == 1
    assert c.attn_kind(FULL) == AttnKind(2, 5e6) and c.attn_kind(WINDOW) == AttnKind(4, 1e4, True)
    assert (c.head_dim, c.value_dim, c.rope_dim, c.value_scale) == (192, 128, 64, 0.707)
    # the adapter reads the published keys into the preset written by hand
    assert c == dataclasses.replace(PRESETS["mimo-tiny"], dtype=jnp.float32)
    leaves = sum(x.size for x in jax.tree.leaves(PARAMS))
    assert leaves == n_params(c) == mimo_v2_flash.param_counts(FILE)["total"]


def test_k_and_v_are_stacked_by_kind_and_a_leading_layers_leaf_stands_in_its_kinds():
    layers, dense = PARAMS["layers"], PARAMS["dense_layers"]
    shapes = {name: leaf.shape for name, leaf in layers.items() if name[:4] in ("attn", "swa_")}
    assert shapes == {"attn_q": (8, 64, 16 * 192), "attn_o": (8, 16 * 128, 64),
                      "attn_k": (2, 64, 2 * 192), "attn_v": (2, 64, 2 * 128),
                      "swa_k": (6, 64, 4 * 192), "swa_v": (6, 64, 4 * 128), "swa_sink": (6, 16)}
    assert layers["swa_sink"].dtype == jnp.float32 and 0.6 < float(layers["swa_sink"].std()) < 1.4
    # the leading layer is a full one: its k and v are that kind's, and it has no sink
    assert dense["attn_k"].shape == (1, 64, 2 * 192) and dense["attn_v"].shape == (1, 64, 2 * 128)
    assert not any(name.startswith("swa_") for name in dense)
    assert layers["moe_in"].shape == (8, 4, 64, 64) and layers["router"].shape == (8, 64, 16)
    assert "shared_in" not in layers and "mlp_gate" not in layers


def test_the_published_widths_count_what_the_issue_counts():
    """3,898.6 M parameters at the cut (the leading dense layer + one period of
    five sliding layers and a full one, 16 of 256 experts, half the
    vocabulary), 308.8 B uncut; analytic, the adapter's and the tree's shapes,
    nothing drawn."""
    file = json.loads((ROOT / "perfbench/configs/mimo-v2-flash.json").read_text())
    c = mimo_v2_flash.program_config(file)
    tree = jax.eval_shape(lambda: init_params(c, jax.random.key(0)))
    total = sum(x.size for x in jax.tree.leaves(tree))
    assert total == n_params(c) == mimo_v2_flash.param_counts(file)["total"]
    # (the issue's 4,523.6 M less the half of the vocabulary its fallback cut: 2 x 312.5 M)
    assert abs(total + 2 * 76_288 * 4096 - 4_523.6e6) < 0.1e6 and c.vocab_size == 76_288
    assert c.leading_kinds == (FULL,) and c.layer_pattern == (WINDOW,) * 5 + (FULL,)
    assert (c.n_attn_layers, c.n_window_layers) == (2, 5)
    assert c.kv_widths(FULL) == (768, 512) and c.kv_widths(WINDOW) == (1536, 1024)
    whole = PRESETS["mimo-v2-flash"]
    assert abs(n_params(whole) - 308.8e9) < 0.05e9
    assert (whole.n_of(FULL), whole.n_of(WINDOW)) == (9, 39)
    # the published pattern: layer 0 full, four sliding, one full, then seven times (5, 1)
    assert list(file["reduced"]["hybrid_layer_pattern"]["from"]) == [
        int(kind == WINDOW) for kind in whole.leading_kinds + whole.layer_pattern]


def test_the_forward_without_a_cache_equals_the_reference_past_the_windows_edge():
    tokens = _tokens(37, seed=1)
    np.testing.assert_allclose(_program(tokens), _reference(tokens, list(range(37))), atol=TOL)


@pytest.mark.parametrize("fault", mimo_v2_flash.FAULTS)
def test_the_reference_with_a_piece_faulted_differs_from_the_program(fault):
    """No sink, the whole head rotated, one base for both kinds, no value
    scale, no window: each moves the logits by far more than the tolerance, so
    the comparison holds the program to each piece."""
    tokens = _tokens(37, seed=1)
    got = _program(tokens)
    assert np.abs(_reference(tokens, list(range(37)), fault=fault) - got).max() > 100 * TOL
    if fault == "window_off":  # window_control.py's two keywords
        assert np.abs(_reference(tokens, [36], window_off=True) - got[36:]).max() > 100 * TOL
        np.testing.assert_allclose(_reference(tokens, [36], cross_own=True), got[36:], atol=TOL)


def test_the_int8_control_departs_from_the_program():
    tokens = _tokens(37, seed=1)
    want, _ = mimo_v2_flash.control_logits(PARAMS, tokens, FILE, positions=list(range(37)))
    assert np.abs(np.asarray(want) - _program(tokens)).max() > 30 * TOL


def test_a_part_of_a_head_is_rotated_and_the_rest_passes():
    x = jnp.asarray(np.random.RandomState(0).standard_normal((2, 5, 3, 192)), jnp.float32)
    pos = jnp.asarray([[0, 1, 2, 3, 4], [7, 8, 9, 10, 11]])
    got = rope(x, pos, 1e4, 64)
    np.testing.assert_array_equal(np.asarray(got[..., 64:]), np.asarray(x[..., 64:]))
    np.testing.assert_allclose(np.asarray(got[..., :64]), np.asarray(rope(x[..., :64], pos, 1e4)),
                               atol=1e-6)
    # dim i is paired with i + 32: position 0 rotates nothing, and a pair keeps its norm
    np.testing.assert_allclose(np.asarray(got[0, 0]), np.asarray(x[0, 0]), atol=1e-6)
    pair = lambda t: np.asarray(t[..., :32]) ** 2 + np.asarray(t[..., 32:64]) ** 2  # noqa: E731
    np.testing.assert_allclose(pair(got), pair(x), rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(rope(x, pos, 1e4, 192)), np.asarray(rope(x, pos, 1e4)))


def test_the_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """Four chips hold four experts each of the router's sixteen: what each
    adds (gates normalised over ALL of a token's picks, a pick on an absent
    expert nothing) sums to the layer with every expert held — in the program
    (``moe_mlp``) and in the reference alike."""
    from finchat_tpu.models.llama import moe_mlp

    uncut = dataclasses.replace(CONFIG, n_experts=16)
    whole = init_params(uncut, jax.random.key(3))["layers"]
    h = jnp.asarray(np.random.RandomState(1).standard_normal((1, 9, 64)), jnp.float32)
    lp = {name: leaf[2] for name, leaf in whole.items()}
    want = np.asarray(moe_mlp(h, lp, uncut))
    shares, ref_shares = [], []
    s = mimo_v2_flash._sizes(FILE)
    for chip in range(4):
        held = slice(4 * chip, 4 * chip + 4)
        # a chip's tree: the router's columns rolled so that ITS range is [0, 4)
        roll = lambda a: jnp.roll(a, -4 * chip, axis=-1)  # noqa: E731
        mine = {**lp, "moe_in": lp["moe_in"][held], "moe_out": lp["moe_out"][held],
                "router": roll(lp["router"]), "router_bias": roll(lp["router_bias"])}
        shares.append(np.asarray(moe_mlp(h, mine, CONFIG)))
        stacked = {name: leaf[None] for name, leaf in mine.items()}
        ref_shares.append(np.asarray(mimo_v2_flash._experts(h[0], stacked, 0, s, lambda w: w)[0]))
    np.testing.assert_allclose(sum(shares), want, atol=1e-5)
    np.testing.assert_allclose(sum(ref_shares), want[0], atol=1e-5)
    assert all(np.abs(share).max() > 1e-3 for share in shares)  # every chip adds something


def test_the_routing_margin_is_a_held_experts_gap_to_the_line():
    x = jnp.asarray(np.random.RandomState(0).standard_normal((6, 64)), jnp.float32)
    router, bias = PARAMS["layers"]["router"][0], PARAMS["layers"]["router_bias"][0]
    picks, gates, margin = mimo_v2_flash._route(x, router, bias, top_k=2, gate_scale=1.0,
                                                norm=True, held=4)
    choice = np.asarray(jax.nn.sigmoid(x @ router) + bias)
    ranked = np.sort(choice, axis=-1)[:, ::-1]
    want = [min(c - r[2] if c >= r[1] else r[1] - c for c in row[:4])
            for row, r in zip(choice, ranked)]
    np.testing.assert_allclose(np.asarray(margin),
                               np.asarray(want) / (mimo_v2_flash.MARGIN_UNIT * choice.std(-1)),
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-5)  # unscaled, normalised
    assert np.asarray(picks).shape == (6, 2)


# --- SPLIT -----------------------------------------------------------------------

@pytest.mark.parametrize("prompt_len, backend", [(7, "ref"), (29, "ref"), (29, "pallas-interpret")])
def test_prefill_in_chunks_then_decode_through_both_pools_past_the_window(prompt_len, backend):
    """Chunks of two pages that start mid-page, then a token a step across
    page boundaries: the full layers' pages grow, the sliding layers' slide."""
    tokens = _tokens(prompt_len + 14, seed=prompt_len)
    want = _reference(tokens, list(range(prompt_len - 1, len(tokens))))
    engine = _engine(backend)
    np.testing.assert_allclose(_split(engine, tokens, prompt_len), want, atol=TOL)
    if prompt_len > 3 * W:
        assert int(engine.state.win_gaps[2]) == (len(tokens) - 1 - W + 1) // PAGE * PAGE
        assert len(engine.window_pager.pages_of(2)) <= window_pages_per_row(W, PAGE) - 1


def test_the_decode_step_counts_the_held_experts_its_rows_touched():
    engine = _engine("pallas-interpret")
    tokens = _tokens(12, seed=2)
    engine.set_page_table_row(1, [1, 2, 3, 4])
    engine.prefill(1, tokens[:11])
    _decode(engine, {1: tokens[11]})
    touched, read = (int(n) for n in np.asarray(engine.moe_experts))
    # one row: 2 picks of 16 in each of the 8 routed layers, a quarter of them held
    assert 0 < touched <= 8 * 2 and read == touched


# --- POOLS -----------------------------------------------------------------------

def test_the_pools_are_as_wide_as_their_kinds_heads_and_k_is_wider_than_v():
    engine = _engine()
    s = engine.state
    assert s.k_pages.shape == (3, 160, PAGE, 2 * 192) and s.v_pages.shape == (3, 160, PAGE, 2 * 128)
    per_row = window_pages_per_row(W, PAGE)
    assert s.win_k_pages.shape[0] == 6 and s.win_k_pages.shape[2:] == (PAGE, 4 * 192)
    assert s.win_v_pages.shape[2:] == (PAGE, 4 * 128) and s.win_table.shape == (SLOTS, per_row)
    assert CONFIG.kv_widths(FULL) == CONFIG.kv_row_widths == (384, 256)
    assert CONFIG.kv_widths(WINDOW) == (768, 512)
    assert page_hbm_bytes(CONFIG, PAGE) == 3 * PAGE * (384 + 256) * 4
    assert page_hbm_bytes(CONFIG, PAGE, kind="window") == 6 * PAGE * (768 + 512) * 4


@pytest.mark.parametrize("fields, said", [
    (dict(attn_kinds=((FULL, AttnKind(2)),)), "once each"),
    (dict(attn_kinds=((FULL, AttnKind(2)), (WINDOW, AttnKind(3)))), "divide n_heads"),
    (dict(attn_kinds=((FULL, AttnKind(2, sink=True)), (WINDOW, AttnKind(4)))), "a sink is the"),
    (dict(rope_kinds=(WINDOW,)), "no rope_kinds"),
    (dict(rope_dim=7), "even number"),
    (dict(rope_dim=256), "even number"),
])
def test_shapes_by_kind_that_do_not_hold_together_are_refused(fields, said):
    with pytest.raises(ValueError, match=said):
        dataclasses.replace(CONFIG, **fields)


def test_an_accepted_configuration_answers_for_a_kind_as_it_did_for_the_model():
    """A configuration that names no shape by kind: both kinds have the
    model's K/V heads, a page's two arrays are equally wide, and the kinds
    that ``rope_kinds`` names are rotated at the model's base."""
    trinity = PRESETS["trinity-tiny"]
    assert trinity.attn_kind(FULL) == AttnKind(2, None) and trinity.attn_kind(WINDOW) == AttnKind(2, 1e4)
    assert trinity.kv_widths(WINDOW) == trinity.kv_widths(FULL) == trinity.kv_row_widths == (32, 32)
    plain = LlamaConfig()
    assert plain.attn_kind() == AttnKind(2, 1e4) and plain.kv_row_widths == (64, 64)
    assert plain.value_dim == plain.head_dim == 32
