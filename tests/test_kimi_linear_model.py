"""Kimi-Linear's block as a MODEL (``tests/test_kimi_linear.py`` has the rule and
the validator, ``tests/test_kimi_linear_engine.py`` the served paths and the snapshots): latent
attention as the FULL kind of the pattern — no q latent, nothing rotated, every
token attended — absorbed against the reference's expanded form; the cache-less
forward against the plain float32 reference of
``perfbench/models/kimi_linear.py``; the counts at the published widths; the
eight shares of a routed layer; the two caches' depths, the leading dense KDA
layer's state first.
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


# --- LATENT ---------------------------------------------------------------------

def test_absorbed_equals_expanded_without_a_q_latent_or_a_rotation_in_both_forms():
    """One latent layer over 70 tokens: the program's absorbed form — the
    cache-less dense form, the chunk form's walk of the row's pages and the
    one-token form over them — against the reference's expanded form; no
    leaf of a q latent exists and the positions move nothing."""
    lp = {name: leaf[0] for name, leaf in PARAMS["layers"].items() if name.startswith("attn_")}
    assert "attn_q_a" not in lp and lp["attn_q_nope"].shape == (64, 4 * 16)
    S = 70
    h = jax.random.normal(jax.random.key(5), (1, S, 64), jnp.float32)
    want = np.asarray(kl._latent(h[0], {k: v[None] for k, v in lp.items()}, 0,
                                 kl._sizes(FILE), lambda w: w))
    x = mla.project(h, lp, CONFIG, jnp.arange(S)[None])
    moved = mla.project(h, lp, CONFIG, 1000 + jnp.arange(S)[None])
    np.testing.assert_array_equal(np.asarray(x.q), np.asarray(moved.q))  # nothing is rotated
    np.testing.assert_array_equal(np.asarray(x.row), np.asarray(moved.row))
    assert x.idx_q is None
    shape = la.LatentShape(32, 0, 24 ** -0.5)

    def out(o_latent):
        return np.asarray(mla.up_values(o_latent, lp, CONFIG) @ lp["attn_o"])[0]

    dense, selected = la.causal_attention(x.q, x.row, None, None, None, shape)
    np.testing.assert_allclose(out(dense), want, atol=1e-5)
    assert int(selected) == S * (S + 1) // 2  # every token j <= t
    # the rows in a pool, pages out of order; a chunk of 12 queries, then one token a row
    table = jnp.asarray([3, 5, 7, 2, 9, 11, 13, 1], jnp.int32)
    pool = jnp.zeros((1, 16, PAGE, 128), jnp.float32).at[0, table[:5]].set(
        jnp.pad(x.row[0], [(0, 5 * PAGE - S), (0, 0)]).reshape(5, PAGE, 128))
    keys = jnp.zeros((1, 16, PAGE, 128), jnp.float32)
    kw = dict(page_size=PAGE, shape=shape)
    chunk, n = la.chunk_attention(x.q[0, 40:52], None, None, pool, keys, jnp.int32(0), table,
                                  40 + jnp.arange(12), jnp.ones((12,), bool), **kw)
    np.testing.assert_allclose(out(chunk[None]), want[40:52], atol=1e-5)
    assert int(n) == sum(range(41, 53))
    for backend in ("ref", "pallas-interpret"):  # the gather form, the walk
        one, n = la.decode_attention(x.q[0, [69, 30]], None, None, pool, keys, jnp.int32(0),
                                     jnp.stack([table, table]), jnp.asarray([70, 31]),
                                     jnp.ones((2,), bool), backend=backend, **kw)
        np.testing.assert_allclose(out(one[None]), want[[69, 30]], atol=1e-5, err_msg=backend)
        assert int(n) == 70 + 31


# --- FORWARD --------------------------------------------------------------------

def test_param_count_and_config():
    c = CONFIG
    assert c.layer_pattern == (LINEAR, LINEAR, FULL, LINEAR) and c.leading_kinds == (LINEAR,)
    assert c.rope_theta is None and c.q_lora_rank == 0 and c.index_topk == 0
    assert (c.n_attn_layers, c.n_state_layers, c.n_of(LINEAR), c.has_state) == (2, 7, 7, True)
    assert c.moe_sparse and (c.n_experts, c.moe_router_width) == (8, 16)
    assert sum(x.size for x in jax.tree.leaves(PARAMS)) == n_params(c) \
        == kl.param_counts(FILE)["total"]
    depth = {name: leaf.shape[0] for name, leaf in PARAMS["layers"].items()}
    assert {depth[n] for n in depth if n.startswith("attn_")} == {2}
    assert {depth[n] for n in depth if n.startswith("gdn_")} == {6}
    assert {depth[n] for n in ("moe_in", "router", "ln_attn", "ln_mlp")} == {8}
    lead = {name: leaf.shape[0] for name, leaf in PARAMS["dense_layers"].items()}
    assert set(lead.values()) == {1} and not any(n.startswith("attn_") for n in lead)
    assert {"gdn_low", "gdn_f2", "gdn_g2", "gdn_g_bias", "mlp_gate"} <= set(lead)
    # the published initialisation: A_log one a head, dt_bias one a key channel
    assert PARAMS["layers"]["gdn_A_log"].shape == (6, 4)
    assert PARAMS["layers"]["gdn_dt_bias"].shape == (6, 4 * 16)


def test_the_published_widths_count_what_the_issue_counts():
    import json
    from pathlib import Path

    file = json.loads((Path(__file__).parent.parent
                       / "perfbench/configs/kimi-linear-48b-a3b.json").read_text())
    cut = kl.param_counts(file)
    assert round(cut["kda"] / 1e6, 2) == 39.52 and round(cut["latent_attention"] / 1e6, 2) == 29.11
    assert round(cut["expert"] / 1e6, 3) == 7.078 and round(cut["total"] / 1e6) == 3027
    assert n_params(kl.program_config(file)) == cut["total"]
    # ISSUE 51's first size, stage one of two: 1 + 12 layers
    first = dict(file, num_hidden_layers=13, linear_attn_config=dict(
        file["linear_attn_config"], kda_layers=[1, 2, 3, 5, 6, 7, 9, 10, 11, 13],
        full_attn_layers=[4, 8, 12]))
    assert round(kl.param_counts(first)["total"] / 1e6) == 4111
    assert kl.kv_bytes_per_token(first) == 3456
    whole = dict(file, num_hidden_layers=27, num_experts=256,
                 linear_attn_config=file["reduced"]["linear_attn_config"]["from"],
                 reduced={})
    assert round(kl.param_counts(whole)["total"] / 1e9, 2) == 49.12
    assert n_params(kl.program_config(whole)) == kl.param_counts(whole)["total"]
    assert kl.kv_bytes_per_token(file) == 2304 and kl.ssm_state_bytes_per_row(file) == 2 << 20


@pytest.mark.parametrize("chunk,sub", [(8, 4), (64, 16)])
def test_forward_equals_the_reference_whatever_the_block(chunk, sub, monkeypatch):
    monkeypatch.setattr(gdn, "CHUNK", chunk)
    monkeypatch.setattr(gdn, "SUB", sub)
    tokens = _tokens(37)
    got = _forward(tokens, dataclasses.replace(CONFIG, max_seq_len=300 + chunk))
    np.testing.assert_allclose(got, _reference(tokens, list(range(37))), atol=TOL)


def test_a_reference_with_the_scalar_decay_or_a_rounded_state_is_another_model():
    tokens = _tokens(37)
    want = _reference(tokens, list(range(37)))
    assert np.abs(_reference(tokens, list(range(37)), scalar_decay=True) - want).max() > 50 * TOL
    rounded = np.asarray(kl.state_control_logits(PARAMS, tokens, FILE,
                                                 positions=list(range(37)))[0])
    assert np.abs(rounded - want).max() > 2 * TOL


LEFT_OUT = ["gate", "decay", "gate_bias"]


@pytest.mark.parametrize("left_out", LEFT_OUT)
def test_a_program_without_one_term_of_the_layer_is_not_the_reference(left_out, monkeypatch):
    params = PARAMS
    if left_out == "l2norm":
        monkeypatch.setattr(gdn, "_l2norm", lambda x: x)
    elif left_out == "gate":  # SiLU where the published gate is a sigmoid
        norm = gdn.gated_head_norm
        monkeypatch.setattr(gdn, "gated_head_norm", lambda o, g, w, eps, sigmoid=False: norm(o, g, w, eps))
    elif left_out == "decay":
        gates = gdn._channel_gates
        monkeypatch.setattr(gdn, "_channel_gates", lambda *a: (0.0 * gates(*a)[0], *gates(*a)[1:]))
    else:
        params = jax.tree.map(lambda a: a, PARAMS)
        for stack in ("layers", "dense_layers"):
            params[stack] = dict(params[stack], gdn_g_bias=0 * params[stack]["gdn_g_bias"])
    tokens = _tokens(21, seed=4)
    config = dataclasses.replace(CONFIG, max_seq_len=200 + LEFT_OUT.index(left_out))
    assert np.abs(_forward(tokens, config, params) - _reference(tokens, list(range(21)))).max() \
        > 20 * TOL


# --- SHARES ---------------------------------------------------------------------

def test_the_eight_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The guide's test: every chip's share of a routed layer (its held range
    of the 16 experts, 2 a chip: eight chips to a layer as the deployment's)
    plus the shared expert counted once is what the uncut layer gives; and the
    reference's share is the program's at the held range."""
    whole_file = {**FILE, "num_experts": 16, "reduced": None}
    whole, params = tiny_models.build("kimi_linear", num_experts=16, reduced=None)
    lp = {k: v[0] for k, v in params["layers"].items()
          if k in ("router", "router_bias", "moe_in", "moe_out", "shared_in", "shared_out")}
    h = jax.random.normal(jax.random.key(8), (2, 5, 64), jnp.float32)
    uncut = moe_mlp(h, lp, whole)
    u = h @ lp["shared_in"]
    only_shared = (jax.nn.silu(u[..., :32]) * u[..., 32:]) @ lp["shared_out"]
    two = dataclasses.replace(whole, n_experts=2)
    shares = jnp.zeros_like(uncut)
    stacked = {k: v[None] for k, v in lp.items()}
    for chip in range(8):
        order = np.roll(np.arange(16), -2 * chip)  # the chip's range first
        part = {**lp, "router": lp["router"][:, order], "router_bias": lp["router_bias"][order],
                "moe_in": lp["moe_in"][order[:2]], "moe_out": lp["moe_out"][order[:2]]}
        share = moe_mlp(h, part, two) - only_shared
        want, _margin = kl._experts(h.reshape(10, 64), stacked, 0, kl._sizes(whole_file),
                                    lambda w: w, shares=(2 * chip, 2 * chip + 2))
        want = want - (only_shared.reshape(10, 64) if chip == 0 else 0.0)
        np.testing.assert_allclose(np.asarray(share).reshape(10, 64), np.asarray(want), atol=1e-5)
        shares = shares + share
    assert jnp.abs(shares + only_shared - uncut).max() < 1e-5
    want, _margin = kl._experts(h.reshape(10, 64), stacked, 0, kl._sizes(whole_file), lambda w: w)
    np.testing.assert_allclose(np.asarray(uncut).reshape(10, 64), np.asarray(want), atol=1e-5)


# --- CACHES / SNAPSHOT / ADMISSION --------------------------------------------------

def test_the_pool_has_the_latent_layers_depth_and_the_state_the_kda_layers_leading_first():
    engine = _engine()
    state = engine.state
    assert state.k_pages.shape == (2, 64, PAGE, 128) and state.v_pages.shape == (2, 64, PAGE, 128)
    assert state.ssm_state.shape == (7, SLOTS, 4, 16, 16) and state.ssm_state.dtype == jnp.float32
    assert state.conv_state.shape == (7, SLOTS, 3, 3 * 4 * 16)
    assert page_hbm_bytes(CONFIG, PAGE) == 2 * PAGE * (128 + 128) * 4  # (a lane tile no indexer reads)
    assert kl.kv_bytes_per_token(dict(FILE, dtype="float32")) == 2 * 40 * 4  # LOGICAL: 32 + 8
    assert SLOTS * 7 * (kl.ssm_state_bytes_per_row(FILE)
                        + kl.conv_tail_bytes_per_row(FILE)) == engine.ssm_state_bytes
    engine.set_page_table_row(1, [3, 4])
    tokens = _tokens(20, seed=8)
    engine.prefill(1, tokens)
    rows = np.asarray(engine.state.k_pages)[:, 3]
    assert np.abs(rows[0]).max() > 0 and np.abs(rows[0] - rows[1]).max() > 0
    s = np.asarray(engine.state.ssm_state)[:, 1]
    assert len({float(np.abs(s[i]).sum()) for i in range(7)}) == 7 and np.abs(s).min(axis=(1, 2, 3)).all() >= 0
    # the LEADING dense layer's state comes first in the stack: it is what the
    # first layer's own projections of the embedding leave
    lp = jax.tree.map(lambda a: a[0], PARAMS["dense_layers"])
    x = PARAMS["embed"][jnp.asarray(tokens)][None]
    _y, (lead_state, _tail) = gdn.mixer(
        rms_norm(x, lp["ln_attn"], 1e-5), lp, CONFIG,
        (jnp.zeros((1, 1, 4, 16, 16)), jnp.zeros((1, 1, 3, 192))), jnp.int32(0),
        SsmRows(jnp.asarray([0]), jnp.asarray([20])))
    np.testing.assert_allclose(s[0], np.asarray(lead_state)[0, 0], atol=1e-5)
