"""Kimi-Linear's block: the delta rule with a decay a KEY CHANNEL
(``models/gdn.py`` with ``gdn_gate_rank``; ``ops/gdn_step.py``'s second form)
three to one with latent attention as the FULL kind of the pattern — no q
latent, nothing rotated, every token attended — behind a leading dense KDA
layer and in front of a held range of routed experts: the program against the
plain float32 reference of ``perfbench/models/kimi_linear.py`` at a small size,
seeded random weights, through every path that carries latent pages AND state.

- RULE: the vector-decay ``_step`` is the written recurrence; ``_chunked`` and
  the packed scan are ``_step`` token by token with log-decays down to -80;
  the kernel (interpreted) is ``_step`` at the cell's tile shape; a decay that
  is constant over a head's channels is the scalar rule bit for bit.
- LATENT: absorbed against expanded without a q latent or a rotation, every
  token attended, in the one-token and the chunk forms.
- FORWARD / SPLIT / RAGGED: against the reference's full forward by logits.
- SHARES: the eight shares and the shared expert once are the uncut layer.
- SNAPSHOT: a row admitted from a head's latent pages + state + conv tail is
  the row prefilled from the start; reset and preemption leave neither behind.
- REFUSED: every ``NOT_CARRIED`` option, with both kinds named.
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


# --- RULE ----------------------------------------------------------------------

def _rule_inputs(n=2, S=29, H=3, dk=8, dv=16, seed=0, n_valid=(29, 18), g_min=-80.0):
    """q, k, v, g, beta as the mixer hands them to the rule: g a key channel,
    most of them mild and one in eight anywhere down to ``g_min`` (a token's
    log-decay reaches -16 softplus(.)); padding with g = beta = 0."""
    r = np.random.RandomState(seed)
    q, k = (r.randn(n, S, H, dk).astype(np.float32) for _ in range(2))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.randn(n, S, H, dv).astype(np.float32)
    live = (np.arange(S)[None, :] < np.asarray(n_valid)[:, None])[..., None]
    g = -np.where(r.rand(n, S, H, dk) < 0.125, r.uniform(0.0, -g_min, (n, S, H, dk)),
                  r.uniform(0.001, 1.5, (n, S, H, dk)))
    g = np.where(live[..., None], g, 0.0).astype(np.float32)
    beta = np.where(live, r.uniform(0.0, 1.0, (n, S, H)), 0.0).astype(np.float32)
    state = (0.3 * r.randn(n, H, dk, dv)).astype(np.float32)
    return state, q, k, v, g, beta


def _recurrence(state, q, k, v, g, beta):
    """The rule token by token, exactly as ISSUE 51 writes it, in float64:
    ``S~ = Diag(alpha) S; u = beta (v - S~^T k); S = S~ + k u^T; o = S^T q``."""
    S_ = np.asarray(state, np.float64).copy()
    q, k, v, g, beta = (np.asarray(t, np.float64) for t in (q, k, v, g, beta))
    out = np.zeros(v.shape)
    for t in range(q.shape[1]):
        S_ = np.exp(g[:, t])[..., None] * S_  # a key channel is a row of S
        u = beta[:, t][..., None] * (v[:, t] - np.einsum("nhkv,nhk->nhv", S_, k[:, t]))
        S_ = S_ + k[:, t][..., :, None] * u[..., None, :]
        out[:, t] = np.einsum("nhkv,nhk->nhv", S_, q[:, t])
    return out, S_


@jax.jit
def _scan_steps(state, q, k, v, g, beta):
    def token(state, t):
        o, state = gdn._step(state, *t)
        return state, o

    state, o = jax.lax.scan(token, state, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def _step_by_step(state, q, k, v, g, beta):
    """``gdn._step`` token after token: the one-token form's own answer."""
    o, state = _scan_steps(*(jnp.asarray(a) for a in (state, q, k, v, g, beta)))
    return np.asarray(o), np.asarray(state)


def test_the_vector_decay_step_is_the_written_recurrence_and_an_inert_row_keeps_its_state():
    state, q, k, v, g, beta = _rule_inputs(S=1, n_valid=(1, 0))
    want_o, want_s = _recurrence(state, q, k, v, g, beta)
    o, s = gdn._step(*(jnp.asarray(t) for t in (state, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                                beta[:, 0])))
    np.testing.assert_allclose(np.asarray(o), want_o[:, 0], atol=1e-6)
    np.testing.assert_allclose(np.asarray(s), want_s, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(s)[1], state[1])
    # and over 29 tokens, decays down to exp(-80) among them
    state, q, k, v, g, beta = _rule_inputs()
    want_o, want_s = _recurrence(state, q, k, v, g, beta)
    got_o, got_s = _step_by_step(state, q, k, v, g, beta)
    np.testing.assert_allclose(got_o, want_o, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)


@pytest.mark.parametrize("chunk,sub", [(1, 4), (5, 4), (8, 4), (29, 16), (64, 16)])
def test_the_chunked_form_is_the_step_token_by_token_down_to_exp_minus_80(chunk, sub, monkeypatch):
    """Blocks of ``chunk`` in sub-blocks of ``sub`` (a block that ``sub`` does
    not divide is one sub-block), over block ends and padding; S = 70 at 64
    puts 4 sub-blocks of 16 in a block — the cell's own shape."""
    monkeypatch.setattr(gdn, "SUB", sub)
    S = 70 if chunk == 64 else 29
    state, q, k, v, g, beta = _rule_inputs(S=S, n_valid=(S, 18))
    want_o, want_s = _step_by_step(state, q, k, v, g, beta)
    o, s = gdn._chunked(*(jnp.asarray(t) for t in (state, q, k, v, g, beta)), chunk)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s)).all()
    np.testing.assert_allclose(np.asarray(s), want_s, atol=2e-5)
    np.testing.assert_allclose(np.asarray(o)[0], want_o[0], atol=2e-5)
    np.testing.assert_allclose(np.asarray(o)[1, :18], want_o[1, :18], atol=2e-5)


def test_every_token_at_minus_80_in_every_channel_neither_overflows_nor_underflows_to_nan():
    state, q, k, v, g, beta = _rule_inputs(S=64, n_valid=(64, 64))
    g = np.full_like(g, -80.0)
    want_o, want_s = _step_by_step(state, q, k, v, g, beta)
    o, s = gdn._chunked(*(jnp.asarray(t) for t in (state, q, k, v, g, beta)), 64)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s)).all()
    np.testing.assert_allclose(np.asarray(o), want_o, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), want_s, atol=2e-5)


@pytest.mark.parametrize("lens,T", [((1, 9, 1, 8, 0, 1), 24), ((12, 12, 12, 12, 12, 12), 72)])
def test_the_packed_scan_is_the_step_token_by_token_from_each_rows_own_slot(lens, T):
    H, dk, dv, n, slots = 2, 4, 6, len(lens), 9
    r = np.random.RandomState(7)
    q, k = (r.randn(T, H, dk).astype(np.float32) for _ in range(2))
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.randn(T, H, dv).astype(np.float32)
    g = -np.where(r.rand(T, H, dk) < 0.2, r.uniform(0, 80, (T, H, dk)),
                  r.uniform(0.01, 1.5, (T, H, dk))).astype(np.float32)
    beta = r.uniform(0.0, 1.0, (T, H)).astype(np.float32)
    leaf = (0.3 * r.randn(2, slots, H, dk, dv)).astype(np.float32)
    row_slot = np.where(np.asarray(lens) > 0, [7, 2, 5, 0, 8, 3],
                        [7, 2, 5, 0, 8, 3][int(np.argmax(lens))])
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
        want_o, want_s = _step_by_step(leaf[1, row_slot[row]][None],
                                       *(t[at][None] for t in (q, k, v, g, beta)))
        np.testing.assert_allclose(o[at], want_o[0], atol=2e-5, err_msg=f"row {row}")
        np.testing.assert_allclose(new[1, row_slot[row]], want_s[0], atol=2e-5)
        touched.add(int(row_slot[row]))
    untouched = [slot for slot in range(slots) if slot not in touched]
    np.testing.assert_array_equal(new[1, untouched], leaf[1, untouched])
    np.testing.assert_array_equal(new[0], leaf[0])


@pytest.mark.parametrize("rows,heads,dk,dv,inert", [
    (2, 32, 128, 128, (1,)),  # the cell's tile: 32 tiles of 128 x 128, a head a tile
    (4, 6, 8, 64, (0, 3)),  # two heads side by side in a tile, halves of one tile and two
])
def test_the_kernel_is_the_step_at_the_cells_tile_shape_in_one_pass_in_place(rows, heads, dk, dv,
                                                                             inert):
    ks = jax.random.split(jax.random.key(rows), 7)
    f32 = jnp.float32
    tile_heads = 1 if dv % 128 == 0 else 2
    by_head = jax.random.normal(ks[0], (3, rows, heads, dk, dv), f32)
    g = -jnp.where(jax.random.uniform(ks[6], (rows, heads, dk)) < 0.1, 80.0, 1.0) \
        * jax.random.uniform(ks[4], (rows, heads, dk), f32, 0.01, 1.0)
    beta = jax.random.uniform(ks[5], (rows, heads), f32, 0.1, 1.0)
    for row in inert:
        g, beta = g.at[row].set(0.0), beta.at[row].set(0.0)
    q = gdn._l2norm(jax.random.normal(ks[1], (rows, heads, dk), f32)) * dk ** -0.5
    k = gdn._l2norm(jax.random.normal(ks[2], (rows, heads, dk), f32))
    v = jax.random.normal(ks[3], (rows, heads, dv), f32)
    state = jnp.stack([gdn._tiles(layer, heads // tile_heads) for layer in by_head])
    want_o, want_s = gdn._step(by_head[1], q, k, v, g, beta)
    before = np.asarray(state)
    o, new = gdn_step.gdn_state_step(state, q, k, v, g, beta, jnp.asarray([1], jnp.int32),
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(gdn._heads(new[1], heads)), np.asarray(want_s), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(new)[[0, 2]], before[[0, 2]])  # the other layers
    for row in inert:  # written back as it was
        np.testing.assert_array_equal(np.asarray(new)[1, row], before[1, row])


def test_a_decay_constant_over_a_heads_channels_is_the_scalar_rule():
    """With ``alpha`` a power of two every product with it is exact, so the
    two orders of one product (``alpha (S^T k)`` and ``S^T (alpha k)``) are
    the same float: the vector rule IS ``models/gdn.py``'s scalar rule, bit for
    bit, on ``ref``; at any other constant they differ by a rounding."""
    state, q, k, v, _g, beta = _rule_inputs(g_min=-1.0)
    halves = np.random.RandomState(3).randint(0, 4, beta.shape).astype(np.float32)  # 1 .. 1/8
    for scalar_g, exact in ((np.log(0.5) * halves, True),
                            (-np.random.RandomState(4).uniform(0.01, 1.5, beta.shape), False)):
        scalar_g = np.where(beta > 0, scalar_g, 0.0).astype(np.float32)
        alpha = np.exp(scalar_g)
        if exact:  # exp(n log 1/2) is a power of two only where the library says so
            scalar_g = np.where(alpha == 2.0 ** -halves, scalar_g, 0.0).astype(np.float32)
            assert (np.exp(scalar_g) != 1.0).sum() > scalar_g.size // 4
        vector_g = np.broadcast_to(scalar_g[..., None], q.shape).copy()
        args = [jnp.asarray(t) for t in (state, q, k, v)]
        for t in range(3):
            at = [a[:, t] for a in args[1:]]
            so, ss = gdn._step(args[0], *at, jnp.asarray(scalar_g[:, t]), jnp.asarray(beta[:, t]))
            vo, vs = gdn._step(args[0], *at, jnp.asarray(vector_g[:, t]), jnp.asarray(beta[:, t]))
            if exact and (np.exp(scalar_g[:, t]) == 2.0 ** -halves[:, t]).all():
                np.testing.assert_array_equal(np.asarray(vo), np.asarray(so))
                np.testing.assert_array_equal(np.asarray(vs), np.asarray(ss))
            np.testing.assert_allclose(np.asarray(vo), np.asarray(so), atol=1e-6)
            np.testing.assert_allclose(np.asarray(vs), np.asarray(ss), atol=1e-6)
        o_s, s_s = gdn._chunked(*args, jnp.asarray(scalar_g), jnp.asarray(beta), 8)
        o_v, s_v = gdn._chunked(*args, jnp.asarray(vector_g), jnp.asarray(beta), 8)
        np.testing.assert_allclose(np.asarray(o_v)[0], np.asarray(o_s)[0], atol=2e-5)
        np.testing.assert_allclose(np.asarray(s_v), np.asarray(s_s), atol=2e-5)


# --- LlamaConfig.__post_init__ -----------------------------------------------------

OLMO, _ = tiny_models.build("olmo_hybrid")
DEEPSEEK, _ = tiny_models.build("deepseek_v32")


@pytest.mark.parametrize("base,fields,holds", [
    # latent attention is a KIND's: the FULL layers of a pattern, beside LINEAR ones
    (CONFIG, {}, lambda c: (c.n_attn_layers, c.n_state_layers, c.kv_row_widths) == (2, 7, (128, 128))),
    # a leading dense layer whose kind is LINEAR: its state first in the stack
    (CONFIG, {"leading_kinds": (LINEAR,)}, lambda c: c.n_leading_of(LINEAR) == 1 and c.n_of(LINEAR) == 7),
    # latent attention without a q latent
    (DEEPSEEK, {"q_lora_rank": 0, "index_topk": 0, "index_heads": 0, "index_head_dim": 0},
     lambda c: c.kv_lora_rank == 32 and c.n_attn_layers == 3),
    # latent attention without a rotation
    (DEEPSEEK, {"rope_theta": None, "rope_scaling": None}, lambda c: c.rope_theta is None),
], ids=["latent_under_a_pattern", "a_leading_linear_layer", "no_q_latent", "no_rotation"])
def test_what_the_validator_refused_before_is_built(base, fields, holds):
    assert holds(dataclasses.replace(base, **fields))


@pytest.mark.parametrize("base,fields,said", [
    (CONFIG, {"layer_pattern": (LINEAR, "sliding_attention", FULL, LINEAR), "window": 8},
     "no mixer, linear or latent attention"),
    (DEEPSEEK, {"layer_pattern": (FULL, "sliding_attention"), "window": 8},
     "no mixer, linear or latent attention"),
    (CONFIG, {"layer_pattern": (LINEAR, "mamba", FULL, LINEAR), "ssm_heads": 4, "ssm_head_dim": 16,
              "ssm_state": 8}, "Mamba-2 mixer|one layer_pattern"),
    (OLMO, {"layer_pattern": (LINEAR, "mamba", LINEAR, FULL), "ssm_heads": 4, "ssm_head_dim": 16,
            "ssm_state": 8}, "the recurrent state by slot has one shape"),
    (OLMO, {"index_topk": 4, "index_heads": 2, "index_head_dim": 8}, "latent attention's"),
    (DEEPSEEK, {"q_lora_rank": 0}, "come off the q latent"),
    (CONFIG, {"rope_scaling": mla.RopeScaling(40.0, 64)}, "comes with a rope_theta"),
    (CONFIG, {"v_head_dim": 0}, "comes with v_head_dim"),
    (CONFIG, {"n_kv_heads": 2}, "n_kv_heads 1"),
    (CONFIG, {"qk_norm": True}, "not combined with a Mamba-2 mixer, a q/k norm"),
    (CONFIG, {"leading_kinds": ("mamba",)}, "leading_kinds names each leading dense layer"),
    (DEEPSEEK, {"leading_kinds": (LINEAR,)}, "go together|stands in front of a layer_pattern"),
    (CONFIG, {"gdn_heads": 0}, "go together"),
    (OLMO, {"gdn_heads": 0, "layer_pattern": (), "gdn_gate_rank": 16}, "gdn_gate_rank is the linear"),
    (CONFIG, {"n_layers": 8}, "whole number of periods"),
])
def test_every_refusal_that_stays_still_refuses(base, fields, said):
    with pytest.raises(ValueError, match=said):
        dataclasses.replace(base, **fields)
