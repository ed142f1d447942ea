"""``deepseek_v32`` (PR 40) on the program's one block, at a size a test holds:
latent attention over ONE row a token (key and value at once), the indexer's
exact top-k with ``index_topk`` UNDER the context so that the selection bites,
a sigmoid group-limited router with a selection bias over a held range of
experts beside a shared one, a leading dense layer in front of the scan.
Everything against the plain reference of ``perfbench/models/deepseek_v32.py``
(float32, the EXPANDED form, top-k by a stable sort).

FORWARD   the cache-less forward; the two controls are not the reference
SELECT    the exact k largest whatever the ties; gather form = mask form =
          the dense reference; a context under index_topk is dense attention
ROUTER    the 16 shares and the shared expert once are the uncut layer; the
          bias chooses and does not weigh; groups limit the picks
SPLIT / RAGGED / CACHES   prefill in chunks then decode, the packed round, the
          pool's two arrays and their bytes, a session resumed from RAM
COUNT     the decode step's selected tokens through engine and scheduler
REFUSED   what is refused at load
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import tiny_models

from finchat_tpu.engine.engine import InferenceEngine
from finchat_tpu.engine.kv_cache import PagedKVCache, page_hbm_bytes
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
from finchat_tpu.models import llama, mla
from finchat_tpu.models.llama import LlamaConfig, forward_full, init_params, moe_mlp, n_params
from finchat_tpu.ops import latent_attention as la
from finchat_tpu.utils.config import EngineConfig
from finchat_tpu.utils.metrics import METRICS
from finchat_tpu.utils.tracing import TRACER
from perfbench.models import deepseek_v32 as ds

FILE = tiny_models.FILES["deepseek_v32"]
CONFIG, PARAMS = tiny_models.build("deepseek_v32")
PAGE, CHUNK, SLOTS = 16, 12, 4
TOL = 2e-4  # float32 against float32; the logits' spread is about 1, a control reads 4 or more


def _tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 300, size=n)]


def _reference(tokens, positions, **kw):
    want, margins = ds.reference_logits(PARAMS, tokens, FILE, positions=positions, **kw)
    assert (np.asarray(margins) > 0).all()
    return np.asarray(want)


def _engine(attn_backend="ref", **options) -> InferenceEngine:
    cfg = EngineConfig(**{"max_seqs": SLOTS, "page_size": PAGE, "num_pages": 64,
                          "max_seq_len": 256, "prefill_chunk": CHUNK, **options})
    return InferenceEngine(CONFIG, PARAMS, cfg, attn_backend=attn_backend)


def _decode(engine, slot_tokens: dict[int, int]) -> np.ndarray:
    active = np.zeros((SLOTS,), bool)
    for slot, token in slot_tokens.items():
        engine.set_last_token(slot, token)
        active[slot] = True
    _, logits = engine.decode(jnp.asarray(active), jnp.zeros((SLOTS,)), jnp.ones((SLOTS,)),
                              jnp.zeros((SLOTS,), jnp.int32), return_logits=True)
    return np.asarray(logits)


# --- FORWARD ---------------------------------------------------------------------

def test_param_count_and_config():
    counted = sum(leaf.size for leaf in jax.tree.leaves(PARAMS))
    assert counted == n_params(CONFIG) == ds.param_counts(FILE)["total"]
    assert set(PARAMS["dense_layers"]) >= {"attn_q_a", "attn_idx_k", "mlp_gate", "mlp_down"}
    assert PARAMS["dense_layers"]["mlp_gate"].shape == (1, 64, 96)
    assert PARAMS["layers"]["moe_in"].shape == (2, 4, 64, 64)  # the scan's two layers
    assert PARAMS["layers"]["router"].shape == (2, 64, 16)  # the router at its whole width
    assert PARAMS["layers"]["router_bias"].dtype == jnp.float32
    assert CONFIG.moe_sparse and CONFIG.n_scan_layers == 2 and CONFIG.n_attn_layers == 3
    assert CONFIG.attention_scale == pytest.approx(24 ** -0.5 * 1.36889 ** 2, rel=1e-5)


def test_forward_equals_the_reference_with_the_selection_active():
    tokens = _tokens(90)
    want = _reference(tokens, list(range(90)))
    got = np.asarray(forward_full(PARAMS, jnp.asarray(tokens)[None], jnp.arange(90)[None],
                                  config=CONFIG)[0])
    assert np.abs(got - want).max() < TOL
    # 90 tokens of context against index_topk 24: the selection bites
    assert np.abs(got[20] - want[20]).max() < TOL


@pytest.mark.parametrize("variant", ["no_selection", "unrotated_index"])
def test_a_control_of_the_selection_is_not_the_reference(variant):
    tokens = _tokens(90)
    positions = list(range(30, 90))
    want = _reference(tokens, positions)
    control = _reference(tokens, positions, variant=variant)
    assert np.abs(control - want).max() > 0.5  # a logit's spread is about 1
    # and up to index_topk tokens of context both ARE the reference
    short = list(range(24))
    assert np.abs(_reference(tokens, short, variant="no_selection")
                  - _reference(tokens, short)).max() < TOL


def test_yarn_frequencies_are_the_references_and_ramp_between_the_betas():
    inv, mult = mla.rope_tables(8, 10000.0, CONFIG.rope_scaling)
    assert np.allclose(inv, ds._inv_freq(FILE), rtol=1e-6) and mult == 1.0
    plain = 10000.0 ** -(np.arange(0, 8, 2) / 8)
    assert inv[0] == pytest.approx(plain[0]) and inv[-1] == pytest.approx(plain[-1] / 40)
    full, _ = mla.rope_tables(64, 10000.0, mla.RopeScaling(40.0, 4096, 32.0, 1.0, 1.0, 1.0))
    ratio = (10000.0 ** -(np.arange(0, 64, 2) / 64)) / full
    assert (np.diff(ratio) >= -1e-4).all() and ratio[0] == pytest.approx(1) \
        and ratio[-1] == pytest.approx(40, rel=1e-5)
    assert mla.softmax_scale(192, mla.RopeScaling(40.0, 4096, 32.0, 1.0, 1.0, 1.0)) \
        == pytest.approx(0.135234, rel=1e-5)


# --- SELECT ----------------------------------------------------------------------

def _pool(seed=0, layers=2, pages=20, width=128, di=16, latent=40):
    ks = jax.random.split(jax.random.key(seed), 2)
    rows = jax.random.normal(ks[0], (layers, pages, PAGE, width), jnp.float32)
    return rows.at[..., latent:].set(0), jax.random.normal(ks[1], (layers, pages, PAGE, di))


def test_the_kth_largest_is_a_sorts_whatever_the_sign_and_the_infinities():
    x = jax.random.normal(jax.random.key(1), (64, 200), jnp.float32).at[:32].add(-3.0)
    x = x.at[5, 50:].set(-jnp.inf).at[6].set(0.0)
    for k in (1, 24, 100, 199, 200):
        assert (la.kth_largest(x, k) == jnp.sort(x, axis=-1)[:, 200 - k]).all()


def test_the_selection_is_exactly_k_and_a_stable_sorts_whatever_the_ties():
    scores = jnp.round(jax.random.normal(jax.random.key(2), (9, 150)) * 2) / 2  # many ties
    allowed = jnp.arange(150)[None, :] <= jnp.arange(60, 150, 10)[:, None]
    mask = la.select(scores, allowed, 24)
    assert (mask.sum(-1) == 24).all() and not (mask & ~allowed).any()
    masked = jnp.where(allowed, scores, -jnp.inf)
    order = jnp.argsort(-masked, axis=-1, stable=True)[:, :24]
    want = jnp.zeros_like(mask).at[jnp.arange(9)[:, None], order].set(True)
    assert (mask == want).all()
    # lax.top_k (a stable sort's first k: the gather form's rule) picks the same set
    picked = jax.lax.top_k(masked, 24)[1]
    assert (jnp.sort(picked, -1) == jnp.sort(order, -1)).all()
    # fewer allowed than k: all of them; no indexer: all of them
    few = jnp.arange(150)[None, :] < 7
    assert (la.select(scores[:1], few, 24) == few).all()
    assert la.select(scores, allowed, 0) is allowed


@pytest.mark.parametrize("kv_len", [[78, 79, 40], [80, 81, 5], [100, 112, 30]])
def test_the_gather_form_equals_the_masked_walk_and_the_dense_reference(kv_len):
    """One query a row: the decode step's gather of the selected rows, the
    chunk form's masked dense walk of the row's pages, and plain dense
    attention under the mask, on one pool through three page tables."""
    rows, keys = _pool()
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (3, 4, 40))
    iq = jax.random.normal(ks[1], (3, 4, 16))
    iw = jax.random.normal(ks[2], (3, 4))
    table = jnp.asarray([[3, 5, 7, 2, 9, 11, 17, 0], [1, 4, 6, 8, 10, 12, 13, 0],
                         [14, 15, 16, 0, 0, 0, 0, 0]], jnp.int32)
    shape = la.LatentShape(32, 24, 0.2)
    kv = jnp.asarray(kv_len)
    kw = dict(page_size=PAGE, shape=shape)
    got, selected = la.decode_attention(q, iq, iw, rows, keys, jnp.int32(1), table, kv,
                                        jnp.ones((3,), bool), **kw)
    assert int(selected) == sum(min(24, n) for n in kv_len)
    for b in range(3):
        walked, n = la.chunk_attention(q[b][None], iq[b][None], iw[b][None], rows, keys,
                                       jnp.int32(1), table[b], kv[b][None] - 1,
                                       jnp.ones((1,), bool), **kw)
        assert int(n) == min(24, kv_len[b])
        assert jnp.abs(got[b] - walked[0]).max() < 1e-5
        flat = rows[1, table[b]].reshape(1, -1, 128)
        scores = la.index_scores(iq[b][None, None], iw[b][None, None],
                                 keys[1, table[b]].reshape(1, -1, 16))
        mask = la.select(scores, (jnp.arange(128) < kv[b])[None, None], 24)
        dense = la.attend_reference(q[b][None, None], flat, mask, shape)[0, 0]
        assert jnp.abs(got[b] - dense).max() < 1e-5


@pytest.mark.parametrize("walk_block", [1024, 32])
def test_a_chunk_walks_as_far_as_its_last_query_and_skips_padding_queries(walk_block, monkeypatch):
    """``walk_block`` 32: two pages a step, so the walk takes several steps
    (the running softmax across them) and stops before the row's last pages."""
    monkeypatch.setattr(la, "WALK_BLOCK", walk_block)
    rows, keys = _pool(seed=4)
    ks = jax.random.split(jax.random.key(5), 3)
    C = 12
    q = jax.random.normal(ks[0], (C, 4, 40))
    iq, iw = jax.random.normal(ks[1], (C, 4, 16)), jax.random.normal(ks[2], (C, 4))
    page_row = jnp.asarray([3, 5, 7, 2, 9, 11, 13, 0], jnp.int32)
    shape = la.LatentShape(32, 10, 0.2)
    for start in (0, 12, 70, 100):
        valid = jnp.arange(C) < 9
        got, selected = la.chunk_attention(q, iq, iw, rows, keys, jnp.int32(0), page_row,
                                           start + jnp.arange(C), valid, page_size=PAGE,
                                           shape=shape)
        flat = rows[0, page_row].reshape(1, -1, 128)
        allowed = (jnp.arange(128)[None] <= (start + jnp.arange(C))[:, None]) & valid[:, None]
        scores = la.index_scores(iq[None], iw[None], keys[0, page_row].reshape(1, -1, 16))
        mask = la.select(scores, allowed[None], 10)
        want = la.attend_reference(q[None], flat, mask, shape)[0]
        assert jnp.abs(got - want)[:9].max() < 1e-5 and int(selected) == int(mask.sum())
        assert int(selected) == sum(min(10, start + i + 1) for i in range(9))


def test_absorbed_equals_expanded():
    """One layer's attention at ``index_topk`` over the context: the program's
    absorbed form (queries through W_uk, values through W_uv) against the
    reference's expanded keys and values."""
    config = dataclasses.replace(CONFIG, index_topk=1000)
    lp = jax.tree.map(lambda a: a[0], PARAMS["layers"])
    h = jax.random.normal(jax.random.key(6), (1, 30, 64), jnp.float32)
    x = mla.project(h, lp, config, jnp.arange(30)[None])
    shape = la.LatentShape(32, 1000, config.attention_scale)
    o_latent, selected = la.causal_attention(x.q, x.row, x.idx_q, x.idx_w, x.idx_k, shape)
    got = mla.up_values(o_latent, lp, config) @ lp["attn_o"]
    with jax.default_matmul_precision("highest"):
        want = ds._attention(h[0], PARAMS["layers"], 0, FILE, ds._sizes(FILE),
                             lambda w: w, "no_selection")
    assert jnp.abs(got[0] - want).max() < 1e-4
    assert int(selected) == 30 * 31 // 2


# --- ROUTER ----------------------------------------------------------------------

def _one_routed_layer(config, seed=3):
    params = init_params(config, jax.random.key(seed))
    lp = {k: v[0] for k, v in params["layers"].items()
          if k in ("router", "router_bias", "moe_in", "moe_out", "shared_in", "shared_out")}
    return lp, jax.random.normal(jax.random.key(seed + 1), (2, 5, config.dim), jnp.float32)


def test_the_16_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The guide's test, with groups and the bias: every chip's share of a
    routed layer (its held range of the 16 experts, 4 a chip) plus the shared
    expert counted once is what the uncut layer gives."""
    whole = dataclasses.replace(CONFIG, n_experts=16)
    lp, h = _one_routed_layer(whole)
    uncut = moe_mlp(h, lp, whole)
    u = h @ lp["shared_in"]
    only_shared = (jax.nn.silu(u[..., :32]) * u[..., 32:]) @ lp["shared_out"]
    shares = jnp.zeros_like(uncut)
    for chip in range(4):
        # the chip that holds experts [4 chip, 4 chip + 4): the router's
        # columns (and the bias) rolled so that its range comes first
        order = np.roll(np.arange(16), -4 * chip)
        part = {**lp, "router": lp["router"][:, order], "router_bias": lp["router_bias"][order],
                "moe_in": lp["moe_in"][order[:4]], "moe_out": lp["moe_out"][order[:4]]}
        shares = shares + moe_mlp(h, part, CONFIG) - only_shared
    # rolling by whole groups of 4 keeps the groups whole: the picks are the same
    assert jnp.abs(shares + only_shared - uncut).max() < 1e-5
    # and the reference's layer is the program's, at the held range
    want, _margin = ds._experts(h.reshape(10, 64), {k: v[None] for k, v in lp.items()}, 0, FILE,
                                ds._sizes(FILE), lambda w: w)
    held = {**lp, "moe_in": lp["moe_in"][:4], "moe_out": lp["moe_out"][:4]}
    assert jnp.abs(moe_mlp(h, held, CONFIG).reshape(10, 64) - want).max() < 1e-5


def test_the_bias_chooses_and_does_not_weigh_and_groups_limit_the_picks():
    lp, h = _one_routed_layer(dataclasses.replace(CONFIG, n_experts=16))
    r = jnp.einsum("bsd,de->bse", h, lp["router"])
    score = jax.nn.sigmoid(r)
    picks, gates = llama._sigmoid_picks(r, lp["router_bias"], CONFIG)
    # gates: the picked SCORES over their sum, times 2.5 — no bias in them
    want = jnp.take_along_axis(score, picks, axis=-1)
    assert jnp.allclose(gates, 2.5 * want / want.sum(-1, keepdims=True), rtol=1e-6)
    assert jnp.allclose(gates.sum(-1), 2.5)
    # a large bias on one expert makes it a pick everywhere and leaves its gate its score's
    loud = lp["router_bias"].at[9].set(5.0)
    picks_loud, gates_loud = llama._sigmoid_picks(r, loud, CONFIG)
    assert (picks_loud == 9).any(-1).all()
    at9 = jnp.take_along_axis(score, picks_loud, -1)
    assert jnp.allclose(gates_loud, 2.5 * at9 / at9.sum(-1, keepdims=True), rtol=1e-6)
    # the picks lie in at most moe_topk_groups (2) of the 4 groups — and with
    # 2 picks of 2 kept groups that binds only with more picks: take 6
    wide = dataclasses.replace(CONFIG, top_k_experts=6)
    picks6, _ = llama._sigmoid_picks(r, lp["router_bias"], wide)
    groups = np.asarray(picks6) // 4
    assert all(len(set(row)) <= 2 for row in groups.reshape(-1, 6))
    free = dataclasses.replace(wide, moe_groups=0, moe_topk_groups=0)
    groups_free = np.asarray(llama._sigmoid_picks(r, lp["router_bias"], free)[0]) // 4
    assert any(len(set(row)) > 2 for row in groups_free.reshape(-1, 6))
    # the reference's router picks the same experts with the same gates
    ref_picks, ref_gates, margin = ds._route(
        h.reshape(10, 64), lp["router"], lp["router_bias"], top_k=2, groups=4, kept_groups=2,
        gate_scale=2.5, norm=True)
    assert np.isinf(np.asarray(margin)).all()  # nothing held: nothing a flip could change
    assert (jnp.sort(ref_picks, -1) == jnp.sort(picks.reshape(10, 2), -1)).all()
    assert jnp.allclose(jnp.sort(ref_gates, -1), jnp.sort(gates.reshape(10, 2), -1), rtol=1e-5)


def test_the_routing_margin_bounds_what_leaves_the_held_share_unchanged():
    """The reference's margin a token: choice scores moved by under a quarter
    of it (so that no pair of them, and no pair of group scores — sums of two —
    closes a gap of the margin) leave which HELD experts are picked as it
    was; and some token's held picks do change within a few margins."""
    lp, h = _one_routed_layer(dataclasses.replace(CONFIG, n_experts=16), seed=9)
    h = jnp.concatenate([h, 2 * h, -h], axis=0).reshape(30, 64)
    kw = dict(top_k=2, groups=4, kept_groups=2, gate_scale=2.5, norm=True, held=4)
    picks, _gates, margin = ds._route(h, lp["router"], lp["router_bias"], **kw)
    assert (np.asarray(margin) > 0).all() and np.isfinite(np.asarray(margin)).all()
    sigma = np.std(np.asarray(jax.nn.sigmoid(h @ lp["router"]) + lp["router_bias"]), axis=-1)
    held = lambda p: sorted(int(e) for e in np.asarray(p) if e < 4)  # noqa: E731
    rng, changed = np.random.RandomState(0), 0
    for t in range(30):
        for scale, must_hold in ((0.24, True), (8.0, False)):
            for _ in range(8):
                noise = (rng.uniform(-1, 1, size=16) * scale * float(margin[t])
                         * ds.MARGIN_UNIT * sigma[t])
                moved, _g, _m = ds._route(h[t:t + 1], lp["router"],
                                          lp["router_bias"] + jnp.asarray(noise, jnp.float32), **kw)
                same = held(moved[0]) == held(picks[t])
                assert same or not must_hold, (t, scale)
                changed += not same
    assert changed > 0


# --- SPLIT / RAGGED / CACHES -------------------------------------------------------

@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
def test_prefill_in_chunks_then_decode_token_by_token(backend):
    """70 tokens in six chunks, then 20 decode steps across two page
    boundaries, every context past index_topk (24): the split path, with the
    in-place append of rows of two widths on the kernel backend."""
    tokens = _tokens(90)
    want = _reference(tokens, list(range(69, 90)))
    engine = _engine(backend)
    engine.set_page_table_row(1, list(range(1, 9)))
    assert np.abs(np.asarray(engine.prefill(1, tokens[:70])) - want[0]).max() < TOL
    for i, token in enumerate(tokens[70:]):
        assert np.abs(_decode(engine, {1: token})[1] - want[1 + i]).max() < TOL
    touched, read, selected = (int(n) for n in engine.moe_experts)
    assert selected == 3 * 24 and 0 <= touched <= 2 * 2  # three layers select; two route
    assert read == (touched if backend != "ref" else 2 * 4)  # the touched pass, or dense dispatch


def test_ragged_rounds_mix_prompt_rows_and_one_token_rows():
    """The benchmark's own two paths on the tiny engine (``correct.py``'s
    packing: a prompt in two chunks, decode rows, rounds that carry both)
    agree with the reference at every position."""
    from perfbench import correct

    engine = _engine(prefill_chunk=32)
    sched = ContinuousBatchingScheduler(engine, eos_id=-1)
    tokens = _tokens(48 + 20, seed=7)
    prompt, forced = tokens[:48], tokens[48:]
    want = _reference(tokens, list(range(47, 68)))
    split = correct._split_path_logits(sched, prompt, forced)
    ragged = correct._ragged_path_logits(sched, prompt, forced)
    assert max(np.abs(g - w).max() for g, w in zip(split, want)) < TOL
    assert max(np.abs(g[:300] - want[i]).max() for i, g in ragged) < TOL
    assert len(sched.free_slots) == SLOTS and sched.allocator.used_count == 0


def test_the_pool_holds_latent_rows_and_index_keys_and_its_bytes_are_creates():
    assert CONFIG.kv_row_widths == (128, 16) and CONFIG.latent_row == 128  # 40 -> one tile
    real = ds.program_config({**FILE, **{k: v for k, v in REAL_WIDTHS.items()}})
    assert real.kv_row_widths == (640, 128)
    cache = PagedKVCache.create(CONFIG, 8, PAGE)
    assert cache.k_pages.shape == (3, 8, PAGE, 128) and cache.v_pages.shape == (3, 8, PAGE, 16)
    assert cache.hbm_bytes() - 2 * 4 == 8 * page_hbm_bytes(CONFIG, PAGE)  # less the scale placeholders
    mistral = LlamaConfig(n_kv_heads=2, n_heads=4, dim=128)
    assert mistral.kv_row_widths == (64, 64)
    assert page_hbm_bytes(mistral, 16) == 2 * 2 * 16 * 64 * 2
    # the adapter's bytes a token are the LOGICAL ones: 40 + 16 columns a layer
    assert ds.kv_bytes_per_token(FILE) == 3 * (40 + 16) * 4
    engine = _engine()
    engine.set_page_table_row(1, [3, 4])
    engine.prefill(1, _tokens(20, seed=2))
    rows = np.asarray(engine.state.k_pages)
    assert (np.abs(rows[:, 3, :, :40]).sum(-1) > 0).all() and np.abs(rows[..., 40:]).max() == 0
    assert (np.abs(np.asarray(engine.state.v_pages)[:, 4, :4]).sum(-1) > 0).all()
    # the rows written ARE the layer's projections of its inputs (layer 0: the embedding's)
    x = PARAMS["embed"][jnp.asarray(_tokens(20, seed=2))][None]
    lp = jax.tree.map(lambda a: a[0], PARAMS["dense_layers"])
    want = mla.project(llama.rms_norm(x, lp["ln_attn"], 1e-6), lp, CONFIG, jnp.arange(20)[None])
    got = np.concatenate([rows[0, 3], rows[0, 4]])[:20]
    assert np.abs(got - np.asarray(want.row[0])).max() < 1e-5


REAL_WIDTHS = {"hidden_size": 7168, "num_attention_heads": 128, "q_lora_rank": 1536,
               "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
               "v_head_dim": 128, "index_n_heads": 64, "index_head_dim": 128}

HEAD = _tokens(2 * PAGE, seed=11)  # two whole pages: a shared head


def _scheduler(**options):
    return ContinuousBatchingScheduler(_engine(**options), eos_id=-1)


async def _stream(sched, prompt, n_new=6, conversation_id=None):
    handle = await sched.submit("seq", prompt, SamplingParams(temperature=0.0, max_new_tokens=n_new),
                                trace_id="t-1", conversation_id=conversation_id)
    tokens = []
    while True:
        event = await asyncio.wait_for(handle.events.get(), timeout=120)
        if event["type"] == "token":
            tokens.append(event["token_id"])
        elif event["type"] == "done":
            return handle, tokens
        else:
            raise AssertionError(event)


def _run(sched, *prompts, **kw):
    async def go():
        await sched.start()
        try:
            got = [await _stream(sched, prompt, **kw) for prompt in prompts]
            await asyncio.sleep(0.05)  # the last round books at the next turn of the loop
            return got
        finally:
            await sched.stop()
    return asyncio.run(go())


@pytest.mark.parametrize("mixed", [False, True])
def test_a_row_admitted_from_a_shared_head_streams_what_the_whole_row_streams(mixed):
    prompt = HEAD + _tokens(13, seed=12)
    [(_handle, whole)] = _run(_scheduler(mixed_step=mixed), prompt)
    sched = _scheduler(mixed_step=mixed)
    assert sched.register_prefix(HEAD + [1, 2, 3]) == len(HEAD)
    [(handle, resumed)] = _run(sched, prompt)
    assert handle.shared_len == len(HEAD) and resumed == whole and len(whole) == 6
    # the greedy stream is the reference's
    want = _reference(prompt + whole, list(range(len(prompt) - 1, len(prompt) + 5)))
    assert [int(np.argmax(row)) for row in want] == whole


def test_a_session_resumed_from_the_ram_tier_carries_both_paged_arrays():
    """A conversation's second turn restores its first turn's pages — latent
    rows AND index keys, gathered and scattered by page id — and streams what
    a cold engine streams for the same tokens."""
    first = _tokens(40, seed=13)
    sched = _scheduler(session_cache=True, session_cache_bytes=1 << 22)
    names = ("finchat_session_cache_hits_total", "finchat_session_cache_restored_tokens_total")
    hits, restored = (METRICS.get(name) for name in names)
    [(_h, answer)] = _run(sched, first, conversation_id="c-1")
    second = first + answer + _tokens(9, seed=14)
    [(_handle, resumed)] = _run(sched, second, conversation_id="c-1")
    assert METRICS.get(names[0]) == hits + 1 and METRICS.get(names[1]) >= restored + 2 * PAGE
    [(_c, cold)] = _run(_scheduler(), second)
    assert resumed == cold


# --- COUNT -----------------------------------------------------------------------

def test_the_decode_step_counts_the_selected_tokens_of_live_rows_over_the_layers():
    engine = _engine()
    lengths = {0: 9, 1: 30, 2: 50}
    for slot, n in lengths.items():
        engine.set_page_table_row(slot, [1 + 4 * slot + i for i in range(4)])
        engine.prefill(slot, _tokens(n, seed=slot))
    _decode(engine, {0: 1, 1: 2, 2: 3})
    assert int(engine.moe_experts[2]) == 3 * (10 + 24 + 24)  # min(index_topk, context) a layer
    _decode(engine, {1: 2})
    assert int(engine.moe_experts[2]) == 3 * 24  # an inert row selects nothing
    _decode(engine, {})
    assert [int(n) for n in engine.moe_experts] == [0, 2 * 4, 0]


def test_the_scheduler_books_the_selection_on_deliver_and_on_the_rounds_event():
    TRACER.configure(enabled=True)
    TRACER.clear()
    sched = _scheduler()
    names = ("finchat_dsa_selected_tokens_total", "finchat_dsa_row_layer_steps_total",
             "finchat_moe_layer_steps_total")
    before = {name: METRICS.get(name) for name in names}
    _run(sched, _tokens(40, seed=4), n_new=6)
    selected, row_layers, moe_layers = (METRICS.get(name) - before[name] for name in names)
    steps = row_layers / 3  # one live row, three layers a step
    assert steps >= 5 and selected == 24 * row_layers  # every context is past index_topk
    assert moe_layers == 2 * steps  # the layers that route: not the leading dense one
    rounds = [ev[5] for ev in TRACER.snapshot() if ev[2] == "round" and "selected_tokens" in ev[5]]
    assert rounds and all(args["selected_tokens"] == 3 * 24 for args in rounds)
    assert METRICS.get("finchat_kv_pool_bytes", labels={"array": "latent"}) \
        == sched.engine.state.k_pages.nbytes
    assert METRICS.get("finchat_kv_pool_bytes", labels={"array": "index_keys"}) \
        == sched.engine.state.v_pages.nbytes
    TRACER.configure(enabled=False)


# --- REFUSED ---------------------------------------------------------------------

@pytest.mark.parametrize("options, named", [
    ({"kv_quant": "int8"}, "engine.kv_quant"),
    ({"spec_tokens": 2}, "engine.spec_tokens"),
    ({"kv_sink_pages": 1, "kv_window_pages": 4}, "engine.kv_sink_pages"),
])
def test_engine_options_that_would_not_carry_latent_pages_are_refused_by_name(options, named):
    with pytest.raises(ValueError, match=named):
        _engine(**options)


def test_quantized_weights_a_mesh_and_disk_records_are_refused():
    cfg = EngineConfig(max_seqs=SLOTS, page_size=PAGE, num_pages=64, max_seq_len=256,
                       prefill_chunk=CHUNK)
    with pytest.raises(ValueError, match="model.quant"):
        InferenceEngine(CONFIG, PARAMS, cfg, attn_backend="ref", quant="int8")
    from finchat_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="mesh"):
        InferenceEngine(CONFIG, PARAMS, cfg, attn_backend="ref", mesh=mesh)
    with pytest.raises(ValueError, match="session_cache_disk_path"):
        _scheduler(session_cache=True, session_cache_disk_path="/tmp/never-made")


@pytest.mark.parametrize("fields, said", [
    ({"n_kv_heads": 2}, "n_kv_heads 1"),
    ({"index_heads": 0}, "go together"),
    ({"kv_lora_rank": 0, "q_lora_rank": 0}, "latent attention's"),
    ({"moe_score": "tanh"}, "moe_score"),
    ({"moe_score": "softmax"}, "'sigmoid' router's"),
    ({"moe_groups": 3}, "moe_groups divides"),
    ({"dense_hidden_dim": 0}, "leading_dense_layers"),
    # (latent attention under a layer_pattern is built since PR 51: tests/test_model.py)
    ({"layer_pattern": ("full_attention", "sliding_attention"), "window": 8}, "stand beside"),
])
def test_configs_that_do_not_hold_together_are_refused(fields, said):
    with pytest.raises(ValueError, match=said):
        dataclasses.replace(CONFIG, **fields)
