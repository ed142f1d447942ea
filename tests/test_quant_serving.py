"""Quantized serving plane end-to-end (ISSUE 14).

The contracts under test:

- int4 weight quantization (models/quant.py Q4Tensor): packed two
  nibbles per byte, per-channel or per-group scales, stacked build
  bitwise-identical to whole-leaf, bounded roundtrip error, forward
  logits inside the quality envelope vs full precision.
- int8 KV as a first-class page dtype on every serving path: the
  packed ragged round equals the split steps, spec-verify acceptance
  stays greedy-exact, and the session tier round-trips the scale planes
  byte-identically (RAM and disk).
- Record-format versioning (SessionDiskTier v2): dtypes stored by NAME
  (v1's ``dtype.str`` made bf16 snapshots unreadable — the latent bug
  this version fixes), v1 records stay readable, cross-mode records are
  refused with a counted quarantine-style fallback instead of serving
  garbage KV.
- The quantized embed encoder ranks like the fp32 one (top-k overlap
  >= 0.99 on a golden corpus).
- Observability: quant mode labels stay inside the declared registries
  and ride every dispatch trace event; the finchat_quant_* family is
  pre-seeded.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finchat_tpu.analysis.sanitizers import scheduler_leak_report
from finchat_tpu.engine.engine import InferenceEngine, commit_first_token, prefill_step
from finchat_tpu.engine.kv_cache import (
    TRASH_PAGE,
    PageAllocator,
    gather_pages_host,
    pages_needed,
    scatter_pages_device,
)
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
from finchat_tpu.engine.session_cache import SessionDiskTier, snap_kv_mode
from finchat_tpu.models.llama import PRESETS, forward_full, init_params
from finchat_tpu.models.quant import (
    Q4Tensor,
    dequantize,
    init_quantized_llama_params,
    quantize_int4,
    quantize_stacked,
    validate_quant_mode,
)
from finchat_tpu.utils.config import EngineConfig
from finchat_tpu.utils.metrics import METRICS
from finchat_tpu.utils.tracing import QUANT_MODES, TRACER

# fp32 pins the byte-identity contracts (the PR 4/10 discipline): int8
# page ints and fp32 scale planes round-trip bit-exactly, so restored KV
# must decode exactly like recomputed KV
CONFIG = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return init_params(CONFIG, jax.random.key(0))


# --- int4 weight machinery --------------------------------------------------


def test_int4_roundtrip_error_bound():
    w = jax.random.normal(jax.random.key(0), (24, 16), jnp.float32)
    qt = quantize_int4(w)
    deq = np.asarray(dequantize(qt, jnp.float32))
    # symmetric rounding: error per element <= half the column's scale
    bound = np.asarray(qt.scale)[0][None, :] / 2 + 1e-7
    assert np.all(np.abs(deq - np.asarray(w)) <= bound)


def test_int4_exact_on_representable_values():
    # values that are exact multiples of amax/7 round-trip exactly
    scale = 0.37
    ints = np.random.default_rng(0).integers(-7, 8, size=(8, 4))
    ints[0, :] = 7  # pin each column's amax so scale = 7*s/7 = s
    w = jnp.asarray(ints * scale, jnp.float32)
    qt = quantize_int4(w)
    assert np.allclose(np.asarray(dequantize(qt, jnp.float32)),
                       np.asarray(w), atol=1e-6)


def test_int4_group_scales_shapes_and_tighter_error():
    w = jax.random.normal(jax.random.key(1), (32, 8), jnp.float32)
    per_col = quantize_int4(w)
    grouped = quantize_int4(w, group_size=8)
    assert per_col.scale.shape == (1, 8)
    assert grouped.scale.shape == (4, 8)
    assert per_col.shape == grouped.shape == (32, 8)
    err_col = float(jnp.max(jnp.abs(dequantize(per_col, jnp.float32) - w)))
    err_grp = float(jnp.max(jnp.abs(dequantize(grouped, jnp.float32) - w)))
    assert err_grp <= err_col + 1e-7
    with pytest.raises(AssertionError):
        quantize_int4(w, group_size=3)  # odd groups can't pack nibble pairs


def test_int4_stacked_bitwise_matches_whole_leaf():
    w = jax.random.normal(jax.random.key(2), (3, 16, 8), jnp.float32)
    stacked = quantize_stacked(w, mode="int4", group_size=4)
    whole = quantize_int4(w, group_size=4)
    assert isinstance(stacked, Q4Tensor)
    assert np.array_equal(np.asarray(stacked.q), np.asarray(whole.q))
    assert np.array_equal(np.asarray(stacked.scale), np.asarray(whole.scale))


@pytest.mark.parametrize("group", [0, 32])
def test_int4_forward_logits_track_fp32(params, group):
    """The quality envelope: an int4 tree's full-causal logits stay within
    a bounded relative delta of the fp32 tree's (coarser than int8 — 15
    levels per group — but bounded)."""
    qparams = init_quantized_llama_params(
        CONFIG, jax.random.key(0), mode="int4", group_size=group)
    tokens = jnp.asarray([[5, 9, 2, 100, 17, 3, 44, 8]], jnp.int32)
    pos = jnp.arange(8)[None, :]
    base = np.asarray(forward_full(params, tokens, pos, config=CONFIG))
    got = np.asarray(forward_full(qparams, tokens, pos, config=CONFIG))
    rel = np.max(np.abs(got - base)) / np.max(np.abs(base))
    assert 0 < rel < 0.6
    if group:
        # per-group scales must not be WORSE than per-channel at the
        # smallest group that spans the whole contraction (same scales)
        assert got.shape == base.shape


def test_quant_mode_validation():
    validate_quant_mode("")
    validate_quant_mode("int8")
    validate_quant_mode("int4")
    with pytest.raises(ValueError):
        validate_quant_mode("int2")
    with pytest.raises(ValueError):
        InferenceEngine(CONFIG, init_params(CONFIG, jax.random.key(0)),
                        EngineConfig(max_seqs=2, page_size=8, num_pages=16,
                                     max_seq_len=64, prefill_chunk=8),
                        quant="fp8")


def test_int4_engine_serves_and_labels(params):
    cfg = EngineConfig(max_seqs=2, page_size=8, num_pages=32, max_seq_len=128,
                       prefill_chunk=8, kv_quant="int8")
    eng = InferenceEngine(CONFIG, params, cfg, quant="int4", quant_group=32)
    assert eng.quant_label == "int4+kv8"
    alloc = PageAllocator(cfg.num_pages)
    eng.set_page_table_row(0, alloc.allocate("s", 4))
    logits = eng.prefill(0, [5, 9, 2, 100, 17, 3])
    assert np.isfinite(np.asarray(logits)).all()


# --- int8-KV on the whole hot path -----------------------------------------


def _kv8_engine(params, **over):
    cfg = EngineConfig(max_seqs=4, page_size=8, num_pages=64, max_seq_len=128,
                       prefill_chunk=8, kv_quant="int8", **over)
    return InferenceEngine(CONFIG, params, cfg), cfg


def test_session_offload_restore_byte_identity_ram_and_disk(params, tmp_path):
    """The ISSUE 14 session contract: an int8-KV page snapshot — data ints
    AND per-token-per-head scale planes — survives offload -> disk record
    -> restore byte-identically, so a resumed turn decodes the exact same
    KV the retiring turn wrote."""
    eng, cfg = _kv8_engine(params)
    alloc = PageAllocator(cfg.num_pages)
    pages = alloc.allocate("s", 4)
    eng.set_page_table_row(0, pages)
    eng.prefill(0, list(range(1, 25)))  # 3 pages of real KV
    snap = eng.offload_pages(pages[:3])
    assert snap[2] is not None and snap[3] is not None  # scale planes travel
    assert snap_kv_mode(snap) == "int8"

    # disk roundtrip (record v2): byte-identical including scales
    tier = SessionDiskTier(str(tmp_path), 1 << 20, async_writes=False,
                           kv_quant="int8")
    assert tier.spill("conv", np.arange(24, dtype=np.int32), 0, snap)
    payload = tier.load("conv")
    assert payload is not None
    for a, b in zip(payload["snap"], snap):
        assert np.array_equal(a, b)

    # restore into FRESH pages on a second engine: gathered bytes equal
    eng2, _ = _kv8_engine(params)
    fresh = [9, 10, 11]
    s = eng2.state
    k, v, ks, vs = scatter_pages_device(
        s.k_pages, s.v_pages, s.k_scales, s.v_scales, fresh, payload["snap"])
    back = gather_pages_host(k, v, ks, vs, fresh)
    for a, b in zip(back, snap):
        assert np.array_equal(a, b)


def test_scatter_pages_cross_mode_raises(params):
    """The last line behind the counted refusal gates: a cross-mode
    snapshot must raise, never value-cast into plausible garbage KV."""
    eng_bf = InferenceEngine(
        CONFIG, params,
        EngineConfig(max_seqs=2, page_size=8, num_pages=32, max_seq_len=64,
                     prefill_chunk=8),
    )
    eng_q8, _ = _kv8_engine(params)
    alloc = PageAllocator(32)
    pages = alloc.allocate("s", 2)
    eng_q8.set_page_table_row(0, pages)
    eng_q8.prefill(0, list(range(1, 10)))
    snap_q8 = eng_q8.offload_pages(pages)
    s = eng_bf.state
    with pytest.raises(ValueError, match="cross-mode"):
        scatter_pages_device(s.k_pages, s.v_pages, s.k_scales, s.v_scales,
                             [3, 4], snap_q8)


def test_import_session_entry_cross_mode_refused_and_counted(params):
    """A cross-mode export (fleet handoff / disk record from an engine
    serving the other page dtype) is refused at import — counted as a
    dequant fallback — and the conversation resumes cold."""
    cfg = EngineConfig(max_seqs=2, page_size=8, num_pages=32, max_seq_len=64,
                       prefill_chunk=8, session_cache=True,
                       session_cache_bytes=1 << 20)
    sched = ContinuousBatchingScheduler(
        InferenceEngine(CONFIG, params, cfg), eos_id=-1)
    snap_q8 = (np.zeros((2, 1, 8, 16), np.int8), np.zeros((2, 1, 8, 16), np.int8),
               np.ones((2, 1, 8, 8), np.float32), np.ones((2, 1, 8, 8), np.float32))
    payload = {"conversation_id": "x", "token_ids": np.arange(8, dtype=np.int32),
               "prefix_len": 0, "snap": snap_q8}
    before = METRICS.get("finchat_quant_dequant_fallbacks_total")
    assert not sched.import_session_entry(payload)
    assert METRICS.get("finchat_quant_dequant_fallbacks_total") == before + 1
    assert sched.session_cache.get("x") is None


def test_ragged_round_matches_split_steps_int8kv(params):
    """The packed ragged round composes with quantized pages: a prompt's
    completing tail and a decode row in ONE ``ragged_mixed_step`` over an
    int8-KV pool give the tokens, the context and the pool (the int8
    pages bit for bit, the scale planes to the last ulp) that ``prefill_step`` +
    ``commit_first_token`` and a ``decode_step`` give an identically
    prepared engine."""
    from finchat_tpu.engine.engine import decode_step, ragged_mixed_step

    CHUNK = 16

    def prepare():
        cfg = EngineConfig(max_seqs=4, page_size=8, num_pages=64,
                           max_seq_len=128, prefill_chunk=CHUNK, kv_quant="int8")
        eng = InferenceEngine(CONFIG, params, cfg)
        alloc = PageAllocator(cfg.num_pages)
        p0 = [3, 7, 11, 200, 42]
        eng.set_page_table_row(0, alloc.allocate("s0", pages_needed(len(p0) + 16, 8)))
        logits = eng.prefill(0, p0)
        eng.state, _ = commit_first_token(
            eng.state, jnp.int32(0), logits,
            jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0))
        p1 = list(range(1, CHUNK + 6))
        eng.set_page_table_row(1, alloc.allocate("s1", pages_needed(len(p1) + 16, 8)))
        eng.state, _ = prefill_step(
            eng.params, eng.state,
            jnp.asarray([p1[:CHUNK]], jnp.int32), jnp.asarray([1], jnp.int32),
            jnp.asarray([0], jnp.int32), jnp.asarray([CHUNK], jnp.int32),
            config=eng.config, page_size=8, attn_backend=eng.attn_backend)
        return eng, p1[CHUNK:]

    def end_state(eng):  # the pool less the trash page, where padding writes land
        st = eng.state
        pool = (st.k_pages, st.v_pages, st.k_scales, st.v_scales)
        return [np.asarray(x) for x in (st.context_lens, st.last_tokens)] + [
            np.asarray(x)[:, TRASH_PAGE + 1:] for x in pool]

    B = R = 4
    zB, oB, kB = jnp.zeros((B,)), jnp.ones((B,)), jnp.zeros((B,), jnp.int32)
    static = dict(config=CONFIG, page_size=8)

    eng_s, tail = prepare()
    eng_s.state, lg = prefill_step(
        eng_s.params, eng_s.state,
        jnp.asarray([tail + [0] * (CHUNK - len(tail))], jnp.int32),
        jnp.asarray([1], jnp.int32), jnp.asarray([CHUNK], jnp.int32),
        jnp.asarray([len(tail)], jnp.int32), attn_backend=eng_s.attn_backend, **static)
    eng_s.state, first1 = commit_first_token(
        eng_s.state, jnp.int32(1), lg[0], jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0))
    eng_s.state, toks, *_ = decode_step(
        eng_s.params, eng_s.state, jnp.zeros((B,), bool).at[0].set(True), zB, oB, kB,
        attn_backend=eng_s.attn_backend, **static)

    eng_r, tail = prepare()
    T = 8
    tokens = tail + [0] * (T - len(tail))
    tok_row = [0] * len(tail) + [1] + [R] * (T - len(tail) - 1)
    eng_r.state, emitted, n_em, _lg = ragged_mixed_step(
        eng_r.params, eng_r.state, jnp.asarray(tokens, jnp.int32),
        jnp.asarray(tok_row, jnp.int32), jnp.asarray([1, 0, 0, 0], jnp.int32),
        jnp.asarray([CHUNK, 0, 0, 0], jnp.int32), jnp.asarray([len(tail), 1, 0, 0], jnp.int32),
        jnp.asarray([False, True, False, False]), jnp.asarray([True, True, False, False]),
        kB, zB, oB, kB, attn_backend=eng_r.attn_backend, spec_width=0, **static)

    assert eng_r.state.k_pages.dtype == jnp.int8 and eng_r.state.k_scales is not None
    assert np.asarray(n_em).tolist() == [1, 1, 0, 0]
    assert [int(emitted[0, 0]), int(emitted[1, 0])] == [int(first1), int(toks[0])]
    for got, want in zip(end_state(eng_r), end_state(eng_s)):
        if got.dtype == np.float32:  # a scale computed at the packed shape: the last ulp
            np.testing.assert_allclose(got, want, rtol=1e-5)
        else:
            np.testing.assert_array_equal(got, want)


def test_spec_verify_acceptance_parity_int8kv(params):
    """Spec verify under quantized KV keeps the greedy-exactness
    contract: oracle drafts fully accept, garbage drafts fully reject,
    and the emitted stream equals token-by-token decode — on the SAME
    int8-KV engine config, so acceptance is judged against the quantized
    model's own greedy stream."""
    cfg = EngineConfig(max_seqs=4, page_size=8, num_pages=64, max_seq_len=128,
                       prefill_chunk=8, kv_quant="int8")
    KD = 3
    prompt = [5, 9, 2, 100, 17, 3]
    n_new = 9

    def arm(eng, alloc, prompt):
        pages = alloc.allocate("s", pages_needed(len(prompt) + n_new, 8))
        eng.set_page_table_row(0, pages)
        logits = eng.prefill(0, prompt)
        eng.state, tok = commit_first_token(
            eng.state, jnp.int32(0), logits,
            jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0))
        return int(tok)

    def plain():
        eng = InferenceEngine(CONFIG, params, cfg)
        out = [arm(eng, PageAllocator(cfg.num_pages), prompt)]
        B = cfg.max_seqs
        active = jnp.zeros((B,), bool).at[0].set(True)
        z, o, zk = jnp.zeros((B,)), jnp.ones((B,)), jnp.zeros((B,), jnp.int32)
        for _ in range(n_new - 1):
            out.append(int(eng.decode(active, z, o, zk)[0]))
        return out

    def spec(drafts_for):
        eng = InferenceEngine(CONFIG, params, cfg)
        out = [arm(eng, PageAllocator(cfg.num_pages), prompt)]
        B = cfg.max_seqs
        active = jnp.zeros((B,), bool).at[0].set(True)
        z, o, zk = jnp.zeros((B,)), jnp.ones((B,)), jnp.zeros((B,), jnp.int32)
        steps = 0
        while len(out) < n_new:
            proposal = list(drafts_for(out))[: min(KD, n_new - len(out) - 1)]
            drafts = np.zeros((B, KD), np.int32)
            n_drafts = np.zeros((B,), np.int32)
            drafts[0, : len(proposal)] = proposal
            n_drafts[0] = len(proposal)
            emitted, n_emitted = eng.decode_spec(
                active, jnp.asarray(drafts), jnp.asarray(n_drafts), z, o, zk)
            n = int(n_emitted[0])
            assert 1 <= n <= len(proposal) + 1
            out.extend(int(t) for t in np.asarray(emitted[0, :n]))
            steps += 1
        return out, steps

    want = plain()
    got, steps = spec(lambda so_far: want[len(so_far): len(so_far) + KD])
    assert got == want
    assert steps == -(-(n_new - 1) // (KD + 1))  # full acceptance
    wrong = [(t + 1) % CONFIG.vocab_size for t in want]
    got, steps = spec(lambda so_far: wrong[len(so_far): len(so_far) + KD])
    assert got == want
    assert steps == n_new - 1  # nothing accepted


@pytest.mark.parametrize("quant,kv_quant", [
    ("", "int8"), ("int8", ""), ("int8", "int8"), ("int4", "")],
    ids=["kv8", "int8", "int8+kv8", "int4"])
def test_scheduler_resume_byte_identity_quantized(params, tmp_path, quant, kv_quant):
    """Scheduler-level, in every quantized mode: turn 2 resumed from the
    session tier (RAM + disk write-through) is byte-identical to a cold
    re-prefill on a fresh engine of the same mode, the resume dispatches
    fewer chunks, and the disk record equals the RAM entry plane for plane
    (with an int8 cache: the scale planes too)."""
    def run(session: bool, turn2_prompt=None):
        cfg = EngineConfig(
            max_seqs=2, page_size=8, num_pages=64, max_seq_len=256,
            prefill_chunk=16, kv_quant=kv_quant, session_cache=session,
            session_cache_bytes=1 << 20,
            session_cache_disk_path=str(tmp_path / "skv") if session else "",
        )
        sched = ContinuousBatchingScheduler(
            InferenceEngine(CONFIG, params, cfg, quant=quant), eos_id=-1)
        rng = np.random.default_rng(3)
        p1 = rng.integers(1, CONFIG.vocab_size, size=40).tolist()
        out = {}

        async def go():
            await sched.start()
            try:
                async def stream(seq, prompt):
                    h = await sched.submit(
                        seq, prompt,
                        SamplingParams(temperature=0.0, max_new_tokens=12),
                        conversation_id="conv")
                    toks = []
                    while True:
                        ev = await asyncio.wait_for(h.events.get(), timeout=120)
                        if ev["type"] == "token":
                            toks.append(ev["token_id"])
                        elif ev["type"] == "done":
                            return toks
                        else:
                            raise AssertionError(ev)

                t1 = await stream("t1", p1)
                if session:
                    cache = sched.session_cache
                    await asyncio.to_thread(cache.disk.flush)
                    entry, record = cache.get("conv"), cache.disk.load("conv")
                    assert np.array_equal(entry.token_ids, record["token_ids"])
                    assert (entry.snap[2] is not None) == (kv_quant == "int8")
                    for a, b in zip(entry.snap, record["snap"]):
                        assert (a is None and b is None) or np.array_equal(a, b)
                prompt2 = turn2_prompt if turn2_prompt is not None else (
                    p1 + t1 + rng.integers(1, CONFIG.vocab_size, size=10).tolist())
                c0 = METRICS.snapshot().get("finchat_prefill_seconds_count", 0)
                t2 = await stream("t2", prompt2)
                out["chunks"] = METRICS.snapshot().get(
                    "finchat_prefill_seconds_count", 0) - c0
                return prompt2, t2
            finally:
                await sched.stop()
                assert scheduler_leak_report(sched) == []

        return asyncio.run(go()) + (out["chunks"],)

    prompt2, warm_t2, warm_chunks = run(True)
    _, cold_t2, cold_chunks = run(False, turn2_prompt=prompt2)
    assert warm_t2 == cold_t2
    assert warm_chunks < cold_chunks


def test_int8_kv_pool_holds_more_pages_per_byte():
    """What an int8 cache buys, as a count from ``page_hbm_bytes`` (the
    sizing function ``PagedKVCache.create`` is held to in
    tests/test_kv_cache.py): the same pool bytes hold at least 1.9 x the
    pages at an 8B bf16 shape with 256-token pages (1.94 x: the fp32 scale
    planes cost 3 %), and at least 1.75 x at this file's fp32 shape, where
    2 KV heads pad to 8 scale rows."""
    from finchat_tpu.engine.kv_cache import page_hbm_bytes

    def ratio(config, page_size):
        return (page_hbm_bytes(config, page_size)
                / page_hbm_bytes(config, page_size, "int8"))

    assert 1.9 <= ratio(PRESETS["llama3-8b"], 256) < 2.0
    assert round(ratio(PRESETS["llama3-8b"], 256), 2) == 1.94
    assert 1.75 <= ratio(CONFIG, 16) < 4.0


# --- quantized embed encoder ------------------------------------------------


def test_quantized_embed_topk_overlap():
    """The retrieval-quality gate: int8 encoder rankings overlap the fp32
    encoder's top-k >= 0.99 on a golden corpus (per-channel weight
    rounding moves cosine scores ~1e-3 — far below ranking resolution)."""
    from finchat_tpu.embed.encoder import (
        EMBED_PRESETS,
        EmbeddingEncoder,
        init_bert_params,
    )
    from finchat_tpu.models.tokenizer import ByteTokenizer

    cfg = EMBED_PRESETS["bge-tiny"]
    p = init_bert_params(cfg, jax.random.key(0))
    enc = EmbeddingEncoder(cfg, p, ByteTokenizer())
    encq = EmbeddingEncoder(cfg, p, ByteTokenizer(), quant="int8")
    corpus = [
        f"{i}: {kind} {3 * i + 7}.{(13 * i) % 100:02d} at {place}-{i % 7}"
        for i, (kind, place) in enumerate(
            (kind, place)
            for kind in ("coffee", "grocery", "rent", "salary", "transfer")
            for place in ("acme", "downtown", "north", "airport")
        )
    ]
    queries = ["coffee purchases", "rent payment", "salary deposit",
               "airport spending", "grocery run downtown"]
    E, Eq = enc.embed_batch(corpus), encq.embed_batch(corpus)
    overlaps = []
    K, EPS = 10, 2e-3
    for q in queries:
        s = E @ enc.embed_query(q)  # fp32 scores (the reference ranking)
        b = np.argsort(-(Eq @ encq.embed_query(q)))[:K]
        # near-tie tolerant: a quantized pick whose FP32 score sits within
        # the quant envelope of the rank-K boundary is not a real ranking
        # change — random tiny weights cluster scores ~1e-3 apart at the
        # boundary, which no ranking (fp32 included) resolves stably
        kth = np.sort(s)[-K]
        overlaps.append(float(np.mean(s[b] >= kth - EPS)))
    assert float(np.mean(overlaps)) >= 0.99
    with pytest.raises(ValueError):
        EmbeddingEncoder(cfg, p, ByteTokenizer(), quant="int4")


# --- record-format versioning ----------------------------------------------


def test_bf16_snapshot_dtype_roundtrips(tmp_path):
    """The v1 latent bug, fixed: bf16 arrays serialize by dtype NAME and
    deserialize bit-exactly (v1 stored np.dtype.str — '<V2' void — and
    every bf16 record quarantined at restore)."""
    import ml_dtypes

    snap = (np.arange(64, dtype=np.float32).astype(ml_dtypes.bfloat16).reshape(2, 2, 16),
            np.ones((2, 2, 16), ml_dtypes.bfloat16), None, None)
    tier = SessionDiskTier(str(tmp_path), 1 << 20, async_writes=False)
    assert tier.spill("c", np.arange(8, dtype=np.int32), 0, snap)
    p = tier.load("c")
    assert p is not None and p["snap"][0].dtype == ml_dtypes.bfloat16
    assert np.array_equal(p["snap"][0].view(np.uint16), snap[0].view(np.uint16))


def test_v1_record_still_readable(tmp_path):
    import json

    snap = (np.ones((2, 1, 4), np.float32), np.ones((2, 1, 4), np.float32),
            None, None)
    blob = SessionDiskTier._serialize("c3", np.arange(4, dtype=np.int32), 0, snap)
    hlen = int.from_bytes(blob[5:9], "big")
    hdr = json.loads(blob[9:9 + hlen])
    payload = blob[9 + hlen:]
    hdr.pop("kv")  # v1 had no mode stamp
    for s in hdr["snap"]:
        if s:
            s["dtype"] = np.dtype(s["dtype"]).str  # v1 stored dtype.str
    h2 = json.dumps(hdr).encode()
    v1 = SessionDiskTier.MAGIC + bytes([1]) + len(h2).to_bytes(4, "big") + h2 + payload
    (tmp_path / SessionDiskTier._fname("c3")).write_bytes(v1)
    tier = SessionDiskTier(str(tmp_path), 1 << 20, async_writes=False)
    p = tier.load("c3")
    assert p is not None and p["snap"][0].dtype == np.float32
    assert np.array_equal(p["snap"][0], snap[0])


@pytest.mark.parametrize("direction", ["q8_into_bf16", "bf16_into_q8"])
def test_cross_mode_record_refused_and_counted(tmp_path, direction):
    """A valid record written under the other page-pool dtype is set
    aside (*.crossmode — quarantine-style, distinct from corruption),
    counted as a dequant fallback, and the conversation cold-starts; the
    startup sweep applies the same policy."""
    if direction == "q8_into_bf16":
        snap = (np.ones((2, 1, 8, 16), np.int8), np.ones((2, 1, 8, 16), np.int8),
                np.ones((2, 1, 8, 8), np.float32), np.ones((2, 1, 8, 8), np.float32))
        writer_mode, reader_mode = "int8", ""
    else:
        snap = (np.ones((2, 1, 8, 16), np.float32),
                np.ones((2, 1, 8, 16), np.float32), None, None)
        writer_mode, reader_mode = "", "int8"
    writer = SessionDiskTier(str(tmp_path), 1 << 20, async_writes=False,
                             kv_quant=writer_mode)
    assert writer.spill("conv", np.arange(8, dtype=np.int32), 0, snap)
    before = METRICS.get("finchat_quant_dequant_fallbacks_total")
    q_before = METRICS.get("finchat_durability_quarantines_total")
    reader = SessionDiskTier(str(tmp_path), 1 << 20, async_writes=False,
                             kv_quant=reader_mode)
    assert "conv" not in reader  # sweep set it aside
    assert reader.load("conv") is None
    assert METRICS.get("finchat_quant_dequant_fallbacks_total") == before + 1
    # NOT a quarantine: the record is valid, just for the other mode
    assert METRICS.get("finchat_durability_quarantines_total") == q_before
    assert list(tmp_path.glob("*.crossmode"))


def test_prefix_only_records_are_mode_agnostic(tmp_path):
    """A record with no snapshot (shared-head-only entry) restores under
    either mode — nothing to scatter, nothing to refuse."""
    writer = SessionDiskTier(str(tmp_path), 1 << 20, async_writes=False,
                             kv_quant="int8")
    assert writer.spill("conv", np.arange(16, dtype=np.int32), 16, None)
    reader = SessionDiskTier(str(tmp_path), 1 << 20, async_writes=False,
                             kv_quant="")
    p = reader.load("conv")
    assert p is not None and p["snap"] is None and p["prefix_len"] == 16


# --- observability ----------------------------------------------------------


def test_quant_labels_in_registry(params):
    """Every label the engine can emit is declared in tracing.QUANT_MODES
    (the timeline consumers' source of truth)."""
    base = EngineConfig(max_seqs=2, page_size=8, num_pages=16,
                        max_seq_len=64, prefill_chunk=8)
    for quant in ("", "int8", "int4"):
        for kv in ("", "int8"):
            eng = InferenceEngine(
                CONFIG, params, dataclasses.replace(base, kv_quant=kv),
                quant=quant)
            assert eng.quant_label in QUANT_MODES, eng.quant_label


def test_quant_metrics_preseeded_and_dispatch_traced(params):
    """Scheduler construction pre-seeds the finchat_quant_* family (mode
    gauges in bits, zeroed fallback/envelope counters) and every dispatch
    trace event carries the quant label."""
    cfg = EngineConfig(max_seqs=2, page_size=8, num_pages=16, max_seq_len=64,
                       prefill_chunk=8, kv_quant="int8")
    sched = ContinuousBatchingScheduler(
        InferenceEngine(CONFIG, params, cfg, quant="int4"), eos_id=-1)
    snap = METRICS.snapshot()
    assert snap.get("finchat_quant_weight_bits") == 4
    assert snap.get("finchat_quant_kv_bits") == 8
    assert "finchat_quant_dequant_fallbacks_total" in snap
    assert "finchat_quant_envelope_exceeded_total" in snap
    TRACER.configure(enabled=True)
    sched._trace_dispatch("decode", [(0, "tid", "decode", None, 8)])  # (slot, id, mode, head, kv)
    ev = TRACER.snapshot()[-1]
    assert ev[2] == "dispatch" and ev[5]["quant"] == "int4+kv8"
