"""Batched retrieval plane (ISSUE 3): embedding microbatcher, batched
multi-query top-k with device-side filters, and retrieval/prefill overlap.

The golden contracts:
- ``query_points_batch`` (device-filter plane) returns byte-identical hit
  lists to ``query_points`` (serial host-mask plane) for every filter
  combination, including the post-hoc security re-check backstop;
- the overlap path (submit_partial → extend_prompt) produces greedy
  token streams identical to a plain submit of the same prompt;
- one bad text in a coalesced embed batch fails only its own request.
"""

import asyncio
import time

import jax
import numpy as np
import pytest

from finchat_tpu.embed.batcher import EmbedMicrobatcher
from finchat_tpu.embed.encoder import EMBED_PRESETS, EmbeddingEncoder, init_bert_params
from finchat_tpu.embed.index import DeviceVectorIndex, QuerySpec, VectorPoint
from finchat_tpu.models.tokenizer import ByteTokenizer
from finchat_tpu.utils.metrics import METRICS

NOW = 1_700_000_000.0


def _point(uid, date, text, vec):
    return VectorPoint(
        id=f"{uid}-{text[:12]}-{date}",
        vector=np.asarray(vec, np.float32),
        payload={"page_content": text, "metadata": {"user_id": uid, "date": date}},
    )


@pytest.fixture(scope="module")
def encoder():
    config = EMBED_PRESETS["bge-tiny"]
    params = init_bert_params(config, jax.random.key(0))
    return EmbeddingEncoder(config, params, ByteTokenizer())


# --- batched multi-query top-k ------------------------------------------

def test_batch_topk_matches_serial_under_all_filters():
    rng = np.random.default_rng(7)
    index = DeviceVectorIndex(dim=8, initial_capacity=4)  # forces growth
    points = [
        _point(f"u{i % 3}", float(i * 1000), f"txn {i}", rng.normal(size=8))
        for i in range(37)
    ]
    index.upsert(points[:10])
    index.upsert(points[10:])  # second upsert exercises the incremental splice
    specs = [
        QuerySpec(rng.normal(size=8), limit=5, user_id="u0"),
        QuerySpec(rng.normal(size=8), limit=3, user_id="u1", date_gte=9_000.0),
        QuerySpec(rng.normal(size=8), limit=50),           # no filters
        QuerySpec(rng.normal(size=8), limit=10, user_id="nobody"),  # unknown user
        QuerySpec(rng.normal(size=8), limit=10, user_id="u2", date_gte=1e12),  # empty window
    ]
    batched = index.query_points_batch(specs)
    for spec, hits in zip(specs, batched):
        serial = index.query_points(
            spec.vector, limit=spec.limit, user_id=spec.user_id, date_gte=spec.date_gte
        )
        assert [p.id for p in serial] == [p.id for p in hits]
    assert batched[3] == [] and batched[4] == []


def test_batch_topk_date_filter_exact_at_modern_epoch():
    """Unix timestamps (~1.7e9) have 128 s float32 spacing — a single-f32
    device date column would mis-filter rows within ~2 min of the cutoff.
    The double-single (hi, lo) compare must match the serial float64 host
    path exactly at second granularity."""
    base = 1_700_000_000.0
    index = DeviceVectorIndex(dim=4, initial_capacity=8)
    index.upsert([
        _point("u", base + 10.0, "just inside", [1, 0, 0, 0]),
        _point("u", base - 10.0, "just outside", [1, 0, 0, 0]),
        _point("u", base, "exactly at cutoff", [1, 0, 0, 0]),
    ])
    spec = QuerySpec(np.asarray([1.0, 0, 0, 0]), limit=8, user_id="u", date_gte=base)
    batched = index.query_points_batch([spec])[0]
    serial = index.query_points(
        spec.vector, limit=spec.limit, user_id=spec.user_id, date_gte=spec.date_gte
    )
    assert [p.id for p in batched] == [p.id for p in serial]
    kept = {p.payload["page_content"] for p in batched}
    assert kept == {"just inside", "exactly at cutoff"}


def test_batch_topk_sees_rows_upserted_after_first_query():
    """The incremental device upload must land new rows without a full
    re-upload being the only correct path."""
    index = DeviceVectorIndex(dim=4, initial_capacity=8)
    index.upsert([_point("u", 1.0, "old row", [0, 1, 0, 0])])
    index.query_points_batch([QuerySpec(np.asarray([1.0, 0, 0, 0]), limit=4)])
    index.upsert([_point("u", 2.0, "new row", [1, 0, 0, 0])])
    hits = index.query_points_batch(
        [QuerySpec(np.asarray([1.0, 0, 0, 0]), limit=4, user_id="u")]
    )[0]
    assert hits and hits[0].payload["page_content"] == "new row"


def test_save_releases_lock_before_file_io(tmp_path, monkeypatch):
    """A snapshot must not stall concurrent queries: the index lock is
    released before compression/IO begins."""
    index = DeviceVectorIndex(dim=4, initial_capacity=8)
    index.upsert([_point("u", 1.0, "row", [1, 0, 0, 0])])
    saw = {}
    orig = np.savez_compressed

    def probe(*args, **kwargs):
        saw["lock_free"] = index._lock.acquire(blocking=False)
        if saw["lock_free"]:
            index._lock.release()
        return orig(*args, **kwargs)

    monkeypatch.setattr(np, "savez_compressed", probe)
    index.save(str(tmp_path / "snap"))
    assert saw["lock_free"] is True
    restored = DeviceVectorIndex.load(str(tmp_path / "snap"), dim=4)
    assert len(restored) == 1


def test_security_post_check_on_both_planes(encoder):
    """A payload whose user_id was tampered with AFTER upsert passes the
    (stale) filter column but must be dropped by the post-hoc re-check —
    on the serial AND the batched retrieval plane."""
    from finchat_tpu.tools.retrieval import TransactionRetriever

    async def run():
        index = DeviceVectorIndex(dim=encoder.dim)
        plain = TransactionRetriever(encoder, index, now=lambda: NOW)
        plain.upsert_transactions("alice", ["ALICE TXN $1", "ALICE TXN $2"], dates=[NOW, NOW])
        # tamper: the interned code column still says alice, payload says eve
        index._points[1].payload["metadata"]["user_id"] = "eve"
        serial_hits = await plain({"user_id": "alice", "search_query": "txn"})

        batcher = EmbedMicrobatcher(encoder, window_ms=0.5, max_batch=8)
        batched = TransactionRetriever(encoder, index, now=lambda: NOW, batcher=batcher)
        batched_hits = await batched({"user_id": "alice", "search_query": "txn"})
        await batcher.close()
        return serial_hits, batched_hits

    serial_hits, batched_hits = asyncio.run(run())
    assert serial_hits == batched_hits
    assert serial_hits == ["ALICE TXN $1"]


def test_batched_retriever_matches_serial(encoder):
    """Full-tool golden: the batched plane returns the same rows in the
    same order as the serial plane for the same query."""
    from finchat_tpu.tools.retrieval import TransactionRetriever

    async def run():
        index = DeviceVectorIndex(dim=encoder.dim)
        serial = TransactionRetriever(encoder, index, now=lambda: NOW)
        serial.upsert_transactions(
            "alice",
            ["GROCERY $54.12", "RENT $2000", "COFFEE $4.50", "GAS $30"],
            dates=[NOW - 86400 * 40, NOW - 86400 * 5, NOW - 86400, NOW - 3600],
        )
        serial.upsert_transactions("bob", ["BOB SECRET $999"], dates=[NOW])
        batcher = EmbedMicrobatcher(encoder, window_ms=0.5, max_batch=8)
        batched = TransactionRetriever(encoder, index, now=lambda: NOW, batcher=batcher)
        args = {"user_id": "alice", "search_query": "purchases", "time_period_days": 7}
        a = await serial.structured(args)
        b = await batched.structured(args)
        await batcher.close()
        return a, b

    a, b = asyncio.run(run())
    assert a == b
    assert len(a) == 3 and not any("BOB" in r["page_content"] for r in a)


# --- embedding microbatcher ---------------------------------------------

async def test_microbatcher_window_flush(encoder):
    """Requests landing inside the wait window ride ONE dispatch."""
    b = EmbedMicrobatcher(encoder, window_ms=30, max_batch=16)
    d0 = METRICS.get("finchat_embed_batch_dispatches_total")
    outs = await asyncio.gather(*[b.embed_one(f"text {i}") for i in range(5)])
    d1 = METRICS.get("finchat_embed_batch_dispatches_total")
    assert d1 - d0 == 1
    assert METRICS.get("finchat_embed_batch_occupancy") == 5
    direct = encoder.embed_batch([f"text {i}" for i in range(5)])
    for i, out in enumerate(outs):
        np.testing.assert_array_equal(out, direct[i])
    await b.close()


async def test_microbatcher_max_batch_flush(encoder):
    """A full bucket dispatches immediately — the window is a CAP on the
    wait, not a floor."""
    b = EmbedMicrobatcher(encoder, window_ms=10_000, max_batch=4)
    t0 = time.perf_counter()
    await asyncio.gather(*[b.embed_one(f"t{i}") for i in range(4)])
    assert time.perf_counter() - t0 < 5.0  # nowhere near the 10 s window
    await b.close()


async def test_microbatcher_error_isolation(encoder):
    """One request's un-encodable text fails only its own future."""
    class Boom(Exception):
        pass

    class FlakyEncoder:
        dim = encoder.dim

        def embed_batch(self, texts):
            if any(t == "BAD" for t in texts):
                raise Boom("bad text")
            return encoder.embed_batch(texts)

    b = EmbedMicrobatcher(FlakyEncoder(), window_ms=30, max_batch=16)
    results = await asyncio.gather(
        b.embed_one("fine 1"), b.embed_one("BAD"), b.embed_one("fine 2"),
        return_exceptions=True,
    )
    assert isinstance(results[1], Boom)
    assert not isinstance(results[0], Exception)
    assert not isinstance(results[2], Exception)
    np.testing.assert_array_equal(results[0], encoder.embed_batch(["fine 1"])[0])
    await b.close()


async def test_microbatcher_threadsafe_ingest_path(encoder):
    """Worker threads (the ingest path) coalesce through the same loop."""
    b = EmbedMicrobatcher(encoder, window_ms=20, max_batch=16)
    b.bind_loop()
    d0 = METRICS.get("finchat_embed_batch_dispatches_total")
    query, ingest = await asyncio.gather(
        b.embed_one("query text"),
        asyncio.to_thread(b.embed_threadsafe, ["ingest 1", "ingest 2"]),
    )
    d1 = METRICS.get("finchat_embed_batch_dispatches_total")
    assert d1 - d0 == 1  # query + ingest shared one dispatch
    assert query.shape == (encoder.dim,) and ingest.shape == (2, encoder.dim)
    await b.close()


# --- retrieval/prefill overlap (scheduler + agent) ----------------------

def _mini_scheduler(max_new=8):
    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.kv_cache import pages_needed
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.utils.config import EngineConfig

    config = PRESETS["mini"]
    page_size = 32
    max_seq_len = 512
    pps = pages_needed(max_seq_len, page_size)
    ecfg = EngineConfig(
        max_seqs=4, page_size=page_size, num_pages=4 * pps + 8,
        max_seq_len=max_seq_len, prefill_chunk=32, session_cache=False,
    )
    params = init_params(config, jax.random.key(0))
    engine = InferenceEngine(config, params, ecfg)
    return ContinuousBatchingScheduler(engine, eos_id=-1), config


async def _collect(handle):
    tokens = []
    while True:
        ev = await handle.events.get()
        if ev["type"] == "token":
            tokens.append(ev["token_id"])
        elif ev["type"] == "done":
            return tokens
        else:
            raise RuntimeError(ev)


async def _wait_parked(handle, timeout=30.0):
    t0 = time.perf_counter()
    while handle.prefill_pos < len(handle.prompt_ids):
        assert time.perf_counter() - t0 < timeout
        await asyncio.sleep(0.02)


def test_partial_extend_golden_equivalence():
    """Greedy tokens from submit_partial→park→extend_prompt must be
    byte-identical to a plain submit of the same full prompt."""
    from finchat_tpu.engine.sampler import SamplingParams

    sched, config = _mini_scheduler()
    rng = np.random.default_rng(0)
    prefix = rng.integers(1, config.vocab_size, size=90).tolist()
    full = prefix + rng.integers(1, config.vocab_size, size=30).tolist()
    samp = SamplingParams(temperature=0.0, max_new_tokens=8)

    async def run():
        await sched.start()
        try:
            plain = await _collect(await sched.submit("plain", full, samp))
            hold = await sched.submit_partial("hold", prefix, samp)
            assert hold is not None
            await _wait_parked(hold)
            assert sched.extend_prompt(hold, full)
            overlapped = await _collect(hold)
            return plain, overlapped
        finally:
            await sched.stop()

    plain, overlapped = asyncio.run(run())
    assert plain == overlapped


def test_partial_extend_mismatch_falls_back_cleanly():
    """A graft that does not extend the held prefix is refused; cancel
    returns every page to the allocator."""
    from finchat_tpu.engine.sampler import SamplingParams

    sched, config = _mini_scheduler()
    rng = np.random.default_rng(1)
    prefix = rng.integers(1, config.vocab_size, size=70).tolist()
    samp = SamplingParams(temperature=0.0, max_new_tokens=4)

    async def run():
        await sched.start()
        try:
            hold = await sched.submit_partial("hold", prefix, samp)
            await _wait_parked(hold)
            divergent = [9] + prefix  # does not start with the prefix
            assert not sched.extend_prompt(hold, divergent)
            assert not sched.extend_prompt(hold, prefix)  # no new tokens
            sched.cancel(hold)
            await asyncio.sleep(0.05)
            assert not sched.prefilling and not sched.decoding
            sched.allocator.check_invariants()
            assert sched.allocator.used_count == 0
        finally:
            await sched.stop()

    asyncio.run(run())


def test_agent_overlap_stream_identical_to_serial():
    """Full-stack golden: the agent's streamed greedy response with
    retrieval_overlap on equals the serial path byte-for-byte, and the
    overlap run actually grafted (not silently fallen back)."""
    from finchat_tpu.agent.graph import LLMAgent
    from finchat_tpu.analysis.sanitizers import scheduler_leak_report
    from finchat_tpu.engine.generator import EngineGenerator, StubGenerator
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.models.tokenizer import get_tokenizer

    async def retriever(args):
        await asyncio.sleep(0.2)  # stand-in for embed+search latency
        return ["COFFEE $4.50 on 2026-07-30", "RENT $2000 on 2026-07-01"]

    async def run(overlap: bool):
        sched, _ = _mini_scheduler()
        await sched.start()
        try:
            gen = EngineGenerator(sched, get_tokenizer())
            agent = LLMAgent(
                StubGenerator(default='retrieve_transactions({"search_query": "spending"})'),
                gen, retriever, "You are Penny.", "Decide retrieval.",
                response_sampling=SamplingParams(temperature=0.0, max_new_tokens=8),
                today=lambda: "2026-08-03",
                retrieval_overlap=overlap,
            )
            text = []
            async for ev in agent.stream_with_status(
                "what did I spend?", "u1", "CTX",
                [], conversation_id=None,
            ):
                if ev["type"] == "response_chunk":
                    text.append(ev["content"])
            return "".join(text)
        finally:
            await sched.stop()
            # the hold taken during the decision and grafted onto the real
            # scheduler left no page, slot or refcount behind
            assert scheduler_leak_report(sched) == []

    g0 = METRICS.get("finchat_partial_grafts_total")
    h0 = METRICS.get("finchat_partial_holds_total")
    l0 = METRICS.get("finchat_tool_launches_total")
    on = asyncio.run(run(True))
    g1 = METRICS.get("finchat_partial_grafts_total")
    assert METRICS.get("finchat_partial_holds_total") - h0 >= 1
    assert METRICS.get("finchat_tool_launches_total") - l0 >= 1
    off = asyncio.run(run(False))
    g2 = METRICS.get("finchat_partial_grafts_total")
    assert on == off and on  # byte-identical, non-empty
    assert g1 - g0 == 1  # overlap run grafted
    assert g2 - g1 == 0  # serial run did not


async def test_release_partial_frees_abandoned_hold():
    """A hold whose stream never runs (retrieval errored upstream) is
    released by the agent's leak guard, not reaped 30 s later."""
    from finchat_tpu.engine.generator import EngineGenerator
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.models.tokenizer import get_tokenizer

    sched, _ = _mini_scheduler()
    await sched.start()
    try:
        gen = EngineGenerator(sched, get_tokenizer())
        samp = SamplingParams(temperature=0.0, max_new_tokens=4)
        hold = await gen.begin_partial("<|system|>\nA long enough prefix text.\n", samp)
        assert hold is not None
        await _wait_parked(hold)
        assert sched.allocator.used_count > 0
        gen.release_partial(hold)
        await asyncio.sleep(0.05)
        assert sched.allocator.used_count == 0
        sched.allocator.check_invariants()
    finally:
        await sched.stop()
