"""Bounded-KV long-context serving (ISSUE 15; SnapStream-style sink +
window with page-granular eviction — engine/kv_cache.BoundedKVPolicy).

The contracts under test:

- eviction is pure host metadata riding the paged indirection: page
  occupancy stays bounded at sink+window pages for arbitrarily long
  sessions, the allocator invariants hold through eviction waves, and
  nothing leaks;
- streams are BYTE-IDENTICAL to the unbounded path while the context
  still fits the bounded budget (the policy is inert until it evicts);
  past it, the stream keeps decoding at flat cost (the divergence
  envelope — quality, not identity, is the contract there);
- a bounded row preempts by SNAPSHOT: the replay restores the surviving
  pages byte-identically and re-prefills only the residual tail, so a
  preempted long stream equals the unpreempted one token-for-token (the
  ISSUE 15 satellite bugfix — the old path re-prefilled tokens the
  policy would immediately evict);
- the session tier round-trips bounded entries through RAM and disk with
  the gap intact (record header field, CRC'd payload), and a gapped
  entry resumes whole-or-not;
- the free-run capture composes: eviction is staged at capture
  boundaries (like budget stops), so captured streams are byte-identical
  to host-stepped ones WITH eviction active;
- ring/seq-sharded prefill is PROMOTED into the ragged round (no more
  reason="ring" demotions): ring-routed prompts ride packed chunk rows
  whose per-page online-softmax is the ring fold's carry.

fp32 config throughout, for the same reason as tests/test_mixed_step.py:
identity contracts must not hide behind (or be excused by) bf16 near-tie
rounding.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finchat_tpu.engine.engine import InferenceEngine
from finchat_tpu.engine.kv_cache import (
    BoundedKVPolicy,
    PageAllocationError,
    pages_needed,
)
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
from finchat_tpu.models.llama import PRESETS, init_params
from finchat_tpu.utils.config import EngineConfig, load_config
from finchat_tpu.utils.metrics import METRICS

CONFIG = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
CHUNK = 16
PAGE = 8
SINK, WINDOW = 1, 4  # budget 5 pages = 40 tokens
BUDGET_TOKENS = (SINK + WINDOW) * PAGE


@pytest.fixture(scope="module")
def params():
    return init_params(CONFIG, jax.random.key(0))


def _sched(params, *, sink=SINK, window=WINDOW, mixed=True, max_seqs=4,
           num_pages=128, eos_id=-1, spec_tokens=0,
           session=False, disk="", max_seq_len=512):
    cfg = EngineConfig(
        max_seqs=max_seqs, page_size=PAGE, num_pages=num_pages,
        max_seq_len=max_seq_len, prefill_chunk=CHUNK, mixed_step=mixed,
        session_cache=session,
        session_cache_bytes=(32 << 20) if session else 0,
        session_cache_disk_path=disk,
        spec_tokens=spec_tokens,
        kv_sink_pages=sink, kv_window_pages=window,
    )
    engine = InferenceEngine(CONFIG, params, cfg)
    return ContinuousBatchingScheduler(engine, eos_id=eos_id)


async def _drain(handle, out):
    while True:
        ev = await asyncio.wait_for(handle.events.get(), timeout=120)
        if ev["type"] == "token":
            out.append(ev["token_id"])
        elif ev["type"] == "done":
            return
        else:
            raise AssertionError(ev)


def _greedy(n):
    return SamplingParams(temperature=0.0, max_new_tokens=n)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, CONFIG.vocab_size, size=n).tolist()


# --- policy units (pure host math) -----------------------------------------


def test_policy_eviction_plan_math():
    bp = BoundedKVPolicy(sink_pages=1, window_pages=4, page_size=8)
    assert bp.enabled and bp.budget_pages == 5 and bp.sink_tokens == 8
    # fits: nothing to evict
    assert bp.plan_eviction(30, 8, 5, 1) == 0
    # 38 written + 8 incoming = 46 tokens -> 6 pages > 5 capacity: evict 1
    assert bp.plan_eviction(38, 8, 5, 1) == 1
    # a whole chunk arriving: evict enough pages for it
    assert bp.plan_eviction(38, 16, 5, 1) == 2
    # pinned head widens the sink but doesn't change the count while
    # enough full post-sink pages exist
    assert bp.plan_eviction(38, 8, 5, 2) == 1
    # infeasible: everything below the partial tail is pinned
    with pytest.raises(PageAllocationError):
        bp.plan_eviction(38, 8, 5, 4)
    # eviction plan is deterministic in the written count alone
    assert all(bp.plan_eviction(w, 1, 5, 1) == (1 if (w + 1) > 40 else 0)
               for w in range(8, 41))


def test_policy_validation():
    # window too small for a prefill chunk between waves
    with pytest.raises(ValueError, match="dispatch burst"):
        BoundedKVPolicy(1, 2, 8).validate(
            prefill_chunk=16, max_pages_per_seq=32)
    # budget exceeding the page-table row width
    with pytest.raises(ValueError, match="max_pages_per_seq"):
        BoundedKVPolicy(4, 8, 8).validate(
            prefill_chunk=16, max_pages_per_seq=8)
    # disabled policy validates vacuously
    BoundedKVPolicy(0, 0, 8).validate(prefill_chunk=512, max_pages_per_seq=4)
    # a valid shape passes
    BoundedKVPolicy(1, 4, 8).validate(prefill_chunk=16, max_pages_per_seq=32)


def test_engine_rejects_infeasible_policy(params):
    with pytest.raises(ValueError, match="dispatch burst"):
        _sched(params, sink=1, window=2)


# --- identity while the context fits ---------------------------------------


def _run_single(params, *, sink, window, prompt, max_new, seed=0, **kw):
    sched = _sched(params, sink=sink, window=window, **kw)
    out: list[int] = []
    peak = {"pages": 0}

    async def go():
        await sched.start()
        try:
            h = await sched.submit("s", prompt, _greedy(max_new))
            task = asyncio.create_task(_drain(h, out))
            while not h.finished:
                peak["pages"] = max(
                    peak["pages"], len(sched.allocator.owned_by("s")))
                await asyncio.sleep(0.001)
            await task
            sched.allocator.check_invariants()
            assert sched.allocator.used_count == 0
        finally:
            await sched.stop()

    asyncio.run(go())
    return out, peak["pages"], sched


def test_bounded_identical_while_context_fits(params):
    """prompt + max_new within sink+window: the policy never evicts and
    the stream is byte-identical to the unbounded engine's."""
    prompt = _prompt(20, seed=1)
    base, _, _ = _run_single(params, sink=0, window=0,
                             prompt=prompt, max_new=12)
    before = METRICS.snapshot().get("finchat_boundedkv_evicted_pages_total", 0)
    bounded, peak, _ = _run_single(params, sink=SINK, window=WINDOW,
                                   prompt=prompt, max_new=12)
    after = METRICS.snapshot().get("finchat_boundedkv_evicted_pages_total", 0)
    assert bounded == base
    assert after == before, "eviction fired inside the window"
    assert peak <= SINK + WINDOW


def test_long_session_bounded_occupancy_and_envelope(params):
    """A session well past the budget: page occupancy stays pinned at
    sink+window while the stream decodes to completion (the divergence
    envelope — past the window the output is a valid greedy decode of the
    bounded attention, not the unbounded oracle's)."""
    prompt = _prompt(40, seed=2)  # 5 pages — at the budget before decode
    max_new = 40  # total 80 tokens = 10 unbounded pages, 2x the budget
    before = METRICS.snapshot().get("finchat_boundedkv_evicted_pages_total", 0)
    out, peak, sched = _run_single(params, sink=SINK, window=WINDOW,
                                   prompt=prompt, max_new=max_new)
    after = METRICS.snapshot().get("finchat_boundedkv_evicted_pages_total", 0)
    assert len(out) == max_new, "bounded stream did not complete"
    assert all(0 <= t < CONFIG.vocab_size for t in out)
    assert peak <= SINK + WINDOW, (peak, "occupancy exceeded the budget")
    # the unbounded requirement would have been 10 pages; eviction made
    # up the difference
    unbounded_pages = pages_needed(len(prompt) + max_new, PAGE)
    assert after - before >= unbounded_pages - (SINK + WINDOW)
    # the control: without the policy the same session's occupancy grows
    # with its context, past the budget, and evicts nothing
    _, control_peak, _ = _run_single(params, sink=0, window=0,
                                     prompt=prompt, max_new=max_new)
    assert SINK + WINDOW < control_peak <= unbounded_pages
    assert METRICS.snapshot().get(
        "finchat_boundedkv_evicted_pages_total", 0) == after


def test_bounded_composes_with_spec(params):
    """Spec verify rows ride bounded rows: the stream completes with occupancy bounded (write bursts covered by
    the eviction reserve) and zero leaks."""
    prompt = (_prompt(4, seed=3) * 5)[:18]  # repetitive: proposals fire
    out, peak, _ = _run_single(
        params, sink=SINK, window=WINDOW, prompt=prompt, max_new=36,
        spec_tokens=2,
    )
    assert len(out) == 36
    assert peak <= SINK + WINDOW


# --- preempt/replay (the satellite bugfix) ---------------------------------


def test_bounded_preempt_replay_byte_identity(params):
    """Preempting a bounded stream AFTER eviction started and replaying
    it yields the exact tokens of the unpreempted run: the replay
    restores the surviving sink+window pages from the preemption snapshot
    (byte-identical KV) and re-prefills only the residual tail — it never
    re-prefills (or re-allocates) evicted tokens."""
    prompt = _prompt(24, seed=4)
    max_new = 36

    def run(preempt: bool):
        sched = _sched(params)
        out: list[int] = []
        info = {}

        async def go():
            await sched.start()
            try:
                h = await sched.submit("s", prompt, _greedy(max_new))
                task = asyncio.create_task(_drain(h, out))
                if preempt:
                    # wait until the policy has actually evicted, then
                    # preempt at a CONSUMED boundary — the condition the
                    # page-pressure path guarantees by draining in-flight
                    # before executing its plan (the identity caveat in
                    # _bounded_preempt_snapshot): a preempt inside an
                    # eviction transition has no identity contract
                    for _ in range(100_000):
                        if (h.kv_gap > 0 and h.generated >= 24
                                and h.kv_gap_pos <= len(h.history) - 1):
                            break
                        await asyncio.sleep(0.001)
                    assert h.kv_gap > 0, "eviction never engaged"
                    sched._preempt(h)
                    info["preempted_gap"] = h.kv_gap
                await task
                sched.allocator.check_invariants()
                info["preempted"] = h.preempted
            finally:
                await sched.stop()

        asyncio.run(go())
        return out, info

    snap0 = METRICS.snapshot()
    clean, _ = run(False)
    replayed, info = run(True)
    snap1 = METRICS.snapshot()
    assert info["preempted"] == 1 and info["preempted_gap"] > 0
    assert replayed == clean, "bounded preempt/replay diverged"
    assert snap1.get("finchat_boundedkv_recompute_fallbacks_total", 0) == \
        snap0.get("finchat_boundedkv_recompute_fallbacks_total", 0), (
            "replay fell back to recompute instead of restoring")


def test_bounded_replay_allocates_only_surviving_pages(params):
    """The sizing half of the satellite bugfix: a preempted bounded
    stream re-admits with at most sink+window pages — never the unbounded
    prompt+budget requirement its full history would imply."""
    prompt = _prompt(24, seed=5)
    sched = _sched(params)

    async def go():
        await sched.start()
        try:
            h = await sched.submit("s", prompt, _greedy(36))
            out: list[int] = []
            task = asyncio.create_task(_drain(h, out))
            for _ in range(100_000):
                if h.kv_gap > 0 and h.generated >= 24:
                    break
                await asyncio.sleep(0.001)
            assert h.kv_gap > 0
            sched._preempt(h)
            # the full-history replay would need 8+ pages unbounded; the
            # bounded sizing caps at the budget
            assert sched._admission_pages(h) <= SINK + WINDOW
            while h.slot < 0 and not h.finished:
                await asyncio.sleep(0.001)
            assert len(sched.allocator.owned_by("s")) <= SINK + WINDOW
            await task
        finally:
            await sched.stop()

    asyncio.run(go())


# --- session tier round trip -----------------------------------------------


def _two_turn(params, *, disk="", fresh_for_turn2=False):
    """Turn 1 evicts and retires; turn 2 extends the history and resumes.
    Returns (turn2 tokens, entry gap, metrics window, scheduler)."""
    prompt1 = _prompt(24, seed=6)
    sched = _sched(params, session=True, disk=disk)
    t1: list[int] = []
    t2: list[int] = []

    async def turn1():
        await sched.start()
        try:
            h = await sched.submit("s1", prompt1, _greedy(32),
                                   conversation_id="conv")
            await _drain(h, t1)
            assert h.kv_gap > 0, "turn 1 never evicted"
        finally:
            await sched.stop()

    asyncio.run(turn1())
    entry = sched.session_cache.get("conv")
    assert entry is not None and entry.kv_gap > 0
    assert entry.n_tokens % PAGE == 0
    gap = entry.kv_gap

    sched2 = sched
    if fresh_for_turn2:
        # restart: a NEW scheduler over the same disk directory must
        # restore the record (RAM tier starts empty)
        # `is not None`: the tier's truth value is its index size, 0 while
        # the write-behind spill is still in flight — exactly when to flush
        if sched.session_cache is not None and sched.session_cache.disk is not None:
            sched.session_cache.disk.flush()
        sched2 = _sched(params, session=True, disk=disk)

    prompt2 = prompt1 + t1 + _prompt(6, seed=7)
    snap0 = METRICS.snapshot()

    async def turn2():
        await sched2.start()
        try:
            h = await sched2.submit("s2", prompt2, _greedy(10),
                                    conversation_id="conv")
            await _drain(h, t2)
            sched2.allocator.check_invariants()
        finally:
            await sched2.stop()

    asyncio.run(turn2())
    snap1 = METRICS.snapshot()
    win = {k: snap1.get(k, 0) - snap0.get(k, 0) for k in (
        "finchat_session_cache_hits_total",
        "finchat_session_cache_restored_tokens_total",
        "finchat_durability_disk_restores_total",
    )}
    return t2, gap, win, sched2


def test_session_roundtrip_bounded_ram(params):
    t2, gap, win, sched2 = _two_turn(params)
    assert len(t2) == 10
    assert win["finchat_session_cache_hits_total"] == 1
    assert win["finchat_session_cache_restored_tokens_total"] > 0
    # the resumed row carries the entry's gap forward
    assert gap > 0


def test_session_roundtrip_bounded_disk(params, tmp_path):
    """Restart between turns: the bounded record (kv_gap in the v2
    header, CRC'd payload) restores from disk and the conversation
    resumes with its sink+window intact."""
    t2, gap, win, _ = _two_turn(
        params, disk=str(tmp_path / "skv"), fresh_for_turn2=True)
    assert len(t2) == 10
    assert win["finchat_durability_disk_restores_total"] == 1
    assert win["finchat_session_cache_hits_total"] == 1


def test_bounded_record_serialization_roundtrip():
    """Record-level: kv_gap survives the v2 header round trip, the CRC
    still covers the payload, and a gap-less record reads as gap 0."""
    from finchat_tpu.engine.session_cache import SessionDiskTier

    ids = np.arange(48, dtype=np.int32)
    snap = (np.ones((2, 3, 8, 4), np.float32), np.ones((2, 3, 8, 4), np.float32),
            None, None)
    blob = SessionDiskTier._serialize("k", ids, 8, snap, kv_gap=16)
    out = SessionDiskTier._deserialize(blob)
    assert out["kv_gap"] == 16
    assert np.array_equal(out["token_ids"], ids)
    assert np.array_equal(out["snap"][0], snap[0])
    # corruption still quarantines: flip a payload byte -> CRC mismatch
    bad = bytearray(blob)
    bad[-1] ^= 0xFF
    with pytest.raises(ValueError, match="checksum"):
        SessionDiskTier._deserialize(bytes(bad))
    # pre-ISSUE-15 records carry no kv_gap field: read as 0
    legacy = SessionDiskTier._serialize("k", ids, 8, snap)
    assert SessionDiskTier._deserialize(legacy)["kv_gap"] == 0


def test_gapped_entry_whole_resume_or_sink_salvage():
    """A bounded entry resumes WHOLE when the prompt extends past its
    span unchanged; a prompt stopping short leaves it intact; divergence
    stales the windowed remainder (it attended to the evicted tokens) and
    salvages at most the pre-gap sink region as a gap-free prefix."""
    from finchat_tpu.engine.session_cache import SessionEntry, SessionKVCache

    def entry(kv_sink=8):
        return SessionEntry(
            conversation_id="c",
            token_ids=np.arange(1, 41, dtype=np.int32),  # 40 tokens
            snap=(np.ones((1, 3, 8, 2), np.float32),
                  np.ones((1, 3, 8, 2), np.float32), None, None),
            kv_gap=16,  # snapshot covers 24 of the 40 tokens
            kv_sink=kv_sink,
        )

    cache = SessionKVCache(1 << 20, page_size=8)
    cache.put(entry(), spill=False)
    # full-prefix prompt that extends past the span: whole resume
    e, matched = cache.match("c", list(range(1, 41)) + [99, 98])
    assert e is not None and matched == 40 and e.kv_gap == 16
    # prompt stopping short: no resume, entry kept INTACT
    e, matched = cache.match("c", list(range(1, 31)))
    assert e is None and matched == 0
    assert cache.get("c") is not None and cache.get("c").kv_gap == 16
    # divergence past the sink: the sink region survives as a gap-free
    # prefix (one 8-token page here) and the windowed remainder is gone
    diverged = list(range(1, 41))
    diverged[20] = 999
    e, matched = cache.match("c", diverged + [99])
    assert e is not None and matched == 8
    assert e.kv_gap == 0 and e.n_tokens == 8
    # a sink-less gapped entry (kv_sink 0) has nothing to salvage
    cache.put(entry(kv_sink=0), spill=False)
    e, matched = cache.match("c", diverged + [99])
    assert e is None and matched == 0
    assert cache.get("c") is None


# --- ring promotion ---------------------------------------------------------


def test_ring_promotion_no_demotion_and_identity(params, monkeypatch):
    """Ring-routed prefill rides the ragged round (no reason="ring"
    demotion; _use_mixed is unconditional): with a decode stream live and
    a ring-eligible prompt admitted, the coexist iterations stay fused
    and the streams equal the plain chunked scheduler's byte-for-byte."""

    def run(force_ring: bool):
        sched = _sched(params, sink=0, window=0)
        if force_ring:
            # route the long prompt down the ring predicate without a seq
            # mesh (the test_prefix_cache idiom): the promoted path must
            # treat it as packed chunk rows inside the ragged round —
            # never demote, never call the seq-sharded entry points
            monkeypatch.setattr(
                sched.engine, "_use_ring_prefill", lambda n: n >= 48)

            def boom(*a, **k):
                raise AssertionError(
                    "ring collective entry point reached from the mixed path")

            monkeypatch.setattr(sched.engine, "prefill_ring", boom)
        rng = np.random.default_rng(13)
        short = rng.integers(1, CONFIG.vocab_size, size=8).tolist()
        long_p = rng.integers(1, CONFIG.vocab_size, size=3 * CHUNK + 5).tolist()

        async def go():
            snap0 = METRICS.snapshot()
            await sched.start()
            try:
                hs = await sched.submit("short", short, _greedy(30))
                outs = {"short": [], "long": []}
                tasks = [asyncio.create_task(_drain(hs, outs["short"]))]
                while len(outs["short"]) < 2:
                    await asyncio.sleep(0.002)
                hl = await sched.submit("long", long_p, _greedy(6))
                tasks.append(asyncio.create_task(_drain(hl, outs["long"])))
                await asyncio.gather(*tasks)
                await asyncio.sleep(0.05)
                snap1 = METRICS.snapshot()
                ring_demotions = (
                    snap1.get('finchat_mixed_demotions_total{reason="ring"}', 0)
                    - snap0.get('finchat_mixed_demotions_total{reason="ring"}', 0)
                )
                coexist = {
                    k: snap1.get(k, 0) - snap0.get(k, 0)
                    for k in ("finchat_coexist_dispatches_total",
                              "finchat_coexist_iterations_total")
                }
                return outs, ring_demotions, coexist
            finally:
                await sched.stop()

        return asyncio.run(go())

    plain, _, _ = run(False)
    promoted, ring_demotions, coexist = run(True)
    assert ring_demotions == 0, "ring rows still demote the mixed path"
    assert promoted == plain, "promoted ring rows changed the streams"
    iters = coexist["finchat_coexist_iterations_total"]
    assert iters > 0, "long prompt never coexisted with the decode stream"
    # the acceptance headline: one fused dispatch per coexist iteration
    # even with the ring-routed row in the mix
    assert coexist["finchat_coexist_dispatches_total"] == iters


@pytest.mark.slow
def test_ring_promotion_real_seq_mesh(params):
    """The same promotion on a REAL seq-sharded mesh: when no decode
    coexists the prompt runs the genuine ring collective (split path);
    when a decode stream is live the ragged round takes the chunk rows —
    the greedy continuation matches the unsharded chunked scheduler
    (the test_parallel ring/chunked equality precedent)."""
    from finchat_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=4, seq=2, expert=1, model=1))
    rng = np.random.default_rng(17)
    short = rng.integers(1, CONFIG.vocab_size, size=8).tolist()
    long_p = rng.integers(1, CONFIG.vocab_size, size=50).tolist()

    def run(use_mesh: bool):
        cfg = EngineConfig(
            max_seqs=2, page_size=PAGE, num_pages=64, max_seq_len=256,
            prefill_chunk=CHUNK, mixed_step=True, session_cache=False,
            ring_prefill_min_tokens=32, ring_prefill_chunk=16,
        )
        engine = InferenceEngine(CONFIG, params, cfg,
                                 mesh=mesh if use_mesh else None)
        sched = ContinuousBatchingScheduler(engine, eos_id=-1)

        async def go():
            snap0 = METRICS.snapshot()
            await sched.start()
            try:
                hs = await sched.submit("short", short, _greedy(24))
                outs = {"short": [], "long": []}
                tasks = [asyncio.create_task(_drain(hs, outs["short"]))]
                while len(outs["short"]) < 2:
                    await asyncio.sleep(0.002)
                if use_mesh:
                    assert engine._use_ring_prefill(len(long_p))
                hl = await sched.submit("long", long_p, _greedy(5))
                tasks.append(asyncio.create_task(_drain(hl, outs["long"])))
                await asyncio.gather(*tasks)
                await asyncio.sleep(0.05)
                snap1 = METRICS.snapshot()
                ring_demotions = (
                    snap1.get('finchat_mixed_demotions_total{reason="ring"}', 0)
                    - snap0.get('finchat_mixed_demotions_total{reason="ring"}', 0)
                )
                return outs, ring_demotions
            finally:
                await sched.stop()

        return asyncio.run(go())

    plain, _ = run(False)
    promoted, ring_demotions = run(True)
    assert ring_demotions == 0
    assert promoted == plain


def test_bounded_rows_never_ring_route(params, monkeypatch):
    """Bounded rows must never take the seq-sharded ring path (the ring
    steps write at absolute positions with no kv_gaps awareness, and a
    segment's burst exceeds the wave's chunk reserve): even a ring-
    eligible prompt rides chunked prefill, evicts, and completes within
    the budget — the ring entry points are never reached."""
    sched = _sched(params)  # bounded: SINK + WINDOW

    def boom(*a, **k):
        raise AssertionError("ring entry point reached on a bounded row")

    monkeypatch.setattr(sched.engine, "_use_ring_prefill", lambda n: n >= 48)
    monkeypatch.setattr(sched.engine, "prefill_ring", boom)
    monkeypatch.setattr(sched.engine, "prefill_ring_segment", boom)
    prompt = _prompt(64, seed=21)  # ring-eligible AND past the 40-token budget
    out: list[int] = []

    async def go():
        await sched.start()
        try:
            h = await sched.submit("s", prompt, _greedy(12))
            assert not sched._ring_routed(h)
            await _drain(h, out)
            sched.allocator.check_invariants()
        finally:
            await sched.stop()

    asyncio.run(go())
    assert len(out) == 12


def test_gapped_entry_refused_on_unbounded_engine(params):
    """A gapped session entry arriving on an engine WITHOUT the bounded
    policy (disk restore / fleet import after the knobs were turned off)
    must cold-start — there is no eviction machinery for it to live
    under; pre-fix this crashed retirement with an AttributeError on
    bounded_kv.sink_tokens."""
    from finchat_tpu.engine.session_cache import SessionEntry

    sched = _sched(params, sink=0, window=0, session=True)
    prompt = _prompt(40, seed=22)
    snap_pages = 3
    entry = SessionEntry(
        conversation_id="conv",
        token_ids=np.asarray(prompt[:40], np.int32),
        snap=tuple(
            np.zeros((CONFIG.n_layers, snap_pages, PAGE,
                      CONFIG.n_kv_heads * CONFIG.head_dim), np.float32)
            if i < 2 else None for i in range(4)
        ),
        kv_gap=16,
        kv_sink=8,
    )
    sched.session_cache.put(entry, spill=False)
    out: list[int] = []

    async def go():
        await sched.start()
        try:
            h = await sched.submit("s", prompt + _prompt(6, seed=23),
                                   _greedy(8), conversation_id="conv")
            await _drain(h, out)
            assert h.kv_gap == 0, "gapped resume leaked onto an unbounded engine"
            sched.allocator.check_invariants()
        finally:
            await sched.stop()

    snap0 = METRICS.snapshot()
    asyncio.run(go())
    snap1 = METRICS.snapshot()
    assert len(out) == 8
    # the admission was a cold start, not a gapped resume
    assert snap1.get("finchat_session_cache_hits_total", 0) == \
        snap0.get("finchat_session_cache_hits_total", 0)


# --- config plumbing --------------------------------------------------------


def test_bounded_kv_env_readers(monkeypatch):
    monkeypatch.setenv("FINCHAT_KV_SINK_PAGES", "3")
    monkeypatch.setenv("FINCHAT_KV_WINDOW_PAGES", "17")
    cfg = load_config()
    assert cfg.engine.kv_sink_pages == 3
    assert cfg.engine.kv_window_pages == 17


def test_boundedkv_metrics_preseeded(params):
    reg = METRICS.labeled(replica="probe-bkv")
    cfg = EngineConfig(
        max_seqs=2, page_size=PAGE, num_pages=32, max_seq_len=128,
        prefill_chunk=CHUNK, session_cache=False,
        kv_sink_pages=SINK, kv_window_pages=WINDOW,
    )
    engine = InferenceEngine(CONFIG, params, cfg)
    ContinuousBatchingScheduler(engine, eos_id=-1, metrics=reg,
                                replica_id="probe-bkv")
    snap = METRICS.snapshot()
    assert snap.get('finchat_boundedkv_sink_pages{replica="probe-bkv"}') == SINK
    assert snap.get('finchat_boundedkv_window_pages{replica="probe-bkv"}') == WINDOW
    assert snap.get('finchat_boundedkv_evicted_pages_total{replica="probe-bkv"}') == 0
    assert snap.get('finchat_boundedkv_bounded_sessions_total{replica="probe-bkv"}') == 0
    assert snap.get('finchat_boundedkv_recompute_fallbacks_total{replica="probe-bkv"}') == 0
