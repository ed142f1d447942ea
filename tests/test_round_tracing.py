"""One clock for host and device (ISSUE 24).

Pins what the scheduler, the jitted steps and the request span now put on
record:

- ROUND: every loop iteration that dispatched or consumed leaves one
  ``round`` event whose phases come from ``ROUND_PHASES``, are >= 0 and sum
  to the round's length — in every loop branch — and tracing never changes
  the tokens.
- DISPATCH: the ``kv_tokens`` a dispatch notes on its open phase annotation
  is the sum of the contexts of the rows that rode it; ``kv_tokens_distinct``
  counts a prefix entry's head once (the yardstick's ``perfbench.live_kv``
  count of the same handles) and ``prefix_rows`` the rows sharing one.
- REQUEST: the ``request`` span says how it ended and how large it was.
- SLOW ROUND: a stalled event loop leaves one WARNING that names the phase.
- RETIRE (ISSUE 53): the round in which an answer ends books the eviction
  under ``retire``, out of ``deliver``; each row ended by ``eos`` / ``length``
  leaves one ``retire`` event whose parts are clocked where the work
  happens, and ``finchat_retire_seconds_total{part}`` moves without the ring.
- SCOPES: every ``DEVICE_SCOPES`` name is in the compiled steps' ``op_name``
  metadata, and finchat-lint R5 rejects a scope, phase or reason literal the
  registries do not declare.
- START-UP: each phase sets its gauge and leaves a ``startup`` event.
"""

import asyncio
import dataclasses
import logging
import re
import textwrap
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finchat_tpu.analysis.core import run_analysis
from finchat_tpu.engine.engine import InferenceEngine, decode_step, ragged_mixed_step
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
from finchat_tpu.models.llama import PRESETS, init_params
from finchat_tpu.utils import faults
from finchat_tpu.utils.config import EngineConfig
from finchat_tpu.utils.metrics import METRICS
from finchat_tpu.utils.tracing import (
    DEVICE_SCOPES,
    FINISH_REASONS,
    RETIRE_PARTS,
    ROUND_PHASES,
    STARTUP_PHASES,
    TRACE_EVENTS,
    TRACER,
    RoundPhases,
)


@pytest.fixture(autouse=True)
def _tracer_reset():
    prev_enabled = TRACER.enabled
    TRACER.configure(enabled=True, flight_dir="")
    TRACER.clear()
    yield
    TRACER.configure(enabled=prev_enabled)
    TRACER.clear()
    faults.disarm_all()


def _engine(preset="tiny", **overrides):
    config = dataclasses.replace(PRESETS[preset], dtype=jnp.float32)
    defaults = dict(max_seqs=4, page_size=8, num_pages=128, max_seq_len=256,
                    prefill_chunk=16, session_cache=False)
    defaults.update(overrides)
    return InferenceEngine(config, init_params(config, jax.random.key(0)),
                           EngineConfig(**defaults))


def _scheduler(eos_id=-1, **overrides):
    return ContinuousBatchingScheduler(_engine(**overrides), eos_id=eos_id)


async def _drain(handle):
    tokens = []
    while True:
        event = await handle.events.get()
        if event["type"] == "token":
            tokens.append(event["token_id"])
        else:
            return tokens, event


def _ring(name):
    return [ev for ev in TRACER.snapshot() if ev[2] == name]


PROMPT_A = [1, 2, 3, 4, 5, 6, 7, 8] * 5
PROMPT_B = PROMPT_A[::-1] * 2

# loop branch -> (engine options, the round kind that proves the branch ran,
# the second request's arrival: "staggered" into the first one's decode,
# "none" for one request alone)
BRANCHES = {
    "split_decode": (dict(mixed_step=False), "decode", "staggered"),
    "spec": (dict(mixed_step=False, spec_tokens=2), "spec", "staggered"),
    "ragged": (dict(), "ragged", "staggered"),
    "prefill_only": (dict(mixed_step=False), "prefill", "none"),
}


def _run_branch(options, arrival, traced, n_new=None):
    """Drive the rehearsal scheduler through one loop branch; returns the
    streams, the riders every dispatch was traced with beside what it noted
    on its phase annotation, and the final dispatch tally."""
    TRACER.configure(enabled=traced)
    TRACER.clear()
    riders_seen, notes = [], []

    async def go():
        sched = _scheduler(**options)
        trace_dispatch = sched._trace_dispatch

        def spy(kind, riders, **kw):
            riders_seen.append((kind, list(riders)))
            trace_dispatch(kind, riders, **kw)

        sched._trace_dispatch = spy
        await sched.start()
        try:
            sampling = SamplingParams(
                temperature=0.0,
                max_new_tokens=n_new or (1 if arrival == "none" else 24))
            first = await sched.submit("a", PROMPT_A, sampling, trace_id="a")
            streams = [asyncio.create_task(_drain(first))]
            if arrival == "staggered":
                while first.generated < 3:
                    await asyncio.sleep(0.001)
                second = await sched.submit("b", PROMPT_B, sampling, trace_id="b")
                streams.append(asyncio.create_task(_drain(second)))
            done = await asyncio.wait_for(asyncio.gather(*streams), timeout=240)
        finally:
            await sched.stop()
        return [tokens for tokens, _end in done], sched._dispatch_tally

    note = RoundPhases.note
    RoundPhases.note = lambda self, **numbers: (notes.append(numbers),
                                                note(self, **numbers))[1]
    try:
        tokens, tally = asyncio.run(go())
    finally:
        RoundPhases.note = note
    assert len(notes) == len(riders_seen)
    return tokens, [(*seen, noted) for seen, noted in zip(riders_seen, notes)], tally


@pytest.mark.parametrize("branch", sorted(BRANCHES))
def test_every_loop_branch_leaves_rounds_that_add_up(branch):
    options, kind, arrival = BRANCHES[branch]
    tokens_on, riders_seen, tally = _run_branch(options, arrival, traced=True)
    rounds, dispatches = _ring("round"), _ring("dispatch")
    assert kind in {ev[5]["kind"] for ev in rounds}, (kind, rounds)
    # every dispatch is booked in exactly one round: the rounds' tallies
    # rise to the scheduler's own, and a round dispatched or consumed
    assert rounds[-1][5]["n"] == tally == len(dispatches)
    previous = 0
    for _ts, _tid, _name, dur, _track, args in rounds:
        phases = {p: args[p] for p in ROUND_PHASES}
        assert set(args) == set(ROUND_PHASES) | {"kind", "n"}
        assert all(seconds >= 0.0 for seconds in phases.values()), phases
        assert sum(phases.values()) == pytest.approx(dur, rel=1e-3), (phases, dur)
        assert args["n"] > previous or phases["fetch_wait"] > 0.0
        previous = args["n"]
    # a dispatch notes the sum of the contexts of the rows that rode it
    assert len(riders_seen) == len(dispatches)
    for (kind_seen, riders, noted), ev in zip(riders_seen, dispatches):
        args = ev[5]
        assert args["kind"] == kind_seen
        assert args["rows"] == [[slot, tid, mode]
                                for slot, tid, mode, _head, _kv in riders]
        # no prefix entry in these runs: every token is on a page of its row's own
        assert all(head is None for *_row, head, _kv in riders)
        assert noted == {"kind": kind_seen, "rows": len(riders),
                         "kv_tokens": sum(kv for *_row, kv in riders),
                         "kv_tokens_distinct": sum(kv for *_row, kv in riders),
                         "prefix_rows": 0}
        assert all(kv >= 1 for *_row, kv in riders), riders
    # every stream ran to its budget: one retirement each, inside a round
    # whose ``retire`` phase holds exactly the retirements it spanned
    retired = _ring("retire")
    assert sorted(ev[1] for ev in retired) == ["a", "b"][:len(tokens_on)]
    for ts, _tid, _name, dur, _track, args in rounds:
        inside = [ev[3] for ev in retired if ts <= ev[0] and ev[0] + ev[3] <= ts + dur]
        assert args["retire"] == pytest.approx(sum(inside), abs=1e-9)
    assert sum(ev[5]["retire"] > 0.0 for ev in rounds) <= len(retired)
    # tracing on or off, the streams are the same tokens
    tokens_off, _riders, _tally = _run_branch(options, arrival, traced=False)
    assert TRACER.snapshot() == []
    assert tokens_on == tokens_off


def test_decode_dispatch_reads_each_row_context():
    """One sequence on the split path: the k-th decode dispatch reads a
    context of prompt + k tokens, and a prefill chunk the context it
    completes."""
    tokens, seen, _tally = _run_branch(dict(mixed_step=False), "none", traced=True)
    assert len(tokens[0]) == 1
    assert [noted["kv_tokens"] for kind, _r, noted in seen if kind == "prefill"] \
        == [16, 32, 40]
    _tokens, seen, _tally = _run_branch(dict(mixed_step=False), "none",
                                        traced=True, n_new=6)
    decode = [noted["kv_tokens"] for kind, _r, noted in seen if kind == "decode"]
    assert decode == [len(PROMPT_A) + k for k in range(1, len(decode) + 1)]


HEAD_A = list(range(1, 33))  # four whole pages
HEAD_B = list(range(101, 117))  # two whole pages
SHARED_HEADS = {
    # name: (prompts, heads registered, the largest set of rows on one head,
    #        tokens that Σ rows' contexts counts more than once)
    "one_head": ([HEAD_A + [40, 41, 42], HEAD_A + [50] * 9, HEAD_A + [60, 61]],
                 [HEAD_A], 3, 2 * 32),
    "two_heads": ([HEAD_A + [40, 41, 42], HEAD_A + [50] * 9, HEAD_B + [60, 61],
                   HEAD_B + [70] * 5], [HEAD_A, HEAD_B], 2, 32 + 16),
    "a_head_of_one_row": ([HEAD_A + [40, 41, 42], [3] * 21, [4] * 9], [HEAD_A], 0, 0),
    "no_head": ([[7, 8, 9] * 5, [9, 8, 7] * 7, [5] * 11], [], 0, 0),
}


@pytest.mark.parametrize("case", sorted(SHARED_HEADS))
def test_decode_dispatch_notes_the_distinct_tokens_and_the_shared_rows(case):
    """Rows admitted on prefix entries decoding together: the annotation's
    ``kv_tokens_distinct`` is Σ rows' contexts with each entry's head once —
    the count the benchmark keeps for itself (``perfbench.live_kv``) on the
    same handles — and ``prefix_rows`` the rows of the set the decode
    kernel's shared-head pass takes."""
    from perfbench import live_kv

    prompts, heads, prefix_rows, repeats = SHARED_HEADS[case]
    seen, notes = [], []

    async def go():
        sched = _scheduler(mixed_step=False)
        for head in heads:
            assert sched.register_prefix(head + [99]) == len(head)
        trace_dispatch = sched._trace_dispatch

        def spy(kind, riders, **kw):
            trace_dispatch(kind, riders, **kw)
            if kind == "decode" and len(riders) == len(prompts):
                slots = {slot for slot, *_rest in riders}
                riding = [h for h in sched.decoding.values() if h.slot in slots]
                seen.append((notes[-1], live_kv.kv_tokens(riding)))

        sched._trace_dispatch = spy
        await sched.start()
        try:
            sampling = SamplingParams(temperature=0.0, max_new_tokens=12)
            handles = [await sched.submit(f"r{i}", p, sampling, trace_id=f"r{i}")
                       for i, p in enumerate(prompts)]
            await asyncio.wait_for(asyncio.gather(*map(_drain, handles)), timeout=240)
        finally:
            await sched.stop()

    note = RoundPhases.note
    RoundPhases.note = lambda self, **numbers: (notes.append(numbers),
                                                note(self, **numbers))[1]
    try:
        asyncio.run(go())
    finally:
        RoundPhases.note = note
    assert len(seen) >= 4, "the rows never decoded together"
    for noted, (total, distinct) in seen:
        assert noted["rows"] == len(prompts) and noted["prefix_rows"] == prefix_rows
        assert noted["kv_tokens"] == total
        assert noted["kv_tokens_distinct"] == distinct == total - repeats


# --- the request span -------------------------------------------------------

def _request_args(trace_id):
    events = [ev for ev in _ring("request") if ev[1] == trace_id]
    assert len(events) == 1, events
    return events[0][5]


def test_request_span_says_how_it_ended_and_how_large_it_was():
    finished0 = {r: METRICS.get("finchat_requests_finished_total",
                                labels={"reason": r}) for r in FINISH_REASONS}
    prompt0 = METRICS.get("finchat_prompt_tokens_total")
    cached0 = METRICS.get("finchat_prompt_tokens_cached_total")
    head = list(range(1, 33))  # four whole pages: a shared head

    async def go():
        greedy = SamplingParams(temperature=0.0, max_new_tokens=4)
        sched = _scheduler(mixed_step=False)
        assert sched.register_prefix(head + [99]) == 32
        await sched.start()
        try:
            # length: the answer runs to max_new_tokens; its head is cached
            h = await sched.submit("len", head + [40, 41, 42], greedy, trace_id="len")
            tokens, end = await asyncio.wait_for(_drain(h), timeout=120)
            assert end == {"type": "done", "reason": "length"}
            # cancelled mid-stream
            h = await sched.submit("can", PROMPT_A, SamplingParams(
                temperature=0.0, max_new_tokens=200), trace_id="can")
            while h.generated < 2:
                await asyncio.sleep(0.001)
            sched.cancel(h)
            # shed: its deadline passed before admission
            h = await sched.submit("shed", PROMPT_A, greedy, trace_id="shed",
                                   deadline=time.perf_counter() - 1.0)
            _tokens, end = await asyncio.wait_for(_drain(h), timeout=120)
            assert end["code"] == "deadline_exceeded"
            # error: a fault fails its prefill
            faults.arm("scheduler.prefill", faults.for_seq("err", RuntimeError("boom")))
            h = await sched.submit("err", PROMPT_A, greedy, trace_id="err")
            _tokens, end = await asyncio.wait_for(_drain(h), timeout=120)
            assert end["type"] == "error"
        finally:
            faults.disarm_all()
            await sched.stop()
        # eos: the same prompt on a scheduler whose EOS is its first token
        sched = _scheduler(eos_id=tokens[0], mixed_step=False)
        await sched.start()
        try:
            h = await sched.submit("eos", head + [40, 41, 42], greedy, trace_id="eos")
            _tokens, end = await asyncio.wait_for(_drain(h), timeout=120)
            assert end == {"type": "done", "reason": "eos"}
        finally:
            await sched.stop()

    asyncio.run(go())
    length = _request_args("len")
    assert length["reason"] == "length" and length["generated"] == 4
    assert length["prompt_tokens"] == 35 and length["cached_tokens"] == 32
    assert 0.0 <= length["queue_wait_s"] < 60.0
    cancelled = _request_args("can")
    assert cancelled["reason"] == "cancelled" and cancelled["generated"] >= 2
    assert cancelled["prompt_tokens"] == len(PROMPT_A) and cancelled["cached_tokens"] == 0
    shed = _request_args("shed")
    assert shed["reason"] == "shed" and shed["queue_wait_s"] is None  # never admitted
    assert shed["prompt_tokens"] == 0 and shed["generated"] == 0
    error = _request_args("err")
    assert error["reason"] == "error" and error["prompt_tokens"] == len(PROMPT_A)
    eos = _request_args("eos")
    assert eos["reason"] == "eos" and eos["generated"] == 1
    assert eos["cached_tokens"] == 0  # no head registered on that scheduler
    for reason in ("length", "cancelled", "shed", "error", "eos"):
        assert reason in FINISH_REASONS
        assert METRICS.get("finchat_requests_finished_total",
                           labels={"reason": reason}) - finished0[reason] == 1
    # admissions booked: len (35, 32 cached), can, err, eos (35) — not shed
    assert METRICS.get("finchat_prompt_tokens_total") - prompt0 == 35 + 40 + 40 + 35
    assert METRICS.get("finchat_prompt_tokens_cached_total") - cached0 == 32


# --- the slow round ----------------------------------------------------------

def test_stalled_event_loop_leaves_one_warning_naming_the_phase(caplog):
    """A task that blocks the event loop for 0.5 s while the scheduler has
    yielded: the round shows a long ``yield``, one WARNING names it, and
    the rate limit keeps a second stall quiet."""
    rounds0 = METRICS.get("finchat_rounds_total")
    yield0 = METRICS.get("finchat_round_phase_seconds_total", labels={"phase": "yield"})

    async def go():
        sched = _scheduler(mixed_step=False)
        await sched.start()
        try:
            h = await sched.submit("a", PROMPT_A, SamplingParams(
                temperature=0.0, max_new_tokens=60), trace_id="a")
            stalls = 0
            while True:
                event = await h.events.get()
                if event["type"] != "token":
                    return
                if h.generated in (20, 30):  # compiled and warm by then
                    if stalls == 0:
                        sched._slow_round_logged = float("-inf")  # forget compiles
                    stalls += 1
                    time.sleep(0.5)  # finchat-lint: disable=event-loop-blocking -- the injected stall
        finally:
            await sched.stop()

    with caplog.at_level(logging.WARNING, logger="finchat_tpu.engine.scheduler"):
        asyncio.run(go())
    slow = [r.getMessage() for r in caplog.records
            if "slow scheduler round" in r.getMessage() and "yield=0.5" in r.getMessage()]
    assert len(slow) == 1, [r.getMessage() for r in caplog.records]
    assert re.search(r"last dispatch decode, 1 decoding rows", slow[0])
    long_rounds = [ev for ev in _ring("round") if ev[5]["yield"] >= 0.5]
    assert len(long_rounds) == 2  # both stalls are in the ring
    assert all(ev[3] >= 0.5 and ev[5]["kind"] == "decode" for ev in long_rounds)
    assert METRICS.get("finchat_rounds_total") - rounds0 == len(_ring("round"))
    assert METRICS.get("finchat_round_phase_seconds_total",
                       labels={"phase": "yield"}) - yield0 >= 1.0


def test_admission_backoff_is_yield_not_scheduler_work():
    """The 50 ms sleep after an admission error is time the loop gave
    away: it reads as ``yield``, not as ``admit`` (which the host-time and
    idle metrics count as the scheduler at work)."""
    async def go():
        sched = _scheduler(mixed_step=False)
        admit, failed = sched._admit, []

        def failing_admit():
            if sched.decoding and not failed:
                failed.append(True)
                raise RuntimeError("injected admission fault")
            admit()

        sched._admit = failing_admit
        await sched.start()
        try:
            h = await sched.submit("a", PROMPT_A, SamplingParams(
                temperature=0.0, max_new_tokens=8), trace_id="a")
            await asyncio.wait_for(_drain(h), timeout=120)
        finally:
            await sched.stop()
        assert failed

    TRACER.configure(enabled=True)
    TRACER.clear()
    asyncio.run(go())
    backed_off = [ev[5] for ev in _ring("round") if ev[5]["yield"] >= 0.05]
    assert len(backed_off) == 1 and backed_off[0]["admit"] < 0.04, backed_off


def test_nested_phase_takes_its_time_out_of_the_outer_one():
    """On an injected clock: no sleep, and so nothing a loaded worker can
    stretch (this case asserted ``time.sleep`` to 8 ms before ISSUE 38)."""
    now = [100.0]
    acc = RoundPhases(clock=lambda: now[0])
    with TRACER.phase("stage", acc) as _:
        now[0] += 0.02
        with TRACER.phase("dispatch", acc):
            now[0] += 0.03
            acc.note(kind="decode", kv_tokens=7)  # no capture: a no-op
        now[0] += 0.01
    assert acc.open is None
    assert acc.seconds["dispatch"] == pytest.approx(0.03)
    assert acc.seconds["stage"] == pytest.approx(0.03)
    assert sum(acc.seconds.values()) == pytest.approx(now[0] - 100.0)
    acc.reset()
    assert set(acc.seconds) == set(ROUND_PHASES) and not any(acc.seconds.values())
    assert RoundPhases().clock is time.perf_counter  # the ring's clock otherwise


# --- the round in which an answer ends (ISSUE 53) -----------------------------

RETIRE_ARGS = ({"reason", "n", "decoding", "context_tokens", "offload_pages", "offload_bytes"}
               | {f"{part}_s" for part in RETIRE_PARTS})
RELEASE_HELD_S = 0.05  # what the slowed ``_release`` of these cases sleeps


def _retire_seconds():
    return {part: METRICS.get("finchat_retire_seconds_total", labels={"part": part})
            for part in RETIRE_PARTS}


def _phase_seconds(phase):
    return METRICS.get("finchat_round_phase_seconds_total", labels={"phase": phase})


def _run_to_its_end(reason, *, tier, traced=True):
    """One request alone on the split path, ended by ``reason``, with a
    ``_release`` that holds the loop RELEASE_HELD_S; returns the handle's
    generated count and the retire counters as they stood before it (the
    probe that finds an EOS id retires a row of its own)."""
    TRACER.configure(enabled=traced)
    TRACER.clear()
    options = dict(mixed_step=False, session_cache=tier)

    async def go():
        greedy = SamplingParams(temperature=0.0, max_new_tokens=6)
        eos_id = -1
        if reason == "eos":  # the third token of the greedy stream, if it is new there
            sched = _scheduler(**options)
            await sched.start()
            try:
                tokens, _end = await asyncio.wait_for(
                    _drain(await sched.submit("probe", PROMPT_A, greedy)), timeout=120)
            finally:
                await sched.stop()
            eos_id = next((t for i, t in enumerate(tokens) if i >= 2 and t not in tokens[:i]),
                          tokens[0])
            TRACER.clear()
        sched = _scheduler(eos_id=eos_id, **options)
        release = sched._release

        def held_release(handle):
            time.sleep(RELEASE_HELD_S)  # the injected hold
            release(handle)

        sched._release = held_release
        before = _retire_seconds(), _phase_seconds("retire")
        await sched.start()
        try:
            h = await sched.submit("a", PROMPT_A, greedy, trace_id="a",
                                   conversation_id="conv" if tier else None)
            _tokens, end = await asyncio.wait_for(_drain(h), timeout=120)
            assert end == {"type": "done", "reason": reason}
        finally:
            await sched.stop()
        return h.generated, *before

    return asyncio.run(go())


@pytest.mark.parametrize("tier", [False, True], ids=["no_session_tier", "session_tier"])
@pytest.mark.parametrize("reason", ["eos", "length"])
def test_a_row_that_ends_leaves_one_retire_event_with_its_parts(reason, tier):
    generated, before, retire0 = _run_to_its_end(reason, tier=tier)
    (ts, trace_id, _name, dur, track, args), = _ring("retire")
    assert trace_id == "a" and track == "engine" and set(args) == RETIRE_ARGS
    assert args["reason"] == reason and args["decoding"] == 0
    assert args["context_tokens"] == len(PROMPT_A) + generated
    parts = {part: args[f"{part}_s"] for part in RETIRE_PARTS}
    assert all(seconds >= 0.0 for seconds in parts.values()), parts
    # the parts are the span less what runs unclocked between them
    assert 0.0 <= dur - sum(parts.values()) < 0.25, (parts, dur)
    assert parts["release"] >= RELEASE_HELD_S and parts["finish"] > 0.0
    if tier:  # the row's whole pages: the prompt's five and none of the answer's
        pages = (len(PROMPT_A) + generated - 1) // 8
        assert args["offload_pages"] == pages and args["offload_bytes"] > 0
        assert args["offload_bytes"] % pages == 0
        assert parts["offload"] > 0.0 and parts["store"] > 0.0
    else:
        assert (args["offload_pages"], args["offload_bytes"]) == (0, 0)
        assert parts["offload"] == 0.0 and parts["store"] == 0.0
    # the round that spans it: the same tally, the retirement under ``retire``
    # and out of ``deliver``, the phases still summing to the round
    (round_dur, round_args), = [(ev[3], ev[5]) for ev in _ring("round")
                                if ev[0] <= ts and ts + dur <= ev[0] + ev[3]]
    assert round_args["n"] == args["n"]
    assert round_args["retire"] == pytest.approx(dur, abs=1e-9)
    assert round_args["deliver"] < RELEASE_HELD_S
    assert sum(round_args[p] for p in ROUND_PHASES) == pytest.approx(round_dur, rel=1e-3)
    assert [ev[5]["retire"] > 0.0 for ev in _ring("round")].count(True) == 1
    # on the request's own timeline, before the span it closes
    names = [ev["name"] for ev in TRACER.export("a")["traceEvents"]]
    assert names.count("retire") == 1 and "request" in names
    # the same seconds on the counters
    for part, seconds in _retire_seconds().items():
        assert seconds - before[part] == pytest.approx(parts[part], abs=1e-9)
    assert _phase_seconds("retire") - retire0 == pytest.approx(dur, abs=1e-9)


def test_the_retire_counters_move_with_the_tracer_off():
    _generated, before, retire0 = _run_to_its_end("length", tier=True, traced=False)
    assert TRACER.snapshot() == []
    moved = {part: seconds - before[part] for part, seconds in _retire_seconds().items()}
    assert moved["release"] >= RELEASE_HELD_S
    assert all(seconds > 0.0 for seconds in moved.values()), moved
    assert _phase_seconds("retire") - retire0 >= sum(moved.values())


@pytest.mark.parametrize("path", ["cancelled", "error"])
def test_an_evict_off_the_answers_end_opens_no_phase_and_leaves_no_retire(path):
    """A cancel (the submitting task's) and a failed prefill (the loop's own,
    before its first dispatch) evict through ``_evict`` itself: no ``retire``
    phase, event or counter."""
    before, retire0 = _retire_seconds(), _phase_seconds("retire")

    async def go():
        sched = _scheduler(mixed_step=False)
        await sched.start()
        try:
            if path == "cancelled":
                h = await sched.submit("gone", PROMPT_A, SamplingParams(
                    temperature=0.0, max_new_tokens=200), trace_id="gone")
                while h.generated < 3:
                    await asyncio.sleep(0.001)
                sched.cancel(h)
                await asyncio.sleep(0.05)  # the rounds that consume what was in flight
            else:
                faults.arm("scheduler.prefill", faults.for_seq("gone", RuntimeError("boom")))
                h = await sched.submit("gone", PROMPT_A, SamplingParams(
                    temperature=0.0, max_new_tokens=4), trace_id="gone")
                _tokens, end = await asyncio.wait_for(_drain(h), timeout=120)
                assert end["type"] == "error"
            assert h.finished
        finally:
            faults.disarm_all()
            await sched.stop()

    asyncio.run(go())
    assert not _ring("retire") and (_ring("round") or path == "error")
    assert all(ev[5]["retire"] == 0.0 for ev in _ring("round"))
    assert _retire_seconds() == before and _phase_seconds("retire") == retire0
    assert [ev[5]["reason"] for ev in _ring("request")] == [path]


# --- what a round lost outside a device step (ISSUE 38) ------------------------

@pytest.mark.parametrize("lost", ["compile", "freeze", "both", "neither"])
def test_a_round_carries_the_compile_and_the_freeze_it_spanned(lost, caplog, monkeypatch):
    """A program compiled while serving and a late heartbeat tick, fed to
    the tracer in mid-stream: the round that closes next carries their
    seconds, no other round does, and the WARNING names them."""
    from finchat_tpu.engine import scheduler as scheduler_module

    monkeypatch.setattr(scheduler_module, "SLOW_ROUND_FLOOR_S", 0.0)
    monkeypatch.setattr(scheduler_module, "SLOW_ROUND_MEDIANS", 0.0)
    fed = {}

    async def go():
        sched = _scheduler(mixed_step=False)
        await sched.start()
        try:
            h = await sched.submit("a", PROMPT_A, SamplingParams(
                temperature=0.0, max_new_tokens=40), trace_id="a")
            while True:
                event = await h.events.get()
                if event["type"] != "token":
                    return
                if h.generated == 20 and not fed:  # compiled and warm by then
                    fed["n"] = sched._dispatch_tally
                    monkeypatch.setattr(TRACER, "_serving", 1)
                    if lost in ("compile", "both"):
                        jax.monitoring.record_event_duration_secs(
                            "/jax/core/compile/jaxpr_trace_duration", 0.5, fun_name="unwarmed")
                        jax.monitoring.record_event_duration_secs(
                            "/jax/core/compile/backend_compile_duration", 0.25,
                            fun_name="jit(unwarmed)")
                    if lost in ("freeze", "both"):
                        TRACER.freeze(time.perf_counter() - 1.5, 1.5, 0.0)
                    monkeypatch.setattr(TRACER, "_serving", 0)
                    sched._slow_round_logged = float("-inf")  # the next round warns
        finally:
            await sched.stop()

    with caplog.at_level(logging.WARNING, logger="finchat_tpu.engine.scheduler"):
        asyncio.run(go())
    compile_s = 0.75 if lost in ("compile", "both") else 0.0
    frozen_s = 1.5 if lost in ("freeze", "both") else 0.0
    carrying = [ev[5] for ev in _ring("round") if "compile_s" in ev[5] or "frozen_s" in ev[5]]
    if lost == "neither":
        assert not carrying
    else:
        assert len(carrying) == 1 and carrying[0]["n"] >= fed["n"]
        assert carrying[0].get("compile_s", 0.0) == pytest.approx(compile_s)
        assert carrying[0].get("frozen_s", 0.0) == pytest.approx(frozen_s)
        assert ("compile_s" in carrying[0]) == bool(compile_s)  # only when non-zero
        assert ("frozen_s" in carrying[0]) == bool(frozen_s)
    said = [r.getMessage() for r in caplog.records if "slow scheduler round" in r.getMessage()]
    program = re.escape("jit(unwarmed)") if compile_s else "none"
    assert any(re.search(rf"compiling or loading {compile_s:.3f} s \(last program {program}\), "
                         rf"process frozen {frozen_s:.3f} s", message)
               for message in said[-1:]), said


def test_streams_are_the_same_tokens_with_the_heartbeat_running():
    """Tracing on with the tracer's thread alive and the stage ``serving``
    against tracing off and no thread: the same tokens on the ragged path."""
    options, _kind, arrival = BRANCHES["ragged"]
    TRACER.serving_started()
    try:
        assert [t.name for t in threading.enumerate()].count("finchat-heartbeat") == 1
        tokens_on, _riders, _tally = _run_branch(options, arrival, traced=True)
        assert _ring("round")
        # the first-time compiles of this run happened while "serving"
        assert any(ev[5]["stage"] == "serving" for ev in _ring("compile")) or not _ring("compile")
    finally:
        TRACER.serving_stopped()
    assert "finchat-heartbeat" not in [t.name for t in threading.enumerate()]
    tokens_off, _riders, _tally = _run_branch(options, arrival, traced=False)
    assert TRACER.snapshot() == []
    assert tokens_on == tokens_off and all(tokens_on)


# --- device scopes -----------------------------------------------------------

def _op_name_parts(compiled_text):
    parts = set()
    for op_name in re.findall(r'op_name="([^"]+)"', compiled_text):
        parts.update(op_name.split("/"))
    return parts


@pytest.mark.parametrize("preset, expected", [
    ("moe-tiny", {"embed", "norm", "attn_qkv", "attn_o", "moe_router",
                  "moe_experts", "head", "sample"}),
    ("tiny", {"embed", "norm", "attn_qkv", "attn_o", "mlp", "head", "sample"}),
])
def test_compiled_steps_carry_every_scope(preset, expected):
    eng = _engine(preset)
    B = eng.engine_cfg.max_seqs
    static = dict(config=eng.config, page_size=eng.page_size,
                  attn_backend=eng.attn_backend, qm_backend=eng.qm_backend)
    f32, i32 = jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.int32)
    decode = decode_step.lower(
        eng.params, eng.state, jnp.ones((B,), bool), f32, f32 + 1, i32,
        return_logits=False, **static).compile().as_text()
    # the jnp reference backend writes KV by scatter; the kernels' append
    # path (kv_append) is the chip's
    assert expected | {"paged_attention", "kv_scatter"} <= _op_name_parts(decode)
    T = eng.ragged_token_buckets()[0]
    ragged = ragged_mixed_step.lower(
        eng.params, eng.state, jnp.zeros((T,), jnp.int32), jnp.zeros((T,), jnp.int32),
        i32, i32, i32, jnp.zeros((B,), bool), jnp.zeros((B,), bool), i32,
        f32, f32 + 1, i32, spec_width=0, **static).compile().as_text()
    assert (expected | {"ragged_paged_attention", "kv_scatter_ragged"}
            <= _op_name_parts(ragged))
    assert expected <= DEVICE_SCOPES


def test_registries_hold_the_new_names():
    assert {"round", "startup", "retire"} <= TRACE_EVENTS
    assert ROUND_PHASES == ("admit", "stage", "dispatch", "fetch_wait", "deliver", "retire",
                            "yield")
    assert RETIRE_PARTS == ("offload", "store", "release", "finish")
    assert {"moe_router", "moe_experts", "paged_attention"} <= DEVICE_SCOPES
    assert {"eos", "length", "cancelled", "shed", "error", "drained"} <= FINISH_REASONS


# --- finchat-lint R5 ---------------------------------------------------------

_MINI_TRACING = """
    SPAN_MARKS = frozenset({"admitted"})
    TRACE_EVENTS = frozenset({"dispatch"})
    ANOMALY_KINDS = frozenset({"shed"})
    ROUND_PHASES = ("admit", "stage")
    DEVICE_SCOPES = frozenset({"moe_experts", "head"})
    FINISH_REASONS = frozenset({"eos", "error"})
    STARTUP_PHASES = ("warmup",)
"""


def _lint(tmp_path, files):
    for rel, src in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src))
    return [f.message for f in
            run_analysis(tmp_path, [tmp_path], rule_filter={"metrics-discipline"}).findings]


def test_r5_rejects_undeclared_scope_phase_reason_and_startup_literals(tmp_path):
    src = """
        import jax
        from finchat_tpu.utils.tracing import TRACER

        @jax.named_scope("head")                  # declared: fine
        def head(x):
            with jax.named_scope("moe_expert"):   # typo: flagged
                return x

        class Sched:
            def go(self, handle, acc):
                with TRACER.phase("stage", acc):  # declared: fine
                    pass
                with TRACER.phase("stagger", acc):    # flagged
                    pass
                handle.span.finish(reason="eos")      # declared: fine
                handle.span.finish(reason="gone")     # flagged
                self._close_span(handle, "error")     # declared: fine
                self._close_span(handle, "vanished")  # flagged
                TRACER.startup("warmup", 1.0)         # declared: fine
                with TRACER.startup_phase("warm_up"): # flagged
                    pass
    """
    messages = _lint(tmp_path, {"finchat_tpu/utils/tracing.py": _MINI_TRACING,
                                "finchat_tpu/sched.py": src})
    assert len(messages) == 5, messages
    for literal, registry in (("moe_expert", "DEVICE_SCOPES"), ("stagger", "ROUND_PHASES"),
                              ("gone", "FINISH_REASONS"), ("vanished", "FINISH_REASONS"),
                              ("warm_up", "STARTUP_PHASES")):
        assert any(f"`{literal}`" in m and registry in m for m in messages), (literal, messages)


def test_r5_takes_retire_as_a_phase_and_an_event_from_the_real_registries(tmp_path):
    from finchat_tpu.utils import tracing

    src = """
        from finchat_tpu.utils.tracing import TRACER

        class Sched:
            def go(self, handle, acc):
                with TRACER.phase("retire", acc):     # declared: fine
                    pass
                TRACER.event("retire", handle.trace_id, dur=0.1)  # declared: fine
                with TRACER.phase("retired", acc):    # flagged
                    pass
                TRACER.event("retires", handle.trace_id)  # flagged
    """
    messages = _lint(tmp_path, {"finchat_tpu/utils/tracing.py": Path(tracing.__file__).read_text(),
                                "finchat_tpu/sched.py": src})
    assert len(messages) == 2, messages
    assert any("`retired`" in m and "ROUND_PHASES" in m for m in messages), messages
    assert any("`retires`" in m for m in messages), messages


def test_r5_leaves_a_registry_the_tracing_module_lacks_unchecked(tmp_path):
    src = """
        import jax

        def f(x):
            with jax.named_scope("anything"):
                return x
    """
    old = 'SPAN_MARKS = frozenset({"a"})\nTRACE_EVENTS = frozenset()\nANOMALY_KINDS = frozenset()\n'
    assert _lint(tmp_path, {"finchat_tpu/utils/tracing.py": old,
                            "finchat_tpu/m.py": src}) == []


# --- start-up ----------------------------------------------------------------

@pytest.fixture
def startup_gauges_restored():
    """The gauges are the process's own, and the benchmark's start-up readers
    (tests/perfbench) read them in whichever test shares this worker."""
    phases = [{"phase": "warmup"}, {"phase": "heads"}]
    before = [METRICS.get("finchat_startup_seconds", labels=p) for p in phases]
    yield
    for p, seconds in zip(phases, before):
        METRICS.set_gauge("finchat_startup_seconds", seconds, labels=p)  # finchat-lint: disable=metrics-discipline -- putting back what the test found


def test_startup_phases_set_their_gauge_and_leave_an_event(startup_gauges_restored):
    labels = {"phase": "warmup"}
    before = METRICS.get("finchat_startup_seconds", labels=labels)
    TRACER.startup("warmup", 1.5)
    with TRACER.startup_phase("heads"):
        assert TRACER.stage == "heads"  # what a program compiled in here is booked under
        time.sleep(0.01)
    assert TRACER.stage == "idle"
    assert METRICS.get("finchat_startup_seconds", labels=labels) - before == pytest.approx(1.5)
    assert METRICS.get("finchat_startup_seconds", labels={"phase": "heads"}) >= 0.01
    events = _ring("startup")
    assert [ev[5]["phase"] for ev in events] == ["warmup", "heads"]
    assert events[0][3] == pytest.approx(1.5) and events[1][3] >= 0.01
    assert {"warmup", "heads"} <= set(STARTUP_PHASES)
    assert np.isfinite(events[0][0])
