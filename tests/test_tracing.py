"""End-to-end request tracing + anomaly flight recorder (ISSUE 12).

Pins the tracing contract:

- TRACE RING: bounded, Chrome-trace-event export per trace id, dispatch
  events correlated by their ``rows`` lists (many requests share one
  ragged dispatch; each still gets its own timeline).
- SPAN IDEMPOTENCE: ``RequestSpan.finish()`` first-call-wins; later calls
  (the preempt-replay / drain-handoff overlap paths exercise them) are
  counted in ``finchat_span_double_finish_total`` and observe nothing.
- PROPAGATION: a trace id submitted through the REAL generator→scheduler
  path yields one timeline containing admitted, prefill dispatches,
  first token, and done — and tracing on vs off never changes the
  greedy streamed output (byte-identity, the satellite contract).
- AGENT MARKS: decide_start / name_commit / tool_launch / tool_adopted /
  response_prefill_hold land on the timeline; streamed output is
  byte-identical with tracing on vs off.
- FLIGHT RECORDER: an anomaly dumps a checksummed file whose events
  include the anomaly and the ring's dispatch spans; corruption is
  detected; per-kind dumps are rate-limited.
- EXEMPLARS: a histogram keeps the last above-p99 trace id and renders
  it after the family.
"""

import asyncio
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import pytest

from finchat_tpu.engine.engine import InferenceEngine
from finchat_tpu.engine.generator import EngineGenerator, StubGenerator
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
from finchat_tpu.models.llama import PRESETS, init_params
from finchat_tpu.models.tokenizer import get_tokenizer
from finchat_tpu.utils import faults
from finchat_tpu.utils.config import EngineConfig
from finchat_tpu.utils.metrics import METRICS, MetricsRegistry
from finchat_tpu.utils.tracing import (
    ANOMALY_KINDS,
    SPAN_MARKS,
    TRACE_EVENT_NAMES,
    TRACER,
    RequestSpan,
    Tracer,
    load_flight_dump,
)


@pytest.fixture(autouse=True)
def _tracer_reset():
    """Every test starts from an enabled, dump-less, empty ring and the
    process tracer is restored afterwards (it is global like METRICS)."""
    prev_enabled, prev_dir = TRACER.enabled, TRACER.flight_dir
    TRACER.configure(enabled=True, flight_dir="")
    TRACER.clear()
    TRACER._last_dump.clear()
    yield
    TRACER.flush_dumps()
    TRACER.configure(enabled=prev_enabled, flight_dir=prev_dir)
    TRACER.clear()
    faults.disarm_all()


def _events(export):
    return [e["name"] for e in export["traceEvents"]]


# --- ring + export --------------------------------------------------------

def test_ring_is_bounded():
    t = Tracer(ring_events=32)
    for i in range(100):
        t.event("ingress", f"t{i}")
    assert len(t.snapshot()) == 32
    # oldest aged out, newest retained
    assert t.export("t0")["traceEvents"] == []
    assert len(t.export("t99")["traceEvents"]) == 1


def test_export_is_chrome_trace_schema():
    TRACER.event("ingress", "req-1", args={"source": "kafka:user_message"})
    TRACER.event("dispatch", dur=0.002,
                 args={"kind": "ragged", "n": 7,
                       "rows": [[0, "req-1", "prefill"], [1, "other", "decode"]]})
    TRACER.event("first_token", "req-1", track="request")
    export = TRACER.export("req-1")
    # the dispatch correlates through its rows even though the event
    # itself is not stamped with the id (shared-dispatch attribution)
    assert _events(export) == ["ingress", "dispatch", "first_token"]
    assert export["displayTimeUnit"] == "ms"
    for ev in export["traceEvents"]:
        assert ev["ph"] in ("X", "i")
        assert isinstance(ev["ts"], float) and ev["ts"] > 0
        assert isinstance(ev["tid"], str) and "pid" in ev and "cat" in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
        else:
            assert ev["s"] == "t"
    # json-serializable end to end (what /debug/trace returns)
    json.dumps(export)
    # the sibling request sees the SAME dispatch on its own timeline
    assert "dispatch" in _events(TRACER.export("other"))


def test_disabled_tracer_records_nothing(tmp_path):
    TRACER.configure(enabled=False, flight_dir=str(tmp_path))
    TRACER.event("ingress", "t1")
    TRACER.anomaly("shed", "t1")
    assert TRACER.snapshot() == []
    TRACER.flush_dumps()
    assert list(tmp_path.iterdir()) == []


def test_registry_names_are_consistent():
    # the agent/scheduler marks the PR depends on are all declared — the
    # R5 span-discipline lint keys on these exact sets
    for name in ("admitted", "prefill_done", "first_token", "done",
                 "decide_start", "name_commit", "tool_launch",
                 "tool_adopted", "response_prefill_hold"):
        assert name in SPAN_MARKS
    for kind in ("breaker_trip", "watchdog_timeout", "shed",
                 "replica_give_up", "record_quarantine", "sigterm_drain"):
        assert kind in ANOMALY_KINDS
    assert "dispatch" in TRACE_EVENT_NAMES and "ingress" in TRACE_EVENT_NAMES


# --- span idempotence -----------------------------------------------------

def test_span_finish_first_call_wins():
    reg = MetricsRegistry()
    span = RequestSpan("seq-1", trace_id="t-span")
    span.mark("admitted")
    span.finish(reg, reason="eos")
    done = span.marks["done"]
    n0 = reg.snapshot()["finchat_request_seconds_count"]
    span.finish(reg, reason="eos")
    span.finish(reg, reason="eos")
    assert span.marks["done"] == done  # untouched by later calls
    assert reg.snapshot()["finchat_request_seconds_count"] == n0  # observed once
    assert reg.get("finchat_span_double_finish_total") == 2


# --- real-scheduler propagation + idempotence regressions -----------------

def _make_scheduler(**cfg_overrides):
    config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
    defaults = dict(
        max_seqs=2, page_size=8, num_pages=64, max_seq_len=128,
        prefill_chunk=16, session_cache=False,
    )
    defaults.update(cfg_overrides)
    params = init_params(config, jax.random.key(0))
    engine = InferenceEngine(config, params, EngineConfig(**defaults))
    return ContinuousBatchingScheduler(engine, eos_id=-1)


async def _drain(handle):
    tokens = []
    while True:
        event = await handle.events.get()
        if event["type"] == "token":
            tokens.append(event["token_id"])
        elif event["type"] == "done":
            return tokens, None
        else:
            return tokens, event


def _greedy(n):
    return SamplingParams(temperature=0.0, max_new_tokens=n)


def test_trace_threads_to_scheduler_and_is_output_invariant():
    """One traced request through the REAL scheduler: the exported
    timeline carries admitted → prefill dispatch(es) → first_token →
    done → request, the dispatch rows attribute the request's slot, and
    the greedy stream is byte-identical to the same run with tracing
    off (the tracing-never-changes-output satellite)."""

    def run(traced: bool):
        TRACER.configure(enabled=traced)
        TRACER.clear()

        async def go():
            sched = _make_scheduler()
            await sched.start()
            try:
                h = await sched.submit(
                    "s0", list(range(1, 14)), _greedy(8),
                    trace_id="req-42" if traced else None,
                )
                return await asyncio.wait_for(_drain(h), timeout=120)
            finally:
                await sched.stop()

        return asyncio.run(go())

    tokens_on, err_on = run(True)
    export = TRACER.export("req-42")
    names = _events(export)
    for expected in ("admitted", "prefill_done", "first_token", "done",
                     "request", "dispatch"):
        assert expected in names, (expected, names)
    # every dispatch event that carried the request names its row mode
    dispatches = [e for e in export["traceEvents"] if e["name"] == "dispatch"]
    modes = {r[2] for e in dispatches for r in e["args"]["rows"]
             if r[1] == "req-42"}
    assert "prefill" in modes and "decode" in modes, modes
    tokens_off, err_off = run(False)
    assert err_on is None and err_off is None
    assert tokens_on == tokens_off  # byte-identical on vs off


def test_double_finish_counted_on_preempt_and_drain_paths():
    """Regression for the ISSUE 12 satellite: finish() is reached from
    many scheduler sites; on the preempt-replay → shutdown-drain flow a
    stream's span can be finished again by a late cleanup (generator
    finalizer, drain-handoff source failing what the adopter already
    finished). First call wins; extras only count."""
    d0 = METRICS.get("finchat_span_double_finish_total")
    n_before = METRICS.snapshot().get("finchat_request_seconds_count", 0)

    async def go():
        sched = _make_scheduler()
        await sched.start()
        try:
            h = await sched.submit("s0", list(range(1, 14)), _greedy(32),
                                   trace_id="req-drain")
            while h.generated < 2:
                await asyncio.sleep(0.002)
            # preempt-replay: the handle goes back to pending mid-stream
            sched._preempt(h)
            assert h.preempted == 1 and not h.finished
        finally:
            # drain fails the pending replay with a retryable error —
            # the FIRST finish of this span
            await sched.shutdown_drain()
        assert h.finished and h.span.finished
        # late cleanups on the handoff/cancel paths re-finish: counted,
        # not double-observed
        sched._finish(h, "eos")
        h.span.finish(reason="eos")
        return h

    asyncio.run(go())
    assert METRICS.get("finchat_span_double_finish_total") - d0 == 2
    assert METRICS.snapshot()["finchat_request_seconds_count"] - n_before == 1


# --- agent marks + byte identity ------------------------------------------

class _PartialResponseGenerator(StubGenerator):
    """Stub response generator exposing the partial-prefill seam, so the
    name-commit hold (and its response_prefill_hold mark) is exercised
    without an engine."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.holds = []

    async def begin_partial(self, prefix_text, sampling,
                            conversation_id=None, deadline=None,
                            trace_id=None):
        hold = type("Hold", (), {"_partial_claimed": False})()
        self.holds.append(hold)
        return hold

    def release_partial(self, partial):
        pass

    async def stream(self, prompt, sampling, conversation_id=None,
                     deadline=None, trace_id=None, partial=None):
        if partial is not None:
            partial._partial_claimed = True
        async for piece in super().stream(prompt, sampling):
            yield piece


def test_agent_marks_and_streamed_output_identity():
    from finchat_tpu.agent.graph import LLMAgent

    tool_text = ('retrieve_transactions({"search_query": "coffee", '
                 '"num_transactions": 2})')

    async def retriever(args):
        await asyncio.sleep(0.005)
        return ["COFFEE $4", "COFFEE $6"]

    def run_turn(traced: bool):
        TRACER.configure(enabled=True)
        TRACER.clear()
        agent = LLMAgent(
            StubGenerator(default=tool_text, chunk_delay=0.005),
            _PartialResponseGenerator(default="Here is my advice."),
            retriever, "SYSTEM", "TOOL", today=lambda: "2026-08-04",
        )

        async def go():
            chunks = []
            async for update in agent.stream_with_status(
                "coffee spend?", "u1", "CTX", [],
                conversation_id="c1",
                trace_id="req-agent" if traced else None,
            ):
                chunks.append(update)
            return chunks

        return asyncio.run(go())

    traced_chunks = run_turn(True)
    names = _events(TRACER.export("req-agent"))
    for mark in ("decide_start", "name_commit", "tool_launch",
                 "tool_adopted", "response_prefill_hold"):
        assert mark in names, (mark, names)
    # name_commit precedes tool adoption on the timeline
    assert names.index("name_commit") < names.index("tool_adopted")
    untraced_chunks = run_turn(False)
    assert _events(TRACER.export("req-agent")) == []  # no id → no events
    # tracing never changes the streamed event protocol (byte identity)
    assert traced_chunks == untraced_chunks


# --- flight recorder ------------------------------------------------------

def test_flight_dump_checksummed_roundtrip(tmp_path):
    TRACER.configure(flight_dir=str(tmp_path))
    TRACER.event("dispatch", args={"kind": "decode", "n": 3,
                                   "rows": [[0, "req-9", "decode"]]})
    TRACER.anomaly("breaker_trip", args={"plane": "decode", "error": "wedged"})
    TRACER.flush_dumps()
    dumps = sorted(tmp_path.glob("flight-*.json"))
    assert len(dumps) == 1 and "breaker_trip" in dumps[0].name
    rec = load_flight_dump(str(dumps[0]))
    assert rec["reason"] == "breaker_trip"
    names = [e["name"] for e in rec["trace"]["traceEvents"]]
    assert names == ["dispatch", "breaker_trip"]
    assert rec["anomaly_args"]["plane"] == "decode"


def test_flight_dump_corruption_detected(tmp_path):
    TRACER.configure(flight_dir=str(tmp_path))
    TRACER.anomaly("shed")
    TRACER.flush_dumps()
    path = next(tmp_path.glob("flight-*.json"))
    raw = bytearray(path.read_bytes())
    raw[-3] ^= 0xFF  # flip a payload byte under the checksum
    path.write_bytes(bytes(raw))
    with pytest.raises(ValueError, match="checksum"):
        load_flight_dump(str(path))
    # truncation is detected too
    path.write_bytes(path.read_bytes()[:-10])
    with pytest.raises(ValueError, match="truncated"):
        load_flight_dump(str(path))


def test_flight_dump_rate_limited_per_kind(tmp_path):
    TRACER.configure(flight_dir=str(tmp_path))
    for _ in range(5):
        TRACER.anomaly("shed")  # a shed wave must not write 5 black boxes
    TRACER.anomaly("watchdog_timeout")  # distinct kind: its own dump
    TRACER.flush_dumps()
    names = [p.name for p in tmp_path.glob("flight-*.json")]
    assert len([n for n in names if "shed" in n]) == 1
    assert len([n for n in names if "watchdog_timeout" in n]) == 1
    # every shed EVENT still landed in the ring (only dumps are limited)
    assert sum(1 for ev in TRACER.snapshot() if ev[2] == "shed") == 5


def test_breaker_trip_dumps_flight_recorder(tmp_path):
    """The ROBUSTNESS breaker drill leaves a black box: the dump contains
    the trip anomaly AND the tripped streams' dispatch spans."""
    TRACER.configure(flight_dir=str(tmp_path))

    async def go():
        sched = _make_scheduler()
        await sched.start()
        try:
            h = await sched.submit("s0", list(range(1, 14)), _greedy(10),
                                   trace_id="req-trip")
            task = asyncio.create_task(_drain(h))
            while h.generated < 2:
                await asyncio.sleep(0.002)
            faults.arm("scheduler.decode",
                       faults.n_shot(sched.breaker_threshold,
                                     RuntimeError("chaos: wedged dispatch")))
            tokens, err = await asyncio.wait_for(task, timeout=120)
            assert err is None  # the stream survived the rebuild
        finally:
            await sched.stop()
            faults.disarm_all()

    asyncio.run(go())
    TRACER.flush_dumps()
    dumps = [p for p in tmp_path.glob("flight-*.json") if "breaker_trip" in p.name]
    assert len(dumps) == 1
    rec = load_flight_dump(str(dumps[0]))
    events = rec["trace"]["traceEvents"]
    assert any(e["name"] == "breaker_trip" for e in events)
    # dispatch spans that carried the tripped request are in the box
    assert any(
        e["name"] == "dispatch"
        and any(r[1] == "req-trip" for r in e["args"]["rows"])
        for e in events
    )
    # ... and the recovery preempt is on the request's own timeline
    assert any(e["name"] == "preempt" for e in events
               if e["args"].get("trace_id") == "req-trip")


# --- exemplars ------------------------------------------------------------

def test_histogram_exemplar_tracks_above_p99():
    reg = MetricsRegistry()
    for i in range(200):
        reg.observe("finchat_lat_seconds", 0.01, trace_id=f"fast-{i}")
    reg.observe("finchat_lat_seconds", 9.0, trace_id="slow-1")
    for i in range(50):
        reg.observe("finchat_lat_seconds", 0.01, trace_id=f"tail-{i}")
    tid, value, ts = reg.exemplar("finchat_lat_seconds")
    assert tid == "slow-1" and value == 9.0
    # rendered after the family as an OpenMetrics-style comment
    text = reg.render_prometheus()
    assert '# exemplar finchat_lat_seconds trace_id="slow-1"' in text


def test_exemplar_through_labeled_view():
    reg = MetricsRegistry()
    view = reg.labeled(replica="3")
    view.observe("finchat_lat_seconds", 4.0, trace_id="r3-slow")
    assert view.exemplar("finchat_lat_seconds")[0] == "r3-slow"
    assert reg.exemplar("finchat_lat_seconds", labels={"replica": "3"})[0] == "r3-slow"


# --- /debug/trace endpoint ------------------------------------------------

async def test_debug_trace_endpoint_prefix_route():
    from finchat_tpu.serve.http import HTTPServer, Request, Response

    TRACER.event("ingress", "req-h", args={"source": "http:/chat"})
    server = HTTPServer("127.0.0.1", 0)

    async def handler(request: Request) -> Response:
        trace_id = request.path.rsplit("/", 1)[-1]
        export = TRACER.export(trace_id)
        if not export["traceEvents"]:
            return Response.json({"detail": "unknown"}, status=404)
        return Response.json(export)

    server.route_prefix("GET", "/debug/trace/", handler)
    await server.start()
    try:
        async def get(path):
            reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
            writer.write(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
            await writer.drain()
            raw = await reader.read()
            writer.close()
            head, _, body = raw.partition(b"\r\n\r\n")
            return int(head.split()[1]), body

        status, body = await get("/debug/trace/req-h")
        assert status == 200
        assert json.loads(body)["traceEvents"][0]["name"] == "ingress"
        status, _ = await get("/debug/trace/nope")
        assert status == 404
    finally:
        await server.stop()
