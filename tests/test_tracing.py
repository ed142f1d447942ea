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
import contextlib
import dataclasses
import json
import threading
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
    COMPILE_CACHE,
    HEARTBEAT_INTERVAL_S,
    SPAN_MARKS,
    TRACE_EVENT_NAMES,
    TRACER,
    Heartbeat,
    RequestSpan,
    Tracer,
    listen_for_compiles,
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


# --- compile events (ISSUE 38) ----------------------------------------------

BACKEND = "/jax/core/compile/backend_compile_duration"


def _ring(name, tracer=TRACER):
    return [ev for ev in tracer.snapshot() if ev[2] == name]


def _compile_counters(stage):
    series = METRICS.snapshot()
    return {family: sum(v for k, v in series.items()
                        if k.startswith(family + "{") and f'stage="{stage}"' in k)
            for family in ("finchat_compiles_total", "finchat_compile_seconds_total",
                           "finchat_compile_trace_seconds_total")}


@contextlib.contextmanager
def _at_stage(stage):
    """Hold the process tracer at a stage without an App, a thread or a
    start-up gauge moved (``startup_phase`` itself: tests/test_round_tracing.py)."""
    attr, held = ("_serving", 1) if stage == "serving" else ("_startup_open", stage)
    if stage != "idle":
        setattr(TRACER, attr, held)
    try:
        yield
    finally:
        TRACER._serving, TRACER._startup_open = 0, None


@pytest.mark.parametrize("stage", ["warmup", "heads", "serving", "idle"])
def test_a_fresh_jitted_function_is_one_compile_event_at_its_stage(stage):
    def fresh_program(x):
        return x * 3 + 1

    fun = jax.jit(fresh_program)
    x = jnp.ones((5,), jnp.float32)
    jax.block_until_ready(x)
    before = _compile_counters(stage)
    serving_s0 = TRACER.serving_compile_s
    with _at_stage(stage):
        assert TRACER.stage == stage
        fun(x)
        fun(x)  # the second call finds the program: no event, no count
    mine = [ev for ev in _ring("compile") if ev[5]["fun_name"] == "jit(fresh_program)"]
    assert len(mine) == 1, _ring("compile")
    ts, _tid, _name, dur, track, args = mine[0]
    assert track == "compile" and dur > 0 and ts + dur <= time.perf_counter()
    assert args["stage"] == stage and args["cache"] in COMPILE_CACHE
    assert args["trace_s"] > 0 and args["lower_s"] > 0
    after = _compile_counters(stage)
    assert after["finchat_compiles_total"] - before["finchat_compiles_total"] == 1
    assert (after["finchat_compile_seconds_total"]
            - before["finchat_compile_seconds_total"]) == pytest.approx(dur)
    assert (after["finchat_compile_trace_seconds_total"]
            - before["finchat_compile_trace_seconds_total"]
            ) == pytest.approx(args["trace_s"] + args["lower_s"])
    # only a program compiled while serving is time a round may have lost
    carried = TRACER.serving_compile_s - serving_s0
    if stage == "serving":
        assert carried == pytest.approx(dur + args["trace_s"] + args["lower_s"])
        assert TRACER.last_compiled == "jit(fresh_program)"
    else:
        assert carried == 0.0
    assert TRACER.stage == "idle"


@pytest.mark.parametrize("heard, cache", [
    (["/jax/compilation_cache/cache_hits"], "hit"),
    (["/jax/compilation_cache/compile_requests_use_cache",
      "/jax/compilation_cache/cache_misses"], "miss"),
    (["/jax/compilation_cache/compile_requests_use_cache"], "off"),
    ([], "off"),
])
def test_cache_is_what_the_thread_heard_since_its_last_compile(heard, cache):
    """``jax.monitoring``'s own events, fed directly: the cache's hit or miss
    belongs to the backend span that closes after it ON THE SAME THREAD, and
    tracing and lowering heard before the span ride with it once."""
    labels = {"stage": "idle", "cache": cache}
    n0 = METRICS.get("finchat_compiles_total", labels=labels)
    s0 = METRICS.get("finchat_compile_seconds_total", labels=labels)

    def elsewhere():  # another thread's hit is not this thread's
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")

    other = threading.Thread(target=elsewhere)
    other.start()
    other.join()
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/jaxpr_trace_duration", 0.25, fun_name="inner")
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/jaxpr_trace_duration", 2.0, fun_name="fed_program")
    jax.monitoring.record_event_duration_secs(  # a lowering rule traces a helper of its own
        "/jax/core/compile/jaxpr_trace_duration", 0.001, fun_name="in_a_lowering_rule")
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/jaxpr_to_mlir_module_duration", 3.0, fun_name="jit_fed_program")
    for event in heard:
        jax.monitoring.record_event(event)
    jax.monitoring.record_event_duration_secs(BACKEND, 0.5, fun_name="jit(fed_program)")
    jax.monitoring.record_event_duration_secs(BACKEND, 0.125, fun_name="jit(next_program)")
    first, second = [ev for ev in _ring("compile") if "_program" in ev[5]["fun_name"]]
    assert first[3] == 0.5 and first[5] == {
        "fun_name": "jit(fed_program)", "cache": cache, "stage": "idle",
        "trace_s": 2.0, "lower_s": 3.0}  # the longest heard: the callee's is inside the caller's
    assert second[5] == {"fun_name": "jit(next_program)", "cache": "off", "stage": "idle",
                         "trace_s": 0.0, "lower_s": 0.0}
    assert METRICS.get("finchat_compiles_total", labels=labels) - n0 == 1 + (cache == "off")
    assert METRICS.get("finchat_compile_seconds_total", labels=labels) - s0 == (
        0.5 + 0.125 * (cache == "off"))


def test_compile_counters_are_booked_with_the_ring_off():
    TRACER.configure(enabled=False)
    n0 = METRICS.get("finchat_compiles_total", labels={"stage": "idle", "cache": "off"})
    jax.monitoring.record_event_duration_secs(BACKEND, 0.5, fun_name="jit(quiet)")
    assert METRICS.get("finchat_compiles_total",
                       labels={"stage": "idle", "cache": "off"}) - n0 == 1
    assert not _ring("compile")


def test_the_listeners_register_once_however_often_they_are_asked():
    assert [listen_for_compiles() for _ in range(3)] == [False] * 3  # the import did
    from jax._src import monitoring

    assert monitoring.get_event_duration_listeners().count(TRACER.on_jax_duration) == 1
    assert monitoring.get_event_listeners().count(TRACER.on_jax_event) == 1


# --- the heartbeat (ISSUE 38) -----------------------------------------------

class _ScriptedTime:
    """A clock, a CPU clock and a sleeper that no wall clock drives: every
    sleep advances the two clocks by the next scripted pair and the last one
    stops the heartbeat."""

    def __init__(self, ticks):
        self.now, self.cpu, self.ticks = 1000.0, 50.0, list(ticks)
        self.heartbeat = None

    def sleep(self, seconds):
        assert seconds == HEARTBEAT_INTERVAL_S
        slept, cpu = self.ticks.pop(0)
        self.now += slept
        self.cpu += cpu
        if not self.ticks:
            self.heartbeat._stopped.set()

    def run(self, tracer):
        self.heartbeat = Heartbeat(tracer, clock=lambda: self.now,
                                   cpu_clock=lambda: self.cpu, sleep=self.sleep)
        self.heartbeat.run()  # on this thread: the script ends it


END = (0.05, 0.0)  # the tick that stops the script is never booked


@pytest.mark.parametrize("ticks, expected", [
    # (slept, CPU seconds of the process meanwhile) a tick -> (due, dur, cpu, owner) a freeze
    ([(0.05, 0.0), (0.051, 0.05), (0.149, 0.1), END], []),            # on time: nothing
    ([(0.05, 0.0), (1.05, 0.0), END], [(1000.1, 1.0, 0.0, "machine")]),
    ([(1.05, 0.19), END], [(1000.05, 1.0, 0.19, "machine")]),         # under 0.2 x dur
    ([(1.05, 0.2), END], [(1000.05, 1.0, 0.2, "process")]),           # the rule's edge
    ([(1.05, 1.0), (0.05, 0.0), (0.35, 0.3), END],
     [(1000.05, 1.0, 1.0, "process"), (1001.15, 0.3, 0.3, "process")]),
])
def test_late_ticks_become_freeze_events_owned_by_the_cpu_rule(ticks, expected):
    tracer = Tracer()
    frozen0 = {owner: METRICS.get("finchat_process_frozen_seconds_total",
                                  labels={"owner": owner}) for owner in ("machine", "process")}
    _ScriptedTime(ticks).run(tracer)
    got = [(ev[0], ev[3], ev[5]["process_cpu_s"], ev[5]["owner"]) for ev in _ring("freeze", tracer)]
    assert [tuple(pytest.approx(x) if isinstance(x, float) else x for x in row)
            for row in expected] == got
    assert all(ev[4] == "host" for ev in _ring("freeze", tracer))
    assert tracer.frozen_s == pytest.approx(sum(row[1] for row in expected))
    for owner in frozen0:
        assert METRICS.get("finchat_process_frozen_seconds_total", labels={"owner": owner}
                           ) - frozen0[owner] == pytest.approx(
            sum(row[1] for row in expected if row[3] == owner))


def _heartbeat_threads():
    return [t for t in threading.enumerate() if t.name == "finchat-heartbeat"]


def test_one_heartbeat_a_process_from_the_first_start_to_the_last_stop():
    tracer = Tracer()
    assert tracer._heartbeat is None and tracer.stage == "idle"
    tracer.serving_started()
    thread = tracer._heartbeat._thread
    assert thread.is_alive() and thread.daemon and tracer.stage == "serving"
    tracer.serving_started()  # a second App of the process
    assert tracer._heartbeat._thread is thread and _heartbeat_threads() == [thread]
    tracer.serving_stopped()
    assert thread.is_alive() and tracer.stage == "serving"
    tracer.serving_stopped()  # joins: the sleeper waits on the stop flag
    assert not thread.is_alive() and tracer._heartbeat is None and tracer.stage == "idle"
    tracer.serving_stopped()  # a stop without a start changes nothing
    assert tracer._serving == 0 and not _heartbeat_threads()


async def test_no_heartbeat_thread_before_app_start_nor_after_stop():
    from finchat_tpu.io.kafka import InMemoryBroker, KafkaClient
    from finchat_tpu.io.store import InMemoryStore
    from finchat_tpu.serve.app import build_app
    from finchat_tpu.utils.config import load_config

    cfg = load_config(overrides={"model.preset": "stub"})
    app = build_app(cfg, store=InMemoryStore(),
                    kafka=KafkaClient(cfg.kafka, broker=InMemoryBroker()),
                    tool_generator=StubGenerator(default="No tool call"),
                    response_generator=StubGenerator(default="fine"))
    assert not _heartbeat_threads() and TRACER.stage == "idle"
    await app.start(serve_http=False)
    try:
        assert len(_heartbeat_threads()) == 1 and TRACER.stage == "serving"
    finally:
        await app.stop()
    assert not _heartbeat_threads() and TRACER.stage == "idle"
    await app.stop()  # stopping twice is not a second App's stop
    assert TRACER._serving == 0


def test_export_adds_the_compiles_and_freezes_a_request_overlapped():
    t = Tracer()
    t.event("ingress", "req", ts=10.0)
    t.event("request", "req", ts=10.0, dur=5.0, track="request")
    t.event("compile", ts=11.0, dur=1.0, track="compile", args={"fun_name": "jit(a)"})
    t.event("freeze", ts=14.5, dur=2.0, track="host", args={"owner": "machine"})
    t.event("compile", ts=3.0, dur=1.0, track="compile", args={"fun_name": "jit(before)"})
    t.event("freeze", ts=15.5, dur=1.0, track="host", args={"owner": "process"})
    t.event("ingress", "other", ts=12.0)
    got = [(e["name"], e["ts"]) for e in t.export("req")["traceEvents"]]
    assert got == [("ingress", 10.0e6), ("request", 10.0e6), ("compile", 11.0e6),
                   ("freeze", 14.5e6)]
    assert t.export("nobody")["traceEvents"] == []
