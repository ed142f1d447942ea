"""Request tracing + anomaly flight recorder (ISSUE 12).

The reference has none (SURVEY §5.1). Three planes here:

1. **Host spans** — :class:`RequestSpan`, per-request lifecycle marks
   (queue → admit → prefill → first token → done) recorded into the
   metrics registry, debug logs, AND — when the request carries a
   ``trace_id`` — the process trace ring below.
2. **Structured trace events** — :class:`Tracer`, a bounded per-process
   ring buffer of ``(ts, trace_id, name, dur, track, args)`` tuples.
   A ``trace_id`` is minted at ingress (Kafka ``message_id`` / HTTP
   ``x-trace-id`` header) and threaded app → agent → tool launcher →
   generator → scheduler; dispatch events additionally record which
   ``(slot, trace_id, mode)`` rows rode each ragged dispatch, so
   per-request device time is attributable even when many requests share
   one dispatch. Events stamp exclusively from host data the code already
   holds — appending to the ring is a deque append, ZERO host syncs are
   added on the hot path (finchat-lint R2 polices the seam).
   ``GET /debug/trace/<trace_id>`` exports one request's correlated
   timeline as Chrome trace-event JSON (opens in Perfetto).
3. **Flight recorder** — on anomaly (breaker trip, watchdog fire, shed,
   replica give-up, record quarantine, SIGTERM drain) the anomaly is
   recorded as its own event and the whole ring is dumped to a
   checksummed file under ``tracing.flight_dir`` — a black box for
   exactly the failure drills ROBUSTNESS.md scripts. Dumps are written
   off-loop (a worker thread) and rate-limited per anomaly kind so an
   anomaly storm cannot grind serving; ``flush_dumps`` joins the writers
   (the graceful drain calls it through ``asyncio.to_thread``).

Host and device share one clock only inside a ``jax.profiler`` capture
(ISSUE 24): each phase of a scheduler round (:data:`ROUND_PHASES`) is a
``jax.profiler.TraceAnnotation`` named ``finchat.<phase>`` — an event of
the capture's host plane, on the clock of its device lines — and every
part of a device step runs under a ``jax.named_scope`` from
:data:`DEVICE_SCOPES`, which the capture shows as the operation's scope
path. The same phases, summed per round on ``perf_counter``, go to the ring
as one ``round`` event and to ``finchat_round_phase_seconds_total``.

Time lost OUTSIDE a device step has two more events on the same ring and
clock (ISSUE 38): ``compile``, one per program XLA compiled or loaded
(``jax.monitoring``'s own events, heard by :func:`listen_for_compiles`), and
``freeze``, one per late tick of a thread that only sleeps
(:class:`Heartbeat`). A ``round`` event says how much of either it carried.
The round in which an answer ENDS has a phase of its own, ``retire``, and
each retired row leaves one ``retire`` event with the seconds of
:data:`RETIRE_PARTS` (ISSUE 53): what the other rows waited for.

Every ``mark()``/event/scope name MUST come from the registries below —
finchat-lint R5's span-discipline check enforces it statically, because a
typo'd name otherwise just silently vanishes from every timeline.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
import zlib
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Iterator

import jax

from finchat_tpu.utils.logging import get_logger
from finchat_tpu.utils.metrics import METRICS, MetricsRegistry

logger = get_logger(__name__)

# ---------------------------------------------------------------------------
# the span/event name registries (finchat-lint R5 span-discipline source
# of truth: every ``span.mark(...)`` / ``TRACER.event(...)`` /
# ``TRACER.anomaly(...)`` literal must appear here)
# ---------------------------------------------------------------------------

#: RequestSpan lifecycle marks (scheduler + agent planes).
SPAN_MARKS = frozenset({
    # scheduler lifecycle (engine/scheduler.py)
    "admitted", "prefill_done", "first_token", "done",
    # agent/tool plane (agent/graph.py, agent/streamparse.py — ISSUE 9
    # overlap made visible per request)
    "decide_start", "name_commit", "tool_launch", "tool_adopted",
    "response_prefill_hold",
})

#: Structured events that are not per-request span marks.
TRACE_EVENTS = frozenset({
    "ingress",          # request entered the serving plane (Kafka/HTTP)
    "dispatch",         # one model dispatch; args.rows = [[slot, tid, mode]]
    "preempt",          # recompute preemption (page pressure / breaker)
    "adopt",            # fleet sibling adopted a drained handle
    "drain_handoff",    # breaker drain handed a stream to a sibling
    "session_migrate",  # session-cache bytes moved between replicas
    "request",          # whole-request complete span (emitted at finish)
    # bounded-KV eviction wave (ISSUE 15): args carry the evicted page
    # count and the affected slots — page occupancy drops are attributable
    # on the timeline without any per-token cost
    "boundedkv_evict",
    # disaggregated serving (ISSUE 17): a prefill-pool replica ran a cold
    # prompt and handed its KV to the serving replica before admission —
    # args carry source/target replicas and the token count
    "disagg_handoff",
    # warm-state fabric hit (ISSUE 17): a shared-head or session restore
    # served from the cluster-wide fabric instead of a local prefill —
    # args.kind distinguishes "head" from "session"
    "fabric_hit",
    # pod plane (ISSUE 20): a survivor adopted a dead host's partitions —
    # args carry the dead host, the inherited partitions, and how many
    # journaled ids replayed into the dedupe ring
    "pod_adopt",
    # pod plane (ISSUE 20): a conversation's session bytes were pulled
    # from a liaison peer and imported warm — args carry peer and bytes
    "pod_session_pull",
    # one scheduler iteration that dispatched or consumed (ISSUE 24):
    # args carry the seconds spent in each of ROUND_PHASES, the last
    # dispatch's kind and the dispatch tally
    "round",
    # one phase of process start-up (STARTUP_PHASES), so an exported ring
    # or a flight dump begins at process start
    "startup",
    # one program compiled, or retrieved from the persistent cache and
    # loaded (ISSUE 38): args carry ``fun_name``, ``cache`` (COMPILE_CACHE),
    # ``stage`` (a STARTUP_PHASES phase, ``serving`` or ``idle``) and the
    # ``trace_s`` / ``lower_s`` Python spent on the program before it
    "compile",
    # the process could not run a thread that only sleeps (ISSUE 38): args
    # carry ``process_cpu_s`` over the gap and ``owner`` (FREEZE_OWNERS)
    "freeze",
    # one row whose answer ended (sampled EOS / its budget) was retired on
    # the scheduler's loop task (ISSUE 53): the round's ``retire`` phase as
    # a span of the request's own timeline; args carry ``reason``, the
    # dispatch tally ``n``, the rows left ``decoding``, ``context_tokens``
    # and the seconds of each of RETIRE_PARTS (``<part>_s``) with the
    # ``offload_pages`` / ``offload_bytes`` the blocking copy moved
    "retire",
})

#: The parts of one scheduler iteration, in the order the loop runs them.
#: ``admit`` preemption plan, admission, eviction wave; ``stage`` building
#: a dispatch's host arrays and row lists, and whatever else the loop does
#: outside the other phases; ``dispatch`` the call into the engine's jitted
#: step until it returns; ``fetch_wait`` awaiting the worker thread that
#: fetches tokens; ``deliver`` handing tokens to the streams; ``retire``
#: (inside ``deliver``, out of its time) finishing a row whose answer ended;
#: ``yield`` the loop given to every other task of the process.
ROUND_PHASES = ("admit", "stage", "dispatch", "fetch_wait", "deliver", "retire", "yield")

#: What retiring a row is made of, in the order it runs (a ``retire``
#: event's ``<part>_s``, ``finchat_retire_seconds_total{part}``): ``offload``
#: the blocking device→host copy of the row's own pages for the session
#: tier, ``store`` building the entry and putting it in the cache,
#: ``release`` pages, slot and prefix reference given back (one device
#: call), ``finish`` the request span's close and the ``done`` event.
RETIRE_PARTS = ("offload", "store", "release", "finish")

#: ``jax.named_scope`` names inside the jitted steps (finchat-lint R5
#: rejects a literal that is not here). A device operation's scope path in
#: a profile ends in the operation; the first of these names on the path
#: is the part of the step the operation belongs to.
DEVICE_SCOPES = frozenset({
    "embed", "norm", "attn_qkv", "attn_o", "mlp", "moe_router",
    "moe_experts", "head", "sample",
    # a model that routes sparsely (models/llama.py moe_mlp): sorting the
    # (token, pick) pairs by expert and bringing the results back; the shared
    # expert beside the routed ones
    "moe_group", "moe_shared",
    # the Mamba-2 mixer beside attention (models/ssm.py)
    "ssm_in", "ssm_conv", "ssm_scan", "ssm_norm", "ssm_out",
    # the gated delta rule of a linear-attention layer (models/gdn.py)
    "gdn_in", "gdn_conv", "gdn_gate", "gdn_scan", "gdn_norm", "gdn_out",
    # the attention callbacks (engine/engine.py)
    "kv_append", "kv_scatter", "paged_attention",
    "kv_scatter_ragged", "ragged_paged_attention",
    # latent attention (models/mla.py, ops/latent_attention.py): the low-rank
    # projections and the absorbed up-projections; the indexer's scores over
    # the context; the exact top-k of them (and the gather of the selected
    # rows); the softmax over the selected latent rows
    "mla_project", "dsa_indexer", "dsa_select", "mla_attention",
    # a layer_plan's kinds (models/sambay.py): the Mamba-1 mixer, the gated
    # memory unit, differential attention's subtraction and sub-norm; and the
    # attention callbacks by the pool they read (engine/engine.py
    # _attention_by_kind): the ONE full-attention cache, walked by the full
    # layer and every cross layer, and the sliding-window layers' own pool
    "m1_in", "m1_conv", "m1_scan", "m1_out", "gmu", "attn_diff",
    "yoco_attention", "swa_attention",
})

#: Why a request's span ended (``RequestSpan.finish(reason=...)``).
FINISH_REASONS = frozenset({
    "eos", "length",    # the answer ended: sampled EOS / max_new_tokens
    "cancelled",        # the client went away
    "shed",             # deadline passed before admission
    "error",            # a fault failed the stream
    "drained",          # graceful shutdown ended it
    "replica_out",      # the replica gave up and no sibling took it
})

#: Phases of process start-up (``finchat_startup_seconds{phase}``).
STARTUP_PHASES = ("artifacts", "engine_init", "warmup", "embed", "heads")

#: Where a ``compile`` event's program came from: the persistent cache held
#: it, it was compiled and written there, or no cache event came with it.
COMPILE_CACHE = ("hit", "miss", "off")

#: A ``compile`` event's ``stage`` outside STARTUP_PHASES: between
#: ``App.start`` and ``App.stop``, and any other time (a test, the
#: benchmark's reference model).
STAGE_SERVING, STAGE_IDLE = "serving", "idle"

#: Who held the process while a heartbeat tick was late: ``machine`` when
#: the process's own CPU clock stood still meanwhile (it was not scheduled:
#: the host's), ``process`` when some thread of ours ran (the interpreter
#: lock was held, the collector ran: the program's).
FREEZE_OWNERS = ("machine", "process")

#: Anomaly kinds — each records an event AND triggers a flight dump.
ANOMALY_KINDS = frozenset({
    "breaker_trip", "watchdog_timeout", "shed", "replica_give_up",
    "record_quarantine", "sigterm_drain",
    # pod plane (ISSUE 20): a liaison peer missed enough heartbeats to be
    # declared dead — the host failure domain tripped; partition adoption
    # follows
    "pod_host_lost",
})

TRACE_EVENT_NAMES = SPAN_MARKS | TRACE_EVENTS | ANOMALY_KINDS

#: Per-row modes a ``dispatch`` event's ``args.rows`` may carry (the third
#: element of each ``[slot, trace_id, mode]`` row) — declared so timeline
#: consumers and tests have one source of truth.
DISPATCH_ROW_MODES = frozenset({
    "prefill", "prefix", "decode", "spec", "constrained", "ring",
})

#: Serving quant-mode labels a ``dispatch`` event's ``args.quant`` may
#: carry (ISSUE 14): the engine's weight mode ("bf16" = unquantized
#: native dtype, "int8", "int4") with "+kv8" appended when the KV page
#: pool is int8 — ``InferenceEngine.quant_label`` must stay inside this
#: set (pinned by tests/test_quant_serving.py), so traced timelines can
#: always distinguish bf16 from quantized dispatches.
QUANT_MODES = frozenset({
    "bf16", "int8", "int4", "bf16+kv8", "int8+kv8", "int4+kv8",
})

# a round is slow when it takes longer than both of these; at most one
# WARNING per interval, so a wedged host cannot flood the log
SLOW_ROUND_FLOOR_S = 0.25
SLOW_ROUND_MEDIANS = 10.0
SLOW_ROUND_LOG_INTERVAL_S = 5.0

# the heartbeat sleeps this long at a time; a tick later than the second
# is a ``freeze``; below this share of the gap on the process's CPU clock
# nothing of ours ran in it
HEARTBEAT_INTERVAL_S = 0.05
HEARTBEAT_LATE_S = 0.1
FREEZE_MACHINE_CPU_SHARE = 0.2

# jax.monitoring's names (jax/_src/dispatch.py, compiler.py): the backend's
# span covers compiling OR retrieving and loading, and the cache's hit /
# miss event fires inside it on the compiling thread
_JAX_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_JAX_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_JAX_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_JAX_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                     "/jax/compilation_cache/cache_misses": "miss"}

_FLIGHT_MAGIC = "FINCHAT-FLIGHT v1"
# per-kind dump rate limit: an anomaly storm (e.g. a shed wave) records
# every EVENT but writes at most one black box per kind per window
_DUMP_MIN_INTERVAL_S = 5.0


def _chrome_event(ev: tuple) -> dict:
    """One ring tuple → one Chrome trace-event object (Perfetto-loadable:
    ``X`` complete events for spans with a duration, ``i`` instants
    otherwise; timestamps in µs on the perf_counter clock)."""
    ts, trace_id, name, dur, track, args = ev
    out: dict = {
        "name": name,
        "cat": "finchat",
        "ph": "X" if dur is not None else "i",
        "ts": round(ts * 1e6, 1),
        "pid": 0,
        "tid": str(track),
        "args": dict(args) if args else {},
    }
    if trace_id is not None:
        out["args"]["trace_id"] = trace_id
    if dur is not None:
        out["dur"] = round(dur * 1e6, 1)
    else:
        out["s"] = "t"  # instant scope: thread
    return out


def _event_carries(ev: tuple, trace_id: str) -> bool:
    """Does this ring tuple belong on ``trace_id``'s timeline? Either it
    is stamped with the id, or it is a dispatch event whose row list
    carries the id (many requests share one ragged dispatch — the PR 10
    coexist attribution made the rows host-known)."""
    if ev[1] == trace_id:
        return True
    args = ev[5]
    if args:
        rows = args.get("rows")
        if rows:
            return any(r[1] == trace_id for r in rows)
    return False


class RoundPhases:
    """The accumulator of one scheduler loop: seconds spent in each of
    ROUND_PHASES since ``reset()``, and the phase that is open now. Phases
    nest as a stack through ``open``, so one task alone opens them: the
    scheduler's loop task, from which every consume path is awaited.
    ``clock`` is ``perf_counter`` (the ring's clock) unless a test injects
    its own."""

    __slots__ = ("seconds", "open", "clock")

    def __init__(self, clock=time.perf_counter) -> None:
        self.clock = clock
        self.reset()

    def reset(self) -> None:
        self.seconds = dict.fromkeys(ROUND_PHASES, 0.0)
        self.open: _Phase | None = None

    def note(self, **numbers) -> None:
        """Attach numbers to the phase that is open now: a capture shows
        them as stats of that ``finchat.<phase>`` event, so what a dispatch
        carried is read on the clock of the kernels that computed it."""
        if self.open is not None:
            self.open._annotation.set_metadata(**numbers)


class _Phase:
    """One phase of a scheduler round: a ``finchat.<phase>`` annotation on
    the profiler's clock (near-free while no capture runs) around a
    duration on the accumulator's clock added to the round's. A phase
    opened inside another takes its time out of the outer one, so a
    round's phases never count a second twice. A plain class, not a
    generator: phases open a dozen times a round. ``started`` / ``ended``
    are its own ends on that clock."""

    __slots__ = ("_name", "_acc", "_outer", "_annotation", "_t0",
                 "started", "ended")

    def __init__(self, name: str, acc: RoundPhases):
        self._name = name
        self._acc = acc

    def __enter__(self) -> None:
        acc = self._acc
        self._outer = outer = acc.open
        acc.open = self
        # the annotation starts when it is constructed
        self._annotation = jax.profiler.TraceAnnotation("finchat." + self._name)
        self.started = self._t0 = now = acc.clock()
        if outer is not None:
            acc.seconds[outer._name] += now - outer._t0

    def __exit__(self, *exc) -> None:
        acc, outer = self._acc, self._outer
        self.ended = now = acc.clock()
        acc.seconds[self._name] += now - self._t0
        if outer is not None:
            outer._t0 = now
        acc.open = outer
        self._annotation.__exit__(*exc)


class _Heard(threading.local):
    """What one thread heard from ``jax.monitoring`` since its last backend
    span: a program's trace, lowering and cache events all come on the
    thread that compiles it, in that order."""
    trace_s = 0.0
    lower_s = 0.0
    cache = "off"


class Heartbeat:
    """A thread that only sleeps, HEARTBEAT_INTERVAL_S at a time. A tick more
    than HEARTBEAT_LATE_S late is a ``freeze`` event from when it was due to
    when it came: nothing else explains a sleeper that was not woken. The
    process's own CPU clock over the gap says whose it was (FREEZE_OWNERS).
    ``clock``, ``cpu_clock`` and ``sleep`` are injected by tests; ``sleep``
    defaults to waiting on the stop flag, so ``stop`` does not wait a tick
    out."""

    def __init__(self, tracer: "Tracer", *, clock=time.perf_counter,
                 cpu_clock=time.process_time, sleep=None):
        self._tracer = tracer
        self._clock, self._cpu_clock = clock, cpu_clock
        self._stopped = threading.Event()
        self._sleep = sleep if sleep is not None else self._stopped.wait
        self._thread: threading.Thread | None = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self.run, daemon=True,
                                        name="finchat-heartbeat")
        self._thread.start()

    def stop(self) -> None:
        self._stopped.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def run(self) -> None:
        clock, cpu_clock = self._clock, self._cpu_clock
        while not self._stopped.is_set():
            due, cpu = clock() + HEARTBEAT_INTERVAL_S, cpu_clock()
            self._sleep(HEARTBEAT_INTERVAL_S)
            late = clock() - due
            if late > HEARTBEAT_LATE_S and not self._stopped.is_set():
                self._tracer.freeze(due, late, cpu_clock() - cpu)


class Tracer:
    """Process-global bounded trace ring + flight recorder.

    Appends are a single ``deque.append`` of a pre-built tuple — safe from
    the event loop and worker threads alike (CPython deque appends are
    atomic), no locks on the hot path. Everything heavier (export, dumps)
    snapshots the ring first.
    """

    def __init__(self, ring_events: int = 65536):
        self.enabled = True
        self.flight_dir = ""
        self._ring: deque = deque(maxlen=max(16, ring_events))
        self._lock = threading.Lock()  # config + dump bookkeeping only
        self._dump_seq = 0
        self._last_dump: dict[str, float] = {}
        self._dump_threads: list[threading.Thread] = []
        # which part of the process's life a program compiles in
        self._startup_open: str | None = None
        self._serving = 0  # Apps started and not yet stopped
        self._heartbeat: Heartbeat | None = None
        self._heard = _Heard()
        # running totals; a scheduler books the difference since its last
        # round (plain attribute reads on the loop: no call, no lock)
        self.serving_compile_s = 0.0
        self.frozen_s = 0.0
        self.last_compiled = ""

    # --- configuration ---------------------------------------------------
    def configure(self, enabled: bool | None = None,
                  ring_events: int | None = None,
                  flight_dir: str | None = None) -> None:
        """Apply the ``tracing.*`` knobs (utils/config.py TracingConfig).
        Resizing the ring keeps the most recent events."""
        with self._lock:
            if enabled is not None:
                self.enabled = bool(enabled)
            if flight_dir is not None:
                self.flight_dir = flight_dir
            if ring_events is not None and ring_events != self._ring.maxlen:
                self._ring = deque(self._ring, maxlen=max(16, ring_events))

    def clear(self) -> None:
        self._ring.clear()

    # --- event recording -------------------------------------------------
    def event(self, name: str, trace_id: str | None = None, *,
              ts: float | None = None, dur: float | None = None,
              track: str = "main", args: dict | None = None) -> None:
        """Append one event to the ring. ``ts``/``dur`` are perf_counter
        seconds; ``dur`` set → a complete ("X") span, else an instant.
        ``name`` must come from the tracing registries (finchat-lint R5).
        No-op when tracing is disabled — callers on hot paths should
        additionally guard row-building with ``TRACER.enabled``."""
        if not self.enabled:
            return
        self._ring.append((
            ts if ts is not None else time.perf_counter(),
            trace_id, name, dur, track, args,
        ))

    @contextlib.contextmanager
    def span(self, name: str, trace_id: str | None = None, *,
             track: str = "main", args: dict | None = None) -> Iterator[None]:
        """Record a complete ("X") event spanning the with-block."""
        if not self.enabled:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.event(name, trace_id, ts=t0,
                       dur=time.perf_counter() - t0, track=track, args=args)

    @staticmethod
    def phase(name: str, acc: RoundPhases) -> _Phase:
        """``with TRACER.phase("stage", acc):`` — one of ROUND_PHASES,
        annotated on the profiler's clock and added to ``acc.seconds``.
        Always on: there is no switch between a round and its clock."""
        return _Phase(name, acc)

    def startup(self, phase: str, seconds: float) -> None:
        """Record one phase of process start-up (STARTUP_PHASES) that just
        ended: the ``finchat_startup_seconds{phase}`` gauge (summed where a
        fleet runs the phase once per replica) and a ``startup`` ring event."""
        labels = {"phase": phase}
        total = seconds + METRICS.get("finchat_startup_seconds", labels=labels)
        METRICS.set_gauge("finchat_startup_seconds", total, labels=labels)  # finchat-lint: disable=metrics-discipline -- a duration set once per phase, not a histogram: the unit is seconds and the name says so (ISSUE 24 names this series)
        self.event("startup", ts=time.perf_counter() - seconds, dur=seconds,
                   track="startup", args=labels)

    @contextlib.contextmanager
    def startup_phase(self, phase: str) -> Iterator[None]:
        """Time the with-block as ``phase``; a program compiled inside it
        has the phase as its ``compile`` event's ``stage``."""
        t0 = time.perf_counter()
        outer, self._startup_open = self._startup_open, phase
        try:
            yield
        finally:
            self._startup_open = outer
        self.startup(phase, time.perf_counter() - t0)

    # --- time lost outside a device step (ISSUE 38) ------------------------
    @property
    def stage(self) -> str:
        return self._startup_open or (STAGE_SERVING if self._serving else STAGE_IDLE)

    def serving_started(self) -> None:
        """An App started: programs compile at stage ``serving`` from here
        on, and the process's first App starts the heartbeat."""
        with self._lock:
            self._serving += 1
            if self._serving == 1:
                self._heartbeat = Heartbeat(self)
                self._heartbeat.start()

    def serving_stopped(self) -> None:
        """An App stopped; the last one joins the heartbeat."""
        with self._lock:
            self._serving = max(0, self._serving - 1)
            heartbeat = None
            if not self._serving:
                heartbeat, self._heartbeat = self._heartbeat, None
        if heartbeat is not None:
            heartbeat.stop()

    def freeze(self, due: float, late: float, process_cpu_s: float) -> None:
        """Book a heartbeat tick that came ``late`` seconds after ``due``."""
        owner = FREEZE_OWNERS[process_cpu_s >= FREEZE_MACHINE_CPU_SHARE * late]
        self.frozen_s += late
        METRICS.inc("finchat_process_frozen_seconds_total", late,
                    labels={"owner": owner})
        self.event("freeze", ts=due, dur=late, track="host",
                   args={"process_cpu_s": process_cpu_s, "owner": owner})

    def on_jax_event(self, event: str, **_kw) -> None:
        """``jax.monitoring``'s plain events: the persistent cache's hit or
        miss belongs to the backend span open on this thread."""
        cache = _JAX_CACHE_EVENTS.get(event)
        if cache is not None:
            self._heard.cache = cache

    def on_jax_duration(self, event: str, seconds: float, fun_name: str = "",
                        **_kw) -> None:
        """``jax.monitoring``'s durations. Tracing and lowering are kept for
        the backend span that follows them on this thread: the LONGEST of
        each heard since the last span, because a jitted callee (every
        ``jax.numpy`` call is one) is traced inside its caller, and a
        lowering rule may trace small functions of its own after the
        program's trace has ended. The backend span becomes the ``compile``
        event and the counters, which are booked whether or not the ring is
        enabled. Nothing else: no logging, no call into JAX."""
        heard = self._heard
        if event == _JAX_TRACE_EVENT:
            heard.trace_s = max(heard.trace_s, seconds)
        elif event == _JAX_LOWER_EVENT:
            heard.lower_s = max(heard.lower_s, seconds)
        elif event == _JAX_BACKEND_EVENT:
            stage, cache = self.stage, heard.cache
            trace_s, lower_s = heard.trace_s, heard.lower_s
            heard.cache, heard.trace_s, heard.lower_s = "off", 0.0, 0.0
            labels = {"stage": stage, "cache": cache}
            METRICS.inc("finchat_compiles_total", labels=labels)
            METRICS.inc("finchat_compile_seconds_total", seconds, labels=labels)
            METRICS.inc("finchat_compile_trace_seconds_total", trace_s + lower_s,
                        labels={"stage": stage})
            if stage == STAGE_SERVING:
                self.serving_compile_s += trace_s + lower_s + seconds
                self.last_compiled = fun_name
            self.event("compile", ts=time.perf_counter() - seconds, dur=seconds,
                       track="compile",
                       args={"fun_name": fun_name, "cache": cache, "stage": stage,
                             "trace_s": trace_s, "lower_s": lower_s})

    def anomaly(self, kind: str, trace_id: str | None = None,
                args: dict | None = None) -> None:
        """Record an anomaly event and dump the ring alongside it (the
        flight recorder). No-op with tracing disabled (an empty/stale ring
        is not a black box); the dump is rate-limited per kind and written
        off-loop."""
        if not self.enabled:
            return
        self.event(kind, trace_id, track="anomaly", args=args)
        self.flight_dump(kind, trace_id=trace_id, args=args)

    # --- export ----------------------------------------------------------
    def snapshot(self) -> list[tuple]:
        return list(self._ring)

    def export(self, trace_id: str) -> dict:
        """One request's correlated timeline as Chrome trace-event JSON
        (``{"traceEvents": [...]}`` — open in Perfetto / chrome://tracing):
        every event stamped with ``trace_id`` plus every dispatch whose
        row list carried it, plus the ``compile`` and ``freeze`` spans that
        overlap those: what the request waited for that was not its own."""
        ring = self.snapshot()
        own = [ev for ev in ring if _event_carries(ev, trace_id)]
        if own:
            first = min(ev[0] for ev in own)
            last = max(ev[0] + (ev[3] or 0.0) for ev in own)
            own += [ev for ev in ring if ev[2] in ("compile", "freeze")
                    and ev[0] < last and ev[0] + ev[3] > first]
            own.sort(key=lambda ev: ev[0])
        events = [_chrome_event(ev) for ev in own]
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"trace_id": trace_id},
        }

    # --- flight recorder -------------------------------------------------
    def flight_dump(self, reason: str, trace_id: str | None = None,
                    args: dict | None = None) -> str | None:
        """Dump the ring to a checksummed file under ``flight_dir``
        (pre-reserved filename returned immediately; the serialize+write
        runs in a worker thread so an anomaly on the scheduler loop never
        blocks serving — finchat-lint R1's seam). Returns the dump path,
        or None when the recorder is disabled or rate-limited."""
        with self._lock:
            if not self.flight_dir:
                return None
            now = time.monotonic()
            last = self._last_dump.get(reason)
            if last is not None and now - last < _DUMP_MIN_INTERVAL_S:
                return None
            self._last_dump[reason] = now
            self._dump_seq += 1
            seq = self._dump_seq
        events = self.snapshot()  # snapshot NOW; the writer thread races nothing
        path = os.path.join(
            self.flight_dir, f"flight-{seq:04d}-{reason}.json"
        )
        t = threading.Thread(
            target=self._write_dump, args=(path, reason, trace_id, args, events),
            daemon=True, name=f"flight-dump-{seq}",
        )
        with self._lock:
            self._dump_threads = [x for x in self._dump_threads if x.is_alive()]
            self._dump_threads.append(t)
        t.start()
        return path

    def _write_dump(self, path: str, reason: str, trace_id: str | None,
                    args: dict | None, events: list[tuple]) -> None:
        try:
            payload = json.dumps({
                "reason": reason,
                "trace_id": trace_id,
                "anomaly_args": args,
                "wall_time": time.time(),
                "trace": {
                    "traceEvents": [_chrome_event(ev) for ev in events],
                    "displayTimeUnit": "ms",
                },
            }, default=str).encode()
            header = f"{_FLIGHT_MAGIC} crc32={zlib.crc32(payload):08x} bytes={len(payload)}\n"
            Path(path).parent.mkdir(parents=True, exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(header.encode())
                f.write(payload)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            METRICS.inc("finchat_flight_dumps_total", labels={"reason": reason})
            logger.warning("flight recorder: %d events dumped to %s (%s)",
                           len(events), path, reason)
        except Exception as e:  # the black box is best-effort by contract
            logger.error("flight recorder: dump to %s failed: %s", path, e)

    def flush_dumps(self, timeout: float = 10.0) -> None:
        """Join in-flight dump writers (call via ``asyncio.to_thread`` from
        async code — the graceful drain does, so the black box lands on
        disk before the process exits)."""
        with self._lock:
            threads = list(self._dump_threads)
        deadline = time.monotonic() + timeout
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))
        with self._lock:
            self._dump_threads = [x for x in self._dump_threads if x.is_alive()]


def load_flight_dump(path: str) -> dict:
    """Parse + verify a flight-recorder file. Raises ``ValueError`` on a
    bad magic, truncation, or checksum mismatch — the black box must be
    trustworthy or loudly not."""
    raw = Path(path).read_bytes()
    nl = raw.find(b"\n")
    if nl < 0:
        raise ValueError(f"{path}: truncated flight dump (no header)")
    header = raw[:nl].decode("latin-1")
    if not header.startswith(_FLIGHT_MAGIC):
        raise ValueError(f"{path}: bad flight-dump magic {header[:32]!r}")
    fields = dict(
        kv.split("=", 1) for kv in header[len(_FLIGHT_MAGIC):].split() if "=" in kv
    )
    payload = raw[nl + 1:]
    if len(payload) != int(fields.get("bytes", -1)):
        raise ValueError(f"{path}: truncated flight dump "
                         f"({len(payload)} != {fields.get('bytes')} bytes)")
    if zlib.crc32(payload) != int(fields.get("crc32", "-1"), 16):
        raise ValueError(f"{path}: flight dump checksum mismatch")
    return json.loads(payload.decode())


# Process-global tracer (one worker process = one ring, matching METRICS).
TRACER = Tracer()

_listening = False


def listen_for_compiles() -> bool:
    """Register ``TRACER``'s two listeners with ``jax.monitoring``, once a
    process however often it is asked (JAX keeps no listener apart from
    another: a second registration would book every program twice). True
    when this call registered them."""
    global _listening
    if _listening:
        return False
    _listening = True
    jax.monitoring.register_event_listener(TRACER.on_jax_event)
    jax.monitoring.register_event_duration_secs_listener(TRACER.on_jax_duration)
    return True


listen_for_compiles()


@dataclass
class RequestSpan:
    """Lifecycle timestamps for one request through the serving stack.

    ``mark()`` names must come from :data:`SPAN_MARKS` (finchat-lint R5).
    With a ``trace_id``, every mark also lands in the process trace ring,
    and ``finish()`` additionally emits the whole-request "request" span,
    whose args say how the request ended (``reason``, one of
    :data:`FINISH_REASONS`), how large it was (``prompt_tokens``, of which
    ``cached_tokens`` were not prefilled because a shared head, a session
    entry or a partial hold supplied them; ``generated``) and how long it
    queued (``queue_wait_s``, creation → ``admitted``; None if never
    admitted). The scheduler sets the token fields at admission.
    ``finish()`` is IDEMPOTENT — it is invoked from many scheduler sites
    (shed, evict, drain, give-up, rebuild-failure) whose flows can
    overlap on the preempt-replay and drain-handoff paths; the first call
    wins, later calls are counted in ``finchat_span_double_finish_total``
    and change nothing.
    """

    request_id: str
    trace_id: str | None = None
    created_at: float = field(default_factory=time.perf_counter)
    marks: dict[str, float] = field(default_factory=dict)
    finished: bool = False
    prompt_tokens: int = 0
    cached_tokens: int = 0
    # of the cached tokens, those a mixer's recurrent state was restored
    # over from a snapshot (0 for a model without a mixer)
    state_restored_tokens: int = 0

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.marks[name] = now - self.created_at
        if self.trace_id is not None and TRACER.enabled:
            TRACER.event(name, self.trace_id, ts=now, track="request")

    def ttft(self) -> float | None:
        """Time to first token, if the request got that far."""
        return self.marks.get("first_token")

    def finish(self, registry: MetricsRegistry = METRICS, *, reason: str,
               generated: int = 0) -> None:
        if self.finished:
            # second finish (preempt-replay / drain-handoff overlap):
            # first call won — count it, change nothing
            registry.inc("finchat_span_double_finish_total")
            return
        self.finished = True
        # TTFT is observed at first-token time by the scheduler (so the
        # histogram is live mid-request); here only the total is recorded.
        self.mark("done")
        registry.observe("finchat_request_seconds", self.marks["done"],
                         trace_id=self.trace_id)
        registry.inc("finchat_requests_finished_total", labels={"reason": reason})
        if self.trace_id is not None and TRACER.enabled:
            TRACER.event("request", self.trace_id, ts=self.created_at,
                         dur=self.marks["done"], track="request",
                         args={"request_id": self.request_id, "reason": reason,
                               "prompt_tokens": self.prompt_tokens,
                               "cached_tokens": self.cached_tokens,
                               "state_restored_tokens": self.state_restored_tokens,
                               "generated": generated,
                               "queue_wait_s": self.marks.get("admitted")})
        logger.debug(
            "span %s: %s",
            self.request_id,
            " ".join(f"{k}={v * 1e3:.1f}ms" for k, v in sorted(self.marks.items(), key=lambda kv: kv[1])),
        )
