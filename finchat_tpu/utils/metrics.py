"""Metrics registry.

The reference has no metrics (SURVEY §5.5); these counters ARE the product's
north-star surface (tok/s/chip, TTFT, queue depth, batch occupancy, KV-page
utilization), exported in Prometheus text format at ``/metrics``.

Session-KV-cache family (engine/session_cache.py, scheduler offload/resume):
``finchat_session_cache_hits_total`` / ``_misses_total`` (admission matches
for conversation-keyed submissions), ``finchat_session_cache_resident_bytes``
and ``finchat_session_cache_entries`` (gauges — host-RAM tier occupancy),
``finchat_session_cache_restored_tokens_total`` (prefill tokens skipped by
resume), ``finchat_session_cache_offloaded_pages_total``,
``finchat_session_cache_evictions_total`` (LRU under the byte budget),
``finchat_session_cache_truncations_total`` (divergent-history cuts), and
the ``finchat_session_restore_seconds`` histogram (H2D resume latency; the
D2H snapshot's seconds are ``finchat_retire_seconds_total{part="offload"}``).

Ragged/mixed-step family (engine ragged_mixed_step, scheduler ragged
path — ISSUE 10): ``finchat_mixed_dispatches_total`` (unified packed
dispatches — one per scheduler iteration on the ragged path),
``finchat_mixed_step_seconds`` (host-side dispatch+fetch time per ragged
round), ``finchat_coexist_iterations_total`` (scheduler iterations where
prefill work and in-flight decodes coexist) and
``finchat_coexist_dispatches_total`` (model dispatches BOOKED to those
iterations by the scheduler's own attribution — together the exact
dispatches-per-coexist-iteration figure tests/test_mixed_step.py holds;
the split path pays >= 2 per such iteration, the ragged path 1),
``finchat_mixed_demotions_total{reason=spec|constrained|ring|other}``
(coexist iterations demoted to the split path, per reason — every one is
pre-seeded at zero and stays there since the ragged rebuild),
``finchat_warmup_compiled_variants`` (serving-variant count of the last
engine warmup — the collapsed row×chunk×mode matrix), and
``finchat_inter_token_seconds`` — a histogram of per-sequence inter-token
gaps LABELED by ``prefill_concurrent`` ("yes" when the emitting iteration
also ran prefill work, "no" for steady decode), the instrument that makes
the ragged step's admission-stall win visible in Prometheus.

Resilience family (scheduler preemption/breaker/deadline plane, ISSUE 5 —
ROBUSTNESS.md): ``finchat_preemptions_total`` (recompute preemptions —
page-pressure victims plus breaker recovery; each keeps prompt+generated
on the handle and replays through admission), ``finchat_sheds_total``
(pending requests shed past their deadline with a structured retryable
error), ``finchat_overload_rejections_total`` (submits rejected at
``max_queue_depth``), ``finchat_dispatch_failures_total`` (whole-round
dispatch failures feeding the breaker streaks),
``finchat_engine_rebuilds_total`` (breaker trips that tore down and
rebuilt device state), ``finchat_breaker_state`` (gauge: 0 closed, 1 open/
rebuilding, 2 half-open awaiting the probe round), and the recovery-
latency histograms ``finchat_engine_rebuild_seconds`` (teardown→rebuilt)
and ``finchat_breaker_recovery_seconds`` (trip → first successful round).
``finchat_kafka_commits_total`` / ``finchat_kafka_dedupe_skips_total``
instrument the at-least-once option (kafka.commit_after_process).

Fleet family (serve/fleet.py — ISSUE 6): with ``fleet.replicas`` > 1 every
per-engine family above (inter-token, dispatches, breaker_state, session
cache, preemptions, ...) is emitted PER REPLICA via a ``replica`` label —
each replica's scheduler and session cache observe through a
``MetricsRegistry.labeled(replica="N")`` view, so one Prometheus scrape
separates a draining replica's recovery from its siblings' steady state.
Fleet-level series: ``finchat_fleet_replicas_live`` (gauge — LIVE replicas
the router spreads over), ``finchat_fleet_drained_streams_total``
(in-flight streams handed to a sibling by a breaker drain),
``finchat_fleet_drain_failures_total`` (streams the give-up drain could
not place on a sibling — each failed with a retryable ``replica_out``
error; counted once per stream), ``finchat_fleet_session_migrations_total`` /
``finchat_fleet_session_handoffs_total`` (cross-replica session-cache
entry moves: lazy route-time migration / drain-time handoff),
``finchat_fleet_session_import_refused_total`` (imported entry's shared
head had no live twin on the adopter — entry dropped, cold resume),
``finchat_fleet_respawns_total`` (supervisor revivals of a given-up
replica), and ``finchat_fleet_reroutes_total`` (messages routed away
from their affinity replica while it was out).

Durability family (ISSUE 7 — session disk tier, answered-message journal,
graceful drain; per replica like the per-engine families, since the disk
tier observes through its cache's labeled view):
``finchat_durability_spills_total`` / ``finchat_durability_spilled_bytes_
total`` (session records written through to disk) and
``finchat_durability_spill_failures_total``,
``finchat_durability_disk_resident_bytes`` / ``finchat_durability_disk_
entries`` (gauges — record-file tier occupancy),
``finchat_durability_disk_evictions_total`` (disk-tier LRU),
``finchat_durability_disk_restores_total`` + the
``finchat_durability_restore_seconds`` histogram (RAM-miss fall-through
loads), ``finchat_durability_quarantines_total`` (corrupt/truncated
records renamed aside — cold start, never a crash),
``finchat_durability_journal_appends_total`` / ``_journal_replayed_total``
/ ``_journal_append_failures_total`` (answered-id journal), and the
process-level ``finchat_durability_graceful_drains_total`` +
``finchat_durability_shutdown_drain_seconds`` histogram (SIGTERM drain).

Retrieval-plane family (embed/batcher.py microbatcher, embed/index.py
batched search, agent/scheduler overlap):
``finchat_embed_batch_occupancy`` (gauge — texts in the last coalesced
dispatch), ``finchat_embed_queue_depth`` (gauge — texts awaiting a
dispatch), ``finchat_embed_batch_dispatches_total`` /
``finchat_embed_requests_total`` / ``finchat_embed_texts_total``
(dispatches ÷ requests is the coalescing figure of merit; < 1 means the
wait-window is batching cross-request), ``finchat_embed_batch_retries_total``
(coalesced dispatch failed, per-request isolation retries),
``finchat_embed_failures_total``, ``finchat_embed_wait_seconds``
(histogram — queueing delay the window adds), and the per-stage retrieval
latency histograms ``finchat_retrieval_embed_seconds`` /
``finchat_retrieval_search_seconds`` / ``finchat_retrieval_graft_seconds``.
Overlap counters: ``finchat_partial_holds_total`` (static-prefix prefills
started), ``finchat_partial_grafts_total`` (extend_prompt grafted the
full prompt onto a hold), ``finchat_partial_fallbacks_total`` (graft
would have invalidated prefilled KV — serial fallback), and
``finchat_partial_stale_reaps_total`` (abandoned holds reclaimed).

Tracing family (utils/tracing.py — ISSUE 12):
``finchat_span_double_finish_total`` (RequestSpan.finish called again
after the first — idempotent by contract, the counter is the exposure
meter for the preempt-replay / drain-handoff overlap paths) and
``finchat_flight_dumps_total{reason=...}`` (anomaly flight-recorder
dumps written, per anomaly kind). Histograms additionally carry
EXEMPLARS: ``observe(..., trace_id=...)`` keeps the last trace id whose
value landed at/above the p99 bucket, rendered as an OpenMetrics-style
comment after the family and readable via ``exemplar()`` — a latency
spike links straight to ``GET /debug/trace/<trace_id>``.

Tool-streaming family (agent/streamparse.py — ISSUE 9; per engine/replica
via the agent's labeled view like every per-engine family):
``finchat_tool_launches_total`` (speculative + adopted tool executions
dispatched by the launcher), ``finchat_tool_speculative_cancels_total``
(in-flight launches cancelled because a later token committed an
argument that invalidated them, or adoption mismatched),
``finchat_tool_fallbacks_total`` (streaming disengaged for a turn —
parser anomaly, incremental/serial mismatch, or a failed speculative
execution retried on the serial path), and the
``finchat_tool_overlap_saved_seconds`` histogram (per adopted launch,
the slice of tool execution that ran under the remainder of the
decision decode — the latency a serial decide→execute turn pays on top).

Disaggregated-serving family (serve/disagg.py — ISSUE 17; per replica via
the scheduler's labeled view): ``finchat_disagg_role`` (gauge — 0 mixed,
1 prefill, 2 decode: the pool the replica serves in),
``finchat_disagg_handoffs_total`` (cold prompts prefilled on the prefill
pool and imported by a serving replica, counted on the importer),
``finchat_disagg_fallbacks_total{reason=no_prefill_replica|prefill_error|
import_refused|serving_pool_empty}`` (turns that fell back to mixed-style
local prefill, per reason — pre-seeded at zero), and the
``finchat_disagg_handoff_seconds`` histogram (prefill-pool submit →
imported on the serving replica, the full handoff detour).

Warm-fabric family (engine/warm_fabric.py — ISSUE 17; per replica, with
the shared disk tier itself observing its durability family under
``replica="fabric"``): ``finchat_fabric_hits_total`` /
``finchat_fabric_misses_total`` (head-snapshot and session-record lookups
against the cluster-wide fabric, counted on the requesting replica),
``finchat_fabric_import_refused_total`` (fabric hit whose KV snapshot
mode mismatched the engine — cold prefill instead), and the
``finchat_fabric_restore_seconds`` histogram (fabric record → device KV,
covering both shared-head restores and session resumes).

Pod family (serve/pod.py — ISSUE 20; host-level, emitted unlabeled on
the global registry — one host process is one reader):
``finchat_pod_hosts_live`` (gauge — this host plus LIVE peers),
``finchat_pod_heartbeats_total`` / ``finchat_pod_heartbeat_failures_
total`` (liaison pings), ``finchat_pod_peer_deaths_total`` /
``finchat_pod_peer_rejoins_total`` (failure-detector verdicts),
``finchat_pod_partition_adoptions_total`` (partitions inherited across
rebalances) + ``finchat_pod_adopted_ids_replayed_total`` (answered ids
replayed from inherited per-partition journals into the dedupe ring),
``finchat_pod_session_pulls_total`` / ``finchat_pod_pull_misses_total``
(cross-host session transfers; misses are peers that had nothing),
``finchat_pod_breaker_trips_total`` (per-peer liaison circuit breaker),
``finchat_pod_cold_starts_total{reason=breaker_open|peer_unreachable|
transfer_corrupt|import_refused}`` (pod-path failures that fell back to
a cold start — pre-seeded at zero; never a user error), and the
``finchat_pod_transfer_seconds`` histogram (pull request → record
imported).
"""

from __future__ import annotations

import threading
import time
from collections import defaultdict
from dataclasses import dataclass, field


def _labeled_key(name: str, labels: dict[str, str] | None) -> str:
    """Internal series key: ``name`` or ``name{k="v",...}`` (labels sorted)
    — one histogram per label combination, Prometheus-style."""
    if not labels:
        return name
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return f"{name}{{{inner}}}"


def _split_key(key: str) -> tuple[str, str]:
    """Inverse of _labeled_key: (base name, label string without braces)."""
    base, _, rest = key.partition("{")
    return base, rest[:-1] if rest else ""


@dataclass
class _Histogram:
    """Fixed-bucket histogram (seconds-scale by default).

    With a ``trace_id`` passed to ``observe``, the histogram keeps an
    EXEMPLAR — the last trace id whose value landed strictly above the
    p99 bucket (the first traced observation seeds it) — so a latency
    spike on a dashboard links straight to that request's exported
    timeline (``/debug/trace/<trace_id>``; ISSUE 12). Bucket-resolution
    "above p99" by design — the exact p99 is not known from bucket
    counts, and the exemplar only has to point at a representative slow
    request."""

    buckets: tuple[float, ...] = (
        0.005, 0.01, 0.025, 0.05, 0.1, 0.2, 0.3, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 100.0,
    )
    counts: list[int] = field(default_factory=list)
    total: float = 0.0
    n: int = 0
    # (trace_id, value, unix_ts) of the last above-p99 observation
    exemplar: tuple[str, float, float] | None = None

    def __post_init__(self) -> None:
        if not self.counts:
            self.counts = [0] * (len(self.buckets) + 1)

    def _bucket_index(self, value: float) -> int:
        for i, edge in enumerate(self.buckets):
            if value <= edge:
                return i
        return len(self.buckets)

    def _q_index(self, q: float) -> int:
        """Index of the bucket containing the q-quantile."""
        target = q * self.n
        seen = 0
        for i in range(len(self.counts)):
            seen += self.counts[i]
            if seen >= target:
                return i
        return len(self.counts) - 1

    def observe(self, value: float, trace_id: str | None = None) -> None:
        self.total += value
        self.n += 1
        idx = self._bucket_index(value)
        self.counts[idx] += 1
        if trace_id is not None:
            # strictly ABOVE the p99 bucket: when 99% of mass sits in one
            # bucket, observations inside it must not churn the exemplar
            # away from the genuine outlier. The first traced observation
            # seeds it so the family always links somewhere.
            if self.exemplar is None or idx > self._q_index(0.99):
                self.exemplar = (trace_id, value, time.time())

    def quantile(self, q: float) -> float:
        """Approximate quantile from bucket edges (upper bound of the bucket)."""
        if self.n == 0:
            return 0.0
        target = q * self.n
        seen = 0
        for i, edge in enumerate(self.buckets):
            seen += self.counts[i]
            if seen >= target:
                return edge
        return float("inf")


class MetricsRegistry:
    """Thread-safe counters / gauges / histograms with Prometheus rendering."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: dict[str, float] = defaultdict(float)
        self._gauges: dict[str, float] = {}
        self._histograms: dict[str, _Histogram] = {}

    def inc(self, name: str, value: float = 1.0,
            labels: dict[str, str] | None = None) -> None:
        with self._lock:
            self._counters[_labeled_key(name, labels)] += value

    def set_gauge(self, name: str, value: float,
                  labels: dict[str, str] | None = None) -> None:
        with self._lock:
            self._gauges[_labeled_key(name, labels)] = value

    def observe(self, name: str, value: float,
                labels: dict[str, str] | None = None,
                trace_id: str | None = None) -> None:
        key = _labeled_key(name, labels)
        with self._lock:
            if key not in self._histograms:
                self._histograms[key] = _Histogram()
            self._histograms[key].observe(value, trace_id=trace_id)

    def exemplar(self, name: str,
                 labels: dict[str, str] | None = None) -> tuple[str, float, float] | None:
        """The histogram's last above-p99 ``(trace_id, value, unix_ts)``
        exemplar, or None (ISSUE 12 — a metrics spike links to a
        timeline)."""
        with self._lock:
            hist = self._histograms.get(_labeled_key(name, labels))
            return hist.exemplar if hist else None

    def get(self, name: str, labels: dict[str, str] | None = None) -> float:
        key = _labeled_key(name, labels)
        with self._lock:
            if key in self._counters:
                return self._counters[key]
            return self._gauges.get(key, 0.0)

    def labeled(self, **labels: str) -> "LabeledMetrics":
        """A view of this registry that stamps ``labels`` onto every
        series it touches — how a fleet replica's scheduler and session
        cache emit the same metric families under a ``replica`` label
        without threading label dicts through every call site."""
        return LabeledMetrics(self, labels)

    def quantile(self, name: str, q: float,
                 labels: dict[str, str] | None = None) -> float:
        with self._lock:
            hist = self._histograms.get(_labeled_key(name, labels))
            return hist.quantile(q) if hist else 0.0

    def snapshot(self) -> dict[str, float]:
        with self._lock:
            snap = dict(self._counters)
            snap.update(self._gauges)
            for name, h in self._histograms.items():
                snap[f"{name}_count"] = h.n
                snap[f"{name}_sum"] = h.total
                if h.n:
                    snap[f"{name}_p50"] = h.quantile(0.50)
                    snap[f"{name}_p95"] = h.quantile(0.95)
            return snap

    def render_prometheus(self) -> str:
        lines: list[str] = []
        with self._lock:
            # label variants of one counter/gauge group under a single
            # TYPE line keyed by the BASE name (Prometheus text format
            # wants a metric's series consecutive) — same discipline as
            # the histogram rendering below
            for store, kind in ((self._counters, "counter"), (self._gauges, "gauge")):
                seen: set[str] = set()
                for key in sorted(store, key=_split_key):
                    base, _lbl = _split_key(key)
                    if base not in seen:
                        seen.add(base)
                        lines.append(f"# TYPE {base} {kind}")
                    lines.append(f"{key} {store[key]}")
            # group label variants of one histogram under a single TYPE
            # line (Prometheus text format wants a metric's series
            # consecutive); labeled bucket lines merge the series labels
            # with the le= edge
            seen_types: set[str] = set()
            for key in sorted(self._histograms, key=_split_key):
                base, lbl = _split_key(key)
                h = self._histograms[key]
                if base not in seen_types:
                    seen_types.add(base)
                    lines.append(f"# TYPE {base} histogram")

                def series(extra: str = "") -> str:
                    both = ",".join(x for x in (lbl, extra) if x)
                    return "{" + both + "}" if both else ""

                cumulative = 0
                for i, edge in enumerate(h.buckets):
                    cumulative += h.counts[i]
                    le = 'le="%s"' % edge
                    lines.append(f"{base}_bucket{series(le)} {cumulative}")
                cumulative += h.counts[-1]
                le_inf = 'le="+Inf"'
                lines.append(f"{base}_bucket{series(le_inf)} {cumulative}")
                lines.append(f"{base}_sum{series()} {h.total}")
                lines.append(f"{base}_count{series()} {h.n}")
                if h.exemplar is not None:
                    # OpenMetrics-style exemplar surfaced as a comment so
                    # plain Prometheus 0.0.4 parsers skip it while humans
                    # (and the verify drives) can jump from a spiked
                    # family to `/debug/trace/<trace_id>` (ISSUE 12).
                    # The trace id is CLIENT-CONTROLLED (Kafka message_id
                    # / x-trace-id header) — escape it so an embedded
                    # newline/quote can't terminate the comment and forge
                    # a metric line into the exposition
                    tid, val, ts = h.exemplar
                    safe = (tid.replace("\\", "\\\\").replace('"', '\\"')
                            .replace("\n", "\\n").replace("\r", "\\r"))
                    lines.append(
                        f'# exemplar {key} trace_id="{safe}" value={val} ts={ts}'
                    )
        return "\n".join(lines) + "\n"


class LabeledMetrics:
    """Registry view with a fixed label set merged into every call.

    Drop-in for ``METRICS`` at the call sites the scheduler and session
    cache use (``inc`` / ``set_gauge`` / ``observe`` / ``get`` /
    ``quantile`` and as a ``Timer`` target): a fleet replica constructs
    its scheduler with ``METRICS.labeled(replica="2")`` and every
    existing metric family comes out as ``name{replica="2"}`` series.
    Call-site labels merge OVER the fixed ones (call-site wins on a key
    collision, which never happens for ``replica``)."""

    def __init__(self, registry: MetricsRegistry, labels: dict[str, str]):
        self._registry = registry
        self.labels = {k: str(v) for k, v in labels.items()}

    def _merge(self, labels: dict[str, str] | None) -> dict[str, str]:
        return {**self.labels, **labels} if labels else self.labels

    def inc(self, name: str, value: float = 1.0,
            labels: dict[str, str] | None = None) -> None:
        self._registry.inc(name, value, labels=self._merge(labels))

    def set_gauge(self, name: str, value: float,
                  labels: dict[str, str] | None = None) -> None:
        self._registry.set_gauge(name, value, labels=self._merge(labels))

    def observe(self, name: str, value: float,
                labels: dict[str, str] | None = None,
                trace_id: str | None = None) -> None:
        self._registry.observe(name, value, labels=self._merge(labels),
                               trace_id=trace_id)

    def exemplar(self, name: str,
                 labels: dict[str, str] | None = None) -> tuple[str, float, float] | None:
        return self._registry.exemplar(name, labels=self._merge(labels))

    def get(self, name: str, labels: dict[str, str] | None = None) -> float:
        return self._registry.get(name, labels=self._merge(labels))

    def quantile(self, name: str, q: float,
                 labels: dict[str, str] | None = None) -> float:
        return self._registry.quantile(name, q, labels=self._merge(labels))


# Process-global registry (one worker process = one registry, matching the
# reference's one-logger-per-process pattern).
METRICS = MetricsRegistry()


class Timer:
    """Context manager: ``with Timer(METRICS, "prefill_seconds"): ...``"""

    def __init__(self, registry: MetricsRegistry, name: str) -> None:
        self._registry = registry
        self._name = name
        self.elapsed = 0.0

    def __enter__(self) -> "Timer":
        self.started = time.perf_counter()
        self._start = self.started
        return self

    def __exit__(self, *exc: object) -> None:
        self.elapsed = time.perf_counter() - self._start
        self._registry.observe(self._name, self.elapsed)
