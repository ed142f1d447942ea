"""Continuous-batching scheduler.

Replaces the reference's "one message at a time per worker" concurrency model
(``main.py:131-159``, SURVEY §2.3) with many sequences multiplexed onto one
model replica:

- Admission: pending sequences are admitted when a slot AND enough KV pages
  for prompt + max_new_tokens are available (no mid-flight OOM).
- Batched chunked prefill interleaved with decode: each loop iteration runs
  ONE prefill round — every prefilling sequence advances one chunk in a
  single [N, chunk] ``prefill_step`` (N padded to a power of two, so at
  most log2(max_seqs) compiled variants) — then one decode step for all
  active slots. A 64-session burst costs a handful of weight-reads instead
  of 64 serial ones, and long prompts cannot starve in-flight decodes
  (SURVEY §7.3 hard part 3).
- Pipelined decode (SURVEY §7.3 hard part 3, "low-latency token
  streaming"): decode step N+1 is dispatched to the device BEFORE step N's
  tokens are fetched, so the device never idles waiting for the host, and
  every device→host fetch runs in a worker thread so the asyncio loop
  (HTTP handlers, Kafka produces) never blocks on the chip. A sequence
  that hits EOS at step N wastes one speculative token at N+1; the host
  discards it. A grammar-constrained sequence needs its host-side pick
  written back before its next step, so it sits OUT the speculative step
  (inactive, trash-redirected) and rejoins the following one — advancing
  every other step while unconstrained streams keep full depth-2 cadence.
- Unified packed ragged step (``engine.mixed_step``, default on; ISSUE
  10): when prefill work and in-flight decodes coexist, the iteration
  runs ONE ``ragged_mixed_step`` dispatch over a PACKED token buffer
  (ops/ragged_paged_attention.py) — every prefilling row advances a
  chunk, every decoding row a token, grammar-constrained rows return
  their logits for the host pick and spec-eligible rows verify a
  (1+Kd)-token draft block, all with on-device sampling — instead of two
  or more serialized dispatches. The split path below serves an iteration
  with no decode beside its prompts, and stays the golden-identical
  fallback (greedy streams are byte-identical either way;
  tests/test_mixed_step.py pins it); demotions are counted per reason in
  ``finchat_mixed_demotions_total``, every reason at zero.
- Session KV cache (engine/session_cache.py): sequences submitted with a
  ``conversation_id`` snapshot their KV pages device→host when they retire
  normally (eos/length, before the pages are freed) and the conversation's
  next turn resumes from the longest matching page-whole token prefix —
  restored pages + prefill starting at the matched offset — instead of
  re-prefilling the whole history. Composes with the shared-prefix entries
  below: a cached head is referenced (refcounted), never copied.
- Per-sequence failure isolation (SURVEY §5.3): an errored sequence is
  evicted, its pages freed, an error event emitted on its stream, and the
  engine keeps serving the others. The process-level watchdog of the
  reference becomes per-sequence.
- Resilience plane (ISSUE 5; ROBUSTNESS.md): recompute preemption
  (``_preempt`` — free a victim's slot and KV pages but keep prompt +
  generated tokens on the handle; re-admission re-prefills and resumes
  with zero duplicate or dropped tokens), used for page pressure (the
  lowest-priority victim yields instead of the head-of-line stalling) and
  as the recovery primitive of the engine circuit breaker
  (``breaker_threshold`` consecutive failed dispatch rounds → all live
  sequences preempt to host, the device state is torn down and rebuilt
  with weights retained, a half-open probe round re-admits). Deadline
  admission: pending requests past their deadline are shed pre-admission
  with a structured retryable error, admission orders
  earliest-deadline-first with a starvation guard, and ``submit`` rejects
  above ``max_queue_depth`` (backpressure instead of an unbounded queue).
- Invariants (SURVEY §5.2): the page allocator's ownership checks run at
  every free; slot bookkeeping is single-task (the step loop) by design.
"""

from __future__ import annotations

import asyncio
import statistics
import time
from collections import deque
from dataclasses import dataclass, field

from typing import TYPE_CHECKING

import jax.numpy as jnp
import numpy as np

from finchat_tpu.engine.engine import InferenceEngine, commit_first_token

if TYPE_CHECKING:  # engine must not import the agent layer at runtime
    from finchat_tpu.agent.constrained import TokenConstraint
from finchat_tpu.engine.kv_cache import (
    PageAllocationError,
    PageAllocator,
    pages_needed,
)
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.utils.faults import inject
from finchat_tpu.utils.logging import get_logger
from finchat_tpu.utils.metrics import METRICS, Timer
from finchat_tpu.utils.tracing import (
    RETIRE_PARTS,
    SLOW_ROUND_FLOOR_S,
    SLOW_ROUND_LOG_INTERVAL_S,
    SLOW_ROUND_MEDIANS,
    TRACER,
    RequestSpan,
    RoundPhases,
)

logger = get_logger(__name__)


def _retire_account() -> dict:
    """What evicting one row took, as a ``retire`` event's args say it: the
    seconds of each of RETIRE_PARTS and what the blocking copy moved."""
    return {"offload_pages": 0, "offload_bytes": 0,
            **{f"{part}_s": 0.0 for part in RETIRE_PARTS}}


class OverloadedError(RuntimeError):
    """``submit`` rejected: the admission queue is at ``max_queue_depth``.
    Retryable by contract — the serving layer surfaces it as a structured
    retryable error chunk instead of an opaque failure."""

    code = "overloaded"
    retryable = True


@dataclass
class SequenceHandle:
    """Host-side record of one in-flight sequence; ``events`` receives
    ``{"type": "token", "token_id": int}``, then one terminal
    ``{"type": "done", "reason": ...}`` or ``{"type": "error", ...}``."""

    seq_id: str
    prompt_ids: list[int]
    sampling: SamplingParams
    constraint: TokenConstraint | None = None
    # session KV cache key: turns of the same conversation resume each
    # other's KV (engine/session_cache.py); None = no cross-turn caching
    conversation_id: str | None = None
    events: asyncio.Queue = field(default_factory=asyncio.Queue)
    slot: int = -1
    prefill_pos: int = 0  # prompt tokens already prefilled
    # full logical→physical page list assigned at admission (shared head
    # pages first, then owned pages) — retirement offload slices it
    page_list: list[int] = field(default_factory=list)
    # tokens covered by READ-ONLY referenced head pages (shared-prefix or
    # session-restored head); the slot's own writes start past this
    shared_len: int = 0
    # tokens whose KV was restored from a session-cache snapshot at
    # admission (0 = cold). Pages covering [shared_len, resumed_len) were
    # copied host→device and never rewritten, so retirement offload reuses
    # the previous entry's host bytes for them instead of a fresh D2H copy
    resumed_len: int = 0
    generated: int = 0
    # the scheduler currently driving this handle: set at submit and
    # REBOUND by a fleet drain adoption (serve/fleet.py) — cleanup paths
    # (generator cancel on disconnect/watchdog) hold a reference to the
    # ORIGINAL scheduler, and evicting there with the adopter's slot index
    # would corrupt the source's slot state; cancel() delegates to owner
    owner: object | None = None
    # prompt + delivered tokens — the prompt-lookup draft source when
    # speculative decoding is on (engine/spec.py); maintained by _deliver
    history: list[int] = field(default_factory=list)
    # incremental n-gram index over ``history`` (engine/spec.py NgramIndex),
    # created lazily by the spec decode path and kept in sync by _deliver —
    # proposing must be O(1) on the event loop, not a history rescan
    ngram_index: object | None = None
    # shared-prefix cache entry this sequence's page table references
    # (scheduler _PrefixEntry); refcounted so retirement can free safely
    prefix_entry: object | None = None
    # on the segmented seq-sharded prefill path (prefill_pos > 0 there
    # means "mid-ring", NOT "ride the chunked batch")
    ring_path: bool = False
    # retrieval/prefill overlap (submit_partial): ``prompt_ids`` is only
    # the prompt's STATIC PREFIX — prefill it, then PARK without
    # committing a first token until extend_prompt grafts the full
    # prompt (or the hold goes stale and is reaped)
    held: bool = False
    held_deadline: float = 0.0
    # the hold was extended into a full prompt: the remaining suffix MUST
    # keep the chunked prefill path (the seq-sharded ring paths assume
    # they owned the prompt from position 0 / their own segment schedule)
    grafted: bool = False
    # completion deadline on the scheduler's monotonic clock
    # (time.perf_counter); None = no deadline. Pending entries past it are
    # shed pre-admission; admission orders earliest-deadline-first; page
    # pressure preempts the latest-deadline victim for a strictly-earlier
    # candidate.
    deadline: float | None = None
    # bounded-KV serving (ISSUE 15; kv_cache.BoundedKVPolicy): tokens the
    # eviction policy dropped from this row's page list — whole pages
    # between the pinned sink and the surviving window; 0 = nothing
    # evicted. Host-deterministic metadata mirrored into the engine's
    # state.kv_gaps between dispatches (eviction waves update both sides
    # together, so every enqueued step sees a table and gap that agree).
    kv_gap: int = 0
    # kv_ctx value at this row's most recent eviction wave (0 = never
    # evicted): while kv_gap_pos exceeds the DELIVERED context, an
    # undelivered in-flight token was computed under an older gap — a
    # preempt taken inside that window recomputes it under the newer gap
    # (the page-pressure path never does: it drains in-flight first).
    kv_gap_pos: int = 0
    # host mirror of the slot's device context length AFTER every
    # DISPATCHED (not merely consumed) step — advanced at dispatch-build
    # time by each dispatch's deterministic context advance. This is the
    # eviction schedule's sole input: the wave runs between dispatches, so
    # kv_ctx at a wave is exactly the next dispatch's write position, and
    # the gap a token's dispatch sees becomes a PURE function of that
    # position — independent of pipeline depth or a preempt/replay boundary (the byte-identity contracts lean on
    # this; delivered-count-plus-inflight inference is phase-dependent).
    kv_ctx: int = 0
    # preempt-replay restore plane for bounded rows (ISSUE 15 satellite):
    # a host snapshot of the SURVIVING pages (sink + window, compacted,
    # page-whole) taken at preemption, so re-admission restores
    # byte-identical KV and re-prefills only the residual tail instead of
    # re-prefilling tokens the policy would immediately evict. None for
    # unbounded rows and rows that never evicted.
    bounded_snap: tuple | None = None
    bounded_snap_tokens: int = 0  # compacted tokens the snapshot covers
    # recompute preemptions survived (page pressure / breaker recovery) —
    # a preempted handle's prompt_ids become its full history and it
    # re-admits through the normal path
    preempted: int = 0
    # admission epoch: bumped by _preempt so a dispatch's membership
    # snapshot (captured as (slot, handle, epoch)) can tell a REPLAYED
    # incarnation from the one it was dispatched against — the same handle
    # can re-admit into the same slot while a stale step is still
    # unconsumed, and slot identity alone would double-deliver its token
    epoch: int = 0
    submitted_at: float = field(default_factory=time.perf_counter)
    first_token_at: float | None = None
    # host arrival time of the last delivered token — feeds the
    # finchat_inter_token_seconds histogram (labeled by whether the
    # emitting iteration also ran prefill work)
    last_token_at: float | None = None
    finished: bool = False
    # end-to-end trace id (utils/tracing.py — ISSUE 12): minted at ingress
    # (Kafka message_id / HTTP header) and threaded down through the agent
    # and generator; None = untraced (direct scheduler submissions)
    trace_id: str | None = None
    span: RequestSpan = None  # type: ignore[assignment]  # set in __post_init__

    def __post_init__(self) -> None:
        if self.span is None:
            self.span = RequestSpan(self.seq_id, trace_id=self.trace_id)
        if not self.history:
            self.history = list(self.prompt_ids)

    def _emit_first_token_metrics(self) -> None:
        if self.first_token_at is None:
            self.first_token_at = time.perf_counter()
            self.span.mark("first_token")
            METRICS.observe("finchat_ttft_seconds", self.first_token_at - self.submitted_at,
                            trace_id=self.trace_id)


@dataclass
class _InFlightStep:
    """A dispatched-but-unconsumed decode step (device arrays + the
    membership snapshot it was dispatched against; members carry the
    handle's admission epoch so a preempted-and-replayed incarnation
    never receives a stale token)."""

    tokens: object  # [max_seqs] int32, device
    logits: object | None  # [n_constrained, vocab] fp32 device slice, or None
    members: list[tuple[int, SequenceHandle, int]]
    constrained_slots: list[int]
    # int32 [2], device: the held experts the step's live rows touched and
    # those the step's form read, each summed over the layers (a model that
    # routes sparsely; else None)
    moe_experts: object | None = None


@dataclass
class _PrefixJob:
    """An in-progress chunked prefix registration (register_prefix_async):
    the head prefills one chunk per prefill round, riding the same batched
    ``prefill_step`` as admitted sequences, so decode steps interleave and
    a midnight refresh never stalls in-flight streams for the whole head
    (VERDICT r4 weak #6). Owns its pages and an engine slot until it
    completes (entry published) or fails (pages freed, future gets 0)."""

    ids: list[int]
    shared_len: int
    owner: str
    pages: list[int]
    slot: int
    future: asyncio.Future
    pos: int = 0


@dataclass
class _PrefixEntry:
    """One registered shared prompt head: its token ids, the pages holding
    its prefilled KV, and a live-reference count so retirement (e.g. the
    date inside the head rolled over) frees the pages only once no
    in-flight sequence's page table still points at them."""

    ids: list[int]
    pages: list[int]
    shared_len: int
    owner: str
    refs: int = 0
    retired: bool = False
    # a model with a mixer: the recurrent state after the head's last token
    # (engine.ssm_snapshot). The pages are referenced by every row that
    # starts from the head; the state is COPIED into the row's slot
    ssm_snap: tuple | None = None


class ContinuousBatchingScheduler:
    # spec-decode all-miss demotion thresholds (see __init__ comment):
    # demote after this many consecutive zero-accept verify steps...
    SPEC_MISS_DEMOTE = 4
    # ...and re-probe after this many pipelined steps
    SPEC_RETRY_EVERY = 16

    def __init__(self, engine: InferenceEngine, eos_id: int,
                 metrics=None, replica_id: str | None = None,
                 fabric=None):
        self.engine = engine
        self.eos_id = eos_id
        # fleet identity (serve/fleet.py): ``replica_id`` tags this
        # scheduler's fault-injection sites (so a chaos test can wedge ONE
        # replica) and ``metrics`` is a METRICS.labeled(replica=...) view
        # so every existing metric family comes out per-replica. Both
        # default to the single-engine behavior unchanged.
        self.replica_id = replica_id
        self.metrics = metrics if metrics is not None else METRICS
        cfg = engine.engine_cfg
        self.allocator = PageAllocator(cfg.num_pages)
        self.free_slots: list[int] = list(range(cfg.max_seqs))
        self.pending: deque[SequenceHandle] = deque()
        self.prefilling: deque[SequenceHandle] = deque()
        self.decoding: dict[int, SequenceHandle] = {}  # slot -> handle
        B = cfg.max_seqs
        self._temperature = np.zeros((B,), np.float32)
        self._top_p = np.ones((B,), np.float32)
        self._top_k = np.zeros((B,), np.int32)
        self._wakeup = asyncio.Event()
        self._task: asyncio.Task | None = None
        self._running = False
        self._rng = np.random.default_rng(0)  # host-side constrained sampling
        # speculative decoding (engine/spec.py): > 0 switches the decode
        # path to depth-1 verify steps with Kd host-proposed drafts —
        # drafting needs the previous token on the HOST, which depth-2
        # pipelining by construction has not fetched yet
        self.spec_k = cfg.spec_tokens
        # all-miss demotion: depth-1 spec steps trade away the depth-2
        # device/host overlap, so sustained non-repetitive traffic (every
        # proposal empty or rejected) would pay that tax forever. After
        # SPEC_MISS_DEMOTE consecutive zero-accept steps the loop reverts
        # to the pipelined path for SPEC_RETRY_EVERY steps, then re-probes
        # (prompt-lookup hit rate changes as the answer starts quoting
        # retrieved rows, so a one-way demotion would miss the recovery).
        self._spec_miss_streak = 0
        self._spec_cooldown = 0
        # unified packed ragged step (engine.mixed_step config): one
        # dispatch advances every prefilling row a chunk, every decoding
        # row a token and spec rows a verify block whenever both
        # populations exist — see _use_mixed / _ragged_round (ISSUE 10).
        self.mixed_enabled = bool(cfg.mixed_step)
        # demotion observability (ISSUE 10 satellite): every reason the
        # old padded mixed step demoted on is pre-seeded at zero, so the
        # erasure (every reason stuck at 0) is visible per replica
        for reason in self.MIXED_DEMOTION_REASONS:
            self.metrics.inc("finchat_mixed_demotions_total", 0.0,
                             labels={"reason": reason})
        # whether the CURRENT loop iteration ran (or will run) prefill
        # work — the finchat_inter_token_seconds label distinguishing the
        # admission-stall case from steady decode
        self._iter_ran_prefill = False
        # dispatch-seam tally attributed to coexist iterations: every
        # model dispatch this scheduler enqueues bumps _dispatch_tally,
        # and the span from one coexist iteration's start to the next
        # accounting point lands in finchat_coexist_dispatches_total — so
        # dispatches-per-coexist-iteration (tests/test_mixed_step.py
        # holds it at 1) is exact, not a racy window over global counters
        self._dispatch_tally = 0
        self._coexist_mark: int | None = None
        # the current loop iteration on the clock (ISSUE 24): seconds per
        # phase, the base phase it runs in, the dispatch tally when it
        # began, the kind of its last dispatch; the last 256 rounds'
        # lengths give the slow-round WARNING its median
        self._phases = RoundPhases()
        self._round_mark = 0
        self._round_kind = "drain"  # a round that only consumed
        self._base_phase = None
        self._recent_rounds: deque[float] = deque(maxlen=256)
        self._slow_round_logged = float("-inf")
        # the tracer's running totals as the last round saw them
        self._seen_compile_s = TRACER.serving_compile_s
        self._seen_frozen_s = TRACER.frozen_s
        # what evicting the last row took (ISSUE 53): seconds by part and
        # the pages and bytes its blocking copy moved, written where the
        # work happens (_maybe_offload, _evict) and read by _retire alone
        self._retired = _retire_account()
        # trace-event track label (utils/tracing.py — ISSUE 12): one
        # Perfetto track per engine so a fleet's dispatch timelines stay
        # separable in one export
        self._trace_track = (
            f"replica-{replica_id}" if replica_id is not None else "engine"
        )
        # bounded-KV long-context serving (ISSUE 15): the engine's
        # sink+window policy (None = unbounded legacy). The
        # finchat_boundedkv_* family pre-seeds per replica — gauges show
        # the configured shape, the counters render from zero so the
        # first eviction wave (and any recompute fallback) is visible.
        self.bounded_kv = getattr(engine, "bounded_kv", None)
        _bp = self.bounded_kv
        self.metrics.set_gauge("finchat_boundedkv_sink_pages",
                               _bp.sink_pages if _bp else 0)
        self.metrics.set_gauge("finchat_boundedkv_window_pages",
                               _bp.window_pages if _bp else 0)
        self.metrics.inc("finchat_boundedkv_evicted_pages_total", 0.0)
        self.metrics.inc("finchat_boundedkv_bounded_sessions_total", 0.0)
        self.metrics.inc("finchat_boundedkv_recompute_fallbacks_total", 0.0)
        # quantized serving plane (ISSUE 14): the engine's quant mode as
        # one label on every dispatch trace event (timelines distinguish
        # bf16/int8/int4 dispatches), plus the finchat_quant_* family —
        # mode gauges (bits per weight / per KV element) and pre-seeded
        # fallback/envelope counters so a mode flip or a refused
        # cross-mode restore is visible from zero
        self._quant_label = getattr(engine, "quant_label", "bf16")
        # a model with latent attention: the form its one-token rows take
        self._latent_form = getattr(engine, "latent_form", None)
        # ... and the form its decode step's indexer takes (None: no indexer selects)
        self._index_form = getattr(engine, "index_form", None)
        # a model with Mamba-2 layers: the tile its one-token state update works on
        self._state_form = getattr(engine, "state_form", None)
        # a model with latent attention: the form its walks take the batch's shared head in
        self._head_form = getattr(engine, "head_form", None)
        _wbits = {"": None, "int8": 8, "int4": 4}.get(
            getattr(engine, "quant", ""))
        _elem_bits = 8 * np.dtype(engine.config.dtype).itemsize
        self.metrics.set_gauge("finchat_quant_weight_bits",
                               _wbits if _wbits else _elem_bits)
        self.metrics.set_gauge(
            "finchat_quant_kv_bits",
            8 if getattr(engine, "kv_quant", "") else _elem_bits,
        )
        self.metrics.inc("finchat_quant_dequant_fallbacks_total", 0.0)
        self.metrics.inc("finchat_quant_envelope_exceeded_total", 0.0)
        # fused dequant-matmul plane (ops/quant_matmul.py): the resolved
        # backend as a gauge (0=ref, 1=pallas-interpret, 2=pallas) plus
        # pre-seeded dispatch/fallback counters — fused engagement (or a
        # stacked-weight fallback) is visible from zero per replica
        _qm = getattr(engine, "qm_backend", "ref")
        self.metrics.set_gauge(
            "finchat_quantmatmul_backend",
            {"ref": 0, "pallas-interpret": 1, "pallas": 2}.get(_qm, 0),
        )
        self.metrics.inc("finchat_quantmatmul_fused_dispatches_total", 0.0)
        self.metrics.inc("finchat_quantmatmul_fallbacks_total", 0.0)
        # whether this engine's compiled steps route quantized matmuls
        # through the fused kernel — one bool for the dispatch tally below
        self._qm_fused = bool(
            getattr(engine, "quant", "") and _qm != "ref"
        )
        # shared-prefix KV cache: matched at admission so identical prompt
        # heads (the constant system prompt every conversation shares) are
        # prefilled ONCE per process instead of per request — see
        # register_prefix / retire_prefixes
        self._prefixes: list[_PrefixEntry] = []
        self._n_prefixes_ever = 0  # unique allocator owner ids
        self._prefix_jobs: deque[_PrefixJob] = deque()
        # log the top_k clamp once per distinct requested value — a
        # misconfigured client retries per message, and per-request warnings
        # would flood the log under load (the clamp itself still applies and
        # is counted in finchat_top_k_clamped_total)
        self._top_k_clamp_warned: set[int] = set()
        # --- resilience plane (ISSUE 5) ---------------------------------
        # engine circuit breaker: consecutive whole-round dispatch failures
        # per plane ("prefill" / "decode" — mixed and spec ride the decode
        # bucket) before the breaker trips and the device state is rebuilt.
        # 0 disables the breaker (legacy: a whole-round failure evicts its
        # in-flight sequences with an error).
        self.breaker_threshold = max(0, cfg.breaker_threshold)
        self.breaker_max_rebuilds = max(1, cfg.breaker_max_rebuilds)
        self.preemption_enabled = bool(cfg.preemption)
        self.edf_starvation_s = max(0.0, cfg.edf_starvation_seconds)
        self.max_queue_depth = max(0, cfg.max_queue_depth)
        # retrieval/prefill overlap (ISSUE 3): how long a parked hold may
        # wait for its extend_prompt before the scheduler reclaims its
        # slot+pages — retrieval is ms-scale (and the tool-streaming
        # plane takes holds at most one decision decode early), so a hold
        # this old means its owner died. engine.partial_hold_ttl_seconds.
        self.hold_ttl_s = max(0.0, cfg.partial_hold_ttl_seconds)
        self._fail_streaks = {"prefill": 0, "decode": 0}
        self._rebuilds_without_success = 0
        self._breaker_tripped_at: float | None = None
        # which plane tripped the breaker: only a successful round of THAT
        # plane closes it (a decode-wedged engine keeps prefilling fine —
        # prefill successes must not mask the wedge or reset the
        # consecutive-rebuild give-up counter)
        self._breaker_bucket: str | None = None
        # callbacks run after an engine rebuild (the serving layer uses one
        # to re-register its shared prompt heads — the rebuild dropped them)
        self.on_rebuild: list = []
        # --- fleet hooks (serve/fleet.py; ISSUE 6) ----------------------
        # drain sink: when set, a breaker trip offers every live/pending
        # handle (preempted to host first — prompt+generated tokens on the
        # handle, device-free) plus its conversation's exported
        # session-cache bytes to the sink instead of riding out the
        # rebuild here; the sink returns True when a sibling replica
        # adopted the stream. Signature: (handle, session_payload) -> bool.
        self.drain_sink = None
        # callbacks fired when the breaker gives up (the supervisor marks
        # this replica OUT and schedules a respawn)
        self.on_give_up: list = []
        # breaker give-up state: True from give-up until revive() —
        # the fleet router stops routing here while set
        self.gave_up = False
        # True while a trip-path rebuild runs in its worker thread
        # (_trip_breaker): the rebuild replaces the page table under the
        # engine, so register_prefix_async must not write a row into the
        # doomed table mid-flight (the row would be lost and the head
        # would prefill against trash pages). Best-effort contract:
        # callers get 0 and the periodic refresh retries.
        self._rebuilding = False
        # breaker state gauge: 0 closed, 1 open (rebuilding), 2 half-open
        # (rebuilt, awaiting the first successful probe round)
        self.metrics.set_gauge("finchat_breaker_state", 0)
        # warm-state fabric (engine/warm_fabric.py — ISSUE 17): when set,
        # this replica's session tier is the fleet's SHARED disk tier,
        # shared prompt heads restore from / publish to the fabric instead
        # of re-prefilling per replica, and the cache keeps the fabric's
        # global holder index current. None = the per-replica PR 7 layout.
        self.fabric = fabric
        # a model with a mixer (models/ssm.py) keeps, beside its pages, a
        # recurrent state by slot. Whatever skips prefill by REFERENCING
        # pages is valid for it only at a position whose state was
        # snapshotted: shared heads keep one (_PrefixEntry.ssm_snap) and a
        # row admitted from a head starts from a copy; a partial match of a
        # head, a session resume and the warm fabric have no state to start
        # from, so those rows recompute from their tokens (counted) or the
        # option is refused here. Sliding-window layers' pages are such memory
        # too (a head keeps its trailing ones, kv_cache.WindowPager), with or
        # without a state beside them
        # (a stand-in engine has no window layers and no limit on a chunk)
        self._prefill_room = getattr(engine, "prefill_room", lambda pos: 1 << 30)
        self._window = getattr(engine.config, "window", 0)
        # a model that drafts with its next-token-prediction module: a decode
        # step yields one token or two a row (_consume_step), and the ONE hidden
        # state the module's next pair waits on is per-row memory that no page
        # holds — a head keeps it as it would a recurrent state
        self._drafts = bool(getattr(engine.config, "mtp_layers", 0))
        self.has_ssm = engine.config.has_state or bool(self._window) or self._drafts
        if self._window:
            self.metrics.inc("finchat_window_pages_freed_total", 0.0)
        self.metrics.set_gauge("finchat_ssm_state_bytes",
                               getattr(engine, "ssm_state_bytes", 0))
        if self.has_ssm:
            if fabric is not None:
                raise ValueError(
                    "fabric.path: the warm-state fabric's head and session records "
                    "hold pages only; a model with recurrent state or sliding-window "
                    "layers cannot resume from them (no recurrent state and no window "
                    "pages in the record)")
            for kind in ("head", "session"):
                self.metrics.inc("finchat_ssm_snapshots_total", 0.0,
                                 labels={"kind": kind})
            self.metrics.inc("finchat_ssm_snapshot_restores_total", 0.0)
            self.metrics.inc("finchat_ssm_recompute_fallbacks_total", 0.0)
            self.metrics.inc("finchat_ssm_step_fallbacks_total", 0.0)
        # a model that routes sparsely (models/llama.py moe_mlp): its decode
        # step counts, on the device, the held experts its live rows touched
        # and those whose weights its form read; booked where the step's
        # tokens are delivered
        self._round_moe_experts: tuple[int, int] | None = None
        if engine.config.moe_sparse:
            self.metrics.inc("finchat_moe_experts_touched_total", 0.0)
            self.metrics.inc("finchat_moe_experts_read_total", 0.0)
            self.metrics.inc("finchat_moe_layer_steps_total", 0.0)
        if self._drafts:  # drafts verified and kept, and rows x decode steps
            for name in ("finchat_draft_tokens_proposed_total",
                         "finchat_draft_tokens_accepted_total",
                         "finchat_decode_row_steps_total"):
                self.metrics.inc(name, 0.0)
        # a model with latent attention (models/mla.py): the step also counts
        # the context tokens its live rows attended to, over the layers (the
        # indexer's selection); the pool's two arrays hold other things than
        # K and V, so their bytes are said by array
        self._round_selected: int | None = None
        latent = bool(engine.config.kv_lora_rank)
        if latent:
            self.metrics.inc("finchat_dsa_selected_tokens_total", 0.0)
            self.metrics.inc("finchat_dsa_row_layer_steps_total", 0.0)
            self.metrics.inc("finchat_latent_attention_calls_total", 0.0,
                             labels={"form": self._latent_form})
            if self._index_form:
                self.metrics.inc("finchat_dsa_index_calls_total", 0.0,
                                 labels={"form": self._index_form})
            self.metrics.inc("finchat_latent_head_walks_total", 0.0,
                             labels={"form": self._head_form})
            if fabric is not None or getattr(cfg, "session_cache_disk_path", ""):
                raise ValueError(
                    "fabric.path / engine.session_cache_disk_path: the warm fabric's and the "
                    "session tier's disk records have not carried a latent model's pages "
                    "(a latent row and an index key a token); only the RAM session tier has")
        state = getattr(engine, "state", None)  # a test's stand-in engine has none
        for array, leaf in zip(("latent", "index_keys") if latent else ("k", "v"),
                               ("k_pages", "v_pages")):
            self.metrics.set_gauge("finchat_kv_pool_bytes",
                                   getattr(getattr(state, leaf, None), "nbytes", 0),
                                   labels={"array": array})
        # disaggregated serving (serve/disagg.py — ISSUE 17): the fleet
        # attaches its DisaggCoordinator to SERVING-pool schedulers only;
        # submit routes cold prompt prefills through it when set
        self.disagg = None
        # pod plane (serve/pod.py — ISSUE 20): the app attaches its
        # PodCoordinator; submit asks it to pull a conversation's session
        # bytes from a liaison peer when nothing local can resume it warm
        self.pod = None
        if fabric is not None:
            # fabric accounting is per calling replica (R5: pre-seeded so
            # the zero state is visible): hits/misses at head registration
            # and shared-tier session restore, refusals on cross-mode RAM
            # head snapshots (disk-record refusals count on the tier's own
            # replica="fabric" view)
            self.metrics.inc("finchat_fabric_hits_total", 0.0)
            self.metrics.inc("finchat_fabric_misses_total", 0.0)
            self.metrics.inc("finchat_fabric_import_refused_total", 0.0)
        # session KV cache (engine/session_cache.py): host-RAM tier keyed by
        # conversation_id; None = disabled. The on_drop hook is where entry
        # references on shared-prefix pages are released.
        self.session_cache = None
        # what the session tier would have resumed, a model with a mixer
        # recomputes: an entry ends on a page boundary behind the row's last
        # state, and a state cannot be rewound to it (_admit counts them)
        self._ssm_session_fallback = (
            self.has_ssm and cfg.session_cache and cfg.session_cache_bytes > 0)
        if self._ssm_session_fallback:
            logger.info("session cache off: its entries hold no recurrent state and no "
                        "window pages (%d and %d layers carry them); resumed turns recompute "
                        "from their tokens", engine.config.n_state_layers,
                        getattr(engine.config, "n_window_layers", 0))
        elif cfg.session_cache and cfg.session_cache_bytes > 0:
            from finchat_tpu.engine.session_cache import (
                SessionDiskTier,
                SessionKVCache,
            )

            # durability plane (ISSUE 7): disk spill tier under the RAM
            # LRU — entries write through to checksummed record files and
            # a RAM miss at admission falls back to disk, so a restarted
            # process resumes conversations warm. Fleet replicas get
            # sibling subdirectories (replica ids are stable across
            # restarts, and migration handles the cross-replica moves) —
            # unless the warm-state fabric is on, in which case every
            # replica shares the fabric's ONE tier (ISSUE 17) and any
            # replica restores any conversation.
            disk = None
            disk_path = getattr(cfg, "session_cache_disk_path", "")
            if fabric is not None:
                disk = fabric.tier
            elif disk_path:
                if replica_id is not None:
                    import os as _os

                    disk_path = _os.path.join(disk_path, f"replica-{replica_id}")
                try:
                    disk = SessionDiskTier(
                        disk_path, cfg.session_cache_disk_bytes,
                        metrics=self.metrics,
                        # records written under the other page-pool dtype
                        # are refused (counted), never scattered (ISSUE 14)
                        kv_quant=engine.kv_quant,
                    )
                except Exception as e:  # durability is best-effort
                    logger.error("session disk tier unavailable at %s: %s",
                                 disk_path, e)
            self.session_cache = SessionKVCache(
                cfg.session_cache_bytes, page_size=cfg.page_size,
                on_drop=self._session_drop, metrics=self.metrics, disk=disk,
                fabric=fabric, fabric_replica=replica_id,
            )

    # --- public API -----------------------------------------------------
    async def start(self) -> None:
        # rebind to the CURRENT loop: an Event pins itself to the loop that
        # first awaits it, so a stop/start cycle across asyncio.run calls
        # (tests, serving restarts) would otherwise raise "bound to a
        # different event loop"
        self._wakeup = asyncio.Event()
        self._running = True
        # warmup-matrix observability (ISSUE 10 satellite): re-emit the
        # engine's compiled-variant tally through this scheduler's metrics
        # view, so fleet replicas label it per replica like every other
        # per-engine family (0 until the engine has been warmed)
        self.metrics.set_gauge(
            "finchat_warmup_compiled_variants",
            getattr(self.engine, "compiled_variants", 0),
        )
        self._task = asyncio.create_task(self._loop())

    async def stop(self) -> None:
        self._running = False
        self._wakeup.set()
        if self._task:
            await self._task
        for job in list(self._prefix_jobs):  # shutdown mid-registration
            self._fail_prefix_job(job)

    async def submit(
        self,
        seq_id: str,
        prompt_ids: list[int],
        sampling: SamplingParams,
        constraint: TokenConstraint | None = None,
        conversation_id: str | None = None,
        deadline: float | None = None,
        trace_id: str | None = None,
    ) -> SequenceHandle:
        if not prompt_ids:
            raise ValueError("empty prompt")
        if self.max_queue_depth > 0 and len(self.pending) >= self.max_queue_depth:
            # backpressure: reject NEW load above the bound with a
            # retryable error instead of queueing unboundedly (preempted
            # sequences bypass submit — they are live streams, not load)
            self.metrics.inc("finchat_overload_rejections_total")
            raise OverloadedError(
                f"admission queue full ({len(self.pending)} >= "
                f"{self.max_queue_depth}); retry with backoff"
            )
        max_len = self.engine.max_pages_per_seq * self.engine.page_size
        if (len(prompt_ids) + sampling.max_new_tokens > max_len
                and self.bounded_kv is None):
            # bounded-KV serving lifts this bound: the eviction policy
            # caps page occupancy at sink+window regardless of context
            # length, which is the whole point (ISSUE 15)
            raise ValueError(
                f"sequence {seq_id}: prompt {len(prompt_ids)} + max_new "
                f"{sampling.max_new_tokens} exceeds max length {max_len}"
            )
        from finchat_tpu.engine.sampler import CANDIDATES

        if sampling.top_k > CANDIDATES:
            if sampling.top_k not in self._top_k_clamp_warned:
                self._top_k_clamp_warned.add(sampling.top_k)
                logger.warning(
                    "sequence %s: top_k=%d exceeds the sampler candidate cap %d; "
                    "clamping (logged once per distinct top_k — further requests "
                    "are clamped silently and counted in "
                    "finchat_top_k_clamped_total; see SamplingParams truncation "
                    "contract)",
                    seq_id, sampling.top_k, CANDIDATES,
                )
            self.metrics.inc("finchat_top_k_clamped_total")
            import dataclasses as _dc

            sampling = _dc.replace(sampling, top_k=CANDIDATES)
        if self.disagg is not None and conversation_id:
            # disaggregated serving (ISSUE 17): a cold prompt prefills on
            # the prefill pool and its KV arrives through the session
            # tier BEFORE admission, so the match below resumes from it.
            # Best-effort: any failure just leaves the local prefill path.
            try:
                await self.disagg.maybe_prefill(
                    self, prompt_ids, conversation_id, trace_id=trace_id
                )
            except Exception as e:
                logger.error("disagg handoff for %s failed: %s",
                             conversation_id, e)
                self.metrics.inc("finchat_disagg_fallbacks_total",
                                 labels={"reason": "prefill_error"})
        if self.pod is not None and conversation_id:
            # pod plane (ISSUE 20): a conversation inherited from another
            # host pulls its newest session record over the liaison BEFORE
            # admission, so the match below resumes from it warm. Every
            # failure inside is a counted cold start, never an error here.
            try:
                await self.pod.maybe_pull(self, conversation_id,
                                          trace_id=trace_id)
            except Exception as e:
                logger.error("pod session pull for %s failed: %s",
                             conversation_id, e)
        handle = SequenceHandle(
            seq_id=seq_id, prompt_ids=list(prompt_ids), sampling=sampling,
            constraint=constraint, conversation_id=conversation_id,
            deadline=deadline, owner=self, trace_id=trace_id,
        )
        self.pending.append(handle)
        self.metrics.set_gauge("finchat_queue_depth", len(self.pending))
        self._wakeup.set()
        return handle

    async def submit_partial(
        self,
        seq_id: str,
        prefix_ids: list[int],
        sampling: SamplingParams,
        conversation_id: str | None = None,
        deadline: float | None = None,
        trace_id: str | None = None,
    ) -> SequenceHandle | None:
        """Start prefilling a prompt whose TAIL is not known yet (the
        retrieval/prefill overlap path): ``prefix_ids`` is the static
        leading part of the final prompt (system head + context + history
        — everything upstream of the retrieval graft point). The sequence
        admits and prefills normally but PARKS when the prefix is done
        instead of committing a first token; ``extend_prompt`` grafts the
        full prompt in when retrieval returns and prefill continues from
        the parked position. Returns None when the prefix can't ride this
        path (empty, over budget, or seq-sharded-ring eligible — the ring
        prefill owns its prompt end-to-end); callers fall back to a plain
        ``submit`` of the full prompt.
        """
        if not prefix_ids:
            return None
        max_len = self.engine.max_pages_per_seq * self.engine.page_size
        if len(prefix_ids) + sampling.max_new_tokens > max_len:
            return None  # the full prompt could never fit either
        if self.engine._use_ring_prefill(len(prefix_ids)):
            return None
        handle = await self.submit(
            seq_id, prefix_ids, sampling, conversation_id=conversation_id,
            deadline=deadline, trace_id=trace_id,
        )
        # no await ran between submit() appending to pending and here (the
        # scheduler loop is a separate task), so the hold flags are set
        # before admission can see the handle
        handle.held = True
        handle.held_deadline = time.perf_counter() + self.hold_ttl_s
        self.metrics.inc("finchat_partial_holds_total")
        return handle

    def extend_prompt(self, handle: SequenceHandle, full_ids: list[int]) -> bool:
        """Graft the full prompt onto a parked/prefilling hold. Returns
        False — leaving the hold untouched, the caller cancels and falls
        back to a plain submit — when the graft would invalidate what was
        already prefilled (``full_ids`` does not extend the held prefix,
        e.g. history was windowed away after the hold was taken) or the
        extra KV pages can't be had."""
        if handle.finished or not handle.held:
            return False
        prefix = handle.prompt_ids
        if len(full_ids) <= len(prefix) or full_ids[: len(prefix)] != prefix:
            self.metrics.inc("finchat_partial_fallbacks_total")
            return False
        max_len = self.engine.max_pages_per_seq * self.engine.page_size
        if (len(full_ids) + handle.sampling.max_new_tokens > max_len
                and self.bounded_kv is None):
            self.metrics.inc("finchat_partial_fallbacks_total")
            return False
        if handle.slot >= 0:
            total = pages_needed(
                len(full_ids) + handle.sampling.max_new_tokens,
                self.engine.page_size,
            )
            if self.bounded_kv is not None:
                total = min(total, self.bounded_kv.budget_pages)
            extra = total - len(handle.page_list)
            if extra > 0:
                if total > self.engine.max_pages_per_seq or not self.allocator.can_allocate(extra):
                    self.metrics.inc("finchat_partial_fallbacks_total")
                    return False
                new_pages = self.allocator.allocate(handle.seq_id, extra)
                handle.page_list = handle.page_list + new_pages
                self.engine.set_page_table_rows({handle.slot: handle.page_list})
        handle.prompt_ids = list(full_ids)
        handle.history = list(full_ids)
        if handle.slot >= 0:
            # admitted already: what the hold prefilled is off the path
            self._book_prompt(handle, handle.prefill_pos)
        handle.held = False
        handle.grafted = True
        self.metrics.inc("finchat_partial_grafts_total")
        self._wakeup.set()
        return True

    def _tally_dispatch(self, kind: str) -> None:
        """Count one enqueued device program (the PR 10 coexist
        attribution); engines whose compiled steps route quantized matmuls
        through the fused kernel also book it on
        finchat_quantmatmul_fused_dispatches_total — every model dispatch
        in that configuration reads packed weights. ``kind`` names the
        round in its ``round`` event and slow-round WARNING."""
        self._dispatch_tally += 1
        self._round_kind = kind
        if self._qm_fused:
            self.metrics.inc("finchat_quantmatmul_fused_dispatches_total")

    def _trace_dispatch(self, kind: str, riders: list, *,
                        ts: float | None = None,
                        dur: float | None = None) -> None:
        """Record one model dispatch in the trace ring (ISSUE 12): which
        ``[slot, trace_id, mode]`` rows rode it, so a request's exported
        timeline shows every dispatch that carried its rows even when many
        requests share one ragged dispatch. ``riders`` are ``(slot,
        trace_id, mode, head, kv)`` per row; Σ kv — the context tokens the
        attention kernel reads, each row's context after the dispatch less
        what a bounded policy evicted — goes with ``kind`` onto the phase
        annotation open now (ISSUE 24), where a profiler capture shows it
        on the clock of the kernels that read them. Beside it
        ``kv_tokens_distinct``, the same tokens with the head of a prefix
        entry (``head``: the entry and the row's tokens on its read-only
        pages, None without one) counted once an entry — what a pass that
        reads a shared page once has to read — and ``prefix_rows``, the
        rows on the entry whose sharing saves most, 0 where no two rows
        share one: the decode kernel's shared-head pass (ops/
        paged_attention.py) finds the same set on the device, from the page
        tables. Host data only — they come from the membership/descriptor
        bookkeeping the round already built, so the event adds zero device
        syncs (finchat-lint R2). Callers guard with ``TRACER.enabled`` so
        the list is never built for nothing."""
        TRACER.event("dispatch", ts=ts, dur=dur, track=self._trace_track,
                     args={"kind": kind, "n": self._dispatch_tally,
                           "quant": self._quant_label,
                           "rows": [[slot, tid, mode]
                                    for slot, tid, mode, _head, _kv in riders]})
        heads: dict[int, list[int]] = {}  # entry: its rows' tokens on its pages
        for *_row, head, _kv in riders:
            if head is not None:
                heads.setdefault(head[0], []).append(head[1])
        kv_tokens = sum(kv for *_row, kv in riders)
        most = max(heads.values(), key=lambda t: (len(t) - 1) * max(t), default=[])
        self._phases.note(
            kind=kind, rows=len(riders), kv_tokens=kv_tokens,
            kv_tokens_distinct=kv_tokens - sum(sum(t) - max(t) for t in heads.values()),
            prefix_rows=len(most) if len(most) > 1 else 0,
            **({"form": self._latent_form} if self._latent_form else {}),
            **({"index_form": self._index_form} if self._index_form else {}),
            **({"head_form": self._head_form} if self._head_form else {}),
            **({"state_form": self._state_form} if self._state_form else {}),
            # a model with sliding-window layers: the tokens ONE window layer
            # reads (a row's context up to the window)
            **({"window_kv_tokens": sum(min(kv, self._window) for *_row, kv in riders)}
               if self._window else {}))

    @staticmethod
    def _rider(handle: SequenceHandle, mode: str, drafts: int = 0) -> tuple:
        """A sequence's row of a dispatch, read AFTER the dispatch advanced
        its ``kv_ctx``; a verify row also reads its own ``drafts``."""
        kv = handle.kv_ctx - handle.kv_gap
        head = None
        if handle.prefix_entry is not None and handle.shared_len:
            head = (id(handle.prefix_entry), min(handle.shared_len, kv))
        return (handle.slot, handle.trace_id or handle.seq_id, mode, head,
                kv + drafts)

    async def _fetch(self, fn):
        """Await ``fn()`` — the device→host copies of a dispatch's results —
        in a worker thread, so the event loop keeps serving (never
        ``block_until_ready`` on the consume path: the finchat-lint R2
        seam). The wait is the round's ``fetch_wait`` phase."""
        with TRACER.phase("fetch_wait", self._phases):
            return await asyncio.to_thread(fn)

    def _book_prompt(self, handle: SequenceHandle, cached: int) -> None:
        """The prompt's size and how much of it needs no prefill, on the
        request's span and the ``finchat_prompt_tokens*`` counters; a later
        call (a hold's graft) books the difference."""
        span = handle.span
        n = len(handle.prompt_ids)
        self.metrics.inc("finchat_prompt_tokens_total", n - span.prompt_tokens)
        self.metrics.inc("finchat_prompt_tokens_cached_total",
                         cached - span.cached_tokens)
        span.prompt_tokens, span.cached_tokens = n, cached

    def _close_span(self, handle: SequenceHandle, reason: str) -> None:
        handle.span.finish(reason=reason, generated=handle.generated)

    def _ring_routed(self, handle: SequenceHandle) -> bool:
        """Does this prefilling handle take the seq-sharded ring path this
        round (prefill_ring / prefill_ring_segment) rather than the chunked
        batch? The ONE routing predicate shared by _prefill_round and the
        mixed-step eligibility check, so they cannot drift. (A grafted hold
        stays chunked even if the full prompt is ring-length: both ring
        paths assume they scheduled the prompt from position 0.)

        Bounded-KV rows (ISSUE 15) NEVER ring-route: the seq-sharded
        steps write KV at absolute positions (no ``kv_gaps`` awareness)
        and a segment's write burst exceeds the eviction wave's chunk
        reserve — either would corrupt a budget-sized page list. Bounded
        long prompts ride chunked prefill instead (packed when decode
        coexists, split rounds otherwise), whose C-token rows bound
        activation memory the way the segment schedule did."""
        return (
            self.bounded_kv is None
            and self.engine._use_ring_prefill(len(handle.prompt_ids))
            and not handle.grafted
            and (handle.prefill_pos == 0 or handle.ring_path
                 or handle.prefix_entry is not None)
        )

    @staticmethod
    def _parked(handle: SequenceHandle) -> bool:
        """A parked overlap hold: prefix prefilled, awaiting extend_prompt
        — not prefill work, never part of a dispatched round. The ONE
        predicate shared by the round builders, the round-failure handler,
        and the idle check, so they cannot drift."""
        return handle.held and handle.prefill_pos >= len(handle.prompt_ids)

    def _prefill_work(self) -> bool:
        """True when a prefill round has something to advance — parked
        holds are NOT work, so an otherwise idle loop can sleep on the
        wakeup event instead of spinning."""
        return any(not self._parked(h) for h in self.prefilling)

    def _reap_stale_holds(self) -> None:
        now = time.perf_counter()
        for handle in list(self.prefilling):
            if handle.held and now > handle.held_deadline:
                logger.warning(
                    "partial hold %s expired after %.0fs without extend_prompt; "
                    "reclaiming its slot and pages", handle.seq_id, self.hold_ttl_s,
                )
                self.metrics.inc("finchat_partial_stale_reaps_total")
                self._evict(handle, "error", error="partial hold expired")
        for handle in list(self.pending):
            if handle.held and now > handle.held_deadline:
                self.metrics.inc("finchat_partial_stale_reaps_total")
                self.pending.remove(handle)
                handle.finished = True
                self._close_span(handle, "error")
                handle.events.put_nowait(
                    {"type": "error", "message": "partial hold expired"}
                )

    def register_prefix(self, prompt_ids: list[int]) -> int:
        """Prefill a shared prompt head ONCE and serve its KV to every
        later request that starts with it (reference parity argument: the
        system prompt — 1.3-4.5k byte tokens rendered per request,
        ``llm_agent.py:14-17`` — is identical for every conversation, so
        re-prefilling it per request is pure waste; this is what makes the
        TTFT target reachable under prompt-heavy RAG traffic).

        Shares whole pages only (a partially-filled page would be written
        by the owning sequence's appends); the remainder re-prefills per
        request. Returns the shared token length (0 = nothing registered).
        Call while the engine is idle (startup) or when a slot is free.
        """
        prep = self._prefix_prep(prompt_ids)
        if not isinstance(prep, tuple):
            return prep  # 0 (unregistrable) or an existing entry's length
        ids, shared_len, owner, pages, slot = prep
        if self._fabric_restore_head(ids, shared_len, pages):
            # warm-state fabric hit (ISSUE 17): the head's KV scattered
            # straight into the reserved pages — no prefill dispatches,
            # and the slot reservation was never used
            self.free_slots.append(slot)
            self._prefixes.append(_PrefixEntry(ids, pages, shared_len, owner))
            return shared_len
        try:
            self.engine.set_page_table_row(slot, pages)
            self.engine.prefill(slot, ids)  # fills exactly the shared pages
            ssm_snap = self._head_snapshot(slot)
        except Exception:
            self.allocator.free(owner, pages)
            raise
        finally:
            try:
                self.engine.reset_slot(slot)
            except Exception as e:
                # the reservation must come back even when the reset (a
                # device op on a possibly-wedged engine) raises — an
                # escaping raise here would skip the slot return and mask
                # the original failure (finchat-lint R3)
                logger.error("slot reset failed after prefix prefill: %s", e)
            self.free_slots.append(slot)
        self._prefixes.append(_PrefixEntry(ids, pages, shared_len, owner,
                                           ssm_snap=ssm_snap))
        self._fabric_store_head(ids, pages)
        logger.info("prefix cache: registered %d shared tokens (%d pages)",
                    shared_len, len(pages))
        return shared_len

    def _release_snapshot(self, entry: "_PrefixEntry") -> None:
        """A head is dropped: what its snapshot holds beside the state (a
        model with sliding-window layers: the head's trailing window pages)
        goes back. A test's stand-in engine has nothing to release."""
        release = getattr(self.engine, "release_snapshot", None)
        if release is not None:
            release(entry.ssm_snap)

    def _chunk(self, ids: list[int], pos: int, C: int) -> list[int]:
        """The next chunk of ``ids`` from ``pos``: ``C`` tokens, or what a
        row's bound of window pages leaves room for (``engine.prefill_room``:
        a model with sliding-window layers, a chunk that starts inside a
        page)."""
        return ids[pos : pos + min(C, self._prefill_room(pos))]

    def _head_snapshot(self, slot: int) -> tuple | None:
        """The recurrent state ``slot`` holds after a head's last token and
        its trailing window pages, to keep with the head's pages (None for a
        model with neither). Taken before the slot is reset."""
        if not self.has_ssm:
            return None
        self.metrics.inc("finchat_ssm_snapshots_total", labels={"kind": "head"})
        # (detach_head: the state's copy and, by ownership, the slot's window
        # pages; a stand-in engine has the copy alone)
        return getattr(self.engine, "detach_head", self.engine.ssm_snapshot)(slot)

    def _fabric_restore_head(self, ids: list[int], shared_len: int,
                             pages: list[int]) -> bool:
        """Try to serve a head registration from the warm-state fabric
        (ISSUE 17): a hit scatters the fleet-shared snapshot into the
        reserved ``pages`` with one H2D copy instead of re-running the
        prefill. Counts hit/miss/refusal on THIS replica's metrics; a
        cross-mode snapshot is refused (scattering it would value-cast
        into garbage KV — the import_session_entry discipline)."""
        if self.fabric is None:
            return False
        snap = self.fabric.load_head(ids)
        if snap is None:
            self.metrics.inc("finchat_fabric_misses_total")
            return False
        from finchat_tpu.engine.session_cache import snap_kv_mode

        if snap_kv_mode(snap) != self.engine.kv_quant:
            self.metrics.inc("finchat_fabric_import_refused_total")
            return False
        try:
            t0 = time.perf_counter()
            self.engine.restore_pages(pages, snap)
        except Exception as e:
            logger.error("fabric head restore failed (%d tokens): %s — "
                         "falling back to local prefill", shared_len, e)
            return False
        self.metrics.inc("finchat_fabric_hits_total")
        self.metrics.observe("finchat_fabric_restore_seconds",
                             time.perf_counter() - t0)
        if TRACER.enabled:
            TRACER.event("fabric_hit", track="fabric",
                         args={"kind": "head", "tokens": shared_len})
        logger.info("prefix cache: head (%d shared tokens) restored from "
                    "the warm fabric", shared_len)
        return True

    def _fabric_store_head(self, ids: list[int], pages: list[int]) -> None:
        """Publish a freshly-prefilled head fleet-wide (best-effort: the
        fabric is an optimization, registration already succeeded)."""
        if self.fabric is None:
            return
        try:
            self.fabric.store_head(ids, self.engine.offload_pages(pages))
        except Exception as e:
            logger.error("fabric head publish failed: %s", e)

    def _prefix_prep(self, prompt_ids: list[int]):
        """Shared admission logic for both register_prefix variants: size
        the whole-page head, dedupe against live entries, reserve pages and
        an engine slot. Returns an int (0 = unregistrable / no capacity, or
        an already-registered entry's shared length) or the reservation
        tuple ``(ids, shared_len, owner, pages, slot)``."""
        page = self.engine.page_size
        n_pages = min(len(prompt_ids) // page, self.engine.max_pages_per_seq)
        if self.bounded_kv is not None:
            # bounded rows reference at most the SINK-sized lead of a
            # shared head (the admission clamp — head pages pin whole, so
            # anything past the sink could never be referenced): pages
            # registered beyond it would sit in the pool unread forever.
            # The verify_boundedkv drive caught the full-length variant
            # starving admission outright: two full prompt heads consumed
            # 87 of 96 pool pages and the bounded rows waited on pages
            # no one could ever free.
            n_pages = min(n_pages, self.bounded_kv.sink_pages)
        if n_pages <= 0:
            return 0
        shared_len = n_pages * page
        ids = list(prompt_ids[:shared_len])
        for entry in self._prefixes:
            if not entry.retired and entry.shared_len == shared_len and entry.ids == ids:
                return shared_len  # already registered
        for job in self._prefix_jobs:
            if job.shared_len == shared_len and job.ids == ids:
                return 0  # registration already in flight; caller may retry
        if (not self.allocator.can_allocate(n_pages) or not self.free_slots
                or not getattr(self.engine, "head_room", lambda: True)()):
            logger.warning("prefix cache: no pages/slot free; not registering")
            return 0
        owner = f"__prefix_{self._n_prefixes_ever}__"
        self._n_prefixes_ever += 1
        pages = self.allocator.allocate(owner, n_pages)
        slot = self.free_slots.pop()
        return ids, shared_len, owner, pages, slot

    async def register_prefix_async(self, prompt_ids: list[int]) -> int:
        """register_prefix for a RUNNING scheduler: the head prefills one
        chunk per prefill round instead of one monolithic inline prefill,
        so in-flight decode streams keep advancing (a decode step
        interleaves with every round — the midnight refresh stops being a
        multi-second stall for every live stream). Resolves to the shared
        token length, 0 on failure (registration is best-effort by
        contract, same as the sync path)."""
        if not self._running:
            return self.register_prefix(prompt_ids)  # engine idle: inline
        if self._rebuilding:
            # the trip-path rebuild is replacing the page table in a
            # worker thread; a row written now would be silently dropped
            # and the head would prefill against trash pages. Best-effort:
            # the refresh loop retries after the rebuild.
            return 0
        prep = self._prefix_prep(prompt_ids)
        if not isinstance(prep, tuple):
            return prep
        ids, shared_len, owner, pages, slot = prep
        if self._fabric_restore_head(ids, shared_len, pages):
            # fabric hit (ISSUE 17): one H2D scatter, no prefill rounds —
            # the chunked-job machinery (and its decode interleaving
            # rationale) is moot when nothing prefills
            self.free_slots.append(slot)
            self._prefixes.append(_PrefixEntry(ids, pages, shared_len, owner))
            return shared_len
        job = _PrefixJob(
            ids=ids, shared_len=shared_len, owner=owner, pages=pages,
            slot=slot, future=asyncio.get_running_loop().create_future(),
        )
        try:
            self.engine.set_page_table_row(slot, pages)
        except Exception:
            # return the reservation (slot + pages) — a transient device
            # error here must not leak them (the refresh loop retries)
            self.allocator.free(owner, pages)
            self.free_slots.append(slot)
            raise
        self._prefix_jobs.append(job)
        self._wakeup.set()
        return await job.future

    def _fail_prefix_job(self, job: _PrefixJob) -> None:
        self._prefix_jobs.remove(job)
        self.allocator.free(job.owner, job.pages)
        try:
            self.engine.reset_slot(job.slot)
        except Exception as e:
            # reset_slot is a device op and the device may be the very
            # reason this job is failing: log, don't propagate — the job
            # is already off _prefix_jobs, so an escaping exception would
            # skip the remaining jobs in unguarded callers
            # (_fail_prefill_round, stop) and kill the scheduler loop,
            # stranding their awaiters forever
            logger.error("reset_slot during prefix-job failure: %s", e)
        # the slot must come back and the future must resolve regardless,
        # or register_prefix_async's awaiter hangs (no later pass can
        # resolve a job that is no longer listed)
        self.free_slots.append(job.slot)
        if not job.future.done():
            job.future.set_result(0)

    def retire_prefixes(self) -> None:
        """Stop matching every registered prefix (the caller is about to
        register fresh heads — e.g. the embedded date rolled over). Pages
        free immediately when unreferenced, else when the last in-flight
        sequence using them releases (_release). Session-cache entries
        referencing a retired head are purged here too: post-rollover
        prompts diverge inside the head, so such an entry can never resume
        again — keeping it would pin the retired head's device pages for
        as long as an idle conversation stays under the host budget."""
        for entry in self._prefixes:
            entry.retired = True
        if self.session_cache is not None:
            self.session_cache.discard_if(
                lambda e: e.prefix_entry is not None and e.prefix_entry.retired
            )
        self._reap_prefixes()

    def _reap_prefixes(self) -> None:
        for entry in list(self._prefixes):
            if entry.retired and entry.refs == 0:
                self.allocator.free(entry.owner, entry.pages)
                self._release_snapshot(entry)
                self._prefixes.remove(entry)

    def _match_prefix(self, prompt_ids: list[int]) -> tuple["_PrefixEntry | None", int]:
        """Longest live registered prefix usable for this prompt: whole
        shared pages only, and at least one prompt token must remain to
        prefill (the commit needs real last-token logits)."""
        return self._scan_prefixes(prompt_ids)[:2]

    def _scan_prefixes(
        self, prompt_ids: list[int]
    ) -> tuple["_PrefixEntry | None", int, bool]:
        """``_match_prefix`` and, third, whether a head was passed over
        because the prompt shares only part of it — a model with a mixer
        keeps a head's state at the head's end only, so such a prompt
        recomputes what it shares (``_admit`` counts it)."""
        page = self.engine.page_size
        cap = ((len(prompt_ids) - 1) // page) * page
        best: tuple[_PrefixEntry | None, int] = (None, 0)
        partial_skipped = False
        for entry in self._prefixes:
            if entry.retired:
                continue
            usable = min(entry.shared_len, cap)
            if usable > best[1] and prompt_ids[:usable] == entry.ids[:usable]:
                if self.has_ssm and usable < entry.shared_len:
                    partial_skipped = True
                    continue
                best = (entry, usable)
        return (*best, partial_skipped)

    def cancel(self, handle: SequenceHandle) -> None:
        """Client went away (e.g. watchdog timeout): evict and free."""
        if handle.finished:
            return
        if handle.owner is not None and handle.owner is not self:
            # a fleet drain adopted this handle elsewhere: its slot/pages
            # live on the adopter now — evicting HERE with the adopter's
            # slot index would free an unrelated stream's slot
            handle.owner.cancel(handle)
            return
        if handle in self.pending:
            self.pending.remove(handle)
            self._finish(handle, "cancelled")
            return
        self._evict(handle, "cancelled")

    # --- internals ------------------------------------------------------
    @staticmethod
    def _remaining_new(handle: SequenceHandle) -> int:
        """Tokens this sequence may still generate — what its KV allocation
        must cover beyond the prompt. Equals ``max_new_tokens`` for a fresh
        submission; a preempted replay's prompt already CONTAINS its
        generated tokens, so sizing by the full budget would over-reserve
        by exactly that amount."""
        return max(1, handle.sampling.max_new_tokens - handle.generated)

    def _admission_pages(self, handle: SequenceHandle) -> int:
        """KV pages an admission must cover for this handle (shared head
        included): the COMPACTED prompt+budget requirement — a bounded
        replay's ``kv_gap`` tokens have no pages — capped at the bounded
        sink+window budget, where the eviction waves keep occupancy
        (ISSUE 15; the satellite bugfix: the pre-bounded sizing allocated
        and re-prefilled pages the policy would immediately evict)."""
        n = len(handle.prompt_ids) + self._remaining_new(handle) - handle.kv_gap
        # (a draft's rows are written one position ahead of what is emitted)
        total = pages_needed(n + self._drafts, self.engine.page_size)
        if self.bounded_kv is not None:
            total = min(total, self.bounded_kv.budget_pages)
        return total

    def _shed_expired(self) -> None:
        """Deadline load shedding: pending requests past their deadline are
        dropped PRE-admission with a structured retryable error — admitting
        them would spend prefill compute on an answer the caller has
        already given up on. Live streams are never shed: a preempted
        handle was admitted once and owes its client the rest of the
        stream, so it replays regardless of deadline."""
        if not self.pending:
            return
        now = time.perf_counter()
        for handle in list(self.pending):
            if (handle.deadline is not None and now > handle.deadline
                    and handle.generated == 0 and not handle.preempted):
                self.pending.remove(handle)
                self.metrics.inc("finchat_sheds_total")
                TRACER.anomaly("shed", handle.trace_id,
                               args={"seq_id": handle.seq_id,
                                     "replica": self.replica_id})
                handle.finished = True
                self._close_span(handle, "shed")
                handle.events.put_nowait({
                    "type": "error",
                    "message": "deadline exceeded before admission; retry with backoff",
                    "code": "deadline_exceeded",
                    "retryable": True,
                })
        self.metrics.set_gauge("finchat_queue_depth", len(self.pending))

    def _prepare_pending(self) -> None:
        """Shed expired entries, then order the queue for admission:
        earliest deadline first (deadline-less entries last, FIFO among
        themselves) with a starvation guard — an entry that has waited
        longer than ``edf_starvation_seconds`` jumps ahead of deadline
        order (FIFO among the starved), so a stream of tight-deadline
        arrivals cannot starve a far-deadline request forever. A pure
        FIFO workload (no deadlines anywhere) is left untouched. Runs up
        to thrice per loop iteration (preemption plan, post-drain
        re-plan, admission) by design: the queue is bounded by
        max_queue_depth and timsort on an already-ordered deque is ~O(n),
        so re-establishing the order beats threading staleness flags
        through the loop."""
        self._shed_expired()
        if len(self.pending) <= 1 or all(h.deadline is None for h in self.pending):
            return
        now = time.perf_counter()

        def key(h: SequenceHandle):
            if now - h.submitted_at > self.edf_starvation_s:
                return (0, 0.0)  # starved: ahead of EDF, FIFO (stable sort)
            return (1, h.deadline if h.deadline is not None else float("inf"))

        self.pending = deque(sorted(self.pending, key=key))

    def _admit(self) -> None:
        self._prepare_pending()
        admitted: dict[int, list[int]] = {}
        ctx_rows: dict[int, int] = {}
        gap_rows: dict[int, int] = {}
        ssm_rows: dict[int, tuple | None] = {}
        page = self.engine.page_size
        while self.pending and self.free_slots:
            handle = self.pending[0]
            partial_skipped = False
            total = self._admission_pages(handle)
            if total > self.engine.max_pages_per_seq:
                break  # head-of-line waits for pages (rejected at submit anyway)
            bsnap = handle.bounded_snap
            if bsnap is not None:
                # bounded preempt-replay (ISSUE 15 satellite): restore the
                # SURVIVING sink+window pages byte-identically from the
                # preemption snapshot and re-prefill only the residual
                # tail. No prefix/session matching — the snapshot already
                # holds the head region, and the evicted tokens between
                # sink and window have no pages to match against.
                ring = False
                session_eligible = False
                entry, shared_len = None, 0
                s_entry, s_matched = None, 0
                head_pages: list[int] = []
                ref_entry = None
                n_restore = -(-handle.bounded_snap_tokens // page)
                resume_pos = handle.bounded_snap_tokens + handle.kv_gap
                restore_snap = bsnap
                resume_gap = handle.kv_gap
            else:
                # a MONOLITHIC ring prefill assumes position 0, so a prefix
                # hit would force such a prompt onto the chunked path —
                # trading away the activation-memory safety the ring exists
                # for; skip matching there. SEGMENTED ring (ring_segment_
                # tokens > 0) composes: the first segment simply starts at
                # shared_len with the cached head folded as prefix, so long
                # RAG prompts keep the system-head TTFT saving.
                ring = (self.bounded_kv is None
                        and self.engine._use_ring_prefill(len(handle.prompt_ids)))
                if ring and self.engine.ring_segment_tokens() == 0:
                    entry, shared_len = None, 0
                else:
                    entry, shared_len, partial_skipped = self._scan_prefixes(
                        handle.prompt_ids)
                if (self.bounded_kv is not None
                        and shared_len > self.bounded_kv.sink_tokens):
                    # bounded rows reference at most the SINK-sized lead
                    # of a shared head: head pages pin whole (they are
                    # refcounted read-only references — the eviction wave
                    # cannot free them), so a head deeper than the sink
                    # would pin more pages than the budget can ever make
                    # room around (the verify_boundedkv drive reproduced
                    # exactly that: a 25-page system head under a 14-page
                    # budget left nothing evictable). The sink IS the
                    # bounded home of the constant head; the rest
                    # re-prefills and evicts like any other context.
                    shared_len = self.bounded_kv.sink_tokens
                # session tier: a per-conversation resume takes over whenever
                # it matches deeper than the constant shared head (it contains
                # the head as its own leading pages). Ring-eligible prompts
                # keep the SP prefill path untouched — only the head
                # composition above applies there.
                s_entry, s_matched = (None, 0)
                session_eligible = (
                    self.session_cache is not None and handle.conversation_id and not ring
                )
                if session_eligible:
                    if self.session_cache.get(handle.conversation_id) is None:
                        # RAM miss falls through to the disk tier (ISSUE 7):
                        # the record re-enters through import_session_entry
                        # (head re-link + refcount), then match() below applies
                        # the usual token comparison and divergence truncation
                        self._restore_session_from_disk(handle.conversation_id)
                    s_entry, s_matched = self.session_cache.match(
                        handle.conversation_id, handle.prompt_ids
                    )
                    if s_entry is None or s_matched <= shared_len:
                        s_entry, s_matched = None, 0
                    if s_entry is not None and self.bounded_kv is None:
                        if s_entry.kv_gap:
                            # a gapped entry (written under a bounded
                            # policy, arriving here via disk restore or a
                            # fleet import after the policy was turned
                            # off) has no eviction machinery to live
                            # under on this engine — cold-start instead
                            s_entry, s_matched = None, 0
                    elif (s_entry is not None
                            and (s_entry.prefix_len > self.bounded_kv.sink_tokens
                                 or pages_needed(s_matched - s_entry.kv_gap, page)
                                 > self.bounded_kv.budget_pages)):
                        # a resume whose head reference or restored pages
                        # exceed the bounded budget cannot be laid out
                        # (entries written by THIS bounded engine fit by
                        # construction; pre-policy or unbounded-sibling
                        # imports may not) — cold-start instead
                        s_entry, s_matched = None, 0
                if s_entry is not None:
                    # shared head pages referenced (never copied); the pages
                    # past the head restore from the host snapshot below
                    head_pages = s_entry.prefix_pages[: min(s_matched, s_entry.prefix_len) // page]
                    n_restore = s_entry.own_pages_for(s_matched, page)
                    ref_entry = s_entry.prefix_entry if head_pages else None
                    resume_pos = s_matched
                    restore_snap = s_entry.snap
                    # a bounded entry resumes with its sink+window intact
                    # (ISSUE 15): the gap travels with the snapshot and the
                    # slot picks up decode exactly where retirement left it
                    resume_gap = s_entry.kv_gap
                else:
                    head_pages = entry.pages[: shared_len // page] if entry else []
                    n_restore = 0
                    ref_entry = entry
                    resume_pos = shared_len
                    restore_snap = None
                    resume_gap = 0
            need = total - len(head_pages)
            if not self.allocator.can_allocate(need):
                break  # head-of-line waits for pages
            self.pending.popleft()
            slot = self.free_slots.pop()
            pages = self.allocator.allocate(handle.seq_id, need)
            if n_restore:
                try:
                    inject("session.restore", seq_id=handle.seq_id)
                    with Timer(self.metrics, "finchat_session_restore_seconds"):
                        self.engine.restore_pages(pages[:n_restore], restore_snap)
                    self.metrics.inc("finchat_session_cache_restored_tokens_total",
                                resume_pos)
                except Exception as e:
                    # a failed restore must not kill the stream OR leak the
                    # allocation: return the pages cleanly and fall back to
                    # a cold start through the plain shared-prefix plan
                    logger.error("session cache restore failed for %s: %s",
                                 handle.seq_id, e)
                    self.allocator.free(handle.seq_id, pages)
                    if bsnap is not None:
                        # bounded replay demotes to a full-history
                        # recompute: the surviving-page bytes are gone, so
                        # the gap resets and the whole history re-prefills
                        # (post-window tokens may diverge — counted)
                        handle.bounded_snap = None
                        handle.bounded_snap_tokens = 0
                        handle.kv_gap = 0
                        self.metrics.inc(
                            "finchat_boundedkv_recompute_fallbacks_total")
                        total = self._admission_pages(handle)
                    s_entry = None  # the admission below is the prefix plan
                    resume_gap = 0
                    head_pages = entry.pages[: shared_len // page] if entry else []
                    ref_entry = entry
                    resume_pos = shared_len
                    need = total - len(head_pages)
                    n_restore = 0
                    if not self.allocator.can_allocate(need):
                        # cold plan needs more pages than the resume did:
                        # requeue at the head and wait like any other
                        self.pending.appendleft(handle)
                        self.free_slots.append(slot)
                        break
                    pages = self.allocator.allocate(handle.seq_id, need)
                else:
                    if bsnap is not None:
                        handle.bounded_snap = None
                        handle.bounded_snap_tokens = 0
            if session_eligible:
                # counted only for an admission that actually went through
                # its plan — a page-starved head-of-line retry or a failed
                # restore (demoted to a cold start above) must not inflate
                # the hit rate
                self.metrics.inc("finchat_session_cache_hits_total" if s_entry is not None
                            else "finchat_session_cache_misses_total")
            # shared/restored head pages lead (logical pages 0..): the slot
            # reads them read-only — its own writes all land at positions >=
            # resume_pos, i.e. in its own pages
            admitted[slot] = head_pages + pages
            handle.page_list = admitted[slot]
            handle.shared_len = len(head_pages) * page
            handle.resumed_len = resume_pos if s_entry is not None else 0
            handle.kv_gap = resume_gap
            handle.kv_ctx = resume_pos
            if resume_gap:
                gap_rows[slot] = resume_gap
            if ref_entry is not None:
                ref_entry.refs += 1
                handle.prefix_entry = ref_entry
            if resume_pos:
                ctx_rows[slot] = resume_pos
                handle.prefill_pos = resume_pos
                if s_entry is None and bsnap is None:
                    self.metrics.inc("finchat_prefix_hits_total")
                    self.metrics.inc("finchat_prefix_tokens_saved_total", shared_len)
            if self.has_ssm:
                # the pages are referenced; the state is copied: the row
                # starts from the head's snapshot, or from zero (engine.
                # ssm_admit), never from what the slot's last row left
                ssm_rows[slot] = ref_entry.ssm_snap if resume_pos else None
                handle.span.state_restored_tokens = resume_pos
                if ((self._ssm_session_fallback and handle.conversation_id)
                        or (partial_skipped and not resume_pos)):
                    self.metrics.inc("finchat_ssm_recompute_fallbacks_total")
            handle.slot = slot
            handle.span.mark("admitted")
            if not handle.preempted:  # a replay's history is no new prompt
                self._book_prompt(handle, resume_pos)
            if handle.constraint is None:
                self._temperature[slot] = handle.sampling.temperature
                self._top_p[slot] = handle.sampling.top_p
                self._top_k[slot] = handle.sampling.top_k
            # constrained slots keep the non-truncating defaults: their
            # device-sampled token is always discarded for the host-side
            # grammar pick (_constrained_pick), and a truncating top_p/top_k
            # here would knock the WHOLE batch off the sampler's exact
            # full-vocab fast path (sampler.py sample())
            self.prefilling.append(handle)
            logger.debug("admitted %s into slot %d (%d pages)", handle.seq_id, slot, need)
        if admitted:
            # ONE device update for the whole admission burst — each
            # per-slot eager update would be a dispatch of its own
            self.engine.set_page_table_rows(admitted)
            if ctx_rows:
                self.engine.set_context_lens_rows(ctx_rows)
            if gap_rows:
                self.engine.set_kv_gap_rows(gap_rows)
            if ssm_rows:
                self.engine.ssm_admit(ssm_rows)
                self.metrics.inc("finchat_ssm_snapshot_restores_total",
                                 sum(snap is not None for snap in ssm_rows.values()))
            self.metrics.set_gauge("finchat_queue_depth", len(self.pending))

    def _finish(self, handle: SequenceHandle, reason: str) -> None:
        handle.finished = True
        self._close_span(handle, reason)
        handle.events.put_nowait({"type": "done", "reason": reason})

    def _release(self, handle: SequenceHandle) -> None:
        if handle.slot >= 0:
            pages = self.allocator.owned_by(handle.seq_id)
            if pages:
                self.allocator.free(handle.seq_id, pages)
            try:
                self.engine.reset_slot(handle.slot)
            except Exception as e:
                # survivable (finchat-lint R3, the _fail_prefix_job bug
                # class): a raising device op here would skip the slot
                # return and the prefix-ref release below, leaking the
                # slot forever — and _release's callers (_evict via
                # watchdog cancel, stop) don't expect a raise. Admission
                # rewrites the page-table row, the context length and (a
                # model with a mixer) the recurrent state anyway; a wedged
                # device trips the breaker.
                logger.error("slot reset failed releasing %s: %s",
                             handle.seq_id, e)
            self.decoding.pop(handle.slot, None)
            if handle in self.prefilling:
                self.prefilling.remove(handle)
            # restore non-truncating defaults: the sampler's exact full-vocab
            # fast path keys on ALL slots' params, so a freed slot must not
            # keep a dead request's top_p/top_k (sampler.py sample())
            self._temperature[handle.slot] = 0.0
            self._top_p[handle.slot] = 1.0
            self._top_k[handle.slot] = 0
            self.free_slots.append(handle.slot)
            handle.slot = -1
            if handle.prefix_entry is not None:
                handle.prefix_entry.refs -= 1
                handle.prefix_entry = None
                self._reap_prefixes()

    def _session_drop(self, entry) -> None:
        """Session-cache ``on_drop`` hook (LRU eviction, replacement, or
        divergence truncation to nothing): release the entry's reference on
        its shared-prefix head so retirement can finally free those pages."""
        if entry.prefix_entry is not None:
            entry.prefix_entry.refs -= 1
            entry.prefix_entry = None
            self._reap_prefixes()

    def _maybe_offload(self, handle: SequenceHandle) -> None:
        """Snapshot a normally-retiring sequence's KV into the session cache
        (device→host) BEFORE its pages are freed. Whole pages only — the
        matcher is page-granular, so a partial tail page could never be
        resumed. The D2H copy blocks (engine.offload_pages) by design: the
        pages are returned to the allocator the moment this returns, and an
        async copy would race the next sequence's writes into them."""
        cache = self.session_cache
        if cache is None or not handle.conversation_id or handle.slot < 0:
            return
        if handle.prefill_pos < len(handle.prompt_ids) or not handle.generated:
            return  # never reached decode; nothing coherent to keep
        page = self.engine.page_size
        # KV-cached tokens: prompt + generated minus the last delivered
        # token, whose KV append belongs to the step that was never
        # consumed. Bounded rows (ISSUE 15) count in COMPACTED coordinates
        # — the snapshot holds only the SURVIVING sink+window pages, and
        # the entry records the gap so a restore resumes with them intact.
        gap = handle.kv_gap
        context = len(handle.history) - 1 - gap
        n_tok = (context // page) * page  # compacted, page-whole
        if n_tok <= 0:
            return
        shared = min(handle.shared_len, n_tok)
        # a shared head without a refcounted entry would store device page
        # ids nobody protects — use-after-free; admission guarantees the pair
        assert shared == 0 or handle.prefix_entry is not None
        # incremental offload: pages covering [shared, resumed_len) were
        # restored from the previous entry's snapshot at admission and never
        # rewritten (the slot's writes start at resumed_len), so reuse those
        # host bytes — without this every retirement re-copies the WHOLE
        # history D2H and the per-turn cost grows linearly again. Gapped
        # rows skip the splice (the page↔token index math shifts under the
        # gap, and a bounded snapshot is at most sink+window pages — the
        # re-copy is O(budget), not O(history), by construction).
        prev = cache.get(handle.conversation_id)
        reuse_pages = 0
        if (gap == 0 and prev is not None and prev.snap is not None
                and prev.kv_gap == 0
                and prev.prefix_len == shared and handle.resumed_len > shared):
            m = min(handle.resumed_len, n_tok, prev.n_tokens)
            reuse_pages = (m - shared) // page
            if reuse_pages and not np.array_equal(
                prev.token_ids[shared : shared + reuse_pages * page],
                np.asarray(handle.history[shared : shared + reuse_pages * page], np.int32),
            ):
                reuse_pages = 0  # entry replaced by a different stream since
        own_ids = handle.page_list[shared // page + reuse_pages : n_tok // page]
        copy_started = time.perf_counter()
        try:
            inject("session.offload", seq_id=handle.seq_id)
            snap_new = self.engine.offload_pages(own_ids) if own_ids else None
        except Exception as e:  # cache is an optimization; never fail eviction
            logger.error("session cache offload failed for %s: %s", handle.seq_id, e)
            return
        copied = time.perf_counter()
        from finchat_tpu.engine.session_cache import SessionEntry, concat_snaps, snap_nbytes

        self._retired.update(offload_s=copied - copy_started, offload_pages=len(own_ids),
                             offload_bytes=snap_nbytes(snap_new))

        entry = SessionEntry(
            conversation_id=handle.conversation_id,
            # token ids cover the ABSOLUTE span [0, n_tok + gap): the
            # evicted tokens' ids must still match the next turn's prompt
            # for the surviving KV to be valid (match() compares them all)
            token_ids=np.asarray(handle.history[: n_tok + gap], np.int32),
            prefix_entry=handle.prefix_entry if shared else None,
            prefix_pages=list(handle.page_list[: shared // page]),
            prefix_len=shared,
            snap=concat_snaps(prev.snap if reuse_pages else None, reuse_pages, snap_new),
            kv_gap=gap,
            # a gapped handle can retire on an UNBOUNDED engine (a fleet
            # sibling adopted its preempt snapshot): record sink 0 there —
            # nothing is salvageable without the policy's sink geometry
            kv_sink=(self.bounded_kv.sink_tokens
                     if gap and self.bounded_kv is not None else 0),
        )
        # reference the shared head BEFORE put(): put may drop an older
        # entry holding the same (possibly retired) head, and a momentary
        # refs==0 would free pages the new entry is about to point at
        if entry.prefix_entry is not None:
            entry.prefix_entry.refs += 1
        if cache.put(entry):
            self.metrics.inc("finchat_session_cache_offloaded_pages_total", len(own_ids))
        elif entry.prefix_entry is not None:
            entry.prefix_entry.refs -= 1
            self._reap_prefixes()
        self._retired["store_s"] = time.perf_counter() - copied

    def _evict(self, handle: SequenceHandle, reason: str, error: str | None = None) -> None:
        if error is None and reason in ("eos", "length"):
            # normal retirement: the sequence's KV is a coherent prefix of
            # this conversation's next turn — offload before pages free
            self._maybe_offload(handle)
        release_started = time.perf_counter()
        self._release(handle)
        released = time.perf_counter()
        if error is not None:
            handle.finished = True
            self._close_span(handle, "error")
            handle.events.put_nowait({"type": "error", "message": error})
        else:
            self._finish(handle, reason)
        self._retired.update(release_s=released - release_started,
                             finish_s=time.perf_counter() - released)

    def _retire(self, handle: SequenceHandle, reason: str) -> None:
        """A row's answer ended (``eos`` / ``length``) where its last token
        was delivered, on the loop task: evict it inside the round's
        ``retire`` phase — a ``finchat.retire`` annotation on the profiler's
        clock, its time out of ``deliver`` — and say what finishing it took.
        The parts (RETIRE_PARTS) are clocked where their work happens and go
        to ``finchat_retire_seconds_total{part}`` whatever the tracer's state,
        and with the phase's own ends to one ``retire`` event on the
        request's timeline. Every other caller of ``_evict`` (cancel, error,
        watchdog) runs outside the loop's phases and leaves neither."""
        took = self._retired = _retire_account()
        trace_id, context_tokens = handle.trace_id, len(handle.history)
        phase = TRACER.phase("retire", self._phases)
        with phase:
            self._evict(handle, reason)
        for part in RETIRE_PARTS:
            self.metrics.inc("finchat_retire_seconds_total", took[f"{part}_s"],
                             labels={"part": part})
        if TRACER.enabled:
            TRACER.event(
                "retire", trace_id, ts=phase.started, dur=phase.ended - phase.started,
                track=self._trace_track,
                args={"reason": reason, "n": self._dispatch_tally,
                      "decoding": len(self.decoding), "context_tokens": context_tokens,
                      **took})

    # --- bounded-KV serving (ISSUE 15; kv_cache.BoundedKVPolicy) --------
    def _bounded_pinned_pages(self, handle: SequenceHandle) -> int:
        """Unevictable leading pages of a bounded row: the attention sink,
        widened to the whole shared-prefix head when the head is larger
        (head pages are refcounted read-only references — dropping one
        from this row's list without freeing it would just shrink the
        sink below the policy, so the head pins whole: an effectively
        larger sink for head-sharing rows)."""
        return max(self.bounded_kv.sink_pages,
                   handle.shared_len // self.engine.page_size)

    def _bounded_evict_wave(self) -> None:  # finchat-lint: hot
        """Page-granular eviction for bounded rows: between dispatches,
        any row whose NEXT dispatch would not fit its page list evicts the
        oldest post-sink page(s) — the pages leave the row's logical page
        list (survivors shift down one logical slot; physically nothing
        moves), return to the pool, and fresh pages extend the tail for
        the incoming writes. ``kv_gap`` grows by a page per eviction and
        the engine mirror (``state.kv_gaps``) updates in the same wave, so
        every enqueued dispatch sees a table and gap that agree — device
        stream order keeps in-flight programs reading the table they were
        dispatched against, which is why no drain is needed.

        The wave is host-deterministic: its sole inputs are each row's
        ``kv_ctx`` (the dispatch-time context mirror — exactly the next
        dispatch's write position, whatever the pipeline depth) and fixed
        per-config reserve constants, so the gap a token is computed under
        is a pure function of its position. That is
        what makes a preempt-replay's gap schedule identical to the
        uninterrupted run's."""
        bp = self.bounded_kv
        if bp is None:
            return
        page = self.engine.page_size
        chunk = self.engine.engine_cfg.prefill_chunk
        pt_rows: dict[int, list[int]] = {}
        gap_rows: dict[int, int] = {}
        evicted_total = 0
        for handle in list(self.prefilling) + list(self.decoding.values()):
            if handle.slot < 0 or handle.finished or self._parked(handle):
                continue
            # the reserve is exactly what the next dispatch WRITES for
            # this row: a prefill chunk, or ONE decode token — spec
            # verify spans are gated to never cross the eviction boundary
            # (_bounded_span_room), so the only dispatch that ever
            # reaches the boundary writes a single token. Reserving the
            # full verify span here would evict one dispatch EARLY
            # whenever the gate demotes at the boundary — and a replay,
            # whose residual chunk regroups those positions, would then
            # see a different gap schedule than the uninterrupted run
            # (the byte-identity contracts pin this).
            prefilling = handle.prefill_pos < len(handle.prompt_ids)
            if prefilling:
                remaining = len(handle.prompt_ids) - handle.prefill_pos
                incoming = min(chunk, remaining)
            else:
                incoming = 1
            try:
                e = bp.plan_eviction(
                    handle.kv_ctx - handle.kv_gap, incoming,
                    len(handle.page_list), self._bounded_pinned_pages(handle),
                )
            except PageAllocationError as err:
                # infeasible plan = a policy/config violation for THIS row
                # (e.g. a shared head pinning almost the whole budget);
                # per-sequence isolation, the others keep serving
                logger.error("bounded eviction infeasible for %s: %s",
                             handle.seq_id, err)
                self._evict(handle, "error", error=str(err))
                continue
            if not e:
                continue
            if handle.kv_gap == 0:
                self.metrics.inc("finchat_boundedkv_bounded_sessions_total")
            pin = self._bounded_pinned_pages(handle)
            victims = handle.page_list[pin : pin + e]
            handle.page_list = (
                handle.page_list[:pin] + handle.page_list[pin + e :]
            )
            self.allocator.free(handle.seq_id, victims)
            # keep capacity constant: fresh tail pages for the incoming
            # writes (the LIFO free list usually hands the same physical
            # pages straight back)
            handle.page_list = handle.page_list + self.allocator.allocate(
                handle.seq_id, e
            )
            handle.kv_gap += e * page
            handle.kv_gap_pos = handle.kv_ctx
            pt_rows[handle.slot] = handle.page_list
            gap_rows[handle.slot] = handle.kv_gap
            evicted_total += e
        if pt_rows:
            self.engine.set_page_table_rows(pt_rows)
            self.engine.set_kv_gap_rows(gap_rows)
            self.metrics.inc("finchat_boundedkv_evicted_pages_total",
                             evicted_total)
            if TRACER.enabled:
                TRACER.event("boundedkv_evict", track=self._trace_track,
                             args={"pages": evicted_total,
                                   "slots": sorted(pt_rows)})

    def _bounded_span_room(self, handle: SequenceHandle) -> int:
        """Tokens this row may still write before its next eviction
        boundary (``page-list capacity + kv_gap``). A spec verify span
        must FIT this room: a span crossing the boundary would give its tail
        tokens the pre-eviction gap, and since a preempt-replay
        regroups spans on a shifted grid, the gap a given token
        sees would stop being a pure function of its position — breaking
        the byte-identity contracts. Unbounded rows have unlimited room
        by construction (capacity covers prompt + max_new)."""
        if self.bounded_kv is None:
            return 1 << 30
        boundary = (len(handle.page_list) * self.engine.page_size
                    + handle.kv_gap)
        return max(0, boundary - handle.kv_ctx)

    # --- resilience plane (ISSUE 5; ROBUSTNESS.md) ----------------------
    def _preempt(self, handle: SequenceHandle, *, for_rebuild: bool = False) -> None:
        """Recompute preemption: free the victim's slot and KV pages but
        keep its prompt AND already-generated tokens on the handle. The
        replay plan sets ``prompt_ids = history`` (prompt + delivered
        tokens), so re-admission re-prefills exactly the stream so far —
        composing with the shared-prefix and session caches, which makes
        the replay usually cheap — and the commit at replay-prefill
        completion samples precisely the NEXT token: zero duplicate or
        dropped tokens on the stream (greedy replay is byte-identical;
        tests/test_resilience.py pins it). Any token of the victim still
        riding an in-flight dispatch is discarded at consume time
        (``handle.slot`` is -1 by then) and recomputed by the replay.

        Used for page pressure (the latest-deadline victim yields instead
        of the earliest-deadline candidate stalling head-of-line) and as
        the circuit breaker's recovery primitive. ``for_rebuild`` skips
        per-slot device resets — the whole device state is about to be
        replaced and the engine may be wedged."""
        if handle.finished:
            return
        slot = handle.slot
        if slot >= 0:
            if self.bounded_kv is not None and handle.kv_gap:
                # bounded rows preempt by SNAPSHOT, not recompute (the
                # ISSUE 15 satellite bugfix): the surviving window's KV
                # cannot be recomputed byte-identically from the token
                # stream (window keys attended to tokens that are gone),
                # and the old sizing re-prefilled — and re-allocated —
                # pages the policy would immediately evict. Gather the
                # surviving compacted pages to host BEFORE they free; the
                # replay restores them and re-prefills only the tail.
                self._bounded_preempt_snapshot(handle, for_rebuild)
            pages = self.allocator.owned_by(handle.seq_id)
            if pages:
                self.allocator.free(handle.seq_id, pages)
            self.decoding.pop(slot, None)
            if handle in self.prefilling:
                self.prefilling.remove(handle)
            self._temperature[slot] = 0.0
            self._top_p[slot] = 1.0
            self._top_k[slot] = 0
            self.free_slots.append(slot)
            handle.slot = -1
            if handle.prefix_entry is not None:
                handle.prefix_entry.refs -= 1
                handle.prefix_entry = None
                if not for_rebuild:
                    self._reap_prefixes()
            if not for_rebuild:
                try:
                    self.engine.reset_slot(slot)
                except Exception as e:
                    # survivable: admission rewrites the page-table row, the
                    # context length and the mixer's state; a wedged device
                    # trips the breaker
                    logger.error("slot reset failed preempting %s: %s",
                                 handle.seq_id, e)
        elif handle in self.pending:
            return  # already queued; nothing to preempt
        handle.prompt_ids = list(handle.history)
        handle.prefill_pos = 0
        handle.kv_ctx = 0
        handle.page_list = []
        handle.shared_len = 0
        handle.resumed_len = 0
        handle.ring_path = False
        handle.grafted = False
        handle.preempted += 1
        handle.epoch += 1  # invalidate stale dispatch-membership snapshots
        # preempted sequences re-admit ahead of new load: they are live
        # streams mid-answer, and _prepare_pending's EDF ordering applies
        # on top when deadlines are in play
        self.pending.appendleft(handle)
        self.metrics.inc("finchat_preemptions_total")
        if TRACER.enabled and handle.trace_id is not None:
            TRACER.event("preempt", handle.trace_id, track=self._trace_track,
                         args={"preempted": handle.preempted,
                               "for_rebuild": for_rebuild})
        self.metrics.set_gauge("finchat_queue_depth", len(self.pending))
        self._wakeup.set()

    def _bounded_preempt_snapshot(self, handle: SequenceHandle,
                                  for_rebuild: bool) -> None:
        """Snapshot a bounded row's surviving pages for its replay (see
        ``_preempt``). ``for_rebuild`` preempts run against a possibly
        wedged device — no snapshot is attempted; the row demotes to a
        full-history recompute (gap reset; post-window tokens may diverge
        from the uninterrupted stream, counted as a recompute fallback).

        The snapshot covers the EXACT compacted context — the partial
        tail page included, not just whole pages: the tail tokens' KV was
        computed against surviving pages that may since have been evicted,
        so RE-computing them at replay would attend a different set and
        break the byte-identity contract. Only the last history token
        (whose KV belongs to the never-consumed step) re-prefills.

        Identity caveat: the contract assumes the preempt was taken at a
        CONSUMED boundary (``kv_gap_pos <= len(history) - 1``) — true for
        the page-pressure path, which drains the in-flight dispatch
        before executing its plan. A mid-flight preempt that lands inside
        an eviction transition (breaker/whole-round-failure paths, which
        carry no identity contract) recomputes the pending boundary token
        under the newer gap — a valid bounded decode, one token per
        page-crossing wide."""
        page = self.engine.page_size
        snap_tokens = len(handle.history) - 1 - handle.kv_gap
        if not for_rebuild and snap_tokens > 0:
            try:
                n = -(-snap_tokens // page)  # whole pages incl. partial tail
                handle.bounded_snap = self.engine.offload_pages(
                    handle.page_list[:n]
                )
                handle.bounded_snap_tokens = snap_tokens
                return
            except Exception as e:
                logger.error("bounded preempt snapshot failed for %s: %s",
                             handle.seq_id, e)
        handle.bounded_snap = None
        handle.bounded_snap_tokens = 0
        handle.kv_gap = 0
        self.metrics.inc("finchat_boundedkv_recompute_fallbacks_total")

    def _preemption_plan(self) -> list[SequenceHandle]:
        """Page-pressure preemption policy: when the earliest-deadline
        pending request cannot be admitted for lack of KV pages, return
        the latest-deadline decoding victims (deadline-less = lowest
        priority) whose deadlines are STRICTLY later than the candidate's
        and whose pages would make the admission fit. Strict deadline
        order makes the policy livelock-free: a victim can never in turn
        preempt the sequence it yielded to. Returns [] when preemption is
        off, nothing is stalled, no eligible victim exists, or even
        preempting every eligible victim would not free enough pages
        (preempting without admitting would be pure loss). Planning only —
        the loop drains the in-flight dispatch before executing the plan,
        so no freed page can still be a target of queued device writes."""
        if not self.preemption_enabled or not self.pending or not self.decoding:
            return []
        self._prepare_pending()
        if not self.pending:
            return []
        cand = self.pending[0]
        if cand.deadline is None:
            return []  # only deadline urgency justifies evicting live KV
        page = self.engine.page_size
        total = self._admission_pages(cand)
        if total > self.engine.max_pages_per_seq:
            return []
        # prefix-aware need (same plan _admit will compute): an admission
        # a shared head would satisfy must not trigger a preemption
        ring = self.engine._use_ring_prefill(len(cand.prompt_ids))
        if ring and self.engine.ring_segment_tokens() == 0:
            shared_len = 0
        else:
            _, shared_len = self._match_prefix(cand.prompt_ids)
        need = total - shared_len // page
        if self.free_slots and self.allocator.can_allocate(need):
            return []  # admissible as-is; _admit will take it

        def eff(h: SequenceHandle) -> float:
            return h.deadline if h.deadline is not None else float("inf")

        pool = [h for h in self.decoding.values()
                if not h.finished and eff(h) > cand.deadline]
        pool.sort(key=eff, reverse=True)
        victims: list[SequenceHandle] = []
        freeable = self.allocator.free_count
        for v in pool:
            victims.append(v)
            freeable += len(self.allocator.owned_by(v.seq_id))
            if freeable >= need:
                return victims
        return []

    # --- fleet surface (serve/fleet.py; ISSUE 6) ------------------------
    def adopt(self, handle: SequenceHandle) -> bool:
        """Admit a handle drained from a sibling replica. The handle
        arrives device-free — ``_preempt`` normalized it (prompt_ids =
        full history, slot -1, no pages, epoch bumped past every stale
        membership snapshot) — and its ``events`` queue travels WITH it,
        so the original consumer keeps streaming with no seam: the next
        token it sees is exactly the next token of the stream. Live
        streams (already-delivered tokens) jump the queue the same way
        local preemption replays do — they are always adopted, exactly as
        a local preempt-replay never counts against the bound. A
        NEVER-admitted handle is plain queued load wearing a drain coat:
        it honors ``max_queue_depth`` like any fresh submit (refused →
        False), or a victim's give-up would transplant its whole backlog
        past the sibling's backpressure bound and lock out new clients
        with OverloadedError until it drains. Returns whether the handle
        was taken."""
        if handle.finished:
            return True
        live = bool(handle.preempted or handle.generated)
        if (not live and self.max_queue_depth > 0
                and len(self.pending) >= self.max_queue_depth):
            return False
        handle.owner = self  # cleanup (cancel) must target THIS scheduler now
        if TRACER.enabled and handle.trace_id is not None:
            TRACER.event("adopt", handle.trace_id, track=self._trace_track,
                         args={"live": live})
        if live:
            self.pending.appendleft(handle)
        else:
            self.pending.append(handle)
        self.metrics.set_gauge("finchat_queue_depth", len(self.pending))
        self._wakeup.set()
        return True

    def export_session(self, conversation_id: str | None) -> dict | None:
        """Portable image of a conversation's session-cache entry for
        cross-replica handoff (device pages dropped; see
        SessionKVCache.export_entry)."""
        if self.session_cache is None or not conversation_id:
            return None
        return self.session_cache.export_entry(conversation_id)

    def import_session_entry(self, payload: dict | None, *,
                             spill: bool = True) -> bool:
        """Adopt a sibling's exported session-cache entry (drain handoff /
        lazy route-time migration). The export carries no device pages —
        an entry whose KV rode a shared-prefix head re-links against THIS
        scheduler's own live registration of the same head (every fleet
        replica registers the same prompt heads), refcounted exactly like
        a local offload. No matching live head → the entry is refused
        (counted) and the conversation resumes cold: KV positions are
        absolute, so the snapshot's pages are meaningless without the
        head KV below them."""
        if payload is None or self.session_cache is None:
            return False
        from finchat_tpu.engine.session_cache import snap_kv_mode

        if (payload.get("snap") is not None
                and snap_kv_mode(payload["snap"]) != self.engine.kv_quant):
            # cross-MODE snapshot (a handoff or disk record from an engine
            # serving the other page-pool dtype): scattering it would
            # value-cast into garbage KV — refuse, count the dequant
            # fallback, resume cold (kv_cache.scatter_pages_device is the
            # raising last line behind this counted gate)
            logger.warning(
                "session import for %s refused: snapshot kv mode %r vs "
                "engine kv_quant %r — cold start",
                payload.get("conversation_id"),
                snap_kv_mode(payload["snap"]), self.engine.kv_quant,
            )
            self.metrics.inc("finchat_quant_dequant_fallbacks_total")
            return False
        prefix_len = int(payload["prefix_len"])
        entry_ref = None
        pages: list[int] = []
        if prefix_len > 0:
            page = self.engine.page_size
            if prefix_len % page:
                # fleet-LEVEL series: unlabeled like the rest of the
                # finchat_fleet_* family (one reader sees all refusals)
                METRICS.inc("finchat_fleet_session_import_refused_total")
                return False
            head_ids = [int(t) for t in payload["token_ids"][:prefix_len]]
            for cand in self._prefixes:
                if (not cand.retired and cand.shared_len >= prefix_len
                        and cand.ids[:prefix_len] == head_ids):
                    entry_ref = cand
                    pages = cand.pages[: prefix_len // page]
                    break
            if entry_ref is None:
                # fleet-LEVEL series: unlabeled like the rest of the
                # finchat_fleet_* family (one reader sees all refusals)
                METRICS.inc("finchat_fleet_session_import_refused_total")
                return False
            # reference BEFORE put (put may drop an older entry holding the
            # same head — a momentary refs==0 would free it), exactly the
            # _maybe_offload discipline
            entry_ref.refs += 1
        ok = self.session_cache.import_entry(
            payload, prefix_entry=entry_ref, prefix_pages=pages, spill=spill
        )
        if not ok and entry_ref is not None:
            entry_ref.refs -= 1
            self._reap_prefixes()
        return ok

    # --- durability plane (ISSUE 7; ROBUSTNESS.md §5) --------------------
    def _restore_session_from_disk(self, conversation_id: str) -> bool:
        """RAM-miss fall-through to the session disk tier: load the
        conversation's record (checksummed; corruption quarantines and
        returns None) and adopt it through ``import_session_entry`` — the
        exact path a fleet handoff takes, so shared-head re-linking and
        refcounts work identically. Returns True when the entry is now
        resident in RAM."""
        cache = self.session_cache
        if cache is None or cache.disk is None:
            return False
        if conversation_id not in cache.disk:
            if self.fabric is not None:
                # with the shared tier this IS the fleet-wide lookup: a
                # miss means no replica ever retired this conversation
                self.metrics.inc("finchat_fabric_misses_total")
            return False
        t0 = time.perf_counter()
        with Timer(self.metrics, "finchat_durability_restore_seconds"):
            payload = cache.disk.load(conversation_id)
            if payload is None:
                return False  # quarantined (corrupt/truncated): cold start
            # an over-RAM-budget record is trimmed to the prefix that
            # fits (partial warm resume); one that can't fit at all is
            # dropped — put() would refuse it every turn, paying a full
            # record read + rewrite for a guaranteed cold start
            payload = cache.fit_payload(payload)
            if payload is None:
                cache.disk.discard(conversation_id)
                return False
            try:
                # spill=False: these bytes just came OFF this disk tier —
                # rewriting the identical record would double restore I/O
                ok = self.import_session_entry(payload, spill=False)
            except Exception as e:
                logger.error("disk session restore failed for %s: %s",
                             conversation_id, e)
                return False
        if ok:
            self.metrics.inc("finchat_durability_disk_restores_total")
            if self.fabric is not None:
                # the record came off the fleet-shared tier: ANY replica's
                # retirement (or a handoff) could have written it — this
                # replica resumes it warm without ever having seen it
                self.metrics.inc("finchat_fabric_hits_total")
                self.metrics.observe("finchat_fabric_restore_seconds",
                                     time.perf_counter() - t0)
                if TRACER.enabled:
                    TRACER.event("fabric_hit", track="fabric",
                                 args={"kind": "session",
                                       "key": conversation_id})
        elif self.fabric is not None:
            self.metrics.inc("finchat_fabric_misses_total")
        return ok

    def spill_sessions(self) -> int:
        """Write every resident session entry through to the disk tier
        (graceful-shutdown tail; puts already write through, so this is a
        retry/freshness pass)."""
        if self.session_cache is None:
            return 0
        return self.session_cache.spill_all()

    async def shutdown_drain(self) -> None:
        """Graceful-shutdown tail (SIGTERM; serve/app.py drain_and_stop):
        stop the loop, then preempt every straggler to host — its coherent
        KV prefix is offloaded into the session tier (which writes through
        to disk) before its slot and pages are released — and fail it with
        a structured retryable ``shutting_down`` error, so its client
        retries against the restarted process instead of hanging. Pending
        never-admitted work fails the same way. Zero slot/page leaks by
        construction: every live handle goes through ``_release``, and the
        only pages still owned afterwards are the shared-prefix heads'
        (device cache, dropped with the process)."""
        await self.stop()
        shutdown_error = {
            "type": "error",
            "message": "server shutting down; retry with backoff",
            "code": "shutting_down", "retryable": True,
        }
        for handle in list(self.decoding.values()) + list(self.prefilling):
            try:
                # mid-decode stragglers have a coherent prompt+generated
                # KV prefix — the same snapshot a normal retirement takes
                self._maybe_offload(handle)
            except Exception as e:
                logger.error("shutdown offload failed for %s: %s",
                             handle.seq_id, e)
            self._release(handle)
            handle.finished = True
            self._close_span(handle, "drained")
            handle.events.put_nowait(dict(shutdown_error))
        for handle in list(self.pending):
            self.pending.remove(handle)
            handle.finished = True
            self._close_span(handle, "drained")
            handle.events.put_nowait(dict(shutdown_error))
        self.metrics.set_gauge("finchat_queue_depth", 0)
        self.spill_sessions()

    def _drain_to_sink(self) -> int:
        """Offer every pending handle — the just-preempted live streams
        AND queued not-yet-admitted work — to the fleet drain sink,
        together with its conversation's exported session-cache bytes.
        Adopted handles leave this scheduler entirely. Runs BEFORE the
        trip purges device-referencing caches (the export must still see
        the entries). Parked/held overlap handles are skipped: their
        extend_prompt seam is bound to this scheduler, and retrieval is
        ms-scale — they replay locally. Returns how many were adopted."""
        sink = self.drain_sink
        if sink is None:
            return 0
        adopted = 0
        for handle in list(self.pending):
            if handle.held:
                continue
            payload = None
            try:
                payload = self.export_session(handle.conversation_id)
            except Exception as e:
                logger.error("session export failed for %s: %s",
                             handle.conversation_id, e)
            try:
                taken = bool(sink(handle, payload))
            except Exception as e:
                logger.error("drain sink failed for %s: %s", handle.seq_id, e)
                taken = False
            if taken:
                self.pending.remove(handle)
                if handle.conversation_id and self.session_cache is not None:
                    # the bytes moved with the stream; keeping the source
                    # entry would let a later divergent turn resume stale
                    self.session_cache.discard(handle.conversation_id)
                adopted += 1
        self.metrics.set_gauge("finchat_queue_depth", len(self.pending))
        return adopted

    def revive(self) -> bool:
        """Supervisor respawn of a given-up replica: the breaker exhausted
        its rebuild budget, the fleet drained this replica's streams to
        siblings and marked it OUT; ``revive`` retries the device-state
        rebuild from a clean slate so the router can bring the replica
        back. Only callable with nothing live here (the drain emptied it).
        Returns True when the engine is serving again."""
        self._revive_prepare()
        if not self._revive_rebuild():
            return False
        self._revive_commit()
        return True

    async def revive_async(self) -> bool:
        """``revive`` with the device rebuild in a worker thread. The
        rebuild reallocates the whole KV pool — seconds of device work at
        real sizes — and the supervisor shares its event loop with every
        SIBLING scheduler, so running it inline would freeze the exact
        streams the drain just saved. Host bookkeeping stays on the loop
        (asyncio futures must resolve there; the OUT replica receives no
        routing, so its idle loop ticks observe only the consistent
        post-prepare state while the thread rebuilds)."""
        self._revive_prepare()
        ok = await asyncio.to_thread(self._revive_rebuild)
        if not ok:
            return False
        self._revive_commit()
        return True

    def _revive_prepare(self) -> None:
        """Clean-slate host bookkeeping ahead of the rebuild. Idempotent —
        the supervisor re-runs it on every backoff retry."""
        if self.decoding or self.prefilling:
            raise RuntimeError("revive() with live sequences; drain first")
        for job in list(self._prefix_jobs):
            # no device ops (a wedged device is why we're here, exactly
            # the trip path's reasoning): the resets below reclaim the
            # slot and pages wholesale, and the future must resolve
            self._prefix_jobs.remove(job)
            if not job.future.done():
                job.future.set_result(0)
        if self.session_cache is not None:
            self.session_cache.discard_if(
                lambda e: e.prefix_len > 0 or e.prefix_entry is not None
            )
        self._prefixes.clear()
        self.allocator.reset()
        self.free_slots = list(range(self.engine.engine_cfg.max_seqs))
        self._temperature[:] = 0.0
        self._top_p[:] = 1.0
        self._top_k[:] = 0

    def _revive_rebuild(self) -> bool:
        """The device-only half (threadable: touches the engine, not
        scheduler state)."""
        try:
            # armable site: a chaos drill wedging this replica's device
            # keeps revive failing too (a broken device fails its rebuild),
            # so the supervisor backs off instead of rejoining a replica
            # that would immediately re-trip (tests/test_fleet.py)
            inject("engine.rebuild", replica=self.replica_id)
            with Timer(self.metrics, "finchat_engine_rebuild_seconds"):
                self.engine.rebuild_device_state()
        except Exception as e:
            logger.error("revive: engine rebuild failed: %s", e)
            return False
        return True

    def _revive_commit(self) -> None:
        self.gave_up = False
        self._rebuilds_without_success = 0
        for bucket in self._fail_streaks:
            self._fail_streaks[bucket] = 0
        self._breaker_bucket = None
        self._breaker_tripped_at = None
        self.metrics.set_gauge("finchat_breaker_state", 0)
        self.metrics.inc("finchat_engine_rebuilds_total")
        for cb in list(self.on_rebuild):
            try:
                cb()
            except Exception as e:
                logger.error("on_rebuild callback failed: %s", e)
        self._wakeup.set()

    async def _round_failed(self, scope: str, error: str) -> None:
        """A whole-round dispatch failure — not attributable to one
        sequence. Breaker off (``breaker_threshold`` 0): legacy behavior,
        the round's population is evicted with an error. Breaker on: the
        failure streak for the plane ('prefill' or 'decode'; mixed and
        spec ride 'decode') advances — below the threshold the round's
        sequences are recompute-preempted and replay through admission (a
        transient blip costs a re-prefill, not the stream); at the
        threshold the breaker trips and the engine device state is
        rebuilt (async: the rebuild itself runs in a worker thread —
        _trip_breaker). Dispatches are never re-consumed after a failure:
        a partially-consumed step cannot be told apart from an unconsumed
        one, and replay recomputes any undelivered token anyway."""
        self.metrics.inc("finchat_dispatch_failures_total")
        if self.breaker_threshold <= 0:
            if scope in ("prefill", "mixed"):
                self._fail_prefill_round(error)
            if scope in ("decode", "mixed", "spec"):
                for handle in list(self.decoding.values()):
                    self._evict(handle, "error", error=error)
            return
        bucket = "prefill" if scope == "prefill" else "decode"
        self._fail_streaks[bucket] += 1
        if self._fail_streaks[bucket] >= self.breaker_threshold:
            await self._trip_breaker(bucket, error)
            return
        if scope in ("prefill", "mixed"):
            for handle in list(self.prefilling):
                if not self._parked(handle):
                    self._preempt(handle)
            for job in list(self._prefix_jobs):
                try:  # registration is best-effort by contract
                    self._fail_prefix_job(job)
                except Exception as e:
                    logger.error("failing prefix job during %s failure: %s",
                                 scope, e)
        if scope in ("decode", "mixed", "spec"):
            for handle in list(self.decoding.values()):
                self._preempt(handle)

    def _note_round_ok(self, bucket: str) -> None:
        """A dispatch round of ``bucket`` completed: its failure streak
        resets; if this is the plane that tripped the breaker, the
        half-open breaker closes (recovery latency observed from trip to
        here) and the consecutive-rebuild give-up counter clears."""
        self._fail_streaks[bucket] = 0
        if self._breaker_bucket in (None, bucket):
            self._rebuilds_without_success = 0
            self._breaker_bucket = None
            if self._breaker_tripped_at is not None:
                self.metrics.observe(
                    "finchat_breaker_recovery_seconds",
                    time.perf_counter() - self._breaker_tripped_at,
                )
                self._breaker_tripped_at = None
                self.metrics.set_gauge("finchat_breaker_state", 0)

    async def _trip_breaker(self, bucket: str, error: str) -> None:
        """Breaker trip: preempt every live sequence to host, tear down
        and rebuild the engine's device state (weights retained, compiled
        variants still valid — shapes are unchanged), reset the page
        allocator and slot bookkeeping, and drop every cache entry that
        referenced device pages (shared-prefix heads, session entries with
        referenced heads). The next loop iteration is the half-open probe:
        admission re-admits via the recompute path, and the first
        successful round closes the breaker. ``breaker_max_rebuilds``
        consecutive trips without a successful round in between give up
        and fail the in-flight streams — a persistently wedged engine
        must not rebuild-loop forever.

        The rebuild itself runs in a worker thread (the same discipline
        as ``revive_async``): reallocating the KV pool is seconds of
        device work at real sizes, and in a fleet every SIBLING replica
        shares this event loop — an inline rebuild would freeze the very
        streams the drain-on-trip just handed them (ISSUE 8 / finchat-lint
        R1; the pre-PR-8 code did exactly that). All host bookkeeping —
        preempts, drain, cache purge, allocator/slot resets — completes
        BEFORE the await, so concurrent coroutines observe a consistent
        emptied scheduler; ``_rebuilding`` gates the one seam that writes
        device state from outside the loop (register_prefix_async)."""
        self._breaker_bucket = bucket
        self._rebuilds_without_success += 1
        if self._rebuilds_without_success > self.breaker_max_rebuilds:
            # black box for the give-up drill (ISSUE 12): the ring holds
            # the tripped rounds' dispatch spans and the failing streams'
            # lifecycle events at the moment this replica goes OUT
            TRACER.anomaly("replica_give_up", args={
                "plane": bucket, "error": str(error)[:200],
                "replica": self.replica_id,
                "rebuilds": self._rebuilds_without_success - 1,
            })
            if self.drain_sink is not None:
                # fleet give-up (ISSUE 6): the streams survive on siblings
                # — preempt every live sequence to host (prompt+generated
                # kept on the handle) and hand it off, instead of failing
                # it; whatever no sibling can adopt fails the legacy way
                logger.error(
                    "breaker: giving up after %d rebuilds; draining %d live "
                    "sequences to sibling replicas (%s)",
                    self._rebuilds_without_success - 1,
                    len(self.decoding) + len(self.prefilling), error,
                )
                for handle in list(self.decoding.values()) + list(self.prefilling):
                    try:
                        self._preempt(handle, for_rebuild=True)
                    except Exception as e:
                        logger.error("preempting %s at breaker give-up: %s",
                                     handle.seq_id, e)
                self._drain_to_sink()
                # whatever no sibling adopted — preempted live streams,
                # parked holds, AND never-admitted queue entries — fails
                # NOW with the retryable error: this scheduler is going
                # OUT, and leaving queued work here would burn another
                # full fail-streak cycle per handle against a known-wedged
                # engine before its client hears anything
                for handle in list(self.pending):
                    self.pending.remove(handle)
                    # the ONLY site counting drain failures — one increment
                    # per stream the drain couldn't save (sink refusals stay
                    # pending and land here; parked holds were never offered
                    # but their streams fail all the same); fleet-LEVEL
                    # series, unlabeled like the rest of finchat_fleet_*
                    METRICS.inc("finchat_fleet_drain_failures_total")
                    handle.finished = True
                    self._close_span(handle, "replica_out")
                    handle.events.put_nowait({
                        "type": "error", "message": error,
                        "code": "replica_out", "retryable": True,
                    })
                # the queue is empty now — an OUT replica must not export
                # phantom backlog for its whole OUT/RESPAWNING period
                self.metrics.set_gauge("finchat_queue_depth",
                                       len(self.pending))
            else:
                logger.error(
                    "breaker: %d consecutive rebuilds without a successful "
                    "round; failing in-flight streams (%s)",
                    self._rebuilds_without_success - 1, error,
                )
                for handle in list(self.decoding.values()) + list(self.prefilling):
                    try:
                        self._evict(handle, "error", error=error)
                    except Exception as e:
                        logger.error("evicting %s after breaker give-up: %s",
                                     handle.seq_id, e)
            for job in list(self._prefix_jobs):
                try:  # slot + pages must come back even on give-up
                    self._fail_prefix_job(job)
                except Exception as e:
                    logger.error("failing prefix job at breaker give-up: %s", e)
            for bucket in self._fail_streaks:
                self._fail_streaks[bucket] = 0
            # the scheduler keeps serving new admissions (degraded): close
            # the gauge and drop the trip timestamp so a later recovery
            # doesn't record the whole given-up idle period as latency —
            # _rebuilds_without_success deliberately persists, so another
            # trip without an intervening success gives up immediately
            self._breaker_tripped_at = None
            self.metrics.set_gauge("finchat_breaker_state", 0)
            # the supervisor marks this replica OUT, reassigns its routing
            # share, and respawns it in the background (revive)
            self.gave_up = True
            for cb in list(self.on_give_up):
                try:
                    cb()
                except Exception as e:
                    logger.error("on_give_up callback failed: %s", e)
            return
        logger.error("breaker tripped (%s): preempting %d live sequences and "
                     "rebuilding engine device state", error,
                     len(self.decoding) + len(self.prefilling))
        # flight recorder (ISSUE 12): the anomaly event + ring dump capture
        # the tripped rounds' dispatch spans and every live stream's
        # lifecycle up to this instant — the black box for the breaker
        # drill ROBUSTNESS.md scripts. Host bookkeeping only; the dump
        # itself writes in a worker thread.
        TRACER.anomaly("breaker_trip", args={
            "plane": bucket, "error": str(error)[:200],
            "replica": self.replica_id, "dispatch_tally": self._dispatch_tally,
            "live": len(self.decoding) + len(self.prefilling),
        })
        if self._breaker_tripped_at is None:
            self._breaker_tripped_at = time.perf_counter()
        self.metrics.set_gauge("finchat_breaker_state", 1)
        for handle in list(self.decoding.values()):
            self._preempt(handle, for_rebuild=True)
        for handle in list(self.prefilling):
            # parked overlap holds included: their prefix KV is going away,
            # so they re-prefill and park again awaiting extend_prompt
            self._preempt(handle, for_rebuild=True)
        for job in list(self._prefix_jobs):
            # no device ops here (the engine may be wedged): the slot and
            # pages are reclaimed wholesale by the resets below
            self._prefix_jobs.remove(job)
            if not job.future.done():
                job.future.set_result(0)
        # fleet drain-on-trip (ISSUE 6): hand the preempted streams — and
        # their conversations' session-cache host bytes — to sibling
        # replicas NOW, before the purge below drops the entries, so the
        # streams continue elsewhere while this replica rebuilds instead
        # of stalling behind the rebuild. Whatever no sibling adopts stays
        # pending and replays here after the rebuild (PR 5 behavior).
        if self.drain_sink is not None:
            adopted = self._drain_to_sink()
            if adopted:
                logger.info("breaker drain: %d streams adopted by siblings",
                            adopted)
        # caches referencing device pages reference a pool that no longer
        # exists: session entries with a referenced head are purged (their
        # on_drop releases the head refs), then the head entries drop
        if self.session_cache is not None:
            self.session_cache.discard_if(
                lambda e: e.prefix_len > 0 or e.prefix_entry is not None
            )
        self._prefixes.clear()
        # host bookkeeping resets BEFORE the rebuild attempt: the old
        # device pool is discarded either way (rebuild drops it first), so
        # this also reclaims the prefix jobs' pages/slots wholesale — a
        # rebuild failure must not strand them owned by dead registrants
        # and stall admission forever
        self.allocator.reset()
        self.free_slots = list(range(self.engine.engine_cfg.max_seqs))
        self._temperature[:] = 0.0
        self._top_p[:] = 1.0
        self._top_k[:] = 0
        try:
            self._rebuilding = True
            try:
                with Timer(self.metrics, "finchat_engine_rebuild_seconds"):
                    await asyncio.to_thread(self.engine.rebuild_device_state)
            finally:
                self._rebuilding = False
        except Exception as e:
            # rebuild itself failed (device gone?): fail what we hold and
            # leave the breaker open — the next trip retries the rebuild
            logger.error("engine rebuild failed: %s", e)
            for handle in list(self.pending):
                if handle.preempted:
                    self.pending.remove(handle)
                    handle.finished = True
                    self._close_span(handle, "error")
                    handle.events.put_nowait(
                        {"type": "error", "message": f"engine rebuild failed: {e}"}
                    )
            return
        for bucket in self._fail_streaks:
            self._fail_streaks[bucket] = 0
        self.metrics.inc("finchat_engine_rebuilds_total")
        self.metrics.set_gauge("finchat_breaker_state", 2)  # half-open
        for cb in list(self.on_rebuild):
            try:
                cb()
            except Exception as e:
                logger.error("on_rebuild callback failed: %s", e)

    async def _prefill_round(self) -> None:
        """Advance EVERY currently-prefilling sequence one chunk in a single
        batched ``prefill_step`` (one weights-read for the whole round). The
        batch dim is padded to the next power of two (round_up_pow2 — the
        same policy Engine.warmup compiles for) so a burst of admissions
        compiles at most log2(max_seqs) prefill variants, not one per N.

        Long prompts on a ``seq > 1`` mesh take the seq-sharded ring path
        instead (engine.prefill_ring, SURVEY §5.7c) and complete in this
        same round."""
        eng = self.engine
        C = eng.engine_cfg.prefill_chunk
        batch: list[SequenceHandle] = []
        # (handle, device logits row, epoch) triples whose prompt completed
        # this round — the epoch tells a preempted-and-replayed incarnation
        # from the one this round prefilled
        completions: list[tuple[SequenceHandle, object, int]] = []
        for handle in list(self.prefilling):
            if self._parked(handle):
                continue  # awaiting extend_prompt
            try:
                inject("scheduler.prefill", seq_id=handle.seq_id, replica=self.replica_id)
                if self._ring_routed(handle):
                    rc = eng.ring_segment_tokens()
                    if rc == 0:
                        assert handle.prefill_pos == 0  # monolithic never
                        # admits with a prefix hit (see _admit)
                        # monolithic one-shot SP prefill (only when
                        # ring_prefill_chunk=0; both sp_modes chunk now):
                        # in-flight decode streams stall for the whole
                        # seq-sharded prefill — the latency trade the
                        # chunked path below exists to avoid
                        with (TRACER.phase("dispatch", self._phases),
                              Timer(self.metrics, "finchat_prefill_seconds") as _pt):
                            ring_logits = eng.prefill_ring(handle.slot, handle.prompt_ids)
                        self._tally_dispatch("ring")
                        handle.prefill_pos = len(handle.prompt_ids)
                        handle.kv_ctx = handle.prefill_pos
                        if TRACER.enabled:
                            self._trace_dispatch(
                                "ring",
                                [self._rider(handle, "ring")],
                                ts=_pt.started, dur=_pt.elapsed,
                            )
                        completions.append((handle, ring_logits, handle.epoch))
                        continue
                    # chunked ring: ONE segment per round — decode steps
                    # interleave between segments, so one long prompt no
                    # longer freezes every other stream (each segment
                    # folds the cached earlier segments into its ring
                    # attention, engine.prefill_ring_segment)
                    handle.ring_path = True
                    seg = handle.prompt_ids[handle.prefill_pos : handle.prefill_pos + rc]
                    with (TRACER.phase("dispatch", self._phases),
                          Timer(self.metrics, "finchat_prefill_seconds") as _pt):
                        seg_logits = eng.prefill_ring_segment(
                            handle.slot, seg, handle.prefill_pos
                        )
                    self._tally_dispatch("ring_segment")
                    handle.prefill_pos += len(seg)
                    handle.kv_ctx = handle.prefill_pos
                    if TRACER.enabled:
                        self._trace_dispatch(
                            "ring_segment",
                            [self._rider(handle, "ring")],
                            ts=_pt.started, dur=_pt.elapsed,
                        )
                    if handle.prefill_pos >= len(handle.prompt_ids):
                        completions.append((handle, seg_logits, handle.epoch))
                    continue
            except Exception as e:  # per-sequence isolation
                logger.error("prefill error for %s: %s", handle.seq_id, e)
                self._evict(handle, "error", error=str(e))
                continue
            batch.append(handle)

        # chunked prefix registrations (register_prefix_async) ride the
        # same batched step: one chunk per round, no logits needed
        jobs = list(self._prefix_jobs)
        if batch or jobs:
            from finchat_tpu.engine.engine import round_up_pow2

            rows = [(h.slot, h.prompt_ids, h.prefill_pos) for h in batch]
            rows += [(j.slot, j.ids, j.pos) for j in jobs]
            N = round_up_pow2(len(rows))
            tokens, slots, starts, n_valids = self._pack_prefill_rows(
                rows, N, C, self._prefill_room)
            with (TRACER.phase("dispatch", self._phases),
                  Timer(self.metrics, "finchat_prefill_seconds") as _pt):
                # host-side dispatch time for the round (device work is
                # async; steady-state it tracks the round cadence)
                logits = eng.prefill_rows(
                    jnp.asarray(tokens), jnp.asarray(slots),
                    jnp.asarray(starts), jnp.asarray(n_valids),
                )
            self._tally_dispatch("prefill")
            for i, handle in enumerate(batch):
                handle.prefill_pos += int(n_valids[i])
                handle.kv_ctx = handle.prefill_pos
                if handle.prefill_pos >= len(handle.prompt_ids):
                    if handle.held:
                        continue  # park: the first token commits only
                        # after extend_prompt grafts the real prompt end
                    completions.append((handle, logits[i], handle.epoch))
            for i, job in enumerate(jobs, start=len(batch)):
                job.pos += int(n_valids[i])
            if TRACER.enabled:
                riders = [self._rider(h, "prefill") for h in batch]
                riders += [(j.slot, f"prefix:{j.owner}", "prefix", None, j.pos)
                           for j in jobs]
                self._trace_dispatch("prefill", riders,
                                     ts=_pt.started, dur=_pt.elapsed)
            for job in jobs:
                if job.pos >= job.shared_len:
                    self._complete_prefix_job(job, "chunked")

        if not completions:
            return  # dispatch-only round, no host sync needed

        tokens_dev = []
        with TRACER.phase("dispatch", self._phases):
            for h, row_logits, _e in completions:
                h.span.mark("prefill_done")
                s = h.sampling
                eng.state, token = commit_first_token(
                    eng.state, jnp.int32(h.slot), row_logits,
                    jnp.float32(s.temperature), jnp.float32(s.top_p), jnp.int32(s.top_k),
                )
                tokens_dev.append(token)
        # one host fetch for all completions (worker thread keeps loop live)
        fetched, logits_host = await self._fetch(
            lambda: (
                [int(np.asarray(t)) for t in tokens_dev],
                [
                    np.asarray(row_logits) if h.constraint is not None else None
                    for h, row_logits, _e in completions
                ],
            )
        )
        with TRACER.phase("deliver", self._phases):
            for (handle, _lg, epoch), token_id, row_host in zip(completions, fetched, logits_host):
                if handle.finished or handle.epoch != epoch:
                    continue  # cancelled/preempted while fetching
                try:
                    if handle.constraint is not None:
                        token_id = self._constrained_pick(handle, row_host)
                    self.prefilling.remove(handle)
                    self.decoding[handle.slot] = handle
                    self._deliver(handle, int(token_id))
                except Exception as e:  # per-sequence isolation (host-side pick
                    # or delivery error must not fail the other sequences)
                    logger.error("prefill completion error for %s: %s", handle.seq_id, e)
                    self._evict(handle, "error", error=str(e))

    @staticmethod
    def _pack_prefill_rows(rows, N: int, C: int, room=lambda pos: 1 << 30):
        """Ragged row arrays for a chunked split-path round
        (_prefill_round; the packed ragged round builds its own buffer):
        one chunk per ``(slot, ids, pos)`` row; padding rows carry the
        first row's slot with ``n_valid 0`` → trash writes."""
        tokens = np.zeros((N, C), np.int32)
        slots = np.zeros((N,), np.int32)
        starts = np.zeros((N,), np.int32)
        n_valids = np.zeros((N,), np.int32)
        slots[:] = rows[0][0]
        for i, (slot, ids, pos) in enumerate(rows):
            chunk = ids[pos : pos + min(C, room(pos))]
            tokens[i, : len(chunk)] = chunk
            slots[i] = slot
            starts[i] = pos
            n_valids[i] = len(chunk)
        return tokens, slots, starts, n_valids

    def _complete_prefix_job(self, job: _PrefixJob, how: str) -> None:
        """A chunked prefix registration finished its last chunk: publish
        the entry, return the engine slot, resolve the caller's future
        (shared by both round paths — they must stay in lock-step)."""
        self._prefix_jobs.remove(job)
        ssm_snap = self._head_snapshot(job.slot)
        self.engine.reset_slot(job.slot)
        self.free_slots.append(job.slot)
        self._prefixes.append(
            _PrefixEntry(job.ids, job.pages, job.shared_len, job.owner,
                         ssm_snap=ssm_snap)
        )
        logger.info(
            "prefix cache: registered %d shared tokens (%d pages, %s)",
            job.shared_len, len(job.pages), how,
        )
        if not job.future.done():
            job.future.set_result(job.shared_len)

    def _fail_prefill_round(self, error: str) -> None:
        """A whole-round prefill failure is not attributable to one
        sequence: fail everything that was IN the dispatch. Parked overlap
        holds whose prefix already finished were skipped from the round
        (they are awaiting extend_prompt, not prefilling), so they must
        survive — the pre-fix behavior evicted them too, failing in-flight
        retrieval overlaps that never touched the failed dispatch."""
        for handle in list(self.prefilling):
            if self._parked(handle):
                continue  # not in the failed round
            self._evict(handle, "error", error=error)
        for job in list(self._prefix_jobs):
            self._fail_prefix_job(job)

    # every label the demotion counter can emit — pre-seeded to 0 at
    # construction so the whole family renders even when (by design, the
    # ISSUE 10 point) spec / constrained never fire again
    MIXED_DEMOTION_REASONS = ("spec", "constrained", "ring", "other")

    def _use_mixed(self) -> bool:
        """Can this iteration run ONE packed ragged dispatch instead of a
        prefill round plus a decode-side dispatch? Both populations must
        exist — and that is now the ONLY condition. The ragged rebuild
        (ISSUE 10) folded spec verify blocks and grammar-constrained
        picks into rows of the packed buffer; ring/
        seq-sharded prefill — the last demotion reason — is promoted too
        (ISSUE 15): a ring-routed prompt rides the packed round as
        ordinary bounded-size chunk rows, where the ragged kernel's
        per-page online-softmax accumulation IS the ring fold's carry
        (ops/ring_attention.py ``ring_attention_with_prefix`` — each chunk
        folds the cached earlier segments page by page), and a
        prefill_chunk-sized row bounds activation memory the way the
        segmented ring schedule did. ``finchat_mixed_demotions_total``
        stays pre-seeded per reason — INCLUDING reason="ring" — so the
        complete erasure is observable (tests/test_mixed_step.py and
        tests/test_bounded_kv.py hold it at zero). The split path — where
        ring-routed rows still run their seq-sharded collective schedule
        when no decode coexists — stays the golden-identical fallback."""
        if not self.mixed_enabled or not self.decoding:
            return False
        rows = [h for h in self.prefilling if not self._parked(h)]
        if not rows and not self._prefix_jobs:
            return False
        return True

    async def _ragged_round(self) -> None:  # finchat-lint: hot
        """Advance EVERY serving population in a single packed ragged
        dispatch (ISSUE 10; engine.ragged_mixed_step over
        ops/ragged_paged_attention.py): prefilling sequences a chunk each,
        plain decode slots a token, grammar-constrained slots a token with
        their logits row returned for the host pick and spec-eligible
        slots a (1+Kd)-token verify block — one model dispatch, one host
        fetch. PR 4's padded mixed step demoted the whole iteration to the
        serialized split path whenever any of those features was live —
        exactly the mix a loaded engine runs; now only ring/seq-sharded
        prefill demotes (_use_mixed). Prefill rows whose prompt completes
        sample their first token on-device in the same dispatch
        (greedy-identical to commit_first_token)."""
        eng = self.engine
        C = eng.engine_cfg.prefill_chunk
        B = eng.engine_cfg.max_seqs
        Kd = self.spec_k
        spec_on = Kd > 0 and self._spec_cooldown == 0
        batch: list[SequenceHandle] = []
        for handle in list(self.prefilling):
            if self._parked(handle):
                continue  # awaiting extend_prompt
            try:
                inject("scheduler.prefill", seq_id=handle.seq_id, replica=self.replica_id)
            except Exception as e:  # per-sequence isolation, as in the split path
                logger.error("prefill error for %s: %s", handle.seq_id, e)
                self._evict(handle, "error", error=str(e))
                continue
            batch.append(handle)
        jobs = list(self._prefix_jobs)
        decode_members = [
            (slot, h, h.epoch) for slot, h in self.decoding.items()
        ]
        if (not batch and not jobs) or not decode_members:
            return  # a fault above drained one side; split paths resume next tick
        inject("scheduler.decode", replica=self.replica_id)
        # mixed-specific armable site (ISSUE 5 satellite): targets ONLY the
        # unified dispatch, so tests can fail the fused round while the
        # split fallback paths stay healthy
        inject("scheduler.mixed", replica=self.replica_id)
        from finchat_tpu.engine.spec import NgramIndex

        # one row per live slot (prefill handles, prefix jobs, decode
        # slots all hold distinct engine slots, so rows <= max_seqs); the
        # descriptor arrays are fixed [max_seqs] — only the packed-token
        # bucket varies the compiled shape
        R = B
        row_slot = np.zeros((R,), np.int32)
        row_start = np.zeros((R,), np.int32)
        row_len = np.zeros((R,), np.int32)
        row_from_device = np.zeros((R,), bool)
        row_arm = np.zeros((R,), bool)
        row_n_drafts = np.zeros((R,), np.int32)
        temp = np.zeros((R,), np.float32)
        top_p = np.ones((R,), np.float32)
        top_k = np.zeros((R,), np.int32)
        packed: list[int] = []
        tok_row: list[int] = []

        completions: list[tuple[int, SequenceHandle, int]] = []  # (row, h, epoch)
        prefill_rows: list[tuple[int, SequenceHandle]] = []
        job_rows: list[tuple[int, _PrefixJob]] = []
        plain_rows: list[tuple[int, int, SequenceHandle, int]] = []
        spec_rows: list[tuple[int, int, SequenceHandle, int]] = []
        pair_rows: list[tuple[int, int, SequenceHandle, int]] = []  # a model that drafts
        constrained_decode: list[tuple[int, int, SequenceHandle, int]] = []
        constrained_rows: list[int] = []  # row indices whose logits the host needs
        spec_consulted = False

        i = 0
        for h in batch:
            chunk = self._chunk(h.prompt_ids, h.prefill_pos, C)
            row_slot[i] = h.slot
            row_start[i] = h.prefill_pos
            row_len[i] = len(chunk)
            packed += chunk
            tok_row += [i] * len(chunk)
            if not h.held and h.prefill_pos + len(chunk) >= len(h.prompt_ids):
                # prompt completes this chunk: arm the row so its first
                # token samples on-device with the sequence's own params
                # (constrained completions keep the non-truncating
                # defaults — the host pick replaces the sample, and a
                # truncating top_p/top_k would knock the whole packed
                # batch off the sampler's exact full-vocab fast path)
                row_arm[i] = True
                completions.append((i, h, h.epoch))
                if h.constraint is not None:
                    constrained_rows.append(i)
                else:
                    s = h.sampling
                    temp[i], top_p[i], top_k[i] = s.temperature, s.top_p, s.top_k
            prefill_rows.append((i, h))
            i += 1
        for job in jobs:
            chunk = self._chunk(job.ids, job.pos, C)
            row_slot[i] = job.slot
            row_start[i] = job.pos
            row_len[i] = len(chunk)
            packed += chunk
            tok_row += [i] * len(chunk)
            job_rows.append((i, job))
            i += 1
        for slot, h, epoch in decode_members:
            row_slot[i] = slot
            row_from_device[i] = True
            row_arm[i] = True
            if h.constraint is not None:
                # host-side grammar pick from this row's returned logits
                # (the depth-1 round consumes within the iteration, so the
                # pick lands before the slot's next dispatch); sampling
                # params stay the non-truncating defaults
                row_len[i] = 1
                packed.append(0)
                tok_row.append(i)
                constrained_rows.append(i)
                constrained_decode.append((i, slot, h, epoch))
                h.kv_ctx += 1
                i += 1
                continue
            prop: list[int] = []
            if spec_on and self._spec_eligible(h):
                spec_consulted = True
                if h.ngram_index is None:  # one-time build; _deliver
                    h.ngram_index = NgramIndex(h.history)  # keeps it in sync
                remaining = h.sampling.max_new_tokens - h.generated
                # bounded rows: the (1 + drafts) verify span must fit the
                # eviction-boundary room (see _bounded_span_room)
                cap = min(Kd, remaining - 1, self._bounded_span_room(h) - 1)
                prop = h.ngram_index.propose(cap) if cap > 0 else []
            s = h.sampling
            temp[i], top_p[i], top_k[i] = s.temperature, s.top_p, s.top_k
            if self._drafts and s.max_new_tokens - h.generated >= 2:
                # the model's own draft: [device last_token, device draft] — both
                # read on the device, verified there (engine._ragged_draft_tail)
                row_len[i], row_n_drafts[i] = 2, 1
                packed += [0, 0]
                tok_row += [i, i]
                pair_rows.append((i, slot, h, epoch))
                h.kv_ctx += 1  # (a kept draft's advance lands at consume)
                i += 1
                continue
            if prop:
                # spec verify row: [device last_token, d1..dKd'] — the
                # drafts ride the packed buffer; acceptance on device
                row_len[i] = 1 + len(prop)
                row_n_drafts[i] = len(prop)
                packed.append(0)
                tok_row.append(i)
                packed += [int(t) for t in prop]
                tok_row += [i] * len(prop)
                spec_rows.append((i, slot, h, epoch))
                # context advances by n_emitted (>= 1) — the extra
                # accepted tokens land on kv_ctx at consume (depth-1)
                h.kv_ctx += 1
            else:
                row_len[i] = 1
                packed.append(0)
                tok_row.append(i)
                plain_rows.append((i, slot, h, epoch))
                h.kv_ctx += 1
            i += 1

        T = eng.ragged_bucket(len(packed))
        packed += [0] * (T - len(packed))
        tok_row += [R] * (T - len(tok_row))
        with (TRACER.phase("dispatch", self._phases),
              Timer(self.metrics, "finchat_mixed_step_seconds") as _mt):
            emitted_dev, n_em_dev, row_logits_dev = eng.ragged_round(
                jnp.asarray(np.asarray(packed, np.int32)),
                jnp.asarray(np.asarray(tok_row, np.int32)),
                jnp.asarray(row_slot), jnp.asarray(row_start),
                jnp.asarray(row_len), jnp.asarray(row_from_device),
                jnp.asarray(row_arm), jnp.asarray(row_n_drafts),
                jnp.asarray(temp), jnp.asarray(top_p), jnp.asarray(top_k),
            )
        self._tally_dispatch("ragged")
        # prefill bookkeeping happens at dispatch: row_len is host data
        for idx, h in prefill_rows:
            h.prefill_pos += int(row_len[idx])
            h.kv_ctx = h.prefill_pos
        for idx, job in job_rows:
            job.pos += int(row_len[idx])
        if TRACER.enabled:
            # dispatch span piggybacking on the round's own row
            # bookkeeping (ISSUE 12): every (slot, trace, mode) row that
            # rode this one ragged dispatch, from host data only
            riders = [self._rider(h, "prefill") for _i, h in prefill_rows]
            riders += [(j.slot, f"prefix:{j.owner}", "prefix", None, j.pos)
                       for _i, j in job_rows]
            riders += [self._rider(h, "constrained")
                       for _i, _slot, h, _e in constrained_decode]
            riders += [self._rider(h, "decode") for _i, _slot, h, _e in plain_rows]
            riders += [self._rider(h, "spec", int(row_n_drafts[i]))
                       for i, _slot, h, _e in spec_rows]
            riders += [self._rider(h, "decode", 1) for _i, _slot, h, _e in pair_rows]
            self._trace_dispatch("ragged", riders,
                                 ts=_mt.started, dur=_mt.elapsed)
        for _idx, job in job_rows:
            if job.pos >= job.shared_len:
                self._complete_prefix_job(job, "ragged")
        logits_sel = None
        if constrained_rows:
            # only the constrained rows' logits cross to host — a device
            # slice [n, vocab], exactly the _dispatch_decode discipline
            logits_sel = eng.logits_rows(row_logits_dev, constrained_rows)
        # ONE host fetch serves decode tokens, spec acceptances, first
        # tokens and the constrained rows' logits (worker thread keeps the
        # event loop live)
        emitted, n_emitted, logits_host = await self._fetch(
            lambda: (
                np.asarray(emitted_dev), np.asarray(n_em_dev),
                np.asarray(logits_sel) if logits_sel is not None else None,
            )
        )
        with TRACER.phase("deliver", self._phases):
            for idx, handle, epoch in completions:
                if handle.finished or handle.epoch != epoch:
                    continue  # cancelled/preempted while fetching
                handle.span.mark("prefill_done")
                try:
                    if handle.constraint is not None:
                        token = self._constrained_pick(
                            handle, logits_host[constrained_rows.index(idx)]
                        )
                    else:
                        token = int(emitted[idx, 0])
                    self.prefilling.remove(handle)
                    self.decoding[handle.slot] = handle
                    self._deliver(handle, int(token))
                except Exception as e:  # per-sequence isolation
                    logger.error("prefill completion error for %s: %s", handle.seq_id, e)
                    self._evict(handle, "error", error=str(e))
            for idx, slot, handle, epoch in constrained_decode:
                if handle.finished or handle.slot != slot or handle.epoch != epoch:
                    continue  # evicted/cancelled/preempted since dispatch
                token = self._constrained_pick(
                    handle, logits_host[constrained_rows.index(idx)]
                )
                self._deliver(handle, token)
            for idx, slot, handle, epoch in plain_rows:
                if handle.finished or handle.slot != slot or handle.epoch != epoch:
                    continue
                self._deliver(handle, int(emitted[idx, 0]))
            for idx, slot, handle, epoch in pair_rows:
                if handle.finished or handle.slot != slot or handle.epoch != epoch:
                    continue
                self._deliver_pair(handle, emitted[idx])
            if self._drafts:
                self.metrics.inc("finchat_decode_row_steps_total", len(decode_members))
            accepted_total = 0
            for idx, slot, handle, epoch in spec_rows:
                if handle.finished or handle.slot != slot or handle.epoch != epoch:
                    continue
                n = int(n_emitted[idx])
                handle.kv_ctx += max(0, n - 1)  # accepted drafts' context advance
                accepted_total += max(0, n - 1)
                for token in emitted[idx, :n]:
                    self._deliver(handle, int(token))
                    if handle.finished:  # EOS / length inside the prefix
                        break
            if accepted_total:
                self.metrics.inc("finchat_spec_tokens_accepted_total", accepted_total)
            if spec_consulted:
                # the all-miss demotion bookkeeping keeps its split-path
                # cadence: a ragged round where every proposal missed (or
                # nothing was accepted) advances the streak
                self._spec_note_step(accepted=accepted_total)

    def _deliver(self, handle: SequenceHandle, token_id: int) -> None:
        now = time.perf_counter()
        if handle.last_token_at is not None:
            # the instrument behind the mixed step's admission-stall win
            # (ISSUE 4): inter-token gaps split by whether this iteration
            # also ran prefill work (admission) or not (steady decode).
            # Deliberately stamped at CONSUME time, not dispatch time: the
            # loop awaits the prefill round BEFORE consuming the in-flight
            # step, so a gap ending at this delivery spans the consuming
            # iteration's prefill work — a step dispatched in steady
            # decode but delivered behind an admission's prefill round WAS
            # stretched by it, and must land in the "yes" series
            self.metrics.observe(
                "finchat_inter_token_seconds", now - handle.last_token_at,
                labels={"prefill_concurrent": "yes" if self._iter_ran_prefill else "no"},
                trace_id=handle.trace_id,
            )
        handle.last_token_at = now
        handle._emit_first_token_metrics()
        handle.generated += 1
        handle.history.append(token_id)
        if handle.ngram_index is not None:
            handle.ngram_index.push(token_id)
        self.metrics.inc("finchat_tokens_generated_total")
        if token_id == self.eos_id:
            self._retire(handle, "eos")
        elif handle.generated >= handle.sampling.max_new_tokens:
            handle.events.put_nowait({"type": "token", "token_id": token_id})
            self._retire(handle, "length")
        else:
            handle.events.put_nowait({"type": "token", "token_id": token_id})

    def _dispatch_decode(self, exclude: set[int] = frozenset()) -> _InFlightStep:
        """Enqueue one decode step on the device; returns without syncing.

        ``exclude`` slots ride the step INACTIVE (KV writes trash-redirected,
        ``context_lens`` frozen, no token delivered) — used for
        grammar-constrained slots whose host-side pick from the previous
        step has not landed yet, so unconstrained streams keep the depth-2
        pipeline cadence while a tool decision is in flight."""
        inject("scheduler.decode", replica=self.replica_id)
        eng = self.engine
        B = eng.engine_cfg.max_seqs
        active = np.zeros((B,), bool)
        draft_ok = np.zeros((B,), bool)
        members = []
        for slot, handle in self.decoding.items():
            if slot in exclude:
                continue
            active[slot] = True
            # a model that drafts: a grammar-constrained row (its token is the
            # host's pick) and a row with one token left ride draft-free
            draft_ok[slot] = (self._drafts and handle.constraint is None
                              and handle.sampling.max_new_tokens - handle.generated >= 2)
            members.append((slot, handle, handle.epoch))
            handle.kv_ctx += 1
        # step logits come back to host only while a grammar-constrained
        # sequence is IN this step (a second compiled decode variant), and
        # only the constrained rows are transferred — a [n, vocab] device
        # slice, not the whole batch's [B, vocab].
        constrained_slots = sorted(
            slot for slot, h, _e in members if h.constraint is not None
        )
        need_logits = bool(constrained_slots)
        with TRACER.phase("dispatch", self._phases):
            result = eng.decode(
                jnp.asarray(active),
                jnp.asarray(self._temperature),
                jnp.asarray(self._top_p),
                jnp.asarray(self._top_k),
                return_logits=need_logits,
                **({"draft_ok": jnp.asarray(draft_ok)} if self._drafts else {}),
            )
        self._tally_dispatch("decode")
        if TRACER.enabled:
            # (a row that may verify a draft is two tokens wide: it reads one row more)
            self._trace_dispatch(
                "decode",
                [self._rider(h, "decode", int(draft_ok[slot])) for slot, h, _e in members],
            )
        next_tokens, logits = result if need_logits else (result, None)
        if logits is not None:
            logits = eng.logits_rows(logits, constrained_slots)
        return _InFlightStep(
            tokens=next_tokens, logits=logits,
            members=members,
            constrained_slots=constrained_slots,
            moe_experts=eng.moe_experts,
        )

    @staticmethod
    def _spec_eligible(handle: SequenceHandle) -> bool:
        """Can this slot benefit from drafts? Greedy, unconstrained, and at
        least 2 tokens to go (a draft needs room for itself + the bonus)."""
        return (
            handle.constraint is None
            and handle.sampling.temperature <= 0.0
            and handle.sampling.max_new_tokens - handle.generated >= 2
        )

    def _spec_candidates(self) -> bool:
        """True when at least one decoding slot can benefit from a verify
        step — otherwise the pipelined depth-2 decode path is strictly
        better."""
        return any(self._spec_eligible(h) for h in self.decoding.values())

    def _constrained_pick(self, handle: SequenceHandle, row_logits) -> int:
        """Host-side grammar pick for one constrained slot: choose the
        token, write it back as the slot's next decode input, and return
        it for delivery. The ONE place the pick's sampling arguments are
        threaded (called from prefill completion, pipelined consume, and
        the spec path)."""
        s = handle.sampling
        token = handle.constraint.pick(
            row_logits, s.temperature, self._rng,
            remaining=s.max_new_tokens - handle.generated,
            top_p=s.top_p, top_k=s.top_k,
        )
        self.engine.set_last_token(handle.slot, token)
        return token

    def _spec_note_step(self, *, accepted: int) -> None:
        """Track the zero-accept streak behind the spec path's demotion:
        SPEC_MISS_DEMOTE consecutive steps with no accepted draft tokens
        put the loop back on the pipelined depth-2 path for
        SPEC_RETRY_EVERY steps (the depth-1 verify cadence only pays for
        itself when drafts land — see class constants)."""
        if accepted > 0:
            self._spec_miss_streak = 0
            return
        self._spec_miss_streak += 1
        if self._spec_miss_streak >= self.SPEC_MISS_DEMOTE:
            self._spec_miss_streak = 0
            self._spec_cooldown = self.SPEC_RETRY_EVERY
            self.metrics.inc("finchat_spec_demotions_total")

    async def _run_spec_step(self) -> None:
        """One speculative verify step: propose drafts from each greedy
        slot's n-gram index, score them all in one forward, deliver the
        accepted prefix + bonus token per slot. Depth-1 by necessity (the
        drafts extend the LAST delivered token); acceptance makes up for
        the lost overlap by committing up to Kd+1 tokens per weights-read.
        """
        from finchat_tpu.engine.spec import NgramIndex

        if not self.decoding:
            return  # consuming the drained pipeline step may have evicted all
        inject("scheduler.decode", replica=self.replica_id)
        eng = self.engine
        B = eng.engine_cfg.max_seqs
        Kd = self.spec_k
        active = np.zeros((B,), bool)
        drafts = np.zeros((B, Kd), np.int32)
        n_drafts = np.zeros((B,), np.int32)
        members = []
        for slot, handle in self.decoding.items():
            active[slot] = True
            members.append((slot, handle, handle.epoch))
            if self._spec_eligible(handle):
                if handle.ngram_index is None:  # one-time build; _deliver
                    handle.ngram_index = NgramIndex(handle.history)  # keeps it in sync
                remaining = handle.sampling.max_new_tokens - handle.generated
                # bounded rows: the verify span must fit the
                # eviction-boundary room (see _bounded_span_room) —
                # computed BEFORE the kv_ctx bump, at the span's start
                cap = min(Kd, remaining - 1,
                          self._bounded_span_room(handle) - 1)
                prop = handle.ngram_index.propose(cap) if cap > 0 else []
                drafts[slot, : len(prop)] = prop
                n_drafts[slot] = len(prop)
        if not n_drafts.any():
            # every candidate missed its n-gram lookup this step: a
            # Kd+1-wide verify forward would cost K× the query compute for
            # an unconditional n_emitted == 1 — run the plain (cheaper,
            # already-warmed) decode step instead (which does its own
            # kv_ctx accounting — bumping here too would double-count
            # this step and skew the eviction schedule off its positions)
            self._spec_note_step(accepted=0)
            await self._consume_step(self._dispatch_decode())
            return
        for _slot, handle, _epoch in members:
            handle.kv_ctx += 1  # the verify's position-0 write

        constrained_slots = sorted(
            slot for slot, h, _e in members if h.constraint is not None
        )
        need_logits = bool(constrained_slots)
        with TRACER.phase("dispatch", self._phases):
            result = eng.decode_spec(
                jnp.asarray(active), jnp.asarray(drafts), jnp.asarray(n_drafts),
                jnp.asarray(self._temperature),
                jnp.asarray(self._top_p),
                jnp.asarray(self._top_k),
                return_logits=need_logits,
            )
        self._tally_dispatch("spec")
        if TRACER.enabled:
            # a verify row reads its context and its own drafts
            self._trace_dispatch("spec", [
                self._rider(h, "spec", int(n_drafts[slot]))
                for slot, h, _e in members
            ])
        emitted, n_emitted, logits = result if need_logits else (*result, None)
        if logits is not None:
            logits = eng.logits_rows(logits, constrained_slots)

        emitted_host, n_emitted_host, logits_host = await self._fetch(
            lambda: (
                np.asarray(emitted),
                np.asarray(n_emitted),
                np.asarray(logits) if logits is not None else None,
            )
        )
        with TRACER.phase("deliver", self._phases):
            accepted_total = 0
            for slot, handle, epoch in members:
                if handle.finished or handle.slot != slot or handle.epoch != epoch:
                    continue  # evicted/cancelled/preempted since dispatch
                if handle.constraint is not None and logits_host is not None:
                    token = self._constrained_pick(
                        handle, logits_host[constrained_slots.index(slot)]
                    )
                    self._deliver(handle, token)
                    continue
                n = int(n_emitted_host[slot])
                handle.kv_ctx += max(0, n - 1)  # accepted drafts' context advance
                accepted_total += max(0, n - 1)
                for token in emitted_host[slot, :n]:
                    self._deliver(handle, int(token))
                    if handle.finished:  # EOS / length inside the prefix
                        break
            if accepted_total:
                self.metrics.inc("finchat_spec_tokens_accepted_total", accepted_total)
            self._spec_note_step(accepted=accepted_total)

    async def _consume_step(self, step: _InFlightStep) -> None:
        """Fetch a dispatched step's tokens (in a worker thread, so the event
        loop keeps serving) and deliver them to the sequences that were in
        the batch when it was dispatched."""
        tokens_host, logits_host, experts = await self._fetch(
            lambda: (
                np.asarray(step.tokens),
                np.asarray(step.logits) if step.logits is not None else None,
                np.asarray(step.moe_experts) if step.moe_experts is not None else None,
            )
        )
        with TRACER.phase("deliver", self._phases):
            if experts is not None:
                touched, read, *selected = (int(count) for count in experts)
                if self.engine.config.moe_sparse:
                    self.metrics.inc("finchat_moe_experts_touched_total", touched)
                    self.metrics.inc("finchat_moe_experts_read_total", read)
                    self.metrics.inc("finchat_moe_layer_steps_total",  # the layers that route
                                     self.engine.config.n_scan_layers
                                     + getattr(self.engine.config, "mtp_layers", 0))
                    self._round_moe_experts = touched, read
                if selected:  # a model with latent attention: the layers that own pages
                    latent_layers = self.engine.config.n_attn_layers
                    self.metrics.inc("finchat_dsa_selected_tokens_total", selected[0])
                    self.metrics.inc("finchat_dsa_row_layer_steps_total",
                                     len(step.members) * latent_layers)
                    self.metrics.inc("finchat_latent_attention_calls_total", latent_layers,
                                     labels={"form": self._latent_form})
                    if self._index_form:
                        self.metrics.inc("finchat_dsa_index_calls_total", latent_layers,
                                         labels={"form": self._index_form})
                    self.metrics.inc("finchat_latent_head_walks_total", latent_layers,
                                     labels={"form": self._head_form})
                    self._round_selected = selected[0]
            for slot, handle, epoch in step.members:
                if handle.finished or handle.slot != slot or handle.epoch != epoch:
                    continue  # evicted/cancelled/preempted since dispatch
                if handle.constraint is not None and logits_host is not None:
                    token = self._constrained_pick(
                        handle, logits_host[step.constrained_slots.index(slot)]
                    )
                    self._deliver(handle, token)
                elif self._drafts:
                    self._deliver_pair(handle, tokens_host[slot])
                else:
                    self._deliver(handle, int(tokens_host[slot]))
            if self._drafts:
                self.metrics.inc("finchat_decode_row_steps_total", len(step.members))

    def _deliver_pair(self, handle: SequenceHandle, pair) -> None:
        """What a row of a model that drafts got from one decode step
        (``engine._draft_decode_step``): its token, and the one behind it where
        the draft was kept — each delivered as any token is, so EOS or the
        budget on the first ends the row there and the second is dropped."""
        first, second = int(pair[0]), int(pair[1])
        if second != -1:  # a draft was verified (-2: and not kept)
            self.metrics.inc("finchat_draft_tokens_proposed_total")
        self._deliver(handle, first)
        if second >= 0:
            self.metrics.inc("finchat_draft_tokens_accepted_total")
            if handle.finished:
                return
            handle.kv_ctx += 1  # the accepted draft's context advance
            self._deliver(handle, second)

    async def _drain_inflight(self, inflight) -> None:
        """Consume an in-flight dispatch OUTSIDE the decode try-block
        (idle drain, pre-mixed drain, pre-preemption drain), converting a
        failure into the whole-round recovery path instead of letting it
        kill the scheduler task. A failed consume is never retried — a
        partially-consumed step cannot be told apart from an unconsumed
        one, and preempt/replay recomputes the undelivered tokens.
        Always returns None (the caller's new ``inflight``)."""
        try:
            await self._consume_step(inflight)
            self._note_round_ok("decode")
        except Exception as e:
            logger.error("in-flight step consume error: %s", e)
            await self._round_failed("decode", str(e))
        return None

    def _close_round(self, *, reopen: bool = True) -> None:  # finchat-lint: hot
        """Book the loop iteration that just ended and start the next one's
        clock (ISSUE 24). An iteration that dispatched or consumed is a
        round: one ``round`` event whose args are the seconds of each phase
        (ROUND_PHASES), the kind of its last dispatch and the dispatch
        tally; the phase counters; and one rate-limited WARNING when it
        took longer than both SLOW_ROUND_FLOOR_S and SLOW_ROUND_MEDIANS
        times the median of the last 256 rounds — what a stalled run
        leaves on standard error. Host clocks only (finchat-lint R2).
        What the process lost outside a device step while the round ran
        rides with it (ISSUE 38): the tracer's running totals of seconds
        spent compiling or loading a program while serving and of seconds
        the process stood frozen, each less what the round before saw —
        ``compile_s`` / ``frozen_s`` in the event's args where non-zero,
        named in the WARNING with the last program compiled.

        Whatever the loop does outside a named phase is ``stage``: the
        iteration runs inside one base ``stage`` phase that the others
        carve their time out of, so a round's phases sum to its length.
        The booking itself runs inside the next iteration's base phase."""
        base = self._base_phase
        if base is not None:
            base.__exit__(None, None, None)
        phases = self._phases.seconds
        dispatched = self._dispatch_tally != self._round_mark
        kind = self._round_kind
        self._phases.reset()
        self._round_mark = self._dispatch_tally
        self._round_kind = "drain"
        self._base_phase = None
        if reopen:
            self._base_phase = TRACER.phase("stage", self._phases)
            self._base_phase.__enter__()
        if base is None or not (dispatched or phases["fetch_wait"] > 0.0):
            return  # an idle turn of the loop is no round
        # the round is its base phase, end to end: nothing in it is unclocked
        started, now = base.started, base.ended
        total = now - started
        experts, self._round_moe_experts = self._round_moe_experts, None
        selected, self._round_selected = self._round_selected, None
        compiled, frozen = TRACER.serving_compile_s, TRACER.frozen_s
        compile_s, frozen_s = compiled - self._seen_compile_s, frozen - self._seen_frozen_s
        self._seen_compile_s, self._seen_frozen_s = compiled, frozen
        if TRACER.enabled:
            args = {**phases, "kind": kind, "n": self._dispatch_tally}
            if experts is not None:  # of the decode step this round delivered
                args["experts_touched"], args["experts_read"] = experts
            if selected is not None:
                args["selected_tokens"] = selected
            if compile_s:
                args["compile_s"] = compile_s
            if frozen_s:
                args["frozen_s"] = frozen_s
            TRACER.event("round", ts=started, dur=total, track=self._trace_track, args=args)
        self.metrics.inc("finchat_rounds_total")
        self.metrics.set_gauge("finchat_batch_occupancy", len(self.decoding))
        for phase, seconds in phases.items():
            if seconds:
                self.metrics.inc("finchat_round_phase_seconds_total", seconds,
                                 labels={"phase": phase})
        if total > SLOW_ROUND_FLOOR_S:  # the only rounds that can be slow
            median = (statistics.median(self._recent_rounds)
                      if self._recent_rounds else 0.0)
            if (total > SLOW_ROUND_MEDIANS * median
                    and now - self._slow_round_logged > SLOW_ROUND_LOG_INTERVAL_S):
                self._slow_round_logged = now
                logger.warning(
                    "slow scheduler round: %.3f s (median of the last %d: "
                    "%.4f s), %s; last dispatch %s, %d decoding rows; "
                    "compiling or loading %.3f s (last program %s), "
                    "process frozen %.3f s",
                    total, len(self._recent_rounds), median,
                    " ".join(f"{k}={v:.3f}" for k, v in phases.items()),
                    kind, len(self.decoding),
                    compile_s, TRACER.last_compiled if compile_s else "none", frozen_s,
                )
        self._recent_rounds.append(total)

    async def _yield(self) -> None:
        """Let every other task of the process run (producers, consumers,
        the agent, the load) — the round's ``yield`` phase."""
        with TRACER.phase("yield", self._phases):
            await asyncio.sleep(0)

    async def _loop(self) -> None:
        logger.info("scheduler loop started (max_seqs=%d)", self.engine.engine_cfg.max_seqs)
        inflight: _InFlightStep | None = None
        phases = self._phases
        phases.reset()  # a loop that died mid-round left its phases open
        self._base_phase = None
        while self._running:
            self._close_round()
            self._reap_stale_holds()
            # attribute the previous coexist iteration's dispatches at the
            # top of EVERY iteration (idle ones included), so the last
            # coexist iteration before a quiet period is still booked
            if self._coexist_mark is not None:
                self.metrics.inc("finchat_coexist_dispatches_total",
                                 self._dispatch_tally - self._coexist_mark)
                self._coexist_mark = None
            # parked holds (prefix prefilled, waiting for extend_prompt)
            # are not work: without the _prefill_work() refinement the
            # loop would busy-spin for the whole retrieval latency
            if not (self.pending or self.decoding or self._prefix_jobs
                    or self._prefill_work()):
                if inflight is not None:  # drain the pipeline before idling
                    self._iter_ran_prefill = False
                    inflight = await self._drain_inflight(inflight)
                    continue
                self._wakeup.clear()
                with TRACER.phase("yield", phases):
                    try:
                        await asyncio.wait_for(self._wakeup.wait(), timeout=0.5)
                    except asyncio.TimeoutError:
                        pass
                continue

            # a drain inside the block books its own phases
            with TRACER.phase("admit", phases):
                try:
                    # page-pressure preemption (ISSUE 5): planned BEFORE any
                    # dispatch and executed only after the in-flight step is
                    # drained, so a freed page can never still be the target of
                    # queued device writes
                    victims = self._preemption_plan()
                    if victims:
                        if inflight is not None:
                            inflight = await self._drain_inflight(inflight)
                            # consuming may have retired slots / freed pages
                            # (or, on a drain failure, preempted the victims
                            # already) — recompute the plan either way
                            victims = self._preemption_plan()
                        cand = self.pending[0].seq_id if self.pending else "?"
                        for victim in victims:
                            logger.info(
                                "page pressure: preempting %s (deadline %.3f) for %s",
                                victim.seq_id, victim.deadline or float("inf"), cand,
                            )
                            self._preempt(victim)
                    self._admit()
                    # bounded-KV eviction wave (ISSUE 15): runs BETWEEN
                    # dispatches — the page-table/gap updates enqueue after
                    # every in-flight program and before this iteration's
                    # dispatch, so device stream order keeps each program
                    # reading the table it was staged against; the freed
                    # pages' next writers are ordered after it too
                    self._bounded_evict_wave()
                except Exception as e:
                    # admission must never kill the loop (e.g. device state
                    # mid-rebuild-failure): log, back off, keep serving what
                    # still runs
                    logger.error("admission error: %s", e)
                    with TRACER.phase("yield", phases):  # a sleep is no work
                        await asyncio.sleep(0.05)

            prefill_active = bool(self._prefix_jobs) or self._prefill_work()
            # label for the inter-token histogram, and the denominator of
            # dispatches per coexist iteration: iterations where prefill
            # work and in-flight decodes
            # coexist are exactly where the ragged step's >=2→1 fusion
            # applies. The mark/attribute pair books every dispatch from a
            # coexist iteration's start to the next accounting point into
            # finchat_coexist_dispatches_total — an exact numerator for
            # dispatches-per-coexist-iteration.
            self._iter_ran_prefill = prefill_active
            if prefill_active and self.decoding:
                self.metrics.inc("finchat_coexist_iterations_total")
                self._coexist_mark = self._dispatch_tally

            if self._spec_cooldown > 0:
                # demoted after sustained all-miss steps: count pipelined
                # steps down to the next spec re-probe
                self._spec_cooldown -= 1

            if self._use_mixed():
                # the mixed path is depth-1 (dispatch + consume
                # within the iteration — the prefill side was synchronous in
                # the split path too): drain any pipelined leftover first
                if inflight is not None:
                    inflight = await self._drain_inflight(inflight)
                if self._use_mixed():  # consuming may have evicted slots
                    try:
                        await self._ragged_round()
                        self._note_round_ok("decode")
                        self._note_round_ok("prefill")
                    except Exception as e:
                        # not attributable to one sequence: the round's
                        # prefill rows AND decode members rode the same
                        # dispatch — recover them together (preempt/replay
                        # under the breaker, legacy eviction without it)
                        logger.error("mixed step error: %s", e)
                        await self._round_failed("mixed", str(e))
                    await self._yield()
                    continue

            # one batched prefill round (all prefilling sequences advance a
            # chunk together), interleaved with decode so TTFT work cannot
            # starve in-flight streams
            if self.prefilling or self._prefix_jobs:
                try:
                    await self._prefill_round()
                    self._note_round_ok("prefill")
                except Exception as e:
                    logger.error("prefill round error: %s", e)
                    await self._round_failed("prefill", str(e))
                try:
                    # a completion flips straight into THIS iteration's
                    # decode dispatch below: its first decode write needs
                    # the wave's capacity guarantee at the advanced
                    # kv_ctx — without it, a completion landing exactly on
                    # a full page list would trash-write its first decode
                    # KV. Idempotent; no-op when no boundary was crossed.
                    with TRACER.phase("admit", phases):
                        self._bounded_evict_wave()
                except Exception as e:
                    logger.error("bounded eviction wave error: %s", e)

            if (
                self.decoding and self.spec_k > 0
                and self._spec_cooldown == 0 and self._spec_candidates()
            ):
                try:
                    # speculative decode is depth-1: constrained picks land
                    # before the next dispatch, so no slot ever sits a step
                    # out. Drain any pipelined step left over from the
                    # depth-2 path before switching modes.
                    if inflight is not None:
                        await self._consume_step(inflight)
                        inflight = None
                    await self._run_spec_step()
                    self._note_round_ok("decode")
                except Exception as e:
                    logger.error("spec decode step error: %s", e)
                    inflight = None
                    await self._round_failed("spec", str(e))
            elif self.decoding:
                try:
                    # a grammar-constrained slot's next input comes from a
                    # host-side pick that lands when its step is CONSUMED —
                    # so such a slot sits out the speculative step dispatched
                    # before that consume (it rejoins the following one,
                    # advancing every other step). Unconstrained slots keep
                    # the full depth-2 cadence throughout (verdict r3 #6).
                    # a decode round counts OK only when a consume actually
                    # succeeded: dispatch-only iterations (inflight was None
                    # right after a failure) must not reset the streak, or a
                    # device whose errors surface at the host FETCH would
                    # oscillate the streak 0↔1 and never trip the breaker
                    consumed = False
                    pending = set(inflight.constrained_slots) if inflight is not None else set()
                    if any(slot not in pending for slot in self.decoding):
                        # depth-2 pipeline: dispatch N+1 (sans pending
                        # constrained slots), then consume N — the device
                        # computes while the host delivers tokens
                        step = self._dispatch_decode(exclude=pending)
                        if inflight is not None:
                            await self._consume_step(inflight)
                            consumed = True
                        inflight = step
                    else:
                        # every decoding slot is waiting on a host pick:
                        # drain, then run depth-1
                        if inflight is not None:
                            await self._consume_step(inflight)
                            inflight = None
                            consumed = True
                        if self.decoding:
                            await self._consume_step(self._dispatch_decode())
                            consumed = True
                    if consumed:
                        self._note_round_ok("decode")
                except Exception as e:
                    # a whole-batch failure is not attributable to one
                    # sequence: recover all in-flight decodes together
                    # (preempt/replay under the breaker, legacy eviction
                    # without it), keep serving. The dropped in-flight
                    # dispatch is never re-consumed — it may be partially
                    # delivered, and replay recomputes the rest anyway.
                    logger.error("decode step error: %s", e)
                    inflight = None
                    await self._round_failed("decode", str(e))
            elif inflight is not None:
                inflight = await self._drain_inflight(inflight)

            await self._yield()
        self._close_round(reopen=False)
        logger.info("scheduler loop stopped")
