"""Application wiring: the Kafka worker loop + HTTP surface.

Behavior parity with the reference ``main.py``:

- lifespan: store connection check → consumer setup → consume task
  (main.py:24-30), plus scheduler startup (new).
- ``GET /health`` → ``{"status": "healthy"}`` (main.py:51-53).
- ``process_message``: context+history fetch (errors drop the message,
  main.py:64-70), stream_with_status fan-out where ONLY ``response_chunk``
  and ``complete`` events reach Kafka (main.py:81-110), flushed error chunk
  on failure (main.py:112-122), post-hoc persistence (main.py:125-129).
- consume loop: per-message watchdog (100 s default — main.py:138) emitting
  the timeout chunk, 10 ms idle sleep, 1 s error backoff (main.py:131-159).
- ``POST /chat`` — the reference's commented-out REST path (main.py:44-49),
  implemented: batch ``llm_agent.query``.
- ``POST /chat/stream`` — SSE stream of the FULL internal event protocol
  (status/retrieval_complete/response_chunk/complete), the "richer consumer"
  SURVEY §2.4 calls for.
- ``GET /metrics`` — Prometheus text (new; SURVEY §5.5).
- Conversation plumbing (new): every chat path assembles its inputs through
  ``_conversation_inputs``, which also threads ``conversation_id`` into the
  agent → generator → scheduler chain as the session-KV-cache key
  (engine/session_cache.py), so a conversation's next turn resumes the KV
  its previous turn already computed.
- Transaction ingestion (new; the reference's upsert pipeline lives outside
  its repo, feeding Qdrant out-of-band — qdrant_tool.py:24-37): both
  ``POST /transactions`` and the ``transaction_upsert`` Kafka topic embed
  rows on-device into the vector index, which snapshots to
  ``vector.persist_path`` so retrieval is not empty-at-boot.
"""

from __future__ import annotations

import asyncio
import gc
import json
import threading
import time
import uuid
from pathlib import Path

import jax

from finchat_tpu.agent.graph import LLMAgent
from finchat_tpu.engine.generator import EngineGenerator, StubGenerator, TextGenerator
from finchat_tpu.engine.engine import InferenceEngine
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
from finchat_tpu.io.kafka import KafkaClient
from finchat_tpu.io.schemas import (
    complete_chunk,
    error_chunk,
    plot_chunk,
    response_chunk,
    timeout_chunk,
)
from finchat_tpu.io.store import ConversationStore, make_store
from finchat_tpu.models.llama import PRESETS, init_params
from finchat_tpu.serve.fleet import LIVE, DedupeRing, EngineFleet, EngineReplica
from finchat_tpu.models.tokenizer import get_tokenizer
from finchat_tpu.serve.http import HTTPServer, Request, Response, StreamingResponse, sse_event
from finchat_tpu.tools.retrieval import TransactionRetriever
from finchat_tpu.utils.config import (
    AI_RESPONSE_TOPIC,
    TRANSACTION_UPSERT_TOPIC,
    USER_MESSAGE_TOPIC,
    AppConfig,
)
from finchat_tpu.utils.logging import get_logger
from finchat_tpu.utils.metrics import METRICS
from finchat_tpu.utils.tracing import TRACER

logger = get_logger(__name__)

_PROMPTS_DIR = Path(__file__).resolve().parent.parent.parent / "prompts"


def load_prompts() -> tuple[str, str]:
    system_prompt = (_PROMPTS_DIR / "system_prompt.txt").read_text()
    tool_prompt = (_PROMPTS_DIR / "tool_prompt.txt").read_text()
    return system_prompt, tool_prompt


def _encode_head(tokenizer, head: str) -> list[int]:
    """Encode a shared prompt head for prefix registration. The final
    encoded token is dropped: a subword tokenizer can merge across the
    head/context string boundary, so the last head token is the only one
    whose identity depends on what follows (the byte tokenizer is
    trivially boundary-stable, but Mixtral serving uses HF BPE). The ONE
    place this boundary rule lives — startup registration and the
    midnight refresh must encode identically or refreshed prefixes would
    silently stop matching."""
    return tokenizer.encode(head, add_bos=True)[:-1]


def register_prompt_prefixes(agent, scheduler, tokenizer) -> set[str]:
    """Prefill each LLM role's constant system head once and share its KV
    across requests (scheduler shared-prefix cache). Returns the
    SUCCESSFULLY registered heads — per head, so one persistently
    failing head (too short for a page, pages exhausted) cannot poison the
    other's registration (see _maybe_refresh_prefix_cache).
    """
    registered: set[str] = set()
    for head in agent.prompt_heads():
        if scheduler.register_prefix(_encode_head(tokenizer, head)) > 0:
            registered.add(head)
    return registered


async def _maybe_refresh_prefix_cache(app: "App") -> None:
    """Re-register the shared prompt heads when they change (midnight date
    rollover): retire the stale prefixes (pages free once the last
    in-flight reference releases) and prefill the fresh heads. Runs from
    the app's periodic checker task — NOT the request path — and registers
    via the scheduler's chunked path (register_prefix_async), so in-flight
    streams keep decoding between head chunks instead of stalling for a
    whole multi-second prefill once a day (VERDICT r4 weak #6)."""
    if not app._prefix_cache_enabled or app.scheduler is None:
        return
    heads = app.agent.prompt_heads()
    if all(h in app._registered_heads for h in heads):
        return  # every current head is live
    tokenizer = getattr(app.agent.tool_generator, "tokenizer", None)
    if tokenizer is None:
        return
    stale = [h for h in app._registered_heads if h not in heads]
    if stale:
        # date rollover: nothing previously registered can match anymore —
        # retire (pages free as in-flight references release) and rebuild
        logger.info("prompt heads changed (date rollover); refreshing prefix cache")
        app.scheduler.retire_prefixes()
        app._registered_heads = set()
    # (re)try only the missing heads; registration is idempotent and cheap
    # on failure, so a persistently failing head retries without churning
    # the successfully registered one
    for head in heads:
        if head in app._registered_heads:
            continue
        if await app.scheduler.register_prefix_async(_encode_head(tokenizer, head)) > 0:
            app._registered_heads.add(head)


async def _prefix_refresh_loop(app: "App") -> None:
    """Periodic freshness checker for the shared-prefix cache. The check
    itself is a few rendered-string comparisons; actual re-registration
    happens at most once a day (date rollover) and runs chunked through
    the scheduler loop. With a fleet, every LIVE replica is checked —
    registration is per device state."""
    while app._running:
        try:
            for target in app._prefix_targets():
                await _maybe_refresh_prefix_cache(target)
        except Exception as e:  # best-effort: the cache is an optimization
            logger.error("prefix cache refresh error: %s", e)
        await asyncio.sleep(app._prefix_refresh_check_s)


class _ReplicaPrefixView:
    """Adapter giving ``_maybe_refresh_prefix_cache`` a per-replica
    target: the single-engine App attribute surface, with the registered
    head set stored ON the replica (shared-head prefill lives in that
    replica's device state, so each replica tracks its own)."""

    def __init__(self, app: "App", rep: EngineReplica):
        self._rep = rep
        self._prefix_cache_enabled = app._prefix_cache_enabled
        self.scheduler = rep.scheduler
        self.agent = rep.agent

    @property
    def _registered_heads(self) -> set:
        return self._rep.registered_heads

    @_registered_heads.setter
    def _registered_heads(self, value: set) -> None:
        self._rep.registered_heads = set(value)


def _make_rebuild_hook(rep: EngineReplica):
    """on_rebuild callback for one fleet replica: the rebuild dropped that
    replica's prefilled heads, so mark them unregistered there (the
    refresh loop re-registers through the chunked path). Keyed so App.start
    can keep the hook idempotent across restarts."""

    def hook() -> None:
        rep.registered_heads.clear()

    hook.key = ("fleet_heads", rep.replica_id)
    return hook


def _load_model_artifacts(cfg: AppConfig) -> tuple:
    """Load everything the engine replicas SHARE — (model config, params,
    tokenizer, mesh). The params tree is immutable jax arrays, so a fleet
    of N replicas costs N KV pools and schedulers, not N copies of the
    weights."""
    config = PRESETS[cfg.model.preset]
    if cfg.model.dtype:
        import dataclasses

        import jax.numpy as jnp

        config = dataclasses.replace(config, dtype=getattr(jnp, cfg.model.dtype))
    tokenizer = get_tokenizer(cfg.model.tokenizer_path)
    if cfg.model.checkpoint_path:
        from finchat_tpu.checkpoints.hf_loader import load_llama_params

        # quantize per-tensor AT LOAD so the full bf16 tree never has to
        # fit in HBM (8B int8/int4 on one 16 GB chip); the engine's own
        # quantize pass is idempotent on the already-quantized leaves
        params = load_llama_params(cfg.model.checkpoint_path, config,
                                   quant=cfg.model.quant,
                                   quant_group=cfg.model.quant_group)
    else:
        logger.warning("no checkpoint configured; using RANDOM weights (preset=%s)", cfg.model.preset)
        if cfg.model.quant:
            from finchat_tpu.models.quant import init_quantized_llama_params

            params = init_quantized_llama_params(
                config, jax.random.key(cfg.model.seed),
                mode=cfg.model.quant, group_size=cfg.model.quant_group,
            )
        else:
            params = init_params(config, jax.random.key(cfg.model.seed))
    from finchat_tpu.parallel.mesh import MeshSpec, build_mesh

    spec = MeshSpec.from_config(cfg.mesh)
    sizes = (spec.data, spec.pipe, spec.seq, spec.expert, spec.model)
    fixed = 1
    for s in sizes:
        if s != -1:
            fixed *= s
    # -1 axes absorb all devices; a fully fixed mesh uses exactly its own
    # product (so e.g. an explicit all-1 config opts out of parallelism even
    # on a multi-chip host, and a 4-chip mesh config works on an 8-chip host)
    n_mesh = jax.device_count() if -1 in sizes else fixed
    mesh = build_mesh(spec, devices=jax.devices()[:n_mesh]) if n_mesh > 1 else None
    return config, params, tokenizer, mesh


def make_engine_replica(
    cfg: AppConfig, artifacts: tuple, replica_id: str | None = None,
    fabric=None,
) -> tuple[EngineGenerator, ContinuousBatchingScheduler]:
    """One engine replica over the shared artifacts: its own KV page pool
    (InferenceEngine device state), scheduler, and session cache. A
    ``replica_id`` routes the scheduler's metrics through a labeled view
    (every metric family per replica) and stamps its fault-injection
    sites. ``fabric`` (engine/warm_fabric.py — ISSUE 17) makes the
    replica's session tier the fleet-shared one and lets its shared
    prompt heads restore from / publish to the cluster-wide store."""
    config, params, tokenizer, mesh = artifacts
    # what moves a row between engines moves its pages by id: never a mixer's
    # recurrent state or a window layer's pages (session, handoff and pod wire
    # formats hold neither), and a
    # latent model's pages (a latent row and an index key a token) have been
    # carried by the RAM session tier alone. Refused by name rather than
    # served from a state of zero or through an unproven record (the
    # scheduler refuses the warm fabric, and a latent model's disk records)
    one_engine = (
        f"a model with recurrent state ({config.n_state_layers} layers)" if config.has_state
        else "a model with latent attention (latent pages)" if config.kv_lora_rank
        else f"a model with sliding-window layers ({config.n_window_layers} layers' window pages)"
        if config.window else None)
    if one_engine:
        from finchat_tpu.serve.disagg import parse_roles

        refused = {
            "fleet.replicas": cfg.fleet.replicas > 1,
            "fleet.roles": any(r != "mixed" for r in parse_roles(
                cfg.fleet.roles, max(cfg.fleet.replicas, 1))),
            "pod.host_id": bool(cfg.pod.host_id),
        }
        named = [option for option, on in refused.items() if on]
        if named:
            raise ValueError(
                f"{one_engine} is served by one engine; not supported with it: "
                f"{', '.join(named)}")
    metrics = METRICS.labeled(replica=replica_id) if replica_id is not None else None
    with TRACER.startup_phase("engine_init"):
        engine = InferenceEngine(config, params, cfg.engine, mesh=mesh,
                                 quant=cfg.model.quant,
                                 quant_group=cfg.model.quant_group)
    if cfg.engine.warmup_on_start:
        with TRACER.startup_phase("warmup"):
            _on_a_fresh_stack(engine.warmup)
    scheduler = ContinuousBatchingScheduler(
        engine, eos_id=tokenizer.eos_id, metrics=metrics,
        replica_id=replica_id, fabric=fabric,
    )
    return EngineGenerator(scheduler, tokenizer), scheduler


def _roomy_caller(slots: int = 66_000):
    """``call(fn)`` → ``fn()``, from a function with ``slots`` local names
    it never binds: its frame alone is over half a MiB, so CPython gives it a
    frame chunk of 1 MiB, and the frames of whatever ``fn`` calls — a couple
    of thousand deep — sit in that ONE chunk behind it."""
    names = " = ".join(f"v{i}" for i in range(slots))
    source = f"def call(fn):\n    if fn is None:\n        {names} = None\n    return fn()\n"
    scope: dict = {}
    exec(compile(source, "<one frame chunk>", "exec"), scope)  # noqa: S102 -- generated from a number, above
    return scope["call"]


def _on_a_fresh_stack(fn):
    """Run ``fn`` to its end on a thread of its own, its frames in one
    chunk; return what it returned, raise what it raised.

    Warm-up is tracing and lowering in Python (PERF.md §6, PR 38). CPython
    3.11 / 3.12 keeps a thread's frames in 16 KiB chunks and frees a chunk as
    its first frame returns, so a call made where a chunk happens to end
    allocates and frees one each time: about 100 x the cost of the call
    (``benchmarks/frame_chunk_cliff.py``). Where a chunk ends is set by every
    frame BELOW — the entry script, asyncio, ``build_app``'s locals — and
    when it fell among JAX's per-equation lowering calls Granite's warm
    warm-up took 162-171 s instead of 127-131 (one more ``with`` in this file
    did it, and so did starting the parent through ``python3 -c``). A thread
    of its own starts from an empty chunk whoever called ``build_app``
    (Granite 66-69 s); alone it only moves the cliff somewhere else
    (Mistral 28 s where the caller's thread read 20.6), so the work runs
    above ``_roomy_caller``'s frame, where no chunk ends within reach."""
    result: list = []

    def run() -> None:
        try:
            result.append((_roomy_caller()(fn), None))
        except BaseException as e:  # handed to the caller, below
            result.append((None, e))

    thread = threading.Thread(target=run, name="finchat-warmup")
    thread.start()
    thread.join()
    value, error = result[0]
    if error is not None:
        raise error
    return value


def make_warm_fabric(cfg: AppConfig):
    """The process's warm-state fabric per config, or None. Best-effort:
    an unusable path logs and serves without the fabric rather than
    failing assembly (the per-replica PR 7 layout still applies)."""
    if not (cfg.fabric.enabled and cfg.fabric.path):
        if cfg.fabric.enabled:
            logger.warning("fabric.enabled is set but fabric.path is empty; "
                           "warm-state fabric stays off")
        return None
    from finchat_tpu.engine.warm_fabric import WarmFabric

    try:
        return WarmFabric(cfg.fabric.path, cfg.engine.session_cache_disk_bytes,
                          kv_quant=cfg.engine.kv_quant)
    except Exception as e:
        logger.error("warm-state fabric unavailable at %s: %s",
                     cfg.fabric.path, e)
        return None


def build_generators(cfg: AppConfig, fabric=None) -> tuple[TextGenerator, TextGenerator, ContinuousBatchingScheduler | None, object]:
    """Construct (tool_generator, response_generator, scheduler, tokenizer).

    ``model.preset == "stub"`` wires canned generators (dev/no-TPU); anything
    else builds the TPU engine with one shared continuous-batching scheduler
    serving both agent roles.
    """
    if cfg.model.preset == "stub":
        stub = StubGenerator(default="I'm Penny, here to help with your finances.")
        return stub, stub, None, get_tokenizer()
    with TRACER.startup_phase("artifacts"):
        artifacts = _load_model_artifacts(cfg)
    generator, scheduler = make_engine_replica(cfg, artifacts, fabric=fabric)
    return generator, generator, scheduler, artifacts[2]


class App:
    """One worker process: HTTP surface + Kafka consume loop + engine."""

    def __init__(self, cfg: AppConfig, *, agent: LLMAgent, store: ConversationStore,
                 kafka: KafkaClient, scheduler: ContinuousBatchingScheduler | None = None,
                 retriever: TransactionRetriever | None = None,
                 fleet: EngineFleet | None = None):
        self.cfg = cfg
        self.agent = agent
        self.store = store
        self.kafka = kafka
        self.scheduler = scheduler
        # engine fleet (serve/fleet.py; ISSUE 6): when set, every chat path
        # routes its conversation to a replica via _agent_for — ``agent``/
        # ``scheduler`` remain replica 0's for the single-engine surface
        # (tests, dev) and are managed THROUGH the fleet lifecycle
        self.fleet = fleet
        self.retriever = retriever
        self.server = HTTPServer(cfg.serve.host, cfg.serve.port)
        self.server.route("GET", "/health", self.health)
        self.server.route("GET", "/metrics", self.metrics)
        # end-to-end request tracing (utils/tracing.py — ISSUE 12): one
        # request's correlated Kafka-ingress→dispatch timeline as Chrome
        # trace-event JSON (open in Perfetto)
        self.server.route_prefix("GET", "/debug/trace/", self.debug_trace)
        self.server.route("POST", "/chat", self.chat)
        self.server.route("POST", "/chat/stream", self.chat_stream)
        self.server.route("POST", "/transactions", self.upsert_transactions)
        self._consume_task: asyncio.Task | None = None
        self._running = False
        self._tracing_serving = False  # between start and stop (TRACER's stage)
        # Kafka-driven concurrency: one task per in-flight message so many
        # conversations batch onto the engine together, with a per-
        # conversation ordering chain (same conversation stays serial —
        # the guarantee the reference gets from partition keying + serial
        # processing, main.py:96/138)
        self._inflight: set[asyncio.Task] = set()
        self._conv_tails: dict[str, asyncio.Task] = {}
        # shared-prefix cache freshness: the registered heads embed today's
        # date, so they go stale at midnight — _maybe_refresh_prefix_cache
        # compares and re-registers on the request paths. build_app fills
        # _registered_heads with what actually registered.
        self._prefix_cache_enabled = cfg.engine.prefix_cache and scheduler is not None
        self._registered_heads: set[str] = set()
        self._prefix_refresh_check_s = 60.0
        self._prefix_refresh_task: asyncio.Task | None = None
        # at-least-once bookkeeping (kafka.commit_after_process): offsets
        # commit only at the CONTIGUOUS-completion watermark per partition
        # — committing a bare message offset would implicitly commit every
        # earlier message still in flight on that partition — plus a
        # bounded message_id dedupe ring so redelivery after a crash
        # doesn't double-answer a conversation
        self._commit_enabled = cfg.kafka.commit_after_process
        self._done_offsets: dict[tuple[str, int], set[int]] = {}
        self._commit_next: dict[tuple[str, int], int] = {}
        # answered-message_id dedupe lives at the ROUTER level (the fleet's
        # ring when one exists): a replica crash plus Kafka redelivery to a
        # sibling replica consults the same ring the original answer was
        # recorded in, so it cannot double-answer (ISSUE 6 satellite —
        # closes the per-replica hole PR 5 documented)
        # ring size's single source of truth is the DedupeRing default,
        # so the fleet's shared ring and this one can never drift
        self._dedupe = fleet.dedupe if fleet is not None else DedupeRing()
        # answered-message journal (io/journal.py — ISSUE 7): answered ids
        # fsync to disk BEFORE their Kafka offset commits, and a restart
        # replays them into the ring, so crash + redelivery cannot
        # double-answer. Failed ids are never journaled (see _done).
        self._journal = None
        if cfg.journal.path:
            from finchat_tpu.io.journal import AnsweredJournal

            try:
                self._journal = AnsweredJournal(
                    cfg.journal.path, fsync=cfg.journal.fsync,
                    keep=self._dedupe.size,
                    num_partitions=getattr(kafka, "num_partitions", 1),
                )
                self._dedupe.preload(self._journal.replay())
            except Exception as e:  # durability is best-effort
                logger.error("answered journal unavailable at %s: %s",
                             cfg.journal.path, e)
                self._journal = None
        # pod plane (serve/pod.py — ISSUE 20): with pod.host_id set, this
        # process is one HOST of a multi-host pod — liaison heartbeats to
        # the peer table, partition adoption (with per-partition journal
        # replay into the shared dedupe ring) on a peer's death, and
        # cross-host session pulls before admission. Off = bit-identical
        # to the plain fleet.
        self.pod = None
        if cfg.pod.host_id:
            from finchat_tpu.serve.pod import PodCoordinator

            try:
                self.pod = PodCoordinator(
                    cfg.pod, fleet=fleet, kafka=kafka,
                    journal=self._journal, dedupe=self._dedupe,
                )
                for sched in self._all_schedulers():
                    sched.pod = self.pod
            except Exception as e:  # the pod plane is best-effort too
                logger.error("pod plane unavailable: %s", e)
                self.pod = None
        # graceful SIGTERM drain (ISSUE 7): set while drain_and_stop runs
        # so the HTTP chat paths stop admitting with a retryable 503
        self._draining = False

    # --- lifespan -------------------------------------------------------
    def _embed_batcher(self):
        """The embedding microbatcher, wherever it is wired: the app's own
        ingestion retriever or the agent's (they are the same object on
        the default on-device path)."""
        return getattr(self.retriever, "batcher", None) or getattr(
            self.agent.retriever, "batcher", None
        )

    async def start(self, serve_http: bool = True) -> None:
        await self.store.check_connection()
        batcher = self._embed_batcher()
        if batcher is not None:
            # bind the coalescing flusher to the serving loop so the
            # threadsafe ingest path can ride the same window as queries
            batcher.bind_loop()
        topics = [USER_MESSAGE_TOPIC]
        if self.retriever is not None:
            topics.append(TRANSACTION_UPSERT_TOPIC)
        self.kafka.setup_consumer(topics=topics)
        if self.fleet is not None:
            # per-replica head bookkeeping: a rebuild drops that replica's
            # prefilled heads only; the refresh loop re-registers them
            # per replica, and a supervisor respawn re-registers eagerly
            for rep in self.fleet.replicas:
                hook = _make_rebuild_hook(rep)
                if hook.key not in {getattr(cb, "key", None)
                                    for cb in rep.scheduler.on_rebuild}:
                    rep.scheduler.on_rebuild.append(hook)
            if self._respawn_heads not in self.fleet.on_respawn:
                self.fleet.on_respawn.append(self._respawn_heads)
            await self.fleet.start()
        elif self.scheduler is not None:
            if self._on_engine_rebuild not in self.scheduler.on_rebuild:
                self.scheduler.on_rebuild.append(self._on_engine_rebuild)
            await self.scheduler.start()
        if self.pod is not None:
            # after setup_consumer: the coordinator snapshots this host's
            # REAL partition assignment as its adoption baseline
            await self.pod.start()
        # the tool grammar's vocabulary tables build in a worker thread from
        # here on, not in front of the first batch's tool decisions
        prepare = getattr(getattr(self.agent, "tool_generator", None),
                          "prepare_grammar", None)
        if prepare is not None:
            prepare("tool_call")
        self._running = True
        self._consume_task = asyncio.create_task(self.consume_messages())
        if self._prefix_cache_enabled:
            self._prefix_refresh_task = asyncio.create_task(_prefix_refresh_loop(self))
        if serve_http:
            await self.server.start()
        # What start-up built (weights' trees, compiled programs, the
        # tokenizer's tables: 0.8 M container objects) lives as long as the
        # process. Left in the collector's oldest generation it is walked
        # whole by every full collection, at a moment of the collector's
        # choosing: 0.4 s with every stream waiting, about 17 s after a
        # batch arrives (PERF.md §6, PR 27). Frozen, a full collection
        # walks what requests made since (0.04 s).
        gc.freeze()
        # from here a program that compiles is an unwarmed shape of the
        # serving path, and a sleeper that wakes late is a frozen process
        self._tracing_serving = True
        TRACER.serving_started()

    async def stop(self) -> None:
        self._running = False
        if self._tracing_serving:
            self._tracing_serving = False
            TRACER.serving_stopped()
        gc.unfreeze()
        if self._prefix_refresh_task:
            self._prefix_refresh_task.cancel()
            try:
                await self._prefix_refresh_task
            except asyncio.CancelledError:
                pass
        if self._consume_task:
            self._consume_task.cancel()
            try:
                await self._consume_task
            except asyncio.CancelledError:
                pass
        for task in list(self._inflight):  # in-flight conversations
            task.cancel()
        if self._inflight:
            await asyncio.gather(*self._inflight, return_exceptions=True)
        batcher = self._embed_batcher()
        if batcher is not None:
            await batcher.close()
        if self.pod is not None:
            await self.pod.stop()
        if self.fleet is not None:
            await self.fleet.stop()
        elif self.scheduler is not None:
            await self.scheduler.stop()
        self._persist_index(force=True)
        await self.server.stop()
        self.kafka.close()
        if self._journal is not None:
            self._journal.close()

    def _all_schedulers(self) -> list:
        if self.fleet is not None:
            return [rep.scheduler for rep in self.fleet.replicas]
        return [self.scheduler] if self.scheduler is not None else []

    async def drain_and_stop(self) -> None:
        """Graceful SIGTERM shutdown (ISSUE 7; ROBUSTNESS.md §5): stop
        admission (Kafka polling halts, HTTP chat returns a retryable
        503), let in-flight streams COMPLETE within
        ``shutdown.deadline_seconds`` (their answers journal and their
        offsets commit exactly as in steady state), then preempt the
        stragglers to host — each one's coherent KV spills through the
        session disk tier and its client gets a retryable
        ``shutting_down`` error — spill every session entry, and exit
        with zero slot/page leaks. The restarted process replays the
        journal, rewinds to the committed watermark, and resumes
        conversations warm from the disk tier."""
        t0 = time.perf_counter()
        METRICS.inc("finchat_durability_graceful_drains_total")
        # black box of the shutdown itself (ISSUE 12): what was in flight
        # when SIGTERM landed; flushed to disk before the process exits
        TRACER.anomaly("sigterm_drain",
                       args={"inflight": len(self._inflight)})
        self._draining = True
        self._running = False
        if self._consume_task:
            self._consume_task.cancel()
            try:
                await self._consume_task
            except asyncio.CancelledError:
                pass
            self._consume_task = None
        deadline = max(0.0, self.cfg.shutdown.deadline_seconds)
        if self._inflight:
            _done, stragglers = await asyncio.wait(
                set(self._inflight), timeout=deadline
            )
            if stragglers:
                logger.warning(
                    "graceful drain: %d in-flight message(s) past the "
                    "%.1fs deadline; preempting to host", len(stragglers),
                    deadline,
                )
        # the fleet supervisor must be down before the per-replica drain:
        # a respawn's device rebuild (revive_async) racing shutdown_drain
        # on the same engine could corrupt allocator/slot state and defeat
        # the zero-leak exit (fleet.stop later is an idempotent no-op for
        # the already-cleared tasks)
        if self.fleet is not None:
            await self.fleet.stop_supervisor()
        # stragglers' engine handles fail with the retryable shutting_down
        # error and their coherent KV spills to the session tier; the loop
        # stops first, so no dispatch races the offload
        for sched in self._all_schedulers():
            try:
                await sched.shutdown_drain()
            except Exception as e:
                logger.error("scheduler shutdown drain failed: %s", e)
        # the straggler tasks observe the error events, emit their
        # retryable error chunks, and complete — committing their offsets
        if self._inflight:
            await asyncio.gather(*self._inflight, return_exceptions=True)
        METRICS.observe(
            "finchat_durability_shutdown_drain_seconds",
            time.perf_counter() - t0,
        )
        # the flight dumps write in worker threads; join them (off-loop)
        # so the black box is on disk before the process exits
        await asyncio.to_thread(TRACER.flush_dumps)
        await self.stop()

    # snapshots are full rewrites (np.savez over the whole collection), so
    # debounce streaming-ingest saves; shutdown always forces one
    _PERSIST_DEBOUNCE_S = 30.0

    def _persist_index(self, force: bool = False) -> None:
        base = self.cfg.vector.snapshot_base()
        if not base or getattr(self.retriever, "index", None) is None:
            return  # no local index (none, or external Qdrant backend)
        import time as _time

        now = _time.monotonic()
        if not force and now - getattr(self, "_last_persist", 0.0) < self._PERSIST_DEBOUNCE_S:
            self._persist_dirty = True
            return
        try:
            self.retriever.index.save(base)
            self._last_persist = now
            self._persist_dirty = False
        except Exception as e:
            logger.error("failed to persist vector index: %s", e)

    def _on_engine_rebuild(self) -> None:
        """Scheduler breaker trip rebuilt the engine's device state: the
        shared prompt heads' prefilled KV is gone with it. Mark them
        unregistered so the periodic prefix-refresh loop re-registers them
        through the chunked path — recovery itself never stalls on a
        multi-second head prefill."""
        self._registered_heads = set()

    # --- fleet routing (serve/fleet.py; ISSUE 6) ------------------------
    def _agent_for(self, conversation_id: str) -> LLMAgent:
        """The agent serving this conversation: the fleet's
        conversation-affinity route (which also migrates the session-cache
        bytes home) with a fleet, the single agent otherwise."""
        if self.fleet is not None:
            return self.fleet.agent_for(conversation_id)
        return self.agent

    def _prefix_targets(self) -> list:
        """Per-scheduler shared-prefix refresh targets (one per LIVE
        replica with a fleet; the app itself single-engine)."""
        if self.fleet is not None:
            return [_ReplicaPrefixView(self, rep) for rep in self.fleet.replicas
                    if rep.state == LIVE and rep.agent is not None]
        return [self]

    async def _respawn_heads(self, rep: EngineReplica) -> None:
        """fleet.on_respawn hook: re-register the shared prompt heads on a
        just-revived replica EAGERLY (the periodic refresh would leave it
        serving head-cold for up to a refresh interval)."""
        if self._prefix_cache_enabled and rep.agent is not None:
            rep.registered_heads = set()
            await _maybe_refresh_prefix_cache(_ReplicaPrefixView(self, rep))

    def _request_deadline(self, wall_anchor_s: float | None = None) -> float | None:
        """Per-request deadline on the scheduler's monotonic clock, or
        None when ``engine.request_deadline_seconds`` is unset. Anchored at
        the Kafka message's producer timestamp when given (broker queueing
        time counts against the allowance, exactly as the waiting client
        experiences it) or at arrival for the HTTP paths."""
        allowance = self.cfg.engine.request_deadline_seconds
        if allowance <= 0:
            return None
        now_wall = time.time()
        anchor = now_wall if wall_anchor_s is None else wall_anchor_s
        return time.perf_counter() + (anchor - now_wall) + allowance

    @staticmethod
    def _message_wall_ts(message) -> float | None:
        """Producer wall-clock seconds from a Kafka message, if stamped."""
        try:
            ts_type, ts_ms = message.timestamp()
        except Exception:
            return None
        if ts_type == 0 or ts_ms is None or ts_ms <= 0:
            return None
        return ts_ms / 1000.0

    # --- at-least-once commit plumbing (kafka.commit_after_process) ------
    # (dedupe ring size lives on serve/fleet.py DedupeRing — one default
    # for the single-engine ring and the fleet-shared ring alike)

    def _note_message_polled(self, msg) -> None:
        """Anchor the partition's commit watermark at the FIRST polled
        offset (poll order is offset order per partition)."""
        if not self._commit_enabled or msg.offset() < 0:
            return
        self._commit_next.setdefault((msg.topic(), msg.partition()), msg.offset())

    def _note_message_done(self, msg) -> None:
        """A message's watchdog-wrapped handling completed (answered,
        errored, timed out, or deduped — all terminal): advance the
        partition's contiguous-completion watermark and commit it."""
        if not self._commit_enabled or msg.offset() < 0:
            return
        tp = (msg.topic(), msg.partition())
        done = self._done_offsets.setdefault(tp, set())
        done.add(msg.offset())
        nxt = self._commit_next.setdefault(tp, msg.offset())
        advanced = False
        while nxt in done:
            done.discard(nxt)
            nxt += 1
            advanced = True
        if advanced:
            self._commit_next[tp] = nxt
            try:
                self.kafka.commit_offset(tp[0], tp[1], nxt)
            except Exception as e:
                logger.error("offset commit failed for %s: %s", tp, e)

    def _seen_message_id(self, message_id) -> bool:
        """Bounded dedupe ring over inbound ``message_id``s: True when this
        id was already handled this process lifetime (redelivery after a
        crash/rebalance must not double-answer). Shared fleet-wide — see
        serve/fleet.py DedupeRing."""
        return self._dedupe.seen(message_id)

    @property
    def _seen_ids(self) -> set:
        """Introspection view of the dedupe ring's id set (tests)."""
        return self._dedupe._ids

    # --- conversation plumbing ------------------------------------------
    def _payload_error(self, payload: dict) -> Response | None:
        """Shared HTTP validation for the chat endpoints; also the
        admission gate during a graceful drain (new work gets a retryable
        503 while in-flight streams finish)."""
        if self._draining:
            return Response.json(
                {"detail": "server shutting down; retry with backoff",
                 "retryable": True}, status=503,
            )
        missing = [k for k in ("conversation_id", "message", "user_id") if k not in payload]
        if missing:
            return Response.json({"detail": f"missing fields: {missing}"}, status=400)
        return None

    async def _conversation_inputs(
        self, payload: dict, *, payload_user_id: bool = True
    ) -> tuple[str, str, str, list]:
        """THE one place a request's conversation state is assembled —
        every chat path (REST, SSE, Kafka) goes through here, so the
        ``conversation_id`` that keys the engine's session KV cache and the
        context/history fetch can never drift apart. Returns
        ``(conversation_id, user_id, user_context, chat_history)``. The
        HTTP paths take ``user_id`` from the validated payload; the Kafka
        path passes ``payload_user_id=False`` to keep the STORED user id
        authoritative (reference main.py:64-70 — a spoofed message field
        must not re-key whose transactions are retrieved)."""
        conversation_id = payload["conversation_id"]
        user_context, stored_user_id = await self.store.get_context(conversation_id)
        chat_history = await self.store.get_history(conversation_id)
        user_id = stored_user_id
        if payload_user_id and "user_id" in payload:
            user_id = payload["user_id"]
        return conversation_id, user_id, user_context, chat_history

    # --- tracing (utils/tracing.py — ISSUE 12) --------------------------
    @staticmethod
    def _kafka_trace_id(message_value: dict | None) -> str | None:
        """The trace id a Kafka message carries BY ITSELF: its
        ``message_id`` (the same id the answered journal and dedupe ring
        key on). None when the producer stamped no id — the handler then
        mints one, which correlation-at-the-watchdog can't recover (the
        watchdog only holds the raw message)."""
        if message_value is None:
            return None
        mid = message_value.get("message_id")
        return str(mid) if mid is not None else None

    @staticmethod
    def _http_trace_id(request: Request) -> str:
        """HTTP ingress trace id: the client's ``x-trace-id`` header when
        given (so an upstream gateway's id correlates end-to-end), else
        minted here."""
        return request.headers.get("x-trace-id") or uuid.uuid4().hex[:16]

    @staticmethod
    def _trace_ingress(trace_id: str, source: str, conversation_id: str) -> None:
        if TRACER.enabled:
            TRACER.event("ingress", trace_id, track="ingress",
                         args={"source": source,
                               "conversation_id": conversation_id})

    # --- HTTP handlers --------------------------------------------------
    async def health(self, request: Request) -> Response:
        return Response.json({"status": "healthy"})

    async def metrics(self, request: Request) -> Response:
        return Response.text(METRICS.render_prometheus(), content_type="text/plain; version=0.0.4")

    async def debug_trace(self, request: Request) -> Response:
        """``GET /debug/trace/<trace_id>`` → Chrome trace-event JSON of
        that request's correlated timeline (ingress, agent decide, tool
        launch/adopt, prefill, every dispatch that carried its rows,
        first token, done). Open the body in Perfetto / chrome://tracing."""
        trace_id = request.path.rsplit("/", 1)[-1]
        if not trace_id:
            return Response.json({"detail": "missing trace id"}, status=400)
        export = TRACER.export(trace_id)
        if not export["traceEvents"]:
            return Response.json(
                {"detail": f"no events for trace_id {trace_id!r} "
                           "(expired from the ring, or never traced)"},
                status=404,
            )
        return Response.json(export)

    async def chat(self, request: Request) -> Response:
        """Batch REST path (the reference's commented POST /process_message,
        main.py:44-49): runs the compiled agent graph."""
        payload = request.json()
        err = self._payload_error(payload)
        if err is not None:
            return err
        conversation_id, user_id, user_context, chat_history = (
            await self._conversation_inputs(payload)
        )
        trace_id = self._http_trace_id(request)
        self._trace_ingress(trace_id, "http:/chat", conversation_id)
        try:
            agent = self._agent_for(conversation_id)
        except RuntimeError:
            # whole fleet out: same retryable signal the Kafka path emits
            return Response.json(
                {"detail": "no live engine replica; retry with backoff",
                 "retryable": True}, status=503,
            )
        result = await agent.query(
            payload["message"], user_id, user_context, chat_history,
            conversation_id=conversation_id,
            deadline=self._request_deadline(),
            trace_id=trace_id,
        )
        body = {
            "response": result["response"],
            "retrieved_transactions_count": result["retrieved_transactions_count"],
        }
        if result.get("plot_data_uri"):
            body["plot_data_uri"] = result["plot_data_uri"]
        return Response.json(body)

    async def chat_stream(self, request: Request) -> Response | StreamingResponse:
        """SSE stream of the full internal event protocol."""
        payload = request.json()
        err = self._payload_error(payload)
        if err is not None:
            return err
        conversation_id, user_id, user_context, chat_history = (
            await self._conversation_inputs(payload)
        )

        deadline = self._request_deadline()
        trace_id = self._http_trace_id(request)
        self._trace_ingress(trace_id, "http:/chat/stream", conversation_id)
        try:
            agent = self._agent_for(conversation_id)
        except RuntimeError:
            return Response.json(
                {"detail": "no live engine replica; retry with backoff",
                 "retryable": True}, status=503,
            )

        async def events():
            updates = agent.stream_with_status(
                payload["message"], user_id, user_context, chat_history,
                conversation_id=conversation_id, deadline=deadline,
                trace_id=trace_id,
            )
            async for update in updates:
                yield sse_event(update)

        return StreamingResponse(chunks=events())

    async def upsert_transactions(self, request: Request) -> Response:
        """Ingestion endpoint: embed rows on-device and upsert them into the
        vector index (the reference's out-of-band Qdrant pipeline made
        first-class). Body: {"user_id": ..., "transactions":
        [{"text": ..., "date"?: unix-ts, ...metadata}]}."""
        if self.retriever is None:
            return Response.json({"detail": "no retriever configured"}, status=503)
        payload = request.json()
        missing = [k for k in ("user_id", "transactions") if k not in payload]
        if missing:
            return Response.json({"detail": f"missing fields: {missing}"}, status=400)
        rows = payload["transactions"]
        if not isinstance(rows, list) or not all(
            isinstance(r, dict) and r.get("text") for r in rows
        ):
            return Response.json(
                {"detail": "transactions must be [{text, date?, ...metadata}]"}, status=400
            )
        try:
            count = await asyncio.to_thread(
                self._ingest_rows, str(payload["user_id"]), rows
            )
        except (TypeError, ValueError) as e:
            return Response.json({"detail": f"bad transaction row: {e}"}, status=400)
        return Response.json({"upserted": count})

    def _ingest_rows(self, user_id: str, rows: list[dict]) -> int:
        """Embed + upsert (blocking: device matmuls); callers thread it off
        the loop. Rows without a ``date`` are stamped individually with now
        (a malformed date raises ValueError → 400 at the handler)."""
        texts = [str(r["text"]) for r in rows]
        now = self.retriever.now()
        dates = [float(r["date"]) if "date" in r else now for r in rows]
        metadatas = [
            {k: v for k, v in r.items() if k not in ("text", "date")} for r in rows
        ]
        self.retriever.upsert_transactions(user_id, texts, dates=dates, metadatas=metadatas)
        self._persist_index()
        return len(texts)

    # --- Kafka worker loop ----------------------------------------------
    async def process_message(self, message, message_value: dict | None = None) -> bool:
        """Handle one user message end-to-end. Returns True only when the
        client was ANSWERED (stream completed); False for drops, errors,
        and sheds — the dedupe ring keeps only answered message_ids, so a
        producer retrying a failed/shed message (as the retryable error
        chunk invites) is reprocessed, never black-holed."""
        if message_value is None:
            message_value = json.loads(message.value().decode("utf-8"))
        msg = message_value["message"]
        conversation_id = message_value["conversation_id"]
        full_message = ""
        logger.info("Received message from Kafka: |%s| %s", conversation_id, msg)

        try:
            conversation_id, user_id, context, chat_history = (
                await self._conversation_inputs(message_value, payload_user_id=False)
            )
        except Exception as e:
            logger.error("Error retrieving context or history for conversation %s: %s", conversation_id, e)
            return False

        # stream_flush_tokens > 1 coalesces N model chunks into one outbound
        # Kafka produce — fewer, larger messages for high-throughput topics
        # (1 = reference behavior: one produce per chunk, main.py:86-96)
        flush_every = max(1, self.cfg.engine.stream_flush_tokens)
        pending_chunks: list[str] = []

        def flush_pending() -> None:
            if pending_chunks:
                text = "".join(pending_chunks)
                pending_chunks.clear()
                self.kafka.produce_message(
                    AI_RESPONSE_TOPIC, conversation_id, response_chunk(message_value, text)
                )
                logger.debug("Processed chunk: %s", text)

        try:
            agent = self._agent_for(conversation_id)
        except RuntimeError as e:
            # whole fleet out: the client gets a retryable error instead of
            # a silent drop (the dedupe ring forgets the id — see _done)
            logger.error("no replica for conversation %s: %s", conversation_id, e)
            self.kafka.produce_error_message(
                AI_RESPONSE_TOPIC, conversation_id,
                error_chunk(message_value, code="overloaded", retryable=True),
            )
            return False

        # trace id minted at ingress (ISSUE 12): the Kafka message_id when
        # the producer stamped one — the SAME id the journal/dedupe plane
        # keys on, so a postmortem can pivot between the answered journal
        # and the timeline — else minted here
        trace_id = self._kafka_trace_id(message_value) or uuid.uuid4().hex[:16]
        self._trace_ingress(trace_id, f"kafka:{USER_MESSAGE_TOPIC}",
                            conversation_id)
        # deadline anchored at the PRODUCER timestamp: broker queueing time
        # counts against the allowance, so a message that sat through a
        # backlog sheds (structured retryable error) instead of burning
        # prefill compute on an answer its client gave up on
        updates = agent.stream_with_status(
            msg, user_id, context, chat_history, conversation_id=conversation_id,
            deadline=self._request_deadline(self._message_wall_ts(message)),
            trace_id=trace_id,
        )
        try:
            async for update in updates:
                if update["type"] == "response_chunk":
                    chunk_text = update["content"]
                    full_message += chunk_text
                    pending_chunks.append(chunk_text)
                    if len(pending_chunks) >= flush_every:
                        flush_pending()
                elif update["type"] == "plot":
                    # NEW capability (additive chunk type; schemas.plot_chunk)
                    self.kafka.produce_message(
                        AI_RESPONSE_TOPIC, conversation_id, plot_chunk(message_value, update["data_uri"])
                    )
                elif update["type"] == "complete":
                    flush_pending()  # never reorder text after the marker
                    self.kafka.produce_message(
                        AI_RESPONSE_TOPIC, conversation_id, complete_chunk(message_value)
                    )
                    logger.info("Complete message sent to Kafka for conversation %s", conversation_id)
                # status / retrieval_complete events are intentionally NOT
                # forwarded (main.py:81-110 forwards only response_chunk +
                # complete; plot is the one additive extension)
        except Exception as e:
            logger.error("Error streaming LLM response: %s", e)
            # best-effort: text the client was owed goes out before the
            # error marker (at flush=1 this is reference behavior exactly)
            try:
                flush_pending()
            except Exception:
                pass
            # structured failures (deadline shed, overload) carry their
            # code + retryable flag so the producer can back off and
            # retry; ordinary errors keep the reference's exact shape
            self.kafka.produce_error_message(
                AI_RESPONSE_TOPIC, conversation_id,
                error_chunk(
                    message_value,
                    code=getattr(e, "code", None),
                    retryable=True if getattr(e, "retryable", False) else None,
                ),
            )
            return False
        finally:
            # guarantee generator finalization: the engine handle's
            # slot/KV release lives in the generator's finally, which a
            # consumer cancelled OUTSIDE __anext__ (watchdog timeout)
            # would otherwise leave to the GC
            await updates.aclose()

        try:
            await self.store.save_ai_message(conversation_id=conversation_id, message=full_message, user_id=user_id)
            logger.info("Message saved to DB for conversation %s", conversation_id)
        except Exception as e:
            logger.error("Error saving AI message to DB: %s", e)
        return True

    async def process_upsert(self, message) -> None:
        """transaction_upsert topic: same body as POST /transactions."""
        payload = json.loads(message.value().decode("utf-8"))
        rows = payload.get("transactions") or []
        user_id = str(payload.get("user_id", ""))
        if not user_id or not all(isinstance(r, dict) and r.get("text") for r in rows):
            logger.error("malformed transaction_upsert message; dropped")
            return
        count = await asyncio.to_thread(self._ingest_rows, user_id, rows)
        logger.info("ingested %d transactions for user %s via Kafka", count, user_id)

    async def _process_with_watchdog(
        self, msg, message_value: dict | None, prev: asyncio.Task | None
    ) -> bool:
        """One in-flight message: wait for the SAME conversation's previous
        message to finish (chunk-ordering guarantee), then run under the
        per-message watchdog (reference main.py:138-153 semantics).
        Returns process_message's answered flag (False on timeout/error)
        — what decides whether the message_id stays in the dedupe ring."""
        if prev is not None:
            try:
                await asyncio.shield(prev)
            except Exception:
                pass  # predecessor's failure was already reported on its stream
        watchdog = self.cfg.engine.watchdog_seconds
        task = asyncio.create_task(self.process_message(msg, message_value))
        try:
            return bool(await asyncio.wait_for(asyncio.shield(task), timeout=watchdog))
        except asyncio.TimeoutError:
            logger.error("Message processing timed out after %s seconds", watchdog)
            # flight recorder (ISSUE 12): the ring at this instant holds
            # the stuck request's dispatch/lifecycle events — exactly what
            # a "why did the watchdog fire" postmortem needs
            TRACER.anomaly(
                "watchdog_timeout", self._kafka_trace_id(message_value),
                args={"watchdog_seconds": watchdog,
                      "conversation_id": (message_value or {}).get(
                          "conversation_id")},
            )
            # cancel the in-flight generation and AWAIT its cleanup — the
            # agent/generator finalizers release the scheduler slot and KV
            # pages — BEFORE emitting the timeout chunk, so a timed-out
            # message can never leak engine capacity (the pre-fix path
            # abandoned the coroutine to wait_for's cancellation and raced
            # the chunk against the release; tests/test_resilience.py pins
            # zero slot/page leakage)
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass
            try:
                if message_value is not None:
                    self.kafka.produce_error_message(
                        AI_RESPONSE_TOPIC,
                        message_value["conversation_id"],
                        timeout_chunk(message_value),
                    )
            except Exception as e:
                logger.error("Failed to send timeout error message: %s", e)
            return False
        except asyncio.CancelledError:
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass
            raise
        except Exception as e:
            logger.error("Error processing message: %s", e)
            return False

    def _spawn_message_task(self, msg) -> None:
        # parse ONCE here; process_message / the timeout path reuse the dict
        try:
            message_value = json.loads(msg.value().decode("utf-8"))
            conv_id = message_value.get("conversation_id", "")
        except Exception:
            message_value = None  # malformed: process_message reports it
            conv_id = ""
        mid = None
        if self._commit_enabled and message_value is not None:
            # redelivery dedupe (at-least-once): a message_id this process
            # already ANSWERED (or holds in flight) is not re-answered —
            # its offset still counts as done so the watermark (and the
            # group) can move past it. Ids whose handling FAILS are
            # removed from the ring in _done below, so a producer retrying
            # a shed/overloaded/timed-out message is reprocessed.
            mid = message_value.get("message_id")
            if mid is not None and self._seen_message_id(mid):
                METRICS.inc("finchat_kafka_dedupe_skips_total")
                logger.warning(
                    "duplicate message_id %s (redelivery); already answered, skipping",
                    mid,
                )
                self._note_message_done(msg)
                return
        prev = self._conv_tails.get(conv_id)
        task = asyncio.create_task(self._process_with_watchdog(msg, message_value, prev))
        self._inflight.add(task)
        if conv_id:
            self._conv_tails[conv_id] = task

        def _done(t: asyncio.Task, conv_id=conv_id, mid=mid) -> None:
            self._inflight.discard(t)
            if conv_id and self._conv_tails.get(conv_id) is t:
                del self._conv_tails[conv_id]
            answered = (
                not t.cancelled() and t.exception() is None and bool(t.result())
            )
            if mid is not None and not answered:
                # never answered: drop the id (set AND ring slot) so a
                # producer retry (the retryable error chunk's invitation)
                # is reprocessed instead of black-holed
                self._dedupe.forget(mid)
            elif mid is not None and self._journal is not None:
                # ANSWERED: journal the id under the message's PARTITION —
                # fsync completes BEFORE the watermark commit below, so a
                # crash between them redelivers the message to a process
                # that already knows it was answered (ISSUE 7; §5), and a
                # host that ADOPTS this partition replays exactly this
                # file into its ring (ISSUE 20; §7)
                self._journal.append(mid, partition=msg.partition())
            # the watchdog-wrapped handler completed (answered, errored, or
            # timed out with the timeout chunk emitted): only now may this
            # offset count toward the committed watermark
            self._note_message_done(msg)

        task.add_done_callback(_done)

    def _max_inflight(self) -> int:
        """Poll-gate bound: a full batch per LIVE replica. OUT/RESPAWNING
        replicas are not capacity — counting them would keep this worker
        claiming messages sized for the whole fleet during an outage,
        load the survivors must absorb instead of the consumer group
        redistributing it. Floored at one batch so a whole-fleet-out
        window still polls (each message gets its structured retryable
        error instead of rotting unclaimed on the partition)."""
        n_replicas = 1
        if self.fleet is not None:
            n_replicas = max(1, len(self.fleet.live_replicas()))
        return max(self.cfg.engine.max_seqs, 1) * n_replicas

    async def consume_messages(self) -> None:
        """Poll Kafka and fan messages out as concurrent tasks — MANY
        conversations in flight batch onto the engine together (the whole
        point of the continuous-batching scheduler; the reference processes
        one message at a time per worker, SURVEY §2.3). Backpressure: stop
        polling while a full batch's worth of messages is already in
        flight, so the broker's consumer group redistributes load instead
        of this worker hoarding it."""
        while self._running:
            try:
                if len(self._inflight) >= self._max_inflight():
                    await asyncio.wait(
                        set(self._inflight), return_when=asyncio.FIRST_COMPLETED
                    )
                    continue
                # poll in a worker thread: the confluent backend's poll
                # blocks up to 100 ms (librdkafka), which would stall every
                # in-flight stream now that polling overlaps processing
                msg = await asyncio.to_thread(self.kafka.poll_message)
                if msg is not None:
                    self._note_message_polled(msg)
                if msg is not None and msg.topic() == TRANSACTION_UPSERT_TOPIC:
                    try:
                        await self.process_upsert(msg)
                    except Exception as e:
                        logger.error("Error ingesting transactions: %s", e)
                    finally:
                        self._note_message_done(msg)
                elif msg is not None:
                    self._spawn_message_task(msg)
                    await asyncio.sleep(0)  # let the new task reach the engine
                else:
                    # deferred snapshot from a debounced ingest save
                    if getattr(self, "_persist_dirty", False):
                        self._persist_index()
                    await asyncio.sleep(0.01)
            except asyncio.CancelledError:
                raise
            except Exception as e:
                logger.error("Error in message consumption: %s", e)
                await asyncio.sleep(1)


def build_app(cfg: AppConfig | None = None, *, store: ConversationStore | None = None,
              kafka: KafkaClient | None = None,
              tool_generator: TextGenerator | None = None,
              response_generator: TextGenerator | None = None,
              retriever=None) -> App:
    """Assemble a worker from config, with injection points for tests/dev."""
    from finchat_tpu.utils.config import load_config

    cfg = cfg or load_config()
    # tracing + flight recorder (utils/tracing.py — ISSUE 12): applied at
    # assembly so every component (scheduler, agent, app ingress) sees one
    # consistently configured process tracer
    TRACER.configure(enabled=cfg.tracing.enabled,
                     ring_events=cfg.tracing.ring_events,
                     flight_dir=cfg.tracing.flight_dir)
    store = store or make_store(cfg.store)
    kafka = kafka or KafkaClient(cfg.kafka)

    scheduler = None
    tokenizer = None
    fleet_replicas: list[EngineReplica] | None = None
    if tool_generator is None or response_generator is None:
        # cluster-wide warm-state fabric (ISSUE 17): one shared session
        # disk tier + head store for every replica built below (a single-
        # engine worker uses it too — restarts and multi-process fleets
        # sharing the path resume each other's conversations warm)
        fabric = make_warm_fabric(cfg) if cfg.model.preset != "stub" else None
        if cfg.fleet.replicas > 1 and cfg.model.preset != "stub":
            # engine fleet (ISSUE 6): N replicas over ONE shared weights
            # tree, each with its own KV pool, scheduler, session cache,
            # and replica-labeled metrics; agents bind per replica below.
            # fleet.roles (ISSUE 17) types each replica into the prefill
            # or serving pool; EngineFleet wires the disagg coordinator.
            from finchat_tpu.serve.disagg import parse_roles

            roles = parse_roles(cfg.fleet.roles, cfg.fleet.replicas)
            with TRACER.startup_phase("artifacts"):
                artifacts = _load_model_artifacts(cfg)
            tokenizer = artifacts[2]
            fleet_replicas = []
            for i in range(cfg.fleet.replicas):
                gen, sched = make_engine_replica(cfg, artifacts,
                                                 replica_id=str(i),
                                                 fabric=fabric)
                fleet_replicas.append(
                    EngineReplica(replica_id=str(i), scheduler=sched,
                                  generator=gen, role=roles[i])
                )
            scheduler = fleet_replicas[0].scheduler
            tool_generator = tool_generator or fleet_replicas[0].generator
            response_generator = response_generator or fleet_replicas[0].generator
        else:
            tool_gen, resp_gen, scheduler, tokenizer = build_generators(cfg, fabric=fabric)
            tool_generator = tool_generator or tool_gen
            response_generator = response_generator or resp_gen

    if retriever is None:
        with TRACER.startup_phase("embed"):
            from finchat_tpu.embed.batcher import EmbedMicrobatcher
            from finchat_tpu.embed.encoder import EMBED_PRESETS, EmbeddingEncoder, init_bert_params
            from finchat_tpu.embed.index import DeviceVectorIndex

            embed_cfg = EMBED_PRESETS[cfg.embed.preset]
            if cfg.embed.checkpoint_path:
                from finchat_tpu.checkpoints.bert_loader import load_bert_params

                embed_params = load_bert_params(cfg.embed.checkpoint_path, embed_cfg)
            else:
                logger.warning(
                    "no embedding checkpoint configured; using RANDOM weights "
                    "(preset=%s) — retrieval rankings will be meaningless", cfg.embed.preset,
                )
                embed_params = init_bert_params(embed_cfg, jax.random.key(1))
            if cfg.embed.tokenizer_path:
                embed_tokenizer = get_tokenizer(cfg.embed.tokenizer_path)
            else:
                if cfg.embed.checkpoint_path:
                    logger.warning(
                        "embed.checkpoint_path is set but embed.tokenizer_path is "
                        "not; falling back to the LLM/byte tokenizer, whose ids "
                        "will NOT match the BERT vocab — retrieval rankings will "
                        "be meaningless. Set FINCHAT_EMBED_TOKENIZER."
                    )
                embed_tokenizer = tokenizer or get_tokenizer()
            encoder = EmbeddingEncoder(
                embed_cfg, embed_params, embed_tokenizer,
                batch_size=cfg.embed.batch_size, quant=cfg.embed.quant,
            )
            if cfg.vector.api_key and not cfg.vector.url:
                logger.warning(
                    "QDRANT_API_KEY is set but QDRANT_URL is not; using the "
                    "on-device vector index — set QDRANT_URL to select the "
                    "external Qdrant backend"
                )
            if cfg.vector.url:
                # deployments with an existing populated Qdrant cluster drop
                # in via QDRANT_URL (reference qdrant_tool.py:24-37); the
                # embeddings still run on-device, only ANN search is external
                from finchat_tpu.tools.qdrant_retriever import QdrantRetriever

                retriever = QdrantRetriever(
                    encoder, url=cfg.vector.url, api_key=cfg.vector.api_key,
                    collection=cfg.vector.collection,
                    default_limit=cfg.vector.default_limit,
                )
            else:
                base = cfg.vector.snapshot_base()
                if base:
                    index = DeviceVectorIndex.load(base, dim=embed_cfg.dim)
                else:
                    index = DeviceVectorIndex(dim=embed_cfg.dim)
                # the embedding microbatcher coalesces concurrent query embeds
                # and ingest upserts into shared encode_batch dispatches; it
                # binds to the serving event loop at App.start
                batcher = EmbedMicrobatcher(
                    encoder, window_ms=cfg.embed.batch_window_ms,
                    max_batch=cfg.embed.batch_max,
                )
                retriever = TransactionRetriever(
                    encoder, index, default_limit=cfg.vector.default_limit,
                    batcher=batcher,
                )

    system_prompt, tool_prompt = load_prompts()

    def make_agent(tool_gen, resp_gen) -> LLMAgent:
        return LLMAgent(
            tool_gen, resp_gen, retriever, system_prompt, tool_prompt,
            response_sampling=SamplingParams(
                temperature=cfg.engine.temperature, top_p=cfg.engine.top_p,
                top_k=cfg.engine.top_k, max_new_tokens=cfg.engine.max_new_tokens,
            ),
            retrieval_overlap=cfg.engine.retrieval_overlap,
            # tool-streaming plane (ISSUE 9): eager tool launch + early
            # prefix hold during the decision decode; the agent derives
            # its finchat_tool_* metrics view from the generator's
            # scheduler, so fleet replicas label the family per replica
            tool_streaming=cfg.engine.tool_streaming,
        )

    agent = make_agent(tool_generator, response_generator)
    fleet = None
    if fleet_replicas is not None:
        # one agent per replica (prompts + retriever shared; each agent's
        # generators are bound to its replica's scheduler); replica 0
        # reuses the agent above so App.agent and the fleet stay one object
        fleet_replicas[0].agent = agent
        for rep in fleet_replicas[1:]:
            rep.agent = make_agent(rep.generator, rep.generator)
        fleet = EngineFleet(fleet_replicas, cfg.fleet,
                            num_partitions=kafka.num_partitions)
    # the App's ingestion endpoints work with any backend exposing
    # upsert_transactions (device index or external Qdrant); snapshot
    # persistence additionally needs a local .index (guarded there)
    app_retriever = retriever if hasattr(retriever, "upsert_transactions") else None
    app = App(cfg, agent=agent, store=store, kafka=kafka, scheduler=scheduler,
              retriever=app_retriever, fleet=fleet)
    if app._prefix_cache_enabled and tokenizer is not None:
        with TRACER.startup_phase("heads"):
            if fleet is not None:
                for rep in fleet.replicas:
                    rep.registered_heads = register_prompt_prefixes(
                        rep.agent, rep.scheduler, tokenizer
                    )
            else:
                app._registered_heads = register_prompt_prefixes(agent, scheduler, tokenizer)
    return app
