"""Engine fleet (ISSUE 6; serve/fleet.py; ROBUSTNESS.md).

Pins the fleet contract:

- RENDEZVOUS ROUTING: conversation→replica routing hashes the
  conversation's KAFKA PARTITION (io/kafka.py ``partition_for_key`` — the
  broker's own key→partition placement), so routing agrees with partition
  assignment by construction; replica loss moves ONLY the lost replica's
  share (≤ ~1/N of conversations) and rejoin restores exactly the old
  mapping.
- DRAIN HANDOFF: a killed replica's in-flight streams are preempted to
  host, adopted by siblings, and complete BYTE-IDENTICAL to an
  undisturbed run — zero user-visible errors; the victim goes OUT and the
  supervisor respawns it once the device heals.
- SESSION MIGRATION: session-cache entries are portable host bytes —
  drain hands them off with the stream, and the router migrates them
  lazily at route time, so a migrated conversation admission-resumes
  (resumed_len > 0) instead of cold-prefilling. Entries riding a shared
  prompt head re-link against the importer's own live registration, and
  are REFUSED (cold resume, counted) when the importer has no matching
  head.
- ROUTER-LEVEL DEDUPE: the answered-``message_id`` ring is shared
  fleet-wide, so replica death + Kafka redelivery to a sibling cannot
  double-answer (closes the per-replica hole PR 5 documented).
"""

import asyncio
import dataclasses
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finchat_tpu.engine.engine import InferenceEngine
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler, _PrefixJob
from finchat_tpu.engine.session_cache import SESSION_KEY_ROLES, session_key
from finchat_tpu.io.kafka import partition_for_key
from finchat_tpu.models.llama import PRESETS, init_params
from finchat_tpu.serve.fleet import (
    LIVE,
    OUT,
    DedupeRing,
    EngineFleet,
    EngineReplica,
    rendezvous_hash,
)
from finchat_tpu.utils import faults
from finchat_tpu.utils.config import EngineConfig, FleetConfig
from finchat_tpu.utils.metrics import METRICS, MetricsRegistry


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faults.disarm_all()


# --- rendezvous routing (pure; no engines) --------------------------------

def _stub_replica(rid: str) -> EngineReplica:
    """Router-only replica: the scheduler surface EngineFleet wires
    (drain_sink assignment target, on_give_up list, no session cache)."""
    sched = types.SimpleNamespace(on_give_up=[], session_cache=None)
    return EngineReplica(replica_id=rid, scheduler=sched)


def _stub_fleet(n: int, num_partitions: int = 32) -> EngineFleet:
    return EngineFleet(
        [_stub_replica(str(i)) for i in range(n)],
        FleetConfig(replicas=n, respawn=False),
        num_partitions=num_partitions,
    )


def test_rendezvous_loss_moves_only_the_lost_share():
    """Removing a candidate reassigns exactly the keys it owned (each to
    its runner-up); every other key keeps its owner. Rejoin restores the
    original mapping bit-for-bit."""
    cands = [str(i) for i in range(4)]
    keys = [str(p) for p in range(64)]
    before = {k: rendezvous_hash(k, cands) for k in keys}
    survivors = [c for c in cands if c != "2"]
    after = {k: rendezvous_hash(k, survivors) for k in keys}
    for k in keys:
        if before[k] == "2":
            assert after[k] != "2"
        else:
            assert after[k] == before[k]
    # rejoin: exactly the old mapping
    assert {k: rendezvous_hash(k, cands) for k in keys} == before
    # and the lost share is ~1/N — not empty, not the whole keyspace
    moved = sum(1 for k in keys if before[k] == "2")
    assert 0 < moved < len(keys) / 2


def test_fleet_reshuffle_fraction_on_replica_loss():
    """Marking one of N replicas OUT reroutes ONLY the conversations
    whose partition it owned: ≤ ~1/N of conversations move (slack for
    hash imbalance), everyone else keeps their replica."""
    fleet = _stub_fleet(4)
    convs = [f"conv-{i}" for i in range(200)]
    before = {c: fleet.replica_for(c).replica_id for c in convs}
    victim = fleet.replicas[1]
    victim.state = OUT
    after = {c: fleet.replica_for(c).replica_id for c in convs}
    moved = [c for c in convs if after[c] != before[c]]
    assert all(before[c] == victim.replica_id for c in moved)
    assert all(after[c] != victim.replica_id for c in convs)
    assert len(moved) <= len(convs) * 2 / 4  # ~1/N with imbalance slack
    # rejoin: everything routes exactly as before the loss
    victim.state = LIVE
    assert {c: fleet.replica_for(c).replica_id for c in convs} == before


def test_routing_agrees_with_kafka_partition_assignment():
    """The routing unit is the Kafka partition: two conversations the
    broker would place on the same partition route to the same replica,
    and the conversation route equals the partition route — so a
    replica's share is expressible as a partition→replica assignment."""
    fleet = _stub_fleet(4, num_partitions=8)
    by_partition: dict[int, str] = {}
    for i in range(100):
        conv = f"c{i}"
        part = partition_for_key(conv, 8)
        assert part == fleet.partition_for(conv)
        rid = fleet.replica_for(conv).replica_id
        assert rid == fleet.replica_for_partition(part).replica_id
        assert by_partition.setdefault(part, rid) == rid
    # the 8 partitions cover several replicas (sanity: it IS spreading)
    assert len(set(by_partition.values())) > 1


def test_overprovisioned_fleet_warns(caplog):
    """The partition is the routing unit: more replicas than partitions
    means the extras can never be routed traffic — that misconfiguration
    must be loud at construction, not a silent capacity black hole."""
    import logging
    with caplog.at_level(logging.WARNING, logger="finchat_tpu.serve.fleet"):
        _stub_fleet(5, num_partitions=4)
    assert any("NO traffic" in r.getMessage() for r in caplog.records)
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="finchat_tpu.serve.fleet"):
        _stub_fleet(4, num_partitions=4)  # at the bound: fine
    assert not caplog.records


def test_no_live_replica_raises():
    fleet = _stub_fleet(2)
    for rep in fleet.replicas:
        rep.state = OUT
    assert fleet.replica_for("c") is None
    with pytest.raises(RuntimeError):
        fleet.agent_for("c")


# --- router-level dedupe ring ---------------------------------------------

def test_dedupe_ring_shared_and_forget_removes_ring_slot():
    ring = DedupeRing(size=4)
    assert not ring.seen("m1")
    assert ring.seen("m1")  # second delivery (sibling replica) skips
    # a FAILED id is forgotten — set and ring slot — so a retry reprocesses
    assert not ring.seen("m2")
    ring.forget("m2")
    assert not ring.seen("m2")
    # overflow evicts oldest, and forget leaves no stale slot behind that
    # could age out a re-added answered id early
    for i in range(10):
        ring.seen(f"fill-{i}")
    assert not ring.seen("m1")  # aged out by overflow, as sized


# --- real-engine fleet: drain handoff + respawn + migration ----------------

def _make_replica(rid: str, params, config, **cfg_overrides) -> EngineReplica:
    defaults = dict(
        max_seqs=3, page_size=8, num_pages=64, max_seq_len=128,
        prefill_chunk=16, session_cache=True, session_cache_bytes=16 << 20,
        breaker_max_rebuilds=1,
    )
    defaults.update(cfg_overrides)
    engine = InferenceEngine(config, params, EngineConfig(**defaults))
    sched = ContinuousBatchingScheduler(
        engine, eos_id=-1, metrics=METRICS.labeled(replica=rid),
        replica_id=rid,
    )
    return EngineReplica(replica_id=rid, scheduler=sched)


def _make_fleet(n: int, **cfg_overrides) -> EngineFleet:
    config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
    params = init_params(config, jax.random.key(0))
    reps = [_make_replica(str(i), params, config, **cfg_overrides)
            for i in range(n)]
    return EngineFleet(
        reps,
        FleetConfig(replicas=n, respawn_backoff_seconds=0.05,
                    supervisor_interval_seconds=0.05),
        num_partitions=16,
    )


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


def _greedy(max_new: int) -> SamplingParams:
    return SamplingParams(temperature=0.0, max_new_tokens=max_new)


def test_drain_handoff_byte_identity_and_respawn():
    """Kill one replica of three mid-stream (wedge its decode AND revive
    sites until healed): every in-flight stream — including the victim's —
    completes on a sibling with the exact greedy tokens of an undisturbed
    run, zero errors; the victim goes OUT (gauge drops) and respawns LIVE
    after the heal (gauge recovers); a wave of new conversations offered
    while it is OUT, and another after it is back, are all answered."""
    prompts = {f"conv-{i}": list(range(7 * i + 1, 7 * i + 15))
               for i in range(6)}

    async def run(fault: bool) -> dict:
        fleet = _make_fleet(3)
        await fleet.start()
        out: dict = {"errors": 0}

        async def wave(tag, avoid=None, n=4) -> int:
            handles = []
            for i in range(n):
                conv = f"{tag}-{i}"
                rep = fleet.replica_for(conv)
                assert rep is not avoid, "the router placed a turn on an OUT replica"
                handles.append(await rep.scheduler.submit(
                    conv, list(range(60 + i, 74 + i)), _greedy(4),
                    conversation_id=conv))
            results = [await asyncio.wait_for(
                asyncio.ensure_future(_drain(h)), timeout=120) for h in handles]
            return sum(1 for _t, e in results if e is None)

        try:
            victim = next(rep for rep in fleet.replicas
                          if any(fleet.replica_for(c) is rep for c in prompts))
            handles = {}
            for conv, prompt in prompts.items():
                rep = fleet.replica_for(conv)
                handles[conv] = await rep.scheduler.submit(
                    conv, prompt, _greedy(10), conversation_id=conv)
            tasks = {c: asyncio.create_task(_drain(h))
                     for c, h in handles.items()}
            if fault:
                while any(h.generated < 2 for h in handles.values()
                          if fleet.replica_for(h.conversation_id) is victim):
                    await asyncio.sleep(0.002)
                dead = [True]

                def wedge(**ctx):
                    if dead[0] and ctx.get("replica") == victim.replica_id:
                        raise RuntimeError("drill: dead replica")

                faults.arm("scheduler.decode", wedge)
                faults.arm("engine.rebuild", wedge)
            results = {c: await asyncio.wait_for(t, timeout=120)
                       for c, t in tasks.items()}
            out["tokens"] = {c: toks for c, (toks, _e) in results.items()}
            out["errors"] = sum(1 for _t, e in results.values()
                                if e is not None)
            if fault:
                # poke the wedged replica until its breaker gives up
                # (probe streams drain to siblings and still complete)
                for i in range(6):
                    if victim.state != LIVE:
                        break
                    h = await victim.scheduler.submit(
                        f"probe{i}", list(range(50 + i, 62 + i)), _greedy(3))
                    _t, e = await asyncio.wait_for(
                        asyncio.ensure_future(_drain(h)), timeout=120)
                    out["errors"] += 1 if e is not None else 0
                for _ in range(2000):
                    if victim.state != LIVE:
                        break
                    await asyncio.sleep(0.01)
                out["victim_out"] = victim.state != LIVE
                out["live_during"] = int(
                    METRICS.get("finchat_fleet_replicas_live"))
                # the outage wave: the router spreads new conversations
                # over the survivors, and every one of them is answered
                out["served_during"] = await wave("during", avoid=victim)
                dead[0] = False  # heal: the supervisor's revive succeeds
                for _ in range(2000):
                    if victim.state == LIVE:
                        break
                    await asyncio.sleep(0.01)
                out["victim_respawned"] = victim.state == LIVE
                out["live_after"] = int(
                    METRICS.get("finchat_fleet_replicas_live"))
                out["served_after"] = await wave("after")
            for rep in fleet.replicas:
                rep.scheduler.allocator.check_invariants()
        finally:
            await fleet.stop()
            faults.disarm_all()
        return out

    clean = asyncio.run(run(False))
    drained0 = METRICS.get("finchat_fleet_drained_streams_total")
    chaos = asyncio.run(run(True))
    assert chaos["errors"] == 0
    assert chaos["tokens"] == clean["tokens"]  # byte-identical on siblings
    assert METRICS.get("finchat_fleet_drained_streams_total") > drained0
    assert chaos["victim_out"] and chaos["live_during"] == 2
    assert chaos["victim_respawned"] and chaos["live_after"] == 3
    # goodput: every turn offered during the outage and after the respawn
    assert chaos["served_during"] == 4 and chaos["served_after"] == 4


def test_cancel_of_drained_handle_targets_adopter():
    """A handle drained to a sibling is OWNED by the adopter: cleanup
    paths (the generator's disconnect/watchdog cancel) still hold the
    SOURCE scheduler, and cancelling there must delegate — evicting on
    the source with the adopter's slot index would kill an unrelated
    stream on the source and leak the slot+pages on the adopter."""

    async def run():
        fleet = _make_fleet(2)
        await fleet.start()
        try:
            a, b = fleet.replicas
            # a live stream on A (the one the 'client' will abandon) and
            # an unrelated stream on A that must survive the cancel
            h = await a.scheduler.submit("drained", list(range(1, 14)),
                                         _greedy(40))
            other = await a.scheduler.submit("bystander", list(range(30, 44)),
                                             _greedy(40))
            while h.generated < 2 or other.generated < 2:
                await asyncio.sleep(0.002)
            # breaker-style drain of h: preempt to host, sibling adopts.
            # Mirror _drain_to_sink faithfully: the drain POPS the handle
            # from the source's pending before offering it — leaving it
            # there makes both schedulers race to admit the same handle
            # (caught by the ISSUE 8 leak sanitizer: the loser strands a
            # slot and a phantom prefilling entry on the source)
            a.scheduler._preempt(h, for_rebuild=True)
            a.scheduler.pending.remove(h)
            b.scheduler.adopt(h)
            assert h.owner is b.scheduler
            while h.slot < 0:  # B admits the replay
                await asyncio.sleep(0.002)
            # the client goes away; the generator's finally still holds A
            a.scheduler.cancel(h)
            for _ in range(500):
                if h.finished and h.slot == -1:
                    break
                await asyncio.sleep(0.01)
            assert h.finished
            # the bystander on A kept streaming (its slot was untouched)
            g0 = other.generated
            for _ in range(500):
                if other.generated > g0 or other.finished:
                    break
                await asyncio.sleep(0.01)
            assert other.generated > g0 or other.finished
            await asyncio.wait_for(asyncio.ensure_future(_drain(other)),
                                   timeout=120)
            for rep in fleet.replicas:
                rep.scheduler.allocator.check_invariants()
            # nothing leaked on the adopter: its slot pool is whole again
            assert len(b.scheduler.free_slots) == 3
            assert not b.scheduler.decoding
        finally:
            await fleet.stop()

    asyncio.run(run())


def test_giveup_with_no_sibling_counts_each_drain_failure_once():
    """Last-replica-standing give-up: the sink refuses every offer (no
    live sibling) and the pending-fail loop fails each stream with a
    retryable ``replica_out`` error — finchat_fleet_drain_failures_total
    moves by EXACTLY one per failed stream (the sink's refusal must not
    also count, or an operator alert keyed on the series reads 2x)."""

    async def run() -> dict:
        config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
        params = init_params(config, jax.random.key(0))
        reps = [_make_replica(str(i), params, config) for i in range(2)]
        fleet = EngineFleet(reps, FleetConfig(replicas=2, respawn=False),
                            num_partitions=16)
        await fleet.start()
        out: dict = {}
        try:
            a, b = fleet.replicas
            fleet._mark_out(b)  # the sink has nowhere to place a drain
            handles = [await a.scheduler.submit(
                f"lone-{i}", list(range(3 * i + 1, 3 * i + 14)), _greedy(40),
                conversation_id=f"lone-{i}") for i in range(2)]
            while any(h.generated < 2 for h in handles):
                await asyncio.sleep(0.002)
            failures0 = METRICS.get("finchat_fleet_drain_failures_total")
            drained0 = METRICS.get("finchat_fleet_drained_streams_total")
            faults.arm("scheduler.decode",
                       lambda **ctx: (_ for _ in ()).throw(
                           RuntimeError("drill: no sibling")))
            results = [await asyncio.wait_for(
                asyncio.ensure_future(_drain(h)), timeout=120)
                for h in handles]
            out["errors"] = [e for _t, e in results]
            out["failures_delta"] = (
                METRICS.get("finchat_fleet_drain_failures_total") - failures0)
            out["drained_delta"] = (
                METRICS.get("finchat_fleet_drained_streams_total") - drained0)
            # the OUT replica's queue is empty — no phantom backlog on
            # the gauge for its whole OUT period
            out["queue_depth"] = METRICS.get(
                "finchat_queue_depth", labels={"replica": "0"})
        finally:
            await fleet.stop()
            faults.disarm_all()
        return out

    out = asyncio.run(run())
    assert all(e is not None and e["code"] == "replica_out"
               and e["retryable"] for e in out["errors"])
    assert out["failures_delta"] == 2  # once per stream, not once per site
    assert out["drained_delta"] == 0
    assert out["queue_depth"] == 0


def test_adopt_honors_queue_bound_for_never_admitted_handles():
    """A give-up drain offers the victim's whole pending queue to
    siblings. Live streams (preempted/generated) always adopt — they jump
    the queue like local preempt-replays, which never count against the
    bound. NEVER-admitted handles are plain queued load: an adopter at
    ``max_queue_depth`` must refuse them (sink returns False → the
    give-up pending-fail loop sheds them retryable), or the transplant
    lands the sibling past its bound and submit() locks out every new
    client with OverloadedError until the foreign backlog drains."""

    async def run() -> None:
        config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
        params = init_params(config, jax.random.key(0))
        a = _make_replica("0", params, config)
        b = _make_replica("1", params, config, max_queue_depth=2)
        fleet = EngineFleet([a, b], FleetConfig(replicas=2, respawn=False),
                            num_partitions=16)
        # schedulers NOT started: submits stay pending (never admitted)
        for i in range(2):
            await b.scheduler.submit(f"b-{i}", list(range(1, 10)),
                                     _greedy(8))
        fresh = await a.scheduler.submit("fresh", list(range(1, 10)),
                                         _greedy(8), conversation_id="cv")
        assert not b.scheduler.adopt(fresh)  # at the bound: refused
        assert fresh.owner is a.scheduler  # untouched — still the source's
        assert len(b.scheduler.pending) == 2
        # the drain sink surfaces the refusal (handle stays with source)
        drained0 = METRICS.get("finchat_fleet_drained_streams_total")
        sink = fleet._make_drain_sink(a)
        assert sink(fresh, None) is False
        assert METRICS.get("finchat_fleet_drained_streams_total") == drained0
        # a LIVE stream adopts even at the bound (queue-jumps like a
        # local preempt-replay) and rebinds its owner
        live = await a.scheduler.submit("live", list(range(1, 10)),
                                        _greedy(8))
        live.preempted = True
        assert b.scheduler.adopt(live)
        assert live.owner is b.scheduler
        assert b.scheduler.pending[0] is live

    asyncio.run(run())


def test_fail_prefix_job_resolves_future_when_reset_slot_raises():
    """``_fail_prefix_job`` runs a device op (reset_slot) that can raise
    on the very dead device that is failing the job. The job is already
    off ``_prefix_jobs`` by then, so nothing later can resolve it — the
    slot must come back and the future must resolve anyway, or the
    register_prefix_async awaiter hangs forever. And the error must NOT
    propagate: two callers (_fail_prefill_round under breaker_threshold=0,
    stop()) are unguarded — an escaping exception there kills the
    scheduler loop and strands every remaining job's awaiter."""

    async def run() -> None:
        config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
        params = init_params(config, jax.random.key(0))
        rep = _make_replica("0", params, config)
        sched = rep.scheduler
        pages = sched.allocator.allocate("__prefix_test__", 2)
        slot = sched.free_slots.pop()
        job = _PrefixJob(ids=list(range(16)), shared_len=16,
                         owner="__prefix_test__", pages=pages, slot=slot,
                         future=asyncio.get_running_loop().create_future())
        sched._prefix_jobs.append(job)

        def dead(_slot):
            raise RuntimeError("drill: device gone")

        sched.engine.reset_slot = dead
        sched._fail_prefix_job(job)  # must neither raise nor hang
        assert job.future.done() and job.future.result() == 0
        assert job not in sched._prefix_jobs
        assert slot in sched.free_slots
        sched.allocator.check_invariants()

    asyncio.run(run())


def test_revive_async_threads_rebuild_and_resolves_prefix_futures():
    """``revive_async`` is what the supervisor runs: the device rebuild —
    seconds of KV-pool reallocation at real sizes — must leave the shared
    event loop free for the sibling schedulers (worker thread), while a
    prefix job stranded from before the give-up resolves device-free on
    the loop (no reset_slot against the dead engine)."""

    async def run() -> None:
        config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
        params = init_params(config, jax.random.key(0))
        rep = _make_replica("0", params, config)
        sched = rep.scheduler
        pages = sched.allocator.allocate("__prefix_test__", 1)
        slot = sched.free_slots.pop()
        job = _PrefixJob(ids=list(range(8)), shared_len=8,
                         owner="__prefix_test__", pages=pages, slot=slot,
                         future=asyncio.get_running_loop().create_future())
        sched._prefix_jobs.append(job)
        sched.gave_up = True
        rebuild_thread: list[int] = []
        real_rebuild = sched.engine.rebuild_device_state

        def spying_rebuild():
            rebuild_thread.append(threading.get_ident())
            real_rebuild()

        sched.engine.rebuild_device_state = spying_rebuild
        assert await sched.revive_async()
        assert rebuild_thread and rebuild_thread[0] != threading.get_ident()
        assert job.future.done() and job.future.result() == 0
        assert not sched._prefix_jobs
        assert not sched.gave_up
        assert len(sched.free_slots) == sched.engine.engine_cfg.max_seqs
        sched.allocator.check_invariants()

    asyncio.run(run())


def test_respawn_rechecks_giveup_before_marking_live():
    """A flaky device can re-wedge DURING the respawn: the on_respawn
    prompt-head re-registration drives real prefill rounds, and a breaker
    give-up fired while state is RESPAWNING is invisible to _mark_out
    (LIVE-guarded). The supervisor must re-check ``gave_up`` after the
    hooks — marking LIVE anyway would route every new conversation to a
    known-wedged engine for a full fail-streak cycle each."""

    async def run() -> dict:
        fleet = _make_fleet(2)
        await fleet.start()
        out: dict = {}
        try:
            a, b = fleet.replicas
            rewedged = {"n": 0}

            def rewedge_once(rep):
                # first attempt: the re-registration "trips to give-up"
                if rep is b and rewedged["n"] == 0:
                    rewedged["n"] += 1
                    rep.scheduler.gave_up = True

            fleet.on_respawn.append(rewedge_once)
            b.scheduler.gave_up = True
            fleet._mark_out(b)
            for _ in range(1000):
                if b.state == LIVE:
                    break
                await asyncio.sleep(0.01)
            out["state"] = b.state
            out["rewedged"] = rewedged["n"]
            out["gave_up"] = b.scheduler.gave_up
        finally:
            await fleet.stop()
        return out

    out = asyncio.run(run())
    assert out["state"] == LIVE  # the retry (no re-wedge) went LIVE
    assert out["rewedged"] == 1  # attempt 1 ran the hooks and was rejected
    assert not out["gave_up"]  # LIVE only with the give-up actually clear


def test_poll_gate_counts_only_live_replicas():
    """The Kafka poll gate sizes in-flight claims by LIVE replicas:
    during an outage a worker polling at full-fleet capacity hoards
    messages the survivors must absorb instead of letting the consumer
    group redistribute them. Floored at one batch so a whole-fleet-out
    window still answers (retryable errors), never black-holes."""
    from finchat_tpu.serve.app import App

    fleet = _stub_fleet(4)
    stub = types.SimpleNamespace(
        cfg=types.SimpleNamespace(engine=types.SimpleNamespace(max_seqs=3)),
        fleet=fleet,
    )
    assert App._max_inflight(stub) == 12
    fleet.replicas[0].state = OUT
    assert App._max_inflight(stub) == 9
    for rep in fleet.replicas:
        rep.state = OUT
    assert App._max_inflight(stub) == 3  # floor: one batch
    stub.fleet = None
    assert App._max_inflight(stub) == 3  # fleetless: one engine, one batch


def test_session_migration_at_route_time():
    """A conversation whose session bytes retired on a replica that then
    went OUT resumes on its rerouted sibling FROM THOSE BYTES: the router
    migrates the entry at route time (counted), the source copy is
    discarded, and admission reports resumed_len > 0 with the greedy
    stream byte-identical to an unmigrated second turn."""

    async def run(kill_home: bool) -> dict:
        fleet = _make_fleet(2)
        await fleet.start()
        try:
            conv = "mig-conv"
            home = fleet.replica_for(conv)
            t1_prompt = list(range(1, 14))
            h1 = await home.scheduler.submit(
                "t1", t1_prompt, _greedy(10), conversation_id=conv)
            t1_tokens, err = await asyncio.wait_for(
                asyncio.ensure_future(_drain(h1)), timeout=120)
            assert err is None
            # retirement offloaded the entry on HOME
            assert home.scheduler.session_cache.get(conv) is not None
            m0 = METRICS.get("finchat_fleet_session_migrations_total")
            if kill_home:
                home.state = OUT
            rep2 = fleet.replica_for(conv)
            if kill_home:
                assert rep2 is not home
                # route-time migration moved the bytes, source discarded
                assert METRICS.get(
                    "finchat_fleet_session_migrations_total") == m0 + 1
                assert home.scheduler.session_cache.get(conv) is None
                assert rep2.scheduler.session_cache.get(conv) is not None
            t2_prompt = t1_prompt + t1_tokens + [7, 8, 9]
            h2 = await rep2.scheduler.submit(
                "t2", t2_prompt, _greedy(8), conversation_id=conv)
            t2_tokens, err = await asyncio.wait_for(
                asyncio.ensure_future(_drain(h2)), timeout=120)
            assert err is None
            return {"t2": t2_tokens, "resumed": h2.resumed_len}
        finally:
            await fleet.stop()

    stay = asyncio.run(run(False))
    moved = asyncio.run(run(True))
    assert moved["t2"] == stay["t2"]  # migration can't change the stream
    assert moved["resumed"] > 0  # admission resumed from migrated bytes
    assert moved["resumed"] == stay["resumed"]  # same profile as staying home


def test_route_time_migration_moves_role_suffixed_keys():
    """The PRODUCTION serving path keys session entries per LLM role
    (``conv#resp`` — agent/graph.py via session_key), while the router is
    asked for the BARE conversation id: route-time migration must find
    and move the suffixed entries too, or lazy migration is inert for
    real traffic (it only ever worked for direct scheduler submissions)."""

    async def run() -> None:
        fleet = _make_fleet(2)
        await fleet.start()
        try:
            conv = "prod-conv"
            key = session_key(conv, "resp")
            home = fleet.replica_for(conv)
            h1 = await home.scheduler.submit(
                "t1", list(range(1, 14)), _greedy(10), conversation_id=key)
            _toks, err = await asyncio.wait_for(
                asyncio.ensure_future(_drain(h1)), timeout=120)
            assert err is None
            assert home.scheduler.session_cache.get(key) is not None
            m0 = METRICS.get("finchat_fleet_session_migrations_total")
            home.state = OUT
            rep2 = fleet.replica_for(conv)  # routed by the BARE id
            assert rep2 is not home
            assert METRICS.get(
                "finchat_fleet_session_migrations_total") == m0 + 1
            assert home.scheduler.session_cache.get(key) is None
            assert rep2.scheduler.session_cache.get(key) is not None
        finally:
            await fleet.stop()

    asyncio.run(run())


def test_drain_sink_routes_by_conversation_not_role_key():
    """A drained handle carries the per-role cache key as its
    conversation_id; the sink must pick the sibling by the BARE
    conversation — the replica the conversation's NEXT TURNS route to —
    or the handed-off session bytes strand on a non-affinity sibling and
    a conversation's #tool/#resp streams can split across replicas."""
    fleet = _stub_fleet(4)
    adopted: list[str] = []
    imported: list[str] = []
    for rep in fleet.replicas:
        rep.scheduler.adopt = (
            lambda h, rid=rep.replica_id: (adopted.append(rid), True)[1])
        rep.scheduler.import_session_entry = (
            lambda p, rid=rep.replica_id: imported.append(rid) or True)
    source = fleet.replicas[0]

    def owner(key):
        return fleet.replica_for_partition(
            fleet.partition_for(key), exclude=source)

    # a conversation whose raw role key would route elsewhere — the
    # regression this pins (routing once hashed handle.conversation_id)
    conv = next(c for c in (f"conv-{i}" for i in range(500))
                if owner(c) is not owner(session_key(c, "resp")))
    expected = owner(conv).replica_id
    sink = source.scheduler.drain_sink
    for role in SESSION_KEY_ROLES:
        handle = types.SimpleNamespace(
            conversation_id=session_key(conv, role), seq_id=f"s-{role}")
        assert sink(handle, {"conversation_id": handle.conversation_id})
    assert adopted == [expected] * 2  # both roles, both on the home sibling
    assert imported == [expected] * 2


def test_session_import_relinks_shared_head_or_refuses():
    """An exported entry whose KV rides a shared prompt head re-links
    against the importer's OWN live registration of that head (ref
    counted); an importer with no matching head refuses the entry
    (counted) instead of serving positionally-wrong KV."""

    async def run():
        fleet = _make_fleet(2)
        await fleet.start()
        try:
            a, b = fleet.replicas
            head = list(range(1, 12))  # page-whole shared part: 8 tokens
            assert a.scheduler.register_prefix(head) >= 8
            payload = {
                "conversation_id": "hc",
                "token_ids": np.asarray(head[:8], np.int32),
                "prefix_len": 8,
                "snap": None,
            }
            refused0 = METRICS.get("finchat_fleet_session_import_refused_total")
            # b has no matching head: refused, counted (unlabeled, like
            # every finchat_fleet_* series), nothing cached
            assert not b.scheduler.import_session_entry(dict(payload))
            assert METRICS.get(
                "finchat_fleet_session_import_refused_total") == refused0 + 1
            assert b.scheduler.session_cache.get("hc") is None
            # a holds the head: the import re-links and takes a reference
            entry_a = a.scheduler._prefixes[0]
            refs0 = entry_a.refs
            assert a.scheduler.import_session_entry(dict(payload))
            got = a.scheduler.session_cache.get("hc")
            assert got is not None and got.prefix_entry is entry_a
            assert entry_a.refs == refs0 + 1
            # dropping the entry releases the reference (on_drop path)
            a.scheduler.session_cache.discard("hc")
            assert entry_a.refs == refs0
        finally:
            await fleet.stop()

    asyncio.run(run())


def test_replica_labeled_metrics_render():
    """Per-replica series share one TYPE line per family and carry the
    replica label — the scrape separates a draining replica from its
    healthy siblings."""
    reg = MetricsRegistry()
    reg.labeled(replica="0").inc("finchat_preemptions_total")
    reg.labeled(replica="1").inc("finchat_preemptions_total", 2)
    reg.labeled(replica="1").set_gauge("finchat_breaker_state", 1)
    assert reg.get("finchat_preemptions_total", {"replica": "0"}) == 1
    assert reg.get("finchat_preemptions_total", {"replica": "1"}) == 2
    text = reg.render_prometheus()
    assert text.count("# TYPE finchat_preemptions_total counter") == 1
    assert 'finchat_preemptions_total{replica="0"} 1' in text
    assert 'finchat_preemptions_total{replica="1"} 2' in text
    assert 'finchat_breaker_state{replica="1"} 1' in text
