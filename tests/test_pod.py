"""Pod-scale multi-host fleet (ISSUE 20; serve/pod.py, io/journal.py v2,
ROBUSTNESS.md §7).

What must hold:

- journal ownership aligns with PARTITION ownership: per-partition files,
  legacy single-file migration (one-way), seq-stamped lines so multiple
  files interleave by true append order at replay — a rebalance can never
  age a recently answered id out of the ring early (the ISSUE 20 bugfix);
- the liaison frame codec detects every corruption (CRC + length), the
  transport is asyncio-only, peers carry circuit breakers, and the
  ``pod.heartbeat`` / ``pod.transfer`` fault sites are armable;
- a host death is a group rebalance: survivors adopt EXACTLY the dead
  host's partitions, replay exactly those journals into their dedupe
  rings (zero double answers after a host-level kill -9), and a rejoin
  under the old member id restores the exact prior mapping;
- the session wire format (the disk tier's checksummed v2 records)
  crosses hosts: a record exported under {fp32, int8-KV} × {bounded,
  unbounded} imports on a DIFFERENT host's fresh engine with
  byte-identical greedy resume — and a cross-KV-mode record is refused
  and counted, never garbage-decoded;
- pod off (no ``pod.host_id``) or liaison-less single host is
  bit-identical to the plain fleet.
"""

import asyncio
import dataclasses
import json
import socket
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finchat_tpu.engine.engine import InferenceEngine
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
from finchat_tpu.engine.session_cache import SessionDiskTier
from finchat_tpu.io.journal import AnsweredJournal, partition_filename
from finchat_tpu.io.kafka import InMemoryBroker, KafkaClient, partition_for_key
from finchat_tpu.models.llama import PRESETS, init_params
from finchat_tpu.serve import pod as pod_mod
from finchat_tpu.serve.fleet import DedupeRing, EngineFleet, EngineReplica
from finchat_tpu.serve.pod import (
    PEER_DEAD,
    PEER_LIVE,
    PeerChannel,
    PodCoordinator,
    decode_frame,
    encode_frame,
    parse_peers,
)
from finchat_tpu.utils import faults
from finchat_tpu.utils.config import (
    GROUP_ID,
    USER_MESSAGE_TOPIC,
    EngineConfig,
    FleetConfig,
    KafkaConfig,
    PodConfig,
)
from finchat_tpu.utils.metrics import METRICS

CONFIG = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
PAGE = 8
CHUNK = 16


@pytest.fixture(autouse=True)
def _clean_pod_state():
    yield
    faults.disarm_all()
    pod_mod._INPROC.clear()


@pytest.fixture(scope="module")
def params():
    return init_params(CONFIG, jax.random.key(0))


def _make_scheduler(params, replica_id="0", kv_quant="", bounded=False):
    cfg = EngineConfig(
        max_seqs=3, page_size=PAGE, num_pages=96, max_seq_len=256,
        prefill_chunk=CHUNK, session_cache=True, kv_quant=kv_quant,
        kv_sink_pages=1 if bounded else 0,
        kv_window_pages=4 if bounded else 0,
    )
    return ContinuousBatchingScheduler(
        InferenceEngine(CONFIG, params, cfg), eos_id=-1, replica_id=replica_id
    )


async def _collect(scheduler, seq_id, prompt_ids, n_new, conversation_id=None):
    handle = await scheduler.submit(
        seq_id, list(prompt_ids),
        SamplingParams(temperature=0.0, max_new_tokens=n_new),
        conversation_id=conversation_id,
    )
    tokens = []
    while True:
        event = await asyncio.wait_for(handle.events.get(), timeout=120)
        if event["type"] == "token":
            tokens.append(event["token_id"])
        elif event["type"] == "done":
            return handle, tokens
        else:
            return handle, event


def _pod_record(sched, key):
    """A conversation's session-cache entry as pod-transfer wire bytes
    (the disk tier's serialized record — exactly what the liaison ships)."""
    payload = sched.export_session(key)
    assert payload is not None
    return SessionDiskTier._serialize(
        key, payload["token_ids"], payload["prefix_len"], payload["snap"],
        payload["kv_gap"], payload["kv_sink"],
    )


def _import_record(sched, raw):
    rec = SessionDiskTier._deserialize(raw)
    rec = sched.session_cache.fit_payload(rec)
    return rec is not None and sched.import_session_entry(rec)


# --- per-partition journal plane -------------------------------------------

def test_journal_per_partition_layout_and_inherited_replay(tmp_path):
    """One file per partition; ``replay(partitions=...)`` replays exactly
    the inherited partitions' ids — the adoption contract."""
    j = AnsweredJournal(str(tmp_path), num_partitions=4)
    j.append("a0", partition=0)
    j.append("b0", partition=2)
    j.append("a1", partition=0)
    j.close()
    assert (tmp_path / partition_filename(0)).exists()
    assert (tmp_path / partition_filename(2)).exists()
    assert not (tmp_path / partition_filename(1)).exists()
    assert AnsweredJournal(str(tmp_path)).partitions_on_disk() == [0, 2]
    # inherited-only replay (compact=False: an adopter never rewrites
    # files it is only just inheriting)
    assert AnsweredJournal(str(tmp_path)).replay(
        partitions=[2], compact=False) == ["b0"]
    assert AnsweredJournal(str(tmp_path)).replay(
        partitions=[0], compact=False) == ["a0", "a1"]
    # full replay interleaves by append order across files
    assert AnsweredJournal(str(tmp_path)).replay() == ["a0", "b0", "a1"]


def test_journal_seq_merge_keeps_global_recency(tmp_path):
    """The ISSUE 20 bugfix pin: replay interleaves MULTIPLE partition
    files by the per-line seq stamp. Naive per-file concatenation (p0
    then p1) would order the stale p1 ids AFTER the newer p0 ids and age
    the still-hot ones out of the ``keep`` window early."""
    j = AnsweredJournal(str(tmp_path), num_partitions=4, keep=3)
    j.append("b0", partition=1)  # oldest
    j.append("b1", partition=1)
    j.append("a0", partition=0)  # newest three
    j.append("a1", partition=0)
    j.append("a2", partition=0)
    j.close()
    # true append order keeps the three newest; the naive p0-then-p1
    # concat would have produced ["a2", "b0", "b1"] — dropping hot ids
    # for stale ones
    assert AnsweredJournal(str(tmp_path), keep=3).replay() == ["a0", "a1", "a2"]


def test_journal_seq_survives_restart_and_adoption_order(tmp_path):
    """Seqs stay monotonic across writer restarts, so a restarted host's
    new appends still sort AFTER everything already on disk — adoption
    replay order is append order even through restarts."""
    j1 = AnsweredJournal(str(tmp_path), num_partitions=2)
    j1.append("old", partition=0)
    j1.close()
    j2 = AnsweredJournal(str(tmp_path), num_partitions=2)
    j2.replay()  # seeds the seq counter past everything on disk
    j2.append("new", partition=1)
    j2.close()
    assert AnsweredJournal(str(tmp_path)).replay() == ["old", "new"]


def test_journal_legacy_migration_one_way(tmp_path, caplog):
    """A pre-ISSUE-20 single ``answered.journal`` splits into
    per-partition files on first startup: each id lands on the partition
    the broker's CRC32 partitioner assigns its JSON form (where its
    redelivery will be consumed), order is preserved, the torn tail is
    dropped, and the legacy file is gone — one-way, logged."""
    mids = ["x1", "x2", "x3", 42]
    legacy = tmp_path / AnsweredJournal.FILENAME
    lines = b""
    for mid in mids:
        body = json.dumps(mid).encode()
        lines += b"v1 %08x " % zlib.crc32(body) + body + b"\n"
    legacy.write_bytes(lines + b"v1 deadbe")  # torn final line (crash)
    import logging
    with caplog.at_level(logging.INFO, logger="finchat_tpu.io.journal"):
        j = AnsweredJournal(str(tmp_path), num_partitions=4)
    assert any("migrated legacy" in r.getMessage() for r in caplog.records)
    assert not legacy.exists()
    for mid in mids:
        part = partition_for_key(json.dumps(mid), 4)
        assert (tmp_path / partition_filename(part)).exists()
    # order preserved across the split (seq-merged replay)
    assert j.replay() == mids
    j.close()
    # idempotent: a second startup has nothing to migrate and replays
    # identically
    assert AnsweredJournal(str(tmp_path), num_partitions=4).replay() == mids


def test_journal_migration_appends_land_in_partition_files(tmp_path):
    """Post-migration appends extend the per-partition files (fsync
    contract unchanged), and replay merges migrated + fresh lines in
    append order."""
    body = json.dumps("m-old").encode()
    (tmp_path / AnsweredJournal.FILENAME).write_bytes(
        b"v1 %08x " % zlib.crc32(body) + body + b"\n"
    )
    j = AnsweredJournal(str(tmp_path), num_partitions=4)
    j.append("m-new", partition=1)
    j.close()
    assert AnsweredJournal(str(tmp_path)).replay() == ["m-old", "m-new"]


def test_journal_fsync_before_return_and_relief_valve(tmp_path, monkeypatch):
    """Re-assert the §5 ordering through the per-partition split: append
    fsyncs the PARTITION file before returning (the commit that follows
    observes a durable id), and ``journal.fsync=false`` skips it."""
    import finchat_tpu.io.journal as journal_mod

    real_fsync = journal_mod.os.fsync
    calls = []

    def spy(fd):
        calls.append(fd)
        real_fsync(fd)

    monkeypatch.setattr(journal_mod.os, "fsync", spy)
    j = AnsweredJournal(str(tmp_path), fsync=True, num_partitions=4)
    assert j.append("m1", partition=3) is True
    assert len(calls) == 1  # durably on disk by the time append returned
    assert AnsweredJournal(str(tmp_path)).replay(
        partitions=[3], compact=False) == ["m1"]
    calls.clear()
    j2 = AnsweredJournal(str(tmp_path), fsync=False, num_partitions=4)
    assert j2.append("m2", partition=3) is True
    assert calls == []  # the relief valve really skips fsync
    j.close()
    j2.close()


def test_journal_torn_line_per_partition(tmp_path):
    """A torn tail in ONE partition file quarantines only that line; the
    file's intact records and every other partition still replay."""
    j = AnsweredJournal(str(tmp_path), num_partitions=4)
    j.append("p0-a", partition=0)
    j.append("p1-a", partition=1)
    j.append("p0-b", partition=0)
    j.close()
    with open(tmp_path / partition_filename(0), "ab") as f:
        f.write(b"v2 dead")  # crash mid-append
    q0 = METRICS.get("finchat_durability_quarantines_total")
    assert AnsweredJournal(str(tmp_path)).replay() == ["p0-a", "p1-a", "p0-b"]
    assert METRICS.get("finchat_durability_quarantines_total") == q0 + 1


# --- liaison frame codec and transport -------------------------------------

def test_frame_codec_roundtrip_and_corruption_detection():
    raw = encode_frame("pull_session", {"key": "c#resp"}, b"payload-bytes")
    op, meta, payload = decode_frame(raw)
    assert (op, meta["key"], payload) == ("pull_session", "c#resp",
                                          b"payload-bytes")
    # bit flip in the payload: CRC catches it
    flipped = bytearray(raw)
    flipped[-1] ^= 0xFF
    with pytest.raises(ValueError, match="checksum"):
        decode_frame(bytes(flipped))
    # truncation: length prefix catches it
    with pytest.raises(ValueError, match="truncated"):
        decode_frame(raw[:-3])
    # wrong magic / unknown version never misparse
    with pytest.raises(ValueError, match="magic"):
        decode_frame(b"XPOD" + raw[4:])
    with pytest.raises(ValueError, match="version"):
        decode_frame(raw[:4] + bytes([99]) + raw[5:])


def test_parse_peers_validates_loudly():
    assert parse_peers("b=tcp:127.0.0.1:9710, c=inproc:hostC") == {
        "b": "tcp:127.0.0.1:9710", "c": "inproc:hostC",
    }
    assert parse_peers("") == {}
    with pytest.raises(ValueError):
        parse_peers("no-address-here")
    with pytest.raises(ValueError):
        parse_peers("b=udp:127.0.0.1:1")


def _pod_cfg(host, listen="", peers="", **kw):
    defaults = dict(heartbeat_interval_seconds=60.0,
                    heartbeat_miss_threshold=2,
                    transfer_timeout_seconds=1.0, transfer_retries=1,
                    retry_backoff_seconds=0.0, breaker_threshold=3,
                    breaker_cooldown_seconds=0.05)
    defaults.update(kw)
    return PodConfig(host_id=host, listen=listen, peers=peers, **defaults)


async def test_inproc_liaison_ping_pull_miss_and_kill():
    coord_a = PodCoordinator(_pod_cfg("hostA", listen="inproc:hostA"))
    await coord_a.start()
    coord_b = PodCoordinator(_pod_cfg("hostB", peers="hostA=inproc:hostA"))
    try:
        peer = coord_b.peers["hostA"]
        op, meta, _ = await coord_b.liaison.call(peer.addr, "ping", {})
        assert op == "pong" and meta["host_id"] == "hostA"
        # no fleet on hostA: every pull is an honest miss
        op, _, _ = await coord_b.liaison.call(
            peer.addr, "pull_session", {"key": "nope"})
        assert op == "miss"
        # unknown ops answer an error frame, never crash the server
        op, meta, _ = await coord_b.liaison.call(peer.addr, "bogus", {})
        assert op == "error" and "bogus" in meta["message"]
        # kill -9: drops off the wire, dials fail from then on
        coord_a.kill()
        with pytest.raises(ConnectionError):
            await coord_b.liaison.call(peer.addr, "ping", {})
    finally:
        coord_a.kill()
        await coord_b.stop()


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


async def test_tcp_liaison_roundtrip_and_refused_dial():
    port = _free_port()
    coord_a = PodCoordinator(_pod_cfg("hostA", listen=f"tcp:127.0.0.1:{port}"))
    await coord_a.start()
    coord_b = PodCoordinator(
        _pod_cfg("hostB", peers=f"hostA=tcp:127.0.0.1:{port}"))
    try:
        peer = coord_b.peers["hostA"]
        op, meta, _ = await coord_b.liaison.call(
            peer.addr, "ping", {}, timeout=2.0)
        assert op == "pong" and meta["host_id"] == "hostA"
        coord_a.kill()
        with pytest.raises((ConnectionError, OSError, asyncio.TimeoutError)):
            await coord_b.liaison.call(peer.addr, "ping", {}, timeout=0.5)
    finally:
        coord_a.kill()
        await coord_b.stop()


def test_breaker_opens_at_threshold_and_half_open_probe():
    cfg = _pod_cfg("hostB", breaker_threshold=2,
                   breaker_cooldown_seconds=3600.0)
    peer = PeerChannel("hostA", "inproc:hostA", cfg)
    trips0 = METRICS.get("finchat_pod_breaker_trips_total")
    assert peer.breaker_allows()
    peer.record_failure()
    assert peer.breaker_allows()  # below threshold
    peer.record_failure()
    assert not peer.breaker_allows()  # open
    assert METRICS.get("finchat_pod_breaker_trips_total") == trips0 + 1
    peer.record_failure()  # further failures do not re-count the trip
    assert METRICS.get("finchat_pod_breaker_trips_total") == trips0 + 1
    # cooldown elapsed -> the half-open probe rides through; success closes
    peer._open_until = 0.0
    assert peer.breaker_allows()
    peer.record_success()
    assert peer.breaker_allows()


async def test_heartbeat_fault_site_death_and_rejoin():
    """``pod.heartbeat`` is armable; miss_threshold consecutive failures
    declare the peer dead (counted + anomaly), and a later pong rejoins
    it."""
    coord_a = PodCoordinator(_pod_cfg("hostA", listen="inproc:hostA"))
    await coord_a.start()
    coord_b = PodCoordinator(_pod_cfg("hostB", peers="hostA=inproc:hostA"))
    peer = coord_b.peers["hostA"]
    try:
        hb0 = METRICS.get("finchat_pod_heartbeats_total")
        await coord_b._heartbeat(peer)
        assert METRICS.get("finchat_pod_heartbeats_total") == hb0 + 1
        assert peer.state == PEER_LIVE and peer.misses == 0

        deaths0 = METRICS.get("finchat_pod_peer_deaths_total")
        fails0 = METRICS.get("finchat_pod_heartbeat_failures_total")
        faults.arm("pod.heartbeat", faults.n_shot(2, RuntimeError("cable cut")))
        await coord_b._heartbeat(peer)
        assert peer.state == PEER_LIVE and peer.misses == 1
        await coord_b._heartbeat(peer)  # second miss = threshold
        assert peer.state == PEER_DEAD
        assert METRICS.get("finchat_pod_peer_deaths_total") == deaths0 + 1
        assert METRICS.get("finchat_pod_heartbeat_failures_total") == fails0 + 2
        assert METRICS.get("finchat_pod_hosts_live") == 1.0

        rejoin0 = METRICS.get("finchat_pod_peer_rejoins_total")
        await coord_b._heartbeat(peer)  # fault exhausted: pong again
        assert peer.state == PEER_LIVE
        assert METRICS.get("finchat_pod_peer_rejoins_total") == rejoin0 + 1
        assert METRICS.get("finchat_pod_hosts_live") == 2.0
    finally:
        coord_a.kill()
        await coord_b.stop()


# --- host death: partition adoption + exactly-once dedupe ------------------

async def test_host_death_adoption_replays_inherited_journals_exactly(tmp_path):
    """The tentpole drill at the coordinator level: hostA dies (kill -9
    of its liaison), hostB's detector declares it dead, evicts its group
    member, adopts EXACTLY hostA's partitions, and replays EXACTLY those
    per-partition journals into its dedupe ring — so a redelivered
    answered id is refused on the adopter: zero double answers. A rejoin
    under the old member id restores the exact prior mapping."""
    broker = InMemoryBroker(num_partitions=8)
    ka = KafkaClient(KafkaConfig(num_partitions=8), broker=broker)
    kb = KafkaClient(KafkaConfig(num_partitions=8), broker=broker)
    ka.setup_consumer([USER_MESSAGE_TOPIC])
    kb.setup_consumer([USER_MESSAGE_TOPIC])
    parts_a = {p for _t, p in ka.assignment()}
    parts_b = {p for _t, p in kb.assignment()}
    assert parts_a and parts_b and parts_a.isdisjoint(parts_b)
    assert parts_a | parts_b == set(range(8))

    # hostA answers one message per owned partition (shared journal dir —
    # in a real pod this is the shared disk fabric)
    ja = AnsweredJournal(str(tmp_path), num_partitions=8)
    for p in sorted(parts_a):
        ja.append(f"mid-a{p}", partition=p)
    ja.close()

    coord_a = PodCoordinator(
        _pod_cfg("hostA", listen="inproc:hostA"), kafka=ka)
    await coord_a.start()
    ring_b = DedupeRing(size=64)
    jb = AnsweredJournal(str(tmp_path), num_partitions=8)
    coord_b = PodCoordinator(
        _pod_cfg("hostB", peers="hostA=inproc:hostA"),
        kafka=kb, journal=jb, dedupe=ring_b,
    )
    await coord_b.start()
    peer = coord_b.peers["hostA"]
    try:
        await coord_b._heartbeat(peer)  # learns hostA's member id
        assert peer.member_id == ka.member_id

        adopt0 = METRICS.get("finchat_pod_partition_adoptions_total")
        replayed0 = METRICS.get("finchat_pod_adopted_ids_replayed_total")
        coord_a.kill()  # kill -9: no drain, no goodbye
        await coord_b._heartbeat(peer)
        await coord_b._heartbeat(peer)  # threshold reached
        assert peer.state == PEER_DEAD

        # the rebalance moved ONLY the dead host's share onto hostB
        assert {p for _t, p in kb.assignment()} == parts_a | parts_b
        assert METRICS.get(
            "finchat_pod_partition_adoptions_total") == adopt0 + len(parts_a)
        assert METRICS.get("finchat_pod_adopted_ids_replayed_total") == (
            replayed0 + len(parts_a))
        assert coord_b._pull_partitions >= parts_a
        # every inherited answered id is in the adopter's ring: the
        # redelivery after the uncommitted-offset rewind dedupes — zero
        # double answers across the host kill
        for p in parts_a:
            assert f"mid-a{p}" in ring_b._ids
        # ids hostA never journaled ARE processed (no over-dedupe)
        assert f"mid-never" not in ring_b._ids

        # hostA rejoins under its old member id: exact mapping restored
        ka.setup_consumer([USER_MESSAGE_TOPIC])
        coord_a2 = PodCoordinator(
            _pod_cfg("hostA", listen="inproc:hostA"), kafka=ka)
        await coord_a2.start()
        await coord_b._heartbeat(peer)
        assert peer.state == PEER_LIVE
        assert {p for _t, p in ka.assignment()} == parts_a
        assert {p for _t, p in kb.assignment()} == parts_b
        coord_a2.kill()
    finally:
        coord_a.kill()
        await coord_b.stop()
        jb.close()


def test_survivor_stream_completes_across_host_kill(params, tmp_path):
    """The same kill with traffic on the survivor: a stream decoding on
    hostB while hostA dies, is declared dead and has its partitions
    adopted and journals replayed completes without an error,
    byte-identical to the undisturbed run; afterwards hostB is the one
    live host and answers a conversation of a partition it adopted."""
    prompt = list(range(1, 14))

    async def run(kill: bool):
        broker = InMemoryBroker(num_partitions=8)
        ka = KafkaClient(KafkaConfig(num_partitions=8), broker=broker)
        kb = KafkaClient(KafkaConfig(num_partitions=8), broker=broker)
        ka.setup_consumer([USER_MESSAGE_TOPIC])
        kb.setup_consumer([USER_MESSAGE_TOPIC])
        parts_a = {p for _t, p in ka.assignment()}
        ja = AnsweredJournal(str(tmp_path / f"j{kill}"), num_partitions=8)
        for p in sorted(parts_a):
            ja.append(f"mid-a{p}", partition=p)
        ja.close()
        coord_a = PodCoordinator(
            _pod_cfg("hostA", listen="inproc:hostA"), kafka=ka)
        await coord_a.start()
        sched_b = _make_scheduler(params, "hostB-0")
        await sched_b.start()
        jb = AnsweredJournal(str(tmp_path / f"j{kill}"), num_partitions=8)
        ring_b = DedupeRing(size=64)
        coord_b = PodCoordinator(
            _pod_cfg("hostB", peers="hostA=inproc:hostA"),
            fleet=_single_replica_fleet(sched_b), kafka=kb, journal=jb,
            dedupe=ring_b,
        )
        sched_b.pod = coord_b
        await coord_b.start()
        peer = coord_b.peers["hostA"]
        try:
            await coord_b._heartbeat(peer)  # learns hostA's member id
            task = asyncio.create_task(_collect(
                sched_b, "b-s", prompt, 48, conversation_id="convS"))
            if kill:
                while not sched_b.decoding or any(
                        h.generated < 2 for h in sched_b.decoding.values()):
                    await asyncio.sleep(0.002)
                assert not task.done()
                coord_a.kill()  # mid-stream: no drain, no goodbye
                await coord_b._heartbeat(peer)
                await coord_b._heartbeat(peer)
                assert peer.state == PEER_DEAD
                assert METRICS.get("finchat_pod_hosts_live") == 1.0
                assert {p for _t, p in kb.assignment()} == set(range(8))
                assert all(f"mid-a{p}" in ring_b._ids for p in parts_a)
            _h, tokens = await task
            assert isinstance(tokens, list) and len(tokens) == 48, tokens
            if kill:
                # a conversation of an adopted partition now belongs here
                conv = next(f"adopt-{i}" for i in range(200)
                            if ka.partition_for(f"adopt-{i}") in parts_a)
                _h, after = await _collect(sched_b, "b-a", prompt, 4,
                                           conversation_id=conv)
                assert isinstance(after, list) and len(after) == 4, after
            return tokens
        finally:
            coord_a.kill()
            await coord_b.stop()
            await sched_b.stop()
            jb.close()
            sched_b.allocator.check_invariants()

    clean = asyncio.run(run(False))
    assert asyncio.run(run(True)) == clean


# --- cross-host session transfer: wire-format compat matrix ----------------

@pytest.mark.parametrize("kv_quant", ["", "int8"])
@pytest.mark.parametrize("bounded", [False, True])
def test_wire_format_cross_host_compat_matrix(params, kv_quant, bounded):
    """v2 session records exported under {fp32, int8-KV} × {bounded,
    unbounded} import on a DIFFERENT host (fresh engine, different
    replica id) with byte-identical greedy resume vs the uninterrupted
    original."""
    t1 = list(range(1, 29)) if bounded else list(range(1, 14))
    n1 = 20 if bounded else 8  # bounded: long enough to open a KV gap

    async def run():
        sched_a = _make_scheduler(params, "hostA-0", kv_quant, bounded)
        await sched_a.start()
        _, toks1 = await _collect(sched_a, "a-t1", t1, n1,
                                  conversation_id="convM")
        raw = _pod_record(sched_a, "convM")  # exported BEFORE turn 2
        if bounded:
            # the bound must have evicted pages: the record carries a gap
            assert SessionDiskTier._deserialize(raw)["kv_gap"] > 0
        t2 = t1 + toks1 + [7, 8, 9]
        h_ref, toks2_ref = await _collect(sched_a, "a-t2", t2, 8,
                                          conversation_id="convM")
        await sched_a.stop()

        sched_b = _make_scheduler(params, "hostB-0", kv_quant, bounded)
        await sched_b.start()
        assert _import_record(sched_b, raw)
        h_mig, toks2_mig = await _collect(sched_b, "b-t2", t2, 8,
                                          conversation_id="convM")
        await sched_b.stop()
        assert h_mig.resumed_len == h_ref.resumed_len > 0
        assert toks2_mig == toks2_ref  # byte-identical resume
        sched_b.allocator.check_invariants()

    asyncio.run(run())


def test_cross_mode_record_refused_and_counted(params):
    """An fp32-KV record arriving on an int8-KV host is refused and
    counted (never value-cast into garbage KV) — the conversation cold
    starts with the golden output."""
    t1 = list(range(1, 14))

    async def run():
        sched_a = _make_scheduler(params, "hostA-0", kv_quant="")
        await sched_a.start()
        _, toks1 = await _collect(sched_a, "a-t1", t1, 8,
                                  conversation_id="convX")
        raw = _pod_record(sched_a, "convX")
        await sched_a.stop()

        sched_b = _make_scheduler(params, "hostB-0", kv_quant="int8")
        await sched_b.start()
        refuse0 = METRICS.get("finchat_quant_dequant_fallbacks_total")
        assert not _import_record(sched_b, raw)
        assert METRICS.get(
            "finchat_quant_dequant_fallbacks_total") == refuse0 + 1
        assert sched_b.session_cache.get("convX") is None
        # cold start still answers (golden int8 output, no stale KV)
        t2 = t1 + toks1 + [7, 8, 9]
        h, _ = await _collect(sched_b, "b-t2", t2, 8, conversation_id="convX")
        assert h.resumed_len == 0
        await sched_b.stop()

    asyncio.run(run())


# --- cross-host migration through the liaison ------------------------------

def _single_replica_fleet(sched):
    return EngineFleet([EngineReplica(replica_id=sched.replica_id,
                                      scheduler=sched)],
                       FleetConfig(replicas=1), num_partitions=8)


def test_pod_session_pull_end_to_end(params):
    """The full tentpole path: hostB's scheduler submit pulls the
    conversation's newest record from hostA over the liaison (deepest
    RAM entry, serialized v2 record, CRC checked), imports it through
    ``import_session_entry``, and resumes byte-identically; misses,
    corrupt transfers, and armed ``pod.transfer`` faults all degrade to
    counted cold starts — never a user error."""
    t1 = list(range(1, 14))

    async def run():
        sched_a = _make_scheduler(params, "hostA-0")
        await sched_a.start()
        coord_a = PodCoordinator(_pod_cfg("hostA", listen="inproc:hostA"),
                                 fleet=_single_replica_fleet(sched_a))
        await coord_a.start()

        sched_b = _make_scheduler(params, "hostB-0")
        await sched_b.start()
        coord_b = PodCoordinator(
            _pod_cfg("hostB", peers="hostA=inproc:hostA"))
        sched_b.pod = coord_b
        try:
            _, toks1 = await _collect(sched_a, "a-t1", t1, 8,
                                      conversation_id="convP")
            t2 = t1 + toks1 + [7, 8, 9]
            pulls0 = METRICS.get("finchat_pod_session_pulls_total")
            h_mig, toks2_mig = await _collect(sched_b, "b-t2", t2, 8,
                                              conversation_id="convP")
            assert METRICS.get(
                "finchat_pod_session_pulls_total") == pulls0 + 1
            assert h_mig.resumed_len > 0  # resumed warm, not cold
            # reference: the uninterrupted turn 2 on hostA
            _, toks2_ref = await _collect(sched_a, "a-t2", t2, 8,
                                          conversation_id="convP")
            assert toks2_mig == toks2_ref  # migrated stream byte-identical

            # one liaison round per conversation: a second unknown key is
            # a counted miss, and is never re-pulled on the next turn
            miss0 = METRICS.get("finchat_pod_pull_misses_total")
            await _collect(sched_b, "b-u1", t1, 4, conversation_id="convU")
            assert METRICS.get("finchat_pod_pull_misses_total") == miss0 + 1
            await _collect(sched_b, "b-u2", t1 + [9], 4,
                           conversation_id="convU")
            assert METRICS.get("finchat_pod_pull_misses_total") == miss0 + 1

            # corrupt transfer: counted cold start, stream still answers
            async def corrupt_export(key):
                return b"garbage-not-a-record"
            coord_a.export_record = corrupt_export
            cold0 = METRICS.get("finchat_pod_cold_starts_total",
                                {"reason": "transfer_corrupt"})
            h_c, _ = await _collect(sched_b, "b-c1", t1, 4,
                                    conversation_id="convC")
            assert METRICS.get("finchat_pod_cold_starts_total",
                               {"reason": "transfer_corrupt"}) == cold0 + 1
            assert h_c.resumed_len == 0

            # armed pod.transfer fault: retries exhaust, counted cold
            # start, stream still answers
            faults.arm("pod.transfer", faults.n_shot(8, RuntimeError("net")))
            unreach0 = METRICS.get("finchat_pod_cold_starts_total",
                                   {"reason": "peer_unreachable"})
            h_f, _ = await _collect(sched_b, "b-f1", t1, 4,
                                    conversation_id="convF")
            assert METRICS.get("finchat_pod_cold_starts_total",
                               {"reason": "peer_unreachable"}) == unreach0 + 1
            assert h_f.resumed_len == 0
        finally:
            coord_a.kill()
            await coord_b.stop()
            await sched_a.stop()
            await sched_b.stop()
            sched_a.allocator.check_invariants()
            sched_b.allocator.check_invariants()

    asyncio.run(run())


# --- graceful degradation: pod off == plain fleet --------------------------

def test_single_host_no_liaison_is_bit_identical(params):
    """The regression pin: a scheduler with the pod plane off, and one
    with a peer-less coordinator attached, produce byte-identical greedy
    streams — single-host pods cost nothing."""

    async def run():
        sched = _make_scheduler(params, "solo-0")
        await sched.start()
        t1 = list(range(1, 14))
        silent = ("finchat_pod_session_pulls_total",
                  "finchat_pod_pull_misses_total",
                  "finchat_pod_heartbeats_total",
                  "finchat_pod_peer_deaths_total")
        before = [METRICS.get(m) for m in silent]
        assert sched.pod is None  # default: plane off
        _, toks_off = await _collect(sched, "s-1", t1, 8,
                                     conversation_id="solo1")
        # liaison-less single-host pod: maybe_pull returns before any I/O
        sched.pod = PodCoordinator(_pod_cfg("solo"))
        _, toks_pod = await _collect(sched, "s-2", t1, 8,
                                     conversation_id="solo2")
        await sched.stop()
        assert toks_pod == toks_off
        # and the peer-less pod moved no counter of the pod plane
        assert [METRICS.get(m) for m in silent] == before

    asyncio.run(run())


def test_pod_off_in_app_config_builds_no_coordinator(tmp_path):
    """``pod.host_id`` empty (the default) never constructs the pod
    plane: the App is structurally the PR 17 fleet."""
    from finchat_tpu.engine.generator import StubGenerator
    from finchat_tpu.io.store import InMemoryStore
    from finchat_tpu.serve.app import build_app
    from finchat_tpu.utils.config import load_config

    cfg = load_config(overrides={"model.preset": "stub"})
    assert cfg.pod.host_id == ""
    app = build_app(
        cfg, store=InMemoryStore(),
        kafka=KafkaClient(cfg.kafka, broker=InMemoryBroker()),
        tool_generator=StubGenerator(default="No tool call"),
        response_generator=StubGenerator(default="fine"),
    )
    assert app.pod is None
    for sched in app._all_schedulers():
        assert getattr(sched, "pod", None) is None
