"""Disaggregated prefill/decode serving + cluster-wide warm-state fabric
(ISSUE 17; serve/disagg.py, engine/warm_fabric.py; ROBUSTNESS.md §6).

The contracts under test:

- ROLE-TYPED POOLS: routing hashes over the SERVING pool only (decode +
  mixed) — prefill replicas never own conversations; an empty serving
  pool falls back to all live replicas with the fallback counted.
- CROSS-POOL HANDOFF: a cold turn prefills on the prefill pool and the
  surviving KV arrives on the serving replica through the EXISTING
  drain-handoff wire format before admission — the stream is
  BYTE-IDENTICAL to a mixed-fleet control and admission resumes
  (resumed_len > 0) instead of cold-prefilling. Bounded-KV entries
  travel with ``kv_gap``/``kv_sink`` intact; a cross-quant-mode snapshot
  is refused AND counted; every fallback leaves the plain local-prefill
  path (clean fallback by contract).
- WARM-STATE FABRIC: one shared disk tier + global index — ANY replica
  resumes ANY conversation warm (fabric hit counted on the restoring
  replica), the shared prompt head prefills ONCE per fleet, and
  route-time deeper-entry-wins is an O(1) index lookup whose migration
  drops only the source's RAM copy (the shared record must survive).
- INGRESS PARITY: HTTP /chat, /chat/stream and the Kafka worker all
  route through the ONE fleet entry (``agent_for``) that performs lazy
  route-time migration — no path can silently serve cold.

fp32 tiny config for the identity contracts (same rationale as
tests/test_mixed_step.py: no bf16 near-tie excuse).
"""

import asyncio
import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finchat_tpu.analysis.sanitizers import scheduler_leak_report
from finchat_tpu.engine.engine import InferenceEngine
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
from finchat_tpu.engine.warm_fabric import WarmFabric
from finchat_tpu.models.llama import PRESETS, init_params
from finchat_tpu.serve.disagg import (
    FALLBACK_REASONS,
    ROLE_DECODE,
    ROLE_MIXED,
    ROLE_PREFILL,
    parse_roles,
)
from finchat_tpu.serve.fleet import LIVE, OUT, EngineFleet, EngineReplica
from finchat_tpu.utils import faults
from finchat_tpu.utils.config import EngineConfig, FleetConfig
from finchat_tpu.utils.metrics import METRICS

CONFIG = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
CHUNK = 16
PAGE = 8


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faults.disarm_all()


@pytest.fixture(scope="module")
def params():
    return init_params(CONFIG, jax.random.key(0))


def _greedy(n: int) -> SamplingParams:
    return SamplingParams(temperature=0.0, max_new_tokens=n)


async def _drain(handle):
    tokens = []
    while True:
        ev = await asyncio.wait_for(handle.events.get(), timeout=120)
        if ev["type"] == "token":
            tokens.append(ev["token_id"])
        elif ev["type"] == "done":
            return tokens, None
        else:
            return tokens, ev


def _make_replica(rid, params, *, role=ROLE_MIXED, fabric=None,
                  **cfg_overrides) -> EngineReplica:
    defaults = dict(
        max_seqs=3, page_size=PAGE, num_pages=64, max_seq_len=256,
        prefill_chunk=CHUNK, session_cache=True,
        session_cache_bytes=16 << 20, breaker_max_rebuilds=1,
    )
    defaults.update(cfg_overrides)
    engine = InferenceEngine(CONFIG, params, EngineConfig(**defaults))
    sched = ContinuousBatchingScheduler(
        engine, eos_id=-1, metrics=METRICS.labeled(replica=rid),
        replica_id=rid, fabric=fabric,
    )
    return EngineReplica(replica_id=rid, scheduler=sched, role=role)


def _make_fleet(roles, params, *, fabric=None, **cfg_overrides) -> EngineFleet:
    reps = [_make_replica(str(i), params, role=role, fabric=fabric,
                          **cfg_overrides)
            for i, role in enumerate(roles)]
    return EngineFleet(
        reps,
        FleetConfig(replicas=len(reps), respawn_backoff_seconds=0.05,
                    supervisor_interval_seconds=0.05),
        num_partitions=16,
    )


def _serving(fleet: EngineFleet) -> EngineReplica:
    return next(r for r in fleet.replicas if r.role != ROLE_PREFILL)


def _get(name: str, rid: str, **labels) -> float:
    return METRICS.get(name, {"replica": rid, **labels})


# --- role parsing + routing (pure; no engines) -----------------------------

def test_parse_roles_contract():
    assert parse_roles("", 3) == [ROLE_MIXED] * 3
    assert parse_roles("prefill,decode", 4) == [
        ROLE_PREFILL, ROLE_DECODE, ROLE_MIXED, ROLE_MIXED]
    assert parse_roles(" Prefill , decode , decode , mixed , mixed ", 3) == [
        ROLE_PREFILL, ROLE_DECODE, ROLE_DECODE]
    with pytest.raises(ValueError):
        parse_roles("prefill,bogus", 2)
    # all-prefill would leave nothing to serve: loud demotion to mixed
    assert parse_roles("prefill,prefill", 2) == [ROLE_MIXED] * 2


def _stub_replica(rid: str, role: str) -> EngineReplica:
    sched = types.SimpleNamespace(on_give_up=[], session_cache=None,
                                  metrics=METRICS.labeled(replica=rid))
    return EngineReplica(replica_id=rid, scheduler=sched, role=role)


def _stub_fleet(roles) -> EngineFleet:
    return EngineFleet(
        [_stub_replica(str(i), r) for i, r in enumerate(roles)],
        FleetConfig(replicas=len(roles), respawn=False),
        num_partitions=32,
    )


def test_routing_excludes_prefill_pool_and_seeds_metrics():
    """Conversations route over the serving pool only; the role gauge and
    every fallback-reason series are pre-seeded per replica (R5: the
    quiet state is scrapeable before the first handoff)."""
    fleet = _stub_fleet([ROLE_PREFILL, ROLE_DECODE, ROLE_DECODE, ROLE_MIXED])
    assert fleet.disagg is not None
    # coordinator attached only to SERVING schedulers (no recursion)
    assert getattr(fleet.replicas[0].scheduler, "disagg", None) is None
    for rep in fleet.replicas[1:]:
        assert rep.scheduler.disagg is fleet.disagg
    for conv in (f"conv-{i}" for i in range(200)):
        assert fleet.replica_for(conv).role != ROLE_PREFILL
    assert _get("finchat_disagg_role", "0") == 1
    assert _get("finchat_disagg_role", "1") == 2
    assert _get("finchat_disagg_role", "3") == 0
    text = METRICS.render_prometheus()
    for rid in ("0", "1", "2", "3"):
        for reason in FALLBACK_REASONS:
            assert (f'finchat_disagg_fallbacks_total{{reason="{reason}",'
                    f'replica="{rid}"}}') in text  # seeded, scrapeable


def test_empty_serving_pool_falls_back_to_prefill_and_counts():
    """Every decode replica down: the prefill replica absorbs routed
    traffic (serving beats shedding) and each absorbed message counts a
    ``serving_pool_empty`` fallback on it."""
    fleet = _stub_fleet([ROLE_PREFILL, ROLE_DECODE])
    before = _get("finchat_disagg_fallbacks_total", "0",
                  reason="serving_pool_empty")
    fleet.replicas[1].state = OUT
    rep = fleet.replica_for("conv-x")
    assert rep is fleet.replicas[0] and rep.role == ROLE_PREFILL
    assert _get("finchat_disagg_fallbacks_total", "0",
                reason="serving_pool_empty") == before + 1


def test_empty_prefill_pool_counts_fallback_and_serves(params):
    """The prefill pool going OUT degrades to exactly mixed serving: the
    cold turn prefills locally (counted no_prefill_replica), completes,
    and is byte-identical to never having had a pool."""
    prompt = list(range(1, 41))

    async def run():
        fleet = _make_fleet([ROLE_PREFILL, ROLE_MIXED], params)
        await fleet.start()
        try:
            serving = _serving(fleet)
            fleet.replicas[0].state = OUT
            before = _get("finchat_disagg_fallbacks_total",
                          serving.replica_id, reason="no_prefill_replica")
            h = await serving.scheduler.submit(
                "t1", prompt, _greedy(6), conversation_id="conv-np")
            toks, err = await _drain(h)
            assert err is None
            assert _get("finchat_disagg_fallbacks_total", serving.replica_id,
                        reason="no_prefill_replica") == before + 1
            return toks
        finally:
            await fleet.stop()

    async def control():
        fleet = _make_fleet([ROLE_MIXED, ROLE_MIXED], params)
        await fleet.start()
        try:
            h = await fleet.replicas[0].scheduler.submit(
                "t1", prompt, _greedy(6), conversation_id="conv-np2")
            toks, err = await _drain(h)
            assert err is None
            return toks
        finally:
            await fleet.stop()

    assert asyncio.run(run()) == asyncio.run(control())


# --- cross-pool handoff ----------------------------------------------------

@pytest.mark.parametrize("storm", [1, 3], ids=["one-turn", "storm"])
def test_cold_turn_handoff_byte_identity_and_warm_resume(params, storm):
    """THE tentpole identity: a cold turn submitted to the serving
    replica prefills on the PREFILL replica, the KV crosses pools over
    the drain-handoff wire format, admission resumes from it
    (resumed_len > 0), and the stream is byte-identical to a mixed-fleet
    control. The source's copy is discarded after the handoff. As a
    storm: several cold conversations offered at once beside a stream
    that is already decoding — every one handed off, every stream as in
    the control, no page or slot left owned."""
    # residue 39 >= one chunk: handoff engages
    prompts = {f"conv-h{i}": list(range(1 + i, 41 + i)) for i in range(storm)}

    async def run(roles) -> dict:
        fleet = _make_fleet(roles, params)
        await fleet.start()
        try:
            serving = _serving(fleet)
            rid = serving.replica_id
            steady = None
            if storm > 1:
                # short of one chunk: never handed off, decodes through the storm
                steady = await serving.scheduler.submit(
                    "steady", list(range(50, 60)), _greedy(24),
                    conversation_id="conv-steady")
                while steady.generated < 2:
                    await asyncio.sleep(0.002)
            h0 = _get("finchat_disagg_handoffs_total", rid)
            handles = {
                conv: await serving.scheduler.submit(
                    "t1-" + conv, prompt, _greedy(8), conversation_id=conv)
                for conv, prompt in prompts.items()
            }
            if steady is not None:
                handles["conv-steady"] = steady
            results = await asyncio.gather(*map(_drain, handles.values()))
            assert all(err is None for _toks, err in results)
            out = {
                "tokens": {c: toks for c, (toks, _e) in zip(handles, results)},
                "resumed": [handles[c].resumed_len for c in prompts],
                "handoffs": _get("finchat_disagg_handoffs_total", rid) - h0,
            }
            if roles[0] == ROLE_PREFILL:
                # source copy discarded — a stale twin could serve
                # diverged KV if the conversation ever re-handed-off
                src = fleet.replicas[0].scheduler.session_cache
                out["source_clean"] = all(src.get(c) is None for c in prompts)
            for rep in fleet.replicas:
                rep.scheduler.allocator.check_invariants()
            return out
        finally:
            await fleet.stop()
            for rep in fleet.replicas:
                assert scheduler_leak_report(rep.scheduler) == []

    disagg = asyncio.run(run([ROLE_PREFILL, ROLE_DECODE]))
    mixed = asyncio.run(run([ROLE_MIXED, ROLE_MIXED]))
    assert disagg["tokens"] == mixed["tokens"]  # byte-identical across pools
    assert disagg["handoffs"] == storm and mixed["handoffs"] == 0
    # admission resumed from the handed KV, not a local cold prefill
    assert all(n > 0 for n in disagg["resumed"])
    assert disagg["source_clean"]
    # the handoff detour was timed
    assert METRICS.snapshot().get(
        'finchat_disagg_handoff_seconds{replica="1"}_count', 0) >= 1


def test_warm_turn_skips_the_handoff(params):
    """A second turn whose residue is under one prefill chunk must NOT
    detour through the prefill pool — the handoff is for cold work
    only (its KV is already home)."""

    async def run():
        fleet = _make_fleet([ROLE_PREFILL, ROLE_DECODE], params)
        await fleet.start()
        try:
            serving = _serving(fleet)
            rid = serving.replica_id
            prompt = list(range(1, 41))
            h = await serving.scheduler.submit(
                "t1", prompt, _greedy(8), conversation_id="conv-w")
            t1, err = await _drain(h)
            assert err is None
            h1 = _get("finchat_disagg_handoffs_total", rid)
            # turn 2: history + a short tail — residue < CHUNK
            h2 = await serving.scheduler.submit(
                "t2", prompt + t1 + [5, 6, 7], _greedy(4),
                conversation_id="conv-w")
            _t2, err = await _drain(h2)
            assert err is None
            assert h2.resumed_len > 0
            assert _get("finchat_disagg_handoffs_total", rid) == h1
        finally:
            await fleet.stop()

    asyncio.run(run())


def test_bounded_kv_gapped_handoff(params):
    """A prompt past the bounded budget evicts DURING the prefill pass:
    the handed-off entry travels with its ``kv_gap``/``kv_sink`` and the
    serving replica's stream equals the mixed bounded control."""
    bounded = dict(kv_sink_pages=1, kv_window_pages=4, num_pages=128)
    prompt = list(range(1, 57))  # 56 tokens > 40-token bounded budget

    async def run(roles) -> dict:
        fleet = _make_fleet(roles, params, **bounded)
        await fleet.start()
        try:
            serving = _serving(fleet)
            h0 = _get("finchat_disagg_handoffs_total", serving.replica_id)
            h = await serving.scheduler.submit(
                "t1", prompt, _greedy(8), conversation_id="conv-b")
            toks, err = await _drain(h)
            assert err is None
            entry = serving.scheduler.session_cache.get("conv-b")
            assert entry is not None and entry.kv_gap > 0
            assert entry.kv_sink is not None
            for rep in fleet.replicas:
                rep.scheduler.allocator.check_invariants()
            return {
                "tokens": toks,
                "handoffs": _get("finchat_disagg_handoffs_total",
                                 serving.replica_id) - h0,
            }
        finally:
            await fleet.stop()

    disagg = asyncio.run(run([ROLE_PREFILL, ROLE_DECODE]))
    mixed = asyncio.run(run([ROLE_MIXED, ROLE_MIXED]))
    assert disagg["handoffs"] == 1
    assert disagg["tokens"] == mixed["tokens"]


def test_crossmode_handoff_refused_and_counted(params):
    """Prefill pool serving int8 KV, decode pool fp32: the exported
    snapshot is refused at import (value-casting it would be garbage
    KV), BOTH counters fire (the quant dequant-fallback gate and the
    disagg import_refused fallback), and the turn completes on the
    local-prefill path byte-identical to a mixed fp32 control."""
    prompt = list(range(1, 41))

    async def run() -> dict:
        reps = [
            _make_replica("0", params, role=ROLE_PREFILL, kv_quant="int8"),
            _make_replica("1", params, role=ROLE_DECODE),
        ]
        fleet = EngineFleet(
            reps, FleetConfig(replicas=2, respawn=False), num_partitions=16)
        await fleet.start()
        try:
            serving = reps[1]
            q0 = _get("finchat_quant_dequant_fallbacks_total", "1")
            f0 = _get("finchat_disagg_fallbacks_total", "1",
                      reason="import_refused")
            h = await serving.scheduler.submit(
                "t1", prompt, _greedy(6), conversation_id="conv-q")
            toks, err = await _drain(h)
            assert err is None
            assert _get("finchat_quant_dequant_fallbacks_total", "1") == q0 + 1
            assert _get("finchat_disagg_fallbacks_total", "1",
                        reason="import_refused") == f0 + 1
            assert serving.scheduler.session_cache.get("conv-q") is not None
            return {"tokens": toks}
        finally:
            await fleet.stop()

    async def control() -> dict:
        fleet = _make_fleet([ROLE_MIXED, ROLE_MIXED], params)
        await fleet.start()
        try:
            h = await fleet.replicas[1].scheduler.submit(
                "t1", prompt, _greedy(6), conversation_id="conv-q2")
            toks, err = await _drain(h)
            assert err is None
            return {"tokens": toks}
        finally:
            await fleet.stop()

    assert asyncio.run(run())["tokens"] == asyncio.run(control())["tokens"]


def test_prefill_pass_error_falls_back_to_local_prefill(params):
    """A fault inside the prefill pass (the pass's own sequence evicted
    with an error) leaves the serving replica on the plain local-prefill
    path: fallback counted, stream completes byte-identical."""
    prompt = list(range(1, 41))

    def wedge(seq_id="", **_ctx):
        if seq_id.startswith("__disagg_"):
            raise RuntimeError("drill: prefill pool fault")

    async def run(fault: bool) -> dict:
        fleet = _make_fleet([ROLE_PREFILL, ROLE_DECODE], params)
        await fleet.start()
        try:
            if fault:
                faults.arm("scheduler.prefill", wedge)
            serving = _serving(fleet)
            e0 = _get("finchat_disagg_fallbacks_total", serving.replica_id,
                      reason="prefill_error")
            h = await serving.scheduler.submit(
                "t1", prompt, _greedy(6), conversation_id="conv-e")
            toks, err = await _drain(h)
            assert err is None
            de = _get("finchat_disagg_fallbacks_total", serving.replica_id,
                      reason="prefill_error") - e0
            for rep in fleet.replicas:
                rep.scheduler.allocator.check_invariants()
            return {"tokens": toks, "errors": de}
        finally:
            await fleet.stop()
            faults.disarm_all()

    clean = asyncio.run(run(False))
    chaos = asyncio.run(run(True))
    assert chaos["errors"] == 1 and clean["errors"] == 0
    assert chaos["tokens"] == clean["tokens"]


def test_handoff_then_decode_breaker_trip_drains_clean(params):
    """The handed-off KV must survive a decode-pool breaker trip racing
    the turn: the decode replica imports the handoff, wedges on its
    first decode round, trips, and the drain hands the stream (with its
    session bytes) to the OTHER decode replica — the client sees the
    byte-identical stream, zero errors, zero leaks."""
    prompt = list(range(1, 41))

    async def run(fault: bool) -> dict:
        fleet = _make_fleet([ROLE_PREFILL, ROLE_DECODE, ROLE_DECODE], params)
        await fleet.start()
        try:
            victim = _serving(fleet)
            if fault:
                dead = [True]

                def wedge(**ctx):
                    if dead[0] and ctx.get("replica") == victim.replica_id:
                        raise RuntimeError("drill: decode pool trip")

                faults.arm("scheduler.decode", wedge)
                faults.arm("engine.rebuild", wedge)
            h0 = _get("finchat_disagg_handoffs_total", victim.replica_id)
            d0 = METRICS.get("finchat_fleet_drained_streams_total")
            h = await victim.scheduler.submit(
                "t1", prompt, _greedy(8), conversation_id="conv-t")
            toks, err = await _drain(h)
            assert err is None
            out = {
                "tokens": toks,
                "handoffs": _get("finchat_disagg_handoffs_total",
                                 victim.replica_id) - h0,
                "drained": METRICS.get(
                    "finchat_fleet_drained_streams_total") - d0,
            }
            if fault:
                for rep in fleet.replicas:
                    if rep is not victim:
                        rep.scheduler.allocator.check_invariants()
            return out
        finally:
            await fleet.stop()
            faults.disarm_all()

    clean = asyncio.run(run(False))
    chaos = asyncio.run(run(True))
    assert clean["handoffs"] == 1 and chaos["handoffs"] == 1
    assert chaos["tokens"] == clean["tokens"]
    assert chaos["drained"] >= 1  # the trip really raced the turn


# --- warm-state fabric -----------------------------------------------------

def _fabric_sched(rid, params, fabric, **cfg_overrides):
    return _make_replica(rid, params, fabric=fabric,
                         **cfg_overrides).scheduler


def test_fabric_session_restore_on_never_seen_replica(params, tmp_path):
    """A conversation retired on replica A resumes WARM on replica B —
    which never saw it — through the fabric's shared tier: fabric hit
    counted on B, resumed_len > 0, and the stream byte-identical to the
    same turn run where the conversation lived."""
    prompt1 = list(range(1, 41))

    async def turn(sched, seq, prompt, conv):
        await sched.start()
        try:
            h = await sched.submit(seq, prompt, _greedy(8),
                                   conversation_id=conv)
            toks, err = await _drain(h)
            assert err is None
            return toks, h.resumed_len
        finally:
            await sched.stop()

    def scenario(dirname, rids):
        fabric = WarmFabric(str(tmp_path / dirname), 32 << 20)
        try:
            a = _fabric_sched(rids[0], params, fabric)
            t1, _ = asyncio.run(turn(a, "t1", prompt1, "conv-f"))
            fabric.flush()
            b = a if rids[1] == rids[0] else _fabric_sched(rids[1], params,
                                                           fabric)
            if b is not a:
                # B starts genuinely cold in RAM — the record must come
                # off the shared tier
                assert b.session_cache.get("conv-f") is None
            prompt2 = prompt1 + t1 + [9, 10, 11]
            hits0 = _get("finchat_fabric_hits_total", rids[1])
            t2, resumed = asyncio.run(turn(b, "t2", prompt2, "conv-f"))
            return {
                "t2": t2, "resumed": resumed,
                "hits": _get("finchat_fabric_hits_total", rids[1]) - hits0,
            }
        finally:
            fabric.close()

    stay = scenario("fab-stay", ("fa", "fa"))
    moved = scenario("fab-move", ("fb", "fc"))
    assert moved["t2"] == stay["t2"]
    assert moved["resumed"] > 0 and moved["resumed"] == stay["resumed"]
    assert moved["hits"] == 1


def test_fabric_head_prefills_once_per_fleet(params, tmp_path):
    """The shared prompt head is prefilled by the FIRST replica to
    register it; every later replica restores the published snapshot
    with one H2D scatter — its engine.prefill is never called — and
    serves streams byte-identical to the prefilling replica's."""
    fabric = WarmFabric(str(tmp_path / "fab-head"), 32 << 20)
    head = list(range(1, 49))  # 48 tokens: 6 whole pages
    prompt = head + list(range(60, 72))

    async def gen(sched, seq):
        await sched.start()
        try:
            h = await sched.submit(seq, prompt, _greedy(8))
            toks, err = await _drain(h)
            assert err is None
            return toks
        finally:
            await sched.stop()

    try:
        a = _fabric_sched("ha", params, fabric)
        misses0 = _get("finchat_fabric_misses_total", "ha")
        assert a.register_prefix(head) == 48  # cold: local prefill + publish
        assert _get("finchat_fabric_misses_total", "ha") == misses0 + 1
        fabric.flush()

        b = _fabric_sched("hb", params, fabric)
        real_prefill = b.engine.prefill
        calls = []
        b.engine.prefill = lambda *a_, **k: (calls.append(1),
                                             real_prefill(*a_, **k))[1]
        hits0 = _get("finchat_fabric_hits_total", "hb")
        assert b.register_prefix(head) == 48  # fabric hit: no prefill
        assert calls == []
        assert _get("finchat_fabric_hits_total", "hb") == hits0 + 1
        assert METRICS.snapshot().get(
            'finchat_fabric_restore_seconds{replica="hb"}_count', 0) >= 1

        ta = asyncio.run(gen(a, "ga"))
        tb = asyncio.run(gen(b, "gb"))
        assert ta == tb  # the restored head KV is the prefilled head KV
    finally:
        fabric.close()


def test_fabric_crossmode_head_refused(params, tmp_path):
    """A head snapshot published by an int8-KV engine is refused by an
    fp32 replica (counted) — it prefills locally instead of scattering a
    value-cast snapshot."""
    fabric = WarmFabric(str(tmp_path / "fab-x"), 32 << 20, kv_quant="int8")
    head = list(range(1, 25))
    try:
        a = _fabric_sched("xa", params, fabric, kv_quant="int8")
        assert a.register_prefix(head) == 24
        fabric.flush()
        b = _fabric_sched("xb", params, fabric)
        r0 = _get("finchat_fabric_import_refused_total", "xb")
        assert b.register_prefix(head) == 24  # still registers, locally
        assert _get("finchat_fabric_import_refused_total", "xb") == r0 + 1
    finally:
        fabric.close()


def test_fabric_migration_is_index_lookup_and_keeps_shared_record(params,
                                                                  tmp_path):
    """Route-time deeper-entry-wins over the fabric: the router asks the
    GLOBAL index who holds the conversation (O(1), no pairwise scan),
    moves the RAM entry, and — the shared-tier discipline — drops only
    the source's RAM copy, so the record both replicas share survives
    the migration."""
    fabric = WarmFabric(str(tmp_path / "fab-mig"), 32 << 20)

    async def run():
        reps = [EngineReplica(replica_id=rid,
                              scheduler=_fabric_sched(rid, params, fabric),
                              role=ROLE_MIXED)
                for rid in ("0", "1")]
        fleet = EngineFleet(
            reps, FleetConfig(replicas=2, respawn=False), num_partitions=16)
        await fleet.start()
        try:
            conv = "conv-m"
            home = fleet.replica_for(conv)
            other = next(r for r in reps if r is not home)
            prompt = list(range(1, 41))
            h = await home.scheduler.submit(
                "t1", prompt, _greedy(8), conversation_id=conv)
            t1, err = await _drain(h)
            assert err is None
            assert fabric.holder(conv)[0] == home.replica_id
            m0 = METRICS.get("finchat_fleet_session_migrations_total")
            home.state = OUT
            rep2 = fleet.replica_for(conv)
            assert rep2 is other
            assert METRICS.get(
                "finchat_fleet_session_migrations_total") == m0 + 1
            # RAM moved; index follows the bytes
            assert home.scheduler.session_cache.get(conv) is None
            assert rep2.scheduler.session_cache.get(conv) is not None
            assert fabric.holder(conv)[0] == rep2.replica_id
            # THE shared-tier contract: the migration did not delete the
            # record both replicas back onto
            fabric.flush()
            assert conv in fabric.tier
            h2 = await rep2.scheduler.submit(
                "t2", prompt + t1 + [3, 4], _greedy(4), conversation_id=conv)
            _t2, err = await _drain(h2)
            assert err is None
            assert h2.resumed_len > 0
        finally:
            await fleet.stop()

    asyncio.run(run())


# --- ingress parity (HTTP /chat, /chat/stream, Kafka) ----------------------

def test_all_ingress_paths_route_through_fleet_agent_for():
    """HTTP /chat, /chat/stream and the Kafka worker all fetch their
    agent through fleet.agent_for — the ONE entry that performs lazy
    route-time session migration — with the BARE conversation id. A
    path reaching the agent any other way would serve migrated
    conversations cold (the regression this pins)."""
    from finchat_tpu.engine.generator import StubGenerator
    from finchat_tpu.io.kafka import (
        InMemoryBroker, KafkaClient, Message,
    )
    from finchat_tpu.io.store import InMemoryStore
    from finchat_tpu.serve.app import build_app
    from finchat_tpu.serve.http import Request
    from finchat_tpu.utils.config import USER_MESSAGE_TOPIC, load_config

    cfg = load_config(overrides={"model.preset": "stub"})
    store = InMemoryStore()
    store.upsert_context("c1", {"user_id": "u9", "name": "Alex",
                                "income": 5000, "savings_goal": 800})
    store.add_user_message("c1", "How am I doing?", "u9")
    broker = InMemoryBroker()
    app = build_app(
        cfg, store=store, kafka=KafkaClient(cfg.kafka, broker=broker),
        tool_generator=StubGenerator(default="No tool call"),
        response_generator=StubGenerator(default="Hi.", chunk_delay=0.001),
    )

    calls: list[str] = []
    real_agent = app.agent

    class RecordingFleet:
        replicas: list = []

        def agent_for(self, conversation_id):
            calls.append(conversation_id)
            return real_agent

    app.fleet = RecordingFleet()
    payload = {"message": "How am I doing?", "conversation_id": "c1",
               "user_id": "u9"}
    body = json.dumps(payload).encode()

    async def drive():
        resp = await app.chat(Request("POST", "/chat", {}, body))
        assert resp.status == 200
        stream = await app.chat_stream(
            Request("POST", "/chat/stream", {}, body))
        async for _chunk in stream.chunks:
            pass
        await app.process_message(
            Message(USER_MESSAGE_TOPIC, "c1", body))

    asyncio.run(drive())
    # one routed lookup per ingress path, always the bare conversation id
    assert calls == ["c1", "c1", "c1"]
