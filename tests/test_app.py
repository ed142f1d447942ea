"""Full message-in → chunks-out pipeline (SURVEY §4.4): in-memory broker +
store + stub generators, asserting the §2.4 outbound chunk schema
byte-for-byte, plus the HTTP surface over a real TCP socket."""

import asyncio
import json

import httpx
import pytest

from finchat_tpu.engine.generator import StubGenerator
from finchat_tpu.io.kafka import InMemoryBroker, KafkaClient, Message
from finchat_tpu.io.store import InMemoryStore
from finchat_tpu.serve.app import build_app
from finchat_tpu.utils.config import (
    AI_RESPONSE_TOPIC,
    USER_MESSAGE_TOPIC,
    load_config,
)

CONTEXT_DOC = {"user_id": "u9", "name": "Alex", "income": 5000, "savings_goal": 800}


def make_app(response_text="Hello there friend", tool_response="No tool call",
             fail_response=False, watchdog=None):
    cfg = load_config(overrides={"model.preset": "stub"})
    if watchdog is not None:
        cfg.engine.watchdog_seconds = watchdog
    broker = InMemoryBroker()
    store = InMemoryStore()
    store.upsert_context("c1", CONTEXT_DOC)
    store.add_user_message("c1", "How am I doing?", "u9")

    response_gen = StubGenerator(default=response_text, fail_with="boom" if fail_response else None,
                                 chunk_delay=0.001)
    app = build_app(
        cfg,
        store=store,
        kafka=KafkaClient(cfg.kafka, broker=broker),
        tool_generator=StubGenerator(default=tool_response),
        response_generator=response_gen,
    )
    return app, broker, store


def inbound(message="How am I doing?", conversation_id="c1", **extra):
    return {"message": message, "conversation_id": conversation_id, "user_id": "u9", **extra}


def kafka_msg(payload):
    return Message(USER_MESSAGE_TOPIC, payload["conversation_id"], json.dumps(payload).encode())


def drain_json(broker):
    return [json.loads(m.value().decode()) for m in broker.drain(AI_RESPONSE_TOPIC)]


async def test_pipeline_chunk_schema_byte_for_byte():
    app, broker, store = make_app(response_text="You are fine.")
    payload = inbound(trace="t-1")
    await app.process_message(kafka_msg(payload))

    out = drain_json(broker)
    assert len(out) >= 2
    # every streamed chunk: reference main.py:86-93
    for chunk in out[:-1]:
        assert chunk["last_message"] is False
        assert chunk["error"] is False
        assert chunk["sender"] == "AIMessage"
        assert chunk["type"] == "response_chunk"
        assert chunk["conversation_id"] == "c1"
        assert chunk["trace"] == "t-1"  # passthrough fields preserved
    # completion marker: main.py:101-108 — message is the ORIGINAL user text
    final = out[-1]
    assert final["last_message"] is True
    assert final["type"] == "complete"
    assert final["message"] == "How am I doing?"
    # reassembled text
    assert "".join(c["message"] for c in out[:-1]) == "You are fine."
    # persisted to store (main.py:126)
    history = await store.get_history("c1")
    assert history[-1].sender == "AIMessage"
    assert history[-1].message == "You are fine."


async def test_pipeline_error_chunk():
    app, broker, _ = make_app(fail_response=True)
    await app.process_message(kafka_msg(inbound()))
    out = drain_json(broker)
    assert len(out) == 1
    err = out[0]
    # error marker: main.py:114-121 — empty message, error=True, NO type key
    assert err["message"] == ""
    assert err["error"] is True
    assert err["last_message"] is True
    assert "type" not in err


async def test_missing_context_drops_message():
    app, broker, _ = make_app()
    await app.process_message(kafka_msg(inbound(conversation_id="unknown")))
    assert drain_json(broker) == []  # dropped silently (main.py:68-70)


async def test_watchdog_timeout_chunk():
    app, broker, _ = make_app(watchdog=0.05)
    app.agent.response_generator.chunk_delay = 10.0  # hang the stream

    async def run_once():
        app._running = True
        task = asyncio.create_task(app.consume_messages())
        await asyncio.sleep(0.3)
        app._running = False
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass

    app.kafka.setup_consumer()
    producer = KafkaClient(app.cfg.kafka, broker=broker)
    producer.produce_message(USER_MESSAGE_TOPIC, "c1", inbound())
    await run_once()
    out = drain_json(broker)
    assert out, "expected a timeout chunk"
    timeout = out[-1]
    assert timeout["message"] == "Request timed out. Please try again."
    assert timeout["error"] is True and timeout["last_message"] is True


async def test_full_loop_end_to_end():
    """Produce on user_message → live consume loop → chunks on ai_response."""
    app, broker, _ = make_app(response_text="All good.")
    await app.start(serve_http=False)
    try:
        producer = KafkaClient(app.cfg.kafka, broker=broker)
        producer.produce_message(USER_MESSAGE_TOPIC, "c1", inbound())
        for _ in range(200):
            out = drain_json(broker)
            if out and out[-1].get("type") == "complete":
                break
            await asyncio.sleep(0.01)
        else:
            raise AssertionError(f"no completion marker; got {drain_json(broker)}")
    finally:
        await app.stop()


async def test_http_surface():
    app, broker, _ = make_app(response_text="Advice here.")
    app.cfg.serve.port = 0  # ephemeral
    app.server.port = 0
    await app.start(serve_http=True)
    try:
        async with httpx.AsyncClient() as client:
            base = f"http://127.0.0.1:{app.server.port}"
            health = await client.get(f"{base}/health")
            assert health.status_code == 200
            assert health.json() == {"status": "healthy"}

            chat = await client.post(f"{base}/chat", json={
                "conversation_id": "c1", "message": "hi", "user_id": "u9",
            })
            assert chat.status_code == 200
            body = chat.json()
            assert body["response"] == "Advice here."
            assert body["retrieved_transactions_count"] == 0

            bad = await client.post(f"{base}/chat", json={"message": "hi"})
            assert bad.status_code == 400

            missing = await client.get(f"{base}/nope")
            assert missing.status_code == 404

            metrics = await client.get(f"{base}/metrics")
            assert metrics.status_code == 200
            assert "finchat" in metrics.text

            # SSE stream carries the FULL event protocol
            async with client.stream("POST", f"{base}/chat/stream", json={
                "conversation_id": "c1", "message": "hi", "user_id": "u9",
            }) as stream:
                events = []
                async for line in stream.aiter_lines():
                    if line.startswith("data: "):
                        events.append(json.loads(line[6:]))
            types = [e["type"] for e in events]
            assert types[0] == "status"
            assert "response_chunk" in types
            assert types[-1] == "complete"
    finally:
        await app.stop()


async def _watch_until(broker, n_complete: int, ticks: int = 500):
    """Poll the ai_response log, recording each record's first-seen tick
    (drain returns the FULL log in per-partition order, which is not a
    global timeline — the (partition, offset) key + tick gives one)."""
    first_seen: dict[tuple[int, int], tuple[int, dict]] = {}
    for tick in range(ticks):
        for m in broker.drain(AI_RESPONSE_TOPIC):
            key = (m.partition(), m.offset())
            if key not in first_seen:
                first_seen[key] = (tick, json.loads(m.value().decode()))
        events = [e for _, e in first_seen.values()]
        if sum(1 for e in events if e.get("type") == "complete") >= n_complete:
            return first_seen
        await asyncio.sleep(0.01)
    raise AssertionError(
        f"only {sum(1 for _, e in first_seen.values() if e.get('type') == 'complete')}"
        f"/{n_complete} completions: {[e for _, e in first_seen.values()]}"
    )


async def test_kafka_conversations_process_concurrently():
    """BASELINE config 4 (Kafka-driven concurrency): two conversations'
    messages in the queue together must INTERLEAVE — the second
    conversation's chunks appear before the first one's complete marker.
    The reference (and the pre-round-4 consume loop) processed one message
    to completion at a time."""
    app, broker, store = make_app(response_text="word " * 30)
    store.upsert_context("c2", {**CONTEXT_DOC, "user_id": "u9"})
    store.add_user_message("c2", "And me?", "u9")
    await app.start(serve_http=False)
    try:
        producer = KafkaClient(app.cfg.kafka, broker=broker)
        producer.produce_message(USER_MESSAGE_TOPIC, "c1", inbound(conversation_id="c1"))
        producer.produce_message(USER_MESSAGE_TOPIC, "c2", inbound(conversation_id="c2"))
        first_seen = await _watch_until(broker, n_complete=2)

        def first_tick(pred):
            ticks = [t for t, e in first_seen.values() if pred(e)]
            return min(ticks) if ticks else None

        c1_done = first_tick(lambda e: e["conversation_id"] == "c1" and e.get("type") == "complete")
        c2_start = first_tick(lambda e: e["conversation_id"] == "c2")
        c2_done = first_tick(lambda e: e["conversation_id"] == "c2" and e.get("type") == "complete")
        c1_start = first_tick(lambda e: e["conversation_id"] == "c1")
        # overlap in either direction proves concurrency
        assert (c2_start is not None and c2_start < c1_done) or (
            c1_start is not None and c1_start < c2_done
        ), f"conversations were processed serially: {c1_start=} {c1_done=} {c2_start=} {c2_done=}"
    finally:
        await app.stop()


async def test_commit_after_process_and_dedupe_ring():
    """kafka.commit_after_process (at-least-once): offsets commit only
    after the watchdog-wrapped handler completes, and a redelivered
    message_id is answered exactly once (dedupe ring)."""
    from finchat_tpu.utils.config import GROUP_ID
    from finchat_tpu.utils.metrics import METRICS

    app, broker, _ = make_app(response_text="Once only.")
    app.cfg.kafka.commit_after_process = True
    app.kafka._manual_commit = True  # client was built before the override
    app._commit_enabled = True
    await app.start(serve_http=False)
    try:
        d0 = METRICS.get("finchat_kafka_dedupe_skips_total")
        producer = KafkaClient(app.cfg.kafka, broker=broker)
        payload = inbound(message_id="m-1")
        producer.produce_message(USER_MESSAGE_TOPIC, "c1", payload)
        producer.produce_message(USER_MESSAGE_TOPIC, "c1", payload)  # redelivery
        for _ in range(300):
            out = drain_json(broker)
            if sum(1 for e in out if e.get("type") == "complete") >= 1:
                break
            await asyncio.sleep(0.01)
        await asyncio.sleep(0.1)  # let the duplicate poll + commit land
        out = drain_json(broker)
        completes = [e for e in out if e.get("type") == "complete"]
        assert len(completes) == 1, f"duplicate message_id answered twice: {out}"
        assert METRICS.get("finchat_kafka_dedupe_skips_total") == d0 + 1
        # both offsets committed: the group's watermark moved past them
        group = broker._groups[GROUP_ID]
        committed = sum(
            off for (topic, _p), off in group.offsets.items()
            if topic == USER_MESSAGE_TOPIC
        )
        assert committed == 2, group.offsets
    finally:
        await app.stop()


async def test_failed_message_id_is_retryable_not_deduped():
    """Only ANSWERED message_ids stay in the dedupe ring: a message whose
    handling failed (error chunk) leaves the ring, so the producer's retry
    is reprocessed instead of black-holed."""
    from finchat_tpu.utils.metrics import METRICS

    app, broker, _ = make_app(fail_response=True)
    app.cfg.kafka.commit_after_process = True
    app.kafka._manual_commit = True
    app._commit_enabled = True
    await app.start(serve_http=False)
    try:
        producer = KafkaClient(app.cfg.kafka, broker=broker)
        payload = inbound(message_id="m-fail")
        producer.produce_message(USER_MESSAGE_TOPIC, "c1", payload)
        for _ in range(300):
            if drain_json(broker):
                break
            await asyncio.sleep(0.01)
        await asyncio.sleep(0.05)  # let the done-callback run
        assert drain_json(broker)[-1]["error"] is True
        assert "m-fail" not in app._seen_ids, "failed id stuck in the dedupe ring"
        d0 = METRICS.get("finchat_kafka_dedupe_skips_total")
        producer.produce_message(USER_MESSAGE_TOPIC, "c1", payload)  # retry
        for _ in range(300):
            if len(drain_json(broker)) >= 2:
                break
            await asyncio.sleep(0.01)
        assert len(drain_json(broker)) >= 2, "retry of a failed message was skipped"
        assert METRICS.get("finchat_kafka_dedupe_skips_total") == d0
    finally:
        await app.stop()


async def test_same_conversation_messages_stay_ordered():
    """Two messages for the SAME conversation must not interleave: the
    second's chunks start only after the first's complete marker (the
    ordering guarantee the reference gets from partition keying + serial
    processing). Same key → same partition → per-partition drain order IS
    the delivery order."""
    app, broker, _ = make_app(response_text="steady " * 10)
    await app.start(serve_http=False)
    try:
        producer = KafkaClient(app.cfg.kafka, broker=broker)
        producer.produce_message(USER_MESSAGE_TOPIC, "c1", inbound(seq="first"))
        producer.produce_message(USER_MESSAGE_TOPIC, "c1", inbound(seq="second"))
        await _watch_until(broker, n_complete=2)

        events = drain_json(broker)  # one partition (same key): exact order
        completes = [i for i, e in enumerate(events) if e.get("type") == "complete"]
        assert len(completes) == 2, events
        # every event before the first complete belongs to the first message
        assert all(e.get("seq") == "first" for e in events[: completes[0]]), events
        assert all(
            e.get("seq") == "second" for e in events[completes[0] + 1 : completes[1]]
        ), events
    finally:
        await app.stop()


# --- warm-up on a stack of its own (ISSUE 38) ---------------------------------

@pytest.mark.parametrize("outcome", ["returns", "raises", "base_exception"])
def test_on_a_fresh_stack_runs_on_its_own_thread_and_hands_back_the_outcome(outcome):
    """``engine.warmup`` runs through this: on a thread whose frames start
    from an empty chunk (so how deep ``build_app``'s caller is cannot move
    the warm-up's seconds), with the caller seeing exactly what a plain call
    would have given."""
    import threading

    from finchat_tpu.serve.app import _on_a_fresh_stack
    from finchat_tpu.utils.tracing import TRACER

    seen = {}

    def work():
        seen["thread"] = threading.current_thread()
        seen["depth"] = len(__import__("inspect").stack())
        seen["stage"] = TRACER.stage  # the phase open in the caller is the worker's too
        if outcome == "raises":
            raise ValueError("warm-up failed")
        if outcome == "base_exception":
            raise KeyboardInterrupt
        return 12.5

    TRACER._startup_open = "engine_init"  # held directly: no start-up gauge moves
    try:
        if outcome == "returns":
            assert _on_a_fresh_stack(work) == 12.5
        else:
            with pytest.raises(ValueError if outcome == "raises" else KeyboardInterrupt):
                _on_a_fresh_stack(work)
    finally:
        TRACER._startup_open = None
    assert seen["thread"] is not threading.main_thread() and seen["thread"].name == "finchat-warmup"
    assert not seen["thread"].is_alive()
    assert seen["depth"] < 9  # the thread's bootstrap, the helper's closure, the roomy frame, the work
    assert seen["stage"] == "engine_init"
