"""The load generator: an asyncio task in the server's own process (the chip
belongs to one process). Ingress is the reference's own — the user's message
is written to the store and produced to ``user_message`` — and every chunk
consumed from ``ai_response`` is stamped with the host clock on arrival.

Open loop over sessions: a session's first turn is *due* at its arrival time,
a later turn a think time after the previous answer's end; latency is counted
from the due instant, and how late the generator sent is recorded per request.
"""

from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass, field

from perfbench.traffic_kinds.sessions import Session, Traffic

POLL_SLEEP_S = 0.002  # observer poll period: bounds the stamp's lateness


@dataclass
class RequestLog:
    """What the client saw of one request. Times are ``perf_counter``."""
    message_id: str
    session_id: str
    due: float
    sent: float = 0.0
    chunk_times: list[float] = field(default_factory=list)  # answer-text chunks
    done: float | None = None      # the final chunk's arrival
    ended: str | None = None       # "complete" | "error" | None (never ended)
    error_code: str | None = None


class LoadGenerator:
    def __init__(self, app, cfg, traffic: Traffic):
        from finchat_tpu.io.kafka import KafkaClient
        from finchat_tpu.utils.config import AI_RESPONSE_TOPIC, USER_MESSAGE_TOPIC

        self.app = app
        self.traffic = traffic
        self.user_topic = USER_MESSAGE_TOPIC
        self.consumer = KafkaClient(cfg.kafka)
        self.consumer.setup_consumer(topics=[AI_RESPONSE_TOPIC])  # join BEFORE producing
        self.producer = KafkaClient(cfg.kafka)
        self.requests: dict[str, RequestLog] = {}
        self._done_events: dict[str, asyncio.Event] = {}
        self._tasks: list[asyncio.Task] = []
        self._observer: asyncio.Task | None = None
        self.t0 = 0.0

    # --- set-up: what the product's own back end would have stored --------
    def store_sessions(self) -> None:
        contexts = {u.user_id: u.context for u in self.traffic.users}
        stamp = int(time.time()) - 86_400
        for s in self.traffic.sessions:
            self.app.store.upsert_context(
                s.session_id, dict(contexts[s.user_id], user_id=s.user_id))
            for sender, text in s.history:
                stamp += 1
                if sender == "user":
                    self.app.store.add_user_message(
                        s.session_id, text, user_id=s.user_id, timestamp=stamp)
                else:
                    # the store's own record shape for an earlier answer
                    self.app.store._messages.append({
                        "conversation_id": s.session_id, "sender": "AIMessage",
                        "user_id": s.user_id, "message": text, "timestamp": stamp})

    # --- the observer: every chunk on ai_response, stamped on arrival ------
    async def _observe(self) -> None:
        while True:
            msg = self.consumer.poll_message()
            if msg is None:
                await asyncio.sleep(POLL_SLEEP_S)
                continue
            now = time.perf_counter()
            chunk = json.loads(msg.value().decode())
            log = self.requests.get(chunk.get("message_id"))
            if log is None:
                continue
            if chunk.get("last_message"):
                log.done = now
                log.ended = "error" if chunk.get("error") else chunk.get("type")
                log.error_code = chunk.get("code")
                self._done_events[log.message_id].set()
            elif chunk.get("type") == "response_chunk":  # a plot chunk is not answer text
                log.chunk_times.append(now)

    # --- one session: its turns, each due a think time after the last -----
    async def _run_session(self, s: Session) -> None:
        due = self.t0 + s.arrival_s
        for k, turn in enumerate(s.turns):
            delay = due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            message_id = f"{s.session_id}-t{k}"
            log = RequestLog(message_id, s.session_id, due)
            self.requests[message_id] = log
            self._done_events[message_id] = asyncio.Event()
            self.app.store.add_user_message(s.session_id, turn.message, user_id=s.user_id)
            log.sent = time.perf_counter()
            self.producer.produce_message(self.user_topic, s.session_id, {
                "message": turn.message, "conversation_id": s.session_id,
                "message_id": message_id})
            await self._done_events[message_id].wait()
            if log.ended != "complete":
                return  # a failed turn ends its session
            if k + 1 < len(s.turns):
                due = log.done + s.turns[k + 1].think_s

    def start(self) -> None:
        self.t0 = time.perf_counter()
        self._observer = asyncio.create_task(self._observe())
        self._tasks = [asyncio.create_task(self._run_session(s))
                       for s in self.traffic.sessions]

    async def stop(self) -> None:
        for task in [*self._tasks, self._observer]:
            if task is not None:
                task.cancel()
        await asyncio.gather(*self._tasks, self._observer, return_exceptions=True)
        self.consumer.close()
        self.producer.close()
