"""Fault injection registry (SURVEY §5.3).

The reference has zero fault injection; its only resilience machinery is
layered timeouts (main.py:136-159). This registry makes failure paths
first-class testable: production code calls ``inject(site, **ctx)`` at
named sites (a no-op unless a handler is armed), and tests arm handlers
that raise, delay, or drop to drive the degradation contracts:

- per-sequence isolation: an injected prefill/decode fault evicts ONE
  sequence with an error event; the engine keeps serving others;
- Kafka produce loss: fire-and-forget chunks vanish silently (reference
  QoS, kafka_client.py:26-27), error chunks are flushed;
- retrieval failure: the answer is still generated with the Error marker
  (llm_agent.py:129-131).
- tool-streaming plane (ISSUE 9): ``tool.execute`` fires inside every
  tool execution — speculative and serial (``agent/graph.py
  _execute_tool``) — so a test can fail an eagerly-launched tool
  mid-decode and assert the structured-retryable serial fallback;
- durability plane (ISSUE 7): ``disk.spill`` (a failed session-record
  write never fails the retiring stream), ``disk.restore`` (a failed /
  corrupt record read quarantines the file and cold-starts the
  conversation — never a crash, never stale KV), and ``journal.append``
  (a failed answered-id append logs and continues — the cost is one
  possible duplicate answer after a crash, the pre-journal trade).

Sites are plain strings; ``ctx`` carries site-specific identifiers (e.g.
``seq_id``) so a handler can target one victim.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Callable, Iterator

Handler = Callable[..., None]

_lock = threading.Lock()
_handlers: dict[str, Handler] = {}


def inject(site: str, **ctx: Any) -> None:
    """Production-side hook: no-op unless a handler is armed for ``site``.
    A handler that raises propagates into the site's own error handling —
    that propagation IS the injected fault."""
    handler = _handlers.get(site)
    if handler is not None:
        handler(**ctx)


def arm(site: str, handler: Handler) -> None:
    with _lock:
        _handlers[site] = handler


def disarm(site: str) -> None:
    with _lock:
        _handlers.pop(site, None)


def disarm_all() -> None:
    with _lock:
        _handlers.clear()


@contextmanager
def armed(site: str, handler: Handler) -> Iterator[None]:
    """Scoped arming for tests."""
    arm(site, handler)
    try:
        yield
    finally:
        disarm(site)


def one_shot(exc: Exception) -> Handler:
    """Handler that raises ``exc`` exactly once, then disarms itself —
    models transient faults (the retry/degrade path must recover)."""
    fired = threading.Event()

    def handler(**_ctx: Any) -> None:
        if not fired.is_set():
            fired.set()
            raise exc

    return handler


def n_shot(n: int, exc: Exception) -> Handler:
    """Handler that raises ``exc`` exactly ``n`` times, then passes —
    models a bounded outage (the circuit breaker's consecutive-failure
    threshold is exactly this shape)."""
    remaining = [n]

    def handler(**_ctx: Any) -> None:
        if remaining[0] > 0:
            remaining[0] -= 1
            raise exc

    return handler


def for_seq(seq_id: str, exc: Exception) -> Handler:
    """Handler that raises only for one victim sequence (ctx['seq_id'])."""

    def handler(**ctx: Any) -> None:
        if ctx.get("seq_id") == seq_id:
            raise exc

    return handler


def for_replica(replica_id: str, inner: Handler) -> Handler:
    """Scope ``inner`` to one fleet replica (ctx['replica'] — each
    replica's scheduler stamps its id on its dispatch sites), so a chaos
    drill can wedge ONE engine while its siblings stay healthy
    (tests/test_fleet.py)."""

    def handler(**ctx: Any) -> None:
        if ctx.get("replica") == replica_id:
            inner(**ctx)

    return handler
