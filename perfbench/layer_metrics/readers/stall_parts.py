"""Reader of the window's stalled time handed out TO THE END: what
``stall_causes.py`` names and, of what it leaves, the round in which an answer
ended and the two phases in which the loop was not at work (ISSUE 53).

Stalled time is ``tracer_round.py``'s ``stall_ms``, walked period by period by
``stall_causes.py``'s own rule (a period between consecutive round starts, its
excess over 3 median periods). Each stalled period's excess is handed out in
this order, a cause never taking more than what is still unnamed:

``frozen_ms`` ``compile_ms`` ``prompt_ms``
                exactly ``stall_causes.parts``: on any event list the two
                readers agree on these three.
``retire_ms``   of the rest, the part ``retire`` events cover (one a row whose
                answer ended by EOS or by its budget, the span of the
                scheduler's ``retire`` phase): by the union with the causes
                above, as ``freeze`` events are read, so a retirement inside a
                prompt round or under a freeze is counted once, there.
``yield_ms``    of the rest, at most ``args.yield`` of the period's own
                ``round``: the loop was with the process's other tasks (Kafka,
                the agent, the store).
``wait_ms``     of the rest, at most ``args.fetch_wait`` of that round: the
                loop waited for the device's tokens.

A phase's seconds are a SUM over the round, not an interval: ``yield_ms`` and
``wait_ms`` cannot tell which of their seconds an earlier cause already covers
(a freeze that fell inside the yield, a prompt round's own yield), so they can
over-name there — never past the excess, so the six parts stay at or under
``stall_ms``. What is then left is the scheduler's own host code (``admit``,
``stage``, ``dispatch``, the rest of ``deliver``) and the gap behind the round.

``offload_ms``  not a part of the split: Σ ``args.offload_s`` of the window's
                ``retire`` events, the blocking device→host copies alone.

0 is a value wherever the window has ``round`` events: no ``retire`` event (no
row ended; or a program that emits none) reads 0.0. None only where
``stall_ms`` is None (fewer than three rounds).
"""
import statistics

from perfbench.layer_metrics.readers.stall_causes import STEADY_KINDS, _measure

QUANTITIES = ("frozen_ms", "compile_ms", "prompt_ms", "retire_ms", "yield_ms", "wait_ms")


def _spans(events, name, before=lambda args: 0.0):
    return [(ts - before(args or {}), ts + dur) for ts, _tid, ev, dur, _track, args in events
            if ev == name and dur is not None]


def parts(events) -> dict | None:
    """``stall_ms``, the six ``QUANTITIES`` and ``offload_ms`` of a list of
    ring tuples, or None where ``stall_ms`` has nothing to read."""
    rounds = sorted((ts, dur, args or {}) for ts, _tid, name, dur, _track, args in events
                    if name == "round" and dur is not None)
    periods = [b[0] - a[0] for a, b in zip(rounds, rounds[1:])]
    if len(periods) < 2:
        return None
    frozen = _spans(events, "freeze")
    compiling = _spans(events, "compile",
                       lambda args: args.get("trace_s", 0.0) + args.get("lower_s", 0.0))
    retiring = _spans(events, "retire")
    limit = 3 * statistics.median(periods)
    out = dict.fromkeys(("stall_ms",) + QUANTITIES, 0.0)
    for (ts, dur, args), period in zip(rounds, periods):
        left = period - limit
        if left <= 0:
            continue
        out["stall_ms"] += left
        lo, hi = ts, ts + period
        named = 0.0  # of the period, what the causes so far cover
        causes = []
        for quantity, spans in (
                ("frozen_ms", frozen), ("compile_ms", compiling),
                ("prompt_ms", [] if args.get("kind") in STEADY_KINDS else [(ts, ts + dur)]),
                ("retire_ms", retiring)):
            causes = causes + spans
            covered = _measure(causes, lo, hi)
            took = min(left, covered - named)
            out[quantity] += took
            left -= took
            named = covered
        for quantity, phase in (("yield_ms", "yield"), ("wait_ms", "fetch_wait")):
            took = min(left, args.get(phase, 0.0))
            out[quantity] += took
            left -= took
    out = {k: 1e3 * v for k, v in out.items()}
    out["offload_ms"] = 1e3 * sum((args or {}).get("offload_s", 0.0)
                                  for _ts, _tid, name, _dur, _track, args in events
                                  if name == "retire")
    return out


def read(ctx, *, quantity: str):
    if quantity not in QUANTITIES + ("offload_ms",):
        raise ValueError(f"stall_parts cannot read {quantity!r}")
    found = parts(ctx.tracer_events)
    return None if found is None else found[quantity]
