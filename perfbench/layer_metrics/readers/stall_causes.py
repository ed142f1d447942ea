"""Reader of what the window's stalled time was lost TO, from ``TRACER``'s
``round``, ``freeze`` and ``compile`` events (all on ``perf_counter``).

Stalled time is ``tracer_round.py``'s ``stall_ms``, by its own rule: of every
period between consecutive round starts, the excess over 3 median periods.
Each stalled period's excess is handed out in this order, a second of overlap
counted once, for the first cause that covers it:

``frozen_ms``   the part ``freeze`` events cover: the process could not run a
                thread that only sleeps (``args.owner`` says whose it was).
``compile_ms``  of the rest, the part ``compile`` events cover, each with the
                ``trace_s`` + ``lower_s`` Python spent on the program before
                the backend's span: a first-time compile or load.
``prompt_ms``   of the rest, the part inside the period's own round where its
                ``kind`` is not ``decode`` / ``drain``: a round that carried a
                row's prompt (an admission after a sampled EOS).

A cause never takes more than the excess still unnamed, so the three parts
and an unnamed remainder sum to ``stall_ms`` of the same events by
construction. 0 is a value; None where ``stall_ms`` is None (no ``round``
events, or fewer than three). A program without ``freeze`` / ``compile``
events reads 0 for those parts.
"""
import statistics

STEADY_KINDS = ("decode", "drain")
QUANTITIES = ("frozen_ms", "compile_ms", "prompt_ms")


def _measure(spans, lo, hi):
    """Length of the union of ``spans`` inside ``[lo, hi)``."""
    total, end = 0.0, lo
    for a, b in sorted(spans):
        a, b = max(a, end), min(b, hi)
        if b > a:
            total += b - a
            end = b
    return total


def parts(events) -> dict | None:
    """``{"stall_ms", "frozen_ms", "compile_ms", "prompt_ms"}`` of a list of
    ring tuples, or None where ``stall_ms`` has nothing to read."""
    rounds = sorted((ts, dur, args or {}) for ts, _tid, name, dur, _track, args in events
                    if name == "round" and dur is not None)
    periods = [b[0] - a[0] for a, b in zip(rounds, rounds[1:])]
    if len(periods) < 2:
        return None
    frozen = [(ts, ts + dur) for ts, _tid, name, dur, _track, _args in events
              if name == "freeze" and dur is not None]
    compiling = [(ts - (args or {}).get("trace_s", 0.0) - (args or {}).get("lower_s", 0.0), ts + dur)
                 for ts, _tid, name, dur, _track, args in events
                 if name == "compile" and dur is not None]
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
                ("prompt_ms", [] if args.get("kind") in STEADY_KINDS else [(ts, ts + dur)])):
            causes = causes + spans
            covered = _measure(causes, lo, hi)
            took = min(left, covered - named)
            out[quantity] += took
            left -= took
            named = covered
    return {k: 1e3 * v for k, v in out.items()}


def read(ctx, *, quantity: str):
    if quantity not in QUANTITIES:
        raise ValueError(f"stall_causes cannot read {quantity!r}")
    found = parts(ctx.tracer_events)
    return None if found is None else found[quantity]
