"""Reader over ``TRACER``'s ``round`` events (one per scheduler iteration
that dispatched or consumed; ``args`` hold the seconds of each phase).

``host_ms``   median over the window's rounds of the round less its
              ``fetch_wait`` and ``yield``: the scheduler's own host time a
              round — hidden while a device step is longer, the round's
              floor as soon as it is not.
``stall_ms``  over the whole window: Σ max(0, period − 3 × median period)
              between consecutive round starts. About 0 in an even run,
              about the lost time where rounds stalled.

None where the program emits no ``round`` events.
"""
import statistics

WAITS = ("fetch_wait", "yield")


def read(ctx, *, quantity: str):
    rounds = sorted((ts, dur, args or {})
                    for ts, _tid, name, dur, _track, args in ctx.tracer_events
                    if name == "round" and dur is not None)
    if quantity == "host_ms":
        if not rounds:
            return None
        return 1e3 * statistics.median(
            dur - sum(args.get(w, 0.0) for w in WAITS) for _ts, dur, args in rounds)
    if quantity == "stall_ms":
        periods = [b[0] - a[0] for a, b in zip(rounds, rounds[1:])]
        if len(periods) < 2:
            return None
        limit = 3 * statistics.median(periods)
        return 1e3 * sum(max(0.0, p - limit) for p in periods)
    raise ValueError(f"tracer_round cannot read {quantity!r}")
