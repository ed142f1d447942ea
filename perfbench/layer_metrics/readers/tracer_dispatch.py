"""Reader over ``TRACER``'s ``dispatch`` events: mean rows (live sequences)
per model dispatch in the window."""


def read(ctx, *, quantity: str = "mean_rows"):
    rows = [len((args or {}).get("rows") or [])
            for _ts, _tid, name, _dur, _track, args in ctx.tracer_events
            if name == "dispatch"]
    rows = [n for n in rows if n > 0]
    return sum(rows) / len(rows) if rows else None
