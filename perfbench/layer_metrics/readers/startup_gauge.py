"""Reader of one phase of the program's own start-up clock: the gauge
``finchat_startup_seconds{phase=...}`` in this process's ``METRICS`` (set
once, long before the window, so the window's two snapshots cannot show
it). None where the program has no such gauge."""


def read(ctx, *, phase: str):
    try:
        from finchat_tpu.utils.metrics import METRICS
    except ImportError:
        return None
    seconds = METRICS.get("finchat_startup_seconds", labels={"phase": phase})
    return seconds if seconds > 0 else None
