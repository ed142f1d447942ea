"""Reader of the program's own count of what compiling cost, by stage: the
counters ``finchat_compile_trace_seconds_total{stage}`` (Python's tracing and
lowering of a program, which no cache removes) and
``finchat_compile_seconds_total{stage,cache}`` (the backend's span: XLA
compiling, or retrieving from the persistent cache and loading) that the
tracer's ``jax.monitoring`` listeners book. A stage is a phase of start-up
(the program's ``STARTUP_PHASES``), ``serving`` between ``App.start`` and
``App.stop``, or ``idle`` (the harness's own reference model and logits
check compile in this process too, outside both).

``startup_front``          Σ of the first family over the start-up phases.
``startup_backend``        Σ of the second family over the start-up phases.
``serving_before_window``  both families at stage ``serving`` up to the
                           window's open — ingest, stored contexts, the
                           lead-in's prompt phase, all inside ``setup_s``:
                           ``Context.prom_before``'s sums (over every label
                           set) less the stages that are not ``serving`` as
                           the process's ``METRICS`` holds them (they stand
                           still once the App has started).

The start-up counters are booked long before the window, so its two
snapshots cannot show them by stage: they are read from this process's
``METRICS``. None where the window's closing snapshot (``Context.prom_after``,
the run's own account of what the program books) holds neither family: a
program without the counters, or no run at all.
"""
import re

FRONT = "finchat_compile_trace_seconds_total"
BACKEND = "finchat_compile_seconds_total"
SERVING = "serving"
_STAGE = re.compile(r'stage="([^"]*)"')


def _series() -> dict:
    """``name{labels}`` → value of the process's own registry."""
    try:
        from finchat_tpu.utils.metrics import METRICS
    except ImportError:
        return {}
    return METRICS.snapshot()


def _by_stage(series: dict, family: str) -> dict:
    found: dict = {}
    for key, value in series.items():
        stage = _STAGE.search(key)
        if stage and key.split("{", 1)[0] == family:
            found[stage.group(1)] = found.get(stage.group(1), 0.0) + value
    return found


QUANTITIES = ("startup_front", "startup_backend", "serving_before_window")


def read(ctx, *, quantity: str):
    if quantity not in QUANTITIES:
        raise ValueError(f"compile_seconds cannot read {quantity!r}")
    try:
        from finchat_tpu.utils.tracing import STARTUP_PHASES
    except ImportError:
        return None
    if FRONT not in ctx.prom_after and BACKEND not in ctx.prom_after:
        return None
    series = _series()
    front, backend = _by_stage(series, FRONT), _by_stage(series, BACKEND)
    if quantity == "startup_front":
        return sum(front.get(p, 0.0) for p in STARTUP_PHASES)
    if quantity == "startup_backend":
        return sum(backend.get(p, 0.0) for p in STARTUP_PHASES)
    return sum(ctx.prom_before.get(family, 0.0)
               - sum(v for stage, v in by.items() if stage != SERVING)
               for family, by in ((FRONT, front), (BACKEND, backend)))
