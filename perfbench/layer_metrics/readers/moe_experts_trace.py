"""Reader of the routed experts' share of their stream bound in the one-token
step, from the run's own capture (``perfbench/xplane_scopes.py``;
``ssm_scan_trace.py`` reads a mixer's state update the same way).

``moe_expert_roofline``: the bytes the routed experts of one iteration of
``module``'s layer scan must move (the model's adapter,
``moe_step_stream_bytes``: the weights of the held experts a step TOUCHED —
the window's mean, from the program's two counters — and the rows' inputs and
outputs, for the mean ``rows`` that the capture's dispatches of ``kinds``
carried) at the chip's peak bandwidth, over the device time of the operations
under ``scope`` in ONE iteration of the scan of ONE step: every such
operation runs once an iteration a step, so that time is the sum over the
distinct operations of each one's mean duration. Over 100 % is a fault in the
count. An implementation that reads every held expert cannot read above
touched / held of what the stream sustains.

None where there is nothing to read it from: a program without the scope, the
``rows`` stat or the counters, or an adapter without the count.
"""
from collections import defaultdict

from perfbench import costs, trace_reduce, xplane_scopes
from perfbench.layer_metrics.readers.scope_trace import TRACE_DIR
from perfbench.models import adapter


def read(ctx, *, scope: str, module: str, kinds: list[str]):
    trace = ctx.device_trace
    path = trace_reduce.find_xplane(TRACE_DIR)
    model = adapter(ctx.model)
    count = getattr(model, "moe_step_stream_bytes", None)
    touched = model.experts_touched(ctx.model, ctx) if count is not None else None
    if trace is None or trace.busy_s <= 0 or path is None or touched is None:
        return None
    paths = xplane_scopes.op_scope_paths(str(path))
    durations = defaultdict(list)
    for _dev, name, _kind, _start, dur in xplane_scopes.device_ops(path):
        scope_path = paths.get(name) or ""
        if f"jit({module})" in scope_path and xplane_scopes.scope_of(scope_path, {scope}) == scope:
            durations[name].append(dur)
    rows = [stats["rows"]
            for events in xplane_scopes.annotations(path).values()
            for _name, _start, _end, stats in events
            if "rows" in stats and stats.get("kind") in kinds]
    if not durations or not rows:
        return None
    period_s = sum(sum(d) / len(d) for d in durations.values()) / 1e9
    nbytes = count(ctx.model, rows=sum(rows) / len(rows), experts_touched=touched)
    peak = costs.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * (nbytes / peak) / period_s
