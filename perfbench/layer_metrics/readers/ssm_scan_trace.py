"""Reader of the one-token state update's share of its stream bound, from the
run's own capture (``perfbench/xplane_scopes.py``; ``scope_trace.py`` reads
the other scopes the same way).

``ssm_state_roofline``: the bytes one layer's update must move (the model's
adapter, ``ssm_step_stream_bytes``: each row's recurrent state read and
written once, and its xs, B, C, dt, y) for the mean ``rows`` that the
capture's dispatches of ``kinds`` carried — a stat of their ``finchat.*``
annotation — at the chip's peak bandwidth, over the device time of the
operations under ``scope`` in ONE layer of ONE step of ``module``: every such
operation runs once a layer a step, so that time is the sum over the
distinct operations of each one's mean duration (a step cut by the capture's
edge then weighs nothing). Over 100 % is a fault in the count.

None where the capture holds nothing to read it from: a program without the
scope, the ``rows`` stat, or an adapter without the count.
"""
from collections import defaultdict

from perfbench import costs, trace_reduce, xplane_scopes
from perfbench.layer_metrics.readers.scope_trace import TRACE_DIR
from perfbench.models import adapter


def read(ctx, *, scope: str, module: str, kinds: list[str]):
    trace = ctx.device_trace
    path = trace_reduce.find_xplane(TRACE_DIR)
    count = getattr(adapter(ctx.model), "ssm_step_stream_bytes", None)
    if trace is None or trace.busy_s <= 0 or path is None or count is None:
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
    layer_step_s = sum(sum(d) / len(d) for d in durations.values()) / 1e9
    nbytes = count(ctx.model, rows=sum(rows) / len(rows))
    peak = costs.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * (nbytes / peak) / layer_step_s
