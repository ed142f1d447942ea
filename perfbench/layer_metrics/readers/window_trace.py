"""Reader of the sliding-window layers' K/V read against its roofline in the
one-token step, from the run's own capture (``perfbench/xplane_scopes.py``;
``yoco_trace.py`` reads one cache walked by many layers the same way).

A sliding-window layer reads, for each row, the row's last ``sliding_window``
tokens — never its whole context, and never a page another row shares with it
once the window has slid past the shared head. The time is that of the
operations under ``scope`` in ONE step of ``module``: the scope's whole device
time in the capture over the steps the capture holds (the ``XLA Modules``
line's count). The bytes are the model's adapter's (``window_stream_bytes``):
the mean ``window_kv_tokens`` that the capture's dispatches of ``kinds`` noted
on their annotation (the program's stat: the sum over the dispatch's rows of
min(context, window), what ONE window layer's walk must read) times a token's
K and V in every sliding layer. The share is of the chip's peak bandwidth
(``peaks.json``). Over 100 % is a fault in the count.

None where there is nothing to read it from: a program without the scope or
the stat, or an adapter without the count.
"""
from perfbench import costs, trace_reduce, xplane_scopes
from perfbench.layer_metrics.readers.scope_trace import TRACE_DIR
from perfbench.models import adapter


def read(ctx, *, scope: str, module: str, kinds: list[str]):
    trace = ctx.device_trace
    path = trace_reduce.find_xplane(TRACE_DIR)
    count = getattr(adapter(ctx.model), "window_stream_bytes", None)
    if trace is None or trace.busy_s <= 0 or path is None or count is None:
        return None
    steps = len(trace.modules.get(f"jit_{module}", ()))
    paths = xplane_scopes.op_scope_paths(str(path))
    under = sum(dur for _dev, name, _kind, _start, dur in xplane_scopes.device_ops(path)
                if f"jit({module})" in (paths.get(name) or "")
                and xplane_scopes.scope_of(paths.get(name), {scope}) == scope)
    noted = [stats["window_kv_tokens"]
             for events in xplane_scopes.annotations(path).values()
             for _name, _start, _end, stats in events
             if "window_kv_tokens" in stats and stats.get("kind") in kinds]
    if not (steps and under and noted):
        return None
    nbytes = count(ctx.model, window_kv_tokens=sum(noted) / len(noted))
    peak = costs.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * (nbytes / peak) / (under / 1e9 / steps)
