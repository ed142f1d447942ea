"""Reader of ONE cache read by many layers against its roofline, from the
run's own capture (``perfbench/xplane_scopes.py``; ``latent_trace.py`` reads a
scope of a step the same way).

A model whose cross-attention layers read the pages its one full-attention
layer wrote (YOCO) walks that cache once a layer that reads it. The time is
that of the operations under ``scope`` in ONE step of ``module``: the scope's
whole device time in the capture over the steps the capture holds (the ``XLA
Modules`` line's count). The bytes are the model's adapter's
(``yoco_stream_bytes``): the context tokens on DISTINCT physical pages
(``scope_trace``'s count: the program's stat less what the harness's samples
count more than once) times a token's K and V, times the layers that read them.
The share is of the chip's peak bandwidth (``peaks.json``). Over 100 % is a
fault in the count.

``quantity`` ``layer_reads``: how many layers read that cache for each one
that wrote it, counted from what RAN — under ``scope`` in ``module``, the
executions of the kernel whose name starts with ``reads`` (the page walk's
custom call: one a reading layer a step) over those of the kernel whose name
starts with ``writes`` (the append's: one a layer that keeps pages there a
step), between the capture's first and last append: whole steps. 8.0 while seven cross layers read the one full layer's pages; a cross
layer that kept a cache of its own would append to it, and the ratio falls
towards 1.

None where there is nothing to read it from: a program without the scope or
the stats, or an adapter without the count.
"""
from perfbench import costs, trace_reduce, xplane_scopes
from perfbench.layer_metrics.readers.scope_trace import TRACE_DIR, _decode_kv_tokens
from perfbench.models import adapter


def _layer_reads(ctx, *, scope: str, module: str, reads: str, writes: str):
    path = trace_reduce.find_xplane(TRACE_DIR)
    if ctx.device_trace is None or path is None:
        return None
    paths = xplane_scopes.op_scope_paths(str(path))
    starts: dict = {}  # device -> kernel -> start times of its executions
    for dev, name, _kind, start, _dur in xplane_scopes.device_ops(path):
        scope_path = paths.get(name) or ""
        if (f"jit({module})" in scope_path
                and xplane_scopes.scope_of(scope_path, {scope}) == scope):
            for kernel in (reads, writes):  # an event is named as its HLO instruction: %kernel.N = ...
                if name.lstrip("%").startswith(kernel):
                    starts.setdefault(dev, {reads: [], writes: []})[kernel].append(start)
    # whole steps only: a layer appends BEFORE it walks, so the walks between
    # the capture's first and last append belong to the appends but the last
    # (a step the capture's edge cut would weigh less than it ran)
    n_reads = n_writes = 0
    for by_kernel in starts.values():
        wrote = sorted(by_kernel[writes])
        if len(wrote) > 1:
            n_reads += sum(wrote[0] <= t < wrote[-1] for t in by_kernel[reads])
            n_writes += len(wrote) - 1
    return n_reads / n_writes if n_writes else None


def read(ctx, *, scope: str, module: str, kinds: list[str] = (), quantity: str = "roofline",
         reads: str = "", writes: str = ""):
    if quantity == "layer_reads":
        return _layer_reads(ctx, scope=scope, module=module, reads=reads, writes=writes)
    trace = ctx.device_trace
    path = trace_reduce.find_xplane(TRACE_DIR)
    count = getattr(adapter(ctx.model), "yoco_stream_bytes", None)
    if trace is None or trace.busy_s <= 0 or path is None or count is None:
        return None
    steps = len(trace.modules.get(f"jit_{module}", ()))
    paths = xplane_scopes.op_scope_paths(str(path))
    under = sum(dur for _dev, name, _kind, _start, dur in xplane_scopes.device_ops(path)
                if f"jit({module})" in (paths.get(name) or "")
                and xplane_scopes.scope_of(paths.get(name), {scope}) == scope)
    tokens = _decode_kv_tokens(path, set(kinds)) if steps and under else None
    if tokens is None:
        return None
    peak = costs.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * (count(ctx.model, kv_tokens=tokens[1]) / peak) / (under / 1e9 / steps)
