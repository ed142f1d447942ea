"""Reader of latent attention's one-token work against its roofline, from the
run's own capture (``perfbench/xplane_scopes.py``; ``ssm_scan_trace.py`` and
``moe_experts_trace.py`` read a scope of the layer scan the same way).

The time is that of the operations under ``scope`` in ONE layer of ONE step
of ``module``: every layer runs them once a step — the leading dense layers
outside the program's scan, the others inside it — so it is their whole
device time in the capture over the steps the capture holds (the ``XLA
Modules`` line's count) and the configuration's layers.

``attention_roofline``  % — what the least a correct one-token attention call
    must do takes at the chip's peaks (the model's adapter,
    ``mla_attention_bound_s``: the larger of its stream time over the SELECTED
    latent rows — the window's mean a row a layer, from the program's two
    counters — and its MXU time, for the mean ``rows`` that the capture's
    dispatches of ``kinds`` carried) over that time.
``index_roofline``  % — the index keys of the context tokens on DISTINCT
    physical pages (``scope_trace``'s count; the adapter's
    ``index_stream_bytes``) at the chip's peak bandwidth over that time.

Over 100 % is a fault in the count. None where there is nothing to read it
from: a program without the scope, the counters or the stats, or an adapter
without the count.
"""
from perfbench import costs, trace_reduce, xplane_scopes
from perfbench.layer_metrics.readers.scope_trace import TRACE_DIR, _decode_kv_tokens
from perfbench.models import adapter


def read(ctx, *, quantity: str, scope: str, module: str, kinds: list[str]):
    trace = ctx.device_trace
    path = trace_reduce.find_xplane(TRACE_DIR)
    model = adapter(ctx.model)
    if trace is None or trace.busy_s <= 0 or path is None:
        return None
    steps = len(trace.modules.get(f"jit_{module}", ()))
    paths = xplane_scopes.op_scope_paths(str(path))
    under = sum(dur for _dev, name, _kind, _start, dur in xplane_scopes.device_ops(path)
                if f"jit({module})" in (paths.get(name) or "")
                and xplane_scopes.scope_of(paths.get(name), {scope}) == scope)
    if not steps or not under:
        return None
    layer_s = under / 1e9 / steps / int(ctx.model["num_hidden_layers"])
    if quantity == "attention_roofline":
        bound = getattr(model, "mla_attention_bound_s", None)
        selected = model.selected_tokens(ctx.model, ctx) if bound is not None else None
        rows = [stats["rows"]
                for events in xplane_scopes.annotations(path).values()
                for _name, _start, _end, stats in events
                if "rows" in stats and stats.get("kind") in kinds]
        if selected is None or not rows:
            return None
        return 100.0 * bound(ctx.model, rows=sum(rows) / len(rows), selected=selected) / layer_s
    if quantity == "index_roofline":
        count = getattr(model, "index_stream_bytes", None)
        tokens = _decode_kv_tokens(path, set(kinds)) if count is not None else None
        if tokens is None:
            return None
        peak = costs.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
        return 100.0 * (count(ctx.model, kv_tokens=tokens[1]) / peak) / layer_s
    raise ValueError(f"latent_trace cannot read {quantity!r}")
