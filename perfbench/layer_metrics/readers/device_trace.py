"""Reader over the reduced device trace (``trace_reduce.DeviceTrace``).

``module_ms``     median device time of one XLA module (``jit_<step>``), ms.
``kernel_share``  % of device busy time inside custom calls whose name or
                  stats contain one of ``patterns``.
``stream_roofline``  % — the least time a decode step could take to stream
                  its bytes (weights + the live KV on distinct physical pages,
                  ``live_kv.py``, from shapes, the model's adapter) at
                  the chip's peak bandwidth (``peaks.json``), over the median
                  device time of ``module``. A stream bound of the whole step,
                  named as such; not a kernel's roofline share.
"""
import statistics

from perfbench import costs
from perfbench.models import adapter


def read(ctx, *, quantity: str, module: str | None = None,
         patterns: list[str] | None = None):
    trace = ctx.device_trace
    if trace is None or trace.busy_s <= 0:
        return None
    if quantity == "kernel_share":
        return 100.0 * trace.kernel_seconds(patterns or []) / trace.busy_s
    durations = trace.modules.get(module)
    if not durations:
        return None
    median_s = statistics.median(durations)
    if quantity == "module_ms":
        return median_s * 1e3
    if quantity == "stream_roofline":
        live = ctx.extra.get("mean_live_kv_tokens")
        if live is None:
            return None
        nbytes = adapter(ctx.model).decode_step_stream_bytes(
            ctx.model, live_kv_tokens=live, ctx=ctx)
        peak = costs.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
        return 100.0 * (nbytes / peak) / median_s
    raise ValueError(f"device_trace cannot read {quantity!r}")
