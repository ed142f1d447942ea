"""Reader over the run's own capture, by named scope and by the program's
``finchat.<phase>`` annotations (``perfbench/xplane_scopes.py``). The
harness hands readers a reduced trace without either, so this one opens the
capture the run just wrote. Everything is on the capture's one clock: no
offset between host and device is computed here.

``scope_share``    % of device busy time in operations under one of ``scopes``.
``kernel_stream_roofline``  % — the bytes one call of a kernel must read
                   (the model's adapter, from the tokens on DISTINCT physical
                   pages that the capture's dispatches of ``kinds`` read:
                   ``_decode_kv_tokens``) at the chip's peak bandwidth, over
                   the mean device time of one call: custom calls under one
                   of ``scopes`` whose name contains one of ``patterns``.
``kv_distinct_share``  % — of the context tokens those dispatches read, Σ
                   rows' contexts, the part on distinct physical pages: what
                   a pass that reads a shared page once has left to read;
                   100 where no row shares a page.
``idle_off_phases``  ms of device idle time inside the capture that none of
                   the ``on`` phases covers (less the ``off`` phases nested
                   in them): the chip waited while the scheduler was not at
                   work.

Each returns None where the capture holds nothing to read it from — a
program without the scopes or the annotations.
"""
from pathlib import Path

from perfbench import costs, trace_reduce, xplane_scopes
from perfbench.live_kv import LIVE_ANNOTATION
from perfbench.models import adapter

TRACE_DIR = Path(__file__).resolve().parents[3] / ".perfbench_work" / "trace"


def read(ctx, *, quantity: str, scopes: list[str] | None = None,
         patterns: list[str] | None = None, kinds: list[str] | None = None,
         on: list[str] | None = None, off: list[str] | None = None):
    trace = ctx.device_trace
    path = trace_reduce.find_xplane(TRACE_DIR)
    if trace is None or trace.busy_s <= 0 or path is None:
        return None
    if quantity == "scope_share":
        paths = xplane_scopes.op_scope_paths(str(path)).values()
        if not any(xplane_scopes.scope_of(p, scopes) in scopes for p in paths):
            return None  # a program without these scopes: nothing to read, not 0 %
        table = xplane_scopes.seconds_by_scope(path, set(scopes))
        return 100.0 * sum(table.get(s, 0.0) for s in scopes) / trace.busy_s
    if quantity == "kernel_stream_roofline":
        return _kernel_stream_roofline(ctx, path, set(scopes), patterns, set(kinds))
    if quantity == "kv_distinct_share":
        tokens = _decode_kv_tokens(path, set(kinds))
        return None if tokens is None else 100.0 * tokens[1] / tokens[0]
    if quantity == "idle_off_phases":
        return _idle_off_phases(trace, path, on, off)
    raise ValueError(f"scope_trace cannot read {quantity!r}")


def _kernel_stream_roofline(ctx, path, scopes, patterns, kinds):
    paths = xplane_scopes.op_scope_paths(str(path))
    calls = [dur for _dev, name, kind, _start, dur in xplane_scopes.device_ops(path)
             if kind == "custom-call" and any(p in name for p in patterns)
             and xplane_scopes.scope_of(paths.get(name), scopes) in scopes]
    tokens = _decode_kv_tokens(path, kinds) if calls else None
    if tokens is None:
        return None
    nbytes = adapter(ctx.model).attention_stream_bytes(ctx.model, kv_tokens=tokens[1])
    peak = costs.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * (nbytes / peak) / (sum(calls) / len(calls) / 1e9)


def _decode_kv_tokens(path, kinds):
    """``(total, distinct)``: the mean ``kv_tokens`` that the capture's
    dispatches of ``kinds`` noted on their annotation (Σ rows' contexts, the
    program's stat), and the same less the tokens it counts more than once
    because rows share their pages: the mean ``kv_tokens - kv_tokens_distinct``
    of the harness's own samples in the capture (``perfbench/live_kv.py``, an
    event every 0.25 s). None where no such dispatch noted its context. A
    capture with the program's stat and without the harness's raises: there
    is no falling back to a count that reads a shared page once a row."""
    noted = [stats["kv_tokens"]
             for events in xplane_scopes.annotations(path).values()
             for _name, _start, _end, stats in events
             if "kv_tokens" in stats and stats.get("kind") in kinds]
    if not noted:
        return None
    repeats = [stats["kv_tokens"] - stats["kv_tokens_distinct"]
               for events in xplane_scopes.annotations(path, LIVE_ANNOTATION).values()
               for _name, _start, _end, stats in events if "kv_tokens_distinct" in stats]
    if not repeats:
        raise ValueError(f"{path}: {len(noted)} dispatches note kv_tokens and no "
                         f"{LIVE_ANNOTATION} event notes kv_tokens_distinct")
    total = sum(noted) / len(noted)
    return total, total - sum(repeats) / len(repeats)


def _idle_off_phases(trace, path, on, off):
    events = [ev for thread in xplane_scopes.annotations(path).values() for ev in thread]
    covered = [(s, e) for name, s, e, _ in events if name[8:] in on]
    holes = [(s, e) for name, s, e, _ in events if name[8:] in off]
    if not covered:
        return None
    working = xplane_scopes.subtract(trace_reduce.union_seconds(covered)[1],
                                     trace_reduce.union_seconds(holes)[1])
    edges = [trace.window_ns[0], *[t for iv in trace.busy_intervals for t in iv],
             trace.window_ns[1]]
    idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    return sum(e - s for s, e in xplane_scopes.subtract(idle, working)) / 1e6
