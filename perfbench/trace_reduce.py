"""From a ``jax.profiler`` trace (``*.xplane.pb``) to device numbers.

Reads the file with ``jax.profiler.ProfileData`` alone. On a TPU the device
plane ``/device:TPU:<n>`` carries the lines ``XLA Modules`` (one event per
executed program, named ``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one
event per executed HLO op, named by the whole HLO instruction; a Pallas kernel
is a ``custom-call`` whose instruction is named after the kernel; a ``while``
spans the ops of its body). From those:

- busy seconds: the union of the op intervals (per chip, averaged);
- per-module durations, grouped by the name without its fingerprint;
- per-kernel seconds, by substring match on the op's name and stats;
- the top operations by total time;
- the idle gaps between busy intervals, each labelled by a caller's
  function of its start and end (what the host was doing).

All times are seconds; the trace's own clock is nanoseconds.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str | Path) -> Path | None:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    return files[-1] if files else None


def union_seconds(intervals: list[tuple[int, int]]) -> tuple[float, list[tuple[int, int]]]:
    """Total covered length of [start, end) intervals (ns → s) and the merged
    intervals themselves."""
    merged: list[list[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    total = sum(e - s for s, e in merged)
    return total / 1e9, [(s, e) for s, e in merged]


@dataclass
class DeviceTrace:
    n_devices: int = 0
    window_ns: tuple[int, int] = (0, 0)          # first op start .. last op end
    busy_s: float = 0.0                          # averaged over devices
    modules: dict[str, list[float]] = field(default_factory=dict)  # name → durations (s)
    op_seconds: dict[str, float] = field(default_factory=dict)     # short op name → total s (mean over devices)
    busy_intervals: list[tuple[int, int]] = field(default_factory=list)  # device 0, merged
    host_events: list[tuple[str, int, int]] = field(default_factory=list)  # (name, start, end)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    def kernel_seconds(self, patterns: list[str]) -> float:
        """Seconds in custom calls whose name contains any of ``patterns``."""
        return sum(s for op, s in self.op_seconds.items()
                   if op.endswith(" custom-call") and any(p in op for p in patterns))

    def top_ops(self, n: int = 10) -> list[list]:
        ranked = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]
        return [[op, s] for op, s in ranked]

    def idle_gaps(self, label, n: int = 10) -> list[list]:
        """The idle time between busy intervals on device 0, summed by
        ``label(start_ns, end_ns) -> str``; the n largest sums."""
        sums: dict[str, float] = defaultdict(float)
        edges = [self.window_ns[0], *[t for iv in self.busy_intervals for t in iv],
                 self.window_ns[1]]
        for start, end in zip(edges[0::2], edges[1::2]):
            if end > start:
                sums[label(start, end)] += (end - start) / 1e9
        return [[k, v] for k, v in sorted(sums.items(), key=lambda kv: -kv[1])[:n]]


_OP_HEAD = re.compile(r"^%?([\w.\-]+) = (\S+?)(?:\{[^ ]*)? ([\w\-]+)\(")
CONTAINER_OPS = ("while", "conditional", "call")  # their time is their children's
_CONTAINER = re.compile(r"^%?((while|conditional|call)[.\d]*) = ")


def short_op(name: str) -> tuple[str, str]:
    """(short name, kind) of an op event. On a TPU the event's name is the
    whole HLO instruction, ``%fusion.271 = bf16[4096,14336]{...} fusion(...)``:
    the short name keeps the instruction's name and result shape, the kind is
    its opcode (``fusion``, ``custom-call``, ``while`` ...). A Pallas kernel is
    a ``custom-call`` named after the kernel (``%ragged_flash_attention.15``)."""
    m = _OP_HEAD.match(name)
    if not m:
        c = _CONTAINER.match(name)  # a tuple-shaped result: only containers matter
        return (c.group(1), c.group(2)) if c else (name[:100], "")
    return f"{m.group(1)} {m.group(2)} {m.group(3)}"[:100], m.group(3)


def reduce_xplane(path: str | Path, *, host_event_prefix: str = "perfbench") -> DeviceTrace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = DeviceTrace()
    per_device_busy = []
    op_totals: dict[str, float] = defaultdict(float)
    lo, hi = None, None
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_event_prefix):
                        start = int(ev.start_ns)
                        out.host_events.append((ev.name, start, start + int(ev.duration_ns)))
            continue
        intervals = []
        for line in plane.lines:
            if line.name == MODULE_LINE:
                for ev in line.events:
                    name = _FINGERPRINT.sub("", ev.name)
                    out.modules.setdefault(name, []).append(ev.duration_ns / 1e9)
            elif line.name == OPS_LINE:
                for ev in line.events:
                    start, dur = int(ev.start_ns), int(ev.duration_ns)
                    intervals.append((start, start + dur))
                    op, kind = short_op(ev.name)
                    if kind in CONTAINER_OPS:
                        continue  # busy time, but not an operation of its own
                    op_totals[op] += dur / 1e9
        if not intervals:
            continue
        busy, merged = union_seconds(intervals)
        per_device_busy.append(busy)
        if not out.busy_intervals:
            out.busy_intervals = merged
        lo = merged[0][0] if lo is None else min(lo, merged[0][0])
        hi = merged[-1][1] if hi is None else max(hi, merged[-1][1])
    out.n_devices = len(per_device_busy)
    if per_device_busy:
        out.busy_s = sum(per_device_busy) / len(per_device_busy)
        out.window_ns = (lo, hi)
        out.op_seconds = {op: s / len(per_device_busy) for op, s in op_totals.items()}
    return out


def describe(path: str | Path, n: int = 40) -> str:
    """A text summary of a trace's planes, lines and commonest events with
    their stats — for looking at one trace by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    rows = []
    for plane in data.planes:
        rows.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            rows.append(f"  line {line.name!r}: {len(events)} events")
            totals: dict[str, list] = {}
            for ev in events:
                t = totals.setdefault(ev.name, [0, 0.0, ev])
                t[0] += 1
                t[1] += ev.duration_ns
            for name, (count, dur, ev) in sorted(totals.items(), key=lambda kv: -kv[1][1])[:n]:
                stats = {k: (str(v)[:100]) for k, v in ev.stats}
                rows.append(f"    {name[:90]!r} x{count} {dur / 1e6:.3f} ms "
                            f"start_ns={int(ev.start_ns)} stats={stats}")
    return "\n".join(rows)


if __name__ == "__main__":  # python3 perfbench/trace_reduce.py <file.xplane.pb>
    import sys

    print(describe(sys.argv[1]))
