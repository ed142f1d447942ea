"""Device time by named scope, and the program's own annotations, from a
``jax.profiler`` capture (``*.xplane.pb``).

Where a v5e capture keeps an operation's scope path (looked at by hand,
PR 24): not in the event's own stats, which ``jax.profiler.ProfileData``
exposes (``device_offset_ps``, ``device_duration_ps``), and in no sibling
line (the device plane has ``XLA Modules``, ``XLA Ops``, ``Async XLA Ops``,
``TC Overlay``), but in the stats of the event's *metadata*: ``tf_op`` is the
``jax.named_scope`` path, ``jit(decode_step)/while/body/closed_call/
moe_experts/bsd,edf->bsef/dot_general:``. ``ProfileData`` does not expose
metadata stats, so ``op_scope_paths`` reads that one table from the file's
protobuf wire format directly; events, times and lines still come from
``ProfileData``. The message layout is tsl's ``xplane.proto``. The one
generated ``xplane_pb2`` of this installation lies inside ``tensorflow``,
whose import takes 12 s and 4,900 modules, in the process that holds the
chip: the field numbers here are held to it by a test instead, and a
capture whose layout has drifted raises (``op_scope_paths``).

Host annotations (``jax.profiler.TraceAnnotation``) are events of the host
planes on the same clock as the device lines; a ``TraceMe``'s metadata
(``set_metadata``) comes back as the event's stats.
"""

from __future__ import annotations

from collections import defaultdict
from functools import lru_cache
from pathlib import Path

from perfbench import trace_reduce

UNSCOPED = "(unscoped)"


# --- protobuf wire format ------------------------------------------------

def _varint(buf, pos: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are skipped."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
            yield number, value
        elif wire == 2:
            size, pos = _varint(buf, pos)
            yield number, buf[pos:pos + size]
            pos += size
        elif wire == 1:
            pos += 8
        elif wire == 5:
            pos += 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {pos}")


def _map_entry(buf) -> tuple[int, memoryview | None]:
    key, value = 0, None
    for number, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


@lru_cache(maxsize=4)
def op_scope_paths(path: str) -> dict[str, str]:
    """{operation event name: scope path} of the device planes: each
    ``XEventMetadata``'s name and its ``tf_op`` stat. A device plane that
    names operations but no ``tf_op`` stat at all is not a program without
    scopes (XLA gives every operation a path): the file is cut to bare
    events or its layout is no longer the one read here, and that raises."""
    space = memoryview(Path(path).read_bytes())
    out: dict[str, str] = {}
    for number, plane in _fields(space):
        if number != 1:  # XSpace.planes
            continue
        name, stat_names, metadata = "", {}, []
        for n, v in _fields(plane):
            if n == 2:
                name = bytes(v).decode()
            elif n == 4:  # event_metadata: map<int64, XEventMetadata>
                metadata.append(_map_entry(v)[1])
            elif n == 5:  # stat_metadata: map<int64, XStatMetadata>
                key, value = _map_entry(v)
                for sn, sv in _fields(value):
                    if sn == 2:
                        stat_names[key] = bytes(sv).decode()
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        if metadata and "tf_op" not in stat_names.values():
            raise ValueError(f"xplane: plane {name!r} of {path} names "
                             f"{len(metadata)} operations but no tf_op stat")
        for meta in metadata:
            op_name, scope = "", None
            for n, v in _fields(meta):
                if n == 2:
                    op_name = bytes(v).decode()
                elif n == 5:  # XStat
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    if 5 in stat:    # str_value
                        scope = bytes(stat[5]).decode()
                    elif 7 in stat:  # ref_value: the string is a stat name
                        scope = stat_names.get(stat[7])
            if op_name and scope:
                out[op_name] = scope
    return out


# --- device time by scope ------------------------------------------------

def scope_of(scope_path: str | None, scopes) -> str:
    """The first component of an operation's scope path that is one of
    ``scopes``; ``UNSCOPED`` where there is none."""
    for part in (scope_path or "").split("/"):
        if part in scopes:
            return part
    return UNSCOPED


@lru_cache(maxsize=2)
def _profile(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


@lru_cache(maxsize=2)
def _device_ops(path: str) -> tuple:
    ops, device = [], -1
    for plane in _profile(path).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        device += 1
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for ev in line.events:
                _short, kind = trace_reduce.short_op(ev.name)
                if kind not in trace_reduce.CONTAINER_OPS:
                    ops.append((device, ev.name, kind, int(ev.start_ns),
                                int(ev.duration_ns)))
    return tuple(ops)


def device_ops(path: str | Path) -> tuple:
    """(device index, event name, opcode, start ns, duration ns) of every
    executed operation that is not a container (``while``, ``conditional``,
    ``call``: their time is their children's), as ``trace_reduce`` counts."""
    return _device_ops(str(path))


def seconds_by_scope(path: str | Path, scopes) -> dict[str, float]:
    """Device seconds of operations by the scope they ran under (mean over
    devices); operations under none of ``scopes`` are ``UNSCOPED``."""
    paths = op_scope_paths(str(path))
    totals: dict[str, float] = defaultdict(float)
    devices = set()
    for device, name, _kind, _start, dur in device_ops(path):
        devices.add(device)
        totals[scope_of(paths.get(name), scopes)] += dur / 1e9
    return {k: v / len(devices) for k, v in totals.items()}


# --- the program's annotations -------------------------------------------

def annotations(path: str | Path, prefix: str = "finchat."):
    """{thread: [(name, start ns, end ns, stats)]} of the host planes' events
    whose name starts with ``prefix``, on the clock of the device lines."""
    threads: dict[str, list] = {}
    for plane in _profile(str(path)).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for i, line in enumerate(plane.lines):
            events = [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                       dict(ev.stats))
                      for ev in line.events if ev.name.startswith(prefix)]
            if events:
                threads[f"{plane.name}:{line.name}:{i}"] = events
    return threads


def subtract(intervals, holes):
    """Merged [start, end) ``intervals`` less merged ``holes``."""
    out, holes, h = [], list(holes), 0
    for start, end in intervals:
        while h < len(holes) and holes[h][1] <= start:
            h += 1
        k = h
        while start < end and k < len(holes) and holes[k][0] < end:
            if holes[k][0] > start:
                out.append((start, holes[k][0]))
            start = max(start, holes[k][1])
            k += 1
        if start < end:
            out.append((start, end))
    return out


if __name__ == "__main__":  # python3 -m perfbench.xplane_scopes <file.xplane.pb> [scope ...]
    import sys

    target, wanted = sys.argv[1], set(sys.argv[2:])
    if not wanted:  # every path component but the jitted function, the
        # control flow's own names and the operation at the end
        control = {"while", "body", "cond", "closed_call", "pallas_call"}
        for scope_path in op_scope_paths(target).values():
            wanted.update(part for part in scope_path.rstrip(":").split("/")[:-1]
                          if "(" not in part and part not in control
                          and not part.startswith("branch_"))
    busy = trace_reduce.reduce_xplane(target).busy_s
    table = seconds_by_scope(target, wanted)
    for scope, seconds in sorted(table.items(), key=lambda kv: -kv[1]):
        print(f"{scope:28s} {seconds:10.6f} s {100 * seconds / busy:7.3f} % of busy")
    print(f"{'(inside containers, no op)':28s} {busy - sum(table.values()):10.6f} s "
          f"{100 * (busy - sum(table.values())) / busy:7.3f} % of busy")
