"""Cut a run's capture down to the slice the reader tests keep beside them.

    python3 tests/perfbench/slice_capture.py <capture.xplane.pb> <out.xplane.pb> \
        [--seconds 0.1] [--max-bytes 121766]

``decode_scoped_v5e.xplane.pb`` is such a slice of a traced run of
``mixtral-report-saturated`` (``perfbench/run.py --trace 1`` on the chip; the
capture is ``.perfbench_work/trace/**/*.xplane.pb``): ``--seconds`` around the
capture's middle ``perfbench_live`` event, so that the slice holds the
harness's count beside the program's dispatch notes. Kept: the device planes'
``XLA Modules`` and ``XLA Ops`` lines with the metadata of the events inside
the slice (and its ``tf_op`` stat; not ``source_stack`` and
``memory_access_breakdown``, a third of the file that nothing reads); of the
host planes the ``finchat.*`` and ``perfbench_live`` events inside it and
``perfbench_sync`` wherever it lies. The slice begins and ends with a whole
program (``whole_modules``), so that shares read on it are the run's, and
narrows until the file fits ``--max-bytes``.

Needs tsl's generated ``xplane_pb2``, loaded from its file inside the
``tensorflow`` package without importing that (``xplane_scopes`` says why).
It touches no JAX, so it may run beside a process that holds the chip.
"""

from __future__ import annotations

import argparse
import importlib.util
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perfbench.live_kv import LIVE_ANNOTATION  # noqa: E402
from perfbench.trace_reduce import DEVICE_PLANE, MODULE_LINE, OPS_LINE  # noqa: E402

DEVICE_LINES = (MODULE_LINE, OPS_LINE)
HOST_PREFIXES = ("finchat.", LIVE_ANNOTATION)
ANYWHERE = "perfbench_sync"
DROPPED_STATS = ("source_stack", "memory_access_breakdown")


def xplane_pb2():
    spec = importlib.util.find_spec("tensorflow")
    if spec is None:
        raise SystemExit("slice_capture: no generated xplane_pb2 in this installation")
    source = Path(spec.origin).parent / "tsl/profiler/protobuf/xplane_pb2.py"
    module_spec = importlib.util.spec_from_file_location("_xplane_pb2", source)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


def _start_ps(line, event) -> int:
    return line.timestamp_ns * 1000 + event.offset_ps


def whole_modules(space, lo_ps: int, hi_ps: int) -> tuple[int, int, int]:
    """The slice's own ends: from the start of the first program that runs
    wholly inside ``[lo_ps, hi_ps)`` to the end of the last, and the start of
    that last one — host events are kept up to there, so that every dispatch
    kept has the program it dispatched ahead of itself in the slice."""
    runs = sorted((_start_ps(line, ev), _start_ps(line, ev) + ev.duration_ps)
                  for plane in space.planes if DEVICE_PLANE.match(plane.name)
                  for line in plane.lines if line.name == MODULE_LINE
                  for ev in line.events
                  if lo_ps <= _start_ps(line, ev) and _start_ps(line, ev) + ev.duration_ps < hi_ps)
    if not runs:
        raise SystemExit("slice_capture: no program runs wholly inside the slice")
    return runs[0][0], max(end for _start, end in runs), runs[-1][0]


def _stat_ids(stats) -> set[int]:
    """The stat metadata a list of stats needs: each one's name and, where
    its value is a reference, the string it refers to."""
    return ({s.metadata_id for s in stats}
            | {s.ref_value for s in stats if s.WhichOneof("value") == "ref_value"})


def cut(space, pb, lo_ps: int, hi_ps: int):
    lo_ps, hi_ps, host_hi_ps = whole_modules(space, lo_ps, hi_ps)
    out = pb.XSpace()
    for plane in space.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        kept_lines = []
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            events = []
            for ev in line.events:
                name = plane.event_metadata[ev.metadata_id].name
                start = _start_ps(line, ev)
                if device:
                    keep = lo_ps <= start and start + ev.duration_ps <= hi_ps
                else:
                    keep = (lo_ps <= start < host_hi_ps and name.startswith(HOST_PREFIXES)
                            or name == ANYWHERE)
                if keep:
                    events.append(ev)
            if events:
                kept_lines.append((line, events))
        if not kept_lines:
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        new.stats.extend(plane.stats)
        stat_ids = _stat_ids(plane.stats)
        for line, events in kept_lines:
            copy = new.lines.add(id=line.id, display_id=line.display_id, name=line.name,
                                 display_name=line.display_name,
                                 timestamp_ns=line.timestamp_ns, duration_ps=line.duration_ps)
            copy.events.extend(events)
            for ev in events:
                if ev.metadata_id not in new.event_metadata:
                    meta = new.event_metadata[ev.metadata_id]
                    meta.CopyFrom(plane.event_metadata[ev.metadata_id])
                    stats = [s for s in meta.stats
                             if plane.stat_metadata[s.metadata_id].name not in DROPPED_STATS]
                    del meta.stats[:]
                    meta.stats.extend(stats)
                stat_ids |= _stat_ids(ev.stats)
        for meta in new.event_metadata.values():
            stat_ids |= _stat_ids(meta.stats)
        for key in stat_ids:
            if key in plane.stat_metadata:
                new.stat_metadata[key].CopyFrom(plane.stat_metadata[key])
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("capture")
    ap.add_argument("out")
    ap.add_argument("--seconds", type=float, default=0.1)
    ap.add_argument("--max-bytes", type=int, default=121766)
    args = ap.parse_args()
    pb = xplane_pb2()
    space = pb.XSpace()
    space.ParseFromString(Path(args.capture).read_bytes())
    live = sorted(_start_ps(line, ev) for plane in space.planes
                  if not DEVICE_PLANE.match(plane.name) for line in plane.lines
                  for ev in line.events
                  if plane.event_metadata[ev.metadata_id].name == LIVE_ANNOTATION)
    if not live:
        raise SystemExit(f"slice_capture: {args.capture} holds no {LIVE_ANNOTATION} event")
    middle, seconds = live[len(live) // 2], args.seconds
    while True:
        half = int(seconds * 1e12 / 2)
        data = cut(space, pb, middle - half, middle + half).SerializeToString()
        if len(data) <= args.max_bytes:
            break
        seconds *= 0.95
    Path(args.out).write_bytes(data)
    print(f"slice_capture: {seconds:.4f} s around the {len(live) // 2}th of {len(live)} "
          f"{LIVE_ANNOTATION} events: {len(data)} bytes -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
