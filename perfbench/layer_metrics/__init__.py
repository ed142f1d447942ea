"""Per-layer metrics: one small data file per metric, one reader per source.

``perfbench/layer_metrics/<metric>.json`` names a reader module under
``readers/`` and its parameters. A reader takes the run's ``Context`` and its
parameters and returns a number, or ``None`` where it finds nothing to read —
the harness then leaves the metric out of the line. A later PR adds a metric
by adding its file (and, for a new source, a reader module) and an entry in
BENCHMARK.json; nothing here is edited.
"""

from __future__ import annotations

import importlib
import json
import math
from dataclasses import dataclass, field
from pathlib import Path

HERE = Path(__file__).resolve().parent


@dataclass
class Context:
    """What a traced run hands its readers. Times are ``perf_counter``
    seconds; ``w0``/``w1`` are the measured window's ends."""
    w0: float
    w1: float
    requests: list                 # load.RequestLog, every request of the run
    tracer_events: list            # TRACER tuples (ts, trace_id, name, dur, track, args) in the window
    prom_before: dict              # family → value summed over label sets, at w0
    prom_after: dict               # the same at w1
    device_trace: object | None    # trace_reduce.DeviceTrace of the mid-window capture
    device: dict                   # the line's ``device`` object
    model: dict                    # the configuration file
    extra: dict = field(default_factory=dict)

    def delta(self, family: str) -> float:
        return self.prom_after.get(family, 0.0) - self.prom_before.get(family, 0.0)


def read_metric(name: str, ctx: Context) -> float | None:
    with open(HERE / f"{name}.json") as f:
        spec = json.load(f)
    reader = importlib.import_module(f"perfbench.layer_metrics.readers.{spec['reader']}")
    value = reader.read(ctx, **spec.get("params", {}))
    if value is None or not math.isfinite(value):
        return None
    return float(value)
