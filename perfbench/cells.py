"""Looking a cell up by name: BENCHMARK.json and the data files it points to.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: a later PR adds any of them with new files and BENCHMARK.json entries.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load(ROOT / "BENCHMARK.json")


def load_traffic(name: str) -> dict:
    """``perfbench/traffic/<name>.json``; a file may ``extend`` another
    (the mix) and override keys of it (a cell's own rate and limits).
    Nested objects merge key by key."""
    params = _load(HERE / "traffic" / f"{name}.json")
    base = params.pop("extends", None)
    if base is None:
        return params
    merged = load_traffic(base)
    for key, value in params.items():
        if isinstance(value, dict) and isinstance(merged.get(key), dict):
            merged[key] = {**merged[key], **value}
        else:
            merged[key] = value
    return merged


@dataclass
class Cell:
    name: str
    config_name: str
    config: dict          # the configuration file, as run
    traffic_name: str
    traffic: dict
    chips: int
    rehearsal: bool       # a CPU rehearsal cell: never a measurement
    end_to_end: list[dict]
    per_layer: list[dict]


def _metrics_of(bench: dict, kind: str, cell: str) -> list[dict]:
    return [m for m in bench[kind] if "workloads" not in m or cell in m["workloads"]]


def load_cell(name: str) -> Cell:
    bench = benchmark()
    rehearsal = False
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    metrics_cell = name
    if entry is None:
        # CPU rehearsal cells (perfbench/rehearsal.json): the whole command
        # at a tiny size, every metric marked not-a-measurement
        reh = _load(HERE / "rehearsal.json")
        entry = next((w for w in reh["workloads"] if w["name"] == name), None)
        if entry is None:
            known = [w["name"] for w in bench["workloads"] + reh["workloads"]]
            raise SystemExit(f"perfbench: unknown workload {name!r}; known: {known}")
        rehearsal = True
        files = {c["name"]: c["file"] for c in reh["configs"]}
        metrics_cell = entry["metrics_of"]  # reports what this real cell reports
    return Cell(
        name=name, config_name=entry["config"],
        config=_load(ROOT / files[entry["config"]]),
        traffic_name=entry["traffic"], traffic=load_traffic(entry["traffic"]),
        chips=int(entry["chips"]), rehearsal=rehearsal,
        end_to_end=_metrics_of(bench, "end_to_end", metrics_cell),
        per_layer=_metrics_of(bench, "per_layer", metrics_cell),
    )
