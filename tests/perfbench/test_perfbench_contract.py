"""BENCHMARK.json against the contract's static rules, and the data files the
harness finds by name."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert BENCH["command"][:2] == ["python3", "perfbench/run.py"]
    # the full check of 24 cells must fit the driver's budget
    n = 24
    assert (2 + 14 * n) * (BENCH["run_seconds"] + 60) + n * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"] + METRICS,
                         ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] and "\t" not in entry[key]


def test_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_entries(metric):
    assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    assert set(cells_of(metric)) <= set(CELLS)


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_moves_a_metric_its_cells_report(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    moved = E2E[metric["moves"]]
    assert set(cells_of(metric)) <= set(cells_of(moved)), (
        f"{metric['name']} moves {moved['name']}, which some of its cells do not report")
    spec = json.loads((ROOT / "perfbench/layer_metrics" / f"{metric['name']}.json").read_text())
    assert (ROOT / "perfbench/layer_metrics/readers" / f"{spec['reader']}.py").exists()


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_reports_setup_another_metric_and_a_layer_metric(cell):
    name = cell["name"]
    e2e = [m["name"] for m in BENCH["end_to_end"] if name in cells_of(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(name in cells_of(m) for m in BENCH["per_layer"])
    assert cell["chips"] in (1, 4)
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_keeps_published_widths(config):
    from perfbench.cells import load_cell

    assert config["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    data = json.loads((ROOT / config["file"]).read_text())
    assert set(config["reduced"]) == set(data["reduced"]) == {"num_hidden_layers"}
    assert (data["hidden_size"], data["intermediate_size"], data["num_attention_heads"],
            data["num_key_value_heads"], data["head_dim"]) == (4096, 14336, 32, 8, 128)
    assert data["assumed"] and data["memory"] and data["source"].startswith("https://")
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    for w in BENCH["workloads"]:
        if w["config"] == config["name"]:
            cell = load_cell(w["name"])
            assert cell.traffic["kind"] == "sessions" and cell.traffic["answer_cap"] > 0


def test_traffic_files_extend_key_by_key():
    from perfbench.cells import load_traffic

    base = load_traffic("report-backlog")
    cell = load_traffic("rehearsal-backlog")
    assert cell["arrival"] == base["arrival"] == {"process": "backlog"}
    assert cell["turns"] == base["turns"] and "extends" not in cell
    assert (cell["answer_cap"], base["answer_cap"]) == (8, 8192)
