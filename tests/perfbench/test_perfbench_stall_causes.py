"""The readers ISSUE 38 added, on hand-made events and a fake registry:
``stall_causes`` hands a window's stalled time (``tracer_round``'s
``stall_ms``, by its own rule) to what it was lost to, and ``compile_seconds``
reads the program's own count of what compiling cost, by stage."""

import json
from pathlib import Path

import pytest

from perfbench.layer_metrics import Context, read_metric
from perfbench.layer_metrics.readers import compile_seconds, stall_causes, tracer_round

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = {  # metric: (reader, quantity, layer, moves, source, unit)
    "stall_frozen_ms.sat": ("stall_causes", "frozen_ms", "scheduler", "output_tok_s", "program_span", "ms"),
    "stall_compile_ms.sat": ("stall_causes", "compile_ms", "scheduler", "output_tok_s", "program_span", "ms"),
    "stall_prompt_ms.sat": ("stall_causes", "prompt_ms", "scheduler", "output_tok_s", "program_span", "ms"),
    "startup_lower_s": ("compile_seconds", "startup_front", "engine steps", "setup_s", "program_counter", "s"),
    "startup_backend_s": ("compile_seconds", "startup_backend", "engine steps", "setup_s", "program_counter", "s"),
    "prewindow_compile_s": ("compile_seconds", "serving_before_window", "engine steps", "setup_s", "program_counter", "s"),
}
STEP = 0.01  # a round every 10 ms: the limit is 30 ms, a period's excess what is over it


def _context(events=(), prom_before=None, prom_after=None):
    return Context(w0=100.0, w1=151.0, requests=[], tracer_events=list(events),
                   prom_before=prom_before or {}, prom_after=prom_after or {}, device_trace=None,
                   device={"kind": "TPU v5 lite"}, model={}, extra={})


def _round(ts, dur=0.009, kind="decode"):
    return (ts, None, "round", dur, "engine", {"kind": kind, "n": 1})


def _freeze(ts, dur, owner="machine"):
    return (ts, None, "freeze", dur, "host", {"process_cpu_s": 0.0, "owner": owner})


def _compile(ts, dur, trace_s=0.0, lower_s=0.0):
    return (ts, None, "compile", dur, "compile",
            {"fun_name": "jit(f)", "cache": "miss", "stage": "serving",
             "trace_s": trace_s, "lower_s": lower_s})


def _window(stalled: dict, extra=()):
    """Rounds 10 ms apart from t = 100; ``stalled`` maps a round's index to
    (its period, its duration, its kind). The events go in unsorted, as a
    ring that several threads append to holds them."""
    events, t = [], 100.0
    for i in range(40):
        period, dur, kind = stalled.get(i, (STEP, 0.009, "decode"))
        events.append(_round(t, dur, kind))
        t += period
    return list(extra) + events


def _parts(events):
    ctx = _context(events)
    got = {q: stall_causes.read(ctx, quantity=q) for q in stall_causes.QUANTITIES}
    got["stall_ms"] = tracer_round.read(ctx, quantity="stall_ms")
    return got


# --- the contract ------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_is_declared_at_the_end_with_a_reader_that_exists(name):
    reader, quantity, layer, moves, source, unit = NEW[name]
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    assert declared[name] == {"name": name, "unit": unit, "better": "lower", "source": source,
                              "layer": layer, "moves": moves}  # no `workloads`: every cell
    assert name in [m["name"] for m in BENCH["per_layer"][-6:]]
    spec = json.loads((ROOT / "perfbench/layer_metrics" / f"{name}.json").read_text())
    assert spec == {"reader": reader, "params": {"quantity": quantity}}
    assert (ROOT / "perfbench/layer_metrics/readers" / f"{reader}.py").exists()


# --- stall_causes ------------------------------------------------------------

CASES = {
    # name: (stalled rounds, other events, expected frozen / compile / prompt ms)
    "no_stall": ({}, [_freeze(100.002, 0.004), _compile(100.1, 0.002)], (0.0, 0.0, 0.0)),
    # round 10 starts at 100.10 and the next comes 1.03 s later: 1,000 ms over the limit
    "freeze_alone": ({10: (1.03, 1.029, "decode")}, [_freeze(100.2, 0.6)], (600.0, 0.0, 0.0)),
    "a_freeze_longer_than_the_excess_takes_the_excess":
        ({10: (1.03, 1.029, "decode")}, [_freeze(100.1, 1.03)], (1000.0, 0.0, 0.0)),
    "compile_alone_with_the_python_before_it":
        ({10: (1.03, 1.029, "decode")}, [_compile(100.5, 0.2, trace_s=0.1, lower_s=0.15)],
         (0.0, 450.0, 0.0)),
    "prompt_alone": ({10: (1.03, 0.9, "ragged")}, [], (0.0, 0.0, 900.0)),
    "a_decode_round_is_no_prompt": ({10: (1.03, 1.029, "decode")}, [], (0.0, 0.0, 0.0)),
    "a_drain_round_is_no_prompt": ({10: (1.03, 1.029, "drain")}, [], (0.0, 0.0, 0.0)),
    # the freeze covers [100.2, 100.6), the compile [100.5, 100.9): 0.1 s of it is the
    # freeze's already; the ragged round covers [100.1, 101.0): 0.1 + 0.1 s are left
    "overlaps_count_once_in_order":
        ({10: (1.03, 0.9, "ragged")}, [_freeze(100.2, 0.4), _compile(100.6, 0.3, trace_s=0.1)],
         (400.0, 300.0, 200.0)),
    "causes_outside_the_stalled_period_name_nothing":
        ({10: (1.03, 1.029, "decode")}, [_freeze(100.0, 0.05), _compile(101.5, 0.3)],
         (0.0, 0.0, 0.0)),
    "two_stalled_periods_each_with_its_own":
        ({10: (0.53, 0.5, "ragged"), 20: (0.23, 0.2, "decode")},
         [_freeze(100.2, 0.1), _compile(100.8, 0.15)], (100.0, 150.0, 400.0)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_stalled_time_goes_to_the_first_cause_that_covers_it(case):
    stalled, extra, (frozen, compiled, prompt) = CASES[case]
    got = _parts(_window(stalled, extra))
    assert got["frozen_ms"] == pytest.approx(frozen, abs=1e-6)
    assert got["compile_ms"] == pytest.approx(compiled, abs=1e-6)
    assert got["prompt_ms"] == pytest.approx(prompt, abs=1e-6)
    # the parts and an unnamed remainder are tracer_round's stall_ms of the same events
    named = got["frozen_ms"] + got["compile_ms"] + got["prompt_ms"]
    assert 0.0 <= named <= got["stall_ms"] + 1e-6
    assert stall_causes.parts(_window(stalled, extra))["stall_ms"] == pytest.approx(got["stall_ms"])


def test_causes_that_cover_everything_leave_no_remainder():
    events = _window({10: (1.03, 1.029, "ragged")},
                     [_freeze(100.0, 0.5), _compile(100.4, 0.4), _freeze(100.7, 0.5)])
    got = _parts(events)
    assert got["frozen_ms"] + got["compile_ms"] + got["prompt_ms"] == pytest.approx(got["stall_ms"])
    assert got["stall_ms"] == pytest.approx(1000.0)


@pytest.mark.parametrize("events", [[], [_freeze(100.0, 1.0)], [_round(100.0), _round(100.01)]],
                         ids=["nothing", "no_rounds", "one_period"])
def test_none_where_stall_ms_has_nothing_to_read(events):
    assert _parts(events) == dict.fromkeys(("frozen_ms", "compile_ms", "prompt_ms", "stall_ms"))
    assert stall_causes.parts(events) is None


def test_a_program_without_the_new_events_reads_zero_beside_its_stall():
    """The parent emits ``round`` events alone: nothing froze or compiled as
    far as it can say, and the prompt part is read all the same."""
    got = _parts(_window({10: (1.03, 0.9, "mixed")}))
    assert (got["frozen_ms"], got["compile_ms"]) == (0.0, 0.0)
    assert got["prompt_ms"] == pytest.approx(900.0) and got["stall_ms"] == pytest.approx(1000.0)


def test_an_unknown_quantity_is_refused():
    with pytest.raises(ValueError):
        stall_causes.read(_context(), quantity="lost_ms")
    with pytest.raises(ValueError):
        compile_seconds.read(_context(), quantity="all_of_it")


# --- compile_seconds -----------------------------------------------------------

FAKE = {
    'finchat_compile_trace_seconds_total{stage="warmup"}': 120.0,
    'finchat_compile_trace_seconds_total{stage="heads"}': 2.0,
    'finchat_compile_trace_seconds_total{stage="embed"}': 0.5,
    'finchat_compile_trace_seconds_total{stage="idle"}': 7.0,
    'finchat_compile_trace_seconds_total{stage="serving"}': 1.25,
    'finchat_compile_seconds_total{cache="hit",stage="warmup"}': 10.0,
    'finchat_compile_seconds_total{cache="miss",stage="warmup"}': 1.0,
    'finchat_compile_seconds_total{cache="hit",stage="artifacts"}': 0.25,
    'finchat_compile_seconds_total{cache="off",stage="idle"}': 3.0,
    'finchat_compile_seconds_total{cache="miss",stage="serving"}': 4.0,
    'finchat_compiles_total{cache="hit",stage="warmup"}': 178.0,
    'finchat_startup_seconds{phase="warmup"}': 131.0,
}


@pytest.mark.parametrize("quantity, want", [
    ("startup_front", 122.5), ("startup_backend", 11.25),
    # at the window's open the families summed to 130.0 and 17.0 over every stage, of
    # which 129.5 and 14.25 were start-up's and the harness's own: 0.5 + 2.75 serving
    ("serving_before_window", 3.25),
])
def test_compile_seconds_reads_the_programs_counters_by_stage(monkeypatch, quantity, want):
    monkeypatch.setattr(compile_seconds, "_series", lambda: dict(FAKE))
    before = {"finchat_compile_trace_seconds_total": 130.0, "finchat_compile_seconds_total": 17.0}
    after = {"finchat_compile_trace_seconds_total": 130.75, "finchat_compile_seconds_total": 18.25}
    ctx = _context(prom_before=before, prom_after=after)
    assert compile_seconds.read(ctx, quantity=quantity) == pytest.approx(want)


@pytest.mark.parametrize("quantity", ["startup_front", "startup_backend", "serving_before_window"])
def test_a_program_that_books_no_such_counter_reads_nothing(monkeypatch, quantity):
    """The parent's window snapshots hold neither family: nothing is read,
    whatever else this process's registry holds (here: another test's)."""
    monkeypatch.setattr(compile_seconds, "_series", lambda: dict(FAKE))
    parents = _context(prom_before={"finchat_rounds_total": 5.0},
                       prom_after={"finchat_rounds_total": 9.0})
    assert compile_seconds.read(parents, quantity=quantity) is None
    name = next(n for n, spec in NEW.items() if spec[1] == quantity)
    assert read_metric(name, parents) is None  # the line leaves the metric out
