"""The reader ISSUE 53 added, on hand-made events: ``stall_parts`` hands a
window's stalled time (``tracer_round``'s ``stall_ms``) out to the end — what
``stall_causes`` names, then the round in which an answer ended (``retire``
events) and the two phases in which the loop was not at work (a ``round``'s
``args.yield`` / ``args.fetch_wait``) — and sums the blocking copies of the
window's retirements."""

import json
from pathlib import Path

import pytest

from perfbench.layer_metrics import Context, read_metric
from perfbench.layer_metrics.readers import stall_causes, stall_parts, tracer_round

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = {  # metric: quantity, in the order the entries stand at the end of `per_layer`
    "stall_retire_ms.sat": "retire_ms",
    "stall_yield_ms.sat": "yield_ms",
    "stall_wait_ms.sat": "wait_ms",
    "retire_offload_ms.sat": "offload_ms",
}
PR_51S_LAST = "moe_kl_expert_roofline.sat"
STEP = 0.01  # a round every 10 ms: the limit is 30 ms, a period's excess what is over it
ALL = stall_parts.QUANTITIES + ("offload_ms",)


def _context(events=()):
    return Context(w0=100.0, w1=151.0, requests=[], tracer_events=list(events),
                   prom_before={}, prom_after={}, device_trace=None,
                   device={"kind": "TPU v5 lite"}, model={}, extra={})


def _round(ts, dur=0.009, kind="decode", **phases):
    return (ts, None, "round", dur, "engine", {"kind": kind, "n": 1, **phases})


def _freeze(ts, dur):
    return (ts, None, "freeze", dur, "host", {"process_cpu_s": 0.0, "owner": "machine"})


def _compile(ts, dur, trace_s=0.0, lower_s=0.0):
    return (ts, None, "compile", dur, "compile",
            {"fun_name": "jit(f)", "cache": "miss", "stage": "serving",
             "trace_s": trace_s, "lower_s": lower_s})


def _retire(ts, dur, offload_s=0.0):
    return (ts, "r", "retire", dur, "engine",
            {"reason": "eos", "n": 1, "decoding": 15, "context_tokens": 9000,
             "offload_s": offload_s, "offload_pages": 64 if offload_s else 0,
             "offload_bytes": (64 << 20) if offload_s else 0,
             "store_s": 0.0, "release_s": dur - offload_s, "finish_s": 0.0})


def _window(stalled: dict, extra=()):
    """Rounds 10 ms apart from t = 100; ``stalled`` maps a round's index to
    (its period, its duration, its kind, its phases). The events go in
    unsorted, as a ring that several threads append to holds them."""
    events, t = [], 100.0
    for i in range(40):
        period, dur, kind, phases = stalled.get(i, (STEP, 0.009, "decode", {}))
        events.append(_round(t, dur, kind, **phases))
        t += period
    return list(extra) + events


def _read(events):
    ctx = _context(events)
    got = {q: stall_parts.read(ctx, quantity=q) for q in ALL}
    got["stall_ms"] = tracer_round.read(ctx, quantity="stall_ms")
    return got


# --- the contract ------------------------------------------------------------

def test_the_four_entries_stand_in_order_right_behind_pr_51s():
    """Appended where the list ended at PR 52, in the order ISSUE 53 gives
    (looked up by name: a later PR appends behind them)."""
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(PR_51S_LAST)
    assert names[at + 1:at + 5] == list(NEW)
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("name", list(NEW))
def test_each_new_metric_is_declared_for_every_cell_with_a_reader_that_exists(name):
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    assert declared[name] == {"name": name, "unit": "ms", "better": "lower",
                              "source": "program_span", "layer": "scheduler",
                              "moves": "output_tok_s"}  # no `workloads`: every cell
    spec = json.loads((ROOT / "perfbench/layer_metrics" / f"{name}.json").read_text())
    assert spec == {"reader": "stall_parts", "params": {"quantity": NEW[name]}}
    assert (ROOT / "perfbench/layer_metrics/readers/stall_parts.py").exists()
    # the harness's own way to the number: 0.0 is printed, never left out
    events = _window({10: (1.03, 1.029, "decode", {})})
    assert read_metric(name, _context(events)) == 0.0
    assert read_metric(name, _context([])) is None


def test_the_accepted_reader_and_its_three_files_name_what_they_named():
    for name, quantity in (("stall_frozen_ms.sat", "frozen_ms"),
                           ("stall_compile_ms.sat", "compile_ms"),
                           ("stall_prompt_ms.sat", "prompt_ms")):
        spec = json.loads((ROOT / "perfbench/layer_metrics" / f"{name}.json").read_text())
        assert spec == {"reader": "stall_causes", "params": {"quantity": quantity}}
    assert stall_causes.QUANTITIES == ("frozen_ms", "compile_ms", "prompt_ms")
    assert stall_parts.QUANTITIES[:3] == stall_causes.QUANTITIES


# --- the split ---------------------------------------------------------------

# round 10 starts at 100.10; where its period is 1.03 s the excess is 1,000 ms
LONG = (1.03, 1.029, "decode")
CASES = {
    # name: (stalled rounds, other events,
    #        expected frozen / compile / prompt / retire / yield / wait / offload ms)
    "no_stall": ({}, [_retire(100.002, 0.004, 0.003)], (0, 0, 0, 0, 0, 0, 3.0)),
    "retire_alone": ({10: (*LONG, {})}, [_retire(100.2, 0.7, 0.4)],
                     (0, 0, 0, 700.0, 0, 0, 400.0)),
    "a_retirement_longer_than_the_excess_takes_the_excess":
        ({10: (*LONG, {})}, [_retire(100.1, 1.02)], (0, 0, 0, 1000.0, 0, 0, 0.0)),
    "two_rows_retired_in_one_round":
        ({10: (*LONG, {})}, [_retire(100.2, 0.3, 0.1), _retire(100.5, 0.2, 0.15)],
         (0, 0, 0, 500.0, 0, 0, 250.0)),
    # the freeze covers [100.2, 100.6): the retirement's [100.3, 100.8) adds 0.2 s
    "a_retirement_under_a_freeze_is_counted_once":
        ({10: (*LONG, {})}, [_freeze(100.2, 0.4), _retire(100.3, 0.5, 0.5)],
         (400.0, 0, 0, 200.0, 0, 0, 500.0)),
    # a ragged round spans [100.1, 101.0): the retirement inside it is the prompt's
    "a_retirement_inside_a_prompt_round_is_the_prompts":
        ({10: (1.03, 0.9, "ragged", {})}, [_retire(100.4, 0.3)],
         (0, 0, 900.0, 0.0, 0, 0, 0.0)),
    "yield_alone": ({10: (*LONG, {"yield": 0.6, "fetch_wait": 0.0})}, [],
                    (0, 0, 0, 0, 600.0, 0, 0.0)),
    "wait_alone": ({10: (*LONG, {"yield": 0.001, "fetch_wait": 0.75})}, [],
                   (0, 0, 0, 0, 1.0, 750.0, 0.0)),
    "a_phase_longer_than_what_is_left_takes_what_is_left":
        ({10: (*LONG, {"yield": 0.9, "fetch_wait": 0.5})}, [_retire(100.2, 0.3)],
         (0, 0, 0, 300.0, 700.0, 0.0, 0.0)),
    # frozen 0.1, compile (with its 0.05 s of Python) 0.15, retire 0.2, then the
    # phases' sums: yield 0.25 and of the wait's 0.4 the 0.3 still unnamed
    "all_six_in_order":
        ({10: (*LONG, {"yield": 0.25, "fetch_wait": 0.4})},
         [_freeze(100.15, 0.1), _compile(100.35, 0.1, trace_s=0.05), _retire(100.5, 0.2, 0.12)],
         (100.0, 150.0, 0, 200.0, 250.0, 300.0, 120.0)),
    "causes_outside_the_stalled_period_name_nothing":
        ({10: (*LONG, {})}, [_retire(100.05, 0.04, 0.01), _retire(101.2, 0.3, 0.02)],
         (0, 0, 0, 0, 0, 0, 30.0)),
    "two_stalled_periods_each_with_its_own":
        ({10: (0.53, 0.5, "decode", {"yield": 0.05}), 20: (0.23, 0.2, "decode", {"fetch_wait": 0.19})},
         [_retire(100.2, 0.4, 0.3)], (0, 0, 0, 400.0, 50.0, 190.0, 300.0)),
    # a program that has no ``retire`` phase or event (the parent): its rounds
    # carry ``yield`` / ``fetch_wait`` all the same
    "a_program_without_retire_events_reads_zero":
        ({10: (*LONG, {"yield": 0.3, "fetch_wait": 0.2})}, [], (0, 0, 0, 0.0, 300.0, 200.0, 0.0)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_stalled_time_is_handed_out_to_the_end(case):
    stalled, extra, want = CASES[case]
    events = _window(stalled, extra)
    got = _read(events)
    for quantity, value in zip(ALL, want):
        assert got[quantity] == pytest.approx(value, abs=1e-6), (quantity, got)
    # the six parts and a remainder are tracer_round's stall_ms of the same events
    named = sum(got[q] for q in stall_parts.QUANTITIES)
    assert 0.0 <= named <= got["stall_ms"] + 1e-6
    assert stall_parts.parts(events)["stall_ms"] == pytest.approx(got["stall_ms"])
    # and the first three are the accepted reader's, whatever else the list holds
    accepted = stall_causes.parts(events)
    for quantity in stall_causes.QUANTITIES:
        assert got[quantity] == pytest.approx(accepted[quantity], abs=1e-9)
    # a window has rounds: every part is a number
    assert all(isinstance(got[q], float) for q in ALL)


def test_parts_that_cover_everything_leave_no_remainder():
    events = _window({10: (1.03, 1.0, "decode", {"yield": 0.2, "fetch_wait": 0.3})},
                     [_freeze(100.1, 0.2), _retire(100.25, 0.4, 0.4)])
    got = _read(events)
    assert sum(got[q] for q in stall_parts.QUANTITIES) == pytest.approx(got["stall_ms"])
    assert got["stall_ms"] == pytest.approx(1000.0)
    assert (got["frozen_ms"], got["retire_ms"]) == pytest.approx((200.0, 350.0))
    assert (got["yield_ms"], got["wait_ms"]) == pytest.approx((200.0, 250.0))


@pytest.mark.parametrize("events", [[], [_retire(100.0, 1.0, 0.5)], [_round(100.0), _round(100.01)]],
                         ids=["nothing", "no_rounds", "one_period"])
def test_none_where_stall_ms_has_nothing_to_read(events):
    assert _read(events) == dict.fromkeys(ALL + ("stall_ms",))
    assert stall_parts.parts(events) is None


def test_an_unknown_quantity_is_refused():
    with pytest.raises(ValueError):
        stall_parts.read(_context(), quantity="deliver_ms")
