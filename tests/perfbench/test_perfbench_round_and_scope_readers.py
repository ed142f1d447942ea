"""The readers PR 24 added: rounds and start-up on hand-made events, device
time by scope and the program's annotations on a capture recorded on a v5e
(``decode_scoped_v5e.xplane.pb``: 0.1 s of the saturated Mixtral cell's decode,
the device plane's modules and operations with their metadata's ``tf_op``,
and the host plane's ``finchat.*``, ``perfbench_live`` and ``perfbench_sync``
events; re-recorded in PR 30 from a traced run of that tree with
``slice_capture.py``, beside this file, so that it holds the harness's count
of the KV on distinct physical pages beside the program's dispatch notes)."""

import importlib.util
import json
from pathlib import Path

import pytest

from perfbench import kernel_costs, trace_reduce, xplane_scopes
from perfbench.live_kv import LIVE_ANNOTATION
from perfbench.layer_metrics import Context, read_metric
from perfbench.layer_metrics.readers import scope_trace, startup_gauge, tracer_round

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CAPTURE = HERE / "decode_scoped_v5e.xplane.pb"
BARE = HERE / "mixed_step_v5e.xplane.pb"  # PR 23's: bare events, no stats
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MIXTRAL = json.loads((ROOT / "perfbench/configs/mixtral-8x7b-v0.1.json").read_text())
NEW = ["moe_share.sat", "attn_kv_roofline.sat", "sched_host_ms.sat", "stall_ms.sat",
       "idle_off_sched_ms.sat", "startup_weights_s", "startup_warmup_s"]
SCOPES = ["embed", "norm", "attn_qkv", "attn_o", "mlp", "moe_router", "moe_experts",
          "head", "sample", "kv_append", "kv_scatter", "paged_attention",
          "kv_scatter_ragged", "ragged_paged_attention"]


@pytest.fixture(autouse=True)
def _the_runs_capture(monkeypatch):
    """The readers open the run's own capture; here that is the recorded one."""
    monkeypatch.setattr(scope_trace.trace_reduce, "find_xplane", lambda _dir: CAPTURE)


def _context(events=(), capture=CAPTURE):
    return Context(
        w0=100.0, w1=151.0, requests=[], tracer_events=list(events), prom_before={},
        prom_after={}, device_trace=trace_reduce.reduce_xplane(capture) if capture else None,
        device={"kind": "TPU v5 lite"}, model=MIXTRAL, extra={})


def _round(ts, dur, phases=None):
    args = {p: 0.0 for p in ("admit", "stage", "dispatch", "fetch_wait", "deliver", "yield")}
    args.update(phases or {}, kind="decode", n=1)
    return (ts, None, "round", dur, "engine", args)


# --- the contract ------------------------------------------------------------

def test_new_metrics_are_declared_with_a_reader_file_each():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        # read in every cell that reports what they move (PR 26), but the one
        # whose scopes a dense model never opens
        assert declared[name].get("workloads") == (
            ["mixtral-report-saturated"] if name == "moe_share.sat" else None)
        assert (ROOT / f"perfbench/layer_metrics/{name}.json").exists()
    assert declared["startup_warmup_s"]["moves"] == "setup_s"


# --- rounds -----------------------------------------------------------------

def test_host_ms_is_the_round_less_its_waits():
    events = [_round(100.0 + 0.016 * i, 0.016 + 0.001 * (i % 3),
                     {"stage": 0.0012, "dispatch": 0.0005, "deliver": 0.0003,
                      "fetch_wait": 0.0130, "yield": 0.001 * (1 + i % 3)})
              for i in range(9)]
    # dur - fetch_wait - yield = 2.0 ms in every round, whatever it yielded
    assert tracer_round.read(_context(events, None), quantity="host_ms") == pytest.approx(2.0)
    assert read_metric("sched_host_ms.sat", _context(events, None)) == pytest.approx(2.0)


def test_stall_ms_reads_nothing_on_even_periods_and_the_pause_on_one():
    even = [_round(100.0 + 0.016 * i, 0.016) for i in range(200)]
    assert tracer_round.read(_context(even, None), quantity="stall_ms") == pytest.approx(0.0)
    # the same rounds, all after the 100th 0.7 s late: one period of 0.716 s
    paused = [_round(ts + (0.7 if i >= 100 else 0.0), dur)
              for i, (ts, _t, _n, dur, _tr, _a) in enumerate(even)]
    got = tracer_round.read(_context(paused, None), quantity="stall_ms")
    assert got == pytest.approx(1e3 * (0.716 - 3 * 0.016))
    assert read_metric("stall_ms.sat", _context(paused, None)) == pytest.approx(got)
    # jitter under three medians is no stall
    jitter = [_round(100.0 + 0.016 * i + (0.010 if i % 7 == 0 else 0.0), 0.016)
              for i in range(200)]
    assert tracer_round.read(_context(jitter, None), quantity="stall_ms") == pytest.approx(0.0)


def test_round_readers_return_none_not_zero_without_round_events():
    other = [(100.5, None, "dispatch", None, "engine", {"kind": "decode", "rows": []})]
    for events in ([], other, [_round(100.0, 0.016)]):
        ctx = _context(events, None)
        assert tracer_round.read(ctx, quantity="stall_ms") is None
    assert tracer_round.read(_context(other, None), quantity="host_ms") is None
    with pytest.raises(ValueError):
        tracer_round.read(_context([], None), quantity="nope")


# --- start-up ----------------------------------------------------------------

def test_startup_gauge_reads_the_program_own_clock():
    from finchat_tpu.utils.metrics import METRICS

    assert startup_gauge.read(_context([], None), phase="no-such-phase") is None
    labels = {"phase": "artifacts"}
    before = METRICS.get("finchat_startup_seconds", labels=labels)
    try:
        METRICS.set_gauge("finchat_startup_seconds", 9.25, labels=labels)
        assert read_metric("startup_weights_s", _context([], None)) == pytest.approx(9.25)
    finally:
        METRICS.set_gauge("finchat_startup_seconds", before, labels=labels)


# --- the byte count ----------------------------------------------------------

def test_paged_attention_bytes_against_hand_arithmetic():
    # Mixtral: 8 KV heads x 128 x bf16 = 2 KiB each for K and for V a token
    assert kernel_costs.paged_attention_stream_bytes(MIXTRAL, kv_tokens=1) == 4096
    # PERF.md section 5: ~114k live tokens x 4 KiB a layer in 1.21 ms is 47 %
    nbytes = kernel_costs.paged_attention_stream_bytes(MIXTRAL, kv_tokens=114_000)
    assert nbytes == 114_000 * 2 * 8 * 128 * 2 == 466_944_000
    assert 100 * (nbytes / 819e9) / 1.21e-3 == pytest.approx(47.1, abs=0.1)


# --- the recorded capture ----------------------------------------------------

def test_scope_paths_come_from_the_metadata_stats():
    paths = xplane_scopes.op_scope_paths(str(CAPTURE))
    experts = [p for name, p in paths.items() if name.startswith("%fusion.216 ")]
    assert experts and all("/moe_experts/" in p for p in experts)
    kernel = [p for name, p in paths.items() if name.startswith("%paged_flash_attention")]
    assert kernel and all("/paged_attention/" in p for p in kernel)
    assert xplane_scopes.scope_of("jit(f)/while/body/moe_experts/dot_general:",
                                  {"moe_experts", "head"}) == "moe_experts"
    assert xplane_scopes.scope_of("jit(f)/while/body/dynamic_slice:", {"head"}) \
        == xplane_scopes.UNSCOPED
    assert xplane_scopes.scope_of(None, {"head"}) == xplane_scopes.UNSCOPED


def test_shares_by_scope_add_up_to_the_busy_time():
    ctx = _context()
    moe = read_metric("moe_share.sat", ctx)
    attn = read_metric("attn_share.sat", ctx)  # PR 23's reader, by kernel name
    # a scope this program never opens (mlp on a MoE model, the prefill
    # scatters in a decode window) reads None: nothing of the busy time
    by_scope = {s: scope_trace.read(ctx, quantity="scope_share", scopes=[s]) or 0.0
                for s in SCOPES}
    assert by_scope["mlp"] == by_scope["kv_scatter_ragged"] == 0.0
    table = xplane_scopes.seconds_by_scope(CAPTURE, set(SCOPES))
    unscoped = 100 * table.get(xplane_scopes.UNSCOPED, 0.0) / ctx.device_trace.busy_s
    assert moe == pytest.approx(by_scope["moe_router"] + by_scope["moe_experts"])
    assert 60 < moe < 80 and 15 < attn < 30 and moe + attn <= 100
    # the kernel is all but the whole of its scope
    assert by_scope["paged_attention"] == pytest.approx(attn, abs=0.2)
    rest = sum(v for s, v in by_scope.items()
               if s not in ("moe_router", "moe_experts", "paged_attention"))
    assert moe + by_scope["paged_attention"] + rest + unscoped == pytest.approx(100, abs=0.5)
    assert unscoped < 3.0  # the layer scan's own weight slices and copies


def test_kernel_roofline_reads_the_dispatch_notes_on_the_capture_clock():
    ctx = _context()
    threads = xplane_scopes.annotations(CAPTURE)
    noted = [stats for events in threads.values() for *_x, stats in events
             if "kv_tokens" in stats]
    assert noted and all(s["kind"] == "decode" and s["kv_tokens"] > 16 for s in noted)
    # the harness's own sample of the same rows, on the same clock: all 16
    # share the system prompt's pages, which the program's stat counts 16 times
    live = [stats for events in xplane_scopes.annotations(CAPTURE, LIVE_ANNOTATION).values()
            for *_x, stats in events]
    assert live and all(s["rows"] == 16 for s in live)
    kv = sum(s["kv_tokens"] for s in noted) / len(noted)
    for s in live:
        assert s["kv_tokens"] == pytest.approx(kv, rel=0.01)  # one count, two readers
        assert (s["kv_tokens"] - s["kv_tokens_distinct"]) % (15 * 128) == 0
    repeats = sum(s["kv_tokens"] - s["kv_tokens_distinct"] for s in live) / len(live)
    value = read_metric("attn_kv_roofline.sat", ctx)
    assert 20 < value < 60
    # by hand: mean tokens on distinct pages x 4 KiB at 819 GB/s over the mean kernel call
    calls = [dur for _d, name, kind, _s, dur in xplane_scopes.device_ops(CAPTURE)
             if kind == "custom-call" and "paged_flash_attention" in name]
    assert value == pytest.approx(
        100 * ((kv - repeats) * 4096 / 819e9) / (sum(calls) / len(calls) / 1e9), rel=1e-6)
    share = read_metric("kv_distinct_share.sat", ctx)
    assert share == pytest.approx(100 * (kv - repeats) / kv, rel=1e-9) and 40 < share < 60


def _notes(monkeypatch, dispatches, samples):
    """Hand-made annotations in the capture's place: the program's dispatch
    notes and the harness's samples."""
    table = {"finchat.": {"t": [("finchat.stage", i, i + 1, dict(d))
                                for i, d in enumerate(dispatches)]},
             LIVE_ANNOTATION: {"t": [(LIVE_ANNOTATION, i, i + 1, dict(d))
                                     for i, d in enumerate(samples)]} if samples else {}}
    monkeypatch.setattr(scope_trace.xplane_scopes, "annotations",
                        lambda _path, prefix="finchat.": table[prefix])


def test_distinct_tokens_and_share_against_hand_arithmetic(monkeypatch):
    _notes(monkeypatch,
           [{"kind": "decode", "rows": 16, "kv_tokens": 120_000},
            {"kind": "decode", "rows": 16, "kv_tokens": 120_016},
            {"kind": "ragged", "rows": 3, "kv_tokens": 9_999},      # another kind: not read
            {"kind": "decode", "rows": 16, "kv_tokens": 120_032}],
           [{"rows": 16, "kv_tokens": 119_990, "kv_tokens_distinct": 119_990 - 57_600},
            {"rows": 16, "kv_tokens": 120_040, "kv_tokens_distinct": 120_040 - 57_600}])
    assert scope_trace._decode_kv_tokens(CAPTURE, {"decode"}) == (120_016, 120_016 - 57_600)
    ctx = _context()
    assert read_metric("kv_distinct_share.sat", ctx) == pytest.approx(100 * 62_416 / 120_016)
    calls = [dur for _d, name, kind, _s, dur in xplane_scopes.device_ops(CAPTURE)
             if kind == "custom-call" and "paged_flash_attention" in name]
    assert read_metric("attn_kv_roofline.sat", ctx) == pytest.approx(
        100 * (62_416 * 4096 / 819e9) / (sum(calls) / len(calls) / 1e9), rel=1e-9)
    # no row shares a page: the whole of it is left to read, and 100 is not 0
    _notes(monkeypatch, [{"kind": "decode", "rows": 2, "kv_tokens": 500}],
           [{"rows": 2, "kv_tokens": 498, "kv_tokens_distinct": 498}])
    assert read_metric("kv_distinct_share.sat", ctx) == 100.0
    # no dispatch of the kind noted its context: nothing to read
    _notes(monkeypatch, [{"kind": "ragged", "rows": 3, "kv_tokens": 9_999}], [])
    assert read_metric("kv_distinct_share.sat", ctx) is None
    assert read_metric("attn_kv_roofline.sat", ctx) is None


def test_a_capture_with_the_programs_count_alone_fails_loudly(monkeypatch):
    """PR 24-29's captures: ``kv_tokens`` on the dispatches and no sample of
    the harness. There is no falling back to a count that reads a shared
    page once a row."""
    _notes(monkeypatch, [{"kind": "decode", "rows": 16, "kv_tokens": 120_000}], [])
    for metric in ("attn_kv_roofline.sat", "kv_distinct_share.sat"):
        with pytest.raises(ValueError, match="no perfbench_live event notes kv_tokens_distinct"):
            read_metric(metric, _context())
    # a sample without the stat is no sample
    _notes(monkeypatch, [{"kind": "decode", "rows": 16, "kv_tokens": 120_000}],
           [{"rows": 16, "kv_tokens": 120_000}])
    with pytest.raises(ValueError, match="kv_tokens_distinct"):
        read_metric("attn_kv_roofline.sat", _context())


def test_idle_off_phases_counts_only_idle_the_scheduler_did_not_cover():
    ctx = _context()
    value = read_metric("idle_off_sched_ms.sat", ctx)
    idle_ms = 1e3 * (ctx.device_trace.window_s - ctx.device_trace.busy_s)
    assert 0.0 <= value <= idle_ms + 1e-9
    # with every phase counted as "off" nothing is covered: all idle time counts
    none_on = scope_trace.read(ctx, quantity="idle_off_phases", on=["admit"],
                               off=["admit", "stage", "dispatch", "deliver",
                                    "fetch_wait", "yield"])
    assert none_on == pytest.approx(idle_ms, abs=1e-6)


def test_one_decode_module_between_consecutive_dispatch_annotations():
    from jax.profiler import ProfileData

    starts = []
    for plane in ProfileData.from_file(str(CAPTURE)).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace_reduce.MODULE_LINE:
                    starts += [int(ev.start_ns) for ev in line.events
                               if ev.name.startswith("jit_decode_step")]
    dispatches = sorted(start for events in xplane_scopes.annotations(CAPTURE).values()
                        for name, start, _end, _stats in events if name == "finchat.dispatch")
    assert len(dispatches) >= 4
    for a, b in zip(dispatches, dispatches[1:]):
        assert sum(1 for s in starts if a <= s < b) == 1


def test_scope_share_is_none_not_zero_for_a_program_without_the_scope():
    # the parent of PR 24 on the chip: every operation has a scope path
    # (jit(decode_step)/while/body/...), none of them under these names
    assert scope_trace.read(_context(), quantity="scope_share",
                            scopes=["no_such_scope"]) is None
    assert scope_trace.read(_context(), quantity="kernel_stream_roofline",
                            scopes=["no_such_scope"], patterns=["paged_flash_attention"],
                            kinds=["decode"]) is None


def test_scope_readers_return_none_where_the_capture_has_nothing(monkeypatch):
    # no dispatch of that kind noted its context in the capture
    assert scope_trace.read(_context(), quantity="kernel_stream_roofline",
                            scopes=["paged_attention"], patterns=["paged_flash_attention"],
                            kinds=["no_such_kind"]) is None
    # no annotation of the program at all (PR 23's capture has perfbench_sync alone)
    monkeypatch.setattr(scope_trace.trace_reduce, "find_xplane", lambda _dir: BARE)
    bare = _context(capture=BARE)
    assert scope_trace.read(bare, quantity="idle_off_phases", on=["stage"],
                            off=["yield"]) is None
    assert read_metric("idle_off_sched_ms.sat", bare) is None
    monkeypatch.setattr(scope_trace.trace_reduce, "find_xplane", lambda _dir: None)
    assert scope_trace.read(_context(), quantity="scope_share", scopes=["head"]) is None
    assert scope_trace.read(_context(capture=None), quantity="scope_share",
                            scopes=["head"]) is None


def test_a_capture_without_the_scope_stat_fails_loudly(monkeypatch):
    """XLA gives every operation a path, so a device plane that names
    operations and no ``tf_op`` stat is a cut file or a changed layout: the
    scope metrics raise there instead of reading None."""
    with pytest.raises(ValueError, match="no tf_op stat"):
        xplane_scopes.op_scope_paths(str(BARE))
    monkeypatch.setattr(scope_trace.trace_reduce, "find_xplane", lambda _dir: BARE)
    with pytest.raises(ValueError, match="no tf_op stat"):
        read_metric("moe_share.sat", _context(capture=BARE))


def test_wire_reader_agrees_with_the_generated_schema():
    """The field numbers ``op_scope_paths`` reads by hand, held to tsl's
    generated ``xplane_pb2`` — loaded from its file: importing it through
    ``tensorflow`` takes 12 s, which is why the reader does not."""
    if importlib.util.find_spec("tensorflow") is None:
        pytest.skip("no generated xplane_pb2 in this installation")
    import slice_capture  # beside this file: it cut the capture with the same schema

    xplane_pb2 = slice_capture.xplane_pb2()
    space = xplane_pb2.XSpace()
    space.ParseFromString(CAPTURE.read_bytes())
    expected = {}
    for plane in space.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for meta in plane.event_metadata.values():
            for stat in meta.stats:
                if plane.stat_metadata[stat.metadata_id].name == "tf_op":
                    expected[meta.name] = (stat.str_value or
                                           plane.stat_metadata[stat.ref_value].name)
    assert len(expected) > 50
    assert xplane_scopes.op_scope_paths(str(CAPTURE)) == expected


def test_subtract_intervals():
    assert xplane_scopes.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]) == \
        [(0, 2), (4, 8), (22, 29)]
    assert xplane_scopes.subtract([(0, 10)], []) == [(0, 10)]
    assert xplane_scopes.subtract([(0, 10)], [(0, 10)]) == []
