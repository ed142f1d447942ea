"""Per-layer readers on hand-made spans, counters and the recorded trace."""

import json
from pathlib import Path

import pytest

from perfbench import trace_reduce
from perfbench.layer_metrics import Context, read_metric
from perfbench.load import RequestLog

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MIXTRAL = json.loads((ROOT / "perfbench/configs/mixtral-8x7b-v0.1.json").read_text())


def _context(**over):
    req = RequestLog("m1", "s1", due=100.0, sent=100.004)
    late = RequestLog("m2", "s2", due=90.0, sent=90.5)  # due before the window
    events = [
        (100.010, "m1", "ingress", None, "ingress", None),
        (100.020, "m1", "decide_start", None, "agent", None),
        (100.030, "m1", "request", 0.500, "request", {"request_id": "seq-1"}),  # the decision
        (100.400, "m1", "first_token", None, "request", None),
        (100.600, "m1", "request", 2.000, "request", {"request_id": "seq-2"}),  # the answer
        (101.100, "m1", "first_token", None, "request", None),
        (100.500, None, "dispatch", None, "sched", {"kind": "ragged", "rows": [[0, "m1", "decode"]] * 3}),
        (100.700, None, "dispatch", None, "sched", {"kind": "decode", "rows": [[0, "m1", "decode"]] * 5}),
        (100.800, None, "dispatch", None, "sched", {"kind": "decode", "rows": []}),
    ]
    base = dict(
        w0=100.0, w1=110.0, requests=[req, late], tracer_events=events,
        prom_before={"finchat_retrieval_embed_seconds_sum": 1.0,
                     "finchat_retrieval_search_seconds_sum": 1.0,
                     "finchat_retrieval_search_seconds_count": 10.0},
        prom_after={"finchat_retrieval_embed_seconds_sum": 1.3,
                    "finchat_retrieval_search_seconds_sum": 1.1,
                    "finchat_retrieval_search_seconds_count": 14.0,
                    "finchat_prefix_tokens_saved_total": 9000.0,
                    "finchat_request_seconds_count": 3.0},
        device_trace=trace_reduce.reduce_xplane(
            Path(__file__).with_name("mixed_step_v5e.xplane.pb")),
        device={"kind": "TPU v5 lite", "memory_peak_bytes": 12_778_729_472},
        model=MIXTRAL, extra={"mean_live_kv_tokens": 100_000.0})
    base.update(over)
    return Context(**base)


@pytest.mark.parametrize("name,want", [
    ("batch_rows.sat", 4.0), ("attn_share.sat", 58.34), ("hbm_peak_gb.sat", 12.778729472),
])
def test_reader_values(name, want):
    assert read_metric(name, _context()) == pytest.approx(want, rel=1e-3)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    empty = _context(requests=[], tracer_events=[], prom_before={}, prom_after={},
                     device_trace=None, device={"kind": "TPU v5 lite"}, extra={})
    for metric in BENCH["per_layer"]:
        assert read_metric(metric["name"], empty) is None, metric["name"]
    # a module that did not run in the capture (the recorded trace holds
    # ragged rounds only): no value, not zero
    for name in ("decode_step_ms.sat", "decode_stream_roofline"):
        assert read_metric(name, _context()) is None


def test_module_time_is_the_median_over_the_capture():
    ctx = _context()
    assert read_metric("decode_step_ms.sat", _context()) is None
    ctx.device_trace.modules["jit_decode_step"] = [0.019, 0.021, 0.020, 0.400]
    assert read_metric("decode_step_ms.sat", ctx) == pytest.approx(20.5)
    from perfbench.layer_metrics.readers import device_trace

    assert device_trace.read(ctx, quantity="module_ms",
                             module="jit_ragged_mixed_step") == pytest.approx(339.235, rel=1e-3)


def test_stream_roofline_is_bytes_over_peak_over_step_time():
    from perfbench import costs

    ctx = _context()
    ctx.device_trace.modules["jit_decode_step"] = [0.040, 0.050, 0.060]
    nbytes = costs.decode_step_stream_bytes(MIXTRAL, live_kv_tokens=100_000.0)
    # 3 layers of 8 experts + the head, bf16, and 12 KiB of KV a token
    assert nbytes == pytest.approx((3 * 1_451_270_144 + 131_072_000) * 2 + 100_000 * 12288)
    from perfbench.layer_metrics.readers import device_trace

    want = 100.0 * (nbytes / 819e9) / 0.050
    read = dict(quantity="stream_roofline", module="jit_decode_step")
    assert device_trace.read(ctx, **read) == pytest.approx(want)
    assert read_metric("decode_stream_roofline", ctx) == pytest.approx(want)
    assert 0 < want < 100
    ctx.device["kind"] = "TPU v99"
    with pytest.raises(KeyError):
        device_trace.read(ctx, **read)
