"""The trace reduction on a small trace recorded on the chip: PR 23's first
traced run of ``mistral7b-advisor-paced`` on one TPU v5e, cut to the device
plane's ``XLA Modules`` and ``XLA Ops`` lines between 100 ms and 2,900 ms of
the capture (two ragged mixed steps) and the host plane's sync annotation."""

from pathlib import Path

import pytest

from perfbench import trace_reduce

FIXTURE = Path(__file__).with_name("mixed_step_v5e.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.reduce_xplane(FIXTURE)


def test_busy_is_the_union_of_op_intervals(trace):
    assert trace.n_devices == 1
    assert trace.window_ns == (101272291, 2897241206)
    assert trace.window_s == pytest.approx(2.795968915)
    # the while over the layers spans its body's ops: the union counts the
    # time once, the per-op sums leave the container out
    assert trace.busy_s == pytest.approx(0.678551902, rel=1e-9)
    assert sum(trace.op_seconds.values()) == pytest.approx(0.678511237, rel=1e-6)
    assert trace.busy_s >= sum(trace.op_seconds.values())
    assert not any(op.startswith("while") for op in trace.op_seconds)


def test_module_durations_group_by_name_without_fingerprint(trace):
    steps = trace.modules["jit_ragged_mixed_step"]
    assert len(steps) == 2 and sum(steps) == pytest.approx(0.67847, abs=1e-5)
    assert sorted(round(s, 3) for s in steps) == [0.256, 0.422]
    assert len(trace.modules["jit_convert_element_type"]) == 109
    assert not any("(" in name for name in trace.modules)


def test_kernel_seconds_by_custom_call_name(trace):
    attn = trace.kernel_seconds(["attention"])
    assert attn == pytest.approx(0.395853952, rel=1e-6)
    assert 0.55 < attn / trace.busy_s < 0.62
    assert trace.kernel_seconds(["no_such_kernel"]) == 0.0
    top = trace.top_ops(3)
    assert top[0][0] == "ragged_flash_attention.15 bf16[32,4320,128] custom-call"
    assert top[0][1] == pytest.approx(0.231149176, rel=1e-6)
    assert [op.split()[-1] for op, _ in top] == ["custom-call", "custom-call", "fusion"]


def test_gaps_are_attributed_by_the_callers_label(trace):
    gaps = trace.idle_gaps(lambda a, b: "long" if b - a >= 1_000_000 else "short")
    assert dict(map(tuple, gaps))["long"] == pytest.approx(2.117039361, rel=1e-6)
    total = sum(s for _, s in gaps)
    assert total == pytest.approx(trace.window_s - trace.busy_s, rel=1e-6)
    seen = []
    trace.idle_gaps(lambda a, b: seen.append((a, b)) or "x")
    assert all(b > a for a, b in seen) and seen == sorted(seen)


def test_host_sync_annotation_is_found(trace):
    assert trace.host_events == [("perfbench_sync", 41176969, 41180889)]


def test_union_seconds_merges_overlaps():
    total, merged = trace_reduce.union_seconds([(0, 10), (5, 20), (30, 40), (40, 45)])
    assert merged == [(0, 20), (30, 45)] and total == pytest.approx(35e-9)


@pytest.mark.parametrize("name,want", [
    ("%fusion.271 = bf16[4096,14336]{1,0:T(8,128)(2,1)} fusion(bf16[16,4096,14336]{2,1,0} %x)",
     ("fusion.271 bf16[4096,14336] fusion", "fusion")),
    ("%ragged_flash_attention.15 = bf16[32,4320,128]{2,1,0:T(8,128)(2,1)S(1)} custom-call(s32[1]{0} %d)",
     ("ragged_flash_attention.15 bf16[32,4320,128] custom-call", "custom-call")),
    ("%while.16 = (s32[]{:T(128)}, bf16[1,4096,4096]{1,2,0}) while((s32[]) %tuple.506)",
     ("while.16", "while")),
    ("dot_general.1", ("dot_general.1", "")),
])
def test_short_op_names(name, want):
    assert trace_reduce.short_op(name) == want
