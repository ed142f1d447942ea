"""``model_type`` "falcon_h1" (PR 27): its configuration file, the counts its
adapter brings, the two controls at a size a test run holds, and the readers
of its three metrics — and what two older tests pin for every committed
configuration and for the last metrics of the list (they fail since this cell
landed: PERF.md section 7), kept here without the part no addition can
satisfy."""

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from perfbench import correct, costs, trace_reduce, xplane_scopes
from perfbench.layer_metrics import Context, read_metric
from perfbench.layer_metrics.readers import scope_trace, ssm_scan_trace
from perfbench.models import adapter, falcon_h1

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
FILE = json.loads((ROOT / "perfbench/configs/falcon-h1-34b-instruct.json").read_text())
CELL = "falcon-h1-report-saturated"
CAPTURE = HERE / "decode_scoped_v5e.xplane.pb"  # Mixtral's decode: no mixer in it
OURS = ["ssm_share.sat", "ssm_state_roofline.sat", "ssm_state_gb.sat"]
PR_24 = ["moe_share.sat", "attn_kv_roofline.sat", "sched_host_ms.sat", "stall_ms.sat",
         "idle_off_sched_ms.sat", "startup_weights_s", "startup_warmup_s"]


# --- the configuration file ---------------------------------------------------

def test_the_file_holds_the_published_block_and_only_depth_is_cut():
    assert adapter(FILE) is falcon_h1
    published = {"hidden_size": 5120, "intermediate_size": 21504, "num_attention_heads": 20,
                 "num_key_value_heads": 4, "head_dim": 128, "vocab_size": 261120,
                 "mamba_d_ssm": 4096, "mamba_n_heads": 32, "mamba_d_head": 128,
                 "mamba_d_state": 256, "mamba_n_groups": 2, "mamba_d_conv": 4,
                 "mamba_chunk_size": 128, "rope_theta": 100000000000, "max_position_embeddings": 262144,
                 "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                                     0.3535533905932738]}
    assert {k: FILE[k] for k in published} == published
    assert list(FILE["reduced"]) == ["num_hidden_layers"]
    cut = FILE["reduced"]["num_hidden_layers"]
    assert cut["from"] == 72 and 4 <= cut["to"] == FILE["num_hidden_layers"] <= 6
    assert FILE["ssm_state_dtype"] == "float32" and FILE["dtype"] == "bfloat16"
    assert set(falcon_h1.WIDTH_KEYS) >= {"hidden_size", "intermediate_size", "head_dim",
                                         "mamba_d_ssm", "mamba_d_state", "mamba_d_head",
                                         "mamba_d_conv", "mamba_n_groups", "mamba_chunk_size"}
    # the three cells differ in the block alone
    mistral = json.loads((ROOT / "perfbench/configs/mistral-7b-v0.3.json").read_text())
    assert FILE["engine"] == mistral["engine"]
    # the worst position's limit lies between the program's largest and the
    # bf16-state control's smallest reading on the chip (PERF.md section 4)
    tol = FILE["logits_tolerance"]
    assert 0.006722 < tol["median"] <= tol["max"] and 0.007107 < tol["max"] < 0.010140
    assert "bfloat16" in tol["set_from"] and "int8" in tol["set_from"]


def test_program_config_carries_every_published_number():
    c = falcon_h1.program_config(FILE)
    assert (c.dim, c.hidden_dim, c.n_heads, c.n_kv_heads, c.head_dim, c.vocab_size, c.n_layers) == (
        5120, 21504, 20, 4, 128, 261120, FILE["num_hidden_layers"])
    assert c.head_dim != c.dim // c.n_heads and not c.n_experts and not c.tie_embeddings
    assert (c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_groups, c.ssm_conv, c.ssm_chunk) == (
        32, 128, 256, 2, 4, 128)
    assert (c.d_ssm, c.ssm_conv_dim, c.ssm_in_dim) == (4096, 5120, 9248)
    assert c.max_seq_len == FILE["engine"]["max_seq_len"] and c.rope_theta == 1e11
    multipliers = (c.embedding_multiplier, c.lm_head_multiplier, c.attention_in_multiplier,
                   c.attention_out_multiplier, c.key_multiplier, c.ssm_in_multiplier,
                   c.ssm_out_multiplier, *c.mlp_multipliers, *c.ssm_multipliers)
    assert len(multipliers) == 14
    assert multipliers == (
        FILE["embedding_multiplier"], FILE["lm_head_multiplier"], FILE["attention_in_multiplier"],
        FILE["attention_out_multiplier"], FILE["key_multiplier"], FILE["ssm_in_multiplier"],
        FILE["ssm_out_multiplier"], *FILE["mlp_multipliers"], *FILE["ssm_multipliers"])
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        falcon_h1.program_config(dict(FILE, mamba_n_heads=16))
    with pytest.raises(ValueError, match="mamba_norm_before_gate"):
        falcon_h1.program_config(dict(FILE, mamba_norm_before_gate=True))
    with pytest.raises(ValueError, match="mlp_bias"):
        falcon_h1.program_config(dict(FILE, mlp_bias=True))


def test_the_counts_are_the_issues_arithmetic():
    from finchat_tpu.models.llama import n_params

    p = falcon_h1.param_counts(FILE)
    assert p["attention"] == 31_457_280 and p["mlp"] == 330_301_440
    assert p["mixer"] == 5120 * 9248 + 4096 * 5120 + 5 * 5120 + 3 * 32 + 4096 == 68_351_072
    assert p["layer"] == p["attention"] + p["mixer"] + p["mlp"] + 2 * 5120
    assert p["embed"] == p["head"] == 261120 * 5120
    assert p["total"] == n_params(falcon_h1.program_config(FILE))
    L = FILE["num_hidden_layers"]
    assert falcon_h1.kv_bytes_per_token(FILE) == 2048 * L  # 2 KiB a token a layer
    assert falcon_h1.attention_stream_bytes(FILE, kv_tokens=1000) == 1000 * 2048
    assert falcon_h1.ssm_state_bytes_per_row(FILE) == 4 << 20  # 4 MiB a row a layer
    small = (2 * 4096 + 2 * 512 + 32) * 4
    assert falcon_h1.ssm_step_stream_bytes(FILE, rows=16) == 16 * (8 * 2 ** 20 + small)
    # a step: weights and head once, the live K/V, and the state of the rows
    # that the window's dispatches carried (the slots where there is no trace)
    weights = (p["layers"] + p["head"]) * 2
    assert falcon_h1.decode_step_stream_bytes(FILE, live_kv_tokens=100_000, ctx=None) \
        == weights + 100_000 * 2048 * L + L * falcon_h1.ssm_step_stream_bytes(FILE, rows=16)
    ctx = _context(tracer_events=[
        (1.0, None, "dispatch", None, "engine", {"kind": "decode", "rows": [[0, "a", "decode"]] * 12}),
        (2.0, None, "dispatch", None, "engine", {"kind": "decode", "rows": [[0, "a", "decode"]] * 14}),
        (3.0, None, "dispatch", None, "engine", {"kind": "decode", "rows": []})])
    assert falcon_h1.decode_step_stream_bytes(FILE, live_kv_tokens=0, ctx=ctx) \
        == weights + L * falcon_h1.ssm_step_stream_bytes(FILE, rows=13)


# --- the two controls, at a size a test run can hold ------------------------------

SMALL = dict(FILE, hidden_size=128, intermediate_size=256, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=64, vocab_size=512,
             mamba_n_heads=8, mamba_d_head=16, mamba_d_ssm=128, mamba_d_state=16,
             mamba_chunk_size=16, engine={"max_seqs": 2, "max_seq_len": 256})


def test_both_controls_read_apart_from_the_sound_program():
    """The bf16 program (the cache-less forward) and the two controls — int8
    matmul weights; the recurrent state rounded to bf16 after every token —
    judged against the float32 reference on the check's own prompts, at 2
    layers of width 128. The int8 control's smallest median is 1.5 x the
    program's largest (0.0068 against 0.0045: with Falcon-H1's small output
    multipliers the residual is mostly the embedding, and the head's own
    rounding sets both). The state control moves the logits (the state is live)
    by less than the program's own bf16 does: at this size no limit on the
    logits can catch it; at the cell's size the worst position's does
    (PERF.md section 4 has the readings the file's ``max`` stands on)."""
    import jax
    import jax.numpy as jnp

    from finchat_tpu.models.llama import forward_full, init_params

    c = dataclasses.replace(falcon_h1.program_config(SMALL), dtype=jnp.bfloat16)
    params = init_params(c, jax.random.key(0))
    program, int8, state = [], [], []
    for seed in (1, 2, 3):
        tokens, positions = correct.seeded_tokens(SMALL, seed, 96)
        want, margins = falcon_h1.reference_logits(params, tokens, SMALL, positions=positions)
        served = forward_full(params, jnp.asarray(tokens)[None],
                              jnp.arange(len(tokens))[None], config=c)[0][jnp.asarray(positions)]
        lowered, _ = falcon_h1.control_logits(params, tokens, SMALL, positions=positions)
        rounded, _ = falcon_h1.state_control_logits(params, tokens, SMALL, positions=positions)
        want, margins = np.asarray(want, np.float32), np.asarray(margins, np.float32)
        for readings, got in ((program, served), (int8, lowered), (state, rounded)):
            got = np.asarray(got, np.float32)
            readings.append(correct._judge([correct.rel_rms(g, w) for g, w in zip(got, want)],
                                           margins)["median_rel_rms"])
    assert min(int8) > 1.4 * max(program) > 0
    assert 0 < max(state) < min(program)


# --- the metrics and their readers -------------------------------------------------

def _context(**over):
    base = dict(w0=100.0, w1=151.0, requests=[], tracer_events=[], prom_before={},
                prom_after={}, device_trace=trace_reduce.reduce_xplane(CAPTURE),
                device={"kind": "TPU v5 lite"}, model=FILE, extra={})
    base.update(over)
    return Context(**base)


@pytest.fixture
def the_runs_capture(monkeypatch):
    monkeypatch.setattr(scope_trace.trace_reduce, "find_xplane", lambda _dir: CAPTURE)


def test_the_three_metrics_are_declared_for_the_cell_alone():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    for name in OURS:
        assert declared[name]["workloads"] == [CELL] and declared[name]["moves"] == "output_tok_s"
        spec = json.loads((ROOT / f"perfbench/layer_metrics/{name}.json").read_text())
        assert (ROOT / f"perfbench/layer_metrics/readers/{spec['reader']}.py").exists()
    assert (declared["ssm_state_gb.sat"]["layer"], declared["ssm_state_gb.sat"]["source"]) \
        == ("device", "program_counter")
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "falcon-h1-34b-instruct", "report-backlog", 1)
    # every metric without a list is read in the new cell too
    assert sum("workloads" not in m for m in BENCH["per_layer"]) >= 11


def test_pr_24s_metrics_stay_declared_in_order_and_unbroken():
    """``test_new_metrics_are_declared_with_a_reader_file_each`` without its
    demand that they be the LAST of the list, which no appended metric meets."""
    names = [m["name"] for m in BENCH["per_layer"]]
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    start = names.index(PR_24[0])
    assert names[start:start + len(PR_24)] == PR_24
    assert names[start + len(PR_24):][:len(OURS)] == OURS  # then this cell's, then later PRs'
    for name in PR_24:
        assert declared[name].get("workloads") == (
            ["mixtral-report-saturated"] if name == "moe_share.sat" else None)
        assert (ROOT / f"perfbench/layer_metrics/{name}.json").exists()
    assert declared["startup_warmup_s"]["moves"] == "setup_s"


def test_state_gauge_is_read_from_the_windows_closing_snapshot():
    assert read_metric("ssm_state_gb.sat", _context()) is None
    assert read_metric("ssm_state_gb.sat", _context(
        prom_after={"finchat_ssm_state_bytes": 0.0})) is None  # a model without a mixer
    assert read_metric("ssm_state_gb.sat", _context(
        prom_after={"finchat_ssm_state_bytes": 340_131_840.0})) == pytest.approx(0.34013184)


def test_a_capture_without_the_mixers_scopes_reads_nothing(the_runs_capture):
    """What the parent commit's program gives, and every llama-block cell."""
    assert read_metric("ssm_share.sat", _context()) is None
    assert read_metric("ssm_state_roofline.sat", _context()) is None


def test_state_roofline_is_bytes_over_peak_over_one_layers_scan(monkeypatch, the_runs_capture):
    """Hand-made operations: two under ``ssm_scan`` in ``decode_step`` run
    once a layer a step (their means add up: 60 + 140 us), one in another
    step and one under another scope do not count; the dispatches of kind
    ``decode`` carried 16 and 14 rows."""
    ops = [(0, "%fusion.1 = f32[] fusion()", "fusion", 0, 50_000),
           (0, "%fusion.1 = f32[] fusion()", "fusion", 0, 70_000),
           (0, "%fusion.2 = f32[] fusion()", "fusion", 0, 140_000),
           (0, "%fusion.3 = f32[] fusion()", "fusion", 0, 900_000),
           (0, "%fusion.4 = f32[] fusion()", "fusion", 0, 900_000)]
    paths = {ops[0][1]: "jit(decode_step)/while/body/closed_call/ssm_scan/mul:",
             ops[2][1]: "jit(decode_step)/while/body/closed_call/ssm_scan/reduce_sum:",
             ops[3][1]: "jit(ragged_mixed_step)/while/body/closed_call/ssm_scan/dot_general:",
             ops[4][1]: "jit(decode_step)/while/body/closed_call/ssm_conv/add:"}
    notes = {"host:thread:0": [
        ("finchat.stage", 0, 1, {"kind": "decode", "rows": 16, "kv_tokens": 1}),
        ("finchat.stage", 2, 3, {"kind": "decode", "rows": 14, "kv_tokens": 1}),
        ("finchat.stage", 4, 5, {"kind": "ragged", "rows": 2, "kv_tokens": 1}),
        ("finchat.deliver", 6, 7, {})]}
    monkeypatch.setattr(xplane_scopes, "op_scope_paths", lambda _path: paths)
    monkeypatch.setattr(xplane_scopes, "device_ops", lambda _path: tuple(ops))
    monkeypatch.setattr(xplane_scopes, "annotations", lambda _path: notes)
    want = 100.0 * (falcon_h1.ssm_step_stream_bytes(FILE, rows=15)
                    / costs.peaks("TPU v5 lite")["hbm_bytes_per_s"]) / 200e-6
    assert read_metric("ssm_state_roofline.sat", _context()) == pytest.approx(want)
    assert 70 < want < 80  # 126 MB at 819 GB/s is 154 us
    # no `rows` on the annotations (the parent's program): nothing to read
    notes["host:thread:0"] = [("finchat.stage", 0, 1, {"kind": "decode", "kv_tokens": 1})]
    assert ssm_scan_trace.read(_context(), scope="ssm_scan", module="decode_step",
                               kinds=["decode"]) is None
    # a model whose adapter has no such count: nothing to read either
    mixtral = json.loads((ROOT / "perfbench/configs/mixtral-8x7b-v0.1.json").read_text())
    assert ssm_scan_trace.read(_context(model=mixtral), scope="ssm_scan", module="decode_step",
                               kinds=["decode"]) is None
