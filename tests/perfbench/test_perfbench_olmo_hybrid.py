"""``model_type`` "olmo_hybrid" (PR 32): its configuration file against the
published keys, the counts its adapter brings against the program's own
parameter tree, page pool and recurrent state, the two controls at a size a
test run holds, and the readers of its three metrics."""

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from perfbench import correct, costs, trace_reduce, xplane_scopes
from perfbench.layer_metrics import Context, read_metric
from perfbench.layer_metrics.readers import scope_trace
from perfbench.models import adapter, olmo_hybrid

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
FILE = json.loads((ROOT / "perfbench/configs/olmo-hybrid-7b.json").read_text())
CELL = "olmo-hybrid-report-saturated"
CAPTURE = HERE / "decode_scoped_v5e.xplane.pb"  # Mixtral's decode: no linear layer in it
OURS = ["gdn_share.sat", "gdn_state_roofline.sat", "gdn_state_gb.sat"]
LINEAR, FULL = "linear_attention", "full_attention"

# the catalog row's `config` (guide model-configs, architectures.jsonl,
# `Olmo-Hybrid-7B`), key for key, but the two keys the depth is cut in
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536, "attention_bias": False,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False, "linear_num_key_heads": 30,
    "linear_num_value_heads": 30, "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}


# --- the configuration file ---------------------------------------------------

def test_the_file_holds_the_published_keys_and_only_depth_is_cut():
    assert adapter(FILE) is olmo_hybrid
    assert {k: FILE[k] for k in PUBLISHED} == PUBLISHED
    # depth, in whole periods of the published pattern, and nothing else
    assert list(FILE["reduced"]) == ["num_hidden_layers", "layer_types"]
    cut = FILE["reduced"]["num_hidden_layers"]
    assert cut["from"] == 32 and cut["to"] == FILE["num_hidden_layers"] in (4, 8)
    assert FILE["layer_types"] == ([LINEAR] * 3 + [FULL]) * (FILE["num_hidden_layers"] // 4)
    assert not set(FILE["reduced"]) & set(olmo_hybrid.WIDTH_KEYS)
    assert set(olmo_hybrid.WIDTH_KEYS) >= {k for k in PUBLISHED if k.startswith("linear_")
                                           and k != "linear_allow_neg_eigval"}
    entry = next(c for c in BENCH["configs"] if c["name"] == "olmo-hybrid-7b")
    assert entry["reduced"] == list(FILE["reduced"]) and entry["source"] == FILE["source"]
    assert FILE["ssm_state_dtype"] == "float32" and FILE["dtype"] == "bfloat16"
    # the four cells differ in the block alone
    mistral = json.loads((ROOT / "perfbench/configs/mistral-7b-v0.3.json").read_text())
    assert FILE["engine"] == mistral["engine"]
    assumed = " ".join(FILE["assumed"])
    for said in ("gated delta rule", "L2-normalised", "THEN the gate", "NO rotation",
                 "norm on each sub-block's output", "blocks of 64", "A_log = log U(0, 16]",
                 "served context 16,384"):
        assert said in assumed, said
    # the median's limit lies between the program's largest and the tighter
    # control's smallest reading on the chip (PERF.md section 4); the worst
    # position's only bounds a broken one (the readings overlap there)
    tol = FILE["logits_tolerance"]
    assert 0.024361 < tol["median"] < 0.038324 and 0.058180 < tol["max"] < 0.127729
    assert "bfloat16" in tol["set_from"] and "int8" in tol["set_from"]


def test_program_config_carries_every_published_number():
    c = olmo_hybrid.program_config(FILE)
    assert (c.dim, c.hidden_dim, c.n_heads, c.n_kv_heads, c.head_dim, c.vocab_size, c.n_layers) == (
        3840, 11008, 30, 30, 128, 100352, FILE["num_hidden_layers"])
    assert c.layer_pattern == (LINEAR, LINEAR, LINEAR, FULL) and c.rope_theta is None
    assert (c.gdn_heads, c.gdn_key_dim, c.gdn_value_dim, c.gdn_conv,
            c.gdn_neg_eigval) == (30, 96, 192, 4, True)
    assert c.qk_norm and c.norm_after and not c.tie_embeddings and not c.n_experts
    assert c.gdn_conv_dim == 11520 and c.state_shape == (30, 96, 192)
    assert c.conv_shape == (3, 11520) and c.norm_eps == 1e-6
    assert c.max_seq_len == FILE["engine"]["max_seq_len"]
    with pytest.raises(ValueError, match="one key head a value head"):
        olmo_hybrid.program_config(dict(FILE, linear_num_key_heads=15))
    with pytest.raises(ValueError, match="layer_types"):
        olmo_hybrid.program_config(dict(FILE, num_hidden_layers=7))
    with pytest.raises(ValueError, match="attention_bias"):
        olmo_hybrid.program_config(dict(FILE, attention_bias=True))
    # a rotated model of this type states its theta; null is "none"
    rotated = dict(FILE, rope_parameters={"rope_theta": 5e5})
    assert olmo_hybrid.program_config(rotated).rope_theta == 5e5


def test_the_counts_are_the_programs_own():
    """The adapter's arithmetic against what the program builds: the
    parameter tree, the page pool (the full layers' alone) and the recurrent
    state (the linear layers' alone), by shapes: nothing is allocated."""
    import jax

    from finchat_tpu.engine.engine import create_state
    from finchat_tpu.engine.kv_cache import page_hbm_bytes
    from finchat_tpu.models.llama import init_params, n_params
    from finchat_tpu.utils.config import EngineConfig

    p = olmo_hybrid.param_counts(FILE)
    assert p["attention"] == 4 * 3840 * 3840 + 2 * 3840 == 58_990_080
    assert p["linear_attention"] == (3840 * (11520 + 5760 + 60) + 5760 * 3840
                                     + 4 * 11520 + 60 + 192) == 88_750_332
    assert p["mlp"] == 3 * 3840 * 11008 == 126_812_160
    assert (p["linear_layer"], p["full_layer"]) == (215_570_172, 185_809_920)
    assert FILE["memory"]["linear_layer_params"] == p["linear_layer"]
    assert FILE["memory"]["full_layer_params"] == p["full_layer"]
    assert FILE["memory"]["period_params"] == 3 * p["linear_layer"] + p["full_layer"]
    assert p["embed"] == p["head"] == 100352 * 3840
    c = olmo_hybrid.program_config(FILE)
    tree = jax.eval_shape(lambda: init_params(c, jax.random.key(0)))
    assert p["total"] == n_params(c) == sum(x.size for x in jax.tree.leaves(tree))
    n_full, n_linear = FILE["layer_types"].count(FULL), FILE["layer_types"].count(LINEAR)
    assert {v.shape[0] for k, v in tree["layers"].items() if k.startswith("attn_")} == {n_full}
    assert {v.shape[0] for k, v in tree["layers"].items() if k.startswith("gdn_")} == {n_linear}

    # 15 KiB a token in each full layer; the linear layers own no pages
    assert olmo_hybrid.attention_stream_bytes(FILE, kv_tokens=1000) == 1000 * 15360
    assert olmo_hybrid.kv_bytes_per_token(FILE) == 15360 * n_full == FILE["memory"]["kv_bytes_per_token"]
    cfg = EngineConfig(**FILE["engine"])
    assert page_hbm_bytes(c, cfg.page_size) == cfg.page_size * olmo_hybrid.kv_bytes_per_token(FILE)
    state = jax.eval_shape(lambda: create_state(c, cfg, cfg.max_seq_len // cfg.page_size))
    nbytes = lambda x: x.size * x.dtype.itemsize  # noqa: E731
    assert state.k_pages.shape == (n_full, cfg.num_pages, cfg.page_size, 3840)
    assert nbytes(state.k_pages) + nbytes(state.v_pages) \
        == cfg.num_pages * cfg.page_size * olmo_hybrid.kv_bytes_per_token(FILE)
    assert state.ssm_state.shape == (n_linear, cfg.max_seqs, 30, 96, 192)
    assert state.conv_state.shape == (n_linear, cfg.max_seqs, 3, 11520)
    row = olmo_hybrid.ssm_state_bytes_per_row(FILE)
    assert row == 30 * 96 * 192 * 4 == 2_211_840  # 2.11 MiB a row a layer
    assert nbytes(state.ssm_state) == n_linear * cfg.max_seqs * row
    assert nbytes(state.conv_state) == n_linear * cfg.max_seqs * olmo_hybrid.conv_tail_bytes_per_row(FILE)
    # what `gdn_state_gb.sat` must read on the chip (finchat_ssm_state_bytes)
    assert (nbytes(state.ssm_state) + nbytes(state.conv_state)) / 1e9 == pytest.approx(
        0.2256 * n_linear / 6, rel=1e-3)

    small = (2 * 30 * 96 + 2 * 5760 + 2 * 30) * 4
    # the scope's operations in one iteration of the layer scan: a period's 3 linear layers
    assert olmo_hybrid.ssm_step_stream_bytes(FILE, rows=16) == 3 * 16 * (2 * row + small)
    # a step: weights and head once, the live K/V in the full layers, and
    # state and tail of the rows the window's dispatches carried
    weights = (p["layers"] + p["head"]) * 2
    per_row = 2 * row + small + 2 * olmo_hybrid.conv_tail_bytes_per_row(FILE)
    assert olmo_hybrid.decode_step_stream_bytes(FILE, live_kv_tokens=100_000, ctx=None) \
        == weights + 100_000 * 15360 * n_full + n_linear * 16 * per_row
    ctx = _context(tracer_events=[
        (1.0, None, "dispatch", None, "engine", {"kind": "decode", "rows": [[0, "a", "decode"]] * 12}),
        (2.0, None, "dispatch", None, "engine", {"kind": "decode", "rows": [[0, "a", "decode"]] * 14})])
    assert olmo_hybrid.decode_step_stream_bytes(FILE, live_kv_tokens=0, ctx=ctx) \
        == weights + n_linear * 13 * per_row


# --- the two controls, at a size a test run can hold ------------------------------

SMALL = dict(FILE, hidden_size=128, intermediate_size=256, num_attention_heads=4,
             num_key_value_heads=4, head_dim=32, vocab_size=512, linear_num_key_heads=4,
             linear_num_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=32,
             engine={"max_seqs": 2, "max_seq_len": 256})


def test_both_controls_read_apart_from_the_reference():
    """The bf16 program (the cache-less forward) and the two controls — int8
    matmul weights; the recurrent state rounded to bf16 after every token —
    judged against the float32 reference on the check's own prompts, at two
    periods of width 128. The int8 control reads above the program; the state
    control moves the logits (the state is live) — how far apart they read
    at the cell's size is PERF.md section 4's, from the chip."""
    import jax
    import jax.numpy as jnp

    from finchat_tpu.models.llama import forward_full, init_params

    c = dataclasses.replace(olmo_hybrid.program_config(SMALL), dtype=jnp.bfloat16)
    params = init_params(c, jax.random.key(0))
    program, int8, state = [], [], []
    for seed in (1, 2):
        tokens, positions = correct.seeded_tokens(SMALL, seed, 96)
        want, margins = olmo_hybrid.reference_logits(params, tokens, SMALL, positions=positions)
        served = forward_full(params, jnp.asarray(tokens)[None],
                              jnp.arange(len(tokens))[None], config=c)[0][jnp.asarray(positions)]
        lowered, _ = olmo_hybrid.control_logits(params, tokens, SMALL, positions=positions)
        rounded, _ = olmo_hybrid.state_control_logits(params, tokens, SMALL, positions=positions)
        want, margins = np.asarray(want, np.float32), np.asarray(margins, np.float32)
        assert np.isinf(margins).all()
        for readings, got in ((program, served), (int8, lowered), (state, rounded)):
            got = np.asarray(got, np.float32)
            readings.append(correct._judge([correct.rel_rms(g, w) for g, w in zip(got, want)],
                                           margins)["median_rel_rms"])
    assert min(int8) > max(program) > 0
    assert min(state) > 1e-4  # rounding the state is no rounding of nothing


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


def test_the_cell_and_its_three_metrics_are_declared_and_for_it_alone():
    """Looked up by name, wherever a later PR's entries put them in their lists."""
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    for name in OURS:
        assert declared[name]["workloads"] == [CELL] and declared[name]["moves"] == "output_tok_s"
        spec = json.loads((ROOT / f"perfbench/layer_metrics/{name}.json").read_text())
        assert (ROOT / f"perfbench/layer_metrics/readers/{spec['reader']}.py").exists()
    assert (declared["gdn_state_gb.sat"]["layer"], declared["gdn_state_gb.sat"]["source"]) \
        == ("device", "program_counter")
    assert declared["gdn_state_roofline.sat"]["better"] == "higher"
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert cell == {"name": CELL, "config": "olmo-hybrid-7b", "traffic": "report-backlog",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    assert [c["file"] for c in BENCH["configs"] if c["name"] == "olmo-hybrid-7b"] \
        == ["perfbench/configs/olmo-hybrid-7b.json"]
    # no older metric's list gained the cell: the ssm_* three read another scope
    for name in ("ssm_share.sat", "ssm_state_roofline.sat", "ssm_state_gb.sat", "moe_share.sat"):
        assert CELL not in declared[name]["workloads"]


def test_state_gauge_is_read_from_the_windows_closing_snapshot():
    assert read_metric("gdn_state_gb.sat", _context()) is None
    assert read_metric("gdn_state_gb.sat", _context(
        prom_after={"finchat_ssm_state_bytes": 225_607_680.0})) == pytest.approx(0.22560768)


def test_a_capture_without_the_linear_layers_scopes_reads_nothing(the_runs_capture):
    """What the parent commit's program gives, and every other cell."""
    assert read_metric("gdn_share.sat", _context()) is None
    assert read_metric("gdn_state_roofline.sat", _context()) is None


def test_state_roofline_is_bytes_over_peak_over_one_linear_layers_update(monkeypatch,
                                                                         the_runs_capture):
    """Hand-made operations: the scan runs over periods, so the operations
    under ``gdn_scan`` in ``decode_step`` are a period's three linear layers'
    (two each here: six distinct operations, each once a period a step) and
    the adapter's count is a period's too; their means add up to 3 x (90 +
    130) us. One in another step and one under another scope do not count;
    the ``decode`` dispatches carried 16 and 14 rows."""
    in_scope = "jit(decode_step)/while/body/closed_call/gdn_scan/"
    ops, paths = [], {}
    for layer in range(3):
        read, write = (f"%fusion.{10 * layer + i} = f32[] fusion()" for i in (1, 2))
        ops += [(0, read, "fusion", 0, 80_000), (0, read, "fusion", 0, 100_000),
                (0, write, "fusion", 0, 130_000)]
        paths.update({read: in_scope + "mul:", write: in_scope + "add:"})
    ops += [(0, "%fusion.3 = f32[] fusion()", "fusion", 0, 900_000),
            (0, "%fusion.4 = f32[] fusion()", "fusion", 0, 900_000)]
    paths.update({
        ops[-2][1]: "jit(ragged_mixed_step)/while/body/closed_call/gdn_scan/dot_general:",
        ops[-1][1]: "jit(decode_step)/while/body/closed_call/gdn_conv/add:"})
    notes = {"host:thread:0": [
        ("finchat.stage", 0, 1, {"kind": "decode", "rows": 16, "kv_tokens": 1}),
        ("finchat.stage", 2, 3, {"kind": "decode", "rows": 14, "kv_tokens": 1}),
        ("finchat.stage", 4, 5, {"kind": "ragged", "rows": 2, "kv_tokens": 1})]}
    monkeypatch.setattr(xplane_scopes, "op_scope_paths", lambda _path: paths)
    monkeypatch.setattr(xplane_scopes, "device_ops", lambda _path: tuple(ops))
    monkeypatch.setattr(xplane_scopes, "annotations", lambda _path: notes)
    want = 100.0 * (olmo_hybrid.ssm_step_stream_bytes(FILE, rows=15)
                    / costs.peaks("TPU v5 lite")["hbm_bytes_per_s"]) / 660e-6
    assert read_metric("gdn_state_roofline.sat", _context()) == pytest.approx(want)
    assert 35 < want < 40  # 3 x 66.9 MB at 819 GB/s is 3 x 81.7 us
    # Falcon-H1's metric reads another scope: nothing of this capture
    assert read_metric("ssm_state_roofline.sat", _context()) is None


# --- what three older cases hold, without the part no layer pattern can meet -------

def test_the_counts_owe_one_another_what_a_layer_pattern_allows():
    """``test_llama_block_counts_equal_the_functions_they_replace[...-kv_bytes_per_token]``
    wants K and V of a token in EVERY layer, and ``test_head_dim_is_honoured_
    where_the_file_has_it`` a wider head in every layer's parameters: here only
    the full-attention layers own pages and heads of that width (both cases
    are red for this file: PERF.md section 7). What they hold otherwise:"""
    n_full = FILE["layer_types"].count(FULL)
    assert olmo_hybrid.kv_bytes_per_token(FILE) \
        == n_full * olmo_hybrid.attention_stream_bytes(FILE, kv_tokens=1)
    wide = dict(FILE, head_dim=2 * FILE["head_dim"])
    assert costs.head_dim(FILE) == FILE["head_dim"] == 3840 // 30
    assert olmo_hybrid.kv_bytes_per_token(wide) == 2 * olmo_hybrid.kv_bytes_per_token(FILE)
    assert olmo_hybrid.attention_stream_bytes(wide, kv_tokens=7) \
        == 2 * olmo_hybrid.attention_stream_bytes(FILE, kv_tokens=7)
    # q, k, v, o and the two norms over q's and k's whole width, a full layer
    assert olmo_hybrid.param_counts(wide)["full_layer"] - olmo_hybrid.param_counts(FILE)["full_layer"] \
        == 2 * 3840 * (30 + 30) * 128 + (30 + 30) * 128
    assert olmo_hybrid.param_counts(wide)["linear_layer"] \
        == olmo_hybrid.param_counts(FILE)["linear_layer"]
    assert olmo_hybrid.program_config(wide).head_dim == 256


def test_pr_30s_metric_stays_declared_with_its_reader():
    """``test_the_metric_is_declared_last_with_its_reader_file`` without its
    demand that it be the LAST of the list: this PR's contract puts new
    entries at the END of their lists, so an appended metric leaves it red."""
    entry = {m["name"]: m for m in BENCH["per_layer"]}["kv_distinct_share.sat"]
    assert entry == {
        "name": "kv_distinct_share.sat", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "scheduler", "moves": "output_tok_s"}
    spec = json.loads((ROOT / "perfbench/layer_metrics/kv_distinct_share.sat.json").read_text())
    assert spec == {"reader": "scope_trace",
                    "params": {"quantity": "kv_distinct_share", "kinds": ["decode"]}}
