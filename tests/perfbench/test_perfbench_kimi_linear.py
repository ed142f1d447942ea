"""``model_type`` "kimi_linear" (Kimi-Linear-48B-A3B, PR 51): its configuration
file against the catalog row's published keys (three keys cut, no width), the
counts its adapter brings against the program's own parameter tree, pool and
state, a step's bytes at a given touched count, the cell and its nine metrics
with their reader files (every one an ACCEPTED reader under new parameters),
the readers on a slice of the cell's own capture and on a capture without the
scopes — and what the parametrised cases of ``test_perfbench_model_adapters.py``
that cannot pass for this file assert otherwise (they assume K and V heads of
the top-level ``head_dim`` kept in every layer, ``intermediate_size`` as the
experts' width and ``num_local_experts`` as their count, and a rotation where
the file has a ``rope_theta``)."""

import json
from pathlib import Path

import pytest

from perfbench import trace_reduce
from perfbench.layer_metrics import Context, read_metric
from perfbench.layer_metrics.readers import latent_trace, moe_experts_trace, scope_trace
from perfbench.layer_metrics.readers import ssm_scan_trace
from perfbench.models import adapter, kimi_linear

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
FILE = json.loads((ROOT / "perfbench/configs/kimi-linear-48b-a3b.json").read_text())
NAME, CELL = "kimi-linear-48b-a3b", "kimi-linear-report-saturated"
TRAFFIC = "report-backlog-lead40-cap16k"
CAPTURE = HERE / "decode_scoped_v5e.xplane.pb"  # Mixtral's decode: none of the new scopes
OWN_CAPTURE = HERE / "kimi_decode_v5e.xplane.pb"
OURS = ["kda_share.sat", "kda_state_roofline.sat", "kda_state_gb.sat", "mla_full_share.sat",
        "mla_full_attn_roofline.sat", "mla_full_kv_gb.sat", "moe_kl_share.sat",
        "moe_kl_experts_touched.sat", "moe_kl_expert_roofline.sat"]
READERS = {"kda_share.sat": "scope_trace", "kda_state_roofline.sat": "ssm_scan_trace",
           "kda_state_gb.sat": "prom_gauge", "mla_full_share.sat": "scope_trace",
           "mla_full_attn_roofline.sat": "latent_trace", "mla_full_kv_gb.sat": "prom_gauge",
           "moe_kl_share.sat": "scope_trace", "moe_kl_experts_touched.sat": "prom_ratio",
           "moe_kl_expert_roofline.sat": "moe_experts_trace"}
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CUT = ("num_hidden_layers", "num_experts", "linear_attn_config")

# the catalog row's `config` (guide model-configs, architectures.jsonl,
# `Kimi-Linear-48B-A3B-Instruct`), key for key, but the three keys the cut changes
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
    "intermediate_size": 9216, "kv_lora_rank": 512, "mla_use_nope": True,
    "model_max_length": 1048576, "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "num_attention_heads": 32, "num_expert_group": 1, "num_experts_per_token": 8,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "routed_scaling_factor": 2.446,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840,
}
KDA_27 = [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26]
WHOLE = {"num_hidden_layers": 27, "num_experts": 256,
         "linear_attn_config": {"full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
                                "kda_layers": KDA_27, "num_heads": 32,
                                "short_conv_kernel_size": 4}}


# --- the configuration file ---------------------------------------------------

def test_the_file_holds_the_rows_keys_and_cuts_three_keys_alone():
    assert adapter(FILE) is kimi_linear
    assert {k: FILE[k] for k in PUBLISHED} == PUBLISHED
    if CATALOG.exists():  # the row itself, where the guide is installed
        row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
                   if '"name": "Kimi-Linear-48B-A3B-Instruct"' in line)
        assert set(row["config"]) == set(PUBLISHED) | set(CUT)
        assert {k: v for k, v in row["config"].items() if k not in CUT} == PUBLISHED
        assert {k: row["config"][k] for k in CUT} == WHOLE
        assert FILE["source"] == row["source_url"]
    assert tuple(FILE["reduced"]) == CUT
    for key in CUT:
        cut = FILE["reduced"][key]
        assert cut["from"] == WHOLE[key] and cut["to"] == FILE[key] != cut["from"] and cut["why"]
    # no width is cut: the nested group's three widths stand equal in `from` and `to`
    group = FILE["reduced"]["linear_attn_config"]
    for width in kimi_linear.LINEAR_WIDTH_KEYS:
        assert group["from"][width] == group["to"][width] == WHOLE["linear_attn_config"][width]
    assert set(group["to"]) == set(group["from"]) == set(kimi_linear.LINEAR_WIDTH_KEYS) | {
        "kda_layers", "full_attn_layers"}
    assert not set(FILE["reduced"]) & set(kimi_linear.WIDTH_KEYS)
    assert not [k for k in FILE["reduced"] if k.endswith(("_dim", "_rank", "_size"))]
    # the lists are the published ones cut to the layers kept: whole periods behind the dense layer
    n = FILE["num_hidden_layers"]
    assert group["to"]["kda_layers"] == [i for i in KDA_27 if i <= n]
    assert group["to"]["full_attn_layers"] == [i for i in WHOLE["linear_attn_config"]["full_attn_layers"]
                                               if i <= n]
    assert (n - FILE["first_k_dense_replace"]) % 4 == 0 and n - 1 >= 4 and FILE["num_experts"] >= 8
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["reduced"] == list(CUT) and entry["source"] == FILE["source"]
    assert entry["file"] == f"perfbench/configs/{NAME}.json" and len(entry["why"]) <= 200
    assert FILE["dtype"] == "bfloat16" and FILE["ssm_state_dtype"] == "float32"
    assert FILE["weights_seed"] == 0 and FILE["expert_bias_init_std"] == 0.02  # a key of THIS file
    assert FILE["engine"] == {"max_seqs": 32, "prefill_chunk": 256, "num_pages": 5120,
                              "page_size": 128, "max_seq_len": 32768}
    assumed = " ".join(FILE["assumed"])
    for said in ("pre-norm", "NO positional encoding", "per head AND per key channel",
                 "ONE conv over [q | k | v]", "[W_f1 | W_g1 | w_b]", "no q latent",
                 "NEITHER it nor q_pe rotated", "ABSORBED form", "chooses and does not weigh",
                 "[gate | up]", "normal x 0.02", "float32 from the layer's input on",
                 "sub-blocks of 16", "served context 32,768"):
        assert said in assumed, said
    assert "THREE pipeline stages" in FILE["deployment"] and "8 chips share each layer" in FILE["deployment"]
    assert "1 token a step where the deployment's sees 8" in FILE["deployment"]
    assert FILE["memory"]["params"]["total"] == 3_026_860_896
    assert set(FILE["logits_tolerance"]) >= {"median", "max", "set_from"}


def test_the_cell_its_traffic_and_its_metrics_are_declared_with_their_reader_files():
    from perfbench.cells import load_cell, load_traffic

    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": NAME, "traffic": TRAFFIC, "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "cap 16,384" in cell["why"]
    # the traffic file: the accepted mix with another cap and nothing else
    raw = json.loads((ROOT / f"perfbench/traffic/{TRAFFIC}.json").read_text())
    assert {k: v for k, v in raw.items() if not k.endswith("_note")} == {
        "extends": "report-backlog-lead40", "answer_cap": 16384}
    base, ours = load_traffic("report-backlog-lead40"), load_traffic(TRAFFIC)
    assert ours["answer_cap"] == 16384 and base["answer_cap"] == 8192
    assert {k: v for k, v in ours.items() if k not in ("answer_cap", "answer_cap_note")} \
        == {k: v for k, v in base.items() if k not in ("answer_cap", "answer_cap_note")}
    loaded = load_cell(CELL)
    assert loaded.traffic["lead_in_s"] == 40 and loaded.traffic["sessions"] == 64
    assert loaded.config["engine"]["max_seq_len"] >= 2 * 16384  # the cap fits beside a prompt
    assert [m["name"] for m in loaded.end_to_end] == ["output_tok_s", "setup_s"]
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    names = [m["name"] for m in BENCH["per_layer"]]
    # by position relative to each other and to what was there, in order and unbroken
    at = names.index(OURS[0])
    assert names[at:at + len(OURS)] == OURS and at > names.index("window2k_kv_gb.sat")
    for name in OURS:
        metric = declared[name]
        assert metric["workloads"] == [CELL] and metric["moves"] == "output_tok_s"
        spec = json.loads((ROOT / f"perfbench/layer_metrics/{name}.json").read_text())
        assert spec["reader"] == READERS[name]
        assert (ROOT / f"perfbench/layer_metrics/readers/{spec['reader']}.py").exists()
        assert name in [m["name"] for m in loaded.per_layer]
    assert {declared[n]["unit"] for n in OURS if "roofline" in n or "share" in n} == {"%"}
    # no reader is new: each of the nine files names a reader an earlier PR accepted
    assert not (ROOT / "perfbench/layer_metrics/readers/kimi_trace.py").exists()
    kda = json.loads((ROOT / "perfbench/layer_metrics/kda_share.sat.json").read_text())
    assert kda["params"]["scopes"] == ["gdn_in", "gdn_conv", "gdn_gate", "gdn_scan", "gdn_norm",
                                       "gdn_out"]
    # nothing that was there is gone or changed: the eight cells before this one, in order
    older = [w["name"] for w in BENCH["workloads"]]
    assert older.index(CELL) == 8 and older[:8] == [
        "mixtral-report-saturated", "mistral7b-report-saturated", "falcon-h1-report-saturated",
        "olmo-hybrid-report-saturated", "granite-h-small-report-saturated",
        "deepseek-v32-report-saturated", "phi4-flash-report-saturated",
        "trinity-mini-report-saturated"]
    assert declared["attn_kv_roofline.sat"]["workloads"] == older[:5]
    for name in ("gdn_share.sat", "gdn_state_roofline.sat", "mla_share.sat",
                 "mla_attn_roofline.sat", "moe256_share.sat", "latent_kv_gb.sat"):
        assert CELL not in declared[name]["workloads"]


def test_program_config_carries_every_published_number():
    """What ``test_program_config_carries_the_published_keys[kimi-linear-48b-a3b]``
    asserts, with what it cannot: the experts' width is ``moe_intermediate_size``
    and their count ``num_experts``; the dense layer's ``intermediate_size`` is
    ``dense_hidden_dim``; the top-level ``head_dim`` 72 is no layer's width (a
    latent head is 192 / 128 wide, a KDA head 128); ``mla_use_nope`` makes the
    published ``rope_theta`` rotate nothing (the program's None)."""
    from finchat_tpu.models.llama import FULL, LINEAR

    c = kimi_linear.program_config(FILE)
    assert (c.dim, c.n_heads, c.n_kv_heads, c.head_dim, c.vocab_size, c.n_layers) == (
        2304, 32, 1, 192, 163840, FILE["num_hidden_layers"])
    assert (c.hidden_dim, c.dense_hidden_dim, c.moe_shared_dim) == (1024, 9216, 1024)
    assert (c.n_experts, c.moe_router_width, c.top_k_experts) == (32, 256, 8) and c.moe_sparse
    assert (c.moe_score, c.moe_select_bias, c.moe_groups, c.moe_gate_scale, c.moe_norm_picks) == (
        "sigmoid", True, 0, 2.446, True)
    assert c.moe_bias_init_std == 0.02 and c.moe_fused_glu
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim) == (
        0, 512, 128, 64, 128)
    assert c.rope_theta is None and c.rope_scaling is None and c.index_topk == 0
    assert c.attention_scale is None  # 192^-1/2 exactly: the default of a head of 192
    assert c.kv_row_widths == (640, 128) and c.latent_row == 640
    assert (c.gdn_heads, c.gdn_key_dim, c.gdn_value_dim, c.gdn_conv, c.gdn_gate_rank) == (
        32, 128, 128, 4, 128) and not c.gdn_neg_eigval
    assert c.state_shape == (32, 128, 128) and c.conv_shape == (3, 3 * 4096)
    assert c.leading_kinds == (LINEAR,) and c.layer_pattern == (LINEAR, LINEAR, FULL, LINEAR)
    assert (c.leading_dense_layers, c.window, c.layer_plan) == (1, 0, ())
    assert (c.n_attn_layers, c.n_state_layers) == (2, 7) and c.has_state
    assert not (c.qk_norm or c.norm_after or c.norm_both or c.attn_gate or c.tie_embeddings)
    assert c.max_seq_len == FILE["engine"]["max_seq_len"] == 32768
    for key, value in (("mla_use_nope", False), ("q_lora_rank", 1536), ("num_expert_group", 4),
                       ("moe_router_activation_func", "softmax"),
                       ("rope_scaling", {"type": "yarn"}), ("num_nextn_predict_layers", 1)):
        with pytest.raises(ValueError, match=key):
            kimi_linear.program_config(dict(FILE, **{key: value}))
    with pytest.raises(ValueError, match="linear_attn_config"):
        kimi_linear.program_config(dict(FILE, num_hidden_layers=10))
    with pytest.raises(ValueError, match="ssm_state_dtype"):
        kimi_linear.program_config(dict(FILE, ssm_state_dtype="bfloat16"))


def test_the_counts_are_the_programs_own_and_the_issues_table():
    """The adapter's arithmetic against what the program builds: the parameter
    tree, the pool and the state, by shapes (nothing is allocated). Also what
    ``test_llama_block_counts_equal_the_functions_they_replace[kimi-linear-48b-a3b-
    kv_bytes_per_token]``, ``[..-attention_stream_bytes]`` and
    ``test_head_dim_is_honoured...[kimi-linear-48b-a3b]`` assert, for a model in
    which TWO layers of nine keep a token, as ONE latent row of 576 columns."""
    import jax

    from finchat_tpu.engine.engine import create_state
    from finchat_tpu.engine.kv_cache import page_hbm_bytes
    from finchat_tpu.models.llama import init_params, n_params
    from finchat_tpu.utils.config import EngineConfig

    p, mem = kimi_linear.param_counts(FILE), FILE["memory"]["params"]
    assert p["kda"] == 39_518_368 == mem["kda"]  # the ISSUE's 39.52 M
    assert p["latent_attention"] == 29_114_880 == mem["latent_attention"]  # 29.11 M
    assert p["expert"] == 3 * 2304 * 1024 == 7_077_888 == mem["expert"] == p["shared"]
    assert p["routed"] == 32 * p["expert"] == mem["routed_experts_a_layer"]
    assert p["router"] == 2305 * 256 == mem["router_with_bias"]
    assert p["dense_layer"] == 103_223_968 == mem["dense_kda_layer"]  # 103.2 M
    assert p["routed_kda_layer"] == 273_683_360 == mem["routed_kda_layer"]  # 273.7 M
    assert p["routed_latent_layer"] == 263_279_872 == mem["routed_latent_layer"]  # 263.3 M
    assert p["embed"] + p["head"] == 2 * 163840 * 2304 == mem["embedding_and_head"]
    assert p["total"] == 3_026_860_896 == mem["total"]
    assert p["layers"] == p["dense_layer"] + 6 * p["routed_kda_layer"] + 2 * p["routed_latent_layer"]
    c = kimi_linear.program_config(FILE)
    tree = jax.eval_shape(lambda: init_params(c, jax.random.key(0)))
    assert p["total"] == n_params(c) == sum(x.size for x in jax.tree.leaves(tree))
    layers, dense = tree["layers"], tree["dense_layers"]
    assert layers["moe_in"].shape == (8, 32, 2304, 2048) and layers["router"].shape == (8, 2304, 256)
    assert layers["gdn_in"].shape == (6, 2304, 12288) and layers["gdn_low"].shape == (6, 2304, 288)
    assert layers["gdn_f2"].shape == layers["gdn_g2"].shape == (6, 128, 4096)
    assert layers["gdn_dt_bias"].shape == (6, 4096) and layers["gdn_A_log"].shape == (6, 32)
    assert layers["attn_q_nope"].shape == (2, 2304, 4096) and "attn_q_a" not in layers
    assert layers["attn_kv_a"].shape == (2, 2304, 576) and layers["attn_uk"].shape == (2, 32, 128, 512)
    assert dense["gdn_in"].shape == (1, 2304, 12288) and dense["mlp_gate"].shape == (1, 2304, 9216)
    assert not [name for name in dense if name.startswith("attn_")]
    # the whole model, and ISSUE 51's first size (stage one of two)
    whole = dict(FILE, **WHOLE, reduced={})
    assert round(kimi_linear.param_counts(whole)["total"] / 1e9, 2) == 49.12
    assert kimi_linear.param_counts(whole)["total"] == n_params(kimi_linear.program_config(whole))
    first = dict(FILE, num_hidden_layers=13, linear_attn_config=dict(
        FILE["linear_attn_config"], kda_layers=[i for i in KDA_27 if i <= 13],
        full_attn_layers=[4, 8, 12]))
    assert round(kimi_linear.param_counts(first)["total"] / 1e6) == 4111
    assert kimi_linear.kv_bytes_per_token(first) == 3456  # a ninth of Olmo-Hybrid's 30,720

    assert kimi_linear.kv_bytes_per_token(FILE) == 2 * 1152 == FILE["memory"]["kv_bytes_per_token"]
    assert kimi_linear.attention_stream_bytes(FILE, kv_tokens=1000) == 1000 * 1152
    assert kimi_linear.latent_row_bytes(FILE) == (512 + 64) * 2
    assert kimi_linear.ssm_state_bytes_per_row(FILE) == 32 * 128 * 128 * 4 == 2 << 20  # 2.0 MiB
    assert kimi_linear.conv_tail_bytes_per_row(FILE) == 3 * 12288 * 4  # 147 KB
    cfg = EngineConfig(**FILE["engine"])
    assert page_hbm_bytes(c, cfg.page_size) == 2 * 128 * (640 + 128) * 2  # TWO layers' depth
    state = jax.eval_shape(lambda: create_state(c, cfg, cfg.max_seq_len // cfg.page_size))
    nbytes = lambda x: x.size * x.dtype.itemsize  # noqa: E731
    assert state.k_pages.shape == (2, 5120, 128, 640) and state.v_pages.shape == (2, 5120, 128, 128)
    assert nbytes(state.k_pages) + nbytes(state.v_pages) == 5120 * page_hbm_bytes(c, 128) \
        == 2_013_265_920
    assert state.ssm_state.shape == (7, 32, 32, 128, 128) and state.ssm_state.dtype.name == "float32"
    assert state.conv_state.shape == (7, 32, 3, 12288) and state.page_table.shape == (32, 256)
    assert nbytes(state.ssm_state) + nbytes(state.conv_state) == 32 * 7 * (
        kimi_linear.ssm_state_bytes_per_row(FILE) + kimi_linear.conv_tail_bytes_per_row(FILE)) \
        == 502_792_192


# --- the yardstick's counts and the readers -----------------------------------

def _context(prom_before=None, prom_after=None, rows=None):
    events = [(0.0, "t", "dispatch", None, "sched", {"rows": [[i, "t", "decode"] for i in range(n)]})
              for n in (rows or [])]
    return Context(w0=0.0, w1=51.0, requests=[], tracer_events=events,
                   prom_before=prom_before or {}, prom_after=prom_after or {},
                   device_trace=None, device={"kind": "TPU v5 lite"}, model=FILE)


def test_the_steps_bytes_follow_the_touched_count_the_latent_rows_and_the_state():
    p = kimi_linear.param_counts(FILE)
    head = p["head"]
    state = 7 * 32 * 2 * ((2 << 20) + 3 * 12288 * 4)
    counted = _context({}, {"finchat_moe_experts_touched_total": 8 * 20.0 * 10,
                            "finchat_moe_layer_steps_total": 8.0 * 10}, rows=[32, 32])
    assert kimi_linear.experts_touched(FILE, counted) == 20.0
    assert kimi_linear.decode_step_stream_bytes(FILE, live_kv_tokens=100_000, ctx=counted) == (
        (p["outside_experts"] + 8 * 20 * p["expert"] + head) * 2 + 100_000 * 2304 + state)
    # without the counter every held expert counts; a longer context adds TWO layers' rows
    assert kimi_linear.experts_touched(FILE, None) is None
    assert kimi_linear.experts_touched(FILE, _context()) is None
    assert kimi_linear.decode_step_stream_bytes(FILE, live_kv_tokens=0) == (
        (p["layers"] + head) * 2 + state)
    assert kimi_linear.decode_step_stream_bytes(FILE, live_kv_tokens=10_000) \
        - kimi_linear.decode_step_stream_bytes(FILE, live_kv_tokens=0) == 10_000 * 2304
    # what the scope readers divide by: a period's four routed layers; the leading
    # dense KDA layer outside the scan and a period's three inside it
    assert kimi_linear.routed_layers_a_period(FILE) == 4
    assert kimi_linear.moe_step_stream_bytes(FILE, rows=32, experts_touched=20.0) \
        == 4 * (20 * p["expert"] + 32 * 2 * 2304) * 2
    assert kimi_linear.scanned_kda_layers(FILE) == 4
    assert kimi_linear.ssm_step_stream_bytes(FILE, rows=32) == 4 * 32 * (
        2 * (2 << 20) + (3 * 4096 + 2 * 4096 + 32) * 4)
    # the latent walk's bound: the distinct rows of TWO layers at the peak, over the file's nine
    assert kimi_linear.mla_attention_bound_s(FILE, rows=32, selected=3000.0) == pytest.approx(
        2 * 32 * 3000 * 1152 / 819e9 / 9)


def test_the_gauges_and_the_ratio_read_the_windows_counters():
    moved = _context({"finchat_moe_experts_touched_total": 1000.0,
                      "finchat_moe_layer_steps_total": 40.0},
                     {"finchat_moe_experts_touched_total": 1000.0 + 8 * 19.5 * 100,
                      "finchat_moe_layer_steps_total": 40.0 + 8 * 100,
                      "finchat_ssm_state_bytes": 502_792_192.0,
                      "finchat_kv_pool_bytes": 2_013_265_920.0})
    assert read_metric("moe_kl_experts_touched.sat", moved) == pytest.approx(19.5)
    assert read_metric("kda_state_gb.sat", moved) == pytest.approx(0.502792192)
    assert read_metric("mla_full_kv_gb.sat", moved) == pytest.approx(2.01326592)
    for name in ("moe_kl_experts_touched.sat", "kda_state_gb.sat", "mla_full_kv_gb.sat"):
        assert read_metric(name, _context()) is None  # the parent: no counter, no gauge


def test_the_state_roofline_counts_the_leading_layer_and_a_periods_three(monkeypatch):
    """``kda_state_roofline.sat`` through the accepted reader: four distinct
    custom calls under ``gdn_scan`` in ``decode_step`` — the leading dense
    layer's outside the scan, three in its body — each at its mean duration,
    against the adapter's count of four layers' state read and written."""
    lead = "jit(decode_step)/jit(main)/gdn_scan/jit(gdn_state_step)/pallas_call"
    body = "jit(decode_step)/jit(main)/while/body/closed_call/gdn_scan/jit(gdn_state_step)/pallas_call"
    named = {"%gdn_state_step = f32[32,32,128] custom-call(...)": lead}
    named.update({f"%gdn_state_step.{i} = f32[32,32,128] custom-call(...)": body
                  for i in (1, 2, 3)})
    ops = tuple((0, name, "custom-call", 1_000 * i, 217_000)
                for i, name in enumerate(list(named) * 3))
    notes = {"host": [("finchat.dispatch", 0, 1, {"kind": "decode", "rows": 32})]}
    monkeypatch.setattr(ssm_scan_trace.trace_reduce, "find_xplane", lambda _dir: CAPTURE)
    monkeypatch.setattr(ssm_scan_trace.xplane_scopes, "op_scope_paths", lambda _path: named)
    monkeypatch.setattr(ssm_scan_trace.xplane_scopes, "device_ops", lambda _path: ops)
    monkeypatch.setattr(ssm_scan_trace.xplane_scopes, "annotations", lambda _path: notes)
    ctx = _context()
    ctx.device_trace = trace_reduce.reduce_xplane(CAPTURE)
    want = 100.0 * (kimi_linear.ssm_step_stream_bytes(FILE, rows=32) / 819e9) / (4 * 217e-6)
    assert read_metric("kda_state_roofline.sat", ctx) == pytest.approx(want)
    assert 75 < want < 80  # 134 MB each way a layer in 217 us


def test_a_capture_without_the_new_scopes_reads_nothing(monkeypatch):
    """Mixtral's decode capture, as the parent gives for any cell: no ``gdn_*``
    and no ``mla_*`` scope. Every capture metric of the nine returns None and
    does not raise (``moe_router`` / ``moe_experts`` are Mixtral's too: the
    share reads, the roofline wants the counters the parent does not move)."""
    for module in (scope_trace, ssm_scan_trace, latent_trace, moe_experts_trace):
        monkeypatch.setattr(module.trace_reduce, "find_xplane", lambda _dir: CAPTURE)
    ctx = _context()
    ctx.device_trace = trace_reduce.reduce_xplane(CAPTURE)
    for name in ("kda_share.sat", "kda_state_roofline.sat", "mla_full_share.sat",
                 "mla_full_attn_roofline.sat", "moe_kl_expert_roofline.sat"):
        assert read_metric(name, ctx) is None, name
    assert read_metric("moe_kl_share.sat", ctx) > 0
    untraced = _context()
    for name in OURS:
        assert read_metric(name, untraced) is None, name


def test_the_readers_on_a_slice_of_the_cells_own_capture(monkeypatch):
    """One whole decode step cut from the traced run of the cell on the chip
    (PR 51, call 3, seed 2147451201; ``tests/perfbench/slice_capture.py``, 18 ms
    of it: under 300 KB the slice keeps the device's lines and no host event,
    so the dispatch's note and the harness's sample are handed in as that
    run's own line had them): the six capture metrics read on the slice what
    the whole capture read (33.13, 12.10, 35.30; 77.97, 68.93, 89.71), each
    roofline under 100, the scopes are there by name, and the step holds four
    state steps, four expert passes, one latent walk and no indexer."""
    from perfbench import xplane_scopes
    from perfbench.live_kv import LIVE_ANNOTATION

    for module in (scope_trace, ssm_scan_trace, latent_trace, moe_experts_trace):
        monkeypatch.setattr(module.trace_reduce, "find_xplane", lambda _dir: OWN_CAPTURE)
    assert xplane_scopes.annotations(str(OWN_CAPTURE)) == {}
    notes = {None: {"host": [("finchat.dispatch", 0, 1, {"kind": "decode", "rows": 32,
                                                        "kv_tokens": 393_113})]},
             LIVE_ANNOTATION: {"host": [(LIVE_ANNOTATION, 0, 1, {
                 "kv_tokens": 393_113, "kv_tokens_distinct": 270_105})]}}
    monkeypatch.setattr(xplane_scopes, "annotations",
                        lambda _path, name=None: notes[name])
    ctx = _context({}, {"finchat_moe_experts_touched_total": 8 * 16.573,
                        "finchat_moe_layer_steps_total": 8.0})
    ctx.device_trace = trace_reduce.reduce_xplane(OWN_CAPTURE)
    assert len(ctx.device_trace.modules["jit_decode_step"]) == 1
    assert read_metric("decode_step_ms.sat", ctx) == pytest.approx(8.0, abs=0.1)
    assert read_metric("kda_share.sat", ctx) == pytest.approx(33.3, abs=0.5)
    assert read_metric("mla_full_share.sat", ctx) == pytest.approx(12.2, abs=0.3)
    assert read_metric("moe_kl_share.sat", ctx) == pytest.approx(35.0, abs=0.8)
    assert read_metric("kda_share.sat", ctx) + read_metric("mla_full_share.sat", ctx) >= 35
    assert read_metric("kda_state_roofline.sat", ctx) == pytest.approx(78.0, abs=1.0)
    # (the note's counts are the window's middle sample, not this step's own: a range)
    assert 50 < read_metric("mla_full_attn_roofline.sat", ctx) < 100
    assert 75 < read_metric("moe_kl_expert_roofline.sat", ctx) < 100
    assert kimi_linear.selected_tokens(FILE, ctx) == pytest.approx(270_105 / 32)
    monkeypatch.undo()
    calls = [name.split(" ")[0].lstrip("%") for _d, name, _kind, _s, _dur
             in xplane_scopes.device_ops(OWN_CAPTURE) if "custom-call(" in name]
    for kernel, count in (("gdn_state_step", 4), ("moe_experts_step", 4),
                          ("paged_latent_attention", 1), ("paged_kv_append", 1)):
        assert len({c for c in calls if c.split(".")[0] == kernel}) == count, kernel
    paths = set(xplane_scopes.op_scope_paths(str(OWN_CAPTURE)).values())
    for scope in ("gdn_in", "gdn_conv", "gdn_gate", "gdn_scan", "gdn_norm", "gdn_out",
                  "mla_project", "mla_attention", "kv_append", "moe_experts", "moe_router",
                  "moe_shared", "attn_o", "mlp", "head"):
        assert any(f"/{scope}/" in p for p in paths), scope
    assert not any("/dsa_indexer/" in p or "/dsa_select/" in p for p in paths)  # every token attended
    assert not any("/swa_attention/" in p or "/ssm_scan/" in p for p in paths)
