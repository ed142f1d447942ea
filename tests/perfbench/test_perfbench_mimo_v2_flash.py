"""``model_type`` "mimo_v2_flash" (MiMo-V2-Flash, PR 57): its configuration file
against the catalog row's published keys, the counts its adapter brings against
the program's own parameter tree and BOTH pools' arrays (K and V of two widths,
pools of two page widths), 308.8 B uncut, a step's bytes at a given touched
count, the cell and its eight metrics with their reader files, the accepted
readers on made-up operations of this cell's shape and on a slice of the cell's
own capture — what the parametrised cases of
``test_perfbench_model_adapters.py`` assert and cannot for this file (they
assume the llama block's ONE K/V shape: K and V heads equally wide, as many in
every layer, kept for the whole context; PERF.md section 7 h)."""

import json
from pathlib import Path

import pytest

from perfbench import trace_reduce
from perfbench.layer_metrics import Context, read_metric
from perfbench.layer_metrics.readers import moe_experts_trace, scope_trace, window_trace
from perfbench.models import adapter, mimo_v2_flash

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
FILE = json.loads((ROOT / "perfbench/configs/mimo-v2-flash.json").read_text())
CELL = "mimo-v2-flash-report-saturated"
CAPTURE = HERE / "decode_scoped_v5e.xplane.pb"  # Mixtral's decode: no window scope
OWN_CAPTURE = HERE / "mimo_decode_v5e.xplane.pb"
OURS = ["gqa16_share.sat", "gqa16_kv_roofline.sat", "swa128_share.sat", "swa128_kv_roofline.sat",
        "window128_kv_gb.sat", "moe_mimo_share.sat", "moe_mimo_experts_touched.sat",
        "moe_mimo_expert_roofline.sat"]
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")

# the catalog row's `config` (guide model-configs, architectures.jsonl,
# `MiMo-V2-Flash`), key for key, but the keys the cut changes
PUBLISHED = {
    "attention_value_scale": 0.707, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 16384, "max_position_embeddings": 262144, "model_type": "mimo_v2_flash",
    "num_attention_heads": 64, "head_dim": 192, "num_key_value_heads": 4,
    "layernorm_epsilon": 1e-05, "rope_theta": 5000000, "tie_word_embeddings": False,
    "partial_rotary_factor": 0.334, "sliding_window": 128, "swa_rope_theta": 10000,
    "attention_bias": False, "v_head_dim": 128, "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "sliding_window_size": 128, "attention_chunk_size": 128,
    "moe_intermediate_size": 2048, "n_shared_experts": None, "num_experts_per_tok": 8,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "routed_scaling_factor": None, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 8, "swa_head_dim": 192, "swa_v_head_dim": 128,
}
PATTERN = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7
CUT = {"num_hidden_layers": (48, 7), "hybrid_layer_pattern": (PATTERN, [0, 1, 1, 1, 1, 1, 0]),
       "moe_layer_freq": ([0] + [1] * 47, [0, 1, 1, 1, 1, 1, 1]), "n_routed_experts": (256, 16),
       "vocab_size": (152576, 76288)}


# --- the configuration file ---------------------------------------------------

def test_the_file_holds_the_published_keys_and_cuts_no_width():
    assert adapter(FILE) is mimo_v2_flash
    assert {k: FILE[k] for k in PUBLISHED} == PUBLISHED
    if CATALOG.exists():  # the row itself, where the guide is installed
        row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
                   if '"name": "MiMo-V2-Flash"' in line)
        assert set(row["config"]) == set(PUBLISHED) | set(CUT)
        assert {k: v for k, v in row["config"].items() if k not in CUT} == PUBLISHED
        assert {k: row["config"][k] for k in CUT} == {k: v[0] for k, v in CUT.items()}
        assert FILE["source"] == row["source_url"]
    assert set(FILE["reduced"]) == set(CUT)
    for key, (was, now) in CUT.items():
        cut = FILE["reduced"][key]
        assert (cut["from"], cut["to"]) == (was, now) and FILE[key] == now and cut["why"]
    # no width is cut: every head count and width of both kinds, the window, the rotated part
    assert not set(FILE["reduced"]) & set(mimo_v2_flash.WIDTH_KEYS)
    assert not [k for k in FILE["reduced"] if k.endswith(("_dim", "_rank"))]
    assert set(mimo_v2_flash.WIDTH_KEYS) >= {
        "hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim", "v_head_dim",
        "swa_head_dim", "swa_v_head_dim", "num_attention_heads", "swa_num_attention_heads",
        "num_key_value_heads", "swa_num_key_value_heads", "sliding_window",
        "partial_rotary_factor", "num_experts_per_tok"}
    # the vocabulary's half is the issue's ONE stated fallback, and says what decided it
    assert "14.5" in FILE["reduced"]["vocab_size"]["why"]
    entry = next(c for c in BENCH["configs"] if c["name"] == "mimo-v2-flash")
    assert entry["reduced"] == list(CUT) and entry["source"] == FILE["source"]
    assert entry["file"] == "perfbench/configs/mimo-v2-flash.json" and len(entry["why"]) <= 200
    assert FILE["dtype"] == "bfloat16"
    assert (FILE["expert_bias_init_std"], FILE["sink_init_std"]) == (0.02, 1.0)  # keys of THIS file
    assert FILE["engine"] == {"max_seqs": 32, "prefill_chunk": 256, "num_pages": 5120,
                              "page_size": 128, "max_seq_len": 32768}
    assumed = " ".join(FILE["assumed"])
    for said in ("the KIND's", "i + 32", "FIRST 64 dims", "v <- 0.707 v", "counts the token itself",
                 "read by nothing", "takes probability and gives no value", "normal x 1",
                 "chooses and does not weigh", "no shared expert", "[gate | up]", "normal x 0.02",
                 "NOT built", "served context 32,768"):
        assert said in assumed, said
    assert "16 chips share each layer" in FILE["deployment"]
    assert "Nothing stands in" in FILE["deployment"]
    assert FILE["memory"]["params"]["total"] == mimo_v2_flash.param_counts(FILE)["total"]
    assert set(FILE["logits_tolerance"]) >= {"median", "max", "set_from"}


def test_the_cell_and_its_metrics_are_declared_last_with_their_reader_files():
    cell = BENCH["workloads"][-1]
    assert cell == {"name": CELL, "config": "mimo-v2-flash",
                    "traffic": "report-backlog-lead40-cap16k", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "of 16 held experts" in cell["why"]
    assert BENCH["configs"][-1]["name"] == "mimo-v2-flash"
    assert (ROOT / "perfbench/traffic/report-backlog-lead40-cap16k.json").exists()
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-len(OURS):] == OURS
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    for name in OURS:
        metric = declared[name]
        assert metric["workloads"] == [CELL] and metric["moves"] == "output_tok_s"
        assert set(metric) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        spec = json.loads((ROOT / f"perfbench/layer_metrics/{name}.json").read_text())
        assert (ROOT / f"perfbench/layer_metrics/readers/{spec['reader']}.py").exists()
    assert {declared[n]["unit"] for n in OURS if "roofline" in n or "share" in n} == {"%"}
    assert {n: declared[n]["better"] for n in OURS} == {
        "gqa16_share.sat": "lower", "gqa16_kv_roofline.sat": "higher", "swa128_share.sat": "lower",
        "swa128_kv_roofline.sat": "higher", "window128_kv_gb.sat": "lower",
        "moe_mimo_share.sat": "lower", "moe_mimo_experts_touched.sat": "lower",
        "moe_mimo_expert_roofline.sat": "higher"}
    readers = {n: json.loads((ROOT / f"perfbench/layer_metrics/{n}.json").read_text())
               for n in OURS}
    assert {n: r["reader"] for n, r in readers.items()} == {
        "gqa16_share.sat": "scope_trace", "gqa16_kv_roofline.sat": "scope_trace",
        "swa128_share.sat": "scope_trace", "swa128_kv_roofline.sat": "window_trace",
        "window128_kv_gb.sat": "prom_gauge", "moe_mimo_share.sat": "scope_trace",
        "moe_mimo_experts_touched.sat": "prom_ratio",
        "moe_mimo_expert_roofline.sat": "moe_experts_trace"}
    # the accepted files' parameters, under this cell's names
    for ours, theirs in (("swa128_share.sat", "swa2k_share.sat"),
                         ("swa128_kv_roofline.sat", "swa2k_kv_roofline.sat"),
                         ("window128_kv_gb.sat", "window2k_kv_gb.sat"),
                         ("moe_mimo_share.sat", "moe128_share.sat"),
                         ("moe_mimo_experts_touched.sat", "moe128_experts_touched.sat"),
                         ("moe_mimo_expert_roofline.sat", "moe128_expert_roofline.sat"),
                         ("gqa16_kv_roofline.sat", "attn_kv_roofline.sat")):
        assert readers[ours] == json.loads(
            (ROOT / f"perfbench/layer_metrics/{theirs}.json").read_text()), ours
    # nothing that was there is gone or changed: the ten cells before this one, in their
    # order, and the accepted lists of cells without this one
    older = [w["name"] for w in BENCH["workloads"]]
    assert older.index(CELL) == 10 and older[7:10] == [
        "trinity-mini-report-saturated", "kimi-linear-report-saturated",
        "joyai-flash-report-saturated"]
    assert declared["attn_kv_roofline.sat"]["workloads"] == older[:5]
    for name in ("swa2k_share.sat", "window2k_kv_gb.sat", "moe128_share.sat", "moe256_share.sat"):
        assert CELL not in declared[name]["workloads"]


def test_program_config_carries_every_published_number():
    """What ``test_program_config_carries_the_published_keys`` asserts for the
    llama block's files, with what it cannot: K/V heads and the rotation base
    are a KIND's, values are narrower than keys, a part of a head is rotated."""
    from finchat_tpu.models.llama import FULL, WINDOW, AttnKind

    c = mimo_v2_flash.program_config(FILE)
    assert (c.dim, c.n_heads, c.head_dim, c.v_head_dim, c.vocab_size, c.n_layers) == (
        4096, 64, 192, 128, 76288, 7)
    assert c.attn_kinds == ((FULL, AttnKind(4, 5e6)), (WINDOW, AttnKind(8, 1e4, sink=True)))
    assert (c.rope_dim, c.value_scale, c.window, c.norm_eps) == (64, 0.707, 128, 1e-5)
    assert (c.hidden_dim, c.dense_hidden_dim, c.moe_shared_dim) == (2048, 16384, 0)
    assert (c.n_experts, c.moe_router_width, c.top_k_experts) == (16, 256, 8) and c.moe_sparse
    assert (c.moe_score, c.moe_select_bias, c.moe_groups, c.moe_gate_scale, c.moe_norm_picks) == (
        "sigmoid", True, 0, 1.0, True)
    assert (c.moe_bias_init_std, c.sink_init_std) == (0.02, 1.0)
    assert c.kv_widths(FULL) == c.kv_row_widths == (768, 512) and c.kv_widths(WINDOW) == (1536, 1024)
    assert c.leading_kinds == (FULL,) and c.layer_pattern == (WINDOW,) * 5 + (FULL,)
    assert not (c.rope_kinds or c.qk_norm or c.qk_head_norm or c.attn_gate or c.norm_both
                or c.tie_embeddings or c.layer_plan)
    assert (c.n_attn_layers, c.n_window_layers, c.n_state_layers) == (2, 5, 0) and not c.has_state
    assert c.max_seq_len == FILE["engine"]["max_seq_len"]
    for key, value in (("scoring_func", "softmax"), ("n_group", 4), ("n_shared_experts", 1),
                       ("add_full_attention_sink_bias", True), ("attention_bias", True)):
        with pytest.raises(ValueError, match=key):
            mimo_v2_flash.program_config(dict(FILE, **{key: value}))
    with pytest.raises(ValueError, match="swa_head_dim is not head_dim"):
        mimo_v2_flash.program_config(dict(FILE, swa_head_dim=128))
    with pytest.raises(ValueError, match="hybrid_layer_pattern"):
        mimo_v2_flash.program_config(dict(FILE, hybrid_layer_pattern=[0, 1]))


def test_the_counts_are_the_programs_own_tree_and_both_pools_arrays():
    """The adapter's arithmetic against what the program builds, by shapes
    (nothing is allocated): the tree, the full pool (2 layers, K 768 beside V
    512 columns) and the window pool (5 layers, 1,536 beside 1,024)."""
    import jax

    from finchat_tpu.engine.engine import create_state, window_pool_pages
    from finchat_tpu.engine.kv_cache import page_hbm_bytes
    from finchat_tpu.models.llama import PRESETS, init_params, n_params
    from finchat_tpu.utils.config import EngineConfig

    p = mimo_v2_flash.param_counts(FILE)
    assert (p["attention_full"], p["attention_window"]) == (89_128_960, 94_371_904)  # + 64 sinks
    assert (p["expert"], p["router"], p["dense_mlp"]) == (25_165_824, 1_048_832, 201_326_592)
    assert p["routed"] == 16 * p["expert"] and p["embed"] == p["head"] == 76288 * 4096
    c = mimo_v2_flash.program_config(FILE)
    tree = jax.eval_shape(lambda: init_params(c, jax.random.key(0)))
    assert p["total"] == n_params(c) == sum(x.size for x in jax.tree.leaves(tree))
    # the whole vocabulary's cut is the issue's 4,523.6 M; its half 625.0 M less
    assert p["total"] + 2 * 76288 * 4096 == pytest.approx(4_523.6e6, abs=0.1e6)
    assert n_params(PRESETS["mimo-v2-flash"]) == pytest.approx(308.8e9, abs=0.05e9)
    layers = tree["layers"]
    assert layers["attn_q"].shape == (6, 4096, 12288) and layers["attn_o"].shape == (6, 8192, 4096)
    assert layers["attn_k"].shape == (1, 4096, 768) and layers["attn_v"].shape == (1, 4096, 512)
    assert layers["swa_k"].shape == (5, 4096, 1536) and layers["swa_v"].shape == (5, 4096, 1024)
    assert layers["swa_sink"].shape == (5, 64) and layers["moe_in"].shape == (6, 16, 4096, 4096)
    assert tree["dense_layers"]["attn_k"].shape == (1, 4096, 768)

    assert mimo_v2_flash.kv_bytes_per_token_by_kind(FILE) == {"full": 2 * 2560, "window": 5 * 5120}
    assert mimo_v2_flash.kv_bytes_per_token(FILE) == 5120 == FILE["memory"]["kv_bytes_per_token"]
    assert mimo_v2_flash.attention_stream_bytes(FILE, kv_tokens=1000) == 1000 * 2560
    assert mimo_v2_flash.window_stream_bytes(FILE, window_kv_tokens=32 * 128) == 32 * 128 * 25600
    assert mimo_v2_flash.window_bytes_per_row(FILE) == 128 * 25600
    assert mimo_v2_flash.routed_layers_a_period(FILE) == 6
    cfg = EngineConfig(**FILE["engine"])
    assert page_hbm_bytes(c, 128) == 128 * 5120 and page_hbm_bytes(c, 128, kind="window") == 128 * 25600
    state = jax.eval_shape(lambda: create_state(c, cfg, cfg.max_seq_len // cfg.page_size))
    nbytes = lambda x: x.size * x.dtype.itemsize  # noqa: E731
    assert state.k_pages.shape == (2, 5120, 128, 768) and state.v_pages.shape == (2, 5120, 128, 512)
    assert nbytes(state.k_pages) + nbytes(state.v_pages) == 5120 * page_hbm_bytes(c, 128) \
        == 3_355_443_200
    n_win = window_pool_pages(c, cfg)
    assert n_win == (32 + 4) * 3 + 1 == 109 and state.win_table.shape == (32, 3)
    assert state.win_k_pages.shape == (5, 109, 128, 1536)
    assert state.win_v_pages.shape == (5, 109, 128, 1024)
    assert nbytes(state.win_k_pages) + nbytes(state.win_v_pages) \
        == 109 * page_hbm_bytes(c, 128, kind="window") == 357_171_200


# --- the yardstick's counts and the readers -----------------------------------

def _context(prom_before=None, prom_after=None, rows=None):
    events = [(0.0, "t", "dispatch", None, "sched", {"rows": [[i, "t", "decode"] for i in range(n)]})
              for n in (rows or [])]
    return Context(w0=0.0, w1=51.0, requests=[], tracer_events=events,
                   prom_before=prom_before or {}, prom_after=prom_after or {},
                   device_trace=None, device={"kind": "TPU v5 lite"}, model=FILE)


def test_the_steps_bytes_follow_the_touched_count_the_full_layers_and_a_window_a_row():
    p = mimo_v2_flash.param_counts(FILE)
    outside = p["layers"] - 6 * p["routed"]
    assert outside == p["outside_experts"]
    window = 32 * 128 * 25600
    assert mimo_v2_flash.decode_step_stream_bytes(FILE, live_kv_tokens=270_000, ctx=None) \
        == (outside + 6 * 16 * p["expert"] + p["head"]) * 2 + 270_000 * 5120 + window
    ctx = _context({}, {"finchat_moe_experts_touched_total": 6 * 9.25,
                        "finchat_moe_layer_steps_total": 6.0}, rows=[32])
    assert mimo_v2_flash.experts_touched(FILE, ctx) == pytest.approx(9.25)
    got = mimo_v2_flash.decode_step_stream_bytes(FILE, live_kv_tokens=270_000, ctx=ctx)
    assert got == pytest.approx((outside + 6 * 9.25 * p["expert"] + p["head"]) * 2
                                + 270_000 * 5120 + window)
    assert 6.0e9 < got < 7.5e9  # the issue's estimate less half a head: about 6.7 GB
    assert mimo_v2_flash.moe_step_stream_bytes(FILE, rows=32, experts_touched=9.25) \
        == 6 * (9.25 * p["expert"] + 32 * 2 * 4096) * 2
    moved = _context({}, {"finchat_moe_experts_touched_total": 6 * 9.25,
                          "finchat_moe_layer_steps_total": 6.0,
                          "finchat_window_kv_bytes": 0.25e9})
    assert read_metric("moe_mimo_experts_touched.sat", moved) == pytest.approx(9.25)
    assert read_metric("window128_kv_gb.sat", moved) == pytest.approx(0.25)
    for name in ("moe_mimo_experts_touched.sat", "window128_kv_gb.sat"):
        assert read_metric(name, _context()) is None  # the parent: no counter, no gauge


def _step_ops():
    """Two decode steps' executed operations as a capture of this cell names
    them: the leading FULL layer's walk and append outside the scan, five
    window layers' and the full layer's in its body — a window layer's walk
    under ``swa_attention`` ALONE (PR 57: it opens no ``paged_attention``
    inside), so that ``paged_attention`` names the full layers' walks."""
    lead = "jit(decode_step)/jit(main)/"
    body = "jit(decode_step)/jit(main)/while/body/closed_call/"
    named = {
        "%paged_kv_append.1 = (bf16[2,5120,128,768]) custom-call(...)": lead + "kv_append/pallas_call",
        "%paged_flash_attention.1 = bf16[32,64,1,128] custom-call(...)":
            lead + "paged_attention/pallas_call",
        "%paged_kv_append.2 = (bf16[5,109,128,1536]) custom-call(...)":
            body + "swa_attention/kv_append/pallas_call",
        "%paged_flash_attention.2 = bf16[32,64,1,128] custom-call(...)":
            body + "swa_attention/pallas_call",
        "%paged_flash_attention.3 = bf16[32,64,1,128] custom-call(...)":
            body + "paged_attention/pallas_call",
        "%moe_experts_step.1 = bf16[32,4096] custom-call(...)":
            body + "moe_experts/jit(moe_experts_step)/pallas_call",
    }
    append, lead_walk, swa_append, swa_walk, full_walk, experts = named
    step = [append, lead_walk] + [swa_append, swa_walk] * 5 + [full_walk] + [experts] * 6
    durations = {append: 8_000, lead_walk: 1_000_000, swa_append: 10_000, swa_walk: 90_000,
                 full_walk: 1_000_000, experts: 600_000}
    return named, tuple((0, name, "custom-call", 10_000_000 * i, durations[name])
                        for i, name in enumerate(step + step))


def test_the_accepted_readers_tell_the_two_kinds_walks_apart(monkeypatch):
    """``gqa16_kv_roofline.sat``: ONE full layer's call against the distinct
    tokens x 2,560 LOGICAL bytes — the mean over the calls under
    ``paged_attention``, which the window layers' walks are not among;
    ``swa128_kv_roofline.sat``: the windows' bytes over the time under
    ``swa_attention`` in one step."""
    from perfbench.live_kv import LIVE_ANNOTATION

    named, ops = _step_ops()
    notes = {"host": [("finchat.dispatch", 0, 1, {"kind": "decode", "rows": 32,
                                                  "kv_tokens": 400_000,
                                                  "window_kv_tokens": 32 * 128})]}
    live = {"host": [("perfbench_live", 0, 1, {"kv_tokens": 400_000,
                                               "kv_tokens_distinct": 280_000})]}
    for module in (scope_trace, window_trace):
        monkeypatch.setattr(module.trace_reduce, "find_xplane", lambda _dir: CAPTURE)
        monkeypatch.setattr(module.xplane_scopes, "op_scope_paths", lambda _path: named)
        monkeypatch.setattr(module.xplane_scopes, "device_ops", lambda _path: ops)
        monkeypatch.setattr(module.xplane_scopes, "annotations",
                            lambda _path, prefix="finchat.": live if prefix == LIVE_ANNOTATION
                            else notes)
    ctx = _context()
    assert read_metric("gqa16_kv_roofline.sat", ctx) is None  # an untraced run
    ctx.device_trace = trace_reduce.reduce_xplane(CAPTURE)
    ctx.device_trace.modules["jit_decode_step"] = [0.01, 0.01]
    want = 100.0 * (280_000 * 2560 / 819e9) / 1e-3
    assert read_metric("gqa16_kv_roofline.sat", ctx) == pytest.approx(want)
    assert 85 < want < 90  # the made-up walk: a millisecond for 717 MB
    under_ns = 5 * (10_000 + 90_000)
    want = 100.0 * (32 * 128 * 25600 / 819e9) / (under_ns / 1e9)
    assert read_metric("swa128_kv_roofline.sat", ctx) == pytest.approx(want)
    assert 20 < want < 30


def test_the_expert_roofline_counts_a_periods_six_layers(monkeypatch):
    body = "jit(decode_step)/jit(main)/while/body/closed_call/moe_experts/"
    named = {f"%moe_experts_step.{i} = bf16[32,4096] custom-call(...)": body + "pallas_call"
             for i in range(6)}
    ops = tuple((0, name, "custom-call", 1_000 * i, 600_000)
                for i, name in enumerate(list(named) * 3))
    notes = {"host": [("finchat.dispatch", 0, 1, {"kind": "decode", "rows": 32})]}
    monkeypatch.setattr(moe_experts_trace.trace_reduce, "find_xplane", lambda _dir: CAPTURE)
    monkeypatch.setattr(moe_experts_trace.xplane_scopes, "op_scope_paths", lambda _path: named)
    monkeypatch.setattr(moe_experts_trace.xplane_scopes, "device_ops", lambda _path: ops)
    monkeypatch.setattr(moe_experts_trace.xplane_scopes, "annotations", lambda _path: notes)
    ctx = _context({}, {"finchat_moe_experts_touched_total": 6 * 9.0,
                        "finchat_moe_layer_steps_total": 6.0})
    ctx.device_trace = trace_reduce.reduce_xplane(CAPTURE)
    want = 100.0 * (mimo_v2_flash.moe_step_stream_bytes(FILE, rows=32, experts_touched=9.0)
                    / 819e9) / (6 * 0.6e-3)
    assert read_metric("moe_mimo_expert_roofline.sat", ctx) == pytest.approx(want)
    assert 85 < want < 100


def test_a_capture_without_the_scopes_reads_nothing(monkeypatch):
    """Mixtral's decode capture, as the parent gives for any cell it can run:
    no ``swa_attention`` scope, no ``window_kv_tokens``: None, and no raise."""
    monkeypatch.setattr(scope_trace.trace_reduce, "find_xplane", lambda _dir: CAPTURE)
    monkeypatch.setattr(window_trace.trace_reduce, "find_xplane", lambda _dir: CAPTURE)
    ctx = _context()
    ctx.device_trace = trace_reduce.reduce_xplane(CAPTURE)
    for name in ("swa128_share.sat", "swa128_kv_roofline.sat"):
        assert read_metric(name, ctx) is None, name
    assert read_metric("moe_mimo_share.sat", ctx) > 0  # Mixtral's capture has the routed scopes


def test_the_readers_on_a_slice_of_the_cells_own_capture(monkeypatch):
    """Three whole decode steps cut from the traced run of the cell on the chip
    (PR 57, call D, seed 2147493101; ``tests/perfbench/slice_capture.py``): the
    eight metrics read on the slice what the whole capture read (30.8, 78.8,
    8.2, 20.0, 0.216, 18.5, 3.24, 85.8), the two kinds' walks stand under their
    own scopes, and the dispatch's annotation carries the windows' tokens."""
    from perfbench import xplane_scopes

    for module in (scope_trace, window_trace, moe_experts_trace):
        monkeypatch.setattr(module.trace_reduce, "find_xplane", lambda _dir: OWN_CAPTURE)
    ctx = _context({}, {"finchat_moe_experts_touched_total": 6 * 3.241,
                        "finchat_moe_layer_steps_total": 6.0,
                        "finchat_window_kv_bytes": 0.2162688e9})
    ctx.device_trace = trace_reduce.reduce_xplane(OWN_CAPTURE)
    assert len(ctx.device_trace.modules["jit_decode_step"]) == 3
    assert read_metric("decode_step_ms.sat", ctx) == pytest.approx(8.11, abs=0.05)
    want = {"gqa16_share.sat": (30.5, 0.4), "gqa16_kv_roofline.sat": (78.7, 0.5),
            "swa128_share.sat": (8.0, 0.2), "swa128_kv_roofline.sat": (19.9, 0.4),
            "window128_kv_gb.sat": (0.2163, 0.0001), "moe_mimo_share.sat": (19.6, 0.3),
            "moe_mimo_experts_touched.sat": (3.241, 0.001),
            "moe_mimo_expert_roofline.sat": (79.8, 1.0)}
    assert set(want) == set(OURS)
    for name, (value, room) in want.items():
        assert read_metric(name, ctx) == pytest.approx(value, abs=room), name
    assert max(read_metric(n, ctx) for n in OURS if "roofline" in n) < 100
    noted = [stats for events in xplane_scopes.annotations(str(OWN_CAPTURE)).values()
             for _name, _start, _end, stats in events if "window_kv_tokens" in stats]
    assert noted and all(s["window_kv_tokens"] == 32 * 128 and s["rows"] == 32 for s in noted)
    paths = xplane_scopes.op_scope_paths(str(OWN_CAPTURE))
    walks = {name: path for name, path in paths.items()
             if name.startswith("%paged_flash_attention") and "pallas_call" in path}
    window = [p for p in walks.values() if "/swa_attention/" in p]
    full = [p for p in walks.values() if "/swa_attention/" not in p]
    assert len(window) == 5 and not [p for p in window if "/paged_attention/" in p]
    assert len(full) == 2 and all("/paged_attention/" in p for p in full)
    assert any("/swa_attention/kv_append/" in p for p in paths.values())
    assert not any("/yoco_attention/" in p for p in paths.values())
