"""``model_type`` "afmoe" (Trinity-Mini, PR 47): its configuration file against
the catalog row's published keys (depth alone cut), the counts its adapter
brings against the program's own parameter tree and its two pools, a step's
bytes at a given touched count, the cell and its six metrics with their reader
files, the new reader (``window_trace``) on made-up operations and on a capture
without its scope — and what the parametrised cases of
``test_perfbench_model_adapters.py`` that cannot pass for this file assert
otherwise (they assume K and V heads kept for the whole context in every
layer, ``intermediate_size`` as the experts' width and ``num_local_experts``
as their count, and attention without a gate)."""

import json
from pathlib import Path

import pytest

from perfbench import trace_reduce
from perfbench.layer_metrics import Context, read_metric
from perfbench.layer_metrics.readers import moe_experts_trace, scope_trace, window_trace
from perfbench.models import adapter, afmoe

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
FILE = json.loads((ROOT / "perfbench/configs/trinity-mini.json").read_text())
CELL = "trinity-mini-report-saturated"
CAPTURE = HERE / "decode_scoped_v5e.xplane.pb"  # Mixtral's decode: no window scope
OURS = ["moe128_share.sat", "moe128_experts_touched.sat", "moe128_expert_roofline.sat",
        "swa2k_share.sat", "swa2k_kv_roofline.sat", "window2k_kv_gb.sat"]
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
S, F = "sliding_attention", "full_attention"

# the catalog row's `config` (guide model-configs, architectures.jsonl,
# `Trinity-Mini`), key for key, but the three keys the cut changes
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 6144, "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 32, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192,
}
CUT = {"num_hidden_layers": (32, 5), "num_dense_layers": (2, 1),
       "layer_types": ([S, S, S, F] * 8, [S, S, S, S, F])}


# --- the configuration file ---------------------------------------------------

def test_the_file_holds_the_published_keys_and_cuts_depth_alone():
    assert adapter(FILE) is afmoe
    assert {k: FILE[k] for k in PUBLISHED} == PUBLISHED
    if CATALOG.exists():  # the row itself, where the guide is installed
        row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
                   if '"name": "Trinity-Mini"' in line)
        assert set(row["config"]) == set(PUBLISHED) | set(CUT)
        assert {k: v for k, v in row["config"].items() if k not in CUT} == PUBLISHED
        assert {k: row["config"][k] for k in CUT} == {k: v[0] for k, v in CUT.items()}
        assert FILE["source"] == row["source_url"]
    assert set(FILE["reduced"]) == set(CUT)
    for key, (was, now) in CUT.items():
        cut = FILE["reduced"][key]
        assert (cut["from"], cut["to"]) == (was, now) and FILE[key] == now and cut["why"]
    # no width is cut: every expert, every head, every row of the vocabulary
    assert not set(FILE["reduced"]) & set(afmoe.WIDTH_KEYS)
    assert not [k for k in FILE["reduced"] if k.endswith(("_dim", "_rank", "_size"))]
    assert set(afmoe.WIDTH_KEYS) >= {"hidden_size", "intermediate_size", "moe_intermediate_size",
                                     "head_dim", "num_experts_per_tok", "sliding_window"}
    entry = next(c for c in BENCH["configs"] if c["name"] == "trinity-mini")
    assert entry["reduced"] == list(CUT) and entry["source"] == FILE["source"]
    assert entry["file"] == "perfbench/configs/trinity-mini.json" and len(entry["why"]) <= 200
    assert FILE["dtype"] == "bfloat16" and FILE["expert_bias_init_std"] == 0.02  # a key of THIS file
    assert FILE["engine"] == {"max_seqs": 32, "prefill_chunk": 256, "num_pages": 3072,
                              "page_size": 128, "max_seq_len": 16384}
    assumed = " ".join(FILE["assumed"])
    for said in ("sqrt(hidden_size)", "input AND on its output", "each HEAD of q and of k",
                 "sigmoid(W_g h)", "full_attention layers are NOT rotated", "i + 64",
                 "counts the token itself", "chooses and does not weigh", "normal x 0.02",
                 "[gate | up]", "served context 16,384"):
        assert said in assumed, said
    assert "WHOLE" in FILE["deployment"] and "2 tokens a step" in FILE["deployment"]
    assert FILE["memory"]["params"]["total"] == 4_241_534_720
    assert set(FILE["logits_tolerance"]) >= {"median", "max", "set_from"}


def test_the_cell_and_its_metrics_are_declared_with_their_reader_files():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "trinity-mini", "traffic": "report-backlog-lead40",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "128 held experts" in cell["why"]
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    names = [m["name"] for m in BENCH["per_layer"]]
    # by position relative to each other and to what was there, not "last"
    at = names.index(OURS[0])
    assert names[at:at + len(OURS)] == OURS and at > names.index("mamba1_state_gb.sat")
    for name in OURS:
        metric = declared[name]
        assert metric["workloads"] == [CELL] and metric["moves"] == "output_tok_s"
        spec = json.loads((ROOT / f"perfbench/layer_metrics/{name}.json").read_text())
        assert (ROOT / f"perfbench/layer_metrics/readers/{spec['reader']}.py").exists()
    assert {declared[n]["unit"] for n in OURS if "roofline" in n or "share" in n} == {"%"}
    readers = {n: json.loads((ROOT / f"perfbench/layer_metrics/{n}.json").read_text())["reader"]
               for n in OURS}
    assert readers == {"moe128_share.sat": "scope_trace", "moe128_experts_touched.sat": "prom_ratio",
                       "moe128_expert_roofline.sat": "moe_experts_trace",
                       "swa2k_share.sat": "scope_trace", "swa2k_kv_roofline.sat": "window_trace",
                       "window2k_kv_gb.sat": "prom_gauge"}
    # nothing that was there is gone or changed: the seven cells before this one,
    # in their order, and the accepted lists of cells without this one
    older = [w["name"] for w in BENCH["workloads"]]
    assert older.index(CELL) == 7 and older[:7] == [
        "mixtral-report-saturated", "mistral7b-report-saturated", "falcon-h1-report-saturated",
        "olmo-hybrid-report-saturated", "granite-h-small-report-saturated",
        "deepseek-v32-report-saturated", "phi4-flash-report-saturated"]
    assert declared["attn_kv_roofline.sat"]["workloads"] == older[:5]
    for name in ("swa_share.sat", "window_kv_gb.sat", "moe256_share.sat", "moe_expert_roofline.sat"):
        assert CELL not in declared[name]["workloads"]


def test_program_config_carries_every_published_number():
    """What ``test_program_config_carries_the_published_keys[trinity-mini]``
    asserts, with what it cannot: the experts' width is
    ``moe_intermediate_size`` and their count ``num_experts``; the dense
    layer's ``intermediate_size`` is ``dense_hidden_dim``."""
    from finchat_tpu.models.llama import FULL, WINDOW

    c = afmoe.program_config(FILE)
    assert (c.dim, c.n_heads, c.n_kv_heads, c.head_dim, c.vocab_size, c.n_layers) == (
        2048, 32, 4, 128, 200192, 5)
    assert (c.hidden_dim, c.dense_hidden_dim, c.moe_shared_dim) == (1024, 6144, 1024)
    assert (c.n_experts, c.moe_router_width, c.top_k_experts) == (128, 128, 8) and c.moe_sparse
    assert (c.moe_score, c.moe_select_bias, c.moe_groups, c.moe_gate_scale, c.moe_norm_picks) == (
        "sigmoid", True, 0, 2.826, True)
    assert c.kv_row_widths == (512, 512) and c.window == 2048
    assert c.leading_kinds == (WINDOW,) and c.layer_pattern == (WINDOW, WINDOW, WINDOW, FULL)
    assert c.rope_theta == 10000.0 and c.rope_kinds == (WINDOW,) and not c.tie_embeddings
    assert c.qk_head_norm and c.attn_gate and c.norm_both and not (c.qk_norm or c.norm_after)
    assert c.embedding_multiplier == 2048 ** 0.5 and c.layer_plan == ()
    assert c.moe_bias_init_std == 0.02
    assert (c.n_attn_layers, c.n_window_layers, c.n_state_layers) == (1, 4, 0) and not c.has_state
    assert c.max_seq_len == FILE["engine"]["max_seq_len"]
    for key, value in (("score_func", "softmax"), ("n_group", 4), ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match=key):
            afmoe.program_config(dict(FILE, **{key: value}))
    with pytest.raises(ValueError, match="layer_types"):
        afmoe.program_config(dict(FILE, layer_types=FILE["layer_types"][:-1]))


def test_the_counts_are_the_programs_own_and_the_issues_table():
    """The adapter's arithmetic against what the program builds: the parameter
    tree and BOTH pools, by shapes (nothing is allocated). Also what
    ``test_llama_block_counts_equal_the_functions_they_replace[trinity-mini-
    kv_bytes_per_token]`` and ``test_head_dim_is_honoured...[trinity-mini]``
    assert, for a model in which ONE layer of five keeps a token's K and V
    for as long as the row lives, and attention has a gate as wide as q."""
    import jax

    from finchat_tpu.engine.engine import create_state, window_pool_pages
    from finchat_tpu.engine.kv_cache import page_hbm_bytes
    from finchat_tpu.models.llama import init_params, n_params
    from finchat_tpu.utils.config import EngineConfig

    p, mem = afmoe.param_counts(FILE), FILE["memory"]["params"]
    assert p["attention"] == 3 * 2048 * 4096 + 2 * 2048 * 512 + 256 == mem["attention_with_gate"]
    assert p["expert"] == 3 * 2048 * 1024 == 6_291_456 == mem["expert"] == p["shared"]
    assert p["routed"] == 128 * p["expert"] == mem["routed_experts_a_layer"]
    assert p["router"] == 2049 * 128 == mem["router_with_bias"]
    assert p["dense_layer"] == 65_020_160 == mem["dense_layer"]
    assert p["routed_layer"] == 839_131_520 == mem["routed_layer"]
    assert p["embed"] + p["head"] == 2 * 200192 * 2048 == mem["embedding_and_head"]
    assert p["total"] == 4_241_534_720 == mem["total"]
    assert p["layers"] == p["dense_layer"] + 4 * p["routed_layer"]
    # the ISSUE's table, in GB of bf16
    gb = lambda n: round(2 * n / 1e9, 3)  # noqa: E731
    assert (gb(p["routed_layer"]), gb(p["dense_layer"]), gb(p["embed"] + p["head"]),
            gb(p["total"])) == (1.678, 0.130, 1.640, 8.483)
    c = afmoe.program_config(FILE)
    tree = jax.eval_shape(lambda: init_params(c, jax.random.key(0)))
    assert p["total"] == n_params(c) == sum(x.size for x in jax.tree.leaves(tree))
    layers, dense = tree["layers"], tree["dense_layers"]
    assert layers["moe_in"].shape == (4, 128, 2048, 2048) and layers["moe_out"].shape == (4, 128, 1024, 2048)
    assert layers["attn_gate"].shape == (4, 2048, 4096) and dense["attn_gate"].shape == (1, 2048, 4096)
    assert layers["attn_q_norm"].shape == (4, 128) and layers["router_bias"].shape == (4, 128)
    assert dense["mlp_gate"].shape == (1, 2048, 6144) and layers["ln_mlp_out"].shape == (4, 2048)
    # a head twice as wide: every projection and the gate, and both head norms
    wide = dict(FILE, head_dim=256)
    assert afmoe.param_counts(wide)["attention"] - p["attention"] \
        == 128 * (3 * 2048 * 32 + 2 * 2048 * 4 + 2)
    assert afmoe.kv_bytes_per_token(wide) == 2 * afmoe.kv_bytes_per_token(FILE)
    assert afmoe.program_config(wide).head_dim == 256

    assert afmoe.kv_bytes_per_token(FILE) == 2048 == FILE["memory"]["kv_bytes_per_token"]
    assert afmoe.kv_bytes_per_token_by_kind(FILE) == {"full": 2048, "window": 4 * 2048}
    assert afmoe.attention_stream_bytes(FILE, kv_tokens=1000) == 1000 * 2048
    assert afmoe.window_stream_bytes(FILE, window_kv_tokens=32 * 2048) == 32 * 2048 * 4 * 2048
    assert afmoe.window_bytes_per_row(FILE, context=100) == 100 * 4 * 2048
    cfg = EngineConfig(**FILE["engine"])
    assert page_hbm_bytes(c, cfg.page_size) == 128 * 2048  # ONE layer's depth
    assert page_hbm_bytes(c, cfg.page_size, kind="window") == 4 * 128 * 2048
    state = jax.eval_shape(lambda: create_state(c, cfg, cfg.max_seq_len // cfg.page_size))
    nbytes = lambda x: x.size * x.dtype.itemsize  # noqa: E731
    assert state.k_pages.shape == state.v_pages.shape == (1, 3072, 128, 512)
    assert nbytes(state.k_pages) + nbytes(state.v_pages) == 3072 * page_hbm_bytes(c, 128) \
        == 805_306_368
    n_win = window_pool_pages(c, cfg)
    assert n_win == (32 + 4) * 18 + 1 == 649 and state.win_table.shape == (32, 18)
    assert state.win_k_pages.shape == (4, 649, 128, 512)
    assert nbytes(state.win_k_pages) + nbytes(state.win_v_pages) \
        == 649 * page_hbm_bytes(c, 128, kind="window") == 680_525_824
    assert state.ssm_state.shape == (1, 1, 1, 1, 1)  # no recurrent state


# --- the yardstick's counts and the readers -----------------------------------

def _context(prom_before=None, prom_after=None, rows=None):
    events = [(0.0, "t", "dispatch", None, "sched", {"rows": [[i, "t", "decode"] for i in range(n)]})
              for n in (rows or [])]
    return Context(w0=0.0, w1=51.0, requests=[], tracer_events=events,
                   prom_before=prom_before or {}, prom_after=prom_after or {},
                   device_trace=None, device={"kind": "TPU v5 lite"}, model=FILE)


def test_the_steps_bytes_follow_the_touched_count_the_full_layer_and_a_window_a_row():
    p = afmoe.param_counts(FILE)
    outside = p["layers"] - 4 * p["routed"]
    head = p["head"]
    counted = _context({}, {"finchat_moe_experts_touched_total": 4 * 100.0 * 10,
                            "finchat_moe_layer_steps_total": 4.0 * 10}, rows=[32, 32])
    assert afmoe.experts_touched(FILE, counted) == 100.0
    assert afmoe.decode_step_stream_bytes(FILE, live_kv_tokens=90_000, ctx=counted) == (
        (outside + 4 * 100 * p["expert"] + head) * 2 + 90_000 * 2048 + 32 * 2048 * 4 * 2048)
    # the ISSUE's estimate: 7.65 GB at 111 touched and 0.30 GB of full-layer K/V
    touched = _context({}, {"finchat_moe_experts_touched_total": 444.0,
                            "finchat_moe_layer_steps_total": 4.0}, rows=[32])
    assert afmoe.decode_step_stream_bytes(FILE, live_kv_tokens=146_000, ctx=touched) / 1e9 \
        == pytest.approx(7.65, abs=0.05)
    # without the counter every expert counts; a longer context adds the ONE full layer's bytes
    assert afmoe.experts_touched(FILE, None) is None and afmoe.experts_touched(FILE, _context()) is None
    assert afmoe.decode_step_stream_bytes(FILE, live_kv_tokens=0) == (
        (p["layers"] + head) * 2 + 32 * 2048 * 4 * 2048)
    assert afmoe.decode_step_stream_bytes(FILE, live_kv_tokens=10_000) \
        - afmoe.decode_step_stream_bytes(FILE, live_kv_tokens=0) == 10_000 * 2048
    # a period's four routed layers under one iteration of the scan
    assert afmoe.routed_layers_a_period(FILE) == 4
    assert afmoe.moe_step_stream_bytes(FILE, rows=32, experts_touched=100.0) \
        == 4 * (100 * p["expert"] + 32 * 2 * 2048) * 2


def test_the_gauge_and_the_ratio_read_the_windows_counters():
    moved = _context({"finchat_moe_experts_touched_total": 1000.0,
                      "finchat_moe_layer_steps_total": 40.0},
                     {"finchat_moe_experts_touched_total": 1000.0 + 4 * 101.5 * 100,
                      "finchat_moe_layer_steps_total": 40.0 + 4 * 100,
                      "finchat_window_kv_bytes": 0.62e9})
    assert read_metric("moe128_experts_touched.sat", moved) == pytest.approx(101.5)
    assert read_metric("window2k_kv_gb.sat", moved) == pytest.approx(0.62)
    for name in ("moe128_experts_touched.sat", "window2k_kv_gb.sat"):
        assert read_metric(name, _context()) is None  # the parent: no counter, no gauge


def _step_ops():
    """Two decode steps' executed operations as a capture names them: the
    leading layer's window walk outside the scan, three window layers' and
    the full layer's in its body, a routed layer's pass — and a ragged round's
    window walk, which is another module's."""
    lead = "jit(decode_step)/jit(main)/swa_attention/"
    body = "jit(decode_step)/jit(main)/while/body/closed_call/"
    named = {
        "%paged_kv_append.1 = (bf16[4,649,128,512]) custom-call(...)": lead + "kv_append/pallas_call",
        "%paged_flash_attention.1 = bf16[32,32,1,128] custom-call(...)":
            lead + "paged_attention/pallas_call",
        "%paged_flash_attention.2 = bf16[32,32,1,128] custom-call(...)":
            body + "swa_attention/paged_attention/pallas_call",
        "%paged_flash_attention.3 = bf16[32,32,1,128] custom-call(...)":
            body + "paged_attention/pallas_call",
        "%moe_experts_step.1 = bf16[32,2048] custom-call(...)":
            body + "moe_experts/jit(moe_experts_step)/pallas_call",
        "%ragged_flash_attention.1 = bf16[8192,32,128] custom-call(...)":
            "jit(ragged_mixed_step)/jit(main)/swa_attention/ragged_paged_attention/pallas_call",
    }
    append, lead_walk, swa_walk, full_walk, experts, ragged = named
    step = [append, lead_walk] + [swa_walk] * 3 + [full_walk, experts]
    ran = step + [ragged] + step
    durations = {append: 5_000, lead_walk: 200_000, swa_walk: 200_000, full_walk: 300_000,
                 experts: 1_500_000, ragged: 9_000_000}
    return named, tuple((0, name, "custom-call", 10_000_000 * i, durations[name])
                        for i, name in enumerate(ran))


def test_the_window_reader_divides_the_windows_bytes_by_the_time_under_its_scope(monkeypatch):
    """``swa2k_kv_roofline.sat``: the dispatches' ``window_kv_tokens`` x 2,048 B
    x 4 sliding layers at the peak, over the time under ``swa_attention`` in
    ONE ``decode_step`` — the leading layer's walk and append outside the scan
    and the three in its body; not the full layer's walk, not the experts, not
    a ragged round's window walk."""
    named, ops = _step_ops()
    notes = {"host": [("finchat.dispatch", 0, 1, {"kind": "decode", "rows": 32,
                                                  "window_kv_tokens": 32 * 2048}),
                      ("finchat.dispatch", 2, 3, {"kind": "decode", "rows": 32,
                                                  "window_kv_tokens": 30 * 2048}),
                      ("finchat.dispatch", 4, 5, {"kind": "mixed", "rows": 3,
                                                  "window_kv_tokens": 5})]}
    monkeypatch.setattr(window_trace.trace_reduce, "find_xplane", lambda _dir: CAPTURE)
    monkeypatch.setattr(window_trace.xplane_scopes, "op_scope_paths", lambda _path: named)
    monkeypatch.setattr(window_trace.xplane_scopes, "device_ops", lambda _path: ops)
    monkeypatch.setattr(window_trace.xplane_scopes, "annotations", lambda _path: notes)
    ctx = _context()
    assert read_metric("swa2k_kv_roofline.sat", ctx) is None  # an untraced run
    ctx.device_trace = trace_reduce.reduce_xplane(CAPTURE)
    ctx.device_trace.modules["jit_decode_step"] = [0.01, 0.01]
    under_ns = 805_000  # a step: 5 + 200 + 3 x 200 us
    nbytes = 31 * 2048 * 4 * 2048
    want = 100.0 * (nbytes / 819e9) / (under_ns / 1e9)
    assert read_metric("swa2k_kv_roofline.sat", ctx) == pytest.approx(want)
    assert 75 < want < 85  # the made-up walk streams at four fifths of the peak
    # a program without the stat (the parent's `window_tokens`), an adapter without the count
    monkeypatch.setattr(window_trace.xplane_scopes, "annotations", lambda _path: {
        "host": [("finchat.dispatch", 0, 1, {"kind": "decode", "window_tokens": 65536})]})
    assert read_metric("swa2k_kv_roofline.sat", ctx) is None
    monkeypatch.setattr(window_trace.xplane_scopes, "annotations", lambda _path: notes)
    ctx.model = {"model_type": "mistral"}
    assert window_trace.read(ctx, scope="swa_attention", module="decode_step",
                             kinds=["decode"]) is None


def test_the_expert_roofline_counts_a_periods_four_layers(monkeypatch):
    """``moe128_expert_roofline.sat`` through the accepted reader: four
    distinct operations under ``moe_experts`` in the scan's body, one a routed
    layer, against the adapter's count of a period's four layers."""
    body = "jit(decode_step)/jit(main)/while/body/closed_call/moe_experts/"
    named = {f"%moe_experts_step.{i} = bf16[32,2048] custom-call(...)": body + "pallas_call"
             for i in range(4)}
    ops = tuple((0, name, "custom-call", 1_000 * i, 1_700_000)
                for i, name in enumerate(list(named) * 3))
    notes = {"host": [("finchat.dispatch", 0, 1, {"kind": "decode", "rows": 32})]}
    monkeypatch.setattr(moe_experts_trace.trace_reduce, "find_xplane", lambda _dir: CAPTURE)
    monkeypatch.setattr(moe_experts_trace.xplane_scopes, "op_scope_paths", lambda _path: named)
    monkeypatch.setattr(moe_experts_trace.xplane_scopes, "device_ops", lambda _path: ops)
    monkeypatch.setattr(moe_experts_trace.xplane_scopes, "annotations", lambda _path: notes)
    ctx = _context({}, {"finchat_moe_experts_touched_total": 4 * 101.0,
                        "finchat_moe_layer_steps_total": 4.0})
    ctx.device_trace = trace_reduce.reduce_xplane(CAPTURE)
    want = 100.0 * (afmoe.moe_step_stream_bytes(FILE, rows=32, experts_touched=101.0) / 819e9) \
        / (4 * 1.7e-3)
    assert read_metric("moe128_expert_roofline.sat", ctx) == pytest.approx(want)
    assert 90 < want < 100


def test_a_capture_without_the_new_scopes_reads_nothing(monkeypatch):
    """Mixtral's decode capture, as a program without window layers gives for
    any cell: no ``swa_attention`` scope, no ``window_kv_tokens``. The readers
    return None and do not raise."""
    monkeypatch.setattr(scope_trace.trace_reduce, "find_xplane", lambda _dir: CAPTURE)
    monkeypatch.setattr(window_trace.trace_reduce, "find_xplane", lambda _dir: CAPTURE)
    ctx = _context()
    ctx.device_trace = trace_reduce.reduce_xplane(CAPTURE)
    for name in ("swa2k_share.sat", "swa2k_kv_roofline.sat"):
        assert read_metric(name, ctx) is None, name
    # pointed at a scope the capture does hold, the new reader still wants its stat
    ctx.device_trace.modules.setdefault("jit_decode_step", [0.01])
    assert window_trace.read(ctx, scope="paged_attention", module="decode_step",
                             kinds=["decode"]) is None
    # Mixtral's capture has `moe_router` and `moe_experts`: the share's reader reads it
    assert read_metric("moe128_share.sat", ctx) > 0


OWN_CAPTURE = HERE / "trinity_decode_v5e.xplane.pb"


def test_the_readers_on_a_slice_of_the_cells_own_capture(monkeypatch):
    """Two whole decode steps cut from the traced run of the cell on the chip
    (PR 47, call 5, seed 2147447400; ``tests/perfbench/slice_capture.py``):
    the four capture metrics read on the slice what the whole capture read
    (64.44, 83.20, 11.51, 52.90), the scopes are there by name, and the
    dispatch's annotation carries ``window_kv_tokens`` = 32 rows x 2,048."""
    from perfbench import xplane_scopes

    for module in (scope_trace, window_trace, moe_experts_trace):
        monkeypatch.setattr(module.trace_reduce, "find_xplane", lambda _dir: OWN_CAPTURE)
    ctx = _context({}, {"finchat_moe_experts_touched_total": 4 * 92.8551,
                        "finchat_moe_layer_steps_total": 4.0})
    ctx.device_trace = trace_reduce.reduce_xplane(OWN_CAPTURE)
    assert len(ctx.device_trace.modules["jit_decode_step"]) == 2
    assert read_metric("decode_step_ms.sat", ctx) == pytest.approx(10.76, abs=0.05)
    assert read_metric("moe128_share.sat", ctx) == pytest.approx(64.4, abs=0.3)
    assert read_metric("moe128_expert_roofline.sat", ctx) == pytest.approx(83.5, abs=0.5)
    assert read_metric("swa2k_share.sat", ctx) == pytest.approx(11.5, abs=0.2)
    assert read_metric("swa2k_kv_roofline.sat", ctx) == pytest.approx(52.8, abs=0.5)
    noted = [stats for events in xplane_scopes.annotations(str(OWN_CAPTURE)).values()
             for _name, _start, _end, stats in events if "window_kv_tokens" in stats]
    assert noted and all(s["window_kv_tokens"] == 32 * 2048 and s["rows"] == 32 for s in noted)
    paths = set(xplane_scopes.op_scope_paths(str(OWN_CAPTURE)).values())
    for scope in ("swa_attention/paged_attention", "swa_attention/kv_append", "moe_experts",
                  "moe_router", "moe_shared", "attn_o", "attn_qkv", "mlp", "head"):
        assert any(f"/{scope}/" in p for p in paths), scope
    assert not any("/yoco_attention/" in p for p in paths)
    # the full layer's walk is under its own `paged_attention`, outside `swa_attention`
    assert any("/paged_attention/" in p and "/swa_attention/" not in p for p in paths)
