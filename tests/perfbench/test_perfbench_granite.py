"""``model_type`` "granitemoehybrid" (PR 34): its configuration file against
the catalog row's published keys, the counts its adapter brings against the
program's own parameter tree, page pool and recurrent state, the step's bytes
with a made-up context, the readers of its five metrics — and what the three
parametrised cases of ``test_perfbench_model_adapters.py`` that cannot pass
for this file assert otherwise."""

import json
from pathlib import Path

import pytest

from perfbench import costs, trace_reduce
from perfbench.layer_metrics import Context, read_metric
from perfbench.layer_metrics.readers import moe_experts_trace, prom_ratio, scope_trace
from perfbench.models import adapter
from perfbench.models import granitemoehybrid as granite

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
FILE = json.loads((ROOT / "perfbench/configs/granite-4.0-h-small.json").read_text())
CELL = "granite-h-small-report-saturated"
CAPTURE = HERE / "decode_scoped_v5e.xplane.pb"  # Mixtral's decode: dense dispatch, no mixer
OURS = ["moe_sparse_share.sat", "moe_experts_touched.sat", "moe_expert_roofline.sat",
        "mamba_share.sat", "mamba_state_roofline.sat", "mamba_state_gb.sat"]
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4

# the catalog row's `config` (guide model-configs, architectures.jsonl,
# `granite-4.0-h-small`), key for key, but the three keys that are cut
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 768, "logits_scaling": 16,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10, "num_key_value_heads": 8,
    "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 1536,
    "tie_word_embeddings": True, "vocab_size": 100352,
}


# --- the configuration file ---------------------------------------------------

def test_the_file_holds_the_published_keys_and_cuts_a_share_and_depth_alone():
    assert adapter(FILE) is granite
    assert {k: FILE[k] for k in PUBLISHED} == PUBLISHED
    if CATALOG.exists():  # the row itself, where the guide is installed
        row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
                   if '"granite-4.0-h-small"' in line)
        cut = set(FILE["reduced"])
        assert {k: v for k, v in row["config"].items() if k not in cut} \
            == {k: FILE[k] for k in row["config"] if k not in cut} == PUBLISHED
        assert FILE["source"] == row["source_url"]
        assert {k: FILE["reduced"][k]["from"] for k in cut} == {k: row["config"][k] for k in cut}
    assert list(FILE["reduced"]) == ["num_local_experts", "num_hidden_layers", "layer_types"]
    held, depth = FILE["reduced"]["num_local_experts"], FILE["reduced"]["num_hidden_layers"]
    assert (held["from"], held["to"]) == (72, 36) and FILE["num_local_experts"] == 36
    assert (depth["from"], depth["to"]) == (40, 10) and FILE["num_hidden_layers"] == 10
    assert FILE["layer_types"] == PERIOD and FILE["reduced"]["layer_types"]["from"] == PERIOD * 4
    assert not set(FILE["reduced"]) & set(granite.WIDTH_KEYS)
    assert set(granite.WIDTH_KEYS) >= {
        "intermediate_size", "shared_intermediate_size", "num_experts_per_tok", "head_dim",
        *(k for k in PUBLISHED if k.startswith("mamba_") and not k.endswith("_bias"))}
    entry = next(c for c in BENCH["configs"] if c["name"] == "granite-4.0-h-small")
    assert entry["reduced"] == list(FILE["reduced"]) and entry["source"] == FILE["source"]
    assert FILE["ssm_state_dtype"] == "float32" and FILE["dtype"] == "bfloat16"
    # the five cells differ in the block alone
    mistral = json.loads((ROOT / "perfbench/configs/mistral-7b-v0.3.json").read_text())
    assert FILE["engine"] == mistral["engine"]
    assumed = " ".join(FILE["assumed"])
    for said in ("ONE routed expert's width", "pre-norm", "softmax over the CHOSEN logits only",
                 "the first half gates", "normalised over all ten picks", "WITH bias",
                 "the gate BEFORE the norm", "NO rotation", "2^-7", "ssm_state_dtype float32",
                 "A_log = log U[1, 16]", "served context 16,384", "blocks of 128"):
        assert said in assumed, said
    for said in ("2 chips", "8 chips", "2.2 tokens", "4.4"):
        assert said in FILE["deployment"], said
    assert "2.2 tokens" in held["why"] and "NOT cut" in depth["why"]
    # each limit lies between the program's largest and the int8 control's
    # smallest reading on the chip (PERF.md section 4); it is the median that
    # holds the control (the worst positions nearly meet). The state's own
    # check stands between float32's 23 mantissa bits and bfloat16's 7
    tol = FILE["logits_tolerance"]
    assert 0.022759 < tol["median"] < 0.086829 and 0.074843 < tol["max"] < 0.101193
    assert "NOT CAUGHT" in tol["set_from"] and "int8" in tol["set_from"]
    assert 7 < FILE["state_check"]["kept_mantissa_bits_min"] < 23


def test_the_cell_and_its_metrics_are_declared_with_their_reader_files():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-small", "report-backlog", 1)
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    for name in OURS:
        metric = declared[name]
        assert metric["workloads"] == [CELL] and metric["moves"] == "output_tok_s"
        spec = json.loads((ROOT / f"perfbench/layer_metrics/{name}.json").read_text())
        assert (ROOT / f"perfbench/layer_metrics/readers/{spec['reader']}.py").exists()
    assert declared["moe_experts_touched.sat"]["layer"] == "engine steps"
    assert {declared[n]["unit"] for n in OURS if "roofline" in n or "share" in n} == {"%"}
    # nothing that was there is gone, and the older cells' lists are as they were
    assert [w["name"] for w in BENCH["workloads"]][:4] == [
        "mixtral-report-saturated", "mistral7b-report-saturated",
        "falcon-h1-report-saturated", "olmo-hybrid-report-saturated"]
    assert declared["ssm_share.sat"]["workloads"] == ["falcon-h1-report-saturated"]
    assert declared["moe_share.sat"]["workloads"] == ["mixtral-report-saturated"]


def test_program_config_carries_every_published_number():
    """What ``test_program_config_carries_the_published_keys[granite-4.0-h-small]``
    asserts, with the one thing it cannot: the published file has ``rope_theta``
    10000 AND ``position_embedding_type`` "nope", and the program's "no
    rotation" is ``rope_theta`` None."""
    from finchat_tpu.models.llama import FULL, MAMBA

    c = granite.program_config(FILE)
    assert (c.dim, c.hidden_dim, c.n_heads, c.n_kv_heads, c.head_dim, c.vocab_size, c.n_layers) == (
        4096, 768, 32, 8, 128, 100352, 10)
    assert c.n_experts == FILE["num_local_experts"] == 36 and c.moe_router_width == 72
    assert (c.top_k_experts, c.moe_shared_dim, c.moe_fused_glu) == (10, 1536, True)
    assert c.max_seq_len == FILE["engine"]["max_seq_len"]
    assert c.rope_theta is None and FILE["rope_theta"] == 10000  # nope: the theta is unused
    assert c.attention_scale == 2 ** -7 and c.residual_multiplier == 0.22
    assert c.embedding_multiplier == 12 and c.lm_head_multiplier == 1 / 16
    assert c.layer_pattern == (MAMBA,) * 5 + (FULL,) + (MAMBA,) * 4 and c.tie_embeddings
    assert (c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_groups, c.ssm_conv) == (128, 64, 128, 1, 4)
    assert c.state_shape == (128, 64, 128) and c.conv_shape == (3, 8448) and c.ssm_in_dim == 16768
    assert c.moe_sparse and (c.n_attn_layers, c.n_state_layers) == (1, 9)
    assert granite.program_config(dict(FILE, head_dim=256)).head_dim == 256
    with pytest.raises(ValueError, match="layer_types"):
        granite.program_config(dict(FILE, num_hidden_layers=9))
    with pytest.raises(ValueError, match="attention_bias"):
        granite.program_config(dict(FILE, attention_bias=True))
    with pytest.raises(ValueError, match="nope"):
        granite.program_config(dict(FILE, position_embedding_type="rope"))
    with pytest.raises(ValueError, match="mamba_expand"):
        granite.program_config(dict(FILE, mamba_n_heads=64))


def test_the_counts_are_the_programs_own():
    """The adapter's arithmetic against what the program builds: the parameter
    tree, the page pool (the attention layer's alone) and the recurrent state
    (the nine mamba layers'), by shapes: nothing is allocated. Also what
    ``test_llama_block_counts_equal_the_functions_they_replace[...-kv_bytes_per_token]``
    and ``test_head_dim_is_honoured_where_the_file_has_it`` assert, for a
    model in which ONE layer of ten owns K/V heads."""
    import jax

    from finchat_tpu.engine.engine import create_state
    from finchat_tpu.engine.kv_cache import page_hbm_bytes
    from finchat_tpu.models.llama import init_params, n_params
    from finchat_tpu.utils.config import EngineConfig

    p = granite.param_counts(FILE)
    assert p["expert"] == 1536 * 4096 + 4096 * 768 == 9_437_184
    assert p["routed"] == 36 * p["expert"] == 339_738_624
    assert p["mixer"] == (4096 * 16768 + 8192 * 4096 + 5 * 8448 + 3 * 128 + 8192) == 102_286_976
    assert p["attention"] == 2 * 4096 * 4096 + 2 * 4096 * 1024 == 41_943_040
    assert p["shared"] == 3 * 4096 * 1536 and p["router"] == 4096 * 72
    assert (p["mamba_layer"], p["attention_layer"]) == (121_464_448, 61_120_512)
    assert p["layers"] == 9 * p["mamba_layer"] + p["attention_layer"] + 10 * p["routed"] \
        == 4_551_686_784 == FILE["memory"]["period_params"]
    assert p["head"] == 0 and p["embed"] == 100352 * 4096  # tied
    assert 10 * p["layer"] == p["layers"]
    c = granite.program_config(FILE)
    tree = jax.eval_shape(lambda: init_params(c, jax.random.key(0)))
    assert p["total"] == n_params(c) == sum(x.size for x in jax.tree.leaves(tree))
    assert {v.shape[0] for k, v in tree["layers"].items() if k.startswith("attn_")} == {1}
    assert {v.shape[0] for k, v in tree["layers"].items() if k.startswith("ssm_")} == {9}
    assert tree["layers"]["moe_in"].shape == (10, 36, 4096, 1536)
    # the whole model by the same counts is the card's 32B with 9B active
    whole = 40 * 72 * p["expert"] + 36 * p["mamba_layer"] + 4 * p["attention_layer"] + p["embed"]
    assert 32.0e9 < whole < 32.5e9
    active = whole - 40 * 62 * p["expert"]
    assert 8.5e9 < active < 9.5e9

    # 4 KiB a token in the one attention layer; the mamba layers own no pages
    assert granite.attention_stream_bytes(FILE, kv_tokens=1000) == 1000 * 4096
    assert granite.kv_bytes_per_token(FILE) == 4096 == FILE["memory"]["kv_bytes_per_token"]
    wide = dict(FILE, head_dim=256)
    assert granite.kv_bytes_per_token(wide) == 2 * granite.kv_bytes_per_token(FILE)
    assert granite.attention_stream_bytes(wide, kv_tokens=7) \
        == 2 * granite.attention_stream_bytes(FILE, kv_tokens=7)
    assert costs.head_dim(FILE) == 128
    # a wider head widens q, k, v, o of the ONE attention layer: a tenth in the mean layer
    assert granite.param_counts(wide)["layers"] - p["layers"] == 2 * 4096 * (32 + 8) * 128
    cfg = EngineConfig(**FILE["engine"])
    assert page_hbm_bytes(c, cfg.page_size) == cfg.page_size * granite.kv_bytes_per_token(FILE)
    state = jax.eval_shape(lambda: create_state(c, cfg, cfg.max_seq_len // cfg.page_size))
    nbytes = lambda x: x.size * x.dtype.itemsize  # noqa: E731
    assert state.k_pages.shape == (1, cfg.num_pages, cfg.page_size, 1024)
    assert nbytes(state.k_pages) + nbytes(state.v_pages) \
        == cfg.num_pages * cfg.page_size * granite.kv_bytes_per_token(FILE)
    assert state.ssm_state.shape == (9, cfg.max_seqs, 128, 64, 128)
    assert state.conv_state.shape == (9, cfg.max_seqs, 3, 8448)
    row = granite.ssm_state_bytes_per_row(FILE)
    assert row == 128 * 64 * 128 * 4 == 4 * 1024 * 1024
    assert nbytes(state.ssm_state) == 9 * cfg.max_seqs * row
    assert nbytes(state.conv_state) == 9 * cfg.max_seqs * granite.conv_tail_bytes_per_row(FILE)
    assert (nbytes(state.ssm_state) + nbytes(state.conv_state)) / 1e9 == pytest.approx(0.6186, rel=1e-3)


def _context(prom_before=None, prom_after=None, rows=None):
    events = [(0.0, "t", "dispatch", None, "sched", {"rows": [[i, "t", "decode"] for i in range(n)]})
              for n in (rows or [])]
    return Context(w0=0.0, w1=51.0, requests=[], tracer_events=events,
                   prom_before=prom_before or {}, prom_after=prom_after or {},
                   device_trace=None, device={"kind": "TPU v5 lite"}, model=FILE)


def test_the_steps_bytes_count_the_touched_experts_from_the_programs_counters():
    p = granite.param_counts(FILE)
    small = (2 * 8192 + 2 * 128 + 128) * 4
    row_state = 2 * 4 * 1024 * 1024 + small
    # the scope's operations in one iteration of the layer scan: a period's nine mamba layers
    assert granite.ssm_step_stream_bytes(FILE, rows=16) == 9 * 16 * row_state
    # a period's ten routed sub-blocks: the touched experts' weights, the rows in and out
    assert granite.moe_step_stream_bytes(FILE, rows=16, experts_touched=33.0) \
        == 10 * (33.0 * 9_437_184 + 16 * 2 * 4096) * 2
    outside = (9 * p["mamba_layer"] + p["attention_layer"] + p["embed"]) * 2
    state = 9 * 16 * (row_state + 2 * 3 * 8448 * 4)
    kv = 100_000 * 4096
    # without a context: every held expert, the engine's slot count of rows
    assert granite.decode_step_stream_bytes(FILE, live_kv_tokens=100_000, ctx=None) \
        == outside + 10 * 36 * p["expert"] * 2 + kv + state
    # with one: 33.25 experts a layer a step over the window (the counters'
    # difference, not their level), 12 rows a dispatch
    ctx = _context({"finchat_moe_experts_touched_total": 1000.0, "finchat_moe_layer_steps_total": 40.0},
                   {"finchat_moe_experts_touched_total": 1000.0 + 133 * 250,
                    "finchat_moe_layer_steps_total": 40.0 + 4 * 250}, rows=[12, 12, 12])
    assert granite.experts_touched(FILE, ctx) == 33.25 and granite.experts_touched(FILE, None) is None
    assert granite.decode_step_stream_bytes(FILE, live_kv_tokens=100_000, ctx=ctx) \
        == outside + 10 * 33.25 * p["expert"] * 2 + kv + 9 * 12 * (row_state + 2 * 3 * 8448 * 4)
    # the ISSUE's estimate of a step: about 10.9 GB at 33 touched and 85k tokens of KV
    ctx33 = _context({}, {"finchat_moe_experts_touched_total": 330.0,
                          "finchat_moe_layer_steps_total": 10.0}, rows=[16])
    assert granite.decode_step_stream_bytes(FILE, live_kv_tokens=85_000, ctx=ctx33) / 1e9 \
        == pytest.approx(10.9, abs=0.15)


def test_the_counters_ratio_reads_the_window_and_nothing_where_nothing_moved():
    moved = _context({"a_total": 10.0, "b_total": 5.0}, {"a_total": 76.0, "b_total": 7.0})
    assert prom_ratio.read(moved, numerator="a_total", denominator="b_total") == 33.0
    assert prom_ratio.read(_context({"a_total": 1.0}, {"a_total": 9.0}),
                           numerator="a_total", denominator="b_total") is None
    assert read_metric("moe_experts_touched.sat", _context(
        {}, {"finchat_moe_experts_touched_total": 66.0, "finchat_moe_layer_steps_total": 2.0})) == 33.0
    assert read_metric("moe_experts_touched.sat", _context()) is None  # the parent: no counters


def test_the_states_bytes_are_a_metric_of_the_cell_and_say_what_precision_it_is_kept_in():
    """``mamba_state_gb.sat`` reads the program's gauge of the state's own
    bytes: 0.6186 GB is 16 slots x 9 layers in float32 (the file's
    ``ssm_state_dtype``); a state stored in bfloat16 would read 0.31 in every
    traced run, and ``program_config`` refuses such an engine
    (``tests/test_granite_hybrid.py``)."""
    rows = 16 * 9 * (granite.ssm_state_bytes_per_row(FILE) + granite.conv_tail_bytes_per_row(FILE))
    ctx = _context({}, {"finchat_ssm_state_bytes": float(rows)})
    assert read_metric("mamba_state_gb.sat", ctx) == pytest.approx(0.6186, rel=1e-3)
    assert read_metric("mamba_state_gb.sat", ctx) == read_metric("ssm_state_gb.sat", ctx)
    assert FILE["ssm_state_dtype"] == "float32"
    assert granite.ssm_state_bytes_per_row(dict(FILE, ssm_state_dtype="bfloat16")) * 2 \
        == granite.ssm_state_bytes_per_row(FILE)


def test_a_capture_without_the_new_scopes_reads_nothing(monkeypatch):
    """Mixtral's decode capture, as the parent's program would give for any
    cell: no ``moe_group`` / ``moe_shared`` / ``ssm_*`` scope and no counter.
    The two rooflines and ``mamba_share.sat`` return None and do not raise;
    ``moe_sparse_share.sat`` finds the two scopes it shares with
    ``moe_share.sat`` and reads what that reads."""
    monkeypatch.setattr(scope_trace.trace_reduce, "find_xplane", lambda _dir: CAPTURE)
    ctx = _context()
    ctx.device_trace = trace_reduce.reduce_xplane(CAPTURE)
    for name in ("moe_expert_roofline.sat", "mamba_share.sat", "mamba_state_roofline.sat",
                 "moe_experts_touched.sat", "mamba_state_gb.sat"):
        assert read_metric(name, ctx) is None, name
    assert read_metric("moe_sparse_share.sat", ctx) == read_metric("moe_share.sat", ctx) > 0
    # with the counters moved, the experts' roofline reads the capture's
    # `moe_experts` operations against the touched experts' bytes
    ctx.prom_after = {"finchat_moe_experts_touched_total": 330.0,
                      "finchat_moe_layer_steps_total": 10.0}
    got = moe_experts_trace.read(ctx, scope="moe_experts", module="decode_step", kinds=["decode"])
    assert got is not None and got > 0
    ctx.prom_after = {"finchat_moe_experts_touched_total": 165.0,
                      "finchat_moe_layer_steps_total": 10.0}
    half = moe_experts_trace.read(ctx, scope="moe_experts", module="decode_step", kinds=["decode"])
    assert half == pytest.approx(got * (16.5 * 9_437_184 + 16 * 8192) / (33 * 9_437_184 + 16 * 8192),
                                 rel=1e-6)
