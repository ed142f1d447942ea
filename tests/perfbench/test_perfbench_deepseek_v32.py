"""``model_type`` "deepseek_v32" (PR 40): its configuration file against the
catalog row's published keys, the counts its adapter brings against the
program's own parameter tree and page pool, the step's bytes and the
attention call's roofline with a made-up context, the readers of its nine
metrics, ``sparse_control.py``'s own ragged path at a test's size — and what
the parametrised cases of ``test_perfbench_model_adapters.py`` that cannot
pass for this file (they assume K and V heads) assert otherwise."""

import dataclasses
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import sparse_control, trace_reduce
from perfbench.layer_metrics import Context, read_metric
from perfbench.layer_metrics.readers import latent_trace, scope_trace
from perfbench.models import adapter
from perfbench.models import deepseek_v32 as ds

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
FILE = json.loads((ROOT / "perfbench/configs/deepseek-v3.2-exp.json").read_text())
CELL = "deepseek-v32-report-saturated"
CAPTURE = HERE / "decode_scoped_v5e.xplane.pb"  # Mixtral's decode: no latent scope, no counter
OURS = ["mla_share.sat", "dsa_share.sat", "mla_attn_roofline.sat", "dsa_index_roofline.sat",
        "dsa_selected_tokens.sat", "latent_kv_gb.sat", "moe256_share.sat",
        "moe256_experts_touched.sat", "moe256_expert_roofline.sat"]
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CUT = {"n_routed_experts": (256, 16), "first_k_dense_replace": (3, 1),
       "num_hidden_layers": (61, 5), "vocab_size": (129280, 64640),
       "num_nextn_predict_layers": (1, 0)}

# the catalog row's `config` (guide model-configs, architectures.jsonl,
# `DeepSeek-V3.2-Exp`), key for key, but the five keys that are cut
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "hidden_act": "silu", "hidden_size": 7168,
    "index_head_dim": 128, "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840, "model_type": "deepseek_v32",
    "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_key_value_heads": 128, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
}


# --- the configuration file ---------------------------------------------------

def test_the_file_holds_the_published_keys_and_cuts_shares_and_depth_alone():
    assert adapter(FILE) is ds
    assert {k: FILE[k] for k in PUBLISHED} == PUBLISHED
    if CATALOG.exists():  # the row itself, where the guide is installed
        row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
                   if '"name": "DeepSeek-V3.2-Exp"' in line)
        assert {k: v for k, v in row["config"].items() if k not in CUT} \
            == {k: FILE[k] for k in row["config"] if k not in CUT} == PUBLISHED
        assert FILE["source"] == row["source_url"]
        assert {k: FILE["reduced"][k]["from"] for k in CUT} == {k: row["config"][k] for k in CUT}
    assert list(FILE["reduced"]) == list(CUT)
    for key, (was, now) in CUT.items():
        cut = FILE["reduced"][key]
        assert (cut["from"], cut["to"], FILE[key]) == (was, now, now) and len(cut["why"]) > 40
    # no width is cut, by the adapter's list and by the contract's rule on names
    assert not set(FILE["reduced"]) & set(ds.WIDTH_KEYS)
    assert not [k for k in FILE["reduced"] if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
    assert set(ds.WIDTH_KEYS) >= {k for k in PUBLISHED if k.endswith(("_dim", "_rank"))} | {
        "hidden_size", "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
        "index_topk", "index_n_heads"}
    entry = next(c for c in BENCH["configs"] if c["name"] == "deepseek-v3.2-exp")
    assert entry["reduced"] == list(FILE["reduced"]) and entry["source"] == FILE["source"]
    assert entry is BENCH["configs"][-1] and FILE["dtype"] == "bfloat16"
    # the six cells differ in the block alone
    mistral = json.loads((ROOT / "perfbench/configs/mistral-7b-v0.3.json").read_text())
    assert FILE["engine"] == mistral["engine"]
    assumed = " ".join(FILE["assumed"])
    for said in ("pre-norm", "ABSORBED", "0.135234", "HALF-SPLIT", "beta_slow = 1", "EXACT",
                 "Hadamard", "fp8", "NOT in the gate", "normalised over all eight picks",
                 "normal x 0.1", "padded to 640", "1,408", "served context 16,384"):
        assert said in assumed, said
    for said in ("16 chips share each layer", "0.5 tokens", "sees 8", "6.4", "WITHOUT its exchange"):
        assert said in FILE["deployment"], said
    assert "0.5 tokens" in FILE["reduced"]["n_routed_experts"]["why"]
    assert "16 stayed" in FILE["reduced"]["n_routed_experts"]["why"]


def test_the_cell_and_its_metrics_are_declared_last_with_their_reader_files():
    assert BENCH["workloads"][-1] == {
        "name": CELL, "config": "deepseek-v3.2-exp", "traffic": "report-backlog", "chips": 1,
        "why": BENCH["workloads"][-1]["why"]}
    assert len(BENCH["workloads"][-1]["why"]) <= 200
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"]][-len(OURS):] == OURS
    for name in OURS:
        metric = declared[name]
        assert metric["workloads"] == [CELL] and metric["moves"] == "output_tok_s"
        spec = json.loads((ROOT / f"perfbench/layer_metrics/{name}.json").read_text())
        assert (ROOT / f"perfbench/layer_metrics/readers/{spec['reader']}.py").exists()
    assert {declared[n]["unit"] for n in OURS if "roofline" in n or "share" in n} == {"%"}
    # nothing that was there is gone; the one accepted entry that changed is
    # attn_kv_roofline.sat, which now lists the five cells whose decode
    # attention is the custom call it times (the latent decode makes none)
    older = [w["name"] for w in BENCH["workloads"]][:5]
    assert older == ["mixtral-report-saturated", "mistral7b-report-saturated",
                     "falcon-h1-report-saturated", "olmo-hybrid-report-saturated",
                     "granite-h-small-report-saturated"]
    assert declared["attn_kv_roofline.sat"]["workloads"] == older
    assert declared["moe_expert_roofline.sat"]["workloads"] == ["granite-h-small-report-saturated"]
    # the three moe256_* are the accepted readers under new names
    for ours, theirs in (("moe256_share.sat", "moe_sparse_share.sat"),
                         ("moe256_experts_touched.sat", "moe_experts_touched.sat"),
                         ("moe256_expert_roofline.sat", "moe_expert_roofline.sat")):
        assert json.loads((ROOT / f"perfbench/layer_metrics/{ours}.json").read_text()) \
            == json.loads((ROOT / f"perfbench/layer_metrics/{theirs}.json").read_text())


def test_program_config_carries_every_published_number():
    """What ``test_program_config_carries_the_published_keys[deepseek-v3.2-exp]``
    asserts, with what it cannot: the published ``num_key_value_heads`` is 128
    and the program's latent cache holds ONE row a token for all heads
    (``n_kv_heads`` 1); a head's width is 128 + 64 and the file has no
    ``head_dim``; ``hidden_dim`` is ONE routed expert's width."""
    c = ds.program_config(FILE)
    assert (c.dim, c.n_heads, c.n_kv_heads, c.head_dim, c.vocab_size, c.n_layers) == (
        7168, 128, 1, 192, 64640, 5)
    assert (c.hidden_dim, c.dense_hidden_dim, c.leading_dense_layers, c.n_scan_layers) == (
        2048, 18432, 1, 4)
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim) == (
        1536, 512, 128, 64, 128)
    assert (c.index_heads, c.index_head_dim, c.index_topk) == (64, 128, 2048)
    assert c.n_experts == FILE["n_routed_experts"] == 16 and c.moe_router_width == 256
    assert (c.top_k_experts, c.moe_shared_dim, c.moe_fused_glu, c.moe_sparse) == (8, 2048, True, True)
    assert (c.moe_score, c.moe_select_bias, c.moe_groups, c.moe_topk_groups, c.moe_gate_scale,
            c.moe_norm_picks) == ("sigmoid", True, 8, 4, 2.5, True)
    assert c.max_seq_len == FILE["engine"]["max_seq_len"] and c.rope_theta == 10000
    assert c.rope_scaling == (40.0, 4096, 32.0, 1.0, 1.0, 1.0) and not c.tie_embeddings
    assert c.attention_scale == pytest.approx(0.135234, rel=1e-5)
    assert c.kv_row_widths == (640, 128) and c.n_attn_layers == 5 and not c.has_state
    for key, value in (("attention_bias", True), ("scoring_func", "softmax"),
                       ("num_nextn_predict_layers", 1)):
        with pytest.raises(ValueError, match=key):
            ds.program_config(dict(FILE, **{key: value}))


def test_the_counts_are_the_programs_own():
    """The adapter's arithmetic against what the program builds: the
    parameter tree and the page pool's two arrays, by shapes (nothing is
    allocated). Also what ``test_llama_block_counts_equal_the_functions_they_
    replace[deepseek-v3.2-exp-*]`` and ``test_head_dim_is_honoured_where_the_
    file_has_it`` assert, for a model whose pages hold no K or V heads."""
    import jax

    from finchat_tpu.engine.engine import create_state
    from finchat_tpu.engine.kv_cache import page_hbm_bytes
    from finchat_tpu.models.llama import init_params, n_params
    from finchat_tpu.utils.config import EngineConfig

    p, mem = ds.param_counts(FILE), FILE["memory"]
    assert p["attention"] == 187_107_328 == mem["attention_params"]
    assert p["indexer"] == 1536 * 8192 + 7168 * 128 + 256 + 7168 * 64 == 13_959_424
    assert p["expert"] == 3 * 7168 * 2048 == 44_040_192 == mem["expert_params"]
    assert p["dense_layer"] == 597_442_816 == mem["dense_layer_params"]
    assert p["routed_layer_outside_experts"] == 246_956_544
    assert p["routed_layer"] == 246_956_544 + 16 * 44_040_192 == 951_599_616
    assert p["layers"] == p["dense_layer"] + 4 * p["routed_layer"] and 5 * p["layer"] == p["layers"]
    assert p["embed"] == p["head"] == 64640 * 7168  # half of the vocabulary
    assert p["total"] == 5_330_527_488 == mem["total_params"]  # 10.66 GB in bf16
    c = ds.program_config(FILE)
    tree = jax.eval_shape(lambda: init_params(c, jax.random.key(0)))
    assert p["total"] == n_params(c) == sum(x.size for x in jax.tree.leaves(tree))
    assert tree["layers"]["moe_in"].shape == (4, 16, 7168, 4096)
    assert tree["layers"]["router"].shape == (4, 7168, 256)
    assert tree["layers"]["router_bias"].shape == (4, 256)
    assert tree["dense_layers"]["mlp_gate"].shape == (1, 7168, 18432)
    assert {v.shape[0] for v in tree["dense_layers"].values()} == {1}
    # the whole model by the same counts is the card's 671B
    whole = ds.param_counts({**FILE, **{k: was for k, (was, _now) in CUT.items()},
                             "num_nextn_predict_layers": 0, "reduced": {}})["total"]
    assert 671.5e9 < whole < 672.5e9

    # 1,408 logical bytes a token a layer: a latent row of 576 and an index key of 128
    assert ds.latent_row_bytes(FILE) == 1152 and ds.index_key_bytes(FILE) == 256
    assert ds.kv_bytes_per_token(FILE) == 5 * 1408 == mem["kv_bytes_per_token"]
    assert ds.attention_stream_bytes(FILE, kv_tokens=1000) == 1000 * 1408
    assert ds.kv_bytes_per_token(FILE) == 5 * ds.attention_stream_bytes(FILE, kv_tokens=1)
    # the pool allocates the row padded to whole lane tiles: 640 + 128 columns
    cfg = EngineConfig(**FILE["engine"])
    assert page_hbm_bytes(c, cfg.page_size) == 5 * cfg.page_size * (640 + 128) * 2
    state = jax.eval_shape(lambda: create_state(c, cfg, cfg.max_seq_len // cfg.page_size))
    nbytes = lambda x: x.size * x.dtype.itemsize  # noqa: E731
    assert state.k_pages.shape == (5, cfg.num_pages, cfg.page_size, 640)
    assert state.v_pages.shape == (5, cfg.num_pages, cfg.page_size, 128)
    assert nbytes(state.k_pages) + nbytes(state.v_pages) \
        == cfg.num_pages * page_hbm_bytes(c, cfg.page_size) == 1_572_864_000
    assert state.ssm_state.size == 1  # no recurrent state: the placeholders


def _context(prom_before=None, prom_after=None, rows=None):
    events = [(0.0, "t", "dispatch", None, "sched", {"rows": [[i, "t", "decode"] for i in range(n)]})
              for n in (rows or [])]
    return Context(w0=0.0, w1=51.0, requests=[], tracer_events=events,
                   prom_before=prom_before or {}, prom_after=prom_after or {},
                   device_trace=None, device={"kind": "TPU v5 lite"}, model=FILE)


def test_the_steps_bytes_count_touched_experts_and_selected_rows_never_the_context():
    p = ds.param_counts(FILE)
    outside = (p["dense_layer"] + 4 * p["routed_layer_outside_experts"] + p["head"]) * 2
    # without a context: every held expert, index_topk rows for each of the slots
    assert ds.decode_step_stream_bytes(FILE, live_kv_tokens=100_000, ctx=None) \
        == outside + 4 * 16 * p["expert"] * 2 + 5 * (100_000 * 256 + 16 * 2048 * 1152)
    # a longer context adds its index keys alone: the selection stays index_topk
    assert ds.decode_step_stream_bytes(FILE, live_kv_tokens=200_000, ctx=None) \
        - ds.decode_step_stream_bytes(FILE, live_kv_tokens=100_000, ctx=None) == 5 * 100_000 * 256
    ctx = _context(
        {"finchat_moe_experts_touched_total": 100.0, "finchat_moe_layer_steps_total": 40.0,
         "finchat_dsa_selected_tokens_total": 0.0, "finchat_dsa_row_layer_steps_total": 0.0},
        {"finchat_moe_experts_touched_total": 100.0 + 6.5 * 400,
         "finchat_moe_layer_steps_total": 40.0 + 400,
         "finchat_dsa_selected_tokens_total": 2000.0 * 6000,
         "finchat_dsa_row_layer_steps_total": 6000.0}, rows=[12, 12])
    assert ds.experts_touched(FILE, ctx) == 6.5 and ds.selected_tokens(FILE, ctx) == 2000.0
    assert ds.experts_touched(FILE, None) is None and ds.selected_tokens(FILE, _context()) is None
    assert ds.decode_step_stream_bytes(FILE, live_kv_tokens=80_000, ctx=ctx) \
        == outside + 4 * 6.5 * p["expert"] * 2 + 5 * (80_000 * 256 + 12 * 2000 * 1152)
    # the ISSUE's estimate of a step: about 5.65 GB of weights at 6.4 touched and an eighth of
    # the vocabulary (this file holds a half: + 0.70 GB of head), and beside
    # them 80k index keys and 16 x 2,048 selected rows in each of 5 layers (0.10 + 0.19 GB)
    ctx64 = _context({}, {"finchat_moe_experts_touched_total": 64.0,
                          "finchat_moe_layer_steps_total": 10.0}, rows=[16])
    assert ds.decode_step_stream_bytes(FILE, live_kv_tokens=80_000, ctx=ctx64) / 1e9 \
        == pytest.approx(5.65 + 0.70 + 0.10 + 0.19, abs=0.05)  # + the half-vocabulary head over the eighth's
    # one routed layer's touched pass, as moe_experts_trace.py asks for it
    assert ds.moe_step_stream_bytes(FILE, rows=16, experts_touched=6.5) \
        == (6.5 * 44_040_192 + 16 * 2 * 7168) * 2


def test_the_attention_calls_roofline_keeps_both_counts():
    """128 heads over one 1,152-byte row: 278,528 FLOP a row, 242 FLOP a byte
    against a ridge of 240 — the bound is the larger of the two times."""
    nbytes, flops = ds.mla_attention_cost(FILE, rows=1, selected=1)
    assert (nbytes, flops) == (1152, 128 * 2 * (576 + 512)) and flops == 278_528
    assert 241 < flops / nbytes < 243
    bound = ds.mla_attention_bound_s(FILE, rows=16, selected=2048)
    assert bound == pytest.approx(max(16 * 2048 * 1152 / 819e9, 16 * 2048 * 278_528 / 197e12))
    assert bound == pytest.approx(46.3e-6, rel=0.01)  # the MXU's time, by a hair
    assert ds.index_stream_bytes(FILE, kv_tokens=80_000) == 80_000 * 256


def test_the_counters_ratios_and_the_pools_gauge_read_the_window():
    moved = _context({}, {"finchat_dsa_selected_tokens_total": 2048.0 * 800,
                          "finchat_dsa_row_layer_steps_total": 800.0,
                          "finchat_moe_experts_touched_total": 640.0,
                          "finchat_moe_layer_steps_total": 100.0,
                          "finchat_kv_pool_bytes": 1_572_864_000.0})
    assert read_metric("dsa_selected_tokens.sat", moved) == 2048.0
    assert read_metric("moe256_experts_touched.sat", moved) == 6.4
    assert read_metric("latent_kv_gb.sat", moved) == pytest.approx(1.572864)
    for name in ("dsa_selected_tokens.sat", "moe256_experts_touched.sat", "latent_kv_gb.sat"):
        assert read_metric(name, _context()) is None  # the parent: no counters, no gauge


def test_a_capture_without_the_new_scopes_reads_nothing(monkeypatch):
    """Mixtral's decode capture, as the parent's program would give for any
    cell: no ``mla_*`` / ``dsa_*`` scope and no counter. The new readers
    return None and do not raise — with the counters moved too, for there is
    no operation under the scope to time."""
    monkeypatch.setattr(scope_trace.trace_reduce, "find_xplane", lambda _dir: CAPTURE)
    monkeypatch.setattr(latent_trace.trace_reduce, "find_xplane", lambda _dir: CAPTURE)
    ctx = _context()
    ctx.device_trace = trace_reduce.reduce_xplane(CAPTURE)
    for name in ("mla_share.sat", "dsa_share.sat", "mla_attn_roofline.sat",
                 "dsa_index_roofline.sat", "moe256_expert_roofline.sat"):
        assert read_metric(name, ctx) is None, name
    ctx.prom_after = {"finchat_dsa_selected_tokens_total": 2048.0,
                      "finchat_dsa_row_layer_steps_total": 1.0}
    assert read_metric("mla_attn_roofline.sat", ctx) is None
    # a scope the capture does hold, read the new reader's way: the time
    # under `paged_attention` in one layer of one step against the bound
    ctx.device_trace.modules.setdefault("jit_decode_step", [0.01])
    got = latent_trace.read(ctx, quantity="attention_roofline", scope="paged_attention",
                            module="decode_step", kinds=["decode"])
    assert got is not None and got > 0
    with pytest.raises(ValueError, match="cannot read"):
        latent_trace.read(ctx, quantity="other", scope="paged_attention",
                          module="decode_step", kinds=["decode"])


def test_sparse_controls_ragged_path_reads_the_reference_with_the_selection_active():
    """``sparse_control.py``'s own packing at a test's size: a prompt of five
    chunks (60 tokens against ``index_topk`` 24), 8 forced tokens, two mixed
    rounds — every position at the reference; and its two controls are not."""
    import jax
    import jax.numpy as jnp

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.kv_cache import PageAllocator
    from finchat_tpu.models.llama import init_params
    from finchat_tpu.utils.config import EngineConfig
    from tests.test_deepseek_v32 import FILE as TINY

    c = dataclasses.replace(ds.program_config(TINY), dtype=jnp.float32)
    params = init_params(c, jax.random.key(0))
    cfg = EngineConfig(max_seqs=4, page_size=16, num_pages=64, max_seq_len=256, prefill_chunk=12)
    sched = SimpleNamespace(engine=InferenceEngine(c, params, cfg, attn_backend="ref"),
                            free_slots=[0, 1, 2, 3], allocator=PageAllocator(64))
    tokens = [int(t) for t in np.random.RandomState(3).randint(0, 300, size=68)]
    positions = list(range(59, 68))
    want, _ = ds.reference_logits(params, tokens, TINY, positions=positions)
    got = sparse_control.ragged_path_logits(sched, tokens[:60], tokens[60:])
    assert [i for i, _g in got] == list(range(9))
    assert max(np.abs(g[:300] - np.asarray(want[i])).max() for i, g in got) < 2e-4
    assert sched.allocator.used_count == 0
    for name in sparse_control.CONTROLS:
        control, _ = ds.reference_logits(params, tokens, TINY, positions=positions, variant=name)
        assert np.abs(np.asarray(control) - np.asarray(want)).max() > 0.5
