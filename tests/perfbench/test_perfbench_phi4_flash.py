"""``model_type`` "phi4flash" (PR 42): its configuration file against the
catalog row's published keys (nothing cut), the counts its adapter brings
against the program's own parameter tree and its two pools, the step's bytes
with a made-up context, the readers of its seven metrics, ``window_control.py``'s
controls at a test's size, that the six accepted configurations emit nothing
new — and what the parametrised cases of ``test_perfbench_model_adapters.py``
that cannot pass for this file (they assume K and V heads in every layer)
assert otherwise."""

import dataclasses
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import trace_reduce
from perfbench.layer_metrics import Context, read_metric
from perfbench.layer_metrics.readers import scope_trace, yoco_trace
from perfbench.models import adapter
from perfbench.models import phi4flash as phi

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
FILE = json.loads((ROOT / "perfbench/configs/phi-4-mini-flash-reasoning.json").read_text())
CELL = "phi4-flash-report-saturated"
CAPTURE = HERE / "decode_scoped_v5e.xplane.pb"  # Mixtral's decode: none of the new scopes
OURS = ["yoco_share.sat", "yoco_kv_roofline.sat", "yoco_layer_reads.sat", "swa_share.sat",
        "window_kv_gb.sat", "mamba1_share.sat", "mamba1_state_gb.sat"]
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
ACCEPTED = ["mistral-7b-v0.3", "mixtral-8x7b-v0.1", "falcon-h1-34b-instruct", "olmo-hybrid-7b",
            "granite-4.0-h-small", "deepseek-v3.2-exp"]

# the catalog row's `config` (guide model-configs, architectures.jsonl,
# `Phi-4-mini-flash-reasoning`), key for key
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
    "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40, "num_hidden_layers": 32,
    "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064,
}


# --- the configuration file ---------------------------------------------------

def test_the_file_holds_the_published_keys_and_cuts_nothing():
    assert adapter(FILE) is phi
    assert {k: FILE[k] for k in PUBLISHED} == PUBLISHED
    if CATALOG.exists():  # the row itself, where the guide is installed
        row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
                   if '"name": "Phi-4-mini-flash-reasoning"' in line)
        assert row["config"] == {k: FILE[k] for k in row["config"]} == PUBLISHED
        assert FILE["source"] == row["source_url"]
    assert FILE["reduced"] == {} and "nothing is cut" in FILE["reduced_why"]
    entry = next(c for c in BENCH["configs"] if c["name"] == "phi-4-mini-flash-reasoning")
    assert entry["reduced"] == [] and entry["source"] == FILE["source"]
    assert entry["file"] == "perfbench/configs/phi-4-mini-flash-reasoning.json"
    assert FILE["dtype"] == "bfloat16" and FILE["ssm_state_dtype"] == "float32"
    assert FILE["engine"] == {"max_seqs": 32, "prefill_chunk": 256, "num_pages": 3072,
                              "page_size": 128, "max_seq_len": 16384}
    kinds = FILE["layer_types"]
    assert kinds[:16] == ["mamba1", "sliding_attention"] * 8
    assert kinds[16:18] == ["mamba1", "full_attention"]
    assert kinds[18:] == ["gmu", "cross_attention"] * 7
    assumed = " ".join(FILE["assumed"])
    for said in ("Mamba-1 (not Mamba-2)", "ceil(hidden_size / 16) = 160", "x BEFORE z",
                 "A_log = log(1 .. 16)", "BEFORE the gate", "j = p // 2", "lam_init = 0.8 - 0.6",
                 "LayerNorm with weight AND bias", "NoPE", "FIRST half gates",
                 "counts the token itself", "biases on W_qkv", "YOCO"):
        assert said in assumed, said
    assert set(phi.WIDTH_KEYS) >= {"hidden_size", "intermediate_size", "sliding_window",
                                   "mamba_d_state", "mamba_expand", "mamba_dt_rank"}
    assert "whole model" in FILE["deployment"] and "32 rows" in FILE["deployment"]


def test_the_cell_and_its_metrics_are_declared_with_their_reader_files():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "phi-4-mini-flash-reasoning",
                    "traffic": "report-backlog-lead40", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "WHOLE model, 32 rows" in cell["why"]
    # ISSUE 42's one fallback: the accepted mix with a lead-in of 40 s and nothing else
    from perfbench.cells import load_traffic

    ours, base = load_traffic("report-backlog-lead40"), load_traffic("report-backlog")
    assert ours.pop("lead_in_s") == 40 and base.pop("lead_in_s") == 20 and ours.pop("lead_in_note")
    assert ours == base
    raw = json.loads((ROOT / "perfbench/traffic/report-backlog-lead40.json").read_text())
    assert set(raw) == {"extends", "lead_in_s", "lead_in_note"}
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(OURS[0])
    assert names[at:at + len(OURS)] == OURS
    for name in OURS:
        metric = declared[name]
        assert metric["workloads"] == [CELL] and metric["moves"] == "output_tok_s"
        spec = json.loads((ROOT / f"perfbench/layer_metrics/{name}.json").read_text())
        assert (ROOT / f"perfbench/layer_metrics/readers/{spec['reader']}.py").exists()
    assert {declared[n]["unit"] for n in OURS if "roofline" in n or "share" in n} == {"%"}
    # nothing that was there is gone or changed: the six cells before this one,
    # in their order, and the accepted lists of cells without this one
    older = [w["name"] for w in BENCH["workloads"]][:6]
    assert older == ["mixtral-report-saturated", "mistral7b-report-saturated",
                     "falcon-h1-report-saturated", "olmo-hybrid-report-saturated",
                     "granite-h-small-report-saturated", "deepseek-v32-report-saturated"]
    assert declared["attn_kv_roofline.sat"]["workloads"] == older[:5]
    assert sorted(c["name"] for c in BENCH["configs"][:6]) == sorted(ACCEPTED)


def test_program_config_carries_every_published_number():
    """What ``test_program_config_carries_the_published_keys[phi-4-mini-flash-
    reasoning]`` asserts, with what it cannot: the program's heads are the
    KERNEL's — a query head padded to a pair's width, a K/V head the pair's
    ``[k1 | k2]`` — so 40 / 20 heads of 64 are 40 / 10 of 128."""
    from finchat_tpu.models.llama import CROSS, FULL, GMU, MAMBA1, WINDOW

    c = phi.program_config(FILE)
    assert (c.dim, c.n_heads, c.n_kv_heads, c.head_dim, c.vocab_size, c.n_layers) == (
        2560, 40, 10, 128, 200064, 32)
    assert c.kv_row_widths == (1280, 1280)  # 20 heads of 64: the published row, 5,120 B a token
    assert c.attention_scale == 0.125 and c.rope_theta is None and c.tie_embeddings
    assert c.layer_plan == (((MAMBA1, WINDOW), 8), ((MAMBA1, FULL), 1), ((GMU, CROSS), 7))
    assert (c.window, c.m1_inner, c.m1_state, c.m1_dt_rank, c.m1_conv) == (512, 5120, 16, 160, 4)
    assert c.cache_readers == 8  # the full layer and the seven cross layers behind it
    assert (c.n_attn_layers, c.n_window_layers, c.n_state_layers) == (1, 8, 9) and c.has_state
    assert c.state_shape == (1, 16, 5120) and c.hidden_dim == 10240
    assert c.max_seq_len == FILE["engine"]["max_seq_len"]
    for key, value in (("mlp_bias", True), ("mamba_dt_rank", 128)):
        with pytest.raises(ValueError, match=key):
            phi.program_config(dict(FILE, **{key: value}))
    with pytest.raises(ValueError, match="layer_types"):
        phi.program_config(dict(FILE, layer_types=FILE["layer_types"][:-1]))


def test_the_counts_are_the_programs_own_and_the_issues_table():
    """The adapter's arithmetic against what the program builds: the
    parameter tree and BOTH pools, by shapes (nothing is allocated). Also what
    ``test_llama_block_counts_equal_the_functions_they_replace[phi-4-mini-
    flash-reasoning-*]`` asserts, for a model in which ONE layer owns full
    pages and seven read them."""
    import jax

    from finchat_tpu.engine.engine import create_state, window_pool_pages
    from finchat_tpu.engine.kv_cache import page_hbm_bytes
    from finchat_tpu.models.llama import init_params, n_params
    from finchat_tpu.utils.config import EngineConfig

    p, mem = phi.param_counts(FILE), FILE["memory"]["params"]
    assert p["mlp"] == 2560 * 20480 + 10240 * 2560 == 78_643_200 == mem["mlp_a_layer"]
    assert p["mixer"] == 41_241_600 == mem["mamba1_mixer"]
    assert p["attention"] == 19_668_864 == mem["attention_with_kv"]
    assert p["gmu"] == 26_214_400 == mem["gmu"] and p["cross"] == 13_112_704 == mem["cross_attention"]
    assert p["embed"] == 200064 * 2560 == mem["embedding_tied"] and p["head"] == 0
    assert p["total"] == 3_852_562_944 == mem["total"]  # 3.85 B: 7.70 GB in bf16
    # the ISSUE's table, in GB of bf16
    gb = lambda n: round(2 * n / 1e9, 2)  # noqa: E731
    assert (gb(32 * p["mlp"]), gb(9 * p["mixer"]), gb(9 * p["attention"]), gb(7 * p["gmu"]),
            gb(7 * p["cross"]), gb(p["embed"]), gb(p["total"])) == (
        5.03, 0.74, 0.35, 0.37, 0.18, 1.02, 7.71)
    c = phi.program_config(FILE)
    tree = jax.eval_shape(lambda: init_params(c, jax.random.key(0)))
    assert p["total"] == n_params(c) == sum(x.size for x in jax.tree.leaves(tree))
    layers = tree["layers"]
    assert layers["mlp_in"].shape == (32, 2560, 20480) and layers["attn_q"].shape == (16, 2560, 2560)
    assert layers["attn_k"].shape == (9, 2560, 1280) and layers["m1_in"].shape == (9, 2560, 10240)
    assert layers["m1_A_log"].shape == (9, 16, 5120) and layers["gmu_out"].shape == (7, 5120, 2560)
    assert "lm_head" not in tree

    assert phi.kv_bytes_per_token(FILE) == 5120 == FILE["memory"]["kv_bytes_per_token"]
    assert phi.kv_bytes_per_token_by_kind(FILE) == {"full": 5120, "window": 8 * 5120}
    assert phi.attention_stream_bytes(FILE, kv_tokens=1000) == 1000 * 5120
    assert phi.yoco_passes(FILE) == 8 and phi.yoco_stream_bytes(FILE, kv_tokens=10) == 8 * 51200
    assert phi.ssm_state_bytes_per_row(FILE) == 327_680 and phi.conv_tail_bytes_per_row(FILE) == 61_440
    cfg = EngineConfig(**FILE["engine"])
    assert page_hbm_bytes(c, cfg.page_size) == 128 * 5120  # ONE layer's depth
    assert page_hbm_bytes(c, cfg.page_size, kind="window") == 8 * 128 * 5120
    state = jax.eval_shape(lambda: create_state(c, cfg, cfg.max_seq_len // cfg.page_size))
    nbytes = lambda x: x.size * x.dtype.itemsize  # noqa: E731
    assert state.k_pages.shape == state.v_pages.shape == (1, 3072, 128, 1280)
    assert nbytes(state.k_pages) + nbytes(state.v_pages) == 3072 * page_hbm_bytes(c, 128) \
        == 2_013_265_920
    n_win = window_pool_pages(c, cfg)
    assert n_win == (32 + 4) * 6 + 1 == 217 and state.win_table.shape == (32, 6)
    assert state.win_k_pages.shape == (8, 217, 128, 1280)
    assert nbytes(state.win_k_pages) + nbytes(state.win_v_pages) \
        == 217 * page_hbm_bytes(c, 128, kind="window") == 1_137_704_960
    assert state.ssm_state.shape == (9, 32, 1, 16, 5120) and state.ssm_state.dtype == np.float32
    assert nbytes(state.ssm_state) + nbytes(state.conv_state) == 9 * 32 * (327_680 + 61_440)


@pytest.mark.parametrize("name", ACCEPTED)
def test_an_accepted_configuration_emits_nothing_new(name):
    """A config without a ``layer_plan`` compiles to the program it was: no
    new parameter leaf, no second pool in its state, none of the new fields
    set — every new path is gated on them."""
    import jax

    from finchat_tpu.engine.engine import create_state
    from finchat_tpu.models.llama import init_params
    from finchat_tpu.utils.config import EngineConfig

    file = json.loads((ROOT / f"perfbench/configs/{name}.json").read_text())
    c = adapter(file).program_config(file)
    assert c.layer_plan == () and not (c.window or c.m1_inner) and c.cache_readers == 1
    tree = jax.eval_shape(lambda: init_params(c, jax.random.key(0)))
    new = ("m1_", "gmu_", "mlp_in", "ln_attn_b", "attn_lam", "attn_subln", "attn_q_b")
    assert not [leaf for leaf in tree["layers"] if leaf.startswith(new)] and "norm_b" not in tree
    cfg = EngineConfig(**file["engine"])
    state = jax.eval_shape(lambda: create_state(c, cfg, cfg.max_seq_len // cfg.page_size))
    assert state.win_k_pages is None and state.win_table is None and state.win_gaps is None
    assert len(jax.tree.leaves(state)) == 11  # the leaves it had


# --- the yardstick's counts and the readers -----------------------------------

def _context(prom_before=None, prom_after=None, rows=None):
    events = [(0.0, "t", "dispatch", None, "sched", {"rows": [[i, "t", "decode"] for i in range(n)]})
              for n in (rows or [])]
    return Context(w0=0.0, w1=51.0, requests=[], tracer_events=events,
                   prom_before=prom_before or {}, prom_after=prom_after or {},
                   device_trace=None, device={"kind": "TPU v5 lite"}, model=FILE)


def test_the_steps_bytes_walk_one_cache_eight_times_and_a_window_a_row():
    p = phi.param_counts(FILE)
    weights = (p["layers"] + p["embed"]) * 2
    state = 9 * 32 * 2 * (327_680 + 61_440)
    assert phi.decode_step_stream_bytes(FILE, live_kv_tokens=85_000, ctx=None) \
        == weights + 8 * 85_000 * 5120 + 32 * 512 * 8 * 5120 + state
    # the ISSUE's estimate: about 12.1 GB at 32 rows over 85k tokens on distinct pages
    assert phi.decode_step_stream_bytes(FILE, live_kv_tokens=85_000) / 1e9 \
        == pytest.approx(12.1, abs=0.05)
    # a longer context adds eight passes over it and nothing to the windows
    assert phi.decode_step_stream_bytes(FILE, live_kv_tokens=95_000) \
        - phi.decode_step_stream_bytes(FILE, live_kv_tokens=85_000) == 8 * 10_000 * 5120
    ctx = _context(rows=[24, 24])
    assert phi.decode_step_stream_bytes(FILE, live_kv_tokens=0, ctx=ctx) \
        == weights + 24 * (512 * 8 * 5120 + 9 * 2 * (327_680 + 61_440))
    assert phi.window_bytes_per_row(FILE, context=100) == 100 * 8 * 5120


def test_the_two_gauges_read_the_windows_closing_snapshot():
    moved = _context({}, {"finchat_window_kv_bytes": 0.84e9, "finchat_ssm_state_bytes": 0.112e9})
    assert read_metric("window_kv_gb.sat", moved) == pytest.approx(0.84)
    # the state's bytes say the precision it is STORED in: half of it would read 0.056
    assert read_metric("mamba1_state_gb.sat", moved) == pytest.approx(0.112)
    for name in ("window_kv_gb.sat", "mamba1_state_gb.sat"):
        assert read_metric(name, _context()) is None  # the parent: no gauge


def _step_ops(cross_appends: int):
    """One decode step's executed operations as a capture names them: the
    full layer's append and walk, seven cross layers' walks in their scan, a
    window layer's append and walk, a fusion — and ``cross_appends`` appends
    of cross layers that keep a cache of their own (the fault)."""
    full = "jit(decode_step)/jit(main)/yoco_attention/"
    cross = "jit(decode_step)/jit(main)/while/body/closed_call/yoco_attention/"
    swa = "jit(decode_step)/jit(main)/while/body/closed_call/swa_attention/"
    named = {
        "%paged_kv_append.9 = (bf16[1,3072,128,1280]{3,2,1,0}, bf16[1,3072,128,1280]{3,2,1,0}) "
        "custom-call(...)": full + "kv_append/pallas_call",
        "%paged_flash_attention.15 = bf16[32,40,1,128]{3,2,1,0} custom-call(...)":
            full + "paged_attention/pallas_call",
        "%paged_flash_attention.16 = bf16[32,40,1,128]{3,2,1,0} custom-call(...)":
            cross + "paged_attention/pallas_call",
        "%paged_kv_append.7 = (bf16[1,3072,128,1280]{3,2,1,0}, bf16[1,3072,128,1280]{3,2,1,0}) "
        "custom-call(...)": cross + "kv_append/pallas_call",
        "%paged_kv_append.8 = (bf16[8,217,128,1280]{3,2,1,0}, bf16[8,217,128,1280]{3,2,1,0}) "
        "custom-call(...)": swa + "kv_append/pallas_call",
        "%paged_flash_attention.14 = bf16[32,40,1,128]{3,2,1,0} custom-call(...)":
            swa + "paged_attention/pallas_call",
        "%fusion.7 = bf16[32,1,20480]{2,1,0} fusion(...)": full + "attn_o/dot_general",
    }
    full_append, full_walk, cross_walk, cross_append, swa_append, swa_walk, fusion = named
    ran = ([full_append, full_walk, fusion] + [cross_walk] * 7 + [cross_append] * cross_appends
           + [swa_append, swa_walk] * 8)
    # three steps and a fourth that the capture's edge cut after its second cross layer
    ran = ran * 3 + ran[:5]
    return named, tuple((0, name, "", 10 * i, 5) for i, name in enumerate(ran))


@pytest.mark.parametrize("cross_appends, want", [(0, 8.0), (7, 1.0)])
def test_the_layer_reads_are_counted_from_the_calls_that_ran(monkeypatch, cross_appends, want):
    """``yoco_layer_reads.sat`` counts executed kernels under the scope: 8
    walks for 1 append while the cross layers read the full layer's pages;
    cross layers that kept (and appended to) a cache of their own read 1.0 —
    no constant of the configuration enters, and a step that the capture's
    edge cut weighs nothing (call 10's capture ended two cross layers into its
    182nd step: a plain ratio of counts read 7.97)."""
    named, ops = _step_ops(cross_appends)
    monkeypatch.setattr(yoco_trace.trace_reduce, "find_xplane", lambda _dir: CAPTURE)
    monkeypatch.setattr(yoco_trace.xplane_scopes, "op_scope_paths", lambda _path: named)
    monkeypatch.setattr(yoco_trace.xplane_scopes, "device_ops", lambda _path: ops)
    ctx = _context()
    assert read_metric("yoco_layer_reads.sat", ctx) is None  # an untraced run
    ctx.device_trace = object()
    assert read_metric("yoco_layer_reads.sat", ctx) == want


def test_a_capture_without_the_new_scopes_reads_nothing(monkeypatch):
    """Mixtral's decode capture, as the parent's program would give for any
    cell: none of the new scopes. The readers return None and do not raise;
    and the new reader, pointed at a scope the capture does hold, reads a
    share of the peak."""
    monkeypatch.setattr(scope_trace.trace_reduce, "find_xplane", lambda _dir: CAPTURE)
    monkeypatch.setattr(yoco_trace.trace_reduce, "find_xplane", lambda _dir: CAPTURE)
    ctx = _context()
    ctx.device_trace = trace_reduce.reduce_xplane(CAPTURE)
    for name in ("yoco_share.sat", "swa_share.sat", "mamba1_share.sat", "yoco_kv_roofline.sat",
                 "yoco_layer_reads.sat"):
        assert read_metric(name, ctx) is None, name
    ctx.device_trace.modules.setdefault("jit_decode_step", [0.01])
    got = yoco_trace.read(ctx, scope="paged_attention", module="decode_step", kinds=["decode"])
    assert got is not None and got > 0
    ctx.model = {"model_type": "mistral"}  # an adapter without the count
    assert yoco_trace.read(ctx, scope="paged_attention", module="decode_step",
                           kinds=["decode"]) is None


# --- the controls at a test's size -----------------------------------------------

def test_window_controls_two_faults_are_not_the_reference_and_the_sound_paths_are():
    """``window_control.py``'s sequence at a test's size: a prompt of three
    windows and more (29 tokens against a window of 8), 9 forced tokens,
    through ``sparse_control.ragged_path_logits`` (a chunk a round, two mixed
    rounds) and ``correct._split_path_logits`` — every position at the
    reference; its two controls and the adapter's two are not."""
    import jax
    import jax.numpy as jnp

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.kv_cache import PageAllocator
    from finchat_tpu.models.llama import init_params
    from finchat_tpu.utils.config import EngineConfig
    from perfbench import correct, window_control
    from perfbench.sparse_control import ragged_path_logits
    from tests.test_phi4_flash import FILE as TINY

    c = dataclasses.replace(phi.program_config(TINY), dtype=jnp.float32)
    params = init_params(c, jax.random.key(0))
    cfg = EngineConfig(max_seqs=4, page_size=4, num_pages=128, max_seq_len=256, prefill_chunk=8)
    sched = SimpleNamespace(engine=InferenceEngine(c, params, cfg, attn_backend="ref"),
                            free_slots=[0, 1, 2, 3], allocator=PageAllocator(128))
    tokens = [int(t) for t in np.random.RandomState(3).randint(0, 211, size=38)]
    positions = list(range(28, 38))
    want, _ = phi.reference_logits(params, tokens, TINY, positions=positions)
    want = np.asarray(want)
    got = ragged_path_logits(sched, tokens[:29], tokens[29:])
    assert [i for i, _g in got] == list(range(10))
    assert max(np.abs(g[:211] - want[i]).max() for i, g in got) < 2e-4
    split = correct._split_path_logits(sched, tokens[:29], tokens[29:])
    assert max(np.abs(g - w).max() for g, w in zip(split, want)) < 2e-4
    assert sched.allocator.used_count == 0 and sched.engine.window_pager.pages_in_use == 0
    assert window_control.CONTROLS == ("window_off", "cross_own")
    for name in window_control.CONTROLS:
        control, _ = phi.reference_logits(params, tokens, TINY, positions=positions, **{name: True})
        assert np.abs(np.asarray(control) - want).max() > 0.05, name
    for control in (phi.control_logits, phi.state_control_logits):
        moved, margins = control(params, tokens, TINY, positions=positions)
        assert np.isinf(np.asarray(margins)).all()
        assert 1e-5 < np.abs(np.asarray(moved) - want).max() < 0.5
