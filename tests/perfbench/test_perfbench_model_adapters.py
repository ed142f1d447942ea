"""A configuration brings its own model (``perfbench/models/<model_type>.py``):
a made-up ``model_type`` lands with files alone, the llama block's adapter
counts what the fixed code counted, and its control separates from the sound
program."""

import dataclasses
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

import perfbench.models
from perfbench import correct, costs, kernel_costs, trace_reduce, xplane_scopes
from perfbench.layer_metrics import Context
from perfbench.layer_metrics.readers import device_trace, scope_trace
from perfbench.models import adapter

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
COMMITTED = {c["name"]: json.loads((ROOT / c["file"]).read_text()) for c in BENCH["configs"]}
CAPTURE = HERE / "decode_scoped_v5e.xplane.pb"

# --- a made-up model_type, by files alone ------------------------------------

MADE_UP_MODULE = '''
"""A block no file of the harness knows: heads wider than hidden / heads, one
expert a token by the larger of two sigmoid scores, a step that streams only
the experts it touched (a program counter says how many)."""
import numpy as np

WIDTH_KEYS = ("hidden_size", "expert_size", "head_dim")


def program_config(config):
    return ("made-up program config", config["num_attention_heads"] * config["head_dim"])


def reference_logits(params, tokens, config, *, positions):
    x = params["embed"][np.asarray(tokens)]
    scores = 1.0 / (1.0 + np.exp(-(x @ params["router"])))        # [S, 2]
    pick = np.argmax(scores, axis=-1)
    x = x + np.einsum("sd,sde->se", x, params["experts"][pick])
    margins = np.abs(scores[:, 0] - scores[:, 1])
    positions = np.asarray(positions)
    return (x @ params["head"])[positions], margins[positions]


def param_counts(config):
    return {"expert": config["hidden_size"] * config["expert_size"]}


def kv_bytes_per_token(config):
    return 2 * config["num_hidden_layers"] * config["num_key_value_heads"] * config["head_dim"] * 2


def decode_step_stream_bytes(config, *, live_kv_tokens, ctx):
    touched = ctx.delta("madeup_experts_touched_total") / ctx.delta("madeup_steps_total")
    return touched * param_counts(config)["expert"] * 2 + live_kv_tokens * kv_bytes_per_token(config)


def attention_stream_bytes(config, *, kv_tokens):
    return kv_tokens * 2 * config["num_key_value_heads"] * config["head_dim"] * 2
'''

MADE_UP_CONFIG = {
    "model_type": "made_up", "hidden_size": 256, "expert_size": 1024,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 64,  # 256 / 8 is 32
    "num_hidden_layers": 2, "num_local_experts": 2, "vocab_size": 300, "dtype": "bfloat16",
    "weights_seed": 0, "embed_preset": "bge-tiny", "engine": {"max_seqs": 2}}


@pytest.fixture
def made_up(tmp_path, monkeypatch):
    """The module as a file beside no other, found through the package's path;
    the configuration as a file in ``tmp_path``."""
    (tmp_path / "made_up.py").write_text(MADE_UP_MODULE)
    monkeypatch.setattr(perfbench.models, "__path__",
                        [*perfbench.models.__path__, str(tmp_path)])
    monkeypatch.delitem(sys.modules, "perfbench.models.made_up", raising=False)
    (tmp_path / "made-up.json").write_text(json.dumps(MADE_UP_CONFIG))
    config = json.loads((tmp_path / "made-up.json").read_text())
    yield config
    sys.modules.pop("perfbench.models.made_up", None)


def _made_up_params(config):
    rng = np.random.RandomState(5)
    d, v = config["hidden_size"], config["vocab_size"]
    return {"embed": rng.randn(v, d).astype(np.float32),
            "router": rng.randn(d, 2).astype(np.float32),
            "experts": (rng.randn(2, d, d) / np.sqrt(d)).astype(np.float32),
            "head": (rng.randn(d, v) / np.sqrt(d)).astype(np.float32)}


def test_made_up_program_config_reaches_the_programs_presets(made_up, tmp_path):
    from finchat_tpu.models.llama import PRESETS
    from perfbench.server import app_config

    try:
        cfg = app_config("made-up", made_up, answer_cap=8, work_dir=tmp_path)
        assert cfg.model.preset == "made-up"
        assert PRESETS["made-up"] == ("made-up program config", 8 * 64)
    finally:
        PRESETS.pop("made-up", None)


@pytest.mark.parametrize("fault, ok", [(None, True), ("the other expert", False)])
def test_made_up_reference_decides_the_logits_check(made_up, monkeypatch, fault, ok):
    """``check_logits`` on a stand-in engine whose two paths return the
    made-up model's own logits (rounded as a bf16 program would): correct.
    With every token sent through the expert its rule rejects: not correct."""
    params = _made_up_params(made_up)
    engine = types.SimpleNamespace(
        params=params, engine_cfg=types.SimpleNamespace(prefill_chunk=8))
    app = types.SimpleNamespace(scheduler=types.SimpleNamespace(engine=engine))
    prompt_len = correct.prompt_length(engine)
    tokens, positions = correct.seeded_tokens(made_up, 11, prompt_len)
    served = dict(params, router=-params["router"]) if fault else params
    logits, margins = adapter(made_up).reference_logits(served, tokens, made_up,
                                                       positions=positions)
    logits = logits * (1 + 0.004 * np.random.RandomState(0).randn(*logits.shape))
    monkeypatch.setattr(correct, "_split_path_logits", lambda *_a: list(logits))
    monkeypatch.setattr(correct, "_ragged_path_logits",
                        lambda *_a: [(i, row) for i, row in enumerate(logits)])
    got = correct.check_logits(app, made_up, 11)
    assert got["ok"] is ok and got["positions"] == len(positions) == 1 + correct.N_DECODE
    # the margins are the made-up rule's own, and some positions fall under the threshold
    assert got["margins"] == [round(float(m), 3) for m in margins]
    assert 0 < got["split"]["skipped_for_routing"] < len(positions)
    if fault:
        assert got["split"]["median_rel_rms"] > 0.3


def test_made_up_counts_feed_both_cost_readers(made_up, monkeypatch):
    monkeypatch.setattr(scope_trace.trace_reduce, "find_xplane", lambda _dir: CAPTURE)
    trace = trace_reduce.reduce_xplane(CAPTURE)
    trace.modules["jit_decode_step"] = [0.010, 0.012, 0.014]
    ctx = Context(
        w0=100.0, w1=151.0, requests=[], tracer_events=[],
        prom_before={"madeup_experts_touched_total": 10.0, "madeup_steps_total": 5.0},
        prom_after={"madeup_experts_touched_total": 310.0, "madeup_steps_total": 205.0},
        device_trace=trace, device={"kind": "TPU v5 lite"}, model=made_up,
        extra={"mean_live_kv_tokens": 50_000.0})
    # the whole step: 1.5 experts touched a step (the counter), 2 x 2 x 2 x 64 x 2 B of KV a token
    nbytes = 1.5 * 256 * 1024 * 2 + 50_000 * 1024
    assert device_trace.read(ctx, quantity="stream_roofline", module="jit_decode_step") \
        == pytest.approx(100 * (nbytes / 819e9) / 0.012)
    # one attention call: head_dim 64 of the file, not 256 / 8
    calls = [dur for _d, name, kind, _s, dur in xplane_scopes.device_ops(CAPTURE)
             if kind == "custom-call" and "paged_flash_attention" in name]
    _total, distinct = scope_trace._decode_kv_tokens(CAPTURE, {"decode"})
    want = 100 * (distinct * 2 * 2 * 64 * 2 / 819e9) / (sum(calls) / len(calls) / 1e9)
    assert scope_trace.read(ctx, quantity="kernel_stream_roofline", scopes=["paged_attention"],
                            patterns=["paged_flash_attention"], kinds=["decode"]) \
        == pytest.approx(want, rel=1e-9)


def test_a_configuration_without_a_model_module_is_refused_by_name():
    with pytest.raises(KeyError, match="perfbench/models/no_such_model.py"):
        adapter({"model_type": "no_such_model"})
    with pytest.raises(KeyError, match="model_type"):
        adapter({"hidden_size": 128})


# --- the llama block's adapter against the code it replaced -------------------

def _parent(config):
    """The counts as PR 23-25 computed them: the head's width taken as hidden
    size over heads, every expert once a step."""
    d, f = config["hidden_size"], config["intermediate_size"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = d // heads
    experts = config.get("num_local_experts", 0)
    layer = (d * heads * hd * 2 + d * kv * hd * 2 + 3 * d * f * max(experts, 1)
             + (d * experts if experts else 0) + 2 * d)
    embed = config["vocab_size"] * d
    n = config["num_hidden_layers"]
    kv_token = 2 * n * kv * hd * 2
    return {"param_counts": {"layer": layer, "layers": layer * n, "embed": embed, "head": embed,
                             "total": layer * n + 2 * embed + d},
            "kv_bytes_per_token": kv_token,
            "decode_step_stream_bytes": (layer * n + embed) * 2 + 123_456 * kv_token,
            "attention_stream_bytes": 123_456 * 2 * kv * hd * 2}


LLAMA_BLOCK = ("mistral", "mixtral")  # the ``model_type``s that are names for llama_block.py


def _counts_agree_with_one_another(config, model, count):
    n, two_bytes = config["num_hidden_layers"], costs.BYTES[config["dtype"]]
    p = model.param_counts(config)
    if count == "param_counts":
        assert p["layers"] == n * p["layer"] and p["embed"] == config["vocab_size"] * config["hidden_size"]
        assert p["total"] == p["layers"] + p["embed"] + p["head"] + config["hidden_size"]
    elif count == "kv_bytes_per_token":  # K and V of a token in every layer
        assert model.kv_bytes_per_token(config) \
            == n * model.attention_stream_bytes(config, kv_tokens=1)
    elif count == "attention_stream_bytes":  # the file's own head width, a token at a time
        assert model.attention_stream_bytes(config, kv_tokens=123_456) == 123_456 * (
            2 * config["num_key_value_heads"] * config["head_dim"] * two_bytes)
    else:  # the weights and the head once, then the live KV a token
        empty = model.decode_step_stream_bytes(config, live_kv_tokens=0, ctx=None)
        assert empty >= (p["layers"] + (p["head"] or p["embed"])) * two_bytes
        assert model.decode_step_stream_bytes(config, live_kv_tokens=123_456, ctx=None) - empty \
            == 123_456 * model.kv_bytes_per_token(config)


@pytest.mark.parametrize("count", ["param_counts", "kv_bytes_per_token",
                                   "decode_step_stream_bytes", "attention_stream_bytes"])
@pytest.mark.parametrize("name", sorted(COMMITTED))
def test_llama_block_counts_equal_the_functions_they_replace(name, count):
    config, model = COMMITTED[name], adapter(COMMITTED[name])
    if config["model_type"] not in LLAMA_BLOCK:
        # an architecture of its own brings its own counts, held to hand
        # arithmetic beside its adapter (test_perfbench_falcon_h1.py); here,
        # what any adapter's counts owe one another
        _counts_agree_with_one_another(config, model, count)
        return
    got = {"param_counts": lambda: model.param_counts(config),
           "kv_bytes_per_token": lambda: model.kv_bytes_per_token(config),
           "decode_step_stream_bytes": lambda: model.decode_step_stream_bytes(
               config, live_kv_tokens=123_456, ctx=None),
           "attention_stream_bytes": lambda: model.attention_stream_bytes(
               config, kv_tokens=123_456)}[count]()
    assert got == _parent(config)[count]
    generic = {"param_counts": costs.param_counts, "kv_bytes_per_token": costs.kv_bytes_per_token,
               "decode_step_stream_bytes": costs.decode_step_stream_bytes,
               "attention_stream_bytes": kernel_costs.paged_attention_stream_bytes}[count]
    assert getattr(model, count) is generic


@pytest.mark.parametrize("name", sorted(COMMITTED))
def test_head_dim_is_honoured_where_the_file_has_it(name):
    config = COMMITTED[name]
    model, wide = adapter(config), dict(config, head_dim=2 * config["head_dim"])
    assert costs.head_dim(config) == config["head_dim"]
    assert costs.head_dim({k: v for k, v in config.items() if k != "head_dim"}) \
        == config["hidden_size"] // config["num_attention_heads"]
    assert model.kv_bytes_per_token(wide) == 2 * model.kv_bytes_per_token(config)
    assert model.attention_stream_bytes(wide, kv_tokens=7) \
        == 2 * model.attention_stream_bytes(config, kv_tokens=7)
    d, heads, kv = (config[k] for k in ("hidden_size", "num_attention_heads", "num_key_value_heads"))
    assert model.param_counts(wide)["layer"] - model.param_counts(config)["layer"] \
        == 2 * d * (heads + kv) * config["head_dim"]
    if config["model_type"] in LLAMA_BLOCK:
        # the program's llama block cannot serve such a file, and says so
        with pytest.raises(ValueError, match="head_dim"):
            model.program_config(wide)
    else:  # a block with a head width of its own serves the file as it stands
        assert model.program_config(wide).head_dim == wide["head_dim"]


@pytest.mark.parametrize("name", sorted(COMMITTED))
def test_program_config_carries_the_published_keys(name):
    config = COMMITTED[name]
    c = adapter(config).program_config(config)
    assert (c.dim, c.hidden_dim, c.n_heads, c.n_kv_heads, c.head_dim, c.vocab_size, c.n_layers) == (
        config["hidden_size"], config["intermediate_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"], config["vocab_size"],
        config["num_hidden_layers"])
    assert c.n_experts == config.get("num_local_experts", 0)
    assert c.max_seq_len == config["engine"]["max_seq_len"] and c.rope_theta == config["rope_theta"]


# --- the control, at a size a test run can hold -------------------------------

@pytest.mark.parametrize("file", ["rehearsal-tiny", "rehearsal-moe-tiny"])
def test_control_reads_apart_from_the_sound_program(file):
    """The reference with int8 weights (the step below bf16) against the bf16
    program, both judged against the float32 reference on the check's own
    prompts: at 2 layers of width 128 the control's smallest median is over
    twice the program's largest (at the cells' sizes: PERF.md section 4)."""
    import jax
    import jax.numpy as jnp

    from finchat_tpu.models.llama import forward_full, init_params

    config = dict(json.loads((ROOT / f"perfbench/configs/{file}.json").read_text()),
                  dtype="bfloat16")
    model = adapter(config)
    c = dataclasses.replace(model.program_config(config), dtype=jnp.bfloat16)
    params = init_params(c, jax.random.key(0))
    program, control = [], []
    for seed in (1, 2, 3):
        tokens, positions = correct.seeded_tokens(config, seed, 96)
        want, margins = model.reference_logits(params, tokens, config, positions=positions)
        lowered, _ = model.control_logits(params, tokens, config, positions=positions)
        served = forward_full(params, jnp.asarray(tokens)[None],
                              jnp.arange(len(tokens))[None], config=c)[0][jnp.asarray(positions)]
        want, margins = np.asarray(want, np.float32), np.asarray(margins, np.float32)
        for readings, got in ((program, served), (control, lowered)):
            got = np.asarray(got, np.float32)
            readings.append(correct._judge([correct.rel_rms(g, w) for g, w in zip(got, want)],
                                           margins)["median_rel_rms"])
    assert min(control) > 2 * max(program) > 0
