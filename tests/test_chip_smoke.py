"""chip_smoke.py's own functions, driven on the CPU at ``tiny`` with the
Pallas kernels in interpret mode — so that the script the driver runs on the
chip after every PR cannot rot unnoticed — and its refusal to run off-chip."""

import asyncio
import dataclasses
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import chip_smoke  # noqa: E402

from finchat_tpu.models.llama import PRESETS  # noqa: E402
from finchat_tpu.utils.config import EngineConfig  # noqa: E402


def test_main_exits_nonzero_off_chip(capsys):
    assert chip_smoke.main() != 0
    captured = capsys.readouterr()
    assert "no TPU" in captured.err
    assert '"ok"' not in captured.out  # no result line


def test_kernels_against_oracles_interpret():
    model = PRESETS["tiny"]
    errors = chip_smoke.check_kernels(
        model.n_heads, model.n_kv_heads, model.head_dim, 8,
        "pallas-interpret", prefill_chunk=16)
    assert set(errors) == {
        "paged_attention[decode]", "paged_attention[prefill chunk]",
        "paged_attention[spec verify]", "paged_attention[decode, int8]",
        "paged_attention[prefill chunk, int8]",
        "kv_append", "ragged_paged_attention", "flash_attention"}


@pytest.mark.parametrize("shared_pages,quantized", [(0, False), (3, False), (3, True)],
                         ids=["no_head", "shared_head", "shared_head_int8"])
def test_decode_at_cell_shape_interpret(shared_pages, quantized):
    """The cell-shaped decode case at a small size: a table four times as
    wide as the longest row, a NaN trash page under its dead entries; and
    with every row on the same three pages at the head of its table, as the
    cells' rows are on the system prompt's."""
    model = PRESETS["tiny"]
    error = chip_smoke.check_decode_at_cell_shape(
        "pallas-interpret", rows=4, n_heads=model.n_heads, n_kv=model.n_kv_heads,
        head_dim=model.head_dim, page_size=8, width=32,
        contexts=(25 if shared_pages else 9, 60), pool_pages=40,
        shared_pages=shared_pages, quantized=quantized)
    assert error < chip_smoke.KERNEL_ATOL + chip_smoke.KERNEL_RTOL * 4


def test_ssm_step_at_cell_shape_interpret():
    """The state update's check at a small size (values only: a time comes
    from the chip): layer 1 of 3, one row inert."""
    errors = chip_smoke.check_ssm_step_at_cell_shape(
        "pallas-interpret", rows=4, heads=4, head_dim=16, state=128, groups=2,
        layers=3, layer=1)
    assert set(errors) == {"y", "state"} and errors["y"] < 1e-4


def test_moe_at_cell_shape_interpret():
    """The expert layer's check at a small size (values and counts only: a
    time comes from the chip): 6 held experts of a router of 12, 2 a token,
    a router built so that 8 rows touch exactly 3 and exactly 6 of them —
    the touched pass (interpreted) and dense dispatch each against the
    reference, with what each read — and the grouped form at 160 tokens."""
    results = chip_smoke.check_moe_at_cell_shape(
        "pallas-interpret", rows=8, tokens=160, touched=(3, 6),
        shrink={"hidden_size": 128, "intermediate_size": 128, "shared_intermediate_size": 64,
                "num_local_experts": 6, "num_experts_per_tok": 2,
                "reduced": {"num_local_experts": {"from": 12, "to": 6, "why": "a test's size"}},
                "mamba_n_heads": 4, "mamba_d_head": 64})
    assert set(results) == {"touched_3_rel", "dense_3_rel", "touched_6_rel", "dense_6_rel",
                            "grouped_rel"}
    assert max(results.values()) < 0.01


def test_moe_at_cell_shape_interpret_for_a_configuration_that_holds_every_expert():
    """The same check told another configuration (``trinity-mini``'s file cut
    to a test's size: 12 of 12 experts held, 2 a token, a sigmoid router with
    a selection bias, so every pick of a row is a held one), the stacks handed
    as the engine's scan hands them, ``[3, E, ...]`` at index 1."""
    results = chip_smoke.check_moe_at_cell_shape(
        "pallas-interpret", configuration="trinity-mini", rows=8, tokens=160, touched=(5, 12),
        layers=3, layer=1,
        shrink={"hidden_size": 128, "moe_intermediate_size": 256, "intermediate_size": 256,
                "num_experts": 12, "num_experts_per_tok": 2, "num_attention_heads": 4,
                "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 512})
    assert set(results) == {"touched_5_rel", "dense_5_rel", "touched_12_rel", "dense_12_rel",
                            "grouped_rel"}
    assert max(results.values()) < 0.01


def test_gdn_step_at_cell_shape_values():
    """The delta rule's one-token update at a small size against the
    recurrence as written (values only: a time comes from the chip): layer 1
    of 3, one row inert."""
    errors = chip_smoke.check_gdn_step_at_cell_shape(
        "ref", rows=4, heads=3, key_dim=8, value_dim=16, layers=3, layer=1)
    assert set(errors) == {"o", "state"} and errors["o"] < 1e-5


@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
def test_gdn_step_with_a_decay_a_key_channel_at_cell_shape_values(backend):
    """The same check in the rule's second form (Kimi Delta Attention, PR 51):
    the decay a vector over a head's key channels, one channel in eight at
    exp(-80), through XLA's ``_step`` and through the interpreted kernel."""
    errors = chip_smoke.check_gdn_step_at_cell_shape(
        backend, rows=4, heads=4, key_dim=8, value_dim=128, layers=3, layer=1,
        channel_decay=True)
    assert set(errors) == {"o", "state"} and errors["o"] < 1e-5


@pytest.mark.no_stall_sanitizer
def test_serving_function_at_tiny_interpret(monkeypatch):
    """The whole serving phase — start-up, logits parity, HTTP, Kafka, the
    counters — through ``pallas-interpret``. The real prompt heads are
    ~5.5k byte-tokens, minutes of interpreter time; short heads keep the
    same paths (prefix registration, tool decision, response) in seconds."""
    import finchat_tpu.serve.app as app_module

    monkeypatch.setenv("FINCHAT_ATTN", "pallas-interpret")
    monkeypatch.setattr(app_module, "load_prompts", lambda: (
        "You are Penny, a finance assistant. Today is {date}.",
        "Decide whether to call a tool."))
    cfg = chip_smoke.smoke_config("tiny", "bge-tiny", port=8933, max_new_tokens=8)
    # few warm-up variants, few pages per row: interpreter time follows both
    cfg.engine.max_seqs = 4
    cfg.engine.page_size = 128
    cfg.engine.num_pages = 40
    cfg.engine.max_seq_len = 1024
    cfg.engine.prefill_chunk = 32
    parity = dataclasses.replace(
        EngineConfig(), max_seqs=2, page_size=16, num_pages=16,
        max_seq_len=128, prefill_chunk=32)
    outcomes = asyncio.run(chip_smoke.serve_and_check(
        cfg, expect_backend="pallas-interpret", parity_engine_cfg=parity,
        parity_prompt_len=48))
    assert len(outcomes["streams"]) == chip_smoke.N_STREAMS + 2
    assert outcomes["served"]["finchat_mixed_dispatches_total"] > 0
