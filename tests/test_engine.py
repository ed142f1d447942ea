"""Golden decode tests (SURVEY §4.5): the paged-cache engine must reproduce
the naive full-context forward pass token-for-token, across page boundaries,
chunked prefill, and interleaved multi-sequence decode."""

import jax
import jax.numpy as jnp
import pytest

from finchat_tpu.engine.engine import InferenceEngine, commit_first_token
from finchat_tpu.engine.kv_cache import PageAllocator, pages_needed
from finchat_tpu.models.llama import PRESETS, forward_full, init_params
from finchat_tpu.utils.config import EngineConfig

CONFIG = PRESETS["tiny"]

# ONE engine shape for every test in this module → prefill/decode compile
# once per process (jit cache keys on shapes + static args).
ENGINE_CFG = EngineConfig(max_seqs=4, page_size=8, num_pages=64, max_seq_len=128, prefill_chunk=8)


def make_engine(params):
    return InferenceEngine(CONFIG, params, ENGINE_CFG)


@pytest.fixture(scope="module")
def params():
    return init_params(CONFIG, jax.random.key(0))


ORACLE_PAD = 64  # fixed shape so the oracle compiles once


def oracle_greedy(params, prompt, n_new):
    """Naive full-forward greedy decode (the correctness oracle). Padded to
    one fixed shape; causality (test_model.py) guarantees padding after the
    last real token cannot affect its logits."""
    seq = list(prompt)
    out = []
    positions = jnp.arange(ORACLE_PAD)[None]
    for _ in range(n_new):
        tokens = jnp.asarray(seq + [0] * (ORACLE_PAD - len(seq)), jnp.int32)[None]
        logits = forward_full(params, tokens, positions, config=CONFIG)
        nxt = int(jnp.argmax(logits[0, len(seq) - 1]))
        out.append(nxt)
        seq.append(nxt)
    return out


def engine_greedy(eng, alloc, slot, prompt, n_new, seq_id="s"):
    pages = alloc.allocate(seq_id, pages_needed(len(prompt) + n_new, eng.page_size))
    eng.set_page_table_row(slot, pages)
    logits = eng.prefill(slot, prompt)
    eng.state, tok = commit_first_token(
        eng.state, jnp.int32(slot), logits, jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0)
    )
    out = [int(tok)]
    B = eng.engine_cfg.max_seqs
    active = jnp.zeros((B,), bool).at[slot].set(True)
    zeros, ones, zk = jnp.zeros((B,)), jnp.ones((B,)), jnp.zeros((B,), jnp.int32)
    for _ in range(n_new - 1):
        nxt = eng.decode(active, zeros, ones, zk)
        out.append(int(nxt[slot]))
    return out


def test_engine_matches_oracle_single_chunk(params):
    eng = make_engine(params)
    alloc = PageAllocator(ENGINE_CFG.num_pages)
    prompt = [3, 7, 11, 200, 42]
    assert engine_greedy(eng, alloc, 0, prompt, 8) == oracle_greedy(params, prompt, 8)


def test_engine_matches_oracle_multi_chunk_prefill(params):
    """Prompt longer than prefill_chunk exercises chunked prefill reading
    earlier pages while writing new ones."""
    eng = make_engine(params)
    alloc = PageAllocator(ENGINE_CFG.num_pages)
    prompt = list(range(1, 28))  # 27 tokens → 4 chunks of 8, crosses pages
    assert engine_greedy(eng, alloc, 1, prompt, 6) == oracle_greedy(params, prompt, 6)


def test_two_sequences_interleaved(params):
    """Two slots decoding in the same batch must not contaminate each other."""
    eng = make_engine(params)
    alloc = PageAllocator(ENGINE_CFG.num_pages)
    prompt_a = [5, 9, 2, 250, 17]
    prompt_b = [100, 101, 102]
    n_new = 8

    pages_a = alloc.allocate("a", pages_needed(len(prompt_a) + n_new, 8))
    pages_b = alloc.allocate("b", pages_needed(len(prompt_b) + n_new, 8))
    eng.set_page_table_row(0, pages_a)
    eng.set_page_table_row(2, pages_b)
    logits_a = eng.prefill(0, prompt_a)
    logits_b = eng.prefill(2, prompt_b)
    eng.state, tok_a = commit_first_token(eng.state, jnp.int32(0), logits_a, jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0))
    eng.state, tok_b = commit_first_token(eng.state, jnp.int32(2), logits_b, jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0))

    got_a, got_b = [int(tok_a)], [int(tok_b)]
    B = 4
    active = jnp.zeros((B,), bool).at[0].set(True).at[2].set(True)
    zeros, ones, zk = jnp.zeros((B,)), jnp.ones((B,)), jnp.zeros((B,), jnp.int32)
    for _ in range(n_new - 1):
        nxt = eng.decode(active, zeros, ones, zk)
        got_a.append(int(nxt[0]))
        got_b.append(int(nxt[2]))

    assert got_a == oracle_greedy(params, prompt_a, n_new)
    assert got_b == oracle_greedy(params, prompt_b, n_new)


def test_slot_reuse_after_reset(params):
    """Freeing a slot and admitting a new sequence must fully isolate it
    from the previous occupant (per-sequence failure isolation, SURVEY §5.3)."""
    eng = make_engine(params)
    alloc = PageAllocator(ENGINE_CFG.num_pages)
    first = engine_greedy(eng, alloc, 0, [9, 8, 7, 6], 5, seq_id="one")
    alloc.free("one", alloc.owned_by("one"))
    eng.reset_slot(0)
    alloc.check_invariants()
    second = engine_greedy(eng, alloc, 0, [9, 8, 7, 6], 5, seq_id="two")
    assert first == second


@pytest.mark.parametrize("n_rows", [1, 2, 3, 4])
def test_batched_slot_updates_have_one_shape_whatever_the_count(params, n_rows):
    """The eager updates between steps are padded to ``max_seqs`` rows (the
    last repeated): the result is the plain update's, and every count traces
    to the shape warm-up ran, so no count compiles on the scheduler's loop."""
    eng = make_engine(params)
    B = ENGINE_CFG.max_seqs
    rows = {slot: [3 + slot, 9 + slot][: 1 + slot % 2] for slot in range(n_rows)}
    eng.set_page_table_rows(rows)
    eng.set_context_lens_rows({slot: 5 + slot for slot in rows})
    eng.set_kv_gap_rows({slot: slot for slot in rows})
    table = jax.device_get(eng.state.page_table)
    for slot in range(B):
        want = rows.get(slot, [])
        assert table[slot, : len(want)].tolist() == want
        assert not table[slot, len(want):].any()
    assert jax.device_get(eng.state.context_lens).tolist() == [
        5 + s if s in rows else 0 for s in range(B)]
    assert jax.device_get(eng.state.kv_gaps).tolist() == [
        s if s in rows else 0 for s in range(B)]
    assert eng._slot_rows(rows).shape == (B,)
    eng.reset_slots(list(rows)[:-1])
    kept = list(rows)[-1]
    assert jax.device_get(eng.state.context_lens).tolist() == [
        5 + s if s == kept else 0 for s in range(B)]
    eng.set_page_table_rows({})  # nothing to write, nothing raised
    eng.reset_slots([])


@pytest.mark.parametrize("rows,width", [([2], 1), ([3, 0], 2), ([1, 2, 3], 4), ([0, 1, 2, 3], 4)])
def test_logits_rows_pads_the_count_to_a_power_of_two(params, rows, width):
    eng = make_engine(params)
    logits = jnp.arange(ENGINE_CFG.max_seqs * 6, dtype=jnp.float32).reshape(-1, 6)
    got = eng.logits_rows(logits, rows)
    assert got.shape == (width, 6)
    assert jnp.array_equal(got[: len(rows)], logits[jnp.asarray(rows)])


@pytest.mark.parametrize("kind,model", [("recurrent state", "falcon_h1"),
                                         ("latent pages", "deepseek_v32")])
def test_one_table_says_what_a_kind_of_per_row_memory_is_not_served_with(kind, model):
    """``NOT_CARRIED`` is the one place that says it: with every engine option
    of a kind's row switched on at once, ONE error names the kind and each
    option beside its reason (a mesh and ``model.quant`` are not engine
    options: tests/test_falcon_h1.py, test_deepseek_v32.py hold those)."""
    import tiny_models
    from finchat_tpu.engine.engine import NOT_CARRIED

    options = {"engine.spec_tokens": {"spec_tokens": 2}, "engine.kv_quant": {"kv_quant": "int8"},
               "engine.kv_sink_pages / engine.kv_window_pages":
                   {"kv_sink_pages": 1, "kv_window_pages": 4}}
    row = {option: why for option, why in NOT_CARRIED[kind].items() if option in options}
    assert len(row) >= 2
    config, params = tiny_models.build(model)
    cfg = EngineConfig(max_seqs=2, page_size=16, num_pages=64, max_seq_len=256, prefill_chunk=12,
                       **{k: v for option in row for k, v in options[option].items()})
    with pytest.raises(ValueError) as refused:
        InferenceEngine(config, params, cfg, attn_backend="ref")
    said = str(refused.value)
    assert f"a model with {kind}" in said
    for option, why in row.items():
        assert f"{option} ({why})" in said
