"""Startup warmup: the first-request path must not compile anything new.

Verdict r3 weak #4/#5: the first real request used to pay full prefill +
decode XLA compilation inside the 100 s watchdog, and the first tool
decision compiled the ``return_logits=True`` decode variant mid-stream.
``InferenceEngine.warmup()`` closes both; these tests pin it.
"""

import jax
import jax.numpy as jnp
import numpy as np

from finchat_tpu.engine.engine import (
    InferenceEngine,
    commit_first_token,
    decode_step,
    prefill_step,
    ragged_mixed_step,
    verify_step,
)
from finchat_tpu.engine.kv_cache import PageAllocator, pages_needed
from finchat_tpu.models.llama import PRESETS, init_params
from finchat_tpu.utils.config import EngineConfig


def _tiny_engine(max_seqs=2, spec_tokens=0):
    config = PRESETS["tiny"]
    engine_cfg = EngineConfig(
        max_seqs=max_seqs, page_size=8, num_pages=32, max_seq_len=64, prefill_chunk=8,
        spec_tokens=spec_tokens,
    )
    params = init_params(config, jax.random.key(0))
    return InferenceEngine(config, params, engine_cfg, attn_backend="ref")


def test_warmup_is_state_neutral():
    eng = _tiny_engine()
    eng.warmup()
    assert np.asarray(eng.state.context_lens).tolist() == [0, 0]
    assert np.asarray(eng.state.page_table).sum() == 0


def test_first_request_path_compiles_nothing_after_warmup():
    eng = _tiny_engine()
    eng.warmup()
    sizes = {
        "prefill": prefill_step._cache_size(),
        "decode": decode_step._cache_size(),
        "commit": commit_first_token._cache_size(),
    }

    # a real first request: admit, prefill (2 chunks), commit, decode with
    # BOTH variants (the return_logits=True one is the tool-decision path)
    alloc = PageAllocator(eng.engine_cfg.num_pages)
    prompt = [3, 7, 11, 200, 42, 9, 13, 55, 21, 8]
    pages = alloc.allocate("s", pages_needed(len(prompt) + 4, eng.page_size))
    eng.set_page_table_row(0, pages)
    logits = eng.prefill(0, prompt)
    eng.state, _ = commit_first_token(
        eng.state, jnp.int32(0), logits,
        jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0),
    )
    B = eng.engine_cfg.max_seqs
    active = jnp.zeros((B,), bool).at[0].set(True)
    zeros, ones, zk = jnp.zeros((B,)), jnp.ones((B,)), jnp.zeros((B,), jnp.int32)
    eng.decode(active, zeros, ones, zk)
    eng.decode(active, zeros, ones, zk, return_logits=True)

    assert prefill_step._cache_size() == sizes["prefill"], "first prefill recompiled"
    assert decode_step._cache_size() == sizes["decode"], "first decode recompiled"
    assert commit_first_token._cache_size() == sizes["commit"], "commit recompiled"


def test_warmup_covers_spec_verify_variants():
    """With spec_tokens > 0 the scheduler's verify path (both return_logits
    variants) must be compiled at startup, not on the first drafted step."""
    eng = _tiny_engine(spec_tokens=2)
    eng.warmup()
    before = verify_step._cache_size()

    B = eng.engine_cfg.max_seqs
    active = jnp.zeros((B,), bool).at[0].set(True)
    drafts = jnp.zeros((B, 2), jnp.int32)
    n_drafts = jnp.zeros((B,), jnp.int32).at[0].set(2)
    zeros, ones, zk = jnp.zeros((B,)), jnp.ones((B,)), jnp.zeros((B,), jnp.int32)
    alloc = PageAllocator(eng.engine_cfg.num_pages)
    # 3 prompt tokens + two verify steps that can each commit spec+1 = 3
    pages = alloc.allocate("s", pages_needed(3 + 2 * 3, eng.page_size))
    eng.set_page_table_row(0, pages)
    eng.prefill(0, [3, 7, 11])
    eng.decode_spec(active, drafts, n_drafts, zeros, ones, zk)
    eng.decode_spec(active, drafts, n_drafts, zeros, ones, zk, return_logits=True)

    assert verify_step._cache_size() == before, "first verify step recompiled"


def test_warmup_covers_ragged_step_variants():
    """With mixed_step on (the default) every packed-token bucket of the
    scheduler's unified ragged dispatch must be compiled at startup — the
    first admission-during-decode must not compile. One bucket axis
    replaces PR 4's row-bucket x chunk-bucket matrix, and spec/
    constrained rows reuse the same variants (ISSUE 10). The round is
    called as the benchmark calls it (``ragged_mixed``: sixteen arguments,
    four values — perfbench/correct.py; ROADMAP D9 (l))."""
    eng = _tiny_engine(spec_tokens=2)
    eng.warmup()
    before = ragged_mixed_step._cache_size()
    assert before > 0, "warmup compiled no ragged variants"
    assert eng.compiled_variants > 0

    B = eng.engine_cfg.max_seqs  # == 2: row 0 prefill, row 1 spec decode
    R = B
    zB = jnp.zeros((B,), jnp.float32)
    no_tail = jnp.zeros((B,), bool)
    for t in eng.ragged_token_buckets():
        # a serving-shaped round: a 3-token prefill row plus a spec verify
        # row with one draft — every feature mix reuses the SAME compiled
        # variant as the all-padding warmup shape
        toks = [5, 6, 7, 0, 9] + [0] * (t - 5)
        tok_row = [0, 0, 0, 1, 1] + [R] * (t - 5)
        _emitted, _n, row_logits, fourth = eng.ragged_mixed(
            jnp.asarray(toks, jnp.int32), jnp.asarray(tok_row, jnp.int32),
            jnp.asarray([0, 1], jnp.int32),  # row slots
            jnp.zeros((R,), jnp.int32),  # row_start
            jnp.asarray([3, 2], jnp.int32),  # row_len
            jnp.asarray([False, True]),  # from_device
            jnp.asarray([False, True]),  # arm
            jnp.asarray([0, 1], jnp.int32),  # n_drafts
            jnp.zeros((R,), jnp.float32), jnp.ones((R,), jnp.float32),
            jnp.zeros((R,), jnp.int32),
            no_tail, zB, jnp.ones((B,), jnp.float32),
            jnp.zeros((B,), jnp.int32), -1,
        )
        assert fourth is None and row_logits.shape == (R, eng.config.vocab_size)
    assert ragged_mixed_step._cache_size() == before, (
        "first ragged dispatch recompiled")
    # state-neutrality with the ragged variants included
    eng2 = _tiny_engine()
    eng2.warmup()
    assert np.asarray(eng2.state.context_lens).tolist() == [0, 0]
    assert np.asarray(eng2.state.page_table).sum() == 0


def test_ragged_bucket_matrix_collapsed():
    """The compiled-variant accounting the warmup gauge reports: the
    ragged bucket list is ONE pow-2 axis whose length never exceeds the
    old row x chunk matrix, and the top bucket covers the worst-case
    packed round (every slot a full chunk)."""
    eng = _tiny_engine()
    buckets = eng.ragged_token_buckets()
    cfg = eng.engine_cfg
    assert buckets == sorted(set(buckets))
    assert buckets[-1] >= cfg.max_seqs * cfg.prefill_chunk
    # old matrix: pow-2 row buckets (log2(max_seqs)+1) x 2 chunk buckets
    import math

    old_matrix = (int(math.log2(1 << (cfg.max_seqs - 1).bit_length())) + 1) * 2
    assert len(buckets) <= max(old_matrix, 1)


def test_warmup_covers_non_power_of_two_max_seqs():
    """The scheduler pads a prefill round to the NEXT power of two, which
    for a non-power-of-two max_seqs exceeds it — warmup must cover that
    largest variant too."""
    config = PRESETS["tiny"]
    engine_cfg = EngineConfig(
        max_seqs=3, page_size=8, num_pages=32, max_seq_len=64, prefill_chunk=8
    )
    eng = InferenceEngine(
        config, init_params(config, jax.random.key(0)), engine_cfg, attn_backend="ref"
    )
    before = prefill_step._cache_size()
    eng.warmup()
    compiled = prefill_step._cache_size() - before
    assert compiled == 3  # N = 1, 2, 4 — includes the 4-row padding variant
