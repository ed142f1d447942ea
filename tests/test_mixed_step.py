"""Unified packed ragged step (engine ragged_mixed_step + scheduler ragged
path, ISSUE 10 — rebuilt from PR 4's padded mixed step).

The contract under test: the ragged path is pure dispatch fusion — greedy
streams are byte-identical to the split path (prefill round + decode-side
dispatches), including the combinations the PADDED mixed step used to demote
(a grammar-constrained slot, spec-decode verify rows and a short-tail
prefill chunk, all coexisting in one iteration);
decode slots advance in EVERY ragged round while a long prompt prefills
(admission fairness); allocator/page-table invariants hold after ragged
rounds; the demotion counter stays at zero for the erased reasons; and a
whole-round prefill failure spares parked overlap holds (regression)."""

import asyncio
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finchat_tpu.engine.engine import (
    InferenceEngine,
    commit_first_token,
    decode_step,
    prefill_step,
    ragged_mixed_step,
    verify_step,
)
from finchat_tpu.engine.kv_cache import PageAllocator, pages_needed
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
from finchat_tpu.models.llama import PRESETS, init_params
from finchat_tpu.models.tokenizer import ByteTokenizer
from finchat_tpu.utils.config import EngineConfig
from finchat_tpu.utils.metrics import METRICS

# fp32: a decode row computes at the packed ragged shape in mixed mode vs
# [max_seqs, 1] in split mode, and under bf16 a last-ulp KV difference can
# flip a LATER near-tie argmax (the chunk-width caveat verify_step
# documents). fp32 pins the byte-identity contract so a structural bug
# cannot hide behind — or be excused by — rounding.
CONFIG = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
CHUNK = 16


@pytest.fixture(scope="module")
def params():
    return init_params(CONFIG, jax.random.key(0))


def _stack(params, mixed=True, max_seqs=4, num_pages=128, eos_id=-1,
           spec_tokens=0):
    cfg = EngineConfig(
        max_seqs=max_seqs, page_size=8, num_pages=num_pages, max_seq_len=128,
        prefill_chunk=CHUNK, mixed_step=mixed, session_cache=False,
        spec_tokens=spec_tokens,
    )
    engine = InferenceEngine(CONFIG, params, cfg)
    return ContinuousBatchingScheduler(engine, eos_id=eos_id)


async def _drain(handle, out):
    while True:
        ev = await asyncio.wait_for(handle.events.get(), timeout=120)
        if ev["type"] == "token":
            out.append(ev["token_id"])
        elif ev["type"] == "done":
            assert handle.events.empty()
            return
        else:
            raise AssertionError(ev)


# --- engine level -----------------------------------------------------------


def test_engine_ragged_step_matches_split_math(params):
    """One packed ragged dispatch == the split dispatches, exactly: a
    completing prefill row's greedy first token (vs prefill + commit), a
    decode row's token (vs a verify row with no drafts — the split spec
    path's plain-slot math), a spec row's accepted prefix (vs verify_step),
    and the resulting context_lens / last_tokens all match an identically
    prepared engine."""

    def prepare():
        cfg = EngineConfig(
            max_seqs=4, page_size=8, num_pages=64, max_seq_len=128,
            prefill_chunk=CHUNK, spec_tokens=2,
        )
        eng = InferenceEngine(CONFIG, params, cfg)
        alloc = PageAllocator(cfg.num_pages)
        # slot 0: decoding
        p0 = [3, 7, 11, 200, 42]
        eng.set_page_table_row(0, alloc.allocate("s0", pages_needed(len(p0) + 16, 8)))
        logits = eng.prefill(0, p0)
        eng.state, _ = commit_first_token(
            eng.state, jnp.int32(0), logits,
            jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0),
        )
        # slot 1: a 2-chunk prompt with only the FIRST chunk prefilled
        p1 = list(range(1, CHUNK + 6))
        eng.set_page_table_row(1, alloc.allocate("s1", pages_needed(len(p1) + 8, 8)))
        eng.state, _ = prefill_step(
            eng.params, eng.state,
            jnp.asarray([p1[:CHUNK]], jnp.int32), jnp.asarray([1], jnp.int32),
            jnp.asarray([0], jnp.int32), jnp.asarray([CHUNK], jnp.int32),
            config=eng.config, page_size=8, attn_backend=eng.attn_backend,
        )
        # slot 2: decoding, will carry spec drafts
        p2 = [9, 9, 9, 9, 9, 9]
        eng.set_page_table_row(2, alloc.allocate("s2", pages_needed(len(p2) + 16, 8)))
        logits = eng.prefill(2, p2)
        eng.state, _ = commit_first_token(
            eng.state, jnp.int32(2), logits,
            jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0),
        )
        return eng, p1

    B = 4
    zB = jnp.zeros((B,), jnp.float32)
    oB = jnp.ones((B,), jnp.float32)
    kB = jnp.zeros((B,), jnp.int32)

    # --- split: prefill tail + commit, verify step ------------------------
    eng_s, p1 = prepare()
    tail = p1[CHUNK:]
    drafts = np.zeros((B, 2), np.int32)
    drafts[2] = [9, 9]
    nd = np.zeros((B,), np.int32)
    nd[2] = 2
    eng_s.state, lg = prefill_step(
        eng_s.params, eng_s.state,
        jnp.asarray([tail + [0] * (CHUNK - len(tail))], jnp.int32),
        jnp.asarray([1], jnp.int32), jnp.asarray([CHUNK], jnp.int32),
        jnp.asarray([len(tail)], jnp.int32),
        config=eng_s.config, page_size=8, attn_backend=eng_s.attn_backend,
    )
    eng_s.state, first1 = commit_first_token(
        eng_s.state, jnp.int32(1), lg[0],
        jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0),
    )
    active = jnp.zeros((B,), bool).at[0].set(True).at[2].set(True)
    eng_s.state, emitted_s, n_em_s, _ = verify_step(
        eng_s.params, eng_s.state, active, jnp.asarray(drafts),
        jnp.asarray(nd), zB, oB, kB,
        config=eng_s.config, page_size=8, attn_backend=eng_s.attn_backend,
    )
    split = dict(
        first1=int(first1), tok0=int(emitted_s[0, 0]),
        em2=np.asarray(emitted_s[2, : int(n_em_s[2])]).tolist(),
        ctx=np.asarray(eng_s.state.context_lens).tolist(),
        last=np.asarray(eng_s.state.last_tokens).tolist(),
    )

    # --- ragged: all of it in ONE packed dispatch ------------------------
    eng_r, p1 = prepare()
    R, T = 4, 32
    toks, tok_row = [], []
    row_slot = np.zeros((R,), np.int32)
    row_start = np.zeros((R,), np.int32)
    row_len = np.zeros((R,), np.int32)
    from_dev = np.zeros((R,), bool)
    arm = np.zeros((R,), bool)
    ndr = np.zeros((R,), np.int32)
    # row 0: slot 1's completing tail
    row_slot[0], row_start[0], row_len[0], arm[0] = 1, CHUNK, len(tail), True
    toks += tail
    tok_row += [0] * len(tail)
    # row 1: slot 0 plain decode
    row_slot[1], row_len[1], from_dev[1], arm[1] = 0, 1, True, True
    toks += [0]
    tok_row += [1]
    # row 2: slot 2 spec verify with drafts [9, 9]
    row_slot[2], row_len[2], from_dev[2], arm[2], ndr[2] = 2, 3, True, True, 2
    toks += [0, 9, 9]
    tok_row += [2] * 3
    toks += [0] * (T - len(toks))
    tok_row += [R] * (T - len(tok_row))
    eng_r.state, emitted, n_em, _logits = ragged_mixed_step(
        eng_r.params, eng_r.state,
        jnp.asarray(toks, jnp.int32), jnp.asarray(tok_row, jnp.int32),
        jnp.asarray(row_slot), jnp.asarray(row_start), jnp.asarray(row_len),
        jnp.asarray(from_dev), jnp.asarray(arm), jnp.asarray(ndr),
        jnp.zeros((R,), jnp.float32), jnp.ones((R,), jnp.float32),
        jnp.zeros((R,), jnp.int32),
        config=eng_r.config, page_size=8, attn_backend=eng_r.attn_backend,
        spec_width=2,
    )
    got = dict(
        first1=int(emitted[0, 0]), tok0=int(emitted[1, 0]),
        em2=np.asarray(emitted[2, : int(n_em[2])]).tolist(),
        ctx=np.asarray(eng_r.state.context_lens).tolist(),
        last=np.asarray(eng_r.state.last_tokens).tolist(),
    )
    assert got == split


def test_engine_ragged_step_accepts_matching_drafts(params):
    """Spec acceptance inside the ragged step is verify_step's math: drafts
    equal to the model's own greedy continuation all commit (n_emitted =
    n_drafts + 1), and the resulting state matches token-by-token decode."""
    cfg = EngineConfig(
        max_seqs=2, page_size=8, num_pages=32, max_seq_len=64,
        prefill_chunk=8, spec_tokens=2,
    )
    eng = InferenceEngine(CONFIG, params, cfg)
    alloc = PageAllocator(cfg.num_pages)
    p = [3, 7, 11, 200, 42]
    eng.set_page_table_row(0, alloc.allocate("s", pages_needed(len(p) + 8, 8)))
    logits = eng.prefill(0, p)
    eng.state, _ = commit_first_token(
        eng.state, jnp.int32(0), logits,
        jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0),
    )
    B = 2
    zB, oB, kB = (jnp.zeros((B,), jnp.float32), jnp.ones((B,), jnp.float32),
                  jnp.zeros((B,), jnp.int32))
    # ground truth: three greedy decode steps over a COPY of the state
    # (decode_step donates its state argument)
    ref_state = jax.tree_util.tree_map(jnp.copy, eng.state)
    ref_tokens = []
    act = jnp.zeros((B,), bool).at[0].set(True)
    for _ in range(3):
        ref_state, toks, *_ = decode_step(
            eng.params, ref_state, act, zB, oB, kB,
            config=eng.config, page_size=8, attn_backend=eng.attn_backend,
        )
        ref_tokens.append(int(toks[0]))
    # ragged spec row drafting exactly those continuations
    R, T = 2, 8
    toks = [0, ref_tokens[0], ref_tokens[1]] + [0] * (T - 3)
    tok_row = [0, 0, 0] + [R] * (T - 3)
    row_slot = np.zeros((R,), np.int32)
    row_len = np.asarray([3, 0], np.int32)
    from_dev = np.asarray([True, False])
    arm = np.asarray([True, False])
    ndr = np.asarray([2, 0], np.int32)
    eng.state, emitted, n_em, _lg = ragged_mixed_step(
        eng.params, eng.state,
        jnp.asarray(toks, jnp.int32), jnp.asarray(tok_row, jnp.int32),
        jnp.asarray(row_slot), jnp.zeros((R,), jnp.int32),
        jnp.asarray(row_len), jnp.asarray(from_dev), jnp.asarray(arm),
        jnp.asarray(ndr),
        jnp.zeros((R,), jnp.float32), jnp.ones((R,), jnp.float32),
        jnp.zeros((R,), jnp.int32),
        config=eng.config, page_size=8, attn_backend=eng.attn_backend,
        spec_width=2,
    )
    assert int(n_em[0]) == 3  # both drafts + bonus token committed
    assert np.asarray(emitted[0, :3]).tolist() == ref_tokens
    assert int(eng.state.context_lens[0]) == len(p) + 3
    assert int(eng.state.last_tokens[0]) == ref_tokens[-1]


# --- scheduler level: byte-identity -----------------------------------------


def _run_workload(params, mixed, with_constraint=False):
    """Two decode streams, then a long prompt admitted mid-decode (so its
    chunks coexist with live decodes), plus optionally a grammar-constrained
    stream. Returns (streams dict, mixed dispatch count)."""
    sched = _stack(params, mixed=mixed)
    tok = ByteTokenizer()
    rng = np.random.default_rng(7)
    short_a = rng.integers(1, CONFIG.vocab_size, size=10).tolist()
    short_b = rng.integers(1, CONFIG.vocab_size, size=14).tolist()
    # 5 full chunks + a 2-token tail: the final ragged round packs a SHORT
    # row instead of padding to the chunk width — identity covers the
    # ragged tail case the old two-bucket scheme special-cased
    long_p = rng.integers(1, CONFIG.vocab_size, size=5 * CHUNK + 2).tolist()

    async def go():
        d0 = METRICS.get("finchat_mixed_dispatches_total")
        await sched.start()
        try:
            ha = await sched.submit(
                "a", short_a, SamplingParams(temperature=0.0, max_new_tokens=28))
            hb = await sched.submit(
                "b", short_b, SamplingParams(temperature=0.0, max_new_tokens=22))
            outs = {"a": [], "b": [], "long": []}
            tasks = [asyncio.create_task(_drain(ha, outs["a"])),
                     asyncio.create_task(_drain(hb, outs["b"]))]
            if with_constraint:
                from finchat_tpu.agent.constrained import GrammarVocab, TokenConstraint

                hc = await sched.submit(
                    "tool", tok.encode("decide", add_bos=True),
                    SamplingParams(temperature=0.0, max_new_tokens=20),
                    constraint=TokenConstraint(GrammarVocab.for_tokenizer(tok)),
                )
                outs["tool"] = []
                tasks.append(asyncio.create_task(_drain(hc, outs["tool"])))
            while len(outs["a"]) < 2 or len(outs["b"]) < 2:
                await asyncio.sleep(0.002)
            hl = await sched.submit(
                "long", long_p, SamplingParams(temperature=0.0, max_new_tokens=6))
            tasks.append(asyncio.create_task(_drain(hl, outs["long"])))
            await asyncio.gather(*tasks)
            sched.allocator.check_invariants()
            assert sched.allocator.used_count == 0
            assert sorted(sched.free_slots) == list(range(4))
            return outs, METRICS.get("finchat_mixed_dispatches_total") - d0
        finally:
            await sched.stop()

    return asyncio.run(go())


def _dispatches_per_coexist_iteration(run):
    """``run()``'s result and the scheduler's own attribution over it:
    model dispatches booked to iterations where prefill work and live
    decodes coexisted, over the count of those iterations."""
    keys = ("finchat_coexist_dispatches_total", "finchat_coexist_iterations_total")
    before = [METRICS.get(k) for k in keys]
    result = run()
    dispatches, iterations = (METRICS.get(k) - b for k, b in zip(keys, before))
    assert iterations > 0, "prefill never coexisted with a live decode"
    return result, dispatches / iterations


def test_mixed_vs_split_streams_identical(params):
    """Greedy streams — two in-flight decodes, a long prompt admitted
    mid-decode, and the long prompt completing mid-batch — are
    byte-identical ragged vs split, and the ragged run actually fused:
    ONE model dispatch per coexist iteration where the split path pays a
    prefill round plus a decode dispatch."""
    (split, n_split), dpi_split = _dispatches_per_coexist_iteration(
        lambda: _run_workload(params, mixed=False))
    (mixed, n_mixed), dpi_mixed = _dispatches_per_coexist_iteration(
        lambda: _run_workload(params, mixed=True))
    assert [len(s) for s in split.values()] == [28, 22, 6]
    assert mixed == split
    assert n_split == 0
    # the long prompt spans 5+ chunks; each coexisted with live decodes
    assert n_mixed >= 5
    assert dpi_mixed <= 1.2 and dpi_split >= 1.8, (dpi_mixed, dpi_split)


def _spec_prompt(seed, rng=None):
    """The spec stream's prompt: the first draw of the workload's rng."""
    rng = rng or np.random.default_rng(seed)
    base = rng.integers(1, CONFIG.vocab_size, size=4).tolist()
    return (base * 5)[:18]


def _demoted_combo_workload(params, mixed, recorded=None, seed=7,
                            spec_oracle=None):
    """The previously-demoted feature mix in ONE scheduler (satellite
    fuzz): spec decode on, a grammar-constrained stream, a
    greedy bystander, and a long prompt with a short tail admitted
    mid-decode — under PR 4 any ONE of these demoted every coexist
    iteration to the split path. ``recorded`` (ragged runs) collects, per
    ragged dispatch, which features were carried.

    ``spec_oracle`` is the spec stream's known greedy continuation (from
    an earlier run): the proposer then drafts the TRUE next tokens, so
    every draft is accepted, the all-miss cooldown never engages, and a
    spec verify row rides every dispatch the stream is live in — whether
    one lands in a coexist dispatch stops depending on what random
    weights happen to repeat. Greedy-exactness makes the stream the same
    under any proposer."""
    if spec_oracle is not None:
        from unittest import mock

        from finchat_tpu.engine.spec import NgramIndex

        def oracle_propose(self, k):
            n = len(self._h)
            return spec_oracle[n:n + k] if self._h == spec_oracle[:n] else []

        with mock.patch.object(NgramIndex, "propose", oracle_propose):
            return _demoted_combo_workload(params, mixed, recorded, seed)
    sched = _stack(params, mixed=mixed, max_seqs=5, num_pages=256,
                   spec_tokens=2)
    if recorded is not None:
        real = sched.engine.ragged_round

        def spy(tokens, tok_row, row_slot, row_start, row_len,
                row_from_device, row_arm, row_n_drafts, *rest):
            nd = np.asarray(row_n_drafts)
            fd = np.asarray(row_from_device)
            rl = np.asarray(row_len)
            recorded.append({
                "prefill": bool(((rl > 0) & ~fd).any()),
                "spec": bool((nd > 0).any()),
                "constrained": any(
                    h.constraint is not None for h in sched.decoding.values()
                ),
                "short_tail": bool(((rl > 0) & ~fd & (rl < CHUNK)).any()),
            })
            return real(tokens, tok_row, row_slot, row_start, row_len,
                        row_from_device, row_arm, row_n_drafts, *rest)

        sched.engine.ragged_round = spy
    tok = ByteTokenizer()
    rng = np.random.default_rng(seed)
    # repetitive prompts: greedy decode on random tiny weights settles into
    # loops, so prompt-lookup proposals (and acceptances) actually fire
    spec_prompt = _spec_prompt(seed, rng)
    by_prompt = rng.integers(1, CONFIG.vocab_size, size=9).tolist()
    long_p = rng.integers(1, CONFIG.vocab_size, size=5 * CHUNK + 3).tolist()

    async def go():
        from finchat_tpu.agent.constrained import GrammarVocab, TokenConstraint

        await sched.start()
        try:
            outs = {"spec": [], "by": [], "tool": [], "long": []}
            hs = await sched.submit(
                "spec", spec_prompt,
                SamplingParams(temperature=0.0, max_new_tokens=64))
            hb = await sched.submit(
                "by", by_prompt, SamplingParams(temperature=0.0, max_new_tokens=56))
            hc = await sched.submit(
                "tool", tok.encode("decide", add_bos=True),
                SamplingParams(temperature=0.0, max_new_tokens=40),
                constraint=TokenConstraint(GrammarVocab.for_tokenizer(tok)),
            )
            tasks = [asyncio.create_task(_drain(hs, outs["spec"])),
                     asyncio.create_task(_drain(hb, outs["by"])),
                     asyncio.create_task(_drain(hc, outs["tool"]))]
            # admit the long prompt inside a live PROPOSAL window: the
            # greedy stream has looped (its n-gram index proposes) and
            # the all-miss cooldown is clear, so the coexist iterations
            # actually carry spec verify rows. Timing only — greedy token
            # VALUES are submission-timing independent, so the split run
            # (same gate) stays byte-comparable.
            for _ in range(30_000):
                if hs.finished or (
                    sched._spec_cooldown == 0
                    and hs.ngram_index is not None
                    and hs.ngram_index.propose(2)
                ):
                    break
                await asyncio.sleep(0.001)
            hl = await sched.submit(
                "long", long_p, SamplingParams(temperature=0.0, max_new_tokens=5))
            tasks.append(asyncio.create_task(_drain(hl, outs["long"])))
            await asyncio.gather(*tasks)
            sched.allocator.check_invariants()
            assert sched.allocator.used_count == 0
            assert sorted(sched.free_slots) == list(range(5))
            return outs
        finally:
            await sched.stop()

    return asyncio.run(go())


@pytest.mark.parametrize("seed", [7, 23, 41])
def test_previously_demoted_combo_byte_identity(params, seed):
    """The erased-demotion fuzz (ISSUE 10 satellite): spec verify rows,
    a grammar-constrained stream, and a
    short-tail prefill coexisting in one iteration — greedy/constrained
    streams byte-identical ragged vs split, with the ragged run actually
    carrying the feature mix in fused dispatches."""
    split, dpi_split = _dispatches_per_coexist_iteration(
        lambda: _demoted_combo_workload(params, mixed=False, seed=seed))
    recorded: list[dict] = []
    ragged, dpi_ragged = _dispatches_per_coexist_iteration(
        lambda: _demoted_combo_workload(
            params, mixed=True, recorded=recorded, seed=seed,
            spec_oracle=_spec_prompt(seed) + split["spec"]))
    assert ragged == split
    assert dpi_ragged <= 1.2 and dpi_split >= 1.8, (dpi_ragged, dpi_split)
    assert recorded, "no ragged dispatch ran"
    assert any(r["prefill"] and r["spec"] and r["constrained"]
               for r in recorded), (
        "no single dispatch carried every previously demoting feature",
        recorded)
    assert any(r["prefill"] and r["constrained"] for r in recorded), (
        "constrained slot never rode a fused dispatch", recorded)
    assert any(r["prefill"] and r["spec"] for r in recorded), (
        "no spec verify row in any coexist dispatch", recorded)
    assert any(r["short_tail"] for r in recorded), recorded


def test_demotion_counter_erased_reasons_stay_zero(params):
    """finchat_mixed_demotions_total (ISSUE 10 satellite): the reason
    family is pre-seeded, and running the previously-demoting feature mix
    increments NONE of the erased reasons (spec / constrained) — the
    erasure is observable, not assumed."""
    before = {
        r: METRICS.get("finchat_mixed_demotions_total", labels={"reason": r})
        for r in ContinuousBatchingScheduler.MIXED_DEMOTION_REASONS
    }
    _demoted_combo_workload(params, mixed=True)
    snap = METRICS.snapshot()
    for reason in ("spec", "constrained"):
        key = f'finchat_mixed_demotions_total{{reason="{reason}"}}'
        assert snap.get(key, 0) == before[reason], (reason, snap.get(key))


# --- scheduler level: admission fairness ------------------------------------


def test_admission_fairness_decode_advances_every_ragged_round(params):
    """While a long prompt prefills, every ragged dispatch carries ALL live
    decoding slots as device-read rows — decode streams advance at least
    one token per scheduler iteration instead of stalling behind a
    serialized prefill round."""
    sched = _stack(params, mixed=True)
    calls: list[tuple[int, int, int]] = []  # (#prefill rows, #decode rows, #decoding)
    real = sched.engine.ragged_round

    def spy(tokens, tok_row, row_slot, row_start, row_len,
            row_from_device, row_arm, row_n_drafts, *rest):
        rl = np.asarray(row_len)
        fd = np.asarray(row_from_device)
        calls.append((
            int(((rl > 0) & ~fd).sum()), int(fd.sum()), len(sched.decoding),
        ))
        return real(tokens, tok_row, row_slot, row_start, row_len,
                    row_from_device, row_arm, row_n_drafts, *rest)

    sched.engine.ragged_round = spy
    rng = np.random.default_rng(3)
    short = rng.integers(1, CONFIG.vocab_size, size=9).tolist()
    long_p = rng.integers(1, CONFIG.vocab_size, size=6 * CHUNK).tolist()

    async def go():
        await sched.start()
        try:
            h1 = await sched.submit(
                "d1", short, SamplingParams(temperature=0.0, max_new_tokens=40))
            h2 = await sched.submit(
                "d2", short[:5], SamplingParams(temperature=0.0, max_new_tokens=36))
            o1, o2 = [], []
            t1 = asyncio.create_task(_drain(h1, o1))
            t2 = asyncio.create_task(_drain(h2, o2))
            while len(o1) < 2 or len(o2) < 2:
                await asyncio.sleep(0.002)
            hl = await sched.submit(
                "long", long_p, SamplingParams(temperature=0.0, max_new_tokens=4))
            ol = []
            tl = asyncio.create_task(_drain(hl, ol))
            await asyncio.gather(t1, t2, tl)
            return o1, o2, ol
        finally:
            await sched.stop()

    o1, o2, ol = asyncio.run(go())
    assert (len(o1), len(o2), len(ol)) == (40, 36, 4)
    assert len(calls) >= 6  # one ragged round per long-prompt chunk, minimum
    for n_prefill, n_decode, n_decoding in calls:
        assert n_prefill >= 1, "a ragged dispatch carried no prefill row"
        assert n_decode == n_decoding, (
            "a decoding slot sat out a ragged dispatch", calls)
        assert n_decode >= 1


# --- scheduler level: invariants under churn --------------------------------


def test_allocator_and_slot_invariants_after_ragged_waves(params):
    """Wave-loaded ragged rounds (pool smaller than offered load, staggered
    budgets, admissions landing while others decode) leave the allocator
    and slot bookkeeping clean."""
    tok = ByteTokenizer()
    sched = _stack(params, mixed=True, max_seqs=3, num_pages=32)

    async def go():
        await sched.start()
        try:
            handles = [
                await sched.submit(
                    f"w{i}", tok.encode(f"wave prompt number {i}", add_bos=True),
                    SamplingParams(temperature=0.0, max_new_tokens=8 + 4 * i),
                )
                for i in range(6)
            ]
            outs = [[] for _ in handles]
            await asyncio.gather(*[
                _drain(h, o) for h, o in zip(handles, outs)
            ])
            return [len(o) for o in outs]
        finally:
            await sched.stop()

    counts = asyncio.run(go())
    assert counts == [8 + 4 * i for i in range(6)], counts
    sched.allocator.check_invariants()
    assert sched.allocator.used_count == 0
    assert sorted(sched.free_slots) == list(range(3))
    assert not sched.prefilling and not sched.decoding
    assert np.asarray(sched.engine.state.context_lens).sum() == 0
    assert np.asarray(sched.engine.state.page_table).sum() == 0


def test_inter_token_histogram_labeled_by_prefill_coexistence(params):
    """The finchat_inter_token_seconds histogram distinguishes tokens
    emitted while prefill work ran (admission) from steady decode — both
    series must be populated by a coexistence workload."""
    y0 = METRICS.quantile("finchat_inter_token_seconds", 0.5,
                          labels={"prefill_concurrent": "yes"})
    before_yes = METRICS.snapshot().get(
        'finchat_inter_token_seconds{prefill_concurrent="yes"}_count', 0)
    before_no = METRICS.snapshot().get(
        'finchat_inter_token_seconds{prefill_concurrent="no"}_count', 0)
    _run_workload(params, mixed=True)
    snap = METRICS.snapshot()
    assert snap['finchat_inter_token_seconds{prefill_concurrent="yes"}_count'] > before_yes
    assert snap['finchat_inter_token_seconds{prefill_concurrent="no"}_count'] > before_no
    assert y0 >= 0.0  # quantile path accepts labels


# --- regression: whole-round failure must spare parked holds ----------------


def test_prefill_round_failure_spares_parked_holds(params, monkeypatch):
    """A whole-round prefill failure touches only the sequences IN the
    dispatch: a parked overlap hold (prefix complete, awaiting
    extend_prompt) was skipped from the round and must survive it
    untouched, then complete normally after its graft. The sequence that
    WAS in the failed round is recompute-preempted and replayed (ISSUE 5
    breaker semantics, default on), so its stream completes too. The
    pre-fix handler evicted everything in self.prefilling, killing
    in-flight retrieval overlaps that never touched the failed dispatch."""
    sched = _stack(params, mixed=False)
    rng = np.random.default_rng(11)
    prefix = rng.integers(1, CONFIG.vocab_size, size=40).tolist()
    full = prefix + rng.integers(1, CONFIG.vocab_size, size=12).tolist()
    samp = SamplingParams(temperature=0.0, max_new_tokens=5)

    real = sched.engine.prefill_rows
    state = {"armed": False, "fired": False}

    def flaky(*args, **kwargs):
        if state["armed"]:
            state["armed"] = False
            state["fired"] = True
            raise RuntimeError("injected whole-round failure")
        return real(*args, **kwargs)

    monkeypatch.setattr(sched.engine, "prefill_rows", flaky)

    async def go():
        await sched.start()
        try:
            hold = await sched.submit_partial("hold", prefix, samp)
            assert hold is not None
            t0 = time.perf_counter()
            while hold.prefill_pos < len(hold.prompt_ids):
                assert time.perf_counter() - t0 < 60
                await asyncio.sleep(0.01)
            assert hold.held and hold in sched.prefilling

            # now fail the NEXT whole round (the victim's dispatch)
            state["armed"] = True
            victim = await sched.submit("victim", full[:20], samp)
            victim_tokens = []
            await asyncio.wait_for(_drain(victim, victim_tokens), timeout=60)
            assert state["fired"]
            # the victim rode the failed round but was preempted and
            # replayed — its stream completed anyway
            assert len(victim_tokens) == 5 and victim.preempted == 1

            # the parked hold survived the failed round UNTOUCHED (its
            # prefilled prefix KV intact — it was not preempted)
            assert not hold.finished and hold in sched.prefilling and hold.held
            assert hold.preempted == 0
            assert hold.prefill_pos >= len(hold.prompt_ids)

            # ...and still completes after its graft
            assert sched.extend_prompt(hold, full)
            tokens = []
            await _drain(hold, tokens)
            return tokens
        finally:
            await sched.stop()

    tokens = asyncio.run(go())
    assert len(tokens) == 5
    sched.allocator.check_invariants()
    assert sched.allocator.used_count == 0
