"""The rule of PR 30 — a decode step must read each DISTINCT physical KV page
of the batch once — on the program's own bookkeeping (a tiny scheduler with
shared heads, on the CPU), and through the readers on hand-made samples.

The count is the yardstick's (``perfbench/live_kv.py``); the program gives the
handles. What holds it to the program: its total equals the ``kv_tokens`` the
scheduler notes on a decode dispatch for the same rows, and the distinct page
ids of those rows' page tables, times the page size, bound its distinct count
from above by less than a page a row and an entry.
"""

import asyncio
import dataclasses
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from perfbench import live_kv

ROOT = Path(__file__).resolve().parents[2]
PAGE = 8
HEAD_A = list(range(1, 33))          # four whole pages
HEAD_B = list(range(101, 117))       # two whole pages


def _scheduler():
    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.utils.config import EngineConfig

    config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
    engine = InferenceEngine(
        config, init_params(config, jax.random.key(0)),
        EngineConfig(max_seqs=4, page_size=PAGE, num_pages=128, max_seq_len=256,
                     prefill_chunk=16, session_cache=False, mixed_step=False))
    return ContinuousBatchingScheduler(engine, eos_id=-1)


async def _drain(handle):
    while (await handle.events.get())["type"] == "token":
        pass


def _drive(prompts, heads=()):
    """Decode ``prompts`` together on the rehearsal scheduler; at every decode
    dispatch that all of them ride, keep the program's ``kv_tokens`` (Σ of the
    riders' contexts, what it notes on the annotation) beside the yardstick's
    sample of the same handles."""
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.utils.tracing import TRACER

    seen = []

    async def go():
        sched = _scheduler()
        for head in heads:
            assert sched.register_prefix(head + [99]) == len(head)
        trace_dispatch = sched._trace_dispatch

        def spy(kind, riders, **kw):
            if kind == "decode" and len(riders) == len(prompts):
                slots = {slot for slot, *_rest in riders}
                riding = [h for h in sched.decoding.values() if h.slot in slots]
                assert len(riding) == len(riders)
                seen.append((sum(kv for *_row, kv in riders),
                             live_kv.sample(riding, PAGE), live_kv.kv_tokens(riding)))
            trace_dispatch(kind, riders, **kw)

        sched._trace_dispatch = spy
        await sched.start()
        try:
            sampling = SamplingParams(temperature=0.0, max_new_tokens=12)
            handles = [await sched.submit(f"r{i}", p, sampling, trace_id=f"r{i}")
                       for i, p in enumerate(prompts)]

            await asyncio.wait_for(asyncio.gather(*map(_drain, handles)), timeout=240)
        finally:
            await sched.stop()

    enabled = TRACER.enabled
    TRACER.configure(enabled=True, flight_dir="")
    try:
        asyncio.run(go())
    finally:
        TRACER.configure(enabled=enabled)
        TRACER.clear()
    assert len(seen) >= 4, "the rows never decoded together"
    return seen


CASES = {
    # name: (prompts, heads registered, tokens the old count reads more than once)
    "three_rows_one_head": ([HEAD_A + [40, 41, 42], HEAD_A + [50] * 9, HEAD_A + [60, 61]],
                            [HEAD_A], 2 * 32),
    "no_head": ([[7, 8, 9] * 5, [9, 8, 7] * 7, [5] * 11], [], 0),
    "two_heads_once_each": ([HEAD_A + [40, 41, 42], HEAD_A + [50] * 9,
                             HEAD_B + [60, 61], HEAD_B + [70] * 5], [HEAD_A, HEAD_B],
                            32 + 16),
    "a_row_beside_a_head": ([HEAD_A + [40, 41, 42], HEAD_A + [50] * 9, [3] * 21],
                            [HEAD_A], 32),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_distinct_tokens_through_the_scheduler(case):
    prompts, heads, repeats = CASES[case]
    for noted, s, (total, distinct) in _drive(prompts, heads):
        # (c) the yardstick's total is the program's stat on the same handles
        assert total == s["kv_tokens"] == noted
        # (a) rows of one entry: its head once, whatever the rows; none: all of it
        assert distinct == s["kv_tokens_distinct"] == noted - repeats
        assert s["rows"] == len(prompts) and s["entries"] == len(heads)
        # (b) a count OF pages: the distinct page ids bound it from above, by
        # less than a page a row and an entry
        assert 0 <= s["page_tokens"] - distinct < PAGE * (len(prompts) + len(heads))


def test_shared_pages_are_the_same_physical_pages():
    """What makes the rule true of the program: rows of one head reference
    the SAME page ids for it, and their own pages are theirs alone."""
    from finchat_tpu.engine.sampler import SamplingParams

    async def go():
        sched = _scheduler()
        sched.register_prefix(HEAD_A + [99])
        await sched.start()
        try:
            sampling = SamplingParams(temperature=0.0, max_new_tokens=16)
            rows = [await sched.submit(f"r{i}", HEAD_A + [40 + i] * 3, sampling)
                    for i in range(3)]
            while not all(h.generated >= 2 for h in rows):
                await asyncio.sleep(0.001)
            lists = [list(h.page_list) for h in rows]
            shared = [h.shared_len for h in rows]

            await asyncio.wait_for(asyncio.gather(*map(_drain, rows)), timeout=240)
        finally:
            await sched.stop()
        return lists, shared

    lists, shared = asyncio.run(go())
    n = len(HEAD_A) // PAGE
    assert shared == [len(HEAD_A)] * 3
    assert lists[0][:n] == lists[1][:n] == lists[2][:n]
    own = [set(pages[n:]) for pages in lists]
    assert not (own[0] & own[1] or own[0] & own[2] or own[1] & own[2])
    assert not set(lists[0][:n]) & (own[0] | own[1] | own[2])


# --- the rule on hand-made handles -------------------------------------------

def _handle(ctx, shared=0, entry=None, gap=0, pages=()):
    return types.SimpleNamespace(kv_ctx=ctx + gap, kv_gap=gap, shared_len=shared,
                                 prefix_entry=entry, page_list=list(pages))


def test_the_rule_by_hand():
    a, b = object(), object()
    rows = [_handle(5000, 3840, a), _handle(7000, 3840, a), _handle(900, 256, b),
            _handle(300), _handle(4000, 3840, a)]
    total, distinct = live_kv.kv_tokens(rows)
    assert total == 17200
    assert distinct == (1160 + 3160 + 644 + 300 + 160) + 3840 + 256 == 9520
    assert live_kv.kv_tokens([]) == (0, 0)
    # a reference shorter than the entry's others counts inside the longest;
    # ``shared_len`` without an entry (nothing refcounted) is the row's own
    assert live_kv.kv_tokens([_handle(500, 384, a), _handle(600, 128, a)]) == (1100, 116 + 472 + 384)
    assert live_kv.kv_tokens([_handle(500, 384, None)] * 2) == (1000, 1000)
    # a bounded policy's evicted tokens are read by nobody
    assert live_kv.kv_tokens([_handle(500, 128, a, gap=256), _handle(400, 128, a)]) \
        == (900, 372 + 272 + 128)
    # the shared length never exceeds what the row has reached
    assert live_kv.kv_tokens([_handle(100, 384, a)]) == (100, 100)


def test_pages_by_hand():
    a = object()
    head = list(range(30))
    rows = [_handle(5000, 3840, a, pages=head + list(range(100, 160))),   # 40 pages live
            _handle(3841, 3840, a, pages=head + list(range(200, 260)))]   # 31 pages live
    s = live_kv.sample(rows, 128)
    assert s == {"rows": 2, "kv_tokens": 8841, "kv_tokens_distinct": 5001, "entries": 1,
                 "page_tokens": (30 + 10 + 1) * 128}
    assert 0 <= s["page_tokens"] - s["kv_tokens_distinct"] < 128 * 3


def test_the_metric_is_declared_last_with_its_reader_file():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = bench["per_layer"][-1]
    assert entry == {"name": "kv_distinct_share.sat", "unit": "%", "better": "lower",
                     "source": "device_trace", "layer": "scheduler", "moves": "output_tok_s"}
    spec = json.loads((ROOT / "perfbench/layer_metrics/kv_distinct_share.sat.json").read_text())
    assert spec == {"reader": "scope_trace",
                    "params": {"quantity": "kv_distinct_share", "kinds": ["decode"]}}
