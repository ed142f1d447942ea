"""The engine's steps through the paged walk's kernels: greedy tokens on the
``ref`` backend and on the kernels, and ``decode_step`` over page tables as the
scheduler lays them out — rows admitted on one prefix entry, a bounded row
whose sink is the shared head. Interpret mode here, on the chip under
``FINCHAT_TESTS_TPU=1`` (tests/test_pallas_attention.py).
"""

import jax
import jax.numpy as jnp
import numpy as np

from paged_walk_cases import INTERPRET

from finchat_tpu.ops.paged_attention import shared_head


def test_engine_end_to_end_pallas_backend():
    """The engine's chunked prefill + decode must produce identical greedy
    tokens whether attention runs through the jnp reference path or the
    Pallas kernels (interpret mode on the CPU test mesh)."""
    from finchat_tpu.engine.engine import InferenceEngine, commit_first_token
    from finchat_tpu.engine.kv_cache import PageAllocator, pages_needed
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.utils.config import EngineConfig

    config = PRESETS["tiny"]
    engine_cfg = EngineConfig(
        max_seqs=2, page_size=8, num_pages=32, max_seq_len=64, prefill_chunk=8
    )
    params = init_params(config, jax.random.key(0))
    prompt = [3, 7, 11, 200, 42, 9, 13, 55, 21, 8]  # 2 chunks
    n_new = 6

    def run(backend):
        eng = InferenceEngine(config, params, engine_cfg, attn_backend=backend)
        alloc = PageAllocator(engine_cfg.num_pages)
        pages = alloc.allocate("s", pages_needed(len(prompt) + n_new, eng.page_size))
        eng.set_page_table_row(0, pages)
        logits = eng.prefill(0, prompt)
        eng.state, tok = commit_first_token(
            eng.state, jnp.int32(0), logits,
            jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0),
        )
        out = [int(tok)]
        B = engine_cfg.max_seqs
        active = jnp.zeros((B,), bool).at[0].set(True)
        zeros, ones, zk = jnp.zeros((B,)), jnp.ones((B,)), jnp.zeros((B,), jnp.int32)
        for _ in range(n_new - 1):
            out.append(int(eng.decode(active, zeros, ones, zk)[0]))
        return out

    assert run("ref") == run("pallas-interpret")


def _decode_logits_on_both_backends(engine, state, active):
    """``decode_step`` from one state on ``ref`` and on the kernels
    (interpret mode here); the step donates its state, so each gets a copy."""
    from finchat_tpu.engine.engine import decode_step

    B = active.shape[0]
    zeros, ones, zk = jnp.zeros((B,)), jnp.ones((B,)), jnp.zeros((B,), jnp.int32)

    def logits(backend):
        _, _, out, _ = decode_step(
            engine.params, jax.tree.map(jnp.copy, state), active, zeros, ones, zk,
            config=engine.config, page_size=engine.page_size,
            attn_backend=backend, return_logits=True)
        return np.asarray(out)[np.asarray(active)]

    return logits("ref"), logits("pallas" if not INTERPRET else "pallas-interpret")


def test_decode_step_over_a_scheduler_built_shared_head_matches_ref():
    """Three rows admitted on ONE prefix entry, as the scheduler lays them
    out (the same four physical pages at the head of each page table): the
    decode step through the kernels, shared-head pass engaged, gives the
    ``ref`` backend's logits."""
    import asyncio
    import dataclasses

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.utils.config import EngineConfig

    page, head = 8, list(range(1, 33))  # four whole pages
    config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
    engine = InferenceEngine(
        config, init_params(config, jax.random.key(0)),
        EngineConfig(max_seqs=4, page_size=page, num_pages=128, max_seq_len=256,
                     prefill_chunk=16, session_cache=False, mixed_step=False),
        attn_backend="ref")
    caught = []
    decode = engine.decode

    def spy(active, *args, **kw):
        if not caught and int(np.sum(np.asarray(active))) == 3:
            caught.append((jax.tree.map(jnp.copy, engine.state), jnp.asarray(active)))
        return decode(active, *args, **kw)

    engine.decode = spy

    async def drain(handle):
        while (await handle.events.get())["type"] == "token":
            pass

    async def go():
        sched = ContinuousBatchingScheduler(engine, eos_id=-1)
        assert sched.register_prefix(head + [99]) == len(head)
        await sched.start()
        try:
            sampling = SamplingParams(temperature=0.0, max_new_tokens=8)
            handles = [await sched.submit(f"r{i}", head + tail, sampling)
                       for i, tail in enumerate([[40, 41, 42], [50] * 9, [60, 61]])]
            await asyncio.wait_for(asyncio.gather(*map(drain, handles)), timeout=240)
        finally:
            await sched.stop()

    asyncio.run(go())
    assert caught, "the three rows never decoded together"
    state, active = caught[0]
    member, shared = shared_head(
        state.page_table, state.context_lens + active, page, active)
    assert (int(member.sum()), int(shared[0])) == (3, 4)
    want, got = _decode_logits_on_both_backends(engine, state, active)
    np.testing.assert_allclose(got, want, atol=1e-4 if INTERPRET else 5e-2, rtol=1e-4)


def test_decode_step_with_a_gapped_row_in_the_shared_head_matches_ref():
    """Bounded KV: a row whose policy evicted pages behind the pinned sink
    keeps the sink at the head of its (compacted) page list. The sink pages
    it shares with two unbounded rows are read in the shared pass, its window
    behind them at compacted positions, as ``ref`` reads them."""
    import dataclasses

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.utils.config import EngineConfig

    page = 8
    config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
    engine = InferenceEngine(
        config, init_params(config, jax.random.key(0)),
        EngineConfig(max_seqs=4, page_size=page, num_pages=64, max_seq_len=128,
                     prefill_chunk=16, session_cache=False, mixed_step=False),
        attn_backend="ref")
    state = engine.state
    table = np.zeros(state.page_table.shape, np.int32)
    table[0, :7] = [3, 4, 5, 10, 11, 12, 13]
    table[1, :5] = [3, 4, 5, 20, 21]
    table[3, :6] = [3, 4, 5, 30, 31, 32]  # slot 2 stays inactive
    contexts = np.array([50, 36, 0, 41 + 3 * page], np.int32)  # absolute
    gaps = np.array([0, 0, 0, 3 * page], np.int32)  # three pages evicted
    pools = [jax.random.normal(jax.random.key(i), pool.shape, pool.dtype)
             for i, pool in enumerate((state.k_pages, state.v_pages))]
    state = dataclasses.replace(
        state, k_pages=pools[0], v_pages=pools[1], page_table=jnp.asarray(table),
        context_lens=jnp.asarray(contexts), kv_gaps=jnp.asarray(gaps),
        last_tokens=jnp.asarray([5, 6, 0, 7], jnp.int32))
    active = jnp.asarray([True, True, False, True])
    member, shared = shared_head(
        state.page_table, state.context_lens - state.kv_gaps + active, page, active)
    assert (member.tolist(), int(shared[0])) == ([1, 1, 0, 1], 3)
    want, got = _decode_logits_on_both_backends(engine, state, active)
    np.testing.assert_allclose(got, want, atol=1e-4 if INTERPRET else 5e-2, rtol=1e-4)
