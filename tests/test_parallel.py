"""Parallel layer on the forced 8-device CPU mesh (SURVEY §4.3): mesh
resolution, ring attention vs reference, TP-sharded inference golden match,
sharded train step, and the driver's multichip dryrun."""

import jax
import jax.numpy as jnp
import pytest

from finchat_tpu.models.llama import LlamaConfig, init_params
from finchat_tpu.ops.refs import mha_reference
from finchat_tpu.ops.ring_attention import ring_attention
from finchat_tpu.parallel.mesh import MeshSpec, build_mesh


def test_mesh_spec_resolution():
    assert MeshSpec(data=2, model=-1).resolve(8) == (2, 1, 1, 1, 4)
    assert MeshSpec(data=1, seq=1, expert=1, model=8).resolve(8) == (1, 1, 1, 1, 8)
    assert MeshSpec(data=1, pipe=2, model=-1).resolve(8) == (1, 2, 1, 1, 4)
    with pytest.raises(ValueError):
        MeshSpec(data=3, model=-1).resolve(8)
    with pytest.raises(ValueError):
        MeshSpec(data=2, model=2).resolve(8)  # product mismatch


def test_pipe_axis_tolerated_by_shardings():
    """SURVEY §2.3: the PP axis exists in the mesh and param/state shardings
    (which never name 'pipe') place cleanly on a pipe>1 mesh."""
    from finchat_tpu.engine.engine import create_state
    from finchat_tpu.parallel.sharding import (
        llama_param_shardings, shard_decode_state, shard_params,
    )
    from finchat_tpu.utils.config import EngineConfig

    mesh = build_mesh(MeshSpec(data=1, pipe=2, seq=1, expert=1, model=4))
    assert mesh.shape["pipe"] == 2
    config = LlamaConfig(
        vocab_size=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=4,
        hidden_dim=64, max_seq_len=32,
    )
    params = shard_params(init_params(config, jax.random.key(0)), llama_param_shardings(mesh))
    ecfg = EngineConfig(max_seqs=2, page_size=8, num_pages=16, max_seq_len=32, prefill_chunk=8)
    state = shard_decode_state(create_state(config, ecfg, 4), mesh)
    assert state.k_pages.sharding.mesh.shape["pipe"] == 2


def test_ring_attention_matches_reference():
    mesh = build_mesh(MeshSpec(data=1, seq=8, expert=1, model=1))
    B, S, H, Hkv, D = 2, 64, 4, 2, 16
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, Hkv, D), jnp.float32)
    for causal in (True, False):
        out = ring_attention(q, k, v, mesh=mesh, causal=causal)
        ref = mha_reference(q, k, v, causal=causal)
        assert float(jnp.abs(out - ref).max()) < 1e-4, f"causal={causal}"


def test_tp_sharded_engine_matches_unsharded():
    """Greedy decode must be bit-identical between 1-device and TP=8."""
    from finchat_tpu.engine.engine import InferenceEngine, commit_first_token
    from finchat_tpu.engine.kv_cache import PageAllocator, pages_needed
    from finchat_tpu.utils.config import EngineConfig

    config = LlamaConfig(
        vocab_size=128, dim=64, n_layers=2, n_heads=8, n_kv_heads=8,
        hidden_dim=128, max_seq_len=64,
    )
    params = init_params(config, jax.random.key(0))
    ecfg = EngineConfig(max_seqs=2, page_size=8, num_pages=16, max_seq_len=64, prefill_chunk=8)
    prompt = [5, 9, 2, 100, 17, 3]
    n_new = 6

    def run(mesh):
        eng = InferenceEngine(config, params, ecfg, mesh=mesh)
        alloc = PageAllocator(ecfg.num_pages)
        pages = alloc.allocate("s", pages_needed(len(prompt) + n_new, 8))
        eng.set_page_table_row(0, pages)
        logits = eng.prefill(0, prompt)
        eng.state, tok = commit_first_token(
            eng.state, jnp.int32(0), logits, jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0)
        )
        out = [int(tok)]
        active = jnp.zeros((2,), bool).at[0].set(True)
        z, o, zk = jnp.zeros((2,)), jnp.ones((2,)), jnp.zeros((2,), jnp.int32)
        for _ in range(n_new - 1):
            out.append(int(eng.decode(active, z, o, zk)[0]))
        return out

    unsharded = run(None)
    tp_mesh = build_mesh(MeshSpec(data=1, seq=1, expert=1, model=8))
    sharded = run(tp_mesh)
    assert unsharded == sharded


def test_train_step_dp_tp_sp():
    from finchat_tpu.parallel.sharding import llama_param_shardings, shard_params
    from finchat_tpu.train.train_step import (
        init_train_state, make_optimizer, make_train_step, shard_batch,
    )

    mesh = build_mesh(MeshSpec(data=2, seq=2, expert=1, model=2))
    config = LlamaConfig(
        vocab_size=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        hidden_dim=64, max_seq_len=32,
    )
    params = shard_params(init_params(config, jax.random.key(0)), llama_param_shardings(mesh))
    optimizer = make_optimizer(learning_rate=1e-2)
    step = make_train_step(config, optimizer, mesh, use_ring_attention=True)
    state = init_train_state(config, params, optimizer)
    tokens = shard_batch(
        jax.random.randint(jax.random.key(1), (4, 16), 0, 64), mesh, seq_sharded=True
    )
    losses = []
    for _ in range(5):
        state, loss = step(state, tokens)
        losses.append(float(loss))
    assert all(jnp.isfinite(jnp.asarray(losses)))
    assert losses[-1] < losses[0], losses  # memorizing one tiny batch


@pytest.mark.slow  # ~50 s: the full multichip dryrun matrix on 8 CPU devices
def test_dryrun_multichip_entrypoint():
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parent.parent / "__graft_entry__.py"
    spec = importlib.util.spec_from_file_location("graft_entry", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.dryrun_multichip(8)

    fn, args = module.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[0] == args[1].shape[0]


def test_ring_prefill_serving_matches_chunked():
    """SURVEY §5.7c: a long prompt prefilled through the seq-sharded ring
    path (TP x SP mesh) must leave the engine in the same state as batched
    chunked prefill — same greedy continuation, same last-token logits."""
    import numpy as np

    from finchat_tpu.engine.engine import InferenceEngine, commit_first_token
    from finchat_tpu.engine.kv_cache import PageAllocator, pages_needed
    from finchat_tpu.utils.config import EngineConfig

    config = LlamaConfig(
        vocab_size=128, dim=64, n_layers=2, n_heads=8, n_kv_heads=4,
        hidden_dim=128, max_seq_len=128,
    )
    params = init_params(config, jax.random.key(0))
    prompt = list(np.random.RandomState(3).randint(1, 128, size=50))
    n_new = 5

    def run(mesh, ring_min):
        ecfg = EngineConfig(
            max_seqs=2, page_size=8, num_pages=32, max_seq_len=128,
            prefill_chunk=16, ring_prefill_min_tokens=ring_min,
        )
        eng = InferenceEngine(config, params, ecfg, mesh=mesh)
        alloc = PageAllocator(ecfg.num_pages)
        pages = alloc.allocate("s", pages_needed(len(prompt) + n_new, 8))
        eng.set_page_table_row(0, pages)
        if ring_min <= len(prompt) and mesh is not None:
            assert eng._use_ring_prefill(len(prompt))
        logits = eng.prefill(0, prompt)
        eng.state, tok = commit_first_token(
            eng.state, jnp.int32(0), logits, jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0)
        )
        out = [int(tok)]
        active = jnp.zeros((2,), bool).at[0].set(True)
        z, o, zk = jnp.zeros((2,)), jnp.ones((2,)), jnp.zeros((2,), jnp.int32)
        for _ in range(n_new - 1):
            out.append(int(eng.decode(active, z, o, zk)[0]))
        return np.asarray(logits, np.float32), out

    mesh = build_mesh(MeshSpec(data=1, seq=2, expert=1, model=4))
    ring_logits, ring_tokens = run(mesh, ring_min=16)  # ring path engaged
    mesh_logits, mesh_tokens = run(mesh, ring_min=10_000)  # chunked, same mesh
    _, plain_tokens = run(None, ring_min=10_000)  # chunked, unsharded

    # same mesh, different prefill path: logits agree to bf16-activation
    # numerics (the accumulation orders differ: blockwise ring softmax vs
    # gathered-pages reference)
    np.testing.assert_allclose(ring_logits, mesh_logits, atol=2e-2, rtol=2e-2)
    # the greedy continuation is identical across ring/chunked/unsharded
    assert ring_tokens == mesh_tokens == plain_tokens


@pytest.mark.parametrize("sp_mode,mesh_spec", [
    ("ring", MeshSpec(data=1, seq=2, expert=1, model=4)),
    # ulysses divisibility: per-TP heads (4) and kv (2) divide seq=2
    ("ulysses", MeshSpec(data=2, seq=2, expert=1, model=2)),
])
def test_segmented_ring_prefill_matches_monolithic(sp_mode, mesh_spec):
    """VERDICT r4 weak #8 (chunked SP prefill): prefilling a long prompt
    in segments — each SP-attending (ring or Ulysses) to itself and
    folding the cached earlier segments (engine.prefill_ring_segment) —
    must leave the engine in the same state as the one-shot SP prefill:
    same final-token logits, same greedy continuation."""
    import numpy as np

    from finchat_tpu.engine.engine import InferenceEngine, commit_first_token
    from finchat_tpu.engine.kv_cache import PageAllocator, pages_needed
    from finchat_tpu.utils.config import EngineConfig

    config = LlamaConfig(
        vocab_size=128, dim=64, n_layers=2, n_heads=8, n_kv_heads=4,
        hidden_dim=128, max_seq_len=256,
    )
    params = init_params(config, jax.random.key(0))
    prompt = list(np.random.RandomState(11).randint(1, 128, size=100))
    n_new = 5
    mesh = build_mesh(mesh_spec)

    def run(ring_chunk):
        ecfg = EngineConfig(
            max_seqs=2, page_size=8, num_pages=64, max_seq_len=256,
            prefill_chunk=16, ring_prefill_min_tokens=16,
            ring_prefill_chunk=ring_chunk, sp_mode=sp_mode,
        )
        eng = InferenceEngine(config, params, ecfg, mesh=mesh)
        assert eng.sp_mode == sp_mode  # no silent fallback in this test
        alloc = PageAllocator(ecfg.num_pages)
        pages = alloc.allocate("s", pages_needed(len(prompt) + n_new, 8))
        eng.set_page_table_row(0, pages)
        if ring_chunk:
            rc = eng.ring_segment_tokens()
            assert rc == ring_chunk  # already a seq multiple here
            logits = None
            for start in range(0, len(prompt), rc):
                logits = eng.prefill_ring_segment(
                    0, prompt[start : start + rc], start
                )
            assert int(np.asarray(eng.state.context_lens)[0]) == len(prompt)
        else:
            logits = eng.prefill_ring(0, prompt)
        eng.state, tok = commit_first_token(
            eng.state, jnp.int32(0), logits, jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0)
        )
        out = [int(tok)]
        active = jnp.zeros((2,), bool).at[0].set(True)
        z, o, zk = jnp.zeros((2,)), jnp.ones((2,)), jnp.zeros((2,), jnp.int32)
        for _ in range(n_new - 1):
            out.append(int(eng.decode(active, z, o, zk)[0]))
        return np.asarray(logits, np.float32), out

    mono_logits, mono_tokens = run(0)
    seg_logits, seg_tokens = run(32)  # 100 tokens -> 4 segments
    # tolerance is the bf16-activation envelope: the segmented fold
    # accumulates in a different order than the monolithic pass
    np.testing.assert_allclose(seg_logits, mono_logits, atol=4e-2, rtol=4e-2)
    assert seg_tokens == mono_tokens


def test_scheduler_decode_progress_during_ring_prefill():
    """The 63-streams-stall cliff is dead: with chunked ring prefill on,
    an in-flight decode stream keeps receiving tokens WHILE a long
    ring-eligible prompt prefills, and the ring-prefilled request streams
    the same tokens as it would with a monolithic ring prefill."""
    import asyncio

    import numpy as np

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.models.tokenizer import ByteTokenizer
    from finchat_tpu.utils.config import EngineConfig

    config = LlamaConfig(
        vocab_size=300, dim=64, n_layers=2, n_heads=8, n_kv_heads=4,
        hidden_dim=128, max_seq_len=256,
    )
    params = init_params(config, jax.random.key(0))
    mesh = build_mesh(MeshSpec(data=1, seq=2, expert=1, model=4))
    tok = ByteTokenizer()
    long_prompt = list(np.random.RandomState(5).randint(5, 250, size=100))

    async def run(ring_chunk):
        ecfg = EngineConfig(
            max_seqs=2, page_size=8, num_pages=64, max_seq_len=256,
            prefill_chunk=16, ring_prefill_min_tokens=64,
            ring_prefill_chunk=ring_chunk,
        )
        eng = InferenceEngine(config, params, ecfg, mesh=mesh)
        sched = ContinuousBatchingScheduler(eng, eos_id=tok.eos_id)
        await sched.start()
        try:
            stream = await sched.submit(
                "stream", [1, 2, 3, 4, 5],
                SamplingParams(temperature=0.0, max_new_tokens=48),
            )
            seen = []
            while len(seen) < 4:  # steady-state decode first
                event = await asyncio.wait_for(stream.events.get(), timeout=120)
                assert event["type"] == "token", event
                seen.append(event["token_id"])
            ring_handle = await sched.submit(
                "ring", long_prompt,
                SamplingParams(temperature=0.0, max_new_tokens=6),
            )
            during = 0
            ring_tokens = []
            while ring_handle.first_token_at is None and not ring_handle.finished:
                event = await asyncio.wait_for(stream.events.get(), timeout=120)
                if event["type"] != "token":
                    break
                during += 1
            while True:
                event = await asyncio.wait_for(ring_handle.events.get(), timeout=120)
                if event["type"] == "token":
                    ring_tokens.append(event["token_id"])
                elif event["type"] == "done":
                    break
                else:
                    raise AssertionError(event)
            return during, ring_tokens
        finally:
            await sched.stop()

    during_seg, seg_tokens = asyncio.run(run(32))  # 100 tokens -> 4 segments
    # the monolithic run is the token-equality oracle only; its `during`
    # count is timing-dependent (a token can land before/after the single
    # ring round) so the stall contrast is not asserted on it
    _, mono_tokens = asyncio.run(run(0))
    assert seg_tokens == mono_tokens  # same stream either way
    # ≥3 extra prefill rounds ran with a decode step interleaving each;
    # the stream must have advanced while the long prompt prefilled
    assert during_seg >= 2, f"stream starved during segmented ring prefill ({during_seg})"


def test_segmented_ring_composes_with_prefix_cache():
    """With chunked ring prefill, a ring-eligible prompt opening with a
    registered shared head KEEPS the prefix-cache hit (the old monolithic
    path had to skip matching — 'ring assumes position 0'): the first
    segment starts at shared_len with the cached head folded as prefix,
    and the stream equals the uncached ring run token-for-token."""
    import asyncio

    import numpy as np

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.models.tokenizer import ByteTokenizer
    from finchat_tpu.utils.config import EngineConfig

    config = LlamaConfig(
        vocab_size=300, dim=64, n_layers=2, n_heads=8, n_kv_heads=4,
        hidden_dim=128, max_seq_len=256,
    )
    params = init_params(config, jax.random.key(0))
    mesh = build_mesh(MeshSpec(data=1, seq=2, expert=1, model=4))
    tok = ByteTokenizer()
    rng = np.random.RandomState(9)
    head = list(rng.randint(5, 250, size=48))  # 6 whole pages
    prompt = head + list(rng.randint(5, 250, size=52))  # 100 total, ring-eligible

    async def run(register):
        ecfg = EngineConfig(
            max_seqs=2, page_size=8, num_pages=64, max_seq_len=256,
            prefill_chunk=16, ring_prefill_min_tokens=64,
            ring_prefill_chunk=32,
        )
        eng = InferenceEngine(config, params, ecfg, mesh=mesh)
        sched = ContinuousBatchingScheduler(eng, eos_id=tok.eos_id)
        if register:
            assert sched.register_prefix(head) == 48
        await sched.start()
        try:
            handle = await sched.submit(
                "s", prompt, SamplingParams(temperature=0.0, max_new_tokens=6)
            )
            tokens = []
            while True:
                event = await asyncio.wait_for(handle.events.get(), timeout=120)
                if event["type"] == "token":
                    tokens.append(event["token_id"])
                elif event["type"] == "done":
                    break
                else:
                    raise AssertionError(event)
            return handle, tokens
        finally:
            await sched.stop()

    from finchat_tpu.utils.metrics import METRICS

    saved0 = METRICS.get("finchat_prefix_tokens_saved_total")
    cached_handle, cached_tokens = asyncio.run(run(True))
    # the hit engaged (48 head tokens never re-prefilled)...
    assert METRICS.get("finchat_prefix_tokens_saved_total") == saved0 + 48
    assert cached_handle.ring_path  # ...on the ring path
    plain_handle, plain_tokens = asyncio.run(run(False))
    assert plain_handle.ring_path
    assert METRICS.get("finchat_prefix_tokens_saved_total") == saved0 + 48
    assert cached_tokens == plain_tokens


def test_ulysses_serving_prefill_matches_chunked():
    """SURVEY §5.7d: sp_mode='ulysses' must serve the seq-sharded long
    prefill with the same greedy continuation as chunked prefill, and an
    indivisible head count must fall back to ring rather than fail."""
    import numpy as np

    from finchat_tpu.engine.engine import InferenceEngine, commit_first_token
    from finchat_tpu.engine.kv_cache import PageAllocator, pages_needed
    from finchat_tpu.utils.config import EngineConfig

    config = LlamaConfig(
        vocab_size=128, dim=64, n_layers=2, n_heads=8, n_kv_heads=4,
        hidden_dim=128, max_seq_len=128,
    )
    params = init_params(config, jax.random.key(0))
    prompt = list(np.random.RandomState(5).randint(1, 128, size=50))
    n_new = 5
    # seq=2, model=2: per-shard H=4, Hkv=2 — both divisible by seq ✓
    mesh = build_mesh(MeshSpec(data=2, seq=2, expert=1, model=2))

    def run(sp_mode, ring_min):
        ecfg = EngineConfig(
            max_seqs=2, page_size=8, num_pages=32, max_seq_len=128,
            prefill_chunk=16, ring_prefill_min_tokens=ring_min, sp_mode=sp_mode,
        )
        eng = InferenceEngine(config, params, ecfg, mesh=mesh)
        if sp_mode == "ulysses" and ring_min <= len(prompt):
            assert eng.sp_mode == "ulysses"  # no silent fallback in this shape
        alloc = PageAllocator(ecfg.num_pages)
        pages = alloc.allocate("s", pages_needed(len(prompt) + n_new, 8))
        eng.set_page_table_row(0, pages)
        logits = eng.prefill(0, prompt)
        eng.state, tok = commit_first_token(
            eng.state, jnp.int32(0), logits, jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0)
        )
        out = [int(tok)]
        active = jnp.zeros((2,), bool).at[0].set(True)
        z, o, zk = jnp.zeros((2,)), jnp.ones((2,)), jnp.zeros((2,), jnp.int32)
        for _ in range(n_new - 1):
            out.append(int(eng.decode(active, z, o, zk)[0]))
        return out

    ulysses_tokens = run("ulysses", ring_min=16)  # seq-sharded path engaged
    chunked_tokens = run("ring", ring_min=10_000)  # chunked on the same mesh
    assert ulysses_tokens == chunked_tokens

    # fallback: seq axis does not divide per-shard KV heads on this mesh
    bad_mesh = build_mesh(MeshSpec(data=1, seq=2, expert=1, model=4))
    ecfg = EngineConfig(
        max_seqs=2, page_size=8, num_pages=32, max_seq_len=128,
        prefill_chunk=16, ring_prefill_min_tokens=16, sp_mode="ulysses",
    )
    eng = InferenceEngine(config, params, ecfg, mesh=bad_mesh)
    assert eng.sp_mode == "ring"  # Hkv/tp = 1 not divisible by seq=2


def test_scheduler_routes_long_prompts_through_ring_prefill():
    """The SERVING path (scheduler), not just the engine API, must engage
    the seq-sharded ring prefill for long prompts on a seq>1 mesh."""
    import asyncio

    import numpy as np

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.models.tokenizer import ByteTokenizer
    from finchat_tpu.utils.config import EngineConfig

    config = LlamaConfig(
        vocab_size=300, dim=64, n_layers=2, n_heads=8, n_kv_heads=4,
        hidden_dim=128, max_seq_len=128,
    )
    mesh = build_mesh(MeshSpec(data=1, seq=2, expert=1, model=4))
    ecfg = EngineConfig(
        max_seqs=2, page_size=8, num_pages=64, max_seq_len=128,
        prefill_chunk=16, ring_prefill_min_tokens=32, warmup_on_start=False,
    )
    engine = InferenceEngine(config, init_params(config, jax.random.key(0)), ecfg, mesh=mesh)
    tok = ByteTokenizer()

    ring_calls: list[int] = []
    real_ring = engine.prefill_ring

    def spy_ring(slot, ids):
        ring_calls.append(len(ids))
        return real_ring(slot, ids)

    engine.prefill_ring = spy_ring

    async def run():
        scheduler = ContinuousBatchingScheduler(engine, eos_id=tok.eos_id)
        await scheduler.start()
        try:
            long_prompt = tok.encode("x" * 60, add_bos=True)  # 61 >= 32
            handle = await scheduler.submit(
                "long", long_prompt, SamplingParams(temperature=0.0, max_new_tokens=4)
            )
            got = 0
            while True:
                event = await asyncio.wait_for(handle.events.get(), timeout=120)
                if event["type"] == "token":
                    got += 1
                elif event["type"] == "done":
                    break
                else:
                    raise AssertionError(event)
            return got
        finally:
            await scheduler.stop()

    got = asyncio.run(run())
    assert got == 4
    assert ring_calls == [61], ring_calls


def test_ulysses_attention_matches_reference():
    """SURVEY §5.7d: Ulysses all-to-all SP == dense reference, MHA and GQA."""
    from finchat_tpu.ops.ulysses import ulysses_attention

    mesh = build_mesh(MeshSpec(data=2, seq=4, expert=1, model=1))
    B, S, D = 2, 64, 16
    for H, Hkv in ((8, 8), (8, 4)):
        kq, kk, kv = jax.random.split(jax.random.key(H), 3)
        q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
        k = jax.random.normal(kk, (B, S, Hkv, D), jnp.float32)
        v = jax.random.normal(kv, (B, S, Hkv, D), jnp.float32)
        for causal in (True, False):
            out = ulysses_attention(q, k, v, mesh=mesh, causal=causal)
            ref = mha_reference(q, k, v, causal=causal)
            assert float(jnp.abs(out - ref).max()) < 1e-4, (H, Hkv, causal)


def test_ulysses_rejects_indivisible_heads():
    from finchat_tpu.ops.ulysses import ulysses_attention

    mesh = build_mesh(MeshSpec(data=1, seq=8, expert=1, model=1))
    q = jnp.zeros((1, 16, 4, 8))  # 4 heads, seq axis 8 -> indivisible
    with pytest.raises(ValueError, match="ring attention instead"):
        ulysses_attention(q, q, q, mesh=mesh)


def test_train_step_ulysses_sp():
    """The Ulysses SP mode trains: DP x SP(ulysses) x TP on the CPU mesh."""
    from finchat_tpu.parallel.sharding import llama_param_shardings, shard_params
    from finchat_tpu.train.train_step import (
        init_train_state, make_optimizer, make_train_step, shard_batch,
    )

    mesh = build_mesh(MeshSpec(data=2, seq=2, expert=1, model=2))
    config = LlamaConfig(
        vocab_size=64, dim=64, n_layers=2, n_heads=8, n_kv_heads=4,
        hidden_dim=64, max_seq_len=32,
    )  # per-TP-shard heads 4/2, divisible by seq=2
    params = shard_params(init_params(config, jax.random.key(0)), llama_param_shardings(mesh))
    optimizer = make_optimizer(learning_rate=1e-2)
    step = make_train_step(config, optimizer, mesh, use_ring_attention=True, sp_mode="ulysses")
    state = init_train_state(config, params, optimizer)
    tokens = shard_batch(
        jax.random.randint(jax.random.key(1), (4, 16), 0, 64), mesh, seq_sharded=True
    )
    losses = []
    for _ in range(5):
        state, loss = step(state, tokens)
        losses.append(float(loss))
    assert all(jnp.isfinite(jnp.asarray(losses)))
    assert losses[-1] < losses[0], losses


def test_pipeline_forward_matches_plain():
    """C4 (SURVEY §2.3): the GPipe-style stage pipeline at pipe=2 computes
    the SAME function as the plain scanned forward, for every microbatch
    count (fill/drain schedule correctness). The data=2 and model=2 axes
    of this mesh partition IN-STAGE (r5): batch shards over data when
    n_micro divides, weights shard Megatron-style over model."""
    import numpy as np

    from finchat_tpu.models.llama import forward, make_causal_attention
    from finchat_tpu.parallel.pipeline import pipeline_forward, shard_params_for_pipeline

    config = LlamaConfig(
        vocab_size=64, dim=32, n_layers=4, n_heads=4, n_kv_heads=2,
        hidden_dim=64, max_seq_len=32, dtype=jnp.float32,
    )
    mesh = build_mesh(MeshSpec(data=2, pipe=2, seq=1, expert=1, model=2))
    params = init_params(config, jax.random.key(0))
    B, S = 4, 16
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, 64)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    ref, _ = forward(params, tokens, positions, config=config,
                     attention=make_causal_attention("ref"))
    from jax.sharding import PartitionSpec as P

    sharded = shard_params_for_pipeline(params, mesh, config)
    # r5: the model=2 axis now actually partitions in-stage (Megatron
    # column/row shards + psum in the stage block), exercised here
    assert sharded["layers"]["attn_q"].sharding.spec == P("pipe", None, "model")
    assert sharded["layers"]["mlp_down"].sharding.spec == P("pipe", "model", None)
    for n_micro in (1, 2, 4):
        got = pipeline_forward(
            sharded, tokens, positions, config=config, mesh=mesh, n_micro=n_micro
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=1e-4, rtol=1e-4,
            err_msg=f"n_micro={n_micro}",
        )

    # PER-ROW position offsets: each stage must use the positions of the
    # microbatch it currently holds, not microbatch 0's
    offsets = jnp.asarray([0, 3, 8, 1])[:, None]
    pos2 = offsets + jnp.arange(S)[None, :]
    ref2, _ = forward(params, tokens, pos2, config=config,
                      attention=make_causal_attention("ref"))
    got2 = pipeline_forward(
        sharded, tokens, pos2, config=config, mesh=mesh, n_micro=4
    )
    np.testing.assert_allclose(
        np.asarray(got2), np.asarray(ref2), atol=1e-4, rtol=1e-4,
        err_msg="per-row positions",
    )


def test_pipeline_sp_forward_matches_plain():
    """PP x SP: with a seq axis in the mesh the stage block ring-attends
    over seq-sharded activations (the ring body runs directly inside the
    all-manual region); the function computed must still equal the plain
    scanned forward — composed with in-stage TP (data=1 on this 8-device
    mesh; the 4-axis composition needs 16 devices and is not run here)."""
    import numpy as np

    from finchat_tpu.models.llama import forward, make_causal_attention
    from finchat_tpu.parallel.pipeline import pipeline_forward, shard_params_for_pipeline

    config = LlamaConfig(
        vocab_size=64, dim=32, n_layers=4, n_heads=4, n_kv_heads=2,
        hidden_dim=64, max_seq_len=32, dtype=jnp.float32,
    )
    mesh = build_mesh(MeshSpec(data=1, pipe=2, seq=2, expert=1, model=2))
    params = init_params(config, jax.random.key(0))
    B, S = 4, 16
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, 64)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    ref, _ = forward(params, tokens, positions, config=config,
                     attention=make_causal_attention("ref"))
    sharded = shard_params_for_pipeline(params, mesh, config)
    for n_micro in (1, 2):
        got = pipeline_forward(
            sharded, tokens, positions, config=config, mesh=mesh, n_micro=n_micro
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=1e-4, rtol=1e-4,
            err_msg=f"n_micro={n_micro}",
        )


def test_pipeline_sp_train_step_learns():
    """PP x SP backward: scan + ppermute(pipe) + ring(seq) + psum(model)
    all transpose; loss decreases memorizing one tiny batch."""
    from finchat_tpu.parallel.pipeline import (
        make_pipeline_train_step, shard_params_for_pipeline,
    )
    from finchat_tpu.train.train_step import init_train_state, make_optimizer

    config = LlamaConfig(
        vocab_size=64, dim=32, n_layers=4, n_heads=4, n_kv_heads=2,
        hidden_dim=64, max_seq_len=32,
    )
    mesh = build_mesh(MeshSpec(data=1, pipe=2, seq=2, expert=1, model=2))
    params = shard_params_for_pipeline(init_params(config, jax.random.key(0)), mesh, config)
    optimizer = make_optimizer(learning_rate=1e-2)
    step = make_pipeline_train_step(config, optimizer, mesh, n_micro=2)
    state = init_train_state(config, params, optimizer)
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, 64)
    losses = []
    for _ in range(5):
        state, loss = step(state, tokens)
        losses.append(float(loss))
    assert all(jnp.isfinite(jnp.asarray(losses))), losses
    assert losses[-1] < losses[0], losses


@pytest.mark.slow  # ~20 s: fresh-interpreter subprocess + 4-axis compile
def test_pipeline_four_axis_composition_subprocess():
    """pipe x data x seq x model ALL > 1 needs 16 devices — more than the
    conftest's 8-device mesh — so it runs in a fresh subprocess with its
    own 16-device virtual CPU mesh: forward equality vs the plain scanned
    forward, and a learning train step."""
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
        import jax
        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp, numpy as np
        from finchat_tpu.models.llama import (
            LlamaConfig, init_params, forward, make_causal_attention,
        )
        from finchat_tpu.parallel.mesh import MeshSpec, build_mesh
        from finchat_tpu.parallel.pipeline import (
            pipeline_forward, shard_params_for_pipeline, make_pipeline_train_step,
        )
        from finchat_tpu.train.train_step import init_train_state, make_optimizer

        config = LlamaConfig(vocab_size=64, dim=32, n_layers=4, n_heads=4,
                             n_kv_heads=2, hidden_dim=64, max_seq_len=32,
                             dtype=jnp.float32)
        mesh = build_mesh(MeshSpec(data=2, pipe=2, seq=2, expert=1, model=2))
        params = init_params(config, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, 64)
        positions = jnp.broadcast_to(jnp.arange(16), (4, 16))
        ref, _ = forward(params, tokens, positions, config=config,
                         attention=make_causal_attention("ref"))
        sharded = shard_params_for_pipeline(params, mesh, config)
        got = pipeline_forward(sharded, tokens, positions, config=config,
                               mesh=mesh, n_micro=2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)
        opt = make_optimizer(learning_rate=1e-2)
        step = make_pipeline_train_step(config, opt, mesh, n_micro=2)
        state = init_train_state(config, sharded, opt)
        losses = []
        for _ in range(4):
            state, loss = step(state, tokens)
            losses.append(float(loss))
        assert losses[-1] < losses[0], losses
        print("FOUR_AXIS_OK")
    """)
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    proc = subprocess.run(
        [sys.executable, "-c", script],
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "FOUR_AXIS_OK" in proc.stdout


def test_pipeline_train_step_learns():
    """The pipelined train step backprops through the fill/drain schedule
    (scan + ppermute transpose): loss decreases memorizing one tiny batch."""
    from finchat_tpu.parallel.pipeline import (
        make_pipeline_train_step, shard_params_for_pipeline,
    )
    from finchat_tpu.train.train_step import init_train_state, make_optimizer

    config = LlamaConfig(
        vocab_size=64, dim=32, n_layers=4, n_heads=4, n_kv_heads=2,
        hidden_dim=64, max_seq_len=32,
    )
    mesh = build_mesh(MeshSpec(data=2, pipe=2, seq=1, expert=1, model=2))
    params = shard_params_for_pipeline(init_params(config, jax.random.key(0)), mesh, config)
    optimizer = make_optimizer(learning_rate=1e-2)
    step = make_pipeline_train_step(config, optimizer, mesh, n_micro=2)
    state = init_train_state(config, params, optimizer)
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, 64)
    losses = []
    for _ in range(5):
        state, loss = step(state, tokens)
        losses.append(float(loss))
    assert all(jnp.isfinite(jnp.asarray(losses))), losses
    assert losses[-1] < losses[0], losses


def test_70b_shardings_fit_v5p16_mesh_shapes():
    """BASELINE config 5 (70B on v5p-16): every llama3-70b param and
    decode-state dim divides the (data=2, model=8) 16-device mesh cleanly —
    no tensor would be forced to replicate (which _fit_sharding refuses
    above 256 MiB). Shape-level check via abstract arrays; no 70B weights
    are materialized."""
    import math

    from finchat_tpu.models.llama import PRESETS
    from jax.sharding import AbstractMesh
    from finchat_tpu.parallel.sharding import llama_param_shardings

    config = PRESETS["llama3-70b"]
    # shape-only: an abstract 16-device v5p mesh (no fabricated devices)
    mesh = AbstractMesh(
        (2, 1, 1, 1, 8), ("data", "pipe", "seq", "expert", "model")
    )

    c = config
    L, D, H, Hkv, hd, F = (c.n_layers, c.dim, c.n_heads, c.n_kv_heads,
                           c.head_dim, c.hidden_dim)
    shapes = {
        "embed": (c.vocab_size, D),
        "layers": {
            "attn_q": (L, D, H * hd), "attn_k": (L, D, Hkv * hd),
            "attn_v": (L, D, Hkv * hd), "attn_o": (L, H * hd, D),
            "mlp_gate": (L, D, F), "mlp_up": (L, D, F), "mlp_down": (L, F, D),
            "ln_attn": (L, D), "ln_mlp": (L, D),
        },
        "norm": (D,),
        "lm_head": (D, c.vocab_size),
    }
    shardings = llama_param_shardings(mesh)

    def check(path, shape, ns, on_mesh=None):
        on_mesh = on_mesh or mesh
        parts = ns.spec if hasattr(ns, "spec") else ns  # NamedSharding | P
        spec = list(parts) + [None] * (len(shape) - len(parts))
        for dim, axes in zip(shape, spec):
            if axes is None:
                continue
            extent = math.prod(
                on_mesh.shape[a] for a in (axes if isinstance(axes, tuple) else (axes,))
            )
            assert dim % extent == 0, f"{path}: dim {dim} !% mesh {axes}={extent}"

    check("embed", shapes["embed"], shardings["embed"])
    for k, shape in shapes["layers"].items():
        check(f"layers/{k}", shape, shardings["layers"][k])
    check("norm", shapes["norm"], shardings["norm"])
    check("lm_head", shapes["lm_head"], shardings["lm_head"])

    # decode-state KV pages: fused Hkv*hd dim divides the model axis
    assert (Hkv * hd) % mesh.shape["model"] == 0
    # int8-KV scale rows shard head-aligned (Hkv % 8 == 0 at 70B)
    assert Hkv % 8 == 0

    # r5: the PIPELINE route to 70B — pipe=4 x model=4 on the same 16
    # chips, with in-stage Megatron TP. Every stage gets a whole number
    # of layers and every Megatron dim divides the in-stage TP extent.
    from jax.sharding import PartitionSpec as P

    from finchat_tpu.parallel.pipeline import _pipeline_layer_specs, _stage_tp

    pp_mesh = AbstractMesh(
        (1, 4, 1, 1, 4), ("data", "pipe", "seq", "expert", "model")
    )
    assert L % pp_mesh.shape["pipe"] == 0  # 80 layers / 4 stages
    tp = _stage_tp(config, pp_mesh)
    assert tp == 4  # in-stage TP actually engages at 70B shapes
    specs = _pipeline_layer_specs(shapes["layers"], tp)
    assert specs["attn_q"] == P("pipe", None, "model")
    assert specs["mlp_down"] == P("pipe", "model", None)
    for k, shape in shapes["layers"].items():
        check(f"pp layers/{k}", shape, specs[k], on_mesh=pp_mesh)


def test_tp_overlap_row_parallel_byte_identity():
    """TP collective-compute overlap (ops/tp_overlap.py): the chunked
    schedule — each output-column chunk's partial-sum psum issued as soon
    as its matmul retires — must be BYTE-identical to the serial
    matmul + one blocking psum at fp32 (each output element keeps the
    same full-K dot and the same single n-way collective reduction), and
    the overlap must be trace-visible (n_chunks psum eqns in the jaxpr —
    the dispatch evidence that the schedule actually engaged, not just a
    knob that fell back to serial)."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from finchat_tpu.ops.tp_overlap import row_parallel_dense

    mesh = build_mesh(MeshSpec(data=1, seq=1, expert=1, model=8))
    M, K, N, n_chunks = 8, 256, 128, 4
    x = jax.random.normal(jax.random.key(0), (M, K), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (K, N), jnp.float32)

    def make(overlap):
        def local(x_l, w_l):
            return row_parallel_dense(x_l, w_l, "model",
                                      overlap=overlap, n_chunks=n_chunks)
        return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(None, "model"), P("model", None)),
                         out_specs=P(None, None))

    serial = make(False)(x, w)
    overlapped = make(True)(x, w)
    # fp32: byte-identical, not allclose — the contract the manual-TP
    # stage path's bit-identical-to-unsharded guarantee rests on
    np.testing.assert_array_equal(np.asarray(serial), np.asarray(overlapped))

    # bf16: envelope-bounded (chunking still never touches an element's
    # K-reduction, so this holds tight; the pinned contract is fp32)
    xb, wb = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    sb = np.asarray(make(False)(xb, wb), np.float32)
    ob = np.asarray(make(True)(xb, wb), np.float32)
    np.testing.assert_allclose(ob, sb, rtol=2e-2, atol=2e-2)

    # trace evidence: the overlapped jaxpr carries n_chunks psum eqns,
    # the serial one exactly 1
    assert str(jax.make_jaxpr(make(True))(x, w)).count("psum") == n_chunks
    assert str(jax.make_jaxpr(make(False))(x, w)).count("psum") == 1


def test_tp_overlap_indivisible_falls_back_serial():
    """An output dim the chunk count does not divide must run the serial
    collective (with a warning), not crash or pad."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from finchat_tpu.ops.tp_overlap import row_parallel_dense

    mesh = build_mesh(MeshSpec(data=1, seq=1, expert=1, model=8))
    x = jax.random.normal(jax.random.key(2), (4, 64), jnp.float32)
    w = jax.random.normal(jax.random.key(3), (64, 30), jnp.float32)  # 30 % 4 != 0

    f = jax.shard_map(
        lambda x_l, w_l: row_parallel_dense(x_l, w_l, "model",
                                            overlap=True, n_chunks=4),
        mesh=mesh, in_specs=(P(None, "model"), P("model", None)),
        out_specs=P(None, None))
    got = f(x, w)
    ref = jax.shard_map(
        lambda x_l, w_l: row_parallel_dense(x_l, w_l, "model"),
        mesh=mesh, in_specs=(P(None, "model"), P("model", None)),
        out_specs=P(None, None))(x, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_pipeline_forward_tp_overlap_matches_serial():
    """The whole manual-TP stage path under the overlap knob: pipeline
    forward with tp_overlap=True is byte-identical at fp32 to the serial
    schedule (engine.tp_overlap / FINCHAT_TP_OVERLAP gate this in
    serving; default off keeps the serial psum as the reference)."""
    import numpy as np

    from finchat_tpu.parallel.pipeline import (
        pipeline_forward,
        shard_params_for_pipeline,
    )

    config = LlamaConfig(
        vocab_size=64, dim=32, n_layers=4, n_heads=4, n_kv_heads=2,
        hidden_dim=64, max_seq_len=32, dtype=jnp.float32,
    )
    mesh = build_mesh(MeshSpec(data=2, pipe=2, seq=1, expert=1, model=2))
    params = init_params(config, jax.random.key(0))
    B, S = 4, 16
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, 64)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    sharded = shard_params_for_pipeline(params, mesh, config)

    serial = pipeline_forward(
        sharded, tokens, positions, config=config, mesh=mesh, n_micro=2)
    overlapped = pipeline_forward(
        sharded, tokens, positions, config=config, mesh=mesh, n_micro=2,
        tp_overlap=True, tp_chunks=4)
    np.testing.assert_array_equal(np.asarray(serial), np.asarray(overlapped))
