"""Int8 paged-KV cache (engine kv_quant): quantization bounds, kernel ≡
scatter parity, attention over the quantized cache ≡ reference over the
SAME dequantized values, and end-to-end engine decode.

The contract: per-token-per-head scales are written once at append time
and never requantized (the page RMW copies existing int8 rows verbatim),
so cached values are bit-stable and the only error is the one-time row
rounding, bounded by amax/254 per element.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paged_walk_cases import (
    PACKED_CASES,
    PACKED_SHAPES,
    PAGE_SIZE,
    SHAPES,
    SHARED_CASES,
    assert_matches_reference,
    walk_case,
)

from finchat_tpu.engine.engine import InferenceEngine, commit_first_token
from finchat_tpu.engine.kv_cache import (
    PagedKVCache,
    gather_kv_q8,
    pages_needed,
    quantize_kv_rows,
    scale_rows,
    scatter_kv_chunk_q8,
    PageAllocator,
)
from finchat_tpu.models.llama import PRESETS, init_params
from finchat_tpu.ops.refs import mha_reference
from finchat_tpu.utils.config import EngineConfig

CONFIG = PRESETS["tiny"]  # n_kv_heads=2, head_dim=32

needs_8_devices = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the 8-device mesh")


def test_quantize_kv_rows_error_bound():
    x = jax.random.normal(jax.random.key(0), (3, 5, 2 * 32), jnp.float32)
    q, s = quantize_kv_rows(x, n_kv=2)
    assert q.dtype == jnp.int8 and s.shape == (3, 5, 2)
    deq = (q.reshape(3, 5, 2, 32).astype(jnp.float32) * s[..., None]).reshape(x.shape)
    err = jnp.abs(deq - x)
    bound = jnp.repeat(s, 32, axis=-1) / 2 + 1e-6  # half a step per element
    assert bool((err <= bound).all())


def test_scale_rows_padding():
    assert scale_rows(2) == 8 and scale_rows(8) == 8 and scale_rows(9) == 16


def _fresh_cache(n_pages=8, page_size=8):
    cache = PagedKVCache.create(CONFIG, n_pages, page_size, kv_quant="int8")
    return cache


def test_scatter_gather_roundtrip():
    """scatter_kv_chunk_q8 → gather_kv_q8 reproduces the written rows to
    quantization tolerance, in the right positions."""
    page_size = 8
    cache = _fresh_cache()
    B, C, Hkv, hd = 2, 6, CONFIG.n_kv_heads, CONFIG.head_dim
    k_new = jax.random.normal(jax.random.key(1), (B, C, Hkv, hd), jnp.float32)
    v_new = jax.random.normal(jax.random.key(2), (B, C, Hkv, hd), jnp.float32)
    page_table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    start_pos = jnp.asarray([0, 5], jnp.int32)
    n_valid = jnp.asarray([6, 4], jnp.int32)  # slot 1: 2 padding lanes

    kp, vp, ks, vs = scatter_kv_chunk_q8(
        cache.k_pages, cache.v_pages, cache.k_scales, cache.v_scales,
        k_new, v_new, page_table, start_pos, n_valid, page_size,
        jnp.int32(0), Hkv,
    )
    k_all, v_all = gather_kv_q8(
        kp, vp, ks, vs, page_table, page_size, jnp.int32(0), Hkv,
        dtype=jnp.float32,
    )
    for b in range(B):
        for i in range(int(n_valid[b])):
            pos = int(start_pos[b]) + i
            for src, got in ((k_new, k_all), (v_new, v_all)):
                want = np.asarray(src[b, i])
                have = np.asarray(got[b, pos])
                amax = np.abs(want).max(axis=-1, keepdims=True)
                assert np.all(np.abs(have - want) <= amax / 127 + 1e-6), (b, i)


def test_append_kernel_matches_scatter():
    """The in-place quantizing append (interpret mode) must write exactly
    what the XLA scatter writes for the same single token: same int8 rows,
    same scales."""
    from finchat_tpu.ops.kv_append import paged_kv_append_q8

    page_size = 8
    Hkv, hd = CONFIG.n_kv_heads, CONFIG.head_dim
    B = 2
    k_row = jax.random.normal(jax.random.key(3), (B, 1, Hkv, hd), jnp.bfloat16)
    v_row = jax.random.normal(jax.random.key(4), (B, 1, Hkv, hd), jnp.bfloat16)
    page_table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([3, 9], jnp.int32)
    n_valid = jnp.asarray([1, 1], jnp.int32)

    ca = _fresh_cache()
    kv_new = jnp.concatenate(
        [k_row.reshape(B, 1, -1), v_row.reshape(B, 1, -1)], axis=-1
    )
    ka, va, ksa, vsa = paged_kv_append_q8(
        kv_new, ca.k_pages, ca.v_pages, ca.k_scales, ca.v_scales,
        page_table, pos, n_valid, jnp.zeros((1,), jnp.int32),
        page_size=page_size, n_kv=Hkv, interpret=True,
    )

    cb = _fresh_cache()
    kb, vb, ksb, vsb = scatter_kv_chunk_q8(
        cb.k_pages, cb.v_pages, cb.k_scales, cb.v_scales,
        k_row, v_row, page_table, pos, n_valid, page_size, jnp.int32(0), Hkv,
    )
    np.testing.assert_array_equal(np.asarray(ka), np.asarray(kb))
    np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
    np.testing.assert_allclose(np.asarray(ksa), np.asarray(ksb), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(vsa), np.asarray(vsb), rtol=1e-6)


def test_trash_redirect_append_q8():
    """n_valid == 0 lanes must write page 0 (trash), even at an
    out-of-range position (the verify-step padding case)."""
    from finchat_tpu.ops.kv_append import paged_kv_append_q8

    page_size = 8
    Hkv, hd = CONFIG.n_kv_heads, CONFIG.head_dim
    ca = _fresh_cache()
    kv_new = jnp.ones((1, 1, 2 * Hkv * hd), jnp.bfloat16)
    page_table = jnp.asarray([[1, 2]], jnp.int32)
    ka, va, ksa, vsa = paged_kv_append_q8(
        kv_new, ca.k_pages, ca.v_pages, ca.k_scales, ca.v_scales,
        page_table, jnp.asarray([100], jnp.int32), jnp.asarray([0], jnp.int32),
        jnp.zeros((1,), jnp.int32), page_size=page_size, n_kv=Hkv, interpret=True,
    )
    assert int(jnp.abs(ka[:, 1:].astype(jnp.int32)).sum()) == 0  # real pages untouched
    assert int(jnp.abs(va[:, 1:].astype(jnp.int32)).sum()) == 0


@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
def test_paged_attention_q8_matches_dequantized_reference(backend):
    """Attention over the int8 cache must equal mha_reference over the SAME
    dequantized K/V — both kernels and the gather path see identical
    semantic values, so the only tolerance is fp accumulation order."""
    from finchat_tpu.ops.dispatch import paged_attention

    page_size = 8
    Hkv, hd, H = CONFIG.n_kv_heads, CONFIG.head_dim, CONFIG.n_heads
    B, C = 2, 1
    cache = _fresh_cache(n_pages=8)
    T = 14
    k_ctx = jax.random.normal(jax.random.key(5), (B, T, Hkv, hd), jnp.float32)
    v_ctx = jax.random.normal(jax.random.key(6), (B, T, Hkv, hd), jnp.float32)
    page_table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    kp, vp, ks, vs = scatter_kv_chunk_q8(
        cache.k_pages, cache.v_pages, cache.k_scales, cache.v_scales,
        k_ctx, v_ctx, page_table, jnp.zeros((B,), jnp.int32),
        jnp.full((B,), T, jnp.int32), page_size, jnp.int32(0), Hkv,
    )
    q = jax.random.normal(jax.random.key(7), (B, C, H, hd), jnp.float32)
    q_offset = jnp.full((B,), T - 1, jnp.int32)
    kv_len = jnp.full((B,), T, jnp.int32)

    got = paged_attention(
        q, kp, vp, page_table, q_offset, kv_len, jnp.zeros((1,), jnp.int32),
        page_size=page_size, n_kv=Hkv, backend=backend,
        k_scales=ks, v_scales=vs,
    )
    # the oracle sees the SAME dequantized values
    k_deq, v_deq = gather_kv_q8(
        kp, vp, ks, vs, page_table, page_size, jnp.int32(0), Hkv,
        dtype=jnp.float32,
    )
    want = mha_reference(q, k_deq, v_deq, causal=True, q_offset=q_offset, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("group,C", SHAPES)
def test_paged_walk_edges_q8_match_dequantized_reference(group, C):
    """The int8 walk on the float walk's cases (paged_walk_cases): every
    edge of the walk as a row, dead table entries on a page whose scales
    are NaN, the oracle over the same dequantized values."""
    from finchat_tpu.ops.paged_attention import paged_flash_attention_q8

    q, sources, table, q_offset, kv_len, layer, k_deq, v_deq = walk_case(
        group, C, quantized=True)
    out = paged_flash_attention_q8(
        q, *sources, table, q_offset, kv_len, layer,
        page_size=PAGE_SIZE, n_kv=2, interpret=True,
    )
    want = mha_reference(q, k_deq, v_deq, causal=True, q_offset=q_offset, kv_len=kv_len)
    assert_matches_reference(out, want, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("group", [4, 5])
@pytest.mark.parametrize("name", SHARED_CASES)
def test_paged_decode_q8_with_a_shared_head_matches_dequantized_reference(name, group):
    """The int8 walk on the float walk's shared-head cases: the scales of the
    shared pages ride the first pass's copies as they ride a row's own."""
    from finchat_tpu.ops.paged_attention import paged_flash_attention_q8

    contexts, heads, *_ = SHARED_CASES[name]
    q, sources, table, q_offset, kv_len, layer, k_deq, v_deq = walk_case(
        group, 1, quantized=True, contexts=contexts, heads=heads)
    out = paged_flash_attention_q8(
        q, *sources, table, q_offset, kv_len, layer,
        page_size=PAGE_SIZE, n_kv=2, interpret=True,
    )
    want = mha_reference(q, k_deq, v_deq, causal=True, q_offset=q_offset, kv_len=kv_len)
    assert_matches_reference(out, want, contexts, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("name", PACKED_CASES)
@pytest.mark.parametrize("group,n_kv", PACKED_SHAPES)
def test_paged_decode_q8_with_heads_sharing_a_tile_matches_dequantized_reference(
        group, n_kv, name):
    """One or two query heads a KV head over the int8 cache: the heads of a
    tile take one block update together, each row under its own head's
    per-token scales (a row of the scale block a head)."""
    from finchat_tpu.ops.paged_attention import paged_flash_attention_q8

    contexts, heads, *_ = SHARED_CASES[name]
    q, sources, table, q_offset, kv_len, layer, k_deq, v_deq = walk_case(
        group, 1, quantized=True, contexts=contexts, heads=heads, n_kv=n_kv)
    out = paged_flash_attention_q8(
        q, *sources, table, q_offset, kv_len, layer,
        page_size=PAGE_SIZE, n_kv=n_kv, interpret=True,
    )
    want = mha_reference(q, k_deq, v_deq, causal=True, q_offset=q_offset, kv_len=kv_len)
    assert_matches_reference(out, want, contexts, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("attn", ["ref", "pallas-interpret"])
def test_engine_int8_kv_logits_track_bf16(attn):
    """End-to-end teacher-forced comparison: drive the int8-KV engine along
    the bf16 engine's exact greedy token path (chunked prefill, per-step
    appends, a page boundary) and require every step's logits to stay
    within quantization tolerance. Token-exact equality is NOT the
    contract — random tiny-model logits have near-ties (observed top-2 gap
    0.006) that flip under any numerics change — logit tracking is."""
    ecfg = dict(max_seqs=2, page_size=8, num_pages=32, max_seq_len=64, prefill_chunk=8)
    params = init_params(CONFIG, jax.random.key(0))
    prompt, n_new = [5, 9, 2, 100, 17, 3, 77, 4, 250, 31], 8  # crosses a page

    def make(kv_quant):
        eng = InferenceEngine(
            CONFIG, params, EngineConfig(**ecfg, kv_quant=kv_quant),
            attn_backend=attn,
        )
        assert eng.kv_quant == kv_quant
        if kv_quant:
            assert eng.state.k_pages.dtype == jnp.int8
        alloc = PageAllocator(eng.engine_cfg.num_pages)
        pages = alloc.allocate("s", pages_needed(len(prompt) + n_new, 8))
        eng.set_page_table_row(0, pages)
        prefill_logits = eng.prefill(0, prompt)
        return eng, np.asarray(prefill_logits, np.float32)

    bf16, pre_b = make("")
    int8, pre_q = make("int8")
    np.testing.assert_allclose(pre_q, pre_b, atol=0.15)

    # bf16's greedy path, teacher-forced into BOTH engines
    token = int(np.argmax(pre_b))
    active = jnp.zeros((2,), bool).at[0].set(True)
    z, o, zk = jnp.zeros((2,)), jnp.ones((2,)), jnp.zeros((2,), jnp.int32)
    for _ in range(n_new - 1):
        bf16.set_last_token(0, token)
        int8.set_last_token(0, token)
        _, logits_b = bf16.decode(active, z, o, zk, return_logits=True)
        _, logits_q = int8.decode(active, z, o, zk, return_logits=True)
        logits_b, logits_q = np.asarray(logits_b[0]), np.asarray(logits_q[0])
        np.testing.assert_allclose(logits_q, logits_b, atol=0.15)
        token = int(np.argmax(logits_b))


@needs_8_devices
def test_ring_prefill_int8_kv_matches_chunked():
    """The SP/ring prefill write path quantizes too (the old engine
    disabled kv_quant under any mesh, so this path could never see an
    int8 cache): a long prompt prefilled through the seq-sharded ring
    path with kv_quant=int8 must leave the cache equivalent to chunked
    int8 prefill — same greedy continuation, close last-token logits."""
    from finchat_tpu.models.llama import LlamaConfig
    from finchat_tpu.parallel.mesh import MeshSpec, build_mesh

    config = LlamaConfig(
        vocab_size=128, dim=64, n_layers=2, n_heads=8, n_kv_heads=8,
        hidden_dim=128, max_seq_len=128,
    )
    params = init_params(config, jax.random.key(0))
    prompt = list(np.random.RandomState(7).randint(1, 128, size=50))
    n_new = 5

    def run(mesh, ring_min):
        ecfg = EngineConfig(
            max_seqs=2, page_size=8, num_pages=32, max_seq_len=128,
            prefill_chunk=16, ring_prefill_min_tokens=ring_min,
            kv_quant="int8",
        )
        eng = InferenceEngine(config, params, ecfg, mesh=mesh)
        assert eng.kv_quant == "int8" and eng.state.k_pages.dtype == jnp.int8
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
    # both paths quantize per-token rows at write, so the CACHED values are
    # identical — but the prefill-time attention differs by the one-time
    # rounding: ring attends over the exact bf16 K/V activations, chunked
    # reads back the quantized cache. Tolerance is the quantization
    # envelope (same 0.15 as test_engine_int8_kv_logits_track_bf16).
    np.testing.assert_allclose(ring_logits, mesh_logits, atol=0.15)
    # decode reads the same quantized cache in both runs; the greedy
    # continuation AFTER the first token must agree (the first committed
    # token comes from the differing prefill logits, so compare decode)
    assert ring_tokens[1:] == mesh_tokens[1:] or ring_tokens == mesh_tokens


@needs_8_devices
def test_segmented_ring_prefill_int8_kv_matches_monolithic():
    """The SEGMENTED SP prefill's int8 branch (gather_kv_q8 of the cached
    prefix + quantized segment scatter, engine._ring_segment_attention_fn)
    must reproduce the monolithic int8 ring prefill: identical cached
    values, so identical greedy decode, and logits within the
    quantization envelope (later segments attend to the DEQUANTIZED
    earlier segments, the monolithic pass to exact bf16 activations)."""
    from finchat_tpu.models.llama import LlamaConfig
    from finchat_tpu.parallel.mesh import MeshSpec, build_mesh

    config = LlamaConfig(
        vocab_size=128, dim=64, n_layers=2, n_heads=8, n_kv_heads=8,
        hidden_dim=128, max_seq_len=256,
    )
    params = init_params(config, jax.random.key(0))
    prompt = list(np.random.RandomState(13).randint(1, 128, size=100))
    n_new = 5
    mesh = build_mesh(MeshSpec(data=1, seq=2, expert=1, model=4))

    def run(ring_chunk):
        ecfg = EngineConfig(
            max_seqs=2, page_size=8, num_pages=64, max_seq_len=256,
            prefill_chunk=16, ring_prefill_min_tokens=16,
            ring_prefill_chunk=ring_chunk, kv_quant="int8",
        )
        eng = InferenceEngine(config, params, ecfg, mesh=mesh)
        assert eng.state.k_pages.dtype == jnp.int8
        alloc = PageAllocator(ecfg.num_pages)
        pages = alloc.allocate("s", pages_needed(len(prompt) + n_new, 8))
        eng.set_page_table_row(0, pages)
        if ring_chunk:
            rc = eng.ring_segment_tokens()
            logits = None
            for start in range(0, len(prompt), rc):
                logits = eng.prefill_ring_segment(0, prompt[start : start + rc], start)
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
    np.testing.assert_allclose(seg_logits, mono_logits, atol=0.15)
    assert seg_tokens[1:] == mono_tokens[1:] or seg_tokens == mono_tokens


@needs_8_devices
def test_tp_sharded_int8_kv_matches_unsharded():
    """VERDICT r4 #5: int8 KV must survive a mesh. Greedy decode through
    the TP=8 engine with kv_quant=int8 must emit the same tokens as the
    single-device int8 engine, with the scale arrays actually sharded over
    their head row dim (Hkv=8 → pad8(Hkv)=Hkv, so row blocks == the page
    shards' head blocks)."""
    from jax.sharding import PartitionSpec as P

    from finchat_tpu.engine.engine import commit_first_token
    from finchat_tpu.models.llama import LlamaConfig
    from finchat_tpu.parallel.mesh import MeshSpec, build_mesh

    config = LlamaConfig(
        vocab_size=128, dim=64, n_layers=2, n_heads=8, n_kv_heads=8,
        hidden_dim=128, max_seq_len=64,
    )
    params = init_params(config, jax.random.key(0))
    ecfg = EngineConfig(max_seqs=2, page_size=8, num_pages=16, max_seq_len=64,
                        prefill_chunk=8, kv_quant="int8")
    prompt, n_new = [5, 9, 2, 100, 17, 3], 6

    def run(mesh):
        eng = InferenceEngine(config, params, ecfg, mesh=mesh)
        assert eng.kv_quant == "int8"
        assert eng.state.k_pages.dtype == jnp.int8
        if mesh is not None:
            assert eng.state.k_scales.sharding.spec == P(None, None, "model", None)
            assert eng.state.v_scales.sharding.spec == P(None, None, "model", None)
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
    sharded = run(build_mesh(MeshSpec(data=1, seq=1, expert=1, model=8)))
    assert unsharded == sharded
