"""Fused dequant-matmul kernels (ops/quant_matmul.py) correctness.

Three contracts, mirroring the attention-kernel test discipline:

1. ``quant_matmul_ref`` is BITWISE the historical inline-dequant math —
   literally ``x @ dequantize(w, x.dtype)`` (or the
   ``preferred_element_type`` einsum at the lm_head site). The reference
   is the CPU/tier-1 serving path, so routing every QTensor/Q4Tensor
   matmul site through the dispatcher must not change a single stream
   byte; this file pins the identity at the op level and the whole-model
   level (tests/test_quant.py, and streams through the scheduler below).
2. Kernel-vs-ref parity across the layout matrix: int8/int4 x
   per-channel/per-group x aligned/ragged shapes — interpret mode on the
   CPU test mesh, Mosaic-compiled under ``FINCHAT_TESTS_TPU=1`` (the same
   ``INTERPRET`` switch as tests/test_pallas_attention.py). The kernel
   tiles K and accumulates fp32, so parity is allclose (tile-order
   summation), not bitwise — same contract as the flash kernels.
3. The kernel honors parallel/sharding.py's packed-K layout: a K-sharded
   shard_map over the forced 8-device CPU mesh (conftest) feeds each
   device its LOCAL packed shard (nibble pairs never split — byte rows
   shard as units) and the psum of per-shard fused matmuls matches the
   unsharded reference.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from finchat_tpu.models.quant import (
    dense,
    dequantize,
    quantize,
    quantize_int4,
)
from finchat_tpu.ops.dispatch import quant_matmul, quant_matmul_backend
from finchat_tpu.ops.quant_matmul import (
    quant_matmul_int4,
    quant_matmul_int8,
    quant_matmul_ref,
)


INTERPRET = jax.default_backend() != "tpu"
# on-chip fp32 dots lower to bf16 multi-pass matmuls in kernel and oracle
# alike, but not identically (tests/test_pallas_attention.py, same reason)
TOL = 2e-5 if INTERPRET else 2e-2
needs_8_devices = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the 8-device mesh")


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(key), shape, dtype)


# --- 1. the reference IS the inline-dequant serving math (bitwise) -------

@pytest.mark.parametrize("mode", ["int8", "int4-pc", "int4-pg"])
def test_ref_is_inline_dequant_bitwise(mode):
    x = _rand(0, (4, 64))
    w = _rand(1, (64, 32))
    if mode == "int8":
        qt = quantize(w)
    else:
        qt = quantize_int4(w, group_size=64 if mode == "int4-pc" else 16)
    got = quant_matmul_ref(x, qt)
    want = x @ dequantize(qt, x.dtype)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the lm_head site: fp32-accumulating einsum, also bitwise
    got32 = quant_matmul_ref(x, qt, preferred_element_type=jnp.float32)
    want32 = jnp.einsum("...k,kn->...n", x, dequantize(qt, x.dtype),
                        preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got32), np.asarray(want32))


def test_dense_routes_through_dispatcher_ref_bitwise():
    """models/quant.dense — THE matmul entry every decoder/encoder site
    uses — must stay bitwise the historical ``x @ dequantize(w)`` on the
    reference backend (the tier-1 path)."""
    x = _rand(2, (3, 48))
    for qt in (quantize(_rand(3, (48, 24))),
               quantize_int4(_rand(4, (48, 24)), group_size=16)):
        got = dense(x, qt, qm_backend="ref")
        want = x @ dequantize(qt, x.dtype)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_backend_resolution_validates():
    import os

    assert quant_matmul_backend() in ("pallas", "ref", "pallas-interpret")
    os.environ["FINCHAT_QUANT_MATMUL"] = "bogus"
    try:
        with pytest.raises(ValueError):
            quant_matmul_backend()
    finally:
        del os.environ["FINCHAT_QUANT_MATMUL"]


def test_stacked_weight_falls_back_to_ref():
    """MoE expert leaves are stacked [E, K, N]; the dispatcher must route
    them to the reference (no fused kernel for 3-D weights) and count the
    fallback."""
    from finchat_tpu.utils.metrics import METRICS

    x = _rand(5, (2, 16))
    qt = quantize(_rand(6, (3, 16, 8)))  # stacked leaf
    before = METRICS.get("finchat_quantmatmul_fallbacks_total")
    # stacked weight: the dispatcher falls back to the inline-dequant
    # reference (same math the MoE expert einsums run) and counts it
    out = quant_matmul(x, qt, backend="pallas-interpret")
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(x @ dequantize(qt, x.dtype)))
    after = METRICS.get("finchat_quantmatmul_fallbacks_total")
    assert after == before + 1


# --- 2. kernel-vs-ref parity matrix (INTERPRET switch) -----------------------

PARITY_CASES = [
    # (name, M, K, N, quant, group)
    ("int8-aligned", 16, 256, 256, "int8", None),
    ("int8-ragged", 7, 130, 96, "int8", None),
    ("int4-per-channel-aligned", 16, 256, 128, "int4", 256),
    ("int4-per-channel-ragged", 5, 96, 80, "int4", 96),
    ("int4-per-group-aligned", 8, 256, 128, "int4", 64),
    ("int4-per-group-ragged", 5, 192, 80, "int4", 32),
]


@pytest.mark.parametrize("name,M,K,N,mode,group",
                         PARITY_CASES, ids=[c[0] for c in PARITY_CASES])
def test_kernel_matches_ref(name, M, K, N, mode, group):
    x = _rand(10, (M, K))
    w = _rand(11, (K, N))
    if mode == "int8":
        qt = quantize(w)
        out = quant_matmul_int8(x, qt.q, qt.scale, interpret=INTERPRET)
    else:
        qt = quantize_int4(w, group_size=group)
        out = quant_matmul_int4(x, qt.q, qt.scale, interpret=INTERPRET)
    ref = quant_matmul_ref(x, qt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=TOL, atol=TOL)


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_kernel_bf16_activations_and_leading_dims(mode):
    """bf16 activations (the serving dtype) through the kernel, with a
    leading batch dim (the [B, S, D] encoder/decoder shape)."""
    x = _rand(12, (2, 5, 128), jnp.bfloat16)
    w = _rand(13, (128, 64))
    if mode == "int8":
        qt = quantize(w)
        out = quant_matmul_int8(x, qt.q, qt.scale, interpret=INTERPRET)
    else:
        qt = quantize_int4(w, group_size=32)
        out = quant_matmul_int4(x, qt.q, qt.scale, interpret=INTERPRET)
    assert out.shape == (2, 5, 64) and out.dtype == jnp.bfloat16
    ref = quant_matmul_ref(x, qt)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


def test_kernel_out_dtype_fp32_head():
    """The lm_head site: fused kernel accumulates fp32 and can emit fp32
    logits directly (preferred_element_type through the dispatcher)."""
    x = _rand(14, (4, 64), jnp.bfloat16)
    qt = quantize(_rand(15, (64, 32)))
    out = quant_matmul(x, qt,
                       backend="pallas-interpret" if INTERPRET else "pallas",
                       preferred_element_type=jnp.float32)
    assert out.dtype == jnp.float32
    ref = quant_matmul_ref(x, qt, preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_quantized_forward_fused_tracks_ref():
    """Whole-model check: every QTensor site (attention projections, MLP,
    lm_head) routed through the interpret-mode kernel tracks the
    inline-dequant forward within kernel-parity tolerance."""
    from finchat_tpu.models.llama import LlamaConfig, forward_full, init_params
    from finchat_tpu.models.quant import quantize_llama_params

    config = LlamaConfig(vocab_size=64, dim=32, n_layers=2, n_heads=4,
                         n_kv_heads=4, hidden_dim=64, max_seq_len=32)
    params = quantize_llama_params(init_params(config, jax.random.key(0)))
    tokens = jax.random.randint(jax.random.key(1), (1, 8), 1, 64)
    positions = jnp.arange(8)[None]
    ref = forward_full(params, tokens, positions, config=config,
                       attn_backend="ref", qm_backend="ref")
    fused = forward_full(params, tokens, positions, config=config,
                         attn_backend="ref", qm_backend="pallas-interpret")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


def test_fused_engine_streams_match_ref_through_scheduler():
    """The backend is resolved once at construction and multiplies
    nothing: an int8 engine on the fused kernel (interpret mode) serves
    greedy fp32 streams through the real scheduler byte-identical to its
    inline-dequant twin, compiles exactly as many warm-up variants, moves
    ``finchat_quantmatmul_fused_dispatches_total`` where the twin does
    not, and both stopped schedulers audit leak-free."""
    import asyncio
    import dataclasses

    from finchat_tpu.analysis.sanitizers import scheduler_leak_report
    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.models.llama import PRESETS
    from finchat_tpu.models.quant import init_quantized_llama_params
    from finchat_tpu.utils.config import EngineConfig
    from finchat_tpu.utils.metrics import METRICS

    config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
    params = init_quantized_llama_params(config, jax.random.key(0), mode="int8")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, config.vocab_size, size=n).tolist()
               for n in (44, 23)]
    fused_counter = "finchat_quantmatmul_fused_dispatches_total"

    def run(qm_backend):
        ecfg = EngineConfig(max_seqs=2, page_size=16, num_pages=16,
                            max_seq_len=96, prefill_chunk=32)
        engine = InferenceEngine(config, params, ecfg, quant="int8",
                                 qm_backend=qm_backend)
        engine.warmup()
        sched = ContinuousBatchingScheduler(engine, eos_id=-1)
        fused0 = METRICS.get(fused_counter)

        async def one(i, prompt):
            handle = await sched.submit(
                f"{qm_backend}-{i}", prompt,
                SamplingParams(temperature=0.0, max_new_tokens=12))
            tokens = []
            while True:
                ev = await asyncio.wait_for(handle.events.get(), timeout=120)
                if ev["type"] == "token":
                    tokens.append(ev["token_id"])
                elif ev["type"] == "done":
                    return tokens
                else:
                    raise AssertionError(ev)

        async def go():
            await sched.start()
            try:
                return list(await asyncio.gather(
                    *(one(i, p) for i, p in enumerate(prompts))))
            finally:
                await sched.stop()

        streams = asyncio.run(go())
        assert scheduler_leak_report(sched) == []
        return streams, engine.compiled_variants, METRICS.get(fused_counter) - fused0

    ref_streams, ref_variants, ref_fused = run("ref")
    fused_streams, fused_variants, fused_fused = run("pallas-interpret")
    assert fused_streams == ref_streams and all(len(s) == 12 for s in ref_streams)
    assert fused_variants == ref_variants > 0
    assert fused_fused > 0 and ref_fused == 0


# --- 3. packed-K sharding: the kernel honors the local-shard layout ------

@needs_8_devices
def test_tp_sharded_int8_kernel_matches_unsharded():
    """K-sharded int8 matmul over the forced 8-device mesh: each device
    runs the fused kernel on its LOCAL [K/8, N] shard (per-output-column
    scale replicated) and the psum matches the unsharded reference."""
    from finchat_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=1, seq=1, expert=1, model=8))
    M, K, N = 8, 512, 64
    x = _rand(20, (M, K))
    qt = quantize(_rand(21, (K, N)))

    def local(x_l, q_l, s_l):
        out = quant_matmul_int8(x_l, q_l, s_l, interpret=True)
        return jax.lax.psum(out, "model")

    f = jax.shard_map(local, mesh=mesh,
                  in_specs=(P(None, "model"), P("model", None), P(None)),
                  out_specs=P(None, None), check_vma=False)
    got = f(x, qt.q, qt.scale)
    ref = quant_matmul_ref(x, qt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@needs_8_devices
def test_tp_sharded_int4_packed_shards_as_bytes():
    """Packed int4 K-sharding (parallel/sharding.py spec): the packed
    [K//2, N] byte rows shard as UNITS (a nibble pair never splits across
    devices) and per-group scales shard with their groups — each device's
    fused kernel sees a self-consistent local shard."""
    from finchat_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=1, seq=1, expert=1, model=8))
    M, K, N, g = 8, 512, 64, 64  # 8 shards x one group each
    x = _rand(22, (M, K))
    qt = quantize_int4(_rand(23, (K, N)), group_size=g)
    assert qt.q.shape == (K // 2, N) and qt.scale.shape == (K // g, N)

    def local(x_l, q_l, s_l):
        out = quant_matmul_int4(x_l, q_l, s_l, interpret=True)
        return jax.lax.psum(out, "model")

    f = jax.shard_map(local, mesh=mesh,
                  in_specs=(P(None, "model"), P("model", None),
                            P("model", None)),
                  out_specs=P(None, None), check_vma=False)
    got = f(x, qt.q, qt.scale)
    ref = quant_matmul_ref(x, qt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_q4_slice_out_cols_roundtrip():
    """tp_overlap chunks a quantized weight along OUTPUT columns without
    unpacking: slicing then dequantizing == dequantizing then slicing."""
    from finchat_tpu.ops.tp_overlap import _slice_out_cols

    qt = quantize_int4(_rand(24, (64, 32)), group_size=16)
    full = dequantize(qt, jnp.float32)
    for start, size in ((0, 8), (8, 16), (24, 8)):
        part = dequantize(_slice_out_cols(qt, start, size), jnp.float32)
        np.testing.assert_array_equal(np.asarray(part),
                                      np.asarray(full[:, start:start + size]))
    q8 = quantize(_rand(25, (64, 32)))
    full8 = dequantize(q8, jnp.float32)
    part8 = dequantize(_slice_out_cols(q8, 8, 16), jnp.float32)
    np.testing.assert_array_equal(np.asarray(part8),
                                  np.asarray(full8[:, 8:24]))
