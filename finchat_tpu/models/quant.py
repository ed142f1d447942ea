"""Int8 / int4 weight-only quantization for serving.

No reference counterpart (the reference calls an external LLM API —
``llm_agent.py:34-45``); this exists because the decode step is
weight-READ-bound on TPU (the builders' July 2026 measurement, not
reproduced since, put ~6 ms of a 9.6 ms step in the dense forward streaming
bf16 weights from HBM). Storing matmul weights as int8 with
per-output-channel scales halves that traffic; the
MXU still computes in bf16 (int8 values up to ±127 are exact in bf16), so
the only numeric change is the weight rounding itself — bounded by the
per-channel max / 127 and asserted in tests/test_quant.py.

Design notes (TPU/JAX-first):
- ``QTensor`` is a registered pytree dataclass, so quantized leaves ride
  ``lax.scan`` over stacked layers, jit boundaries, and GSPMD sharding
  exactly like plain arrays. Scanning slices ``q[L, K, N] -> [K, N]`` and
  ``scale[L, N] -> [N]`` together.
- Scales are per-OUTPUT-column (the non-contracted axis). Matmul sites
  dequantize INLINE (``x @ (q * s)``): inside jit XLA fuses the
  upcast+scale into the dot's operand read, so HBM still streams int8
  while the MXU computes bf16. Post-matmul scaling (``(x @ q) * s``) is
  mathematically equal but NOT used: under row-parallel TP it reorders
  the scale past the partial-sum psum, whose bf16 rounding then differs
  from the single-device result — inline dequant keeps TP decode
  bit-identical to unsharded (tests/test_quant.py).
- Quantize AFTER ``shard_params``: ``quantize`` is plain jnp, so on
  GSPMD-sharded inputs the amax reduce runs over the (replicated)
  contraction axis per shard and ``q``/``scale`` inherit the weight's
  placement — no parallel spec bookkeeping for the quantized tree.
- ``int4`` (ISSUE 14) rides the same machinery one level down:
  ``Q4Tensor`` packs two signed nibbles per int8 byte along the
  CONTRACTION axis (row 2i in the low nibble, row 2i+1 in the high — an
  arithmetic ``<< 4 >> 4`` / ``>> 4`` pair unpacks with sign), with
  per-output-column scales that may additionally be per-GROUP along K
  (``group_size``; 0 = one group = per-channel). Dequantization is
  inline at the matmul site exactly like int8 — HBM streams 0.5
  byte/weight, the MXU still computes in the activation dtype.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import Array

# layer-stack leaves that are matmul weights [., K, N] (contract over -2);
# norms and the (precision-sensitive, tiny) MoE router stay full precision
QUANT_LAYER_LEAVES = frozenset({
    "attn_q", "attn_k", "attn_v", "attn_o",
    "mlp_gate", "mlp_up", "mlp_down",
    "moe_gate", "moe_up", "moe_down",
})


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QTensor:
    """Int8 weight + per-output-column scale for right-multiplication.

    ``q``: int8 ``[..., K, N]``; ``scale``: fp32 ``[..., N]`` such that the
    represented weight is ``q * scale[..., None, :]``.
    """

    q: Array
    scale: Array

    @property
    def shape(self) -> tuple[int, ...]:
        return self.q.shape

    @property
    def ndim(self) -> int:
        return self.q.ndim


def quantize(w: Array) -> QTensor:
    """Symmetric int8 per-output-column quantization of ``w[..., K, N]``."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=-2)  # [..., N]
    scale = jnp.where(amax > 0, amax, 1.0) / 127.0
    q = jnp.clip(jnp.round(w32 / scale[..., None, :]), -127, 127).astype(jnp.int8)
    return QTensor(q=q, scale=scale)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Q4Tensor:
    """Int4 weight (two nibbles per int8 byte along K) + per-group,
    per-output-column scales for right-multiplication.

    ``q``: int8 ``[..., K//2, N]`` — byte ``i`` holds row ``2i`` in its low
    nibble and row ``2i+1`` in its high nibble (signed, [-8, 7]).
    ``scale``: fp32 ``[..., G, N]`` with ``G = K / group_size`` groups along
    the contraction axis (G = 1 is per-output-channel). The represented
    weight row ``k`` is ``unpack(q)[k] * scale[k // group_size]``.
    """

    q: Array
    scale: Array

    @property
    def shape(self) -> tuple[int, ...]:
        # the LOGICAL weight shape (unpacked K), what callers reason about
        return self.q.shape[:-2] + (self.q.shape[-2] * 2, self.q.shape[-1])

    @property
    def ndim(self) -> int:
        return self.q.ndim


def quantize_int4(w: Array, group_size: int = 0) -> Q4Tensor:
    """Symmetric int4 quantization of ``w[..., K, N]`` with per-group
    (``group_size`` rows of K per scale; 0 = whole-column) scales."""
    w32 = w.astype(jnp.float32)
    K, N = w32.shape[-2:]
    assert K % 2 == 0, f"int4 packing needs an even contraction dim, got {K}"
    g = group_size or K
    assert K % g == 0 and g % 2 == 0, (K, g)
    G = K // g
    lead = w32.shape[:-2]
    wg = w32.reshape(*lead, G, g, N)
    amax = jnp.max(jnp.abs(wg), axis=-2)  # [..., G, N]
    scale = jnp.where(amax > 0, amax, 1.0) / 7.0
    q = jnp.clip(jnp.round(wg / scale[..., None, :]), -8, 7).astype(jnp.int8)
    q = q.reshape(*lead, K, N)
    packed = (q[..., 0::2, :] & jnp.int8(0x0F)) | (q[..., 1::2, :] << 4)
    return Q4Tensor(q=packed, scale=scale)


def _unpack_int4(packed: Array) -> Array:
    """[..., K//2, N] packed bytes → [..., K, N] signed nibble values
    (int8). Arithmetic shifts restore the sign of each nibble."""
    lo = (packed << 4) >> 4  # rows 0, 2, 4, ...
    hi = packed >> 4  # rows 1, 3, 5, ...
    half, N = packed.shape[-2:]
    lead = packed.shape[:-2]
    return jnp.stack([lo, hi], axis=-2).reshape(*lead, half * 2, N)


def _dequantize_int4(qt: Q4Tensor, dtype: Any) -> Array:
    K, N = qt.shape[-2:]
    G = qt.scale.shape[-2]
    lead = qt.q.shape[:-2]
    w = _unpack_int4(qt.q).astype(jnp.float32)
    wg = w.reshape(*lead, G, K // G, N) * qt.scale[..., None, :]
    return wg.reshape(*lead, K, N).astype(dtype)


def dequantize(qt: QTensor | Q4Tensor, dtype: Any = jnp.bfloat16) -> Array:
    """Materialize the represented weight (int8 or int4). Inside jit, XLA
    fuses the unpack+upcast+scale into the consuming dot's operand read —
    used at einsum sites where the scale cannot commute past a summed
    axis."""
    if isinstance(qt, Q4Tensor):
        return _dequantize_int4(qt, dtype)
    return (qt.q.astype(jnp.float32) * qt.scale[..., None, :]).astype(dtype)


def _set_stacked_slice(buf: Array, i: Array, part: Array) -> Array:
    """In-place-able write of slice ``i`` into the stacked output buffer
    (donated, so XLA updates the buffer rather than copying the stack)."""
    return jax.lax.dynamic_update_index_in_dim(buf, part, i, 0)


_set_stacked_slice = jax.jit(_set_stacked_slice, donate_argnums=(0,))


def quantize_stacked(w: Array, mode: str = "int8",
                     group_size: int = 0) -> QTensor | Q4Tensor:
    """``quantize`` (or ``quantize_int4`` per ``mode``) for layer-stacked
    leaves ``[L, ..., K, N]``, one leading slice at a time. BIT-identical
    to whole-leaf quantization (the amax reduce is over the contraction
    axis only — independent per leading index — and div/round/clip are
    elementwise; asserted in tests/test_quant.py), but the fp32 upcast
    transient inside ``quantize`` (``w32 = w.astype(float32)``) is capped
    at 1/L of the leaf — the difference between fitting and OOM when
    materializing an 8B int8 tree next to already-built leaves on one
    16 GB v5e chip.

    Two OOM guards beyond the slicing itself (ADVICE r5):

    - The loop SYNCHRONIZES on each slice (``jax.block_until_ready``)
      before dispatching the next. Async dispatch would otherwise enqueue
      all L slice programs at once and several ~235 MB fp32 transients
      could be live simultaneously during 8B init — exactly the cap this
      function promises.
    - The stacked q/scale build incrementally via DONATED in-place slice
      writes instead of ``jnp.stack``: the stack briefly held every
      per-slice part AND the stacked copy — a 2x-int8 transient, ~3.8 GB
      on the 8B mlp stack next to the still-live bf16 input — while the
      donated write keeps ONE output buffer plus a single in-flight slice.

    2D (unstacked) weights fall through to whole-leaf quantization."""
    qfn = (lambda x: quantize_int4(x, group_size)) if mode == "int4" else quantize
    cls = Q4Tensor if mode == "int4" else QTensor
    if w.ndim < 3:
        return qfn(w)
    L = w.shape[0]
    q = scale = None
    for i in range(L):
        # eager on purpose: jit-fusing quantize flips round() boundary
        # cases (see init_quantized_llama_params) and would break the
        # bit-identity promised above
        part = qfn(w[i])
        jax.block_until_ready(part.q)  # one slice's transients at a time  # finchat-lint: disable=event-loop-blocking -- deliberate per-slice sync bounding quantization transients (PR 1 satellite); startup/checkpoint path
        if q is None:
            q = jnp.zeros((L,) + part.q.shape, part.q.dtype)
            scale = jnp.zeros((L,) + part.scale.shape, part.scale.dtype)
        idx = jnp.int32(i)
        q = _set_stacked_slice(q, idx, part.q[None])
        scale = _set_stacked_slice(scale, idx, part.scale[None])
    return cls(q=q, scale=scale)


def dense(x: Array, w: Array | QTensor | Q4Tensor, *,
          qm_backend: str | None = None) -> Array:
    """``x @ w`` for a plain or quantized weight. Quantized leaves route
    through ``ops/dispatch.quant_matmul`` (PR 16): the reference backend
    is BITWISE the historical inline dequant ``x @ dequantize(w, x.dtype)``
    (see the module docstring for why not post-matmul scaling) and stays
    the CPU/tier-1 serving path; the Pallas backend streams the weight
    packed from HBM and dequantizes in the matmul tile loop, so the bf16
    tensor never rematerializes per layer. ``qm_backend`` follows the
    ops/dispatch contract: jitted callers (the engine) resolve once and
    pass it statically; ``None`` resolves env at trace time."""
    if isinstance(w, (QTensor, Q4Tensor)):
        from finchat_tpu.ops.dispatch import quant_matmul

        return quant_matmul(x, w, backend=qm_backend)
    return x @ w


def flat_fence(product: Array) -> Array:
    """The identity, as a fence: a projection's product ``[B, S, N]`` held FLAT
    before its columns are split into heads. What it stops is the TPU
    compiler's layout assignment carrying the head-major layout of the split
    (and of the rotation behind it) back through the dot into the WEIGHT:
    unfenced, a layer's ``attn_q`` and ``attn_k`` were sliced out of their
    stack into on-chip memory and transposed there before the matmul could
    start (Mistral: ``bf16[1,4096,4096]`` ``{2,1,0}`` -> ``{1,2,0}``, 59 us of
    a 1.2 ms layer; Phi-4-flash: its whole stack of 16 ``attn_q``, 210 MB,
    transposed in HBM once a step), where ``attn_v`` and the MLP's weights,
    whose product is not rotated, are read straight from the stack inside the
    matmul's fusion. Fenced, q and k are read that way too. The product is
    rounded where it was, so no value changes. Found in the programs compiled
    for a described v5e (``benchmarks/relayout_probe.py`` lists them,
    ``tests/test_tpu_compile.py`` holds the count at 0) and measured on the
    chip (PERF.md sections 5 and 6, PR 45)."""
    return jax.lax.optimization_barrier(product)


def should_quantize(name: str) -> bool:
    """The ONE definition of which param leaves quantize: the layer-stack
    matmul weights plus the (untied) ``lm_head``. Shared by engine-side
    quantization, streaming random init, and the per-tensor checkpoint
    loader so the three paths can never diverge."""
    return name in QUANT_LAYER_LEAVES or name == "lm_head"


def validate_quant_mode(quant: str) -> None:
    """The ONE weight-quant-mode validator shared by the engine and the
    checkpoint loader, so the two serving construction paths cannot
    drift. (CLI surfaces additionally constrain via argparse choices,
    and the embed encoder supports only the int8 subset — both narrower
    than, never wider than, this set.)"""
    if quant and quant not in ("int8", "int4"):
        raise ValueError(
            f"unknown quant mode {quant!r} (supported: 'int8', 'int4')"
        )


def init_quantized_llama_params(config: Any, key: Any, mode: str = "int8",
                                group_size: int = 0) -> dict[str, Any]:
    """Random-init a param tree with matmul weights ALREADY int8/int4 — each
    leaf quantizes at creation (models/llama.py ``leaf_transform``), so the
    full bf16 tree never coexists with the int8 one. This is what lets a
    random-weight llama3-8b (16 GB bf16) materialize on one 16 GB v5e chip
    from a seed; checkpoint serving gets the same effect from the loader's
    per-tensor path. Identical numerics to ``quantize_llama_params``
    applied after ``init_params`` (asserted in tests/test_quant.py).

    Stacked leaves go through ``quantize_stacked`` (shared with the HF
    loader's per-tensor path): whole-leaf eager ``quantize`` would
    MATERIALIZE its fp32 upcast on top of the already-built tree.
    (jit-fusing quantize would avoid the transient too but changes the
    division into reciprocal-multiply and flips round() boundary cases —
    observed 1 ulp on ~0.006% of weights — breaking the bit-identity
    this docstring promises.)"""

    def leaf_transform(name: str, w: Any) -> Any:
        return (quantize_stacked(w, mode=mode, group_size=group_size)
                if should_quantize(name) else w)

    from finchat_tpu.models.llama import init_params

    return init_params(config, key, leaf_transform=leaf_transform)


def quantize_llama_params(params: dict[str, Any], mode: str = "int8",
                          group_size: int = 0) -> dict[str, Any]:
    """Quantize a Llama/Mixtral param tree's matmul weights in place of the
    bf16 leaves (models/llama.py layout). Embedding (a gather, not a
    matmul), norms, and the MoE router stay full precision; ``lm_head`` is
    quantized when present (tied-embedding models keep the dense path).
    ``mode`` selects int8 (per-output-channel scales) or int4 (packed
    nibbles, ``group_size`` rows of K per scale; 0 = per-channel)."""
    validate_quant_mode(mode or "int8")

    def q(leaf: Any) -> Any:
        if isinstance(leaf, (QTensor, Q4Tensor)):
            return leaf  # idempotent (pre-quantized streaming load)
        if mode == "int4":
            return quantize_int4(leaf, group_size)
        return quantize(leaf)

    layers = {
        name: q(leaf) if should_quantize(name) else leaf
        for name, leaf in params["layers"].items()
    }
    out = {**params, "layers": layers}
    if "lm_head" in params:
        out["lm_head"] = q(params["lm_head"])
    return out
