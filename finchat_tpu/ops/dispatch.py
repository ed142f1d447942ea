"""Kernel backend dispatch: Pallas kernels on TPU, jnp references on CPU.

One switch per kernel family for the whole engine (SURVEY §7.2 step 4
wiring): ``FINCHAT_ATTN`` for the attention kernels and
``FINCHAT_QUANT_MATMUL`` for the fused dequant-matmul plane. Resolution
order (same for both):

1. the env var: ``pallas`` | ``ref`` | ``pallas-interpret``
   (the last runs the Pallas kernels through the interpreter on any backend
   — what the CI mesh uses to exercise kernel code paths without a TPU);
2. default: ``pallas`` when the runtime backend is TPU, else ``ref``.

The reference implementations are the correctness oracles and the serving
path on a CPU backend. Nothing switches backend behind the resolution: a
``pallas`` kernel that Mosaic refuses is an error, and the process entry
refuses a CPU backend nobody asked for (utils/runtime.py), so ``ref`` on an
accelerator host is always somebody's explicit choice.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import Array

from finchat_tpu.utils.logging import get_logger

logger = get_logger(__name__)

_VALID = ("pallas", "ref", "pallas-interpret")


def attention_backend() -> str:
    """Resolve the default backend. Callers that jit should resolve ONCE and
    pass the result through as a static argument (the engine does) — reading
    env inside a traced function would bake the first resolution into the
    jit cache."""
    choice = os.getenv("FINCHAT_ATTN", "").strip().lower()
    if choice:
        if choice not in _VALID:
            raise ValueError(f"FINCHAT_ATTN must be one of {_VALID}, got {choice!r}")
        return choice
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def paged_attention(
    q: Array,  # [B, C, H, D]
    k_pages: Array,  # [L, P, page_size, Hkv*D] — full-depth cache (or int8)
    v_pages: Array,
    page_table: Array,  # [B, max_pages]
    q_offset: Array,  # [B]
    kv_len: Array,  # [B]
    layer: Array,  # [1] int32 — which layer's pages to read
    *,
    page_size: int,
    n_kv: int,
    backend: str | None = None,
    k_scales: Array | None = None,  # int8 cache: [L, P, SPAD, page_size] fp32
    v_scales: Array | None = None,
    shared: tuple[Array, Array] | None = None,  # decode: ``shared_head``'s
    scale: float | None = None,  # the softmax scale; None = D ** -0.5
    window: int = 0,  # > 0: a sliding-window layer (a query and the window - 1 before it)
    sink: Array | None = None,  # [H] float32: the layer's softmax sink (``refs.mha_reference``)
) -> Array:
    """Paged-KV attention via the requested (or default) backend. An int8
    cache (engine kv_quant) is detected from the page dtype; the scale
    arrays must then be provided. ``shared`` hands the kernel a decode
    batch's shared head where the caller derived it once for every layer
    (ops/paged_attention.py ``shared_head``; the reference has no use for it)."""
    backend = backend or attention_backend()
    quantized = k_pages.dtype == jnp.int8
    if quantized:
        assert k_scales is not None and v_scales is not None
    if backend == "ref":
        from finchat_tpu.engine.kv_cache import gather_kv_any
        from finchat_tpu.ops.refs import mha_reference

        lay = jnp.asarray(layer, jnp.int32).reshape(())
        k_all, v_all = gather_kv_any(
            k_pages, v_pages, k_scales, v_scales, page_table, page_size,
            lay, n_kv, dtype=q.dtype,
        )
        return mha_reference(
            q, k_all, v_all, causal=True, q_offset=q_offset, kv_len=kv_len, scale=scale,
            window=window, sink=sink,
        )
    interpret = backend == "pallas-interpret"
    if quantized:
        from finchat_tpu.ops.paged_attention import paged_flash_attention_q8

        assert not window, "no sliding-window form over the int8 cache"

        return paged_flash_attention_q8(
            q, k_pages, v_pages, k_scales, v_scales, page_table,
            q_offset, kv_len, layer, shared,
            page_size=page_size, n_kv=n_kv, scale=scale, interpret=interpret,
        )
    from finchat_tpu.ops.paged_attention import paged_flash_attention

    return paged_flash_attention(
        q, k_pages, v_pages, page_table, q_offset, kv_len, layer, shared,
        page_size=page_size, n_kv=n_kv, scale=scale, interpret=interpret,
        **({"window": window} if window else {}),
        **({"sink": sink} if sink is not None else {}),
    )


def ragged_paged_attention(
    q: Array,  # [T, H, D] — packed ragged token buffer
    k_pages: Array,  # [L, P, page_size, Hkv*D] — full-depth cache (or int8)
    v_pages: Array,
    page_table: Array,  # [R, max_pages] — per-ROW physical page lists
    tok_row: Array,  # [T] — owning row per packed token (R = padding)
    tok_pos: Array,  # [T] — absolute position per packed token
    kv_len: Array,  # [R] — valid KV per row incl. this dispatch's tokens
    layer: Array,  # [1] int32
    *,
    page_size: int,
    n_kv: int,
    backend: str | None = None,
    k_scales: Array | None = None,  # int8 cache: [L, P, SPAD, page_size] fp32
    v_scales: Array | None = None,
    kv_gap: Array | None = None,  # [R] — bounded-KV window offset per row
    scale: float | None = None,  # the softmax scale; None = D ** -0.5
    window: int = 0,  # > 0: a sliding-window layer (a query and the window - 1 before it)
    block_q: int = 0,  # > 0: the kernel's query block (0 = its own default)
    sink: Array | None = None,  # [H] float32: the layer's softmax sink
) -> Array:
    """Ragged paged-KV attention (ops/ragged_paged_attention.py): prefill
    chunks, decode tokens, and spec verify blocks as rows of ONE packed
    buffer. An int8 cache (engine kv_quant) is detected from the page
    dtype; the scale arrays must then be provided. ``kv_gap`` is the
    bounded-KV per-row eviction offset (tokens dropped between the pinned
    sink pages and the surviving window — see
    ragged_paged_attention_ref); None/zeros = exact unbounded attention."""
    backend = backend or attention_backend()
    quantized = k_pages.dtype == jnp.int8
    if quantized:
        assert k_scales is not None and v_scales is not None
    if backend == "ref":
        from finchat_tpu.ops.ragged_paged_attention import (
            ragged_paged_attention_ref,
        )

        return ragged_paged_attention_ref(
            q, k_pages, v_pages, page_table, tok_row, tok_pos, kv_len, layer,
            page_size=page_size, n_kv=n_kv,
            k_scales=k_scales if quantized else None,
            v_scales=v_scales if quantized else None,
            kv_gap=kv_gap, scale=scale, window=window, sink=sink,
        )
    interpret = backend == "pallas-interpret"
    if quantized:
        from finchat_tpu.ops.ragged_paged_attention import (
            ragged_flash_attention_q8,
        )

        assert not window, "no sliding-window form over the int8 cache"

        return ragged_flash_attention_q8(
            q, k_pages, v_pages, k_scales, v_scales, page_table,
            tok_row, tok_pos, kv_len, layer,
            page_size=page_size, n_kv=n_kv, scale=scale, interpret=interpret,
            kv_gap=kv_gap,
        )
    from finchat_tpu.ops.ragged_paged_attention import ragged_flash_attention

    return ragged_flash_attention(
        q, k_pages, v_pages, page_table, tok_row, tok_pos, kv_len, layer,
        page_size=page_size, n_kv=n_kv, scale=scale, interpret=interpret,
        kv_gap=kv_gap, **({"window": window} if window else {}),
        **({"block_q": block_q} if block_q else {}),
        **({"sink": sink} if sink is not None else {}),
    )


def quant_matmul_backend() -> str:
    """Resolve the fused dequant-matmul backend (``FINCHAT_QUANT_MATMUL``:
    ``pallas`` | ``ref`` | ``pallas-interpret``; default ``pallas`` on TPU,
    ``ref`` elsewhere — the reference is the CPU/tier-1 serving path).
    Same discipline as ``attention_backend``: jitted callers resolve ONCE
    outside the trace and pass the result through (the engine keys its
    compiled steps on it); a ``None`` backend reaching ``quant_matmul``
    inside a trace resolves env at TRACE time and bakes that answer into
    the jit cache."""
    choice = os.getenv("FINCHAT_QUANT_MATMUL", "").strip().lower()
    if choice:
        if choice not in _VALID:
            raise ValueError(
                f"FINCHAT_QUANT_MATMUL must be one of {_VALID}, got {choice!r}"
            )
        return choice
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def quant_matmul(
    x: Array,
    w,  # models/quant QTensor | Q4Tensor
    *,
    backend: str | None = None,
    preferred_element_type=None,
) -> Array:
    """Quantized matmul via the requested (or default) backend: the fused
    Pallas kernel streams the weight PACKED from HBM (ops/quant_matmul.py)
    and dequantizes in-tile; the reference is bitwise the historical
    inline-dequant math. Shapes the kernel does not tile — stacked
    (ndim > 2) weight leaves, i.e. the MoE expert einsums — fall back to
    the reference and count on ``finchat_quantmatmul_fallbacks_total``
    (once per TRACE, not per dispatch: this routing runs at trace time
    inside the engine's compiled steps)."""
    from finchat_tpu.models.quant import Q4Tensor
    from finchat_tpu.ops.quant_matmul import (
        quant_matmul_int4,
        quant_matmul_int8,
        quant_matmul_ref,
    )

    backend = backend or quant_matmul_backend()
    if backend != "ref" and w.q.ndim != 2:
        from finchat_tpu.utils.metrics import METRICS

        METRICS.inc("finchat_quantmatmul_fallbacks_total")
        logger.warning(
            "quant_matmul: no fused kernel for stacked weight shape %s; "
            "falling back to the inline-dequant reference", w.q.shape,
        )
        backend = "ref"
    if backend == "ref":
        return quant_matmul_ref(
            x, w, preferred_element_type=preferred_element_type
        )
    interpret = backend == "pallas-interpret"
    if isinstance(w, Q4Tensor):
        return quant_matmul_int4(
            x, w.q, w.scale, interpret=interpret,
            out_dtype=preferred_element_type,
        )
    return quant_matmul_int8(
        x, w.q, w.scale, interpret=interpret,
        out_dtype=preferred_element_type,
    )


def causal_attention(q: Array, k: Array, v: Array, *, backend: str | None = None,
                     scale: float | None = None) -> Array:
    """Full contiguous causal attention (training / one-shot prefill)."""
    backend = backend or attention_backend()
    if backend == "ref":
        from finchat_tpu.ops.refs import mha_reference

        return mha_reference(q, k, v, causal=True, scale=scale)
    from finchat_tpu.ops.flash_attention import flash_attention

    return flash_attention(q, k, v, causal=True, scale=scale,
                           interpret=(backend == "pallas-interpret"))
