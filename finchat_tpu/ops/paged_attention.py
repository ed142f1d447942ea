"""Pallas ragged paged attention for TPU — the decode-side hot kernel.

SURVEY §7.3 hard part #1: this kernel gates the decode-throughput target.
The jnp reference path (engine/kv_cache.py ``gather_kv`` + ``mha_reference``)
materializes every sequence's pages into a dense ``[B, max_pages*page_size]``
KV copy per layer per step — reading AND writing the whole allocation-shaped
cache through HBM each token. This kernel instead reads K/V pages **in
place** via a scalar-prefetched page table, so per-step HBM traffic is
exactly the live KV bytes (ragged per sequence), with Pallas double-buffering
the page DMAs behind the MXU work.

Cache layout: ``[n_layers, P, page_size, Hkv*hd]`` — token-major pages,
heads fused into the minor dim (see engine/kv_cache.py for why). The kernel
takes the FULL-depth cache plus a scalar-prefetched layer index, because the
cache rides the model's layer scan as a carry; slicing one layer out with
XLA would copy it.

Design:
- grid ``(B, nq, max_pages)`` — page axis innermost; online-softmax state
  (m, l, acc) carries across a sequence's pages in VMEM scratch. All KV
  heads are processed in ONE program (a static inner unroll): TPU grid
  iterations cost ~1 µs each, and a per-(kv-head) grid axis multiplied the
  count by Hkv — ~30 ms/step of pure grid overhead at TinyLlama bench
  shapes (measured round 4, benchmarks/profile_decode.py).
- per-head K/V tiles are VALUE slices ``k_blk[:, h*hd:(h+1)*hd]`` of the
  loaded ``(page_size, Hkv*hd)`` block — in-kernel value slicing is exempt
  from Mosaic's DMA tile-alignment rules.
- the K/V BlockSpec index map resolves ``page_table[b, p]`` at DMA time
  (PrefetchScalarGridSpec); pages that are causally skippable or past
  ``kv_len[b]`` are redirected to the trash page (physical page 0, the same
  page the writers park padding in), and consecutive identical block
  indices are not re-fetched by the pipeline.
- GQA: each kv head's ``group = H // Hkv`` query heads ride in the same
  q block, so each page is fetched once per (b, q-block).

Serves both decode (C = 1) and paged chunked prefill (C = chunk) — the same
causal/ragged masking as ``ops.refs.mha_reference`` with ``q_offset``/
``kv_len`` semantics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from finchat_tpu.ops.flash_attention import (
    NEG_INF,
    _online_softmax_update,
    _pick_block,
    _round_up,
)

TRASH_PAGE = 0


def _pad_chunk(q: Array) -> tuple[Array, int]:
    """Pad a short multi-token chunk to whole 8-row sublane tiles. The
    kernel collapses its ``(group, bq, D)`` query block to ``(group*bq, D)``,
    and Mosaic has no layout for that cast when ``bq`` is not a multiple of
    8 — a 3-token spec-verify block failed on the v5e with "unsupported
    shape cast vector<1x8x3x64xbf16> -> vector<24x64xbf16>". The padding
    rows are computed like any query and dropped by the wrapper; C = 1
    (decode) is a plain squeeze and stays as it is."""
    C = q.shape[1]
    if C == 1 or C % 8 == 0:
        return q, C
    padded = _round_up(C, 8)
    return jnp.pad(q, ((0, 0), (0, padded - C), (0, 0), (0, 0))), padded


def _paged_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32
    page_table_ref,  # [B, max_pages] int32 in SMEM
    q_offset_ref,  # [B] int32
    kv_len_ref,  # [B] int32
    # blocks
    q_ref,  # [1, H, Bq, D]
    k_ref,  # [1, 1, page_size, Hkv*D] — one physical page
    v_ref,
    o_ref,  # [1, H, Bq, D]
    # scratch
    m_scr,  # [Rpad, 128] fp32
    l_scr,
    acc_scr,  # [Rpad, D] fp32
    *,
    block_q: int,
    page_size: int,
    n_kv: int,
    group: int,
    scale: float,
):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    p = pl.program_id(2)
    n_pages = pl.num_programs(2)

    Bq = block_q
    D = q_ref.shape[-1]
    Rh = group * Bq  # scratch rows per kv head
    q_off = q_offset_ref[b]
    kv_len = kv_len_ref[b]

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    page_start = p * page_size
    q_max = q_off + (qi + 1) * Bq - 1
    needed = jnp.logical_and(page_start < kv_len, page_start <= q_max)

    @pl.when(needed)
    def _accumulate():
        rows = jax.lax.broadcasted_iota(jnp.int32, (Rh, page_size), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (Rh, page_size), 1)
        q_pos = q_off + qi * Bq + rows % Bq
        kv_pos = page_start + cols
        invalid = jnp.logical_or(kv_pos >= kv_len, kv_pos > q_pos)

        for h in range(n_kv):  # static unroll over kv heads
            # row r = (query head h*group + r // Bq), position r % Bq
            q_blk = q_ref[0, h * group:(h + 1) * group].reshape(Rh, D)
            k_blk = k_ref[0, 0, :, h * D:(h + 1) * D]  # [PS, D] value slice
            v_blk = v_ref[0, 0, :, h * D:(h + 1) * D]
            r0 = h * Rh

            m_new, l_new, acc_new = _online_softmax_update(
                q_blk, k_blk, v_blk, invalid,
                m_scr[r0:r0 + Rh, :1], l_scr[r0:r0 + Rh, :1],
                acc_scr[r0:r0 + Rh], scale,
            )
            m_scr[r0:r0 + Rh, :1] = m_new
            l_scr[r0:r0 + Rh, :1] = l_new
            acc_scr[r0:r0 + Rh] = acc_new

    @pl.when(p == n_pages - 1)
    def _finalize():
        R = n_kv * Rh
        out = acc_scr[:R] / jnp.maximum(l_scr[:R, :1], 1e-30)
        o_ref[0] = out.reshape(n_kv * group, Bq, D).astype(o_ref.dtype)


def _paged_kernel_q8(
    # scalar prefetch
    layer_ref,
    page_table_ref,
    q_offset_ref,
    kv_len_ref,
    # blocks
    q_ref,  # [1, H, Bq, D]
    k_ref,  # [1, 1, page_size, Hkv*D] int8 — one physical page
    v_ref,
    ks_ref,  # [1, 1, SPAD, page_size] fp32 — per-token-per-head scales
    vs_ref,
    o_ref,
    # scratch
    m_scr,
    l_scr,
    acc_scr,
    *,
    block_q: int,
    page_size: int,
    n_kv: int,
    group: int,
    scale: float,
):
    """Int8-KV variant of ``_paged_kernel``: identical control flow; K/V
    tiles dequantize in VMEM (int8 page * per-token scale row) before the
    same online-softmax update, so HBM streams half the KV bytes."""
    b = pl.program_id(0)
    qi = pl.program_id(1)
    p = pl.program_id(2)
    n_pages = pl.num_programs(2)

    Bq = block_q
    D = q_ref.shape[-1]
    Rh = group * Bq
    q_off = q_offset_ref[b]
    kv_len = kv_len_ref[b]

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    page_start = p * page_size
    q_max = q_off + (qi + 1) * Bq - 1
    needed = jnp.logical_and(page_start < kv_len, page_start <= q_max)

    @pl.when(needed)
    def _accumulate():
        rows = jax.lax.broadcasted_iota(jnp.int32, (Rh, page_size), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (Rh, page_size), 1)
        q_pos = q_off + qi * Bq + rows % Bq
        kv_pos = page_start + cols
        invalid = jnp.logical_or(kv_pos >= kv_len, kv_pos > q_pos)

        for h in range(n_kv):  # static unroll over kv heads
            q_blk = q_ref[0, h * group:(h + 1) * group].reshape(Rh, D)
            ks = ks_ref[0, 0, h, :][:, None]  # [PS, 1] per-token scale
            vs = vs_ref[0, 0, h, :][:, None]
            k_blk = (k_ref[0, 0, :, h * D:(h + 1) * D].astype(jnp.float32) * ks
                     ).astype(q_blk.dtype)
            v_blk = (v_ref[0, 0, :, h * D:(h + 1) * D].astype(jnp.float32) * vs
                     ).astype(q_blk.dtype)
            r0 = h * Rh

            m_new, l_new, acc_new = _online_softmax_update(
                q_blk, k_blk, v_blk, invalid,
                m_scr[r0:r0 + Rh, :1], l_scr[r0:r0 + Rh, :1],
                acc_scr[r0:r0 + Rh], scale,
            )
            m_scr[r0:r0 + Rh, :1] = m_new
            l_scr[r0:r0 + Rh, :1] = l_new
            acc_scr[r0:r0 + Rh] = acc_new

    @pl.when(p == n_pages - 1)
    def _finalize():
        R = n_kv * Rh
        out = acc_scr[:R] / jnp.maximum(l_scr[:R, :1], 1e-30)
        o_ref[0] = out.reshape(n_kv * group, Bq, D).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "n_kv", "scale", "block_q", "interpret"),
)
def paged_flash_attention_q8(
    q: Array,  # [B, C, H, D]
    k_pages: Array,  # [L, P, page_size, Hkv*D] int8
    v_pages: Array,
    k_scales: Array,  # [L, P, SPAD, page_size] fp32
    v_scales: Array,
    page_table: Array,
    q_offset: Array,
    kv_len: Array,
    layer: Array,
    *,
    page_size: int,
    n_kv: int,
    scale: float | None = None,
    block_q: int = 128,
    interpret: bool = False,
) -> Array:
    """Attention over the int8 paged KV cache; same contract as
    ``paged_flash_attention`` with the scale arrays riding the same
    scalar-prefetched page indirection."""
    B, n_queries, H, D = q.shape
    max_pages = page_table.shape[1]
    assert H % n_kv == 0, (H, n_kv)
    assert k_pages.shape[2] == page_size, (k_pages.shape, page_size)
    assert k_pages.shape[3] == n_kv * D, (k_pages.shape, n_kv, D)
    assert k_scales.shape[3] == page_size, (k_scales.shape, page_size)
    group = H // n_kv
    scale = scale if scale is not None else D ** -0.5

    q_offset = jnp.asarray(q_offset, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    page_table = jnp.asarray(page_table, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)

    q, C = _pad_chunk(q)
    bq = _pick_block(C, block_q)
    nq = C // bq
    r_pad = _round_up(max(H * bq, 8), 8)
    spad = k_scales.shape[2]

    q_t = q.transpose(0, 2, 1, 3)  # [B, H, C, D]

    def kv_index(b, qi, p, layer_ref, page_table_ref, q_offset_ref, kv_len_ref):
        page_start = p * page_size
        q_max = q_offset_ref[b] + (qi + 1) * bq - 1
        needed = jnp.logical_and(page_start < kv_len_ref[b], page_start <= q_max)
        phys = jnp.where(needed, page_table_ref[b, p], TRASH_PAGE)
        return (layer_ref[0], phys, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, nq, max_pages),
        in_specs=[
            pl.BlockSpec((1, H, bq, D), lambda b, qi, p, *_: (b, 0, qi, 0)),
            pl.BlockSpec((1, 1, page_size, n_kv * D), kv_index),
            pl.BlockSpec((1, 1, page_size, n_kv * D), kv_index),
            pl.BlockSpec((1, 1, spad, page_size), kv_index),
            pl.BlockSpec((1, 1, spad, page_size), kv_index),
        ],
        out_specs=pl.BlockSpec((1, H, bq, D), lambda b, qi, p, *_: (b, 0, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((r_pad, 128), jnp.float32),
            pltpu.VMEM((r_pad, 128), jnp.float32),
            pltpu.VMEM((r_pad, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel_q8,
        block_q=bq, page_size=page_size, n_kv=n_kv, group=group, scale=scale,
    )
    out_t = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, C, D), q.dtype),
        interpret=interpret,
    )(layer, page_table, q_offset, kv_len, q_t, k_pages, v_pages, k_scales, v_scales)
    return out_t.transpose(0, 2, 1, 3)[:, :n_queries]


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "n_kv", "scale", "block_q", "interpret"),
)
def paged_flash_attention(
    q: Array,  # [B, C, H, D] — C = 1 for decode, chunk size for prefill
    k_pages: Array,  # [L, P, page_size, Hkv*D] — full-depth cache, in place
    v_pages: Array,
    page_table: Array,  # [B, max_pages] int32 physical page ids (0 = trash)
    q_offset: Array,  # [B] int32 — absolute position of q[:, 0]
    kv_len: Array,  # [B] int32 — valid KV length incl. this chunk's tokens
    layer: Array,  # [1] int32 — which layer's pages to read
    *,
    page_size: int,
    n_kv: int,
    scale: float | None = None,
    block_q: int = 128,
    interpret: bool = False,
) -> Array:
    """Attention over the paged KV cache; returns [B, C, H, D].

    Causal with absolute positions (query row i of batch b is at
    ``q_offset[b] + i``); sequences with ``kv_len == 0`` produce zeros.
    The current chunk's K/V must already be in the pages (the decode append
    kernel or the prefill scatter runs first).
    """
    B, n_queries, H, D = q.shape
    max_pages = page_table.shape[1]
    assert H % n_kv == 0, (H, n_kv)
    assert k_pages.shape[2] == page_size, (k_pages.shape, page_size)
    assert k_pages.shape[3] == n_kv * D, (k_pages.shape, n_kv, D)
    group = H // n_kv
    scale = scale if scale is not None else D ** -0.5

    q_offset = jnp.asarray(q_offset, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    page_table = jnp.asarray(page_table, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)

    q, C = _pad_chunk(q)
    bq = _pick_block(C, block_q)
    nq = C // bq
    r_pad = _round_up(max(H * bq, 8), 8)

    q_t = q.transpose(0, 2, 1, 3)  # [B, H, C, D]

    def kv_index(b, qi, p, layer_ref, page_table_ref, q_offset_ref, kv_len_ref):
        # resolve logical page -> physical page at DMA time; redirect pages
        # that contribute nothing to the trash page (repeat fetches of the
        # same block index are skipped by the pipeline)
        page_start = p * page_size
        q_max = q_offset_ref[b] + (qi + 1) * bq - 1
        needed = jnp.logical_and(page_start < kv_len_ref[b], page_start <= q_max)
        phys = jnp.where(needed, page_table_ref[b, p], TRASH_PAGE)
        return (layer_ref[0], phys, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, nq, max_pages),
        in_specs=[
            pl.BlockSpec((1, H, bq, D), lambda b, qi, p, *_: (b, 0, qi, 0)),
            pl.BlockSpec((1, 1, page_size, n_kv * D), kv_index),
            pl.BlockSpec((1, 1, page_size, n_kv * D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, H, bq, D), lambda b, qi, p, *_: (b, 0, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((r_pad, 128), jnp.float32),
            pltpu.VMEM((r_pad, 128), jnp.float32),
            pltpu.VMEM((r_pad, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel,
        block_q=bq, page_size=page_size, n_kv=n_kv, group=group, scale=scale,
    )
    out_t = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, C, D), q.dtype),
        interpret=interpret,
    )(layer, page_table, q_offset, kv_len, q_t, k_pages, v_pages)
    return out_t.transpose(0, 2, 1, 3)[:, :n_queries]
