"""Pallas paged attention for TPU — the decode-side hot kernel.

SURVEY §7.3 hard part #1: this kernel gates the decode-throughput target.
The jnp reference path (engine/kv_cache.py ``gather_kv`` + ``mha_reference``)
materializes every sequence's pages into a dense ``[B, max_pages*page_size]``
KV copy per layer per step — reading AND writing the whole allocation-shaped
cache through HBM each token. This kernel instead reads K/V pages **in
place** through the scalar-prefetched page table, so per-step HBM traffic is
the live KV bytes (ragged per sequence) and nothing else.

Cache layout: ``[n_layers, P, page_size, Hkv*hd]`` — token-major pages,
heads fused into the minor dim (see engine/kv_cache.py for why). The kernel
takes the FULL-depth cache plus a scalar-prefetched layer index, because the
cache rides the model's layer scan as a carry; slicing one layer out with
XLA would copy it.

Design — a walk as long as the row, several pages a block:
- grid ``(B, nq)``: one program per (sequence, query block). The width of
  the page table is NOT a grid axis: a table of 128 entries over a row of 60
  live pages used to cost 68 dead grid steps a row (PERF.md §6, PR 25).
- the cache stays in HBM (``ANY`` memory space). Inside a program a loop
  whose trip count is the row's own ``ceil(live pages / pages_per_block)``
  copies one block of pages at a time through the page table into a
  double-buffered VMEM scratch (``pltpu.make_async_copy``, one DMA semaphore
  per buffer slot and source array), the next block's copies in flight
  while this block is computed. Live pages are those below ``kv_len`` and
  not entirely in the causal future of the query block; no table entry at
  or beyond that count is ever read — the slots of a last, partial block
  re-read the row's last live page, and their positions are masked.
- the online-softmax state (m, l, acc; fp32, VMEM scratch) is updated once
  a block of ``pages_per_block * page_size`` tokens (about 512), so the
  masks and the scratch read-modify-writes are paid once a block and the
  score / value products run over ``[rows, block]`` tiles. All KV heads are
  processed in ONE program (a static inner unroll), each on a VALUE slice
  ``buf[slot, :, :, h*hd:(h+1)*hd]`` of the loaded block.
- ``pages_per_block`` follows from static shapes alone (``_pages_per_block``).
- GQA: each kv head's ``group = H // Hkv`` query heads ride in the same
  q block, so each page is fetched once per (b, q-block).
- the int8 cache (``paged_flash_attention_q8``) is the same walk with the
  per-token scale blocks riding the same copies; the scales are applied to
  the logits and the probabilities (``_online_softmax_update``), whose
  lanes they already lie along.

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

BLOCK_TOKENS = 512  # KV tokens per online-softmax update, where they fit
SCORE_TILE_BYTES = 1 << 19  # one kv head's fp32 [rows, block] logit tile
KV_BUFFER_BYTES = 8 << 20  # both slots of the K and the V block


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


def _pages_per_block(page_size: int, head_rows: int, width: int, itemsize: int,
                     max_pages: int) -> int:
    """Pages copied and computed together: ``BLOCK_TOKENS`` tokens, halved
    while one kv head's logit tile (``head_rows = group * block_q`` query
    rows) or the double-buffered K and V blocks (``width = Hkv * hd``
    elements a token) outgrow their VMEM budgets; never under one page nor
    over the table."""
    tokens = BLOCK_TOKENS
    while tokens > page_size and (
            head_rows * tokens * 4 > SCORE_TILE_BYTES
            or 4 * tokens * width * itemsize > KV_BUFFER_BYTES):
        tokens //= 2
    return max(1, min(tokens // page_size, max_pages))


def _paged_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32
    page_table_ref,  # [B, max_pages] int32 in SMEM
    q_offset_ref,  # [B] int32
    kv_len_ref,  # [B] int32
    # blocks
    q_ref,  # [1, H, Bq, D]
    *refs,  # HBM sources, o_ref, m/l/acc scratch, VMEM buffers, semaphores
    block_q: int,
    page_size: int,
    pages_per_block: int,
    n_kv: int,
    group: int,
    scale: float,
    quantized: bool,
):
    """One (sequence, query block): walk the row's live pages a block at a
    time. ``refs`` holds, in order, the HBM sources ``k, v`` (int8 cache:
    ``k, v, k_scales, v_scales``, ``[L, P, ...]`` each), the output block,
    the m / l / acc scratch, one ``[2, pages_per_block, ...]`` VMEM buffer
    per source, and the DMA semaphores ``[2, n_sources]``."""
    n_src = 4 if quantized else 2
    sources = refs[:n_src]
    o_ref, m_scr, l_scr, acc_scr = refs[n_src:n_src + 4]
    buffers = refs[n_src + 4:2 * n_src + 4]
    sems = refs[2 * n_src + 4]

    b = pl.program_id(0)
    qi = pl.program_id(1)
    Bq, ppb = block_q, pages_per_block
    D = q_ref.shape[-1]
    Rh = group * Bq  # scratch rows per kv head
    T = ppb * page_size  # tokens per block
    layer = layer_ref[0]
    q_off = q_offset_ref[b]
    kv_len = kv_len_ref[b]

    # live pages: below kv_len, and not wholly after this q block's last row
    q_max = q_off + (qi + 1) * Bq - 1
    n_live = jnp.minimum(pl.cdiv(kv_len, page_size), q_max // page_size + 1)
    n_live = jnp.clip(n_live, 0, page_table_ref.shape[1])
    n_blocks = pl.cdiv(n_live, ppb)

    def copies(j, slot, table_ref=None):
        """Block j's page copies into ``slot``; without ``table_ref`` only
        their shapes matter (a wait counts bytes, not addresses)."""
        out = []
        for i in range(ppb):
            if table_ref is None:
                phys = 0
            else:  # a partial last block re-reads the last live page
                phys = table_ref[b, jnp.minimum(j * ppb + i, n_live - 1)]
            out += [pltpu.make_async_copy(src.at[layer, phys], buf.at[slot, i],
                                          sems.at[slot, s])
                    for s, (src, buf) in enumerate(zip(sources, buffers))]
        return out

    m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(n_blocks > 0)
    def _first():
        for c in copies(0, 0, page_table_ref):
            c.start()

    def block(j, carry):
        slot = j % 2

        @pl.when(j + 1 < n_blocks)
        def _next():
            for c in copies(j + 1, 1 - slot, page_table_ref):
                c.start()

        for c in copies(j, slot):
            c.wait()

        rows = jax.lax.broadcasted_iota(jnp.int32, (Rh, T), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (Rh, T), 1)
        q_pos = q_off + qi * Bq + rows % Bq
        kv_pos = j * T + cols
        invalid = jnp.logical_or(kv_pos >= kv_len, kv_pos > q_pos)

        for h in range(n_kv):  # static unroll over kv heads
            # row r = (query head h*group + r // Bq), position r % Bq
            q_blk = q_ref[0, h * group:(h + 1) * group].reshape(Rh, D)
            k_blk = buffers[0][slot, :, :, h * D:(h + 1) * D].reshape(T, D)
            v_blk = buffers[1][slot, :, :, h * D:(h + 1) * D].reshape(T, D)
            k_scale = v_scale = None
            if quantized:  # int8 is exact in the query dtype
                k_blk = k_blk.astype(jnp.float32).astype(q_blk.dtype)
                v_blk = v_blk.astype(jnp.float32).astype(q_blk.dtype)
                k_scale, v_scale = (
                    jnp.concatenate([buf[slot, i, h:h + 1, :] for i in range(ppb)],
                                    axis=1)  # [1, T] per-token scales
                    for buf in buffers[2:])
            r0 = h * Rh

            m_new, l_new, acc_new = _online_softmax_update(
                q_blk, k_blk, v_blk, invalid,
                m_scr[r0:r0 + Rh, :1], l_scr[r0:r0 + Rh, :1],
                acc_scr[r0:r0 + Rh], scale, k_scale, v_scale,
            )
            m_scr[r0:r0 + Rh, :1] = m_new
            l_scr[r0:r0 + Rh, :1] = l_new
            acc_scr[r0:r0 + Rh] = acc_new
        return carry

    jax.lax.fori_loop(0, n_blocks, block, None)

    R = n_kv * Rh
    out = acc_scr[:R] / jnp.maximum(l_scr[:R, :1], 1e-30)
    o_ref[0] = out.reshape(n_kv * group, Bq, D).astype(o_ref.dtype)


def _paged_call(q, sources, page_table, q_offset, kv_len, layer, *,
                page_size, n_kv, scale, block_q, interpret):
    """The walk over ``sources`` = ``(k_pages, v_pages)`` or, for the int8
    cache, ``(k_pages, v_pages, k_scales, v_scales)``."""
    B, n_queries, H, D = q.shape
    k_pages = sources[0]
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
    ppb = _pages_per_block(page_size, group * bq, n_kv * D,
                           k_pages.dtype.itemsize, page_table.shape[1])

    q_t = q.transpose(0, 2, 1, 3)  # [B, H, C, D]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, nq),
        in_specs=[pl.BlockSpec((1, H, bq, D), lambda b, qi, *_: (b, 0, qi, 0))]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(sources),
        out_specs=pl.BlockSpec((1, H, bq, D), lambda b, qi, *_: (b, 0, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((r_pad, 128), jnp.float32),
            pltpu.VMEM((r_pad, 128), jnp.float32),
            pltpu.VMEM((r_pad, D), jnp.float32),
            *(pltpu.VMEM((2, ppb) + src.shape[2:], src.dtype) for src in sources),
            pltpu.SemaphoreType.DMA((2, len(sources))),
        ],
    )
    kernel = functools.partial(
        _paged_kernel,
        block_q=bq, page_size=page_size, pages_per_block=ppb, n_kv=n_kv,
        group=group, scale=scale, quantized=len(sources) == 4,
    )
    out_t = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, C, D), q.dtype),
        interpret=interpret,
    )(layer, page_table, q_offset, kv_len, q_t, *sources)
    return out_t.transpose(0, 2, 1, 3)[:, :n_queries]


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
    assert k_scales.shape[3] == page_size, (k_scales.shape, page_size)
    return _paged_call(
        q, (k_pages, v_pages, k_scales, v_scales), page_table, q_offset, kv_len,
        layer, page_size=page_size, n_kv=n_kv, scale=scale, block_q=block_q,
        interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "n_kv", "scale", "block_q", "interpret"),
)
def paged_flash_attention(
    q: Array,  # [B, C, H, D] — C = 1 for decode, chunk size for prefill
    k_pages: Array,  # [L, P, page_size, Hkv*D] — full-depth cache, in place
    v_pages: Array,
    page_table: Array,  # [B, max_pages] int32 physical page ids
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
    kernel or the prefill scatter runs first). Table entries at or beyond a
    row's live page count are never read.
    """
    return _paged_call(
        q, (k_pages, v_pages), page_table, q_offset, kv_len, layer,
        page_size=page_size, n_kv=n_kv, scale=scale, block_q=block_q,
        interpret=interpret)
