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
- decode (C = 1) reads a SHARED HEAD once a call (PERF.md §6, PR 31). Rows
  admitted on one prefix entry hold the same physical pages at the head of
  their page tables (16 rows on the system prompt's 31 pages in every cell
  of the benchmark: half of what a call used to read was those pages over
  again). ``shared_head`` reads the set off the page tables, the contexts
  and the active mask — the engine once a step, outside the layer scan —
  and the kernel adapts on it inside the ONE call: program 0 first walks
  the head's pages with every row's queries stacked (a ``[rows * 8, D]``
  tile a kv head, each row's group of query heads padded to 8 so that a
  row's partial result is whole sublane tiles of the scratch), through the
  same double-buffered copies and the same block update; then each member's
  own walk starts at the table column behind the head, from its rows of that
  partial m / l / acc instead of from nothing. No member, or a head of no
  page: the first walk has no block and every row walks from column 0 —
  the same program, compiled once whatever the sharing. The walks of one
  decode call are a CHAIN: each starts the first block of the next walk
  (the next row's, in the next grid program: scratch and semaphores outlive
  a program) beside its own last block, so only the call's first copy is
  uncovered — a row's own walk is 7-8 blocks where it was 15, and its
  uncovered first copy had become a fifth of the call. At C > 1 none of
  this is traced.

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
VMEM_BYTES = 31 << 19  # 15.5 MiB of the v5e's 16 MiB of scoped VMEM: blocks, state, buffers


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
                     max_pages: int, reserved: int = 0) -> int:
    """Pages copied and computed together: ``BLOCK_TOKENS`` tokens, halved
    while one kv head's logit tile (``head_rows = group * block_q`` query
    rows) or the double-buffered K and V blocks (``width = Hkv * hd``
    elements a token) outgrow their VMEM budgets — their own, and what the
    call's query and output blocks and its softmax state (``reserved``
    bytes) leave of the whole: at 30 KV heads a token row is 7.5 KiB and a
    128-query prefill block's state alone 5.6 MiB; never under one page nor
    over the table."""
    tokens = BLOCK_TOKENS
    # an int8 block also stands dequantized beside its buffers, head by head
    # (float32, then the query dtype: 6 bytes an element; Mosaic keeps every
    # head's copy of the static unroll)
    token_bytes = width * (4 * itemsize + (6 if itemsize == 1 else 0))
    while tokens > page_size and (
            head_rows * tokens * 4 > SCORE_TILE_BYTES
            or tokens * token_bytes > min(KV_BUFFER_BYTES, VMEM_BYTES - reserved)):
        tokens //= 2
    return max(1, min(tokens // page_size, max_pages))


def shared_head(page_table: Array, kv_len: Array, page_size: int,
                active: Array | None = None) -> tuple[Array, Array]:
    """The shared head of a decode batch, read off what the step already
    holds: ``(member [B] int32, head [2] int32 = (n_shared, lead))``.

    A set is the active rows whose first table entry is the same non-zero
    physical page; its run is the leading table columns on which every row
    of the set holds the same page id, each a whole page below every such
    row's ``kv_len`` (so live and, for a decode query at ``kv_len - 1``,
    causal for all of them). Of several sets the one whose run saves most
    page reads, ``(rows - 1) * run``, is taken: ``member`` marks its rows,
    ``lead`` is one of them. Nothing to save: ``n_shared`` 0, no member.
    An equal page id at an equal column is the same bytes at the same
    (compacted) positions, which is all the kernel's first pass needs."""
    B, W = page_table.shape
    first = page_table[:, 0]
    cand = first != 0
    if active is not None:
        cand &= active
    same = cand[:, None] & cand[None, :] & (first[:, None] == first[None, :])
    cols = jnp.arange(W, dtype=jnp.int32)
    whole = cols[None, :] < (kv_len // page_size)[:, None]  # [B, W]
    agree = jnp.all(
        ~same[:, :, None]
        | ((page_table[:, None, :] == page_table[None, :, :]) & whole[None]),
        axis=1)  # [B, W]: every row of i's set holds i's page at the column
    run = jnp.min(jnp.where(agree, W, cols[None, :]), axis=1)  # first that fails
    saved = (jnp.sum(same, axis=1) - 1) * run
    lead = jnp.argmax(saved).astype(jnp.int32)
    n_shared = jnp.where(saved[lead] > 0, run[lead], 0).astype(jnp.int32)
    member = (same[lead] & (n_shared > 0)).astype(jnp.int32)
    return member, jnp.stack([n_shared, lead])


def _paged_kernel(
    *refs,  # scalar prefetch, blocks, scratch: unpacked below
    block_q: int,
    page_size: int,
    pages_per_block: int,
    n_kv: int,
    group: int,
    scale: float,
    quantized: bool,
    shared_rows: int,
):
    """One (sequence, query block): walk the row's live pages a block at a
    time. ``refs`` holds, in order: the scalar prefetch ``layer [1]``,
    ``page_table [B, max_pages]``, ``q_offset [B]``, ``kv_len [B]``; the query
    block ``[1, H, Bq, D]``; the HBM sources ``k, v`` (int8 cache: ``k, v,
    k_scales, v_scales``, ``[L, P, ...]`` each); the output block; the m / l /
    acc scratch; one ``[2, pages_per_block, ...]`` VMEM buffer per source; the
    DMA semaphores ``[2, n_sources]``.

    With ``shared_rows`` (decode, C = 1) the scalar prefetch also has
    ``member [B]`` and ``head [2]`` (``shared_head``), the blocks the stacked
    queries ``[Hkv, shared_rows, D]`` (row ``b * (shared_rows / B) + g`` is
    query head ``g`` of sequence ``b``'s group), and the scratch a second
    m / l / acc of ``shared_rows`` rows a kv head, filled by program 0, and
    one SMEM word: the buffer slot the next walk of the call starts in."""
    n_src = 4 if quantized else 2
    layer_ref, page_table_ref, q_offset_ref, kv_len_ref, *refs = refs
    if shared_rows:
        member_ref, head_ref, q_ref, qs_ref, *refs = refs
    else:
        q_ref, *refs = refs
    sources = refs[:n_src]
    o_ref, *own_state = refs[n_src:n_src + 4]
    refs = refs[n_src + 4:]
    if shared_rows:
        shared_state, slot_ref, refs = refs[:3], refs[3], refs[4:]
    buffers, sems = refs[:n_src], refs[n_src]

    b = pl.program_id(0)
    qi = pl.program_id(1)
    Bq, ppb = block_q, pages_per_block
    D = q_ref.shape[-1]
    Rh = group * Bq  # scratch rows per kv head
    T = ppb * page_size  # tokens per block
    layer = layer_ref[0]
    q_off = q_offset_ref[b]
    kv_len = kv_len_ref[b]

    def walk(row, first, n_pages, limit, causal, q_of, state, R,
             slot0=0, primed=False, then=None):
        """Online softmax of ``R`` query rows a kv head (``q_of(h)``, state in
        ``state``'s rows ``h*R .. (h+1)*R``) over table columns ``first ..
        first + n_pages`` of ``row``; positions at or beyond ``limit`` are
        masked, and with ``causal`` those after a query row's own. Block j
        lands in buffer slot ``(slot0 + j) % 2``. ``primed``: the walk before
        this one already started block 0's copies; ``then = (row, first,
        n_pages)``: the walk after this one, whose block 0 this one starts
        beside its own last block. Returns the slot that block lands in."""
        m_ref, l_ref, acc_ref = state
        n_blocks = pl.cdiv(n_pages, ppb)

        def copies(slot, block=None):
            """One block's page copies into ``slot``; ``block = (row, the
            column of its first page, the walk's last column)``, without
            which only their shapes matter (a wait counts bytes, not
            addresses)."""
            out = []
            for i in range(ppb):
                if block is None:
                    phys = 0
                else:  # a partial last block re-reads the last live page
                    phys = page_table_ref[block[0], jnp.minimum(block[1] + i, block[2])]
                out += [pltpu.make_async_copy(src.at[layer, phys], buf.at[slot, i],
                                              sems.at[slot, s])
                        for s, (src, buf) in enumerate(zip(sources, buffers))]
            return out

        def start(slot, row, first, n_pages, j=0):
            @pl.when(j * ppb < n_pages)
            def _():
                for c in copies(slot, (row, first + j * ppb, first + n_pages - 1)):
                    c.start()

        @pl.when(jnp.logical_not(primed))
        def _first():
            start(slot0, row, first, n_pages)

        if then is not None:
            @pl.when(n_blocks == 0)
            def _empty():
                start(slot0, *then)

        def block(j, carry):
            slot = (slot0 + j) % 2
            start(1 - slot, row, first, n_pages, j + 1)
            if then is not None:
                @pl.when(j + 1 == n_blocks)
                def _then():
                    start(1 - slot, *then)

            for c in copies(slot):
                c.wait()

            kv_pos = (first + j * ppb) * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (R, T), 1)
            invalid = kv_pos >= limit
            if causal:
                rows = jax.lax.broadcasted_iota(jnp.int32, (R, T), 0)
                q_pos = q_off + qi * Bq + rows % Bq
                invalid = jnp.logical_or(invalid, kv_pos > q_pos)

            for h in range(n_kv):  # static unroll over kv heads
                q_blk = q_of(h)
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
                r0 = h * R

                m_new, l_new, acc_new = _online_softmax_update(
                    q_blk, k_blk, v_blk, invalid,
                    m_ref[r0:r0 + R, :1], l_ref[r0:r0 + R, :1],
                    acc_ref[r0:r0 + R], scale, k_scale, v_scale,
                )
                m_ref[r0:r0 + R, :1] = m_new
                l_ref[r0:r0 + R, :1] = l_new
                acc_ref[r0:r0 + R] = acc_new
            return carry

        jax.lax.fori_loop(0, n_blocks, block, None)
        return (slot0 + n_blocks) % 2

    def reset(state):
        m_ref, l_ref, acc_ref = state
        m_ref[:] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

    def own_walk(row):
        """``(row, first column, pages)`` of a row's own walk: its live pages
        — below kv_len, and not wholly after this q block's last row — behind
        the shared head where it is a member."""
        q_max = q_offset_ref[row] + (qi + 1) * Bq - 1
        n_live = jnp.minimum(pl.cdiv(kv_len_ref[row], page_size), q_max // page_size + 1)
        n_live = jnp.clip(n_live, 0, page_table_ref.shape[1])
        first = jnp.where(member_ref[row] != 0, head_ref[0], 0) if shared_rows else 0
        return row, first, jnp.maximum(n_live - first, 0)

    reset(own_state)
    chain = {}
    if shared_rows:
        # the batch's shared head (``shared_head``): program 0 reads those
        # pages ONCE for every row's queries stacked, and a member's own walk
        # starts behind them from its rows of that partial result. The walks
        # of one call are a chain: each starts the first block of the next
        # beside its own last one, so only the call's first copy is uncovered
        n_shared, gp, B = head_ref[0], _round_up(group, 8), pl.num_programs(0)

        @pl.when(b == 0)
        def _call():
            slot_ref[0] = 0

        @pl.when(jnp.logical_and(b == 0, n_shared > 0))
        def _shared():
            reset(shared_state)
            slot_ref[0] = walk(
                head_ref[1], 0, n_shared, n_shared * page_size, False,
                lambda h: qs_ref[h], shared_state, shared_rows, then=own_walk(0))

        @pl.when(member_ref[b] != 0)
        def _resume():
            for h in range(n_kv):
                at = pl.ds(pl.multiple_of(h * shared_rows + b * gp, 8), gp)
                for own, shared in zip(own_state, shared_state):
                    own[h * Rh:(h + 1) * Rh] = shared[at, :][:group]

        row, first, n_pages = own_walk(jnp.minimum(b + 1, B - 1))
        chain = dict(slot0=slot_ref[0], primed=jnp.logical_or(b > 0, n_shared > 0),
                     then=(row, first, jnp.where(b + 1 < B, n_pages, 0)))

    _row, first, n_pages = own_walk(b)
    slot = walk(b, first, n_pages, kv_len, True,
                lambda h: q_ref[0, h * group:(h + 1) * group].reshape(Rh, D),
                own_state, Rh, **chain)
    if shared_rows:
        slot_ref[0] = slot

    m_scr, l_scr, acc_scr = own_state
    R = n_kv * Rh
    out = acc_scr[:R] / jnp.maximum(l_scr[:R, :1], 1e-30)
    o_ref[0] = out.reshape(n_kv * group, Bq, D).astype(o_ref.dtype)


def _paged_call(q, sources, page_table, q_offset, kv_len, layer, shared, *,
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
    # decode over more than one row: the shared-head pass, each row's group
    # of query heads padded to whole 8-row tiles of the stacked block
    gp = _round_up(group, 8)
    shared_rows = B * gp if C == 1 and B > 1 else 0

    q_t = q.transpose(0, 2, 1, 3)  # [B, H, C, D]
    q_spec = pl.BlockSpec((1, H, bq, D), lambda b, qi, *_: (b, 0, qi, 0))
    prefetch, blocks, in_specs = [layer, page_table, q_offset, kv_len], [q_t], [q_spec]
    state = [pltpu.VMEM((r_pad, 128), jnp.float32),
             pltpu.VMEM((r_pad, 128), jnp.float32),
             pltpu.VMEM((r_pad, D), jnp.float32)]
    if shared_rows:
        if shared is None:  # a decode query sees what lies below its own position
            shared = shared_head(page_table, jnp.minimum(kv_len, q_offset + 1), page_size)
        prefetch += [jnp.asarray(x, jnp.int32) for x in shared]
        stacked = jnp.pad(q.reshape(B, n_kv, group, D),
                          ((0, 0), (0, 0), (0, gp - group), (0, 0)))
        blocks.append(stacked.transpose(1, 0, 2, 3).reshape(n_kv, shared_rows, D))
        in_specs.append(pl.BlockSpec((n_kv, shared_rows, D), lambda b, qi, *_: (0, 0, 0)))
        state += [pltpu.VMEM((n_kv * shared_rows, 128), jnp.float32),
                  pltpu.VMEM((n_kv * shared_rows, 128), jnp.float32),
                  pltpu.VMEM((n_kv * shared_rows, D), jnp.float32),
                  pltpu.SMEM((1,), jnp.int32)]
    # what stands in VMEM beside the K and V buffers: the query and output
    # blocks (the pipeline keeps two of each) and the softmax state
    reserved = (2 * q.dtype.itemsize * (2 * H * max(bq, 8) * D + n_kv * shared_rows * D)
                + 4 * (r_pad + n_kv * shared_rows) * (2 * 128 + D))
    ppb = _pages_per_block(page_size, max(group * bq, shared_rows), n_kv * D,
                           k_pages.dtype.itemsize, page_table.shape[1], reserved)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, nq),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)] * len(sources),
        out_specs=pl.BlockSpec((1, H, bq, D), lambda b, qi, *_: (b, 0, qi, 0)),
        scratch_shapes=[
            *state,
            *(pltpu.VMEM((2, ppb) + src.shape[2:], src.dtype) for src in sources),
            pltpu.SemaphoreType.DMA((2, len(sources))),
        ],
    )
    kernel = functools.partial(
        _paged_kernel,
        block_q=bq, page_size=page_size, pages_per_block=ppb, n_kv=n_kv,
        group=group, scale=scale, quantized=len(sources) == 4,
        shared_rows=shared_rows,
    )
    out_t = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, C, D), q.dtype),
        interpret=interpret,
    )(*prefetch, *blocks, *sources)
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
    shared: tuple[Array, Array] | None = None,
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
        layer, shared, page_size=page_size, n_kv=n_kv, scale=scale, block_q=block_q,
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
    shared: tuple[Array, Array] | None = None,  # ``shared_head``'s, at C = 1
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
        q, (k_pages, v_pages), page_table, q_offset, kv_len, layer, shared,
        page_size=page_size, n_kv=n_kv, scale=scale, block_q=block_q,
        interpret=interpret)
