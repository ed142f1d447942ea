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
- ``pages_per_block`` follows from static shapes alone (``_pages_per_block``):
  512 tokens, halved while a K block is over 2 MiB (both slots of K and V
  over ``KV_BUFFER_BYTES``) or the call over the 16 MiB of scoped VMEM a
  kernel has on the v5e without asking — 4 pages of 128 at 8 or 4 KV heads,
  2 at 30. No ``vmem_limit_bytes`` is asked: at 30 heads 512-token blocks
  under a 26 MiB limit ran SLOWER than 256 (the copies alone 1,383 against
  1,337 us a call: a walk's partial last block copies its last page once
  more for every page it lacks, 1.5 pages of 0.94 MB a walk against 0.5).
- a TILE (PERF.md section 6, PR 33). A block update works on tiles of 8
  sublanes whatever they hold, and its max / exp / sum chain, its result
  pops and its three state read-modify-writes are paid a head: at 30 KV
  heads of ONE query row each (Olmo-Hybrid) that, not the copies, was the
  call's time (58 % of its stream bound where 8 heads of 4 rows read 81).
  Where a head's ``group * block_q`` rows are fewer than half a tile and
  divide it (1 or 2 rows, at decode only), ``pack = 8 // rows`` KV heads
  share ONE update (``_heads_per_tile``): the queries come BLOCK-DIAGONAL
  (``_block_diagonal``: row ``i`` of a tile holds its head's query at lanes
  ``i*hd .. (i+1)*hd`` of ``pack*hd``, zeros elsewhere), so one product
  with the tile's lanes ``buf[slot, :, :, t*pack*hd:(t+1)*pack*hd]`` of
  the K block gives each row its own head's logits (the same bf16 products
  in the same float32 sums, plus zeros), one chain and one aligned
  read-modify-write serve 8 rows, and the product with the same lanes of
  the V block leaves each row's result at its head's lanes of a
  ``pack*hd``-wide acc (the other lanes are dropped when the row is
  written out). 30 = 3 x 8 + 6: the last tile has the lanes of 6 heads and
  two rows of zeros. The weight pushes are the K and V bytes and do not
  change; the chains a block fall from 30 to 4, and the call is as long as
  its copies (88-91 % of the bound). At 4 rows a head (half a tile) a head
  keeps its own tile, as at 5 and at 8 or more: those bodies are traced
  as they were.
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
  row's partial result is whole sublane tiles of the scratch; where KV
  heads share a tile, a ``[rows * 8, pack * D]`` tile a tile of heads,
  every row real), through the
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
- a WINDOW layer's decode call (``window`` > 0, C = 1; PERF.md section 6, PR
  49) is handed its row's bounded page list — ``window / page_size + 2``
  columns in compacted coordinates, of which all but one hold live pages
  whatever the context — and no shared page, by contract. So the stacked pass
  is NOT traced (no stacked queries, no second m / l / acc: at Phi-4-flash's 32
  rows 3.8 MiB of state and a ``[256, T]`` logit tile that bounded the block);
  the chain across rows stays; and a block is the TABLE cut in the fewest
  equal parts whose double-buffered K and V fit (``_pages_per_block``'s
  ``whole_table``): the 6 columns at a 10-head cache ONE block of 7.5 MiB,
  18 columns at 4 heads two blocks of 9 pages — where blocks of 512 tokens
  cost 8 page slots for 5 live pages and 20 for 17, every row, every layer.
  A block starts and waits the copies of its LIVE pages alone (the first
  always; the others each under its own ``pl.when``): what the dead slots of
  the V buffer hold meets a probability of exactly 0 and only has to be
  finite, so program 0 zeroes that buffer once a call. Every other call is
  traced as it was.

- a LATENT cache (``paged_latent_attention``; PERF.md section 6, PR 41). Where
  a token's ONE row is key and value at once for every head (models/mla.py)
  and an indexer keeps a subset of the context, the decode call is the same
  walk with one source (one DMA stream feeds both matmuls: the value block
  is the key block's first lanes), ONE KV "head" whose rows are all the
  query heads, and the selection as a mask block beside the positions'
  mask. The shared head's stacked rows (16 x 128) take their block update a
  tile of whole sequences at a time (``LATENT_TILE_BYTES`` of logits), each
  sequence under its own mask. Blocks are ``LATENT_BLOCK_TOKENS`` (1,024):
  the call is bound by the MXU (295 kFLOP a context token a row), not by the
  copies, and at 128 query rows a block's fixed work showed (0.334 ms a
  layer at the cell's shapes at 512, 0.289 at 1,024, 0.323 at 2,048, where a
  partial last block's waste overtakes it).

- the INDEXER's scores over its key pages (``paged_index_scores``; PERF.md
  section 6, PR 43): the walk again — the copies, the chain across rows, the
  shared head once for all rows' queries stacked — with the index keys as its
  one source and, in place of a block's softmax update, ``sum_h w[h] ReLU(q[h]
  . key)`` stored at the block's columns of a ``[rows, tokens]`` float32
  output that stays in VMEM over the grid. No state to reset or resume, no
  mask, nothing written when a walk ends; blocks of ``INDEX_BLOCK_TOKENS``
  (2,048: a key page is 32 KB and a block's fixed work shows sooner than its
  partial last block's repeats). Staging the table's keys for XLA's einsum
  cost 0.15 ms a layer at the cell's shapes whatever the contexts; the walk
  0.05.

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
SUBLANES = 8  # rows of a float32 tile: the least a block update works on
BLOCK_STATE_BYTES = 12 << 20  # the most a query block's blocks and softmax state may take
# the latent form asks for more than the scoped default: 16 rows' stacked queries
# of 128 heads and their softmax state are 12 MiB before a block is copied
LATENT_VMEM_BYTES = 48 << 20
# ... and walks in longer blocks: at 128 query rows a block's fixed work (the state's
# read-modify-write, the matmuls' fill and drain) is a fifth of a 512-token block's time
LATENT_BLOCK_TOKENS = 1024
LATENT_TILE_BYTES = 1 << 20  # the float32 logit tile of the stacked rows that take one update
# the index form's block: a key page is a fifth of a latent page and a block's work three
# operations an element, so a block's fixed work (the loop, the waits, the matmul's fill)
# shows before a partial last block's repeats do: alone on a v5e at the cell's shapes 71.7
# us a layer at 512 tokens, 51.8 at 1,024, 45.9 at 2,048, 46.5 at 4,096; with every row at
# 16,384 tokens 186 / 126 / 103 / 106 (PERF.md section 6, PR 43)
INDEX_BLOCK_TOKENS = 2048


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


def _heads_per_tile(group: int, block_q: int) -> int:
    """KV heads whose query rows take ONE block update together
    (``_paged_kernel``). An update works on tiles of 8 sublanes whatever they
    hold, and at 30 heads of one row each the 30 dependent chains a block, not
    the copies, set the call's time (PERF.md section 6, PR 33): where a head's
    ``group * block_q`` rows are fewer than half a tile (1 or 2, at decode)
    and divide it, as many heads as fill it share one. Half a tile (4 rows:
    Mistral, Mixtral) stays a head a tile here, as 5 rows (they do not divide)
    and 8 or more (a tile of their own) must."""
    rows = group * block_q
    return SUBLANES // rows if 2 * rows < SUBLANES and SUBLANES % rows == 0 else 1


def _pages_per_block(page_size: int, head_rows: int, width: int, itemsize: int,
                     max_pages: int, reserved: int = 0, whole_table: bool = False) -> int:
    """Pages copied and computed together: ``BLOCK_TOKENS`` tokens, halved
    while one tile's logits (``head_rows`` query rows: a KV head's, or those
    of the heads that share a tile) or the double-buffered K and V blocks
    (``width = Hkv * hd`` elements a token) outgrow their VMEM budgets —
    their own, and what the call's query and output blocks and its softmax
    state (``reserved`` bytes) leave of the whole: at 30 KV heads a token row
    is 7.5 KiB and a 128-query prefill block's state alone 5.6 MiB; never
    under one page nor over the table. ``KV_BUFFER_BYTES`` bounds a block by
    its BYTES (2 MiB of K: 512 tokens at 8 KV heads, 256 at 30), and that is
    the better block, not only the one that fits: with more VMEM asked for
    (``vmem_limit_bytes``) 512 tokens at 30 heads ran 3.5 % slower, copies
    alone, than 256 — a walk's partial last block copies its last page again
    for every page it lacks (PERF.md section 6, PR 33).

    ``whole_table`` (a window layer's one-token call: the table is the row's
    bounded page list, live but for its last column): the table cut in the
    fewest equal parts that fit the same budgets, ``ceil(max_pages / n)`` pages
    for the smallest such ``n`` — no partial last block but the rounding's."""
    # an int8 block also stands dequantized beside its buffers, head by head
    # (float32, then the query dtype: 6 bytes an element; Mosaic keeps every
    # head's copy of the static unroll)
    token_bytes = width * (4 * itemsize + (6 if itemsize == 1 else 0))

    def over(tokens):
        return (head_rows * tokens * 4 > SCORE_TILE_BYTES
                or tokens * token_bytes > min(KV_BUFFER_BYTES, VMEM_BYTES - reserved))

    if whole_table:
        for parts in range(1, max_pages + 1):
            pages = -(-max_pages // parts)
            if pages == 1 or not over(pages * page_size):
                return pages
    tokens = BLOCK_TOKENS
    while tokens > page_size and over(tokens):
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
    pack: int,
    scale: float,
    quantized: bool,
    shared_rows: int,
    latent_rows: int = 0,
    window: int = 0,
    index_heads: int = 0,
    chained: bool = False,
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
    one SMEM word: the buffer slot the next walk of the call starts in.

    A TILE is ``pack`` KV heads (``_heads_per_tile``) whose query rows take
    ONE block update together. With ``pack`` 1 it is a KV head, as above.
    With more, the query block is ``[1, tiles, 8, pack * D]``, BLOCK-DIAGONAL
    (``_block_diagonal``): row ``i * group + g`` of a tile holds query head
    ``g`` of the tile's ``i``-th KV head at lanes ``i * D .. (i + 1) * D`` and
    zeros elsewhere, so ONE product with the tile's ``pack * D`` lanes of the
    K block gives each row its own head's logits (the zeros add nothing), one
    max / exp / sum chain and one aligned read-modify-write of m / l / acc
    serve 8 rows, and the product of the probabilities with the same lanes of
    the V block leaves each row's result at its head's lanes of a ``pack * D``
    wide acc; the other lanes hold other heads' values under this row's
    weights and are dropped at the end. The stacked queries are ``[tiles,
    shared_rows, pack * D]`` likewise, a sequence's 8 rows one tile.

    With ``latent_rows`` (``paged_latent_attention``: decode over a LATENT
    cache) there is ONE source, whose token row is key and value at once for
    every query head: the query block is ``[1, H, Dk]`` (``Dk`` the row's
    width), the value block the first ``D`` lanes of the key block that the
    one copy brought, and behind the queries comes a block ``keep [B,
    (max_pages + pages_per_block - 1) * page_size]`` int32 — the selection: a
    token whose entry is 0 is masked like one beyond ``kv_len``. The shared
    head's stacked queries are ``[1, shared_rows, Dk]`` and take the block
    update ``latent_rows`` rows at a time (whole sequences: a tile of rows
    where the other forms have a tile of heads), each sequence's rows under
    its own mask. The other forms' bodies are traced as they were.

    With ``index_heads`` (``paged_index_scores``: the indexer's scores over
    its key pages) the walk is the same — the copies, the chain, the shared
    head once for the stacked rows — and a block's work is not a softmax: the
    one source holds an index KEY a token, the query block is ``[1, Hi, Dk]``
    (``Hi = index_heads``, a multiple of 8; the stacked queries ``[1, B * Hi,
    Dk]``), behind the queries come the heads' weights ``[B * Hi, 128]``
    float32 (a row's weight on every lane), and the output block is the WHOLE
    ``[B, (max_pages + pages_per_block - 1) * page_size]`` float32 score
    array, resident over the grid: a block stores ``sum_h w[h] ReLU(q[h] .
    key)`` at its columns of its sequence's row (the stacked pass
    ``LATENT_TILE_BYTES`` of products = whole sequences at a time, at every
    sequence's row; a row that is no member walks from column 0 and
    overwrites them). There is no softmax state, no mask and nothing to write
    when a walk ends: columns no walk reaches — at or beyond ``kv_len``
    rounded up to a block, a dead row's — hold whatever the buffer held."""
    n_src = 1 if latent_rows or index_heads else 4 if quantized else 2
    layer_ref, page_table_ref, q_offset_ref, kv_len_ref, *refs = refs
    if shared_rows:
        member_ref, head_ref, q_ref, qs_ref, *refs = refs
    else:
        q_ref, *refs = refs
    if latent_rows:
        keep_ref, *refs = refs
    if index_heads:
        w_ref, *refs = refs
    sources, o_ref, refs = refs[:n_src], refs[n_src], refs[n_src + 1:]
    if not index_heads:
        own_state, refs = refs[:3], refs[3:]
    if shared_rows and not index_heads:
        shared_state, refs = refs[:3], refs[3:]
    chained = chained or bool(shared_rows)
    if chained:
        slot_ref, *refs = refs
    buffers, sems = refs[:n_src], refs[n_src]

    b = pl.program_id(0)
    qi = pl.program_id(1)
    Bq, ppb = block_q, pages_per_block
    D = o_ref.shape[-1]
    Dk = q_ref.shape[-1]  # a key row's lanes: a head's D, or the latent row
    Rt = pack * group * Bq  # scratch rows per tile
    n_tiles = pl.cdiv(n_kv, pack)
    T = ppb * page_size  # tokens per block
    live_only = bool(window) and chained  # a window's one-token walk copies LIVE pages alone
    layer = layer_ref[0]
    q_off = q_offset_ref[b]
    kv_len = kv_len_ref[b]

    def walk(row, first, n_pages, update, slot0=0, primed=False, then=None):
        """Table columns ``first .. first + n_pages`` of ``row``, a block at
        a time: ``update(slot, first, j)`` works on block j once it stands in
        buffer slot ``slot = (slot0 + j) % 2``. ``primed``: the walk before
        this one already started block 0's copies; ``then = (row, first,
        n_pages)``: the walk after this one, whose block 0 this one starts
        beside its own last block. Returns the slot that block lands in."""
        n_blocks = pl.cdiv(n_pages, ppb)

        def copies(slot, block=None):
            """One block's page copies into ``slot``, a list a page; ``block
            = (row, the column of its first page, the walk's last column)``,
            without which only their shapes matter (a wait counts bytes, not
            addresses)."""
            out = []
            for i in range(ppb):
                if block is None:
                    phys = 0
                else:  # a partial last block re-reads the last live page
                    phys = page_table_ref[block[0], jnp.minimum(block[1] + i, block[2])]
                out.append([pltpu.make_async_copy(src.at[layer, phys], buf.at[slot, i],
                                                  sems.at[slot, s])
                            for s, (src, buf) in enumerate(zip(sources, buffers))])
            return out

        def each(slot, act, block=None, addressed=True):
            """``act`` on every copy of a block into ``slot``; ``live_only``:
            on those of its live pages alone (``block`` says which, also for
            a wait, whose copies are not ``addressed``)."""
            for i, page in enumerate(copies(slot, block if addressed else None)):
                if live_only and i:
                    @pl.when(block[1] + i <= block[2])
                    def _(page=page):
                        for c in page:
                            act(c)
                else:
                    for c in page:
                        act(c)

        def start(slot, row, first, n_pages, j=0):
            @pl.when(j * ppb < n_pages)
            def _():
                each(slot, lambda c: c.start(), (row, first + j * ppb, first + n_pages - 1))

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

            live = (row, first + j * ppb, first + n_pages - 1) if live_only else None
            each(slot, lambda c: c.wait(), live, addressed=False)
            update(slot, first, j)
            return carry

        jax.lax.fori_loop(0, n_blocks, block, None)
        return (slot0 + n_blocks) % 2

    def softmax(limit, causal, q_of, state, R, tiles=n_tiles, keep_of=None):
        """A walk's ``update``: the online softmax of ``R`` query rows a tile
        (``q_of(t)``, state in ``state``'s rows ``t*R .. (t+1)*R``) over a
        block; positions at or beyond ``limit`` are masked, and with
        ``causal`` those after a query row's own, and with ``keep_of`` (the
        latent form) those where ``keep_of(t, the block's first column)`` [R
        or 1, T] is 0."""
        m_ref, l_ref, acc_ref = state

        def update(slot, first, j):
            # (the latent form's rows of a walk share their positions' mask: one row of it)
            kv_pos = (first + j * ppb) * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (1 if latent_rows else R, T), 1)
            invalid = kv_pos >= limit
            if causal:
                rows = jax.lax.broadcasted_iota(jnp.int32, (R, T), 0)
                q_pos = q_off + qi * Bq + rows % Bq
                invalid = jnp.logical_or(invalid, kv_pos > q_pos)
                if window:  # a query sees itself and the window - 1 before it
                    invalid = jnp.logical_or(invalid, kv_pos <= q_pos - window)

            for t in range(tiles):  # static unroll over tiles of kv heads
                h0 = t * pack
                W = D if latent_rows else (min(h0 + pack, n_kv) - h0) * D  # the tile's lanes
                q_blk = q_of(t, W)
                masked = invalid
                if latent_rows:  # one row a token: the key, its first lanes the value
                    k_blk = buffers[0][slot].reshape(T, Dk)
                    v_blk = k_blk[:, :D]
                    masked = jnp.logical_or(invalid, keep_of(t, first + j * ppb) == 0)
                else:
                    k_blk = buffers[0][slot, :, :, h0 * D:h0 * D + W].reshape(T, W)
                    v_blk = buffers[1][slot, :, :, h0 * D:h0 * D + W].reshape(T, W)
                k_scale = v_scale = None
                if quantized:  # int8 is exact in the query dtype
                    k_blk = k_blk.astype(jnp.float32).astype(q_blk.dtype)
                    v_blk = v_blk.astype(jnp.float32).astype(q_blk.dtype)
                    k_scale, v_scale = (
                        jnp.concatenate([buf[slot, i, h0:h0 + pack, :] for i in range(ppb)],
                                        axis=1)  # [pack, T] per-token scales
                        for buf in buffers[2:])
                    if pack > 1:  # a row of scales a query row of the tile
                        k_scale, v_scale = (
                            own_head(R, T, lambda i, s=s: s[i:i + 1, :])
                            for s in (k_scale, v_scale))
                r0 = t * R

                m_new, l_new, acc_new = _online_softmax_update(
                    q_blk, k_blk, v_blk, masked,
                    m_ref[r0:r0 + R, :1], l_ref[r0:r0 + R, :1],
                    acc_ref[r0:r0 + R, :W], scale, k_scale, v_scale,
                )
                m_ref[r0:r0 + R, :1] = m_new
                l_ref[r0:r0 + R, :1] = l_new
                acc_ref[r0:r0 + R, :W] = acc_new

        return update

    def scores(tile_of, R, tiles=1):
        """A walk's ``update`` in the index form: ``sum_h w[h] ReLU(q[h] .
        key)`` of a block's keys for ``R // index_heads`` sequences a tile —
        ``tile_of(t)``: their queries [R, Dk], the heads' weights [R, 1]
        float32, their rows of the output — stored at the block's columns of
        those rows. Float32 products' sums, float32 weights, a float32 sum
        over the heads."""
        def update(slot, first, j):
            keys = buffers[0][slot].reshape(T, Dk)
            at = pl.ds(pl.multiple_of((first + j * ppb) * page_size, page_size), T)
            for t in range(tiles):
                q, w, rows = tile_of(t)
                s = jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)  # [R, T]
                s = jnp.maximum(s, 0.0) * w
                o_ref[rows, at] = jnp.sum(s.reshape(R // index_heads, index_heads, T), axis=1)

        return update

    def own_head(rows, cols, piece):
        """``[rows, cols]`` float32 in which each query row of a tile (a
        sequence's ``Rt`` rows one tile, each head's rows one after another)
        has ``piece(i)`` of its own head, the tile's ``i``-th."""
        row_head = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) % Rt // (Rt // pack)
        out = jnp.zeros((rows, cols), jnp.float32)
        for i in range(pack):
            out = jnp.where(row_head == i, piece(i), out)
        return out

    def keep_block(col, seq):
        """The selection's entries of sequence ``seq`` for the block whose
        first table column is ``col``: [1, T] int32."""
        return keep_ref[pl.ds(seq, 1), pl.ds(pl.multiple_of(col * page_size, page_size), T)]

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

    if not index_heads:
        reset(own_state)
    if live_only:  # what a dead slot of the V buffer holds meets a probability of 0: finite
        @pl.when(b == 0)
        def _finite():
            buffers[1][...] = jnp.zeros(buffers[1].shape, buffers[1].dtype)

    chain = {}
    if chained:
        # The walks of one call are a chain: each starts the first block of
        # the next beside its own last one, so only the call's first copy is
        # uncovered. With ``shared_rows`` also the batch's shared head
        # (``shared_head``): program 0 reads those pages ONCE for every row's
        # queries stacked, and a member's own walk starts behind them from its
        # rows of that partial result
        n_shared = head_ref[0] if shared_rows else 0
        gp, B = _round_up(pack * group, 8), pl.num_programs(0)

        @pl.when(b == 0)
        def _call():
            slot_ref[0] = 0

    if shared_rows:
        stacked = {}
        if latent_rows:  # a tile is `latent_rows` stacked rows: whole sequences
            def stacked_keep(t, col):
                return jnp.concatenate(
                    [jnp.broadcast_to(keep_block(col, t * (latent_rows // gp) + i), (gp, T))
                     for i in range(latent_rows // gp)], axis=0)

            stacked = dict(tiles=shared_rows // latent_rows, keep_of=stacked_keep)

        @pl.when(jnp.logical_and(b == 0, n_shared > 0))
        def _shared():
            if index_heads:
                # every sequence's row of the head's columns, as many sequences a
                # tile as keep its float32 products in their budget and divide the batch
                seqs = shared_rows // index_heads
                per = max(d for d in range(1, seqs + 1) if seqs % d == 0 and (
                    d == 1 or d * index_heads * T * 4 <= LATENT_TILE_BYTES))

                def tile(t):
                    rows = slice(t * per * index_heads, (t + 1) * per * index_heads)
                    return qs_ref[0, rows, :], w_ref[rows, :1], slice(t * per, (t + 1) * per)

                slot_ref[0] = walk(head_ref[1], 0, n_shared,
                                   scores(tile, per * index_heads, seqs // per),
                                   then=own_walk(0))
                return
            reset(shared_state)
            if latent_rows:
                def stacked_q(t, W):
                    return qs_ref[0, t * latent_rows:(t + 1) * latent_rows, :]
            else:
                def stacked_q(t, W):
                    return qs_ref[t, :, :W]
            slot_ref[0] = walk(
                head_ref[1], 0, n_shared, softmax(
                    n_shared * page_size, False, stacked_q, shared_state,
                    latent_rows or shared_rows, **stacked), then=own_walk(0))

        if not index_heads:
            @pl.when(member_ref[b] != 0)
            def _resume():
                for t in range(n_tiles):
                    at = pl.ds(pl.multiple_of(t * shared_rows + b * gp, 8), gp)
                    for own, shared in zip(own_state, shared_state):
                        own[t * Rt:(t + 1) * Rt] = shared[at, :][:Rt]

    if chained:
        row, first, n_pages = own_walk(jnp.minimum(b + 1, B - 1))
        chain = dict(slot0=slot_ref[0], primed=jnp.logical_or(b > 0, n_shared > 0),
                     then=(row, first, jnp.where(b + 1 < B, n_pages, 0)))

    _row, first, n_pages = own_walk(b)
    if index_heads:  # nothing outlives a block but its scores: no state, no output to finish
        own = pl.ds(pl.multiple_of(b * index_heads, 8), index_heads)
        slot = walk(b, first, n_pages, scores(
            lambda t: (q_ref[0], w_ref[own, :1], pl.ds(b, 1)), index_heads), **chain)
        if chained:
            slot_ref[0] = slot
        return
    own_keep = {}
    if latent_rows:
        def own_q(t, W):
            return q_ref[0]

        own_keep = dict(keep_of=lambda t, col: keep_block(col, b))
    elif pack == 1:
        def own_q(t, W):
            return q_ref[0, t * group:(t + 1) * group].reshape(Rt, D)
    else:
        def own_q(t, W):
            return q_ref[0, t, :, :W]
    # (a latent row's one query stands on its last token: ``kv_len`` is its causal bound)
    slot = walk(b, first, n_pages,
                softmax(kv_len, not latent_rows, own_q, own_state, Rt, **own_keep), **chain)
    if chained:
        slot_ref[0] = slot

    m_scr, l_scr, acc_scr = own_state
    R = n_kv * group * Bq
    if pack == 1:
        out = acc_scr[:R] / jnp.maximum(l_scr[:R, :1], 1e-30)
    else:  # a row's own head is at lanes i * D .. (i + 1) * D of its acc
        full = acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)
        out = own_head(full.shape[0], D, lambda i: full[:, i * D:(i + 1) * D])[:R]
    if latent_rows:
        o_ref[0] = out.astype(o_ref.dtype)
    else:
        o_ref[0] = out.reshape(n_kv * group, Bq, D).astype(o_ref.dtype)


def _block_diagonal(q: Array, n_kv: int, pack: int) -> Array:
    """Decode queries ``[B, 1, H, D]`` as ``[B, tiles, 8, pack * D]``: a tile's
    row ``i * group + g`` is query head ``g`` of its ``i``-th KV head at lanes
    ``i * D .. (i + 1) * D``, zeros elsewhere (and in the rows of a last tile
    that has fewer heads) — see ``_paged_kernel``."""
    B, _, H, D = q.shape
    group, n_tiles = H // n_kv, -(-n_kv // pack)
    heads = jnp.pad(q.reshape(B, n_kv, group, D),
                    ((0, 0), (0, n_tiles * pack - n_kv), (0, 0), (0, 0)))
    own = jnp.eye(pack, dtype=q.dtype).reshape(1, 1, pack, 1, pack, 1)
    return (heads.reshape(B, n_tiles, pack, group, 1, D) * own).reshape(
        B, n_tiles, pack * group, pack * D)


def _fit_block(bq: int, heads: int, head_dim: int, itemsize: int) -> int:
    """The query block a chunk call may take: a FIT rule, read off the call's
    own shapes (no speed-up, no model's name). A query block's VMEM — the
    query and output blocks, twice each, and the softmax state — grows with
    heads x block, and past ``BLOCK_STATE_BYTES`` no page of K and V fits
    beside it: 40 heads of 128 at a block of 128 are 13.1 MiB and the chip's
    compiler refuses the call by 1 MiB (PR 42). Such a call takes half the
    block; 32 heads of 128 (10.5 MiB) and fewer keep theirs, so every call
    that compiled before this rule is traced as it was."""
    while bq > 8 and heads * bq * (4 * itemsize * head_dim + 4 * (256 + head_dim)) \
            > BLOCK_STATE_BYTES:
        bq //= 2
    return bq


def _paged_call(q, sources, page_table, q_offset, kv_len, layer, shared, *,
                page_size, n_kv, scale, block_q, interpret, window=0):
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
    bq = _fit_block(bq, H, D, q.dtype.itemsize)
    nq = C // bq
    # a tile: the KV heads of one block update, their lanes of K, V and acc
    pack = _heads_per_tile(group, bq)
    n_tiles, Wt = -(-n_kv // pack), pack * D
    r_pad = _round_up(max(n_tiles * pack * group * bq, 8), 8)
    # decode over more than one row: the shared-head pass, each row's query
    # heads of a tile padded to whole 8-row tiles of the stacked block
    gp = _round_up(pack * group, 8)
    # (a window's table holds no shared page: its walks are chained and start at column 0)
    chained = n_queries == 1 and B > 1
    shared_rows = B * gp if chained and not window else 0

    if pack == 1:
        q_t = q.transpose(0, 2, 1, 3)  # [B, H, C, D]
        q_spec = pl.BlockSpec((1, H, bq, D), lambda b, qi, *_: (b, 0, qi, 0))
        q_bytes = H * max(bq, 8) * D
    else:
        q_t = _block_diagonal(q, n_kv, pack)  # [B, tiles, 8, Wt]
        q_spec = pl.BlockSpec((1, n_tiles, gp, Wt), lambda b, qi, *_: (b, 0, 0, 0))
        q_bytes = n_tiles * gp * Wt
    prefetch, blocks, in_specs = [layer, page_table, q_offset, kv_len], [q_t], [q_spec]
    state = [pltpu.VMEM((r_pad, 128), jnp.float32),
             pltpu.VMEM((r_pad, 128), jnp.float32),
             pltpu.VMEM((r_pad, Wt), jnp.float32)]
    if shared_rows:
        if shared is None:  # a decode query sees what lies below its own position
            shared = shared_head(page_table, jnp.minimum(kv_len, q_offset + 1), page_size)
        prefetch += [jnp.asarray(x, jnp.int32) for x in shared]
        if pack == 1:
            stacked = jnp.pad(q.reshape(B, n_kv, group, D),
                              ((0, 0), (0, 0), (0, gp - group), (0, 0)))
        else:
            stacked = q_t
        blocks.append(stacked.transpose(1, 0, 2, 3).reshape(n_tiles, shared_rows, Wt))
        in_specs.append(pl.BlockSpec((n_tiles, shared_rows, Wt), lambda b, qi, *_: (0, 0, 0)))
        state += [pltpu.VMEM((n_tiles * shared_rows, 128), jnp.float32),
                  pltpu.VMEM((n_tiles * shared_rows, 128), jnp.float32),
                  pltpu.VMEM((n_tiles * shared_rows, Wt), jnp.float32)]
    if chained:
        state.append(pltpu.SMEM((1,), jnp.int32))
    # what stands in VMEM beside the K and V buffers: the query and output
    # blocks (the pipeline keeps two of each) and the softmax state
    reserved = (2 * q.dtype.itemsize * (q_bytes + H * max(bq, 8) * D
                                        + n_tiles * shared_rows * Wt)
                + 4 * (r_pad + n_tiles * shared_rows) * (2 * 128 + Wt))
    ppb = _pages_per_block(page_size, max(pack * group * bq, shared_rows), n_kv * D,
                           k_pages.dtype.itemsize, page_table.shape[1], reserved,
                           whole_table=chained and window > 0)

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
        group=group, pack=pack, scale=scale, quantized=len(sources) == 4,
        shared_rows=shared_rows, window=window, chained=chained,
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
    static_argnames=("page_size", "n_kv", "scale", "block_q", "interpret", "window"),
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
    window: int = 0,
) -> Array:
    """Attention over the paged KV cache; returns [B, C, H, D].

    Causal with absolute positions (query row i of batch b is at
    ``q_offset[b] + i``); sequences with ``kv_len == 0`` produce zeros.
    The current chunk's K/V must already be in the pages (the decode append
    kernel or the prefill scatter runs first). Table entries at or beyond a
    row's live page count are never read.

    ``window`` > 0 (a sliding-window layer): a query also masks the positions
    ``window`` or more before its own. The caller's table holds the window's
    pages alone, in compacted coordinates (the engine's ``win_table`` and
    ``win_gaps``), and hands in a ``shared`` of no pages: the shared head's
    pass reads whole pages for every row, which a window does not allow — so
    a window's one-token call does not trace that pass, nor read ``shared``,
    and walks its table in the fewest blocks that fit (the module's list).
    """
    return _paged_call(
        q, (k_pages, v_pages), page_table, q_offset, kv_len, layer, shared,
        page_size=page_size, n_kv=n_kv, scale=scale, block_q=block_q,
        interpret=interpret, window=window)


@functools.partial(jax.jit, static_argnames=("page_size", "value_width", "scale", "interpret"))
def paged_latent_attention(
    q: Array,  # [B, H, Dk] — one query a row, a head's lanes the latent row's
    pages: Array,  # [L, P, page_size, Dk] — a token's ONE row: key, and value in its head
    keep: Array,  # [B, max_pages * page_size] bool — the selection
    page_table: Array,  # [B, max_pages] int32
    kv_len: Array,  # [B] int32 — the row's tokens, its own included; 0: a dead row
    layer: Array,  # [1] int32
    shared: tuple[Array, Array] | None = None,  # ``shared_head``'s
    *,
    page_size: int,
    value_width: int,
    scale: float,
    interpret: bool = False,
) -> Array:
    """Decode attention over a LATENT paged cache (ops/latent_attention.py):
    every head of row ``b`` attends the tokens ``j < kv_len[b]`` with
    ``keep[b, j]``, scores against the whole token row, values its first
    ``value_width`` lanes; returns [B, H, value_width]. ``_paged_kernel``'s
    walk — whole pages double-buffered as far as the row goes, the batch's
    shared head once for all rows' queries stacked — with one source, one KV
    "head" of ``H`` query rows, and the selection as a mask block. A row
    without a kept token gives zeros."""
    B, H, Dk = q.shape
    max_pages = page_table.shape[1]
    assert pages.shape[2:] == (page_size, Dk), (pages.shape, page_size, Dk)
    page_table = jnp.asarray(page_table, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    q_offset = jnp.maximum(kv_len - 1, 0)  # a decode query stands on its row's last token
    gp = _round_up(H, SUBLANES)
    shared_rows = B * gp if B > 1 else 0
    ppb = max(1, min(LATENT_BLOCK_TOKENS // page_size, max_pages))
    # sequences whose stacked rows take one block update: as many as keep the
    # float32 logit tile in its budget, and divide the batch
    per = max((d for d in range(1, B + 1)
               if B % d == 0 and d * gp * ppb * page_size * 4 <= LATENT_TILE_BYTES), default=1)
    # (a partial last block reads on behind the table's last column)
    keep = jnp.pad(keep.astype(jnp.int32), ((0, 0), (0, (ppb - 1) * page_size)))
    prefetch = [jnp.asarray(layer, jnp.int32), page_table, q_offset, kv_len]
    blocks, in_specs = [q], [pl.BlockSpec((1, H, Dk), lambda b, qi, *_: (b, 0, 0))]
    state = [pltpu.VMEM((gp, 128), jnp.float32), pltpu.VMEM((gp, 128), jnp.float32),
             pltpu.VMEM((gp, value_width), jnp.float32)]
    if shared_rows:
        if shared is None:
            shared = shared_head(page_table, kv_len, page_size, kv_len > 0)
        prefetch += [jnp.asarray(x, jnp.int32) for x in shared]
        blocks.append(jnp.pad(q, ((0, 0), (0, gp - H), (0, 0))).reshape(1, shared_rows, Dk))
        in_specs.append(pl.BlockSpec((1, shared_rows, Dk), lambda b, qi, *_: (0, 0, 0)))
        state += [pltpu.VMEM((shared_rows, 128), jnp.float32),
                  pltpu.VMEM((shared_rows, 128), jnp.float32),
                  pltpu.VMEM((shared_rows, value_width), jnp.float32),
                  pltpu.SMEM((1,), jnp.int32)]
    blocks.append(keep)
    in_specs.append(pl.BlockSpec(keep.shape, lambda b, qi, *_: (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, 1),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, value_width), lambda b, qi, *_: (b, 0, 0)),
        scratch_shapes=[*state, pltpu.VMEM((2, ppb) + pages.shape[2:], pages.dtype),
                        pltpu.SemaphoreType.DMA((2, 1))],
    )
    kernel = functools.partial(
        _paged_kernel, block_q=1, page_size=page_size, pages_per_block=ppb, n_kv=1,
        group=H, pack=1, scale=scale, quantized=False, shared_rows=shared_rows,
        latent_rows=per * gp)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, value_width), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=LATENT_VMEM_BYTES),
        interpret=interpret,
    )(*prefetch, *blocks, pages)


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def paged_index_scores(
    idx_q: Array,  # [B, Hi, Dk] — one index query a head a row
    idx_w: Array,  # [B, Hi] float32 — the heads' weights (both scales inside)
    pages: Array,  # [L, P, page_size, Dk] — a token's index key
    page_table: Array,  # [B, max_pages] int32
    kv_len: Array,  # [B] int32 — the row's tokens, its own included; 0: a dead row
    layer: Array,  # [1] int32
    shared: tuple[Array, Array] | None = None,  # ``shared_head``'s
    *,
    page_size: int,
    interpret: bool = False,
) -> Array:
    """The indexer's scores of one query a row over the row's own pages
    (ops/latent_attention.py ``index_scores`` without the staged copy of the
    table's pages): ``[B, max_pages * page_size]`` float32, ``I[b, j] = sum_h
    w[b, h] ReLU(q[b, h] . key[b, j])`` for ``j < kv_len[b]`` — every such
    token by every head, products of the stored values summed in float32,
    then a float32 sum over the heads. COLUMNS AT OR BEYOND ``kv_len[b]``
    (all of a dead row's) HOLD ANYTHING, NaN included: the walk stops at the
    row's last live page and nothing fills what it does not reach. ``select``
    reads scores only ``where(allowed, ., -inf)``; any other reader must mask
    likewise. ``_paged_kernel``'s walk in its index form: whole pages
    double-buffered as far as the row goes, the batch's shared head scored
    once for all rows' queries stacked, the walks of the call one chain."""
    B, Hi, Dk = idx_q.shape
    max_pages = page_table.shape[1]
    assert pages.shape[2:] == (page_size, Dk), (pages.shape, page_size, Dk)
    page_table = jnp.asarray(page_table, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    q_offset = jnp.maximum(kv_len - 1, 0)  # the query stands on its row's last token
    gp = _round_up(Hi, SUBLANES)  # (a head of zeros under a weight of zero adds nothing)
    shared_rows = B * gp if B > 1 else 0
    ppb = max(1, min(INDEX_BLOCK_TOKENS // page_size, max_pages))
    q = jnp.pad(idx_q, ((0, 0), (0, gp - Hi), (0, 0)))
    weights = jnp.broadcast_to(
        jnp.pad(idx_w.astype(jnp.float32), ((0, 0), (0, gp - Hi))).reshape(B * gp, 1),
        (B * gp, 128))
    prefetch = [jnp.asarray(layer, jnp.int32), page_table, q_offset, kv_len]
    blocks, in_specs = [q], [pl.BlockSpec((1, gp, Dk), lambda b, qi, *_: (b, 0, 0))]
    scratch = []
    if shared_rows:
        if shared is None:
            shared = shared_head(page_table, kv_len, page_size, kv_len > 0)
        prefetch += [jnp.asarray(x, jnp.int32) for x in shared]
        blocks.append(q.reshape(1, shared_rows, Dk))
        in_specs.append(pl.BlockSpec((1, shared_rows, Dk), lambda b, qi, *_: (0, 0, 0)))
        scratch.append(pltpu.SMEM((1,), jnp.int32))
    blocks.append(weights)
    in_specs.append(pl.BlockSpec(weights.shape, lambda b, qi, *_: (0, 0)))
    # (a partial last block writes on behind the table's last column)
    out_shape = (B, (max_pages + ppb - 1) * page_size)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, 1),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(out_shape, lambda b, qi, *_: (0, 0)),
        scratch_shapes=[*scratch, pltpu.VMEM((2, ppb) + pages.shape[2:], pages.dtype),
                        pltpu.SemaphoreType.DMA((2, 1))],
    )
    kernel = functools.partial(
        _paged_kernel, block_q=1, page_size=page_size, pages_per_block=ppb, n_kv=1,
        group=gp, pack=1, scale=1.0, quantized=False, shared_rows=shared_rows,
        index_heads=gp)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        interpret=interpret,
    )(*prefetch, *blocks, pages)[:, :max_pages * page_size]
