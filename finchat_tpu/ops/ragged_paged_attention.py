"""Pallas ragged paged attention for TPU — one dispatch shape for every row.

ISSUE 10 / ROADMAP item 1, following "Ragged Paged Attention: A
High-Performance and Flexible LLM Inference Kernel for TPU" (PAPERS.md).
PR 4's ``mixed_step`` unified prefill and decode into one dispatch, but as a
PADDED ``[rows, chunk]`` buffer: every decode row paid dense compute for the
whole padded column width (two chunk buckets bounded the waste, at the price
of a row-bucket × chunk-bucket warmup matrix), and anything that was not
exactly "a chunk or a single token" — spec-decode verify blocks, decode-loop
rows, grammar-constrained picks — was demoted to the serialized split path.

Here the batch is a PACKED token buffer: each row owns a contiguous span of
``q`` tokens and carries its own descriptors —

- ``tok_row [T]``: which row each packed token belongs to (``R`` marks
  buffer padding). Rows must be packed in ascending, contiguous order.
- ``tok_pos [T]``: the token's absolute position in its sequence.
- ``page_table [R, max_pages]``: per-row physical page list (0 = trash).
- ``kv_len [R]``: valid KV length per row INCLUDING this dispatch's tokens.

A 512-token prefill chunk, a 1-token decode row, and a (1+Kd)-token spec
verify block are all just rows of different lengths in the same buffer, so
ONE compiled variant per packed-token bucket serves every feature mix — no
per-mode variants, no dense decode-row compute per padded column.

Kernel design (the Pallas path; the ``jax.lax`` reference below is the
CPU/tier-1 oracle and the serving path on non-TPU backends):

- rows are aligned to ``block_q`` (default 8, the fp32 sublane tile) inside
  the kernel wrapper — a gather/scatter of ``q``/``o`` only, O(T·H·D). On
  the MXU an 8-row tile is the minimum issue width, so a 1-token decode row
  padded to 8 sublanes costs the same MXU cycles as 1 row would: alignment
  padding is free compute, unlike the old chunk-width padding.
- grid ``(n_q_blocks, max_pages)`` with the page axis innermost; each
  q block belongs to exactly ONE row (alignment guarantees it), resolved at
  DMA time from the scalar-prefetched ``blk_row`` map, so the online-softmax
  scratch carries across the row's pages from one grid step to the next.
  (ops/paged_attention.py walked its pages the same way until PR 25 gave it
  a loop as long as the row over blocks of several pages; this kernel keeps
  the table-wide grid until a benchmark cell runs it inside a window.)
- K/V pages resolve through the per-row page table at DMA time
  (PrefetchScalarGridSpec); pages past ``kv_len`` or entirely in the causal
  future of the block redirect to the trash page and are skipped by the
  pipeline (consecutive identical block indices are not re-fetched).
- GQA: all KV heads in one program (static unroll), same as the paged
  kernel — a per-head grid axis multiplied the ~1 µs/iteration grid cost.
- int8-KV variant dequantizes per-token-per-head scale rows in VMEM, so the
  ragged kernel slots into the existing on-chip parity matrix (PARITY.md).

Cache layout and the full-depth ``layer`` scalar-prefetch contract are
identical to ops/paged_attention.py (the cache rides the model's layer scan
as a carry).

Bounded-KV serving (ISSUE 15, SnapStream-style sink+window): the per-row
page indirection is exactly what makes page-granular eviction free — an
evicted page just leaves the row's page list and the survivors pack the
front. The wrappers accept a per-row ``kv_gap`` (evicted-token count, the
``kv_window_start`` offset) and shift masking into compacted coordinates
(:func:`_compact_window`) while positions/rotary stay absolute upstream;
the kernel bodies are gap-oblivious.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from finchat_tpu.ops.flash_attention import NEG_INF, _online_softmax_update, _round_up
from finchat_tpu.ops.paged_attention import _key_lanes, _pair_queries

TRASH_PAGE = 0


def _compact_window(tok_row, tok_pos, kv_len, kv_gap, R: int):
    """Bounded-KV coordinate shift (SnapStream sink+window serving —
    ISSUE 15): ``kv_gap[r]`` tokens of row ``r`` were evicted between the
    pinned sink pages and the surviving window, and the row's page table
    already walks only the SURVIVORS (an evicted page just left the list).
    Masking and page-bound math therefore run in COMPACTED coordinates —
    query positions and kv lengths shift down by the row's gap — while the
    caller's rotary positions stay absolute (keys keep their original RoPE,
    so relative distances to surviving tokens are exact).

    Compacted-coordinate causality is exact for the surviving set: every
    live query sits past the whole evicted region, so ``c_kv <= c_q`` iff
    ``abs_kv <= abs_q`` for sink tokens (unshifted, below the gap) and
    window tokens (shifted by the same gap) alike. ``kv_gap=None`` (or all
    zeros) is the identity — the unbounded paths are bit-unchanged."""
    if kv_gap is None:
        return tok_pos, kv_len
    gap = jnp.asarray(kv_gap, jnp.int32)
    safe = jnp.minimum(jnp.asarray(tok_row, jnp.int32), R - 1)
    # the clamp guards padding tokens (tok_pos 0); real tokens of a gapped
    # row always sit past the evicted region (the scheduler's invariant)
    tok_pos = jnp.maximum(jnp.asarray(tok_pos, jnp.int32) - gap[safe], 0)
    kv_len = jnp.maximum(jnp.asarray(kv_len, jnp.int32) - gap, 0)
    return tok_pos, kv_len


def ragged_paged_attention_ref(
    q: Array,  # [T, H, D] packed query tokens
    k_pages: Array,  # [L, P, page_size, Hkv*D] full-depth cache (or int8)
    v_pages: Array,
    page_table: Array,  # [R, max_pages] int32 per-row physical pages
    tok_row: Array,  # [T] int32 — owning row per packed token (R = padding)
    tok_pos: Array,  # [T] int32 — absolute position per packed token
    kv_len: Array,  # [R] int32 — valid KV per row incl. this dispatch's tokens
    layer: Array,  # [1] int32
    *,
    page_size: int,
    n_kv: int,
    scale: float | None = None,
    k_scales: Array | None = None,  # int8 cache: [L, P, SPAD, page_size] fp32
    v_scales: Array | None = None,
    kv_gap: Array | None = None,  # [R] int32 — bounded-KV window offset
    window: int = 0,  # > 0: a sliding-window layer (``mha_reference``)
    sink: Array | None = None,  # [H] float32: the layer's softmax sink (``mha_reference``)
) -> Array:
    """``jax.lax`` reference for the ragged kernel — the correctness oracle
    AND the CPU/tier-1 serving path (ops/dispatch.py backend "ref").

    Deliberately computed as per-token calls into the SAME ``gather_kv`` +
    ``mha_reference`` math the split-path reference backend uses (each
    packed token is one batch element with ``Sq = 1``): at fp32 a ragged
    dispatch is bitwise the split path's math per token, which is what the
    mixed-vs-split byte-identity tests (tests/test_mixed_step.py) lean on.
    Padding tokens (``tok_row == R``) read the trash row with ``kv_len 0``
    and produce zeros, exactly like an inactive decode slot.

    ``kv_gap`` (bounded KV, ISSUE 15 — see :func:`_compact_window`) is the
    per-row count of evicted tokens: the gather below already walks only
    the surviving pages (eviction compacted the page list), so the only
    change is the coordinate shift; None/zeros is bit-identical to the
    unbounded path.
    """
    from finchat_tpu.engine.kv_cache import gather_kv_any
    from finchat_tpu.ops.refs import mha_reference

    T = q.shape[0]
    R, MP = page_table.shape
    tok_pos, kv_len = _compact_window(tok_row, tok_pos, kv_len, kv_gap, R)
    lay = jnp.asarray(layer, jnp.int32).reshape(())
    # row R = an all-trash row with kv_len 0 (the padding-token row)
    pt_pad = jnp.concatenate(
        [jnp.asarray(page_table, jnp.int32), jnp.zeros((1, MP), jnp.int32)]
    )
    kv_pad = jnp.concatenate(
        [jnp.asarray(kv_len, jnp.int32), jnp.zeros((1,), jnp.int32)]
    )
    row = jnp.minimum(jnp.asarray(tok_row, jnp.int32), R)
    pt_tok = pt_pad[row]  # [T, MP] — per-token page row
    kv_tok = kv_pad[row]  # [T]
    k_all, v_all = gather_kv_any(
        k_pages, v_pages, k_scales, v_scales, pt_tok, page_size, lay, n_kv,
        dtype=q.dtype,
    )  # [T, MP*page_size, Hkv, hd]
    out = mha_reference(
        q[:, None], k_all, v_all, causal=True,
        q_offset=jnp.asarray(tok_pos, jnp.int32), kv_len=kv_tok, scale=scale, window=window,
        sink=sink,
    )  # [T, 1, H, D]
    return out[:, 0]


def _ragged_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32
    page_table_ref,  # [R+1, max_pages] int32 in SMEM (row R = trash)
    blk_row_ref,  # [NB] int32 — owning row per aligned q block (R = padding)
    aln_start_ref,  # [R+1] int32 — row's first aligned token index
    pos0_ref,  # [R+1] int32 — absolute position of the row's first q token
    qlen_ref,  # [R+1] int32 — real q tokens in the row
    kvlen_ref,  # [R+1] int32
    # blocks
    q_ref,  # [H, Bq, D]
    k_ref,  # [1, 1, page_size, Hkv*D] — one physical page
    v_ref,
    *refs,  # (with ``sink``: [Rpad, 128] fp32, a state row's sink logit on every lane)
    block_q: int,
    page_size: int,
    n_kv: int,
    group: int,
    scale: float,
    window: int = 0,
    key_width: int = 0,
    sink: bool = False,
):
    """``refs``: the output block ``o_ref`` [H, Bq, Dv] and the scratch
    ``m_scr``, ``l_scr`` [Rpad, 128], ``acc_scr`` [Rpad, Dv] fp32. With
    ``key_width`` (keys wider than values, ops/paged_attention.py
    ``_key_lanes`` / ``_pair_queries``) the query block is ``Dk`` lanes wide,
    a head's K block the two lane tiles that hold its keys; with ``sink`` a
    block's m / l start at (the sink, 1)."""
    if sink:
        sink_ref, *refs = refs
    o_ref, m_scr, l_scr, acc_scr = refs
    j = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)

    Bq = block_q
    D = o_ref.shape[-1]
    Dk = q_ref.shape[-1]
    Rh = group * Bq  # scratch rows per kv head
    r = blk_row_ref[j]
    pos0 = pos0_ref[r]
    a0 = aln_start_ref[r]
    q_len = qlen_ref[r]
    kv_len = kvlen_ref[r]

    @pl.when(p == 0)
    def _init():
        if sink:  # the sink is in the sum before any key: exp(sink - sink) = 1
            m_scr[:] = sink_ref[:]
            l_scr[:] = jnp.ones(l_scr.shape, jnp.float32)
        else:
            m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
            l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    page_start = p * page_size
    # last VALID q position is pos0 + q_len - 1; the block-level bound uses
    # the unclamped block end (an over-fetch of at most one page for the
    # alignment-padding rows — masked in compute, never wrong)
    q_max = pos0 + (j * Bq + Bq - 1 - a0)
    needed = jnp.logical_and(page_start < kv_len, page_start <= q_max)

    @pl.when(needed)
    def _accumulate():
        rows = jax.lax.broadcasted_iota(jnp.int32, (Rh, page_size), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (Rh, page_size), 1)
        qi = j * Bq - a0 + rows % Bq  # token index WITHIN the row
        q_pos = pos0 + qi
        kv_pos = page_start + cols
        invalid = (kv_pos >= kv_len) | (kv_pos > q_pos) | (qi >= q_len)
        if window:  # a sliding-window layer: itself and the window - 1 before it
            invalid = invalid | (kv_pos <= q_pos - window)

        for h in range(n_kv):  # static unroll over kv heads
            q_blk = q_ref[h * group:(h + 1) * group].reshape(Rh, Dk)
            k0 = _key_lanes(h, key_width, Dk) if key_width else h * D
            k_blk = k_ref[0, 0, :, k0:k0 + Dk]  # [PS, Dk] value slice
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
        # fully-masked rows (alignment padding, padding blocks) have l = 0
        # and finalize to exact zeros — discarded by the wrapper's gather
        out = acc_scr[:R] / jnp.maximum(l_scr[:R, :1], 1e-30)
        o_ref[...] = out.reshape(n_kv * group, Bq, D).astype(o_ref.dtype)


def _ragged_kernel_q8(
    # scalar prefetch
    layer_ref,
    page_table_ref,
    blk_row_ref,
    aln_start_ref,
    pos0_ref,
    qlen_ref,
    kvlen_ref,
    # blocks
    q_ref,  # [H, Bq, D]
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
    """Int8-KV variant: identical control flow; K/V tiles dequantize in
    VMEM (int8 page * per-token scale row) before the same online-softmax
    update — the ragged kernel joins the on-chip parity matrix (PARITY.md)
    at both cache dtypes."""
    j = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)

    Bq = block_q
    D = q_ref.shape[-1]
    Rh = group * Bq
    r = blk_row_ref[j]
    pos0 = pos0_ref[r]
    a0 = aln_start_ref[r]
    q_len = qlen_ref[r]
    kv_len = kvlen_ref[r]

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    page_start = p * page_size
    q_max = pos0 + (j * Bq + Bq - 1 - a0)
    needed = jnp.logical_and(page_start < kv_len, page_start <= q_max)

    @pl.when(needed)
    def _accumulate():
        rows = jax.lax.broadcasted_iota(jnp.int32, (Rh, page_size), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (Rh, page_size), 1)
        qi = j * Bq - a0 + rows % Bq
        q_pos = pos0 + qi
        kv_pos = page_start + cols
        invalid = (kv_pos >= kv_len) | (kv_pos > q_pos) | (qi >= q_len)

        for h in range(n_kv):  # static unroll over kv heads
            q_blk = q_ref[h * group:(h + 1) * group].reshape(Rh, D)
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
        o_ref[...] = out.reshape(n_kv * group, Bq, D).astype(o_ref.dtype)


def _aligned_layout(tok_row, tok_pos, T: int, R: int, block_q: int):
    """Device-side packed→aligned layout: per-row lengths from the token→row
    map, rows padded up to ``block_q`` alignment (so every aligned block
    belongs to exactly one row), and the token scatter/gather index.

    Returns ``(dest [T], blk_row [NB], aln_start [R+1], pos0 [R+1],
    q_len [R+1], NB, TALN)`` — all int32; rows ``R`` entries are the
    padding row (0 tokens). Requires packed tokens sorted by row
    (contiguous spans, ascending) — the engine packs them that way.
    """
    tok_row = jnp.asarray(tok_row, jnp.int32)
    tok_pos = jnp.asarray(tok_pos, jnp.int32)
    TALN = _round_up(T + R * (block_q - 1), block_q)
    NB = TALN // block_q
    valid = tok_row < R
    seg = jnp.where(valid, tok_row, R)
    q_len = jax.ops.segment_sum(
        valid.astype(jnp.int32), seg, num_segments=R + 1
    ).astype(jnp.int32)
    q_len = q_len.at[R].set(0)
    q_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(q_len[:R], dtype=jnp.int32)]
    )  # [R+1] exclusive
    aln_len = -(-q_len // block_q) * block_q
    aln_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(aln_len[:R], dtype=jnp.int32)]
    )  # [R+1]
    tok_idx = jnp.arange(T, dtype=jnp.int32)
    dest = jnp.where(
        valid, aln_start[seg] + (tok_idx - q_start[seg]), TALN
    )  # TALN = dropped by mode="drop"
    blk_row = jnp.full((NB,), R, jnp.int32).at[dest // block_q].set(
        seg, mode="drop"
    )
    # absolute position of each row's first q token (0 for empty rows —
    # their kv_len/q_len of 0 masks everything anyway)
    is_first = (tok_idx == q_start[seg]) & valid
    pos0 = jnp.zeros((R + 1,), jnp.int32).at[
        jnp.where(is_first, seg, R + 1)
    ].set(tok_pos, mode="drop")
    return dest, blk_row, aln_start, pos0, q_len, NB, TALN


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "n_kv", "scale", "block_q", "interpret", "window"),
)
def ragged_flash_attention(  # finchat-lint: hot
    q: Array,  # [T, H, D] packed
    k_pages: Array,  # [L, P, page_size, Hkv*D]
    v_pages: Array,
    page_table: Array,  # [R, max_pages]
    tok_row: Array,  # [T]
    tok_pos: Array,  # [T]
    kv_len: Array,  # [R]
    layer: Array,  # [1]
    *,
    page_size: int,
    n_kv: int,
    scale: float | None = None,
    block_q: int = 8,
    interpret: bool = False,
    kv_gap: Array | None = None,  # [R] int32 — bounded-KV window offset
    window: int = 0,  # > 0: a sliding-window layer's mask beside the causal one
    sink: Array | None = None,  # [H] float32: the layer's softmax sink
) -> Array:
    """Ragged paged attention over the native-dtype cache; returns
    [T, H, Dv] (``Dv`` the values' width: the V array's, which need not be
    the keys'). Same descriptor contract as ``ragged_paged_attention_ref``
    (the oracle tests pin them against each other). ``kv_gap`` shifts a
    bounded row into compacted coordinates at the wrapper level
    (:func:`_compact_window`) — the kernel body is gap-oblivious: its
    page-bound and causal masks simply run on the compacted inputs."""
    T, H, Dq = q.shape
    R, max_pages = page_table.shape
    assert H % n_kv == 0, (H, n_kv)
    assert k_pages.shape[2] == page_size, (k_pages.shape, page_size)
    assert k_pages.shape[3] == n_kv * Dq, (k_pages.shape, n_kv, Dq)
    group = H // n_kv
    scale = scale if scale is not None else Dq ** -0.5
    D = v_pages.shape[3] // n_kv
    key_width = Dq if Dq != D else 0  # keys wider than values: see the kernel
    if key_width:
        q = _pair_queries(q, n_kv)
    Dk = q.shape[-1]
    tok_pos, kv_len = _compact_window(tok_row, tok_pos, kv_len, kv_gap, R)

    layer = jnp.asarray(layer, jnp.int32)
    pt_pad = jnp.concatenate(
        [jnp.asarray(page_table, jnp.int32),
         jnp.zeros((1, max_pages), jnp.int32)]
    )
    kv_pad = jnp.concatenate(
        [jnp.asarray(kv_len, jnp.int32), jnp.zeros((1,), jnp.int32)]
    )
    dest, blk_row, aln_start, pos0, q_len, NB, TALN = _aligned_layout(
        tok_row, tok_pos, T, R, block_q
    )
    q_aln = jnp.zeros((TALN, H, Dk), q.dtype).at[dest].set(q, mode="drop")
    q_t = q_aln.transpose(1, 0, 2)  # [H, TALN, Dk] — head-major blocks

    r_pad = _round_up(max(H * block_q, 8), 8)

    def kv_index(j, p, layer_ref, pt_ref, blk_row_ref, aln_start_ref,
                 pos0_ref, qlen_ref, kvlen_ref):
        r = blk_row_ref[j]
        page_start = p * page_size
        q_max = pos0_ref[r] + (j + 1) * block_q - 1 - aln_start_ref[r]
        needed = jnp.logical_and(page_start < kvlen_ref[r],
                                 page_start <= q_max)
        phys = jnp.where(needed, pt_ref[r, p], TRASH_PAGE)
        return (layer_ref[0], phys, 0, 0)

    extra, extra_specs = [], []
    if sink is not None:  # a state row's sink on every lane: row (head, query) as the acc's
        rows = jnp.repeat(jnp.asarray(sink, jnp.float32), block_q)
        extra.append(jnp.broadcast_to(
            jnp.pad(rows, (0, r_pad - rows.shape[0]))[:, None], (r_pad, 128)))
        extra_specs.append(pl.BlockSpec((r_pad, 128), lambda j, p, *_: (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(NB, max_pages),
        in_specs=[
            pl.BlockSpec((H, block_q, Dk), lambda j, p, *_: (0, j, 0)),
            pl.BlockSpec((1, 1, page_size, n_kv * Dq), kv_index),
            pl.BlockSpec((1, 1, page_size, n_kv * D), kv_index),
            *extra_specs,
        ],
        out_specs=pl.BlockSpec((H, block_q, D), lambda j, p, *_: (0, j, 0)),
        scratch_shapes=[
            pltpu.VMEM((r_pad, 128), jnp.float32),
            pltpu.VMEM((r_pad, 128), jnp.float32),
            pltpu.VMEM((r_pad, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _ragged_kernel,
        block_q=block_q, page_size=page_size, n_kv=n_kv, group=group,
        scale=scale, window=window,
        **({"key_width": key_width} if key_width else {}),
        **({"sink": True} if sink is not None else {}),
    )
    o_t = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, TALN, D), q.dtype),
        interpret=interpret,
    )(layer, pt_pad, blk_row, aln_start, pos0, q_len, kv_pad, q_t,
      k_pages, v_pages, *extra)
    o_aln = o_t.transpose(1, 0, 2)  # [TALN, H, D]
    return jnp.take(o_aln, jnp.minimum(dest, TALN - 1), axis=0)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "n_kv", "scale", "block_q", "interpret"),
)
def ragged_flash_attention_q8(  # finchat-lint: hot
    q: Array,  # [T, H, D] packed
    k_pages: Array,  # [L, P, page_size, Hkv*D] int8
    v_pages: Array,
    k_scales: Array,  # [L, P, SPAD, page_size] fp32
    v_scales: Array,
    page_table: Array,
    tok_row: Array,
    tok_pos: Array,
    kv_len: Array,
    layer: Array,
    *,
    page_size: int,
    n_kv: int,
    scale: float | None = None,
    block_q: int = 8,
    interpret: bool = False,
    kv_gap: Array | None = None,  # [R] int32 — bounded-KV window offset
) -> Array:
    """Int8-KV ragged paged attention; same contract as
    ``ragged_flash_attention`` with the scale arrays riding the same
    scalar-prefetched page indirection (and the same wrapper-level
    bounded-KV coordinate shift)."""
    T, H, D = q.shape
    R, max_pages = page_table.shape
    assert H % n_kv == 0, (H, n_kv)
    assert k_pages.shape[2] == page_size, (k_pages.shape, page_size)
    assert k_pages.shape[3] == n_kv * D, (k_pages.shape, n_kv, D)
    assert k_scales.shape[3] == page_size, (k_scales.shape, page_size)
    group = H // n_kv
    scale = scale if scale is not None else D ** -0.5
    tok_pos, kv_len = _compact_window(tok_row, tok_pos, kv_len, kv_gap, R)
    spad = k_scales.shape[2]

    layer = jnp.asarray(layer, jnp.int32)
    pt_pad = jnp.concatenate(
        [jnp.asarray(page_table, jnp.int32),
         jnp.zeros((1, max_pages), jnp.int32)]
    )
    kv_pad = jnp.concatenate(
        [jnp.asarray(kv_len, jnp.int32), jnp.zeros((1,), jnp.int32)]
    )
    dest, blk_row, aln_start, pos0, q_len, NB, TALN = _aligned_layout(
        tok_row, tok_pos, T, R, block_q
    )
    q_aln = jnp.zeros((TALN, H, D), q.dtype).at[dest].set(q, mode="drop")
    q_t = q_aln.transpose(1, 0, 2)

    r_pad = _round_up(max(H * block_q, 8), 8)

    def kv_index(j, p, layer_ref, pt_ref, blk_row_ref, aln_start_ref,
                 pos0_ref, qlen_ref, kvlen_ref):
        r = blk_row_ref[j]
        page_start = p * page_size
        q_max = pos0_ref[r] + (j + 1) * block_q - 1 - aln_start_ref[r]
        needed = jnp.logical_and(page_start < kvlen_ref[r],
                                 page_start <= q_max)
        phys = jnp.where(needed, pt_ref[r, p], TRASH_PAGE)
        return (layer_ref[0], phys, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(NB, max_pages),
        in_specs=[
            pl.BlockSpec((H, block_q, D), lambda j, p, *_: (0, j, 0)),
            pl.BlockSpec((1, 1, page_size, n_kv * D), kv_index),
            pl.BlockSpec((1, 1, page_size, n_kv * D), kv_index),
            pl.BlockSpec((1, 1, spad, page_size), kv_index),
            pl.BlockSpec((1, 1, spad, page_size), kv_index),
        ],
        out_specs=pl.BlockSpec((H, block_q, D), lambda j, p, *_: (0, j, 0)),
        scratch_shapes=[
            pltpu.VMEM((r_pad, 128), jnp.float32),
            pltpu.VMEM((r_pad, 128), jnp.float32),
            pltpu.VMEM((r_pad, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _ragged_kernel_q8,
        block_q=block_q, page_size=page_size, n_kv=n_kv, group=group,
        scale=scale,
    )
    o_t = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, TALN, D), q.dtype),
        interpret=interpret,
    )(layer, pt_pad, blk_row, aln_start, pos0, q_len, kv_pad, q_t,
      k_pages, v_pages, k_scales, v_scales)
    o_aln = o_t.transpose(1, 0, 2)
    return jnp.take(o_aln, jnp.minimum(dest, TALN - 1), axis=0)
