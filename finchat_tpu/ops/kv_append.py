"""Pallas in-place decode KV append — the write half of the decode hot path.

The XLA alternative (``engine/kv_cache.py scatter_kv_chunk``) lowers to a
scatter that rebuilds the destination buffer: ~22 ms/step for a 1.5 GB
TinyLlama cache on v5e (builders' July 2026 measurement, not reproduced
since), both as scan xs→ys and as an in-carry scatter — XLA never does it
in place. This kernel does: ``input_output_aliases`` pins the output to the
input buffer and a row's append read-modify-writes the SLAB that holds its
token — the dtype's packed tile, 16 token rows of bfloat16 (``slab_rows``) —
so a step's traffic is B slabs each way, not B pages and not the cache.

Mosaic constraints that shaped the design (v5e; round 4 and PR 49 — see git
history for the failed variants):
- DMA slices must be tile-aligned in the trailing two dims: a single-token
  ``(1, hd)`` copy is rejected. The pool lies ``T(8,128)(2,1)``: a bfloat16
  tile is 16 token rows, and a dynamic slice of 16 rows of the page at an
  offset that is a multiple of 16 (``pl.multiple_of``) is whole tiles — Mosaic
  takes it where it lies, at every accepted row width (512 to 3,840 columns,
  640 beside 128). Hence RMW of the slab with the token row inserted by a
  masked select: an eighth of the page's bytes each way (PR 49; until then
  the whole page moved, 2 x 640 KiB a row for 5 KiB of payload at a 1,280-wide
  row, and a program a row waited on its own page before the next row's
  started: 24-121 us a layer).
- Dynamic (scalar-prefetch-dependent) OUTPUT BlockSpec index maps compile
  but fail at runtime; manual ``make_async_copy`` into an ``ANY``-space
  aliased output works.

ONE program walks the rows (grid ``(1,)``): every row's two slab reads are
started before the first is waited, a row is patched as soon as ITS reads
stand (a DMA semaphore a row and direction) and its writes are started at
once, and all writes are waited last — a layer's append is two DMA latencies
and the slabs' bytes. Rows never share a slab but on the trash page, where
inactive rows' writes may land in any order. The layer is a scalar-prefetch
operand so the kernel indexes the full-depth cache that the model's layer scan
carries (no per-layer dynamic-slice copies).

Serves decode (C = 1) and, a call a chunk position, the speculative verify
step (``engine.py`` ``inplace_append``). Prefill chunks keep the XLA scatter:
one full-cache copy amortized over a whole batched chunk is noise next to the
prefill matmuls. The int8 form (``paged_kv_append_q8``) still moves whole
pages, a program a row: it runs in no cell.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TRASH_PAGE = 0


def slab_rows(page_size: int, itemsize: int) -> int:
    """Token rows of the SLAB a one-token append moves: the dtype's packed
    tile (32 bytes of sublanes: 16 rows of bfloat16, 8 of float32), so that a
    slab at an offset that is a multiple of its rows is whole tiles of the
    pool's layout — or the page, where a page is smaller."""
    return min(page_size, 32 // itemsize)


def _append_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32
    page_table_ref,  # [B, max_pages] int32
    pos_ref,  # [B] int32 — absolute write position (the token's position)
    n_valid_ref,  # [B] int32 — 1 = live slot, 0 = inactive (trash redirect)
    # blocks
    kv_new_ref,  # [B, 1, HD + HV] VMEM — every row's k row ++ v row
    k_any,  # [L, P, PS, HD] ANY (aliased to output 0)
    v_any,
    o_k,  # aliased outputs (same buffers as k_any / v_any)
    o_v,
    # scratch
    k_scr,  # [B, slab, HD] VMEM
    v_scr,  # [B, slab, HV]
    sems,  # DMA semaphores (2, B): a row's two reads, its two writes
    *,
    page_size: int,
):
    """ONE program walks the rows: every row's slab reads are in flight before
    the first is waited, each row is patched as its slab stands and its
    writes started at once, and the writes are waited last."""
    B, slab = k_scr.shape[:2]
    hd, hv = k_scr.shape[-1], v_scr.shape[-1]
    layer = layer_ref[0]

    def where(b):
        """``(physical page, the slab's first token row, the token's row of
        the slab)`` of row ``b``'s append."""
        pos = pos_ref[b]
        valid = n_valid_ref[b] > 0
        # the table read happens BEFORE the select, so an invalid lane's pos
        # (e.g. a trash-redirected verify-step position at the slot's length
        # limit) must not index past the table row — read column 0 instead
        logical = jnp.where(valid, pos // page_size, 0)
        phys = jnp.where(valid, page_table_ref[b, logical], TRASH_PAGE)
        off = pos % page_size
        t0 = pl.multiple_of(off // slab * slab, slab)
        return phys, t0, off - t0

    def slabs(b, back, phys=0, t0=0):
        """Row ``b``'s two slab copies, pool -> scratch or (``back``) scratch
        -> pool; a wait takes them unaddressed (it counts bytes)."""
        out = []
        for pool, aliased, scr in ((k_any, o_k, k_scr), (v_any, o_v, v_scr)):
            at = (aliased if back else pool).at[layer, phys, pl.ds(t0, slab)]
            src, dst = (scr.at[b], at) if back else (at, scr.at[b])
            out.append(pltpu.make_async_copy(src, dst, sems.at[int(back), b]))
        return out

    def read(b, carry):
        phys, t0, _at = where(b)
        for c in slabs(b, False, phys, t0):
            c.start()
        return carry

    def patch(b, carry):
        for c in slabs(b, False):
            c.wait()
        phys, t0, at = where(b)
        hit = jax.lax.broadcasted_iota(jnp.int32, (slab, 1), 0) == at
        k_scr[b] = jnp.where(hit, kv_new_ref[b, :, 0:hd], k_scr[b])
        v_scr[b] = jnp.where(hit, kv_new_ref[b, :, hd:hd + hv], v_scr[b])
        for c in slabs(b, True, phys, t0):
            c.start()
        return carry

    def written(b, carry):
        for c in slabs(b, True):
            c.wait()
        return carry

    jax.lax.fori_loop(0, B, read, None)
    jax.lax.fori_loop(0, B, patch, None)
    jax.lax.fori_loop(0, B, written, None)


def _append_kernel_q8(
    # scalar prefetch
    layer_ref,  # [1] int32
    page_table_ref,  # [B, max_pages] int32
    pos_ref,  # [B] int32
    n_valid_ref,  # [B] int32
    # blocks
    kv_new_ref,  # [1, 1, 2*HD] VMEM float — k row ++ v row (unquantized)
    k_any,  # [L, P, PS, HD] int8 ANY (aliased to output 0)
    v_any,
    ks_any,  # [L, P, SPAD, PS] fp32 ANY (aliased to output 2)
    vs_any,
    o_k, o_v, o_ks, o_vs,  # aliased outputs
    # scratch
    k_scr,  # [PS, HD] int8
    v_scr,
    ks_scr,  # [SPAD, PS] fp32
    vs_scr,
    sems,  # DMA semaphores (8,)
    *,
    page_size: int,
    n_kv: int,
):
    """Quantizing decode append: RMW one data page AND its scale block per
    sequence. The new token's row is quantized per head (amax/127) INSIDE
    the kernel; existing rows are copied back bit-identical (per-token
    scales — no requantization, no drift)."""
    b = pl.program_id(0)
    pos = pos_ref[b]
    off = pos % page_size
    layer = layer_ref[0]
    valid = n_valid_ref[b] > 0
    logical = jnp.where(valid, pos // page_size, 0)  # OOB-safe for trash lanes
    phys = jnp.where(valid, page_table_ref[b, logical], TRASH_PAGE)
    hd_fused = k_scr.shape[-1]
    hd = hd_fused // n_kv

    copies_in = [
        pltpu.make_async_copy(k_any.at[layer, phys], k_scr, sems.at[0]),
        pltpu.make_async_copy(v_any.at[layer, phys], v_scr, sems.at[1]),
        pltpu.make_async_copy(ks_any.at[layer, phys], ks_scr, sems.at[2]),
        pltpu.make_async_copy(vs_any.at[layer, phys], vs_scr, sems.at[3]),
    ]
    for c in copies_in:
        c.start()
    for c in copies_in:
        c.wait()

    rows = jax.lax.broadcasted_iota(jnp.int32, (page_size, 1), 0)
    hit = rows == off  # [PS, 1]
    srows = jax.lax.broadcasted_iota(jnp.int32, ks_scr.shape, 0)
    scols = jax.lax.broadcasted_iota(jnp.int32, ks_scr.shape, 1)
    for h in range(n_kv):
        sl = slice(h * hd, (h + 1) * hd)
        for new_ref_off, scr, s_scr in ((0, k_scr, ks_scr), (hd_fused, v_scr, vs_scr)):
            row = kv_new_ref[0, :, new_ref_off + h * hd:new_ref_off + (h + 1) * hd]
            row32 = row.astype(jnp.float32)  # [1, hd]
            amax = jnp.max(jnp.abs(row32))
            scale = jnp.where(amax > 0, amax, 1.0) / 127.0
            q8 = jnp.clip(jnp.round(row32 / scale), -127, 127).astype(jnp.int8)
            scr[:, sl] = jnp.where(hit, q8, scr[:, sl])
            s_hit = jnp.logical_and(srows == h, scols == off)
            s_scr[:] = jnp.where(s_hit, scale, s_scr[:])

    copies_out = [
        pltpu.make_async_copy(k_scr, o_k.at[layer, phys], sems.at[4]),
        pltpu.make_async_copy(v_scr, o_v.at[layer, phys], sems.at[5]),
        pltpu.make_async_copy(ks_scr, o_ks.at[layer, phys], sems.at[6]),
        pltpu.make_async_copy(vs_scr, o_vs.at[layer, phys], sems.at[7]),
    ]
    for c in copies_out:
        c.start()
    for c in copies_out:
        c.wait()


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "n_kv", "interpret"),
    donate_argnums=(1, 2, 3, 4),
)
def paged_kv_append_q8(
    kv_new: Array,  # [B, 1, 2*Hkv*hd] float — fused k row ++ v row
    k_pages: Array,  # [L, P, page_size, Hkv*hd] int8
    v_pages: Array,
    k_scales: Array,  # [L, P, scale_rows, page_size] fp32
    v_scales: Array,
    page_table: Array,
    pos: Array,
    n_valid: Array,
    layer: Array,
    *,
    page_size: int,
    n_kv: int,
    interpret: bool = False,
) -> tuple[Array, Array, Array, Array]:
    """Quantizing in-place append for the int8 KV cache; returns the
    (aliased) data and scale arrays."""
    B = kv_new.shape[0]
    HD = k_pages.shape[-1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, 1, 2 * HD), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((page_size, HD), k_pages.dtype),
            pltpu.VMEM((page_size, HD), k_pages.dtype),
            pltpu.VMEM(k_scales.shape[2:], jnp.float32),
            pltpu.VMEM(v_scales.shape[2:], jnp.float32),
            pltpu.SemaphoreType.DMA((8,)),
        ],
    )
    kernel = functools.partial(_append_kernel_q8, page_size=page_size, n_kv=n_kv)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
            jax.ShapeDtypeStruct(k_scales.shape, jnp.float32),
            jax.ShapeDtypeStruct(v_scales.shape, jnp.float32),
        ],
        # flattened operands: 4 scalar-prefetch, kv_new, then the 4 aliased
        input_output_aliases={5: 0, 6: 1, 7: 2, 8: 3},
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32), page_table, pos, n_valid, kv_new,
      k_pages, v_pages, k_scales, v_scales)


@functools.partial(
    jax.jit, static_argnames=("page_size", "interpret"), donate_argnums=(1, 2)
)
def paged_kv_append(
    kv_new: Array,  # [B, 1, 2*Hkv*hd] — fused k row ++ v row per sequence
    k_pages: Array,  # [L, P, page_size, Hkv*hd]
    v_pages: Array,
    page_table: Array,  # [B, max_pages] int32
    pos: Array,  # [B] int32 absolute write positions
    n_valid: Array,  # [B] int32 (0 redirects the write to the trash page)
    layer: Array,  # [1] int32
    *,
    page_size: int,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """Append one token's K/V per sequence into layer ``layer``'s pages,
    in place. Returns the (aliased) cache pair. The two arrays' rows may
    differ in width (a latent row and an index key: ``kv_new`` is the one
    beside the other)."""
    B = kv_new.shape[0]
    HD, HV = k_pages.shape[-1], v_pages.shape[-1]
    slab = slab_rows(page_size, k_pages.dtype.itemsize)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((B, 1, HD + HV), lambda i, *_: (0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, slab, HD), k_pages.dtype),
            pltpu.VMEM((B, slab, HV), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, B)),
        ],
    )
    kernel = functools.partial(_append_kernel, page_size=page_size)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # flattened operand order: 4 scalar-prefetch, kv_new, k_pages, v_pages
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32), page_table, pos, n_valid, kv_new, k_pages, v_pages)
