"""Pallas in-place decode KV append — the write half of the decode hot path.

The XLA alternative (``engine/kv_cache.py scatter_kv_chunk``) lowers to a
scatter that rebuilds the destination buffer: ~22 ms/step for a 1.5 GB
TinyLlama cache on v5e (builders' July 2026 measurement, not reproduced
since), both as scan xs→ys and as an in-carry scatter — XLA never does it
in place. This
kernel does: ``input_output_aliases`` pins the output to the input buffer
and each program read-modify-writes exactly ONE page, so per-step traffic is
B pages instead of the whole cache.

Mosaic constraints that shaped the design (discovered on v5e hardware,
round 4 — see git history for the failed variants):
- DMA slices must be tile-aligned in the trailing two dims: a single-token
  ``(1, hd)`` copy is rejected, a full page ``(page_size, Hkv*hd)`` is
  legal. Hence RMW of the whole page with the token row inserted by a
  masked select, not a token-granular write.
- Dynamic (scalar-prefetch-dependent) OUTPUT BlockSpec index maps compile
  but fail at runtime; manual ``make_async_copy`` into an ``ANY``-space
  aliased output works.

Grid is ``(B,)`` — one program per sequence per layer; the layer is a
scalar-prefetch operand so the kernel indexes the full-depth cache that the
model's layer scan carries (no per-layer dynamic-slice copies).

Serves decode only (C = 1). Prefill chunks keep the XLA scatter: one
full-cache copy amortized over a whole batched chunk is noise next to the
prefill matmuls.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TRASH_PAGE = 0


def _append_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32
    page_table_ref,  # [B, max_pages] int32
    pos_ref,  # [B] int32 — absolute write position (the token's position)
    n_valid_ref,  # [B] int32 — 1 = live slot, 0 = inactive (trash redirect)
    # blocks
    kv_new_ref,  # [1, 1, 2*HD] VMEM — k row ++ v row
    k_any,  # [L, P, PS, HD] ANY (aliased to output 0)
    v_any,
    o_k,  # aliased outputs (same buffers as k_any / v_any)
    o_v,
    # scratch
    k_scr,  # [PS, HD] VMEM
    v_scr,
    sems,  # DMA semaphores (4,)
    *,
    page_size: int,
):
    b = pl.program_id(0)
    pos = pos_ref[b]
    off = pos % page_size
    layer = layer_ref[0]
    valid = n_valid_ref[b] > 0
    # the table read happens BEFORE the select, so an invalid lane's pos
    # (e.g. a trash-redirected verify-step position at the slot's length
    # limit) must not index past the table row — read column 0 instead
    logical = jnp.where(valid, pos // page_size, 0)
    phys = jnp.where(valid, page_table_ref[b, logical], TRASH_PAGE)
    hd, hv = k_scr.shape[-1], v_scr.shape[-1]

    kin = pltpu.make_async_copy(k_any.at[layer, phys], k_scr, sems.at[0])
    vin = pltpu.make_async_copy(v_any.at[layer, phys], v_scr, sems.at[1])
    kin.start()
    vin.start()
    kin.wait()
    vin.wait()

    row = jax.lax.broadcasted_iota(jnp.int32, (page_size, 1), 0)
    hit = row == off
    k_scr[:] = jnp.where(hit, kv_new_ref[0, :, 0:hd], k_scr[:])
    v_scr[:] = jnp.where(hit, kv_new_ref[0, :, hd:hd + hv], v_scr[:])

    kout = pltpu.make_async_copy(k_scr, o_k.at[layer, phys], sems.at[2])
    vout = pltpu.make_async_copy(v_scr, o_v.at[layer, phys], sems.at[3])
    kout.start()
    vout.start()
    kout.wait()
    vout.wait()


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

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, 1, HD + HV), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((page_size, HD), k_pages.dtype),
            pltpu.VMEM((page_size, HV), v_pages.dtype),
            pltpu.SemaphoreType.DMA((4,)),
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
