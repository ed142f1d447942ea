"""Fused dequant-matmul Pallas kernels: packed int8/int4 weight reads.

The quantized serving plane (models/quant.py, PR 14) stores matmul weights
as int8 (per-output-column scales) or packed int4 nibbles (per-group
scales along K), and every matmul site dequantizes INLINE —
``x @ dequantize(w, x.dtype)``. That contract is what keeps TP decode
bit-identical to unsharded, but on its own it leaves the HBM win to XLA's
mercy: whenever the fusion breaks, the bf16 weight REMATERIALIZES and the
decode step streams full-width weights again — the weight-read share of
the step is only conditionally halved/quartered. (Whether and where the
fusion breaks on the chip is not measured.)

This module makes the packed read structural instead of incidental:

- ``quant_matmul_int8`` / ``quant_matmul_int4``: Pallas matmul kernels
  whose weight operand is the PACKED array exactly as stored — int8
  ``[K, N]`` or nibble-packed ``[K//2, N]`` — with per-channel or
  per-group fp32 scales. Dequantization (nibble unpack via the arithmetic
  ``<< 4 >> 4`` pair — the same idiom as models/quant._unpack_int4 —
  upcast, scale) happens in VMEM/registers inside the K-tile loop, so HBM
  only ever streams 1 or 0.5 bytes per weight. Accumulation is fp32
  (``preferred_element_type``), written back once per (m, n) tile.
- ``quant_matmul_ref``: the ``jax.lax`` oracle, constructed to be
  BITWISE the pre-existing inline-dequant math (literally
  ``x @ dequantize(w, x.dtype)``, or the ``preferred_element_type``
  einsum for the lm_head site). Exactly like
  ``ragged_paged_attention_ref``, the reference IS the CPU/tier-1
  serving path — routing through it must not change a single stream
  byte, and tests/test_quant_matmul.py pins that.

``ops/dispatch.py quant_matmul`` routes between them (FINCHAT_QUANT_MATMUL
env: pallas | ref | pallas-interpret), and ``models/quant.dense`` — the
one matmul entry every QTensor/Q4Tensor site in the decoder and the
quantized embed encoder goes through — calls the dispatcher.

Layout notes (why the kernel honors parallel/sharding.py's packed-K
specs): the kernel sees only the LOCAL shard — int8 ``[K_local, N_local]``
or packed ``[K_local//2, N_local]`` with the matching scale shard — and
never unpacks across the shard boundary, because the nibble pair (rows
2i, 2i+1) always lives inside one byte and byte rows shard as units.

Tiling: grid (M/bm, N/bn, K/bk) with K innermost ("arbitrary" — it
accumulates); fp32 VMEM accumulator scratch per (m, n) tile. Ragged
shapes are zero-padded in the wrapper — exact, since zero weight rows /
columns contribute zero to every output element (padded scale entries are
1.0 so no 0*inf hazards exist even in theory).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from finchat_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def quant_matmul_ref(x: Array, w, *, preferred_element_type=None) -> Array:
    """The inline-dequant oracle — bitwise the serving math this kernel
    replaces. ``w`` is a models/quant QTensor or Q4Tensor. With
    ``preferred_element_type`` the contraction is the lm_head einsum
    (fp32 logits); without it, the plain ``@`` every dense site used."""
    from finchat_tpu.models.quant import dequantize

    w_deq = dequantize(w, x.dtype)
    if preferred_element_type is None:
        return x @ w_deq
    return jnp.einsum("...k,kn->...n", x, w_deq,
                      preferred_element_type=preferred_element_type)


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def _pick_bk(K: int, g: int) -> int:
    """K-tile size honoring the scale-group layout: every K-tile must be
    a whole number of groups (bk % g == 0) or lie inside one group
    (g % bk == 0), so the in-kernel scale slice is static-shaped."""
    if g % 128 == 0 or 128 % g == 0:
        bk = 128
    else:
        bk = g  # odd group sizes: one group per tile
    return min(bk, max(g, _round_up(K, 2)))


def _qmm_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *,
                bk: int, bn: int, g: int, n_groups: int, packed: bool,
                compute_dtype):
    """One (m, n, k) grid step: unpack + dequantize the weight tile in
    VMEM, fp32-accumulate its contribution to the (m, n) output tile."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]
    if packed:
        # nibble unpack, the models/quant._unpack_int4 arithmetic: low
        # nibble = row 2i, high nibble = row 2i+1, sign via shift pairs —
        # in int32, because Mosaic legalizes no 8-bit vector shift
        q = q.astype(jnp.int32)
        lo = (q << 28) >> 28
        hi = q >> 4
        q = jnp.stack([lo, hi], axis=-2).reshape(bk, q.shape[-1])

    # per-group scales: the scale block holds ALL groups' rows for this
    # n-tile (n_groups is small — K/g); load this k-tile's rows from the
    # ref at a dynamic row offset with static shapes (the wrapper
    # guarantees bk % g == 0 or g % bk == 0). A value-level
    # ``dynamic_slice`` has no Mosaic lowering; a ``pl.ds`` ref load does.
    k_idx = pl.program_id(2)
    if bk <= g:
        # the whole tile lies inside one group
        s_rows = s_ref[pl.ds(k_idx * bk // g, 1), :]  # [1, bn]
        s_tile = jnp.broadcast_to(s_rows, (bk, bn))
    else:
        # whole groups per tile: broadcast each group row over its g rows
        npg = bk // g
        s_rows = s_ref[pl.ds(k_idx * npg, npg), :]  # [npg, bn]
        s_tile = jnp.broadcast_to(
            s_rows[:, None, :], (npg, g, bn)
        ).reshape(bk, bn)

    # in-register dequant: int values are exact in fp32; the cast to the
    # activation dtype mirrors the reference's dequantize(w, x.dtype)
    w = (q.astype(jnp.float32) * s_tile).astype(compute_dtype)
    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("packed", "group_size", "out_dtype", "interpret"),
)  # finchat-lint: hot
def _quant_matmul_2d(x: Array, q: Array, scale: Array, *, packed: bool,
                     group_size: int, out_dtype, interpret: bool) -> Array:
    """Fused dequant-matmul on flattened operands: x [M, K] @ packed
    weight (int8 [K, N] / int4 [K//2, N]) with scale [G, N]."""
    M, K = x.shape
    N = q.shape[-1]
    g = group_size
    G = scale.shape[0]
    assert K % g == 0 and G == K // g, (K, g, G)

    bm = min(128, _round_up(M, 8))
    bn = 128
    bk = _pick_bk(K, g)

    Mp, Np, Kp = _round_up(M, bm), _round_up(N, bn), _round_up(K, bk)
    Gp = Kp // g
    # scale rows pad to the sublane tile so the block load stays aligned
    Gpad = max(8, _round_up(Gp, 8))
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    if Kp != K:
        # zero weight rows are exact padding (contribute 0 per element);
        # packed rows pad at K//2 granularity (one byte = two rows)
        x = jnp.pad(x, ((0, 0), (0, Kp - K)))
        krows = (Kp - K) // 2 if packed else Kp - K
        q = jnp.pad(q, ((0, krows), (0, 0)))
    if Np != N:
        q = jnp.pad(q, ((0, 0), (0, Np - N)))
    if (Gpad, Np) != scale.shape:
        scale = jnp.pad(scale, ((0, Gpad - G), (0, Np - N)),
                        constant_values=1.0)

    kq = bk // 2 if packed else bk
    out = pl.pallas_call(
        functools.partial(
            _qmm_kernel, bk=bk, bn=bn, g=g, n_groups=Gp, packed=packed,
            compute_dtype=x.dtype,
        ),
        grid=(Mp // bm, Np // bn, Kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((kq, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((Gpad, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, q, scale)
    return out[:M, :N]


def quant_matmul_int8(x: Array, q: Array, scale: Array, *,
                      interpret: bool = False,
                      out_dtype=None) -> Array:
    """``x @ (q * scale)`` with q int8 ``[K, N]`` streamed packed and
    per-output-column fp32 ``scale [N]`` applied in-tile. ``x`` may carry
    leading batch dims; they flatten into M."""
    lead = x.shape[:-1]
    out = _quant_matmul_2d(
        x.reshape(-1, x.shape[-1]), q, scale.reshape(1, -1),
        packed=False, group_size=q.shape[0],
        out_dtype=out_dtype or x.dtype, interpret=interpret,
    )
    return out.reshape(*lead, q.shape[-1])


def quant_matmul_int4(x: Array, q: Array, scale: Array, *,
                      interpret: bool = False,
                      out_dtype=None) -> Array:
    """``x @ dequant(q, scale)`` with q nibble-packed int4 ``[K//2, N]``
    streamed AS PACKED and per-group fp32 ``scale [G, N]`` (G = 1 is
    per-channel) applied in-tile after the in-register unpack."""
    K = q.shape[0] * 2
    G = scale.shape[0]
    lead = x.shape[:-1]
    out = _quant_matmul_2d(
        x.reshape(-1, x.shape[-1]), q, scale,
        packed=True, group_size=K // G,
        out_dtype=out_dtype or x.dtype, interpret=interpret,
    )
    return out.reshape(*lead, q.shape[-1])
