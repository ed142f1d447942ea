"""Pallas flash attention (contiguous KV) for TPU.

Blocked online-softmax attention — the prefill-side hot kernel (SURVEY §7.2
step 4). Replaces the all-at-once ``mha_reference`` (ops/refs.py), which
materializes the full [B, H, Sq, Sk] logit tensor in HBM; this kernel keeps
one (block_q × block_k) logit tile in VMEM at a time, so HBM traffic is
O(Q + K + V + O) instead of O(Sq·Sk).

Semantics match ``mha_reference`` exactly (same masking, same fp32-softmax /
bf16-PV numerics):

- causal with ``q_offset``: query row i has absolute position
  ``q_offset[b] + i`` within the KV axis (chunked prefill / decode);
- ``kv_len[b]`` masks KV right-padding per batch element;
- GQA: KV heads are grouped, never materialized at H (the grid iterates KV
  heads; each program handles that head's ``group = H // Hkv`` query heads).

Layout: kernels run head-major ([B, H, S, D]) so every block's trailing two
dims are a Mosaic-tileable (rows, head_dim) tile; the public API stays
[B, S, H, D] and the wrapper transposes (XLA fuses these into neighbors).

Grid layout: ``(B, Hkv, nq, nk)`` with the KV-block axis innermost, so the
m/l/acc scratch accumulators carry across KV blocks of one (batch, kv-head,
q-block) program family. Fully-future causal blocks are compute-skipped via
``pl.when``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pick_block(size: int, preferred: int) -> int:
    """Largest power-of-two block ≤ preferred that divides size."""
    b = min(preferred, size)
    while size % b:
        b //= 2
    return max(b, 1)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _online_softmax_update(
    q_blk: Array,  # [R, D] (R = group * block_q) input dtype
    k_blk: Array,  # [Bk, D]
    v_blk: Array,  # [Bk, D]
    invalid: Array,  # [R, Bk] bool — masked-out logits
    m_prev: Array,  # [R, 1] fp32
    l_prev: Array,  # [R, 1] fp32
    acc_prev: Array,  # [R, D] fp32
    scale: float,
    k_scale: Array | None = None,  # [1, Bk] fp32 — per-token dequant scales
    v_scale: Array | None = None,
) -> tuple[Array, Array, Array]:
    """One flash-attention block update, fp32 softmax state. With
    ``k_scale`` / ``v_scale`` the block holds quantized integers (exact in
    the input dtype) and the per-token scales are applied to the logits and
    the probabilities: the same products as over a dequantized block, with
    the scale rows lying along the tile's lanes as they are stored."""
    s = jax.lax.dot_general(
        q_blk, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = s * scale if k_scale is None else s * (k_scale * scale)
    s = jnp.where(invalid, NEG_INF, s)

    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # explicit zeroing: rows whose every logit is masked have m_new = NEG_INF
    # and exp(s - m_new) = 1 there — the mask, not the exp, must decide
    p = jnp.where(invalid, 0.0, jnp.exp(s - m_new))
    correction = jnp.exp(m_prev - m_new)
    l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
    pv = p if v_scale is None else p * v_scale
    acc_new = acc_prev * correction + jax.lax.dot_general(
        pv.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def _flash_kernel(
    # scalar prefetch
    q_offset_ref,  # [B] int32 in SMEM
    kv_len_ref,  # [B] int32
    # blocks (head-major)
    q_ref,  # [1, G, Bq, D]
    k_ref,  # [1, 1, Bk, D]
    v_ref,  # [1, 1, Bk, D]
    o_ref,  # [1, G, Bq, D]
    # scratch
    m_scr,  # [Rpad, 128] fp32
    l_scr,
    acc_scr,  # [Rpad, D] fp32
    *,
    block_q: int,
    block_k: int,
    group: int,
    scale: float,
    causal: bool,
):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    Bq, Bk = block_q, block_k
    R = group * Bq  # rows = (query head within group) × (query position)
    q_off = q_offset_ref[b]
    kv_len = kv_len_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    # block-level skip: KV block entirely after this Q block's last row, or
    # entirely past the valid KV length
    q_max = q_off + (qi + 1) * Bq - 1
    k_start = ki * Bk
    needed = k_start < kv_len
    if causal:
        needed = jnp.logical_and(needed, k_start <= q_max)

    @pl.when(needed)
    def _accumulate():
        q_blk = q_ref[0].reshape(R, q_ref.shape[3])  # row r = head r//Bq, pos r%Bq
        k_blk = k_ref[0, 0]  # [Bk, D]
        v_blk = v_ref[0, 0]

        rows = jax.lax.broadcasted_iota(jnp.int32, (R, Bk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (R, Bk), 1)
        q_pos = q_off + qi * Bq + rows % Bq
        kv_pos = k_start + cols
        invalid = kv_pos >= kv_len
        if causal:
            invalid = jnp.logical_or(invalid, kv_pos > q_pos)

        m_new, l_new, acc_new = _online_softmax_update(
            q_blk, k_blk, v_blk, invalid,
            m_scr[:R, :1], l_scr[:R, :1], acc_scr[:R], scale,
        )
        m_scr[:R, :1] = m_new
        l_scr[:R, :1] = l_new
        acc_scr[:R] = acc_new

    @pl.when(ki == nk - 1)
    def _finalize():
        out = acc_scr[:R] / jnp.maximum(l_scr[:R, :1], 1e-30)
        o_ref[0] = out.reshape(group, Bq, -1).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: Array,  # [B, Sq, H, D]
    k: Array,  # [B, Sk, Hkv, D]
    v: Array,  # [B, Sk, Hkv, D]
    *,
    q_offset: Array | None = None,  # [B] int32 — abs position of q[:, 0]
    kv_len: Array | None = None,  # [B] int32 — valid KV length
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 256,
    interpret: bool = False,
) -> Array:
    """Drop-in Pallas replacement for ``ops.refs.mha_reference``."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    assert H % Hkv == 0, (H, Hkv)
    group = H // Hkv
    scale = scale if scale is not None else D ** -0.5

    if q_offset is None:
        q_offset = jnp.zeros((B,), jnp.int32)
    else:
        q_offset = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (B,))
    if kv_len is None:
        kv_len = jnp.full((B,), Sk, jnp.int32)
    else:
        kv_len = jnp.asarray(kv_len, jnp.int32)

    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Sk, block_k)
    nq, nk = Sq // bq, Sk // bk
    r_pad = _round_up(max(group * bq, 8), 8)

    # head-major layouts for Mosaic-aligned trailing dims
    q_t = q.transpose(0, 2, 1, 3)  # [B, H, Sq, D]
    k_t = k.transpose(0, 2, 1, 3)  # [B, Hkv, Sk, D]
    v_t = v.transpose(0, 2, 1, 3)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, group, bq, D), lambda b, h, qi, ki, *_: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, qi, ki, *_: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, qi, ki, *_: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, group, bq, D), lambda b, h, qi, ki, *_: (b, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((r_pad, 128), jnp.float32),
            pltpu.VMEM((r_pad, 128), jnp.float32),
            pltpu.VMEM((r_pad, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _flash_kernel,
        block_q=bq, block_k=bk, group=group, scale=scale, causal=causal,
    )
    out_t = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        interpret=interpret,
    )(q_offset, kv_len, q_t, k_t, v_t)
    return out_t.transpose(0, 2, 1, 3)
