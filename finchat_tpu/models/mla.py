"""Latent attention with a sparse-attention indexer (the DeepSeek-V3.2 family).

A layer's attention, with ``h`` the normed input::

    c_q = RMSNorm(W_dq h);  q = W_uq c_q  ->  heads of [q_nope | q_rope]  (leaves ``attn_q_nope``, ``attn_q_rope``)
    [c | k_r] = W_dkv h;  c_kv = RMSNorm(c);  k_rope = RoPE(k_r)   ONE for all heads
    [k_nope_i | v_i] = W_ukv,i c_kv          (leaves ``attn_uk`` [H, nope, R], ``attn_uv`` [H, R, v])
    s_i[t, j] = scale (q_nope_i[t] . k_nope_i[j] + RoPE(q_rope_i)[t] . k_rope[j])
    o_i = softmax_{j <= t, j in S_t}(s_i) v;  y = W_o [o_1 .. o_H]

The program runs it in the ABSORBED form, the same numbers: ``q'_i = W_uk,i^T
q_nope_i``, scores ``[q'_i | q_rope_i] . [c_kv[j] | k_rope[j]]`` against the
token's one latent row — which is all a page holds — and ``o_i = W_uv,i
(sum_j p_ij c_kv[j])``. ``project`` makes the queries and the row,
``up_values`` the last step; what lies between (the cache write, the indexer,
the selection, the softmax over the selected rows) is the attention callback's
(``ops/latent_attention.py``; the engine builds it over its page table).

The indexer, every layer: ``q^I = W_qb c_q`` (heads of ``index_head_dim``,
rotated on their first ``qk_rope_dim`` dims), ``k^I = LayerNorm(W_k h)``
(rotated alike, ONE row a token: the second paged array), ``w = W_w h
heads^-1/2``; ``I[t, j] = sum_h w[t, h] ReLU(q^I_h[t] . k^I[j]) dim^-1/2``,
and ``S_t`` the ``index_topk`` tokens ``j <= t`` with the largest ``I[t, j]``.

The rotation pairs dimension ``i`` with ``i + half`` (the program's ``rope``
convention; a weight-layout choice), at YaRN's corrected frequencies.

Two pieces are data of the config and absent where it says so. ``q_lora_rank``
0: ``q = W_q h`` in ONE projection, no latent and no norm (leaves
``attn_q_nope`` / ``attn_q_rope`` input-major ``[D, H x]``; there is then no
``c_q`` for an indexer to read, and none is built). ``rope_theta`` None:
neither ``q_rope`` nor ``k_r`` is rotated (NoPE: the heads' last
``qk_rope_dim`` dims are then plain dims that all heads share one key for).
"""

from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from finchat_tpu.models.quant import dense, flat_fence


class RopeScaling(NamedTuple):
    """YaRN (``rope_scaling`` of the published config): frequencies that turn
    fewer than ``beta_slow`` times over the original window are interpolated
    by ``factor``, those that turn more than ``beta_fast`` times are kept, a
    linear ramp between. cos and sin are scaled by the ratio of the two
    mscales (1 where they are equal)."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(head_dim: int, scaling: RopeScaling | None) -> float:
    """``head_dim ** -0.5``, times YaRN's ``mscale_all_dim`` correction
    squared (q and k each carry one)."""
    m = yarn_mscale(scaling.factor, scaling.mscale_all_dim) if (
        scaling is not None and scaling.mscale_all_dim) else 1.0
    return head_dim ** -0.5 * m * m


def rope_tables(dim: int, theta: float, scaling: RopeScaling | None) -> tuple[np.ndarray, float]:
    """``(inv_freq [dim / 2] float32, the multiplier on cos and sin)``."""
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    inv_freq = theta ** -exponent
    if scaling is None:
        return inv_freq.astype(np.float32), 1.0

    def correction_dim(rotations: float) -> float:
        return dim * math.log(scaling.original_max_position / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0, 1)
    keep = 1.0 - ramp  # 1 = the plain frequency, 0 = interpolated by `factor`
    inv_freq = inv_freq / scaling.factor * (1 - keep) + inv_freq * keep
    ratio = yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(
        scaling.factor, scaling.mscale_all_dim)
    return inv_freq.astype(np.float32), ratio


def rotate(x: Array, positions: Array, inv_freq: np.ndarray, mult: float = 1.0) -> Array:
    """Rotate the FIRST ``2 * len(inv_freq)`` dims of ``x`` [B, S, ..., D] by
    ``positions`` [B, S], float32 math; the rest passes through."""
    n = 2 * len(inv_freq)
    angles = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)  # [B,S,half]
    angles = angles.reshape(*angles.shape[:2], *(1,) * (x.ndim - 3), -1)
    cos, sin = jnp.cos(angles) * mult, jnp.sin(angles) * mult
    x32 = x[..., :n].astype(jnp.float32)
    x1, x2 = x32[..., :n // 2], x32[..., n // 2:]
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([rotated.astype(x.dtype), x[..., n:]], axis=-1)


class LatentInputs(NamedTuple):
    """What a latent-attention callback takes for a chunk of tokens."""
    q: Array  # [B, S, H, R + rope] absorbed queries [W_uk^T q_nope | RoPE(q_rope)]
    row: Array  # [B, S, latent_row] the tokens' latent rows [c_kv | k_rope | 0]
    idx_q: Array | None  # [B, S, Hi, Di] the indexer's queries (None: no indexer)
    idx_w: Array | None  # [B, S, Hi] float32, its head weights (scaled)
    idx_k: Array | None  # [B, S, Di] the tokens' index-key rows


# callback: fn(inputs, layer_cache, layer_idx) -> (o_latent [B, S, H, R],
# new_layer_cache, selected) — ``selected`` int32 scalar: the context tokens
# the chunk's live queries attended to, summed (the step's counter)
LatentAttentionFn = Callable[[LatentInputs, Any, Array], tuple[Array, Any, Array]]


def _from_latent(c_q: Array, w: Array) -> Array:
    """``c_q`` [B, S, Q] through an up-projection kept OUTPUT-major, ``w``
    [N, Q]: the layout the TPU compiler gives a weight whose contraction is
    the q latent's 1,536 columns — kept input-major, all three (q_nope, q_rope,
    the indexer's queries) were transposed in HBM on every step, 12 % of it
    (PERF.md section 5). The product is split into heads and rotated, so it
    is fenced flat (``flat_fence``: else each weight is staged out of its stack
    before its matmul)."""
    return flat_fence(jnp.einsum("bsq,nq->bsn", c_q, w))


def project(h: Array, lp: dict[str, Array], c, positions: Array,
            qm_backend: str | None = None) -> LatentInputs:
    """The absorbed queries, the latent row and the indexer's inputs of
    ``h`` [B, S, D] at ``positions`` [B, S]."""
    from finchat_tpu.models.llama import rms_norm as rms  # llama imports this module

    B, S, _ = h.shape
    H, R, rope_d = c.n_heads, c.kv_lora_rank, c.qk_rope_dim
    rotated = c.rope_theta is not None  # (None: NoPE, the "rope" dims are plain dims)
    if rotated:
        inv_freq, mult = rope_tables(rope_d, c.rope_theta, c.rope_scaling)

    def turn(x: Array) -> Array:
        return rotate(x, positions, inv_freq, mult) if rotated else x
    if c.q_lora_rank:
        c_q = rms(dense(h, lp["attn_q_a"], qm_backend=qm_backend), lp["attn_q_a_norm"],
                  c.norm_eps)
        q_nope = _from_latent(c_q, lp["attn_q_nope"]).reshape(B, S, H, -1)
        q_rope = _from_latent(c_q, lp["attn_q_rope"]).reshape(B, S, H, -1)
    else:  # q is one projection of the input: no latent, no norm
        q_nope = flat_fence(dense(h, lp["attn_q_nope"], qm_backend=qm_backend)).reshape(B, S, H, -1)
        q_rope = flat_fence(dense(h, lp["attn_q_rope"], qm_backend=qm_backend)).reshape(B, S, H, -1)
    kv = dense(h, lp["attn_kv_a"], qm_backend=qm_backend)  # [B,S,R+rope]
    c_kv = rms(kv[..., :R], lp["attn_kv_a_norm"], c.norm_eps)
    k_rope = turn(kv[..., R:])
    q_abs = jnp.einsum("bshn,hnr->bshr", q_nope, lp["attn_uk"],
                       preferred_element_type=jnp.float32).astype(h.dtype)
    q_abs = jnp.concatenate([q_abs, turn(q_rope)], axis=-1)
    pad = jnp.zeros((B, S, c.latent_row - R - rope_d), h.dtype)
    row = jnp.concatenate([c_kv, k_rope, pad], axis=-1)
    if not c.index_topk:
        return LatentInputs(q_abs, row, None, None, None)
    Hi, Di = c.index_heads, c.index_head_dim
    idx_q = _from_latent(c_q, lp["attn_idx_q_b"]).reshape(B, S, Hi, Di)
    k = dense(h, lp["attn_idx_k"], qm_backend=qm_backend).astype(jnp.float32)
    mean = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean((k - mean) ** 2, axis=-1, keepdims=True)
    idx_k = ((k - mean) * jax.lax.rsqrt(var + 1e-6)).astype(h.dtype) \
        * lp["attn_idx_k_norm"] + lp["attn_idx_k_bias"]
    idx_w = dense(h, lp["attn_idx_w"], qm_backend=qm_backend).astype(jnp.float32) \
        * (Hi ** -0.5 * Di ** -0.5)  # the heads' and the dot's scales, once
    return LatentInputs(q_abs, row, turn(idx_q), idx_w, turn(idx_k))


def up_values(o_latent: Array, lp: dict[str, Array], c) -> Array:
    """``o_i = W_uv,i (sum_j p_ij c_kv[j])``: [B, S, H, R] -> [B, S, H * v]."""
    out = jnp.einsum("bshr,hrv->bshv", o_latent, lp["attn_uv"],
                     preferred_element_type=jnp.float32)
    return out.astype(o_latent.dtype).reshape(*o_latent.shape[:2], -1)


def n_attention_params(c) -> int:
    """One layer's attention and indexer parameters."""
    d, H = c.dim, c.n_heads
    # q through its latent (down, norm, up), or one projection of the input
    q = (d * c.q_lora_rank + c.q_lora_rank + c.q_lora_rank * H * c.head_dim
         if c.q_lora_rank else d * H * c.head_dim)
    attn = (q + d * (c.kv_lora_rank + c.qk_rope_dim) + c.kv_lora_rank
            + c.kv_lora_rank * H * (c.qk_nope_dim + c.v_head_dim) + H * c.v_head_dim * d)
    if c.index_topk:
        attn += (c.q_lora_rank * c.index_heads * c.index_head_dim + d * c.index_head_dim
                 + 2 * c.index_head_dim + d * c.index_heads)
    return attn


def init_attention(c, key: Array, depth: int, rand_init: Callable) -> dict[str, Array]:
    """The ``attn_*`` leaves of ``depth`` latent-attention layers (the
    indexer's with ``index_topk``), stacked."""
    d, H, R, Q = c.dim, c.n_heads, c.kv_lora_rank, c.q_lora_rank
    ks = jax.random.split(key, 8)
    if Q:
        q_leaves = {
            "attn_q_a": rand_init("attn_q_a", ks[0], (depth, d, Q), d),
            "attn_q_a_norm": jnp.ones((depth, Q), c.dtype),
            # W_uq's columns by what they make: every head's q_nope, every head's
            # q_rope (one matrix whose heads are [128 | 64] wide was re-laid every step)
            # (both output-major, [N, Q]: ``_from_latent``)
            "attn_q_nope": rand_init("attn_q_nope", ks[1], (depth, H * c.qk_nope_dim, Q), Q),
            "attn_q_rope": rand_init("attn_q_rope", jax.random.fold_in(ks[1], 1),
                                     (depth, H * c.qk_rope_dim, Q), Q),
        }
    else:  # W_q's columns by what they make, input-major as every projection of h
        q_leaves = {
            "attn_q_nope": rand_init("attn_q_nope", ks[1], (depth, d, H * c.qk_nope_dim), d),
            "attn_q_rope": rand_init("attn_q_rope", jax.random.fold_in(ks[1], 1),
                                     (depth, d, H * c.qk_rope_dim), d),
        }
    leaves = {
        **q_leaves,
        "attn_kv_a": rand_init("attn_kv_a", ks[2], (depth, d, R + c.qk_rope_dim), d),
        "attn_kv_a_norm": jnp.ones((depth, R), c.dtype),
        # W_ukv's two halves, each as the absorbed form multiplies by it: a
        # head's keys' [nope, R] (q'_i = W_uk,i^T q_nope_i) and its values'
        # [R, v] — in one matrix [R, H (nope + v)] both were re-laid every step
        "attn_uk": rand_init("attn_uk", ks[3], (depth, H, c.qk_nope_dim, R), R),
        "attn_uv": rand_init("attn_uv", jax.random.fold_in(ks[3], 1), (depth, H, R, c.v_head_dim), R),
        "attn_o": rand_init("attn_o", ks[4], (depth, H * c.v_head_dim, d), H * c.v_head_dim),
    }
    if c.index_topk:
        Hi, Di = c.index_heads, c.index_head_dim
        leaves.update({
            "attn_idx_q_b": rand_init("attn_idx_q_b", ks[5], (depth, Hi * Di, Q), Q),
            "attn_idx_k": rand_init("attn_idx_k", ks[6], (depth, d, Di), d),
            "attn_idx_k_norm": jnp.ones((depth, Di), c.dtype),
            "attn_idx_k_bias": jnp.zeros((depth, Di), c.dtype),
            "attn_idx_w": rand_init("attn_idx_w", ks[7], (depth, d, Hi), d),
        })
    return leaves
