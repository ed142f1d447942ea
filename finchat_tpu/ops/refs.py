"""jnp reference implementations for every kernel in ``ops/``.

These are the correctness oracles (SURVEY §4.2): Pallas kernels are validated
against them in CPU interpret mode and on TPU. They are also the fallback
attention path on CPU, where Mosaic kernels don't run.

Numerics policy: bf16 inputs, fp32 softmax (logits and normalizer), bf16
output — the same policy the Pallas kernels implement.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array

NEG_INF = -1e30  # large-negative mask value; avoids NaN from (-inf) - (-inf)


def gqa_repeat(kv: Array, n_heads: int) -> Array:
    """Broadcast KV heads up to the query head count for grouped-query
    attention. kv: [..., n_kv_heads, head_dim] -> [..., n_heads, head_dim]."""
    n_kv = kv.shape[-2]
    if n_kv == n_heads:
        return kv
    assert n_heads % n_kv == 0, (n_heads, n_kv)
    reps = n_heads // n_kv
    return jnp.repeat(kv, reps, axis=-2)


def mha_reference(
    q: Array,  # [B, Sq, H, D]
    k: Array,  # [B, Sk, Hkv, D]
    v: Array,  # [B, Sk, Hkv, Dv] — as wide as the keys, or not (the output is [.., Dv])
    *,
    causal: bool = True,
    q_offset: Array | int = 0,  # absolute position of q[0] within the kv axis
    kv_len: Array | None = None,  # [B] valid kv length (rest is padding)
    scale: float | None = None,
    window: int = 0,  # > 0: a query sees itself and the window - 1 positions before it
    sink: Array | None = None,  # [H] float32: a logit a head that takes probability, gives no value
) -> Array:
    """Masked multi-head attention with GQA, fp32 softmax.

    ``q_offset`` supports chunked prefill / decode: query row i has absolute
    position ``q_offset + i`` and may attend to kv positions ≤ its own.
    ``kv_len`` masks right-padding in the kv axis (per batch element).
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else D ** -0.5

    k = gqa_repeat(k, H)
    v = gqa_repeat(v, H)

    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale

    kv_pos = jnp.arange(Sk)[None, None, None, :]  # [1,1,1,Sk]
    mask = jnp.zeros((B, 1, Sq, Sk), dtype=bool)
    if causal:
        if jnp.ndim(q_offset) == 0:
            q_pos = jnp.broadcast_to(q_offset + jnp.arange(Sq), (B, Sq))
        else:
            q_pos = q_offset[:, None] + jnp.arange(Sq)[None, :]
        mask = mask | (kv_pos > q_pos[:, None, :, None])
        if window:
            mask = mask | (kv_pos <= q_pos[:, None, :, None] - window)
    if kv_len is not None:
        mask = mask | (kv_pos >= kv_len[:, None, None, None])

    logits = jnp.where(mask, NEG_INF, logits)
    if sink is not None:  # one more logit in the softmax's sum, dropped before the values
        column = jnp.broadcast_to(sink.astype(jnp.float32)[None, :, None, None], (B, H, Sq, 1))
        weights = jax.nn.softmax(jnp.concatenate([logits, column], axis=-1), axis=-1)[..., :Sk]
    else:
        weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights.astype(v.dtype), v)
    return out.astype(q.dtype)
