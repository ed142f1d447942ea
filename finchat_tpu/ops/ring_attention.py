"""Ring attention over the ``seq`` mesh axis (SURVEY §5.7c).

Long-prefill RAG prompts (unbounded history + up to 10k retrieved
transactions, reference qdrant_tool.py:145 / llm_agent.py:234-236) are the
scaling axis this product actually has. Ring attention shards the sequence
across devices: each device keeps its Q block resident and the K/V blocks
rotate around the ICI ring via ``ppermute``, with a blockwise online-softmax
accumulation — peak memory O(S/n) per device, comms overlapped with compute
by XLA's collective scheduler.

Math: the standard streaming-softmax recurrence. Fully-masked blocks are
handled by zeroing probabilities under the mask (never exp'ing a -inf
difference), so intermediate ring steps that a causal Q block cannot see
contribute exactly nothing.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from finchat_tpu.ops.refs import gqa_repeat

_NEG = -1e30


def online_fold(q32, k_blk, v_blk, m, l, acc, *, scale: float, H: int, invalid):
    """One streaming-softmax accumulation step shared by every attention
    body that merges multiple K/V sources (ring hops, cached-prefix
    blocks, causal segment blocks): fold ``k_blk``/``v_blk`` [B, K, Hkv, D]
    into the carry (m, l, acc) for queries ``q32`` [B, Sq, H, D] fp32.
    ``invalid`` broadcasts against the [B, H, Sq, K] logits; masked
    probabilities are zeroed explicitly so fully-masked blocks contribute
    exactly nothing (never exp'ing a -inf difference)."""
    k_rep = gqa_repeat(k_blk, H)
    v_rep = gqa_repeat(v_blk, H)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q32, k_rep.astype(jnp.float32)) * scale
    logits = jnp.where(invalid, _NEG, logits)
    m_new = jnp.maximum(m, logits.max(axis=-1))
    p = jnp.where(invalid, 0.0, jnp.exp(logits - m_new[..., None]))
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v_rep.astype(jnp.float32)
    )
    return m_new, l_new, acc_new


def fold_prefix_blocks(q32, kp, vp, prefix_len, m, l, acc, *,
                       scale: float, H: int, prefix_block: int = 1024):
    """Fold a cached, possibly-padded K/V prefix [B, P, Hkv, D] into the
    online-softmax carry, blockwise so [Sq, P] logits never materialize
    at full prefix length. Every prefix position precedes every query by
    construction; only the ``pos >= prefix_len`` padding tail masks."""
    P = kp.shape[1]
    PB = min(prefix_block, P)
    while P % PB:  # static: blocks must tile the prefix exactly, or
        PB -= 1    # the clamped last dynamic_slice would misposition

    def fold_block(b, carry):
        m, l, acc = carry
        k_blk = lax.dynamic_slice_in_dim(kp, b * PB, PB, axis=1)
        v_blk = lax.dynamic_slice_in_dim(vp, b * PB, PB, axis=1)
        pos = b * PB + jnp.arange(PB)
        invalid = (pos >= prefix_len)[None, None, None, :]
        return online_fold(q32, k_blk, v_blk, m, l, acc,
                           scale=scale, H=H, invalid=invalid)

    return lax.fori_loop(0, P // PB, fold_block, (m, l, acc))


def _ring_body(q, k0, v0, *, axis: str, varying: tuple, n_blocks: int, causal: bool, scale: float,
               prefix=None, prefix_block: int = 1024):
    """Per-device function under shard_map. q/k0/v0: [B, Sblk, H(kv), D].

    ``prefix`` (segmented serving prefill): an optional
    ``(k_prefix, v_prefix, prefix_len)`` of ALREADY-CACHED earlier
    tokens, replicated over the seq axis. Every prefix position precedes
    every Q row by construction, so the fold is unmasked except for the
    ``pos >= prefix_len`` tail (page-table padding). It seeds the online-
    softmax carry BEFORE the ring steps — the flash-decoding-style merge
    that lets a long prefill run as segments without losing cross-segment
    attention. Folded blockwise (``prefix_block``) so the [Sq, P] logits
    never materialize at full prefix length."""
    B, Sq, H, D = q.shape
    idx = lax.axis_index(axis)
    q_pos = idx * Sq + jnp.arange(Sq)  # global positions of my Q rows

    q32 = q.astype(jnp.float32)

    def accumulate(t, m, l, acc, k_cur, v_cur):
        """Fold the currently-held KV block into the online softmax."""
        src = (idx - t) % n_blocks  # which global block we hold at step t
        kv_pos = src * Sq + jnp.arange(k_cur.shape[1])

        def update(m, l, acc):
            if causal:
                invalid = kv_pos[None, None, None, :] > q_pos[None, None, :, None]
            else:
                invalid = jnp.zeros((1, 1, 1, k_cur.shape[1]), bool)
            return online_fold(q32, k_cur, v_cur, m, l, acc,
                               scale=scale, H=H, invalid=invalid)

        if not causal:
            return update(m, l, acc)
        # skip blocks that are entirely in this Q block's future (~half the
        # ring steps); predicate is local-only — no collectives under cond
        return lax.cond(src <= idx, update, lambda m, l, acc: (m, l, acc), m, l, acc)

    perm = [(i, (i + 1) % n_blocks) for i in range(n_blocks)]

    def step(t, carry):
        m, l, acc, k_cur, v_cur = carry
        m, l, acc = accumulate(t, m, l, acc, k_cur, v_cur)
        k_next = lax.ppermute(k_cur, axis, perm)
        v_next = lax.ppermute(v_cur, axis, perm)
        return m, l, acc, k_next, v_next

    # mark the accumulators device-varying so the fori_loop carry types match
    # (they're combined with ring-varying k/v inside the loop)
    m0 = lax.pcast(jnp.full((B, H, Sq), _NEG, jnp.float32), varying, to="varying")
    l0 = lax.pcast(jnp.zeros((B, H, Sq), jnp.float32), varying, to="varying")
    acc0 = lax.pcast(jnp.zeros((B, H, Sq, D), jnp.float32), varying, to="varying")

    if prefix is not None:
        kp, vp, prefix_len = prefix
        m0, l0, acc0 = fold_prefix_blocks(
            q32, kp, vp, prefix_len, m0, l0, acc0,
            scale=scale, H=H, prefix_block=prefix_block,
        )
    # n_blocks-1 steps each ending in a ring hop; the final block is folded
    # in WITHOUT the trailing (discarded) ppermute pair
    m, l, acc, k_last, v_last = lax.fori_loop(
        0, n_blocks - 1, step, (m0, l0, acc0, k0, v0)
    )
    m, l, acc = accumulate(n_blocks - 1, m, l, acc, k_last, v_last)

    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B,H,Sq,D]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B,Sq,H,D]


@partial(jax.jit, static_argnames=("mesh", "axis", "batch_axis", "head_axis", "causal"))
def ring_attention(
    q: jax.Array,  # [B, S, H, D] sharded on S over `axis`
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "seq",
    batch_axis: str | None = None,
    head_axis: str | None = None,
    causal: bool = True,
) -> jax.Array:
    """Sequence-parallel attention; result sharded like q. ``batch_axis``
    (DP) and ``head_axis`` (TP over heads) compose with the seq ring."""
    n_blocks = mesh.shape[axis]
    scale = q.shape[-1] ** -0.5
    spec = P(batch_axis, axis, head_axis, None)
    varying = tuple(a for a in (batch_axis, axis, head_axis) if a)
    fn = jax.shard_map(
        partial(_ring_body, axis=axis, varying=varying, n_blocks=n_blocks, causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)


@partial(jax.jit, static_argnames=("mesh", "axis", "batch_axis", "head_axis", "causal"))
def ring_attention_with_prefix(
    q: jax.Array,  # [B, S, H, D] sharded on S over `axis`
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,
    k_prefix: jax.Array,  # [B, P, Hkv, D] cached earlier tokens (replicated
    v_prefix: jax.Array,  # over `axis`; may be padded past prefix_len)
    prefix_len: jax.Array,  # scalar int32 — valid prefix positions
    *,
    mesh: Mesh,
    axis: str = "seq",
    batch_axis: str | None = None,
    head_axis: str | None = None,
    causal: bool = True,
) -> jax.Array:
    """Ring attention for ONE SEGMENT of a longer sequence: the segment's
    Q/K/V ride the ring exactly as in ``ring_attention`` (intra-segment
    causality is offset-invariant), while the already-cached prefix K/V is
    folded into each device's online-softmax carry first. This is what
    makes the seq-sharded serving prefill chunkable — segments interleave
    with decode steps instead of one monolithic stall — without losing
    attention to earlier segments."""
    n_blocks = mesh.shape[axis]
    scale = q.shape[-1] ** -0.5
    spec = P(batch_axis, axis, head_axis, None)
    pspec = P(batch_axis, None, head_axis, None)  # prefix: whole copy per seq shard
    varying = tuple(a for a in (batch_axis, axis, head_axis) if a)

    def body(q, k0, v0, kp, vp, plen):
        return _ring_body(
            q, k0, v0, axis=axis, varying=varying, n_blocks=n_blocks,
            causal=causal, scale=scale, prefix=(kp, vp, plen),
        )

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec, pspec, pspec, P()),
        out_specs=spec,
    )
    return fn(q, k, v, k_prefix, v_prefix, jnp.asarray(prefix_len, jnp.int32))
