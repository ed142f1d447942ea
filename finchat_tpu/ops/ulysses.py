"""Ulysses-style sequence parallelism: all-to-all head scatter (SURVEY §5.7d).

The alternative SP mode to ring attention (ops/ring_attention.py) for long
sequences, after DeepSpeed-Ulysses: instead of rotating K/V blocks around
the ring, ONE all-to-all redistributes the sharding from sequence-sharded
``[B, S/n, H, D]`` to head-sharded ``[B, S, H/n, D]``, each device runs
ordinary FULL-sequence attention over its head group, and a second
all-to-all restores sequence sharding. Two collectives total (vs n-1 ring
hops), at the cost of requiring ``heads % n == 0`` and a full-sequence
attention footprint per device — the right trade when heads are plentiful
and S fits once per chip; ring attention remains the mode for S beyond one
chip's HBM.

GQA note: K/V heads are scattered over the same axis, so ``n`` must divide
``n_kv_heads`` too (else fall back to ring). Head groups stay aligned with
GQA groups because the head axis is sharded in contiguous blocks.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from finchat_tpu.ops.refs import mha_reference


def _ulysses_prefix_body(q, k, v, kp, vp, prefix_len, *, axis: str, n: int,
                         varying: tuple, causal: bool, seg_block: int = 1024):
    """Per-device Ulysses attention for ONE SEGMENT of a longer sequence:
    head-scatter the segment as usual, then fold the CACHED prefix K/V
    (this device's head group of it) into the online-softmax carry before
    the segment's own causal attention — the same flash-decoding-style
    merge the chunked ring prefill uses (ops/ring_attention.py), in the
    Ulysses layout.

    In: q [B, S/n, H, D], k/v [B, S/n, Hkv, D] (seq shards);
    kp/vp [B, P, Hkv, D] (FULL prefix, replicated over the seq axis,
    padded past ``prefix_len``). Out: [B, S/n, H, D].
    """
    from finchat_tpu.ops.ring_attention import fold_prefix_blocks, online_fold

    def seq_to_heads(x):
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)

    q_h = seq_to_heads(q)  # [B, S, H/n, D] — full segment, my head group
    k_h = seq_to_heads(k)
    v_h = seq_to_heads(v)
    B, S, Hg, D = q_h.shape
    idx = lax.axis_index(axis)
    # my head group's slice of the prefix (contiguous blocks keep GQA
    # groups aligned, same invariant as the scatter itself)
    hkv_g = kp.shape[2] // n
    kp_g = lax.dynamic_slice_in_dim(kp, idx * hkv_g, hkv_g, axis=2)
    vp_g = lax.dynamic_slice_in_dim(vp, idx * hkv_g, hkv_g, axis=2)

    q32 = q_h.astype(jnp.float32)
    scale = D ** -0.5
    # fresh accumulators must be born device-varying to match the
    # seq-varying values folded into them (same pattern as _ring_body)
    m = lax.pcast(jnp.full((B, Hg, S), -1e30, jnp.float32), varying, to="varying")
    l = lax.pcast(jnp.zeros((B, Hg, S), jnp.float32), varying, to="varying")
    acc = lax.pcast(jnp.zeros((B, Hg, S, D), jnp.float32), varying, to="varying")
    m, l, acc = fold_prefix_blocks(
        q32, kp_g, vp_g, prefix_len, m, l, acc, scale=scale, H=Hg,
    )
    # the segment itself: blockwise causal fold (index-causal — a constant
    # position offset does not change intra-segment causality)
    SB = min(seg_block, S)
    while S % SB:
        SB -= 1

    def fold_seg_block(b, carry):
        m, l, acc = carry
        k_blk = lax.dynamic_slice_in_dim(k_h, b * SB, SB, axis=1)
        v_blk = lax.dynamic_slice_in_dim(v_h, b * SB, SB, axis=1)
        kv_pos = b * SB + jnp.arange(SB)
        if causal:
            invalid = kv_pos[None, None, None, :] > jnp.arange(S)[None, None, :, None]
        else:
            invalid = jnp.zeros((1, 1, 1, SB), bool)
        return online_fold(q32, k_blk, v_blk, m, l, acc,
                           scale=scale, H=Hg, invalid=invalid)

    m, l, acc = lax.fori_loop(0, S // SB, fold_seg_block, (m, l, acc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B, Hg, S, D]
    out = out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, S, Hg, D]
    return heads_to_seq(out)


def _ulysses_body(q, k, v, *, axis: str, causal: bool):
    """Per-device function under shard_map.

    In: q [B, S/n, H, D], k/v [B, S/n, Hkv, D] (local shards).
    Out: [B, S/n, H, D].
    """
    # seq-sharded -> head-sharded: split the local head axis into n groups,
    # all-to-all exchanges (my seq block of your head group) so every device
    # ends with the FULL sequence of its own head group.
    def seq_to_heads(x):
        # [B, S/n, h, D] -> [B, S, h/n, D]
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):
        # [B, S, h/n, D] -> [B, S/n, h, D]
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)

    q_h = seq_to_heads(q)
    k_h = seq_to_heads(k)
    v_h = seq_to_heads(v)
    out_h = mha_reference(q_h, k_h, v_h, causal=causal)
    return heads_to_seq(out_h)


def ulysses_supported(
    n_heads: int, n_kv_heads: int, mesh: Mesh,
    axis: str = "seq", head_axis: str | None = None,
) -> bool:
    """THE divisibility predicate for the head scatter — shared by
    ``ulysses_attention``'s own check and the engine's sp_mode resolution
    (engine/engine.py) so the two can never drift: per-TP-shard head
    counts (query AND kv) must divide by the seq-axis extent."""
    n = mesh.shape.get(axis, 1)
    tp = mesh.shape.get(head_axis, 1) if head_axis else 1
    return not (
        n_heads % tp or n_kv_heads % tp
        or (n_heads // tp) % n or (n_kv_heads // tp) % n
    )


@partial(jax.jit, static_argnames=("mesh", "axis", "batch_axis", "head_axis", "causal"))
def ulysses_attention(
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
    """Sequence-parallel attention via head scatter; result sharded like q.
    ``batch_axis`` (DP) and ``head_axis`` (TP over heads) compose with the
    seq scatter — the all-to-all then redistributes each TP shard's heads.

    Requires the (per-TP-shard) head counts divisible by
    ``n = mesh.shape[axis]`` (checked); callers fall back to ring attention
    otherwise.
    """
    H, Hkv = q.shape[2], k.shape[2]
    if not ulysses_supported(H, Hkv, mesh, axis=axis, head_axis=head_axis):
        raise ValueError(
            f"ulysses needs per-shard heads divisible by the seq axis: "
            f"H={H}, Hkv={Hkv}, mesh={dict(mesh.shape)} — use ring attention instead"
        )
    spec = P(batch_axis, axis, head_axis, None)
    fn = jax.shard_map(
        partial(_ulysses_body, axis=axis, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)


@partial(jax.jit, static_argnames=("mesh", "axis", "batch_axis", "head_axis", "causal"))
def ulysses_attention_with_prefix(
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
    """Ulysses attention for ONE SEGMENT of a longer sequence (see
    ``_ulysses_prefix_body``) — what makes the chunked serving prefill
    available under ``sp_mode='ulysses'`` too, not just ring."""
    H, Hkv = q.shape[2], k.shape[2]
    if not ulysses_supported(H, Hkv, mesh, axis=axis, head_axis=head_axis):
        raise ValueError(
            f"ulysses needs per-shard heads divisible by the seq axis: "
            f"H={H}, Hkv={Hkv}, mesh={dict(mesh.shape)} — use ring attention instead"
        )
    n = mesh.shape[axis]
    varying = tuple(a for a in (batch_axis, axis, head_axis) if a)
    spec = P(batch_axis, axis, head_axis, None)
    pspec = P(batch_axis, None, head_axis, None)
    fn = jax.shard_map(
        partial(_ulysses_prefix_body, axis=axis, n=n, varying=varying, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec, pspec, pspec, P()),
        out_specs=spec,
    )
    return fn(q, k, v, k_prefix, v_prefix, jnp.asarray(prefix_len, jnp.int32))
