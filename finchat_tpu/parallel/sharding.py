"""Sharding rules: logical param/state layout → mesh placement.

Megatron-style TP expressed as GSPMD constraints — we annotate the weights
and let XLA's SPMD partitioner insert the collectives (psum after
row-parallel matmuls etc.), instead of hand-writing NCCL calls the way
GPU frameworks do:

- attention q/k/v projections column-parallel over heads (``model`` axis),
  output projection row-parallel → one all-reduce;
- MLP gate/up column-parallel, down row-parallel → one all-reduce;
- embeddings + lm_head feature/vocab sharded; norms replicated;
- KV-cache pages sharded over KV heads on ``model`` (matches the k/v
  projection sharding, so cache writes are local);
- batch-bearing engine state sharded on ``data`` where useful; page tables
  and lengths replicated (they are tiny and host-updated).

Parity note: the reference has no parallelism to mirror (SURVEY §2.3); this
module IS the new framework surface specified there.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from finchat_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def llama_param_shardings(mesh: Mesh) -> dict[str, Any]:
    """PartitionSpec tree matching models/llama.py:init_params layout.

    Leading axis of every ``layers`` leaf is the stacked layer axis — never
    sharded (it is scanned over)."""
    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    return {
        # replicated: a feature- or vocab-sharded table makes the token
        # gather's output sharding ambiguous under GSPMD (needs an explicit
        # out_sharding at the lookup); revisit when embed HBM matters.
        "embed": ns(None, None),
        "layers": {
            "attn_q": ns(None, None, "model"),  # [L, D, H*hd] column-parallel
            "attn_k": ns(None, None, "model"),
            "attn_v": ns(None, None, "model"),
            "attn_o": ns(None, "model", None),  # [L, H*hd, D] row-parallel
            "mlp_gate": ns(None, None, "model"),
            "mlp_up": ns(None, None, "model"),
            "mlp_down": ns(None, "model", None),
            # MoE (models with n_experts > 0): EP over `expert` on the
            # leading expert dim, TP over `model` on the hidden dim — the
            # expert-sum becomes a psum over EP shards (GSPMD inserts it)
            "router": ns(None, None, None),  # fp32 routing, replicated
            "moe_gate": ns(None, "expert", None, "model"),  # [L, E, D, F]
            "moe_up": ns(None, "expert", None, "model"),
            "moe_down": ns(None, "expert", "model", None),  # [L, E, F, D]
            "ln_attn": ns(None, None),
            "ln_mlp": ns(None, None),
        },
        "norm": ns(None),
        "lm_head": ns(None, "model"),  # vocab-sharded logits
    }


def decode_state_shardings(mesh: Mesh, n_kv_heads: int | None = None) -> dict[str, Any]:
    """Shardings for engine.DecodeState fields (see engine/engine.py).

    ``n_kv_heads`` guards the fused-dim split: sharding [.., Hkv*hd] on
    ``model`` is only a whole-KV-head split (the locality the Pallas paged
    kernel's per-head value slices rely on) when the model axis divides
    Hkv. The fused dim often divides NUMERICALLY even when the head count
    doesn't (model=8, Hkv=4, hd=64 → 256/8 splits mid-head), so divisibility
    of the byte count is not enough — pass the head count and the pages
    replicate when it doesn't divide."""
    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    kv_whole_heads = (
        n_kv_heads is None or n_kv_heads % mesh.shape.get("model", 1) == 0
    )
    if not kv_whole_heads:
        logger.warning(
            "model axis %d does not divide n_kv_heads %s; replicating KV pages",
            mesh.shape.get("model", 1), n_kv_heads,
        )
    kv_spec = ns(None, None, None, "model") if kv_whole_heads else ns(None, None, None, None)
    # int8-KV scale arrays [L, P, pad8(Hkv), PS]: the head ROW dim (2)
    # splits over the model axis in the same whole-KV-head blocks the fused
    # page minor dim does — but only when the sublane padding can't
    # interleave with the split (Hkv % 8 == 0 makes pad8(Hkv) == Hkv, so row
    # blocks == head blocks and the placement is communication-free,
    # matching Llama-3-8B/70B's Hkv=8). Otherwise they replicate: scales
    # are ~6% of the pages' bytes, so replication is cheap and strictly
    # better than a misaligned shard that GSPMD would repair with gathers.
    # When kv_quant is off these leaves are (1,1,1,1) placeholders and
    # _fit_sharding quietly replicates them.
    scale_spec = (
        ns(None, None, "model", None)
        if kv_whole_heads and n_kv_heads is not None and n_kv_heads % 8 == 0
        else ns(None, None, None, None)
    )
    return {
        # [L, pages, page_size, Hkv*hd] — the fused KV-head dim on the model
        # axis (head-major within the fused dim, so a model-axis shard is a
        # whole number of KV heads — matching the k/v projection sharding,
        # keeping cache writes local)
        "k_pages": kv_spec,
        "v_pages": kv_spec,
        "k_scales": scale_spec,
        "v_scales": scale_spec,
        "page_table": ns(None, None),
        "context_lens": ns(None),
        "last_tokens": ns(None),
        "kv_gaps": ns(None),
        "rng": ns(),
    }


# Tensors above this size refuse to silently replicate when their sharded
# dim doesn't divide the mesh axis — at that scale replication means HBM
# blow-up on real checkpoints and the config error must fail fast. Small
# (debug-model) tensors replicate with a warning so tiny presets run on any
# mesh.
_REPLICATE_LIMIT_BYTES = 256 * 1024 * 1024


def _fit_sharding(
    sharding: NamedSharding, shape: tuple[int, ...], nbytes: int
) -> NamedSharding:
    """Drop (replicate) any spec axis whose mesh extent does not divide the
    array dimension; raise instead when the tensor is too large to replicate
    safely. Production-sized configs divide evenly and are untouched."""
    mesh = sharding.mesh
    spec = list(sharding.spec) + [None] * (len(shape) - len(sharding.spec))
    fitted = []
    for dim, axes in zip(shape, spec):
        if axes is None:
            fitted.append(None)
            continue
        extent = 1
        for a in (axes if isinstance(axes, tuple) else (axes,)):
            extent *= mesh.shape[a]
        if dim % extent:
            if nbytes > _REPLICATE_LIMIT_BYTES:
                raise ValueError(
                    f"dim of size {dim} (tensor shape {shape}, {nbytes} bytes) is not "
                    f"divisible by mesh axes {axes!r} = {extent}; refusing to replicate "
                    "a tensor this large — fix the mesh/model config"
                )
            if dim > 1:  # size-1 dims (placeholder leaves) replicate silently
                logger.warning(
                    "replicating dim of size %d (not divisible by mesh axes %r = %d)",
                    dim, axes, extent,
                )
            fitted.append(None)
        else:
            fitted.append(axes)
    return NamedSharding(mesh, P(*fitted))


def shard_params(params: dict[str, Any], shardings: dict[str, Any]) -> dict[str, Any]:
    """Place a (host or single-device) param tree onto the mesh. Sharding
    entries with no matching param (e.g. ``lm_head`` under tied embeddings,
    MoE specs on a dense model) are pruned at every dict level; non-dividing
    dims are replicated."""

    def prune(spec, tree):
        if isinstance(spec, dict) and isinstance(tree, dict):
            return {k: prune(spec[k], v) for k, v in tree.items()}
        return spec

    from finchat_tpu.models.quant import Q4Tensor, QTensor

    def place(x, s):
        if isinstance(x, QTensor):
            # pre-quantized leaf (streaming int8 load): q takes the weight's
            # spec; the per-output-column scale [..., N] drops the spec's
            # contraction axis (-2)
            spec = list(s.spec) + [None] * (x.q.ndim - len(s.spec))
            scale_s = NamedSharding(s.mesh, P(*spec[:-2], spec[-1]))
            return QTensor(
                q=jax.device_put(x.q, _fit_sharding(s, x.q.shape, x.q.nbytes)),
                scale=jax.device_put(
                    x.scale, _fit_sharding(scale_s, x.scale.shape, x.scale.nbytes)
                ),
            )
        if isinstance(x, Q4Tensor):
            # int4: q is the weight spec over the PACKED [.., K//2, N]
            # layout (K-axis shards that stop dividing simply replicate via
            # _fit_sharding); the per-group scale [..., G, N] keeps the
            # output axis and replicates the group axis
            spec = list(s.spec) + [None] * (x.q.ndim - len(s.spec))
            scale_s = NamedSharding(s.mesh, P(*spec[:-2], None, spec[-1]))
            return Q4Tensor(
                q=jax.device_put(x.q, _fit_sharding(s, x.q.shape, x.q.nbytes)),
                scale=jax.device_put(
                    x.scale, _fit_sharding(scale_s, x.scale.shape, x.scale.nbytes)
                ),
            )
        return jax.device_put(x, _fit_sharding(s, x.shape, x.nbytes))

    pruned = prune(shardings, params)
    return jax.tree.map(
        place, params, pruned,
        is_leaf=lambda x: isinstance(x, (QTensor, Q4Tensor)),
    )


def shard_decode_state(state, mesh: Mesh, n_kv_heads: int | None = None):
    """Place an engine DecodeState onto the mesh."""
    import dataclasses

    sh = decode_state_shardings(mesh, n_kv_heads)
    return dataclasses.replace(
        state,
        **{
            f: jax.device_put(
                getattr(state, f),
                _fit_sharding(sh[f], getattr(state, f).shape, getattr(state, f).nbytes),
            )
            for f in sh
        },
    )
