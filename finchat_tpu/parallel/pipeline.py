"""Pipeline parallelism over the ``pipe`` mesh axis (SURVEY §2.3 C4).

Until round 4 the ``pipe`` axis was pure surface — exposed in the mesh but
nothing could run at ``pipe > 1``. This module is the stage scheduler: a
GPipe-style microbatch pipeline expressed the TPU way, as a ``shard_map``
over the mesh with stage-to-stage activation transfer via ``ppermute`` —
point-to-point neighbor sends that ride DCN between hosts (mesh.py puts
``pipe`` right after ``data``).

The shard_map is ALL-manual: a partial-manual mapping (``axis_names=
{'pipe'}`` with data/model left GSPMD-auto) computes the identical forward
but its TRANSPOSE trips an XLA check failure in this toolchain ("Invalid
binary instruction opcode copy", hlo_instruction.cc:1585) — found while
bringing up the backward pass, round 4. In-stage TP therefore uses the
OTHER route that note anticipated: manual Megatron collectives in the
stage block (round 5) — layer weights arrive as column/row shards over
``model`` (``_pipeline_layer_specs``) and ``models/llama._layer`` psums
the two row-parallel projections over the axis, so a ``pipe x model``
mesh actually partitions both ways. In-stage DP shards the batch over
``data`` into the body (each data coordinate pipelines its own slice;
the shard_map transpose psums layer grads over data). PP x SP shards
the sequence over ``seq``: inside the manual region the ring body runs
DIRECTLY (no nested shard_map) with K/V rotating via ppermute("seq") —
see ``_sp_ring_attention``. All four axes compose in one step.

Layer placement falls out of the existing stacked-layer layout: every
``layers`` leaf is ``[L, ...]``, so sharding the leading axis over ``pipe``
(parallel/sharding.py) gives each stage a contiguous block of L/P layers
with no resharding — the same pytree serves the plain scanned forward
(pipe=1) and the pipeline.

Schedule: the classic forward-fill/drain loop. With P stages and M
microbatches, tick t of ``M + P - 1``:

  stage 0 ingests microbatch t (while t < M); every stage runs its local
  layer block on the activation it holds; activations hop one stage via
  ppermute; the last stage banks its output for microbatch t-(P-1).

Bubble fraction is (P-1)/(M+P-1) — callers pick ``n_micro >> P``. The loop
is a ``lax.scan`` so the whole pipeline is reverse-differentiable (ppermute
transposes to the reverse permutation), giving 1F1B-equivalent memory via
the usual remat-on-stage trade (``remat=True`` checkpoints each stage
block).

Composition note: the pipeline body runs cache-less attention (the
training / long-prefill shape) — full causal when ``seq == 1``, the
seq-sharded ring when ``seq > 1``.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from finchat_tpu.models.llama import (
    LlamaConfig,
    _layer,
    lm_head,
    make_causal_attention,
    rms_norm,
)
from finchat_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def _stage_block(x, layers_local, positions, *, config, attention, remat,
                 tp_axis, tp_size, tp_overlap=False, tp_chunks=4,
                 qm_backend=None):
    """Run this stage's local layer block (scan over L/P layers)."""

    def body(x, scanned):
        layer_params, = scanned
        x, _ = _layer(
            x, layer_params, None, jnp.int32(0),
            positions=positions, config=config, attention=attention,
            tp_axis=tp_axis, tp_size=tp_size,
            tp_overlap=tp_overlap, tp_chunks=tp_chunks,
            qm_backend=qm_backend,
        )
        return x, None

    if remat:
        body = jax.checkpoint(body)
    x, _ = lax.scan(body, x, (layers_local,))
    return x


def _pipeline_body(
    layers_local: dict[str, Any],
    x: jax.Array,  # [B(/data), S, D] embedded input (replicated over pipe)
    positions: jax.Array,  # [B, S]
    *,
    config: LlamaConfig,
    n_micro: int,
    n_stages: int,
    attention,
    remat: bool,
    tp_axis,
    tp_size: int,
    tp_overlap: bool,
    tp_chunks: int,
    qm_backend,
    carry_varying: tuple,
):
    """Per-device pipeline schedule under shard_map (manual axis: pipe)."""
    B, S, D = x.shape
    mb = B // n_micro
    stage = lax.axis_index("pipe")
    is_first = stage == 0
    is_last = stage == n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    # the carries vary over pipe (per-stage) plus whatever axes the
    # activations shard over (data / seq), passed in by the caller
    held0 = lax.pcast(jnp.zeros((mb, S, D), x.dtype), carry_varying, to="varying")
    out0 = lax.pcast(jnp.zeros((B, S, D), x.dtype), carry_varying, to="varying")

    def tick(carry, t):
        held, outputs = carry
        # stage 0 ingests microbatch t (clamped; junk past M never reaches
        # the last stage before the loop ends)
        start = jnp.minimum(t, n_micro - 1) * mb
        ingest = lax.dynamic_slice_in_dim(x, start, mb, axis=0)
        act = jnp.where(is_first, ingest, held)
        # NOTE: every stage must use the positions of the microbatch it is
        # currently processing — stage s at tick t holds microbatch t-s.
        # With per-row position offsets this matters; slice with the same
        # clamp as the ingest and shift by the stage index.
        pos_start = jnp.clip(t - stage, 0, n_micro - 1) * mb
        pos_mb = lax.dynamic_slice_in_dim(positions, pos_start, mb, axis=0)
        act = _stage_block(
            act, layers_local, pos_mb,
            config=config, attention=attention, remat=remat,
            tp_axis=tp_axis, tp_size=tp_size,
            tp_overlap=tp_overlap, tp_chunks=tp_chunks,
            qm_backend=qm_backend,
        )
        # bank the last stage's finished microbatch t-(P-1)
        out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1) * mb
        prev = lax.dynamic_slice_in_dim(outputs, out_idx, mb, axis=0)
        bank = jnp.where(jnp.logical_and(is_last, t >= n_stages - 1), act, prev)
        outputs = lax.dynamic_update_slice_in_dim(outputs, bank, out_idx, axis=0)
        # hop to the next stage (the last stage's act is not forwarded)
        held = lax.ppermute(act, "pipe", perm)
        return (held, outputs), None

    (_, outputs), _ = lax.scan(
        tick, (held0, out0), jnp.arange(n_micro + n_stages - 1)
    )
    # stack per-stage outputs on a leading pipe axis; caller takes the last
    return outputs[None]


# Megatron split of a stage's layer leaves (leading dim is the stacked
# layer axis, sharded over pipe): column-parallel out dims, row-parallel
# in dims; everything else (norms, MoE leaves) replicated in-stage
_TP_COL = ("attn_q", "attn_k", "attn_v", "mlp_gate", "mlp_up")
_TP_ROW = ("attn_o", "mlp_down")


def _stage_tp(config: LlamaConfig, mesh: Mesh) -> int:
    """In-stage TP degree: the mesh's ``model`` extent when the head /
    hidden dims divide it (and the model is dense); 1 (replicated, with a
    warning) otherwise — matching v1 behavior for odd shapes."""
    tp = mesh.shape.get("model", 1)
    if tp == 1:
        return 1
    ok = (
        not config.n_experts
        and config.n_heads % tp == 0
        and config.n_kv_heads % tp == 0
        and config.hidden_dim % tp == 0
    )
    if not ok:
        logger.warning(
            "pipeline in-stage TP disabled: model axis %d does not divide "
            "heads/kv/hidden (%d/%d/%d) or the model is MoE; stages run "
            "replicated over model",
            tp, config.n_heads, config.n_kv_heads, config.hidden_dim,
        )
        return 1
    return tp


def _pipeline_layer_specs(layers: dict[str, Any], tp: int) -> dict[str, Any]:
    def spec(name: str) -> P:
        if tp > 1 and name in _TP_COL:
            return P("pipe", None, "model")
        if tp > 1 and name in _TP_ROW:
            return P("pipe", "model", None)
        return P("pipe")

    return {name: spec(name) for name in layers}


def _sp_ring_attention(varying: tuple, n_blocks: int):
    """Stage-block attention for PP x SP: the sequence dim arrives
    already sharded over ``seq`` (a manual axis of the enclosing
    shard_map), so the ring body runs DIRECTLY — no nested shard_map —
    with K/V blocks rotating via ppermute("seq")."""
    from finchat_tpu.ops.ring_attention import _ring_body

    def attention(q, k, v, cache, layer_idx):
        out = _ring_body(
            q, k, v, axis="seq", varying=varying, n_blocks=n_blocks,
            causal=True, scale=q.shape[-1] ** -0.5,
        )
        return out, cache

    return attention


def pipeline_forward(
    params: dict[str, Any],
    tokens: jax.Array,  # [B, S] int32
    positions: jax.Array,  # [B, S] int32
    *,
    config: LlamaConfig,
    mesh: Mesh,
    n_micro: int,
    attn_backend: str = "ref",
    remat: bool = True,
    tp_overlap: bool = False,
    tp_chunks: int = 4,
    qm_backend: str | None = None,
) -> jax.Array:
    """Full forward through the stage pipeline; returns logits [B,S,vocab].

    Requires ``n_layers % pipe == 0`` and ``B % n_micro == 0``. Embedding,
    final norm, and the LM head run replicated outside the pipeline (they
    are small next to the layer stack). ``tp_overlap`` (engine.tp_overlap
    / FINCHAT_TP_OVERLAP) switches the in-stage row-parallel outputs from
    the serial layer-end psum to the chunked collective–compute overlap
    schedule (ops/tp_overlap.py) — byte-identical per element, engaged
    only when the model axis is actually active."""
    n_stages = mesh.shape["pipe"]
    assert config.n_layers % n_stages == 0, (config.n_layers, n_stages)
    # in-stage DP: the batch dim shards over `data` INTO the pipeline
    # body when it divides (each data coordinate pipelines its own batch
    # slice; the scan/ppermute/psum transpose sums layer grads over data
    # automatically). Falls back to replicated batch otherwise.
    dp = mesh.shape.get("data", 1)
    if tokens.shape[0] % (dp * n_micro):
        logger.warning(
            "pipeline in-stage DP disabled: batch %d does not split into "
            "data=%d x n_micro=%d; the data axis runs replicated",
            tokens.shape[0], dp, n_micro,
        )
        dp = 1
    assert tokens.shape[0] % (dp * n_micro) == 0, (tokens.shape, dp, n_micro)
    # PP x SP: the sequence dim shards over `seq` into the body when it
    # divides; the stage block then ring-attends (K/V rotate the seq
    # ring) instead of full-sequence attention, so per-device activations
    # are O(S/seq) on top of the microbatch split.
    sp = mesh.shape.get("seq", 1)
    if tokens.shape[1] % sp:
        logger.warning(
            "pipeline in-stage SP disabled: seq len %d not divisible by "
            "seq axis %d; the seq axis runs replicated",
            tokens.shape[1], sp,
        )
        sp = 1
    if sp > 1 and attn_backend != "ref":
        # the SP stage block runs the fp32 ring body directly (it must —
        # the seq dim is already sharded in the manual region); other
        # backends have no seq-sharded stage variant
        logger.warning(
            "pipeline SP stage block uses the ring attention body; "
            "attn_backend=%r is ignored inside the pipeline", attn_backend,
        )
    tp = _stage_tp(config, mesh)
    tp_axis = "model" if tp > 1 else None

    dp_axes = ("data",) if dp > 1 else ()
    seq_axes = ("seq",) if sp > 1 else ()
    x_spec = P(dp_axes or None, "seq" if sp > 1 else None)
    if sp > 1:
        # activations inside the body vary over every engaged axis; the
        # ring accumulators must be born with the same varying set
        act_varying = dp_axes + ("pipe",) + seq_axes + (("model",) if tp > 1 else ())
        attention = _sp_ring_attention(act_varying, sp)
    else:
        attention = make_causal_attention(attn_backend)

    x = params["embed"][tokens]
    layer_specs = _pipeline_layer_specs(params["layers"], tp)
    fn = jax.shard_map(
        partial(
            _pipeline_body,
            config=config, n_micro=n_micro, n_stages=n_stages,
            attention=attention, remat=remat, tp_axis=tp_axis, tp_size=tp,
            tp_overlap=tp_overlap and tp > 1, tp_chunks=tp_chunks,
            qm_backend=qm_backend,
            carry_varying=dp_axes + ("pipe",) + seq_axes,
        ),
        mesh=mesh,
        in_specs=(layer_specs, x_spec, x_spec),
        out_specs=P("pipe", *x_spec),
    )
    stacked = fn(params["layers"], x, positions)  # [pipe, B, S, D]
    x = stacked[-1]

    x = rms_norm(x, params["norm"], config.norm_eps)
    return lm_head(params, x, config=config)


def make_pipeline_train_step(
    config: LlamaConfig,
    optimizer,
    mesh: Mesh,
    *,
    n_micro: int,
    attn_backend: str = "ref",
    remat: bool = True,
    tp_overlap: bool = False,
    tp_chunks: int = 4,
):
    """Jitted train step running the forward through the stage pipeline.

    The backward pass re-traverses the schedule in reverse (scan transpose;
    ppermute transposes to the reverse hop), so gradients for each stage's
    layers accumulate on that stage — no parameter resharding. Params must
    be placed with ``shard_params_for_pipeline``.
    """
    import optax

    from finchat_tpu.train.train_step import TrainState

    def loss_fn(params, tokens):
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        logits = pipeline_forward(
            params, tokens, positions,
            config=config, mesh=mesh, n_micro=n_micro,
            attn_backend=attn_backend, remat=remat,
            tp_overlap=tp_overlap, tp_chunks=tp_chunks,
        )
        targets = tokens[:, 1:]
        ce = optax.softmax_cross_entropy_with_integer_labels(logits[:, :-1, :], targets)
        return ce.mean()

    @partial(jax.jit, donate_argnums=(0,))
    def train_step(state: "TrainState", tokens: jax.Array):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, tokens)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return TrainState(params=params, opt_state=opt_state, step=state.step + 1), loss

    return train_step


def shard_params_for_pipeline(params: dict[str, Any], mesh: Mesh,
                              config: LlamaConfig | None = None) -> dict[str, Any]:
    """Place params with the stacked layer axis sharded over ``pipe`` and
    — when ``config`` is given and divisible — the Megatron dims over
    ``model`` (matching the pipeline's all-manual in_specs exactly, so
    entry incurs no resharding); embed/norm/head replicated."""
    from finchat_tpu.parallel.sharding import shard_params

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    tp = _stage_tp(config, mesh) if config is not None else 1
    shardings: dict[str, Any] = {
        "embed": ns(),
        "layers": {
            name: NamedSharding(mesh, spec)
            for name, spec in _pipeline_layer_specs(params["layers"], tp).items()
        },
        "norm": ns(),
    }
    if "lm_head" in params:
        shardings["lm_head"] = ns()
    return shard_params(params, shardings)
