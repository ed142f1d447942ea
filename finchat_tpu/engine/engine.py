"""Inference engine: compiled prefill/decode step functions over the paged
KV cache.

TPU-first shape discipline (SURVEY §7.3 hard part #2): every jitted entry
point has ONE static shape per (batch-bucket) —

- ``prefill_step``: ``N × prefill_chunk`` tokens — N sequences advance one
  chunk together (batched prefill; a 64-session burst is a handful of
  steps, not 64 serial weight-reads).
  Arbitrary prompt lengths become rounds of fixed-size chunks (chunked
  prefill, SURVEY §5.7a) so there is no bucketing recompile storm;
  exhausted prompts ride later rounds with ``n_valid = 0``.
- ``decode_step``: the full ``max_seqs`` slot batch, every step. Inactive
  slots ride along writing their KV to the trash page.
- ``ragged_mixed_step``: ONE packed ragged dispatch advancing every
  prefilling sequence a chunk, every decoding slot a token and every
  spec-decode slot a (1+Kd)-token verify block — rows of a PACKED token
  buffer (ops/ragged_paged_attention.py), each carrying its own length,
  page list, and sampling params, with on-device sampling preserved
  throughout. The scheduler's mixed path (engine.mixed_step config,
  default on) cuts a coexisting iteration from two-or-more serialized
  model dispatches to one, with no per-mode demotions (ISSUE 10; PR 4's
  padded ``[rows, chunk]`` buffer demoted on spec/constrained work and
  paid dense decode-row compute per padded column).

State is donated on every call and the KV cache is updated IN PLACE by the
Pallas append kernel (ops/kv_append.py) on the decode path — XLA's scatter
would copy the multi-GB cache every token (measured ~22 ms/step, round 4).
"""

from __future__ import annotations

import contextlib
import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from finchat_tpu.engine.kv_cache import (
    PagedKVCache,
    scatter_kv_chunk,
)
from finchat_tpu.engine.sampler import distribution, draw, sample, verify_draft
from finchat_tpu.models.llama import (
    WINDOW,
    LlamaConfig,
    forward,
    lm_head,
    mtp_block,
    mtp_logits,
    trunk_logits,
)
from finchat_tpu.models.mla import LatentInputs
from finchat_tpu.models.ssm import SsmRows
from finchat_tpu.ops import ssm_step
from finchat_tpu.ops.latent_attention import LatentShape, decode_form, index_form
from finchat_tpu.ops.dispatch import paged_attention
from finchat_tpu.utils.config import EngineConfig
from finchat_tpu.utils.logging import get_logger

logger = get_logger(__name__)


# What a kind of per-row memory beyond paged K/V cannot be served with: kind ->
# option -> why, in a phrase (``InferenceEngine._refuse_what_a_kind_cannot_carry``).
# A new kind adds a row here, not a method.
NOT_CARRIED: dict[str, dict[str, str]] = {
    # config.has_state: a Mamba-2 mixer, Mamba-1 or linear-attention layers
    "recurrent state": {
        "engine.spec_tokens": "rejected drafts rewind a row, and no page holds the state",
        "engine.kv_sink_pages / engine.kv_window_pages": "they re-lay a row's pages beside a state that keeps every token",
        "mesh.* > 1": "the state has no sharding rule",
    },
    # config.kv_lora_rank: a latent row and an index key a token in the page pool
    "latent pages": {
        "engine.kv_quant": "int8 pages keep a scale a KV head",
        "model.quant": "the absorbed projections have no quantized form",
        "engine.spec_tokens": "verify_step attends K/V heads",
        "engine.kv_sink_pages / engine.kv_window_pages": "they re-lay a row's pages under the indexer's selection",
        "mesh.* > 1": "the latent pool has no sharding rule",
    },
    # config.window: sliding-window layers' pages in a second pool, a bounded
    # page list a row kept by the host (kv_cache.WindowPager: window / page_size
    # + 2 pages a row, 3 where the window is ONE page) — as wide as the window
    # layers' own K/V heads where attention's shape is a kind's (config.attn_kinds)
    "window pages": {
        "engine.spec_tokens": "verify_step walks one pool, and a rejected draft rewinds a row",
        "mesh.* > 1": "the window pool (and a kind's own k / v stacks) has no sharding rule",
    },
    # config.mtp_layers: a next-token-prediction module that drafts on the
    # device; its block's latent pages, a row's pending draft and the hidden
    # state it waits on ride DecodeState (recurrent state, window pages and an
    # indexer are refused with it by LlamaConfig itself)
    "a drafting module": {
        "engine.kv_quant": "the module's block keeps latent rows",
        "engine.spec_tokens": "host-drafted rows go through verify_step, which runs no module; "
                              "the model's own drafts are verified in decode_step",
        "engine.kv_sink_pages / engine.kv_window_pages": "a rejected draft rewinds a row by position",
        "mesh.* > 1": "the draft state has no sharding rule",
    },
}


def round_up_pow2(n: int) -> int:
    """The batch/shape padding policy shared by the scheduler's prefill
    rounds, warmup's variant enumeration, and ring-prefill length buckets —
    ONE rule so startup warmup always covers what serving dispatches."""
    p = 1
    while p < n:
        p *= 2
    return p


@jax.tree_util.register_dataclass
@dataclass
class DecodeState:
    """Device-resident engine state (a pytree; all leaves are arrays).

    ``k_scales``/``v_scales`` are the int8-KV-cache scale arrays
    (kv_cache.py); (1,1,1,1) placeholders when kv_quant is off so the
    pytree structure is mode-independent.

    ``kv_gaps`` is the bounded-KV compaction offset per slot (ISSUE 15;
    kv_cache.BoundedKVPolicy): tokens the eviction policy has dropped from
    the slot's page list, always a whole-page multiple, 0 for unbounded
    rows. ``context_lens`` stays ABSOLUTE (it feeds rotary positions);
    every KV write offset and attention mask runs at the COMPACTED
    position ``absolute - kv_gaps[slot]``, so the surviving sink+window
    pages pack the front of the page list and an evicted page simply
    stops being referenced. All zeros reduces every compacted expression
    to the legacy absolute one bit-for-bit."""

    # [L, P, page_size, Hkv*hd] (model dtype, or int8); for a model with
    # latent attention (config.kv_row_widths) the first array holds the latent
    # rows [.., latent_row] and the second the indexer's key rows [.., Di]
    k_pages: Array
    v_pages: Array
    k_scales: Array  # [L, P, scale_rows, page_size] fp32 (or (1,1,1,1))
    v_scales: Array
    page_table: Array  # [max_seqs, max_pages_per_seq] int32 (0 = trash)
    context_lens: Array  # [max_seqs] int32 — ABSOLUTE tokens seen (rotary)
    last_tokens: Array  # [max_seqs] int32 — next decode input per slot
    kv_gaps: Array  # [max_seqs] int32 — evicted tokens (bounded KV; 0 = none)
    rng: Array
    # the second kind of per-row state (a model with a mixer, models/ssm.py,
    # or with linear-attention layers, models/gdn.py): held by SLOT, not by
    # page, and never shared — a row that starts from a shared head starts
    # from a COPY of the head's snapshot. (1, ...) placeholders for a model
    # without either, as k_scales has. The two kinds of cache have their own
    # depths: the pool the layers' that own pages (config.n_attn_layers),
    # these the layers' that carry state (config.n_state_layers)
    ssm_state: Array  # [Ls, max_seqs, *config.stored_state_shape] float32 — the recurrence's state
    conv_state: Array  # [Ls, max_seqs, K-1, C] float32 — the conv's last inputs
    # the second POOL (a model with sliding-window layers, config.window; None
    # for every other model: no leaf, no operand): the window layers' pages,
    # each slot's bounded page list of them from column 0 and the tokens
    # before it — kv_gaps' compacted coordinates for ONE kind of layer, kept
    # by the host (kv_cache.WindowPager) and uploaded when a list changes
    win_k_pages: Array | None = None  # [Lw, Pw, page_size, Hkv*hd]
    win_v_pages: Array | None = None
    win_table: Array | None = None  # [max_seqs, window / page_size + 2] int32
    win_gaps: Array | None = None  # [max_seqs] int32
    # a model that drafts with its next-token-prediction module (config.
    # mtp_layers; None for every other model: no leaf, no operand). The module's
    # row for the pair (h_{i-1}, t_i) lies in slot i of the pool's LAST layer, so
    # at a step's start the slots below ``context_lens`` are written, and the
    # slot AT it too where a draft is pending (the draft came out of it)
    mtp_hidden: Array | None = None  # [max_seqs, D] — the trunk's pre-norm output at context_lens - 1
    draft_tokens: Array | None = None  # [max_seqs] int32 — the token drafted for context_lens + 1
    draft_probs: Array | None = None  # [max_seqs, vocab] float32 — what it was drawn from
    has_draft: Array | None = None  # [max_seqs] bool — one is pending


def create_state(
    config: LlamaConfig, engine_cfg: EngineConfig, max_pages_per_seq: int,
    kv_quant: str = "",
) -> DecodeState:
    cache = PagedKVCache.create(
        config, engine_cfg.num_pages, engine_cfg.page_size, kv_quant=kv_quant
    )
    return DecodeState(
        k_pages=cache.k_pages,
        v_pages=cache.v_pages,
        k_scales=cache.k_scales,
        v_scales=cache.v_scales,
        page_table=jnp.zeros((engine_cfg.max_seqs, max_pages_per_seq), jnp.int32),
        context_lens=jnp.zeros((engine_cfg.max_seqs,), jnp.int32),
        last_tokens=jnp.zeros((engine_cfg.max_seqs,), jnp.int32),
        kv_gaps=jnp.zeros((engine_cfg.max_seqs,), jnp.int32),
        rng=jax.random.key(engine_cfg.max_seqs),
        **_ssm_leaves(config, engine_cfg.max_seqs),
        **_window_leaves(config, engine_cfg),
        **_draft_leaves(config, engine_cfg.max_seqs),
    )


def _draft_leaves(config: LlamaConfig, max_seqs: int) -> dict[str, Array]:
    if not config.mtp_layers:
        return {}
    return {"mtp_hidden": jnp.zeros((max_seqs, config.dim), config.dtype),
            "draft_tokens": jnp.zeros((max_seqs,), jnp.int32),
            "draft_probs": jnp.zeros((max_seqs, config.vocab_size), jnp.float32),
            "has_draft": jnp.zeros((max_seqs,), bool)}


def window_pool_pages(config: LlamaConfig, engine_cfg: EngineConfig) -> int:
    """Pages of the window layers' pool: every slot's bound, four shared
    heads' beside (the system and tool prompts', and their successors while a
    refresh retires them), and the trash page — what no traffic can exhaust."""
    from finchat_tpu.engine.kv_cache import window_pages_per_row

    per_row = window_pages_per_row(config.window, engine_cfg.page_size)
    return (engine_cfg.max_seqs + 4) * per_row + 1


def _window_leaves(config: LlamaConfig, engine_cfg: EngineConfig) -> dict[str, Array]:
    if not config.window:
        return {}
    from finchat_tpu.engine.kv_cache import window_pages_per_row

    pool = PagedKVCache.create_window(
        config, window_pool_pages(config, engine_cfg), engine_cfg.page_size)
    per_row = window_pages_per_row(config.window, engine_cfg.page_size)
    return {"win_k_pages": pool.k_pages, "win_v_pages": pool.v_pages,
            "win_table": jnp.zeros((engine_cfg.max_seqs, per_row), jnp.int32),
            "win_gaps": jnp.zeros((engine_cfg.max_seqs,), jnp.int32)}


def _ssm_leaves(config: LlamaConfig, max_seqs: int) -> dict[str, Array]:
    c = config
    if not c.has_state:
        return {"ssm_state": jnp.zeros((1, 1, 1, 1, 1), jnp.float32),
                "conv_state": jnp.zeros((1, 1, 1, 1), jnp.float32)}
    return {
        "ssm_state": jnp.zeros((c.n_state_layers, max_seqs, *c.stored_state_shape), jnp.float32),
        "conv_state": jnp.zeros((c.n_state_layers, max_seqs, *c.conv_shape), jnp.float32),
    }


def _forward_cached(params, state: DecodeState, tokens: Array, positions: Array, *,
                    config: LlamaConfig, attention, ssm_rows: SsmRows | None,
                    **kw) -> tuple[Array, DecodeState] | tuple[Array, DecodeState, Array]:
    """``forward`` over the state's caches; returns its output and the state
    with the caches it advanced: the K/V pool and, for a model with a mixer,
    the recurrent state (``ssm_rows`` says whose state each batch row is).
    With ``moe_live`` among ``kw``, ``forward``'s count comes back third."""
    cache = (state.k_pages, state.v_pages, state.k_scales, state.v_scales)
    if config.window:  # (the FULL layer's pool, the WINDOW layers')
        cache = (cache, (state.win_k_pages, state.win_v_pages, state.k_scales, state.v_scales))
    if not config.has_state:
        out, cache, *count = forward(params, tokens, positions, config=config,
                                     attention=attention, cache=cache, **kw)
        ssm = (state.ssm_state, state.conv_state)
    else:
        out, (cache, ssm), *count = forward(
            params, tokens, positions, config=config, attention=attention,
            cache=cache, ssm_cache=(state.ssm_state, state.conv_state),
            ssm_rows=ssm_rows, **kw)
    window = {}
    if config.window:
        cache, (win_k, win_v, _ks, _vs) = cache
        window = {"win_k_pages": win_k, "win_v_pages": win_v}
    k_pages, v_pages, k_scales, v_scales = cache
    return out, dataclasses.replace(
        state, k_pages=k_pages, v_pages=v_pages, k_scales=k_scales,
        v_scales=v_scales, ssm_state=ssm[0], conv_state=ssm[1], **window), *count


def _attention_by_kind(full, window, config: LlamaConfig):
    """The attention callback of a model with sliding-window layers — a
    ``layer_plan``'s (``sambay.attention``) or a ``layer_pattern``'s
    (``llama._layer``): a FULL layer writes and reads its pool through
    ``full``, a plan's CROSS layer reads it there and writes nothing (``k``
    None), a WINDOW layer goes through ``window`` over the second pool and the
    slots' bounded page lists. The scopes are what the capture's readers look
    for: ``swa_attention`` around a window layer; ``yoco_attention`` around the
    ONE cache a plan's full and cross layers walk, while a pattern's full
    layers, each walking pages of its own, stay under ``full``'s own scopes
    (``paged_attention``), as every model's without window layers. Each of the
    two callbacks is built for ITS kind's K/V heads (``LlamaConfig.attn_kind``)."""
    def attention(q: Array, k: Array | None, v: Array | None, cache: Any, layer_idx: Array,
                  kind: str, sink: Array | None = None):
        pool, win_pool = cache
        if kind == WINDOW:
            with jax.named_scope("swa_attention"):
                # (``sink``: the layer's softmax sinks, where the kind has them)
                out, win_pool = window(q, k, v, win_pool, layer_idx,
                                       **({} if sink is None else {"sink": sink}))
        elif config.layer_plan:
            with jax.named_scope("yoco_attention"):
                out, pool = full(q, k, v, pool, layer_idx)
        else:
            out, pool = full(q, k, v, pool, layer_idx)
        return out, (pool, win_pool)

    return attention


@partial(jax.jit, donate_argnums=(0, 1))
def _ssm_clear_slots(ssm_state: Array, conv_state: Array, keep: Array):
    """Zero the recurrent state of every slot not in ``keep`` [max_seqs], in
    place and at one shape whatever the count (an eager ``.at[].set`` would
    copy the whole state and compile per count)."""
    return (jnp.where(keep[None, :, None, None, None], ssm_state, 0.0),
            jnp.where(keep[None, :, None, None], conv_state, 0.0))


@partial(jax.jit, static_argnames=("config",), donate_argnums=(0, 1))
def _ssm_load_slot(ssm_state: Array, conv_state: Array, slot: Array, snap: tuple, *,
                   config: LlamaConfig):
    """Copy a snapshot (one slot's state ``[Ls, *config.state_shape]``, every
    layer) into ``slot``, as the device holds it."""
    return (jax.lax.dynamic_update_index_in_dim(
                ssm_state, config.state_to_stored(snap[0]), slot, 1),
            jax.lax.dynamic_update_index_in_dim(conv_state, snap[1], slot, 1))


@partial(jax.jit, static_argnames=("config",))
def _ssm_read_slot(ssm_state: Array, conv_state: Array, slot: Array, *, config: LlamaConfig):
    """One slot's state, every layer, as the recurrence writes it
    (``[Ls, *config.state_shape]``: a snapshot does not depend on how the
    device lays the state out) and its conv tail."""
    return (config.state_to_logical(
                jax.lax.dynamic_index_in_dim(ssm_state, slot, 1, keepdims=False)),
            jax.lax.dynamic_index_in_dim(conv_state, slot, 1, keepdims=False))


def _latent_shape(config: LlamaConfig, module: bool = False) -> LatentShape | None:
    """What the attention callbacks of a model with latent attention need of
    its config (None for every other model). ``module``: the callback is the
    next-token-prediction module's block's, whose slot 0 holds nothing."""
    if not config.kv_lora_rank:
        return None
    return LatentShape(config.kv_lora_rank, config.index_topk,
                       config.attention_scale or config.head_dim ** -0.5,
                       **({"skip": 1} if module else {}))


def _latent_attention(write, page_rows: Array, start: Array, n_valid: Array,
                      page_size: int, latent: LatentShape, backend: str,
                      rows: SsmRows | None = None,
                      shared: tuple[Array, Array] | None = None, pair: bool = False):
    """The ``LatentAttentionFn`` of a step: ``write(row, idx_k, cache,
    layer_idx)`` puts the chunk's rows into the pool; then row ``n``'s
    ``n_valid[n]`` queries, at the compacted positions ``start[n] ..``, attend
    over its pages ``page_rows[n]``. With ``rows`` the tokens arrive PACKED
    ``[1, T]`` (the ragged step): row ``n``'s lie from ``rows.pack[0][n]`` on.
    One-token rows take the form ``latent_attention.decode_form`` reads off
    ``backend`` and the table's width (``shared``: the decode step's
    ``shared_head``, for the walk). ``pair``: the decode step of a model that
    drafts — two tokens a row, ONE walk (``latent_attention.pair_attention``)."""
    from finchat_tpu.ops.latent_attention import packed_attention, rows_attention

    def attention(x: LatentInputs, cache: Any, layer_idx: Array):
        # (no indexer: the second array's one lane tile, LlamaConfig.kv_row_widths)
        idx_k = x.idx_k if x.idx_k is not None else jnp.zeros(
            (*x.row.shape[:2], cache[1].shape[-1]), x.row.dtype)
        cache = write(x.row, idx_k, cache, layer_idx)
        kw = dict(page_size=page_size, shape=latent, backend=backend)
        layer = layer_idx.reshape(())
        if rows is None:
            out, selected = rows_attention(x.q, x.idx_q, x.idx_w, cache[0], cache[1], layer,
                                           page_rows, start, n_valid, shared=shared,
                                           **({"own": x.row} if pair else {}), **kw)
        else:
            out, selected = packed_attention(
                *(None if a is None else a[0] for a in (x.q, x.idx_q, x.idx_w)),
                cache[0], cache[1], layer, page_rows, rows.pack[0], start, n_valid,
                width=rows.width, **({"own": x.row[0]} if pair else {}), **kw)
            out = out[None]
        return out, cache, selected

    return attention


def _paged_attention_fn(
    page_table: Array, start_pos: Array, n_valid: Array,
    page_size: int, n_kv: int, attn_backend: str,
    inplace_append: bool = False,
    decode: bool = False,
    scale: float | None = None,
    latent: LatentShape | None = None,
    window: int = 0,
    pair: bool = False,
):
    """Build the model's attention callback for paged prefill/decode.

    ``page_table`` [B, max_pages], ``start_pos`` [B] (absolute position of
    the first query token), ``n_valid`` [B] (real tokens in this chunk; 0
    for inactive decode slots). The callback receives the FULL-depth cache
    (carried through the layer scan) plus the layer index.

    ``inplace_append`` forces the in-place append (a token's slab read,
    patched and written a row: ``ops/kv_append.py``) for C > 1
    (one single-token append per chunk position) — used by the speculative
    verify step, whose few-token chunks would otherwise pay the scatter's
    full-cache copy every step, exactly what the append kernel exists to
    avoid.

    ``decode`` (one token a slot, ``n_valid`` the active mask): the batch's
    shared head — rows holding the same physical pages at the head of their
    tables, as rows admitted on one prefix entry do — is read off these three
    arrays HERE, once a step and outside the layer scan, and the kernel reads
    those pages once for all of its rows (``ops.paged_attention``).

    ``scale``: the model's softmax scale (``LlamaConfig.attention_scale``;
    None = head_dim ** -0.5), handed to the kernel as it is.

    ``latent`` (a model with latent attention, ``_latent_shape``): the
    callback is a ``LatentAttentionFn`` (models/mla.py) — it writes the
    token's latent row into the first paged array and its index key into the
    second, the same append or scatter, and attends by
    ops/latent_attention.py over the indexer's selection. ``pair`` (with
    ``decode``; a model that drafts): TWO tokens a slot, ``n_valid`` 0, 1 or 2.
    """
    interpret = attn_backend == "pallas-interpret"
    if latent is not None:
        def write(row: Array, idx_k: Array, cache: Any, layer_idx: Array):
            k_pages, v_pages, k_scales, v_scales = cache
            B, C = row.shape[:2]
            if C == 1 and attn_backend != "ref":
                from finchat_tpu.ops.kv_append import paged_kv_append

                with jax.named_scope("kv_append"):
                    k_pages, v_pages = paged_kv_append(
                        jnp.concatenate([row, idx_k], axis=-1), k_pages, v_pages, page_table,
                        start_pos, n_valid, layer_idx.reshape(1), page_size=page_size,
                        interpret=interpret)
                return k_pages, v_pages, k_scales, v_scales
            if pair and attn_backend != "ref":  # a token and its draft: two appends a row
                from finchat_tpu.ops.kv_append import paged_kv_append

                with jax.named_scope("kv_append"):
                    for i in range(C):
                        k_pages, v_pages = paged_kv_append(
                            jnp.concatenate([row[:, i:i + 1], idx_k[:, i:i + 1]], axis=-1),
                            k_pages, v_pages, page_table, start_pos + i,
                            (i < n_valid).astype(jnp.int32), layer_idx.reshape(1),
                            page_size=page_size, interpret=interpret)
                return k_pages, v_pages, k_scales, v_scales
            with jax.named_scope("kv_scatter"):
                return _scatter_kv(cache, row[:, :, None], idx_k[:, :, None], page_table,
                                   start_pos, n_valid, page_size, layer_idx, 1)

        shared = None
        if decode and decode_form(attn_backend, page_table.shape[1] * page_size,
                                  latent.topk) == "walk":
            from finchat_tpu.ops.paged_attention import shared_head

            with jax.named_scope("mla_attention"):  # once a step, outside the layer scan
                # (a pair's walk ends on its FIRST token: what both tokens see)
                seen = jnp.minimum(n_valid, 1) if pair else n_valid
                shared = shared_head(page_table, start_pos + seen, page_size, n_valid > 0)
        return _latent_attention(write, page_table, start_pos, n_valid, page_size, latent,
                                 attn_backend, shared=shared, pair=pair)
    shared = None
    if decode and attn_backend != "ref" and window:
        shared = (jnp.zeros((page_table.shape[0],), jnp.int32), jnp.zeros((2,), jnp.int32))
    elif decode and attn_backend != "ref":
        from finchat_tpu.ops.paged_attention import shared_head

        with jax.named_scope("paged_attention"):
            shared = shared_head(page_table, start_pos + n_valid, page_size,
                                 n_valid > 0)
    window_kw = {"window": window} if window else {}

    def attention(q: Array, k: Array, v: Array, cache: Any, layer_idx: Array,
                  sink: Array | None = None):
        k_pages, v_pages, k_scales, v_scales = cache
        quantized = k_pages.dtype == jnp.int8  # static under trace
        B, C = q.shape[:2]
        layer = layer_idx.reshape(1)
        if k is None:
            pass  # a CROSS layer: the pages are another layer's, written already
        elif (C == 1 or inplace_append) and attn_backend != "ref":
            # decode / spec verify: in-place appends of a token's slab (no
            # cache copy); token i of the chunk is valid iff i < n_valid
            with jax.named_scope("kv_append"):
                for i in range(C):
                    kv_new = jnp.concatenate(
                        [k[:, i].reshape(B, 1, -1), v[:, i].reshape(B, 1, -1)],
                        axis=-1,
                    )
                    i_valid = (i < n_valid).astype(jnp.int32)
                    if quantized:
                        from finchat_tpu.ops.kv_append import paged_kv_append_q8

                        k_pages, v_pages, k_scales, v_scales = paged_kv_append_q8(
                            kv_new, k_pages, v_pages, k_scales, v_scales,
                            page_table, start_pos + i, i_valid, layer,
                            page_size=page_size, n_kv=n_kv, interpret=interpret,
                        )
                    else:
                        from finchat_tpu.ops.kv_append import paged_kv_append

                        k_pages, v_pages = paged_kv_append(
                            kv_new, k_pages, v_pages, page_table, start_pos + i,
                            i_valid, layer, page_size=page_size, interpret=interpret,
                        )
        else:
            # prefill chunk (or jnp reference path): XLA scatter — one
            # cache copy amortized over the whole batched chunk
            with jax.named_scope("kv_scatter"):
                k_pages, v_pages, k_scales, v_scales = _scatter_kv(
                    (k_pages, v_pages, k_scales, v_scales), k, v,
                    page_table, start_pos, n_valid, page_size, layer_idx, n_kv,
                )
        # (a window layer's walk reads under its caller's ``swa_attention`` alone, so
        # that ``paged_attention`` names the walks over the pool that grows with the context)
        with contextlib.nullcontext() if window else jax.named_scope("paged_attention"):
            out = paged_attention(
                q, k_pages, v_pages, page_table, start_pos, start_pos + n_valid,
                layer, page_size=page_size, n_kv=n_kv, backend=attn_backend,
                k_scales=k_scales if quantized else None,
                v_scales=v_scales if quantized else None,
                shared=shared, scale=scale, **window_kw,
                **({} if sink is None else {"sink": sink}),
            )
        return out, (k_pages, v_pages, k_scales, v_scales)

    return attention


def _pool(state: DecodeState) -> tuple[Array, Array, Array, Array]:
    return state.k_pages, state.v_pages, state.k_scales, state.v_scales


def _shifted(hidden: Array, first: Array, carried: Array) -> Array:
    """The hidden state BELOW each token of a step: ``hidden`` [B, S, D] moved
    on by one, with ``carried`` [B, S, D] (the slot's ``mtp_hidden``) where
    ``first`` [B, S] marks a row's first token of the step."""
    B, S, D = hidden.shape
    before = jnp.roll(hidden.reshape(B * S, D), 1, axis=0).reshape(B, S, D)
    return jnp.where(first[..., None], carried.astype(hidden.dtype), before)


def _module_rows(params, state: DecodeState, attention, below: Array, tokens: Array,
                 positions: Array, *, config: LlamaConfig, qm_backend: str,
                 attn_backend: str, **count) -> tuple:
    """The next-token-prediction module behind a step's trunk: the pair of
    slot ``i`` is (``below``: the trunk's pre-norm output at ``i - 1``, the
    token at ``i``), and the module's rows land in the slots of the tokens they
    consume, through ``attention`` (the trunk's callback over the pool's last
    layer, slot 0 masked). Returns the state with the pool it wrote, the
    block's output [B, S, D] and, with ``moe_live`` among ``count``, the
    block's counts."""
    out, pool, *counts = mtp_block(
        params, below, tokens, positions, config=config, attention=attention,
        cache=_pool(state), qm_backend=qm_backend, moe_backend=attn_backend, **count)
    k_pages, v_pages, k_scales, v_scales = pool
    return (dataclasses.replace(state, k_pages=k_pages, v_pages=v_pages, k_scales=k_scales,
                                v_scales=v_scales), out, *counts)


def _verify_pairs(params, hidden: Array, drafts: tuple[Array, Array, Array], rng: Array,
                  sampling: tuple[Array, Array, Array], *, config: LlamaConfig, qm_backend: str):
    """What a row emits from the trunk's pre-norm outputs ``hidden`` [R, 2, D]
    at its token and its draft: ``drafts`` = (the draft, the distribution it
    was drawn from, whether the row has one), verified against the row's
    sampling distribution at the first position (``sampler.verify_draft``; a
    row without one draws from it), and a token from the second position's for
    a row whose draft was kept. Returns ``(logits [R, 2, vocab], kept, first,
    second, the [R, 2] token array a step hands back — its second column the
    second token, or -2: a draft was verified and not kept, or -1: none was —,
    the keys left: the state's and the next draft's)``."""
    tokens, probs, has = drafts
    # kept as ONE array: each of the softmax's passes over it would otherwise
    # run the head's matmul again (0.7 ms a pass at the cell's size, four of them)
    logits = jax.lax.optimization_barrier(
        trunk_logits(params, hidden, config=config, qm_backend=qm_backend))
    rng, k_verify, k_second, k_draft = jax.random.split(rng, 4)
    kept, first = verify_draft(distribution(logits[:, 0], *sampling), probs, tokens, has, k_verify)
    second = draw(distribution(logits[:, 1], *sampling), k_second)
    emitted = jnp.stack([first, jnp.where(kept, second, jnp.where(has, -2, -1))], axis=1)
    return logits, kept, first, second, emitted.astype(jnp.int32), rng, k_draft


def _next_draft(params, last_pair: Array, key: Array, sampling: tuple[Array, Array, Array], *,
                config: LlamaConfig, qm_backend: str) -> tuple[Array, Array]:
    """``(the draft, the distribution it is drawn from)`` off the module's
    output at a row's last pair ``last_pair`` [R, D]."""
    q_next = distribution(jax.lax.optimization_barrier(
        mtp_logits(params, last_pair, config=config, qm_backend=qm_backend)), *sampling)
    return draw(q_next, key), q_next


def _carry_on(state: DecodeState, slots: Array, advanced: Array, last: Array,
              **drafts) -> DecodeState:
    """``slots`` [R] whose rows ``advanced`` [R] leave ``last`` [R, D] — their
    last token's pre-norm output — as the hidden state the module's next pair
    waits on, and ``drafts`` (``has_draft`` / ``draft_tokens`` / ``draft_probs``
    [R, ...]; none: no pending draft). A padding row's slot is dropped."""
    at = jnp.where(advanced, slots, state.mtp_hidden.shape[0])
    pending = {"has_draft": jnp.zeros_like(advanced), **drafts}
    return dataclasses.replace(
        state, mtp_hidden=state.mtp_hidden.at[at].set(
            last.astype(state.mtp_hidden.dtype), mode="drop"),
        **{name: getattr(state, name).at[at].set(value, mode="drop")
           for name, value in pending.items()})


@partial(jax.jit, static_argnames=("config", "page_size", "attn_backend", "qm_backend"), donate_argnums=(1,))
def prefill_step(
    params: dict[str, Any],
    state: DecodeState,
    tokens: Array,  # [N, C] — one chunk of N sequences' prompts
    slots: Array,  # [N] int32
    start_pos: Array,  # [N] int32 — absolute position of tokens[i, 0]
    n_valid: Array,  # [N] int32 — real tokens in this chunk per sequence
    *,
    config: LlamaConfig,
    page_size: int,
    attn_backend: str = "ref",
    qm_backend: str = "ref",
) -> tuple[DecodeState, Array]:
    """Run one prefill chunk for N sequences; returns (state,
    last-valid-token logits [N, vocab])."""
    N, C = tokens.shape
    positions = start_pos[:, None] + jnp.arange(C)[None, :]  # [N, C] — rotary
    page_rows = state.page_table[slots]  # [N, max_pages]

    # KV writes and masking run COMPACTED (bounded KV, ISSUE 15): a row
    # whose policy evicted kv_gaps[slot] tokens writes this chunk
    # kv_gaps[slot] positions earlier in its (compacted) page list, while
    # the rotary positions above stay absolute. Zero gaps = identity.
    attention = _paged_attention_fn(
        page_rows, start_pos - state.kv_gaps[slots], n_valid,
        page_size, config.attn_kind().n_kv_heads, attn_backend, scale=config.attention_scale,
        latent=_latent_shape(config),
    )
    if config.window:
        attention = _attention_by_kind(attention, _paged_attention_fn(
            state.win_table[slots], start_pos - state.win_gaps[slots], n_valid,
            page_size, config.attn_kind(WINDOW).n_kv_heads, attn_backend,
            scale=config.attention_scale, window=config.window), config)
    # hidden states only, then project just each sequence's last valid row:
    # full-chunk fp32 logits would be [N, C, vocab] — 4.2 GB at
    # 64 x 128 x 128256 (an 8B model) — vs 33 MB for [N, vocab]
    # a mixer's state: each row starts from its slot's and leaves its last
    # state there, so a prompt's chunks carry it from round to round
    hidden, state = _forward_cached(
        params, state, tokens, positions,
        config=config, attention=attention,
        ssm_rows=SsmRows(slots, n_valid, backend=attn_backend),
        return_hidden=True, qm_backend=qm_backend, moe_backend=attn_backend,
        **({"prenorm": True} if config.mtp_layers else {}),
    )
    last_hidden = jnp.take_along_axis(
        hidden, jnp.maximum(n_valid - 1, 0)[:, None, None], axis=1
    )[:, 0]  # [N, D]
    if config.mtp_layers:
        # the module's rows for every prompt token, in the same slots
        last_logits = trunk_logits(params, last_hidden, config=config, qm_backend=qm_backend)
        state, _out = _module_rows(
            params, state, _paged_attention_fn(
                page_rows, start_pos - state.kv_gaps[slots], n_valid, page_size,
                config.n_kv_heads, attn_backend, latent=_latent_shape(config, module=True)),
            _shifted(hidden, jnp.broadcast_to(jnp.arange(C)[None, :] == 0, (N, C)),
                     jnp.broadcast_to(state.mtp_hidden[slots][:, None], hidden.shape)),
            tokens, positions, config=config, qm_backend=qm_backend, attn_backend=attn_backend)
        state = _carry_on(state, slots, n_valid > 0, last_hidden)
    else:
        last_logits = lm_head(params, last_hidden, config=config,
                              qm_backend=qm_backend)  # [N, vocab]

    new_state = dataclasses.replace(
        state, context_lens=state.context_lens.at[slots].add(n_valid),
    )
    return new_state, last_logits


def _scatter_kv(cache, k, v, page_table, start_pos, n_valid, page_size,
                layer_idx, n_kv):
    """Write one chunk's K/V into the paged cache (XLA scatter),
    dispatching on the cache dtype — the ONE place the int8-vs-native
    write choice lives for the scatter paths (chunked prefill, ring
    prefill, ring segments)."""
    k_pages, v_pages, k_scales, v_scales = cache
    if k_pages.dtype == jnp.int8:
        from finchat_tpu.engine.kv_cache import scatter_kv_chunk_q8

        return scatter_kv_chunk_q8(
            k_pages, v_pages, k_scales, v_scales, k, v,
            page_table, start_pos, n_valid, page_size, layer_idx, n_kv,
        )
    k_pages, v_pages = scatter_kv_chunk(
        k_pages, v_pages, k, v, page_table, start_pos, n_valid,
        page_size, layer_idx,
    )
    return k_pages, v_pages, k_scales, v_scales


def _ring_prefill_attention_fn(mesh, page_table: Array, start_pos: Array, n_valid: Array,
                               page_size: int, n_kv: int, sp_mode: str = "ring"):
    """Attention callback for the seq-sharded long-prompt prefill: SP
    attention over the ``seq`` mesh axis for the compute — ring (K/V blocks
    rotate the ICI ring) or Ulysses (all-to-all head scatter, SURVEY
    §5.7d) per ``sp_mode`` — and an XLA scatter for the cache write (one
    cache copy amortized over the WHOLE prompt)."""

    def attention(q: Array, k: Array, v: Array, cache: Any, layer_idx: Array):
        k_pages, v_pages, k_scales, v_scales = cache
        if sp_mode == "ulysses":
            from finchat_tpu.ops.ulysses import ulysses_attention

            out = ulysses_attention(
                q, k, v, mesh=mesh, axis="seq", head_axis="model", causal=True
            )
        else:
            from finchat_tpu.ops.ring_attention import ring_attention

            out = ring_attention(
                q, k, v, mesh=mesh, axis="seq", head_axis="model", causal=True
            )
        cache = _scatter_kv(
            (k_pages, v_pages, k_scales, v_scales), k, v,
            page_table, start_pos, n_valid, page_size, layer_idx, n_kv,
        )
        return out, cache

    return attention


def _ring_segment_attention_fn(mesh, page_table: Array, prefix_pages: int,
                               start_pos: Array, n_valid: Array,
                               page_size: int, n_kv: int,
                               sp_mode: str = "ring"):
    """Attention callback for ONE SEGMENT of a chunked seq-sharded
    prefill: the segment's Q/K/V SP-attend over the ``seq`` axis — ring
    or Ulysses per ``sp_mode`` — while the ALREADY-CACHED earlier
    segments are gathered from their pages and folded into the
    online-softmax carry (ops/ring_attention.py
    ``ring_attention_with_prefix`` / ops/ulysses.py
    ``ulysses_attention_with_prefix``). This is what lets the scheduler
    run a long SP prefill in rounds interleaved with decode steps —
    killing the every-stream stall of the monolithic path — without
    losing cross-segment attention."""

    def attention(q: Array, k: Array, v: Array, cache: Any, layer_idx: Array):
        from finchat_tpu.engine.kv_cache import gather_kv_any
        if sp_mode == "ulysses":
            from finchat_tpu.ops.ulysses import (
                ulysses_attention_with_prefix as attn_with_prefix,
            )
        else:
            from finchat_tpu.ops.ring_attention import (
                ring_attention_with_prefix as attn_with_prefix,
            )

        k_pages, v_pages, k_scales, v_scales = cache
        lay = jnp.asarray(layer_idx, jnp.int32).reshape(())
        # the GATHER is bounded to the static prefix-page bucket (folding
        # max_pages every segment would cost O(segments x max_seq_len));
        # the SCATTER below keeps the full row — the segment's own pages
        # lie past the prefix
        kp, vp = gather_kv_any(
            k_pages, v_pages, k_scales, v_scales,
            page_table[:, :prefix_pages], page_size, lay, n_kv, dtype=q.dtype,
        )
        out = attn_with_prefix(
            q, k, v, kp, vp, start_pos[0],
            mesh=mesh, axis="seq", head_axis="model", causal=True,
        )
        # cache write AFTER the gather: the prefix fold must see only
        # earlier segments (positions < start_pos); this segment's own
        # tokens enter attention through the ring, not the cache
        cache = _scatter_kv(
            (k_pages, v_pages, k_scales, v_scales), k, v,
            page_table, start_pos, n_valid, page_size, layer_idx, n_kv,
        )
        return out, cache

    return attention


@partial(jax.jit, static_argnames=("config", "page_size", "mesh", "prefix_pages", "sp_mode", "qm_backend"), donate_argnums=(1,))
def ring_prefill_segment_step(
    params: dict[str, Any],
    state: DecodeState,
    tokens: Array,  # [1, S] — ONE segment, padded to a seq-axis multiple
    slot: Array,  # scalar int32
    start_pos: Array,  # scalar int32 — absolute position of tokens[0, 0]
    n_valid: Array,  # scalar int32 — real tokens in this segment
    *,
    config: LlamaConfig,
    page_size: int,
    mesh,
    prefix_pages: int,
    sp_mode: str = "ring",
    qm_backend: str = "ref",
) -> tuple[DecodeState, Array]:
    """One segment of a chunked seq-sharded prefill (SURVEY §5.7c +
    VERDICT r4 weak #8): segments attend to the cached earlier segments
    via the prefix fold and to themselves via the ring, so the scheduler
    can interleave decode steps between segments. Returns (state,
    last-valid-token logits [vocab]) — callers use the logits of the
    FINAL segment only.

    ``prefix_pages`` (static, power-of-two-bucketed by the caller) bounds
    the gather+fold to the pages that can actually hold the prefix —
    without it every segment would dequantize and fold max_seq_len
    positions per layer, costing O(segments x max_seq_len) attention
    instead of the monolithic path's O(S^2/2)."""
    S = tokens.shape[1]
    positions = start_pos + jnp.arange(S)[None, :]  # RoPE is absolute
    page_row = jax.lax.dynamic_slice_in_dim(state.page_table, slot, 1, axis=0)

    attention = _ring_segment_attention_fn(
        mesh, page_row, prefix_pages, start_pos[None], n_valid[None],
        page_size, config.n_kv_heads, sp_mode,
    )
    hidden, (k_pages, v_pages, k_scales, v_scales) = forward(
        params, tokens, positions,
        config=config, attention=attention,
        cache=(state.k_pages, state.v_pages, state.k_scales, state.v_scales),
        return_hidden=True, qm_backend=qm_backend,
    )
    last_hidden = jax.lax.dynamic_index_in_dim(
        hidden[0], jnp.maximum(n_valid - 1, 0), axis=0, keepdims=False
    )  # [D]
    last_logits = lm_head(params, last_hidden, config=config,
                          qm_backend=qm_backend)  # [vocab]

    new_state = dataclasses.replace(
        state,
        k_pages=k_pages,
        v_pages=v_pages,
        k_scales=k_scales,
        v_scales=v_scales,
        context_lens=state.context_lens.at[slot].add(n_valid),
    )
    return new_state, last_logits


@partial(jax.jit, static_argnames=("config", "page_size", "mesh", "sp_mode", "qm_backend"), donate_argnums=(1,))
def ring_prefill_step(
    params: dict[str, Any],
    state: DecodeState,
    tokens: Array,  # [1, S] — the WHOLE prompt, padded to a seq-axis multiple
    slot: Array,  # scalar int32
    n_valid: Array,  # scalar int32 — real prompt tokens
    *,
    config: LlamaConfig,
    page_size: int,
    mesh,
    sp_mode: str = "ring",
    qm_backend: str = "ref",
) -> tuple[DecodeState, Array]:
    """Seq-sharded single-shot prefill for long RAG prompts (SURVEY §5.7c).

    The sequence dim is sharded over the mesh's ``seq`` axis: activations
    and attention state are O(S / seq) per device, with the cross-device
    exchange done per ``sp_mode`` — K/V blocks rotating the ICI ring
    (ops/ring_attention.py) or Ulysses all-to-all head scatter
    (ops/ulysses.py) — so prompts beyond one chip's HBM become servable.
    Composes with TP (``model`` axis) via the head axis.
    Returns (state, last-valid-token logits [vocab])."""
    S = tokens.shape[1]
    positions = jnp.arange(S)[None, :]  # [1, S]
    page_row = jax.lax.dynamic_slice_in_dim(state.page_table, slot, 1, axis=0)

    attention = _ring_prefill_attention_fn(
        mesh, page_row, jnp.zeros((1,), jnp.int32), n_valid[None], page_size,
        config.n_kv_heads, sp_mode,
    )
    # hidden states only — a full [S, vocab] fp32 logits tensor at long-S
    # would cost GBs in exactly the regime this path exists for; project
    # the single last-valid row instead
    hidden, (k_pages, v_pages, k_scales, v_scales) = forward(
        params, tokens, positions,
        config=config, attention=attention,
        cache=(state.k_pages, state.v_pages, state.k_scales, state.v_scales),
        return_hidden=True, qm_backend=qm_backend,
    )
    last_hidden = jax.lax.dynamic_index_in_dim(
        hidden[0], jnp.maximum(n_valid - 1, 0), axis=0, keepdims=False
    )  # [D]
    last_logits = lm_head(params, last_hidden, config=config,
                          qm_backend=qm_backend)  # [vocab]

    new_state = dataclasses.replace(
        state,
        k_pages=k_pages,
        v_pages=v_pages,
        k_scales=k_scales,
        v_scales=v_scales,
        context_lens=state.context_lens.at[slot].add(n_valid),
    )
    return new_state, last_logits


@partial(jax.jit, donate_argnums=(0,))
def commit_first_token(
    state: DecodeState, slot: Array, logits: Array, temperature: Array, top_p: Array, top_k: Array
) -> tuple[DecodeState, Array]:
    """Sample the first generated token from prefill logits and arm the slot
    for decode. (temperature/top_p/top_k are scalars for this one sequence.)"""
    rng, sub = jax.random.split(state.rng)
    token = sample(logits[None], sub, temperature[None], top_p[None], top_k[None])[0]
    new_state = dataclasses.replace(
        state,
        last_tokens=state.last_tokens.at[slot].set(token),
        rng=rng,
    )
    return new_state, token


@partial(
    jax.jit,
    static_argnames=("config", "page_size", "attn_backend", "qm_backend",
                     "return_logits"),
    donate_argnums=(1,),
)
def decode_step(
    params: dict[str, Any],
    state: DecodeState,
    active: Array,  # [max_seqs] bool
    temperature: Array,  # [max_seqs]
    top_p: Array,  # [max_seqs]
    top_k: Array,  # [max_seqs] int32
    *,
    config: LlamaConfig,
    page_size: int,
    attn_backend: str = "ref",
    qm_backend: str = "ref",
    return_logits: bool = False,
    draft_ok: Array | None = None,  # [max_seqs] bool — a model that drafts: see below
) -> tuple[DecodeState, Array, Array | None, Array | None]:
    """One decode step for ALL slots; returns (state, next_tokens [max_seqs],
    logits?, moe_experts?).

    A model with a next-token-prediction module (``config.mtp_layers``) takes
    ``_draft_decode_step`` — the same step at width 2: each row verifies its
    pending draft and emits one token or two, ``next_tokens`` is ``[max_seqs,
    2]`` and ``draft_ok`` says which rows may use a draft at all. Every other
    model compiles the program below, as it always did.

    Each active slot's ``last_token`` KV is appended at ``context_lens`` and
    the next token sampled from its logits. Inactive slots write to the
    trash page and their sampled tokens are ignored by the host.

    ``return_logits=True`` additionally returns the step logits [B, vocab]
    (fp32) — the host-side path for grammar-constrained sampling
    (agent/constrained.py), which overrides ``last_tokens`` afterwards.

    A model that routes sparsely (``config.moe_sparse``) also returns, as
    int32 ``[2]`` beside the tokens, both summed over the layers: the number
    of distinct held experts that ACTIVE rows picked — the expert weights this
    step had to read — and the number whose weights the step's form DID read
    (``moe_mlp``: every held one under dense dispatch, the touched ones where
    ``ops/moe_step.py``'s pass ran). None for every other model: no output,
    no operation.
    """
    if config.mtp_layers:
        return _draft_decode_step(
            params, state, active, jnp.ones_like(active) if draft_ok is None else draft_ok,
            temperature, top_p, top_k, config=config, page_size=page_size,
            attn_backend=attn_backend, qm_backend=qm_backend, return_logits=return_logits)
    tokens = state.last_tokens[:, None]  # [B, 1]
    positions = state.context_lens[:, None]  # [B, 1] — absolute (rotary)
    n_valid = active.astype(jnp.int32)  # [B]

    # write + mask at the compacted position (bounded KV; zero-gap rows
    # reduce to the legacy absolute math bit-for-bit)
    attention = _paged_attention_fn(
        state.page_table, state.context_lens - state.kv_gaps, n_valid,
        page_size, config.attn_kind().n_kv_heads, attn_backend, decode=True, scale=config.attention_scale,
        latent=_latent_shape(config),
    )
    if config.window:
        attention = _attention_by_kind(attention, _paged_attention_fn(
            state.win_table, state.context_lens - state.win_gaps, n_valid,
            page_size, config.attn_kind(WINDOW).n_kv_heads, attn_backend, decode=True,
            scale=config.attention_scale, window=config.window), config)
    # a mixer's state advances one token in every active slot, in place
    # (row i IS slot i, no gather: on a kernel backend ops/ssm_step.py's one
    # pass over the layer's state, on `ref` a slice, _step and an update)
    logits, state, *experts = _forward_cached(
        params, state, tokens, positions,
        config=config, attention=attention,
        ssm_rows=SsmRows(None, n_valid, backend=attn_backend),
        qm_backend=qm_backend, moe_backend=attn_backend,
        **({"moe_live": active[:, None]} if config.moe_sparse or config.kv_lora_rank else {}),
    )
    step_logits = logits[:, 0, :]  # [B, vocab]

    rng, sub = jax.random.split(state.rng)
    next_tokens = sample(step_logits, sub, temperature, top_p, top_k)

    new_state = dataclasses.replace(
        state,
        context_lens=state.context_lens + n_valid,
        last_tokens=jnp.where(active, next_tokens, state.last_tokens),
        rng=rng,
    )
    return (new_state, next_tokens, (step_logits if return_logits else None),
            experts[0] if experts else None)


def _draft_decode_step(params, state: DecodeState, active: Array, draft_ok: Array,
                       temperature: Array, top_p: Array, top_k: Array, *,
                       config: LlamaConfig, page_size: int, attn_backend: str,
                       qm_backend: str, return_logits: bool):
    """``decode_step`` of a model that drafts with its next-token-prediction
    module: draft, verify, emit one token or two — all on the device, so the
    scheduler's depth-2 pipeline stays as it is.

    A row whose last token is ``x`` (position ``p``) with a pending draft ``d
    ~ q``: the trunk runs ``[x, d]`` at ``[p, p + 1]`` in ONE pass (the
    latent walk once for both, ``pair_attention``); ``verify_draft`` keeps
    ``d`` against the row's sampling distribution at ``p`` or redraws; kept,
    the row also emits a token from the distribution at ``p + 1``. The module
    then runs the emitted tokens with the trunk's pre-norm outputs below them,
    writes its rows in the slots of the tokens it consumed, and drafts the
    next ``d'`` from the last — with the distribution it came from, which the
    state carries to the next step. A rejected draft's rows (the trunk's and
    the module's at ``p + 1``) lie beyond the new length and are overwritten.

    A row WITHOUT a usable draft (``has_draft`` off: its first step, a host
    pick through ``set_last_token``; or ``draft_ok`` off: a grammar-constrained
    row) emits one token from the distribution at ``p``; its module pairs are
    (the carried hidden state, ``x``) and (the output at ``p``, the emitted
    token), so it leaves a draft too.

    Returns ``decode_step``'s tuple with ``next_tokens`` [B, 2]: the second
    column is the second token, or -2 (a draft was verified and not kept), or
    -1 (none was)."""
    c = config
    ctx = state.context_lens
    has = state.has_draft & active & draft_ok
    tokens = jnp.stack([state.last_tokens, jnp.where(has, state.draft_tokens, 0)], axis=1)
    pair = jnp.arange(2, dtype=jnp.int32)[None, :]
    n_valid = jnp.where(active, 1 + has.astype(jnp.int32), 0)

    def attention_of(start, n, module=False):
        return _paged_attention_fn(
            state.page_table, start - state.kv_gaps, n, page_size, c.n_kv_heads, attn_backend,
            decode=True, scale=c.attention_scale, latent=_latent_shape(c, module), pair=True)

    hidden, state, experts = _forward_cached(
        params, state, tokens, ctx[:, None] + pair, config=c,
        attention=attention_of(ctx, n_valid), ssm_rows=None, return_hidden=True, prenorm=True,
        qm_backend=qm_backend, moe_backend=attn_backend, moe_live=pair < n_valid[:, None])
    sampling = (temperature, top_p, top_k)
    logits, kept, first, second, emitted, rng, k_draft = _verify_pairs(
        params, hidden, (state.draft_tokens, state.draft_probs, has), state.rng, sampling,
        config=c, qm_backend=qm_backend)
    n_emitted = jnp.where(active, 1 + kept.astype(jnp.int32), 0)

    # the module: the pairs of the emitted tokens, or (no draft) the pair that
    # was waiting on the carried hidden state and the emitted token's
    start = ctx + has.astype(jnp.int32)
    n_pairs = jnp.where(active, jnp.where(has & ~kept, 1, 2), 0)
    below = jnp.where(has[:, None, None], hidden,
                      jnp.stack([state.mtp_hidden.astype(hidden.dtype), hidden[:, 0]], axis=1))
    consumed = jnp.where(has[:, None], jnp.stack([first, second], axis=1),
                         jnp.stack([state.last_tokens, first], axis=1))
    state, out, m_experts = _module_rows(
        params, state, attention_of(start, n_pairs, module=True), below, consumed,
        start[:, None] + pair, config=c, qm_backend=qm_backend, attn_backend=attn_backend,
        moe_live=pair < n_pairs[:, None])
    d_next, q_next = _next_draft(
        params, jnp.take_along_axis(out, jnp.maximum(n_pairs - 1, 0)[:, None, None], axis=1)[:, 0],
        k_draft, sampling, config=c, qm_backend=qm_backend)

    carry = jnp.where(kept[:, None], hidden[:, 1], hidden[:, 0]).astype(state.mtp_hidden.dtype)
    new_state = dataclasses.replace(
        state, context_lens=ctx + n_emitted,
        last_tokens=jnp.where(active, jnp.where(kept, second, first), state.last_tokens),
        rng=rng,
        mtp_hidden=jnp.where(active[:, None], carry, state.mtp_hidden),
        draft_tokens=jnp.where(active, d_next, state.draft_tokens),
        draft_probs=jnp.where(active[:, None], q_next, state.draft_probs),
        has_draft=state.has_draft | active,
    )
    return new_state, emitted, (logits[:, 0] if return_logits else None), experts + m_experts


def _ragged_draft_tail(params, state: DecodeState, h: Array, tok_in: Array, tok_pos: Array,
                       tok_valid: Array, tok_off: Array, safe_row: Array, q_start: Array,
                       row_slot: Array, row_len: Array, eff_start: Array, row_arm: Array,
                       pair_row: Array, has_draft: Array, row_last: Array, temperature: Array,
                       top_p: Array, top_k: Array, module_attention, *, config: LlamaConfig,
                       qm_backend: str, attn_backend: str):
    """``ragged_mixed_step`` behind the trunk's pass, for a model that drafts:
    what ``_draft_decode_step`` does for a slot batch, over a PACKED round. A
    pair row (``pair_row``: a decode row packed as its last token and its
    draft) verifies its draft where it has one (``has_draft``) and emits one
    token or two; every other armed row (a prompt that completes, a decode row
    packed as ONE token: the host picks its token, or ``perfbench/correct.py``
    forces it) emits one from its last position. Then the module, once over
    the buffer: a prompt chunk's pairs are (the output below each token, the
    token), the slot's carried hidden state below a row's first; a pair row's
    are the emitted tokens' — or, without a draft, the pair that waited on the
    carried state and the emitted token's — and it drafts from its last, so a
    pair row leaves the round with a draft pending and every other row with
    none. ``h`` [T, D] is the trunk's PRE-NORM output. Returns
    ``ragged_mixed_step``'s tuple; a pair row's second ``emitted`` column is
    ``_draft_decode_step``'s (-2: verified and not kept, -1: no draft)."""
    c = config
    T = h.shape[0]
    at = lambda idx: jnp.clip(idx, 0, T - 1)  # noqa: E731
    last_idx = at(q_start + row_len - 1)
    sampling = (temperature, top_p, top_k)
    # the sampling positions: a pair row's two tokens, else the row's last token
    logits, kept, first, second, emitted, rng, k_draft = _verify_pairs(
        params, h[jnp.stack([jnp.where(pair_row, at(q_start), last_idx), at(q_start + 1)], axis=1)],
        (state.draft_tokens[row_slot], state.draft_probs[row_slot], has_draft), state.rng,
        sampling, config=c, qm_backend=qm_backend)
    n_emitted = jnp.where(row_arm, 1 + kept.astype(jnp.int32), 0)

    # the module's pass over the buffer
    paired, drafted = pair_row[safe_row], has_draft[safe_row]
    verified = paired & drafted  # its pairs are the emitted tokens', one slot on
    below = jnp.where(verified[:, None], h, _shifted(
        h[None], (tok_off == 0)[None], state.mtp_hidden[row_slot][safe_row][None])[0])
    consumed = jnp.where(verified, jnp.where(tok_off == 0, first[safe_row], second[safe_row]),
                         jnp.where(paired & (tok_off == 1), first[safe_row], tok_in))
    positions = tok_pos + verified.astype(jnp.int32)
    n_pairs = jnp.where(pair_row, jnp.where(has_draft & ~kept, 1, 2), row_len)
    live = tok_valid & (tok_off < n_pairs[safe_row])
    start = eff_start + has_draft.astype(jnp.int32)
    state, out = _module_rows(
        params, state, module_attention(
            positions, jnp.where(row_len > 0, start + n_pairs, 0), live, n_pairs),
        below[None], consumed[None], positions[None], config=c, qm_backend=qm_backend,
        attn_backend=attn_backend)
    d_next, q_next = _next_draft(params, out[0][at(q_start + n_pairs - 1)], k_draft, sampling,
                                 config=c, qm_backend=qm_backend)
    state = _carry_on(
        state, row_slot, row_len > 0,
        h[jnp.where(pair_row, at(q_start + n_emitted - 1), last_idx)],
        has_draft=pair_row & row_arm, draft_tokens=d_next, draft_probs=q_next)

    last_token = jnp.where(kept, second, first)
    state = dataclasses.replace(
        state,
        context_lens=state.context_lens.at[row_slot].add(
            jnp.where(pair_row, n_emitted, row_len)),
        last_tokens=state.last_tokens.at[row_slot].add(
            jnp.where(row_arm, last_token - row_last, 0)),
        rng=rng,
    )
    return state, emitted, n_emitted, logits[:, 0]


def _ragged_attention_fn(
    page_rows: Array,  # [R, max_pages] per-ROW page lists (host-gathered)
    tok_row: Array,  # [T] int32 — owning row per packed token (R = padding)
    tok_pos: Array,  # [T] int32 — absolute position per packed token
    row_kv_len: Array,  # [R] int32 — valid KV per row incl. this dispatch
    tok_valid: Array,  # [T] bool — real token (False = buffer padding)
    page_size: int,
    n_kv: int,
    attn_backend: str,
    row_gap: Array | None = None,  # [R] int32 — bounded-KV eviction gap
    scale: float | None = None,  # the model's softmax scale (None = D ** -0.5)
    latent: LatentShape | None = None,  # a model with latent attention ...
    rows: SsmRows | None = None,  # ... and how its packed tokens lie in rows
    window: int = 0,  # a sliding-window layer's callback (see _paged_attention_fn)
    block_q: int = 0,  # the ragged kernel's query block (0 = its default)
    pair: bool = False,  # a model that drafts: rows of two tokens walk as one-token rows do
):
    """Attention callback for the packed ragged step (``ragged_mixed_step``):
    per-token KV writes through the chunk scatter (one full-cache copy per
    round, amortized over every row — the mixed-step trade), then the ragged
    paged kernel (ops/ragged_paged_attention.py) reads each row's pages in
    place. The ``jax.lax`` reference backend computes each packed token as
    its own batch element of the SAME ``gather_kv`` + ``mha_reference`` math
    the split path uses — the fp32 byte-identity contract's foundation.

    ``row_gap`` (bounded KV, ISSUE 15) shifts each row's KV WRITE to its
    compacted position and rides into the kernel as the per-row
    ``kv_gap`` offset, so the gather walks the surviving pages while
    ``tok_pos`` — and the rotary positions upstream — stay absolute."""
    from finchat_tpu.ops.dispatch import ragged_paged_attention

    R = page_rows.shape[0]
    safe_row = jnp.minimum(tok_row, R - 1)
    # per-token page rows for the scatter; padding tokens write the trash
    # page (n_valid 0 redirects them inside the scatter)
    pt_tok = page_rows[safe_row]  # [T, max_pages]
    n_valid_tok = tok_valid.astype(jnp.int32)
    if row_gap is None:
        tok_wpos = tok_pos
    else:
        # valid tokens of a gapped row always sit past the evicted region
        # (the scheduler's eviction/restore invariant), so the uniform
        # subtraction is exact; the clamp only guards padding tokens
        tok_wpos = jnp.maximum(tok_pos - row_gap[safe_row], 0)
    if latent is not None:
        def write(row: Array, idx_k: Array, cache: Any, layer_idx: Array):
            with jax.named_scope("kv_scatter_ragged"):
                return _scatter_kv(cache, row[0][:, None, None], idx_k[0][:, None, None],
                                   pt_tok, tok_wpos, n_valid_tok, page_size, layer_idx, 1)

        # a row's compacted start: its first packed token's write position
        start = tok_wpos[jnp.minimum(rows.pack[0], tok_wpos.shape[0] - 1)]
        return _latent_attention(write, page_rows, start, rows.n_valid, page_size, latent,
                                 attn_backend, rows=rows, pair=pair)

    # (jit keys on the keywords a call passes: the other models' calls stay as they were)
    kernel_kw = {k: v for k, v in (("window", window), ("block_q", block_q)) if v}

    def attention(q: Array, k: Array, v: Array, cache: Any, layer_idx: Array,
                  sink: Array | None = None):
        k_pages, v_pages, k_scales, v_scales = cache
        quantized = k_pages.dtype == jnp.int8  # static under trace
        T = q.shape[1]
        layer = layer_idx.reshape(1)
        if k is not None:  # (None: a CROSS layer reads pages another layer wrote)
            with jax.named_scope("kv_scatter_ragged"):
                # each packed token is one (B=T, C=1) scatter row at its own
                # COMPACTED position through its own page list
                k_pages, v_pages, k_scales, v_scales = _scatter_kv(
                    (k_pages, v_pages, k_scales, v_scales),
                    k.reshape(T, 1, n_kv, -1), v.reshape(T, 1, n_kv, -1),
                    pt_tok, tok_wpos, n_valid_tok, page_size, layer_idx, n_kv,
                )
        with jax.named_scope("ragged_paged_attention"):
            out = ragged_paged_attention(
                q[0], k_pages, v_pages, page_rows, tok_row, tok_pos,
                row_kv_len, layer, page_size=page_size, n_kv=n_kv,
                backend=attn_backend,
                k_scales=k_scales if quantized else None,
                v_scales=v_scales if quantized else None,
                kv_gap=row_gap, scale=scale, **kernel_kw,
                **({} if sink is None else {"sink": sink}),
            )
        return out[None], (k_pages, v_pages, k_scales, v_scales)

    return attention


@partial(
    jax.jit,
    static_argnames=("config", "page_size", "attn_backend", "qm_backend",
                     "spec_width", "max_row_tokens"),
    donate_argnums=(1,),
)
def ragged_mixed_step(
    params: dict[str, Any],
    state: DecodeState,
    tokens: Array,  # [T] int32 PACKED token buffer (0 at device-read positions)
    tok_row: Array,  # [T] int32 — owning row, ascending contiguous (R = padding)
    row_slot: Array,  # [R] int32 — engine slot per row
    row_start: Array,  # [R] int32 — abs pos of the row's first token (prefill)
    row_len: Array,  # [R] int32 — tokens in the row (0 = padding row)
    row_from_device: Array,  # [R] bool — token 0 reads last_tokens[slot] and the
    #   row starts at context_lens[slot] (decode rows, spec verify rows)
    row_arm: Array,  # [R] bool — commit this row's sampled token to last_tokens
    row_n_drafts: Array,  # [R] int32 — spec rows: row_len == 1 + n_drafts
    temperature: Array,  # [R] — PER-ROW sampling params
    top_p: Array,  # [R]
    top_k: Array,  # [R] int32
    *,
    config: LlamaConfig,
    page_size: int,
    attn_backend: str = "ref",
    qm_backend: str = "ref",
    spec_width: int = 0,
    max_row_tokens: int = 0,  # a mixer's row width (0 = the buffer's length)
) -> tuple[DecodeState, Array, Array, Array]:
    """ONE packed ragged dispatch advancing every serving population at once
    (the scheduler's mixed path, ISSUE 10 — built on
    ops/ragged_paged_attention.py): prefill chunks of any length, 1-token
    decode rows, grammar-constrained rows (host overrides via the returned
    logits), and (1+Kd)-token spec verify rows are rows of ONE packed
    buffer. Returns ``(state, emitted [R, W], n_emitted [R], row_logits
    [R, vocab])`` with ``W = spec_width + 1``.

    - Device-read rows (``row_from_device``) take their first token from
      ``state.last_tokens[slot]`` and start at ``context_lens[slot]`` ON
      DEVICE; spec rows' drafts ride the packed buffer at offsets 1..Kd.
    - Spec acceptance is the ``verify_step`` math verbatim: draft i commits
      iff it equals THIS forward's argmax at its position;
      ``emitted[r, :n_emitted[r]]`` are the row's tokens (1..Kd+1 for spec
      rows, 1 for armed plain rows, 0 for mid-prompt prefill rows), and
      rejected drafts' KV lands beyond the new ``context_lens``.
    - ``row_logits`` is each row's sampling-position logits (position 0
      for device rows, the last valid chunk token for prefill rows) — the
      host-side grammar-pick path, exactly ``decode_step return_logits``.
    - One rng split for the packed round — the same discipline as
      ``decode_step``; greedy streams are rng-independent.
    - ``last_tokens`` commits as a DELTA scatter-add so duplicate-slot
      padding rows (delta 0) cannot race the real row's write.

    Numerics contract (tests/test_mixed_step.py): same MATH as the split
    path per token; greedy streams byte-identical at fp32. The documented
    bf16 near-tie caveat of ``verify_step``/PR 4 applies unchanged: a
    token computed at the packed shape can differ in the last ulp from the
    ``[max_seqs, 1]`` shape and flip a later near-tie argmax — either
    stream is a valid greedy decode.
    """
    T = tokens.shape[0]
    R = row_slot.shape[0]
    W = spec_width + 1
    tok_row = jnp.asarray(tok_row, jnp.int32)
    safe_row = jnp.minimum(tok_row, R - 1)
    tok_valid = tok_row < R
    q_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(row_len, dtype=jnp.int32)[:-1]]
    )  # [R] exclusive — rows packed in ascending contiguous order
    tok_off = jnp.arange(T, dtype=jnp.int32) - q_start[safe_row]
    eff_start = jnp.where(
        row_from_device, state.context_lens[row_slot], row_start
    )  # [R]
    tok_pos = jnp.where(tok_valid, eff_start[safe_row] + tok_off, 0)
    row_last = state.last_tokens[row_slot]  # [R]
    tok_in = jnp.where(
        tok_valid & row_from_device[safe_row] & (tok_off == 0),
        row_last[safe_row], tokens,
    )
    page_rows = state.page_table[row_slot]  # [R, max_pages]
    # a model that drafts: a decode row of ``row_n_drafts`` 1 is packed as TWO
    # tokens, its last one and its pending draft, both read on the device —
    # the second is live only where the slot holds a draft
    tok_live, eff_len = tok_valid, row_len
    if config.mtp_layers:
        pair_row = row_n_drafts > 0
        has_draft = pair_row & state.has_draft[row_slot]
        is_draft = tok_valid & pair_row[safe_row] & (tok_off == 1)
        tok_in = jnp.where(is_draft, state.draft_tokens[row_slot][safe_row], tok_in)
        tok_live = tok_valid & ~(is_draft & ~has_draft[safe_row])
        eff_len = jnp.where(pair_row, 1 + has_draft.astype(jnp.int32), row_len)
    row_kv_len = jnp.where(row_len > 0, eff_start + eff_len, 0)  # [R]
    row_gap = state.kv_gaps[row_slot]  # [R] — bounded-KV compaction offset

    # a mixer's conv and scan must not run across a row boundary: the packed
    # tokens are regrouped to [R, row_width] rows, each from its slot's state
    # (a padding row rides inert), and its last state goes back there.
    # Latent attention regroups its queries the same way (a row's chunk shares
    # the row's pages and walks them once)
    def packed_rows(n_valid: Array = eff_len) -> SsmRows:
        return SsmRows(
            row_slot, n_valid, pack=(q_start, tok_row, tok_off),
            width=min(T, max_row_tokens or T), backend=attn_backend,
        )

    latent = _latent_shape(config)
    # the ragged kernel's query block, read off the round and the model: a
    # round of 2,048 tokens or more is prompts (at most max_seqs of its tokens
    # are decode rows'), and where SEVERAL layers walk one layer's pages
    # (config.cache_readers: a full-attention layer and the cross layers
    # behind it) that walk at blocks of 8 is the round — 98 ms a reading layer
    # at 8,192 tokens, 27 at blocks of 64 (PERF.md section 6, PR 42). A model
    # whose layers each walk their own pages keeps the kernel's default at
    # every size: not measured there (PERF.md section 7, Left by PR 42 (1))
    block_q = 64 if T >= 2048 and config.cache_readers > 1 else 0
    attention = _ragged_attention_fn(
        page_rows, tok_row, tok_pos, row_kv_len, tok_live,
        page_size, config.attn_kind().n_kv_heads, attn_backend, row_gap=row_gap,
        scale=config.attention_scale,
        latent=latent, rows=packed_rows() if latent is not None else None, block_q=block_q,
        **({"pair": True} if config.mtp_layers else {}),
    )
    if config.window:
        attention = _attention_by_kind(attention, _ragged_attention_fn(
            state.win_table[row_slot], tok_row, tok_pos, row_kv_len, tok_valid,
            page_size, config.attn_kind(WINDOW).n_kv_heads, attn_backend,
            row_gap=state.win_gaps[row_slot], scale=config.attention_scale,
            window=config.window, block_q=block_q), config)
    ssm_rows = packed_rows() if config.has_state else None
    # hidden states only, then project only each row's sampling positions —
    # the [T, vocab] fp32 logits tensor would cost GBs at production shapes
    hidden, state = _forward_cached(
        params, state, tok_in[None], tok_pos[None],
        config=config, attention=attention, ssm_rows=ssm_rows,
        return_hidden=True, qm_backend=qm_backend, moe_backend=attn_backend,
        **({"prenorm": True} if config.mtp_layers else {}),
    )
    h = hidden[0]  # [T, D]
    if config.mtp_layers:
        return _ragged_draft_tail(
            params, state, h, tok_in, tok_pos, tok_valid, tok_off, safe_row, q_start, row_slot,
            row_len, eff_start, row_arm, pair_row, has_draft, row_last, temperature, top_p, top_k,
            lambda pos, kv_len, live, n: _ragged_attention_fn(
                page_rows, tok_row, pos, kv_len, live, page_size, config.n_kv_heads,
                attn_backend, row_gap=row_gap, scale=config.attention_scale,
                latent=_latent_shape(config, module=True), rows=packed_rows(n), pair=True),
            config=config, qm_backend=qm_backend, attn_backend=attn_backend)

    # sampling positions: spec rows need logits at EVERY row position
    # (ascending, for draft acceptance); every other row only at its last
    # valid token — all W columns point there, so column 0 is always the
    # row's sampling position
    col = jnp.arange(W, dtype=jnp.int32)[None, :]  # [1, W]
    last_off = jnp.maximum(row_len - 1, 0)[:, None]  # [R, 1]
    sel_off = jnp.where(
        (row_n_drafts > 0)[:, None], jnp.minimum(col, last_off), last_off
    )
    sel_idx = jnp.clip(q_start[:, None] + sel_off, 0, T - 1)  # [R, W]
    logits = lm_head(params, h[sel_idx], config=config,
                     qm_backend=qm_backend)  # [R, W, vocab] fp32
    preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [R, W]

    # spec acceptance — verify_step's math over the packed drafts: draft
    # column i (1..W-1) is accepted while every earlier draft matched and
    # it equals the model's prediction for its position
    cols_d = jnp.arange(1, W, dtype=jnp.int32)[None, :]  # [1, W-1]
    draft_tok = tok_in[jnp.clip(q_start[:, None] + cols_d, 0, T - 1)]
    match = (cols_d <= row_n_drafts[:, None]) & (draft_tok == preds[:, :-1])
    accepted = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)  # [R]

    rng, sub = jax.random.split(state.rng)
    row_logits = logits[:, 0, :]  # [R, vocab] — each row's sampling position
    sampled0 = sample(row_logits, sub, temperature, top_p, top_k)  # [R]
    emitted = jnp.concatenate([sampled0[:, None], preds[:, 1:]], axis=1)
    n_emitted = jnp.where(
        row_arm, jnp.where(row_n_drafts > 0, accepted + 1, 1), 0
    )
    last_tok = jnp.take_along_axis(emitted, accepted[:, None], axis=1)[:, 0]

    # context advance: spec rows move by what they EMITTED (rejected
    # drafts' KV stays beyond the new length); every other row by its
    # packed length (chunk for prefill, 1 for decode, 0 for padding)
    advance = jnp.where(row_n_drafts > 0, n_emitted, row_len)
    delta = jnp.where(row_arm, last_tok - row_last, 0)
    state = dataclasses.replace(
        state,
        context_lens=state.context_lens.at[row_slot].add(advance),
        last_tokens=state.last_tokens.at[row_slot].add(delta),
        rng=rng,
    )

    return state, emitted, n_emitted, row_logits


@partial(
    jax.jit,
    static_argnames=("config", "page_size", "attn_backend", "qm_backend",
                     "return_logits"),
    donate_argnums=(1,),
)
def verify_step(
    params: dict[str, Any],
    state: DecodeState,
    active: Array,  # [max_seqs] bool
    drafts: Array,  # [max_seqs, Kd] int32 — host-proposed draft tokens
    n_drafts: Array,  # [max_seqs] int32 — live drafts per slot (0 = plain decode)
    temperature: Array,  # [max_seqs]
    top_p: Array,  # [max_seqs]
    top_k: Array,  # [max_seqs] int32
    *,
    config: LlamaConfig,
    page_size: int,
    attn_backend: str = "ref",
    qm_backend: str = "ref",
    return_logits: bool = False,
) -> tuple[DecodeState, Array, Array, Array | None]:
    """Speculative-decoding verify step (prompt-lookup style): one forward
    over ``[last_token, draft_1..draft_Kd]`` per slot scores every draft in
    a single weights-read; the accepted prefix plus one model token commit
    together. Returns ``(state, emitted [B, K], n_emitted [B], logits?)``
    where ``K = Kd + 1`` and ``emitted[b, :n_emitted[b]]`` are the tokens
    produced this step (1..K per slot).

    Greedy-exactness contract (tests/test_spec_decode.py): draft i is
    accepted iff it equals THIS forward's argmax at its position, and
    position i's scores attend only to positions <= i (the paged kernel's
    causal mask) — so acceptance never changes a token, only how many
    commit per step, and the emitted stream is always a self-consistent
    greedy continuation. Bit-equality with token-by-token ``decode_step``
    additionally requires the C=K forward to round like the C=1 forward;
    that holds on the small test configs (asserted) but a bf16 near-tie
    can flip under a different chunk width at scale — either stream is a
    valid greedy decode of the same weights. Rejected drafts' KV lands
    beyond the new ``context_lens`` — masked by every future step and
    overwritten when those positions are reached for real.

    Non-greedy and grammar-constrained slots ride with ``n_drafts = 0``:
    their single token is sampled from position-0 logits with the full
    sampler (bit-identical math to ``decode_step``), and
    ``return_logits=True`` hands position-0 logits to the host for
    constrained picks, as in ``decode_step``.
    """
    B, Kd = drafts.shape
    tokens = jnp.concatenate([state.last_tokens[:, None], drafts], axis=1)  # [B, K]
    K = Kd + 1
    positions = state.context_lens[:, None] + jnp.arange(K)[None, :]  # rotary
    n_valid = jnp.where(active, 1 + n_drafts, 0)  # [B] tokens whose KV is written

    # compacted write/mask coordinates (bounded KV; see decode_step) —
    # rejected drafts' KV still lands beyond the new compacted length and
    # is overwritten when those positions are reached for real
    attention = _paged_attention_fn(
        state.page_table, state.context_lens - state.kv_gaps, n_valid,
        page_size, config.n_kv_heads, attn_backend, inplace_append=True, scale=config.attention_scale,
    )
    logits, (k_pages, v_pages, k_scales, v_scales) = forward(
        params, tokens, positions,
        config=config, attention=attention,
        cache=(state.k_pages, state.v_pages, state.k_scales, state.v_scales),
        qm_backend=qm_backend,
    )  # [B, K, vocab]

    preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, K]
    # draft column i (1..Kd) is accepted while every earlier draft matched
    # and it equals the model's prediction for its position
    col = jnp.arange(1, K)[None, :]  # [1, Kd]
    match = (col <= n_drafts[:, None]) & (tokens[:, 1:] == preds[:, :-1])
    accepted = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)  # [B]
    n_emitted = jnp.where(active, accepted + 1, 0)

    # non-greedy slots (always draft-free) sample position 0 with the full
    # sampler — same math and rng discipline as decode_step
    rng, sub = jax.random.split(state.rng)
    step_logits = logits[:, 0, :]  # [B, vocab] fp32
    sampled0 = sample(step_logits, sub, temperature, top_p, top_k)
    emitted = jnp.concatenate([sampled0[:, None], preds[:, 1:]], axis=1)  # [B, K]
    last = jnp.take_along_axis(emitted, accepted[:, None], axis=1)[:, 0]

    new_state = dataclasses.replace(
        state,
        k_pages=k_pages,
        v_pages=v_pages,
        k_scales=k_scales,
        v_scales=v_scales,
        context_lens=state.context_lens + n_emitted,
        last_tokens=jnp.where(active, last, state.last_tokens),
        rng=rng,
    )
    return new_state, emitted, n_emitted, (step_logits if return_logits else None)


class InferenceEngine:
    """Host-side wrapper owning the device state and compiled steps.

    Synchronous single-sequence generation lives here (the minimum
    end-to-end slice, BASELINE config 1); the continuous-batching scheduler
    (engine/scheduler.py) drives the same step functions for many sequences.
    """

    def __init__(self, config: LlamaConfig, params: dict[str, Any], engine_cfg: EngineConfig,
                 mesh=None, attn_backend: str | None = None, quant: str = "",
                 quant_group: int = 0, qm_backend: str | None = None):
        from finchat_tpu.models.quant import validate_quant_mode
        from finchat_tpu.ops.dispatch import attention_backend, quant_matmul_backend

        validate_quant_mode(quant)
        self.config = config
        self.attn_backend = attn_backend or attention_backend()
        # fused dequant-matmul backend (ops/quant_matmul.py): resolved ONCE
        # here — dispatch discipline, same as attn_backend — and passed
        # STATIC through every compiled step. Unquantized engines pin "ref"
        # so the knob adds zero compiled variants for them (bf16 weights
        # never reach the dispatcher anyway).
        self.qm_backend = (qm_backend or quant_matmul_backend()) if quant else "ref"
        if ("pallas" in (self.attn_backend, self.qm_backend) and mesh is not None
                and mesh.shape.get("model", 1) > 1):
            # heads, KV pages and weights shard over `model`, and the SPMD
            # partitioner refuses a Mosaic call with sharded operands
            # ("Mosaic kernels cannot be automatically partitioned. Please
            # wrap the call in a shard_map" — seen on four v5e chips,
            # PR 21). The serving kernels need that shard_map over `model`
            # first (ROADMAP S8); until then say so here, before warm-up
            # trips over it.
            raise ValueError(
                f"the compiled 'pallas' kernels do not run under a model>1 "
                f"mesh (model={mesh.shape['model']}, attention backend "
                f"{self.attn_backend!r}, quant-matmul backend "
                f"{self.qm_backend!r}): they are not sharded over the model "
                f"axis yet. Serve one chip per engine (mesh.model=1), or set "
                f"FINCHAT_ATTN=ref (and FINCHAT_QUANT_MATMUL=ref) to run "
                f"tensor-parallel on the jax.numpy references."
            )
        # TP collective-overlap knob (ops/tp_overlap.py): surfaced on the
        # engine for the manual-TP stage path and the metrics plane;
        # default off — on CPU the serial psum IS the reference schedule
        self.tp_overlap = engine_cfg.tp_overlap
        self.engine_cfg = engine_cfg
        self.page_size = engine_cfg.page_size
        # serving-variant count of the last warmup() (0 = not warmed yet);
        # the scheduler emits it as the finchat_warmup_compiled_variants
        # gauge — the ISSUE 10 warmup-matrix-collapse instrument
        self.compiled_variants = 0
        # the last decode step's counts of held experts [touched, read], on the
        # device (a model that routes sparsely; else None): the scheduler
        # fetches them with the step's tokens
        self.moe_experts = None
        self.max_pages_per_seq = min(
            engine_cfg.num_pages - 1,
            -(-engine_cfg.max_seq_len // engine_cfg.page_size),
        )
        # a model with latent attention: the form its one-token rows take, as
        # the steps' traces read it off the same table (latent_attention.decode_form)
        self.latent_form = decode_form(
            attn_backend, self.max_pages_per_seq * engine_cfg.page_size,
            config.index_topk) if config.kv_lora_rank else None
        # ... and, where an indexer selects among the table's tokens, how the
        # decode step's indexer comes by its scores (latent_attention.index_form)
        self.index_form = index_form(attn_backend) if config.kv_lora_rank and (
            0 < config.index_topk < self.max_pages_per_seq * engine_cfg.page_size) else None
        # ... and the form its walks take the batch's shared head in, by the static shapes
        # the call itself reads (paged_attention.latent_head_form; a model that drafts walks
        # a token and its draft as 2 x heads query rows); the gather form takes none
        self.head_form = None
        if config.kv_lora_rank:
            from finchat_tpu.ops.paged_attention import latent_head_form

            self.head_form = latent_head_form(
                engine_cfg.max_seqs, config.n_heads * (2 if config.mtp_layers else 1),
                config.latent_row, config.kv_lora_rank, jnp.dtype(config.dtype).itemsize,
            ) if self.latent_form == "walk" else "none"
        # a model with Mamba-2 layers on a kernel backend: the tile its decode
        # step's state update works on, by the static shapes (ssm_step.tile_heads)
        self.state_form = None
        if config.ssm_heads and self.attn_backend != "ref":
            pairs = ssm_step.tile_heads(*config.state_shape, config.ssm_groups) == 2
            self.state_form = "pairs" if pairs else "heads"
        self.mesh = mesh
        # bounded-KV long-context serving (ISSUE 15): attention-sink +
        # sliding-window page eviction. The policy is pure host math; the
        # device side is the kv_gaps state leaf + compacted write/mask
        # coordinates in every step function. None = unbounded (legacy).
        from finchat_tpu.engine.kv_cache import BoundedKVPolicy

        _bp = BoundedKVPolicy(
            max(0, engine_cfg.kv_sink_pages),
            max(0, engine_cfg.kv_window_pages),
            engine_cfg.page_size,
        )
        if _bp.enabled:
            _bp.validate(
                prefill_chunk=engine_cfg.prefill_chunk,
                max_pages_per_seq=self.max_pages_per_seq,
                spec_tokens=engine_cfg.spec_tokens,
            )
        self.bounded_kv = _bp if _bp.enabled else None
        if config.window:
            self._refuse_with_window_layers()
        # int8 KV composes with a mesh: pages shard over the fused KV-head
        # minor dim, scales over their head row dim (decode_state_shardings;
        # aligned blocks when Hkv % 8 == 0, replicated — they're ~6% of the
        # pages — otherwise), and the SP-prefill write path quantizes too
        self.kv_quant = kv_quant = engine_cfg.kv_quant
        self._refuse_what_a_kind_cannot_carry(mesh, quant)
        if config.moe_fused_glu:
            self._refuse_with_held_experts(mesh, quant)
        state = create_state(config, engine_cfg, self.max_pages_per_seq, kv_quant=kv_quant)
        if mesh is not None:
            # TP placement: params sharded Megatron-style, KV pages sharded
            # over the fused KV-head dim on the model axis; XLA propagates
            # the rest.
            from finchat_tpu.parallel.sharding import (
                llama_param_shardings,
                shard_decode_state,
                shard_params,
            )

            params = shard_params(params, llama_param_shardings(mesh))
            state = shard_decode_state(state, mesh, config.n_kv_heads)
        if quant:
            # after sharding on purpose: quantize is plain jnp, so q/scale
            # inherit each weight's GSPMD placement (models/quant.py);
            # idempotent on trees the checkpoint loader already quantized
            from finchat_tpu.models.quant import quantize_llama_params

            params = quantize_llama_params(params, mode=quant,
                                           group_size=quant_group)
        self.quant = quant
        self.quant_group = quant_group
        self.params = params
        self.state = state
        self.sp_mode = self._resolve_sp_mode(engine_cfg.sp_mode)
        # a model with sliding-window layers: the host's half of the second
        # pool — each slot's bounded page list — and the context length of
        # every slot as the host knows it (the steps that carry recurrent
        # state advance a row by what the host packed, nothing is rewound)
        self.window_pager = None
        self._host_ctx = np.zeros((engine_cfg.max_seqs,), np.int64)
        if config.window:
            from finchat_tpu.engine.kv_cache import WindowPager

            self.window_pager = WindowPager(
                window_pool_pages(config, engine_cfg), engine_cfg.max_seqs, config.window,
                engine_cfg.page_size)
            self._window_gauges()

    def _refuse_with_window_layers(self) -> None:
        """Sliding-window layers keep a bounded page list of their OWN kind
        (``kv_cache.WindowPager``); ``BoundedKVPolicy`` bounds a ROW for every
        layer alike and is an approximation of full attention: the two do not
        combine. The window's pages must also be whole, and a chunk must fit
        beside the window in a row's bound."""
        cfg, W = self.engine_cfg, self.config.window
        from finchat_tpu.engine.kv_cache import window_pages_per_row

        if self.bounded_kv is not None:
            raise ValueError(
                f"engine.kv_sink_pages / engine.kv_window_pages bound a row's pages in EVERY "
                f"layer (an approximate serving option); this model's {self.config.n_window_layers} "
                f"sliding-window layers keep an exact window of {W} tokens on a bounded page "
                "list of their own and its full-attention layer keeps the whole row: set both "
                "to 0")
        if W % cfg.page_size or cfg.prefill_chunk > 2 * cfg.page_size:
            raise ValueError(
                f"a model with sliding-window layers (window {W}) needs whole pages in the "
                f"window (engine.page_size {cfg.page_size}) and a chunk of at most two pages "
                f"(engine.prefill_chunk {cfg.prefill_chunk}): a row holds window / page_size + 2 "
                f"= {window_pages_per_row(W, cfg.page_size)} pages a window layer")
        if cfg.kv_quant:
            raise ValueError("engine.kv_quant has no sliding-window form: the int8 pages' "
                             "kernels take no window")

    def _window_gauges(self) -> None:
        from finchat_tpu.engine.kv_cache import page_hbm_bytes
        from finchat_tpu.utils.metrics import METRICS

        per_page = page_hbm_bytes(self.config, self.page_size, kind="window")
        METRICS.set_gauge("finchat_window_kv_bytes", self.window_pager.pages_in_use * per_page)
        METRICS.set_gauge("finchat_window_kv_pool_bytes",
                          self.window_pager.allocator.num_pages * per_page)

    def prefill_room(self, start: int) -> int:
        """Tokens ONE dispatch may carry for a row whose next token stands at
        ``start``: what fits a row's bound of window pages (a chunk that
        starts inside a page ends at a page's end); no limit for a model
        without window layers."""
        if self.window_pager is None:
            return self.engine_cfg.max_seq_len
        return self.window_pager.room(start)

    def head_room(self) -> bool:
        """Whether one more shared head may be registered: its trailing window
        pages must fit beside every slot's bound (always, without window
        layers)."""
        return self.window_pager is None or self.window_pager.room_for_head()

    def _window_advance(self, spans: list[tuple[int, int | None, int]]) -> None:
        """Before a dispatch that carries ``(slot, start, tokens)`` rows
        (``start`` None: the slot's context as the host knows it): slide every
        row's window pages, upload the lists that changed, keep the host's
        context lengths. (Callers ask ``window_pager`` first: the host copies
        of a dispatch's descriptors are made for this alone.)"""
        pager = self.window_pager
        from finchat_tpu.utils.metrics import METRICS

        freed = 0
        for slot, start, n in spans:
            start = int(self._host_ctx[slot]) if start is None else int(start)
            freed += pager.advance(slot, start, int(n))
            self._host_ctx[slot] = start + int(n)
        if freed:
            METRICS.inc("finchat_window_pages_freed_total", freed)
        self._window_upload()

    def _window_upload(self) -> None:
        pager = self.window_pager
        if pager is not None and pager.dirty:
            pager.dirty = False
            # COPIES: the pager goes on changing its arrays in place, and a
            # device array made from a host buffer may alias it (the CPU
            # backend) or read it after this returns (a transfer in flight)
            self.state = dataclasses.replace(
                self.state, win_table=jnp.asarray(pager.table.copy()),
                win_gaps=jnp.asarray(pager.gaps.copy()))
            self._window_gauges()

    def _refuse_what_a_kind_cannot_carry(self, mesh, quant: str) -> None:
        """Each kind of per-row memory beyond paged K/V is written and read by
        ``prefill_step``, ``decode_step`` and ``ragged_mixed_step`` alone:
        every option that reaches another step, rewinds a row or re-lays its
        pages is refused here by name, from ONE table (``NOT_CARRIED``) — none
        may run and be silently wrong."""
        cfg, c = self.engine_cfg, self.config
        on = {
            "engine.kv_quant": bool(cfg.kv_quant),
            "model.quant": bool(quant),
            "engine.spec_tokens": cfg.spec_tokens > 0,
            "engine.kv_sink_pages / engine.kv_window_pages": self.bounded_kv is not None,
            "mesh.* > 1": mesh is not None and mesh.devices.size > 1,
        }
        held = {"recurrent state": c.has_state, "latent pages": bool(c.kv_lora_rank),
                "window pages": bool(c.window), "a drafting module": bool(c.mtp_layers)}
        refused = []  # every kind the model holds says its own reason
        for kind, options in NOT_CARRIED.items():
            named = [f"{option} ({why})" for option, why in options.items() if on[option]]
            if held[kind] and named:
                refused.append(
                    f"a model with {kind} is served by prefill_step, decode_step and "
                    f"ragged_mixed_step only; not supported with it: {', '.join(named)}")
        if refused:
            raise ValueError("; ".join(refused))

    def _refuse_with_held_experts(self, mesh, quant: str) -> None:
        """A model whose expert stacks are a held range of a wider router
        (``moe_mlp``): where its experts live is said ONCE, by the range or by
        a mesh's ``expert`` axis; and no quantized matmul runs over the
        grouped stacks (ROADMAP R1)."""
        c = self.config
        if (mesh is not None and mesh.shape.get("expert", 1) > 1
                and c.moe_router_width != c.n_experts):
            raise ValueError(
                f"mesh.expert={mesh.shape['expert']} together with a held range of experts "
                f"(the first {c.n_experts} of {c.moe_router_width}): one or the other says "
                "where experts live")
        if quant:
            raise ValueError(
                f"model.quant={quant!r} is not supported for a model with fused-GLU expert "
                "stacks: the grouped matmul (lax.ragged_dot) takes no quantized operand")

    @property
    def ssm_state_bytes(self) -> int:
        """Device bytes of the recurrent state and the conv tails (0 for a
        model without a mixer): the second kind of per-row memory."""
        if not self.config.has_state:
            return 0
        return int(self.state.ssm_state.nbytes + self.state.conv_state.nbytes)

    def ssm_snapshot(self, slot: int) -> tuple | None:
        """A copy of ``slot``'s recurrent state and conv tail, every layer
        (device arrays) — what a shared head keeps beside its pages. None
        for a model without a mixer. A model that drafts: the ONE hidden state
        its module's next pair waits on (``mtp_hidden``), which a row admitted
        from the head starts from as it would from a state."""
        if self.config.mtp_layers:  # (the slot an operand: one program for every slot)
            return (jax.lax.dynamic_index_in_dim(
                self.state.mtp_hidden, jnp.int32(slot), 0, keepdims=False),)
        if not self.config.has_state:
            return None
        return _ssm_read_slot(self.state.ssm_state, self.state.conv_state, jnp.int32(slot),
                              config=self.config)

    def detach_head(self, slot: int) -> tuple | None:
        """What a shared head keeps of the slot that just prefilled it:
        ``ssm_snapshot``'s copy of the state and — a model with sliding-window
        layers — the slot's trailing window pages, by OWNERSHIP and not by
        copy: the slot holds none of them afterwards, a row admitted from the
        snapshot references them, ``release_snapshot`` ends it. Only the
        registration of a head calls this (``scheduler._head_snapshot``);
        ``ssm_snapshot`` alone changes nothing."""
        snap = self.ssm_snapshot(slot)
        if self.window_pager is None:
            return snap
        head = self.window_pager.detach_head(slot, int(self._host_ctx[slot]))
        self._window_upload()
        # (a model with window layers and no recurrent state: no copy, the pages alone)
        return (*(snap or (None, None)), head)

    def release_snapshot(self, snap: tuple | None) -> None:
        """A head's snapshot (``detach_head``) is dropped: the window pages it
        holds go back once no row reads them. No-op for every other snapshot."""
        if self.window_pager is not None and snap is not None and len(snap) > 2:
            self.window_pager.release_head(snap[2])
            self._window_gauges()

    def ssm_restore(self, slot: int, snap: tuple) -> None:
        """Start ``slot`` from a snapshot (admission from a shared head: its
        state copied in and, where the snapshot holds a head's window pages,
        those referenced in place of the slot's own)."""
        if self.config.mtp_layers:
            self.state = dataclasses.replace(
                self.state, mtp_hidden=jax.lax.dynamic_update_index_in_dim(
                    self.state.mtp_hidden, snap[0], jnp.int32(slot), 0))
        if self.config.has_state:
            ssm_state, conv_state = _ssm_load_slot(
                self.state.ssm_state, self.state.conv_state, jnp.int32(slot), tuple(snap[:2]),
                config=self.config)
            self.state = dataclasses.replace(
                self.state, ssm_state=ssm_state, conv_state=conv_state)
        if self.window_pager is not None and len(snap) > 2:
            self.window_pager.release(slot)
            self.window_pager.share(slot, snap[2])
            self._window_upload()

    def ssm_admit(self, rows: dict[int, tuple | None]) -> None:
        """Admission owns the state: every admitted slot starts from its
        head's snapshot or, with none, from zero — whatever the slot's last
        row left and whether or not its release-time reset went through (the
        recurrence has no page table or context length to mask a stale
        state)."""
        cold = [slot for slot, snap in rows.items() if snap is None]
        if cold:
            if self.config.has_state:
                self._ssm_clear(cold)
            if self.window_pager is not None:  # (a head's window pages, like its state)
                for slot in cold:
                    self.window_pager.release(slot)
                self._window_upload()
        for slot, snap in rows.items():
            if snap is not None:
                self.ssm_restore(slot, snap)

    @property
    def quant_label(self) -> str:
        """The serving quant mode as ONE label ("bf16", "int8", "int4",
        with "+kv8" when the page pool is int8) — stamped on dispatch
        trace events and the finchat_quant_* gauges so traced timelines
        and dashboards distinguish quantized dispatches (ISSUE 14). Must
        stay within tracing.QUANT_MODES (pinned by tests)."""
        base = self.quant or "bf16"
        return base + ("+kv8" if self.kv_quant else "")

    def _resolve_sp_mode(self, sp_mode: str) -> str:
        """Validate the configured SP mode against this model/mesh; Ulysses
        needs per-TP-shard head counts divisible by the seq axis
        (ops/ulysses.py) — fall back to ring (always valid) otherwise."""
        if sp_mode not in ("ring", "ulysses"):
            raise ValueError(f"unknown sp_mode {sp_mode!r} (supported: 'ring', 'ulysses')")
        if sp_mode == "ulysses" and self.mesh is not None:
            from finchat_tpu.ops.ulysses import ulysses_supported

            c = self.config
            if not ulysses_supported(c.n_heads, c.n_kv_heads, self.mesh,
                                     axis="seq", head_axis="model"):
                logger.warning(
                    "sp_mode=ulysses needs per-shard heads divisible by the seq "
                    "axis (H=%d, Hkv=%d, mesh=%s); falling back to ring",
                    c.n_heads, c.n_kv_heads, dict(self.mesh.shape),
                )
                return "ring"
        return sp_mode

    # --- low-level ops used by the scheduler ----------------------------
    def set_page_table_row(self, slot: int, pages: list[int]) -> None:
        self.set_page_table_rows({slot: pages})

    def set_page_table_rows(self, rows: dict[int, list[int]]) -> None:
        """Assign several slots' page lists in ONE device update: each
        eager ``.at[].set`` is its own dispatch, and a per-slot loop at
        batch 64 pays 64 of them."""
        if not rows:
            return
        idx = self._slot_rows(rows)
        mat = np.zeros((len(idx), self.max_pages_per_seq), np.int32)
        for i, pages in enumerate(rows.values()):
            mat[i, : len(pages)] = pages
        mat[len(rows):] = mat[len(rows) - 1]
        self.state = dataclasses.replace(
            self.state,
            page_table=self.state.page_table.at[jnp.asarray(idx)].set(jnp.asarray(mat)),
        )

    def _slot_rows(self, slots) -> np.ndarray:
        """The slots of one batched eager update as an index of ``max_seqs``
        entries, the last slot repeated (a repeated row writes the same
        values again). One shape whatever the count, and the shape warm-up
        runs: an update shaped by its row count compiled on the scheduler's
        loop, for seconds, whenever a count first came up (PERF.md §6, the
        stalls of PR 24). More slots than ``max_seqs`` keep their count."""
        idx = np.asarray(list(slots), np.int32)
        pad = self.engine_cfg.max_seqs - len(idx)
        return np.concatenate([idx, np.full((pad,), idx[-1], np.int32)]) if pad > 0 else idx

    def _slot_values(self, values, n: int) -> np.ndarray:
        """``values`` padded like :meth:`_slot_rows`'s index of ``n`` entries."""
        vals = np.asarray(list(values), np.int32)
        return np.concatenate([vals, np.full((n - len(vals),), vals[-1], np.int32)])

    def logits_rows(self, logits: Array, rows: list[int]) -> Array:
        """``[n', vocab]``: the rows of a step's logits that the host picks
        from (grammar-constrained rows), ``n'`` the next power of two (the
        last row repeated; the host reads the first ``len(rows)``). A
        handful of shapes, each run in warm-up, where a gather shaped by the
        count compiled on the loop in the middle of a tool decision."""
        n = min(round_up_pow2(len(rows)), logits.shape[0])
        idx = list(rows) + [rows[-1]] * (n - len(rows))
        return logits[jnp.asarray(idx, jnp.int32)]

    def set_context_lens_rows(self, rows: dict[int, int]) -> None:
        """Set several slots' context lengths in ONE device update — used by
        prefix-cache admission to start a slot at the shared prefix length
        (see set_page_table_rows for why batching matters)."""
        if not rows:
            return
        idx = self._slot_rows(rows)
        vals = jnp.asarray(self._slot_values(rows.values(), len(idx)))
        for slot, n in rows.items():
            self._host_ctx[slot] = n
        self.state = dataclasses.replace(
            self.state, context_lens=self.state.context_lens.at[jnp.asarray(idx)].set(vals)
        )

    def set_kv_gap_rows(self, rows: dict[int, int]) -> None:
        """Set several slots' bounded-KV compaction gaps in ONE device
        update (eviction waves / bounded session restores — see
        set_page_table_rows for why batching matters). The gap is host-
        deterministic metadata: the scheduler mirrors it on the handle and
        updates both sides together between dispatches, so every enqueued
        step sees a page table and gap that agree."""
        if not rows:
            return
        idx = self._slot_rows(rows)
        vals = jnp.asarray(self._slot_values(rows.values(), len(idx)))
        self.state = dataclasses.replace(
            self.state, kv_gaps=self.state.kv_gaps.at[jnp.asarray(idx)].set(vals)
        )

    def set_last_token(self, slot: int, token: int) -> None:
        """Override a slot's next decode input — used by grammar-constrained
        sampling after a host-side pick replaces the device-sampled token."""
        self.state = dataclasses.replace(
            self.state, last_tokens=self.state.last_tokens.at[slot].set(token),
            # a draft was made behind the token this one replaces: dropped
            **({"has_draft": self.state.has_draft.at[slot].set(False)}
               if self.config.mtp_layers else {}),
        )

    def reset_slot(self, slot: int) -> None:
        self.reset_slots([slot])

    def reset_slots(self, slots: list[int]) -> None:
        """Clear several slots in one device update (see set_page_table_rows
        for why batching matters)."""
        if not slots:
            return
        idx = jnp.asarray(self._slot_rows(slots))
        self.state = dataclasses.replace(
            self.state,
            page_table=self.state.page_table.at[idx].set(0),
            context_lens=self.state.context_lens.at[idx].set(0),
            last_tokens=self.state.last_tokens.at[idx].set(0),
            kv_gaps=self.state.kv_gaps.at[idx].set(0),
            **({"has_draft": self.state.has_draft.at[idx].set(False)}
               if self.config.mtp_layers else {}),
        )
        self._host_ctx[list(slots)] = 0
        if self.window_pager is not None:
            for slot in slots:
                self.window_pager.release(slot)
            self._window_upload()
        if self.config.has_state:
            self._ssm_clear(slots)

    def _ssm_clear(self, slots: list[int]) -> None:
        keep = np.ones((self.engine_cfg.max_seqs,), bool)
        keep[list(slots)] = False
        ssm_state, conv_state = _ssm_clear_slots(
            self.state.ssm_state, self.state.conv_state, jnp.asarray(keep))
        self.state = dataclasses.replace(
            self.state, ssm_state=ssm_state, conv_state=conv_state)

    def offload_pages(self, page_ids: list[int]):
        """Snapshot physical pages device→host (all layers, K+V+scales) for
        the session KV cache. Blocks until the D2H copy lands — the caller
        is about to free these pages (see kv_cache.gather_pages_host)."""
        from finchat_tpu.engine.kv_cache import gather_pages_host

        s = self.state
        return gather_pages_host(
            s.k_pages, s.v_pages, s.k_scales, s.v_scales, page_ids
        )

    def restore_pages(self, page_ids: list[int], host: tuple) -> None:
        """Write a host snapshot back into freshly allocated pages (session
        cache resume). One XLA scatter per turn — off the jitted hot path."""
        from finchat_tpu.engine.kv_cache import scatter_pages_device

        s = self.state
        k_pages, v_pages, k_scales, v_scales = scatter_pages_device(
            s.k_pages, s.v_pages, s.k_scales, s.v_scales, page_ids, host
        )
        self.state = dataclasses.replace(
            self.state, k_pages=k_pages, v_pages=v_pages,
            k_scales=k_scales, v_scales=v_scales,
        )

    def rebuild_device_state(self) -> None:
        """Tear down and recreate ALL device-resident engine state — KV
        pool, page table, context lens, last tokens, rng — with the weights
        retained (scheduler circuit-breaker recovery: a wedged or poisoned
        device state is replaced wholesale; in-flight sequences were
        recompute-preempted to host and replay through admission). The old
        state is dropped BEFORE the new allocation so peak HBM stays one
        pool, and the new arrays have identical shapes/dtypes/shardings, so
        every compiled step variant (warmup's work) remains valid — no
        recompilation on the recovery path."""
        self.state = None  # free the old pool before allocating the new one
        state = create_state(
            self.config, self.engine_cfg, self.max_pages_per_seq,
            kv_quant=self.kv_quant,
        )
        if self.mesh is not None:
            from finchat_tpu.parallel.sharding import shard_decode_state

            state = shard_decode_state(state, self.mesh, self.config.n_kv_heads)
        self.state = state
        self._host_ctx[:] = 0
        if self.window_pager is not None:
            self.window_pager.reset()
            self._window_upload()

    def _use_ring_prefill(self, prompt_len: int) -> bool:
        return (
            self.mesh is not None
            and self.mesh.shape.get("seq", 1) > 1
            and prompt_len >= self.engine_cfg.ring_prefill_min_tokens
        )

    def _ring_bucket(self, n: int) -> int:
        """Pad a ring-prefill length to a power-of-two bucket (rounded up to
        a seq-axis multiple) so the jit variant count is log2-bounded and
        warmable — per-length shapes would compile fresh per request."""
        n_seq = self.mesh.shape["seq"]
        return -(-round_up_pow2(n) // n_seq) * n_seq

    def prefill_ring(self, slot: int, prompt_ids: list[int]) -> Array:
        """Seq-sharded one-shot prefill of a long prompt (ring attention
        over the mesh's ``seq`` axis); returns last-token logits."""
        assert self.mesh is not None and self.mesh.shape.get("seq", 1) > 1
        n = len(prompt_ids)
        S = self._ring_bucket(n)
        tokens = jnp.asarray(prompt_ids + [0] * (S - n), jnp.int32)[None, :]
        self.state, last_logits = ring_prefill_step(
            self.params, self.state, tokens, jnp.int32(slot), jnp.int32(n),
            config=self.config, page_size=self.page_size, mesh=self.mesh,
            sp_mode=self.sp_mode, qm_backend=self.qm_backend,
        )
        return last_logits

    def ring_segment_tokens(self) -> int:
        """Segment size for the CHUNKED SP prefill (0 = monolithic): the
        configured ``ring_prefill_chunk`` rounded up to a seq-axis
        multiple. Applies to both sp_modes — ring and Ulysses each have a
        prefix-fold segment variant."""
        rc = self.engine_cfg.ring_prefill_chunk
        if rc <= 0 or self.mesh is None:
            return 0
        n_seq = self.mesh.shape.get("seq", 1)
        return -(-rc // n_seq) * n_seq

    def _prefix_page_bucket(self, start_pos: int) -> int:
        """Static page count for a segment's prefix gather: pow-2 bucket
        of the pages holding positions [0, start_pos), capped at the row
        width. Floored at the pages one segment spans so prefixes shorter
        than a segment (a shared-prefix-cache hit on the FIRST segment)
        reuse the smallest warmed bucket instead of compiling a fresh
        sub-rc variant on the request path — the extra gathered pages are
        masked, and their cost is bounded by one segment's own size."""
        floor = -(-self.ring_segment_tokens() // self.page_size)
        need = max(-(-start_pos // self.page_size), 1)
        return min(max(round_up_pow2(need), round_up_pow2(floor)),
                   self.max_pages_per_seq)

    def prefill_ring_segment(self, slot: int, seg_ids: list[int], start_pos: int) -> Array:
        """One segment of a chunked seq-sharded prefill. A segment with
        no cached prefix (``start_pos == 0``) runs the plain ring step
        (bucketed shape shared with the monolithic path); segments with a
        prefix — later segments, or a FIRST segment starting past a
        shared-prefix-cache hit — run the prefix-fold step at the fixed
        segment shape. Returns last-valid-token logits — meaningful for
        the FINAL segment."""
        rc = self.ring_segment_tokens()
        assert rc > 0, "segmented ring prefill requires ring_prefill_chunk > 0"
        n = len(seg_ids)
        assert 0 < n <= rc
        if start_pos == 0:
            return self.prefill_ring(slot, seg_ids)
        tokens = jnp.asarray(seg_ids + [0] * (rc - n), jnp.int32)[None, :]
        self.state, last_logits = ring_prefill_segment_step(
            self.params, self.state, tokens, jnp.int32(slot),
            jnp.int32(start_pos), jnp.int32(n),
            config=self.config, page_size=self.page_size, mesh=self.mesh,
            prefix_pages=self._prefix_page_bucket(start_pos),
            sp_mode=self.sp_mode, qm_backend=self.qm_backend,
        )
        return last_logits

    def prefill_rows(self, tokens: Array, slots: Array, start_pos: Array,
                     n_valid: Array) -> Array:
        """One ``prefill_step`` dispatch over ``[N, prefill_chunk]`` rows;
        returns the last-valid-token logits ``[N, vocab]``. THE call site
        for warm-up, ``prefill_batch`` and the scheduler's prefill round:
        jit keys on which keywords a call passes, so a second site that
        spelled them differently would serve variants warm-up never
        compiled."""
        if self.window_pager is not None:
            self._window_advance([
                (int(s), int(p), int(n)) for s, p, n in zip(
                    np.asarray(slots), np.asarray(start_pos), np.asarray(n_valid)) if n > 0])
        self.state, logits = prefill_step(
            self.params, self.state, tokens, slots, start_pos, n_valid,
            config=self.config, page_size=self.page_size,
            attn_backend=self.attn_backend, qm_backend=self.qm_backend,
        )
        return logits

    def prefill_batch(self, items: list[tuple[int, list[int]]]) -> list[Array]:
        """Chunked prefill of N whole prompts together; returns each
        sequence's final-chunk last-token logits (one [vocab] array per
        item, in input order).

        All N sequences advance one ``prefill_chunk`` per round; prompts
        that are exhausted ride the remaining rounds with ``n_valid = 0``
        (their KV writes go to the trash page). One weights-read serves the
        whole batch per round instead of per sequence.

        Prompts past ``ring_prefill_min_tokens`` on a ``seq > 1`` mesh take
        the seq-sharded ring path instead (one shot, O(S/seq) activation
        memory per device).
        """
        assert items, "empty prefill batch"
        ring = [(i, slot, ids) for i, (slot, ids) in enumerate(items)
                if self._use_ring_prefill(len(ids))]
        if ring:
            results: list[Array | None] = [None] * len(items)
            for i, slot, ids in ring:
                results[i] = self.prefill_ring(slot, ids)
            rest = [(i, it) for i, it in enumerate(items)
                    if results[i] is None]
            if rest:
                rest_logits = self.prefill_batch([it for _, it in rest])
                for (i, _), lg in zip(rest, rest_logits):
                    results[i] = lg
            assert all(r is not None for r in results)
            return results  # type: ignore[return-value]

        C = self.engine_cfg.prefill_chunk
        N = len(items)
        slots = jnp.asarray([slot for slot, _ in items], jnp.int32)
        prompts = [ids for _, ids in items]
        assert all(prompts), "empty prompt in prefill batch"
        rounds = max(-(-len(p) // C) for p in prompts)
        last_logits: list[Array | None] = [None] * N
        for r in range(rounds):
            chunk_tokens = []
            n_valid = []
            start = []
            for p in prompts:
                chunk = p[r * C:(r + 1) * C]
                n_valid.append(len(chunk))
                start.append(min(r * C, len(p)))
                chunk_tokens.append(chunk + [0] * (C - len(chunk)))
            logits = self.prefill_rows(
                jnp.asarray(chunk_tokens, jnp.int32), slots,
                jnp.asarray(start, jnp.int32), jnp.asarray(n_valid, jnp.int32),
            )
            for i, p in enumerate(prompts):
                if n_valid[i] and r * C + n_valid[i] == len(p):
                    last_logits[i] = logits[i]
        assert all(l is not None for l in last_logits)
        return last_logits  # type: ignore[return-value]

    def prefill(self, slot: int, prompt_ids: list[int]) -> Array:
        """Chunked prefill of a whole prompt into a slot; returns the final
        chunk's last-token logits."""
        return self.prefill_batch([(slot, prompt_ids)])[0]

    def warmup(self, prefill_batch_sizes: list[int] | None = None) -> float:
        """Compile every serving step variant with state-neutral executions
        (verdict r3 weak #4/#5: the first request used to pay full XLA
        compilation inside the 100 s watchdog, and the first tool decision
        triggered a fresh compile of the return_logits decode variant).

        - ``prefill_step`` for every power-of-two batch the scheduler can
          dispatch (it pads rounds to powers of two) — run with
          ``n_valid = 0`` so writes land in the trash page and
          ``context_lens`` gains zero;
        - ``decode_step`` with ``return_logits`` False AND True, all slots
          inactive;
        - ``commit_first_token`` (slot 0's last_token is overwritten by the
          slot's real first prefill completion).

        Returns the wall-clock seconds spent (mostly XLA compilation).
        """
        import time

        import numpy as np

        t0 = time.perf_counter()
        cfg = self.engine_cfg
        B = cfg.max_seqs
        n_variants = 0  # compiled-variant tally → finchat_warmup_compiled_variants
        if prefill_batch_sizes is None:
            # every power of two up to AND INCLUDING the scheduler's largest
            # round padding (round_up_pow2 — the shared policy; for a
            # non-power-of-two max_seqs the padding exceeds it)
            top = round_up_pow2(B)
            prefill_batch_sizes = [1]
            while prefill_batch_sizes[-1] < top:
                prefill_batch_sizes.append(prefill_batch_sizes[-1] * 2)
        C = cfg.prefill_chunk
        for n in prefill_batch_sizes:
            zeros = jnp.zeros((n,), jnp.int32)
            self.prefill_rows(jnp.zeros((n, C), jnp.int32), zeros, zeros, zeros)
            n_variants += 1
        if cfg.mixed_step:
            # the packed ragged variants the scheduler's mixed path
            # dispatches (ragged_mixed_step) — ONE pow-2 packed-token
            # bucket axis, descriptors fixed at [max_seqs]; all-padding
            # rows (row_len 0, nothing armed) keep it state-neutral.
            # Replaces PR 4's row-bucket × chunk-bucket matrix AND its
            # per-mode demotions — the collapsed warmup matrix is the point
            # (ISSUE 10; the gauge below records it).
            R = B
            rz = jnp.zeros((R,), jnp.int32)
            rflags = jnp.zeros((R,), bool)
            for t in self.ragged_token_buckets():
                self.state, _, _, _ = ragged_mixed_step(
                    self.params, self.state,
                    jnp.zeros((t,), jnp.int32), jnp.full((t,), R, jnp.int32),
                    rz, rz, rz, rflags, rflags, rz,
                    jnp.zeros((R,), jnp.float32), jnp.ones((R,), jnp.float32),
                    jnp.zeros((R,), jnp.int32),
                    config=self.config, page_size=self.page_size,
                    attn_backend=self.attn_backend, qm_backend=self.qm_backend,
                    spec_width=self.ragged_spec_width, **self._ragged_kw(),
                )
                n_variants += 1
        inactive = jnp.zeros((B,), bool)
        temp = jnp.full((B,), 1.0, jnp.float32)
        top_p = jnp.ones((B,), jnp.float32)
        top_k = jnp.zeros((B,), jnp.int32)
        for return_logits in (False, True):
            self.state, *_ = decode_step(
                self.params, self.state, inactive, temp, top_p, top_k,
                config=self.config, page_size=self.page_size,
                attn_backend=self.attn_backend, qm_backend=self.qm_backend, return_logits=return_logits,
                **self._draft_kw(inactive),
            )
            n_variants += 1
        if cfg.spec_tokens > 0:
            # both verify-step variants (the scheduler's spec decode path)
            zero_drafts = jnp.zeros((B, cfg.spec_tokens), jnp.int32)
            zero_n = jnp.zeros((B,), jnp.int32)
            for return_logits in (False, True):
                self.state, _, _, _ = verify_step(
                    self.params, self.state, inactive, zero_drafts, zero_n,
                    temp, top_p, top_k,
                    config=self.config, page_size=self.page_size,
                    attn_backend=self.attn_backend, qm_backend=self.qm_backend, return_logits=return_logits,
                )
                n_variants += 1
        self.state, _ = commit_first_token(
            self.state, jnp.int32(0),
            jnp.zeros((self.config.vocab_size,), jnp.float32),
            jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0),
        )
        n_variants += 1
        # the eager updates the scheduler makes between steps, each at its
        # one padded shape, and the constrained rows' logits gather at each
        # of its counts: slot 0 is empty here, so nothing changes
        self.set_page_table_rows({0: []})
        self.set_context_lens_rows({0: 0})
        self.set_kv_gap_rows({0: 0})
        self.set_last_token(0, 0)
        step_logits = jnp.zeros((B, self.config.vocab_size), jnp.float32)
        for n in sorted({min(round_up_pow2(k), B) for k in range(1, B + 1)}):
            self.logits_rows(step_logits, [0] * n)
        del step_logits
        if self.config.has_state or self.config.mtp_layers:
            # the state's own three programs (admission from a head's
            # snapshot, the reset of a slot, the snapshot itself): slot 0 is
            # zero here, so reading and restoring it changes nothing
            self.ssm_restore(0, self.ssm_snapshot(0))
            n_variants += 3
        self.reset_slots([0])
        # ring-prefill length buckets (seq > 1 meshes): every bucket the
        # router can produce, INCLUDING the top one covering max_seq_len
        # (stopping at max_seq_len itself would miss e.g. the 8192 bucket a
        # 5000-token prompt maps to under a 6000 max)
        if self.mesh is not None and self.mesh.shape.get("seq", 1) > 1:
            rc = self.ring_segment_tokens()
            # segmented: a no-prefix first segment is min(prompt, rc)
            # tokens, so the plain-ring buckets that can actually occur
            # are bucket(min(ring_min, rc))..bucket(rc) — when ring_min >
            # rc every first segment is exactly rc (warming only
            # bucket(ring_min) would leave the always-used bucket(rc)
            # cold). Monolithic keeps the full enumeration.
            ring_min = self.engine_cfg.ring_prefill_min_tokens
            if rc > 0:
                S = self._ring_bucket(min(ring_min, rc))
                top = self._ring_bucket(rc)
            else:
                S = self._ring_bucket(ring_min)
                top = self._ring_bucket(self.engine_cfg.max_seq_len)
            while True:
                self.state, _ = ring_prefill_step(
                    self.params, self.state, jnp.zeros((1, S), jnp.int32),
                    jnp.int32(0), jnp.int32(0),
                    config=self.config, page_size=self.page_size,
                    mesh=self.mesh, sp_mode=self.sp_mode, qm_backend=self.qm_backend,
                )
                n_variants += 1
                if S >= top:
                    break
                S = self._ring_bucket(S + 1)
            if rc > 0:
                # later segments: fixed rc shape x each prefix-page
                # bucket a start position can map to (pow-2 enumeration,
                # same policy as the ring buckets)
                pb = self._prefix_page_bucket(rc)
                top_pb = self._prefix_page_bucket(self.engine_cfg.max_seq_len)
                while True:
                    self.state, _ = ring_prefill_segment_step(
                        self.params, self.state, jnp.zeros((1, rc), jnp.int32),
                        jnp.int32(0), jnp.int32(rc), jnp.int32(0),
                        config=self.config, page_size=self.page_size,
                        mesh=self.mesh, prefix_pages=pb,
                        sp_mode=self.sp_mode, qm_backend=self.qm_backend,
                    )
                    n_variants += 1
                    if pb >= top_pb:
                        break
                    pb = min(pb * 2, top_pb)
        if (self.engine_cfg.session_cache and self.engine_cfg.session_cache_bytes > 0
                and not (self.config.has_state or self.config.window)):
            # the session tier's offload at a row's end is an eager take shaped
            # by its page count (kv_cache.gather_pages_host): every bucket once
            from finchat_tpu.engine.kv_cache import TRASH_PAGE, gather_bucket

            n = 1
            while n <= gather_bucket(self.max_pages_per_seq):
                self.offload_pages([TRASH_PAGE] * n)
                n *= 2
        np.asarray(self.state.context_lens)  # barrier: compilation done
        elapsed = time.perf_counter() - t0
        # recorded for the warmup-matrix-collapse observability (ISSUE 10):
        # the scheduler re-emits it as the finchat_warmup_compiled_variants
        # gauge through its (possibly replica-labeled) metrics view
        self.compiled_variants = n_variants
        # the variant COUNT is quant-independent by construction (weight
        # dtype never keys a jit cache entry — the quantized tree swaps in
        # under the same traced shapes), and qm_backend-independent too
        # (resolved once at construction, one static value per engine —
        # tests/test_quant_matmul.py holds ref/fused counts equal), so the
        # collapsed-matrix gauge stays comparable across modes; the
        # labels make mode and matmul backend visible
        logger.info(
            "engine warmup [%s, qm=%s]: prefill batches %s + %d serving "
            "variants compiled in %.1fs",
            self.quant_label, self.qm_backend, prefill_batch_sizes,
            n_variants, elapsed,
        )
        return elapsed

    def decode(self, active, temperature, top_p, top_k, return_logits: bool = False,
               draft_ok=None):
        """One ``decode_step``. A model that drafts (``config.mtp_layers``)
        returns ``next_tokens`` [max_seqs, 2] (``_draft_decode_step`` says what
        the second column holds) and reads ``draft_ok`` [max_seqs] bool: the
        rows that may use their pending draft (None: every row)."""
        from finchat_tpu.utils.metrics import METRICS

        METRICS.inc("finchat_decode_dispatches_total")
        if self.window_pager is not None:
            self._window_advance([(int(s), None, 1) for s in np.flatnonzero(np.asarray(active))])
        drafts = self._draft_kw(active, draft_ok)
        self.state, next_tokens, logits, self.moe_experts = decode_step(
            self.params, self.state, active, temperature, top_p, top_k,
            config=self.config, page_size=self.page_size,
            attn_backend=self.attn_backend, qm_backend=self.qm_backend, return_logits=return_logits,
            **drafts,
        )
        return (next_tokens, logits) if return_logits else next_tokens

    @property
    def ragged_spec_width(self) -> int:
        """``ragged_mixed_step``'s ``spec_width``: the host's drafts a row
        (``engine.spec_tokens``), or ONE for a model that drafts with its own
        module (a decode row is then its last token and its draft)."""
        return 1 if self.config.mtp_layers else self.engine_cfg.spec_tokens

    def _draft_kw(self, active, draft_ok=None) -> dict:
        """What only a model that drafts passes to ``decode_step`` (jit keys on
        the keywords a call passes: warm-up and ``decode`` spell them alike)."""
        if not self.config.mtp_layers:
            return {}
        return {"draft_ok": jnp.ones_like(active) if draft_ok is None else draft_ok}

    def ragged_token_buckets(self) -> list[int]:
        """Packed-token buckets for the ragged mixed step (ascending
        pow-2). ONE dimension replaces PR 4's row-bucket × chunk-bucket
        matrix: the dispatch shape varies only in the packed buffer length
        (descriptors are fixed at ``[max_seqs]``), so the compiled-variant
        count is log2 in max_seqs × chunk instead of their product — and
        spec/constrained rows reuse the SAME variants instead of
        demoting to per-mode dispatch schedules. Floored at 64 tokens:
        small rounds pad into the smallest warmed bucket (padding rows are
        fully masked), trading a little dead compute at light load for
        fewer startup compiles."""
        cfg = self.engine_cfg
        top = round_up_pow2(
            cfg.max_seqs * max(cfg.prefill_chunk, cfg.spec_tokens + 1)
        )
        buckets = [min(64, top)]
        while buckets[-1] < top:
            buckets.append(buckets[-1] * 2)
        return buckets

    def ragged_bucket(self, n_tokens: int) -> int:
        """Smallest warmed packed-token bucket holding ``n_tokens``."""
        return next(b for b in self.ragged_token_buckets() if b >= n_tokens)

    def ragged_round(self, tokens, tok_row, row_slot, row_start, row_len,  # finchat-lint: hot
                     row_from_device, row_arm, row_n_drafts, temperature, top_p, top_k):
        """One packed ragged dispatch (see ragged_mixed_step); returns
        ``(emitted, n_emitted, row_logits)`` device arrays — the scheduler
        fetches once per round. Counted at the dispatch seam like decode():
        one enqueued device program, one count."""
        from finchat_tpu.utils.metrics import METRICS

        METRICS.inc("finchat_mixed_dispatches_total")
        if self.window_pager is not None:
            self._window_advance([
                (int(s), None if dev else int(p), int(n)) for s, p, n, dev in zip(
                    np.asarray(row_slot), np.asarray(row_start), np.asarray(row_len),
                    np.asarray(row_from_device)) if n > 0])
        self.state, emitted, n_emitted, row_logits = ragged_mixed_step(
            self.params, self.state, tokens, tok_row, row_slot,
            row_start, row_len, row_from_device, row_arm, row_n_drafts,
            temperature, top_p, top_k,
            config=self.config, page_size=self.page_size,
            attn_backend=self.attn_backend, qm_backend=self.qm_backend,
            spec_width=self.ragged_spec_width, **self._ragged_kw(),
        )
        return emitted, n_emitted, row_logits

    def ragged_mixed(self, tokens, tok_row, row_slot, row_start, row_len,
                     row_from_device, row_arm, row_n_drafts, temperature, top_p, top_k,
                     loop_active, loop_temperature, loop_top_p, loop_top_k, eos_id: int):
        """``ragged_round`` as the benchmark calls it (perfbench/correct.py,
        sparse_control.py): five arguments no step reads, a fourth value that
        is None; it goes when they call the short form (ROADMAP D9 (l))."""
        return *self.ragged_round(tokens, tok_row, row_slot, row_start, row_len, row_from_device,
                                  row_arm, row_n_drafts, temperature, top_p, top_k), None

    def _ragged_kw(self) -> dict:
        """What only a model with a mixer or with latent attention (both
        regroup a round's packed tokens to rows) passes to ``ragged_mixed_step``
        (jit keys on the keywords a call passes: the others' calls stay as
        warm-up compiled them): no row of a round is longer than a chunk."""
        if not (self.config.has_state or self.config.kv_lora_rank):
            return {}
        return {"max_row_tokens": self.engine_cfg.prefill_chunk}

    def decode_spec(self, active, drafts, n_drafts, temperature, top_p, top_k,
                    return_logits: bool = False):
        """Speculative verify step (see verify_step). ``drafts`` [B, Kd]
        keys the compiled shape — callers pad to a fixed Kd."""
        from finchat_tpu.utils.metrics import METRICS

        # counted at the DISPATCH seam like decode() and the ragged round:
        # a verify step is one enqueued device program, and dispatches per
        # coexist iteration on the split path must see the spec plane too
        METRICS.inc("finchat_decode_dispatches_total")
        self.state, emitted, n_emitted, logits = verify_step(
            self.params, self.state, active, drafts, n_drafts,
            temperature, top_p, top_k,
            config=self.config, page_size=self.page_size,
            attn_backend=self.attn_backend, qm_backend=self.qm_backend, return_logits=return_logits,
        )
        return (emitted, n_emitted, logits) if return_logits else (emitted, n_emitted)
