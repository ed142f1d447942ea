"""Llama-family decoder in pure JAX.

Replaces the reference's external LLM calls (``llm_agent.py:34-45`` — two
ChatGoogleGenerativeAI instances) with an in-tree model. Design is TPU-first:

- Params are plain pytrees with all layers STACKED on a leading axis so the
  forward pass is a single ``lax.scan`` over layers — one compiled layer body
  instead of n_layers inlined copies (fast compiles, identical HLO per step).
- bf16 weights/activations, fp32 softmax and RMSNorm accumulation (MXU-
  friendly dtype policy).
- The attention inner op is a pluggable callback so the same forward serves
  training (full causal), chunked prefill, and paged decode, with either the
  jnp reference or Pallas kernels underneath.
- Static shapes everywhere; positions are explicit inputs (no data-dependent
  Python control flow under jit).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import Array, lax

from finchat_tpu.models import gdn, mla, sambay
from finchat_tpu.models.quant import Q4Tensor, QTensor, dense, dequantize, flat_fence
from finchat_tpu.models.ssm import mixer, scaled
from finchat_tpu.ops import moe_step, ssm_step

# attention callback signature:
#   fn(q[B,S,H,D], k[B,S,Hkv,D], v[B,S,Hkv,D], layer_cache, layer_idx) ->
#   (out[B,S,H,D], new_layer_cache)
AttentionFn = Callable[[Array, Array, Array, Any, Array], tuple[Array, Any]]

# the kinds of layer a ``layer_pattern`` may name (the published configs' own
# words): softmax attention over the paged cache, or the gated delta rule over
# a recurrent state by slot (models/gdn.py), or the Mamba-2 mixer alone
# (models/ssm.py) over its own state by slot, or softmax attention over a
# window (``sliding_attention``: the same projections as a FULL layer's, pages
# in a second pool on a bounded page list a row). Each is followed by the MLP
FULL, LINEAR, MAMBA = "full_attention", "linear_attention", "mamba"
WINDOW = sambay.WINDOW
# the kinds only a ``layer_plan`` may name (models/sambay.py, which has a
# block of its own for WINDOW layers too): the Mamba-1 mixer, a gated memory
# unit (no state, no cache) and attention that reads another layer's pages
MAMBA1, GMU, CROSS = sambay.MAMBA1, sambay.GMU, sambay.CROSS

# moe_mlp's one rule among its THREE forms (see there; ``_moe_form``). Dense
# dispatch computes every held expert over every token: router width / picks
# a token times the FLOPs the picks need. Up to this factor it is taken
# (static shapes, no sort, no gather; Mixtral's 8 / 2: a step touches every
# expert anyway); past it a model "routes sparsely"
# (``LlamaConfig.moe_sparse``) ...
MOE_DENSE_WASTE_MAX = 4
# ... and its calls of more than this many tokens take the grouped form. Up to
# it a pass over the held stacks is bound by the weights' bytes and not by its
# FLOPs (a token is one FLOP a weight byte; a v5e has 240 of them a byte): the
# touched pass (ops/moe_step.py) reads the experts the call touched, once, and
# dense dispatch — its reference, and the form where the pass does not apply —
# all held ones. On the chip the pass is never the slower of the two: a layer
# of Granite's at 16 / 64 / 128 tokens with all 36 touched 906 / 909 / 914 us
# against 910 / 912 / 918 (PERF.md section 6, PR 35), so no second threshold
MOE_DENSE_TOKENS_MAX = 128


@dataclass(frozen=True)
class AttnKind:
    """What of attention's shape is a KIND's (``LlamaConfig.attn_kinds``): the
    K/V heads its layers keep a token, the base its q and k are rotated at
    (None: not rotated), and whether its softmax has a SINK — one learned
    logit a query head that takes probability and gives no value."""
    n_kv_heads: int
    rope_theta: float | None = 10_000.0
    sink: bool = False


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 260
    dim: int = 128
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 2
    hidden_dim: int = 256
    rope_theta: float | None = 10_000.0  # None = q and k are not rotated
    # the kinds of layer whose q and k are rotated (a ``layer_pattern``'s
    # words); empty = every attention layer. (WINDOW,): window layers rotated,
    # full layers not
    rope_kinds: tuple[str, ...] = ()
    norm_eps: float = 1e-5
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = False
    # MoE (Mixtral-family): 0 = dense MLP. When > 0 the per-layer MLP is
    # n_experts SwiGLU experts with top-k routing; expert weights shard
    # over the mesh's `expert` axis (EP) — see moe_mlp below.
    n_experts: int = 0
    top_k_experts: int = 2
    # a chip's share of a wider layer: the router scores ``moe_router_width``
    # experts (0 = n_experts) of which THIS process holds the first
    # ``n_experts``; a pick that falls on an absent expert adds nothing here
    # (its chip adds it), and the gates stay normalised over all picks.
    # Nothing stands in for the absent chip or its exchange
    moe_router_width: int = 0
    # a shared expert of this width beside the routed ones: every token,
    # unweighted. 0 = none
    moe_shared_dim: int = 0
    # the GLU's two halves as one matrix, [gate | up]: ``moe_in [E, D, 2F]``
    # and ``moe_out [E, F, D]`` instead of ``moe_gate`` / ``moe_up`` /
    # ``moe_down`` (and ``shared_in`` / ``shared_out`` beside them)
    moe_fused_glu: bool = False
    # a head's width where it is not dim / n_heads (0 = that quotient)
    head_dim: int = 0
    # the softmax scale where it is not head_dim ** -0.5 (None = that): handed
    # to the attention kernels' ``scale`` as it is, never folded into q
    attention_scale: float | None = None
    # one scalar on BOTH sub-blocks' outputs before the residual addition
    residual_multiplier: float = 1.0
    # scalar µP multipliers (Falcon-H1). 1 = absent: nothing is emitted for
    # it, so a config without them compiles to the program it always was
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    mlp_multipliers: tuple[float, float] = (1.0, 1.0)  # gate, down
    # a Mamba-2 mixer (models/ssm.py). Without a ``layer_pattern``: beside
    # attention in every layer, both on one normed input, their outputs summed
    # into the residual (Falcon-H1). With one: the MAMBA layers' mixer, alone
    # in attention's place. ssm_heads 0 = none: today's block
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0  # state channels a head-channel (N)
    ssm_groups: int = 1  # groups of B/C
    ssm_conv: int = 4  # width of the causal depthwise conv
    ssm_chunk: int = 128  # block of the chunked scan
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple[float, ...] = (1.0,) * 5  # z, xs, B, C, dt
    # layers of more than one kind: the kinds of ONE period, repeated down the
    # depth (Olmo-Hybrid: three LINEAR, then one FULL; Granite-4.0-H: five
    # MAMBA, one FULL, four MAMBA). Empty = every layer alike, today's block.
    # Parameters and caches are stacked by KIND: FULL layers own pages of the
    # first pool and WINDOW layers (``window``, below) of the second, both kinds
    # share the ``attn_*`` stacks; only LINEAR or MAMBA layers (or the mixer in
    # every layer, above) own state
    layer_pattern: tuple[str, ...] = ()
    # attention's shape by KIND, where the FULL and the WINDOW layers of a
    # pattern differ in it: ((kind, AttnKind), ...) names both. Each kind then
    # has ``attn_k`` / ``attn_v`` stacks of its own (the WINDOW layers'
    # ``swa_k`` / ``swa_v``, and ``swa_sink`` [Lw, H] float32 where they have
    # a sink) and pages as wide as ITS heads (``kv_widths``); ``n_kv_heads``,
    # ``rope_theta`` and ``rope_kinds`` are then not read for those layers.
    # Empty = both kinds share the ``attn_*`` stacks: today's block
    attn_kinds: tuple[tuple[str, AttnKind], ...] = ()
    # the leading dims of a q and a k head that are rotated (dim i paired with
    # i + rope_dim / 2); the others pass as they are. 0 = the whole head
    rope_dim: int = 0
    # a scalar on v before attention (1 = absent: nothing is emitted)
    value_scale: float = 1.0
    # the seeded sinks' standard deviation (a checkpoint brings its own)
    sink_init_std: float = 1.0
    # RMSNorm over the whole width of q and of k, before the heads are split
    qk_norm: bool = False
    # RMSNorm over each HEAD of q and of k, after the split: one weight vector
    # of ``head_dim`` for q and one for k (``attn_q_norm`` / ``attn_k_norm``
    # [L, head_dim]); not both this and ``qk_norm``
    qk_head_norm: bool = False
    # a sigmoid gate on attention's output before its projection, from a
    # projection of the layer's normed input (``attn_gate`` [L, D, H * hd]):
    # a = W_o (o * sigmoid(W_g h))
    attn_gate: bool = False
    # the Olmo family's placement: a norm on each sub-block's OUTPUT and none
    # on its input, x = x + Norm(f(x))
    norm_after: bool = False
    # a norm on each sub-block's input AND its output, x = x + Norm_out(f(
    # Norm_in(x))): ``ln_attn_out`` / ``ln_mlp_out`` beside ``ln_attn`` /
    # ``ln_mlp``; not both this and ``norm_after``
    norm_both: bool = False
    # the LINEAR layers' gated delta rule (models/gdn.py); gdn_heads 0 = none
    gdn_heads: int = 0
    gdn_key_dim: int = 0  # a head's keys and queries
    gdn_value_dim: int = 0  # a head's values
    gdn_conv: int = 4  # width of the causal depthwise conv over q, k, v
    gdn_neg_eigval: bool = False  # beta in (0, 2): negative eigenvalues allowed
    # 0: the decay a scalar a head and a SiLU output gate as wide as the values
    # (Olmo-Hybrid). r > 0 (Kimi Delta Attention): the decay a VECTOR over a
    # head's key channels through a projection of rank r (``dt_bias`` one a
    # channel), and a SIGMOID output gate through another of rank r with a bias
    gdn_gate_rank: int = 0
    # latent attention (models/mla.py): q and kv go through low-rank latents
    # with an RMSNorm on each (``q_lora_rank`` 0: q is ONE projection of the
    # input, no latent and no norm); ONE row a token, [c_kv | k_rope], is key
    # and value at once for every head, and it is what the pool's pages hold.
    # kv_lora_rank 0 = none: today's block. ``head_dim`` is then a head's
    # q/k width, qk_nope_dim + qk_rope_dim, and ``n_kv_heads`` 1. A KIND's, not
    # a model's: under a ``layer_pattern`` the FULL layers are the latent ones
    # (they own the pool's pages) beside LINEAR layers that own state by slot.
    # ``rope_theta`` None: neither q_rope nor k_rope is rotated
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0  # the rotated part of a head's q and of the shared key
    # a head's values where they are not as wide as its keys — latent
    # attention's (required there), or K/V heads' (0 = ``head_dim``: keys of
    # 192 over values of 128 are pages of two widths, ``kv_widths``)
    v_head_dim: int = 0
    # YaRN's per-frequency correction of the rotation (models/mla.py
    # ``RopeScaling``); None = plain frequencies
    rope_scaling: Any = None
    # the indexer beside latent attention: ``index_heads`` query heads of
    # ``index_head_dim`` score every context token against ONE key row a token
    # (a second paged array on the same page table), and a query attends to
    # its ``index_topk`` best-scored tokens only — the exact k largest.
    # index_topk 0 = none: every token of the context is attended
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # the router's rule (moe_mlp), as data: "softmax" = the k largest logits,
    # gates the softmax over them (Mixtral, Granite); "sigmoid" = scores
    # sigmoid(logit), picks by score + a selection bias that chooses and does
    # not weigh (leaf ``router_bias`` [L, R] float32, with ``moe_select_bias``),
    # limited to the ``moe_topk_groups`` best of ``moe_groups`` groups (a
    # group's score the sum of its 2 largest; 0 = no groups), gates the picked
    # SCORES, over their sum with ``moe_norm_picks``, times ``moe_gate_scale``
    moe_score: str = "softmax"
    moe_select_bias: bool = False
    moe_groups: int = 0
    moe_topk_groups: int = 0
    moe_gate_scale: float = 1.0
    moe_norm_picks: bool = True
    # the seeded selection bias's standard deviation (``init_params``; a
    # checkpoint brings its own): it trains from zero to BALANCE the experts'
    # load, so it is drawn small against the scores' spread (a sigmoid of a
    # unit-normal logit spreads by 0.21) — large enough to move picks, small
    # enough not to herd every row onto the same experts
    moe_bias_init_std: float = 0.1
    # dense layers in front of the routed ones (an MLP of ``dense_hidden_dim``
    # in the experts' place): stacks of their own, ``params["dense_layers"]``,
    # run before the scan; ``n_layers`` counts them and a ``layer_pattern``
    # covers the layers behind them. ``leading_kinds`` names each one's kind
    # (FULL or WINDOW; empty = every one FULL): its pages come first
    # in its kind's pool; or LINEAR: its state comes first in the state stack
    leading_dense_layers: int = 0
    dense_hidden_dim: int = 0
    leading_kinds: tuple[str, ...] = ()
    # layers of more than one kind in more than one RUN of periods: segments
    # of (the kinds of one period, its repeats), one after another down the
    # depth — 8 x (mamba1, sliding_attention), 1 x (mamba1, full_attention),
    # 7 x (gmu, cross_attention) is Phi-4-mini-flash (models/sambay.py has the
    # kinds and their block: LayerNorm or RMSNorm, a fused [gate | up] MLP).
    # Empty = a ``layer_pattern`` or none: the blocks above. Parameters and
    # caches are stacked by kind as under a pattern; WINDOW layers own a second
    # pool and a bounded page list a row, CROSS layers own nothing
    layer_plan: tuple[tuple[tuple[str, ...], int], ...] = ()
    # a WINDOW layer (a plan's or a pattern's) attends the token itself and the
    # ``window - 1`` before it; set where the model has such layers, and only there
    window: int = 0
    # the MAMBA1 layers' mixer: channels E, state channels a channel N, the
    # rank dt passes through, the causal depthwise conv's width
    m1_inner: int = 0
    m1_state: int = 16
    m1_dt_rank: int = 0
    m1_conv: int = 4
    # next-token-prediction modules behind the trunk (``num_nextn_predict_layers``;
    # DeepSeek-V3's): ``params["mtp"]`` — two norms, a projection of [embedding
    # | trunk hidden] back to ``dim``, ONE block of the trunk's own routed kind
    # with leaves and latent pages of its own (the pool is one layer deeper),
    # a norm before the trunk's head. A model that has one DRAFTS with it: the
    # decode step verifies the module's token at width 2 (engine/engine.py).
    # 0 = none: today's block. More than one is not built
    mtp_layers: int = 0
    # (a plan's block is models/sambay.py's and has ONE form: differential
    # attention — n_heads / n_kv_heads / head_dim are then the kernel's, a
    # pair's width —, LayerNorm with bias, biases on the attention projections)

    def __post_init__(self) -> None:
        if not self.head_dim:
            object.__setattr__(self, "head_dim", self.dim // self.n_heads)
        if self.layer_plan:
            if (self.layer_pattern or self.ssm_heads or self.gdn_heads or self.kv_lora_rank
                    or self.n_experts or self.leading_dense_layers or self.qk_norm
                    or self.norm_after or self.qk_head_norm or self.attn_gate
                    or self.norm_both or self.rope_kinds):
                raise ValueError("a layer_plan is not combined with a layer_pattern, a Mamba-2 "
                                 "mixer, linear or latent attention, experts, a q/k norm, an "
                                 "output gate, rope_kinds, norm_after or norm_both")
            sambay.validate(self)
        elif self.m1_inner:
            raise ValueError(f"m1_inner and {MAMBA1!r}, {GMU!r} and {CROSS!r} layers are a "
                             "layer_plan's")
        elif bool(self.window) != (WINDOW in self.layer_pattern + self.leading_kinds):
            raise ValueError(f"window and {WINDOW!r} layers (in layer_pattern or leading_kinds; "
                             f"{MAMBA1!r}, {GMU!r} and {CROSS!r} are a layer_plan's) go together")
        if self.window and not self.layer_plan and (
                self.ssm_heads or self.gdn_heads or self.kv_lora_rank):
            raise ValueError(f"{WINDOW!r} layers of a layer_pattern stand beside {FULL!r} layers "
                             "alone: no mixer, linear or latent attention (a row's snapshot "
                             "would hold state and window pages of two blocks)")
        if self.attn_kinds:
            kinds = dict(self.attn_kinds)
            if (set(kinds) != {FULL, WINDOW} or len(kinds) != len(self.attn_kinds)
                    or self.layer_plan or not self.window or self.rope_kinds):
                raise ValueError(
                    f"attn_kinds names {FULL!r} and {WINDOW!r}, once each, for a layer_pattern "
                    "with window layers, and says which kinds are rotated itself (no rope_kinds)")
            if any(self.n_heads % shape.n_kv_heads for shape in kinds.values()):
                raise ValueError("attn_kinds: a kind's K/V heads divide n_heads")
            if kinds[FULL].sink:  # (a full layer's walk has a stacked pass to start too)
                raise ValueError(f"attn_kinds: a sink is the {WINDOW!r} kind's softmax's")
        if (self.rope_dim or self.value_scale != 1.0 or (self.v_head_dim and not self.kv_lora_rank)) \
                and (self.layer_plan or self.kv_lora_rank):
            raise ValueError("rope_dim, value_scale and a v_head_dim beside K/V heads are the "
                             "llama block's: not a layer_plan's, nor latent attention's")
        if self.rope_dim % 2 or self.rope_dim > self.head_dim:
            raise ValueError("rope_dim: an even number of a head's leading dims")
        if self.qk_norm and self.qk_head_norm:
            raise ValueError("qk_norm (over the whole width) and qk_head_norm (over each head): "
                             "one or the other")
        if self.norm_after and self.norm_both:
            raise ValueError("norm_after (output alone) and norm_both (input and output): one "
                             "or the other")
        if set(self.rope_kinds) - {FULL, WINDOW} or (self.rope_kinds and self.rope_theta is None):
            raise ValueError(f"rope_kinds names the rotated kinds among {FULL!r} and {WINDOW!r}, "
                             "and comes with a rope_theta")
        if self.kv_lora_rank:
            if self.head_dim != self.qk_nope_dim + self.qk_rope_dim or self.n_kv_heads != 1:
                raise ValueError(
                    "latent attention: head_dim is qk_nope_dim + qk_rope_dim and n_kv_heads 1 "
                    "(one latent row a token serves every head)")
            if not self.v_head_dim:
                raise ValueError("latent attention comes with v_head_dim")
            if self.rope_theta is None and self.rope_scaling is not None:
                raise ValueError("rope_scaling corrects a rotation: it comes with a rope_theta")
            if (self.ssm_heads or self.qk_norm or self.norm_after or self.qk_head_norm
                    or self.attn_gate or self.norm_both):
                raise ValueError("latent attention is not combined with a Mamba-2 mixer, a q/k "
                                 "norm, an output gate, norm_after or norm_both")
        if bool(self.index_topk) != bool(self.index_heads and self.index_head_dim):
            raise ValueError("index_topk, index_heads and index_head_dim go together")
        if self.index_topk and not self.kv_lora_rank:
            raise ValueError("the indexer's selection is latent attention's (kv_lora_rank)")
        if self.index_topk and not self.q_lora_rank:
            raise ValueError("the indexer's queries come off the q latent (q_lora_rank)")
        if self.moe_score not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_score {self.moe_score!r}: 'softmax' or 'sigmoid'")
        if self.moe_score == "softmax" and (
                self.moe_select_bias or self.moe_groups or self.moe_gate_scale != 1.0):
            raise ValueError("a selection bias, groups and a gate scale are the 'sigmoid' "
                             "router's")
        if self.moe_groups and (
                (self.moe_router_width or self.n_experts) % self.moe_groups
                or not 0 < self.moe_topk_groups <= self.moe_groups):
            raise ValueError("moe_groups divides the router's width, and moe_topk_groups of "
                             "them are kept")
        if self.leading_dense_layers and (
                not self.dense_hidden_dim or not self.n_experts
                or self.leading_dense_layers >= self.n_layers):
            raise ValueError("leading_dense_layers: dense layers of dense_hidden_dim in front of "
                             "routed ones")
        if self.leading_kinds and (
                len(self.leading_kinds) != self.leading_dense_layers
                or set(self.leading_kinds) - {FULL, WINDOW, LINEAR}):
            raise ValueError(f"leading_kinds names each leading dense layer {FULL!r}, "
                             f"{WINDOW!r} or {LINEAR!r}")
        pattern = self.layer_pattern
        if pattern:
            if set(pattern) - {FULL, LINEAR, MAMBA, WINDOW} or self.n_scan_layers % len(pattern):
                raise ValueError(
                    f"layer_pattern {pattern}: kinds are {FULL!r}, {LINEAR!r}, {MAMBA!r} and "
                    f"{WINDOW!r} ({MAMBA1!r}, {GMU!r} and {CROSS!r} are a layer_plan's), and the "
                    f"{self.n_scan_layers} layers behind the leading dense ones are a whole "
                    "number of periods")
            if (MAMBA in pattern) != bool(self.ssm_heads):
                raise ValueError(f"under a layer_pattern, ssm_heads and {MAMBA!r} layers go "
                                 "together (the mixer beside attention in EVERY layer is "
                                 "ssm_heads without a pattern)")
            if MAMBA in pattern and LINEAR in pattern:
                raise ValueError(f"{MAMBA!r} and {LINEAR!r} layers in one layer_pattern: the "
                                 "recurrent state by slot has one shape")
        if (LINEAR in pattern) != bool(self.gdn_heads):
            raise ValueError(f"gdn_heads and {LINEAR!r} layers in layer_pattern go together")
        if LINEAR in self.leading_kinds and LINEAR not in pattern:
            raise ValueError(f"a leading {LINEAR!r} layer stands in front of a layer_pattern "
                             "with such layers (the state stack is theirs)")
        if self.gdn_gate_rank and not self.gdn_heads:
            raise ValueError("gdn_gate_rank is the linear layers' (gdn_heads)")
        if self.mtp_layers:
            # the module's block is the trunk's routed latent block; what a
            # rejected draft rewinds has to be pages (ROADMAP R9)
            missing = [name for name, absent in (
                ("latent attention (kv_lora_rank)", not self.kv_lora_rank),
                ("fused-GLU experts (n_experts, moe_fused_glu)",
                 not (self.n_experts and self.moe_fused_glu))) if absent]
            refused = [name for name, on in (
                ("more than one module (mtp_layers > 1)", self.mtp_layers > 1),
                ("a layer_pattern or layer_plan (recurrent state, window pages)",
                 bool(self.layer_pattern or self.layer_plan)),
                ("a Mamba-2 mixer (ssm_heads)", bool(self.ssm_heads)),
                ("an indexer (index_topk)", bool(self.index_topk))) if on]
            if missing or refused:
                raise ValueError(
                    "mtp_layers (a next-token-prediction module that drafts) is built for ONE "
                    "module over a trunk of routed latent-attention layers"
                    + (f"; it needs {', '.join(missing)}" if missing else "")
                    + (f"; it does not combine with {', '.join(refused)}" if refused else ""))
        if not self.moe_router_width:
            object.__setattr__(self, "moe_router_width", self.n_experts)
        if self.moe_router_width < self.n_experts:
            raise ValueError(
                f"the {self.n_experts} held experts are not among the router's "
                f"{self.moe_router_width}")
        if (self.moe_shared_dim
                or self.moe_router_width != self.n_experts) and not self.moe_fused_glu:
            raise ValueError("a shared expert and a held range of experts come with the "
                             "fused GLU layout (moe_fused_glu)")

    def n_of(self, kind: str) -> int:
        """Layers of ``kind``: the depth of that kind's stacks."""
        if self.layer_plan:
            return sambay.kinds_of(self.layer_plan).count(kind)
        pattern = self.layer_pattern or (FULL,)
        return self.n_leading_of(kind) + pattern.count(kind) * (
            self.n_scan_layers // len(pattern))

    @property
    def kinds_of_leading(self) -> tuple[str, ...]:
        """The leading dense layers' kinds, in order (``leading_kinds``, or
        every one FULL)."""
        return self.leading_kinds or (FULL,) * self.leading_dense_layers

    def n_leading_of(self, kind: str) -> int:
        """Leading dense layers of ``kind``: their pages come first in that
        kind's pool."""
        return self.kinds_of_leading.count(kind)

    @property
    def n_attn_layers(self) -> int:
        """Layers that own K/V pages: the depth of the page pool (a
        next-token-prediction module's block owns pages of its own, behind the
        trunk's)."""
        return self.n_of(FULL) + self.mtp_layers

    @property
    def cache_readers(self) -> int:
        """Layers that walk ONE layer's pages of the full pool in a step: the
        layer that wrote them and, under a plan, the CROSS layers behind it."""
        return 1 + self.n_of(CROSS) if self.layer_plan else 1

    @property
    def n_window_layers(self) -> int:
        """Layers that own WINDOW pages: the depth of the second pool."""
        return self.n_of(WINDOW)

    @property
    def n_kv_layers(self) -> int:
        """Layers with attention projections of their own (the ``attn_*``
        stacks of a model without a plan): the FULL and the WINDOW ones."""
        return self.n_of(FULL) + self.n_of(WINDOW)

    @property
    def n_scan_layers(self) -> int:
        """Layers in the scanned stacks (``params["layers"]``): all but the
        leading dense ones."""
        return self.n_layers - self.leading_dense_layers

    @property
    def latent_row(self) -> int:
        """Columns of a token's latent row in its page, ``[c_kv | k_rope]``
        padded to whole 128-lane tiles (576 -> 640: a minor dimension of 576
        is padded so in HBM whatever the shape says)."""
        return -(-(self.kv_lora_rank + self.qk_rope_dim) // 128) * 128

    def attn_kind(self, kind: str = FULL) -> AttnKind:
        """Attention's shape in the layers of ``kind``: ``attn_kinds``' entry,
        or the model's own (``n_kv_heads``; ``rope_theta`` where the kind is
        rotated, ``rope_kinds``; no sink)."""
        for name, shape in self.attn_kinds:
            if name == kind:
                return shape
        rotated = self.rope_theta is not None and (not self.rope_kinds or kind in self.rope_kinds)
        return AttnKind(self.n_kv_heads, self.rope_theta if rotated else None)

    @property
    def value_dim(self) -> int:
        """A K/V head's values: ``v_head_dim``, or as wide as its keys."""
        return self.v_head_dim or self.head_dim

    def kv_widths(self, kind: str = FULL) -> tuple[int, int]:
        """Columns of a token's row in each of the two paged arrays of the
        pool that the layers of ``kind`` own — THE place a page's width is
        decided (``PagedKVCache.create`` / ``create_window`` and
        ``page_hbm_bytes`` read it): the kind's K heads side by side in the
        first array and its V heads in the second — as wide as each is, so
        keys of 192 over values of 128 are arrays of two widths —, or for
        latent attention the latent row in the first array and the indexer's
        key row in the second (without an indexer ONE lane tile, never read: a
        minor dimension of 1 is padded to 128 lanes in HBM whatever the shape
        says, and the append's slab copy takes whole tiles only)."""
        if self.kv_lora_rank:
            return self.latent_row, self.index_head_dim or 128
        n = self.attn_kind(kind).n_kv_heads
        return n * self.head_dim, n * self.value_dim

    @property
    def kv_row_widths(self) -> tuple[int, int]:
        """``kv_widths`` of the FULL layers' pool (every model's first)."""
        return self.kv_widths(FULL)

    @property
    def n_state_layers(self) -> int:
        """Layers that carry recurrent state by slot: the depth of
        ``DecodeState.ssm_state`` / ``conv_state``."""
        if self.ssm_heads and not self.layer_pattern:
            return self.n_layers
        return self.n_of(LINEAR) + self.n_of(MAMBA) + self.n_of(MAMBA1)

    @property
    def moe_sparse(self) -> bool:
        """Many small experts: dense dispatch over the held stacks would
        compute more than ``MOE_DENSE_WASTE_MAX`` times what the picks need,
        and a step touches only some of them — ``moe_mlp`` groups the tokens
        of a large call by expert and reads only the touched experts in a
        small one, and ``decode_step`` counts the held experts a step
        touched and read."""
        return self.moe_router_width > MOE_DENSE_WASTE_MAX * self.top_k_experts

    @property
    def has_state(self) -> bool:
        """This model carries recurrent state (a mixer in every layer, or
        LINEAR layers): what no page holds and no row can be rewound over."""
        return self.n_state_layers > 0

    @property
    def state_shape(self) -> tuple[int, ...]:
        """One slot's recurrent state in one layer (float32). A LINEAR layer's
        ``dk x dv`` matrices stand ``gdn_tile_heads`` side by side: tiles
        ``[dk, n dv]`` (models/gdn.py has the views to and from heads)."""
        if self.gdn_heads:
            n = self.gdn_tile_heads
            return (self.gdn_heads // n, self.gdn_key_dim, n * self.gdn_value_dim)
        if self.m1_inner:  # a MAMBA1 layer's [N, E]: the channels along the lanes
            return (1, self.m1_state, self.m1_inner)
        return (self.ssm_heads, self.ssm_head_dim, self.ssm_state)

    @property
    def stored_state_shape(self) -> tuple[int, ...]:
        """``state_shape`` as the device holds it (``create_state``'s leaf).
        A Mamba-2 head narrower than a lane tile is stored in pairs with the
        state axis on sublanes (``ops/ssm_step.py`` ``stored_shape``: the
        one-token kernel's form, by the static shapes; ``state_to_logical`` is
        the view back); every other state lies as ``state_shape`` says."""
        if self.gdn_heads or self.m1_inner:
            return self.state_shape
        return ssm_step.stored_shape(*self.state_shape, self.ssm_groups)

    def state_to_logical(self, stored: Array) -> Array:
        """``[..., *stored_state_shape]`` as ``[..., *state_shape]``."""
        if self.stored_state_shape == self.state_shape:
            return stored
        return ssm_step.to_logical(stored, self.state_shape, self.ssm_groups)

    def state_to_stored(self, state: Array) -> Array:
        """``[..., *state_shape]`` as ``[..., *stored_state_shape]``."""
        if self.stored_state_shape == self.state_shape:
            return state
        return ssm_step.to_stored(state, self.ssm_groups)

    @property
    def gdn_tile_heads(self) -> int:
        """Heads whose matrices share a tile of the state: the fewest that
        fill whole 128-lane tiles (Olmo-Hybrid: 2 x 192 = 3 x 128; a minor
        dimension of 192 alone is padded to 256 in HBM, a third more bytes in
        every pass over the state) and leave two tiles or more (the one-token
        kernel works a row's tiles in two halves); one where none does."""
        H, dv = self.gdn_heads, self.gdn_value_dim
        fits = [n for n in range(1, H // 2 + 1) if H % n == 0 and n * dv % 128 == 0]
        return fits[0] if fits else 1

    @property
    def conv_shape(self) -> tuple[int, ...]:
        """One slot's conv tail in one layer (float32): the last K-1 inputs."""
        if self.gdn_heads:
            return (self.gdn_conv - 1, self.gdn_conv_dim)
        if self.m1_inner:
            return (self.m1_conv - 1, self.m1_inner)
        return (self.ssm_conv - 1, self.ssm_conv_dim)

    @property
    def gdn_conv_dim(self) -> int:
        """Channels through the LINEAR layer's conv: [q | k | v]."""
        return self.gdn_heads * (2 * self.gdn_key_dim + self.gdn_value_dim)

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels through the conv: xs and the groups' B and C."""
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state

    @property
    def ssm_in_dim(self) -> int:
        """The mixer's input projection: [z | xs | B | C | dt]."""
        return self.d_ssm + self.ssm_conv_dim + self.ssm_heads


# Model shapes follow the public architecture cards; "tiny"/"mini" are
# random-weight debug configs.
PRESETS: dict[str, LlamaConfig] = {
    "tiny": LlamaConfig(),
    "mini": LlamaConfig(vocab_size=260, dim=512, n_layers=8, n_heads=8, n_kv_heads=4, hidden_dim=1536, max_seq_len=4096),
    "tinyllama-1.1b": LlamaConfig(
        vocab_size=32_000, dim=2048, n_layers=22, n_heads=32, n_kv_heads=4,
        hidden_dim=5632, rope_theta=10_000.0, max_seq_len=2048,
    ),
    "llama3-8b": LlamaConfig(
        vocab_size=128_256, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        hidden_dim=14_336, rope_theta=500_000.0, max_seq_len=8192,
    ),
    "llama3-70b": LlamaConfig(
        vocab_size=128_256, dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
        hidden_dim=28_672, rope_theta=500_000.0, max_seq_len=8192,
    ),
    # random-weight MoE debug config (Mixtral-shaped routing, tiny dims)
    "moe-tiny": LlamaConfig(
        vocab_size=260, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
        hidden_dim=256, n_experts=4, top_k_experts=2,
    ),
    "mixtral-8x7b": LlamaConfig(
        vocab_size=32_000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        hidden_dim=14_336, rope_theta=1_000_000.0, max_seq_len=8192,
        n_experts=8, top_k_experts=2,
    ),
    # Trinity-Mini (arcee-ai, ``afmoe``): window layers (rotated) three to one
    # full layer (not rotated) as kinds of the pattern, a q/k norm a head, a
    # gated attention output, norms on a sub-block's input and output; two
    # leading dense window layers, then 128 routed experts of 1,024 at 8 a
    # token (sigmoid scores, a selection bias, one group) beside a shared one.
    # The 30 layers behind the leading two start inside a period, so the
    # pattern is their kinds spelled out, one period
    "trinity-mini": LlamaConfig(
        vocab_size=200_192, dim=2048, n_layers=32, n_heads=32, n_kv_heads=4, head_dim=128,
        hidden_dim=1024, rope_theta=10_000.0, rope_kinds=(WINDOW,), max_seq_len=16_384,
        embedding_multiplier=2048 ** 0.5, qk_head_norm=True, attn_gate=True, norm_both=True,
        n_experts=128, top_k_experts=8, moe_shared_dim=1024, moe_fused_glu=True,
        moe_score="sigmoid", moe_select_bias=True, moe_gate_scale=2.826,
        moe_bias_init_std=0.02, leading_dense_layers=2, dense_hidden_dim=6144,
        leading_kinds=(WINDOW, WINDOW),
        layer_pattern=(((WINDOW,) * 3 + (FULL,)) * 8)[2:], window=2048,
    ),
    # the same block at a size a test holds (tests/tiny_models.py builds it from
    # the published keys): ONE leading dense window layer, two periods, a window
    # of 8 tokens, 32 experts at 4 a token
    "trinity-tiny": LlamaConfig(
        vocab_size=211, dim=64, n_layers=9, n_heads=8, n_kv_heads=2, head_dim=16,
        hidden_dim=32, rope_theta=10_000.0, rope_kinds=(WINDOW,), max_seq_len=256,
        embedding_multiplier=8.0, qk_head_norm=True, attn_gate=True, norm_both=True,
        n_experts=32, top_k_experts=4, moe_shared_dim=32, moe_fused_glu=True,
        moe_score="sigmoid", moe_select_bias=True, moe_gate_scale=2.826,
        moe_bias_init_std=0.02, leading_dense_layers=1, dense_hidden_dim=96,
        leading_kinds=(WINDOW,),
        layer_pattern=(WINDOW, WINDOW, WINDOW, FULL), window=8,
    ),
    # MiMo-V2-Flash (XiaomiMiMo, ``mimo_v2_flash``, 309B-A15B): attention's
    # shape is a KIND's — full layers of 4 K/V heads rotated at 5e6, window
    # layers (128 tokens) of 8 at 1e4 with a sink in the softmax —, keys of 192
    # over values of 128 in both, the first 64 dims of a head rotated, values
    # scaled by 0.707; a leading dense full layer, then 256 routed experts of
    # 2,048 at 8 a token (sigmoid scores, a selection bias, no shared expert).
    # The 47 layers behind the leading one (four window, one full, then seven
    # times five window, one full) are their kinds spelled out, one period
    "mimo-v2-flash": LlamaConfig(
        vocab_size=152_576, dim=4096, n_layers=48, n_heads=64, n_kv_heads=4, head_dim=192,
        v_head_dim=128, hidden_dim=2048, rope_theta=5_000_000.0, rope_dim=64, value_scale=0.707,
        max_seq_len=32_768, n_experts=256, top_k_experts=8, moe_fused_glu=True,
        moe_score="sigmoid", moe_select_bias=True, moe_bias_init_std=0.02,
        leading_dense_layers=1, dense_hidden_dim=16_384, leading_kinds=(FULL,),
        layer_pattern=(((WINDOW,) * 5 + (FULL,)) * 8)[1:], window=128,
        attn_kinds=((FULL, AttnKind(4, 5_000_000.0)), (WINDOW, AttnKind(8, 10_000.0, sink=True))),
    ),
    # the same block at a size a test holds (tests/tiny_models.py builds it
    # from the published keys): a leading dense full layer, two periods of
    # (three window, one full), a window of 8 tokens, 4 of 16 experts held at
    # 2 a token; the heads at their published widths (the kernels cut a key
    # of 192 as lane tiles)
    "mimo-tiny": LlamaConfig(
        vocab_size=211, dim=64, n_layers=9, n_heads=16, n_kv_heads=2, head_dim=192,
        v_head_dim=128, hidden_dim=32, rope_theta=5_000_000.0, rope_dim=64, value_scale=0.707,
        max_seq_len=256, n_experts=4, moe_router_width=16, top_k_experts=2, moe_fused_glu=True,
        moe_score="sigmoid", moe_select_bias=True, moe_bias_init_std=0.02,
        leading_dense_layers=1, dense_hidden_dim=96, leading_kinds=(FULL,),
        layer_pattern=(WINDOW, WINDOW, WINDOW, FULL), window=8,
        attn_kinds=((FULL, AttnKind(2, 5_000_000.0)), (WINDOW, AttnKind(4, 10_000.0, sink=True))),
    ),
}


def n_params(config: LlamaConfig) -> int:
    """Analytic parameter count (no materialization); tests hold
    ``init_params`` and the benchmark's adapters to it."""
    c = config
    if c.layer_plan:
        return sambay.n_params(c)
    d, hd = c.dim, c.head_dim

    def attn_of(kind: str) -> int:  # q, the kind's k and v, o, its sinks
        a = c.attn_kind(kind)
        return (d * c.n_heads * hd + d * a.n_kv_heads * (hd + c.value_dim)
                + c.n_heads * c.value_dim * d + a.sink * c.n_heads)

    attn = mla.n_attention_params(c) if c.kv_lora_rank else 0  # (else: by kind, below)
    mlp = 3 * d * c.hidden_dim
    if c.n_experts:
        # the held experts, the router at its whole width (and its selection
        # bias), the shared expert
        mlp = (mlp * c.n_experts + (d + c.moe_select_bias) * c.moe_router_width
               + 3 * d * c.moe_shared_dim)
    if c.qk_norm:
        attn += (c.n_heads + c.n_kv_heads) * hd
    if c.qk_head_norm:
        attn += 2 * hd
    if c.attn_gate:
        attn += d * c.n_heads * hd
    attention = c.n_kv_layers * attn
    if not c.kv_lora_rank:
        attention += sum(c.n_of(kind) * attn_of(kind) for kind in (FULL, WINDOW))
    norms = 4 * d if c.norm_both else 2 * d
    per_layer = mlp + norms
    # in/out projections, conv weight and bias, A_log, dt_bias, D, the gated
    # norm's weight: in every layer, or in the MAMBA layers of a pattern
    ssm = (d * c.ssm_in_dim + c.d_ssm * d
           + (c.ssm_conv + 1) * c.ssm_conv_dim + 3 * c.ssm_heads + c.d_ssm)
    if c.ssm_heads and not c.layer_pattern:
        per_layer += ssm
    # [q | k | v | gate] and [b | a] in, out, the conv, A_log, dt_bias, the norm
    d_v = c.gdn_heads * c.gdn_value_dim
    linear = (d * (c.gdn_conv_dim + d_v + 2 * c.gdn_heads) + d_v * d
              + c.gdn_conv * c.gdn_conv_dim + 2 * c.gdn_heads + c.gdn_value_dim)
    if c.gdn_gate_rank:
        # [q | k | v] in, [W_f1 | W_g1 | w_b], W_f2, W_g2 and its bias, out, the
        # conv, A_log a head, dt_bias a key channel, the norm
        r, d_k = c.gdn_gate_rank, c.gdn_heads * c.gdn_key_dim
        linear = (d * (c.gdn_conv_dim + 2 * r + c.gdn_heads) + r * (d_k + d_v) + d_v + d_v * d
                  + c.gdn_conv * c.gdn_conv_dim + c.gdn_heads + d_k + c.gdn_value_dim)
    total = (c.vocab_size * d + c.n_scan_layers * per_layer + attention
             + c.leading_dense_layers * (3 * d * c.dense_hidden_dim + norms)
             + c.n_of(LINEAR) * linear + c.n_of(MAMBA) * ssm + d)
    if not c.tie_embeddings:
        total += d * c.vocab_size
    # a next-token-prediction module: one routed block of the trunk's kind, the
    # projection of [embedding | hidden], its three norms (embedding and head shared)
    total += c.mtp_layers * (per_layer + attn + 2 * d * d + 3 * d)
    return total


# Leaves with more elements than this random-init directly in the model
# dtype instead of fp32-then-cast: the fp32 intermediate for a stacked 8B
# leaf (mlp_down [32,14336,4096] = 7.5 GB) plus the already-materialized
# quantized leaves would overflow one v5e chip's 16 GB HBM during
# init_quantized init. Small (test-preset) leaves keep the fp32->cast
# path so pinned golden decode sequences are unchanged. Module-level so
# tests can patch it to exercise the large-leaf branch at small shapes.
FP32_INIT_MAX_ELEMS = 1 << 28
# Leaves with more elements than this are drawn one index of their leading
# axis at a time into a buffer that is updated in place: a stack of held
# experts [10, 36, 4096, 1536] is 4.5 GB in bf16, and its random bits and the
# normal transform's temporaries beside it and the tree drawn so far do not
# fit a 16 GB chip. Above every leaf an older configuration draws (their
# values are as they were). Module-level, as the one above, for tests
SLICED_INIT_MIN_ELEMS = 1 << 31


@partial(jax.jit, donate_argnums=(0,))
def _set_leading(leaf: Array, part: Array, i: Array) -> Array:
    return lax.dynamic_update_index_in_dim(leaf, part, i, 0)


def init_params(
    config: LlamaConfig, key: Array, leaf_transform: Any = None
) -> dict[str, Any]:
    """Random-init params as a pytree with stacked layers.

    Layout (L = n_layers, leading axis of every ``layers`` leaf):
      embed[vocab, dim]
      layers/attn_{q,k,v,o}[L, ...], layers/mlp_{gate,up,down}[L, ...],
      layers/ln_attn[L, dim], layers/ln_mlp[L, dim]
      layers/ssm_{in,out,conv_w,conv_b,A_log,dt_bias,D,norm}[L, ...] (with
      ``ssm_heads``; the recurrence's own A_log, dt_bias, D stay float32)
      norm[dim], lm_head[dim, vocab] (absent when tie_embeddings)
    With a ``layer_pattern`` the stacks are by KIND (``_stack_kinds``): the
    ``attn_*`` leaves (``attn_{q,k}_norm`` with ``qk_norm`` or
    ``qk_head_norm``, ``attn_gate`` with ``attn_gate``) have the FULL and
    WINDOW layers' depth (with ``attn_kinds`` ``attn_k`` / ``attn_v`` the FULL
    layers' alone, ``swa_k`` / ``swa_v`` / ``swa_sink`` the WINDOW layers'), the ``gdn_*`` leaves the LINEAR layers', the MLP and
    the norms (``ln_{attn,mlp}_out`` too with ``norm_both``) every layer's;
    without one this is the tree it always was.

    ``leaf_transform(name, array)`` is applied to each MATMUL weight at
    creation, before the next leaf materializes — so e.g. int8 quantization
    (models/quant.py init_quantized_llama_params) never holds the full
    bf16 tree, which for llama3-8b alone exceeds one v5e chip's 16 GB HBM.
    """
    c = config
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    tf = leaf_transform or (lambda name, x: x)

    def rand_init(name: str, k: Array, shape: tuple[int, ...], fan_in: int) -> Array:
        import math

        # see FP32_INIT_MAX_ELEMS: large leaves skip the fp32 intermediate
        gen_dtype = c.dtype if math.prod(shape) > FP32_INIT_MAX_ELEMS else jnp.float32
        if math.prod(shape) > SLICED_INIT_MIN_ELEMS:  # see there: layer by layer, in place
            leaf = jnp.zeros(shape, c.dtype)
            for i in range(shape[0]):
                part = jax.random.normal(jax.random.fold_in(k, i), shape[1:], gen_dtype)
                leaf = _set_leading(leaf, (part * fan_in ** -0.5).astype(c.dtype), jnp.int32(i))
            return tf(name, leaf)
        return tf(name, (jax.random.normal(k, shape, gen_dtype) * fan_in ** -0.5).astype(c.dtype))

    if c.layer_plan:
        params = {"embed": rand_init("embed", k_embed, (c.vocab_size, c.dim), c.dim),
                  "layers": sambay.init_layers(c, k_layers, rand_init),
                  "norm": jnp.ones((c.dim,), c.dtype),
                  "norm_b": jnp.zeros((c.dim,), c.dtype)}
        if not c.tie_embeddings:
            params["lm_head"] = rand_init("lm_head", k_head, (c.dim, c.vocab_size), c.dim)
        return params
    keys = jax.random.split(k_layers, 8)
    L, D, H, Hkv, hd, F = c.n_scan_layers, c.dim, c.n_heads, c.n_kv_heads, c.head_dim, c.hidden_dim
    # the leading dense layers' stacks are by kind too: the attention leaves as
    # deep as the FULL and WINDOW ones among them, the ``gdn_*`` as the LINEAR
    Ld_linear = c.n_leading_of(LINEAR)
    Ld_attn = c.leading_dense_layers - Ld_linear
    La = c.n_kv_layers - Ld_attn

    def attention_leaves(depth: int, ks: Array, leading: bool = False) -> dict[str, Array]:
        if c.kv_lora_rank:
            return mla.init_attention(c, ks[0], depth, rand_init)
        gate = {"attn_gate": rand_init("attn_gate", jax.random.fold_in(ks[3], 1),
                                       (depth, D, H * hd), D)} if c.attn_gate else {}
        # k and v by KIND where ``attn_kinds`` says so (a kind's heads; the FULL
        # layers' under the names they always had, the WINDOW layers' ``swa_*``
        # with keys of their own, and their sinks): else ONE stack for both
        stacks = [("attn", c.attn_kind(FULL), depth, lambda k: k)]
        if c.attn_kinds:
            stacks = [(own, c.attn_kind(kind),
                       c.n_leading_of(kind) if leading else c.n_of(kind) - c.n_leading_of(kind),
                       salted)
                      for kind, own, salted in (
                          (FULL, "attn", lambda k: k),
                          (WINDOW, "swa", lambda k: jax.random.fold_in(k, 5)))]
        kv = {}
        for own, a, n, salted in stacks:
            if not n:
                continue
            kv[f"{own}_k"] = rand_init(f"{own}_k", salted(ks[1]), (n, D, a.n_kv_heads * hd), D)
            kv[f"{own}_v"] = rand_init(f"{own}_v", salted(ks[2]),
                                       (n, D, a.n_kv_heads * c.value_dim), D)
            if a.sink:
                kv[f"{own}_sink"] = c.sink_init_std * jax.random.normal(
                    salted(ks[0]), (n, H), jnp.float32)
        return {
            "attn_q": rand_init("attn_q", ks[0], (depth, D, H * hd), D),
            **kv,
            "attn_o": rand_init("attn_o", ks[3], (depth, H * c.value_dim, D), H * c.value_dim),
            **gate,
        }

    def norm_leaves(depth: int) -> dict[str, Array]:
        names = ("ln_attn", "ln_mlp") + (("ln_attn_out", "ln_mlp_out") if c.norm_both else ())
        return {name: jnp.ones((depth, D), c.dtype) for name in names}

    params: dict[str, Any] = {
        "embed": rand_init("embed", k_embed, (c.vocab_size, D), D),
        "layers": {**attention_leaves(La, keys), **norm_leaves(L)},
        "norm": jnp.ones((D,), c.dtype),
    }
    if c.leading_dense_layers:
        # the dense layers in front: stacks of their own, keys of their own
        Ld, Fd = c.leading_dense_layers, c.dense_hidden_dim
        kd = jax.random.split(jax.random.fold_in(k_layers, 3), 7)
        params["dense_layers"] = {
            **(attention_leaves(Ld_attn, kd, leading=True) if Ld_attn else {}),
            **(gdn.init_params(c, jax.random.fold_in(kd[0], 2), Ld_linear, rand_init)
               if Ld_linear else {}),
            **norm_leaves(Ld),
            "mlp_gate": rand_init("mlp_gate", kd[4], (Ld, D, Fd), D),
            "mlp_up": rand_init("mlp_up", kd[5], (Ld, D, Fd), D),
            "mlp_down": rand_init("mlp_down", kd[6], (Ld, Fd, D), Fd),
        }
    if c.n_experts and c.moe_fused_glu:
        E, Fs = c.n_experts, c.moe_shared_dim
        params["layers"].update(
            {
                "router": jax.random.normal(
                    keys[7], (L, D, c.moe_router_width), jnp.float32) * D ** -0.5,
                "moe_in": rand_init("moe_in", keys[4], (L, E, D, 2 * F), D),
                "moe_out": rand_init("moe_out", keys[6], (L, E, F, D), F),
            }
        )
        if c.moe_select_bias:
            # trained from zero; drawn so that it moves picks
            params["layers"]["router_bias"] = c.moe_bias_init_std * jax.random.normal(
                jax.random.fold_in(keys[7], 1), (L, c.moe_router_width), jnp.float32)
        if Fs:
            ks = jax.random.split(keys[5])
            params["layers"].update(
                {
                    "shared_in": rand_init("shared_in", ks[0], (L, D, 2 * Fs), D),
                    "shared_out": rand_init("shared_out", ks[1], (L, Fs, D), Fs),
                }
            )
    elif c.n_experts:
        E = c.n_experts
        params["layers"].update(
            {
                # router stays fp32: routing is precision-sensitive, tiny
                "router": jax.random.normal(keys[7], (L, D, E), jnp.float32) * D ** -0.5,
                "moe_gate": rand_init("moe_gate", keys[4], (L, E, D, F), D),
                "moe_up": rand_init("moe_up", keys[5], (L, E, D, F), D),
                "moe_down": rand_init("moe_down", keys[6], (L, E, F, D), F),
            }
        )
    else:
        params["layers"].update(
            {
                "mlp_gate": rand_init("mlp_gate", keys[4], (L, D, F), D),
                "mlp_up": rand_init("mlp_up", keys[5], (L, D, F), D),
                "mlp_down": rand_init("mlp_down", keys[6], (L, F, D), F),
            }
        )
    if c.ssm_heads:
        # keys of their own, so that the other leaves are the ones a config
        # without the mixer draws. The recurrence's parameters take Mamba-2's
        # published initialisation: with plain normal draws the state would
        # decay in one token or never, and nothing downstream would see it
        ks = jax.random.split(jax.random.fold_in(k_layers, 1), 6)
        Hs, Cc, K = c.ssm_heads, c.ssm_conv_dim, c.ssm_conv
        Lm = c.n_state_layers  # every layer, or the MAMBA layers of a pattern
        dt = jnp.exp(jax.random.uniform(
            ks[4], (Lm, Hs), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        params["layers"].update(
            {
                "ssm_in": rand_init("ssm_in", ks[0], (Lm, D, c.ssm_in_dim), D),
                "ssm_out": rand_init("ssm_out", ks[1], (Lm, c.d_ssm, D), c.d_ssm),
                "ssm_conv_w": jax.random.uniform(
                    ks[2], (Lm, K, Cc), jnp.float32, -1.0, 1.0).astype(c.dtype) * K ** -0.5,
                "ssm_conv_b": jax.random.uniform(
                    ks[3], (Lm, Cc), jnp.float32, -1.0, 1.0).astype(c.dtype) * K ** -0.5,
                "ssm_A_log": jnp.log(jax.random.uniform(ks[5], (Lm, Hs), jnp.float32, 1.0, 16.0)),
                "ssm_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                "ssm_D": jnp.ones((Lm, Hs), jnp.float32),
                "ssm_norm": jnp.ones((Lm, c.d_ssm), c.dtype),
            }
        )
    if c.qk_norm or c.qk_head_norm:
        q_width, k_width = (hd, hd) if c.qk_head_norm else (H * hd, Hkv * hd)
        for stack, depth in (("layers", La), ("dense_layers", Ld_attn)):
            if depth:
                params[stack].update({"attn_q_norm": jnp.ones((depth, q_width), c.dtype),
                                      "attn_k_norm": jnp.ones((depth, k_width), c.dtype)})
    if c.gdn_heads:
        params["layers"].update(gdn.init_params(
            c, jax.random.fold_in(k_layers, 2), c.n_of(LINEAR) - Ld_linear, rand_init))
    if not c.tie_embeddings:
        params["lm_head"] = rand_init("lm_head", k_head, (D, c.vocab_size), D)
    if c.mtp_layers:
        params["mtp"] = _init_mtp(c, jax.random.fold_in(k_layers, 4), rand_init)
    return params


def _init_mtp(c: LlamaConfig, key: Array, rand_init: Callable) -> dict[str, Any]:
    """The next-token-prediction module's leaves (keys of their own, so the
    trunk's are those a config without the module draws): ``enorm``, ``hnorm``,
    ``eh_proj`` [2 D, D] (rows: the embedding half first), ``layer`` — ONE
    routed latent-attention block's leaves at depth 1, named as the trunk's —
    and ``norm`` before the trunk's head."""
    D, E, F, Fs = c.dim, c.n_experts, c.hidden_dim, c.moe_shared_dim
    ks = jax.random.split(key, 8)
    layer = {
        **mla.init_attention(c, ks[0], 1, rand_init),
        "ln_attn": jnp.ones((1, D), c.dtype), "ln_mlp": jnp.ones((1, D), c.dtype),
        "router": jax.random.normal(ks[1], (1, D, c.moe_router_width), jnp.float32) * D ** -0.5,
        "moe_in": rand_init("moe_in", ks[2], (1, E, D, 2 * F), D),
        "moe_out": rand_init("moe_out", ks[3], (1, E, F, D), F),
    }
    if c.moe_select_bias:
        layer["router_bias"] = c.moe_bias_init_std * jax.random.normal(
            jax.random.fold_in(ks[1], 1), (1, c.moe_router_width), jnp.float32)
    if Fs:
        layer["shared_in"] = rand_init("shared_in", ks[4], (1, D, 2 * Fs), D)
        layer["shared_out"] = rand_init("shared_out", ks[5], (1, Fs, D), Fs)
    return {"enorm": jnp.ones((D,), c.dtype), "hnorm": jnp.ones((D,), c.dtype),
            "eh_proj": rand_init("eh_proj", ks[6], (2 * D, D), 2 * D),
            "layer": layer, "norm": jnp.ones((D,), c.dtype)}


def _stack_kinds(name: str, by_kind: bool = False) -> tuple[str, ...] | None:
    """Whose depth the stacked leaf ``name`` has under a ``layer_pattern``:
    the layers of these kinds, or (None) every layer. ``by_kind``
    (``LlamaConfig.attn_kinds``): k and v are a kind's — ``attn_k`` / ``attn_v``
    the FULL layers', ``swa_*`` the WINDOW layers'."""
    if name.startswith("gdn_"):
        return (LINEAR,)
    if name.startswith("ssm_"):
        return (MAMBA,)
    if name.startswith("swa_"):
        return (WINDOW,)
    if by_kind and name in ("attn_k", "attn_v"):
        return (FULL,)
    return (FULL, WINDOW) if name.startswith("attn_") else None


@jax.named_scope("norm")
def rms_norm(x: Array, weight: Array, eps: float) -> Array:
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(x.dtype) * weight


def rope(x: Array, positions: Array, theta: float, rotated: int = 0) -> Array:
    """Rotary position embedding, fp32 math. x: [B,S,H,D], positions: [B,S].
    ``rotated`` > 0: the head's first ``rotated`` dims alone (dim i paired with
    i + rotated / 2); the others pass as they are."""
    if rotated and rotated < x.shape[-1]:
        return jnp.concatenate(
            [rope(x[..., :rotated], positions, theta), x[..., rotated:]], axis=-1)
    B, S, H, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)  # [half]
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]  # [B,S,1,half]
    sin = jnp.sin(angles)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


class StackedLeaf(NamedTuple):
    """A layer's leaf left where it lies in its stack ``[L, ...]``, with the
    layer's index. A grouped matmul is a custom call, whose operand has to be
    a buffer of its own: handed a layer's slice of the held experts it would
    be handed a COPY (0.45 GB a layer at Granite's size, all ten alive at once
    where the compiler unrolls a scan of one period: 4.5 GB). Handed the whole
    stack with the other layers' groups empty it copies nothing."""
    stack: Array
    index: Array

    def take(self) -> Array:
        return lax.dynamic_index_in_dim(self.stack, self.index, 0, keepdims=False)


def moe_mlp(h: Array, layer_params: dict[str, Array], config: LlamaConfig,
            qm_backend: str | None = None, live: Array | None = None,
            backend: str = "ref") -> Array | tuple[Array, Array]:
    """Top-k routed SwiGLU experts: the router scores ``moe_router_width``
    experts in float32, the ``top_k_experts`` largest are a token's picks, and
    their gates are the softmax over the picked logits alone
    (``moe_score`` "softmax"); or (``"sigmoid"``, ``_sigmoid_picks``) the
    scores are sigmoids, a selection bias and a limit to the best groups
    choose the picks, and the gates are the picked scores, normalised and
    scaled — the router's rule is data of ``LlamaConfig``.

    This process holds the first ``n_experts`` of them (Mixtral: all of
    them). A pick on an absent expert adds nothing here — the
    chip that holds it adds it — and the gates stay normalised over ALL picks,
    so the shares of the chips that split a layer sum to the whole layer; a
    shared expert (``moe_shared_dim``) is added to every token, unweighted.

    Which of THREE forms a call takes is ONE rule (``_moe_form``) on static
    shapes and leaf types (``LlamaConfig.moe_sparse``, ``MOE_DENSE_WASTE_MAX``,
    ``MOE_DENSE_TOKENS_MAX``), never an option or a model's name:

    - dense dispatch — every held expert computes over all tokens with its
      gate zeroed where not routed; expert weights carry a leading E axis
      that shards over the mesh's ``expert`` axis (parallel/sharding.py) and
      XLA turns the expert-sum into a psum over the EP shards. Static shapes,
      no token dropping; FLOPs scale with E rather than with the picks, and
      every held expert's weights are read. Taken where that waste is at most
      ``MOE_DENSE_WASTE_MAX`` x (Mixtral's 8 / 2: its steps touch every
      expert), and by a model that routes sparsely for a call of at most
      ``MOE_DENSE_TOKENS_MAX`` tokens where the touched pass does not apply
      (the ``ref`` backend: this form is that kernel's reference; quantized
      stacks; separate gate / up / down leaves, the only ones an ``expert``
      mesh axis shards);
    - touched — the same sum with the all-zero terms not computed: ONE Pallas
      pass over the held stacks that brings in only the experts that the
      call's (live) tokens picked (``ops/moe_step.py``; bound by the weights'
      bytes, of which it reads the touched experts' alone). A model that
      routes sparsely, a call of at most ``MOE_DENSE_TOKENS_MAX`` tokens on a
      kernel ``backend`` (``pallas`` / ``pallas-interpret``: the engine's,
      resolved once, as ``SsmRows.backend`` is), unquantized fused stacks;
    - grouped — the (token, pick) pairs sorted by expert, pairs on absent
      experts behind the last group and not computed, one grouped (ragged)
      matmul over the held stacks each way (``lax.ragged_dot``, static
      capacity tokens x top_k), scattered back with the gates: a model that
      routes sparsely, for more tokens than that.

    ``live`` [B, S] bool (a model that routes sparsely, the decode step): also
    returns int32 ``[2]``: the number of distinct held experts that live
    tokens picked — the expert weights this layer's step had to read — and
    the number whose weights the call's form DID read (the touched ones in
    the touched pass, else every held one: the other forms' operands are the
    whole stacks). Without ``live`` every token counts as live."""
    c = config
    E, k = c.n_experts, c.top_k_experts
    form = _moe_form(c, h.shape[0] * h.shape[1], layer_params, backend)
    with jax.named_scope("moe_router"):
        # router in fp32 (routing decisions are precision-sensitive; the router
        # leaf itself is kept fp32 by init_params / the checkpoint loader)
        r = jnp.einsum("bsd,de->bse", h, layer_params["router"],
                       preferred_element_type=jnp.float32)  # [B,S,R]
        # exactly-k selection from top_k INDICES (threshold comparison would
        # over-select on tied logits); softmax over the selected logits only
        # (Mixtral renormalization), scattered back to expert positions
        if c.moe_score == "sigmoid":
            top_idx, w = _sigmoid_picks(r, layer_params.get("router_bias"), c)
        else:
            top_vals, top_idx = jax.lax.top_k(r, k)  # [B,S,k]
            w = jax.nn.softmax(top_vals, axis=-1)  # [B,S,k]
        if form != "grouped" or live is not None:
            onehot = jax.nn.one_hot(top_idx, E, dtype=w.dtype)  # [B,S,k,E]; absent (>= E): zeros
        if form != "grouped":
            gates = jnp.einsum("bske,bsk->bse", onehot, w).astype(h.dtype)  # [B,S,E]
        if live is not None or form == "touched":
            on = onehot > 0 if live is None else (onehot > 0) & live[:, :, None, None]
            picked = jnp.any(on, axis=(0, 1, 2))
        if live is not None:
            touched = jnp.sum(picked.astype(jnp.int32))
            read = touched if form == "touched" else jnp.int32(E)
        if form == "touched":
            ids, n, gate_cols = moe_step.plan(picked, gates.reshape(-1, E))

    def expert_mm(spec: str, x: Array, w: Array | QTensor | Q4Tensor) -> Array:
        # int8/int4 serving: the stacked-expert einsums keep INLINE dequant
        # (no fused kernel tiles the leading E axis — ops/dispatch
        # quant_matmul counts the would-be route as a fallback); XLA fuses
        # the upcast+scale into the dot's operand read where it can
        if isinstance(w, (QTensor, Q4Tensor)):
            if qm_backend not in (None, "ref"):
                from finchat_tpu.utils.metrics import METRICS

                METRICS.inc("finchat_quantmatmul_fallbacks_total")
            w = dequantize(w, x.dtype)
        return jnp.einsum(spec, x, w)

    def glu(u: Array) -> Array:  # [gate | up] -> SiLU(gate) * up
        gate, up = jnp.split(u, 2, axis=-1)
        return jax.nn.silu(gate.astype(jnp.float32)).astype(up.dtype) * up

    if form == "grouped":
        out = _moe_grouped(h, top_idx, w, layer_params, c, glu)
    elif form == "touched":
        with jax.named_scope("moe_experts"):
            # the stacks as they lie, with the layer's index: no slice is cut
            w_in, w_out = (leaf if isinstance(leaf, StackedLeaf) else StackedLeaf(leaf[None], 0)
                           for leaf in (layer_params["moe_in"], layer_params["moe_out"]))
            out = moe_step.moe_experts_step(
                h.reshape(-1, h.shape[-1]), gate_cols, ids, n, w_in.stack, w_out.stack,
                w_in.index, interpret=backend == "pallas-interpret").reshape(h.shape)
    else:
        with jax.named_scope("moe_experts"):
            def held(name: str) -> Array:  # the layer's own slice fuses into the dot
                leaf = layer_params[name]
                return leaf.take() if isinstance(leaf, StackedLeaf) else leaf

            if c.moe_fused_glu:
                act = glu(expert_mm("bsd,edf->bsef", h, held("moe_in")))
            else:
                gate = expert_mm("bsd,edf->bsef", h, layer_params["moe_gate"])
                up = expert_mm("bsd,edf->bsef", h, layer_params["moe_up"])
                act = jax.nn.silu(gate.astype(jnp.float32)).astype(up.dtype) * up
            act = act * gates[..., None]  # zero non-routed experts pre-projection
            out = expert_mm("bsef,efd->bsd", act,
                            held("moe_out") if c.moe_fused_glu else layer_params["moe_down"])
    if c.moe_shared_dim:
        with jax.named_scope("moe_shared"):
            out = out + dense(glu(dense(h, layer_params["shared_in"], qm_backend=qm_backend)),
                              layer_params["shared_out"], qm_backend=qm_backend)
    return out if live is None else (out, jnp.stack([touched, read]))


def _sigmoid_picks(r: Array, bias: Array | None, config: LlamaConfig) -> tuple[Array, Array]:
    """The "sigmoid" router's picks and gates from the logits ``r`` [B,S,R]
    (float32): scores ``sigmoid(r)``; the picks are the ``top_k_experts``
    largest of score + ``bias`` (the selection bias chooses and does not
    weigh) inside the ``moe_topk_groups`` groups whose two largest sum
    highest; the gates are the picked SCORES, over their sum
    (``moe_norm_picks``), times ``moe_gate_scale``."""
    c = config
    score = jax.nn.sigmoid(r)
    choice = score if bias is None else score + bias
    if c.moe_groups:
        G = c.moe_groups
        grouped = choice.reshape(*choice.shape[:-1], G, -1)  # [B,S,G,R/G]
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # [B,S,G]
        kept = jax.lax.top_k(group_score, c.moe_topk_groups)[1]  # [B,S,g]
        keep = jnp.any(jax.nn.one_hot(kept, G, dtype=jnp.bool_), axis=-2)  # [B,S,G]
        choice = jnp.where(keep[..., None], grouped, -jnp.inf).reshape(choice.shape)
    top_idx = jax.lax.top_k(choice, c.top_k_experts)[1]  # [B,S,k]
    w = jnp.take_along_axis(score, top_idx, axis=-1)
    if c.moe_norm_picks:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return top_idx, w * c.moe_gate_scale


def _moe_form(config: LlamaConfig, tokens: int, layer_params: dict[str, Any],
              backend: str) -> str:
    """``moe_mlp``'s one rule: ``dense``, ``touched`` or ``grouped`` for a
    call of ``tokens`` tokens, from static shapes, the expert leaves' types
    and the kernel backend the caller resolved."""
    if not config.moe_sparse:
        return "dense"
    if tokens > MOE_DENSE_TOKENS_MAX:
        return "grouped"
    if backend == "ref" or not config.moe_fused_glu:
        return "dense"
    stacks = [leaf.stack if isinstance(leaf, StackedLeaf) else leaf
              for leaf in (layer_params["moe_in"], layer_params["moe_out"])]
    quantized = any(isinstance(stack, (QTensor, Q4Tensor)) for stack in stacks)
    return "dense" if quantized else "touched"


def _moe_grouped(h: Array, top_idx: Array, w: Array, layer_params: dict[str, Array],
                 config: LlamaConfig, glu: Callable[[Array], Array]) -> Array:
    """``moe_mlp``'s grouped form: ``top_idx`` [B,S,k] are the picks as
    indices into the HELD stacks (outside [0, E): an absent expert), ``w``
    their gates."""
    E, k = config.n_experts, top_idx.shape[-1]
    B, S, D = h.shape
    with jax.named_scope("moe_group"):
        expert = top_idx.reshape(-1)  # [N*k], pair p is token p // k
        held = (expert >= 0) & (expert < E)
        # a stable sort by expert, the pairs on absent experts last: behind
        # the last group, where the grouped matmul computes nothing
        order = jnp.argsort(jnp.where(held, expert, E), stable=True)
        sizes = jnp.sum(jax.nn.one_hot(expert, E, dtype=jnp.int32), axis=0)  # absent: no group
        x = h.reshape(B * S, D)[order // k]  # [N*k, D], grouped by expert

        def groups(leaf: Array | StackedLeaf) -> tuple[Array, Array]:
            if not isinstance(leaf, StackedLeaf):
                return leaf, sizes
            # the whole stack as [L * E, ...] (no copy): this layer's groups
            # at their place, every other layer's empty
            every = jnp.zeros((leaf.stack.shape[0] * E,), jnp.int32)
            return (leaf.stack.reshape(-1, *leaf.stack.shape[2:]),
                    lax.dynamic_update_slice(every, sizes, (leaf.index * E,)))
    with jax.named_scope("moe_experts"):
        act = glu(lax.ragged_dot(x, *groups(layer_params["moe_in"])))
        y = lax.ragged_dot(act, *groups(layer_params["moe_out"]))  # [N*k, D]
    with jax.named_scope("moe_group"):
        # back to the pairs' own order, a token's picks side by side; rows
        # behind the last group are not the matmul's to define: gate 0, and out
        back = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0], dtype=order.dtype))
        gate = jnp.where(held, w.reshape(-1), 0.0).reshape(B * S, k)
        y = jnp.where(gate[..., None] != 0, y[back].reshape(B * S, k, D), 0)
        out = jnp.einsum("nkd,nk->nd", y, gate.astype(y.dtype),
                         preferred_element_type=jnp.float32)
        return out.astype(h.dtype).reshape(B, S, D)


def _layer(
    x: Array,
    layer_params: dict[str, Array],
    layer_cache: Any,
    layer_idx: Array,
    *,
    positions: Array,
    config: LlamaConfig,
    attention: AttentionFn,
    tp_axis: str | None = None,
    tp_size: int = 1,
    tp_overlap: bool = False,
    tp_chunks: int = 4,
    qm_backend: str | None = None,
    ssm_cache: Any = None,
    ssm_rows: Any = None,
    kind: str = FULL,
    moe_live: Array | None = None,
    moe_backend: str = "ref",
    dense_mlp: bool = False,
) -> tuple[Array, ...]:
    """One decoder layer. Under GSPMD (the usual path) ``tp_axis`` is
    None — the compiler partitions from the param shardings. Under an
    ALL-MANUAL ``shard_map`` (the stage pipeline, parallel/pipeline.py)
    pass the TP mesh axis + size: weights arrive as Megatron shards
    (column-parallel q/k/v/gate/up, row-parallel o/down), head counts are
    local, and the two row-parallel outputs all-reduce over ``tp_axis`` —
    serially, or with the chunked collective–compute overlap schedule
    (``tp_overlap``, ops/tp_overlap.py — byte-identical per element).
    ``qm_backend`` routes quantized matmul leaves (ops/dispatch).

    With ``config.ssm_heads`` the Mamba-2 mixer (models/ssm.py) reads the
    same normed input as attention, its output joins attention's in the
    residual, and the layer returns ``(x, cache, ssm_cache)``: the recurrent
    state rides beside the KV cache (``ssm_rows`` says whose it is).

    ``kind`` is the layer's place in ``config.layer_pattern``: a LINEAR layer
    runs the gated delta rule (models/gdn.py) in attention's place, over the
    recurrent state alone; ``layer_idx`` is the layer's index among its OWN
    kind, which is how the caches are stacked; a MAMBA layer runs the Mamba-2
    mixer alone there. With ``config.norm_after`` the two norms stand on the
    sub-blocks' outputs instead of their inputs. With ``moe_live`` (see
    ``moe_mlp``) the counts of held experts touched and read are the last
    element; ``moe_backend`` is ``moe_mlp``'s ``backend``.

    With ``config.kv_lora_rank`` attention is latent (models/mla.py) and
    ``attention`` a ``LatentAttentionFn``; the context tokens the live queries
    attended to are then a third count beside the experts'. ``dense_mlp``: a
    leading dense layer of a model that routes (an MLP of
    ``dense_hidden_dim`` in the experts' place; it counts no experts).

    A WINDOW layer is a FULL layer whose callback masks the window and keeps
    its pages in the second pool: a model with such layers hands ``attention``
    the kind as a sixth argument. The attention sub-block's further pieces are
    data of the config, each absent unless set: a q/k norm a head
    (``qk_head_norm``), the rotation of some kinds only (``rope_kinds``), a
    sigmoid gate on the output (``attn_gate``), a norm on a sub-block's input
    AND output (``norm_both``)."""
    c = config
    B, S, D = x.shape
    hq = c.n_heads // tp_size
    shape = c.attn_kind(kind)  # (read by the FULL and WINDOW layers of the llama block alone)
    hkv = shape.n_kv_heads // tp_size

    def norm_in(x: Array, name: str) -> Array:
        return x if c.norm_after else rms_norm(x, layer_params[name], c.norm_eps)

    def norm_out(y: Array, name: str) -> Array:
        if c.norm_both:
            return rms_norm(y, layer_params[name + "_out"], c.norm_eps)
        return rms_norm(y, layer_params[name], c.norm_eps) if c.norm_after else y

    h = norm_in(x, "ln_attn")
    selected = jnp.int32(0)  # (a latent model's LINEAR layers attend to nothing)
    if kind == LINEAR:
        assert tp_axis is None, "manual-TP stage blocks have no linear-attention layers"
        mixed, ssm_cache = gdn.mixer(h, layer_params, c, ssm_cache, layer_idx, ssm_rows,
                                     qm_backend=qm_backend)
        with jax.named_scope("gdn_out"):
            x = x + norm_out(mixed, "ln_attn")
        new_layer_cache = layer_cache
    elif kind == MAMBA:
        assert tp_axis is None, "manual-TP stage blocks have no mixer"
        mixed, ssm_cache = mixer(h, layer_params, c, ssm_cache, layer_idx, ssm_rows,
                                 qm_backend=qm_backend)
        with jax.named_scope("ssm_out"):
            x = x + scaled(norm_out(mixed, "ln_attn"), c.residual_multiplier)
        new_layer_cache = layer_cache
    elif c.kv_lora_rank:
        assert tp_axis is None, "manual-TP stage blocks have no latent attention"
        with jax.named_scope("mla_project"):
            inputs = mla.project(h, layer_params, c, positions, qm_backend=qm_backend)
        # the callback opens its own scopes (engine/engine.py)
        o_latent, new_layer_cache, selected = attention(inputs, layer_cache, layer_idx)
        with jax.named_scope("mla_project"):
            attn_out = mla.up_values(o_latent, layer_params, c)
        with jax.named_scope("attn_o"):
            x = x + dense(attn_out, layer_params["attn_o"], qm_backend=qm_backend)
    else:
        if c.ssm_heads and not c.layer_pattern:
            assert tp_axis is None, "manual-TP stage blocks have no mixer"
            mixed, ssm_cache = mixer(h, layer_params, c, ssm_cache, layer_idx, ssm_rows,
                                     qm_backend=qm_backend)
        with jax.named_scope("attn_qkv"):
            h = scaled(h, c.attention_in_multiplier)

            def heads(t: Array, n: int, norm: str = "", width: int = c.head_dim) -> Array:
                if norm and c.qk_norm:  # over the whole width, before the split
                    t = rms_norm(t, layer_params[norm], c.norm_eps)
                t = t.reshape(B, S, n, width)
                if norm and c.qk_head_norm:  # over each head, one weight vector for all
                    t = rms_norm(t, layer_params[norm], c.norm_eps)
                return t

            # q and k fenced flat: the heads' layout must not reach the weights
            q = heads(flat_fence(dense(h, layer_params["attn_q"], qm_backend=qm_backend)),
                      hq, "attn_q_norm")
            # (k and v by kind, ``attn_kinds``: a WINDOW layer's are ``swa_*``)
            own = "swa" if c.attn_kinds and kind == WINDOW else "attn"
            k = heads(scaled(flat_fence(dense(h, layer_params[f"{own}_k"], qm_backend=qm_backend)),
                             c.key_multiplier), hkv, "attn_k_norm")
            v = heads(scaled(dense(h, layer_params[f"{own}_v"], qm_backend=qm_backend),
                             c.value_scale), hkv, width=c.value_dim)
            if shape.rope_theta is not None:
                q = rope(q, positions, shape.rope_theta, c.rope_dim)
                k = rope(k, positions, shape.rope_theta, c.rope_dim)

        # the attention callback opens its own scopes (engine/engine.py)
        attn_out, new_layer_cache = attention(
            q, k, v, layer_cache, layer_idx, *((kind,) if c.window else ()),
            **({"sink": layer_params["swa_sink"]} if shape.sink else {}))
        with jax.named_scope("attn_o"):
            if c.attn_gate:
                assert tp_axis is None, "manual-TP stage blocks have no output gate"
                gate = jax.nn.sigmoid(dense(h, layer_params["attn_gate"],
                                            qm_backend=qm_backend).astype(jnp.float32))
                attn_out = (attn_out.reshape(B, S, -1).astype(jnp.float32)
                            * gate).astype(attn_out.dtype)
            if tp_axis is not None:
                from finchat_tpu.ops.tp_overlap import row_parallel_dense

                attn_proj = row_parallel_dense(
                    attn_out.reshape(B, S, -1), layer_params["attn_o"], tp_axis,
                    overlap=tp_overlap, n_chunks=tp_chunks, qm_backend=qm_backend,
                )
            else:
                attn_proj = dense(attn_out.reshape(B, S, -1), layer_params["attn_o"],
                                  qm_backend=qm_backend)
            x = x + scaled(norm_out(scaled(attn_proj, c.attention_out_multiplier), "ln_attn"),
                           c.residual_multiplier)
            if c.ssm_heads and not c.layer_pattern:
                x = x + mixed

    h = norm_in(x, "ln_mlp")
    if c.n_experts and not dense_mlp:
        assert tp_axis is None, "manual-TP stage blocks are dense-only (PPxEP future work)"
        moe_out = moe_mlp(h, layer_params, c, qm_backend=qm_backend, live=moe_live,
                          backend=moe_backend)
        if moe_live is not None:
            moe_out, experts = moe_out
        with jax.named_scope("moe_experts"):
            # the residual add fuses into the down matmul
            x = x + scaled(norm_out(moe_out, "ln_mlp"), c.residual_multiplier)
    else:
        with jax.named_scope("mlp"):
            gate = scaled(dense(h, layer_params["mlp_gate"], qm_backend=qm_backend),
                          c.mlp_multipliers[0])
            up = dense(h, layer_params["mlp_up"], qm_backend=qm_backend)
            act = jax.nn.silu(gate.astype(jnp.float32)).astype(up.dtype) * up
            if tp_axis is not None:
                from finchat_tpu.ops.tp_overlap import row_parallel_dense

                down = row_parallel_dense(
                    act, layer_params["mlp_down"], tp_axis,
                    overlap=tp_overlap, n_chunks=tp_chunks, qm_backend=qm_backend,
                )
            else:
                down = dense(act, layer_params["mlp_down"], qm_backend=qm_backend)
            x = x + scaled(norm_out(scaled(down, c.mlp_multipliers[1]), "ln_mlp"),
                           c.residual_multiplier)
    out = (x, new_layer_cache, ssm_cache) if c.has_state else (x, new_layer_cache)
    if moe_live is None:
        return out
    if dense_mlp or not c.n_experts:
        experts = jnp.zeros((2,), jnp.int32)
    return (*out, jnp.append(experts, selected) if c.kv_lora_rank else experts)


def forward(
    params: dict[str, Any],
    tokens: Array,  # [B, S] int32
    positions: Array,  # [B, S] int32 absolute positions
    *,
    config: LlamaConfig,
    attention: AttentionFn,
    cache: Any = None,  # full-depth cache pytree (carried), or None
    remat: bool = False,  # checkpoint each scanned layer (training)
    return_hidden: bool = False,  # post-norm hidden states, no LM head
    prenorm: bool = False,  # with return_hidden: the last layer's output BEFORE the final norm
    qm_backend: str | None = None,  # quantized-matmul backend (ops/dispatch)
    ssm_cache: Any = None,  # (ssm_state, conv_state) of a model with a mixer
    ssm_rows: Any = None,  # models/ssm.py SsmRows: whose state each row is
    moe_live: Array | None = None,  # [B, S] bool: count the experts these tokens touch
    moe_backend: str = "ref",  # the kernel backend of moe_mlp's touched pass
) -> tuple[Array, Any] | tuple[Array, Any, Array]:
    """Run the decoder; returns (logits[B,S,vocab] fp32, new_cache) — or
    (hidden[B,S,D], new_cache) with ``return_hidden``, for callers that
    project only a subset of positions (the seq-sharded long prefill keeps
    one row; a full [S, vocab] fp32 logits tensor there would cost GBs).

    The cache rides the layer scan as part of the CARRY and the attention
    callback receives the whole cache plus the layer index (kernels index
    the layer via scalar prefetch). The alternative — slicing the cache as
    scan xs and restacking updates as ys — forces XLA to write a fresh
    full-cache buffer every step (~22 ms/step for a 1.5 GB cache in the
    builders' July 2026 measurement, not reproduced since); carrying it
    lets the in-place Pallas writers (ops/kv_append.py) keep the buffer
    aliased end to end.

    A model with a mixer (``config.ssm_heads``) carries its recurrent state
    the same way: ``ssm_cache`` rides the carry beside ``cache`` and comes
    back as ``new_cache = (cache, ssm_cache)``; without one (the cache-less
    forward) every row starts from zero state and ``new_cache`` is as ever.

    With ``moe_live`` (a model that routes sparsely, ``config.moe_sparse``)
    a third element comes back, int32 ``[2]``, both summed over the layers:
    the number of distinct held experts that the live tokens picked — the
    expert weights this step had to read — and the number whose weights the
    form that ``moe_mlp`` took did read.
    """
    c = config
    if c.has_state and cache is not None and ssm_cache is None:
        # a cached row continues from its recurrent state: a step that hands
        # in none would silently run the mixer from zero
        raise NotImplementedError(
            "a forward over a KV cache needs the mixer's ssm_cache too "
            f"({c.n_state_layers} layers carry recurrent state): this step "
            "does not carry it")
    with jax.named_scope("embed"):
        x = scaled(params["embed"][tokens], c.embedding_multiplier)  # [B,S,D]
    if c.layer_plan:
        assert moe_live is None, "a layer_plan routes nothing"
        x, new_cache = _forward_plan(params, x, c, attention, cache, ssm_cache, ssm_rows,
                                     remat, qm_backend)
        if not return_hidden:
            x = lm_head(params, x, config=c, qm_backend=qm_backend)
        return x, new_cache

    # the scan runs over PERIODS of the layer pattern; inside one the kinds
    # are static and its layers stand one after another in the body. A period
    # of one layer scans the stacks as they lie; a longer one indexes each
    # layer's leaves out of the WHOLE stacks (a slice of a period's slice
    # would copy the period's weights every step). Do not make a run of
    # layers of one kind a scan of its own inside the period's: on the v5e a
    # ragged round with that nested loop hung one time in ten (PERF.md §6,
    # PR 32)
    pattern = c.layer_pattern or (FULL,)
    n_periods = c.n_scan_layers // len(pattern)
    stacks = params["layers"]

    def one_layer(carry, layer_params, layer_idx, kind, dense_mlp=False):
        x, cache, ssm, experts = carry
        out = _layer(
            x, layer_params, cache, layer_idx,
            positions=positions, config=c, attention=attention,
            qm_backend=qm_backend, ssm_cache=ssm, ssm_rows=ssm_rows, kind=kind,
            moe_live=moe_live, moe_backend=moe_backend, dense_mlp=dense_mlp,
        )
        if moe_live is not None:
            experts = experts + out[-1]
        # the layer returns its ssm cache only where the model has state
        return (*out[:2], out[2] if c.has_state else ssm, experts)

    # a layer's leaves come to the body as the scan's slices, or, where a
    # period is longer than one layer or the experts' stacks must stay whole
    # (below), are indexed out of the stacks inside it
    by_index = len(pattern) > 1 or c.moe_sparse
    by_kind = bool(c.attn_kinds)  # k and v stacked by kind (``_stack_kinds``)

    def pool_index(i, kind):  # the leading layers' pages come first in their kind's pool
        n_lead = c.n_leading_of(kind)
        return i + n_lead if n_lead else i

    def scan_body(carry, scanned):
        layer_params, period_idx = scanned
        if not by_index:
            return one_layer(carry, layer_params, pool_index(period_idx, pattern[0]),
                             pattern[0]), None
        for j, kind in enumerate(pattern):
            def among(kinds):
                """The layer's index among the scan's layers of ``kinds`` (what
                the stacks and the caches, both by kind, are indexed by; None:
                down the whole depth), or None where it is of none of them."""
                if kinds is None:
                    return period_idx * len(pattern) + j
                if kind not in kinds:
                    return None
                first, *rest = [period_idx * pattern.count(k) + pattern[:j].count(k)
                                for k in kinds if k in pattern]
                return sum(rest, first)

            # the grouped matmul and the touched pass take the experts' whole
            # stacks (StackedLeaf)
            whole = ("moe_in", "moe_out") if c.moe_sparse else ()
            layer_params = {
                name: StackedLeaf(leaf, among(None)) if name in whole else jax.tree.map(
                    lambda a, i=among(_stack_kinds(name, by_kind)): lax.dynamic_index_in_dim(
                        a, i, 0, keepdims=False), leaf)
                for name, leaf in stacks.items()
                if among(_stack_kinds(name, by_kind)) is not None}
            carry = one_layer(carry, layer_params, pool_index(among((kind,)), kind), kind)
        return carry, None

    if remat:
        # per-layer remat: backward recomputes one layer at a time, so live
        # residuals stay O(one layer) instead of O(n_layers)
        scan_body = jax.checkpoint(scan_body)

    carry = (x, cache, ssm_cache, None if moe_live is None else jnp.zeros(
        (3 if c.kv_lora_rank else 2,), jnp.int32))
    leading = c.kinds_of_leading
    for i, kind in enumerate(leading):
        def at(name):  # the layer's place in the leading stack ``name``, by kind as the scan's
            kinds = _stack_kinds(name, by_kind)
            if kinds is None:
                return i
            return sum(leading[:i].count(k) for k in kinds) if kind in kinds else None

        carry = one_layer(
            carry, {name: jax.tree.map(lambda a, j=at(name): a[j], leaf)
                    for name, leaf in params["dense_layers"].items() if at(name) is not None},
            jnp.int32(leading[:i].count(kind)), kind, dense_mlp=True)
    (x, new_cache, ssm_cache, experts), _ = lax.scan(
        scan_body, carry, (None if by_index else stacks, jnp.arange(n_periods)))
    if ssm_cache is not None:
        new_cache = (new_cache, ssm_cache)

    if not prenorm:  # (a next-token-prediction module reads the output before it: mtp_block)
        x = rms_norm(x, params["norm"], c.norm_eps)
    if not return_hidden:
        x = lm_head(params, x, config=c, qm_backend=qm_backend)
    return (x, new_cache) if moe_live is None else (x, new_cache, experts)


def trunk_logits(params: dict[str, Any], prenorm: Array, *, config: LlamaConfig,
                 qm_backend: str | None = None) -> Array:
    """The trunk's logits of its last layer's output ``prenorm`` [..., D]
    (``forward(return_hidden=True, prenorm=True)``): the final norm, the head."""
    return lm_head(params, rms_norm(prenorm, params["norm"], config.norm_eps),
                   config=config, qm_backend=qm_backend)


def mtp_block(
    params: dict[str, Any],
    hidden: Array,  # [B, S, D] — the trunk's last-layer output BEFORE its final norm, at i
    tokens: Array,  # [B, S] int32 — the token at i + 1
    positions: Array,  # [B, S] int32 — where the pair stands (see below)
    *,
    config: LlamaConfig,
    attention: AttentionFn,
    cache: Any = None,
    qm_backend: str | None = None,
    moe_live: Array | None = None,
    moe_backend: str = "ref",
) -> tuple[Array, Any] | tuple[Array, Any, Array]:
    """The next-token-prediction module over pairs ``(h_i, t_{i+1})``::

        u_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]
        h'_i = RoutedBlock_mtp(u_i);  logits_i = Head(RMSNorm_sh(h'_i))  ~ t_{i+2}

    Returns ``(h' [B, S, D], new_cache)`` (``mtp_logits`` is the last line) and,
    with ``moe_live``, the block's counts as ``_layer`` gives them. Embedding
    and head are the trunk's. The block is ONE more application of ``_layer``
    with leaves of its own; its pages are the pool's LAST layer. The rotation
    is relative, so a caller may stand pair ``i`` at position ``i`` (the
    cache-less forward) or at ``i + 1``, the slot of the token it consumes
    (the served steps: a slot is then a function of the tokens up to and
    including its own, as the trunk's is, and a shared head's pages hold the
    module's rows too)."""
    c, mtp = config, params["mtp"]
    with jax.named_scope("mtp_project"):
        emb = scaled(params["embed"][tokens], c.embedding_multiplier)
        u = jnp.concatenate([rms_norm(emb, mtp["enorm"], c.norm_eps),
                             rms_norm(hidden.astype(emb.dtype), mtp["hnorm"], c.norm_eps)], axis=-1)
        x = dense(u, mtp["eh_proj"], qm_backend=qm_backend)
    with jax.named_scope("mtp_block"):
        whole = ("moe_in", "moe_out") if c.moe_sparse else ()
        layer_params = {name: StackedLeaf(leaf, jnp.int32(0)) if name in whole
                        else jax.tree.map(lambda a: a[0], leaf)
                        for name, leaf in mtp["layer"].items()}
        out = _layer(x, layer_params, cache, jnp.int32(c.n_of(FULL)), positions=positions,
                     config=c, attention=attention, qm_backend=qm_backend, kind=FULL,
                     moe_live=moe_live, moe_backend=moe_backend)
    return out


def mtp_logits(params: dict[str, Any], h: Array, *, config: LlamaConfig,
               qm_backend: str | None = None) -> Array:
    """The module's logits of its block's output ``h`` [..., D]: its own norm,
    the trunk's head."""
    with jax.named_scope("mtp_head"):
        return lm_head(params, rms_norm(h, params["mtp"]["norm"], config.norm_eps),
                       config=config, qm_backend=qm_backend)


def _forward_plan(params: dict[str, Any], x: Array, config: LlamaConfig, attention: Any,
                  cache: Any, ssm_cache: Any, ssm_rows: Any, remat: bool,
                  qm_backend: str | None) -> tuple[Array, Any]:
    """``forward``'s layers under a ``layer_plan``: one scan a segment over
    its periods (a segment of one period stands unrolled), a layer's leaves
    indexed out of the whole stacks by the count of earlier layers that have
    the leaf (``sambay.stack_kinds``). The carry holds, beside the caches, the
    memory the last MAMBA1 layer left for the GMU layers: one token's ``y``,
    never a state. ``cache`` is ``(the FULL layer's pool, the WINDOW layers'
    pool)`` (each the four arrays of ``PagedKVCache.layers_pytree``), or None:
    then the FULL layer's K and V ride in its place for the CROSS layers."""
    c = config
    stacks = params["layers"]
    kinds = sambay.kinds_of(c.layer_plan)
    B, S, _ = x.shape
    memory = jnp.zeros((B, S, c.m1_inner), x.dtype) if GMU in kinds else None
    if cache is None and CROSS in kinds:
        kv = jnp.zeros((B, S, c.n_kv_heads, c.head_dim), x.dtype)
        cache = (kv, kv)
    # the segment whose MAMBA1 layers' y the GMU layers read: the last with one
    memory_segment = max((i for i, (p, _r) in enumerate(c.layer_plan) if MAMBA1 in p),
                         default=-1) if GMU in kinds else -1
    carry = (x, cache, ssm_cache, memory)
    before: dict[str, int] = {k: 0 for k in sambay.PLAN_KINDS}  # layers of a kind so far
    depth0 = 0
    for seg, (period, repeats) in enumerate(c.layer_plan):
        def body(carry, p_idx, period=period, before=dict(before), depth0=depth0, seg=seg):
            x, cache, ssm, memory = carry
            for j, kind in enumerate(period):
                def at(of):  # this layer's index among the layers of kinds ``of``
                    return sum(before[k] + p_idx * period.count(k) + period[:j].count(k)
                               for k in of)

                lp = {name: jax.tree.map(
                    lambda a, i=at(sambay.stack_kinds(name)): lax.dynamic_index_in_dim(
                        a, i, 0, keepdims=False), leaf)
                    for name, leaf in stacks.items() if kind in sambay.stack_kinds(name)}
                # a CROSS layer reads the pool of the FULL layer before it
                cache_idx = at((FULL,)) - 1 if kind == CROSS else at((kind,))
                x, cache, ssm, memory = sambay.layer(
                    x, lp, c, kind=kind, depth=depth0 + p_idx * len(period) + j,
                    attention_fn=attention, cache=cache,
                    cache_idx=jnp.asarray(cache_idx, jnp.int32), ssm_cache=ssm,
                    state_idx=jnp.asarray(at((MAMBA1,)), jnp.int32), ssm_rows=ssm_rows,
                    memory=memory, keep_memory=seg == memory_segment, qm_backend=qm_backend)
            return (x, cache, ssm, memory), None

        if remat:
            body = jax.checkpoint(body)
        if repeats == 1:
            carry, _ = body(carry, 0)
        else:
            carry, _ = lax.scan(body, carry, jnp.arange(repeats))
        for k in sambay.PLAN_KINDS:
            before[k] += repeats * period.count(k)
        depth0 += repeats * len(period)
    x, cache, ssm_cache, _memory = carry
    x = sambay.layer_norm(x, params["norm"], params["norm_b"], c.norm_eps)
    return x, (cache if ssm_cache is None else (cache, ssm_cache))


@jax.named_scope("head")
def lm_head(params: dict[str, Any], x: Array, *, config: LlamaConfig,
            qm_backend: str | None = None) -> Array:
    """Project hidden states [..., D] to fp32 logits [..., vocab]. A
    quantized head routes through quant_matmul (the reference backend is
    bitwise the historical dequantize-then-einsum; the fused kernel
    accumulates fp32 and streams the head packed)."""
    head = params["embed"].T if config.tie_embeddings else params["lm_head"]
    if isinstance(head, (QTensor, Q4Tensor)):
        from finchat_tpu.ops.dispatch import quant_matmul

        return scaled(quant_matmul(x, head, backend=qm_backend,
                                   preferred_element_type=jnp.float32),
                      config.lm_head_multiplier)
    return scaled(jnp.einsum("...d,dv->...v", x, head, preferred_element_type=jnp.float32),
                  config.lm_head_multiplier)


def make_causal_attention(backend: str, scale: float | None = None,
                          config: LlamaConfig | None = None) -> AttentionFn:
    """Cache-less causal attention over the whole sequence (training, tests,
    one-shot prefill) on an explicitly-resolved backend. Callers that jit
    must resolve the backend OUTSIDE the traced function and key their jit
    cache on it — resolving env state at trace time bakes the first answer
    into the cache (see ops/dispatch.py). ``scale``: the model's softmax
    scale (``LlamaConfig.attention_scale``; None = head_dim ** -0.5). With
    the ``config`` of a model with latent attention: its callback
    (``mla.LatentAttentionFn``), dense over the sequence, the same selection.
    With one of a plan or of window layers: a WINDOW layer, and a layer whose
    keys and values differ in width (``v_head_dim``), take ``ops/refs.py``'s
    ``mha_reference`` on EVERY backend — the contiguous kernel has no window,
    no sink and one head width; no served step comes here."""
    from finchat_tpu.ops.dispatch import causal_attention

    if config is not None and config.kv_lora_rank:
        from finchat_tpu.ops import latent_attention

        shape = latent_attention.LatentShape(
            config.kv_lora_rank, config.index_topk, scale or config.head_dim ** -0.5)

        def latent(x: mla.LatentInputs, layer_cache: Any, layer_idx: Array):
            out, selected = latent_attention.causal_attention(
                x.q, x.row, x.idx_q, x.idx_w, x.idx_k, shape)
            return out, layer_cache, selected

        return latent

    if config is not None and (config.layer_plan or config.window):
        from finchat_tpu.ops.refs import mha_reference

        def by_kind(q: Array, k: Array | None, v: Array | None, layer_cache: Any,
                    layer_idx: Array, kind: str = FULL, sink: Array | None = None):
            # dense over the sequence: a plan's FULL layer leaves its K and V in
            # the cache's place, a CROSS layer reads them, a WINDOW layer masks
            if kind == CROSS:
                k, v = layer_cache
            elif kind == FULL and config.layer_plan:
                layer_cache = (k, v)
            if kind == WINDOW:
                return mha_reference(q, k, v, causal=True, scale=scale, window=config.window,
                                     sink=sink), layer_cache
            if k.shape[-1] != v.shape[-1]:  # (the contiguous kernel takes one width)
                return mha_reference(q, k, v, causal=True, scale=scale), layer_cache
            return causal_attention(q, k, v, backend=backend, scale=scale), layer_cache

        return by_kind

    def attention(q: Array, k: Array, v: Array, layer_cache: Any, layer_idx: Array) -> tuple[Array, Any]:
        return causal_attention(q, k, v, backend=backend, scale=scale), layer_cache

    return attention


def full_causal_attention(q: Array, k: Array, v: Array, layer_cache: Any, layer_idx: Array) -> tuple[Array, Any]:
    """Backend resolved per-call — ONLY for non-jitted use or single-trace
    contexts; jitted callers should use make_causal_attention(backend)."""
    from finchat_tpu.ops.dispatch import causal_attention

    return causal_attention(q, k, v), layer_cache


@partial(jax.jit, static_argnames=("config", "attn_backend", "qm_backend"))
def _forward_full_jit(
    params: dict[str, Any], tokens: Array, positions: Array, *, config: LlamaConfig, attn_backend: str,
    qm_backend: str | None = None,
) -> Array:
    logits, _ = forward(
        params, tokens, positions, config=config,
        attention=make_causal_attention(attn_backend, config.attention_scale, config),
        cache=None, qm_backend=qm_backend,
    )
    return logits


def forward_full(
    params: dict[str, Any], tokens: Array, positions: Array, *,
    config: LlamaConfig, attn_backend: str | None = None,
    qm_backend: str | None = None,
) -> Array:
    """Convenience jitted forward with full causal attention, no cache.
    The backends resolve at CALL time and key the jit cache."""
    if attn_backend is None:
        from finchat_tpu.ops.dispatch import attention_backend

        attn_backend = attention_backend()
    return _forward_full_jit(params, tokens, positions, config=config,
                             attn_backend=attn_backend, qm_backend=qm_backend)
