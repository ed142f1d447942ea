"""``model_type`` "deepseek_v32": latent attention (MLA) with the lightning
indexer's top-k selection in every layer, ``first_k_dense_replace`` dense
layers in front of layers of many routed experts (a sigmoid, group-limited
router with a selection bias) beside one shared expert.

The file's ``n_routed_experts`` is what THIS chip holds of the published
``reduced.n_routed_experts.from`` experts the router scores: the held range is
``[0, n_routed_experts)``; a pick on an absent expert adds nothing (its chip
adds it); the gates are normalised over ALL picks. ``vocab_size`` is this
chip's slice of the vocabulary: ids, logits and sampling are over the slice.

The plain reference is the EXPANDED form (it shares no algebra with the
program's absorbed attention), float32 at ``highest``: per-head keys and
values made from the latents, the indexer's scores over the whole causal
context, the ``index_topk`` largest by a plain sort, full softmax attention
under that mask in blocks of queries; the experts a loop over the held ones
with masks; no cache, no kernel::

    c_q = RMSNorm(W_dq h);  q = W_uq c_q -> heads of [q_nope | q_rope]
    [c | k_r] = W_dkv h;  c_kv = RMSNorm(c);  k_rope = RoPE(k_r)     one for all heads
    [k_nope_i | v_i] = W_ukv,i c_kv
    s_i[t, j] = scale (q_nope_i[t] . k_nope_i[j] + RoPE(q_rope_i)[t] . k_rope[j])
    o_i = softmax_{j <= t, j in S_t}(s_i) v_i;  y = W_o [o_1 .. o_H]
    q^I = W_qb c_q (heads, first rope dims rotated);  k^I = LayerNorm(W_k h) (rotated alike)
    I[t, j] = sum_h (W_w h)[t, h] heads^-1/2 ReLU(q^I_h[t] . k^I[j]) dim^-1/2
    S_t = the min(index_topk, t + 1) largest I[t, j] over j <= t
    s = sigmoid(W_r h);  choice = s + b;  the topk_group groups whose 2 largest sum highest;
    picks = the num_experts_per_tok largest choices inside them
    g_e = routed_scaling_factor s_e / sum_picks s;  y = sum_{picked, held} g_e E_e(h) + Shared(h)

The rotation pairs dimension ``i`` with ``i + half`` (the program's layout;
``assumed`` in the file) at YaRN's corrected frequencies. It reads the
program's parameter tree (``dense_layers`` and ``layers``) and nothing else
of the program; one layer and one matmul weight are upcast at a time.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.costs import BYTES
from perfbench.models.falcon_h1 import _rows_of
from perfbench.reference import _f32, _rms_norm

F32 = jnp.float32
WIDTH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "q_lora_rank",
              "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "index_head_dim", "index_n_heads", "index_topk", "num_experts_per_tok",
              "num_attention_heads", "n_group", "topk_group", "n_shared_experts")
QUERY_BLOCK = 256  # queries whose scores [heads, block, context] are alive at once
HEAD_BLOCK = 32768  # columns of the head upcast at a time
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9  # a v5e chip (perfbench/peaks.json)
# The unit of the routing margin, in standard deviations of a token's 256
# choice scores: ``correct.py`` compares positions whose margin is at least
# 0.2, which then stands at 0.05 sigma. Measured on the chip at the cell's
# widths (PERF.md section 4, PR 40: 1,024 positions of 16 seeds): 73 read over
# 0.06 — a flipped pick on a held expert, a gate of about 0.3: 0.11 to 0.39 —
# all but three of them under 0.05 sigma (those at 0.055, 0.075 and 0.092:
# 0.13, 0.23, 0.16), none above 0.1; from 0.05 sigma up 25 to 37 of a check's
# 64 positions are compared (at 0.2 sigma 2 to 10: under correct.py's 4 on a
# third of the seeds), and the file's ``max`` has room for the three
MARGIN_UNIT = 0.25


def _sizes(config: dict) -> dict:
    cut = config.get("reduced", {}).get("n_routed_experts")
    held = int(config["n_routed_experts"])
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    scaling = config["rope_scaling"]
    m = 0.1 * float(scaling["mscale_all_dim"]) * math.log(float(scaling["factor"])) + 1.0
    return {"d": int(config["hidden_size"]), "H": int(config["num_attention_heads"]),
            "q_lora": int(config["q_lora_rank"]), "kv_lora": int(config["kv_lora_rank"]),
            "nope": nope, "rope": rope, "v": int(config["v_head_dim"]),
            "Hi": int(config["index_n_heads"]), "Di": int(config["index_head_dim"]),
            "topk": int(config["index_topk"]),
            "held": held, "router": int(cut["from"]) if cut else held,
            "top_k": int(config["num_experts_per_tok"]),
            "groups": int(config["n_group"]), "kept_groups": int(config["topk_group"]),
            "gate_scale": float(config["routed_scaling_factor"]),
            "f": int(config["moe_intermediate_size"]),
            "fs": int(config["moe_intermediate_size"]) * int(config["n_shared_experts"]),
            "fd": int(config["intermediate_size"]),
            "n": int(config["num_hidden_layers"]), "n_dense": int(config["first_k_dense_replace"]),
            "scale": (nope + rope) ** -0.5 * m * m}


def program_config(config: dict):
    from finchat_tpu.models.llama import LlamaConfig

    s = _sizes(config)
    if "kv_lora_rank" not in LlamaConfig.__dataclass_fields__:
        raise KeyError("this checkout's block (finchat_tpu/models/llama.py) has no latent "
                       "attention, no indexer and no sigmoid router: it cannot run model_type "
                       "'deepseek_v32'")
    from finchat_tpu.models.mla import RopeScaling

    for key, want in (("attention_bias", False), ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("hidden_act", "silu"), ("moe_layer_freq", 1)):
        if config.get(key, want) != want:
            raise ValueError(f"{key}: this adapter builds {want!r}")
    if config.get("num_nextn_predict_layers"):
        raise ValueError("num_nextn_predict_layers: the next-token-prediction module is not "
                         "loaded (it drafts; no output of the main model depends on it)")
    scaling = config["rope_scaling"]
    if scaling.get("type") != "yarn":
        raise ValueError("rope_scaling.type: this adapter builds 'yarn'")
    return LlamaConfig(
        vocab_size=int(config["vocab_size"]), dim=s["d"], n_layers=s["n"], n_heads=s["H"],
        n_kv_heads=1, head_dim=s["nope"] + s["rope"], hidden_dim=s["f"],
        rope_theta=float(config["rope_theta"]), norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["engine"].get("max_seq_len", 8192)),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        n_experts=s["held"], top_k_experts=s["top_k"], moe_router_width=s["router"],
        moe_shared_dim=s["fs"], moe_fused_glu=True, attention_scale=s["scale"],
        q_lora_rank=s["q_lora"], kv_lora_rank=s["kv_lora"], qk_nope_dim=s["nope"],
        qk_rope_dim=s["rope"], v_head_dim=s["v"],
        rope_scaling=RopeScaling(
            float(scaling["factor"]), int(scaling["original_max_position_embeddings"]),
            float(scaling["beta_fast"]), float(scaling["beta_slow"]),
            float(scaling["mscale"]), float(scaling["mscale_all_dim"])),
        index_heads=s["Hi"], index_head_dim=s["Di"], index_topk=s["topk"],
        moe_score="sigmoid", moe_select_bias=True, moe_groups=s["groups"],
        moe_topk_groups=s["kept_groups"], moe_gate_scale=s["gate_scale"],
        moe_norm_picks=bool(config.get("norm_topk_prob", True)),
        leading_dense_layers=s["n_dense"], dense_hidden_dim=s["fd"],
    )


# --- the plain reference ----------------------------------------------------

def _inv_freq(config: dict) -> np.ndarray:
    """YaRN's frequencies over the ``qk_rope_head_dim`` rotated dims: plain
    where a dim turns more than ``beta_fast`` times over the original window,
    divided by ``factor`` where fewer than ``beta_slow``, a ramp between."""
    sc, dim, theta = config["rope_scaling"], int(config["qk_rope_head_dim"]), float(config["rope_theta"])
    plain = theta ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)

    def dim_of(turns: float) -> float:
        window = float(sc["original_max_position_embeddings"])
        return dim * math.log(window / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(float(sc["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(sc["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (plain / float(sc["factor"]) * ramp + plain * (1.0 - ramp)).astype(np.float32)


def _rotate(x, inv_freq, rotated: bool = True):
    """``x`` [S, ..., D]: its first ``2 len(inv_freq)`` dims rotated by the
    token's position, dim ``i`` paired with ``i + half``."""
    if not rotated:
        return x
    n = 2 * len(inv_freq)
    angle = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(inv_freq)[None, :]
    angle = angle.reshape(x.shape[0], *(1,) * (x.ndim - 2), -1)
    a, b = x[..., :n // 2], x[..., n // 2:n]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, x[..., n:]], axis=-1)


@jax.jit
def _matmul(x, w):
    return x @ _f32(w)


@partial(jax.jit, static_argnames=("topk",))
def _selection(scores, *, topk):
    """``[S, S]`` bool: for each query the ``min(topk, t + 1)`` context tokens
    ``j <= t`` with the largest score, by a plain sort of the row."""
    S = scores.shape[0]
    causal = jnp.tril(jnp.ones((S, S), bool))
    masked = jnp.where(causal, scores, -jnp.inf)
    if topk >= S:
        return causal
    order = jnp.argsort(-masked, axis=-1, stable=True)  # ties: the lower position first
    rank = jnp.argsort(order, axis=-1)
    return causal & (rank < topk)


@partial(jax.jit, static_argnames=("scale",))
def _attend(q_nope, q_rope, k_nope, k_rope, v, mask, *, scale):
    """A block of queries ``[Q, H, .]`` against the whole context, expanded
    form: ``[Q, H, v]``."""
    s = (jnp.einsum("qhn,khn->hqk", q_nope, k_nope)
         + jnp.einsum("qhr,kr->hqk", q_rope, k_rope)) * scale
    s = jnp.where(mask[None], s, -jnp.inf)
    return jnp.einsum("hqk,khv->qhv", jax.nn.softmax(s, axis=-1), v)


def _attention(h, lp, j, config: dict, s: dict, cast, variant: str | None):
    """One layer's latent attention over the normed inputs ``h`` [S, d].
    ``variant``: None, or one of the controls — ``no_selection`` (every
    context token attended) and ``unrotated_index`` (the selection made by
    index queries and keys that were not rotated) — or ``rounded_index``: the
    indexer's queries, keys and head weights rounded to bfloat16, everything
    else float32 — no fault, but what a bfloat16 program's selection is made
    from: where the context is over ``index_topk`` the tokens scored within a
    rounding of the k-th change sides (PERF.md section 4)."""
    S, H, eps = h.shape[0], s["H"], float(config["rms_norm_eps"])
    inv_freq = _inv_freq(config)
    c_q = _rms_norm(_matmul(h, cast(lp["attn_q_a"][j])), _f32(lp["attn_q_a_norm"][j]), eps)
    # the program keeps W_uq's columns apart (every head's q_nope, every head's
    # q_rope) and, as the indexer's W_qb, output-major [N, q_lora_rank]
    q_nope = _matmul(c_q, cast(lp["attn_q_nope"][j].T)).reshape(S, H, s["nope"])
    q_rope = _rotate(_matmul(c_q, cast(lp["attn_q_rope"][j].T)).reshape(S, H, s["rope"]), inv_freq)
    kv = _matmul(h, cast(lp["attn_kv_a"][j]))
    c_kv = _rms_norm(kv[:, :s["kv_lora"]], _f32(lp["attn_kv_a_norm"][j]), eps)
    k_rope = _rotate(kv[:, s["kv_lora"]:], inv_freq)
    # the program keeps W_ukv's halves apart: a head's keys' [nope, R], its values' [R, v]
    k_nope = jnp.einsum("sr,hnr->shn", c_kv, cast(lp["attn_uk"][j]).astype(F32))
    v = jnp.einsum("sr,hrv->shv", c_kv, cast(lp["attn_uv"][j]).astype(F32))

    rotated = variant != "unrotated_index"
    iq = _rotate(_matmul(c_q, cast(lp["attn_idx_q_b"][j].T)).reshape(S, s["Hi"], s["Di"]),
                 inv_freq, rotated)
    ik = _matmul(h, cast(lp["attn_idx_k"][j]))
    ik = (ik - ik.mean(-1, keepdims=True)) * jax.lax.rsqrt(ik.var(-1, keepdims=True) + 1e-6)
    ik = _rotate(ik * _f32(lp["attn_idx_k_norm"][j]) + _f32(lp["attn_idx_k_bias"][j]),
                 inv_freq, rotated)
    iw = _matmul(h, cast(lp["attn_idx_w"][j])) * s["Hi"] ** -0.5
    if variant == "rounded_index":  # what a bfloat16 program's indexer is given
        iq, ik, iw = (a.astype(jnp.bfloat16).astype(F32) for a in (iq, ik, iw))
    out = []
    for a in range(0, S, QUERY_BLOCK):
        b = min(a + QUERY_BLOCK, S)
        dots = jax.nn.relu(jnp.einsum("qhd,kd->qhk", iq[a:b], ik)) * s["Di"] ** -0.5
        out.append(jnp.einsum("qhk,qh->qk", dots, iw[a:b]))
    scores = jnp.concatenate(out, axis=0)  # [S, S]
    mask = _selection(scores, topk=S if variant == "no_selection" else s["topk"])
    o = jnp.concatenate(
        [_attend(q_nope[a:a + QUERY_BLOCK], q_rope[a:a + QUERY_BLOCK], k_nope, k_rope, v,
                 mask[a:a + QUERY_BLOCK], scale=s["scale"]) for a in range(0, S, QUERY_BLOCK)])
    return _matmul(o.reshape(S, H * s["v"]), cast(lp["attn_o"][j]))


@jax.jit
def _glu(h, w_in, w_out):
    a, b = jnp.split(h @ _f32(w_in), 2, axis=-1)
    return (jax.nn.silu(a) * b) @ _f32(w_out)


@partial(jax.jit, static_argnames=("top_k", "groups", "kept_groups", "gate_scale", "norm", "held"))
def _route(h, router, bias, *, top_k, groups, kept_groups, gate_scale, norm, held=0):
    """``(picks [T, k], gates [T, k], margin [T])`` of the sigmoid,
    group-limited router. The margin is the smallest change of a choice score,
    in ``MARGIN_UNIT`` standard deviations of the token's choice scores, that would change
    what the HELD experts ``[0, held)`` add: a held expert entering or leaving
    the picks, a group changing sides of the kept / dropped line while a group
    with held experts is kept, or such a group crossing that line. A flip among
    absent experts moves only the gates' common denominator, by a hair."""
    score = jax.nn.sigmoid(h @ _f32(router))  # [T, R]
    choice = score + bias
    T, R = choice.shape
    size = R // groups
    in_group = jnp.sort(choice.reshape(T, groups, size), axis=-1)
    group_score = in_group[..., -1] + in_group[..., -2]  # a group's two largest
    order = jnp.argsort(-group_score, axis=-1)
    keep = jnp.zeros((T, groups), bool).at[jnp.arange(T)[:, None], order[:, :kept_groups]].set(True)
    eligible = jnp.repeat(keep, size, axis=-1)
    masked = jnp.where(eligible, choice, -jnp.inf)
    ranked = jnp.argsort(-masked, axis=-1)
    picks = ranked[:, :top_k]
    gates = jnp.take_along_axis(score, picks, axis=-1)
    if norm:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    # the margin
    ranked_groups = jnp.take_along_axis(group_score, order, axis=-1)
    line = ranked_groups[:, kept_groups - 1] - ranked_groups[:, min(kept_groups, groups - 1)]
    with_held = jnp.arange(groups) < -(-held // size)  # the groups that hold held experts
    any_kept = jnp.any(keep & with_held[None], axis=-1)
    to_enter = jnp.min(jnp.where(with_held[None], ranked_groups[:, kept_groups - 1:kept_groups]
                                 - group_score, jnp.inf), axis=-1)
    last_pick = jnp.take_along_axis(masked, ranked[:, top_k - 1:top_k], axis=-1)
    first_out = jnp.take_along_axis(masked, ranked[:, top_k:top_k + 1], axis=-1)
    is_held = (jnp.arange(R) < held)[None] & eligible
    picked = masked >= last_pick
    to_flip = jnp.min(jnp.where(is_held, jnp.where(picked, masked - first_out, last_pick - masked),
                                jnp.inf), axis=-1)
    margin = jnp.where(any_kept, jnp.minimum(line, to_flip), to_enter) / (
        MARGIN_UNIT * jnp.std(choice, axis=-1))
    return picks, gates * gate_scale, margin if held else jnp.full((T,), jnp.inf)


def _experts(h, lp, j, config: dict, s: dict, cast):
    """``(the layer's output [T, d], its routing margin [T])``."""
    picks, gates, margin = _route(
        h, lp["router"][j], _f32(lp["router_bias"][j]), top_k=s["top_k"], groups=s["groups"],
        kept_groups=s["kept_groups"], gate_scale=s["gate_scale"],
        norm=bool(config.get("norm_topk_prob", True)), held=s["held"])
    out = _glu(h, cast(lp["shared_in"][j]), cast(lp["shared_out"][j]))
    for e in range(s["held"]):  # the held range starts at expert 0
        g = jnp.sum(jnp.where(picks == e, gates, 0.0), axis=-1)  # 0 where not picked
        out = out + g[:, None] * _glu(h, cast(lp["moe_in"][j, e]), cast(lp["moe_out"][j, e]))
    return out, margin


@jax.jit
def _mlp(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def _forward(params, tokens, config: dict, *, cast=None, variant: str | None = None):
    """``(the residual stream [tokens, hidden] behind the last layer, each
    token's smallest routing margin over the layers)``; under
    ``default_matmul_precision("highest")``."""
    cast = cast or (lambda w: w)
    s, eps = _sizes(config), float(config["rms_norm_eps"])
    x = _f32(params["embed"][jnp.asarray(tokens, jnp.int32)])
    margins = jnp.full((x.shape[0],), jnp.inf, F32)
    for i in range(s["n"]):
        dense = i < s["n_dense"]
        lp, j = (params["dense_layers"], i) if dense else (params["layers"], i - s["n_dense"])
        h = _rms_norm(x, _f32(lp["ln_attn"][j]), eps)
        x = x + _attention(h, lp, j, config, s, cast, variant)
        h = _rms_norm(x, _f32(lp["ln_mlp"][j]), eps)
        if dense:
            x = x + _mlp(h, cast(lp["mlp_gate"][j]), cast(lp["mlp_up"][j]), cast(lp["mlp_down"][j]))
        else:
            routed, margin = _experts(h, lp, j, config, s, cast)
            x, margins = x + routed, jnp.minimum(margins, margin)
    return x, margins


def reference_logits(params, tokens, config: dict, *, positions, cast=None,
                     variant: str | None = None):
    """Float32 logits ``[len(positions), vocab]`` of a full causal forward
    over ``tokens``, and each position's smallest routing margin over the
    routed layers (``_route``, in ``MARGIN_UNIT``s; ``correct.py`` leaves
    positions under 0.2 out — a flip there exchanges one of eight gates of
    about 0.3 on a HELD expert for nothing, a fifth of the logits' spread:
    PERF.md section 4). ``cast`` stands in for
    the upcast of each matmul weight, ``variant`` for one of ``_attention``'s
    controls."""
    cast = cast or (lambda w: w)
    with jax.default_matmul_precision("highest"):
        x, margins = _forward(params, tokens, config, cast=cast, variant=variant)
        x = _rms_norm(x, _f32(params["norm"]), float(config["rms_norm_eps"]))[jnp.asarray(positions)]
        head = params["embed"].T if config.get("tie_word_embeddings") else params["lm_head"]
        logits = jnp.concatenate(
            [_matmul(x, cast(head[:, c:c + HEAD_BLOCK])) for c in range(0, head.shape[1], HEAD_BLOCK)],
            axis=-1)
    return logits, margins[jnp.asarray(positions)]


def control_logits(params, tokens, config: dict, *, positions):
    """The reference with every matmul weight (the latent projections, the
    indexer's, the dense MLP's, the routed and shared experts', the head)
    taken through int8, a scale an output channel: the step below the
    bfloat16 the configuration states. The router and its bias (float32 in
    the program), the norms and activations stay float32."""
    def through_int8(w):
        w = jnp.asarray(w).astype(F32)
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale

    return reference_logits(params, tokens, config, positions=positions, cast=through_int8)


# --- the yardstick's counts ---------------------------------------------------

def param_counts(config: dict) -> dict:
    """Parameters by group, of what THIS chip holds (``n_routed_experts``
    routed experts a routed layer, ``vocab_size`` rows of the vocabulary).
    ``layer`` is the MEAN layer."""
    s, d = _sizes(config), int(config["hidden_size"])
    H = s["H"]
    attention = (d * s["q_lora"] + s["q_lora"] + s["q_lora"] * H * (s["nope"] + s["rope"])
                 + d * (s["kv_lora"] + s["rope"]) + s["kv_lora"]
                 + s["kv_lora"] * H * (s["nope"] + s["v"]) + H * s["v"] * d)
    indexer = s["q_lora"] * s["Hi"] * s["Di"] + d * s["Di"] + 2 * s["Di"] + d * s["Hi"]
    expert, shared = 3 * d * s["f"], 3 * d * s["fs"]
    router = (d + 1) * s["router"]  # and the selection bias
    dense_layer = attention + indexer + 3 * d * s["fd"] + 2 * d
    outside = attention + indexer + shared + router + 2 * d  # a routed layer outside its experts
    routed = s["held"] * expert
    n_routed = s["n"] - s["n_dense"]
    layers = s["n_dense"] * dense_layer + n_routed * (outside + routed)
    embed = int(config["vocab_size"]) * d
    head = 0 if config.get("tie_word_embeddings") else embed
    return {"attention": attention, "indexer": indexer, "expert": expert, "routed": routed,
            "shared": shared, "router": router, "dense_layer": dense_layer,
            "routed_layer_outside_experts": outside, "routed_layer": outside + routed,
            "layer": layers // s["n"] if layers % s["n"] == 0 else layers / s["n"],
            "layers": layers, "embed": embed, "head": head, "total": layers + embed + head + d}


def latent_row_bytes(config: dict) -> int:
    """A token's latent row in one layer, ``[c_kv | k_rope]``: LOGICAL bytes
    (the page pads 576 columns to 640)."""
    s = _sizes(config)
    return (s["kv_lora"] + s["rope"]) * BYTES[config.get("dtype", "bfloat16")]


def index_key_bytes(config: dict) -> int:
    return _sizes(config)["Di"] * BYTES[config.get("dtype", "bfloat16")]


def kv_bytes_per_token(config: dict) -> int:
    """What a context token keeps in every layer: its latent row (key and
    value at once) and its index key — 1,408 B a layer."""
    return _sizes(config)["n"] * (latent_row_bytes(config) + index_key_bytes(config))


def selected_tokens(config: dict, ctx) -> float | None:
    """Context tokens a row attended to in a layer of a step, over the
    window: the program's two counters (``index_topk`` while every context is
    over it). None without them."""
    steps = ctx.delta("finchat_dsa_row_layer_steps_total") if ctx is not None else 0.0
    return ctx.delta("finchat_dsa_selected_tokens_total") / steps if steps > 0 else None


def experts_touched(config: dict, ctx) -> float | None:
    """Held experts a routed layer a step touched, over the window: the
    program's two counters (the second counts the layers that route). None
    where there is no context or the counters did not move."""
    steps = ctx.delta("finchat_moe_layer_steps_total") if ctx is not None else 0.0
    return ctx.delta("finchat_moe_experts_touched_total") / steps if steps > 0 else None


def mla_attention_cost(config: dict, *, rows: float, selected: float) -> tuple[float, float]:
    """``(bytes, flops)`` the least a correct one-token attention call of ONE
    layer must do for ``rows`` rows of ``selected`` tokens each: every selected
    latent row read once, each query head's dot over the row's 576 columns and
    its weighted sum over the first 512."""
    s = _sizes(config)
    width = s["kv_lora"] + s["rope"]
    return (rows * selected * latent_row_bytes(config),
            rows * selected * s["H"] * 2 * (width + s["kv_lora"]))


def mla_attention_bound_s(config: dict, *, rows: float, selected: float) -> float:
    """The roofline of that call on a v5e: the larger of its stream time and
    its MXU time (242 FLOP a byte against the chip's ridge of 240: both are
    kept)."""
    nbytes, flops = mla_attention_cost(config, rows=rows, selected=selected)
    return max(nbytes / PEAK_BYTES, flops / PEAK_FLOPS)


def index_stream_bytes(config: dict, *, kv_tokens: float) -> float:
    """One layer's indexer pass of a decode step: the index keys of the
    ``kv_tokens`` context tokens on distinct physical pages."""
    return kv_tokens * index_key_bytes(config)


def attention_stream_bytes(config: dict, *, kv_tokens: float) -> float:
    """What a context token costs ONE layer's decode attention to keep
    available: its latent row and its index key. (The accepted reader of
    ``attn_kv_roofline.sat`` divides this by a custom call the latent decode
    does not make; the new cell is not on that metric's list. What a CALL
    must read is ``mla_attention_cost`` — the selected rows, never the
    context.)"""
    return kv_tokens * (latent_row_bytes(config) + index_key_bytes(config))


def moe_step_stream_bytes(config: dict, *, rows: float, experts_touched: float) -> float:
    """Bytes the operations under scope ``moe_experts`` in ONE iteration of
    the program's layer scan (one routed layer) must move at least: the
    weights of the ``experts_touched`` held experts that the step's rows
    picked and each row's input and output."""
    return (experts_touched * param_counts(config)["expert"]
            + rows * 2 * int(config["hidden_size"])) * BYTES[config.get("dtype", "bfloat16")]


def decode_step_stream_bytes(config: dict, *, live_kv_tokens: float, ctx=None) -> float:
    """Bytes one decode step must move at least: everything outside the
    routed experts once, in every routed layer the held experts the step
    TOUCHED (the program's counter through ``ctx``; all held ones without
    it), the head once, and in every layer the index keys of the live context
    (``live_kv_tokens`` = tokens on distinct physical pages) and each row's
    SELECTED latent rows (the program's counter; ``index_topk`` without it) —
    never the whole context's."""
    p, s = param_counts(config), _sizes(config)
    two = BYTES[config.get("dtype", "bfloat16")]
    n_routed = s["n"] - s["n_dense"]
    outside = p["layers"] - n_routed * p["routed"]
    touched = n_routed * (experts_touched(config, ctx) or s["held"]) * p["expert"]
    picked = _rows_of(config, ctx) * (selected_tokens(config, ctx) or s["topk"])
    return ((outside + touched + (p["head"] or p["embed"])) * two
            + s["n"] * (live_kv_tokens * index_key_bytes(config)
                        + picked * latent_row_bytes(config)))
