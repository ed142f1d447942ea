"""``model_type`` "mimo_v2_flash": Xiaomi's MiMo-V2-Flash (309B-A15B).
``hybrid_layer_pattern`` names each layer full (0) or sliding (1) — five
sliding to one full — and attention's shape is the KIND's: full layers keep
``num_key_value_heads`` K/V heads rotated at ``rope_theta``, sliding layers
``swa_num_key_value_heads`` at ``swa_rope_theta`` over a window of
``sliding_window`` tokens with a learned SINK in the softmax. In both a head's
keys are ``head_dim`` wide over values of ``v_head_dim``, the first
``int(head_dim * partial_rotary_factor)`` dims of a head are rotated, and v is
scaled by ``attention_value_scale``. ``moe_layer_freq`` 0 is a dense SwiGLU
layer, 1 a layer of ``n_routed_experts`` experts at ``num_experts_per_tok`` a
token with no shared expert. The file's ``n_routed_experts`` is what THIS chip
holds of the ``reduced.n_routed_experts.from`` experts the router scores: the
held range is ``[0, n_routed_experts)`` and a pick on an absent expert adds
nothing (its chip adds it; nothing stands in for it).

The plain reference is float32 at ``highest``, the equations as the issue and
the configuration's ``assumed`` write them, one layer, one block of queries and
one expert at a time — no kernel, no cache, no pages, no batching::

    h  = RMSNorm_in(x)
    q  = W_q h -> H heads of dk;  k = W_k h -> G x dk;  v = value_scale W_v h -> G x dv
    q, k: the FIRST r = int(dk x partial_rotary_factor) dims of a head rotated
          at the kind's base (dim i with i + r / 2), the others as they are
    s_tj = q_t . k_j / sqrt(dk), j <= t; a sliding layer: 0 <= t - j < W
    full:    p = softmax(s)
    sliding: m = max(max_j s_tj, b_i);  p_tj = exp(s_tj - m) / (sum_j exp(s_tj - m) + exp(b_i - m))
    x  = x + W_o concat_i(sum_j p_tj v_j)
    h' = RMSNorm_post(x)
    dense layer:   x = x + W_down (SiLU(W_gate h') * W_up h')
    routed layer:  s = sigmoid(W_r h');  picks = the k largest of s + c;
                   g_e = s_e / sum_picks s;  x = x + sum_{picked, held} g_e E_e(h')
    logits = W_head RMSNorm(x_L)

It reads the program's parameter tree (``dense_layers`` for the leading dense
layers, ``layers`` for the routed ones; ``attn_q`` / ``attn_o`` of both kinds in
one stack in the model's order, ``attn_k`` / ``attn_v`` the full layers' and
``swa_k`` / ``swa_v`` / ``swa_sink`` the sliding layers') and nothing else of
the program; one layer and one matmul weight are upcast at a time.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from perfbench.costs import BYTES
from perfbench.models.afmoe import _period, experts_touched  # noqa: F401  (the readers call it)
from perfbench.models.deepseek_v32 import _glu, _mlp  # SwiGLU, fused [gate | up] and apart
from perfbench.models.falcon_h1 import _matmul, _rows_of
from perfbench.reference import _f32, _rms_norm, _rope

F32 = jnp.float32
FULL, WINDOW = "full_attention", "sliding_attention"  # the program's words for 0 and 1
WIDTH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim",
              "v_head_dim", "swa_head_dim", "swa_v_head_dim", "num_attention_heads",
              "swa_num_attention_heads", "num_key_value_heads", "swa_num_key_value_heads",
              "sliding_window", "sliding_window_size", "partial_rotary_factor",
              "num_experts_per_tok", "n_group", "topk_group")
QUERY_BLOCK = 256  # queries whose scores [heads, block, context] are alive at once
HEAD_BLOCK = 32768  # columns of the head upcast at a time
# The unit of the routing margin, in standard deviations of a token's 256
# choice scores (``_route``): ``correct.py`` compares positions whose margin is
# at least 0.2, which then stands at 0.05 sigma — ``deepseek_v32.py``'s unit,
# the other file of 16 held experts of 256 at 8 a token: only a HELD expert
# that enters or leaves the picks changes what this chip adds
MARGIN_UNIT = 0.25


def _sizes(config: dict) -> dict:
    n = int(config["num_hidden_layers"])
    pattern, freq = list(config["hybrid_layer_pattern"]), list(config["moe_layer_freq"])
    if len(pattern) != n or len(freq) != n or set(pattern) - {0, 1} or set(freq) - {0, 1}:
        raise ValueError("hybrid_layer_pattern and moe_layer_freq name every layer 0 or 1")
    n_dense = freq.index(1) if 1 in freq else n
    if 0 in freq[n_dense:] or not 0 < n_dense < n:
        raise ValueError("moe_layer_freq: dense layers in front of routed ones")
    for key, other in (("swa_head_dim", "head_dim"), ("swa_v_head_dim", "v_head_dim"),
                       ("swa_num_attention_heads", "num_attention_heads"),
                       ("sliding_window_size", "sliding_window")):
        if config[key] != config[other]:
            raise ValueError(f"{key} is not {other}: the program's kinds differ in K/V heads, "
                             "rotation base and sink alone")
    cut = config.get("reduced", {}).get("n_routed_experts")
    held = int(config["n_routed_experts"])
    hd = int(config["head_dim"])
    return {"d": int(config["hidden_size"]), "H": int(config["num_attention_heads"]),
            "hd": hd, "dv": int(config["v_head_dim"]),
            "rot": int(hd * float(config["partial_rotary_factor"])) // 2 * 2,
            "value_scale": float(config["attention_value_scale"]),
            "W": int(config["sliding_window"]),
            "kinds": [WINDOW if p else FULL for p in pattern], "n": n, "n_dense": n_dense,
            FULL: {"Hkv": int(config["num_key_value_heads"]), "theta": float(config["rope_theta"]),
                   "sink": bool(config["add_full_attention_sink_bias"])},
            WINDOW: {"Hkv": int(config["swa_num_key_value_heads"]),
                     "theta": float(config["swa_rope_theta"]),
                     "sink": bool(config["add_swa_attention_sink_bias"])},
            "held": held, "router": int(cut["from"]) if cut else held,
            "top_k": int(config["num_experts_per_tok"]), "f": int(config["moe_intermediate_size"]),
            "fd": int(config["intermediate_size"]), "norm": bool(config["norm_topk_prob"]),
            "gate_scale": float(config["routed_scaling_factor"] or 1.0),
            "eps": float(config["layernorm_epsilon"])}


def program_config(config: dict):
    from finchat_tpu.models import llama
    from finchat_tpu.models.llama import LlamaConfig

    s = _sizes(config)
    if "attn_kinds" not in LlamaConfig.__dataclass_fields__:
        raise KeyError("this checkout's block (finchat_tpu/models/llama.py) has no attention "
                       "shape by kind of layer, no keys wider than values over K/V heads, no "
                       "partial rotation and no sink in a softmax: it cannot run model_type "
                       "'mimo_v2_flash'")
    for key, want in (("attention_bias", False), ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("hidden_act", "silu"), ("n_group", 1),
                      ("topk_group", 1), ("n_shared_experts", None),
                      ("add_full_attention_sink_bias", False)):
        if config.get(key, want) != want:
            raise ValueError(f"{key}: this adapter builds {want!r}")
    shape = {kind: llama.AttnKind(s[kind]["Hkv"], s[kind]["theta"], sink=s[kind]["sink"])
             for kind in (FULL, WINDOW)}
    assumed = {k: v for k, v in config.items() if k in ("expert_bias_init_std", "sink_init_std")}
    return LlamaConfig(
        vocab_size=int(config["vocab_size"]), dim=s["d"], n_layers=s["n"], n_heads=s["H"],
        n_kv_heads=s[FULL]["Hkv"], head_dim=s["hd"], v_head_dim=s["dv"], hidden_dim=s["f"],
        rope_theta=s[FULL]["theta"], rope_dim=s["rot"], value_scale=s["value_scale"],
        norm_eps=s["eps"], max_seq_len=int(config["engine"].get("max_seq_len", 8192)),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        n_experts=s["held"], top_k_experts=s["top_k"], moe_router_width=s["router"],
        moe_fused_glu=True, moe_score="sigmoid", moe_select_bias=True,
        moe_gate_scale=s["gate_scale"], moe_norm_picks=s["norm"],
        moe_bias_init_std=float(assumed.get("expert_bias_init_std", 0.02)),
        sink_init_std=float(assumed.get("sink_init_std", 1.0)),
        leading_dense_layers=s["n_dense"], dense_hidden_dim=s["fd"],
        leading_kinds=tuple(s["kinds"][:s["n_dense"]]),
        layer_pattern=_period(s["kinds"][s["n_dense"]:]), window=s["W"],
        attn_kinds=((FULL, shape[FULL]), (WINDOW, shape[WINDOW])),
    )


# --- the plain reference ----------------------------------------------------

@partial(jax.jit, static_argnames=("window",))
def _attend(q, k, v, sink, first, *, window):
    """A block of queries ``[Q, G, n, dk]`` (``n`` query heads a K/V head)
    whose first stands at position ``first``, against the whole sequence's
    ``k`` ``[S, G, dk]`` and ``v`` ``[S, G, dv]``. ``window`` 0: causal alone.
    ``sink`` ``[G, n]`` or None: a logit a query head in the softmax's sum that
    takes probability and gives no value."""
    S = k.shape[0]
    t = first + jnp.arange(q.shape[0])[:, None]
    j = jnp.arange(S)[None, :]
    seen = j <= t
    if window:
        seen = seen & (t - j < window)
    scores = jnp.einsum("qhgd,khd->hgqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    if sink is not None:
        m = jnp.maximum(m, sink[:, :, None, None])
    p = jnp.exp(scores - m)
    total = jnp.sum(p, axis=-1, keepdims=True)
    if sink is not None:
        total = total + jnp.exp(sink[:, :, None, None] - m)
    return jnp.einsum("hgqk,khd->qhgd", p / total, v)


def _rotate(t, theta: float, width: int):
    """The first ``width`` dims of every head of ``t`` [S, heads, dk] rotated
    (dim i with i + width / 2: ``reference._rope``), the others as they are."""
    pos = jnp.arange(t.shape[0])
    return jnp.concatenate([_rope(t[..., :width], pos, theta), t[..., width:]], axis=-1)


def _attention(h, leaves: dict, s: dict, cast, kind: str, fault: str | None):
    """One layer's attention sub-block over its normed input ``h`` [S, d].
    ``leaves``: the layer's own ``q``, ``k``, ``v``, ``o`` and (a kind with
    one) ``sink``. ``fault``: one of ``FAULTS``."""
    S, H, hd, dv, a = h.shape[0], s["H"], s["hd"], s["dv"], s[kind]
    G = a["Hkv"]
    q = _matmul(h, cast(leaves["q"])).reshape(S, H, hd)
    k = _matmul(h, cast(leaves["k"])).reshape(S, G, hd)
    v = _matmul(h, cast(leaves["v"])).reshape(S, G, dv)
    theta = s[FULL]["theta"] if fault == "one_theta" else a["theta"]
    width = hd if fault == "rotate_all" else s["rot"]
    q, k = _rotate(q, theta, width), _rotate(k, theta, width)
    if fault != "no_value_scale":
        v = s["value_scale"] * v
    window = s["W"] if kind == WINDOW and fault != "window_off" else 0
    sink = (_f32(leaves["sink"]).reshape(G, H // G)
            if a["sink"] and fault != "sink_off" else None)
    q = q.reshape(S, G, H // G, hd)  # query head i reads KV head i // (H / G)
    o = jnp.concatenate([_attend(q[b:b + QUERY_BLOCK], k, v, sink, b, window=window)
                         for b in range(0, S, QUERY_BLOCK)]).reshape(S, H * dv)
    return _matmul(o, cast(leaves["o"]))


@partial(jax.jit, static_argnames=("top_k", "gate_scale", "norm", "held"))
def _route(h, router, bias, *, top_k, gate_scale, norm, held):
    """``(picks [T, k], gates [T, k], margin [T])``: scores ``sigmoid(W_r h)``
    over the router's whole width, the picks the ``top_k`` largest of score +
    bias (the bias chooses and does not weigh; no groups), the gates the picked
    scores over their sum, times ``gate_scale``. The margin is the smallest
    change of a choice score, in ``MARGIN_UNIT`` standard deviations of the
    token's choice scores, that makes a HELD expert ``[0, held)`` enter or
    leave the picks (as ``afmoe.py`` reckons it, over the held range alone: a
    flip among absent experts moves only the gates' common denominator)."""
    score = jax.nn.sigmoid(h @ _f32(router))
    choice = score + bias
    ranked = jnp.argsort(-choice, axis=-1)
    picks = ranked[:, :top_k]
    gates = jnp.take_along_axis(score, picks, axis=-1)
    if norm:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    last = jnp.take_along_axis(choice, ranked[:, top_k - 1:top_k], axis=-1)
    first_out = jnp.take_along_axis(choice, ranked[:, top_k:top_k + 1], axis=-1)
    is_held = (jnp.arange(choice.shape[-1]) < held)[None]
    to_flip = jnp.min(jnp.where(is_held, jnp.where(choice >= last, choice - first_out,
                                                   last - choice), jnp.inf), axis=-1)
    return picks, gates * gate_scale, to_flip / (MARGIN_UNIT * jnp.std(choice, axis=-1))


def _experts(h, lp, j, s: dict, cast, held: range | None = None):
    """``(what the routed layer adds here [T, d], margin [T])``: the held
    experts' terms (``held``: these experts of the router's width, out of the
    stacks' rows ``0 ..``; None: the file's held range)."""
    held = range(s["held"]) if held is None else held
    picks, gates, margin = _route(h, lp["router"][j], _f32(lp["router_bias"][j]),
                                  top_k=s["top_k"], gate_scale=s["gate_scale"], norm=s["norm"],
                                  held=s["held"])
    out = jnp.zeros_like(h)
    for row, e in enumerate(held):  # one expert upcast at a time
        g = jnp.sum(jnp.where(picks == e, gates, 0.0), axis=-1)  # 0 where not picked
        out = out + g[:, None] * _glu(h, cast(lp["moe_in"][j, row]), cast(lp["moe_out"][j, row]))
    return out, margin


def _forward(params, tokens, config: dict, *, cast=None, fault: str | None = None):
    """``(the residual stream [tokens, hidden] behind the last layer, each
    token's smallest routing margin over the routed layers)``; under
    ``default_matmul_precision("highest")``."""
    cast = cast or (lambda w: w)
    s = _sizes(config)
    eps = s["eps"]
    x = _f32(params["embed"][jnp.asarray(tokens, jnp.int32)])
    margins = jnp.full((x.shape[0],), jnp.inf, F32)
    for i, kind in enumerate(s["kinds"]):
        dense = i < s["n_dense"]
        lp = params["dense_layers"] if dense else params["layers"]
        # the layer's place in its stack (leading layers' and scanned ones' apart):
        # down the depth, among the attention layers (both kinds), among its kind
        before = s["kinds"][:i] if dense else s["kinds"][s["n_dense"]:i]
        j, jk = len(before), before.count(kind)
        own = "swa" if kind == WINDOW else "attn"
        leaves = {"q": lp["attn_q"][j], "o": lp["attn_o"][j], "k": lp[f"{own}_k"][jk],
                  "v": lp[f"{own}_v"][jk],
                  **({"sink": lp[f"{own}_sink"][jk]} if s[kind]["sink"] else {})}
        x = x + _attention(_rms_norm(x, _f32(lp["ln_attn"][j]), eps), leaves, s, cast, kind, fault)
        h = _rms_norm(x, _f32(lp["ln_mlp"][j]), eps)
        if dense:
            x = x + _mlp(h, cast(lp["mlp_gate"][j]), cast(lp["mlp_up"][j]), cast(lp["mlp_down"][j]))
        else:
            m, margin = _experts(h, lp, j, s, cast)
            x, margins = x + m, jnp.minimum(margins, margin)
    return x, margins


FAULTS = ("sink_off", "rotate_all", "one_theta", "no_value_scale", "window_off")


def reference_logits(params, tokens, config: dict, *, positions, cast=None,
                     fault: str | None = None, window_off: bool = False,
                     cross_own: bool = False):
    """Float32 logits ``[len(positions), vocab]`` of a full causal forward
    over ``tokens``, and each position's smallest routing margin over the
    routed layers (``_route``, in ``MARGIN_UNIT``s; ``correct.py`` leaves
    positions under 0.2 out). ``cast`` stands in for the upcast of each matmul
    weight; ``fault`` for one of ``FAULTS`` (a piece of the attention sub-block
    left out or put where it does not belong: the controls); ``window_off``
    (``perfbench/window_control.py``) is the fault of that name. That script
    passes ``cross_own`` too: this model has no cross layer, so it is the
    model unchanged."""
    del cross_own
    cast = cast or (lambda w: w)
    with jax.default_matmul_precision("highest"):
        x, margins = _forward(params, tokens, config, cast=cast,
                              fault="window_off" if window_off else fault)
        x = _rms_norm(x, _f32(params["norm"]), float(config["layernorm_epsilon"]))[
            jnp.asarray(positions)]
        head = params["embed"].T if config.get("tie_word_embeddings") else params["lm_head"]
        logits = jnp.concatenate(
            [_matmul(x, cast(head[:, c:c + HEAD_BLOCK])) for c in range(0, head.shape[1], HEAD_BLOCK)],
            axis=-1)
    return logits, margins[jnp.asarray(positions)]


def control_logits(params, tokens, config: dict, *, positions):
    """The reference with every matmul weight (attention's four projections,
    the dense MLP's, the held experts', the head) taken through int8, a scale
    an output channel: the step below the bfloat16 the configuration states.
    The router and its bias and the sinks (float32 in the program), the norms
    and activations stay float32."""
    def through_int8(w):
        w = jnp.asarray(w).astype(F32)
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale

    return reference_logits(params, tokens, config, positions=positions, cast=through_int8)


# --- the yardstick's counts ---------------------------------------------------

def param_counts(config: dict) -> dict:
    """Parameters by group, of what THIS chip holds (``n_routed_experts``
    experts a routed layer; the router at its whole width); ``layer`` is the
    MEAN layer."""
    s = _sizes(config)
    d, H = s["d"], s["H"]
    attention = {kind: d * H * s["hd"] + d * s[kind]["Hkv"] * (s["hd"] + s["dv"])
                 + H * s["dv"] * d + s[kind]["sink"] * H for kind in (FULL, WINDOW)}
    expert = 3 * d * s["f"]
    router = (d + 1) * s["router"]  # and the selection bias
    norms = 2 * d
    routed = s["held"] * expert
    layers = 0
    for i, kind in enumerate(s["kinds"]):
        layers += attention[kind] + norms + (3 * d * s["fd"] if i < s["n_dense"]
                                             else router + routed)
    embed = int(config["vocab_size"]) * d
    head = 0 if config.get("tie_word_embeddings") else embed
    n_routed = s["n"] - s["n_dense"]
    return {"attention_full": attention[FULL], "attention_window": attention[WINDOW],
            "expert": expert, "routed": routed, "router": router,
            "dense_mlp": 3 * d * s["fd"],
            "layer": layers // s["n"] if layers % s["n"] == 0 else layers / s["n"],
            "layers": layers, "outside_experts": layers - n_routed * routed,
            "embed": embed, "head": head, "total": layers + embed + head + d}


def attention_stream_bytes(config: dict, *, kv_tokens: float) -> float:
    """ONE full layer's pass over ``kv_tokens`` context tokens: their K and V
    as wide as each is (LOGICAL bytes: 4 heads x (192 + 128) x 2 B a token)."""
    s = _sizes(config)
    return (kv_tokens * s[FULL]["Hkv"] * (s["hd"] + s["dv"])
            * BYTES[config.get("dtype", "bfloat16")])


def kv_bytes_per_token_by_kind(config: dict) -> dict:
    """K and V of a token by the kind of pool that holds them: ``full`` grows
    with the context; ``window`` is all sliding layers' and is held for the
    last ``sliding_window`` tokens alone, whatever the context."""
    s, two = _sizes(config), BYTES[config.get("dtype", "bfloat16")]
    return {name: int(s["kinds"].count(kind) * s[kind]["Hkv"] * (s["hd"] + s["dv"]) * two)
            for name, kind in (("full", FULL), ("window", WINDOW))}


def kv_bytes_per_token(config: dict) -> int:
    """What a token of context costs for as long as the row lives: the full
    layers' K and V. The window pool's share is bounded a row
    (``window_bytes_per_row``)."""
    return kv_bytes_per_token_by_kind(config)["full"]


def window_bytes_per_row(config: dict, *, context: float | None = None) -> float:
    """The sliding layers' K and V that one decode token of a row at
    ``context`` tokens reads: the last ``sliding_window`` tokens in each."""
    s = _sizes(config)
    tokens = s["W"] if context is None else min(float(context), s["W"])
    return tokens * kv_bytes_per_token_by_kind(config)["window"]


def window_stream_bytes(config: dict, *, window_kv_tokens: float) -> float:
    """Bytes one step's sliding layers must read: ``window_kv_tokens`` (the sum
    over the step's rows of min(context, ``sliding_window``): the program's
    stat on a dispatch) times a token's K and V in every sliding layer."""
    return window_kv_tokens * kv_bytes_per_token_by_kind(config)["window"]


def routed_layers_a_period(config: dict) -> int:
    """Routed layers in one period of the program's layer scan (the leading
    layer routes nothing and stands in front of the scan)."""
    s = _sizes(config)
    return len(_period(s["kinds"][s["n_dense"]:]))


def moe_step_stream_bytes(config: dict, *, rows: float, experts_touched: float) -> float:
    """Bytes the operations under scope ``moe_experts`` in ONE iteration of
    the program's layer scan must move at least — a PERIOD's routed layers,
    each a distinct set of operations in the scan's body: in each the weights
    of the ``experts_touched`` held experts that the step's rows picked and
    each row's input and output."""
    one = (experts_touched * param_counts(config)["expert"]
           + rows * 2 * int(config["hidden_size"])) * BYTES[config.get("dtype", "bfloat16")]
    return routed_layers_a_period(config) * one


def decode_step_stream_bytes(config: dict, *, live_kv_tokens: float, ctx=None) -> float:
    """Bytes one decode step must move at least: everything outside the
    routed experts once, in every routed layer the held experts the step
    TOUCHED (the program's counter through ``ctx``; all held ones without it),
    the head once, the full layers' K and V of the live context
    (``live_kv_tokens`` = tokens on distinct physical pages) and each row's
    window in every sliding layer."""
    p, s = param_counts(config), _sizes(config)
    two = BYTES[config.get("dtype", "bfloat16")]
    n_routed = s["n"] - s["n_dense"]
    touched = n_routed * (experts_touched(config, ctx) or s["held"]) * p["expert"]
    return ((p["outside_experts"] + touched + (p["head"] or p["embed"])) * two
            + live_kv_tokens * kv_bytes_per_token(config)
            + _rows_of(config, ctx) * window_bytes_per_row(config))
