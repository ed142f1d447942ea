"""``model_type`` "afmoe": Arcee's Trinity family (Trinity-Mini, 26B-A3B).
``layer_types`` names each layer ``sliding_attention`` or ``full_attention``
(three to one); the first ``num_dense_layers`` layers have a dense SwiGLU MLP,
every later one ``num_experts`` routed experts at ``num_experts_per_tok`` a
token beside ``num_shared_experts`` shared ones. All experts are held here.

The plain reference is float32 at ``highest``, the equations as the issue and
the configuration's ``assumed`` write them, one layer, one head group, one
block of queries and one expert at a time — no kernel, no cache, no pages, no
batching::

    x0 = E[token] * sqrt(hidden)                                      (mup_enabled)
    h  = RMSNorm_in(x)
    q  = W_q h -> H heads of 128;  k = W_k h -> Hkv x 128;  v = W_v h;  g = W_g h -> H x 128
    q  = RMSNorm_q(q), k = RMSNorm_k(k)      a HEAD at a time, one weight vector of 128 each
    sliding layer: q, k = RoPE(q, k) (all 128 dims, dim i with i + 64); t attends t - W + 1 .. t
    full layer:    no rotation;                                          t attends 0 .. t
    o  = softmax(q k^T / sqrt(128)) v;   a = W_o (o * sigmoid(g))
    x  = x + RMSNorm_post_attn(a)
    h  = RMSNorm_pre_mlp(x)
    dense layer:   m = W_down (SiLU(W_gate h) * W_up h)
    routed layer:  s = sigmoid(W_r h);  picks = the k largest of s + b;
                   g_e = route_scale * s_e / sum_picks s;  m = Shared(h) + sum_picks g_e E_e(h)
    x  = x + RMSNorm_post_mlp(m)
    logits = W_head RMSNorm(x_L)

It reads the program's parameter tree (``dense_layers`` for the leading dense
layers, ``layers`` for the routed ones; the attention leaves of both kinds of
layer in one stack, in the model's order) and nothing else of the program; one
layer and one matmul weight are upcast at a time.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from perfbench.costs import BYTES
from perfbench.models.deepseek_v32 import _glu, _mlp  # SwiGLU, fused [gate | up] and apart
from perfbench.models.falcon_h1 import _matmul, _rows_of
from perfbench.reference import _f32, _rms_norm, _rope

F32 = jnp.float32
WINDOW, FULL = "sliding_attention", "full_attention"
WIDTH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim",
              "num_attention_heads", "num_key_value_heads", "num_experts_per_tok",
              "num_shared_experts", "sliding_window", "n_group", "topk_group",
              "num_expert_groups", "num_limited_groups")
QUERY_BLOCK = 256  # queries whose scores [heads, block, context] are alive at once
HEAD_BLOCK = 32768  # columns of the head upcast at a time
# The unit of the routing margin, in standard deviations of a token's 128
# choice scores (``_route``): ``correct.py`` compares positions whose margin is
# at least 0.2, which then stands at 0.01 sigma. EVERY expert is held, so every
# flipped pick changes what the layer adds — the ninth choice for the eighth,
# the smallest of eight normalised gates — and the ninth stands only 0.06
# sigma behind the eighth on average, four routed layers deep. Measured on the
# chip at the cell's widths (PERF.md section 4, PR 47, call 1: 512 positions of
# 8 seeds): without a flip a position reads 0.0107-0.0135; a flipped pick
# 0.09-0.34; 116 positions read over 0.02, 102 of them under 0.01 sigma, 11 at
# 0.01-0.015, 3 at 0.015-0.02 and none of the 101 above. From 0.01 sigma up 20
# to 37 of a check's 64 positions are compared (from 0.02 up 9 to 17: too near
# ``correct.py``'s 4 for every seed of every later run), a twentieth of them
# flipped: the median does not see those, and the file's ``max`` has room for
# them (``logits_tolerance.set_from``). Those readings are of the selection
# bias's first draw (sigma 0.1); at the file's 0.02 call 5 read the same levels
# (20-29 compared a path, medians 0.0110-0.0131, a flip 0.09-0.31)
MARGIN_UNIT = 0.05


def _sizes(config: dict) -> dict:
    kinds = list(config["layer_types"])
    n = int(config["num_hidden_layers"])
    if len(kinds) != n or set(kinds) - {WINDOW, FULL}:
        raise ValueError("layer_types names every layer sliding_attention or full_attention")
    f = int(config["moe_intermediate_size"])
    return {"d": int(config["hidden_size"]), "H": int(config["num_attention_heads"]),
            "Hkv": int(config["num_key_value_heads"]), "hd": int(config["head_dim"]),
            "W": int(config["sliding_window"]), "kinds": kinds, "n": n,
            "n_dense": int(config["num_dense_layers"]), "E": int(config["num_experts"]),
            "top_k": int(config["num_experts_per_tok"]), "f": f,
            "fs": f * int(config["num_shared_experts"]), "fd": int(config["intermediate_size"]),
            "gate_scale": float(config["route_scale"]), "norm": bool(config["route_norm"]),
            "theta": float(config["rope_theta"]), "eps": float(config["rms_norm_eps"]),
            "x0": float(config["hidden_size"]) ** 0.5 if config.get("mup_enabled") else 1.0}


def _period(kinds: list[str]) -> tuple[str, ...]:
    """The shortest run of kinds that ``kinds`` repeats a whole number of times."""
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return tuple(kinds[:p])
    return tuple(kinds)


def program_config(config: dict):
    from finchat_tpu.models.llama import LlamaConfig

    s = _sizes(config)
    if "leading_kinds" not in LlamaConfig.__dataclass_fields__:
        raise KeyError("this checkout's block (finchat_tpu/models/llama.py) has no window layers "
                       "in a layer_pattern, no q/k norm a head, no gated attention output and "
                       "no norm on a sub-block's output beside its input: it cannot run "
                       "model_type 'afmoe'")
    for key, want in (("score_func", "sigmoid"), ("hidden_act", "silu"), ("rope_scaling", None),
                      ("n_group", 1), ("topk_group", 1), ("num_expert_groups", 1),
                      ("num_limited_groups", 1)):
        if config.get(key, want) != want:
            raise ValueError(f"{key}: this adapter builds {want!r}")
    if not 0 < s["n_dense"] < s["n"]:
        raise ValueError("num_dense_layers: dense layers in front of routed ones")
    return LlamaConfig(
        vocab_size=int(config["vocab_size"]), dim=s["d"], n_layers=s["n"], n_heads=s["H"],
        n_kv_heads=s["Hkv"], head_dim=s["hd"], hidden_dim=s["f"], rope_theta=s["theta"],
        rope_kinds=(WINDOW,), norm_eps=s["eps"],
        max_seq_len=int(config["engine"].get("max_seq_len", 8192)),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        embedding_multiplier=s["x0"], qk_head_norm=True, attn_gate=True, norm_both=True,
        n_experts=s["E"], top_k_experts=s["top_k"], moe_shared_dim=s["fs"], moe_fused_glu=True,
        moe_score="sigmoid", moe_select_bias=True, moe_gate_scale=s["gate_scale"],
        moe_bias_init_std=float(config.get("expert_bias_init_std", 0.02)),
        moe_norm_picks=s["norm"], leading_dense_layers=s["n_dense"], dense_hidden_dim=s["fd"],
        leading_kinds=tuple(s["kinds"][:s["n_dense"]]),
        layer_pattern=_period(s["kinds"][s["n_dense"]:]), window=s["W"],
    )


# --- the plain reference ----------------------------------------------------

@partial(jax.jit, static_argnames=("window",))
def _attend(q, k, v, first, *, window):
    """A block of queries ``[Q, Hkv, G, D]`` whose first stands at position
    ``first``, against the whole sequence's ``k``, ``v`` ``[S, Hkv, D]``.
    ``window`` 0: causal alone."""
    S = k.shape[0]
    t = first + jnp.arange(q.shape[0])[:, None]
    j = jnp.arange(S)[None, :]
    seen = j <= t
    if window:
        seen = seen & (t - j < window)
    scores = jnp.einsum("qhgd,khd->hgqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
    weights = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hgqk,khd->qhgd", weights, v)


def _attention(h, lp, j, s: dict, cast, kind: str, fault: str | None):
    """One layer's attention sub-block over its normed input ``h`` [S, d],
    before the output norm. ``fault``: one of the pieces left out or put where
    it does not belong, for the tests and ``window_control.py``."""
    S, H, Hkv, hd = h.shape[0], s["H"], s["Hkv"], s["hd"]
    q = _matmul(h, cast(lp["attn_q"][j])).reshape(S, H, hd)
    k = _matmul(h, cast(lp["attn_k"][j])).reshape(S, Hkv, hd)
    v = _matmul(h, cast(lp["attn_v"][j])).reshape(S, Hkv, hd)
    if fault != "no_head_norm":
        q = _rms_norm(q, _f32(lp["attn_q_norm"][j]), s["eps"])
        k = _rms_norm(k, _f32(lp["attn_k_norm"][j]), s["eps"])
    if kind == WINDOW or fault == "full_rotated":  # dim i with i + 64 (reference._rope)
        q, k = (_rope(t, jnp.arange(S), s["theta"]) for t in (q, k))
    window = s["W"] if kind == WINDOW and fault != "window_off" else 0
    q = q.reshape(S, Hkv, H // Hkv, hd)  # query head i reads KV head i // (H / Hkv)
    o = jnp.concatenate([_attend(q[a:a + QUERY_BLOCK], k, v, a, window=window)
                         for a in range(0, S, QUERY_BLOCK)]).reshape(S, H * hd)
    if fault != "no_gate":
        o = o * jax.nn.sigmoid(_matmul(h, cast(lp["attn_gate"][j])))
    return _matmul(o, cast(lp["attn_o"][j]))


@partial(jax.jit, static_argnames=("top_k", "gate_scale", "norm"))
def _route(h, router, bias, *, top_k, gate_scale, norm):
    """``(picks [T, k], gates [T, k], margin [T])``: scores ``sigmoid(W_r h)``,
    the picks the ``top_k`` largest of score + bias (the bias chooses and does
    not weigh), the gates the picked scores over their sum, times
    ``gate_scale``. The margin is the gap between the last chosen and the
    first rejected choice score, in ``MARGIN_UNIT`` standard deviations of the
    token's choice scores."""
    score = jax.nn.sigmoid(h @ _f32(router))
    choice = score + bias
    ranked = jnp.argsort(-choice, axis=-1)
    picks = ranked[:, :top_k]
    gates = jnp.take_along_axis(score, picks, axis=-1)
    if norm:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    last = jnp.take_along_axis(choice, ranked[:, top_k - 1:top_k], axis=-1)[:, 0]
    first_out = jnp.take_along_axis(choice, ranked[:, top_k:top_k + 1], axis=-1)[:, 0]
    margin = (last - first_out) / (MARGIN_UNIT * jnp.std(choice, axis=-1))
    return picks, gates * gate_scale, margin


def _experts(h, lp, j, s: dict, cast):
    """``(the routed layer's MLP output [T, d] before its norm, margin [T])``."""
    picks, gates, margin = _route(h, lp["router"][j], _f32(lp["router_bias"][j]),
                                  top_k=s["top_k"], gate_scale=s["gate_scale"], norm=s["norm"])
    out = _glu(h, cast(lp["shared_in"][j]), cast(lp["shared_out"][j]))
    for e in range(s["E"]):  # one expert upcast at a time
        g = jnp.sum(jnp.where(picks == e, gates, 0.0), axis=-1)  # 0 where not picked
        out = out + g[:, None] * _glu(h, cast(lp["moe_in"][j, e]), cast(lp["moe_out"][j, e]))
    return out, margin


def _forward(params, tokens, config: dict, *, cast=None, fault: str | None = None):
    """``(the residual stream [tokens, hidden] behind the last layer, each
    token's smallest routing margin over the routed layers)``; under
    ``default_matmul_precision("highest")``."""
    cast = cast or (lambda w: w)
    s = _sizes(config)
    eps = s["eps"]
    x = _f32(params["embed"][jnp.asarray(tokens, jnp.int32)]) * s["x0"]
    margins = jnp.full((x.shape[0],), jnp.inf, F32)
    for i, kind in enumerate(s["kinds"]):
        dense = i < s["n_dense"]
        lp, j = (params["dense_layers"], i) if dense else (params["layers"], i - s["n_dense"])
        a = _attention(_rms_norm(x, _f32(lp["ln_attn"][j]), eps), lp, j, s, cast, kind, fault)
        x = x + (a if fault == "no_out_norm" else _rms_norm(a, _f32(lp["ln_attn_out"][j]), eps))
        h = _rms_norm(x, _f32(lp["ln_mlp"][j]), eps)
        if dense:
            m = _mlp(h, cast(lp["mlp_gate"][j]), cast(lp["mlp_up"][j]), cast(lp["mlp_down"][j]))
        else:
            m, margin = _experts(h, lp, j, s, cast)
            margins = jnp.minimum(margins, margin)
        x = x + (m if fault == "no_out_norm" else _rms_norm(m, _f32(lp["ln_mlp_out"][j]), eps))
    return x, margins


FAULTS = ("full_rotated", "no_head_norm", "no_gate", "no_out_norm")


def reference_logits(params, tokens, config: dict, *, positions, cast=None,
                     fault: str | None = None, window_off: bool = False,
                     cross_own: bool = False):
    """Float32 logits ``[len(positions), vocab]`` of a full causal forward
    over ``tokens``, and each position's smallest routing margin over the
    routed layers (``_route``, in ``MARGIN_UNIT``s; ``correct.py`` leaves
    positions under 0.2 out). ``cast`` stands in for the upcast of each matmul
    weight; ``fault`` for one of ``FAULTS`` (a piece of the attention
    sub-block or the output norms wrong on purpose: the tests' controls);
    ``window_off`` (``perfbench/window_control.py``): the sliding layers attend
    every token. That script passes ``cross_own`` too: this model has no cross
    layer, so it is the model unchanged."""
    del cross_own
    cast = cast or (lambda w: w)
    with jax.default_matmul_precision("highest"):
        x, margins = _forward(params, tokens, config, cast=cast,
                              fault="window_off" if window_off else fault)
        x = _rms_norm(x, _f32(params["norm"]), float(config["rms_norm_eps"]))[jnp.asarray(positions)]
        head = params["embed"].T if config.get("tie_word_embeddings") else params["lm_head"]
        logits = jnp.concatenate(
            [_matmul(x, cast(head[:, c:c + HEAD_BLOCK])) for c in range(0, head.shape[1], HEAD_BLOCK)],
            axis=-1)
    return logits, margins[jnp.asarray(positions)]


def control_logits(params, tokens, config: dict, *, positions):
    """The reference with every matmul weight (attention's four projections
    and its gate, the dense MLP's, the routed and shared experts', the head)
    taken through int8, a scale an output channel: the step below the bfloat16
    the configuration states. The router and its bias (float32 in the
    program), the norms and activations stay float32."""
    def through_int8(w):
        w = jnp.asarray(w).astype(F32)
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale

    return reference_logits(params, tokens, config, positions=positions, cast=through_int8)


# --- the yardstick's counts ---------------------------------------------------

def param_counts(config: dict) -> dict:
    """Parameters by group; ``layer`` is the MEAN layer."""
    s = _sizes(config)
    d, hq, hkv = s["d"], s["H"] * s["hd"], s["Hkv"] * s["hd"]
    attention = 3 * d * hq + 2 * d * hkv + 2 * s["hd"]  # q, o, the gate; k, v; the two head norms
    expert, shared = 3 * d * s["f"], 3 * d * s["fs"]
    router = (d + 1) * s["E"]  # and the selection bias
    norms = 4 * d  # on both sub-blocks' inputs and outputs
    dense_layer = attention + 3 * d * s["fd"] + norms
    outside = attention + shared + router + norms  # a routed layer outside its experts
    routed = s["E"] * expert
    n_routed = s["n"] - s["n_dense"]
    layers = s["n_dense"] * dense_layer + n_routed * (outside + routed)
    embed = int(config["vocab_size"]) * d
    head = 0 if config.get("tie_word_embeddings") else embed
    return {"attention": attention, "expert": expert, "routed": routed, "shared": shared,
            "router": router, "dense_layer": dense_layer,
            "routed_layer_outside_experts": outside, "routed_layer": outside + routed,
            "layer": layers // s["n"] if layers % s["n"] == 0 else layers / s["n"],
            "layers": layers, "embed": embed, "head": head, "total": layers + embed + head + d}


def attention_stream_bytes(config: dict, *, kv_tokens: float) -> float:
    """ONE layer's pass over ``kv_tokens`` context tokens: their K and V."""
    s = _sizes(config)
    return kv_tokens * 2 * s["Hkv"] * s["hd"] * BYTES[config.get("dtype", "bfloat16")]


def kv_bytes_per_token_by_kind(config: dict) -> dict:
    """K and V of a token by the kind of pool that holds them: ``full`` grows
    with the context; ``window`` is all sliding layers' and is held for the
    last ``sliding_window`` tokens alone, whatever the context."""
    one, kinds = attention_stream_bytes(config, kv_tokens=1), _sizes(config)["kinds"]
    return {"full": int(kinds.count(FULL) * one), "window": int(kinds.count(WINDOW) * one)}


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


def experts_touched(config: dict, ctx) -> float | None:
    """Experts a routed layer a step touched, over the window: the program's
    two counters (the second counts the layers that route). None where there
    is no context or the counters did not move."""
    steps = ctx.delta("finchat_moe_layer_steps_total") if ctx is not None else 0.0
    return ctx.delta("finchat_moe_experts_touched_total") / steps if steps > 0 else None


def routed_layers_a_period(config: dict) -> int:
    """Routed layers in one period of the program's layer scan."""
    s = _sizes(config)
    return len(_period(s["kinds"][s["n_dense"]:]))


def moe_step_stream_bytes(config: dict, *, rows: float, experts_touched: float) -> float:
    """Bytes the operations under scope ``moe_experts`` in ONE iteration of
    the program's layer scan must move at least — a PERIOD's routed layers,
    each a distinct set of operations in the scan's body: in each the weights
    of the ``experts_touched`` experts that the step's rows picked and each
    row's input and output."""
    one = (experts_touched * param_counts(config)["expert"]
           + rows * 2 * int(config["hidden_size"])) * BYTES[config.get("dtype", "bfloat16")]
    return routed_layers_a_period(config) * one


def decode_step_stream_bytes(config: dict, *, live_kv_tokens: float, ctx=None) -> float:
    """Bytes one decode step must move at least: everything outside the
    routed experts once, in every routed layer the experts the step TOUCHED
    (the program's counter through ``ctx``; all of them without it), the head
    once, the full layers' K and V of the live context (``live_kv_tokens`` =
    tokens on distinct physical pages) and each row's window in every sliding
    layer."""
    p, s = param_counts(config), _sizes(config)
    two = BYTES[config.get("dtype", "bfloat16")]
    n_routed = s["n"] - s["n_dense"]
    outside = p["layers"] - n_routed * p["routed"]
    touched = n_routed * (experts_touched(config, ctx) or s["E"]) * p["expert"]
    return ((outside + touched + (p["head"] or p["embed"])) * two
            + live_kv_tokens * kv_bytes_per_token(config)
            + _rows_of(config, ctx) * window_bytes_per_row(config))
