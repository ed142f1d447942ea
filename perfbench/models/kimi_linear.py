"""``model_type`` "kimi_linear": Moonshot's Kimi-Linear (48B-A3B).
``linear_attn_config`` names each layer (counted from 1) a ``kda_layers`` one
— Kimi Delta Attention: the gated delta rule with a decay a KEY CHANNEL — or a
``full_attn_layers`` one: latent attention (MLA) with no q latent, NOT rotated
(``mla_use_nope``), every context token attended. The first
``first_k_dense_replace`` layers end in a dense SwiGLU, every later one in
``num_experts`` routed experts at ``num_experts_per_token`` a token (sigmoid
scores, a selection bias, one group) beside ``num_shared_experts`` shared ones.

The file's ``num_experts`` is what THIS chip holds of the published
``reduced.num_experts.from`` experts the router scores: the held range is
``[0, num_experts)``; a pick on an absent expert adds nothing (its chip adds
it); the gates are normalised over ALL picks.

The plain reference is float32 at ``highest``: the linear layer's recurrence as
it is written, token by token; latent attention in the EXPANDED form (per-head
keys and values made from the latent: it shares no algebra with the program's
absorbed form); the experts a loop over the held ones — no chunks, no cache,
no kernel, no state carried in from anywhere (pre-norm, eps ``rms_norm_eps``)::

    KDA, h the normed input, state S in R^{dk x dv} a head:
    [q~ | k~ | v~] = SiLU(conv_K([W_q h | W_k h | W_v h]))         causal, depthwise, no bias
    q = (q~_h / |q~_h|) dk^-1/2,   k = k~_h / |k~_h|                L2 norm a head, eps 1e-6
    g = -exp(A_log,h) softplus(W_f2 (W_f1 h) + dt_bias)  in R^{H x dk};   alpha = exp(g)
    beta = sigmoid(w_b,h . h)
    S~ = Diag(alpha) S;   u = beta (v - S~^T k);   S = S~ + k u^T;   o = S^T q
    y = W_o [ RMSNorm_dv(o_h) * sigmoid((W_g2 (W_g1 h) + b_g)_h) ]_h

    latent: q = W_q h -> heads of [q_nope | q_pe];  [c | k_pe] = W_dkv h;  c_kv = RMSNorm(c)
    [k_nope_i | v_i] = W_ukv,i c_kv
    s_i[t, j] = (nope + pe)^-1/2 (q_nope_i[t] . k_nope_i[j] + q_pe_i[t] . k_pe[j])   nothing rotated
    o_i = softmax_{j <= t}(s_i) v_i;   y = W_o [o_1 .. o_H]

    dense layer: m = W_down (SiLU(W_gate h) * W_up h)
    routed layer: s = sigmoid(W_r h);  picks = the k largest of s + b;
                  g_e = routed_scaling_factor s_e / sum_picks s;
                  m = sum_{picked, held} g_e E_e(h) + Shared(h)

It reads the program's parameter tree (``dense_layers`` for the leading dense
layers, ``layers`` for the routed ones, each stacked by KIND: ``gdn_*`` over
the KDA layers, ``attn_*`` over the latent ones) and nothing else of the
program; one layer and one matmul weight are upcast at a time. What the
published keys do not say is listed in the configuration file under
``assumed``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from perfbench.costs import BYTES
from perfbench.models.afmoe import _period  # the shortest run of kinds that repeats
from perfbench.models.deepseek_v32 import _glu, _matmul, _mlp, experts_touched
from perfbench.models.falcon_h1 import _rows_of
from perfbench.models.granitemoehybrid import _kept_state_dtype  # what the engine allocates
from perfbench.reference import _f32, _rms_norm

F32 = jnp.float32
LINEAR, FULL = "linear_attention", "full_attention"
WIDTH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim",
              "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "num_attention_heads", "num_key_value_heads", "num_experts_per_token",
              "num_shared_experts", "num_expert_group", "topk_group")
# inside ``linear_attn_config`` (a nested group: the contract compares the key
# whole, so the file's ``reduced`` names it and a test holds these three equal
# in its ``from`` and ``to``)
LINEAR_WIDTH_KEYS = ("head_dim", "num_heads", "short_conv_kernel_size")
QUERY_BLOCK = 256  # queries whose scores [heads, block, context] are alive at once
HEAD_BLOCK = 32768  # columns of the head upcast at a time
L2_EPS = 1e-6
PEAK_BYTES = 819e9  # a v5e chip's HBM stream (perfbench/peaks.json)
# The unit of the routing margin, in standard deviations of a token's 256
# choice scores (``_route``): ``correct.py`` compares positions whose margin is
# at least 0.2, which then stands at 0.02 sigma. 32 of 256 experts are held, so
# about 2.2 held experts a sigma stand round the line between the eighth and
# the ninth choice, and twelve routed layers deep a position clears 0.02 sigma
# in all of them about one time in three (DeepSeek's 16 held, four layers deep,
# stand at 0.05 sigma for the same share). PERF.md section 4 has the readings
MARGIN_UNIT = 0.1


def _sizes(config: dict) -> dict:
    lin = config["linear_attn_config"]
    n = int(config["num_hidden_layers"])
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    if kda & full or kda | full != set(range(1, n + 1)):
        raise ValueError("linear_attn_config: kda_layers and full_attn_layers name each of "
                         "the num_hidden_layers layers (counted from 1) once")
    kinds = [LINEAR if i in kda else FULL for i in range(1, n + 1)]
    cut = (config.get("reduced") or {}).get("num_experts")
    held = int(config["num_experts"])
    f = int(config["moe_intermediate_size"])
    H_l, hd_l = int(lin["num_heads"]), int(lin["head_dim"])
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    return {"d": int(config["hidden_size"]), "n": n, "kinds": kinds,
            "n_dense": int(config["first_k_dense_replace"]),
            "n_linear": kinds.count(LINEAR), "n_full": kinds.count(FULL),
            # the KDA layer: keys and values of one width, the gate's rank that width
            "Hl": H_l, "dk": hd_l, "dv": hd_l, "K": int(lin["short_conv_kernel_size"]),
            "rank": hd_l, "conv": 3 * H_l * hd_l, "d_v": H_l * hd_l,
            # the latent layer
            "H": int(config["num_attention_heads"]), "kv_lora": int(config["kv_lora_rank"]),
            "nope": nope, "rope": rope, "v": int(config["v_head_dim"]),
            "scale": (nope + rope) ** -0.5,
            # the feed-forward
            "held": held, "router": int(cut["from"]) if cut else held,
            "top_k": int(config["num_experts_per_token"]), "f": f,
            "fs": f * int(config["num_shared_experts"]), "fd": int(config["intermediate_size"]),
            "gate_scale": float(config["routed_scaling_factor"]),
            "norm": bool(config.get("moe_renormalize", True)),
            "eps": float(config["rms_norm_eps"])}


def program_config(config: dict):
    from finchat_tpu.models.llama import LlamaConfig

    s = _sizes(config)
    if "gdn_gate_rank" not in LlamaConfig.__dataclass_fields__:
        raise KeyError("this checkout's block (finchat_tpu/models/llama.py) has no delta rule "
                       "with a decay a key channel and no latent attention as a kind of a "
                       "layer_pattern: it cannot run model_type 'kimi_linear'")
    for key, want in (("mla_use_nope", True), ("q_lora_rank", None), ("rope_scaling", None),
                      ("moe_router_activation_func", "sigmoid"), ("hidden_act", "silu"),
                      ("moe_layer_freq", 1), ("num_expert_group", 1), ("topk_group", 1),
                      ("num_nextn_predict_layers", 0)):
        if config.get(key, want) != want:
            raise ValueError(f"{key}: this adapter builds {want!r}")
    if not 0 < s["n_dense"] < s["n"]:
        raise ValueError("first_k_dense_replace: dense layers in front of routed ones")
    c = LlamaConfig(
        vocab_size=int(config["vocab_size"]), dim=s["d"], n_layers=s["n"], n_heads=s["H"],
        n_kv_heads=1, head_dim=s["nope"] + s["rope"], hidden_dim=s["f"],
        rope_theta=None,  # mla_use_nope: the published rope_theta rotates nothing
        norm_eps=s["eps"], max_seq_len=int(config["engine"].get("max_seq_len", 8192)),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        n_experts=s["held"], top_k_experts=s["top_k"], moe_router_width=s["router"],
        moe_shared_dim=s["fs"], moe_fused_glu=True, moe_score="sigmoid", moe_select_bias=True,
        moe_gate_scale=s["gate_scale"], moe_norm_picks=s["norm"],
        moe_bias_init_std=float(config.get("expert_bias_init_std", 0.02)),
        kv_lora_rank=s["kv_lora"], qk_nope_dim=s["nope"], qk_rope_dim=s["rope"],
        v_head_dim=s["v"], leading_dense_layers=s["n_dense"], dense_hidden_dim=s["fd"],
        leading_kinds=tuple(s["kinds"][:s["n_dense"]]),
        layer_pattern=_period(s["kinds"][s["n_dense"]:]),
        gdn_heads=s["Hl"], gdn_key_dim=s["dk"], gdn_value_dim=s["dv"], gdn_conv=s["K"],
        gdn_gate_rank=s["rank"],
    )
    stated = jnp.dtype(config.get("ssm_state_dtype", "float32"))
    if _kept_state_dtype(c) != stated:
        raise ValueError(
            f"ssm_state_dtype: the file states {stated.name}, and this checkout's engine keeps "
            f"the recurrent state in {_kept_state_dtype(c).name}")
    return c


# --- the plain reference ----------------------------------------------------

@partial(jax.jit, static_argnames=("H", "dk", "dv", "K", "rank", "eps", "state_dtype",
                                   "scalar_decay"))
def _kda(h, w_in, w_low, w_f2, w_g2, g_bias, w_out, conv_w, a_log, dt_bias, norm_w, *,
         H, dk, dv, K, rank, eps, state_dtype, scalar_decay=False):
    """One KDA layer over its normed input ``h`` [T, d], token by token.
    ``scalar_decay`` (a test's control): the decay a head's MEAN over its
    channels — the scalar rule the vector one must not be mistaken for."""
    T = h.shape[0]
    qkv = h @ _f32(w_in)
    f, gl, b = jnp.split(h @ _f32(w_low), [rank, 2 * rank], axis=-1)
    qkv = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[-1]), F32), qkv], axis=0)
    qkv = jax.nn.silu(sum(qkv[j:j + T] * _f32(conv_w)[j][None, :] for j in range(K)))
    q, k, v = jnp.split(qkv, [H * dk, 2 * H * dk], axis=-1)
    q, k, v = q.reshape(T, H, dk), k.reshape(T, H, dk), v.reshape(T, H, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) * dk ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    g = -jnp.exp(_f32(a_log))[None, :, None] * jax.nn.softplus(
        (f @ _f32(w_f2) + _f32(dt_bias)).reshape(T, H, dk))
    if scalar_decay:
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    alpha, beta = jnp.exp(g), jax.nn.sigmoid(b)
    gate = (gl @ _f32(w_g2) + _f32(g_bias)).reshape(T, H, dv)

    def token(S, t):
        q_t, k_t, v_t, alpha_t, beta_t = t
        S = alpha_t[:, :, None] * S  # Diag(alpha): a key channel is a row of S
        u = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * u[:, None, :]
        # the state as the serving system would hold it between two steps
        kept = jnp.finfo(state_dtype)
        S = jax.lax.reduce_precision(S, exponent_bits=kept.nexp, mantissa_bits=kept.nmant)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((H, dk, dv), F32), (q, k, v, alpha, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * _f32(norm_w)
    return (o * jax.nn.sigmoid(gate)).reshape(T, H * dv) @ _f32(w_out)


@partial(jax.jit, static_argnames=("scale",))
def _attend(q_nope, q_pe, k_nope, k_pe, v, first, *, scale):
    """A block of queries ``[Q, H, .]`` whose first stands at position
    ``first`` against the whole sequence, expanded form: ``[Q, H, v]``."""
    t = first + jnp.arange(q_nope.shape[0])[:, None]
    seen = jnp.arange(k_nope.shape[0])[None, :] <= t
    s = (jnp.einsum("qhn,khn->hqk", q_nope, k_nope)
         + jnp.einsum("qhr,kr->hqk", q_pe, k_pe)) * scale
    s = jnp.where(seen[None], s, -jnp.inf)
    return jnp.einsum("hqk,khv->qhv", jax.nn.softmax(s, axis=-1), v)


def _latent(h, lp, j, s: dict, cast):
    """One latent layer over its normed input ``h`` [S, d]: nothing rotated,
    every token ``j <= t`` attended."""
    S, H = h.shape[0], s["H"]
    q_nope = _matmul(h, cast(lp["attn_q_nope"][j])).reshape(S, H, s["nope"])
    q_pe = _matmul(h, cast(lp["attn_q_rope"][j])).reshape(S, H, s["rope"])
    kv = _matmul(h, cast(lp["attn_kv_a"][j]))
    c_kv = _rms_norm(kv[:, :s["kv_lora"]], _f32(lp["attn_kv_a_norm"][j]), s["eps"])
    k_pe = kv[:, s["kv_lora"]:]
    # the program keeps W_ukv's halves apart: a head's keys' [nope, R], its values' [R, v]
    k_nope = jnp.einsum("sr,hnr->shn", c_kv, cast(lp["attn_uk"][j]).astype(F32))
    v = jnp.einsum("sr,hrv->shv", c_kv, cast(lp["attn_uv"][j]).astype(F32))
    o = jnp.concatenate(
        [_attend(q_nope[a:a + QUERY_BLOCK], q_pe[a:a + QUERY_BLOCK], k_nope, k_pe, v, a,
                 scale=s["scale"]) for a in range(0, S, QUERY_BLOCK)])
    return _matmul(o.reshape(S, H * s["v"]), cast(lp["attn_o"][j]))


@partial(jax.jit, static_argnames=("top_k", "gate_scale", "norm", "held"))
def _route(h, router, bias, *, top_k, gate_scale, norm, held):
    """``(picks [T, k], gates [T, k], margin [T])``: scores ``sigmoid(W_r h)``,
    the picks the ``top_k`` largest of score + bias (the bias chooses and does
    not weigh), the gates the picked scores over their sum, times
    ``gate_scale``. The margin is the smallest change of a choice score, in
    ``MARGIN_UNIT`` standard deviations of the token's choice scores, that
    would change what the HELD experts ``[0, held)`` add: a held expert
    entering or leaving the picks. A flip among absent experts moves only the
    gates' common denominator, by a hair."""
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


def _experts(h, lp, j, s: dict, cast, shares: tuple[int, int] | None = None):
    """``(the routed layer's MLP output [T, d], margin [T])``. ``shares`` (a
    test's): the experts ``[lo, hi)`` of a tree that holds the WHOLE router's
    width, as the chip that holds them would add them (the shared expert with
    ``lo`` 0 alone): the shares sum to the uncut layer."""
    picks, gates, margin = _route(h, lp["router"][j], _f32(lp["router_bias"][j]),
                                  top_k=s["top_k"], gate_scale=s["gate_scale"], norm=s["norm"],
                                  held=s["held"])
    lo, hi = shares or (0, s["held"])
    out = jnp.zeros_like(h)
    if lo == 0:
        out = _glu(h, cast(lp["shared_in"][j]), cast(lp["shared_out"][j]))
    for e in range(lo, hi):  # one expert upcast at a time
        g = jnp.sum(jnp.where(picks == e, gates, 0.0), axis=-1)  # 0 where not picked
        out = out + g[:, None] * _glu(h, cast(lp["moe_in"][j, e]), cast(lp["moe_out"][j, e]))
    return out, margin


def _forward(params, tokens, config: dict, *, cast=None, state_dtype=jnp.float32,
             scalar_decay: bool = False):
    """``(the residual stream [tokens, hidden] behind the last layer, each
    token's smallest routing margin over the routed layers)``; under
    ``default_matmul_precision("highest")``."""
    cast = cast or (lambda w: w)
    s = _sizes(config)
    eps = s["eps"]
    x = _f32(params["embed"][jnp.asarray(tokens, jnp.int32)])
    margins = jnp.full((x.shape[0],), jnp.inf, F32)
    # a layer's place in its stack: the leading dense layers' and the routed
    # ones' are apart, each by kind
    seen = {(dense, kind): 0 for dense in (True, False) for kind in (LINEAR, FULL)}
    for i, kind in enumerate(s["kinds"]):
        dense = i < s["n_dense"]
        lp = params["dense_layers"] if dense else params["layers"]
        j = seen[dense, kind]
        seen[dense, kind] += 1
        h = _rms_norm(x, _f32(lp["ln_attn"][i if dense else i - s["n_dense"]]), eps)
        if kind == LINEAR:
            x = x + _kda(
                h, cast(lp["gdn_in"][j]), cast(lp["gdn_low"][j]), cast(lp["gdn_f2"][j]),
                cast(lp["gdn_g2"][j]), lp["gdn_g_bias"][j], cast(lp["gdn_out"][j]),
                lp["gdn_conv_w"][j], lp["gdn_A_log"][j], lp["gdn_dt_bias"][j],
                lp["gdn_norm"][j], H=s["Hl"], dk=s["dk"], dv=s["dv"], K=s["K"], rank=s["rank"],
                eps=eps, state_dtype=state_dtype, scalar_decay=scalar_decay)
        else:
            x = x + _latent(h, lp, j, s, cast)
        m = i if dense else i - s["n_dense"]
        h = _rms_norm(x, _f32(lp["ln_mlp"][m]), eps)
        if dense:
            x = x + _mlp(h, cast(lp["mlp_gate"][m]), cast(lp["mlp_up"][m]), cast(lp["mlp_down"][m]))
        else:
            routed, margin = _experts(h, lp, m, s, cast)
            x, margins = x + routed, jnp.minimum(margins, margin)
    return x, margins


def reference_logits(params, tokens, config: dict, *, positions, cast=None,
                     state_dtype=jnp.float32, scalar_decay: bool = False):
    """Float32 logits ``[len(positions), vocab]`` of a full causal forward
    over ``tokens``, and each position's smallest routing margin over the
    routed layers (``_route``, in ``MARGIN_UNIT``s; ``correct.py`` leaves
    positions under 0.2 out). ``cast`` stands in for the upcast of each matmul
    weight, ``state_dtype`` for the precision the recurrent state is kept in
    between tokens, ``scalar_decay`` for ``_kda``'s: the controls."""
    cast = cast or (lambda w: w)
    with jax.default_matmul_precision("highest"):
        x, margins = _forward(params, tokens, config, cast=cast, state_dtype=state_dtype,
                              scalar_decay=scalar_decay)
        x = _rms_norm(x, _f32(params["norm"]), float(config["rms_norm_eps"]))[jnp.asarray(positions)]
        head = params["embed"].T if config.get("tie_word_embeddings") else params["lm_head"]
        logits = jnp.concatenate(
            [_matmul(x, cast(head[:, c:c + HEAD_BLOCK])) for c in range(0, head.shape[1], HEAD_BLOCK)],
            axis=-1)
    return logits, margins[jnp.asarray(positions)]


def control_logits(params, tokens, config: dict, *, positions):
    """The reference with every matmul weight (the KDA layer's projections,
    the latent layer's, the dense MLP's, the routed and shared experts', the
    head) taken through int8, a scale an output channel: the step below the
    bfloat16 the configuration states. The router and its bias, the conv,
    ``A_log``, ``dt_bias``, the gate's bias, the norms, the activations and the
    state stay float32."""
    def through_int8(w):
        w = jnp.asarray(w).astype(F32)
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale

    return reference_logits(params, tokens, config, positions=positions, cast=through_int8)


def state_control_logits(params, tokens, config: dict, *, positions):
    """A second control: the reference with its recurrent state rounded to
    bfloat16 after every token — the step below the float32 the configuration
    states for the state (``ssm_state_dtype``). No benchmark run calls it."""
    return reference_logits(params, tokens, config, positions=positions,
                            state_dtype=jnp.bfloat16)


# --- the yardstick's counts ---------------------------------------------------

def param_counts(config: dict) -> dict:
    """Parameters by group, of what THIS chip holds (``num_experts`` routed
    experts a routed layer). ``layer`` is the MEAN layer."""
    s = _sizes(config)
    d, H = s["d"], s["H"]
    # [q | k | v] in, [W_f1 | W_g1 | w_b], W_f2, W_g2 and b_g, out, the conv,
    # A_log a head, dt_bias a key channel, the norm
    kda = (d * (s["conv"] + 2 * s["rank"] + s["Hl"]) + s["rank"] * 2 * s["d_v"] + s["d_v"]
           + s["d_v"] * d + s["K"] * s["conv"] + s["Hl"] + s["Hl"] * s["dk"] + s["dv"])
    # W_q, W_dkv and its norm, W_ukv, W_o
    latent = (d * H * (s["nope"] + s["rope"]) + d * (s["kv_lora"] + s["rope"]) + s["kv_lora"]
              + s["kv_lora"] * H * (s["nope"] + s["v"]) + H * s["v"] * d)
    expert, shared = 3 * d * s["f"], 3 * d * s["fs"]
    router = (d + 1) * s["router"]  # and the selection bias
    routed = s["held"] * expert
    outside = shared + router + 2 * d  # a routed layer outside its mixer and its experts
    mixers = {LINEAR: kda, FULL: latent}
    layers = sum(mixers[kind] + (3 * d * s["fd"] + 2 * d if i < s["n_dense"]
                                 else outside + routed)
                 for i, kind in enumerate(s["kinds"]))
    embed = int(config["vocab_size"]) * d
    head = 0 if config.get("tie_word_embeddings") else embed
    n_routed = s["n"] - s["n_dense"]
    return {"kda": kda, "latent_attention": latent, "expert": expert, "routed": routed,
            "shared": shared, "router": router,
            "dense_layer": mixers[s["kinds"][0]] + 3 * d * s["fd"] + 2 * d,
            "routed_kda_layer": kda + outside + routed,
            "routed_latent_layer": latent + outside + routed,
            "outside_experts": layers - n_routed * routed,
            "layer": layers // s["n"] if layers % s["n"] == 0 else layers / s["n"],
            "layers": layers, "embed": embed, "head": head, "total": layers + embed + head + d}


def latent_row_bytes(config: dict) -> int:
    """A token's latent row in one layer, ``[c_kv | k_pe]``: LOGICAL bytes (the
    page pads 576 columns to 640)."""
    s = _sizes(config)
    return (s["kv_lora"] + s["rope"]) * BYTES[config.get("dtype", "bfloat16")]


def kv_bytes_per_token(config: dict) -> int:
    """What a context token keeps for as long as its row lives: its latent row
    (key and value at once) in every LATENT layer; a KDA layer's memory does
    not grow with the context."""
    return _sizes(config)["n_full"] * latent_row_bytes(config)


def attention_stream_bytes(config: dict, *, kv_tokens: float) -> float:
    """ONE latent layer's pass over ``kv_tokens`` context tokens on distinct
    physical pages: their latent rows, once."""
    return kv_tokens * latent_row_bytes(config)


def ssm_state_bytes_per_row(config: dict) -> int:
    """One row's recurrent state in one KDA layer, in ``ssm_state_dtype``: a
    ``dk x dv`` matrix a head."""
    s = _sizes(config)
    return s["Hl"] * s["dk"] * s["dv"] * BYTES[config.get("ssm_state_dtype", "float32")]


def conv_tail_bytes_per_row(config: dict) -> int:
    s = _sizes(config)
    return (s["K"] - 1) * s["conv"] * BYTES[config.get("ssm_state_dtype", "float32")]


def scanned_kda_layers(config: dict) -> int:
    """KDA layers whose one-token update is a DISTINCT operation of the decode
    step: each leading dense one (outside the program's layer scan) and each
    of one period of the scan's body — what ``ssm_scan_trace.py`` sums (every
    distinct operation under the scope once, at its mean duration)."""
    s = _sizes(config)
    return (s["kinds"][:s["n_dense"]].count(LINEAR)
            + _period(s["kinds"][s["n_dense"]:]).count(LINEAR))


def ssm_step_stream_bytes(config: dict, *, rows: float) -> float:
    """Bytes the operations under scope ``gdn_scan`` that ``ssm_scan_trace.py``
    times must move at least: for each of ``scanned_kda_layers`` every row's
    state read and written once, and its k, q, alpha (a key channel each),
    v, beta in and o out (float32, a few KiB a row). Logical bytes."""
    s = _sizes(config)
    small = (3 * s["Hl"] * s["dk"] + 2 * s["d_v"] + s["Hl"]) * 4
    return scanned_kda_layers(config) * rows * (2 * ssm_state_bytes_per_row(config) + small)


def selected_tokens(config: dict, ctx) -> float | None:
    """Context tokens on DISTINCT physical pages a row of the capture's decode
    dispatches, the mean: what ``latent_trace.py`` multiplies by its mean
    ``rows`` again. Every token is attended (no indexer), and the walk reads a
    page that rows share once for all of them (``live_kv.py``'s count, as the
    other decode rooflines since PR 30). None without a capture's counts."""
    from perfbench import trace_reduce, xplane_scopes
    from perfbench.layer_metrics.readers.scope_trace import TRACE_DIR, _decode_kv_tokens

    path = trace_reduce.find_xplane(TRACE_DIR) if ctx is not None else None
    tokens = _decode_kv_tokens(path, {"decode"}) if path is not None else None
    if tokens is None:
        return None
    rows = [stats["rows"] for events in xplane_scopes.annotations(path).values()
            for _name, _start, _end, stats in events
            if "rows" in stats and stats.get("kind") == "decode"]
    return tokens[1] * len(rows) / sum(rows) if rows and sum(rows) else None


def mla_attention_bound_s(config: dict, *, rows: float, selected: float) -> float:
    """What the least a correct one-token pass over the latent pages takes on a
    v5e, a layer of the FILE's depth: ``latent_trace.py`` divides the time under
    ``mla_attention`` by the steps and by ``num_hidden_layers``, and only the
    ``n_full`` latent layers open the scope, so the bound is the MEAN layer's —
    the latent layers' stream time (``rows x selected`` = the distinct context
    tokens, a latent row each, at the chip's peak) over all the file's layers."""
    s = _sizes(config)
    return (s["n_full"] * attention_stream_bytes(config, kv_tokens=rows * selected)
            / PEAK_BYTES / s["n"])


def routed_layers_a_period(config: dict) -> int:
    """Routed layers in one period of the program's layer scan."""
    s = _sizes(config)
    return len(_period(s["kinds"][s["n_dense"]:]))


def moe_step_stream_bytes(config: dict, *, rows: float, experts_touched: float) -> float:
    """Bytes the operations under scope ``moe_experts`` in ONE iteration of the
    program's layer scan must move at least — a PERIOD's routed layers, each a
    distinct set of operations in the scan's body: in each the weights of the
    ``experts_touched`` held experts that the step's rows picked and each
    row's input and output."""
    one = (experts_touched * param_counts(config)["expert"]
           + rows * 2 * int(config["hidden_size"])) * BYTES[config.get("dtype", "bfloat16")]
    return routed_layers_a_period(config) * one


def decode_step_stream_bytes(config: dict, *, live_kv_tokens: float, ctx=None) -> float:
    """Bytes one decode step must move at least: everything outside the routed
    experts once, in every routed layer the held experts the step TOUCHED (the
    program's counter through ``ctx``; all held ones without it), the head
    once, the latent layers' rows of the live context (``live_kv_tokens`` =
    tokens on distinct physical pages), and in every KDA layer each row's
    state and conv tail read and written once."""
    p, s = param_counts(config), _sizes(config)
    two = BYTES[config.get("dtype", "bfloat16")]
    n_routed = s["n"] - s["n_dense"]
    touched = n_routed * (experts_touched(config, ctx) or s["held"]) * p["expert"]
    rows = _rows_of(config, ctx)
    state = s["n_linear"] * rows * 2 * (
        ssm_state_bytes_per_row(config) + conv_tail_bytes_per_row(config))
    return ((p["outside_experts"] + touched + (p["head"] or p["embed"])) * two
            + live_kv_tokens * kv_bytes_per_token(config) + state)
