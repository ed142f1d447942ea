"""``model_type`` "phi4flash": Phi-4-mini-flash-reasoning, the SambaY
decoder-hybrid-decoder. ``layer_types`` names each layer: in the first half
``mamba1`` and ``sliding_attention`` alternate, then one ``mamba1`` and THE
``full_attention`` layer, then ``gmu`` and ``cross_attention`` alternate. Every
layer's second sub-block is a dense MLP with a fused ``[gate | up]`` input.

The plain reference here is float32 at ``highest``, the equations as they are
written, token by token and head by head — no kernels, no cache, no pages, no
batching, no padded heads::

    x0 = embed[token]
    x <- x + Mixer_l(LN1_l(x));  x <- x + W_down [SiLU(g) * u],  [g | u] = W_in LN2_l(x)
    logits = LN(x) . embed^T                                       tied

    mamba1     [x | z] = W_in h;  x = SiLU(conv4(x) + b);  [r | B | C] = W_x x
               dt = softplus(W_dt r + b_dt);  A = -exp(A_log)      [E, N]
               S_t = exp(dt_t A) * S_{t-1} + (dt_t x_t) (x) B_t;  y_t = S_t C_t + D x_t
               out = W_out (y_t * SiLU(z_t));  layer 16's y_t is the memory m_t
    gmu        out = W_out' (m_t * SiLU(W_in' h_t))
    attention  q = W_q h + b_q (40 heads of 64); k, v (20 of 64) — a cross
               layer has W_q and W_o alone and takes the k, v that the full layer
               computed. Pair p: q1 = q[2p], q2 = q[2p+1]; j = p // 2:
               k1 = k[2j], k2 = k[2j+1], V = [v[2j] | v[2j+1]]
               A_i = softmax(q_i k_i^T / 8 + mask);  o_p = (A_1 - lam A_2) V
               o_p = RMSNorm_128(o_p; w_sub) (1 - lam_init)
               lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init
               lam_init = 0.8 - 0.6 exp(-0.3 l);  W_o [o_0 .. o_19] + b_o
               mask: causal; a sliding layer also t - j < sliding_window

It reads the program's parameter tree (stacked by the kinds that have a leaf:
``attn_q`` / ``attn_o`` over the 16 attention layers, ``attn_k`` / ``attn_v``
over the 9 that own K and V, ``m1_*`` over the 9 mamba layers, ``gmu_*`` over
7, the MLP and norms over all; ``m1_A_log`` lies ``[N, E]``) and nothing else of
the program. One layer and one matmul weight are upcast at a time, the pairs of
heads one after another, and the head in blocks of the vocabulary. What the
published config does not say is in the configuration file under ``assumed``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from perfbench.costs import BYTES
from perfbench.models.falcon_h1 import _matmul, _rows_of
from perfbench.reference import _f32

F32 = jnp.float32
MAMBA1, WINDOW, FULL, GMU, CROSS = ("mamba1", "sliding_attention", "full_attention", "gmu",
                                    "cross_attention")
WIDTH_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads",
              "sliding_window", "mamba_d_state", "mamba_d_conv", "mamba_expand",
              "mamba_dt_rank")
HEAD_BLOCK = 32768  # columns of the head upcast at a time


def _sizes(config: dict) -> dict:
    d = int(config["hidden_size"])
    kinds = list(config["layer_types"])
    if len(kinds) != int(config["num_hidden_layers"]) or set(kinds) - {MAMBA1, WINDOW, FULL, GMU,
                                                                        CROSS}:
        raise ValueError("layer_types names every layer mamba1, sliding_attention, "
                         "full_attention, gmu or cross_attention")
    H, Hkv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    return {"d": d, "E": int(config["mamba_expand"]) * d, "N": int(config["mamba_d_state"]),
            "K": int(config["mamba_d_conv"]), "R": int(config["mamba_dt_rank"]),
            "I": int(config["intermediate_size"]), "H": H, "Hkv": Hkv,
            "hd": int(config.get("head_dim") or d // H),
            "W": int(config["sliding_window"]), "kinds": kinds,
            "n": {k: kinds.count(k) for k in (MAMBA1, WINDOW, FULL, GMU, CROSS)}}


def _plan(kinds: list[str]) -> tuple:
    """``layer_types`` as the program's segments: periods of two layers, a
    run of equal periods one segment."""
    if len(kinds) % 2:
        raise ValueError("layer_types pairs a mixer layer with an attention layer")
    plan: list[list] = []
    for i in range(0, len(kinds), 2):
        period = (kinds[i], kinds[i + 1])
        if plan and plan[-1][0] == period:
            plan[-1][1] += 1
        else:
            plan.append([period, 1])
    return tuple((p, r) for p, r in plan)


def program_config(config: dict):
    from finchat_tpu.models.llama import LlamaConfig

    s = _sizes(config)
    if "layer_plan" not in LlamaConfig.__dataclass_fields__:
        raise KeyError("this checkout's block (finchat_tpu/models/llama.py) has no layer_plan "
                       "(segments of mamba1, sliding_attention, gmu and cross_attention "
                       "layers): it cannot run model_type 'phi4flash'")
    if config.get("mlp_bias") or config.get("lm_head_bias"):
        raise ValueError("the program's block has no mlp_bias and no lm_head_bias")
    if s["hd"] * s["H"] != s["d"]:
        raise ValueError("head_dim is hidden_size / num_attention_heads in this model")
    if s["R"] != -(-s["d"] // 16):
        raise ValueError("mamba_dt_rank is ceil(hidden_size / 16) (Mamba-1's 'auto')")
    c = LlamaConfig(
        vocab_size=int(config["vocab_size"]), dim=s["d"], n_layers=len(s["kinds"]),
        # the kernel's heads (finchat_tpu/models/sambay.py): a query head padded
        # to a PAIR's width, a K/V head the pair's [k1 | k2] and [v1 | v2]
        n_heads=s["H"], n_kv_heads=s["Hkv"] // 2, head_dim=2 * s["hd"],
        attention_scale=float(s["hd"]) ** -0.5, hidden_dim=s["I"], rope_theta=None,
        norm_eps=float(config["layer_norm_eps"]),
        max_seq_len=int(config["engine"].get("max_seq_len", 8192)),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        layer_plan=_plan(s["kinds"]), window=s["W"], m1_inner=s["E"], m1_state=s["N"],
        m1_dt_rank=s["R"], m1_conv=s["K"],
    )
    stated = jnp.dtype(config.get("ssm_state_dtype", "float32"))
    if _kept_state_dtype(c) != stated:
        raise ValueError(f"ssm_state_dtype: the file states {stated.name}, and this checkout's "
                         f"engine keeps the Mamba-1 state in {_kept_state_dtype(c).name}")
    return c


def _kept_state_dtype(c):
    from finchat_tpu.engine.engine import create_state
    from finchat_tpu.utils.config import EngineConfig

    tiny = EngineConfig(max_seqs=1, num_pages=2, page_size=c.window, max_seq_len=c.window)
    return jax.eval_shape(lambda: create_state(c, tiny, 1)).ssm_state.dtype


# --- the plain reference ----------------------------------------------------

def _layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(jnp.var(x, axis=-1, keepdims=True) + eps)
            * _f32(weight) + _f32(bias))


@partial(jax.jit, static_argnames=("N", "R", "K", "state_dtype"))
def _mamba1(h, w_in, conv_w, conv_b, w_x, w_dt, dt_b, a_log, D, w_out, *, N, R, K, state_dtype):
    """``(out, y)``: the mixer's output and the recurrence's ``y`` before the
    gate. ``state_dtype``: what the state is rounded to after every token."""
    T = h.shape[0]
    x, z = jnp.split(h @ _f32(w_in), 2, axis=-1)
    E = x.shape[-1]
    padded = jnp.concatenate([jnp.zeros((K - 1, E), F32), x], axis=0)
    w = _f32(conv_w)
    x = jax.nn.silu(_f32(conv_b)[None, :] + sum(padded[k:k + T] * w[k][None, :] for k in range(K)))
    r, Bm, Cm = jnp.split(x @ _f32(w_x), [R, R + N], axis=-1)
    dt = jax.nn.softplus(r @ _f32(w_dt) + _f32(dt_b))
    A = -jnp.exp(_f32(a_log)).T  # the program keeps [N, E]
    kept = jnp.finfo(state_dtype)

    def token(S, t):
        x_t, dt_t, B_t, C_t = t
        S = jnp.exp(dt_t[:, None] * A) * S + (dt_t * x_t)[:, None] * B_t[None, :]
        S = jax.lax.reduce_precision(S, exponent_bits=kept.nexp, mantissa_bits=kept.nmant)
        return S, S @ C_t

    _S, y = jax.lax.scan(token, jnp.zeros((E, N), F32), (x, dt, Bm, Cm))
    y = y + _f32(D) * x
    return (y * jax.nn.silu(z)) @ _f32(w_out), y


@jax.jit
def _keys_values(h, wk, bk, wv, bv):
    return h @ _f32(wk) + _f32(bk), h @ _f32(wv) + _f32(bv)


@partial(jax.jit, static_argnames=("H", "Hkv", "window", "eps"))
def _diff_attention(h, k, v, wq, bq, wo, bo, lam4, subln, depth, *, H, Hkv, window, eps):
    """Differential attention of one layer over its normed input ``h`` and the
    keys and values ``k``, ``v`` ``[T, Hkv x hd]`` (its own, or the full
    layer's). ``window`` 0: causal alone."""
    T = h.shape[0]
    hd = k.shape[-1] // Hkv
    q = (h @ _f32(wq) + _f32(bq)).reshape(T, H, hd)
    k, v = k.reshape(T, Hkv, hd), v.reshape(T, Hkv, hd)
    pos = jnp.arange(T)
    seen = pos[:, None] >= pos[None, :]
    if window:
        seen = seen & (pos[:, None] - pos[None, :] < window)
    lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * depth)
    lq1, lk1, lq2, lk2 = _f32(lam4)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam_init

    def pair(p):
        j = p // 2

        def weights(q_i, k_i):
            scores = (q_i @ k_i.T) / jnp.sqrt(F32(hd))
            return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)

        a1 = weights(q[:, 2 * p], k[:, 2 * j])
        a2 = weights(q[:, 2 * p + 1], k[:, 2 * j + 1])
        o = (a1 - lam * a2) @ jnp.concatenate([v[:, 2 * j], v[:, 2 * j + 1]], axis=-1)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * _f32(subln)
        return o * (1.0 - lam_init)

    o = jax.lax.map(pair, jnp.arange(H // 2))  # [pairs, T, 2 hd]: one pair at a time
    return jnp.moveaxis(o, 0, 1).reshape(T, -1) @ _f32(wo) + _f32(bo)


@jax.jit
def _gmu(h, memory, w_in, w_out):
    return (memory * jax.nn.silu(h @ _f32(w_in))) @ _f32(w_out)


@jax.jit
def _mlp(h, w_in, w_down):
    g, u = jnp.split(h @ _f32(w_in), 2, axis=-1)
    return (jax.nn.silu(g) * u) @ _f32(w_down)


def _forward(params, tokens, config: dict, *, cast=None, state_dtype=jnp.float32,
             window_off: bool = False, cross_own: bool = False):
    """The residual stream ``[tokens, hidden]`` behind the last layer; under
    ``default_matmul_precision("highest")``. The faults ``window_control.py``
    makes on purpose: ``window_off`` — the sliding layers attend every token;
    ``cross_own`` — each cross layer takes keys and values of its OWN input
    (through the full layer's projections) where the model reads the full
    layer's."""
    cast = cast or (lambda w: w)
    s = _sizes(config)
    eps = float(config["layer_norm_eps"])
    lp = params["layers"]
    seen = {"m1": 0, "gmu": 0, "qo": 0, "kv": 0}
    memory = kv = full_kv = None
    x = _f32(params["embed"][jnp.asarray(tokens, jnp.int32)])
    for i, kind in enumerate(s["kinds"]):
        h = _layer_norm(x, lp["ln_attn"][i], lp["ln_attn_b"][i], eps)
        if kind == MAMBA1:
            j = seen["m1"]
            seen["m1"] += 1
            mixed, memory = _mamba1(
                h, cast(lp["m1_in"][j]), lp["m1_conv_w"][j], lp["m1_conv_b"][j],
                cast(lp["m1_x"][j]), cast(lp["m1_dt"][j]), lp["m1_dt_b"][j], lp["m1_A_log"][j],
                lp["m1_D"][j], cast(lp["m1_out"][j]), N=s["N"], R=s["R"], K=s["K"],
                state_dtype=state_dtype)
        elif kind == GMU:
            j = seen["gmu"]
            seen["gmu"] += 1
            mixed = _gmu(h, memory, cast(lp["gmu_in"][j]), cast(lp["gmu_out"][j]))
        else:
            a = seen["qo"]
            seen["qo"] += 1
            if kind != CROSS:
                j = seen["kv"]
                seen["kv"] += 1
                kv = _keys_values(h, cast(lp["attn_k"][j]), lp["attn_k_b"][j],
                                  cast(lp["attn_v"][j]), lp["attn_v_b"][j])
                if kind == FULL:
                    full_kv, full_j = kv, j
            elif cross_own:
                kv = _keys_values(h, cast(lp["attn_k"][full_j]), lp["attn_k_b"][full_j],
                                  cast(lp["attn_v"][full_j]), lp["attn_v_b"][full_j])
            else:
                kv = full_kv
            mixed = _diff_attention(
                h, *kv, cast(lp["attn_q"][a]), lp["attn_q_b"][a], cast(lp["attn_o"][a]),
                lp["attn_o_b"][a], lp["attn_lam"][a], lp["attn_subln"][a], F32(i),
                H=s["H"], Hkv=s["Hkv"],
                window=s["W"] if kind == WINDOW and not window_off else 0, eps=eps)
        x = x + mixed
        h = _layer_norm(x, lp["ln_mlp"][i], lp["ln_mlp_b"][i], eps)
        x = x + _mlp(h, cast(lp["mlp_in"][i]), cast(lp["mlp_down"][i]))
    return x


def reference_logits(params, tokens, config: dict, *, positions, cast=None,
                     state_dtype=jnp.float32, window_off: bool = False,
                     cross_own: bool = False):
    """Float32 logits ``[len(positions), vocab]`` of a full causal forward
    over ``tokens``. The margins are ``inf``: nothing routes. ``cast`` stands
    in for the upcast of each matmul weight, ``state_dtype`` for the precision
    the Mamba-1 state is kept in between tokens, ``window_off`` / ``cross_own``
    for a wrong mask and a wrong cache: the controls."""
    cast = cast or (lambda w: w)
    with jax.default_matmul_precision("highest"):
        x = _forward(params, tokens, config, cast=cast, state_dtype=state_dtype,
                     window_off=window_off, cross_own=cross_own)
        x = _layer_norm(x, params["norm"], params["norm_b"],
                        float(config["layer_norm_eps"]))[jnp.asarray(positions)]
        head = params["embed"].T  # tied
        logits = jnp.concatenate(
            [_matmul(x, cast(head[:, c:c + HEAD_BLOCK])) for c in range(0, head.shape[1], HEAD_BLOCK)],
            axis=-1)
    return logits, jnp.full((len(positions),), jnp.inf, F32)


def control_logits(params, tokens, config: dict, *, positions):
    """The reference with every matmul weight (the mixers', attention's, the
    MLPs', the head) taken through int8, a scale an output channel: the step
    below the bfloat16 the configuration states. Biases, norms, the conv, the
    recurrence's own parameters, activations and the state stay float32."""
    def through_int8(w):
        w = jnp.asarray(w).astype(F32)
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale

    return reference_logits(params, tokens, config, positions=positions, cast=through_int8)


def state_control_logits(params, tokens, config: dict, *, positions):
    """A second control: the reference with its Mamba-1 state rounded to
    bfloat16 after every token — the step below the float32 the configuration
    states for the state (``ssm_state_dtype``)."""
    return reference_logits(params, tokens, config, positions=positions,
                            state_dtype=jnp.bfloat16)


# --- the yardstick's counts ---------------------------------------------------

def param_counts(config: dict) -> dict:
    """Parameters by group; ``layer`` is the MEAN layer of the plan."""
    s = _sizes(config)
    d, E, N, R, K, I = s["d"], s["E"], s["N"], s["R"], s["K"], s["I"]
    hq, hkv = s["H"] * s["hd"], s["Hkv"] * s["hd"]
    mlp = d * 2 * I + I * d
    norms = 4 * d  # two LayerNorms, weight and bias
    # in, x_proj, dt_proj and its bias, A_log, the conv and its bias, D, out
    mixer = d * 2 * E + E * (R + 2 * N) + R * E + E + E * N + (K + 1) * E + E + E * d
    # W_q and W_o with biases, lambda's four vectors, the sub-norm
    cross = d * hq + hq + hq * d + d + 4 * s["hd"] + 2 * s["hd"]
    attention = cross + 2 * (d * hkv + hkv)  # and W_k, W_v with biases
    gmu = 2 * d * E
    per = {MAMBA1: mixer, WINDOW: attention, FULL: attention, GMU: gmu, CROSS: cross}
    layers = sum(per[k] + mlp + norms for k in s["kinds"])
    n = len(s["kinds"])
    embed = int(config["vocab_size"]) * d
    head = 0 if config.get("tie_word_embeddings") else embed
    return {"mlp": mlp, "mixer": mixer, "attention": attention, "cross": cross, "gmu": gmu,
            "mlp_all": n * mlp,
            "layer": layers // n if layers % n == 0 else layers / n,
            "layers": layers, "embed": embed, "head": head,
            "total": layers + embed + head + 2 * d}


def attention_stream_bytes(config: dict, *, kv_tokens: float) -> float:
    """ONE pass over the full-attention layer's cache (the full layer's own,
    or a cross layer's): K and V of every context token of the batch;
    ``kv_tokens`` = tokens on distinct physical pages (``live_kv.py``)."""
    s = _sizes(config)
    return kv_tokens * 2 * s["Hkv"] * s["hd"] * BYTES[config.get("dtype", "bfloat16")]


def kv_bytes_per_token_by_kind(config: dict) -> dict:
    """K and V of a token by the kind of pool that holds them: ``full`` grows
    with the context (ONE layer: the cross layers own nothing); ``window`` is
    all sliding layers' and is held for the last ``sliding_window`` tokens
    alone, whatever the context."""
    one, n = attention_stream_bytes(config, kv_tokens=1), _sizes(config)["n"]
    return {"full": int(n[FULL] * one), "window": int(n[WINDOW] * one)}


def kv_bytes_per_token(config: dict) -> int:
    """What a token of context costs for as long as the row lives: the full
    layer's K and V (5,120 B). The window pool's share is bounded a row
    (``window_bytes_per_row``)."""
    return kv_bytes_per_token_by_kind(config)["full"]


def window_bytes_per_row(config: dict, *, context: float | None = None) -> float:
    """The sliding layers' K and V that one decode token of a row at
    ``context`` tokens reads: the last ``sliding_window`` tokens in each."""
    s = _sizes(config)
    tokens = s["W"] if context is None else min(float(context), s["W"])
    return tokens * kv_bytes_per_token_by_kind(config)["window"]


def ssm_state_bytes_per_row(config: dict) -> int:
    """One row's Mamba-1 state in one layer, in ``ssm_state_dtype``."""
    s = _sizes(config)
    return s["E"] * s["N"] * BYTES[config.get("ssm_state_dtype", "float32")]


def conv_tail_bytes_per_row(config: dict) -> int:
    s = _sizes(config)
    return (s["K"] - 1) * s["E"] * BYTES[config.get("ssm_state_dtype", "float32")]


def yoco_passes(config: dict) -> int:
    """Layers that walk the full layer's cache in one step: itself and the
    cross layers."""
    n = _sizes(config)["n"]
    return n[FULL] + n[CROSS]


def yoco_stream_bytes(config: dict, *, kv_tokens: float) -> float:
    """Bytes one step's passes over the ONE full-attention cache must move:
    ``yoco_passes`` times the tokens on distinct physical pages."""
    return yoco_passes(config) * attention_stream_bytes(config, kv_tokens=kv_tokens)


def decode_step_stream_bytes(config: dict, *, live_kv_tokens: float, ctx=None) -> float:
    """Bytes one decode step must move at least: every layer's weights and the
    head (the tied embedding) once; the ONE full-attention cache once for each
    layer that reads it (``live_kv_tokens`` = tokens on distinct physical
    pages); each row's window in every sliding layer; each row's Mamba-1 state
    and conv tail read and written once a mamba layer."""
    p, s = param_counts(config), _sizes(config)
    rows = _rows_of(config, ctx)
    weights = (p["layers"] + (p["head"] or p["embed"])) * BYTES[config.get("dtype", "bfloat16")]
    state = s["n"][MAMBA1] * rows * 2 * (ssm_state_bytes_per_row(config)
                                         + conv_tail_bytes_per_row(config))
    return (weights + yoco_stream_bytes(config, kv_tokens=live_kv_tokens)
            + rows * window_bytes_per_row(config) + state)
