"""``model_type`` "falcon_h1": in every layer a Mamba-2 mixer beside GQA
attention, both on one normed input, their outputs summed into the residual;
then a SwiGLU MLP; fourteen scalar µP multipliers.

The plain reference here follows the published block (the configuration
file's ``source``) in float32 at ``highest``: full causal attention over the
whole sequence, and the mixer's recurrence as it is written, token by token —
no chunks, no cache, no state carried in from anywhere::

    u   = (h * ssm_in_multiplier) W_in            [z | xs | B | C | dt] * ssm_multipliers
    xBC = SiLU(conv_K([xs | B | C]) + b)          causal, depthwise
    dt  = softplus(dt + dt_bias),  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t xs_t (x) B_t,   y_t = S_t C_t + D xs_t
    m   = (RMSNorm_groups(y * SiLU(z)) W_out) * ssm_out_multiplier

It reads the program's parameter tree (layers stacked on a leading axis) and
nothing else of the program. One layer and one matmul weight are upcast at a
time, and the head is computed in blocks of the vocabulary (261,120 x 5,120
in float32 would be 5.35 GB), so that it fits beside the serving engine.

What the published config does not say, and the configuration file lists
under ``assumed``: the five ``ssm_multipliers`` scale z, xs, B, C, dt in that
order; the gated norm's groups are ``mamba_n_groups`` equal parts of
``mamba_d_ssm``; ``key_multiplier`` scales k before the rotation.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from perfbench.costs import BYTES
from perfbench.reference import _f32, _rms_norm, _rope

F32 = jnp.float32
WIDTH_KEYS = ("hidden_size", "intermediate_size", "head_dim", "mamba_d_ssm", "mamba_d_state",
              "mamba_d_head", "mamba_d_conv", "mamba_n_groups", "mamba_chunk_size",
              "mamba_expand", "mlp_expansion_factor")
HEAD_BLOCK = 32768  # columns of the head upcast at a time


def _sizes(config: dict) -> dict:
    H, P = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    if H * P != int(config["mamba_d_ssm"]):
        raise ValueError("mamba_d_ssm is not mamba_n_heads x mamba_d_head")
    G, N = int(config["mamba_n_groups"]), int(config["mamba_d_state"])
    return {"H": H, "P": P, "G": G, "N": N, "K": int(config["mamba_d_conv"]),
            "d_ssm": H * P, "conv": H * P + 2 * G * N, "in": 2 * H * P + 2 * G * N + H}


def program_config(config: dict):
    from finchat_tpu.models.llama import LlamaConfig

    s = _sizes(config)
    if not (config.get("mamba_rms_norm", True) and not config.get("mamba_norm_before_gate")):
        raise ValueError("the program's mixer gates, then norms (mamba_rms_norm true, "
                         "mamba_norm_before_gate false)")
    for key in ("attention_bias", "mamba_proj_bias", "mlp_bias", "projectors_bias"):
        if config.get(key):
            raise ValueError(f"the program's block has no {key}")
    if "ssm_heads" not in LlamaConfig.__dataclass_fields__:
        raise KeyError("this checkout's block (finchat_tpu/models/llama.py) has no mixer and "
                       "no head_dim field: it cannot run model_type 'falcon_h1'")
    return LlamaConfig(
        vocab_size=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]), n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]), head_dim=int(config["head_dim"]),
        hidden_dim=int(config["intermediate_size"]),
        rope_theta=float(config["rope_theta"]), norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["engine"].get("max_seq_len", 8192)),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        embedding_multiplier=float(config["embedding_multiplier"]),
        lm_head_multiplier=float(config["lm_head_multiplier"]),
        attention_in_multiplier=float(config["attention_in_multiplier"]),
        attention_out_multiplier=float(config["attention_out_multiplier"]),
        key_multiplier=float(config["key_multiplier"]),
        mlp_multipliers=tuple(float(m) for m in config["mlp_multipliers"]),
        ssm_heads=s["H"], ssm_head_dim=s["P"], ssm_state=s["N"], ssm_groups=s["G"],
        ssm_conv=s["K"], ssm_chunk=int(config["mamba_chunk_size"]),
        ssm_in_multiplier=float(config["ssm_in_multiplier"]),
        ssm_out_multiplier=float(config["ssm_out_multiplier"]),
        ssm_multipliers=tuple(float(m) for m in config["ssm_multipliers"]),
    )


# --- the plain reference ----------------------------------------------------

@partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "hd", "theta", "in_m", "key_m", "out_m"))
def _attention(h, wq, wk, wv, wo, *, n_heads, n_kv_heads, hd, theta, in_m, key_m, out_m):
    S = h.shape[0]
    a_in = h * in_m
    pos = jnp.arange(S)
    q = _rope((a_in @ _f32(wq)).reshape(S, n_heads, hd), pos, theta)
    k = _rope(((a_in @ _f32(wk)) * key_m).reshape(S, n_kv_heads, hd), pos, theta)
    v = (a_in @ _f32(wv)).reshape(S, n_kv_heads, hd)
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    scores = jnp.where((pos[:, None] >= pos[None, :])[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return (out.reshape(S, n_heads * hd) @ _f32(wo)) * out_m


@partial(jax.jit, static_argnames=("H", "P", "G", "N", "K", "in_m", "out_m", "mup", "eps",
                                   "state_dtype"))
def _mixer(h, w_in, w_out, conv_w, conv_b, a_log, dt_bias, d_skip, norm_w, *,
           H, P, G, N, K, in_m, out_m, mup, eps, state_dtype):
    T = h.shape[0]
    d_ssm, gn = H * P, G * N
    u = (h * in_m) @ _f32(w_in)
    z, xs, Bm, Cm, dt = jnp.split(u, [d_ssm, 2 * d_ssm, 2 * d_ssm + gn, 2 * d_ssm + 2 * gn], axis=-1)
    z, xs, Bm, Cm, dt = (t * m for t, m in zip((z, xs, Bm, Cm, dt), mup))
    xbc = jnp.concatenate([jnp.zeros((K - 1, d_ssm + 2 * gn), F32),
                           jnp.concatenate([xs, Bm, Cm], axis=-1)], axis=0)
    conv = _f32(conv_b)[None, :] + sum(xbc[k:k + T] * _f32(conv_w)[k][None, :] for k in range(K))
    xs, Bm, Cm = jnp.split(jax.nn.silu(conv), [d_ssm, d_ssm + gn], axis=-1)
    dt = jax.nn.softplus(dt + _f32(dt_bias))  # [T, H]
    A = -jnp.exp(_f32(a_log))  # [H]
    xs = xs.reshape(T, H, P)
    # head h reads group h // (H / G)
    Bh = jnp.repeat(Bm.reshape(T, G, N), H // G, axis=1)
    Ch = jnp.repeat(Cm.reshape(T, G, N), H // G, axis=1)

    def token(S, t):
        x_t, dt_t, B_t, C_t = t
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        # the state as the serving system would hold it between two steps
        # (reduce_precision, not a pair of converts: the TPU compiler may
        # drop those — xla_allow_excess_precision — and round nothing)
        kept = jnp.finfo(state_dtype)
        S = jax.lax.reduce_precision(S, exponent_bits=kept.nexp, mantissa_bits=kept.nmant)
        return S, jnp.einsum("hpn,hn->hp", S, C_t) + _f32(d_skip)[:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), F32), (xs, dt, Bh, Ch))
    y = y.reshape(T, d_ssm) * jax.nn.silu(z)
    parts = y.reshape(T, G, d_ssm // G)
    parts = parts * jax.lax.rsqrt(jnp.mean(parts * parts, axis=-1, keepdims=True) + eps)
    return ((parts.reshape(T, d_ssm) * _f32(norm_w)) @ _f32(w_out)) * out_m


def reference_logits(params, tokens, config: dict, *, positions, cast=None,
                     state_dtype=jnp.float32):
    """Float32 logits ``[len(positions), vocab]`` of a full causal forward
    over ``tokens``; the margins are ``inf`` (nothing is routed). ``cast``
    stands in for the upcast of each matmul weight, ``state_dtype`` for the
    precision the recurrent state is kept in between tokens: the controls."""
    cast = cast or (lambda w: w)
    s = _sizes(config)
    eps = float(config["rms_norm_eps"])
    lp = params["layers"]
    tokens = jnp.asarray(tokens, jnp.int32)
    gate_m, down_m = (float(m) for m in config["mlp_multipliers"])
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens]) * float(config["embedding_multiplier"])
        for i in range(int(config["num_hidden_layers"])):
            h = _rms_norm(x, _f32(lp["ln_attn"][i]), eps)
            m = _mixer(
                h, cast(lp["ssm_in"][i]), cast(lp["ssm_out"][i]), lp["ssm_conv_w"][i],
                lp["ssm_conv_b"][i], lp["ssm_A_log"][i], lp["ssm_dt_bias"][i],
                lp["ssm_D"][i], lp["ssm_norm"][i],
                H=s["H"], P=s["P"], G=s["G"], N=s["N"], K=s["K"],
                in_m=float(config["ssm_in_multiplier"]),
                out_m=float(config["ssm_out_multiplier"]),
                mup=tuple(float(v) for v in config["ssm_multipliers"]), eps=eps,
                state_dtype=state_dtype)
            a = _attention(
                h, cast(lp["attn_q"][i]), cast(lp["attn_k"][i]), cast(lp["attn_v"][i]),
                cast(lp["attn_o"][i]), n_heads=int(config["num_attention_heads"]),
                n_kv_heads=int(config["num_key_value_heads"]), hd=int(config["head_dim"]),
                theta=float(config["rope_theta"]),
                in_m=float(config["attention_in_multiplier"]),
                key_m=float(config["key_multiplier"]),
                out_m=float(config["attention_out_multiplier"]))
            x = x + m + a
            h = _rms_norm(x, _f32(lp["ln_mlp"][i]), eps)
            # one weight upcast at a time: the three are 1.3 GB in float32
            gate = _matmul(h, cast(lp["mlp_gate"][i])) * gate_m
            act = jax.nn.silu(gate) * _matmul(h, cast(lp["mlp_up"][i]))
            x = x + _matmul(act, cast(lp["mlp_down"][i])) * down_m
        x = _rms_norm(x, _f32(params["norm"]), eps)[jnp.asarray(positions)]
        head = params["embed"].T if config.get("tie_word_embeddings") else params["lm_head"]
        logits = jnp.concatenate(
            [_matmul(x, cast(head[:, c:c + HEAD_BLOCK])) for c in range(0, head.shape[1], HEAD_BLOCK)],
            axis=-1) * float(config["lm_head_multiplier"])
    return logits, jnp.full((len(positions),), jnp.inf, F32)


@jax.jit
def _matmul(x, w):
    return x @ _f32(w)


def control_logits(params, tokens, config: dict, *, positions):
    """The reference with every matmul weight (both mixers' projections, the
    MLP, the head) taken through int8, a scale an output channel: the step
    below the bfloat16 the configuration states, as ``llama_block.py`` takes
    it. Activations and the state stay float32."""
    def through_int8(w):
        w = jnp.asarray(w).astype(F32)
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale

    return reference_logits(params, tokens, config, positions=positions, cast=through_int8)


def state_control_logits(params, tokens, config: dict, *, positions):
    """A second control: the reference with its recurrent state rounded to
    bfloat16 after every token — the step below the float32 the
    configuration states for the state (``ssm_state_dtype``). No benchmark
    run calls it; PERF.md says whether the limits catch it."""
    return reference_logits(params, tokens, config, positions=positions,
                            state_dtype=jnp.bfloat16)


# --- the yardstick's counts ---------------------------------------------------

def param_counts(config: dict) -> dict:
    d, f = int(config["hidden_size"]), int(config["intermediate_size"])
    heads, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    hd, s = int(config["head_dim"]), _sizes(config)
    attn = d * heads * hd * 2 + d * kv * hd * 2
    mixer = (d * s["in"] + s["d_ssm"] * d + (s["K"] + 1) * s["conv"] + 3 * s["H"] + s["d_ssm"])
    layer = attn + mixer + 3 * d * f + 2 * d
    n_layers = int(config["num_hidden_layers"])
    embed = int(config["vocab_size"]) * d
    head = 0 if config.get("tie_word_embeddings") else embed
    return {"attention": attn, "mixer": mixer, "mlp": 3 * d * f, "layer": layer,
            "layers": layer * n_layers, "embed": embed, "head": head,
            "total": layer * n_layers + embed + head + d}


def kv_bytes_per_token(config: dict) -> int:
    return (2 * int(config["num_hidden_layers"]) * int(config["num_key_value_heads"])
            * int(config["head_dim"]) * BYTES[config.get("dtype", "bfloat16")])


def attention_stream_bytes(config: dict, *, kv_tokens: float) -> float:
    """One layer's paged decode attention call: K and V of every context
    token of the batch, for each of the KV heads; ``kv_tokens`` = tokens on
    distinct physical pages (``live_kv.py``), a shared page counted once."""
    return (kv_tokens * 2 * int(config["num_key_value_heads"]) * int(config["head_dim"])
            * BYTES[config.get("dtype", "bfloat16")])


def ssm_state_bytes_per_row(config: dict) -> int:
    """One row's recurrent state in one layer, in ``ssm_state_dtype``."""
    s = _sizes(config)
    return s["H"] * s["P"] * s["N"] * BYTES[config.get("ssm_state_dtype", "float32")]


def ssm_step_stream_bytes(config: dict, *, rows: float) -> float:
    """Bytes ONE layer's one-token state update must move at least: each
    row's state read and written once, and its xs, B, C, dt in and y out
    (float32, a few KiB a row)."""
    s = _sizes(config)
    small = (2 * s["d_ssm"] + 2 * s["G"] * s["N"] + s["H"]) * 4
    return rows * (2 * ssm_state_bytes_per_row(config) + small)


def _rows_of(config: dict, ctx) -> float:
    """Mean rows of the window's dispatches, from the context's own
    ``dispatch`` events (what ``batch_rows.sat`` reads); the engine's slot
    count where there is no trace."""
    rows = [len(args["rows"]) for _ts, _tid, name, _dur, _track, args
            in (ctx.tracer_events if ctx is not None else ())
            if name == "dispatch" and (args or {}).get("rows")]
    return sum(rows) / len(rows) if rows else float(config["engine"]["max_seqs"])


def decode_step_stream_bytes(config: dict, *, live_kv_tokens: float, ctx=None) -> float:
    """Bytes one decode step must move at least: every layer's weights and
    the head once, the live K/V of the batch (``live_kv_tokens`` = tokens on
    distinct physical pages, a shared page counted once), and every row's
    recurrent state read and written once a layer."""
    p = param_counts(config)
    weights = (p["layers"] + (p["head"] or p["embed"])) * BYTES[config.get("dtype", "bfloat16")]
    state = (int(config["num_hidden_layers"])
             * ssm_step_stream_bytes(config, rows=_rows_of(config, ctx)))
    return weights + live_kv_tokens * kv_bytes_per_token(config) + state
