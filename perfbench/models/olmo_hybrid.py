"""``model_type`` "olmo_hybrid": ``layer_types`` names each layer
``linear_attention`` (the gated delta rule over a matrix of state a head) or
``full_attention`` (plain multi-head softmax attention, no rotation), three
to one; every layer ends in a SwiGLU MLP, and each sub-block's norm stands on
its OUTPUT: ``x = x + Norm(mixer(x)); x = x + Norm(MLP(x))``.

The plain reference here is float32 at ``highest``: full causal attention
over the whole sequence, and the linear layer's recurrence as it is written,
token by token — no chunks, no cache, no state carried in from anywhere::

    [q~ | k~ | v~] = SiLU(conv_K([W_q x | W_k x | W_v x]))       causal, depthwise, no bias
    q = (q~_h / |q~_h|) dk^-1/2,   k = k~_h / |k~_h|             L2 norm a head, eps 1e-6
    beta = 2 sigmoid(w_b,h . x)                                  2: linear_allow_neg_eigval
    g = -exp(A_log,h) softplus(w_a,h . x + dt_bias,h),   alpha = exp(g)
    S~ = alpha S;   u = beta (v - S~^T k);   S = S~ + k u^T;   o = S^T q
    y = W_o [ RMSNorm_dv(o_h) * SiLU((W_g x)_h) ]_h

    full layer: q, k = RMSNorm(W_q x), RMSNorm(W_k x) over the whole width;
    causal softmax(q k^T / sqrt(head)) v, W_o; no rotation (rope_theta null)

It reads the program's parameter tree (stacked by KIND of layer: ``attn_*``
over the full layers, ``gdn_*`` over the linear ones, the MLP and the two
norms over all) and nothing else of the program. One layer and one matmul
weight are upcast at a time, and the head is computed in blocks of the
vocabulary. What the published config does not say is listed in the
configuration file under ``assumed``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from perfbench.costs import BYTES
from perfbench.reference import _f32, _rms_norm

F32 = jnp.float32
LINEAR, FULL = "linear_attention", "full_attention"
WIDTH_KEYS = ("hidden_size", "intermediate_size", "head_dim", "linear_key_head_dim",
              "linear_value_head_dim", "linear_conv_kernel_dim", "linear_num_key_heads",
              "linear_num_value_heads")
HEAD_BLOCK = 32768  # columns of the head upcast at a time
L2_EPS = 1e-6


def _sizes(config: dict) -> dict:
    H = int(config["linear_num_value_heads"])
    if int(config["linear_num_key_heads"]) != H:
        raise ValueError("the program's linear layer has one key head a value head")
    dk, dv = int(config["linear_key_head_dim"]), int(config["linear_value_head_dim"])
    kinds = list(config["layer_types"])
    if len(kinds) != int(config["num_hidden_layers"]) or set(kinds) - {LINEAR, FULL}:
        raise ValueError("layer_types names every layer linear_attention or full_attention")
    return {"H": H, "dk": dk, "dv": dv, "K": int(config["linear_conv_kernel_dim"]),
            "conv": H * (2 * dk + dv), "d_v": H * dv, "kinds": kinds,
            "n_linear": kinds.count(LINEAR), "n_full": kinds.count(FULL),
            "hd": int(config.get("head_dim")
                      or int(config["hidden_size"]) // int(config["num_attention_heads"]))}


def _period(kinds: list[str]) -> tuple[str, ...]:
    """The shortest prefix of ``kinds`` that, repeated, gives all of it."""
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return tuple(kinds[:p])
    raise AssertionError


def program_config(config: dict):
    from finchat_tpu.models.llama import LlamaConfig

    s = _sizes(config)
    if config.get("attention_bias"):
        raise ValueError("the program's block has no attention_bias")
    if "layer_pattern" not in LlamaConfig.__dataclass_fields__:
        raise KeyError("this checkout's block (finchat_tpu/models/llama.py) has no layer "
                       "pattern and no linear-attention layer: it cannot run model_type "
                       "'olmo_hybrid'")
    theta = (config.get("rope_parameters") or {}).get("rope_theta", config.get("rope_theta"))
    return LlamaConfig(
        vocab_size=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]), n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]), head_dim=s["hd"],
        hidden_dim=int(config["intermediate_size"]),
        rope_theta=None if theta is None else float(theta),
        norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["engine"].get("max_seq_len", 8192)),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        layer_pattern=_period(s["kinds"]), qk_norm=True, norm_after=True,
        gdn_heads=s["H"], gdn_key_dim=s["dk"], gdn_value_dim=s["dv"], gdn_conv=s["K"],
        gdn_neg_eigval=bool(config["linear_allow_neg_eigval"]),
    )


# --- the plain reference ----------------------------------------------------

@partial(jax.jit, static_argnames=("n_heads", "hd", "eps"))
def _attention(x, wq, wk, wv, wo, q_norm, k_norm, *, n_heads, hd, eps):
    S = x.shape[0]
    pos = jnp.arange(S)
    q = _rms_norm(x @ _f32(wq), _f32(q_norm), eps).reshape(S, n_heads, hd)
    k = _rms_norm(x @ _f32(wk), _f32(k_norm), eps).reshape(S, -1, hd)
    v = (x @ _f32(wv)).reshape(S, -1, hd)
    rep = n_heads // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    scores = jnp.where((pos[:, None] >= pos[None, :])[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(S, n_heads * hd) @ _f32(wo)


@partial(jax.jit, static_argnames=("H", "dk", "dv", "K", "neg_eigval", "eps", "state_dtype"))
def _linear_attention(x, w_in, w_ba, w_out, conv_w, a_log, dt_bias, norm_w, *,
                      H, dk, dv, K, neg_eigval, eps, state_dtype):
    T = x.shape[0]
    qkv, gate = jnp.split(x @ _f32(w_in), [H * (2 * dk + dv)], axis=-1)
    b, a = jnp.split(x @ _f32(w_ba), 2, axis=-1)  # [T, H] each
    qkv = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[-1]), F32), qkv], axis=0)
    qkv = jax.nn.silu(sum(qkv[j:j + T] * _f32(conv_w)[j][None, :] for j in range(K)))
    q, k, v = jnp.split(qkv, [H * dk, 2 * H * dk], axis=-1)
    q, k, v = q.reshape(T, H, dk), k.reshape(T, H, dk), v.reshape(T, H, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) * dk ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    beta = jax.nn.sigmoid(b) * (2.0 if neg_eigval else 1.0)
    alpha = jnp.exp(-jnp.exp(_f32(a_log)) * jax.nn.softplus(a + _f32(dt_bias)))

    def token(S, t):
        q_t, k_t, v_t, alpha_t, beta_t = t
        S = alpha_t[:, None, None] * S
        u = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * u[:, None, :]
        # the state as the serving system would hold it between two steps
        # (reduce_precision, not a pair of converts: the TPU compiler may
        # drop those and round nothing)
        kept = jnp.finfo(state_dtype)
        S = jax.lax.reduce_precision(S, exponent_bits=kept.nexp, mantissa_bits=kept.nmant)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((H, dk, dv), F32), (q, k, v, alpha, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * _f32(norm_w)
    return (o * jax.nn.silu(gate.reshape(T, H, dv))).reshape(T, H * dv) @ _f32(w_out)


@jax.jit
def _matmul(x, w):
    return x @ _f32(w)


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
    seen = {LINEAR: 0, FULL: 0}
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        for i, kind in enumerate(s["kinds"]):
            j = seen[kind]  # the layer's place in its own kind's stacks
            seen[kind] += 1
            if kind == LINEAR:
                m = _linear_attention(
                    x, cast(lp["gdn_in"][j]), cast(lp["gdn_ba"][j]), cast(lp["gdn_out"][j]),
                    lp["gdn_conv_w"][j], lp["gdn_A_log"][j], lp["gdn_dt_bias"][j],
                    lp["gdn_norm"][j], H=s["H"], dk=s["dk"], dv=s["dv"], K=s["K"],
                    neg_eigval=bool(config["linear_allow_neg_eigval"]), eps=eps,
                    state_dtype=state_dtype)
            else:
                m = _attention(
                    x, cast(lp["attn_q"][j]), cast(lp["attn_k"][j]), cast(lp["attn_v"][j]),
                    cast(lp["attn_o"][j]), lp["attn_q_norm"][j], lp["attn_k_norm"][j],
                    n_heads=int(config["num_attention_heads"]), hd=s["hd"], eps=eps)
            x = x + _rms_norm(m, _f32(lp["ln_attn"][i]), eps)
            # one weight upcast at a time: the three are 0.5 GB in float32
            act = jax.nn.silu(_matmul(x, cast(lp["mlp_gate"][i]))) * _matmul(x, cast(lp["mlp_up"][i]))
            x = x + _rms_norm(_matmul(act, cast(lp["mlp_down"][i])), _f32(lp["ln_mlp"][i]), eps)
        x = _rms_norm(x, _f32(params["norm"]), eps)[jnp.asarray(positions)]
        head = params["embed"].T if config.get("tie_word_embeddings") else params["lm_head"]
        logits = jnp.concatenate(
            [_matmul(x, cast(head[:, c:c + HEAD_BLOCK])) for c in range(0, head.shape[1], HEAD_BLOCK)],
            axis=-1)
    return logits, jnp.full((len(positions),), jnp.inf, F32)


def control_logits(params, tokens, config: dict, *, positions):
    """The reference with every matmul weight (both kinds of mixer's
    projections, the MLP, the head) taken through int8, a scale an output
    channel: the step below the bfloat16 the configuration states, as
    ``llama_block.py`` takes it. Activations and the state stay float32."""
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
    """Parameters by group. ``layer`` is the MEAN layer of the pattern (the
    two kinds differ): ``layers`` over the depth."""
    d, f, s = int(config["hidden_size"]), int(config["intermediate_size"]), _sizes(config)
    heads, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    mlp = 3 * d * f
    # q, k, v, o and the two norms over the whole width of q and of k
    attention = d * heads * s["hd"] * 2 + d * kv * s["hd"] * 2 + (heads + kv) * s["hd"]
    # [q | k | v | gate] and [b | a] in, out, the conv, A_log, dt_bias, the norm
    linear = (d * (s["conv"] + s["d_v"] + 2 * s["H"]) + s["d_v"] * d + s["K"] * s["conv"]
              + 2 * s["H"] + s["dv"])
    full_layer, linear_layer = attention + mlp + 2 * d, linear + mlp + 2 * d
    layers = s["n_full"] * full_layer + s["n_linear"] * linear_layer
    embed = int(config["vocab_size"]) * d
    head = 0 if config.get("tie_word_embeddings") else embed
    n = len(s["kinds"])
    return {"attention": attention, "linear_attention": linear, "mlp": mlp,
            "full_layer": full_layer, "linear_layer": linear_layer,
            "layer": layers // n if layers % n == 0 else layers / n,
            "layers": layers, "embed": embed, "head": head,
            "total": layers + embed + head + d}


def attention_stream_bytes(config: dict, *, kv_tokens: float) -> float:
    """One full-attention layer's paged decode call: K and V of every context
    token of the batch, for each of the KV heads; ``kv_tokens`` = tokens on
    distinct physical pages (``live_kv.py``), a shared page counted once."""
    return (kv_tokens * 2 * int(config["num_key_value_heads"]) * _sizes(config)["hd"]
            * BYTES[config.get("dtype", "bfloat16")])


def kv_bytes_per_token(config: dict) -> int:
    """K and V of a token in every layer that owns pages: the full-attention
    layers alone (a linear layer's memory does not grow with the context)."""
    return int(_sizes(config)["n_full"] * attention_stream_bytes(config, kv_tokens=1))


def ssm_state_bytes_per_row(config: dict) -> int:
    """One row's recurrent state in one linear layer, in ``ssm_state_dtype``:
    a ``dk x dv`` matrix a head."""
    s = _sizes(config)
    return s["H"] * s["dk"] * s["dv"] * BYTES[config.get("ssm_state_dtype", "float32")]


def conv_tail_bytes_per_row(config: dict) -> int:
    s = _sizes(config)
    return (s["K"] - 1) * s["conv"] * BYTES[config.get("ssm_state_dtype", "float32")]


def ssm_step_stream_bytes(config: dict, *, rows: float) -> float:
    """Bytes the operations under scope ``gdn_scan`` in ONE iteration of the
    program's layer scan must move at least. The scan runs over PERIODS of
    the layer pattern and a period's linear layers (three) stand one after
    another in its body, so that is what ``ssm_scan_trace.py`` times (each
    distinct operation under the scope once): for each of them every row's
    state read and written once, and its q, k, v, alpha, beta in and o out
    (float32, a few KiB a row). Logical bytes: a layout that pads the state
    reads as a lower share."""
    s = _sizes(config)
    small = (2 * s["H"] * s["dk"] + 2 * s["d_v"] + 2 * s["H"]) * 4
    return _period(s["kinds"]).count(LINEAR) * rows * (2 * ssm_state_bytes_per_row(config) + small)


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
    the head once, the live K/V of the batch in the full-attention layers
    (``live_kv_tokens`` = tokens on distinct physical pages, a shared page
    counted once), and in every linear layer each row's recurrent state and
    conv tail read and written once."""
    p, s = param_counts(config), _sizes(config)
    weights = (p["layers"] + (p["head"] or p["embed"])) * BYTES[config.get("dtype", "bfloat16")]
    rows = _rows_of(config, ctx)
    periods = len(s["kinds"]) // len(_period(s["kinds"]))
    state = (periods * ssm_step_stream_bytes(config, rows=rows)
             + s["n_linear"] * rows * 2 * conv_tail_bytes_per_row(config))
    return weights + live_kv_tokens * kv_bytes_per_token(config) + state
