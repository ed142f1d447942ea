"""``model_type`` "granitemoehybrid": ``layer_types`` names each layer ``mamba``
(a Mamba-2 mixer alone) or ``attention`` (GQA softmax attention with NO
positional encoding and a softmax scale that is a key of the file), nine to
one; EVERY layer's second sub-block is a mixture of many small routed experts
beside one shared expert; four scalars (embedding, residual, attention,
logits).

The file's ``num_local_experts`` is what THIS chip holds of the published
``reduced.num_local_experts.from`` experts the router scores: the held range
is ``[0, num_local_experts)``, and a pick on an absent expert adds nothing
(the chip that holds it adds it). The gates are normalised over ALL picks.

The plain reference here is float32 at ``highest``: full causal attention
over the whole sequence, the mixer's recurrence as it is written, token by
token, and the experts as a loop over the held ones with masks — no chunks,
no cache, no kernels, no sorting::

    x0 = embedding_multiplier * embed[token]
    x <- x + residual_multiplier * Mixer(RMSNorm(x))          by layer_types
    h = RMSNorm(x);  x <- x + residual_multiplier * (Routed(h) + Shared(h))
    logits = (RMSNorm(x) . embed^T) / logits_scaling          tied

    r = W_r h (72, float32);  I = the 10 largest;  g = softmax(r_I)
    E_e(h) = W_out,e [SiLU(a) * b],  [a | b] = W_in,e h
    Routed(h) = sum over e in I and HELD of g_e E_e(h)
    Shared(h) = W_out,s [SiLU(a_s) * b_s],  [a_s | b_s] = W_in,s h

    mamba: [z | xBC | dt] = W_in h;  xBC = SiLU(conv4(xBC) + b)
           dt = softplus(dt + dt_bias), A = -exp(A_log)
           S <- exp(dt A) S + dt x (x) B,  y = S C + D x     a head, S in R^{64 x 128}
           W_out RMSNorm(y * SiLU(z))                        one group over the width
    attention: q, k, v = W h, NO rotation, causal softmax(attention_multiplier q k^T) v, W_o

It reads the program's parameter tree (stacked by KIND of layer: ``attn_*``
over the attention layers, ``ssm_*`` over the mamba ones, the experts, the
router and the two norms over all) and nothing else of the program. One layer
and one matmul weight are upcast at a time, and the head is computed in
blocks of the vocabulary. What the published config does not say is listed
in the configuration file under ``assumed``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.costs import BYTES
from perfbench.models.falcon_h1 import _matmul, _mixer, _rows_of
from perfbench.models.olmo_hybrid import _period
from perfbench.reference import _f32, _rms_norm

F32 = jnp.float32
MAMBA, ATTENTION = "mamba", "attention"
WIDTH_KEYS = ("hidden_size", "intermediate_size", "shared_intermediate_size",
              "num_experts_per_tok", "head_dim", "mamba_d_head", "mamba_d_state",
              "mamba_d_conv", "mamba_n_heads", "mamba_n_groups", "mamba_expand",
              "mamba_chunk_size")
HEAD_BLOCK = 32768  # columns of the head upcast at a time


def _sizes(config: dict) -> dict:
    H, P = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    d = int(config["hidden_size"])
    if H * P != int(config["mamba_expand"]) * d:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x hidden_size")
    G, N = int(config["mamba_n_groups"]), int(config["mamba_d_state"])
    kinds = list(config["layer_types"])
    if len(kinds) != int(config["num_hidden_layers"]) or set(kinds) - {MAMBA, ATTENTION}:
        raise ValueError("layer_types names every layer mamba or attention")
    held = int(config["num_local_experts"])
    cut = config.get("reduced", {}).get("num_local_experts")
    return {"H": H, "P": P, "G": G, "N": N, "K": int(config["mamba_d_conv"]),
            "d_ssm": H * P, "conv": H * P + 2 * G * N, "in": 2 * H * P + 2 * G * N + H,
            "kinds": kinds, "n_mamba": kinds.count(MAMBA), "n_attention": kinds.count(ATTENTION),
            "held": held, "router": int(cut["from"]) if cut else held,
            "top_k": int(config["num_experts_per_tok"]),
            "f": int(config["intermediate_size"]), "fs": int(config["shared_intermediate_size"]),
            "hd": int(config.get("head_dim") or d // int(config["num_attention_heads"]))}


def program_config(config: dict):
    from finchat_tpu.models import llama
    from finchat_tpu.models.llama import LlamaConfig

    s = _sizes(config)
    for key in ("attention_bias", "mamba_proj_bias"):
        if config.get(key):
            raise ValueError(f"the program's block has no {key}")
    if not config.get("mamba_conv_bias", True):
        raise ValueError("the program's mixer has a conv bias (mamba_conv_bias true)")
    if config.get("position_embedding_type") != "nope":
        raise ValueError("position_embedding_type: this adapter builds the 'nope' model (no "
                         "rotation, whatever rope_theta says)")
    if not hasattr(llama, "MAMBA") or "moe_router_width" not in LlamaConfig.__dataclass_fields__:
        raise KeyError("this checkout's block (finchat_tpu/models/llama.py) has no `mamba` "
                       "layer kind and no held range of experts: it cannot run model_type "
                       "'granitemoehybrid'")
    kinds = tuple({MAMBA: llama.MAMBA, ATTENTION: llama.FULL}[k] for k in _period(s["kinds"]))
    c = LlamaConfig(
        vocab_size=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]), n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]), head_dim=s["hd"],
        hidden_dim=s["f"], rope_theta=None, norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["engine"].get("max_seq_len", 8192)),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        n_experts=s["held"], top_k_experts=s["top_k"], moe_router_width=s["router"],
        moe_shared_dim=s["fs"], moe_fused_glu=True,
        attention_scale=float(config["attention_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        lm_head_multiplier=1.0 / float(config["logits_scaling"]),
        ssm_heads=s["H"], ssm_head_dim=s["P"], ssm_state=s["N"], ssm_groups=s["G"],
        ssm_conv=s["K"], ssm_chunk=int(config.get("program_ssm_chunk", 128)),
        layer_pattern=kinds,
    )
    stated = jnp.dtype(config.get("ssm_state_dtype", "float32"))
    if _kept_state_dtype(c) != stated:
        raise ValueError(
            f"ssm_state_dtype: the file states {stated.name}, and this checkout's engine keeps "
            f"the recurrent state in {_kept_state_dtype(c).name} (the logits cannot see that "
            "difference in this model, PERF.md section 4: so it is refused here)")
    return c


def _kept_state_dtype(c):
    """The dtype the program's engine keeps a slot's recurrent state in
    between two tokens: read off what ``create_state`` would allocate (shapes
    alone; nothing is allocated)."""
    from finchat_tpu.engine.engine import create_state
    from finchat_tpu.utils.config import EngineConfig

    tiny = EngineConfig(max_seqs=1, num_pages=2, page_size=128, max_seq_len=128)
    return jax.eval_shape(lambda: create_state(c, tiny, 1)).ssm_state.dtype


# --- the plain reference ----------------------------------------------------

@partial(jax.jit, static_argnames=("n_heads", "hd", "scale"))
def _attention(h, wq, wk, wv, wo, *, n_heads, hd, scale):
    S = h.shape[0]
    pos = jnp.arange(S)
    q = (h @ _f32(wq)).reshape(S, n_heads, hd)
    k = (h @ _f32(wk)).reshape(S, -1, hd)
    v = (h @ _f32(wv)).reshape(S, -1, hd)
    rep = n_heads // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
    scores = jnp.where((pos[:, None] >= pos[None, :])[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(S, n_heads * hd) @ _f32(wo)


@partial(jax.jit, static_argnames=("top_k", "swap"))
def _route(h, router, *, top_k, swap):
    """``(picks [T, k], gates [T, k])``: the ``top_k`` largest router logits
    and the softmax over them alone. ``swap``: the last pick gives way to the
    first expert that was not picked (what a flip at the smallest margin does)."""
    r = h @ _f32(router)
    vals, idx = jax.lax.top_k(r, top_k + 1)
    keep = jnp.arange(top_k).at[-1].add(int(swap))
    return idx[:, keep], jax.nn.softmax(vals[:, keep], axis=-1)


@jax.jit
def _glu(h, w_in, w_out):
    a, b = jnp.split(h @ _f32(w_in), 2, axis=-1)
    return (jax.nn.silu(a) * b) @ _f32(w_out)


def _experts(h, lp, i, s: dict, cast, swap: bool):
    picks, gates = _route(h, lp["router"][i], top_k=s["top_k"], swap=swap)
    out = _glu(h, cast(lp["shared_in"][i]), cast(lp["shared_out"][i]))
    for e in range(s["held"]):  # the held range starts at expert 0
        g = jnp.sum(jnp.where(picks == e, gates, 0.0), axis=-1)  # 0 where not picked
        out = out + g[:, None] * _glu(h, cast(lp["moe_in"][i, e]), cast(lp["moe_out"][i, e]))
    return out


@partial(jax.jit, static_argnames=("H", "P", "G", "N", "K", "state_dtype"))
def _final_state(h, w_in, conv_w, conv_b, a_log, dt_bias, *, H, P, G, N, K, state_dtype):
    """The state ``[H, P, N]`` that ``falcon_h1._mixer``'s recurrence ends on
    over the normed inputs ``h`` (that function keeps its outputs and drops
    the state): the same equations, every multiplier 1."""
    T, d_ssm, gn = h.shape[0], H * P, G * N
    _z, xs, Bm, _Cm, dt = jnp.split(h @ _f32(w_in), [d_ssm, 2 * d_ssm, 2 * d_ssm + gn,
                                                     2 * d_ssm + 2 * gn], axis=-1)
    xbc = jnp.concatenate([jnp.zeros((K - 1, d_ssm + gn), F32),
                           jnp.concatenate([xs, Bm], axis=-1)], axis=0)
    w, b = _f32(conv_w)[:, :d_ssm + gn], _f32(conv_b)[:d_ssm + gn]  # the conv is depthwise
    conv = b[None, :] + sum(xbc[k:k + T] * w[k][None, :] for k in range(K))
    xs, Bm = jnp.split(jax.nn.silu(conv), [d_ssm], axis=-1)
    dt = jax.nn.softplus(dt + _f32(dt_bias))
    A = -jnp.exp(_f32(a_log))
    Bh = jnp.repeat(Bm.reshape(T, G, N), H // G, axis=1)
    kept = jnp.finfo(state_dtype)

    def token(S, t):
        x_t, dt_t, B_t = t
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return jax.lax.reduce_precision(S, exponent_bits=kept.nexp,
                                        mantissa_bits=kept.nmant), None

    return jax.lax.scan(token, jnp.zeros((H, P, N), F32), (xs.reshape(T, H, P), dt, Bh))[0]


def _forward(params, tokens, config: dict, *, cast=None, state_dtype=jnp.float32,
             swap_layer: int | None = None, states: list | None = None):
    """The residual stream ``[tokens, hidden]`` behind the last layer (before
    the final norm); under ``default_matmul_precision("highest")``. Into
    ``states``, if given, goes each mamba layer's recurrent state after the
    last token, in the layers' order."""
    cast = cast or (lambda w: w)
    s = _sizes(config)
    eps, res = float(config["rms_norm_eps"]), float(config["residual_multiplier"])
    lp = params["layers"]
    seen = {MAMBA: 0, ATTENTION: 0}
    x = _f32(params["embed"][jnp.asarray(tokens, jnp.int32)]) * float(config["embedding_multiplier"])
    for i, kind in enumerate(s["kinds"]):
        j = seen[kind]  # the layer's place in its own kind's stacks
        seen[kind] += 1
        h = _rms_norm(x, _f32(lp["ln_attn"][i]), eps)
        if kind == MAMBA:
            shape = {k: s[k] for k in ("H", "P", "G", "N", "K")}
            m = _mixer(
                h, cast(lp["ssm_in"][j]), cast(lp["ssm_out"][j]), lp["ssm_conv_w"][j],
                lp["ssm_conv_b"][j], lp["ssm_A_log"][j], lp["ssm_dt_bias"][j],
                lp["ssm_D"][j], lp["ssm_norm"][j], **shape, in_m=1.0, out_m=1.0,
                mup=(1.0,) * 5, eps=eps, state_dtype=state_dtype)
            if states is not None:
                states.append(_final_state(
                    h, cast(lp["ssm_in"][j]), lp["ssm_conv_w"][j], lp["ssm_conv_b"][j],
                    lp["ssm_A_log"][j], lp["ssm_dt_bias"][j], **shape, state_dtype=state_dtype))
        else:
            m = _attention(
                h, cast(lp["attn_q"][j]), cast(lp["attn_k"][j]), cast(lp["attn_v"][j]),
                cast(lp["attn_o"][j]), n_heads=int(config["num_attention_heads"]),
                hd=s["hd"], scale=float(config["attention_multiplier"]))
        x = x + res * m
        h = _rms_norm(x, _f32(lp["ln_mlp"][i]), eps)
        x = x + res * _experts(h, lp, i, s, cast, swap=i == swap_layer)
    return x


def reference_logits(params, tokens, config: dict, *, positions, cast=None,
                     state_dtype=jnp.float32, swap_layer: int | None = None):
    """Float32 logits ``[len(positions), vocab]`` of a full causal forward
    over ``tokens``. The margins are ``inf``: every position is compared (a
    flip between the 10th and the 11th of 72 logits exchanges the smallest of
    ten gates for its near-equal; PERF.md section 4 has the account, and
    ``swap_layer`` — the flip made on purpose in one layer, at every token —
    is how a test bounds it). ``cast`` stands in for the upcast of each
    matmul weight, ``state_dtype`` for the precision the recurrent state is
    kept in between tokens: the controls."""
    cast = cast or (lambda w: w)
    with jax.default_matmul_precision("highest"):
        x = _forward(params, tokens, config, cast=cast, state_dtype=state_dtype,
                     swap_layer=swap_layer)
        x = _rms_norm(x, _f32(params["norm"]), float(config["rms_norm_eps"]))[jnp.asarray(positions)]
        head = params["embed"].T if config.get("tie_word_embeddings") else params["lm_head"]
        logits = jnp.concatenate(
            [_matmul(x, cast(head[:, c:c + HEAD_BLOCK])) for c in range(0, head.shape[1], HEAD_BLOCK)],
            axis=-1) / float(config["logits_scaling"])
    return logits, jnp.full((len(positions),), jnp.inf, F32)


def reference_state(params, tokens, config: dict, *, state_dtype=jnp.float32):
    """Each mamba layer's recurrent state ``[mamba layers, H, P, N]`` after
    the last of ``tokens``, by the reference: what a slot of the program's
    ``ssm_state`` holds when the row has seen them. With ``state_dtype``
    bfloat16: the control that ``state_control_logits`` is, read at the state
    itself."""
    states: list = []
    with jax.default_matmul_precision("highest"):
        _forward(params, tokens, config, state_dtype=state_dtype, states=states)
    return jnp.stack(states)


def control_logits(params, tokens, config: dict, *, positions):
    """The reference with every matmul weight (the mixers', attention's, the
    routed and the shared experts', the head) taken through int8, a scale an
    output channel: the step below the bfloat16 the configuration states, as
    ``llama_block.py`` takes it. The router (float32 in the program),
    activations and the state stay float32."""
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


def state_distance(got, want) -> list[float]:
    """A layer at a time: the RMS difference between two states over the RMS
    of ``want``; ``inf`` for a reading that is not finite."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return [float(np.sqrt(np.mean((g - w) ** 2) / np.mean(w ** 2))) if np.isfinite(g).all()
            else float("inf") for g, w in zip(got, want)]


def kept_mantissa_bits(state) -> list[int]:
    """A layer at a time: how many of float32's 23 mantissa bits the values
    of a kept state use — 23 less the trailing zero bits that all but a
    hundredth of its non-zero values share. A state kept in float32 between
    two tokens reads 23, one rounded to bfloat16 (or stored in it) reads 7,
    whatever the values are: the precision the file states as
    ``ssm_state_dtype``, read off the state itself, where the logits cannot
    see it (PERF.md section 4)."""
    out = []
    for layer in np.asarray(state, np.float32):
        mantissa = layer.reshape(-1).view(np.uint32)[layer.reshape(-1) != 0] & 0x7FFFFF
        lowest = mantissa & (~mantissa + 1)  # the lowest set bit; 0 where the mantissa is 0
        zeros = np.where(lowest == 0, 23, np.log2(np.maximum(lowest, 1))).astype(np.int64)
        out.append(23 - int(np.percentile(zeros, 1, method="lower")) if zeros.size else 0)
    return out


# --- the yardstick's counts ---------------------------------------------------

def param_counts(config: dict) -> dict:
    """Parameters by group, of what THIS chip holds (``num_local_experts``
    routed experts a layer). ``layer`` is the MEAN layer of the pattern."""
    d, s = int(config["hidden_size"]), _sizes(config)
    heads, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    expert, shared, router = 3 * d * s["f"], 3 * d * s["fs"], d * s["router"]
    attention = d * heads * s["hd"] * 2 + d * kv * s["hd"] * 2
    # in and out projections, the conv and its bias, A_log, dt_bias, D, the gated norm
    mixer = d * s["in"] + s["d_ssm"] * d + (s["K"] + 1) * s["conv"] + 3 * s["H"] + s["d_ssm"]
    outside = shared + router + 2 * d  # a layer's second sub-block outside its routed experts
    mamba_layer, attention_layer = mixer + outside, attention + outside
    routed = s["held"] * expert
    layers = (s["n_mamba"] * mamba_layer + s["n_attention"] * attention_layer
              + len(s["kinds"]) * routed)
    embed = int(config["vocab_size"]) * d
    head = 0 if config.get("tie_word_embeddings") else embed
    n = len(s["kinds"])
    return {"expert": expert, "routed": routed, "shared": shared, "router": router,
            "mixer": mixer, "attention": attention,
            "mamba_layer": mamba_layer, "attention_layer": attention_layer,
            "layer": layers // n if layers % n == 0 else layers / n,
            "layers": layers, "embed": embed, "head": head,
            "total": layers + embed + head + d}


def attention_stream_bytes(config: dict, *, kv_tokens: float) -> float:
    """One attention layer's paged decode call: K and V of every context
    token of the batch, for each of the KV heads; ``kv_tokens`` = tokens on
    distinct physical pages (``live_kv.py``), a shared page counted once."""
    return (kv_tokens * 2 * int(config["num_key_value_heads"]) * _sizes(config)["hd"]
            * BYTES[config.get("dtype", "bfloat16")])


def kv_bytes_per_token(config: dict) -> int:
    """K and V of a token in every layer that owns pages: the attention
    layers alone (a mamba layer's memory does not grow with the context)."""
    return int(_sizes(config)["n_attention"] * attention_stream_bytes(config, kv_tokens=1))


def ssm_state_bytes_per_row(config: dict) -> int:
    """One row's recurrent state in one mamba layer, in ``ssm_state_dtype``."""
    s = _sizes(config)
    return s["H"] * s["P"] * s["N"] * BYTES[config.get("ssm_state_dtype", "float32")]


def conv_tail_bytes_per_row(config: dict) -> int:
    s = _sizes(config)
    return (s["K"] - 1) * s["conv"] * BYTES[config.get("ssm_state_dtype", "float32")]


def ssm_step_stream_bytes(config: dict, *, rows: float) -> float:
    """Bytes the operations under scope ``ssm_scan`` in ONE iteration of the
    program's layer scan must move at least. The scan runs over PERIODS of the
    layer pattern and a period's mamba layers (nine) stand one after another
    in its body, so that is what ``ssm_scan_trace.py`` times (each distinct
    operation under the scope once): for each of them every row's state read
    and written once, and its xs, B, C, dt in and y out (float32, a few KiB a
    row)."""
    s = _sizes(config)
    small = (2 * s["d_ssm"] + 2 * s["G"] * s["N"] + s["H"]) * 4
    return _period(s["kinds"]).count(MAMBA) * rows * (2 * ssm_state_bytes_per_row(config) + small)


def moe_step_stream_bytes(config: dict, *, rows: float, experts_touched: float) -> float:
    """Bytes the operations under scope ``moe_experts`` in ONE iteration of
    the program's layer scan (a period: every one of its layers routes) must
    move at least: in each layer the weights of the ``experts_touched`` held
    experts that the step's rows picked (a mean, from the program's counter:
    what a step has to read, not what one implementation reads), and each
    row's input and output."""
    s, two = _sizes(config), BYTES[config.get("dtype", "bfloat16")]
    layer = (experts_touched * param_counts(config)["expert"]
             + rows * 2 * int(config["hidden_size"])) * two
    return len(_period(s["kinds"])) * layer


def experts_touched(config: dict, ctx) -> float | None:
    """Held experts a layer a step touched, over the window: the program's
    two counters. None where there is no context or the counters did not
    move (a program without them)."""
    steps = ctx.delta("finchat_moe_layer_steps_total") if ctx is not None else 0.0
    return ctx.delta("finchat_moe_experts_touched_total") / steps if steps > 0 else None


def decode_step_stream_bytes(config: dict, *, live_kv_tokens: float, ctx=None) -> float:
    """Bytes one decode step must move at least: everything outside the
    routed experts once (mixers, attention, shared experts, routers, norms),
    in every layer the held experts the step TOUCHED (the program's counter
    through ``ctx``; all held ones without it), the head (the tied embedding)
    once, the live K/V of the batch in the attention layers
    (``live_kv_tokens`` = tokens on distinct physical pages, a shared page
    counted once), and in every mamba layer each row's recurrent state and
    conv tail read and written once."""
    p, s = param_counts(config), _sizes(config)
    two = BYTES[config.get("dtype", "bfloat16")]
    n = len(s["kinds"])
    outside = p["layers"] - n * p["routed"]
    touched = n * (experts_touched(config, ctx) or s["held"]) * p["expert"]
    rows = _rows_of(config, ctx)
    periods = n // len(_period(s["kinds"]))
    state = (periods * ssm_step_stream_bytes(config, rows=rows)
             + s["n_mamba"] * rows * 2 * conv_tail_bytes_per_row(config))
    return ((outside + touched + (p["head"] or p["embed"])) * two
            + live_kv_tokens * kv_bytes_per_token(config) + state)
