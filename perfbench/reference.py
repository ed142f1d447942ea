"""The plain reference of the configurations: a float32 ``jax.numpy`` forward.

No kernels, no cache, no batching: full causal attention over the whole
sequence, GQA by repeating KV heads, RMSNorm, rotate-half RoPE, and either a
dense SwiGLU MLP or Mixtral's routed experts (top-k of E by router logit,
softmax over the selected logits only, each token computed through its k
experts alone — not through all E as the program's ``moe_mlp`` does).
It follows the published Mistral-7B / Mixtral-8x7B block; it reads the
program's parameter tree (layers stacked on a leading axis) and nothing else
of the program.

Weights are upcast one layer (one expert) at a time so that a 7B-wide layer
fits beside the serving engine's own state. On a TPU a float32 matmul runs in
reduced precision unless ``default_matmul_precision("highest")`` is set, so
``forward_logits`` sets it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(x):
    return jnp.asarray(x).astype(F32)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rope(x, positions, theta):
    """x [S, H, D]; rotate-half convention (first half with second half)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = positions.astype(F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


@partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "theta", "eps"))
def _attention_block(x, ln, wq, wk, wv, wo, *, n_heads, n_kv_heads, theta, eps):
    S, D = x.shape
    hd = D // n_heads
    h = _rms_norm(x, _f32(ln), eps)
    pos = jnp.arange(S)
    q = _rope((h @ _f32(wq)).reshape(S, n_heads, hd), pos, theta)
    k = _rope((h @ _f32(wk)).reshape(S, n_kv_heads, hd), pos, theta)
    v = (h @ _f32(wv)).reshape(S, n_kv_heads, hd)
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return x + out.reshape(S, D) @ _f32(wo)


@jax.jit
def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


@partial(jax.jit, static_argnames=("top_k",))
def _route(h, router, *, top_k):
    """Per-token expert weights [S, E]: softmax over the top-k logits,
    zero elsewhere (Mixtral's renormalisation). Also the routing margin [S]:
    the gap between the last chosen and the first rejected router logit, in
    units of the token's router-logit spread — where it is small, rounding
    the layer's input to bf16 can choose another expert."""
    logits = h @ _f32(router)
    vals, idx = jax.lax.top_k(logits, top_k + 1)
    w = jax.nn.softmax(vals[:, :top_k], axis=-1)
    weights = jnp.zeros_like(logits).at[jnp.arange(h.shape[0])[:, None], idx[:, :top_k]].add(w)
    margin = (vals[:, top_k - 1] - vals[:, top_k]) / (jnp.std(logits, axis=-1) + 1e-9)
    return weights, margin


def _layer(x, lp, i, *, n_heads, n_kv_heads, theta, eps, n_experts, top_k):
    x = _attention_block(
        x, lp["ln_attn"][i], lp["attn_q"][i], lp["attn_k"][i], lp["attn_v"][i],
        lp["attn_o"][i], n_heads=n_heads, n_kv_heads=n_kv_heads, theta=theta, eps=eps)
    h = _rms_norm(x, _f32(lp["ln_mlp"][i]), eps)
    if not n_experts:
        return x + _swiglu(h, lp["mlp_gate"][i], lp["mlp_up"][i], lp["mlp_down"][i]), None
    weights, margin = _route(h, lp["router"][i], top_k=top_k)
    out = jnp.zeros_like(x)
    for e in range(n_experts):  # one expert upcast at a time
        y = _swiglu(h, lp["moe_gate"][i, e], lp["moe_up"][i, e], lp["moe_down"][i, e])
        out = out + weights[:, e:e + 1] * y
    return x + out, margin


def forward_logits(params, tokens, *, n_layers, n_heads, n_kv_heads, rope_theta,
                   norm_eps, n_experts=0, top_k_experts=2, tie_embeddings=False,
                   positions=None, return_margins=False):
    """Float32 logits ``[len(positions), vocab]`` of a full causal forward
    over ``tokens`` (a 1-D sequence), at ``positions`` (default: all). With
    ``return_margins`` also each position's smallest routing margin over the
    layers (``inf`` for a dense model): see ``_route``."""
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        margins = jnp.full((tokens.shape[0],), jnp.inf, F32)
        for i in range(n_layers):
            x, margin = _layer(x, params["layers"], i, n_heads=n_heads, n_kv_heads=n_kv_heads,
                               theta=float(rope_theta), eps=float(norm_eps),
                               n_experts=n_experts, top_k=top_k_experts)
            if margin is not None:
                margins = jnp.minimum(margins, margin)
        x = _rms_norm(x, _f32(params["norm"]), norm_eps)
        if positions is not None:
            x, margins = x[jnp.asarray(positions)], margins[jnp.asarray(positions)]
        head = params["embed"].T if tie_embeddings else params["lm_head"]
        logits = x @ _f32(head)
        return (logits, margins) if return_margins else logits
