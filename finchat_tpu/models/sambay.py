"""The layer kinds of a ``layer_plan`` (``models/llama.py``): the
decoder-hybrid-decoder of Phi-4-mini-flash-reasoning (SambaY). A plan is a
list of SEGMENTS, each one period of kinds and its repeats; the model is three
of them — 8 x (``mamba1``, ``sliding_attention``), 1 x (``mamba1``,
``full_attention``), 7 x (``gmu``, ``cross_attention``)::

    x <- x + Mixer_l(LN1_l(x));  x <- x + W_down [SiLU(g) * u],  [g | u] = W_in LN2_l(x)

The block has ONE form, the published model's: LayerNorm with weight and bias,
biases on the attention projections, differential attention. A second model of
plans that wants RMSNorm, no biases or plain heads brings the field and the
branch with it; none stands here unexercised.

    mamba1   [x | z] = W_in h;  x = SiLU(conv_K(x) + b);  [r | B | C] = W_x x
             dt = softplus(W_dt r + b_dt);  A = -exp(A_log)          [E, N]
             S_t = exp(dt_t A) * S_{t-1} + (dt_t x_t) (x) B_t;  y_t = S_t C_t + D x_t
             out = W_out (y_t * SiLU(z_t));  the LAST mamba1 layer's y_t is the memory m_t
    gmu      out = W_out (m_t * SiLU(W_in h_t)): no state, no cache
    attention (window, full, cross alike) differential: pairs of query heads
             (q1, q2) against pairs of key heads (k1, k2) and values [v1 | v2]:
             o = RMSNorm((softmax(q1 k1) - lam softmax(q2 k2)) V) (1 - lam_init)
             a ``sliding_attention`` layer masks ``t - j >= window`` and owns a
             bounded page list of its own; a ``cross_attention`` layer has W_q
             and W_o only and reads the pages the last ``full_attention`` layer
             wrote for the same row (YOCO: one cache, many readers)

The differential form needs no kernel of its own: a cache row read as KV heads
of ``2 x 64`` (``[k1 | k2]``, ``[v1 | v2]``: the same bytes), ``q1`` as
``[q1 | 0]`` and ``q2`` as ``[0 | q2]``, is plain grouped-query attention at
head 128, whose two results a pair are subtracted and normed here. So
``LlamaConfig.n_heads`` / ``n_kv_heads`` / ``head_dim`` of such a model are the
KERNEL's (40 / 10 / 128 for the published 40 / 20 / 64).

The Mamba-1 state is kept ``[layers, slots, 1, N, E]`` float32: the channels
along the lanes (``[E, N]`` would pad 16 lanes to 128 in HBM, eight times the
bytes). A prompt's tokens advance it one at a time in a loop that carries the
state and stops at the longest row's length: nothing of ``[tokens, E, N]`` is
ever materialised.
"""

from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import Array, lax

from finchat_tpu.models.quant import dense, flat_fence
from finchat_tpu.models.ssm import SsmRows, _read, _to_packed, _to_rows, _write, causal_conv

FULL = "full_attention"
WINDOW, MAMBA1, GMU, CROSS = "sliding_attention", "mamba1", "gmu", "cross_attention"
PLAN_KINDS = (FULL, WINDOW, MAMBA1, GMU, CROSS)
ATTENTION_KINDS = (WINDOW, FULL, CROSS)  # layers with W_q and W_o
KV_KINDS = (WINDOW, FULL)  # layers with W_k and W_v: the ones that own pages


def kinds_of(plan) -> list[str]:
    """The plan's layers in order."""
    return [kind for period, repeats in plan for _ in range(repeats) for kind in period]


def validate(c) -> None:
    """``LlamaConfig.__post_init__``'s checks of a ``layer_plan``."""
    kinds = kinds_of(c.layer_plan)
    bad = [p for p in c.layer_plan
           if len(p) != 2 or not p[0] or not isinstance(p[1], int) or p[1] < 1]
    if bad or set(kinds) - set(PLAN_KINDS):
        raise ValueError(
            f"layer_plan {c.layer_plan}: segments of (kinds of one period, repeats >= 1); "
            f"kinds are {', '.join(repr(k) for k in PLAN_KINDS)}")
    if len(kinds) != c.n_layers:
        raise ValueError(f"layer_plan names {len(kinds)} layers, n_layers is {c.n_layers}")
    if (WINDOW in kinds) != bool(c.window):
        raise ValueError(f"window and {WINDOW!r} layers in layer_plan go together")
    if (MAMBA1 in kinds) != bool(c.m1_inner):
        raise ValueError(f"m1_inner and {MAMBA1!r} layers in layer_plan go together")
    for first, then in ((MAMBA1, GMU), (FULL, CROSS)):
        if then in kinds and first not in kinds[:kinds.index(then)]:
            raise ValueError(f"a {then!r} layer reads what a {first!r} layer before it computed")
    if kinds.count(FULL) > 1 and CROSS in kinds:
        raise ValueError(f"{CROSS!r} layers read the ONE {FULL!r} layer's pages")
    if c.n_heads % 2 or c.head_dim % 2:
        raise ValueError("differential attention pairs heads: n_heads and head_dim are even")


def stack_kinds(name: str) -> tuple[str, ...]:
    """The kinds of layer that have the stacked leaf ``name``: its depth is
    the count of those layers, in the plan's order."""
    if name.startswith("m1_"):
        return (MAMBA1,)
    if name.startswith("gmu_"):
        return (GMU,)
    if name.startswith(("attn_k", "attn_v")):
        return KV_KINDS
    return ATTENTION_KINDS if name.startswith("attn_") else PLAN_KINDS


def n_params(c) -> int:
    """Analytic parameter count of a model with a ``layer_plan``."""
    kinds = kinds_of(c.layer_plan)
    d, E, N, R = c.dim, c.m1_inner, c.m1_state, c.m1_dt_rank
    # the published query heads are half the kernel's width
    hq, hkv = c.n_heads * c.head_dim // 2, c.n_kv_heads * c.head_dim
    norm = 2 * d  # weight and bias
    # projections and biases, lambda's four vectors, the sub-norm's weight
    qo = d * hq + hq * d + hq + d + 4 * c.head_dim // 2 + c.head_dim
    kv = 2 * d * hkv + 2 * hkv
    m1 = d * 2 * E + E * (R + 2 * N) + R * E + E + N * E + (c.m1_conv + 1) * E + E + E * d
    per = {MAMBA1: m1, GMU: 2 * d * E, CROSS: qo, FULL: qo + kv, WINDOW: qo + kv}
    total = c.vocab_size * d + norm + sum(per[k] + 3 * d * c.hidden_dim + 2 * norm for k in kinds)
    return total if c.tie_embeddings else total + d * c.vocab_size


def init_layers(c, key: Array, rand_init: Callable) -> dict[str, Array]:
    """``params["layers"]`` of a model with a ``layer_plan``: stacks by the
    kinds that have the leaf (``stack_kinds``)."""
    kinds = kinds_of(c.layer_plan)
    L, d, F = len(kinds), c.dim, c.hidden_dim
    n_qo = sum(k in ATTENTION_KINDS for k in kinds)
    n_kv = sum(k in KV_KINDS for k in kinds)
    hq, hkv = c.n_heads * c.head_dim // 2, c.n_kv_heads * c.head_dim
    ks = jax.random.split(key, 16)
    out: dict[str, Array] = {
        "ln_attn": jnp.ones((L, d), c.dtype), "ln_mlp": jnp.ones((L, d), c.dtype),
        "mlp_in": rand_init("mlp_in", ks[0], (L, d, 2 * F), d),
        "mlp_down": rand_init("mlp_down", ks[1], (L, F, d), F),
        "attn_q": rand_init("attn_q", ks[2], (n_qo, d, hq), d),
        "attn_o": rand_init("attn_o", ks[3], (n_qo, hq, d), hq),
        "attn_k": rand_init("attn_k", ks[4], (n_kv, d, hkv), d),
        "attn_v": rand_init("attn_v", ks[5], (n_kv, d, hkv), d),
    }

    def small(k, shape):  # trained from zero in the published model; drawn so that they act
        return (0.1 * jax.random.normal(k, shape, jnp.float32)).astype(c.dtype)

    kb = jax.random.split(ks[7], 4)
    out.update(
        ln_attn_b=small(ks[6], (L, d)), ln_mlp_b=small(jax.random.fold_in(ks[6], 1), (L, d)),
        attn_q_b=small(kb[0], (n_qo, hq)), attn_o_b=small(kb[1], (n_qo, d)),
        attn_k_b=small(kb[2], (n_kv, hkv)), attn_v_b=small(kb[3], (n_kv, hkv)),
        # lambda's four vectors (lq1, lk1, lq2, lk2) and the sub-norm's weight
        attn_lam=small(ks[8], (n_qo, 4, c.head_dim // 2)).astype(jnp.float32),
        attn_subln=jnp.ones((n_qo, c.head_dim), c.dtype))
    n_m1, n_gmu = kinds.count(MAMBA1), kinds.count(GMU)
    if n_m1:
        E, N, R, K = c.m1_inner, c.m1_state, c.m1_dt_rank, c.m1_conv
        # Mamba-1's published initialisation: A = -(1 .. N) in every channel,
        # dt in [1e-3, 1e-1] log-uniform through the inverse softplus, D = 1
        dt = jnp.exp(jax.random.uniform(ks[9], (n_m1, E), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        out.update(
            m1_in=rand_init("m1_in", ks[10], (n_m1, d, 2 * E), d),
            m1_x=rand_init("m1_x", ks[11], (n_m1, E, R + 2 * N), E),
            m1_dt=rand_init("m1_dt", ks[12], (n_m1, R, E), R),
            m1_out=rand_init("m1_out", ks[13], (n_m1, E, d), E),
            m1_conv_w=jax.random.uniform(ks[14], (n_m1, K, E), jnp.float32,
                                         -1.0, 1.0).astype(c.dtype) * K ** -0.5,
            m1_conv_b=jax.random.uniform(jax.random.fold_in(ks[14], 1), (n_m1, E), jnp.float32,
                                         -1.0, 1.0).astype(c.dtype) * K ** -0.5,
            m1_A_log=jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[None, :, None], (n_m1, N, E)),
            m1_dt_b=dt + jnp.log(-jnp.expm1(-dt)),
            m1_D=jnp.ones((n_m1, E), jnp.float32),
        )
    if n_gmu:
        E = c.m1_inner
        out.update(gmu_in=rand_init("gmu_in", ks[15], (n_gmu, d, E), d),
                   gmu_out=rand_init("gmu_out", jax.random.fold_in(ks[15], 1), (n_gmu, E, d), E))
    return out


@jax.named_scope("norm")
def layer_norm(x: Array, weight: Array, bias: Array, eps: float) -> Array:
    """LayerNorm with weight and bias, float32 inside."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * lax.rsqrt(jnp.var(x32, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * weight + bias


SCAN_UNROLL = 4  # tokens a trip of the scan's loop: the state stays on chip between them


def _scan_tokens(state: Array, x: Array, dt: Array, A: Array, Bm: Array, Cm: Array,
                 n_steps: Array) -> tuple[Array, Array]:
    """The recurrence over the first ``n_steps`` of S tokens, one at a time,
    the state ``[n, N, E]`` carried: x, dt ``[n, S, E]``, Bm, Cm ``[n, S, N]``
    (dt 0 = inert); returns ``(y [n, S, E] without the skip, state)``. A trip
    of the loop advances ``SCAN_UNROLL`` tokens (where S is a multiple of it)
    so that XLA keeps the state in registers between them: 11.0 -> 5.0 ms a
    layer at 32 rows x 256 tokens on the v5e, the same bits (PERF.md section
    6, PR 42); tokens past ``n_steps`` in the last trip ride with dt 0."""
    n, S = x.shape[:2]
    U = SCAN_UNROLL if S % SCAN_UNROLL == 0 else 1
    # time-major, in trips: [S / U, U, n, ...]
    xs = tuple(jnp.moveaxis(t, 1, 0).reshape(S // U, U, n, t.shape[-1]) for t in (x, dt, Bm, Cm))

    def body(b, carry):
        state, y = carry
        x_b, dt_b, B_b, C_b = (lax.dynamic_index_in_dim(a, b, 0, keepdims=False) for a in xs)
        ys = []
        for u in range(U):
            state = (jnp.exp(dt_b[u][:, None, :] * A[None]) * state
                     + (dt_b[u] * x_b[u])[:, None, :] * B_b[u][:, :, None])
            ys.append(jnp.sum(state * C_b[u][:, :, None], axis=1))
        return state, lax.dynamic_update_index_in_dim(y, jnp.stack(ys), b, 0)

    state, y = lax.fori_loop(0, (n_steps + U - 1) // U, body, (state, jnp.zeros_like(xs[0])))
    return jnp.moveaxis(y.reshape(S, n, -1), 0, 1), state


def mamba1(h: Array, lp: dict[str, Any], c, cache: Any, layer_idx: Array,
           rows: SsmRows | None, qm_backend: str | None = None) -> tuple[Array, Array, Any]:
    """The Mamba-1 mixer over the normed input ``h`` [B,S,D]: its output, the
    recurrence's ``y`` before the gate (the memory a GMU reads) and the
    updated ``cache`` (``(state [L,slots,1,N,E], conv tail [L,slots,K-1,E])``
    float32, or None: every row from zero, nothing kept)."""
    f32 = jnp.float32
    E, N, R = c.m1_inner, c.m1_state, c.m1_dt_rank
    with jax.named_scope("m1_in"):
        x, z = jnp.split(dense(h, lp["m1_in"], qm_backend=qm_backend), 2, axis=-1)
    packed = rows is not None and rows.pack is not None
    if packed:
        x = _to_rows(x[0], rows)
    n, S = x.shape[:2]
    if rows is None:
        rows = SsmRows(None, jnp.full((n,), S, jnp.int32))
    with jax.named_scope("m1_conv"):
        tail = (jnp.zeros((n, c.m1_conv - 1, E), f32) if cache is None
                else _read(cache[1], layer_idx, rows))
        x, tail = causal_conv(x.astype(f32), tail, rows.n_valid, lp["m1_conv_w"].astype(f32),
                              lp["m1_conv_b"].astype(f32))
        if cache is not None:
            conv_state = _write(cache[1], tail, layer_idx, rows)
        x = x.astype(h.dtype)  # the projections below read the model's dtype
    with jax.named_scope("m1_scan"):
        r, Bm, Cm = jnp.split(dense(x, lp["m1_x"], qm_backend=qm_backend).astype(f32),
                              [R, R + N], axis=-1)
        live = jnp.arange(S, dtype=jnp.int32)[None, :] < rows.n_valid[:, None]
        dt = dense(r.astype(h.dtype), lp["m1_dt"], qm_backend=qm_backend).astype(f32)
        dt = jnp.where(live[..., None], jax.nn.softplus(dt + lp["m1_dt_b"]), 0.0)
        A = -jnp.exp(lp["m1_A_log"])  # [N, E]
        x32 = x.astype(f32)
        state = (jnp.zeros((n, N, E), f32) if cache is None
                 else _read(cache[0], layer_idx, rows).reshape(n, N, E))
        if S == 1:
            d0 = dt[:, 0]
            state = (jnp.exp(d0[:, None, :] * A[None]) * state
                     + (d0 * x32[:, 0])[:, None, :] * Bm[:, 0, :, None])
            y = jnp.sum(state * Cm[:, 0, :, None], axis=1)[:, None]
        else:
            y, state = _scan_tokens(state, x32, dt, A, Bm, Cm,
                                    jnp.minimum(jnp.max(rows.n_valid), S))
        y = y + lp["m1_D"] * x32
        if cache is not None:
            cache = (_write(cache[0], state.reshape(n, 1, N, E), layer_idx, rows), conv_state)
    if packed:
        y = _to_packed(y, rows)[None]
    with jax.named_scope("m1_out"):
        gated = (y * jax.nn.silu(z.astype(f32))).astype(h.dtype)
        out = dense(gated, lp["m1_out"], qm_backend=qm_backend)
    return out, y.astype(h.dtype), cache


@jax.named_scope("gmu")
def gmu(h: Array, memory: Array, lp: dict[str, Any], qm_backend: str | None = None) -> Array:
    """The gated memory unit: the memory of the SAME token, gated by this
    layer's input and projected back."""
    gate = jax.nn.silu(dense(h, lp["gmu_in"], qm_backend=qm_backend).astype(jnp.float32))
    return dense((memory.astype(jnp.float32) * gate).astype(h.dtype), lp["gmu_out"],
                 qm_backend=qm_backend)


def lambda_init(depth: Array | int) -> Array:
    """Differential attention's ``lam_init`` of the layer at ``depth``."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, jnp.float32))


def attention(h: Array, lp: dict[str, Any], c, attention_fn: Callable, cache: Any,
              cache_idx: Array, kind: str, depth: Array | int,
              qm_backend: str | None = None) -> tuple[Array, Any]:
    """An attention layer of a plan over the normed input ``h``: projections
    with their biases, the callback — ``attention_fn(q, k, v, cache,
    cache_idx, kind)``; ``k`` and ``v`` None for a CROSS layer, which writes
    nothing and reads index ``cache_idx`` of the FULL layer's pool — and the
    differential pairing around it."""
    B, S, _ = h.shape
    H, Hkv, hd = c.n_heads, c.n_kv_heads, c.head_dim

    def project(name: str) -> Array:
        return dense(h, lp[name], qm_backend=qm_backend) + lp[name + "_b"]

    with jax.named_scope("attn_qkv"):
        # query head 2p is q1 of pair p, 2p + 1 its q2: [q1 | 0] and [0 | q2]
        q = flat_fence(project("attn_q")).reshape(B, S, H // 2, 2, hd // 2)
        zero = jnp.zeros_like(q[..., :1, :])
        q = jnp.concatenate(
            [jnp.concatenate([q[..., :1, :], zero], axis=-1),
             jnp.concatenate([zero, q[..., 1:, :]], axis=-1)], axis=-2).reshape(B, S, H, hd)
        k = v = None
        if kind != CROSS:
            k = flat_fence(project("attn_k")).reshape(B, S, Hkv, hd)
            v = project("attn_v").reshape(B, S, Hkv, hd)
    # the callback opens its own scopes (engine/engine.py)
    out, cache = attention_fn(q, k, v, cache, cache_idx, kind)
    with jax.named_scope("attn_diff"):
        lam_init = lambda_init(depth)
        lq1, lk1, lq2, lk2 = lp["attn_lam"].astype(jnp.float32)
        lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam_init
        o = out.astype(jnp.float32).reshape(B, S, H // 2, 2, hd)
        o = o[..., 0, :] - lam * o[..., 1, :]
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + c.norm_eps)
        out = (o * lp["attn_subln"].astype(jnp.float32) * (1.0 - lam_init)).astype(h.dtype)
    with jax.named_scope("attn_o"):
        proj = dense(out.reshape(B, S, -1), lp["attn_o"], qm_backend=qm_backend)
        return proj + lp["attn_o_b"], cache


def layer(x: Array, lp: dict[str, Any], c, *, kind: str, depth: Array | int,
          attention_fn: Callable, cache: Any, cache_idx: Array, ssm_cache: Any,
          state_idx: Array, ssm_rows: SsmRows | None, memory: Array | None,
          keep_memory: bool, qm_backend: str | None = None
          ) -> tuple[Array, Any, Any, Array | None]:
    """One layer of a plan: ``(x, cache, ssm_cache, memory)``. ``cache_idx``
    is the layer's index in ITS pool (a CROSS layer's: the FULL layer's),
    ``state_idx`` among the layers with state, ``depth`` down the model."""
    def norm(x: Array, name: str) -> Array:
        return layer_norm(x, lp[name], lp[name + "_b"], c.norm_eps)

    h = norm(x, "ln_attn")
    if kind == MAMBA1:
        mixed, y, ssm_cache = mamba1(h, lp, c, ssm_cache, state_idx, ssm_rows, qm_backend)
        if keep_memory:
            memory = y
    elif kind == GMU:
        mixed = gmu(h, memory, lp, qm_backend)
    else:
        mixed, cache = attention(h, lp, c, attention_fn, cache, cache_idx, kind, depth,
                                 qm_backend)
    x = x + mixed
    with jax.named_scope("mlp"):
        gate, up = jnp.split(dense(norm(x, "ln_mlp"), lp["mlp_in"], qm_backend=qm_backend),
                             2, axis=-1)
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(up.dtype) * up
        x = x + dense(act, lp["mlp_down"], qm_backend=qm_backend)
    return x, cache, ssm_cache, memory
