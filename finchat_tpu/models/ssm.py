"""The Mamba-2 mixer that ``models/llama.py``'s block runs beside attention
when the config has ``ssm_heads`` (Falcon-H1: both mixers read one normed
input in parallel and their outputs are summed into the residual).

Per head ``h`` of ``H`` (B/C group ``g = h // (H / G)``), state ``S`` in
``R^{P x N}``::

    S_t = exp(dt_t A) S_{t-1} + dt_t xs_t (x) B_t^(g)
    y_t = S_t C_t^(g) + D xs_t

A single token is that recurrence as written (``_step``; the decode step's
whole slot batch on a kernel backend: ``ops/ssm_step.py``, the same update in
one in-place pass over the state); a chunk of tokens is the same recurrence
in the state-space-duality form (``_chunked``):
matmuls inside blocks of ``ssm_chunk`` tokens, the state passed from block to
block. Padding tokens ride with ``dt = 0``: the state passes through them
unchanged, so the state a row leaves is the state after its last REAL token,
wherever the block or the buffer ends.

The recurrent state and the conv's tail are per-SLOT device state
(``engine.DecodeState.ssm_state`` / ``conv_state``), held by slot and not by
page. ``SsmRows`` tells the mixer which slot each batch row starts from and
leaves its state in; with no cache (the cache-less forward) every row starts
from zero and nothing is kept. All of it is float32 from the projection's
output to the gated norm: the state is an accumulator over the whole
context. The carried state lies as the one-token kernel wants it
(``ops/ssm_step.py`` ``stored_shape``: heads narrower than a lane tile in
pairs, the state axis on sublanes — Granite's 128 heads of 64 x 128 as 64
tiles of 128 x 128); the decode step's whole slot batch on a kernel backend
advances it where it lies, every other path here — the chunked form, ``_step``
on ``ref`` or over gathered slots — reads and writes ``[N, H, P, Ns]`` through
``to_logical`` / ``to_stored``, as ``models/gdn.py`` does through ``_heads`` /
``_tiles``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array, lax

from finchat_tpu.models.quant import dense
from finchat_tpu.ops.ssm_step import ssm_state_step, to_logical, to_stored
from finchat_tpu.utils.metrics import METRICS

_HIGHEST = lax.Precision.HIGHEST


@dataclass
class SsmRows:
    """How a dispatch's batch rows map onto the engine's slots.

    ``slots`` [N]: the slot each row's state is read from and written to;
    None = row i IS slot i (the decode step's whole slot batch: no gather
    and no scatter — the conv's tail is sliced and updated in place, and the
    recurrent state advances inside the carried array: ``ops/ssm_step.py``'s
    kernel, or on ``ref`` a slice, ``_step`` and an in-place update).
    ``n_valid`` [N]:
    the row's real tokens; a row with 0 rides inert (state and tail
    untouched, whatever its slot — padding rows repeat a live row's slot).
    ``pack``: the tokens arrive as ONE packed buffer ``[1, T]`` (the ragged
    step) and are regrouped to ``[N, width]`` rows for the conv and the scan
    — neither may run across a row boundary — ``(q_start [N], tok_row [T],
    tok_off [T])``. ``backend``: the kernel backend the engine resolved for
    its step (``attn_backend``: ``pallas``, ``pallas-interpret`` or ``ref``)."""

    slots: Array | None
    n_valid: Array
    pack: tuple[Array, Array, Array] | None = None
    width: int = 0
    backend: str = "ref"


def scaled(x: Array, m: float) -> Array:
    """``x * m`` for a µP scalar; a multiplier of 1 emits nothing, so a
    config without multipliers compiles to the program it always was."""
    return x if m == 1.0 else x * jnp.asarray(m, x.dtype)


def mup_vector(c) -> np.ndarray | None:
    """The five ``ssm_multipliers`` spread over the projection's output
    ``[z | xs | B | C | dt]``; None when all are 1."""
    if all(m == 1.0 for m in c.ssm_multipliers):
        return None
    gn = c.ssm_groups * c.ssm_state
    widths = (c.d_ssm, c.d_ssm, gn, gn, c.ssm_heads)
    return np.concatenate([np.full((w,), m, np.float32)
                           for w, m in zip(widths, c.ssm_multipliers)])


def _to_rows(x: Array, rows: SsmRows) -> Array:
    """Packed ``[T, ...]`` → ``[N, width, ...]``; columns past a row's length
    hold another row's tokens and ride masked (``n_valid``)."""
    q_start = rows.pack[0]
    idx = q_start[:, None] + jnp.arange(rows.width, dtype=jnp.int32)[None, :]
    return x[jnp.minimum(idx, x.shape[0] - 1)]


def _to_packed(y: Array, rows: SsmRows) -> Array:
    """``[N, width, ...]`` → packed ``[T, ...]`` (buffer padding reads row N-1)."""
    _q_start, tok_row, tok_off = rows.pack
    r = jnp.minimum(tok_row, y.shape[0] - 1)
    return y[r, jnp.clip(tok_off, 0, rows.width - 1)]


def _read(leaf: Array, layer_idx: Array, rows: SsmRows) -> Array:
    layer = lax.dynamic_index_in_dim(leaf, layer_idx.reshape(()), 0, keepdims=False)
    return layer if rows.slots is None else jnp.take(layer, rows.slots, axis=0)


def _write(leaf: Array, new: Array, layer_idx: Array, rows: SsmRows) -> Array:
    new = new.astype(leaf.dtype)
    if rows.slots is None:
        # inert slots already hold their old value in `new` (dt 0, old tail)
        return lax.dynamic_update_index_in_dim(leaf, new, layer_idx.reshape(()), 0)
    # an inert row must not race the live row whose slot it repeats
    slot = jnp.where(rows.n_valid > 0, rows.slots, leaf.shape[1])
    return leaf.at[layer_idx.reshape(()), slot].set(new, mode="drop")


def causal_conv(x: Array, tail: Array, n_valid: Array, w: Array, b: Array | None
                ) -> tuple[Array, Array]:
    """Depthwise causal conv of width K with bias (None = none), then SiLU.
    ``x`` [N,S,C]; ``tail`` [N,K-1,C] the row's last K-1 inputs before ``x``;
    ``w`` [K,C] with ``w[K-1]`` on the current token. Returns (out [N,S,C],
    the K-1 inputs ending at the row's last real token)."""
    K, S = w.shape[0], x.shape[1]
    full = jnp.concatenate([tail, x], axis=1)  # [N, K-1+S, C]
    bias = 0.0 if b is None else b[None, None, :]
    out = bias + sum(full[:, k:k + S] * w[k][None, None, :] for k in range(K))
    idx = n_valid[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    new_tail = jnp.take_along_axis(full, idx[:, :, None], axis=1)
    return jax.nn.silu(out), new_tail


def _step(state, xs, dt, A, Bm, Cm, D):
    """One token. state [N,G,Hg,P,Ns]; xs [N,G,Hg,P]; dt [N,G,Hg] (0 = inert);
    Bm, Cm [N,G,Ns]. ``y`` is written over the OLD state, S_t C = exp(dt A)
    (S_{t-1} C) + dt (B.C) xs, so that the state need be read only once; XLA
    reads it twice all the same (two fusions), which is why the decode step
    of a kernel backend takes ``ops/ssm_step.py`` instead."""
    dA = jnp.exp(dt * A)
    Bb, Cb = Bm[:, :, None, None, :], Cm[:, :, None, None, :]
    new = state * dA[..., None, None] + (dt[..., None] * xs)[..., None] * Bb
    y = (dA[..., None] * jnp.sum(state * Cb, axis=-1)
         + (dt * jnp.sum(Bm * Cm, axis=-1)[:, :, None])[..., None] * xs
         + D[None, :, :, None] * xs)
    return y, new


def _chunked(state, xs, dt, A, Bm, Cm, D, chunk: int):
    """S tokens in blocks of ``chunk``. state [N,G,Hg,P,Ns]; xs [N,S,G,Hg,P];
    dt [N,S,G,Hg]; Bm, Cm [N,S,G,Ns]."""
    n, S = xs.shape[:2]
    Q = min(chunk, S)
    pad = -S % Q
    if pad:  # dt 0: the state passes through unchanged
        xs, dt, Bm, Cm = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                          for t in (xs, dt, Bm, Cm))

    def blocks(t):  # [N, S, ...] -> [S/Q, N, Q, ...]
        return jnp.moveaxis(t.reshape(n, -1, Q, *t.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None, None]

    def block(state, blk):
        x, d, Bq, Cq = blk
        cum = jnp.cumsum(d * A, axis=1)  # [N,Q,G,Hg], <= 0, inclusive
        # decay from token j to token i, i >= j: the difference first, so
        # that nothing overflows
        seg = cum[:, :, None] - cum[:, None, :]  # [N,Qi,Qj,G,Hg]
        L = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
        cb = jnp.einsum("nigs,njgs->nijg", Cq, Bq, precision=_HIGHEST)
        w = L * cb[..., None] * d[:, None]  # dt_j
        y = jnp.einsum("nijgh,njghp->nighp", w, x, precision=_HIGHEST)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "nigs,nghps->nighp", Cq, state, precision=_HIGHEST)
        to_end = jnp.exp(cum[:, -1:] - cum) * d  # [N,Q,G,Hg]
        state = state * jnp.exp(cum[:, -1])[..., None, None] + jnp.einsum(
            "njghp,njgs->nghps", x * to_end[..., None], Bq, precision=_HIGHEST)
        return state, y + D[None, None, :, :, None] * x

    state, y = lax.scan(block, state, tuple(blocks(t) for t in (xs, dt, Bm, Cm)))
    y = jnp.moveaxis(y, 0, 1).reshape(n, -1, *y.shape[3:])
    return y[:, :S], state


def gated_norm(y: Array, z: Array, weight: Array, groups: int, eps: float) -> Array:
    """``RMSNorm_grouped(y * SiLU(z))``: the gate first
    (``mamba_norm_before_gate`` false), the norm over ``groups`` equal parts."""
    g = y * jax.nn.silu(z)
    parts = g.reshape(*g.shape[:-1], groups, -1)
    parts = parts * lax.rsqrt(jnp.mean(parts * parts, axis=-1, keepdims=True) + eps)
    return parts.reshape(g.shape) * weight.astype(jnp.float32)


def mixer(h: Array, lp: dict[str, Any], c, cache: Any, layer_idx: Array,
          rows: SsmRows | None, qm_backend: str | None = None) -> tuple[Array, Any]:
    """The mixer's output for the normed input ``h`` [B,S,D] and the updated
    ``cache`` (``(ssm_state [L,slots,*stored_shape(H,P,Ns,G)], conv_state
    [L,slots,K-1,C])`` float32, or None: every row from zero, nothing kept)."""
    f32 = jnp.float32
    H, P, Ns, G = c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_groups
    Hg, gn = H // G, G * Ns
    with jax.named_scope("ssm_in"):
        u = dense(scaled(h, c.ssm_in_multiplier), lp["ssm_in"],
                  qm_backend=qm_backend).astype(f32)
        mup = mup_vector(c)
        if mup is not None:
            u = u * mup
        z, xbc, dt = jnp.split(u, [c.d_ssm, 2 * c.d_ssm + 2 * gn], axis=-1)
    packed = rows is not None and rows.pack is not None
    if packed:
        xbc, dt = _to_rows(xbc[0], rows), _to_rows(dt[0], rows)
    n, S = xbc.shape[:2]
    if rows is None:
        rows = SsmRows(None, jnp.full((n,), S, jnp.int32))
    if cache is None:
        state = jnp.zeros((n, H, P, Ns), f32)
        tail = jnp.zeros((n, c.ssm_conv - 1, xbc.shape[-1]), f32)
    with jax.named_scope("ssm_conv"):
        if cache is not None:
            tail = _read(cache[1], layer_idx, rows)
        xbc, tail = causal_conv(xbc, tail, rows.n_valid, lp["ssm_conv_w"].astype(f32),
                                lp["ssm_conv_b"].astype(f32))
        if cache is not None:
            conv_state = _write(cache[1], tail, layer_idx, rows)
    with jax.named_scope("ssm_scan"):
        xs, Bm, Cm = jnp.split(xbc, [c.d_ssm, c.d_ssm + gn], axis=-1)
        live = jnp.arange(S, dtype=jnp.int32)[None, :] < rows.n_valid[:, None]
        dt = jnp.where(live[..., None], jax.nn.softplus(dt + lp["ssm_dt_bias"]), 0.0)
        A = -jnp.exp(lp["ssm_A_log"])
        Bm, Cm = Bm.reshape(n, S, G, Ns), Cm.reshape(n, S, G, Ns)
        one_token = S == 1 and cache is not None and rows.backend != "ref"
        if one_token and rows.slots is None:
            # the decode step: every slot's state advances where it lies
            y, state = ssm_state_step(
                cache[0], xs.reshape(n, H, P), dt[:, 0], A, Bm[:, 0], Cm[:, 0],
                lp["ssm_D"], layer_idx.reshape(1),
                interpret=rows.backend == "pallas-interpret")
            cache = (state, conv_state)
        else:
            if one_token:  # gathered slots: XLA's two passes over the state
                METRICS.inc("finchat_ssm_step_fallbacks_total")
            if cache is not None:  # the carried state as the device holds it (ops/ssm_step.py)
                state = to_logical(_read(cache[0], layer_idx, rows), (H, P, Ns), G)
            A, D = A.reshape(G, Hg), lp["ssm_D"].reshape(G, Hg)
            xs = xs.reshape(n, S, G, Hg, P)
            dt = dt.reshape(n, S, G, Hg)
            state = state.reshape(n, G, Hg, P, Ns)
            if S == 1:
                y, state = _step(state, xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D)
            else:
                y, state = _chunked(state, xs, dt, A, Bm, Cm, D, c.ssm_chunk)
            if cache is not None:
                cache = (_write(cache[0], to_stored(state.reshape(n, H, P, Ns), G), layer_idx, rows),
                         conv_state)
        y = y.reshape(n, S, c.d_ssm)
    if packed:
        y = _to_packed(y, rows)[None]
    with jax.named_scope("ssm_norm"):
        y = gated_norm(y, z, lp["ssm_norm"], G, c.norm_eps).astype(h.dtype)
    with jax.named_scope("ssm_out"):
        out = scaled(dense(y, lp["ssm_out"], qm_backend=qm_backend), c.ssm_out_multiplier)
    return out, cache
