"""The gated delta rule: the mixer of a ``linear_attention`` layer
(``models/llama.py`` ``layer_pattern``; Olmo-Hybrid runs three such layers to
one of full attention). It stands in attention's place and owns no pages: its
memory is a matrix a head, by slot, beside the Mamba-2 mixer's in
``engine.DecodeState`` (``models/ssm.py``, whose conv, row packing and slot
reads and writes it shares).

Per head ``h`` of ``H``, state ``S`` in ``R^{dk x dv}`` float32, input ``x``::

    [q~ | k~ | v~] = SiLU(causal_depthwise_conv_K([W_q x | W_k x | W_v x]))
    q = (q~_h / |q~_h|) dk^-1/2,   k = k~_h / |k~_h|
    beta = sigmoid(w_b,h . x)                  (x 2 with ``gdn_neg_eigval``)
    g = -exp(A_log,h) softplus(w_a,h . x + dt_bias,h),   alpha = exp(g)
    S~ = alpha S_{t-1};   u = beta (v - S~^T k);   S_t = S~ + k u^T;   o = S_t^T q
    y = W_o [ RMSNorm_dv(o_h) * SiLU((W_g x)_h) ]_h

Unlike Mamba-2's update this one READS the state before it writes it (``S~^T
k`` feeds the rank-one correction). A single token is the recurrence as
written (``_step``); a chunk of tokens is the same recurrence in the WY /
UT-transform form (``_chunked``): inside a block of ``CHUNK`` tokens the
corrected values ``u`` solve one unit-lower-triangular system (by its
inverse, a product of matmuls), the rest is matmuls, and the state passes
from block to block. A padding token rides with
``alpha = 1, beta = 0``: the state passes through it unchanged.

The ragged step hands its rows over as ONE packed buffer. The conv runs
along that buffer (``_packed_conv``), and the chunked form regroups it to
rows by rank of length (``_packed_scan``): only the rows that CAN hold more
than a block get the row width, every other row one block, so that a round
of one prompt's chunk beside decode rows does not cost a round of prompts.

Two decays, one rule (``LlamaConfig.gdn_gate_rank``). 0: the above, ``alpha``
a scalar a head (Olmo-Hybrid). A rank ``r``: Kimi Delta Attention — the decay a
VECTOR over the head's ``dk`` key channels, through a low-rank projection, and
a sigmoid output gate through another with a bias::

    g = -exp(A_log,h) softplus(W_f2 (W_f1 x) + dt_bias)  in R^{H x dk};  alpha = exp(g)
    beta = sigmoid(w_b,h . x)
    S~ = Diag(alpha) S_{t-1};   u = beta (v - S~^T k);   S_t = S~ + k u^T;   o = S_t^T q
    y = W_o [ RMSNorm_dv(o_h) * sigmoid((W_g2 (W_g1 x) + b_g)_h) ]_h

``g`` then carries a trailing ``dk`` axis through ``_step``, ``_chunked``, the
packed scan and the kernel, which branch on its rank; with ``alpha`` constant
over a head's channels the two are the same numbers. A token's log-decay may
reach -80 a channel, so the chunked form never computes ``exp(-cumsum g)``:
every exponent is a difference ``G_i - G_j <= 0`` (``_decayed_products``).

The state is laid out ``[layers, slots, H / n, dk, n dv]`` float32
(``LlamaConfig.state_shape``): ``n`` heads' matrices side by side along the
lanes of one tile, so that the minor dimension is whole 128-lane tiles
(Olmo-Hybrid: 15 tiles of 96 x 384; ``[.., 96, 192]`` pads 192 to 256 in
HBM). The snapshot, reset and admission programs of the engine take the leaf
as it comes. The decode step's whole slot batch on a kernel backend advances
it where it lies (``ops/gdn_step.py``: this rule in one in-place pass, on the
row-block pipeline it shares with ``ops/ssm_step.py``); every other path —
the chunked form, the packed scan, ``_step`` on ``ref`` or over gathered
slots — reads and writes ``[N, H, dk, dv]`` through ``_heads`` / ``_tiles``.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import Array, lax

from finchat_tpu.models.quant import Q4Tensor, QTensor, dense
from finchat_tpu.models.ssm import SsmRows, _read, _to_packed, _to_rows, _write, causal_conv
from finchat_tpu.ops.gdn_step import gdn_state_step
from finchat_tpu.utils.metrics import METRICS

_HIGHEST = lax.Precision.HIGHEST
_L2_EPS = 1e-6
CHUNK = 64  # tokens a block of the chunked (WY) form
SUB = 16  # tokens a sub-block of a block's channel decays (``_decayed_products``)
_PAIR_BYTES = 64 * 1024 * 1024  # of a sub-block's pairwise decays alive at once


def init_params(c, key: Array, n: int, rand_init: Callable) -> dict[str, Array]:
    """The ``gdn_*`` leaves of ``n`` stacked layers. The projections are the
    block's plain normal draws; the recurrence's own parameters take the
    published initialisation (``A_log = log U(0, 16]``, ``dt_bias`` the
    inverse softplus of ``exp(U[log 1e-3, log 1e-1])``): with plain normal
    draws the state would die in a token or never."""
    ks = jax.random.split(key, 6)
    D, H, K = c.dim, c.gdn_heads, c.gdn_conv
    d_v = H * c.gdn_value_dim
    if c.gdn_gate_rank:
        return _init_channel_params(c, ks, key, n, rand_init)
    dt = jnp.exp(jax.random.uniform(ks[4], (n, H), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    return {
        # [q | k | v | gate] and, apart (60 columns would end the wide matmul
        # off a lane boundary), [b | a]
        "gdn_in": rand_init("gdn_in", ks[0], (n, D, c.gdn_conv_dim + d_v), D),
        "gdn_ba": rand_init("gdn_ba", ks[1], (n, D, 2 * H), D),
        "gdn_out": rand_init("gdn_out", ks[2], (n, d_v, D), d_v),
        "gdn_conv_w": jax.random.uniform(
            ks[3], (n, K, c.gdn_conv_dim), jnp.float32, -1.0, 1.0).astype(c.dtype) * K ** -0.5,
        "gdn_A_log": jnp.log(jax.random.uniform(ks[5], (n, H), jnp.float32, 1e-4, 16.0)),
        "gdn_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "gdn_norm": jnp.ones((n, c.gdn_value_dim), c.dtype),
    }


def _init_channel_params(c, ks, key: Array, n: int, rand_init: Callable) -> dict[str, Array]:
    """``init_params`` with ``gdn_gate_rank``: ``gdn_in`` is [q | k | v] alone;
    ``gdn_low`` the three small projections of the input side by side, [W_f1 |
    W_g1 | w_b] (288 columns at the published sizes: apart from the wide one,
    as ``gdn_ba``); ``gdn_f2`` / ``gdn_g2`` the low-rank projections' second
    halves; ``dt_bias`` one a key channel, ``A_log`` one a head, both at the
    published initialisation; the gate's bias drawn small (a checkpoint's is
    trained; zeros would hide it from every comparison)."""
    D, H, K, r = c.dim, c.gdn_heads, c.gdn_conv, c.gdn_gate_rank
    d_k, d_v = H * c.gdn_key_dim, H * c.gdn_value_dim
    more = jax.random.split(jax.random.fold_in(key, 1), 4)
    dt = jnp.exp(jax.random.uniform(ks[4], (n, d_k), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "gdn_in": rand_init("gdn_in", ks[0], (n, D, c.gdn_conv_dim), D),
        "gdn_low": rand_init("gdn_low", ks[1], (n, D, 2 * r + H), D),
        "gdn_f2": rand_init("gdn_f2", more[0], (n, r, d_k), r),
        "gdn_g2": rand_init("gdn_g2", more[1], (n, r, d_v), r),
        "gdn_g_bias": (0.1 * jax.random.normal(more[2], (n, d_v), jnp.float32)).astype(c.dtype),
        "gdn_out": rand_init("gdn_out", ks[2], (n, d_v, D), d_v),
        "gdn_conv_w": jax.random.uniform(
            ks[3], (n, K, c.gdn_conv_dim), jnp.float32, -1.0, 1.0).astype(c.dtype) * K ** -0.5,
        "gdn_A_log": jnp.log(jax.random.uniform(ks[5], (n, H), jnp.float32, 1e-4, 16.0)),
        "gdn_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt), a channel
        "gdn_norm": jnp.ones((n, c.gdn_value_dim), c.dtype),
    }


def _l2norm(x: Array) -> Array:
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


def _heads(tiles: Array, H: int) -> Array:
    """The leaf's tiles ``[N, H/n, dk, n dv]`` as heads ``[N, H, dk, dv]``."""
    N, T, dk, W = tiles.shape
    n = H // T
    return tiles.reshape(N, T, dk, n, W // n).swapaxes(2, 3).reshape(N, H, dk, W // n)


def _tiles(state: Array, T: int) -> Array:
    """Heads ``[N, H, dk, dv]`` as the leaf's ``T`` tiles ``[N, T, dk, n dv]``."""
    N, H, dk, dv = state.shape
    n = H // T
    return state.reshape(N, T, n, dk, dv).swapaxes(2, 3).reshape(N, T, dk, n * dv)


def _step(state, q, k, v, g, beta):
    """One token. state [N,H,dk,dv]; q, k [N,H,dk]; v [N,H,dv]; g, beta
    [N,H] (0, 0 = inert). ``o`` is read off the OLD state, ``S_t^T q = alpha
    S^T q + (k.q) u``, beside ``S^T k``: one pass reads the state for both
    products and a second rewrites it, where the recurrence as written
    (``S_t^T q`` from the new state) would read it a third time. ``g``
    [N,H,dk]: a decay a key channel, ``S~ = Diag(alpha) S``; the two products
    are then ``S^T (alpha k)`` and ``S^T (alpha q)``, still off the old state."""
    if g.ndim == q.ndim:
        a = jnp.exp(g)
        u = beta[..., None] * (v - jnp.sum(state * (a * k)[..., None], axis=-2))
        new = state * a[..., None] + k[..., None] * u[..., None, :]
        Sq = jnp.sum(state * (a * q)[..., None], axis=-2)
        return Sq + jnp.sum(k * q, axis=-1, keepdims=True) * u, new
    a = jnp.exp(g)[..., None]
    Sk = jnp.sum(state * k[..., None], axis=-2)
    Sq = jnp.sum(state * q[..., None], axis=-2)
    u = beta[..., None] * (v - a * Sk)
    new = state * a[..., None] + k[..., None] * u[..., None, :]
    return a * Sq + jnp.sum(k * q, axis=-1, keepdims=True) * u, new


def _unit_lower_inverse(A: Array) -> Array:
    """``(I + A)^-1`` for strictly lower-triangular ``A`` [..., Q, Q], by
    halves: ``[[T11, 0], [-T22 A21 T11, T22]]`` with the two diagonal halves
    inverted the same way (batched together), down to blocks of 16 rows
    solved by forward substitution. Block forward substitution throughout,
    as stable as the row-by-row solve (keys that repeat under ``beta`` near 2
    make ``A``'s powers overflow long before its nilpotency ends them, so the
    product of ``I + A^(2^i)`` will not do), in a handful of batched matmuls
    where XLA's triangular solve is a loop of Q steps a call (on the TPU a
    custom call a block that took a fifth of a ragged round)."""
    Q = A.shape[-1]
    if Q > 16 and Q % 2 == 0:
        h = Q // 2
        T11, T22 = _unit_lower_inverse(jnp.stack([A[..., :h, :h], A[..., h:, h:]]))
        T21 = -jnp.einsum("...ij,...jk,...kl->...il", T22, A[..., h:, :h], T11,
                          precision=_HIGHEST)
        return jnp.concatenate([jnp.concatenate([T11, jnp.zeros_like(T11)], axis=-1),
                                jnp.concatenate([T21, T22], axis=-1)], axis=-2)
    eye = jnp.eye(Q, dtype=A.dtype)
    rows = [jnp.broadcast_to(eye[0], A.shape[:-2] + (Q,))]
    for i in range(1, Q):  # row i of the inverse from the rows above it
        rows.append(eye[i] - jnp.einsum("...j,...jk->...k", A[..., i, :i],
                                        jnp.stack(rows, axis=-2), precision=_HIGHEST))
    return jnp.stack(rows, axis=-2)


def _blocks(tensors, Q: int):
    """``[N, S, H, ...]`` tensors as blocks ``[S/Q, N, H, Q, ...]``, S padded
    to whole blocks (padding: g 0, beta 0 — the state passes through it
    unchanged)."""
    n, S = tensors[0].shape[:2]
    pad = -S % Q
    if pad:
        tensors = tuple(jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2)) for t in tensors)

    def blocks(t):
        t = jnp.moveaxis(t.reshape(n, -1, Q, *t.shape[2:]), 1, 0)
        return jnp.moveaxis(t, 2, 3)

    return tuple(blocks(t) for t in tensors)


def _scan_blocks(block, state, blks, S: int):
    """``block`` from block to block over ``blks`` ``[S/Q, N, H, ...]`` (one
    block: no loop); returns ``(o [N, S, H, dv], the last state)``."""
    if blks[0].shape[0] == 1:
        state, o = block(state, tuple(t[0] for t in blks))
        o = o[None]
    else:
        state, o = lax.scan(block, state, blks)
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)  # [N, S/Q, Q, H, dv]
    return o.reshape(o.shape[0], -1, *o.shape[3:])[:, :S], state


def _chunked(state, q, k, v, g, beta, chunk: int):
    """S tokens in blocks of ``chunk``, the WY form. state [N,H,dk,dv]; q, k
    [N,S,H,dk]; v [N,S,H,dv]; g, beta [N,S,H]. With ``G_ij = exp(sum g
    (j, i])``, inside a block ``(I + A) U = beta (V - G_i0 K S_0)`` for the
    strictly lower ``A_ij = beta_i G_ij (k_i . k_j)``; then ``O = G_i0 Q S_0
    + (G_ij (q_i . k_j))_{j <= i} U`` and ``S_Q = G_Q0 S_0 + (G_Qj K)^T U``.
    What does not read the state — ``G``, ``(I + A)^-1``, the masked ``q k^T``
    — is computed for all blocks at once; the scan from block to block is
    five matmuls a block. ``g`` [N,S,H,dk]: ``_chunked_channels``."""
    if g.ndim > beta.ndim:
        return _chunked_channels(state, q, k, v, g, beta, chunk)
    S = q.shape[1]
    Q = min(chunk, S)
    qb, kb, vb, gb, bb = _blocks((q, k, v, g, beta), Q)  # gb, bb [B,N,H,Q]
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    strict = jnp.tril(jnp.ones((Q, Q), bool), -1)
    cum = jnp.cumsum(gb, axis=-1)  # <= 0, inclusive
    # decay from token j to token i, i >= j: the difference first, so that
    # nothing overflows
    G = jnp.where(lower, jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :], 0.0)), 0.0)
    kk = jnp.einsum("bnhik,bnhjk->bnhij", kb, kb, precision=_HIGHEST)
    solve = _unit_lower_inverse(jnp.where(strict, bb[..., None] * G * kk, 0.0))
    Gqk = G * jnp.einsum("bnhik,bnhjk->bnhij", qb, kb, precision=_HIGHEST)
    from_start = jnp.exp(cum)[..., None]
    to_end = jnp.exp(cum[..., -1:] - cum)[..., None]
    through = jnp.exp(cum[..., -1])[..., None, None]

    def block(state, blk):
        qb, kb, vb, bb, solve, Gqk, from_start, to_end, through = blk
        rhs = bb[..., None] * (
            vb - from_start * jnp.einsum("nhik,nhkv->nhiv", kb, state, precision=_HIGHEST))
        u = jnp.einsum("nhij,nhjv->nhiv", solve, rhs, precision=_HIGHEST)  # (I + A) U = rhs
        o = (from_start * jnp.einsum("nhik,nhkv->nhiv", qb, state, precision=_HIGHEST)
             + jnp.einsum("nhij,nhjv->nhiv", Gqk, u, precision=_HIGHEST))
        state = state * through + jnp.einsum("nhjk,nhjv->nhkv", kb * to_end, u,
                                             precision=_HIGHEST)
        return state, o

    return _scan_blocks(block, state, (qb, kb, vb, bb, solve, Gqk, from_start, to_end, through), S)


def _decayed_products(x: Array, k: Array, cum: Array) -> Array:
    """``M_ij = sum_c x_ic k_jc exp(cum_ic - cum_jc)`` for ``j <= i``, 0 above
    the diagonal, for each of x's leading pair: x [2, ..., Q, dk] (the keys
    and the queries), k and cum [..., Q, dk], ``cum`` the inclusive running sum
    of the log-decays (<= 0, falling). No exponent is ever positive, whatever
    ``cum`` reaches: inside a sub-block of ``SUB`` tokens the difference is
    taken pair by pair before its ``exp`` (a ``[SUB, SUB, dk]`` product a
    sub-block, made and reduced ``_PAIR_BYTES`` at a time: whole, it is 2 GB
    for a round of 8,192 tokens at 32 heads of 128); across sub-blocks it is
    split at the last token before the later one, ``exp(cum_i - ref) exp(ref -
    cum_j)`` with both factors <= 1, the keys scaled once a sub-block and the
    rest a matmul."""
    Q, dk = k.shape[-2:]
    sub = SUB if Q % SUB == 0 else Q
    n = Q // sub
    lead = k.shape[:-2]

    def subs(t):  # [..., Q, dk] -> [..., n, sub, dk]
        return t.reshape(*t.shape[:-2], n, sub, dk)

    xs, ks, cs = subs(x), subs(k), subs(cum)
    lower = jnp.tril(jnp.ones((sub, sub), bool))[..., None]

    def pairs(item):  # one leading index: xs [2, n, sub, dk]; ks, cs [n, sub, dk]
        xs, ks, cs = item
        diff = jnp.where(lower, cs[:, :, None, :] - cs[:, None, :, :], 0.0)
        return jnp.sum(jnp.where(lower, xs[..., :, None, :] * (ks[:, None, :, :] * jnp.exp(diff)),
                                 0.0), axis=-1)  # [2, n, sub, sub]

    flat = (jnp.moveaxis(xs, 0, -4).reshape(-1, 2, n, sub, dk),
            ks.reshape(-1, n, sub, dk), cs.reshape(-1, n, sub, dk))
    at_once = max(1, _PAIR_BYTES // (4 * Q * sub * dk))
    if flat[1].shape[0] <= at_once:
        diag = jax.vmap(pairs)(flat)
    else:
        diag = lax.map(pairs, flat, batch_size=at_once)
    diag = jnp.moveaxis(diag.reshape(*lead, 2, n, sub, sub), -4, 0)  # [2, ..., n, sub, sub]
    if n == 1:
        return diag[..., 0, :, :]
    # ref_I: the running sum at the last token before sub-block I (I = 0 has
    # no earlier sub-block: its row of ``before`` is empty)
    ref = jnp.concatenate([jnp.zeros_like(cs[..., :1, 0, :]), cs[..., :-1, -1, :]], axis=-2)
    x_in = xs * jnp.exp(cs - ref[..., :, None, :])
    before = (jnp.arange(Q)[None, :] < (jnp.arange(n) * sub)[:, None])[..., None]  # [n, Q, 1]
    k_out = jnp.where(before, k[..., None, :, :] * jnp.exp(
        jnp.where(before, ref[..., :, None, :] - cum[..., None, :, :], 0.0)), 0.0)
    off = jnp.einsum("...nic,...njc->...nij", x_in, k_out, precision=_HIGHEST)  # [2, ..., n, sub, Q]
    on = (diag[..., :, :, None, :] * jnp.eye(n, dtype=diag.dtype)[:, None, :, None])
    return (off + on.reshape(2, *lead, n, sub, Q)).reshape(2, *lead, Q, Q)


def _chunked_channels(state, q, k, v, g, beta, chunk: int):
    """``_chunked`` with a decay a key channel, ``g`` [N,S,H,dk]: the same WY
    form with ``G_ij`` inside the products over the channels — ``A_ij = beta_i
    sum_c k_ic k_jc G_ijc`` and ``(q_i . k_j)`` alike (``_decayed_products``) —
    the decay from the block's start on ``k`` and ``q`` before they meet the
    state, and the state scaled a ROW (key channel) from block to block."""
    S = q.shape[1]
    Q = min(chunk, S)
    qb, kb, vb, gb, bb = _blocks((q, k, v, g, beta), Q)  # gb [B,N,H,Q,dk]
    strict = jnp.tril(jnp.ones((Q, Q), bool), -1)
    cum = jnp.cumsum(gb, axis=-2)  # <= 0, inclusive, a channel
    kk, Gqk = _decayed_products(jnp.stack([kb, qb]), kb, cum)
    solve = _unit_lower_inverse(jnp.where(strict, bb[..., None] * kk, 0.0))
    from_start = jnp.exp(cum)
    k_in, q_in = kb * from_start, qb * from_start
    k_out = kb * jnp.exp(cum[..., -1:, :] - cum)
    through = jnp.exp(cum[..., -1, :])[..., None]  # [B,N,H,dk,1]

    def block(state, blk):
        k_in, q_in, k_out, vb, bb, solve, Gqk, through = blk
        rhs = bb[..., None] * (
            vb - jnp.einsum("nhik,nhkv->nhiv", k_in, state, precision=_HIGHEST))
        u = jnp.einsum("nhij,nhjv->nhiv", solve, rhs, precision=_HIGHEST)  # (I + A) U = rhs
        o = (jnp.einsum("nhik,nhkv->nhiv", q_in, state, precision=_HIGHEST)
             + jnp.einsum("nhij,nhjv->nhiv", Gqk, u, precision=_HIGHEST))
        state = state * through + jnp.einsum("nhjk,nhjv->nhkv", k_out, u, precision=_HIGHEST)
        return state, o

    return _scan_blocks(block, state, (k_in, q_in, k_out, vb, bb, solve, Gqk, through), S)


def _packed_conv(x: Array, tail: Array, rows: SsmRows, w: Array) -> tuple[Array, Array]:
    """``causal_conv`` over the ragged step's packed buffer ``x`` [T,C], rows
    one after another from ``q_start``: the conv runs along the buffer as if
    it were one row, and each row's first K-1 outputs, which would read the
    row before, are computed apart from the row's ``tail`` [N,K-1,C] and
    written over them. The work follows the tokens the buffer holds, where
    regrouping to ``[N, width]`` rows first costs ``N x width`` whatever they
    hold. Returns (out [T,C], the new tails)."""
    K, T = w.shape[0], x.shape[0]
    q_start = rows.pack[0]
    along = jnp.pad(x, [(K - 1, 0), (0, 0)])
    out = sum(along[k:k + T] * w[k][None, :] for k in range(K))
    first = jnp.arange(K - 1, dtype=jnp.int32)[None, :]  # a row's first K-1 tokens
    at = q_start[:, None] + first
    head = jnp.concatenate([tail, x[jnp.minimum(at, T - 1)]], axis=1)  # [N, 2(K-1), C]
    fixed = sum(head[:, k:k + K - 1] * w[k][None, None, :] for k in range(K))
    out = out.at[jnp.where(first < rows.n_valid[:, None], at, T)].set(fixed, mode="drop")
    # the K-1 inputs ending at the row's last real token: of the old tail
    # while the row holds fewer than K-1
    back = rows.n_valid[:, None] + first  # index into [tail | the row's tokens]
    new_tail = jnp.where(
        (back < K - 1)[..., None],
        jnp.take_along_axis(tail, jnp.minimum(back, K - 2)[..., None], axis=1),
        x[jnp.clip(q_start[:, None] + back - (K - 1), 0, T - 1)])
    return jax.nn.silu(out), new_tail


def _packed_scan(leaf: Array, layer_idx: Array, rows: SsmRows, q, k, v, g, beta
                 ) -> tuple[Array, Array]:
    """The ragged step's rows through the chunked form, from the packed
    buffer and back: q, k [T,H,dk]; v [T,H,dv]; g, beta [T,H] (g [T,H,dk]: a
    decay a key channel); ``leaf`` the
    state ``[L,slots,H/n,dk,n dv]``. Returns (o [T,H,dv], the leaf updated).

    Rows are regrouped to ``[N, width]`` for the scan, which costs ``N x
    width`` whatever they hold; but of a buffer of T tokens at most ``T //
    (CHUNK + 1)`` rows hold more than one block. So the rows are ranked by
    length: those that many longest get the whole width, every other row
    ONE block and no loop (a decode row beside a prompt's chunk rides there)."""
    T, n = q.shape[0], rows.n_valid.shape[0]
    q_start, tok_row, tok_off = rows.pack
    n_long = min(n, T // (CHUNK + 1)) if rows.width > CHUNK else n
    order = jnp.argsort(-rows.n_valid)
    rank = jnp.argsort(order)[jnp.minimum(tok_row, n - 1)]  # [T] the token's row's
    o = None
    for lo, hi, width in ((0, n_long, rows.width), (n_long, n, CHUNK)):
        if lo == hi:
            continue
        of = order[lo:hi]
        grp = SsmRows(rows.slots[of], rows.n_valid[of], (q_start[of], None, None), width)
        live = (jnp.arange(width, dtype=jnp.int32)[None, :] < grp.n_valid[:, None])[..., None]
        qr, kr, vr, gr, br = (_to_rows(t, grp) for t in (q, k, v, g, beta))
        og, state = _chunked(_heads(_read(leaf, layer_idx, grp), q.shape[1]), qr, kr, vr,
                             jnp.where(live[..., None] if gr.ndim > br.ndim else live, gr, 0.0),
                             jnp.where(live, br, 0.0), CHUNK)
        leaf = _write(leaf, _tiles(state, leaf.shape[2]), layer_idx, grp)
        og = og[jnp.clip(rank - lo, 0, hi - lo - 1), jnp.clip(tok_off, 0, width - 1)]
        o = og if o is None else jnp.where((rank < lo)[:, None, None], o, og)
    return o, leaf


def _gates(ba: Array, lp: dict[str, Any], live: Array, neg_eigval: bool) -> tuple[Array, Array]:
    """``(g, beta)`` [N,S,H] from the projection ``[b | a]``; a token that is
    not ``live`` (padding) gets ``g = 0, beta = 0`` and leaves the state alone."""
    b, a = jnp.split(ba, 2, axis=-1)
    beta = jax.nn.sigmoid(b) * (2.0 if neg_eigval else 1.0)
    g = -jnp.exp(lp["gdn_A_log"]) * jax.nn.softplus(a + lp["gdn_dt_bias"])
    return jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)


def _channel_gates(h: Array, lp: dict[str, Any], c, live: Array,
                   qm_backend: str | None = None) -> tuple[Array, Array, Array]:
    """``(g [..,H,dk], beta [..,H], the output gate's logits [..,H,dv])`` of a
    layer with ``gdn_gate_rank`` from its input ``h``; a token that is not
    ``live`` gets ``g = 0, beta = 0``.

    The DECAY's path is float32 from the input on, as ``A_log`` and
    ``dt_bias`` are: ``W_f1 h`` keeps its float32 sum, and ``W_f2 (.)`` is a
    float32 product (rank-wide: a few MFLOP a token). ``softplus`` sits near
    -2 .. -7 (``dt_bias``), where a bfloat16 rounding of its argument (steps of
    0.016-0.03) is 2-3 % of its value: of the log-decay, every token, for
    as long as the state remembers — the softmax's and the norms' policy, for
    the same reason."""
    f32, r, H = jnp.float32, c.gdn_gate_rank, c.gdn_heads
    quantized = isinstance(lp["gdn_low"], (QTensor, Q4Tensor))
    if quantized:  # (its products come in the activations' dtype)
        low = dense(h, lp["gdn_low"], qm_backend=qm_backend).astype(f32)
    else:
        low = jnp.matmul(h, lp["gdn_low"], preferred_element_type=f32)
    f, gl, b = jnp.split(low, [r, 2 * r], axis=-1)
    if quantized:
        decay = dense(f.astype(h.dtype), lp["gdn_f2"], qm_backend=qm_backend).astype(f32)
    else:
        decay = jnp.matmul(f, lp["gdn_f2"].astype(f32), precision=_HIGHEST)
    decay = decay + lp["gdn_dt_bias"]
    g = -jnp.exp(lp["gdn_A_log"])[:, None] * jax.nn.softplus(
        decay.reshape(*decay.shape[:-1], H, c.gdn_key_dim))
    gate = (dense(gl.astype(h.dtype), lp["gdn_g2"], qm_backend=qm_backend).astype(f32)
            + lp["gdn_g_bias"].astype(f32))
    live = jnp.asarray(live)
    return (jnp.where(live[..., None], g, 0.0),
            jnp.where(live, jax.nn.sigmoid(b), 0.0),
            gate.reshape(*gate.shape[:-1], H, c.gdn_value_dim))


def gated_head_norm(o: Array, gate: Array, weight: Array, eps: float,
                    sigmoid: bool = False) -> Array:
    """``RMSNorm_dv(o_h) * SiLU(gate_h)`` (``sigmoid``: ``* sigmoid(gate_h)``):
    the norm over each head's values first, one weight ``[dv]`` for all heads,
    then the gate."""
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    if sigmoid:
        return o * weight.astype(jnp.float32) * jax.nn.sigmoid(gate)
    return o * weight.astype(jnp.float32) * jax.nn.silu(gate)


def mixer(h: Array, lp: dict[str, Any], c, cache: Any, layer_idx: Array,
          rows: SsmRows | None, qm_backend: str | None = None) -> tuple[Array, Any]:
    """The layer's output for its input ``h`` [B,S,D] and the updated
    ``cache`` (``(state [L,slots,H/n,dk,n dv], conv tail [L,slots,K-1,C])``
    float32, indexed by the layer's place among the LINEAR layers; or None:
    every row from zero, nothing kept)."""
    f32 = jnp.float32
    H, dk, dv = c.gdn_heads, c.gdn_key_dim, c.gdn_value_dim
    channels = bool(c.gdn_gate_rank)  # a decay a key channel, a sigmoid gate: the module's docstring
    with jax.named_scope("gdn_in"):
        if channels:
            qkv = dense(h, lp["gdn_in"], qm_backend=qm_backend).astype(f32)
        else:
            qkv, gate = jnp.split(dense(h, lp["gdn_in"], qm_backend=qm_backend).astype(f32),
                                  [c.gdn_conv_dim], axis=-1)
            ba = dense(h, lp["gdn_ba"], qm_backend=qm_backend).astype(f32)
    packed = rows is not None and rows.pack is not None
    n, S = (rows.n_valid.shape[0], rows.width) if packed else qkv.shape[:2]
    if rows is None:
        rows = SsmRows(None, jnp.full((n,), S, jnp.int32))
    if channels:
        with jax.named_scope("gdn_gate"):
            live = True if packed else (
                jnp.arange(S, dtype=jnp.int32)[None, :] < rows.n_valid[:, None])[..., None]
            g, beta, gate = _channel_gates(h, lp, c, live, qm_backend)
    if cache is None:
        state = jnp.zeros((n, H, dk, dv), f32)
        tail = jnp.zeros((n, c.gdn_conv - 1, c.gdn_conv_dim), f32)
    with jax.named_scope("gdn_conv"):
        if cache is not None:
            tail = _read(cache[1], layer_idx, rows)
        w = lp["gdn_conv_w"].astype(f32)
        if packed:  # along the buffer's T tokens, not over N rows of `width`
            qkv, tail = _packed_conv(qkv[0], tail, rows, w)
        else:
            qkv, tail = causal_conv(qkv, tail, rows.n_valid, w, None)
        if cache is not None:
            conv_state = _write(cache[1], tail, layer_idx, rows)
    with jax.named_scope("gdn_scan"):
        q, k, v = jnp.split(qkv, [H * dk, 2 * H * dk], axis=-1)
        q = _l2norm(q.reshape(*q.shape[:-1], H, dk)) * dk ** -0.5
        k = _l2norm(k.reshape(*k.shape[:-1], H, dk))
        v = v.reshape(*v.shape[:-1], H, dv)
        if packed:
            g, beta = (g[0], beta[0]) if channels else _gates(ba[0], lp, True, c.gdn_neg_eigval)
            o, state_leaf = _packed_scan(cache[0], layer_idx, rows, q, k, v, g, beta)
            cache = (state_leaf, conv_state)
            o = o[None]
        else:
            if not channels:
                live = (jnp.arange(S, dtype=jnp.int32)[None, :] < rows.n_valid[:, None])[..., None]
                g, beta = _gates(ba, lp, live, c.gdn_neg_eigval)
            one_token = S == 1 and cache is not None and rows.backend != "ref"
            if one_token and rows.slots is None:
                # the decode step: every slot's state advances where it lies
                o, state_leaf = gdn_state_step(
                    cache[0], q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                    layer_idx.reshape(1), interpret=rows.backend == "pallas-interpret")
                cache = (state_leaf, conv_state)
                o = o[:, None]
            else:
                if one_token:  # gathered slots: XLA's two passes over the state
                    METRICS.inc("finchat_ssm_step_fallbacks_total")
                if cache is not None:
                    state = _heads(_read(cache[0], layer_idx, rows), H)
                if S == 1:
                    o, state = _step(state, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
                    o = o[:, None]
                else:
                    o, state = _chunked(state, q, k, v, g, beta, CHUNK)
                if cache is not None:
                    cache = (_write(cache[0], _tiles(state, cache[0].shape[2]), layer_idx, rows),
                             conv_state)
    with jax.named_scope("gdn_norm"):
        # (the scalar rule's call is the four arguments it always was: a test swaps the
        # function for one that takes no more)
        y = gated_head_norm(o, gate.reshape(*o.shape), lp["gdn_norm"], c.norm_eps,
                            **({"sigmoid": True} if channels else {}))
        y = y.reshape(*y.shape[:2], H * dv).astype(h.dtype)
    with jax.named_scope("gdn_out"):
        out = dense(y, lp["gdn_out"], qm_backend=qm_backend)
    return out, cache
