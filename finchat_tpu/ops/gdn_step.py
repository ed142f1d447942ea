"""Pallas one-token gated-delta-rule state update — ONE pass over the
recurrent state, in place (``models/gdn.py`` has the rule; ``_step`` there is
this kernel's reference and the ``ref`` backend's body).

The rule READS the state before it writes it (``S^T k`` feeds the rank-one
correction), and XLA cannot fuse a reduction over the state with the rewrite
that depends on it: ``_step`` over the carried state compiles to a pass that
reads it for ``S^T k`` and ``S^T q`` and a second that reads it again and
rewrites it (PERF.md section 6, PR 39: 3 x 35 MB a layer where the rule needs
2 x 35). Here a block of rows comes into VMEM once; for each tile ``Sk = S^T
k`` and ``Sq = S^T q`` are reduced from that copy, ``u = beta (v - alpha
Sk)``, ``S <- alpha S + k u^T`` is written where it lies and goes back to
where it came from, and ``o = alpha Sq + (k.q) u`` comes off the OLD state
(``_step``'s algebra), so nothing is read twice. All of it float32 on the
VPU: no product goes through the MXU.

The pipeline — blocks of rows on manual DMAs that never overlap a read with
a write, two VMEM buffers, a block's tiles in two halves that hide the
arithmetic, the state aliased to its output, the layer a scalar-prefetch
operand — is ``ops/ssm_step.py``'s (``in_place_pass`` / ``in_place_call``):
one body, two updates.

Layout. The state is ``[L, slots, H / n, dk, n dv]``: ``n`` heads' matrices
side by side along the lanes of one tile (``LlamaConfig.state_shape``:
Olmo-Hybrid's 30 heads of 96 x 192 as 15 tiles of 96 x 384 — three whole
lane tiles, where ``[.., 96, 192]`` pads every row of 192 to 256 in HBM, a
third more bytes in every pass). ``dk`` lies on sublanes, so ``S^T k`` is a
sum over sublanes and ``u``, ``v``, ``alpha``, ``beta``, ``k.q`` and ``o``
are lane vectors ``[1, n dv]`` in ``v``'s own order that broadcast along
sublanes for free; only ``k`` and ``q`` need a head's column spread over its
``dv`` lanes, and arrive as ``[dk, H]`` columns (a few KiB a row, transposed
by XLA). A tile is worked through in chunks of 128 lanes: a chunk takes one
head's column or, where two heads meet inside it, a select between two.

Two decays, one rule. ``g`` ``[N, H]`` is a scalar a head (``alpha`` one of the
lane vectors above). ``g`` ``[N, H, dk]`` is a vector over the head's key
channels (Kimi Delta Attention): ``S~ = Diag(alpha) S`` scales the state's
SUBLANES, so ``alpha`` arrives as a ``[dk, H]`` column as ``k`` and ``q`` do,
``S~^T k = S^T (alpha * k)`` and ``S~^T q = S^T (alpha * q)`` are the same two
sublane sums over the old state with other columns, and the rewrite is ``S *
alpha + k u^T`` with ``alpha`` spread like ``k``: still ONE pass, in place
(``_channels_kernel``; the wrapper picks by ``g``'s rank, and the scalar
form's program is what it was).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array, lax

from finchat_tpu.ops.ssm_step import in_place_call, in_place_pass

_LANES = 128


def _spread(cols, at: int, *, dv: int, lane):
    """The chunk of lanes from ``at`` (``lane``: their iota ``[dk, cw]``) of a
    tile whose heads are ``dv`` lanes each: each head's ``[dk, 1]`` column of
    ``cols`` over the lanes that are the head's."""
    cw = lane.shape[1]
    heads = range(at // dv, (at + cw - 1) // dv + 1)
    out = jnp.broadcast_to(cols[heads[-1]], lane.shape)
    for h in reversed(heads[:-1]):
        out = jnp.where(lane < (h + 1) * dv - at, cols[h], out)
    return out


def _step_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32
    # blocks (r rows a grid step)
    cols_ref,  # [r, 2, dk, H] — k then q, dk on sublanes
    vec_ref,  # [r, 4, T, W] — alpha, beta, v, k.q: a head's number over its dv lanes
    s_any,  # [L, N, T, dk, W] ANY (aliased to o_any)
    o_ref,  # [r, T, W]
    o_any,  # the same buffer as s_any
    # scratch
    buf,  # [2, r, T, dk, W] VMEM
    sems,
):
    r, H = cols_ref.shape[0], cols_ref.shape[3]
    T, dk, W = buf.shape[2:]
    n = H // T  # heads a tile
    dv = W // n
    cw = _LANES if W % _LANES == 0 else W  # lanes a chunk
    spread = functools.partial(_spread, dv=dv, lane=lax.broadcasted_iota(jnp.int32, (dk, cw), 1))

    def advance(i, tiles):
        slot = i % 2
        for row in range(r):
            for t in tiles:
                kq = [[cols_ref[row, which, :, h:h + 1] for h in range(t * n, (t + 1) * n)]
                      for which in range(2)]
                for at in range(0, W, cw):
                    lanes = slice(at, at + cw)
                    S = buf[slot, row, t, :, lanes]
                    k, q = spread(kq[0], at), spread(kq[1], at)
                    a, beta, v, k_q = (vec_ref[row, which, t:t + 1, lanes] for which in range(4))
                    u = beta * (v - a * jnp.sum(S * k, axis=0, keepdims=True))
                    buf[slot, row, t, :, lanes] = S * a + k * u
                    o_ref[row, t:t + 1, lanes] = (
                        a * jnp.sum(S * q, axis=0, keepdims=True) + k_q * u)

    in_place_pass(layer_ref, s_any, o_any, buf, sems, advance)


def _channels_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32
    # blocks (r rows a grid step)
    cols_ref,  # [r, 4, dk, H] — k, alpha, alpha * k, alpha * q; dk on sublanes
    vec_ref,  # [r, 3, T, W] — beta, v, k.q: a head's number over its dv lanes
    s_any,  # [L, N, T, dk, W] ANY (aliased to o_any)
    o_ref,  # [r, T, W]
    o_any,  # the same buffer as s_any
    # scratch
    buf,  # [2, r, T, dk, W] VMEM
    sems,
):
    """``_step_kernel`` with the decay a vector over the key channels: the
    same walk over rows, tiles and chunks of lanes, four columns spread where
    it spreads two."""
    r, H = cols_ref.shape[0], cols_ref.shape[3]
    T, dk, W = buf.shape[2:]
    n = H // T  # heads a tile
    dv = W // n
    cw = _LANES if W % _LANES == 0 else W  # lanes a chunk
    spread = functools.partial(_spread, dv=dv, lane=lax.broadcasted_iota(jnp.int32, (dk, cw), 1))

    def advance(i, tiles):
        slot = i % 2
        for row in range(r):
            for t in tiles:
                cols = [[cols_ref[row, which, :, h:h + 1] for h in range(t * n, (t + 1) * n)]
                        for which in range(4)]
                for at in range(0, W, cw):
                    lanes = slice(at, at + cw)
                    S = buf[slot, row, t, :, lanes]
                    k, a, ak, aq = (spread(c, at) for c in cols)
                    beta, v, k_q = (vec_ref[row, which, t:t + 1, lanes] for which in range(3))
                    u = beta * (v - jnp.sum(S * ak, axis=0, keepdims=True))
                    buf[slot, row, t, :, lanes] = S * a + k * u
                    o_ref[row, t:t + 1, lanes] = jnp.sum(S * aq, axis=0, keepdims=True) + k_q * u

    in_place_pass(layer_ref, s_any, o_any, buf, sems, advance)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def gdn_state_step(
    state: Array,  # [L, N, T, dk, n dv] float32 — every slot's state, all linear layers
    q: Array,  # [N, H, dk]
    k: Array,  # [N, H, dk]
    v: Array,  # [N, H, dv]
    g: Array,  # [N, H], or [N, H, dk]: a decay a key channel; 0 with beta 0 = inert: the
    #          row's state is written back as it was
    beta: Array,  # [N, H]
    layer: Array,  # [1] int32
    *,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """Advance layer ``layer``'s state of every slot by one token, in place.
    Returns ``(o [N, H, dv], state)`` (the state aliased to its input)."""
    _L, N, T, _dk, W = state.shape
    H, dv = v.shape[1:]
    f32 = jnp.float32

    def lanes(x):  # [N, H] -> [N, T, W]: a head's number over its dv lanes
        return jnp.repeat(x.astype(f32), dv, axis=-1).reshape(N, T, W)

    if g.ndim == 3:  # a decay a key channel
        a = jnp.exp(g.astype(f32))
        cols = jnp.stack([k, a, a * k, a * q], axis=1).astype(f32).transpose(0, 1, 3, 2)
        vec = jnp.stack([lanes(beta), v.astype(f32).reshape(N, T, W),
                         lanes(jnp.sum(k * q, axis=-1))], axis=1)  # [N, 3, T, W]
        o, state = in_place_call(
            _channels_kernel, state, layer, [], [cols, vec],
            jax.ShapeDtypeStruct((N, T, W), f32), interpret=interpret)
        return o.reshape(N, H, dv), state
    cols = jnp.stack([k, q], axis=1).astype(f32).transpose(0, 1, 3, 2)  # [N, 2, dk, H]
    vec = jnp.stack([lanes(jnp.exp(g)), lanes(beta), v.astype(f32).reshape(N, T, W),
                     lanes(jnp.sum(k * q, axis=-1))], axis=1)  # [N, 4, T, W]
    o, state = in_place_call(
        _step_kernel, state, layer, [], [cols, vec],
        jax.ShapeDtypeStruct((N, T, W), f32), interpret=interpret)
    return o.reshape(N, H, dv), state
