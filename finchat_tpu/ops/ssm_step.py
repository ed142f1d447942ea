"""Pallas one-token Mamba-2 state update — ONE pass over the recurrent state,
in place (``models/ssm.py`` has the recurrence; ``_step`` there is this
kernel's reference and the ``ref`` backend's body).

XLA compiles ``_step`` over the carried ``[L, slots, H, P, Ns]`` state to two
fusions that both read it: the in-place update, and a second pass over the
old state for ``y`` (PERF.md section 5, PR 27: 0.206 + 0.090 ms a layer
against a stream bound of 0.165). Here each row's state comes into VMEM once:
``new = exp(dt A) S + (dt xs) (x) B`` is computed where it lies and goes back
to where it came from (``input_output_aliases``), and ``y = new . C`` is
reduced from the same copy. The layer is a scalar-prefetch operand, as in
``ops/kv_append.py``: the kernel indexes the full-depth state that the layer
scan carries, and layers and slots it does not visit are not touched.

The stream. On a v5e a write stream runs at 655 GB/s and a read stream at
752 GB/s, and a read and a write in flight TOGETHER share 650 GB/s between
them: the grid's own double buffering (block i+1 in while block i-1 goes
out) takes 205 us a layer for the cell's 134 MB, and so does XLA's in-place
fusion. So the state rides manual DMAs that never overlap: read block i+1,
then write block i, each started when the other has landed (two VMEM
buffers). The arithmetic hides behind them — the first half of a block's
heads is advanced while the block before it is written, the second half
while the block after it is read; the first block comes in and the last goes
out as two halves, so that neither end leaves the arithmetic uncovered.

Layout, by the static shapes ``(H, P, Ns, G)`` (``tile_heads``: the ONE place
the form is decided; the engine allocates what ``stored_shape`` gives). Where a
head's ``[P, Ns]`` fills whole lane tiles by itself (Falcon-H1's ``[128,
256]``) the state is stored as the recurrence writes it, ``[.., H, P, Ns]``: P
on sublanes and Ns on lanes, so ``B`` and ``C`` ``[1, Ns]`` broadcast along
sublanes for free, while ``dt xs`` has to arrive with P on sublanes: the
wrapper hands it over as ``[P, H]`` columns (a few KiB a row, transposed by
XLA), and ``y`` comes back the same way (``_step_kernel``). That form pays a
lane-broadcast, a lane-reduce and a masked store for every ``[8, Ns]`` strip
of a head, which a head of 64 rows by 128 lanes — the canonical Mamba-2 head:
Granite-4.0's, Nemotron-H's, Bamba's — cannot hide behind its DMAs (PERF.md
section 6, PR 52). So where two heads make one lane tile (2 P = 128, an even
count of heads a group) the state is STORED as pairs with the state axis on
sublanes, ``[.., H / 2, Ns, 2 P]`` — the same bytes — and ``_pairs_kernel``
needs no cross-lane operation a head: ``dt xs`` and ``y`` are lane-dense rows
``[1, 2 P]`` as the model lays them (``[N, H, P]`` viewed ``[N, H / 2, 2 P]``:
no transpose in the wrapper), ``y`` is a sum over SUBLANES, the decay a
select between a pair's two scalars, and ``B`` and ``C`` become columns
spread along the lanes once a row and group (one 128 x 128 transpose each),
not once a head. Every other reader and writer of the state — the chunked
form, ``_step`` on ``ref`` or over gathered slots, the engine's snapshots —
goes through ``to_logical`` / ``to_stored``. ``exp(dt A)`` is one scalar a
head and rides in SMEM with the layer. ``D xs`` is added outside (no pass over
the state needs it).

Shared. The stream above — ``rows_per_block``, the DMAs, the two buffers, the
halves, the aliasing, the layer as a scalar-prefetch operand — is
``in_place_pass`` and ``in_place_call``, and ``ops/gdn_step.py`` (the gated
delta rule's one-token update over ``[L, slots, tiles, dk, n dv]``) runs on
the same two: one pipeline, two updates, each kernel bringing its
``advance`` and the operands beside the state (ROADMAP D14).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import Array, lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one block of rows in VMEM; two of them are held. At the cell's 4 MiB a row,
# blocks of 1 / 2 / 4 rows take 199.4 / 195.2 / 193.3 us a layer on the chip
# (PERF.md section 6, PR 28): every switch of direction costs about 0.24 us
_BLOCK_BYTES = 8 * 1024 * 1024


_LANES = 128


def tile_heads(H: int, P: int, Ns: int, G: int) -> int:
    """Heads side by side in one tile of the stored state — THE place the
    one-token update's form is decided, from the static shapes alone: 2 where
    two heads make one lane tile (2 P = 128 lanes), pairs do not straddle a
    B/C group and leave two tiles or more a row (the pipeline works a row's
    tiles in two halves); else 1, a head's ``[P, Ns]`` as the recurrence
    writes it."""
    pairs = 2 * P == _LANES and Ns % _LANES == 0 and (H // G) % 2 == 0 and H >= 4
    return 2 if pairs else 1


def stored_shape(H: int, P: int, Ns: int, G: int) -> tuple[int, int, int]:
    """One slot's state in one layer as the device holds it: ``[H, P, Ns]``,
    or pairs of heads with the state axis on sublanes, ``[H / 2, Ns, 2 P]``."""
    n = tile_heads(H, P, Ns, G)
    return (H, P, Ns) if n == 1 else (H // n, Ns, n * P)


def to_stored(state: Array, G: int) -> Array:
    """``[..., H, P, Ns]`` as ``[..., *stored_shape]``."""
    H, P, Ns = state.shape[-3:]
    n = tile_heads(H, P, Ns, G)
    if n == 1:
        return state
    tiles = state.reshape(*state.shape[:-3], H // n, n, P, Ns)
    return jnp.moveaxis(tiles, -1, -3).reshape(*state.shape[:-3], H // n, Ns, n * P)


def to_logical(stored: Array, heads: tuple[int, int, int], G: int) -> Array:
    """``[..., *stored_shape]`` as ``[..., H, P, Ns]`` (``heads``)."""
    H, P, Ns = heads
    n = tile_heads(H, P, Ns, G)
    if n == 1:
        return stored
    tiles = stored.reshape(*stored.shape[:-3], H // n, Ns, n, P)
    return jnp.moveaxis(tiles, -3, -1).reshape(*stored.shape[:-3], H, P, Ns)


def rows_per_block(rows: int, row_bytes: int) -> int:
    """The most rows (a divisor of ``rows``) whose state fits a block; one
    where a single row is larger than that."""
    return max([d for d in range(1, rows + 1)
                if rows % d == 0 and d * row_bytes <= _BLOCK_BYTES] or [1])


def in_place_pass(layer_ref, s_any, o_any, buf, sems, advance):
    """One grid step of the row-block pipeline that the one-token state
    kernels share (this file's and ``ops/gdn_step.py``'s; they differ in
    ``advance`` and in the operands beside the state). ``s_any`` and
    ``o_any`` are the ONE carried buffer ``[L, N, tiles, ...]`` in HBM, ``buf``
    two blocks of ``r`` rows in VMEM, ``sems`` DMA semaphores [2 (in, out), 2
    (buffer), 2 (half of the tiles)]; ``advance(i, tiles)`` updates those
    tiles of every row of block ``i`` where they lie in ``buf[i % 2]``."""
    i, steps = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]
    r, tiles = buf.shape[1:3]

    halves = (range(tiles // 2), range(tiles // 2, tiles))

    def copy(out: bool, block, half: int):
        slot, part = block % 2, pl.ds(halves[half].start, len(halves[half]))
        hbm = (o_any if out else s_any).at[layer, pl.ds(block * r, r), part]
        vmem = buf.at[slot, :, part]
        src, dst = (vmem, hbm) if out else (hbm, vmem)
        return pltpu.make_async_copy(src, dst, sems.at[int(out), slot, half])

    def start(out: bool, block):  # both halves, back to back
        copy(out, block, 0).start()
        copy(out, block, 1).start()

    def wait(out: bool, block):
        copy(out, block, 0).wait()
        copy(out, block, 1).wait()

    first, last = i == 0, i + 1 == steps

    @pl.when(first)
    def _():
        start(False, i)
        copy(False, i, 0).wait()

    # block i's first half is in VMEM; behind it block i-1 goes out or, in
    # the first step, block i's second half comes in
    advance(i, halves[0])

    @pl.when(first)
    def _():
        copy(False, i, 1).wait()

    @pl.when(jnp.logical_not(first))
    def _():
        wait(True, i - 1)

    @pl.when(jnp.logical_not(last))
    def _():
        start(False, i + 1)

    @pl.when(last)
    def _():
        copy(True, i, 0).start()

    advance(i, halves[1])

    @pl.when(jnp.logical_not(last))
    def _():
        wait(False, i + 1)
        start(True, i)

    @pl.when(last)
    def _():
        copy(True, i, 1).start()
        wait(True, i)


def in_place_call(kernel, state: Array, layer: Array, scalars: list[Array],
                  blocks: list[Array], out: jax.ShapeDtypeStruct, *, interpret: bool):
    """The ``pallas_call`` around ``in_place_pass``: ``state`` ``[L, N, tiles,
    ...]`` stays in HBM and comes back in its own buffer
    (``input_output_aliases``), ``scalars`` ride in SMEM behind the layer,
    ``blocks`` ``[N, ...]`` come in and ``out`` ``[N, ...]`` goes back
    ``rows_per_block`` rows a grid step. ``kernel`` takes ``(layer, *scalars,
    *blocks, state, out, state, buf, sems)``. Returns ``(out, state)``."""
    N = state.shape[1]
    row_bytes = math.prod(state.shape[2:]) * state.dtype.itemsize
    r = rows_per_block(N, row_bytes)

    def rows(x):
        return pl.BlockSpec((r, *x.shape[1:]), lambda i, *_: (i,) + (0,) * (len(x.shape) - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 + len(scalars),
        grid=(N // r,),
        in_specs=[*map(rows, blocks), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[rows(out), pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((2, r, *state.shape[2:]), state.dtype),
            pltpu.SemaphoreType.DMA((2, 2, 2)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[out, jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # flattened operands: the scalar prefetch, the blocks, then the state
        input_output_aliases={1 + len(scalars) + len(blocks): 1},
        compiler_params=pltpu.CompilerParams(
            # a step's DMAs are started in the step before it
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * r * row_bytes + 8 * 1024 * 1024,
        ),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32), *scalars, *blocks, state)


def _step_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32
    decay_ref,  # [N, H] float32 — exp(dt A); 1 for an inert row
    # blocks (r rows a grid step)
    dtx_ref,  # [r, P, H] — dt * xs, P on sublanes; 0 for an inert row
    bc_ref,  # [r, G, 2, Ns] — each group's B row, then its C row
    s_any,  # [L, N, H, P, Ns] ANY (aliased to o_any)
    y_ref,  # [r, P, H]
    o_any,  # the same buffer as s_any
    # scratch
    buf,  # [2, r, H, P, Ns] VMEM
    sems,
):
    r, G = bc_ref.shape[:2]
    H = buf.shape[2]

    def advance(i, heads):
        slot = i % 2
        for row in range(r):
            for h in heads:
                g = h // (H // G)
                new = (decay_ref[i * r + row, h] * buf[slot, row, h]
                       + dtx_ref[row, :, h:h + 1] * bc_ref[row, g, 0:1, :])
                buf[slot, row, h] = new
                y_ref[row, :, h:h + 1] = jnp.sum(
                    new * bc_ref[row, g, 1:2, :], axis=-1, keepdims=True)

    in_place_pass(layer_ref, s_any, o_any, buf, sems, advance)


def _pairs_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32
    decay_ref,  # [N, H] float32 — exp(dt A); 1 for an inert row
    # blocks (r rows a grid step)
    dtx_ref,  # [r, T, 2P] — dt * xs as the model lays it; 0 for an inert row
    bc_ref,  # [r, G, 2, Ns] — each group's B row, then its C row
    s_any,  # [L, N, T, Ns, 2P] ANY (aliased to o_any)
    y_ref,  # [r, T, 2P]
    o_any,  # the same buffer as s_any
    # scratch
    buf,  # [2, r, T, Ns, 2P] VMEM
    sems,
):
    """``_step_kernel`` over PAIRS of heads with the state axis on sublanes:
    nothing crosses lanes for a head, ``y`` leaves as a lane-dense row."""
    r, G = bc_ref.shape[:2]
    T, Ns, W = buf.shape[2:]
    second = lax.broadcasted_iota(jnp.int32, (1, W), 1) >= W // 2  # the pair's other head

    def advance(i, tiles):
        slot = i % 2
        for row in range(r):
            n, group = i * r + row, None
            for t in tiles:
                if t // (T // G) != group:
                    # the group's B and C as columns down the sublanes, spread
                    # along the lanes: once a row and group, not once a head
                    group = t // (T // G)
                    Bb, Cb = (jnp.broadcast_to(bc_ref[row, group, which:which + 1, :], (W, Ns)).T
                              for which in range(2))
                decay = jnp.where(second, decay_ref[n, 2 * t + 1], decay_ref[n, 2 * t])
                new = decay * buf[slot, row, t] + Bb * dtx_ref[row, t:t + 1, :]
                buf[slot, row, t] = new
                y_ref[row, t:t + 1, :] = jnp.sum(new * Cb, axis=0, keepdims=True)

    in_place_pass(layer_ref, s_any, o_any, buf, sems, advance)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def ssm_state_step(
    ssm_state: Array,  # [L, N, *stored_shape(H, P, Ns, G)] float32 — every slot's state, all layers
    xs: Array,  # [N, H, P]
    dt: Array,  # [N, H]; 0 = inert: the row's state is written back as it was
    A: Array,  # [H] (negative)
    Bm: Array,  # [N, G, Ns]
    Cm: Array,  # [N, G, Ns]
    D: Array,  # [H]
    layer: Array,  # [1] int32
    *,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """Advance layer ``layer``'s state of every slot by one token, in place.
    Returns ``(y [N, H, P], ssm_state)`` (the state aliased to its input)."""
    N, H, P = xs.shape
    G, Ns = Bm.shape[1:]
    assert ssm_state.shape[2:] == stored_shape(H, P, Ns, G), (ssm_state.shape, (H, P, Ns, G))
    pairs = tile_heads(H, P, Ns, G) == 2
    f32 = jnp.float32
    decay = jnp.exp(dt * A[None, :]).astype(f32)
    dtx = (dt[..., None] * xs).astype(f32)
    # dt x goes in as y comes back: a pair's lane-dense row as the model lays
    # it, [N, H / 2, 2 P], or a head's column with P on sublanes, [N, P, H]
    dtx = dtx.reshape(N, H // 2, 2 * P) if pairs else dtx.transpose(0, 2, 1)
    bc = jnp.stack([Bm, Cm], axis=2).astype(f32)  # [N, G, 2, Ns]
    y, ssm_state = in_place_call(
        _pairs_kernel if pairs else _step_kernel, ssm_state, layer, [decay], [dtx, bc],
        jax.ShapeDtypeStruct(dtx.shape, f32), interpret=interpret)
    y = y.reshape(N, H, P) if pairs else y.transpose(0, 2, 1)
    return y + D[None, :, None] * xs, ssm_state
