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

Layout. A head's ``[P, Ns]`` tile has P on sublanes and Ns on lanes, so ``B``
and ``C`` ``[1, Ns]`` broadcast along sublanes for free, while ``dt xs`` has
to arrive with P on sublanes: the wrapper hands it over as ``[P, H]`` columns
(a few KiB a row, transposed by XLA), and ``y`` comes back the same way.
``exp(dt A)`` is one scalar a head and rides in SMEM with the layer. ``D xs``
is added outside (no pass over the state needs it).

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
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one block of rows in VMEM; two of them are held. At the cell's 4 MiB a row,
# blocks of 1 / 2 / 4 rows take 199.4 / 195.2 / 193.3 us a layer on the chip
# (PERF.md section 6, PR 28): every switch of direction costs about 0.24 us
_BLOCK_BYTES = 8 * 1024 * 1024


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


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def ssm_state_step(
    ssm_state: Array,  # [L, N, H, P, Ns] float32 — every slot's state, all layers
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
    _L, N, H, P, _Ns = ssm_state.shape
    f32 = jnp.float32
    decay = jnp.exp(dt * A[None, :]).astype(f32)
    dtx = (dt[..., None] * xs).astype(f32).transpose(0, 2, 1)  # [N, P, H]
    bc = jnp.stack([Bm, Cm], axis=2).astype(f32)  # [N, G, 2, Ns]
    y, ssm_state = in_place_call(
        _step_kernel, ssm_state, layer, [decay], [dtx, bc],
        jax.ShapeDtypeStruct((N, P, H), f32), interpret=interpret)
    return y.transpose(0, 2, 1) + D[None, :, None] * xs, ssm_state
