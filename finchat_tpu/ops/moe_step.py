"""Pallas one-token expert step — ONE pass over the held expert stacks that
brings into VMEM only the experts the call's live tokens picked
(``models/llama.py`` ``moe_mlp`` has the rule that takes it; dense dispatch
there is this kernel's reference and the ``ref`` backend's body).

Dense dispatch over the held stacks is two XLA fusions a layer that stream
EVERY held expert's weights (PERF.md section 5, PR 34: 601 + 301 us for 679 MB
at 92 % of the HBM peak) where a step of 16 rows x 10 picks touches 30 of 36.
The mathematics here is dense dispatch's, term for term, with the all-zero
terms not computed::

    out = sum over touched e of (glu(h . W_in[e]) * gate[:, e]) . W_out[e]

the same weights and activations in the model's dtype, each matmul's output
rounded to it as the einsums round theirs, the gate applied before the
projection, float32 accumulation over the expert width and over the experts,
one cast at the end. An expert no live token picked has a zero gate in every
live row, so leaving it out changes no live row's value.

The grid runs over ``E`` slots x tiles of the expert width, and how an expert
is cut follows from the call's static shapes (``width_tile``): an expert whose
three blocks are at most ``_WHOLE_BYTES`` is ONE grid step — ``W_in[e]`` and
``W_out[e]`` read where they lie, contiguously (Trinity-Mini's ``[2048, 2 x
1024]``: 12 MiB a step) — and a larger one is cut into the narrowest tiles whose
``D x tile`` block reaches ``_BLOCK_BYTES`` (256 columns at Granite's ``[4096,
768]`` and DeepSeek's ``[7168, 2048]``). The tile is judged INSIDE the
compiled decode step (``benchmarks/moe_step_in_step.py``), not alone: at
Trinity-Mini's shape 512 columns read faster than 256 alone and slower in the
step, and only the whole width reads in the step what it reads alone (PERF.md
section 6, PR 48; the suspect is the strided read of a column tile of
``W_in[e]`` at a power-of-two stride, 64 KiB a group of 16 rows there, 48 KiB
at Granite's, whose pass reads the same at every tile). ``plan`` sorts the
touched ids first (ascending); slot ``s < n`` maps to expert ``ids[s]``, and a
slot ``s >= n`` maps to the very block the last touched slot ended on (the
same expert AND the same tile), so the pipeline issues no copy for it, and
``pl.when`` skips its arithmetic. ``n = 0`` yields zeros. A step's three
blocks are the gate columns and the up columns of the fused ``[gate | up]``
``W_in[e]`` — two block specs on the one operand — and ``W_out[e]``'s rows.
The layer is a scalar-prefetch operand, as in ``ops/ssm_step.py``: the kernel
indexes the whole stacks ``[L, E, ...]`` (a layer's slice handed to a custom
call would be a copy of 0.45 GB), and experts and layers it does not visit
are not read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# where an expert is cut into tiles, the least bytes of ONE of a grid step's
# three blocks (``D x tile`` elements of the weights): PR 35's cut, 256 columns
# at Granite's and DeepSeek's shapes, kept so that their programs stay as they
# were (Granite's pass reads within 0.4 % at 128 to 768 columns inside its
# decode step, PR 48: the block's size is not what its speed hangs on)
_BLOCK_BYTES = 2 * 1024 * 1024
# the most bytes of an expert (its three blocks) that is ONE grid step: two
# buffers of it are then at most a quarter of a v5e's 128 MiB of VMEM. Measured
# inside Trinity-Mini's decode step (12 MiB an expert, PR 48): the pass 1,304 us
# whole against 1,353 / 1,429 / 1,484 at tiles of 128 / 256 / 512 columns;
# Granite's 18 MiB expert reads the same whole or cut and stays cut
_WHOLE_BYTES = 16 * 1024 * 1024


def width_tile(width: int, dim: int, itemsize: int) -> int:
    """Columns of the expert ``width`` that a grid step brings in, for a model
    width ``dim`` and weights of ``itemsize`` bytes: the whole width where the
    expert's three blocks are at most ``_WHOLE_BYTES``; else the smallest
    multiple of 128 that divides it and makes a block ``dim x tile`` at least
    ``_BLOCK_BYTES``, else the whole width."""
    if 3 * dim * width * itemsize <= _WHOLE_BYTES:
        return width
    for tile in range(128, width, 128):
        if width % tile == 0 and dim * tile * itemsize >= _BLOCK_BYTES:
            return tile
    return width


def plan(picked: Array, gates: Array) -> tuple[Array, Array, Array]:
    """What the kernel rides on, from the router's own outputs: ``picked``
    [E] bool (the held experts that live tokens picked) and ``gates`` [T, E].
    Returns ``(ids [E] int32, n [1] int32, gate_cols [E, T, 1] float32)``: the
    touched ids first and ascending (a stable sort), how many they are, and
    each expert's gates as a column (``T`` on sublanes: a lane broadcast in
    the kernel, no select of a dynamic lane)."""
    ids = jnp.argsort(jnp.logical_not(picked), stable=True).astype(jnp.int32)
    n = jnp.sum(picked.astype(jnp.int32)).reshape(1)
    return ids, n, gates.T[:, :, None].astype(jnp.float32)


def _kernel(
    # scalar prefetch
    layer_ref,  # [1] int32
    ids_ref,  # [E] int32 — the touched experts first
    n_ref,  # [1] int32 — how many of them
    # blocks
    h_ref,  # [T, D]
    gate_ref,  # [T, 1] float32 — this slot's expert's gates
    wg_ref,  # [D, tile] — the gate columns of W_in[e]
    wu_ref,  # [D, tile] — its up columns
    wo_ref,  # [tile, D] — W_out[e]'s rows
    o_ref,  # [T, D]
    # scratch
    acc,  # [T, D] float32
):
    s, j = pl.program_id(0), pl.program_id(1)
    f32, dtype = jnp.float32, h_ref.dtype

    @pl.when((s == 0) & (j == 0))
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(s < n_ref[0])
    def _():
        h = h_ref[...]
        # every product is taken in float32 and rounded once to the model's
        # dtype: what a multiplication in that dtype is
        g = jnp.dot(h, wg_ref[...], preferred_element_type=f32).astype(dtype)
        u = jnp.dot(h, wu_ref[...], preferred_element_type=f32).astype(dtype)
        a = (jax.nn.silu(g.astype(f32)).astype(dtype).astype(f32) * u.astype(f32)).astype(dtype)
        a = (a.astype(f32) * gate_ref[...]).astype(dtype)
        acc[...] += jnp.dot(a, wo_ref[...], preferred_element_type=f32)

    @pl.when((s == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1))
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_experts_step(
    h: Array,  # [T, D]
    gate_cols: Array,  # [E, T, 1] float32 (``plan``)
    ids: Array,  # [E] int32 (``plan``)
    n: Array,  # [1] int32 (``plan``)
    w_in: Array,  # [L, E, D, 2F] — every layer's held experts, [gate | up]
    w_out: Array,  # [L, E, F, D]
    layer: Array,  # [1] int32
    *,
    interpret: bool = False,
) -> Array:
    """The routed experts' sub-block of layer ``layer`` for ``T`` tokens,
    reading the ``n`` experts ``ids[:n]`` of the stacks and no other."""
    T, D = h.shape
    E, F = w_out.shape[1:3]
    tile = width_tile(F, D, w_in.dtype.itemsize)
    nj = F // tile
    # whole sublane tiles of the model's dtype (a row of zeros adds nothing)
    pad = -T % (32 // h.dtype.itemsize)
    if pad:
        h = jnp.pad(h, [(0, pad), (0, 0)])
        gate_cols = jnp.pad(gate_cols, [(0, 0), (0, pad), (0, 0)])
    Tp = T + pad

    def block(s, j, ids, n):
        """Slot ``s``, tile ``j`` -> (expert, tile): its own while the slot is
        a touched one, else where the last touched slot ended."""
        live = s < n[0]
        return (ids[jnp.where(live, s, jnp.maximum(n[0] - 1, 0))],
                jnp.where(live, j, nj - 1))

    def columns(half):  # of W_in[e]: gate (0) or up (1)
        def index(s, j, layer, ids, n):
            e, t = block(s, j, ids, n)
            return layer[0], e, 0, half * nj + t
        return pl.BlockSpec((None, None, D, tile), index)

    def rows(s, j, layer, ids, n):
        e, t = block(s, j, ids, n)
        return layer[0], e, t, 0

    def gate(s, j, layer, ids, n):
        return block(s, j, ids, n)[0], 0, 0

    whole = pl.BlockSpec((Tp, D), lambda s, j, *_: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(E, nj),
        in_specs=[
            whole,
            pl.BlockSpec((None, Tp, 1), gate),
            columns(0),
            columns(1),
            pl.BlockSpec((None, None, tile, D), rows),
        ],
        out_specs=whole,
        scratch_shapes=[pltpu.VMEM((Tp, D), jnp.float32)],
    )
    weights = 3 * D * tile * w_in.dtype.itemsize
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, D), h.dtype),
        compiler_params=pltpu.CompilerParams(
            # the accumulator is carried from step to step
            dimension_semantics=("arbitrary", "arbitrary"),
            # a step's three blocks, double-buffered, and the rows' buffers
            vmem_limit_bytes=2 * weights + 16 * Tp * D + 8 * 1024 * 1024,
        ),
        name="moe_experts_step",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), ids, n, h, gate_cols, w_in, w_in, w_out)
    return out[:T]
