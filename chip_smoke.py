#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

One process drives the default serving path once at the full width of
``tinyllama-1.1b`` (bf16, random weights from the seed) with the
``bge-base-en`` encoder, through the entry points a user calls: it assembles
the server exactly as ``python -m finchat_tpu`` does (``load_config`` →
``build_app`` → ``app.start(serve_http=True)``; memory Kafka broker, in-memory
store) and talks to it over HTTP and Kafka. Engine options stay at their
defaults except ``max_new_tokens``, which only bounds the run's length. The
mesh is pinned (all axes 1) so that a host with four visible chips does not
silently become TP=4.

Phases, each fatal on its first failure (nothing is downgraded to a warning):

1. device: ``jax.default_backend()`` must be ``tpu`` — also when the process
   inherits ``JAX_PLATFORMS=cpu``;
2. kernels: the default path's Pallas kernels (paged attention for decode and
   for a prefill chunk, the in-place KV append, ragged paged attention, flash
   attention), Mosaic-compiled at the model's widths, against their
   ``jax.numpy`` oracles; paged decode attention and the one-token Mamba-2
   state update also at the shapes of the benchmark's cells, the latter with
   its own device time against its stream bound;
3. start-up: ``build_app`` (warm-up compiles every serving variant); the
   engine must have resolved the compiled ``pallas`` backend;
4. logits: one prefill → decode comparison, ``pallas`` engine vs ``ref``
   engine, on the app's own weights — logits, not sampled tokens;
5. serving: ``GET /health``; ``POST /transactions`` then a retrieval through
   the agent's retriever; four concurrent ``POST /chat/stream`` requests
   staggered so that a later one prefills while an earlier one decodes; two
   Kafka turns of one conversation. Every stream must end in ``complete``
   with tokens and no ``error``; the ragged round and the steady decode step
   must both have run; no rebuild, breaker trip, shed, quant-matmul fallback,
   failed dispatch or anomaly (watchdog fire included) may have happened; and
   no engine step may have compiled after warm-up.

The last line of standard output is one JSON object naming the device as JAX
reports it. Warm-up seconds are printed as set-up time; nothing here is a
speed measurement.
"""

from __future__ import annotations

import asyncio
import dataclasses
import faulthandler
import json
import sys
import threading
import time
import urllib.request
from functools import partial

# the driver allows 1200 s; past this the process dumps every thread's stack
# and exits non-zero on its own instead of hanging until it is killed
DEADLINE_SECONDS = 1150

MODEL_PRESET = "tinyllama-1.1b"
EMBED_PRESET = "bge-base-en"
PORT = 8931
N_STREAMS = 4
MAX_NEW_TOKENS = 96  # long enough that stream A still decodes while B-D prefill

# Kernel parity, bf16 in and out. The kernel rounds unnormalised
# probabilities to bf16 page by page and rescales its fp32 accumulator; the
# oracle rounds the normalised weights once. bf16 keeps 8 mantissa bits
# (eps 2^-8 = 3.9e-3), so outputs of O(1) magnitude may differ by a few ulps.
KERNEL_ATOL = KERNEL_RTOL = 2e-2
# Engine logits, pallas vs ref: the attention outputs above differ by bf16
# ulps, and each of the 2 x n_layers residual additions rounds to bf16 again,
# so the difference random-walks to a few percent of the logits' spread. The
# statistic is the RMS difference over the vocabulary relative to the
# reference logits' standard deviation (a wrong page, mask or head mapping
# decorrelates the logits and puts it near 1).
LOGITS_RMS_TOL = 0.1

CONTEXT = {"name": "Ada", "income": 90000, "savings_goal": 20000}
TRANSACTIONS = [
    {"text": "Blue Bottle Coffee $4.50", "amount": -4.5, "category": "coffee"},
    {"text": "Whole Foods Market $82.17", "amount": -82.17, "category": "groceries"},
    {"text": "Shell gas station $41.00", "amount": -41.0, "category": "transport"},
    {"text": "Payroll deposit $3,200.00", "amount": 3200.0, "category": "income"},
    {"text": "Philz Coffee $5.25", "amount": -5.25, "category": "coffee"},
]


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def require(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def say(message: str) -> None:
    print(message, flush=True)


# --- phase 2: kernels against their oracles --------------------------------

def kernel_error(name: str, got, want, note: str = "") -> float:
    """Max abs error of a kernel's output against its oracle's; raises
    ``SmokeFailure`` on a non-finite output or an element out of tolerance."""
    import jax.numpy as jnp
    import numpy as np

    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(want.astype(jnp.float32))
    require(np.isfinite(got).all(), f"kernel {name}: non-finite output")
    diff = np.abs(got - want)
    error = float(diff.max())
    bad = diff > KERNEL_ATOL + KERNEL_RTOL * np.abs(want)
    require(not bad.any(),
            f"kernel {name}: {int(bad.sum())} of {bad.size} elements off "
            f"the oracle (max abs err {error:.4f})")
    say(f"kernel {name}: ok ({note}max abs err {error:.4f})")
    return error


def check_kernels(n_heads: int, n_kv: int, head_dim: int, page_size: int,
                  backend: str, *, prefill_chunk: int = 512) -> dict[str, float]:
    """Each default-path kernel vs its oracle at the given widths; returns
    the max abs error per kernel. ``backend`` is ``pallas`` (compiled) on
    the chip; the CPU test passes ``pallas-interpret``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from finchat_tpu.engine.kv_cache import scale_rows, scatter_kv_chunk
    from finchat_tpu.ops import dispatch
    from finchat_tpu.ops.flash_attention import flash_attention
    from finchat_tpu.ops.kv_append import paged_kv_append
    from finchat_tpu.ops.refs import mha_reference

    interpret = backend == "pallas-interpret"
    dtype = jnp.bfloat16
    H, D = n_heads, head_dim
    errors: dict[str, float] = {}

    def close(name: str, got, want) -> None:
        errors[name] = kernel_error(name, got, want)

    def cache(seed: int, n_pages: int):
        k1, k2 = jax.random.split(jax.random.key(seed))
        shape = (2, n_pages, page_size, n_kv * D)  # two layers; layer 1 is read
        return jax.random.normal(k1, shape, dtype), jax.random.normal(k2, shape, dtype)

    def shuffled_table(rows: int, max_pages: int, seed: int):
        perm = np.random.RandomState(seed).permutation(rows * max_pages) + 1
        return jnp.asarray(perm.reshape(rows, max_pages), jnp.int32)

    layer = jnp.asarray([1], jnp.int32)
    kw = dict(page_size=page_size, n_kv=n_kv)
    max_pages = 8
    span = max_pages * page_size

    def cache_q8(seed: int, n_pages: int):
        """An int8 cache with its per-token-per-head scale blocks."""
        keys = jax.random.split(jax.random.key(seed), 4)
        shape = (2, n_pages, page_size, n_kv * D)
        sshape = (2, n_pages, scale_rows(n_kv), page_size)
        return (*(jax.random.randint(k, shape, -127, 128, jnp.int8) for k in keys[:2]),
                *(jax.random.uniform(k, sshape, jnp.float32, 0.004, 0.012)
                  for k in keys[2:]))

    def paged(name: str, C: int, ctx: list[int], *, quantized: bool = False) -> None:
        B = len(ctx)
        scales = {}
        if quantized:
            k_pages, v_pages, scales["k_scales"], scales["v_scales"] = cache_q8(
                1, 1 + B * max_pages)
        else:
            k_pages, v_pages = cache(1, 1 + B * max_pages)
        table = shuffled_table(B, max_pages, 0)
        kv_len = jnp.asarray(ctx, jnp.int32)
        q_offset = jnp.maximum(kv_len - C, 0)
        q = jax.random.normal(jax.random.key(2), (B, C, H, D), dtype)
        args = (q, k_pages, v_pages, table, q_offset, kv_len, layer)
        got = dispatch.paged_attention(*args, backend=backend, **kw, **scales)
        want = dispatch.paged_attention(*args, backend="ref", **kw, **scales)
        # an empty slot is exact zeros from the kernel (the oracle's fully
        # masked softmax averages V instead, so it is not compared there)
        live = np.asarray(ctx) > 0
        require(not np.asarray(got.astype(jnp.float32))[~live].any(),
                f"kernel {name}: an empty slot produced non-zero output")
        close(name, got[live], want[live])

    # decode: one query per slot; an empty slot, one token, both sides of a
    # page boundary, a full row
    decode_ctx = [0, 1, page_size, page_size + 1, span // 2 + 3, span]
    paged("paged_attention[decode]", 1, decode_ctx)
    # one prefill chunk at an offset, and a first chunk
    chunk_ctx = [prefill_chunk, min(span, prefill_chunk + page_size + 5)]
    paged("paged_attention[prefill chunk]", prefill_chunk, chunk_ctx)
    # a spec-verify block: one token and two drafts, padded to a sublane tile
    paged("paged_attention[spec verify]", 3, [3, page_size + 2, span // 2 + 3, span])
    # the int8 cache (kv_quant=int8) through the same walk
    paged("paged_attention[decode, int8]", 1, decode_ctx, quantized=True)
    paged("paged_attention[prefill chunk, int8]", prefill_chunk, chunk_ctx,
          quantized=True)

    # in-place append vs the XLA scatter (a copy: exact outside the trash page)
    B = 8
    k_pages, v_pages = cache(3, 1 + B * max_pages)
    table = jnp.arange(1, 1 + B * max_pages, dtype=jnp.int32).reshape(B, max_pages)
    pos = jnp.asarray([(i * 53 + i) % span for i in range(B)], jnp.int32)
    n_valid = jnp.asarray([0 if i == 3 else 1 for i in range(B)], jnp.int32)
    k_new = jax.random.normal(jax.random.key(4), (B, 1, n_kv, D), dtype)
    v_new = jax.random.normal(jax.random.key(5), (B, 1, n_kv, D), dtype)
    want_k, want_v = scatter_kv_chunk(
        k_pages, v_pages, k_new, v_new, table, pos, n_valid, page_size, jnp.int32(1))
    kv_new = jnp.concatenate([k_new.reshape(B, 1, -1), v_new.reshape(B, 1, -1)], -1)
    got_k, got_v = paged_kv_append(
        kv_new, k_pages, v_pages, table, pos, n_valid, layer,
        page_size=page_size, interpret=interpret)
    exact = bool(jnp.array_equal(got_k[:, 1:], want_k[:, 1:])
                 and jnp.array_equal(got_v[:, 1:], want_v[:, 1:]))
    require(exact, "kernel kv_append: pages differ from the XLA scatter")
    errors["kv_append"] = 0.0
    say("kernel kv_append: ok (bit-equal to the scatter outside the trash page)")

    # ragged: a prefill chunk at an offset, decode rows around a page
    # boundary, a spec-verify-sized row, an empty row, buffer padding
    rows = [(prefill_chunk // 2 + 7, page_size), (1, 0), (1, page_size - 1),
            (1, page_size), (1, span // 2), (4, 40), (0, 0), (prefill_chunk // 4, 0)]
    R = len(rows)
    T = 2 * prefill_chunk
    tok_row = np.full((T,), R, np.int32)
    tok_pos = np.zeros((T,), np.int32)
    kv_len = np.zeros((R,), np.int32)
    t = 0
    for r, (q_len, ctx_before) in enumerate(rows):
        tok_row[t:t + q_len] = r
        tok_pos[t:t + q_len] = ctx_before + np.arange(q_len)
        kv_len[r] = ctx_before + q_len
        t += q_len
    k_pages, v_pages = cache(6, 1 + R * max_pages)
    args = (jax.random.normal(jax.random.key(7), (T, H, D), dtype), k_pages,
            v_pages, shuffled_table(R, max_pages, 1), jnp.asarray(tok_row),
            jnp.asarray(tok_pos), jnp.asarray(kv_len), layer)
    close("ragged_paged_attention",
          dispatch.ragged_paged_attention(*args, backend=backend, **kw)[:t],
          dispatch.ragged_paged_attention(*args, backend="ref", **kw)[:t])

    # flash (contiguous KV)
    keys = jax.random.split(jax.random.key(8), 3)
    S = prefill_chunk
    q = jax.random.normal(keys[0], (2, S, H, D), dtype)
    k = jax.random.normal(keys[1], (2, S, n_kv, D), dtype)
    v = jax.random.normal(keys[2], (2, S, n_kv, D), dtype)
    close("flash_attention", flash_attention(q, k, v, causal=True, interpret=interpret),
          mha_reference(q, k, v, causal=True))
    return errors


def check_decode_at_cell_shape(backend: str, *, rows: int = 16, n_heads: int = 32,
                               n_kv: int = 8, head_dim: int = 128,
                               page_size: int = 128, width: int = 128,
                               contexts: tuple[int, int] = (5000, 12000),
                               pool_pages: int = 1600, shared_pages: int = 0,
                               quantized: bool = False, window: int = 0) -> float:
    """Paged decode attention vs its oracle at the decode shape of the
    benchmark's cells (``mixtral-report-saturated``; 20 / 4 heads for
    ``falcon-h1-report-saturated``): ragged contexts under a page table far
    wider than any row, dead entries on the trash page. The kernel's trash
    page holds NaN (an int8 cache: NaN scales) — a dead page read would show
    — and the oracle, which gathers the table's whole width before it masks,
    gets the same cache with that page zeroed. With ``shared_pages`` every
    row holds the same physical pages at the head of its table, as the cells'
    16 rows hold the system prompt's 31: the kernel's shared-head pass reads
    them once for all rows. With ``window`` (a sliding-window layer of
    ``phi4-flash-report-saturated``: 40 / 10 heads, a table of 6 pages, the
    contexts COMPACTED to what a row's bounded page list holds) a query masks
    what lies ``window`` or more before it and no shared head is read.
    Returns the max abs error."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from finchat_tpu.engine.kv_cache import scale_rows
    from finchat_tpu.ops import dispatch

    dtype = jnp.bfloat16
    rng = np.random.RandomState(25)
    ctx = rng.randint(contexts[0], contexts[1] + 1, size=rows)
    live = -(-ctx // page_size)
    require(live.max() <= width and live.sum() < pool_pages,
            "cell-shape case: the contexts do not fit the table or the pool")
    require(shared_pages * page_size <= ctx.min(),
            "cell-shape case: the shared head is longer than a row")
    table = np.zeros((rows, width), np.int32)
    pool = rng.permutation(np.arange(1, pool_pages))
    head, used = pool[:shared_pages], shared_pages
    for row, n in enumerate(live):
        table[row, :shared_pages] = head
        table[row, shared_pages:n] = pool[used:used + n - shared_pages]
        used += n - shared_pages
    keys = jax.random.split(jax.random.key(25), 5)
    shape = (2, pool_pages, page_size, n_kv * head_dim)  # layer 1 is read
    # the trash page set in place (at 30 KV heads K and V are 3.1 GB each: a
    # second copy of the cache for the oracle would not fit beside the first)
    trash = jax.jit(lambda x, value: x.at[:, 0].set(value), donate_argnums=0)
    kv_len = jnp.asarray(ctx, jnp.int32)
    q = jax.random.normal(keys[4], (rows, 1, n_heads, head_dim), dtype)
    rest = (jnp.asarray(table), kv_len - 1, kv_len, jnp.asarray([1], jnp.int32))
    kw = dict(page_size=page_size, n_kv=n_kv)
    if window:  # (the engine hands a window layer's call a shared head of no pages)
        kw.update(window=window, shared=(jnp.zeros((rows,), jnp.int32), jnp.zeros((2,), jnp.int32)))

    def oracle(pages, **scales):
        # a row at a time: the oracle gathers a row's whole table, 0.12 GB of
        # K at 30 KV heads, 2 GB for 16 rows at once
        return jnp.concatenate([
            dispatch.paged_attention(q[r:r + 1], *pages, rest[0][r:r + 1], rest[1][r:r + 1],
                                     rest[2][r:r + 1], rest[3], backend="ref", **kw, **scales)
            for r in range(rows)])

    if quantized:
        pages = [jax.random.randint(k, shape, -127, 128, jnp.int8) for k in keys[:2]]
        sshape = (2, pool_pages, scale_rows(n_kv), page_size)
        poisoned = [trash(jax.random.uniform(k, sshape, jnp.float32, 0.004, 0.012), jnp.nan)
                    for k in keys[2:4]]
        names = ("k_scales", "v_scales")
        got = dispatch.paged_attention(q, *pages, *rest, backend=backend, **kw,
                                       **dict(zip(names, poisoned)))
        # the trash page's scales are whatever the oracle likes: its int8 page
        # is masked, and zero scales keep the product finite
        clean = [trash(x, 0.0) for x in poisoned]
        want = oracle(pages, **dict(zip(names, clean)))
    else:
        pages = [trash(jax.random.normal(k, shape, dtype), jnp.nan) for k in keys[:2]]
        got = jax.block_until_ready(
            dispatch.paged_attention(q, *pages, *rest, backend=backend, **kw))
        pages = [trash(x, 0) for x in pages]
        want = oracle(pages)
    # a non-finite output here means a dead table entry was read
    name = "paged_attention[decode, cell shape" + (", int8" if quantized else "") + (
        f", window {window}" if window else "") + (
        f", {shared_pages} shared pages]" if shared_pages else "]")
    return kernel_error(
        name, got, want,
        f"{rows} rows, {n_heads} / {n_kv} heads, {int(ctx.sum())} context tokens, "
        f"{int(live.sum())} live of {rows * width} table entries; ")


def device_ops_us(run, calls: int) -> list[tuple[str, float]]:
    """``(operation name, microseconds)`` of every device operation in a
    profiler capture of ``calls`` calls of ``run()`` (which returns an array
    of the last call to wait on)."""
    import tempfile
    from pathlib import Path

    import jax

    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for _ in range(calls):
            out = run()
        out.block_until_ready()
        jax.profiler.stop_trace()
        path = next(Path(trace_dir).rglob("*.xplane.pb"))
        return [(ev.name.split(" = ")[0], ev.duration_ns / 1e3)
                for plane in jax.profiler.ProfileData.from_file(str(path)).planes
                if plane.name.startswith("/device:TPU:")
                for line in plane.lines if line.name == "XLA Ops" for ev in line.events]


def check_ssm_step_at_cell_shape(backend: str, *, rows: int = 16, heads: int = 32,
                                 head_dim: int = 128, state: int = 256,
                                 groups: int = 2, layers: int = 5, layer: int = 3,
                                 timed_calls: int = 20) -> dict[str, float]:
    """The one-token Mamba-2 state update (``ops/ssm_step.py``) vs
    ``models/ssm.py``'s ``_step`` at the shape of the benchmark's cell
    (``falcon-h1-report-saturated``): 16 rows of 32 heads of 128 x 256 float32
    in layer 3 of 5, one row inert (``granite-h-small-report-saturated``'s by
    its arguments: 128 heads of 64 x 128 in nine layers, stored as pairs).
    ``y`` and the layer's new state to float32 round-off; the inert row and
    every other layer bit for bit. On the chip
    (``pallas``) also the kernel's own device time from a profiler capture,
    against its stream bound (every row's state read and written once at
    819 GB/s: what ``ssm_state_roofline.sat`` divides by) — the number to
    tune ``ops/ssm_step.py``'s block size by."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from finchat_tpu.models.ssm import _step
    from finchat_tpu.ops.ssm_step import ssm_state_step, to_logical, to_stored

    f32, hg = jnp.float32, heads // groups
    ks = jax.random.split(jax.random.key(28), 7)
    ssm_state = jax.random.normal(ks[0], (layers, rows, heads, head_dim, state), f32)
    xs = jax.random.normal(ks[1], (rows, heads, head_dim), f32)
    dt = jax.nn.softplus(jax.random.normal(ks[2], (rows, heads), f32)).at[1].set(0.0)
    A = -jnp.exp(jax.random.normal(ks[3], (heads,), f32))
    Bm = jax.random.normal(ks[4], (rows, groups, state), f32)
    Cm = jax.random.normal(ks[5], (rows, groups, state), f32)
    D = jax.random.normal(ks[6], (heads,), f32)
    at = jnp.asarray([layer], jnp.int32)

    want_y, want_new = jax.jit(_step)(
        ssm_state[layer].reshape(rows, groups, hg, head_dim, state),
        xs.reshape(rows, groups, hg, head_dim), dt.reshape(rows, groups, hg),
        A.reshape(groups, hg), Bm, Cm, D.reshape(groups, hg))
    before = np.asarray(ssm_state)
    # (the kernel takes the state as the device holds it: ops/ssm_step.py stored_shape)
    y, ssm_state = ssm_state_step(to_stored(ssm_state, groups), xs, dt, A, Bm, Cm, D, at,
                                  interpret=backend == "pallas-interpret")
    after = np.asarray(to_logical(ssm_state, (heads, head_dim, state), groups))
    errors = {}
    for name, got, want in (("y", y, want_y.reshape(y.shape)),
                            ("state", after[layer], np.asarray(want_new).reshape(after[layer].shape))):
        got, want = np.asarray(got), np.asarray(want)
        require(np.isfinite(got).all(), f"kernel ssm_state_step: non-finite {name}")
        errors[name] = float(np.abs(got - want).max())
        # y sums 256 products of O(1) numbers in another order than _step
        require(np.allclose(got, want, rtol=1e-5, atol=1e-4),
                f"kernel ssm_state_step: {name} off _step (max abs err {errors[name]:.3g})")
    untouched = [i for i in range(layers) if i != layer]
    require(np.array_equal(after[untouched], before[untouched]),
            "kernel ssm_state_step: a layer the grid does not visit changed")
    require(np.array_equal(after[layer, 1], before[layer, 1]),
            "kernel ssm_state_step: the inert row's state changed")
    say(f"kernel ssm_state_step: ok (layer {layer} of {layers}, {rows} rows; max abs "
        f"err y {errors['y']:.3g}, state {errors['state']:.3g})")
    if backend != "pallas":
        return errors

    def once():
        nonlocal ssm_state
        y, ssm_state = ssm_state_step(ssm_state, xs, dt, A, Bm, Cm, D, at)
        return y

    ops = device_ops_us(once, timed_calls)
    kernel = [us for name, us in ops if "ssm_state_step" in name]
    require(len(kernel) == timed_calls,
            f"kernel ssm_state_step: {len(kernel)} custom calls in a capture of {timed_calls}")
    small = (2 * heads * head_dim + 2 * groups * state + heads) * 4
    bound_us = 1e6 * rows * (2 * heads * head_dim * state * 4 + small) / 819e9
    errors.update(kernel_us=float(np.mean(kernel)),
                  call_us=sum(us for _name, us in ops) / timed_calls, bound_us=bound_us)
    say(f"kernel ssm_state_step: {errors['kernel_us']:.1f} us a call (min "
        f"{min(kernel):.1f}, max {max(kernel):.1f}), {errors['call_us']:.1f} us with the "
        f"operations around it; stream bound {bound_us:.1f} us: "
        f"{100 * bound_us / errors['call_us']:.1f} % of it")
    return errors


def _router_picking(h, picks, width: int):
    """A router ``[D, width]`` float32 under which row ``i`` of ``h`` [T, D]
    picks exactly ``picks[i]`` (in that order): the least-norm solution of
    ``h @ router = Z`` with ``Z`` 4 - 0.2 j on the row's j-th pick and -4
    elsewhere (T <= D; the margins are far above a bf16 matmul's error)."""
    import numpy as np

    h = np.asarray(h, np.float64)
    Z = np.full((h.shape[0], width), -4.0)
    for i, row in enumerate(picks):
        Z[i, list(row)] = 4.0 - 0.2 * np.arange(len(row))
    return (h.T @ np.linalg.solve(h @ h.T, Z)).astype(np.float32)


def check_moe_at_cell_shape(backend: str, *, configuration: str = "granite-4.0-h-small",
                            rows: int = 16, tokens: int = 4096,
                            touched: tuple[int, ...] = (30, 36), layers: int = 0, layer: int = 0,
                            timed_calls: int = 10, shrink: dict | None = None) -> dict[str, float]:
    """``moe_mlp`` at the shape of ``perfbench/configs/<configuration>.json``'s
    routed layer — by default ``granite-h-small-report-saturated``'s: 36 held
    experts of 768, fused [gate | up], of a router of 72, 10 a token, beside a
    shared expert of 1,536; hidden 4,096; ``trinity-mini`` is 128 of 128 of
    1,024, 8 a token, hidden 2,048 (``rows`` 32, ``touched`` about 93 there);
    ``shrink`` replaces keys of the file, for a CPU run — against the plain
    reference's loop over the held experts (the file's adapter under
    ``perfbench/models/``):

    - the one-token step's ``rows`` tokens under a router built so that the
      rows pick exactly ``touched`` of the held experts (where the router is
      wider than what is held, half of a row's picks are absent ones), in
      BOTH of its forms: ``backend``'s (``ops/moe_step.py``'s pass over the
      touched experts on a kernel backend) and dense dispatch over every held
      stack (``ref``), with the counts each returns; ``layers`` > 0 hands both
      the stacks ``[layers, E, ...]`` with the index ``layer``, as the
      engine's scan does (0: a layer's own leaf);
    - the top ragged bucket's ``tokens`` (the grouped form: the (token, pick)
      pairs sorted by expert through ``lax.ragged_dot``; 0 = not this leg).

    On the chip (``pallas``) also each form's device time from a profiler
    capture against its bound: the step's, without the shared expert, against
    the bytes of the experts it TOUCHED (a layer of the adapter's
    ``moe_step_stream_bytes``, what ``moe_expert_roofline.sat`` divides by),
    the bucket's against its pairs' FLOPs at 197 TF/s and its bytes. To read
    the pass alone at another tile, set ``ops.moe_step._WHOLE_BYTES`` /
    ``_BLOCK_BYTES`` and ``jax.clear_caches()`` between calls — but a tile is
    judged inside the compiled step (``benchmarks/moe_step_in_step.py``): PR 48
    read 512 columns faster than 256 alone and slower there."""
    import dataclasses
    import inspect
    import json
    from pathlib import Path

    import jax
    import jax.numpy as jnp
    import numpy as np

    from finchat_tpu.models.llama import StackedLeaf, moe_mlp
    from perfbench.models import adapter

    file = json.loads((Path(__file__).resolve().parent
                       / f"perfbench/configs/{configuration}.json").read_text())
    file.update(shrink or {})
    model = adapter(file)
    c, s = model.program_config(file), model._sizes(file)
    D, E, F, Fs, R = c.dim, c.n_experts, c.hidden_dim, c.moe_shared_dim, c.moe_router_width
    k = c.top_k_experts
    held_picks = k if R == E else k // 2  # of a row; the others fall on absent experts
    ks = jax.random.split(jax.random.key(34), 8)
    bf16 = jnp.bfloat16

    def normal(key, shape, fan_in, dtype=bf16):
        return (jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5).astype(dtype)

    lp = {"router": normal(ks[0], (D, R), D, jnp.float32),
          "moe_in": normal(ks[1], (E, D, 2 * F), D), "moe_out": normal(ks[2], (E, F, D), F),
          "shared_in": normal(ks[3], (D, 2 * Fs), D), "shared_out": normal(ks[4], (Fs, D), Fs)}
    if c.moe_select_bias:
        lp["router_bias"] = c.moe_bias_init_std * jax.random.normal(ks[7], (R,), jnp.float32)
    results = {}
    # one adapter's loop takes the pick to swap, another's also returns the margin
    swap = {"swap": False} if "swap" in inspect.signature(model._experts).parameters else {}

    # the expert stacks as the engine's scan hands them: ``[layers, E, ...]`` and
    # the index (the other layers zeros)
    stacks = {name: StackedLeaf(jnp.pad(lp[name][None], [(layer, layers - layer - 1)] + [(0, 0)] * 3),
                                jnp.asarray(layer, jnp.int32))
              for name in ("moe_in", "moe_out")} if layers else {}

    def against_reference(label, got, h, lp):
        with jax.default_matmul_precision("highest"):
            want = model._experts(h[0].astype(jnp.float32),
                                  {name: leaf[None] for name, leaf in lp.items()}, 0, s,
                                  lambda w: w, **swap)
        want = want[0] if isinstance(want, tuple) else want
        got, want = np.asarray(got[0].astype(jnp.float32)), np.asarray(want)
        require(np.isfinite(got).all(), f"moe_mlp {label}: non-finite output")
        rel = float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))
        # bf16 inputs and one bf16 rounding of every expert's output; a pick
        # that flips at a margin of a bf16 step exchanges near-equal gates
        require(rel < 0.03, f"moe_mlp {label}: off the reference by {rel:.4f} of its spread")
        return rel

    def timed(label, run, bound_us, note):
        ops = device_ops_us(run, timed_calls)
        call_us = sum(us for _name, us in ops) / timed_calls
        by_op = {}
        for name, us in ops:
            by_op[name] = by_op.get(name, 0.0) + us / timed_calls
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:6]
        say(f"moe_mlp {label}: {call_us:.1f} us a call; {note} {bound_us:.1f} us: "
            f"{100 * bound_us / call_us:.1f} % of it; largest operations "
            + ", ".join(f"{name} {us:.0f}" for name, us in top))
        return call_us

    # the one-token step: the rows' 5 held picks walk round the touched set
    h = jax.random.normal(ks[5], (1, rows, D), jnp.float32).astype(bf16)
    live = jnp.ones((1, rows), bool)
    routed = dataclasses.replace(c, moe_shared_dim=0)  # what is timed: the routed experts alone
    for t in touched:
        held = np.sort(np.random.RandomState(t).permutation(E)[:t])
        picks = [[*(held[(held_picks * i + j) % t] for j in range(held_picks)),
                  *(E + (held_picks * i + j) % (R - E) for j in range(k - held_picks))]
                 for i in range(rows)]
        lp_t = {**lp, "router": jnp.asarray(_router_picking(h[0].astype(jnp.float32), picks, R))}
        lp_run = {**lp_t, **stacks}
        for form, form_backend in (("touched", backend), ("dense", "ref")):
            label = f"{form} at {t} of {E}"
            run = jax.jit(lambda h, lp, b=form_backend: moe_mlp(h, lp, c, live=live, backend=b))
            got, counts = run(h, lp_run)
            counts = tuple(int(n) for n in counts)
            want_read = t if form == "touched" and form_backend != "ref" else E
            require(counts == (t, want_read),
                    f"moe_mlp {label}: counts (touched, read) {counts}, not {(t, want_read)}")
            rel = results[f"{form}_{t}_rel"] = against_reference(label, got, h, lp_t)
            say(f"moe_mlp {label}: ok ({rows} tokens, touched / read {counts}; rms error "
                f"{rel:.4f} of the reference's spread)")
            if backend == "pallas":
                one_layer = (t * 3 * D * F + 2 * rows * D) * 2  # the touched experts, the rows
                bound_us = results[f"bound_{t}_us"] = 1e6 * one_layer / 819e9
                alone = jax.jit(lambda h, lp, b=form_backend: moe_mlp(h, lp, routed, backend=b))
                results[f"{form}_{t}_us"] = timed(
                    label + ", without the shared expert", lambda: alone(h, lp_run), bound_us,
                    f"stream bound of the {t} touched experts")

    if not tokens:
        return results
    h = jax.random.normal(ks[6], (1, tokens, D), jnp.float32).astype(bf16)
    run = jax.jit(lambda h, lp: moe_mlp(h, lp, c, backend=backend))
    rel = results["grouped_rel"] = against_reference("grouped", run(h, lp), h, lp)
    say(f"moe_mlp grouped: ok ({tokens} tokens; rms error {rel:.4f} of the reference's spread)")
    if backend == "pallas":
        held_pairs = float(np.sum(np.asarray(jax.lax.top_k(
            h[0].astype(jnp.float32) @ lp["router"], k)[1]) < E))
        flops = 2 * held_pairs * D * 3 * F + 2 * tokens * D * 3 * Fs
        nbytes = 2 * (E * 3 * D * F + 3 * D * Fs + 2 * tokens * D)
        results["grouped_bound_us"] = bound_us = 1e6 * max(flops / 197e12, nbytes / 819e9)
        results["grouped_us"] = timed(
            "grouped", lambda: run(h, lp), bound_us,
            f"bound of {held_pairs:.0f} held pairs ({flops / 1e12:.2f} TFLOP at 197 TF/s, "
            f"{nbytes / 1e9:.2f} GB at 819 GB/s)")
    return results


def check_gdn_step_at_cell_shape(backend: str, *, rows: int = 16, heads: int = 30,
                                key_dim: int = 96, value_dim: int = 192,
                                layers: int = 6, layer: int = 4,
                                timed_calls: int = 20,
                                channel_decay: bool = False) -> dict[str, float]:
    """The one-token gated-delta-rule update (``ops/gdn_step.py``, in place
    over the whole slot batch as ``decode_step`` runs it) vs the recurrence as
    it is written, in float64, at the shape of the benchmark's cell
    (``olmo-hybrid-report-saturated``): 16 rows of 30 heads of 96 x 192
    float32 — 15 tiles of 96 x 384 as ``LlamaConfig.state_shape`` lays them —
    in layer 4 of 6, one row inert. ``o`` and the layer's new state to float32
    round-off; the inert row and every other layer bit for bit. On the chip
    (``pallas``) also the kernel's device time from a profiler capture against
    its stream bound (every row's state read and written once at 819 GB/s:
    what ``gdn_state_roofline.sat`` divides by) — the number to tune the
    kernel by — and beside it XLA's ``models/gdn.py`` ``_step`` over the same
    leaf, the two passes it replaces (the ``ref`` backend's body and the
    fallback for gathered slots), which is also the form whose values a
    ``ref`` backend checks. ``channel_decay``: the decay a vector over a head's
    key channels (the kernel's second form; ``kimi-linear-report-saturated``
    runs it at 32 rows of 32 heads of 128 x 128, a head a tile, in 7 layers),
    one channel in eight decaying to nothing in the token (exp(-80))."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from finchat_tpu.models import gdn
    from finchat_tpu.models.llama import FULL, LINEAR, LlamaConfig
    from finchat_tpu.models.ssm import SsmRows, _read, _write
    from finchat_tpu.ops.gdn_step import gdn_state_step

    f32 = jnp.float32
    tiles = LlamaConfig(n_layers=4, layer_pattern=(LINEAR, LINEAR, LINEAR, FULL), gdn_heads=heads,
                        gdn_key_dim=key_dim, gdn_value_dim=value_dim).state_shape[0]
    ks = jax.random.split(jax.random.key(32), 6)
    by_head = 0.3 * jax.random.normal(ks[0], (layers * rows, heads, key_dim, value_dim), f32)
    state = gdn._tiles(by_head, tiles).reshape(layers, rows, tiles, key_dim, -1)
    q = gdn._l2norm(jax.random.normal(ks[1], (rows, heads, key_dim), f32)) * key_dim ** -0.5
    k = gdn._l2norm(jax.random.normal(ks[2], (rows, heads, key_dim), f32))
    v = jax.random.normal(ks[3], (rows, heads, value_dim), f32)
    if channel_decay:
        hard = jax.random.uniform(jax.random.fold_in(ks[4], 1), (rows, heads, key_dim)) < 0.125
        g = -jnp.where(hard, 80.0, jax.random.uniform(ks[4], (rows, heads, key_dim), f32, 0.01, 1.5))
        g = g.at[1].set(0.0)
    else:
        g = -jax.random.uniform(ks[4], (rows, heads), f32, 0.01, 1.5).at[1].set(0.0)
    beta = jax.random.uniform(ks[5], (rows, heads), f32, 0.0, 2.0).at[1].set(0.0)
    at = jnp.asarray([layer], jnp.int32)

    S = np.asarray(by_head, np.float64).reshape(layers, rows, heads, key_dim, value_dim)[layer]
    q64, k64, v64, g64, b64 = (np.asarray(t, np.float64) for t in (q, k, v, g, beta))
    S = (np.exp(g64)[..., None] if channel_decay else np.exp(g64)[..., None, None]) * S
    u = b64[..., None] * (v64 - np.einsum("nhkv,nhk->nhv", S, k64))
    want_new = S + k64[..., :, None] * u[..., None, :]
    want_o = np.einsum("nhkv,nhk->nhv", want_new, q64)
    whole = SsmRows(None, jnp.ones((rows,), jnp.int32))

    @partial(jax.jit, donate_argnums=0)
    def xla_update(state, q, k, v, g, beta, at):  # what `mixer` runs on `ref`
        with jax.named_scope("gdn_scan"):
            o, new = gdn._step(gdn._heads(_read(state, at, whole), heads), q, k, v, g, beta)
            return o, _write(state, gdn._tiles(new, tiles), at, whole)

    label = "gdn step (XLA's _step)" if backend == "ref" else "kernel gdn_state_step"
    before = np.asarray(state)
    if backend == "ref":
        o, state = xla_update(state, q, k, v, g, beta, at)
    else:
        o, state = gdn_state_step(state, q, k, v, g, beta, at,
                                  interpret=backend == "pallas-interpret")
    after = np.asarray(state)
    errors = {}
    for name, got, want in (("o", o, want_o),
                            ("state", gdn._heads(jnp.asarray(after[layer]), heads), want_new)):
        got = np.asarray(got)
        require(np.isfinite(got).all(), f"{label}: non-finite {name}")
        errors[name] = float(np.abs(got - want).max())
        require(np.allclose(got, want, rtol=1e-5, atol=1e-4),
                f"{label}: {name} off the recurrence (max abs err {errors[name]:.3g})")
    untouched = [i for i in range(layers) if i != layer]
    require(np.array_equal(after[untouched], before[untouched]),
            f"{label}: a layer the update does not name changed")
    require(np.array_equal(after[layer, 1], before[layer, 1]),
            f"{label}: the inert row's state changed")
    say(f"{label}: ok (layer {layer} of {layers}, {rows} rows, state "
        f"{list(state.shape)}; max abs err o {errors['o']:.3g}, state {errors['state']:.3g})")
    if backend != "pallas":
        return errors

    def once(update):
        def run():
            nonlocal state
            o, state = update(state, q, k, v, g, beta, at)
            return o
        return run

    small = ((3 if channel_decay else 2) * heads * key_dim + 2 * heads * value_dim
             + (1 if channel_decay else 2) * heads) * 4
    bound_us = 1e6 * rows * (2 * heads * key_dim * value_dim * 4 + small) / 819e9
    errors["bound_us"] = bound_us
    for label, update in (("kernel gdn_state_step", gdn_state_step), ("XLA's _step", xla_update)):
        by_op: dict[str, float] = {}
        for name, us in device_ops_us(once(update), timed_calls):
            by_op[name] = by_op.get(name, 0.0) + us / timed_calls
        call_us = sum(by_op.values())
        if update is gdn_state_step:
            kernel = [us for name, us in by_op.items() if "gdn_state_step" in name]
            require(len(kernel) == 1, f"kernel gdn_state_step: {len(kernel)} custom calls a call")
            errors.update(kernel_us=kernel[0], call_us=call_us)
        else:
            errors["xla_us"] = call_us
        say(f"{label}: {call_us:.1f} us a call in {len(by_op)} operations ("
            + ", ".join(f"{name} {us:.1f}" for name, us in sorted(by_op.items(), key=lambda x: -x[1])[:6])
            + f"); stream bound {bound_us:.1f} us: {100 * bound_us / call_us:.1f} % of it")
    return errors


# --- phase 4: engine logits, compiled kernels vs the reference backend ------

def check_pair_walk_at_cell_shape(backend: str, *, rows: int = 32, heads: int = 32,
                                  page_size: int = 128, width: int = 256,
                                  contexts: tuple[int, int] = (9000, 18000),
                                  pool_pages: int = 5120, shared_pages: int = 31) -> float:
    """The width-2 latent walk (``ops/latent_attention.py`` ``pair_attention``:
    a token and its draft against ONE walk of the row's latent pages, the
    second token's own row joined outside the walk) at
    ``joyai-flash-report-saturated``'s shape — 32 rows of 2 x 32 heads over
    640-column latent rows at 9-18k contexts, the rows on the system prompt's
    31 pages — against the chunk form on ``ref`` (a row at a time, the mask
    form). Rows of both widths ride: every third row has no draft (its second
    token's output is computed and unread), and the module's mask (slot 0
    skipped) is one of the two cases. The walk takes the shared head in BOTH
    forms (``paged_attention.latent_head_form``: ``folded``, the rule's at this
    shape, and ``stacked``, the rule's share of the MXU's peak set to 0 for the
    call), each held to ``ref``, and the distance between the two is printed.
    Returns the worst max abs error."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from finchat_tpu.ops import paged_attention
    from finchat_tpu.ops.latent_attention import LatentShape, rows_attention
    from finchat_tpu.ops.paged_attention import shared_head

    rng = np.random.RandomState(55)
    ctx = rng.randint(contexts[0], contexts[1] + 1, size=rows)
    live = -(-(ctx + 2) // page_size)
    require(live.max() <= width and live.sum() < pool_pages,
            "pair-walk case: the contexts do not fit the table or the pool")
    table = np.zeros((rows, width), np.int32)
    pool = rng.permutation(np.arange(1, pool_pages))
    used = shared_pages
    for row, n in enumerate(live):
        table[row, :shared_pages] = pool[:shared_pages]
        table[row, shared_pages:n] = pool[used:used + n - shared_pages]
        used += n - shared_pages
    keys = jax.random.split(jax.random.key(55), 3)
    pages = jax.random.normal(keys[0], (2, pool_pages, page_size, 640), jnp.bfloat16)
    pages = pages.at[..., 576:].set(0)  # a latent row's padding
    keys_pages = jnp.zeros((2, pool_pages, page_size, 128), jnp.bfloat16)
    q = jax.random.normal(keys[1], (rows, 2, heads, 576), jnp.bfloat16)
    start = jnp.asarray(ctx, jnp.int32)
    n_valid = jnp.asarray(np.where(np.arange(rows) % 3 == 2, 1, 2), jnp.int32)
    table, layer = jnp.asarray(table), jnp.int32(1)
    # the pair's own rows as the step has written them
    at = (table[jnp.arange(rows)[:, None], (start[:, None] + jnp.arange(2)) // page_size],
          (start[:, None] + jnp.arange(2)) % page_size)
    own = pages[1][at]
    worst = apart = 0.0
    the_rule = paged_attention.LATENT_MXU_SHARE
    for skip in (0, 1):
        shape = LatentShape(512, 0, 192 ** -0.5, skip)
        args = (q, None, None, pages, keys_pages, layer, table, start, n_valid)
        shared = shared_head(table, start + 1, page_size, n_valid > 0)
        want, _ = jax.jit(lambda *a: rows_attention(
            *a, page_size=page_size, shape=shape, backend="ref"))(*args)
        read = (jnp.arange(2)[None, :] < n_valid[:, None])[..., None, None]
        got = {}
        for form, share in (("folded", the_rule), ("stacked", 0.0)):
            try:  # the form is read while the call is traced
                paged_attention.LATENT_MXU_SHARE = share
                paged_attention.paged_latent_attention.clear_cache()
                require(paged_attention.latent_head_form(rows, 2 * heads, 640, 512, 2) == form,
                        f"pair-walk case: the rule does not give {form} at this shape")
                got[form], _ = jax.jit(lambda *a: rows_attention(
                    *a, page_size=page_size, shape=shape, backend=backend, shared=shared,
                    own=own))(*args)
            finally:
                paged_attention.LATENT_MXU_SHARE = the_rule
                paged_attention.paged_latent_attention.clear_cache()
            error = kernel_error(f"pair walk (skip {skip}, head {form})",
                                 jnp.where(read, got[form], 0), jnp.where(read, want, 0))
            worst = max(worst, error)
        apart = max(apart, float(jnp.max(jnp.abs(
            jnp.where(read, got["folded"].astype(jnp.float32)
                      - got["stacked"].astype(jnp.float32), 0)))))
    say(f"pair walk {backend} vs ref at {rows} rows x 2 x {heads} heads, contexts "
        f"{ctx.min()}-{ctx.max()}, {shared_pages} shared pages, the head folded and stacked: "
        f"ok (worst {worst:.4f}; the two forms {apart:.6f} apart)")
    return worst


def check_draft_step_at_cell_shape(backend: str, *, layers: int = 2, held: int = 8,
                                   rows: int = 32, prompt_len: int = 300, steps: int = 6) -> float:
    """``decode_step`` of a model that drafts, at ``joyai-flash-report-
    saturated``'s widths and rows (the file's, with ``layers`` trunk layers and
    ``held`` experts so that two engines fit the chip at once): prefill, a
    first step without a draft, then greedy width-2 steps on two engines over
    the SAME weights, one per backend — the emitted pairs, the first position's
    logits and the pending draft agree. Returns the worst relative RMS
    difference of the logits."""
    import json
    from pathlib import Path

    import jax
    import jax.numpy as jnp
    import numpy as np

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.models.llama import init_params
    from finchat_tpu.utils.config import EngineConfig
    from perfbench.models import adapter

    file = json.loads((Path(__file__).resolve().parent
                       / "perfbench/configs/joyai-llm-flash.json").read_text())
    file = {**file, "num_hidden_layers": layers, "n_routed_experts": held}
    config = adapter(file).program_config(file)
    params = init_params(config, jax.random.key(0))
    cfg = EngineConfig(max_seqs=rows, page_size=128, num_pages=64, max_seq_len=2048,
                       prefill_chunk=256)
    prompt = [int(t) for t in np.random.RandomState(5).randint(1, 256, size=prompt_len)]
    active = jnp.zeros((rows,), bool).at[0].set(True)
    zeros, ones = jnp.zeros((rows,), jnp.float32), jnp.ones((rows,), jnp.float32)
    top_k = jnp.zeros((rows,), jnp.int32)

    def run(attn_backend):
        engine = InferenceEngine(config, params, cfg, attn_backend=attn_backend)
        engine.set_page_table_row(0, list(range(1, 9)))
        out = [np.asarray(engine.prefill(0, prompt), np.float32)]
        engine.set_last_token(0, int(np.argmax(out[0])))
        pairs = []
        for _ in range(steps):
            pair, logits = engine.decode(active, zeros, ones, top_k, return_logits=True)
            pairs.append(tuple(int(t) for t in np.asarray(pair)[0]))
            out.append(np.asarray(logits[0], np.float32))
            pairs.append(int(engine.state.draft_tokens[0]))
        return out, pairs

    got, got_pairs = run(backend)
    want, want_pairs = run("ref")
    worst = max(float(np.sqrt(np.mean((g - w) ** 2)) / np.std(w)) for g, w in zip(got, want))
    require(worst <= LOGITS_RMS_TOL, f"draft step: {backend} differs from ref by an RMS of "
            f"{worst:.4f} of the logits' std (limit {LOGITS_RMS_TOL})")
    require(got_pairs == want_pairs, f"draft step: {backend} emitted {got_pairs}, ref {want_pairs}")
    say(f"draft step {backend} vs ref at {rows} rows, {layers} layers + the module, {held} of 256 "
        f"experts held: ok over {steps} steps (worst RMS diff {worst:.4f}; pairs {got_pairs[::2]})")
    return worst


def check_engine_logits(config, params, mesh, engine_cfg, backend: str, *,
                        prompt_len: int, n_decode: int = 3) -> float:
    """Prefill a seeded prompt (more than one chunk) then decode
    ``n_decode`` teacher-forced steps on two engines over the SAME weights,
    one per attention backend; every step's logits must agree within
    ``LOGITS_RMS_TOL`` of the reference logits' spread. Returns the worst
    relative RMS difference seen."""
    import jax.numpy as jnp
    import numpy as np

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.kv_cache import PageAllocator, pages_needed

    rng = np.random.RandomState(0)
    prompt = [int(t) for t in rng.randint(1, 256, size=prompt_len)]
    B = engine_cfg.max_seqs

    def make(attn_backend: str):
        engine = InferenceEngine(config, params, engine_cfg, mesh=mesh,
                                 attn_backend=attn_backend)
        pages = PageAllocator(engine_cfg.num_pages).allocate(
            "smoke", pages_needed(prompt_len + n_decode + 1, engine.page_size))
        engine.set_page_table_row(0, pages)
        return engine, np.asarray(engine.prefill(0, prompt), np.float32)

    worst = 0.0

    def compare(step: str, got: np.ndarray, want: np.ndarray) -> None:
        nonlocal worst
        require(np.isfinite(got).all(), f"logits[{step}]: non-finite")
        require(got.shape == (config.vocab_size,),
                f"logits[{step}]: shape {got.shape}, expected ({config.vocab_size},)")
        rel = float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))
        worst = max(worst, rel)
        require(rel <= LOGITS_RMS_TOL,
                f"logits[{step}]: {backend} differs from ref by an RMS of "
                f"{rel:.4f} of the logits' std (limit {LOGITS_RMS_TOL})")

    test, pre_test = make(backend)
    ref, pre_ref = make("ref")
    compare("prefill", pre_test, pre_ref)
    active = jnp.zeros((B,), bool).at[0].set(True)
    zeros, ones = jnp.zeros((B,), jnp.float32), jnp.ones((B,), jnp.float32)
    top_k = jnp.zeros((B,), jnp.int32)
    token = int(np.argmax(pre_ref))
    for i in range(n_decode):
        test.set_last_token(0, token)
        ref.set_last_token(0, token)
        _, got = test.decode(active, zeros, ones, top_k, return_logits=True)
        _, want = ref.decode(active, zeros, ones, top_k, return_logits=True)
        want = np.asarray(want[0], np.float32)
        compare(f"decode {i}", np.asarray(got[0], np.float32), want)
        token = int(np.argmax(want))
    say(f"logits {backend} vs ref: ok over prefill ({prompt_len} tokens) + "
        f"{n_decode} decode steps (worst RMS diff {worst:.4f} of the logits' std)")
    return worst


# --- phase 5: the server, over HTTP and Kafka -------------------------------

def _get(url: str, timeout: float = 60) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read()


def _post(url: str, payload: dict, timeout: float = 300) -> bytes:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read()


def _stream(base: str, payload: dict, trace_id: str,
            responding: threading.Event | None = None,
            timeout: float = 600) -> list[dict]:
    """POST /chat/stream and collect the SSE events; sets ``responding``
    once the agent reports that response generation began."""
    req = urllib.request.Request(
        base + "/chat/stream", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", "x-trace-id": trace_id})
    events = []
    with urllib.request.urlopen(req, timeout=timeout) as r:
        for line in r:
            line = line.strip()
            if not line.startswith(b"data:"):
                continue
            events.append(json.loads(line[5:]))
            if (responding is not None and events[-1].get("type") == "status"
                    and events[-1].get("message") == "Generating response..."):
                responding.set()
    return events


def _counters(metrics_text: str) -> dict[str, float]:
    """Prometheus text → each family's value summed over its label sets."""
    totals: dict[str, float] = {}
    for line in metrics_text.splitlines():
        if not line or line.startswith("#"):
            continue
        series, _, value = line.rpartition(" ")
        name = series.split("{", 1)[0]
        totals[name] = totals.get(name, 0.0) + float(value)
    return totals


def _engine_step_cache_sizes() -> dict[str, int]:
    """Compiled-variant count of every jitted engine step. Warm-up's
    contract is that serving dispatches only what start-up compiled, so
    none of these may grow inside the serving window. (Host-side helpers —
    a page-table row update for a new row count, the session tier's first
    offload — compile small programs of their own and are not steps.)"""
    from finchat_tpu.engine import engine as engine_module

    return {name: fn._cache_size() for name, fn in vars(engine_module).items()
            if hasattr(fn, "_cache_size")}


async def serve_and_check(cfg, *, expect_backend: str, parity_engine_cfg=None,
                          parity_prompt_len: int = 0) -> dict:
    """Start the server from ``cfg`` the way ``python -m finchat_tpu``
    does, run phases 3-5 against it, stop it. Returns the outcomes; raises
    ``SmokeFailure`` on the first check that does not hold."""
    import jax

    from finchat_tpu.io.kafka import KafkaClient
    from finchat_tpu.serve.app import build_app
    from finchat_tpu.utils.config import AI_RESPONSE_TOPIC, USER_MESSAGE_TOPIC
    from finchat_tpu.utils.metrics import METRICS
    from finchat_tpu.utils.tracing import TRACER

    compiles = 0

    def on_event(event: str, _duration: float, **_kw) -> None:
        nonlocal compiles
        if event == "/jax/core/compile/backend_compile_duration":
            compiles += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)

    outcomes: dict = {}
    # the registry and the trace ring are process-wide: failures count from
    # here (start-up included), not from whatever ran in the process before
    at_entry = _counters(METRICS.render_prometheus())
    entered = time.perf_counter()
    t0 = time.monotonic()
    app = build_app(cfg)
    engine = app.scheduler.engine
    outcomes["startup_seconds"] = round(time.monotonic() - t0, 1)
    outcomes["compiled_variants"] = engine.compiled_variants
    say(f"start-up (set-up, not a metric): build_app took "
        f"{outcomes['startup_seconds']} s, {engine.compiled_variants} serving "
        f"variants warmed, {compiles} programs compiled or loaded from the cache")
    say(f"attn_backend: {engine.attn_backend}")
    require(engine.attn_backend == expect_backend,
            f"engine resolved attn_backend={engine.attn_backend!r}, expected "
            f"{expect_backend!r} (FINCHAT_ATTN set? not on a TPU?)")
    require(engine.compiled_variants > 0, "engine warm-up did not run")

    if parity_engine_cfg is not None:
        check_engine_logits(engine.config, engine.params, engine.mesh,
                            parity_engine_cfg, expect_backend,
                            prompt_len=parity_prompt_len)

    base = f"http://127.0.0.1:{cfg.serve.port}"
    convs = [f"smoke-http-{i}" for i in range(N_STREAMS)] + ["smoke-kafka"]
    questions = ["What did I spend on coffee this month?",
                 "How big should my emergency fund be?",
                 "Am I on track for my savings goal?",
                 "Plot my grocery spending.",
                 "How much did I earn last month?"]
    for conv, question in zip(convs, questions):
        app.store.upsert_context(conv, dict(CONTEXT, user_id=f"user-{conv}"))
        app.store.add_user_message(conv, question, user_id=f"user-{conv}")

    await app.start(serve_http=True)
    try:
        health = json.loads(await asyncio.to_thread(_get, base + "/health"))
        require(health == {"status": "healthy"}, f"/health returned {health}")
        say("GET /health: ok")

        # ingest for two users, then retrieve through the agent's retriever:
        # the rows come back for their owner and nobody else
        owner, other = f"user-{convs[0]}", "user-someone-else"
        for user, rows in ((owner, TRANSACTIONS), (other, TRANSACTIONS[:2])):
            body = json.loads(await asyncio.to_thread(
                _post, base + "/transactions", {"user_id": user, "transactions": rows}))
            require(body == {"upserted": len(rows)}, f"/transactions returned {body}")
        hits = await app.agent.retriever(
            {"search_query": "coffee", "num_transactions": 3, "user_id": owner})
        require(len(hits) == 3 and all(h in [r["text"] for r in TRANSACTIONS] for h in hits),
                f"retrieval returned {hits}")
        none = await app.agent.retriever(
            {"search_query": "coffee", "user_id": "user-with-no-rows"})
        require(none == [], f"retrieval leaked rows across users: {none}")
        say(f"POST /transactions + retrieval: ok ({len(TRANSACTIONS)} + 2 rows "
            f"embedded on device, top-3 returned to their owner only)")

        # --- the serving window: no engine step may compile in here
        steps_before = _engine_step_cache_sizes()
        compiles_before = compiles
        before = _counters((await asyncio.to_thread(_get, base + "/metrics")).decode())

        responding = threading.Event()
        payloads = [{"conversation_id": c, "message": q, "user_id": f"user-{c}"}
                    for c, q in zip(convs[:N_STREAMS], questions)]
        first = asyncio.create_task(asyncio.to_thread(
            _stream, base, payloads[0], "smoke-trace-0", responding))
        # B-D start once A is generating its response: their prompts then
        # prefill while A decodes, which is the ragged round's population
        while not responding.is_set() and not first.done():
            await asyncio.sleep(0.01)
        rest = [asyncio.create_task(asyncio.to_thread(
            _stream, base, p, f"smoke-trace-{i}"))
            for i, p in enumerate(payloads[1:], start=1)]
        streams = await asyncio.gather(first, *rest)

        outcomes["streams"] = []
        for i, events in enumerate(streams):
            kinds = [e.get("type") for e in events]
            trace = json.loads(await asyncio.to_thread(
                _get, f"{base}/debug/trace/smoke-trace-{i}"))
            first_tokens = sum(e["name"] == "first_token" for e in trace["traceEvents"])
            outcome = {
                "request": f"POST /chat/stream #{i}", "events": len(events),
                "ended": kinds[-1] if kinds else None,
                "retrieved": "retrieval_complete" in kinds,
                "sequences_with_tokens": first_tokens,
            }
            outcomes["streams"].append(outcome)
            say(f"request: {json.dumps(outcome)}")
            require("error" not in kinds, f"stream {i} carried an error event: {events}")
            require(bool(kinds) and kinds[-1] == "complete",
                    f"stream {i} did not end in complete: {kinds[-3:]}")
            require(first_tokens >= 1, f"stream {i} generated no token")

        # two Kafka turns of one conversation: user_message → ai_response
        consumer = KafkaClient(cfg.kafka)
        consumer.setup_consumer(topics=[AI_RESPONSE_TOPIC])
        producer = KafkaClient(cfg.kafka)
        conv = convs[-1]
        for turn, text in enumerate([questions[-1], "And the month before?"]):
            if turn:
                app.store.add_user_message(conv, text, user_id=f"user-{conv}")
            message_id = f"smoke-kafka-{turn}"
            producer.produce_message(USER_MESSAGE_TOPIC, conv, {
                "message": text, "conversation_id": conv, "message_id": message_id})
            chunks = []
            kafka_deadline = time.monotonic() + 300
            while not (chunks and chunks[-1].get("last_message")):
                require(time.monotonic() < kafka_deadline,
                        f"kafka turn {turn}: no final chunk within 300 s")
                msg = consumer.poll_message()
                if msg is None:
                    await asyncio.sleep(0.02)
                    continue
                chunks.append(json.loads(msg.value().decode()))
            final = chunks[-1]
            outcome = {"request": f"kafka turn {turn}", "chunks": len(chunks),
                       "ended": final.get("type"), "error": final.get("error")}
            outcomes["streams"].append(outcome)
            say(f"request: {json.dumps(outcome)}")
            require(not any(c.get("error") for c in chunks),
                    f"kafka turn {turn} carried an error chunk: {chunks}")
            require(final.get("type") == "complete" and final.get("message") == text,
                    f"kafka turn {turn} ended in {final}")
        consumer.close()
        producer.close()

        after = _counters((await asyncio.to_thread(_get, base + "/metrics")).decode())
        steps_after = _engine_step_cache_sizes()
    finally:
        await app.stop()

    def delta(name: str) -> float:
        return after.get(name, 0.0) - before.get(name, 0.0)

    served = {name: delta(name) for name in (
        "finchat_mixed_dispatches_total", "finchat_decode_dispatches_total",
        "finchat_tokens_generated_total", "finchat_prefix_hits_total",
        "finchat_session_cache_hits_total")}
    outcomes["served"] = served
    say(f"served: {json.dumps(served)}")
    n_requests = N_STREAMS + 2
    require(served["finchat_mixed_dispatches_total"] > 0,
            "the ragged round never ran (no prefill coexisted with a decode)")
    require(served["finchat_decode_dispatches_total"] > 0,
            "the steady decode step never ran")
    require(served["finchat_tokens_generated_total"] >= n_requests,
            f"only {served['finchat_tokens_generated_total']} tokens for "
            f"{n_requests} requests")

    for name in ("finchat_engine_rebuilds_total", "finchat_sheds_total",
                 "finchat_quantmatmul_fallbacks_total",
                 "finchat_dispatch_failures_total"):
        grew = after.get(name, 0.0) - at_entry.get(name, 0.0)
        require(grew == 0, f"{name} grew by {grew}")
    require(after.get("finchat_breaker_state", 0.0) == 0, "the breaker is not closed")
    anomalies = [ev[2] for ev in TRACER.snapshot()
                 if ev[4] == "anomaly" and ev[0] >= entered]
    require(not anomalies, f"anomalies recorded (watchdog, breaker, shed): {anomalies}")
    grown = {n: (steps_before[n], steps_after[n]) for n in steps_after
             if steps_after[n] != steps_before.get(n)}
    require(not grown, f"engine steps compiled after warm-up: {grown}")
    outcomes["other_compiles_in_window"] = compiles - compiles_before
    say("no rebuild, breaker trip, shed, fallback, failed dispatch or anomaly; "
        f"no engine step compiled after warm-up ({outcomes['other_compiles_in_window']} "
        "small host-side programs did)")
    return outcomes


def smoke_config(model_preset: str, embed_preset: str, *, mesh_model: int = 1,
                 port: int = PORT, max_new_tokens: int = MAX_NEW_TOKENS):
    """The smoke's config: ``load_config`` with everything at its default
    except the presets, the explicit mesh, the port and the length bound."""
    from finchat_tpu.utils.config import load_config

    return load_config(None, {
        "model.preset": model_preset, "embed.preset": embed_preset,
        "mesh.data": 1, "mesh.pipe": 1, "mesh.seq": 1, "mesh.expert": 1,
        "mesh.model": mesh_model,
        "serve.port": port, "engine.max_new_tokens": max_new_tokens,
    })


def main(mesh_model: int = 1) -> int:
    # sys.__stderr__: a real file descriptor even when stderr is captured
    faulthandler.dump_traceback_later(DEADLINE_SECONDS, exit=True,
                                      file=sys.__stderr__)
    try:
        return _run(mesh_model)
    finally:
        faulthandler.cancel_dump_traceback_later()


def _run(mesh_model: int) -> int:
    try:
        import jax

        from finchat_tpu.models.llama import PRESETS
        from finchat_tpu.utils.config import EngineConfig
        from finchat_tpu.utils.runtime import device_facts, enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e}); run it from the "
              "root of a checkout", file=sys.stderr)
        return 2

    device = device_facts()
    say(f"platform: {device['platform']}  device_kind: {device['kind']}  "
        f"count: {device['count']}")
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU — jax.default_backend() is "
              f"{jax.default_backend()!r} (JAX_PLATFORMS="
              f"{jax.config.jax_platforms!r}); this check runs on the chip only",
              file=sys.stderr)
        return 2
    say(f"compile cache: {enable_compile_cache()}")

    model = PRESETS[MODEL_PRESET]
    cfg = smoke_config(MODEL_PRESET, EMBED_PRESET, mesh_model=mesh_model)
    require(mesh_model <= device["count"],
            f"mesh.model={mesh_model} needs that many chips, found {device['count']}")
    say(f"model: {MODEL_PRESET} {cfg.model.dtype} (random weights, seed "
        f"{cfg.model.seed}); embed: {EMBED_PRESET}; mesh model={mesh_model}; "
        f"engine defaults except max_new_tokens={cfg.engine.max_new_tokens} "
        f"(max_seqs={cfg.engine.max_seqs}, page_size={cfg.engine.page_size}, "
        f"prefill_chunk={cfg.engine.prefill_chunk})")

    check_kernels(model.n_heads, model.n_kv_heads, model.head_dim,
                  cfg.engine.page_size, "pallas",
                  prefill_chunk=cfg.engine.prefill_chunk)
    check_decode_at_cell_shape("pallas")
    # the cells' decode batch: 16 rows on the system prompt's 31 pages, at
    # both head shapes, float and int8 cache (the shared-head pass)
    for heads in (dict(n_heads=32, n_kv=8), dict(n_heads=20, n_kv=4)):
        for quantized in (False, True):
            check_decode_at_cell_shape("pallas", shared_pages=31,
                                       quantized=quantized, **heads)
    check_ssm_step_at_cell_shape("pallas")
    # a layer pattern's two kinds of layer at their cell's shape: the paged
    # kernel at one query head a KV head, and the delta rule's one-token update
    check_decode_at_cell_shape("pallas", shared_pages=31, n_heads=30, n_kv=30)
    check_gdn_step_at_cell_shape("pallas")
    # ... and in its second form, at kimi-linear-report-saturated's shape (PR 51)
    check_gdn_step_at_cell_shape("pallas", rows=32, heads=32, key_dim=128, value_dim=128,
                                 layers=7, layer=4, channel_decay=True)
    # many small experts and the mixer as a layer kind at their cell's shape:
    # the state kernel at 128 heads of 64 x 128, attention with a softmax scale
    # of its own is check_kernels' and the cell's logits check's, the expert
    # layer in both of its forms
    check_ssm_step_at_cell_shape("pallas", heads=128, head_dim=64, state=128, groups=1,
                                 layers=9, layer=5)
    check_moe_at_cell_shape("pallas")
    # one cache read by eight layers and eight window layers at their cell's
    # shape (phi4-flash-report-saturated): 32 rows, 40 query heads of a PAIR's
    # width over 10 K/V heads, on the system prompt's 31 pages; and a window
    # layer's call over its rows' bounded page lists
    check_decode_at_cell_shape("pallas", rows=32, n_heads=40, n_kv=10, shared_pages=31,
                               contexts=(4000, 9000), pool_pages=3072)
    check_decode_at_cell_shape("pallas", rows=32, n_heads=40, n_kv=10, width=6,
                               contexts=(513, 768), pool_pages=217, window=512)
    # a model that drafts (joyai-flash-report-saturated, PR 55): the width-2
    # latent walk at the cell's shape, and the draft-and-verify decode step
    check_pair_walk_at_cell_shape("pallas")
    check_draft_step_at_cell_shape("pallas")
    # the parity engines share the app's weights; their own KV pools are
    # small — two slots, one prompt of a chunk and a half
    parity_cfg = dataclasses.replace(
        EngineConfig(), max_seqs=2, num_pages=32, max_seq_len=2048,
        max_new_tokens=8)
    asyncio.run(serve_and_check(
        cfg, expect_backend="pallas", parity_engine_cfg=parity_cfg,
        parity_prompt_len=cfg.engine.prefill_chunk * 3 // 2))

    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
