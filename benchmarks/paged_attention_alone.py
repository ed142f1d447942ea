"""Paged decode attention alone on the chip, at the shapes of the cell
``mixtral-report-saturated`` (PERF.md section 6, PR 25).

One ``paged_flash_attention`` call a layer over a bf16 cache of ``--layers``
layers (3) and 1,600 pages: 16 rows, 32 query heads, 8 KV heads, head 128, page 128, one query a
row, contexts drawn between 5k and 12k tokens (mean about 7.75k, as in the
cell's capture), the first ``--shared-pages`` pages (31) of every row
physically shared (the system prompt), which the kernel's shared-head pass
reads once for all rows; ``--shared-pages 0`` is the bypass, every page a
row's own. ``--heads 20 --kv-heads 4`` are Falcon-H1's head counts, ``--heads
30 --kv-heads 30 --layers 2`` Olmo-Hybrid's cell (ONE query head a KV head, a
token row of 7.5 KiB: two full-attention layers' pool is 6.3 GB, a third does
not fit beside it). The page table is given at each width of ``--widths``:
``served`` is the engine's 128 (``max_seq_len`` / page), ``live`` the widest
row's live pages — what the table's dead entries cost is the difference.

Times are device times of the kernel's own operation in a ``jax.profiler``
capture (the ``XLA Ops`` line), so they are what ``attn_kv_roofline.sat``
divides by; the bytes are that metric's too (the K and V of every token on a
DISTINCT physical page, KV heads x 128 x 2 B each, at 819 GB/s:
``share_of_distinct_stream_bound``; ``share_of_stream_bound`` counts a shared
page once a row, as the metric did before PR 30). A wall-clock figure over
the same calls is printed beside them. Runs on the chip only:

    chiprun -- python3 benchmarks/paged_attention_alone.py
    chiprun -- python3 benchmarks/paged_attention_alone.py --tree <checkout>

``--tree`` times the kernel of another checkout (the parent commit unpacked
somewhere under the repo) with this script's inputs. The script runs in no
cell. It stays because a change to the kernel is judged alone first, parent
against change at every cell's head counts (PERF.md section 6, PRs 25, 31,
33), and ROADMAP S13 (a) still needs it at 5 query heads a KV head.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROWS, HEAD_DIM, PAGE = 16, 128, 128
POOL_PAGES, SERVED_WIDTH = 1600, 128
HBM_BYTES_PER_S = 819e9  # one v5e chip (perfbench/peaks.json)


def contexts(seed: int):
    """16 context lengths in [5000, 12000], mean about 7,750."""
    import numpy as np

    rng = np.random.RandomState(seed % (2 ** 32))
    return (5000 + 7000 * rng.beta(1.2, 1.85, size=ROWS)).astype(np.int64)


def page_table(ctx, width: int, seed: int, shared_pages: int):
    """Each row's live pages: the shared head, then private pages drawn
    without replacement from the pool; dead entries are the trash page 0."""
    import numpy as np

    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    private = rng.permutation(np.arange(1 + shared_pages, POOL_PAGES))
    table = np.zeros((ROWS, width), np.int32)
    used = 0
    for row, n in enumerate(-(-ctx // PAGE)):
        table[row, :shared_pages] = np.arange(1, 1 + shared_pages)
        table[row, shared_pages:n] = private[used:used + n - shared_pages]
        used += n - shared_pages
    return table


def kernel_events(trace_dir: str) -> list[float]:
    """Device durations (us) of the kernel's operations in the capture."""
    from jax.profiler import ProfileData

    path = next(Path(trace_dir).rglob("*.xplane.pb"))
    found = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                # an event's name is the whole HLO instruction, operands
                # included: the kernel is the one the instruction is named for
                found += [ev.duration_ns / 1e3 for ev in line.events
                          if "paged_flash_attention" in ev.name.split(" = ")[0]]
    return found


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=str(Path(__file__).resolve().parents[1]))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--widths", default="served,live")
    ap.add_argument("--steps", type=int, default=40,
                    help="calls of all the layers in the capture")
    ap.add_argument("--shared-pages", type=int, default=31,
                    help="leading pages every row shares (0: none, the bypass)")
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--layers", type=int, default=3,
                    help="depth of the cache: a cell's pool (2 at 30 KV heads)")
    args = ap.parse_args()
    heads, kv_heads, shared_pages = args.heads, args.kv_heads, args.shared_pages
    layers = args.layers
    sys.path.insert(0, args.tree)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from finchat_tpu.ops.paged_attention import paged_flash_attention

    if jax.default_backend() != "tpu":
        print("paged_attention_alone: no TPU; a time comes only from the chip",
              file=sys.stderr)
        return 2

    ctx = contexts(args.seed)
    keys = jax.random.split(jax.random.key(args.seed % (2 ** 31)), 3)
    shape = (layers, POOL_PAGES, PAGE, kv_heads * HEAD_DIM)
    k_pages = jax.random.normal(keys[0], shape, jnp.bfloat16)
    v_pages = jax.random.normal(keys[1], shape, jnp.bfloat16)
    q = jax.random.normal(keys[2], (ROWS, 1, heads, HEAD_DIM), jnp.bfloat16)
    kv_len = jnp.asarray(ctx, jnp.int32)
    q_offset = kv_len - 1

    @jax.jit
    def all_layers(q, k_pages, v_pages, table):
        def layer(i, acc):
            return acc + paged_flash_attention(
                q, k_pages, v_pages, table, q_offset, kv_len, i[None],
                page_size=PAGE, n_kv=kv_heads).astype(jnp.float32)
        return jax.lax.fori_loop(0, layers, layer, jnp.zeros(q.shape, jnp.float32))

    token_us = 1e6 * 2 * kv_heads * HEAD_DIM * 2 / HBM_BYTES_PER_S
    distinct = int(ctx.sum()) - (ROWS - 1) * shared_pages * PAGE
    stream_us, distinct_us = token_us * int(ctx.sum()), token_us * distinct
    live_width = int(-(-ctx.max() // PAGE))
    result = {
        "tree": args.tree, "seed": args.seed, "device": jax.devices()[0].device_kind,
        "heads": heads, "kv_heads": kv_heads, "layers": layers,
        "shared_pages": shared_pages,
        "context_tokens": int(ctx.sum()), "distinct_page_tokens": distinct,
        "live_pages": int((-(-ctx // PAGE)).sum()),
        "widest_row_pages": live_width, "stream_bound_us": stream_us,
        "distinct_stream_bound_us": distinct_us, "widths": {},
    }
    for name in args.widths.split(","):
        width = {"served": SERVED_WIDTH, "live": live_width}.get(name) or int(name)
        table = jnp.asarray(page_table(ctx, width, args.seed, shared_pages))
        out = all_layers(q, k_pages, v_pages, table).block_until_ready()
        assert bool(jnp.isfinite(out).all())
        with tempfile.TemporaryDirectory() as trace_dir:
            jax.profiler.start_trace(trace_dir)
            t0 = time.perf_counter()
            for _ in range(args.steps):
                out = all_layers(q, k_pages, v_pages, table)
            out.block_until_ready()
            wall_us = 1e6 * (time.perf_counter() - t0) / (args.steps * layers)
            jax.profiler.stop_trace()
            calls = kernel_events(trace_dir)
        call_us = float(np.mean(calls)) if calls else None
        result["widths"][name] = {
            "table_width": width, "grid_steps_old_walk": ROWS * width,
            "kernel_calls": len(calls), "call_us": call_us,
            "call_us_min_max": [min(calls), max(calls)] if calls else None,
            "wall_us_per_call": wall_us,
            "share_of_stream_bound": 100 * stream_us / call_us if calls else None,
            "share_of_distinct_stream_bound": (100 * distinct_us / call_us
                                               if calls else None),
        }
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
