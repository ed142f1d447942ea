#!/usr/bin/env python3
"""benchmarks/frame_chunk_cliff.py — where CPython's frame chunks end.

    python3 benchmarks/frame_chunk_cliff.py [--depths 400] [--calls 200000]

No JAX, no chip: the interpreter alone. CPython 3.11 / 3.12 keeps a thread's
frames in chunks of 16 KiB and frees a chunk as soon as its first frame
returns, so a call made from the last frame a chunk holds allocates and frees
a chunk every time. This script recurses to each depth in turn, times a loop
of calls to a two-argument function there, and prints the depths at which the
loop is over 1.5 x slower than at depth 0: on 3.12.12 two depths in 400, at 94
and 134 x (PERF.md section 6, PR 38).

Why it is kept: the serving stack's warm-up is Python tracing and lowering,
thousands of small calls a program at a depth of a hundred frames and more,
and which of them straddle a chunk's end is set by every frame below. The
same warm-up took 127-131 s or 162-171 s on the chip by that alone
(`serve/app.py` `_on_a_fresh_stack` has the cure for the warm-up). A round of
the scheduler, or any hot loop, can sit on the same cliff: when a host path
is slower than its code explains, look here first.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _leaf(a, b):
    return a


def _hot(calls: int) -> float:
    t = time.perf_counter()
    for _ in range(calls):
        _leaf(1, 2)
    return time.perf_counter() - t


def _down(depth: int, calls: int) -> float:
    if depth == 0:
        return _hot(calls)
    return _down(depth - 1, calls)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--depths", type=int, default=400)
    ap.add_argument("--calls", type=int, default=200_000)
    args = ap.parse_args()
    sys.setrecursionlimit(max(sys.getrecursionlimit(), args.depths + 100))
    base = min(_down(0, args.calls) for _ in range(3))
    slow = []
    for depth in range(args.depths):
        ratio = min(_down(depth, args.calls) for _ in range(2)) / base
        if ratio > 1.5:
            slow.append({"depth": depth, "times_slower": round(ratio, 1)})
    print(json.dumps({"python": sys.version.split()[0], "calls": args.calls,
                      "base_ms": round(base * 1e3, 3), "depths_tried": args.depths,
                      "slow_depths": slow}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
