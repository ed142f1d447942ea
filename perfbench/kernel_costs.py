"""Bytes a single kernel must move, from shapes: the yardstick's own, like
``costs.py`` (a change to the program cannot move them).

A kernel's roofline share is the least time the chip could take for the
call — here the bytes over the peak HBM bandwidth of ``peaks.json``, since a
decode attention call does two operations a byte — over the kernel's device
time. Anything over 100 % is a fault in the count or in the time, never a
result.
"""

from __future__ import annotations

from perfbench.costs import BYTES


def paged_attention_stream_bytes(config: dict, *, kv_tokens: float) -> float:
    """Bytes ONE layer's paged decode attention call must read at least: the
    K and the V of every context token of every row in the batch
    (``kv_tokens`` = Σ rows' context lengths), for each KV head. The queries
    and the output — one token a row — are a few KiB and are left out."""
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    kv_heads = int(config["num_key_value_heads"])
    return (kv_tokens * 2 * kv_heads * (d // heads)
            * BYTES[config.get("dtype", "bfloat16")])
