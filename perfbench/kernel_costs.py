"""Bytes a single kernel must move, from shapes: the yardstick's own, like
``costs.py`` (a change to the program cannot move them), and like them taken
or replaced by ``perfbench/models/<model_type>.py``.

A kernel's roofline share is the least time the chip could take for the
call — here the bytes over the peak HBM bandwidth of ``peaks.json``, since a
decode attention call does two operations a byte — over the kernel's device
time. Anything over 100 % is a fault in the count or in the time, never a
result.
"""

from __future__ import annotations

from perfbench.costs import BYTES, head_dim


def paged_attention_stream_bytes(config: dict, *, kv_tokens: float) -> float:
    """Bytes ONE layer's paged decode attention call must read at least: the
    K and the V of every context token of the batch, for each KV head.
    ``kv_tokens`` = tokens on distinct physical pages (``live_kv.py``): a
    page that several rows share is in the pool once and must be read once,
    so Σ rows' context lengths overstates it wherever rows share a head (PR
    30; readings before it counted a shared page once a row). The queries and
    the output — one token a row — are a few KiB and are left out."""
    return (kv_tokens * 2 * int(config["num_key_value_heads"]) * head_dim(config)
            * BYTES[config.get("dtype", "bfloat16")])
