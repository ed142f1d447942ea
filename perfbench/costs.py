"""Bytes and operations from shapes, and the table of peaks.

These are the yardstick's own: a change to the program cannot move them.
Counts are what the algorithm needs, not what an implementation happens to
do. The counts here are generic over the published keys of a decoder block
(attention with ``head_dim`` where the file has it, a gated MLP or
``num_local_experts`` of them, every expert counted once a step). Which counts
a configuration is held to is its model's own choice: the readers ask
``perfbench/models/<model_type>.py``, which takes these or brings its own.
"""

from __future__ import annotations

import json
from pathlib import Path

BYTES = {"bfloat16": 2, "float32": 4, "float16": 2}


def peaks(device_kind: str) -> dict:
    with open(Path(__file__).resolve().parent / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"device kind {device_kind!r} is not in perfbench/peaks.json")
    return table[device_kind]


def head_dim(config: dict) -> int:
    """A head's width: the file's ``head_dim``, else hidden size over heads."""
    return int(config.get("head_dim")
               or int(config["hidden_size"]) // int(config["num_attention_heads"]))


def param_counts(config: dict) -> dict:
    """Parameters by group, from the published keys of a configuration."""
    d, f = int(config["hidden_size"]), int(config["intermediate_size"])
    heads, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    hd = head_dim(config)
    experts = int(config.get("num_local_experts", 0))
    attn = d * heads * hd * 2 + d * kv * hd * 2
    mlp = 3 * d * f * max(experts, 1) + (d * experts if experts else 0)
    layer = attn + mlp + 2 * d
    n_layers = int(config["num_hidden_layers"])
    embed = int(config["vocab_size"]) * d
    head = 0 if config.get("tie_word_embeddings") else embed
    return {"layer": layer, "layers": layer * n_layers, "embed": embed, "head": head,
            "total": layer * n_layers + embed + head + d}


def kv_bytes_per_token(config: dict) -> int:
    return (2 * int(config["num_hidden_layers"]) * int(config["num_key_value_heads"])
            * head_dim(config) * BYTES[config.get("dtype", "bfloat16")])


def decode_step_stream_bytes(config: dict, *, live_kv_tokens: float, ctx=None) -> float:
    """Bytes one decode step must read at least: every layer's weights and the
    output head once (the embedding is a gather of a few rows), and the live
    KV of the batch: ``live_kv_tokens`` = tokens on distinct physical pages
    (``live_kv.py``; a page that rows share is read once, not once a row:
    PR 30). ``ctx`` (the readers' ``Context``) is
    for a model that counts what a step touched from a program counter; this
    count needs none."""
    p = param_counts(config)
    weights = (p["layers"] + (p["head"] or p["embed"])) * BYTES[config.get("dtype", "bfloat16")]
    return weights + live_kv_tokens * kv_bytes_per_token(config)
