"""Assembling the system under test exactly as ``python -m finchat_tpu`` does
(``load_config`` → ``build_app`` → ``app.start``), from a configuration file.

The configuration's ``LlamaConfig`` is registered under the file's name in the
program's ``PRESETS`` before ``build_app``; no program file changes.
"""

from __future__ import annotations

import socket
from pathlib import Path

from perfbench.synth_tokenizer import write_tokenizer_dir

MESH_ONE_DEVICE = {"mesh.data": 1, "mesh.pipe": 1, "mesh.seq": 1,
                   "mesh.expert": 1, "mesh.model": 1}


def llama_config(config: dict):
    """The program's model config from the published keys of a
    configuration file (HF ``config.json`` names)."""
    from finchat_tpu.models.llama import LlamaConfig

    heads = int(config["num_attention_heads"])
    head_dim = int(config.get("head_dim", config["hidden_size"] // heads))
    if head_dim * heads != int(config["hidden_size"]):
        raise ValueError("the program's block has head_dim = hidden_size / heads")
    return LlamaConfig(
        vocab_size=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]), n_heads=heads,
        n_kv_heads=int(config["num_key_value_heads"]),
        hidden_dim=int(config["intermediate_size"]),
        rope_theta=float(config["rope_theta"]), norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["engine"].get("max_seq_len", 8192)),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        n_experts=int(config.get("num_local_experts", 0)),
        top_k_experts=int(config.get("num_experts_per_tok", 2)),
    )


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def app_config(name: str, config: dict, *, answer_cap: int, work_dir: Path):
    """``load_config`` with the configuration's own engine options, the
    synthetic tokenizer at the model's vocabulary size, the mesh pinned to
    one device, and the configuration's weights seed; everything else at its
    default."""
    from finchat_tpu.models.llama import PRESETS
    from finchat_tpu.utils.config import load_config

    PRESETS[name] = llama_config(config)
    tok_dir = write_tokenizer_dir(work_dir / f"tokenizer-{config['vocab_size']}",
                                  int(config["vocab_size"]))
    overrides = {
        "model.preset": name, "model.dtype": config.get("dtype", "bfloat16"),
        "model.tokenizer_path": str(tok_dir),
        # one model for every --seed: with random weights the tool decision
        # is a property of the weights (the configuration's `weights_note`)
        "model.seed": int(config["weights_seed"]),
        "embed.preset": config.get("embed_preset", "bge-base-en"),
        "serve.host": "127.0.0.1", "serve.port": free_port(),
        "engine.max_new_tokens": int(answer_cap),
        "tracing.ring_events": 1 << 18,
        **MESH_ONE_DEVICE,
    }
    overrides.update({f"engine.{k}": v for k, v in config["engine"].items()})
    return load_config(None, overrides)
