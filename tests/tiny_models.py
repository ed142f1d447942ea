"""The configurations' blocks at a size a test holds, built once: what
tests/test_falcon_h1.py, test_olmo_hybrid.py, test_granite_hybrid.py,
test_deepseek_v32.py, test_phi4_flash.py, test_trinity_mini.py, test_kimi_linear.py, test_joyai.py and test_mimo_v2_flash.py check against the plain references
of ``perfbench/models``, and what tests/test_decode_pipeline.py serves, one
for each kind of per-row memory the engine has.

``FILES[name]`` is the configuration file (the published keys, small
numbers), ``build(name)`` its program configuration and seeded float32
weights (built once a process), ``SHAPES[name]`` the ``(page_size, prefill_chunk, max_seqs)`` its
tests serve it at. ``build`` also takes a name of ``models.llama.PRESETS``
(``tiny``: paged K/V and nothing else).
"""

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp

from finchat_tpu.models.llama import (
    CROSS,
    FULL,
    GMU,
    LINEAR,
    MAMBA1,
    PRESETS,
    WINDOW,
    init_params,
)

# name -> its adapter under perfbench/models
ADAPTERS = {"falcon_h1": "falcon_h1", "olmo_hybrid": "olmo_hybrid",
            "granite_hybrid": "granitemoehybrid", "deepseek_v32": "deepseek_v32",
            "phi4_flash": "phi4flash", "trinity_mini": "afmoe", "kimi_linear": "kimi_linear",
            "joyai": "joyai_llm_flash", "joyai_repeats": "joyai_llm_flash",
            "mimo_v2_flash": "mimo_v2_flash"}
SHAPES = {"tiny": (8, 16, 4), "falcon_h1": (16, 12, 4), "olmo_hybrid": (16, 12, 4),
          "granite_hybrid": (16, 12, 4), "deepseek_v32": (16, 12, 4), "phi4_flash": (4, 8, 4),
          "trinity_mini": (4, 8, 4), "kimi_linear": (16, 12, 4), "joyai": (16, 12, 4),
          "joyai_repeats": (16, 12, 4), "mimo_v2_flash": (4, 8, 4)}
FILES: dict[str, dict] = {}

# Falcon-H1's block at a size a test holds: head_dim 32 is not 64 / 4, two
# groups of B/C, every multiplier away from 1, the scan in blocks of 8
FILES["falcon_h1"] = {
    "model_type": "falcon_h1", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "vocab_size": 300, "rope_theta": 1e11, "rms_norm_eps": 1e-5,
    "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_ssm": 64, "mamba_d_state": 8,
    "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "embedding_multiplier": 5.65, "lm_head_multiplier": 0.0625,
    "attention_in_multiplier": 0.9, "attention_out_multiplier": 0.3,
    "key_multiplier": 0.11, "mlp_multipliers": [0.17, 0.5], "ssm_in_multiplier": 1.3,
    "ssm_out_multiplier": 1.5, "ssm_multipliers": [1.2, 1.5, 1.4, 1.6, 2.0],
    "engine": {"max_seq_len": 256, "max_seqs": 4}, "dtype": "float32",
}

# Olmo-Hybrid's block at a size a test holds: two whole periods of three
# linear layers and a full one, keys half as wide as values, 4 heads of 16
# with as many KV heads, no rotation, the WY form in blocks of 8
FILES["olmo_hybrid"] = {
    "model_type": "olmo_hybrid", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 8, "num_attention_heads": 4, "num_key_value_heads": 4,
    "vocab_size": 300, "rms_norm_eps": 1e-6,
    "layer_types": ([LINEAR] * 3 + [FULL]) * 2,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4, "linear_key_head_dim": 8,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
    "engine": {"max_seq_len": 256, "max_seqs": 4}, "dtype": "float32",
}

# Granite-4.0-H's block at a size a test holds: one whole period (five mamba,
# one attention, four mamba), 12 routed experts of 32 of which 6 are held, 2 a
# token, a shared expert of 48, 8 mixer heads of 16 with 16 state channels,
# 4 / 2 attention heads of 16, the published scalars
GRANITE_KINDS = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
FILES["granite_hybrid"] = {
    "model_type": "granitemoehybrid", "hidden_size": 64, "intermediate_size": 32,
    "shared_intermediate_size": 48, "num_local_experts": 6, "num_experts_per_tok": 2,
    "reduced": {"num_local_experts": {"from": 12, "to": 6, "why": "a chip's share"}},
    "num_hidden_layers": 10, "layer_types": GRANITE_KINDS,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 300, "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "position_embedding_type": "nope", "rope_theta": 10000,
    "attention_multiplier": 0.0078125, "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "logits_scaling": 16,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16, "mamba_n_groups": 1,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "program_ssm_chunk": 8,
    "engine": {"max_seq_len": 256, "max_seqs": 4}, "dtype": "float32",
}

# DeepSeek-V3.2's block at a size a test holds: one dense layer and two
# routed ones; 4 heads of [16 | 8] over latents of 32; an indexer of 4 heads
# of 16 that keeps 24 tokens; 16 routed experts of 32 in 4 groups, 2 groups
# kept, 2 a token, of which 4 are held (half of group 0 ... the whole of it
# here), a shared expert of 32; YaRN over an original window of 64
FILES["deepseek_v32"] = {
    "model_type": "deepseek_v32", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_routed_experts": 4, "num_experts_per_tok": 2,
    "reduced": {"n_routed_experts": {"from": 16, "to": 4, "why": "a chip's share"}},
    "n_group": 4, "topk_group": 2, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "first_k_dense_replace": 1, "num_hidden_layers": 3, "num_nextn_predict_layers": 0,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "index_n_heads": 4, "index_head_dim": 16, "index_topk": 24,
    "rope_theta": 10000, "rope_scaling": {
        "type": "yarn", "factor": 40, "original_max_position_embeddings": 64,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
    "rms_norm_eps": 1e-6, "vocab_size": 300, "tie_word_embeddings": False,
    "engine": {"max_seq_len": 256, "max_seqs": 4}, "dtype": "float32",
}

# the published plan at a size a test holds: 2 x (mamba1, sliding), (mamba1,
# full), 2 x (gmu, cross); 8 / 4 heads of 8 in 4 / 2 pairs, a window of 8
# tokens = two pages of 4, Mamba-1 of 128 channels x 4 state channels
PHI4_KINDS = ([MAMBA1, WINDOW] * 2 + [MAMBA1, FULL] + [GMU, CROSS] * 2)
FILES["phi4_flash"] = {
    "model_type": "phi4flash", "hidden_size": 64, "intermediate_size": 96,
    "num_attention_heads": 8, "num_key_value_heads": 4, "num_hidden_layers": 10,
    "layer_types": PHI4_KINDS, "sliding_window": 8, "vocab_size": 211, "layer_norm_eps": 1e-5,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
    "mamba_d_state": 4, "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 4,
    "engine": {"max_seq_len": 256, "max_seqs": 4}, "dtype": "float32",
    "ssm_state_dtype": "float32",
}

# Trinity-Mini's block at a size a test holds: ONE leading dense window layer,
# then two whole periods of three window layers and a full one; 8 / 2 heads of
# 16 (hidden 64: a head is not hidden / heads), a window of 8 tokens = two
# pages of 4; 32 routed experts of 32 at 4 a token (over MOE_DENSE_WASTE_MAX
# x 4, so that the model routes sparsely as the published 128 at 8 do) beside
# a shared one, every expert held
TRINITY_KINDS = [WINDOW] + [WINDOW, WINDOW, WINDOW, FULL] * 2
FILES["trinity_mini"] = {
    "model_type": "afmoe", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_experts": 32, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "num_dense_layers": 1, "num_hidden_layers": 9,
    "layer_types": TRINITY_KINDS, "sliding_window": 8, "global_attn_every_n_layers": 4,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "score_func": "sigmoid", "route_norm": True, "route_scale": 2.826, "n_group": 1,
    "topk_group": 1, "num_expert_groups": 1, "num_limited_groups": 1, "mup_enabled": True,
    "hidden_act": "silu", "rope_theta": 10000, "rope_scaling": None, "rms_norm_eps": 1e-5,
    "vocab_size": 211, "tie_word_embeddings": False,
    "engine": {"max_seq_len": 256, "max_seqs": 4}, "dtype": "float32",
}

# Kimi-Linear's block at a size a test holds: ONE leading dense KDA layer, then
# two whole periods (KDA, KDA, latent, KDA); 4 KDA heads of 16 (keys and values
# alike, the gate's rank 16), 4 latent heads of [16 | 8] over a latent of 32
# with no q latent and no rotation; 16 routed experts of 32 at 2 a token (over
# MOE_DENSE_WASTE_MAX x 2: it routes sparsely, as the published 256 at 8 do)
# of which 8 are held, beside a shared one
FILES["kimi_linear"] = {
    "model_type": "kimi_linear", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_token": 2,
    "reduced": {"num_experts": {"from": 16, "to": 8, "why": "a chip's share"}},
    "num_shared_experts": 1, "first_k_dense_replace": 1, "num_hidden_layers": 9,
    "linear_attn_config": {"kda_layers": [1, 2, 3, 5, 6, 7, 9], "full_attn_layers": [4, 8],
                           "head_dim": 16, "num_heads": 4, "short_conv_kernel_size": 4},
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "q_lora_rank": None, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "mla_use_nope": True, "rope_theta": 10000, "rope_scaling": None,
    "moe_router_activation_func": "sigmoid", "moe_renormalize": True, "moe_layer_freq": 1,
    "num_expert_group": 1, "topk_group": 1, "use_grouped_topk": True,
    "routed_scaling_factor": 2.446, "num_nextn_predict_layers": 0, "hidden_act": "silu",
    "rms_norm_eps": 1e-5, "vocab_size": 300, "tie_word_embeddings": False,
    "engine": {"max_seq_len": 256, "max_seqs": 4}, "dtype": "float32",
    "ssm_state_dtype": "float32",
}

# JoyAI-LLM-Flash's block at a size a test holds: one leading dense layer, two
# routed ones and the next-token-prediction module; 4 heads of [16 | 8] through
# a q latent of 32, rotated, no indexer; 16 routed experts of 32 at 2 a token
# (it routes sparsely) of which 4 are held, beside a shared one
FILES["joyai"] = {
    "model_type": "joyai_llm_flash", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_routed_experts": 4, "num_experts_per_tok": 2,
    "reduced": {"n_routed_experts": {"from": 16, "to": 4, "why": "a chip's share"}},
    "n_group": 1, "topk_group": 1, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "first_k_dense_replace": 1, "num_hidden_layers": 3, "num_nextn_predict_layers": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 32000000, "rope_scaling": None, "rms_norm_eps": 1e-6, "vocab_size": 256,
    "tie_word_embeddings": False,
    "engine": {"max_seq_len": 256, "max_seqs": 4}, "dtype": "float32",
}
# ... and the same block with its module SHAPED so that a greedy stream keeps
# some drafts (with seeded weights the module's argmax is the trunk's one time
# in 256): the module's block adds nothing and its projection passes the
# trunk's hidden state through, so it proposes the token the trunk just emitted
# — kept wherever the stream repeats a token, rejected elsewhere
FILES["joyai_repeats"] = FILES["joyai"]

# MiMo-V2-Flash's block at a size a test holds: ONE leading dense full layer,
# then two whole periods of three sliding layers and a full one; 16 query heads
# over 2 K/V heads in full layers and 4 in sliding ones, keys of 192 over
# values of 128 (the published widths: the kernels cut such a key as lane
# tiles), 64 dims rotated at two bases, a sink in the sliding layers, a window
# of 8 tokens = two pages of 4; 16 routed experts of 32 at 2 a token (it routes
# sparsely) of which 4 are held, no shared expert
FILES["mimo_v2_flash"] = {
    "model_type": "mimo_v2_flash", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_routed_experts": 4, "num_experts_per_tok": 2,
    "reduced": {"n_routed_experts": {"from": 16, "to": 4, "why": "a chip's share"}},
    "n_shared_experts": None, "norm_topk_prob": True, "scoring_func": "sigmoid",
    "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc", "routed_scaling_factor": None,
    "num_hidden_layers": 9, "hybrid_layer_pattern": [0, 1, 1, 1, 0, 1, 1, 1, 0],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1, 1, 1],
    "num_attention_heads": 16, "num_key_value_heads": 2, "head_dim": 192, "v_head_dim": 128,
    "swa_num_attention_heads": 16, "swa_num_key_value_heads": 4, "swa_head_dim": 192,
    "swa_v_head_dim": 128, "rope_theta": 5000000, "swa_rope_theta": 10000,
    "partial_rotary_factor": 0.334, "attention_value_scale": 0.707, "attention_bias": False,
    "sliding_window": 8, "sliding_window_size": 8, "attention_chunk_size": 8,
    "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False,
    "hidden_act": "silu", "layernorm_epsilon": 1e-5, "max_position_embeddings": 262144,
    "vocab_size": 211, "tie_word_embeddings": False,
    "engine": {"max_seq_len": 256, "max_seqs": 4}, "dtype": "float32",
}


def repeats_the_last_token(params):
    dim = params["mtp"]["eh_proj"].shape[1]
    mtp = dict(params["mtp"])
    mtp["layer"] = {k: jnp.zeros_like(v) if k in ("attn_o", "moe_out", "shared_out") else v
                    for k, v in mtp["layer"].items()}
    mtp["eh_proj"] = jnp.concatenate([jnp.zeros((dim, dim)), jnp.eye(dim)]).astype(
        mtp["eh_proj"].dtype)
    return {**params, "mtp": mtp}


@functools.cache
def build(name: str, **changes):
    """``(config, params)`` of ``name``: float32, weights from seed 0;
    ``changes`` replace keys of its file."""
    if name in PRESETS:
        config = PRESETS[name]
    else:
        adapter = importlib.import_module(f"perfbench.models.{ADAPTERS[name]}")
        config = adapter.program_config(FILES[name] | changes)
    config = dataclasses.replace(config, dtype=jnp.float32)
    # one compiled initialiser, not a dispatch a leaf: the model tests build
    # their weights as they are imported, which every xdist worker does for
    # every file while it collects (16 s against 4 for Granite's block)
    params = jax.jit(init_params, static_argnums=0)(config, jax.random.key(0))
    return config, repeats_the_last_token(params) if name == "joyai_repeats" else params
