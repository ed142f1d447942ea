"""``models/quant.py`` ``flat_fence``: the product of a projection that is about
to be split into heads is fenced flat, so that the TPU compiler's layout for
the heads cannot reach the weight (PR 45; ``tests/test_tpu_compile.py`` holds
what that buys in the compiled step). The fence changes NO value: with it, and
with the plain identity put in its place at the three modules' sites,
``forward``'s logits are bit-identical on one token and on a chunk, and
so is the gradient of a scalar loss — in every model that runs a fenced site:
the llama block with rotation, with ``qk_norm``, with Falcon-H1's
``key_multiplier`` beside its mixer, a ``layer_pattern`` and a ``layer_plan``
model, and the latent one.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finchat_tpu.models import llama, mla, sambay
from finchat_tpu.models.llama import LlamaConfig, forward, init_params, make_causal_attention
from finchat_tpu.models.quant import dense, flat_fence
from finchat_tpu.models.sambay import CROSS, FULL, GMU, MAMBA1, WINDOW
from perfbench.models import adapter

ENGINE = {"max_seq_len": 256, "max_seqs": 4}
FILES = {
    # models/llama.py `_layer`, q and k rotated
    "rotation": LlamaConfig(),
    "rotation, grouped heads, experts": LlamaConfig(n_heads=4, n_kv_heads=2, n_experts=4,
                                                    top_k_experts=2),
    # ... q and k normed over the whole width before the split, no rotation, in a pattern
    "qk_norm in a layer_pattern": {
        "model_type": "olmo_hybrid", "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 4, "num_attention_heads": 4, "num_key_value_heads": 4,
        "vocab_size": 300, "rms_norm_eps": 1e-6,
        "layer_types": ["linear_attention"] * 3 + ["full_attention"],
        "linear_num_key_heads": 4, "linear_num_value_heads": 4, "linear_key_head_dim": 8,
        "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
        "engine": ENGINE},
    # ... k scaled between the fence and the split, a mixer beside attention
    "key_multiplier beside a mixer": {
        "model_type": "falcon_h1", "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 32, "vocab_size": 300, "rope_theta": 1e11, "rms_norm_eps": 1e-5,
        "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_ssm": 64, "mamba_d_state": 8,
        "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 8,
        "embedding_multiplier": 5.65, "lm_head_multiplier": 0.0625,
        "attention_in_multiplier": 0.9, "attention_out_multiplier": 0.3,
        "key_multiplier": 0.11, "mlp_multipliers": [0.17, 0.5], "ssm_in_multiplier": 1.3,
        "ssm_out_multiplier": 1.5, "ssm_multipliers": [1.2, 1.5, 1.4, 1.6, 2.0],
        "engine": ENGINE},
    # models/sambay.py `attention`: biases behind the fence, differential pairs
    "layer_plan": {
        "model_type": "phi4flash", "hidden_size": 64, "intermediate_size": 96,
        "num_attention_heads": 8, "num_key_value_heads": 4, "num_hidden_layers": 10,
        "layer_types": [MAMBA1, WINDOW] * 2 + [MAMBA1, FULL] + [GMU, CROSS] * 2,
        "sliding_window": 8, "vocab_size": 211, "layer_norm_eps": 1e-5,
        "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
        "mamba_d_state": 4, "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 4,
        "ssm_state_dtype": "float32", "engine": ENGINE},
    # models/mla.py `_from_latent`: the q latent's three up-projections
    "latent": {
        "model_type": "deepseek_v32", "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "n_routed_experts": 4, "num_experts_per_tok": 2,
        "reduced": {"n_routed_experts": {"from": 16, "to": 4, "why": "a chip's share"}},
        "n_group": 4, "topk_group": 2, "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "first_k_dense_replace": 1, "num_hidden_layers": 3, "num_nextn_predict_layers": 0,
        "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 32,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "index_n_heads": 4, "index_head_dim": 16, "index_topk": 24,
        "rope_theta": 10000, "rope_scaling": {
            "type": "yarn", "factor": 40, "original_max_position_embeddings": 64,
            "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
        "rms_norm_eps": 1e-6, "vocab_size": 300, "tie_word_embeddings": False,
        "engine": ENGINE},
}
# the fenced products in one trace of `forward` (a scan's body is traced once)
FENCES = {"rotation": 2, "rotation, grouped heads, experts": 2, "qk_norm in a layer_pattern": 2,
          "key_multiplier beside a mixer": 2, "layer_plan": 2 + 2 + 1, "latent": 3 + 3}


def _config(name):
    file = FILES[name]
    config = file if isinstance(file, LlamaConfig) else adapter(file).program_config(file)
    # the CPU backend has no bfloat16 dot with a float32 result (`mla.project`'s absorbed q)
    return dataclasses.replace(config, dtype=jnp.float32) if config.kv_lora_rank else config


def _logits_fn(config):
    """A fresh jitted ``forward`` over full causal attention: a trace of its own,
    so that it sees whatever stands at the fenced sites NOW."""
    attention = make_causal_attention("ref", config.attention_scale, config)
    return jax.jit(lambda params, tokens, positions: forward(
        params, tokens, positions, config=config, attention=attention)[0])


def _unfenced(monkeypatch):
    for module in (llama, sambay, mla):
        monkeypatch.setattr(module, "flat_fence", lambda product: product)


def _inputs(config, rows, length):
    rng = np.random.RandomState(length)
    tokens = jnp.asarray(rng.randint(0, config.vocab_size, size=(rows, length)), jnp.int32)
    return tokens, jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32) + 3, (rows, length))


def test_the_fence_is_the_identity_and_the_sites_go_through_it():
    x = jnp.arange(24, dtype=jnp.bfloat16).reshape(2, 3, 4)
    w = jnp.ones((4, 8), jnp.bfloat16)
    assert jnp.array_equal(flat_fence(x), x) and flat_fence(x).dtype == x.dtype
    assert jnp.array_equal(flat_fence(dense(x, w)), dense(x, w))
    assert "optimization_barrier" in str(jax.make_jaxpr(lambda x: flat_fence(dense(x, w)))(x))


@pytest.mark.parametrize("name", list(FILES))
def test_the_fence_changes_no_logit_and_no_gradient(monkeypatch, name):
    config = _config(name)
    # bfloat16: a product rounded elsewhere would show
    assert config.dtype == (jnp.float32 if config.kv_lora_rank else jnp.bfloat16)
    params = init_params(config, jax.random.key(7))
    one_token, chunk = _inputs(config, 3, 1), _inputs(config, 2, 21)

    def loss(fn):
        def scalar(params):
            logits = fn(params, *chunk)
            return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - logits[..., 5])
        return scalar

    def derivative(scalar):
        if not config.layer_plan:
            return jax.grad(scalar)
        # Mamba-1's scan is a loop over tokens with no reverse rule: the loss's
        # slope along one direction of every weight, forward mode
        direction = jax.tree.map(lambda x: jnp.full_like(x, 0.01), params)
        return lambda params: [jax.jvp(scalar, (params,), (direction,))[1]]

    def readings():
        fn = _logits_fn(config)
        fences = str(jax.make_jaxpr(fn)(params, *chunk)).count("optimization_barrier")
        return (fences, fn(params, *one_token), fn(params, *chunk),
                jax.jit(derivative(loss(fn)))(params))

    fences, token_logits, chunk_logits, grads = readings()
    assert fences == FENCES[name]
    _unfenced(monkeypatch)
    none, plain_token_logits, plain_chunk_logits, plain_grads = readings()
    assert none == 0
    assert np.isfinite(np.asarray(chunk_logits)).all() and np.ptp(np.asarray(chunk_logits)) > 0
    np.testing.assert_array_equal(np.asarray(token_logits), np.asarray(plain_token_logits))
    np.testing.assert_array_equal(np.asarray(chunk_logits), np.asarray(plain_chunk_logits))
    moved = 0
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(grads),
                                 jax.tree.leaves(plain_grads)):
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32),
                                      err_msg=jax.tree_util.keystr(path))
        moved += bool(np.any(np.asarray(got, np.float32) != 0))
    assert moved > len(jax.tree.leaves(grads)) // 2  # the loss reaches the weights
