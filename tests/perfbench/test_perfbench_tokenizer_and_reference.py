"""The synthetic tokenizer round-trips bytes, gives every id text and builds a
grammar vocabulary; the plain reference agrees with the program's forward."""

import dataclasses

import numpy as np
import pytest

from perfbench.synth_tokenizer import build_vocab, write_tokenizer_dir


@pytest.fixture(scope="module")
def tokenizer(tmp_path_factory):
    from finchat_tpu.models.tokenizer import HFTokenizer

    return HFTokenizer(str(write_tokenizer_dir(tmp_path_factory.mktemp("tok"), 1024)))


def test_vocab_layout():
    vocab = build_vocab(1024)
    assert len(vocab) == 1024 and sorted(vocab.values()) == list(range(1024))
    assert (vocab["<pad>"], vocab["<s>"], vocab["</s>"]) == (256, 257, 258)
    with pytest.raises(ValueError):
        build_vocab(200)


def test_prompts_encode_byte_for_byte(tokenizer):
    from finchat_tpu.models.tokenizer import ByteTokenizer

    text = "<|user|>\nHow much did I spend on café ☕ — $4.50?\n"
    ids = tokenizer.encode(text, add_bos=True)
    assert ids[0] == tokenizer.bos_id == 257 and tokenizer.eos_id == 258
    assert ids[1:] == ByteTokenizer().encode(text)
    assert tokenizer.decode(ids) == text


def test_every_id_but_the_specials_decodes_to_text(tokenizer):
    assert tokenizer.vocab_size == 1024
    empty = [i for i in range(tokenizer.vocab_size) if not tokenizer.decode([i])]
    assert empty == [256, 257, 258]


def test_grammar_vocab_builds(tokenizer):
    from finchat_tpu.agent.constrained import GrammarVocab

    assert GrammarVocab.for_tokenizer(tokenizer) is not None


def test_tokenizer_dir_is_reused(tmp_path):
    first = write_tokenizer_dir(tmp_path / "t", 512)
    stamp = (first / "tokenizer.json").stat().st_mtime_ns
    assert write_tokenizer_dir(tmp_path / "t", 512) == first
    assert (first / "tokenizer.json").stat().st_mtime_ns == stamp


@pytest.mark.parametrize("preset", ["tiny", "moe-tiny"])
def test_reference_agrees_with_the_program_at_float32(preset):
    import jax
    import jax.numpy as jnp

    from finchat_tpu.models.llama import PRESETS, forward_full, init_params
    from perfbench.reference import forward_logits

    c = dataclasses.replace(PRESETS[preset], dtype=jnp.float32)
    params = init_params(c, jax.random.key(3))
    tokens = np.random.RandomState(0).randint(0, c.vocab_size, size=48)
    want = np.asarray(forward_full(params, jnp.asarray(tokens)[None],
                                   jnp.arange(len(tokens))[None], config=c))[0]
    got = np.asarray(forward_logits(
        params, tokens, n_layers=c.n_layers, n_heads=c.n_heads, n_kv_heads=c.n_kv_heads,
        rope_theta=c.rope_theta, norm_eps=c.norm_eps, n_experts=c.n_experts,
        top_k_experts=c.top_k_experts))
    # float32 both sides; only the order of additions differs
    np.testing.assert_allclose(got, want, atol=2e-5 * float(np.std(want)) * 10, rtol=0)
    last = np.asarray(forward_logits(
        params, tokens, n_layers=c.n_layers, n_heads=c.n_heads, n_kv_heads=c.n_kv_heads,
        rope_theta=c.rope_theta, norm_eps=c.norm_eps, n_experts=c.n_experts,
        top_k_experts=c.top_k_experts, positions=[len(tokens) - 1]))
    np.testing.assert_allclose(last[0], got[-1], atol=1e-5)
    # routing margins: none for a dense model, a finite gap for routed experts
    _, margins = forward_logits(
        params, tokens, n_layers=c.n_layers, n_heads=c.n_heads, n_kv_heads=c.n_kv_heads,
        rope_theta=c.rope_theta, norm_eps=c.norm_eps, n_experts=c.n_experts,
        top_k_experts=c.top_k_experts, positions=[0, 5], return_margins=True)
    margins = np.asarray(margins)
    assert margins.shape == (2,)
    assert np.isinf(margins).all() if not c.n_experts else (np.isfinite(margins) & (margins >= 0)).all()


def test_costs_from_shapes():
    import json
    from pathlib import Path

    from perfbench import costs

    root = Path(__file__).resolve().parents[2]
    mixtral = json.loads((root / "perfbench/configs/mixtral-8x7b-v0.1.json").read_text())
    p = costs.param_counts(dict(mixtral, num_hidden_layers=32))
    assert p["layer"] == mixtral["memory"]["per_layer_params"] == 1_451_270_144
    assert 46.5e9 < p["total"] < 46.9e9  # the published 46.7 B
    assert costs.kv_bytes_per_token(dict(mixtral, num_hidden_layers=32)) == 131072
    # the dense block of the same widths (Mistral-7B): 218 M a layer, 7.25 B in all
    dense = dict(mixtral, num_hidden_layers=32, num_local_experts=0, vocab_size=32768)
    p = costs.param_counts(dense)
    assert p["layer"] == 218_112_000 and 7.2e9 < p["total"] < 7.3e9
    assert costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("TPU v99")
