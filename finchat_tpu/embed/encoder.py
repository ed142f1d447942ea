"""TPU-batched embedding encoder (BERT/bge family).

Replaces the reference's OpenAI embeddings API call
(``tools/qdrant_tool.py:28,137``) with an in-tree bidirectional encoder:
token+position embeddings → post-LN transformer stack → pooling (CLS for the
bge recipe, masked mean as an option) → L2 normalization. Layer semantics
match HuggingFace ``BertModel`` (biases everywhere, exact GELU, token-type
row 0 folded into the position table) so real bge-base-en checkpoints load
via ``checkpoints/bert_loader.py`` and reproduce HF outputs — see
tests/test_bert_loader.py for the torch parity proof. Queries are batched
and padded to fixed buckets so the encoder is one compiled function per
bucket (no recompiles per request), and upserts ride the same batched path.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from finchat_tpu.models.quant import dense as quant_dense
from finchat_tpu.models.quant import quantize_stacked
from finchat_tpu.models.tokenizer import Tokenizer
from finchat_tpu.ops.refs import mha_reference

# the encoder's matmul leaves — what int8 weight-only quantization covers
# (embeddings are gathers, LayerNorm scales/biases are precision-sensitive
# and tiny; biases ride unquantized like the decoder's norms)
BERT_QUANT_LEAVES = ("qkv", "attn_out", "mlp_in", "mlp_out")


def quantize_bert_params(params: dict[str, Any]) -> dict[str, Any]:
    """Int8-quantize the encoder's stacked matmul weights (ISSUE 14): the
    SAME ``QTensor`` machinery as the decoder (models/quant.py — per-slice
    ``quantize_stacked``, per-output-column scales, inline dequant fused
    into the dot), so the retrieval plane rides the serving quant mode.
    Idempotent on already-quantized trees."""
    from finchat_tpu.models.quant import Q4Tensor, QTensor

    layers = {
        name: (leaf if isinstance(leaf, (QTensor, Q4Tensor))
               or name not in BERT_QUANT_LEAVES
               else quantize_stacked(leaf))
        for name, leaf in params["layers"].items()
    }
    return {**params, "layers": layers}


@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 260
    dim: int = 64
    n_layers: int = 2
    n_heads: int = 4
    hidden_dim: int = 128
    max_position: int = 512
    norm_eps: float = 1e-12
    dtype: Any = jnp.bfloat16
    pooling: str = "mean"  # "mean" | "cls" (bge uses CLS)

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads


EMBED_PRESETS: dict[str, BertConfig] = {
    # byte-vocab debug encoder
    "bge-tiny": BertConfig(),
    # bge-base-en architecture (BAAI/bge-base-en-v1.5 card): BERT-base,
    # CLS pooling + L2 norm
    "bge-base-en": BertConfig(
        vocab_size=30_522, dim=768, n_layers=12, n_heads=12, hidden_dim=3072,
        max_position=512, pooling="cls",
    ),
}


def init_bert_params(config: BertConfig, key: Array) -> dict[str, Any]:
    c = config
    keys = jax.random.split(key, 8)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5).astype(c.dtype)

    L, D, F = c.n_layers, c.dim, c.hidden_dim
    return {
        "tok_embed": dense(keys[0], (c.vocab_size, D), D),
        "pos_embed": dense(keys[1], (c.max_position, D), D),
        "embed_ln_scale": jnp.ones((D,), c.dtype),
        "embed_ln_bias": jnp.zeros((D,), c.dtype),
        "layers": {
            "qkv": dense(keys[2], (L, D, 3 * D), D),
            "qkv_bias": jnp.zeros((L, 3 * D), c.dtype),
            "attn_out": dense(keys[3], (L, D, D), D),
            "attn_out_bias": jnp.zeros((L, D), c.dtype),
            "ln1_scale": jnp.ones((L, D), c.dtype),
            "ln1_bias": jnp.zeros((L, D), c.dtype),
            "mlp_in": dense(keys[4], (L, D, F), D),
            "mlp_in_bias": jnp.zeros((L, F), c.dtype),
            "mlp_out": dense(keys[5], (L, F, D), F),
            "mlp_out_bias": jnp.zeros((L, D), c.dtype),
            "ln2_scale": jnp.ones((L, D), c.dtype),
            "ln2_bias": jnp.zeros((L, D), c.dtype),
        },
    }


def _layer_norm(x: Array, scale: Array, bias: Array, eps: float) -> Array:
    x32 = x.astype(jnp.float32)
    mean = x32.mean(axis=-1, keepdims=True)
    var = ((x32 - mean) ** 2).mean(axis=-1, keepdims=True)
    out = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return out.astype(x.dtype) * scale + bias


@partial(jax.jit, static_argnames=("config", "qm_backend"))
def encode_batch(
    params: dict[str, Any],
    tokens: Array,  # [B, S] int32 (right-padded)
    lengths: Array,  # [B] int32 valid lengths
    *,
    config: BertConfig,
    qm_backend: str = "ref",
) -> Array:
    """Encode a padded batch → L2-normalized embeddings [B, dim] fp32."""
    c = config
    B, S = tokens.shape
    x = params["tok_embed"][tokens] + params["pos_embed"][:S][None, :, :]
    x = _layer_norm(x, params["embed_ln_scale"], params["embed_ln_bias"], c.norm_eps)

    valid = (jnp.arange(S)[None, :] < lengths[:, None])  # [B, S]

    def body(x, layer):
        # quant_dense = plain ``x @ w`` on unquantized leaves; QTensor
        # leaves (the embed.quant path, quantize_bert_params) route via
        # ops/dispatch.quant_matmul — the inline-dequant reference on
        # CPU, the fused packed-read Pallas kernel under qm_backend
        qkv = quant_dense(x, layer["qkv"], qm_backend=qm_backend) + layer["qkv_bias"]  # [B,S,3D]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, S, c.n_heads, c.head_dim)
        k = k.reshape(B, S, c.n_heads, c.head_dim)
        v = v.reshape(B, S, c.n_heads, c.head_dim)
        attn = mha_reference(q, k, v, causal=False, kv_len=lengths)
        x = _layer_norm(
            x + quant_dense(attn.reshape(B, S, -1), layer["attn_out"],
                            qm_backend=qm_backend)
            + layer["attn_out_bias"],
            layer["ln1_scale"], layer["ln1_bias"], c.norm_eps,
        )
        # exact (erf) GELU — what BERT/bge checkpoints were trained with
        h = jax.nn.gelu(
            (quant_dense(x, layer["mlp_in"], qm_backend=qm_backend)
             + layer["mlp_in_bias"]).astype(jnp.float32),
            approximate=False,
        ).astype(x.dtype)
        x = _layer_norm(
            x + quant_dense(h, layer["mlp_out"], qm_backend=qm_backend)
            + layer["mlp_out_bias"],
            layer["ln2_scale"], layer["ln2_bias"], c.norm_eps,
        )
        return x, None

    x, _ = jax.lax.scan(body, x, params["layers"])

    if c.pooling == "cls":
        pooled = x[:, 0, :].astype(jnp.float32)
    else:  # masked mean
        mask = valid[:, :, None].astype(jnp.float32)
        pooled = (x.astype(jnp.float32) * mask).sum(axis=1) / jnp.maximum(mask.sum(axis=1), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)


_BUCKETS = (32, 64, 128, 256, 512)


class EmbeddingEncoder:
    """Host-side wrapper: tokenize, bucket-pad, encode on device.

    ``batch_size`` (EmbedConfig.batch_size) caps the rows per device call so
    a 10k-row ingest doesn't materialize one giant activation tensor.
    """

    def __init__(self, config: BertConfig, params: dict[str, Any], tokenizer: Tokenizer,
                 *, batch_size: int = 64, quant: str = ""):
        if quant and quant != "int8":
            raise ValueError(
                f"unknown embed quant mode {quant!r} (supported: 'int8')"
            )
        self.config = config
        # embed.quant: the retrieval plane rides the serving quant mode —
        # int8 weight-only via the decoder's QTensor machinery (ISSUE 14);
        # quality gate: quantized-vs-fp32 top-k overlap >= 0.99
        # (tests/test_quant_serving.py)
        self.params = quantize_bert_params(params) if quant else params
        self.quant = quant
        # resolve the fused-matmul backend ONCE (ops/dispatch discipline:
        # env must not be read inside the jitted encode); unquantized
        # encoders pin "ref" so they don't add a compiled variant per env
        if quant:
            from finchat_tpu.ops.dispatch import quant_matmul_backend

            self.qm_backend = quant_matmul_backend()
        else:
            self.qm_backend = "ref"
        self.tokenizer = tokenizer
        self.batch_size = batch_size

    @property
    def dim(self) -> int:
        return self.config.dim

    def _bucket(self, n: int) -> int:
        for b in _BUCKETS:
            if n <= b and b <= self.config.max_position:
                return b
        return min(_BUCKETS[-1], self.config.max_position)

    def embed_batch(self, texts: list[str]) -> np.ndarray:
        """Embed texts → [n, dim] fp32 numpy (one device call per micro-batch)."""
        out = np.empty((len(texts), self.dim), np.float32)
        for lo in range(0, len(texts), self.batch_size):
            out[lo : lo + self.batch_size] = self._embed_micro(texts[lo : lo + self.batch_size])
        return out

    def _embed_micro(self, texts: list[str]) -> np.ndarray:
        encode = getattr(self.tokenizer, "encode_with_specials", self.tokenizer.encode)
        ids = [encode(t)[: self.config.max_position] for t in texts]
        lengths = [max(1, len(i)) for i in ids]
        bucket = self._bucket(max(lengths))
        # the batch is padded like the length (rows of one pad token, cut
        # from the result): coalesced queries arrive in any count, and a
        # count that compiles its own program stalls its requests for
        # seconds the first time it comes up
        rows = 1
        while rows < len(ids):
            rows *= 2
        padded = np.zeros((rows, bucket), np.int32)
        for row, seq in enumerate(ids):
            padded[row, : len(seq)] = seq[:bucket]
        lengths += [1] * (rows - len(ids))
        out = encode_batch(
            self.params, jnp.asarray(padded), jnp.asarray(lengths, jnp.int32),
            config=self.config, qm_backend=self.qm_backend,
        )
        return np.asarray(out)[: len(ids)]

    def embed_query(self, text: str) -> np.ndarray:
        return self.embed_batch([text])[0]
