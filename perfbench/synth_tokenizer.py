"""A synthetic HF tokenizer directory at a model's own vocabulary size.

With no checkpoint the program falls back to its 260-id byte tokenizer, under
which a 32k-wide random-weight head emits text for one sampled token in 128:
a client would see its "first token" at a random depth. The program's own
answer is ``model.tokenizer_path``. This module writes such a directory:

- ids 0..255 are the byte-level symbols, id = byte value, and there are no
  merges, so a prompt encodes byte for byte (prompt lengths stay what the
  byte tokenizer gives);
- 256 ``<pad>``, 257 ``<s>``, 258 ``</s>`` (the byte tokenizer's own ids);
- every further id up to the vocabulary size is a filler ``~00259`` ...:
  never produced by encoding, always decoded to non-empty text, so every
  generated token becomes one chunk on the wire.
"""

from __future__ import annotations

import json
from pathlib import Path

N_BYTES = 256
PAD, BOS, EOS = "<pad>", "<s>", "</s>"
SPECIALS = (PAD, BOS, EOS)


def bytes_to_unicode() -> list[str]:
    """GPT-2's byte → printable-character table, indexed by byte value."""
    keep = (list(range(ord("!"), ord("~") + 1)) + list(range(0xA1, 0xAC + 1))
            + list(range(0xAE, 0xFF + 1)))
    table, extra = {}, 0
    for b in range(N_BYTES):
        if b in keep:
            table[b] = chr(b)
        else:
            table[b] = chr(N_BYTES + extra)
            extra += 1
    return [table[b] for b in range(N_BYTES)]


def build_vocab(vocab_size: int) -> dict[str, int]:
    if vocab_size < N_BYTES + len(SPECIALS):
        raise ValueError(f"vocab_size {vocab_size} cannot hold bytes and specials")
    vocab = {sym: i for i, sym in enumerate(bytes_to_unicode())}
    for token in SPECIALS:
        vocab[token] = len(vocab)
    while len(vocab) < vocab_size:
        vocab[f"~{len(vocab):05d}"] = len(vocab)
    return vocab


def write_tokenizer_dir(path: str | Path, vocab_size: int) -> Path:
    """Write ``tokenizer.json`` + ``tokenizer_config.json`` under ``path``
    (idempotent: an existing directory of the right size is kept, so a
    cell's runs reuse it)."""
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers

    path = Path(path)
    marker = path / "perfbench_vocab_size.txt"
    if marker.exists() and marker.read_text().strip() == str(vocab_size):
        return path
    path.mkdir(parents=True, exist_ok=True)
    tok = Tokenizer(models.BPE(vocab=build_vocab(vocab_size), merges=[]))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False, use_regex=False)
    tok.decoder = decoders.ByteLevel()
    tok.add_special_tokens(list(SPECIALS))
    tok.save(str(path / "tokenizer.json"))
    (path / "tokenizer_config.json").write_text(json.dumps({
        "tokenizer_class": "PreTrainedTokenizerFast",
        "bos_token": BOS, "eos_token": EOS, "pad_token": PAD,
        "clean_up_tokenization_spaces": False,
        "model_max_length": 1 << 20,
    }))
    (path / "special_tokens_map.json").write_text(json.dumps(
        {"bos_token": BOS, "eos_token": EOS, "pad_token": PAD}))
    marker.write_text(str(vocab_size))
    return path
