"""Tokenizers and chat templating.

The reference delegates tokenization to external APIs (Gemini/OpenAI); here
it is in-tree. Two implementations behind one protocol:

- ``ByteTokenizer`` — self-contained UTF-8 byte-level vocab (256 bytes +
  specials). Used by tests and wherever no tokenizer directory is
  configured, so the whole stack runs with zero downloaded assets.
- ``HFTokenizer`` — adapter over a local HuggingFace tokenizer directory
  (Llama/TinyLlama checkpoints), gated on files being present.

Also here: ``IncrementalDecoder`` (UTF-8-safe streaming detokenization — a
multibyte codepoint split across two decode steps must not emit mojibake)
and the chat template that renders (system, history, user) into the prompt,
playing the role of the reference's ChatPromptTemplate (llm_agent.py:47-51).
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Protocol, Sequence

from finchat_tpu.io.schemas import ChatMessage


class Tokenizer(Protocol):
    vocab_size: int
    bos_id: int
    eos_id: int
    pad_id: int

    def encode(self, text: str, add_bos: bool = False) -> list[int]: ...

    def decode(self, ids: Sequence[int]) -> str: ...


@dataclass
class ByteTokenizer:
    """UTF-8 bytes 0..255, then PAD/BOS/EOS/EOT specials."""

    vocab_size: int = 260
    pad_id: int = 256
    bos_id: int = 257
    eos_id: int = 258
    eot_id: int = 259  # end-of-turn marker used by the chat template

    def encode(self, text: str, add_bos: bool = False) -> list[int]:
        ids = list(text.encode("utf-8"))
        return ([self.bos_id] if add_bos else []) + ids

    def encode_with_specials(self, text: str) -> list[int]:
        """Encoder-style framing (the embedding path's [CLS]...[SEP])."""
        return [self.bos_id] + self.encode(text) + [self.eos_id]

    def decode(self, ids: Sequence[int]) -> str:
        return bytes(i for i in ids if i < 256).decode("utf-8", errors="replace")


# the keys of ``tokenizer_config.json`` / ``special_tokens_map.json`` that name
# ONE special token (``additional_special_tokens`` names a list)
_SPECIAL_TOKEN_KEYS = ("bos_token", "eos_token", "unk_token", "sep_token",
                       "pad_token", "cls_token", "mask_token")


def _read_json(path: Path) -> dict:
    return json.loads(path.read_text(encoding="utf-8")) if path.exists() else {}


def _clean_up_tokenization(text: str) -> str:
    """transformers' ``clean_up_tokenization``: the spaces an English
    word-level vocabulary leaves before punctuation and contractions."""
    for spaced, joined in ((" .", "."), (" ?", "?"), (" !", "!"), (" ,", ","),
                           (" ' ", "'"), (" n't", "n't"), (" 'm", "'m"),
                           (" 's", "'s"), (" 've", "'ve"), (" 're", "'re")):
        text = text.replace(spaced, joined)
    return text


class _TokenizersBackend:
    """A directory's ``tokenizer.json`` read by ``tokenizers`` itself — the
    library that does the work under ``transformers``' fast tokenizers — with
    what ``AutoTokenizer`` adds from the files beside it: the special tokens'
    names (``tokenizer_config.json``; where that file lists no
    ``added_tokens_decoder``, ``special_tokens_map.json`` over it), a name a
    string or a dict with ``content``, and a named special that the file does
    not list among its added tokens is added as one; the file's truncation and
    padding switched off (an ``encode`` here asks for neither);
    ``clean_up_tokenization_spaces`` as the config states it, False where it
    says nothing (transformers' default). Importing ``transformers`` brings
    ``torch``, ``sklearn`` and ``pandas`` into the process — 20 s of every
    start on a serving host (PERF.md section 6, PR 54) — to read this one
    file. What a tokenizer CLASS writes over the file from the config is not
    applied but DETECTED (``overlaid``, and ``_hf_backend`` then leaves the
    directory to ``AutoTokenizer``). Not mirrored: a class's own defaults for
    keys the config leaves out (``save_pretrained`` always writes them)."""

    def __init__(self, path: Path):
        from tokenizers import Tokenizer as TokenizersTokenizer

        tok = TokenizersTokenizer.from_file(str(path / "tokenizer.json"))
        tok.no_truncation()
        tok.no_padding()
        config = _read_json(path / "tokenizer_config.json")
        names = config if "added_tokens_decoder" in config else {
            **config, **_read_json(path / "special_tokens_map.json")}

        def content(value) -> str | None:
            return value.get("content") if isinstance(value, dict) else value

        special = {key: content(names[key]) for key in _SPECIAL_TOKEN_KEYS
                   if names.get(key) is not None}
        named = list(dict.fromkeys(
            [*special.values(),
             *(content(t) for t in names.get("additional_special_tokens") or [])]))
        have = {t.content for t in tok.get_added_tokens_decoder().values()}
        missing = [t for t in named if t not in have]
        if missing:
            tok.add_special_tokens(missing)
        self._tok = tok
        # None where the files name no such token (every named one has an id by now)
        self.bos_token_id, self.eos_token_id, self.pad_token_id = (
            tok.token_to_id(special[key]) if key in special else None
            for key in ("bos_token", "eos_token", "pad_token"))
        # the NAMED specials' ids, as transformers' ``all_special_ids``
        # (agent/constrained.py ``token_texts`` gives them no text)
        self.all_special_ids = sorted({tok.token_to_id(t) for t in named})
        self._clean_up = bool(config.get("clean_up_tokenization_spaces", False))
        self.overlaid = self._overlaid(config)

    def _overlaid(self, config: dict) -> list[str]:
        """The keys of ``tokenizer_config.json`` whose value a tokenizer class
        would write over a ``tokenizer.json`` that says otherwise: the framing
        (``LlamaTokenizerFast`` and its kin rebuild the post-processor from
        ``add_bos_token`` / ``add_eos_token``), the normalizer
        (``BertTokenizerFast``, where the file's state has the key) and the
        pre-tokenizer's ``add_prefix_space`` (every fast tokenizer; False where
        the config leaves it out). Empty for a directory that agrees with itself."""
        tok, keys = self._tok, []
        framed = tok.encode("a", add_special_tokens=True)
        text = [i for i, added in enumerate(framed.special_tokens_mask) if not added]
        # (a vocabulary with no piece for the probe: all of it is framing, on both sides)
        head, tail = (framed.ids[:text[0]], framed.ids[text[-1] + 1:]) if text else (framed.ids,) * 2
        for key, got, token_id in (("add_bos_token", head, self.bos_token_id),
                                   ("add_eos_token", tail, self.eos_token_id)):
            if config.get(key) is not None and got != [token_id] * bool(config[key]):
                keys.append(key)

        def state(part) -> dict:
            return json.loads(part.__getstate__()) if part is not None else {}

        normalizer = state(tok.normalizer)
        for key, theirs in (("do_lower_case", "lowercase"), ("strip_accents", "strip_accents"),
                            ("tokenize_chinese_chars", "handle_chinese_chars")):
            if key in config and normalizer.get(theirs, config[key]) != config[key]:
                keys.append(key)
        prefix = config.get("add_prefix_space", False)
        if prefix is not None and state(tok.pre_tokenizer).get("add_prefix_space", prefix) != prefix:
            keys.append("add_prefix_space")
        return keys

    def __len__(self) -> int:
        return self._tok.get_vocab_size(with_added_tokens=True)

    def encode(self, text: str, add_special_tokens: bool) -> list[int]:
        return self._tok.encode(text, add_special_tokens=add_special_tokens).ids

    def convert_ids_to_tokens(self, ids: Sequence[int]) -> list[str | None]:
        """The vocabulary's own pieces ('▁foo', '<0x0A>'), undecoded."""
        return [self._tok.id_to_token(i) for i in ids]

    def decode(self, ids: Sequence[int], skip_special_tokens: bool) -> str:
        text = self._tok.decode(list(ids), skip_special_tokens=skip_special_tokens)
        return _clean_up_tokenization(text) if self._clean_up else text


def _hf_backend(path: str):
    """What reads the directory, chosen by what it holds: ``tokenizers`` where
    there is a ``tokenizer.json`` that its config does not overrule, else
    (SentencePiece only, or a config the class applies over the file)
    ``AutoTokenizer``."""
    if (Path(path) / "tokenizer.json").exists():
        backend = _TokenizersBackend(Path(path))
        if not backend.overlaid:
            return backend
    from transformers import AutoTokenizer  # deferred: heavy import

    return AutoTokenizer.from_pretrained(path, local_files_only=True)


class HFTokenizer:
    """Local HuggingFace tokenizer adapter (no network)."""

    def __init__(self, path: str):
        self._tok = _hf_backend(path)
        self.vocab_size = len(self._tok)
        self.bos_id = self._tok.bos_token_id or 0
        self.eos_id = self._tok.eos_token_id or 0
        self.pad_id = self._tok.pad_token_id if self._tok.pad_token_id is not None else self.eos_id
        self.eot_id = self.eos_id

    def encode(self, text: str, add_bos: bool = False) -> list[int]:
        ids = self._tok.encode(text, add_special_tokens=False)
        return ([self.bos_id] if add_bos else []) + ids

    def encode_with_specials(self, text: str) -> list[int]:
        """The tokenizer's own special framing — [CLS]...[SEP] for BERT
        vocabularies (what bge embeddings expect), <s>... for Llama ones."""
        return self._tok.encode(text, add_special_tokens=True)

    def decode(self, ids: Sequence[int]) -> str:
        return self._tok.decode(ids, skip_special_tokens=True)


def get_tokenizer(tokenizer_path: str = "") -> Tokenizer:
    if tokenizer_path:
        return HFTokenizer(tokenizer_path)
    return ByteTokenizer()


class IncrementalDecoder:
    """Streaming detokenizer that never emits a torn UTF-8 sequence.

    For byte-level vocabs a single emoji spans 4 tokens; flushing after each
    token must buffer incomplete prefixes. For HF tokenizers the same applies
    to byte-fallback pieces, handled by decoding the running tail.
    """

    def __init__(self, tokenizer: Tokenizer):
        self._tok = tokenizer
        self._pending: list[int] = []
        self._emitted = ""

    def push(self, token_id: int) -> str:
        """Feed one token id; return newly-safe text (possibly '')."""
        if isinstance(self._tok, ByteTokenizer):
            if token_id >= 256:
                return ""  # specials carry no text
            self._pending.append(token_id)
            raw = bytes(self._pending)
            try:
                text = raw.decode("utf-8")
                self._pending.clear()
                return text
            except UnicodeDecodeError as e:
                tail = len(raw) - e.start
                if tail > 3:
                    # a valid incomplete UTF-8 tail is ≤3 bytes; this is
                    # garbage — emit with replacement instead of buffering
                    # forever.
                    self._pending.clear()
                    return raw.decode("utf-8", errors="replace")
                # emit the valid prefix, keep the incomplete tail buffered
                valid = raw[: e.start].decode("utf-8")
                self._pending = list(raw[e.start:])
                return valid
        # HF path: decode the whole pending tail; emit only when the decoded
        # text doesn't end in the replacement char (torn byte-fallback).
        self._pending.append(token_id)
        text = self._tok.decode(self._pending)
        if text and not text.endswith("�"):
            self._pending.clear()
            return text
        return ""

    def flush(self) -> str:
        text = self._tok.decode(self._pending) if self._pending else ""
        self._pending.clear()
        return text


# ---------------------------------------------------------------------------
# Chat templating — the native replacement for the reference's
# ChatPromptTemplate: system(system_prompt + "\n" + context) / history / user
# (reference llm_agent.py:47-51).
# ---------------------------------------------------------------------------

_ROLE_TAGS = {"system": "<|system|>", "user": "<|user|>", "assistant": "<|assistant|>"}


def render_chat_head(system_prompt: str) -> str:
    """The constant leading string of a rendered prompt for a given system
    text — BY CONSTRUCTION a byte prefix of ``render_chat`` output (which
    builds its first part from this), so the shared-prefix KV cache and
    the prompt builders can never drift apart."""
    return f"{_ROLE_TAGS['system']}\n{system_prompt}\n"


def render_chat_prefix(
    system_prompt: str,
    context: str,
    history: Sequence[ChatMessage],
) -> str:
    """Everything of a rendered prompt that is known BEFORE the final user
    turn's content: system turn (system + context), the chat history, and
    the opening user tag. BY CONSTRUCTION a byte prefix of ``render_chat``
    with the same arguments (render_chat builds from this), so the
    retrieval/prefill overlap plane can prefill it while retrieval is
    still deciding what the user turn will carry — the two can never
    drift apart."""
    parts = [f"{render_chat_head(system_prompt)}{context}\n"]
    for turn in history:
        role = "user" if turn.is_user else "assistant"
        parts.append(f"{_ROLE_TAGS[role]}\n{turn.message}\n")
    parts.append(f"{_ROLE_TAGS['user']}\n")
    return "".join(parts)


def render_chat(
    system_prompt: str,
    context: str,
    history: Sequence[ChatMessage],
    user_input: str,
) -> str:
    """Render the prompt string fed to the decoder.

    Structure parity with the reference prompt template: one system turn
    holding ``{system_prompt}\\n{context}``, then the chat history in order,
    then the new user turn, then the assistant tag left open for generation.
    """
    return (
        f"{render_chat_prefix(system_prompt, context, history)}"
        f"{user_input}\n{_ROLE_TAGS['assistant']}\n"
    )
