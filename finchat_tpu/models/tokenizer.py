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

from dataclasses import dataclass
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


class HFTokenizer:
    """Local HuggingFace tokenizer adapter (no network)."""

    def __init__(self, path: str):
        from transformers import AutoTokenizer  # deferred: heavy import

        self._tok = AutoTokenizer.from_pretrained(path, local_files_only=True)
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
