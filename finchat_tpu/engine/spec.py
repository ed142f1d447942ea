"""Prompt-lookup draft proposal for speculative decoding.

No reference counterpart (the reference's LLM is a remote API). The
workload argument: the reference stuffs retrieved transaction rows and
chat history into the prompt (``qdrant_tool.py:145``, ``llm_agent.py:
234-236``) and the model's answers quote them back — generated text
heavily overlaps the prompt. Prompt-lookup decoding (n-gram matching
against the sequence's own token history) drafts those continuations for
free on the host: no draft model, no extra device memory, and the verify
step (engine.verify_step) scores all drafts in one weights-read. On a
miss the sequence degrades to plain one-token decode — token-for-token
identical to the non-speculative path under greedy, and each verify step
costs about the same device time as a decode step (~1.07x in the
builders' July 2026 measurement, not reproduced since). Throughput is not
strictly never-worse, though: the scheduler's spec mode runs depth-1 (dispatch then consume serially), so
on sustained all-miss traffic it gives up the depth-2 device/host
overlap of the plain decode path. The scheduler therefore drops a
sequence back to the pipelined non-spec path after
``SPEC_MISS_DEMOTE`` consecutive empty/all-rejected proposals.

``NgramIndex`` is incremental — O(n-gram widths) per appended token and
O(1) per proposal — because the scheduler proposes on the asyncio event
loop every verify step for every greedy slot; rescanning a few thousand
history tokens per slot per step would stall the very decode cadence
speculation is meant to speed up.
"""

from __future__ import annotations


class NgramIndex:
    """Incremental most-recent-occurrence index over a token history.

    For each n in ``[min_ngram, ngram]`` tracks where the latest and
    second-latest occurrence of every n-gram CONTINUES (the position right
    after it). ``propose`` matches the history's suffix n-gram (longest n
    first) against its second-latest occurrence — the latest is always the
    suffix itself — and drafts the tokens that followed it.
    """

    def __init__(self, history: list[int] | None = None, *,
                 ngram: int = 3, min_ngram: int = 2, max_history: int = 4096):
        assert 1 <= min_ngram <= ngram
        self._ns = tuple(range(ngram, min_ngram - 1, -1))  # longest first
        self._h: list[int] = []
        self._latest: dict[tuple, int] = {}
        self._prev: dict[tuple, int] = {}
        # cap the initial build: indexing a 32k-token ring-prefilled prompt
        # would do ~2 dict inserts per token ON THE EVENT LOOP (the
        # scheduler builds lazily at the first spec step); matches the
        # one-shot wrapper's cap below
        for tok in (history or [])[-max_history:]:
            self.push(tok)

    def push(self, token: int) -> None:
        """Append one token and index the n-grams it completes."""
        h = self._h
        h.append(token)
        L = len(h)
        for n in self._ns:
            if L >= n:
                key = (n, *h[L - n:])
                old = self._latest.get(key)
                if old is not None:
                    self._prev[key] = old
                self._latest[key] = L  # continuation starts here

    def propose(self, k: int) -> list[int]:
        """Up to ``k`` draft tokens continuing the current history, or
        ``[]`` when no suffix n-gram recurred earlier."""
        h = self._h
        L = len(h)
        if k <= 0:
            return []
        for n in self._ns:
            if L < n + 1:
                continue
            key = (n, *h[L - n:])
            start = self._latest.get(key)
            if start == L:  # the suffix's own entry; use the one before
                start = self._prev.get(key)
            if start is not None and start < L:
                return h[start:start + k]
        return []


def propose_ngram_drafts(
    history: list[int],
    k: int,
    *,
    ngram: int = 3,
    min_ngram: int = 2,
    max_history: int = 4096,
) -> list[int]:
    """One-shot convenience wrapper over ``NgramIndex`` (callers with a
    live sequence keep a persistent index instead — see the scheduler)."""
    if k <= 0:
        return []
    return NgramIndex(
        history, ngram=ngram, min_ngram=min_ngram, max_history=max_history
    ).propose(k)
