"""Text-generation abstraction consumed by the agent layer.

``TextGenerator`` is the seam where the reference called the Gemini API
(``llm_agent.py:88`` invoke, ``llm_agent.py:243`` astream): the agent only
sees "prompt in → text chunks out". Implementations:

- ``EngineGenerator`` — the TPU continuous-batching engine.
- ``StubGenerator`` — canned responses for tests and the no-TPU dev loop
  (plays the role of SURVEY §4.4's fake backend).
"""

from __future__ import annotations

import asyncio
import itertools
from typing import AsyncIterator, Callable, Protocol

from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
from finchat_tpu.models.tokenizer import IncrementalDecoder, Tokenizer
from finchat_tpu.utils.logging import get_logger

logger = get_logger(__name__)


class GenerationError(RuntimeError):
    """Generation failed. ``code``/``retryable`` carry the scheduler's
    structured error fields when present (deadline shed, overload) so the
    serving layer can emit a retryable error chunk instead of an opaque
    one."""

    def __init__(self, message: str, *, code: str | None = None,
                 retryable: bool = False):
        super().__init__(message)
        self.code = code
        self.retryable = retryable


class TextGenerator(Protocol):
    # ``conversation_id`` keys the engine's cross-turn session KV cache
    # (engine/session_cache.py); None = no cross-turn reuse. ``deadline``
    # (monotonic time.perf_counter) feeds the scheduler's shed/EDF
    # admission; None = no deadline. ``trace_id`` threads the ingress-
    # minted end-to-end trace id into the scheduler's span/dispatch
    # events (utils/tracing.py — ISSUE 12); None = untraced. Non-engine
    # implementations may ignore all three.
    async def stream(
        self, prompt: str, sampling: SamplingParams,
        conversation_id: str | None = None,
        deadline: float | None = None,
        trace_id: str | None = None,
    ) -> AsyncIterator[str]: ...

    async def generate(
        self, prompt: str, sampling: SamplingParams,
        conversation_id: str | None = None,
        deadline: float | None = None,
        trace_id: str | None = None,
    ) -> str: ...


class EngineGenerator:
    def __init__(self, scheduler: ContinuousBatchingScheduler, tokenizer: Tokenizer):
        self.scheduler = scheduler
        self.tokenizer = tokenizer
        self._ids = itertools.count()
        self._grammar_vocabs: dict[str, object] = {}  # grammar name -> GrammarVocab

    # --- prompt budgeting (SURVEY §5.7; VERDICT r1 task 7) ---------------
    # The agent uses these to window history BEFORE submit, so over-long
    # conversations degrade gracefully instead of erroring at the scheduler
    # (the reference stuffs unbounded history, llm_agent.py:234-236, and
    # leans on the external API as backstop; here the budget is explicit).
    def count_tokens(self, text: str) -> int:
        return len(self.tokenizer.encode(text, add_bos=True))

    def prompt_budget(self, sampling: SamplingParams) -> int:
        """Max prompt tokens a sequence may carry and still have room for
        ``max_new_tokens`` in its KV allocation."""
        eng = self.scheduler.engine
        max_len = eng.max_pages_per_seq * eng.page_size
        return max(1, max_len - sampling.max_new_tokens)

    def prepare_grammar(self, grammar: str):
        """Start (once) the build of a grammar's vocabulary tables and return
        its task. The app calls it at start-up, so that the first tool
        decision does not wait for an O(vocab) build: 1.5-2 s at a 261k
        vocabulary, in front of every request of the first batch."""
        from finchat_tpu.agent.constrained import GrammarVocab

        if grammar != "tool_call":
            raise ValueError(f"unknown grammar {grammar!r}")
        # single-flight: cache the build TASK, not the result, so concurrent
        # first requests share one O(vocab) build (token decode + dense DFA
        # table), run off the event loop so in-flight decodes aren't stalled
        task = self._grammar_vocabs.get(grammar)
        if task is None:
            task = asyncio.ensure_future(
                asyncio.to_thread(GrammarVocab.for_tokenizer, self.tokenizer)
            )
            self._grammar_vocabs[grammar] = task
        return task

    async def _make_constraint(self, grammar: str):
        from finchat_tpu.agent.constrained import TokenConstraint

        task = self.prepare_grammar(grammar)
        try:
            vocab = await task
        except Exception:
            # evict the failed build so the next request retries instead of
            # re-raising a stale error forever
            if self._grammar_vocabs.get(grammar) is task:
                del self._grammar_vocabs[grammar]
            raise
        return TokenConstraint(vocab)

    # --- retrieval/prefill overlap (ISSUE 3) -----------------------------
    async def begin_partial(
        self, prefix_text: str, sampling: SamplingParams,
        conversation_id: str | None = None,
        deadline: float | None = None,
        trace_id: str | None = None,
    ):
        """Start prefilling a prompt's static prefix while its tail (the
        retrieval graft) is still being computed. Returns an opaque handle
        to pass to ``stream(..., partial=...)``, or None when the prefix
        can't ride the overlap path (over budget, ring-eligible, grammar
        use). The final encoded token is dropped — a subword tokenizer can
        merge across the graft boundary, so the last prefix token is the
        only one whose identity depends on what follows (the same boundary
        rule as the shared-prefix head registration, serve/app.py)."""
        if sampling.grammar:
            return None  # constrained decodes need per-token host control
        prefix_ids = self.tokenizer.encode(prefix_text, add_bos=True)[:-1]
        if not prefix_ids or len(prefix_ids) > self.prompt_budget(sampling):
            return None
        return await self.scheduler.submit_partial(
            f"seq-{next(self._ids)}", prefix_ids, sampling,
            conversation_id=conversation_id, deadline=deadline,
            trace_id=trace_id,
        )

    def release_partial(self, partial) -> None:
        """Drop an unconsumed partial hold (retrieval errored before
        generation, or the caller bailed): frees its slot and pages. A
        hold that was already claimed by ``stream`` is left alone."""
        if partial is not None and not getattr(partial, "_partial_claimed", False):
            self.scheduler.cancel(partial)

    async def stream(
        self, prompt: str, sampling: SamplingParams,
        conversation_id: str | None = None,
        partial=None,
        deadline: float | None = None,
        trace_id: str | None = None,
    ) -> AsyncIterator[str]:
        prompt_ids = self.tokenizer.encode(prompt, add_bos=True)
        budget = self.prompt_budget(sampling)
        if len(prompt_ids) > budget:
            # token-level backstop beneath the agent's structural windowing:
            # keep the head (system rules) and the tail (latest turns + open
            # assistant tag) and drop the middle, so a too-long prompt still
            # answers instead of raising at submit
            head = budget // 4
            tail = budget - head
            logger.warning(
                "prompt of %d tokens exceeds budget %d; splicing head %d + tail %d",
                len(prompt_ids), budget, head, tail,
            )
            prompt_ids = prompt_ids[:head] + prompt_ids[-tail:]
        handle = None
        if partial is not None:
            from finchat_tpu.utils.metrics import METRICS, Timer

            # claim BEFORE the extend attempt: whatever happens next, the
            # hold is this stream's to consume or cancel
            partial._partial_claimed = True
            with Timer(METRICS, "finchat_retrieval_graft_seconds"):
                grafted = self.scheduler.extend_prompt(partial, prompt_ids)
            if grafted:
                handle = partial
            else:
                # graft point invalidated (windowing changed the prefix,
                # budget splice, pages unavailable): clean serial fallback
                self.scheduler.cancel(partial)
        if handle is None:
            seq_id = f"seq-{next(self._ids)}"
            constraint = await self._make_constraint(sampling.grammar) if sampling.grammar else None
            handle = await self.scheduler.submit(
                seq_id, prompt_ids, sampling, constraint=constraint,
                conversation_id=conversation_id, deadline=deadline,
                trace_id=trace_id,
            )
        decoder = IncrementalDecoder(self.tokenizer)
        try:
            while True:
                event = await handle.events.get()
                if event["type"] == "token":
                    text = decoder.push(event["token_id"])
                    if text:
                        yield text
                elif event["type"] == "done":
                    tail = decoder.flush()
                    if tail:
                        yield tail
                    return
                else:  # error — carry the scheduler's structured fields
                    # (deadline shed / overload) so the serving layer can
                    # emit a retryable error chunk
                    raise GenerationError(
                        event["message"],
                        code=event.get("code"),
                        retryable=bool(event.get("retryable", False)),
                    )
        finally:
            if not handle.finished:
                self.scheduler.cancel(handle)

    async def generate(
        self, prompt: str, sampling: SamplingParams,
        conversation_id: str | None = None,
        partial=None,
        deadline: float | None = None,
        trace_id: str | None = None,
    ) -> str:
        return "".join([
            piece async for piece in self.stream(
                prompt, sampling, conversation_id=conversation_id,
                partial=partial, deadline=deadline, trace_id=trace_id,
            )
        ])


class StubGenerator:
    """Deterministic canned generator.

    ``rules`` maps a predicate over the prompt to a response; first match
    wins, else ``default``. Streams word-by-word with an optional delay to
    exercise real async interleaving in tests.
    """

    def __init__(
        self,
        default: str = "This is a canned response.",
        rules: list[tuple[Callable[[str], bool], str]] | None = None,
        chunk_delay: float = 0.0,
        fail_with: str | None = None,
    ):
        self.default = default
        self.rules = rules or []
        self.chunk_delay = chunk_delay
        self.fail_with = fail_with
        self.calls: list[str] = []  # prompts seen, for test assertions

    def _respond(self, prompt: str) -> str:
        for predicate, response in self.rules:
            if predicate(prompt):
                return response
        return self.default

    async def stream(
        self, prompt: str, sampling: SamplingParams,
        conversation_id: str | None = None,
        deadline: float | None = None,
        trace_id: str | None = None,
    ) -> AsyncIterator[str]:
        self.calls.append(prompt)
        if self.fail_with is not None:
            raise GenerationError(self.fail_with)
        response = self._respond(prompt)
        pieces = response.split(" ")
        for i, piece in enumerate(pieces):
            if self.chunk_delay:
                await asyncio.sleep(self.chunk_delay)
            yield piece + (" " if i < len(pieces) - 1 else "")

    async def generate(
        self, prompt: str, sampling: SamplingParams,
        conversation_id: str | None = None,
        deadline: float | None = None,
        trace_id: str | None = None,
    ) -> str:
        return "".join([piece async for piece in self.stream(prompt, sampling)])
