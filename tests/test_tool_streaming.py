"""Agent-level tool-streaming plane (ISSUE 9): eager launch during the
decision decode, byte-identical parity with the serial path, the
tool.execute fault fallback, and the early response-prefix hold."""

import asyncio
import time
import types

from finchat_tpu.agent.graph import LLMAgent
from finchat_tpu.engine.generator import StubGenerator
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.utils import faults
from finchat_tpu.utils.metrics import METRICS

SYSTEM = "You are Penny."
TOOL = "Decide retrieval."


class PacedToolGenerator(StubGenerator):
    """Word-paced decision decode that records when its stream ended —
    the boundary eager launches must beat."""

    def __init__(self, text, chunk_delay=0.01):
        super().__init__(default=text, chunk_delay=chunk_delay)
        self.stream_ended_at = None

    async def stream(self, *args, **kwargs):
        async for piece in super().stream(*args, **kwargs):
            yield piece
        self.stream_ended_at = time.perf_counter()


class TimedRetriever:
    def __init__(self, rows=("COFFEE $4",), delay=0.0):
        self.rows = list(rows)
        self.delay = delay
        self.calls = []
        self.called_at = []

    async def __call__(self, args):
        self.called_at.append(time.perf_counter())
        self.calls.append(dict(args))
        if self.delay:
            await asyncio.sleep(self.delay)
        return list(self.rows)


def make_agent(tool_text, retriever, response="Here is my advice.", **kw):
    return LLMAgent(
        PacedToolGenerator(tool_text), StubGenerator(default=response),
        retriever, SYSTEM, TOOL, today=lambda: "2026-08-03", **kw,
    )


async def test_tool_launches_before_decode_completes():
    tool_gen = PacedToolGenerator(
        'retrieve_transactions({"search_query": "coffee", '
        '"num_transactions": 5, "time_period_days": 30})',
        chunk_delay=0.02,
    )
    retriever = TimedRetriever(delay=0.01)
    agent = LLMAgent(tool_gen, StubGenerator(default="ok"), retriever,
                     SYSTEM, TOOL)
    saved0 = METRICS.snapshot().get("finchat_tool_overlap_saved_seconds_sum", 0.0)
    launches0 = METRICS.get("finchat_tool_launches_total")
    result = await agent.query("what did I spend on coffee?", "u1")
    assert result["retrieved_transactions_count"] == 1
    assert METRICS.get("finchat_tool_launches_total") - launches0 >= 1
    # the eager launch beat the end of the decision decode ...
    assert retriever.called_at[0] < tool_gen.stream_ended_at
    # ... and the overlap-saved histogram saw nonzero hidden tool time
    saved = METRICS.snapshot()["finchat_tool_overlap_saved_seconds_sum"] - saved0
    assert saved > 0.0
    # the adopted launch carried the FINAL validated args
    assert retriever.calls[-1]["search_query"] == "coffee"
    assert retriever.calls[-1]["num_transactions"] == 5
    assert retriever.calls[-1]["user_id"] == "u1"  # server-side injection


async def test_streaming_matches_serial_path_byte_identical():
    cases = [
        'retrieve_transactions({"search_query": "groceries", "num_transactions": 2})',
        "No tool call",
        'retrieve_transactions({bad json})',  # named-without-args rescue
        "I cannot help with that",  # off-grammar, no tool named
    ]
    for tool_text in cases:
        outcomes = {}
        for streaming in (False, True):
            retriever = TimedRetriever(rows=["t1", "t2"])
            agent = make_agent(tool_text, retriever, tool_streaming=streaming)
            result = await agent.query("spending?", "u7", "CTX", [])
            outcomes[streaming] = (
                result["response"],
                result["state"].retrieved_transactions,
                # speculation may run interim/subset executions, but the
                # data the answer sees and the injected identity must match
                retriever.calls[-1].get("user_id") if retriever.calls else None,
            )
        assert outcomes[True] == outcomes[False], tool_text


async def test_late_arg_commit_cancels_and_relaunches():
    """Acceptance pin: a late token invalidating an eagerly-launched
    argument (the date window changes WHICH rows score — not a refine
    key) cancels the speculative call; only the relaunch is adopted."""
    c0 = METRICS.get("finchat_tool_speculative_cancels_total")

    class UnblockOnSecond(TimedRetriever):
        async def __call__(self, args):
            self.called_at.append(time.perf_counter())
            self.calls.append(dict(args))
            if len(self.calls) > 1:
                return ["windowed row"]
            await asyncio.sleep(5.0)  # the stale launch can never finish
            return ["stale row"]

    retriever = UnblockOnSecond()
    agent = make_agent(
        'retrieve_transactions({"search_query": "rent", "time_period_days": 7})',
        retriever,
    )
    result = await agent.query("rent?", "u1")
    assert result["state"].retrieved_transactions == ["windowed row"]
    assert [c.get("time_period_days") for c in retriever.calls] == [None, 7]
    assert METRICS.get("finchat_tool_speculative_cancels_total") - c0 >= 1


async def test_late_refine_key_adopts_sliced_superset():
    """A late num_transactions commit refines (slices) the in-flight
    launch's result instead of relaunching — one retriever execution."""
    retriever = TimedRetriever(rows=["a", "b", "c"], delay=0.01)
    agent = make_agent(
        'retrieve_transactions({"search_query": "rent", "num_transactions": 2})',
        retriever,
    )
    result = await agent.query("rent?", "u1")
    assert result["state"].retrieved_transactions == ["a", "b"]
    assert len(retriever.calls) == 1  # launch survived the late commit
    assert "num_transactions" not in retriever.calls[0]  # speculative subset


async def test_tool_execute_fault_falls_back_to_serial_retry():
    """Satellite: an injected tool failure mid-decode (tool.execute site)
    degrades to the serial path — the answer is built from the retried
    serial execution, the fallback is counted, and the speculative error
    carries the structured retryable contract (pinned in
    test_streamparse.py::test_launcher_failure_is_structured_retryable)."""
    f0 = METRICS.get("finchat_tool_fallbacks_total")
    retriever = TimedRetriever(rows=["row A"])
    agent = make_agent(
        'retrieve_transactions({"search_query": "x"})', retriever,
        response="Answer.",
    )
    with faults.armed("tool.execute", faults.one_shot(RuntimeError("index down"))):
        result = await agent.query("spending?", "u1")
    assert result["response"] == "Answer."
    assert result["state"].retrieved_transactions == ["row A"]  # serial retry won
    assert METRICS.get("finchat_tool_fallbacks_total") - f0 >= 1


async def test_tool_execute_persistent_fault_degrades_like_serial():
    retriever = TimedRetriever()

    def always(**ctx):
        raise RuntimeError("index down")

    agent = make_agent('retrieve_transactions({"search_query": "x"})', retriever)
    with faults.armed("tool.execute", always):
        result = await agent.query("spending?", "u1")
    # both the speculative launch and the serial retry failed: the
    # reference degradation contract holds (Error marker, answer made)
    assert result["response"] == "Here is my advice."
    assert result["state"].retrieved_transactions == ["Error: index down"]


class FakePartialGenerator(StubGenerator):
    """Response-role double exposing the hold-park-graft seam, so the
    early-prefix behavior is testable without an engine."""

    def __init__(self):
        super().__init__(default="resp")
        self.begun = []
        self.released = []
        self.stream_partials = []

    async def begin_partial(self, prefix_text, sampling, conversation_id=None,
                            deadline=None):
        self.begun.append((prefix_text, time.perf_counter()))
        return types.SimpleNamespace(hold=len(self.begun))

    def release_partial(self, partial):
        # EngineGenerator contract: a hold the stream claimed is the
        # stream's to manage — release only unclaimed ones
        if not getattr(partial, "_partial_claimed", False):
            self.released.append(partial)

    async def stream(self, prompt, sampling, conversation_id=None,
                     deadline=None, partial=None):
        if partial is not None:
            partial._partial_claimed = True  # the EngineGenerator contract
        self.stream_partials.append(partial)
        async for piece in super().stream(prompt, sampling):
            yield piece

    async def generate(self, prompt, sampling, conversation_id=None,
                       deadline=None, partial=None):
        if partial is not None:
            partial._partial_claimed = True
        self.stream_partials.append(partial)
        return self.default


async def test_prefix_hold_taken_at_name_commit_and_consumed():
    tool_gen = PacedToolGenerator(
        'retrieve_transactions({"search_query": "coffee"})', chunk_delay=0.02,
    )
    resp = FakePartialGenerator()
    retriever = TimedRetriever()
    agent = LLMAgent(tool_gen, resp, retriever, SYSTEM, TOOL)
    result = await agent.query("coffee?", "u1")
    assert result["response"] == "resp"
    assert len(resp.begun) == 1
    # the hold was taken DURING the decision decode (at name-commit) ...
    assert resp.begun[0][1] < tool_gen.stream_ended_at
    # ... and handed to response generation, not leaked
    assert len(resp.stream_partials) == 1 and resp.stream_partials[0].hold == 1
    assert resp.released == []


async def test_prefix_hold_released_when_serial_parse_overrules():
    """Grammatical call whose string value smuggles the no-tool literal:
    the incremental plane commits a name (prefix hold taken, tool
    launched) but the AUTHORITATIVE serial parse refuses the turn — the
    no-tool scan wins in parse_tool_decision's first 80 chars. The plane
    must converge on the serial outcome: no retrieval, launch abandoned,
    hold released."""
    resp = FakePartialGenerator()
    retriever = TimedRetriever()
    agent = LLMAgent(
        PacedToolGenerator('retrieve_transactions({"search_query": "No tool call"})'),
        resp, retriever, SYSTEM, TOOL,
    )
    f0 = METRICS.get("finchat_tool_fallbacks_total")
    result = await agent.query("hello", "u1")
    assert result["retrieved_transactions_count"] == 0
    assert result["response"] == "resp"
    assert METRICS.get("finchat_tool_fallbacks_total") - f0 >= 1
    # the eagerly-taken hold was given back, none left claimed
    assert len(resp.begun) == 1
    assert len(resp.released) == 1
