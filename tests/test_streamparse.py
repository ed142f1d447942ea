"""Tool-streaming plane (ISSUE 9): the incremental parser's event/commit
semantics, its split-point invariance against the serial parser, and the
ToolLauncher's speculative launch / cancel / adopt lifecycle."""

import asyncio
import random

import pytest

from finchat_tpu.agent.state import ToolCall
from finchat_tpu.agent.streamparse import (
    ArgComplete,
    CallComplete,
    NoToolComplete,
    ParseAnomaly,
    StreamingToolParser,
    ToolLauncher,
    ToolNameComplete,
    ToolResult,
    ToolStreamError,
)
from finchat_tpu.agent.toolcall import parse_tool_decision

VALID_RETRIEVE = (
    'retrieve_transactions({"search_query": "coffee shops", '
    '"num_transactions": 25, "time_period_days": 30})'
)
VALID_PLOT = (
    'create_financial_plot({"chart_type": "pie", "title": "Spending", '
    '"search_query": "all spending"})'
)


def feed_all(parser, text, pieces=None):
    events = []
    for piece in pieces if pieces is not None else [text]:
        events.extend(parser.feed(piece))
    return events


# --- event semantics ------------------------------------------------------

def test_valid_call_event_stream_and_commit_order():
    parser = StreamingToolParser()
    events = feed_all(parser, VALID_RETRIEVE, list(VALID_RETRIEVE))  # char-by-char
    kinds = [type(e).__name__ for e in events]
    assert kinds == [
        "ToolNameComplete", "ArgComplete", "ArgComplete", "ArgComplete",
        "CallComplete",
    ]
    assert events[0] == ToolNameComplete("retrieve_transactions")
    assert events[1] == ArgComplete("search_query", "coffee shops")
    assert events[2] == ArgComplete("num_transactions", 25)
    assert events[3] == ArgComplete("time_period_days", 30)
    final = parser.finish()
    assert final == events[-1].call
    assert final == parse_tool_decision(VALID_RETRIEVE)


def test_string_arg_commits_only_at_closing_quote():
    parser = StreamingToolParser()
    evs = parser.feed('retrieve_transactions({"search_query": "half a quer')
    assert not any(isinstance(e, ArgComplete) for e in evs)
    assert parser.launchable_call() is None  # arg not launch-safe yet
    evs = parser.feed("y")
    assert not any(isinstance(e, ArgComplete) for e in evs)
    evs = parser.feed('"')  # the commit point
    assert evs == [ArgComplete("search_query", "half a query")]
    call = parser.launchable_call()
    assert call is not None and call.args["search_query"] == "half a query"


def test_int_arg_commits_at_terminator():
    parser = StreamingToolParser()
    parser.feed('retrieve_transactions({"search_query": "x", "num_transactions": 41')
    assert parser.feed("2") == []  # still accumulating digits
    evs = parser.feed("}")  # terminator commits AND closes the object
    assert evs == [ArgComplete("num_transactions", 412)]
    assert isinstance(parser.feed(")")[0], CallComplete)


def test_no_tool_literal_and_anomaly():
    parser = StreamingToolParser()
    assert feed_all(parser, "No tool call") == [NoToolComplete()]
    assert parser.finish() is None

    parser = StreamingToolParser()
    events = feed_all(parser, "Sure! I will retrieve_transactions({})")
    assert len(events) == 1 and isinstance(events[0], ParseAnomaly)
    # the serial parser still decides (regex searches anywhere)
    assert parser.finish() == parse_tool_decision(
        "Sure! I will retrieve_transactions({})"
    )
    assert parser.feed("more") == []  # permanently disengaged


def test_launchable_requires_name_and_required_args():
    parser = StreamingToolParser()
    parser.feed("retrieve_transactions(")
    assert parser.launchable_call() is None  # search_query not committed
    parser.feed('{"num_transactions": 5, ')
    assert parser.launchable_call() is None
    parser.feed('"search_query": "rent"')
    call = parser.launchable_call()
    assert call.name == "retrieve_transactions"
    assert call.args["search_query"] == "rent"
    assert call.args["num_transactions"] == 5  # committed extras ride along


# --- split-point invariance fuzz (satellite) ------------------------------

CORPUS = [
    VALID_RETRIEVE,
    VALID_PLOT,
    'retrieve_transactions({})',
    'retrieve_transactions({"search_query": "café ümläut €99"})',
    'retrieve_transactions({"num_transactions": 10000})',
    'create_financial_plot({"chart_type": "bar", "title": "T"})',
    "No tool call",
    "No tool call.",  # trailing junk: off-grammar, still parses serially
    "no tool call",  # case drift: off-grammar, serial no-tool rule applies
    "",
    "   \n\t  ",
    "I don't know what you mean.",
    "Sure — retrieve_transactions is the tool I'd use",  # named, no parens
    'retrieve_transactions({"search_query": "a}b"})',  # regex/JSON quirk row
    'retrieve_transactions({"search_query": "unterminated',
    'retrieve_transactions({"search_query": "x", "num_transactions":',
    'retrieve_transactions({bad json})',
    'retrieve_transactions  ({"search_query": "x"})',  # ws the regex takes
    'create_financial_plot({"chart_type": "volcano"})',  # off-enum value
    'retrieve_transactions({"num_transactions": 007})',  # leading zeros
    'retrieve_transactions({"search_query": "x"}) trailing words',
    'ééé retrieve_transactions({"search_query": "x"})',
    # grammatical call whose value smuggles the no-tool literal: the
    # serial no-tool scan overrules the incremental CallComplete
    'retrieve_transactions({"search_query": "No tool call"})',
]


def chunkings(text, rng):
    yield [text]
    yield list(text)  # per-char (per-token SSE flush)
    for _ in range(4):  # random decode-burst splits, incl. mid-JSON-string
        if not text:
            yield []
            continue
        cuts = sorted(rng.sample(range(1, len(text) + 1), min(rng.randint(1, 7), len(text))))
        pieces, prev = [], 0
        for cut in cuts:
            pieces.append(text[prev:cut])
            prev = cut
        if prev < len(text):
            pieces.append(text[prev:])
        yield pieces


def test_split_point_invariance_against_serial_parser():
    """For every corpus text and every chunking (whole, per-char, random
    bursts), finish() must equal parse_tool_decision(text) and the event
    stream must be identical — the incremental plane may never let the
    chunk boundaries of a burst of tokens change the outcome."""
    rng = random.Random(9)
    for text in CORPUS:
        serial = parse_tool_decision(text)
        reference_events = None
        for pieces in chunkings(text, rng):
            parser = StreamingToolParser()
            events = feed_all(parser, text, pieces)
            assert parser.finish() == serial, (text, pieces)
            if reference_events is None:
                reference_events = events
            else:
                assert events == reference_events, (text, pieces)


def test_truncated_prefixes_never_complete_and_stay_serial_identical():
    for cut in range(len(VALID_RETRIEVE)):
        prefix = VALID_RETRIEVE[:cut]
        parser = StreamingToolParser()
        events = feed_all(parser, prefix, list(prefix))
        assert not any(isinstance(e, CallComplete) for e in events)
        assert parser.finish() == parse_tool_decision(prefix), prefix


# --- launcher lifecycle ---------------------------------------------------

class Recorder:
    """Execute seam double: records launches, optionally stalls so a
    later commit can invalidate an in-flight one."""

    def __init__(self, delay=0.0):
        self.delay = delay
        self.started: list[dict] = []
        self.finished: list[dict] = []
        self.cancelled: list[dict] = []

    async def __call__(self, call: ToolCall) -> ToolResult:
        self.started.append(call.args)
        try:
            if self.delay:
                await asyncio.sleep(self.delay)
        except asyncio.CancelledError:
            self.cancelled.append(call.args)
            raise
        self.finished.append(call.args)
        return ToolResult([f"rows for {call.args.get('search_query')}"])


def _drive(parser, launcher, text):
    for event in parser.feed(text):
        if isinstance(event, ParseAnomaly):
            launcher.abandon()
        elif isinstance(event, CallComplete):
            launcher.update(event.call)
        elif isinstance(event, ArgComplete):
            launcher.update(parser.launchable_call())


async def test_launcher_eager_launch_and_adoption():
    recorder = Recorder()
    parser = StreamingToolParser()
    launcher = ToolLauncher(recorder)
    _drive(parser, launcher, 'retrieve_transactions({"search_query": "rent"')
    await asyncio.sleep(0)  # let the launched task start
    assert len(recorder.started) == 1  # launched before ")" ever decodes
    _drive(parser, launcher, "})")
    launcher.mark_decode_done()
    final = parser.finish()
    result = await launcher.result_for(final)
    assert result.texts == ["rows for rent"]
    assert len(recorder.started) == 1  # adopted, not re-run


async def test_late_token_invalidates_eager_launch():
    """A later token committing a result-changing argument (the date
    window — NOT a refine key) cancels the in-flight speculative launch
    and relaunches — the acceptance-pinned invalidation path."""
    recorder = Recorder(delay=10.0)  # first launch can never finish in time
    parser = StreamingToolParser()
    launcher = ToolLauncher(recorder, refine=lambda result, call: result)
    _drive(parser, launcher, 'retrieve_transactions({"search_query": "rent", ')
    await asyncio.sleep(0)  # let the speculative task enter its sleep
    assert len(recorder.started) == 1
    _drive(parser, launcher, '"time_period_days": 3')
    await asyncio.sleep(0)
    assert len(recorder.started) == 1  # int not committed yet → no change
    _drive(parser, launcher, "0})")
    await asyncio.sleep(0)  # let the relaunched task start
    assert len(recorder.started) == 2  # relaunched with the refined args
    recorder.delay = 0.0
    launcher.mark_decode_done()
    result = await launcher.result_for(parser.finish())
    assert result.texts == ["rows for rent"]
    await asyncio.sleep(0)  # let the cancelled task unwind
    assert recorder.cancelled == [{"search_query": "rent"}]
    assert recorder.finished == [{"search_query": "rent", "time_period_days": 30}]


async def test_late_refine_key_keeps_launch_and_refines_at_adoption():
    """A late-committed REFINE KEY (num_transactions) must NOT cancel the
    in-flight launch: the adopter slices the speculative superset."""
    recorder = Recorder(delay=0.05)

    async def execute(call):
        recorder.started.append(call.args)
        await asyncio.sleep(0.05)
        recorder.finished.append(call.args)
        return ToolResult(["r1", "r2", "r3", "r4"])

    def refine(result, call):
        n = call.args.get("num_transactions")
        return ToolResult(result.texts[:n]) if n else result

    parser = StreamingToolParser()
    launcher = ToolLauncher(execute, refine=refine)
    _drive(parser, launcher, 'retrieve_transactions({"search_query": "rent", ')
    await asyncio.sleep(0)
    assert len(recorder.started) == 1
    _drive(parser, launcher, '"num_transactions": 2})')
    await asyncio.sleep(0)
    assert len(recorder.started) == 1  # refine key: launch survives
    launcher.mark_decode_done()
    result = await launcher.result_for(parser.finish())
    assert result.texts == ["r1", "r2"]  # superset sliced at adoption
    assert recorder.finished == [{"search_query": "rent"}]  # ran ONCE


async def test_launcher_mismatch_reruns_final_call():
    recorder = Recorder()
    launcher = ToolLauncher(recorder)
    launcher.update(ToolCall("retrieve_transactions", {"search_query": "a"}))
    await asyncio.sleep(0.01)
    final = ToolCall("retrieve_transactions", {"search_query": "b"})
    result = await launcher.result_for(final)
    assert result.texts == ["rows for b"]
    assert recorder.started == [{"search_query": "a"}, {"search_query": "b"}]


async def test_launcher_failure_is_structured_retryable():
    async def boom(call):
        raise RuntimeError("index down")

    launcher = ToolLauncher(boom)
    launcher.update(ToolCall("retrieve_transactions", {"search_query": "x"}))
    with pytest.raises(ToolStreamError) as exc:
        await launcher.result_for(ToolCall("retrieve_transactions", {"search_query": "x"}))
    # parity with the scheduler's structured error contract
    # (generator.GenerationError / io.schemas.error_chunk fields)
    assert exc.value.code == "tool_execute_failed"
    assert exc.value.retryable is True


async def test_abandon_cancels_without_adoption():
    recorder = Recorder(delay=10.0)
    launcher = ToolLauncher(recorder)
    launcher.update(ToolCall("retrieve_transactions", {"search_query": "x"}))
    await asyncio.sleep(0)
    launcher.abandon()
    await asyncio.sleep(0)
    assert recorder.cancelled == [{"search_query": "x"}]
    assert launcher.abandoned


async def test_refine_key_growing_via_duplicate_commit_relaunches():
    """Review regression: the grammar doesn't track used keys, so a
    duplicate-key decode can GROW num_transactions after the launch
    (n=5 → n=20). Refine can only slice down — the launcher must cancel
    and relaunch, never adopt the smaller speculative fetch."""
    recorder = Recorder()
    parser = StreamingToolParser()
    launcher = ToolLauncher(recorder, refine=lambda result, call: result)
    text = ('retrieve_transactions({"num_transactions": 5, '
            '"search_query": "coffee", "num_transactions": 20})')
    assert parse_tool_decision(text).args["num_transactions"] == 20  # last wins
    cut = text.index('"coffee"') + len('"coffee"')  # search_query committed
    _drive(parser, launcher, text[:cut])
    await asyncio.sleep(0)
    assert recorder.started == [{"search_query": "coffee", "num_transactions": 5}]
    _drive(parser, launcher, text[cut:])
    await asyncio.sleep(0)
    # the grown limit invalidated the n=5 launch
    assert len(recorder.started) == 2
    launcher.mark_decode_done()
    result = await launcher.result_for(parser.finish())
    assert result.texts == ["rows for coffee"]
    assert recorder.finished[-1]["num_transactions"] == 20


def test_refinable_direction_contract():
    from finchat_tpu.agent.streamparse import refinable
    base = ToolCall("retrieve_transactions", {"search_query": "x"})
    grown = ToolCall("retrieve_transactions",
                     {"search_query": "x", "num_transactions": 7})
    assert refinable(base, grown)  # absent in base: superset fetch, slice down
    assert refinable(grown, base) is False  # final wants the default 10k: can't grow 7
    tighter = ToolCall("retrieve_transactions",
                       {"search_query": "x", "num_transactions": 3})
    assert refinable(grown, tighter)  # 7 -> 3 slices down
    assert refinable(tighter, grown) is False  # 3 -> 7 would grow


async def test_settle_prefix_propagates_caller_cancellation():
    """Review regression: a client disconnect delivered while the agent
    awaits the prefix settle must CANCEL the turn, not be swallowed."""
    from finchat_tpu.agent.graph import LLMAgent
    from finchat_tpu.engine.generator import StubGenerator

    agent = LLMAgent(StubGenerator(), StubGenerator(), None, "s", "t")

    class NeverDone:
        async def hold(self):
            await asyncio.sleep(30)

    state = type("S", (), {"partial_prefill": None})()
    prefix_task = asyncio.ensure_future(NeverDone().hold())

    async def settle():
        await agent._settle_prefix(state, prefix_task, keep=True)
        return "not cancelled"

    outer = asyncio.ensure_future(settle())
    await asyncio.sleep(0.01)
    outer.cancel()
    with pytest.raises(asyncio.CancelledError):
        await outer
    await asyncio.sleep(0)
    assert prefix_task.cancelled()  # the in-flight hold task was reaped
