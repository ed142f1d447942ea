"""Grammar-constrained tool-decision decoding (agent/constrained.py).

The few-shot call formats in prompts/tool_prompt.txt are acceptance cases
(SURVEY §7.3 hard part #5: they become test cases), and an end-to-end run
through the scheduler must ALWAYS yield parsable output even from a
random-weight model — the whole point of constraining.
"""

import asyncio

import jax
import numpy as np
import pytest

from finchat_tpu.agent.constrained import (
    DEAD,
    GrammarVocab,
    TokenConstraint,
    build_tool_grammar,
)
from finchat_tpu.agent.toolcall import parse_tool_decision
from finchat_tpu.models.tokenizer import ByteTokenizer


@pytest.fixture(scope="module")
def dfa():
    return build_tool_grammar()


def accepts(dfa, text: str) -> bool:
    state = dfa.step_string(dfa.start, text)
    return state != DEAD and dfa.eos_ok[state]


def is_live_prefix(dfa, text: str) -> bool:
    return dfa.step_string(dfa.start, text) != DEAD


@pytest.mark.parametrize(
    "text",
    [
        "No tool call",
        'retrieve_transactions({"search_query": "grocery store purchases", "num_transactions": 20})',
        'retrieve_transactions({"search_query": "all purchases", "time_period_days": 2})',
        "retrieve_transactions({})",
        'retrieve_transactions({"num_transactions": 100})',
        'retrieve_transactions({ "search_query" : "coffee" , "num_transactions" : 5 })',
        '  No tool call',  # leading whitespace tolerated
    ],
)
def test_grammar_accepts_valid_outputs(dfa, text):
    assert accepts(dfa, text)


@pytest.mark.parametrize(
    "text",
    [
        "Hello! I'm here to help",  # prose
        "no tool call",  # wrong case is not the literal contract
        "retrieve_transactions(",  # incomplete: not accepting (but live)
        'retrieve_transactions({"user_id": "u1"})',  # user_id is NOT grammatical
        'retrieve_transactions({"search_query": 5})',  # wrong value type
        'retrieve_transactions({"num_transactions": "many"})',
        "retrieve_transactions({}) extra",  # trailing junk
        'make_coffee({})',  # unknown tool
    ],
)
def test_grammar_rejects_invalid_outputs(dfa, text):
    assert not accepts(dfa, text)


def test_incomplete_prefixes_stay_live(dfa):
    for prefix in ["No to", "retrieve_trans", 'retrieve_transactions({"sea', 'retrieve_transactions({"num_transactions": 1']:
        assert is_live_prefix(dfa, prefix)


def test_every_accepted_output_parses():
    """Grammar ⊆ parser: anything the DFA accepts must produce a well-formed
    decision in toolcall.parse_tool_decision."""
    samples = [
        "No tool call",
        'retrieve_transactions({"search_query": "rent payments", "num_transactions": 3})',
        'retrieve_transactions({"time_period_days": 30})',
        "retrieve_transactions({})",
    ]
    dfa = build_tool_grammar()
    for text in samples:
        assert accepts(dfa, text)
        if text == "No tool call":
            assert parse_tool_decision(text) is None
        else:
            call = parse_tool_decision(text)
            assert call is not None and call.name == "retrieve_transactions"
            assert "user_id" not in call.args


def test_start_mask_byte_vocab():
    tok = ByteTokenizer()
    vocab = GrammarVocab.for_tokenizer(tok)
    allowed, eos_ok, _ = vocab.mask(vocab.dfa.start)
    assert not eos_ok  # empty output is not grammatical
    assert allowed[ord("N")] and allowed[ord("r")] and allowed[ord(" ")]
    assert not allowed[ord("H")] and not allowed[ord("{")]
    # specials carry no text and are never allowed
    assert not allowed[tok.pad_id] and not allowed[tok.bos_id]


def test_constrained_pick_greedy_forces_grammar():
    """Even with adversarial logits (all mass on junk), picks stay in-grammar
    and terminate; the result always parses."""
    tok = ByteTokenizer()
    vocab = GrammarVocab.for_tokenizer(tok)
    c = TokenConstraint(vocab)
    rng = np.random.default_rng(0)
    logits = np.zeros((tok.vocab_size,), np.float32)
    logits[ord("H")] = 100.0  # the model "wants" to say Hello
    out = []
    for _ in range(128):
        t = c.pick(logits, 0.0, rng)
        if t == tok.eos_id:
            break
        out.append(t)
    text = tok.decode(out)
    dfa = build_tool_grammar()
    assert accepts(dfa, text), text


def test_constrained_pick_model_head_wider_than_tokenizer():
    """tinyllama's 32,000-wide head under the 260-id byte tokenizer: the
    ids the tokenizer does not know are never picked, however much mass
    they hold, at any temperature."""
    tok = ByteTokenizer()
    vocab = GrammarVocab.for_tokenizer(tok)
    rng = np.random.default_rng(0)
    logits = np.zeros((32_000,), np.float32)
    logits[tok.vocab_size:] = 100.0
    for temperature in (0.0, 0.5):
        c = TokenConstraint(vocab)
        out = []
        for step in range(96):
            t = c.pick(logits, temperature, rng, remaining=96 - step)
            if t == tok.eos_id:
                break
            out.append(t)
        assert all(t < tok.vocab_size for t in out)
        assert accepts(build_tool_grammar(), tok.decode(out))


def test_constrained_sampling_terminates_and_parses():
    """Stochastic picks (temperature 1) across many seeds: always grammatical."""
    tok = ByteTokenizer()
    vocab = GrammarVocab.for_tokenizer(tok)
    dfa = vocab.dfa
    budget = 96  # tool_sampling's max_new_tokens: closing mode must land it
    for seed in range(5):
        rng = np.random.default_rng(seed)
        c = TokenConstraint(vocab)
        logits = np.asarray(rng.normal(size=(tok.vocab_size,)) * 3, np.float32)
        out = []
        for step in range(budget):
            t = c.pick(logits, 1.0, rng, remaining=budget - step)
            if t == tok.eos_id:
                break
            out.append(t)
        else:
            pytest.fail("did not terminate within budget")
        text = tok.decode(out)
        assert accepts(dfa, text), text
        parse_tool_decision(text)  # must not raise


async def _run_constrained_engine():
    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.generator import EngineGenerator
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.utils.config import EngineConfig

    tok = ByteTokenizer()
    config = PRESETS["tiny"]
    engine_cfg = EngineConfig(
        max_seqs=2, page_size=8, num_pages=64, max_seq_len=256, prefill_chunk=16
    )
    params = init_params(config, jax.random.key(0))
    engine = InferenceEngine(config, params, engine_cfg)
    scheduler = ContinuousBatchingScheduler(engine, eos_id=tok.eos_id)
    gen = EngineGenerator(scheduler, tok)
    await scheduler.start()
    try:
        text = await gen.generate(
            "User: What did I spend on coffee?",
            SamplingParams(temperature=0.7, max_new_tokens=96, grammar="tool_call"),
        )
    finally:
        await scheduler.stop()
    return text


def test_engine_constrained_generation_end_to_end():
    """A RANDOM-weight model through the real scheduler produces grammatical,
    parsable tool decisions — structure comes from the constraint alone."""
    text = asyncio.run(_run_constrained_engine())
    dfa = build_tool_grammar()
    state = dfa.step_string(dfa.start, text)
    # either completed (accepting) or hit the token budget mid-grammar (live)
    assert state != DEAD, text
    parse_tool_decision(text)  # never raises


def test_token_texts_sentencepiece_style():
    """decode([i]) strips the SentencePiece leading-space marker; token_texts
    must recover the real emitted text ('▁No' -> ' No') or the DFA diverges
    from the stream."""
    from finchat_tpu.agent.constrained import token_texts

    class FakeSPInner:
        all_special_ids = [0]

        def convert_ids_to_tokens(self, ids):
            table = {0: "<s>", 1: "▁No", 2: "▁tool", 3: "call", 4: "<0x7B>", 5: "to"}
            return [table[i] for i in ids]

    class FakeSPTokenizer:
        vocab_size = 6
        eos_id = 0
        _tok = FakeSPInner()

        def decode(self, ids):
            # single-token decode strips the marker — the trap
            return "".join(
                {0: "", 1: "No", 2: "tool", 3: "call", 4: "{", 5: "to"}[i] for i in ids
            )

    texts = token_texts(FakeSPTokenizer())
    assert texts == ["", " No", " tool", "call", "{", "to"]


def test_grammar_vocab_multitoken_literal_with_sp_texts():
    """With correct per-token texts, a multi-token path through the literal
    'No tool call' stays live and lands accepting."""
    from finchat_tpu.agent.constrained import GrammarVocab, build_tool_grammar

    vocab = GrammarVocab(build_tool_grammar(), ["", "No", " tool", " call", "xx"], eos_id=0)
    allowed, _, _ = vocab.mask(vocab.dfa.start)
    assert allowed[1] and not allowed[4] and not allowed[0]
    s = vocab.advance(vocab.dfa.start, 1)  # "No"
    allowed, _, _ = vocab.mask(s)
    assert allowed[2]  # " tool"
    s = vocab.advance(s, 2)
    s = vocab.advance(s, 3)  # " call"
    assert vocab.dfa.eos_ok[s]


def test_string_values_exclude_parser_breaking_chars():
    """Grammar ⊆ parser: '}' and ')' cannot appear inside string values
    (they would truncate toolcall.py's non-greedy extraction regex)."""
    dfa = build_tool_grammar()
    bad = 'retrieve_transactions({"search_query": "food} 2024"})'
    prefix = bad[: bad.index("}") + 1]  # up to and including the in-string '}'
    assert not is_live_prefix(dfa, prefix)


@pytest.mark.parametrize(
    "text",
    [
        'create_financial_plot({"chart_type": "bar", "title": "Spending This Month", "search_query": "all purchases", "time_period_days": 30})',
        'create_financial_plot({"chart_type": "pie"})',
        "create_financial_plot({})",
    ],
)
def test_grammar_accepts_plot_calls(dfa, text):
    assert accepts(dfa, text)


@pytest.mark.parametrize(
    "text",
    [
        'create_financial_plot({"chart_type": "donut"})',  # not in the enum
        'create_financial_plot({"chart_type": bar})',  # unquoted enum
    ],
)
def test_grammar_rejects_bad_plot_calls(dfa, text):
    assert not accepts(dfa, text)


def test_plot_call_parses_with_validation():
    call = parse_tool_decision(
        'create_financial_plot({"chart_type": "pie", "title": "Food", "num_transactions": 50})'
    )
    assert call is not None and call.name == "create_financial_plot"
    assert call.args["chart_type"] == "pie" and call.args["title"] == "Food"
    assert call.args["num_transactions"] == 50
    # bad chart type degrades to the default, never an error
    call = parse_tool_decision('create_financial_plot({"chart_type": "donut"})')
    assert call.args["chart_type"] == "bar"


def _pick_by_the_whole_vocabulary(c, logits, temperature, rng, remaining, top_p=1.0, top_k=0):
    """The pick as it was written before it was cut to the allowed ids: every
    pass over the whole vocabulary, ``rng.choice`` for the draw."""
    allowed, eos_ok, ends = c.vocab.mask(c.state)
    logits = logits[: allowed.shape[0]]
    feasible = allowed & (c.vocab._distance_np[ends] <= remaining - 2)
    if not (feasible.any() or eos_ok):
        return c.vocab.eos_id
    allowed = feasible.copy()
    if eos_ok:
        allowed[c.vocab.eos_id] = True
    if not allowed.any():
        return c.vocab.eos_id
    masked = np.where(allowed, logits.astype(np.float64), -np.inf)
    if temperature <= 0.0:
        return int(masked.argmax())
    z = masked / temperature
    if top_k and top_k > 0:
        z = np.where(z < np.partition(z, -top_k)[-top_k], -np.inf, z)
    if top_p < 1.0:
        order = np.argsort(-z)
        probs = np.exp(z[order] - z.max())
        probs /= probs.sum()
        keep = (np.cumsum(probs) - probs) < top_p
        keep[0] = True
        z[order[~keep]] = -np.inf
    p = np.exp(z - z.max())
    return int(rng.choice(len(p), p=p / p.sum()))


@pytest.mark.parametrize("temperature,top_p,top_k", [
    (0.0, 1.0, 0), (0.5, 1.0, 0), (1.0, 0.9, 0), (1.0, 1.0, 5), (0.7, 0.8, 40)])
def test_pick_over_the_allowed_ids_is_the_pick_over_the_vocabulary(temperature, top_p, top_k):
    """Cut to the allowed ids and drawn in two levels, a pick is the token the
    whole-vocabulary pick gives from the same generator, state by state along
    a whole decision, under a head wider than the tokenizer too."""
    tok = ByteTokenizer()
    vocab = GrammarVocab.for_tokenizer(tok)
    for seed in range(4):
        data = np.random.default_rng(100 + seed)
        rng_new, rng_old = np.random.default_rng(seed), np.random.default_rng(seed)
        c = TokenConstraint(vocab)
        for step in range(96):
            logits = np.asarray(data.normal(size=(tok.vocab_size + 40,)) * 3, np.float32)
            want = _pick_by_the_whole_vocabulary(
                c, logits, temperature, rng_old, 96 - step, top_p, top_k)
            got = c.pick(logits, temperature, rng_new, remaining=96 - step,
                         top_p=top_p, top_k=top_k)
            assert got == want, (seed, step)
            if got == tok.eos_id:
                break
        else:
            pytest.fail("did not terminate within budget")


@pytest.mark.parametrize("size", [1, 7, 512, 513, 5000])
def test_draw_is_the_index_choice_draws(size):
    from finchat_tpu.agent.constrained import _draw

    rng = np.random.default_rng(size)
    for _ in range(50):
        w = rng.random(size) * (rng.random(size) < 0.6)  # zero weights among them
        if not w.any():
            w[size // 2] = 1.0
        u = rng.random()
        cdf = np.cumsum(w)
        want = int((cdf / cdf[-1]).searchsorted(u, side="right"))
        got = _draw(w, u)
        assert w[got] > 0.0
        assert got == want
    assert _draw(np.asarray([0.0, 2.0, 0.0]), 0.999999) == 1
