"""The one way a decode round is dispatched — ``_dispatch_decode`` a step
ahead of the consume, the ragged round when a prompt rides beside decoding
rows — over every kind of per-row memory the engine has.

A step ahead means that when a row ends (EOS, its budget, a cancel, a
preemption) a step that carries it is already on the device: it writes the
row's state, ring and pages once more, its window pages have slid on the
host, and the slot goes to the next tenant behind it. Each case below serves
a small mix through two slots and compares every stream, byte for byte, with
the same request served ALONE by a fresh scheduler (nothing beside it, no
tenant before it): what a dead step, a held-out row or a change of path left
behind would show as a different token. Each ends with nothing leaked: pages,
window pages, slots (the leak sanitizer audits the schedulers besides).

The cases are ``async def``: conftest runs them on the stall sanitizer's loop
(no callback may block a second), so each kind's programs are compiled by a
fixture first — ``warmup`` and the reference runs — outside that loop.
"""

import asyncio
import functools

import numpy as np
import pytest
import tiny_models

from finchat_tpu.engine.engine import InferenceEngine
from finchat_tpu.engine.sampler import SamplingParams
from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
from finchat_tpu.utils.config import EngineConfig

# one tiny configuration for each kind of per-row memory
KINDS = {
    "paged_kv": "tiny",
    "mamba2_state_beside_attention": "falcon_h1",
    "delta_rule_state": "olmo_hybrid",
    "mamba_layers_and_held_experts": "granite_hybrid",
    "latent_and_index_pages": "deepseek_v32",
    "window_ring_mamba1_and_one_shared_cache": "phi4_flash",
}
# Granite's block ties its head to its embeddings, and at this size the tied
# head echoes the last token whatever the state holds: a stream that could
# show nothing. With a head of its own it streams what its state makes of it
CHANGES = {"granite_hybrid": {"tie_word_embeddings": False}}
SLOTS = 2
pytestmark = pytest.mark.parametrize("kind", list(KINDS.values()), ids=list(KINDS))


def _model(kind):
    return tiny_models.build(kind, **CHANGES.get(kind, {}))


def _scheduler(kind, eos_id=-1):
    config, params = _model(kind)
    page, chunk, _ = tiny_models.SHAPES[kind]
    cfg = EngineConfig(max_seqs=SLOTS, page_size=page, num_pages=128, max_seq_len=256,
                       prefill_chunk=chunk)
    return ContinuousBatchingScheduler(
        InferenceEngine(config, params, cfg, attn_backend="ref"), eos_id=eos_id)


def _prompt(kind, n, seed):
    vocab = _model(kind)[0].vocab_size
    return [int(t) for t in np.random.RandomState(seed).randint(1, vocab, size=n)]


async def _drain(handle):
    tokens = []
    while True:
        event = await asyncio.wait_for(handle.events.get(), timeout=120)
        if event["type"] == "token":
            tokens.append(event["token_id"])
        elif event["type"] == "done":
            assert handle.events.empty()
            handle.ended = event["reason"]
            return tokens
        else:
            raise AssertionError(event)


async def _serve(sched, requests, handles=None, **submit):
    """Submit ``{name: (prompt, n_new)}`` in order, all at once; every handle
    (into ``handles``, as each is made, where a spy reads them) and stream."""
    handles = {} if handles is None else handles
    for name, (prompt, n_new) in requests.items():
        handles[name] = await sched.submit(name, prompt, _greedy(n_new), **submit.get(name, {}))
    streams = await asyncio.gather(*(_drain(h) for h in handles.values()))
    return handles, dict(zip(handles, streams))


def _greedy(n_new):
    return SamplingParams(temperature=0.0, max_new_tokens=n_new)


class Refs:
    """A kind's requests and what each streams served alone."""

    def __init__(self, kind):
        _page, chunk, _ = tiny_models.SHAPES[kind]
        self.requests = {
            "a": (_prompt(kind, 9, 1), 16),
            "b": (_prompt(kind, 14, 2), 22),
            "c": (_prompt(kind, 2 * chunk + 3, 3), 10),  # three chunks: the last is short
            "long": (_prompt(kind, 3 * chunk + 5, 4), 6),
            "d": (_prompt(kind, 5, 5), 7),
        }
        self.alone = {}
        _scheduler(kind).engine.warmup()

        async def go():
            for name, (prompt, n_new) in self.requests.items():
                sched = _scheduler(kind)
                await sched.start()
                try:
                    _, streams = await _serve(sched, {name: (prompt, n_new)})
                finally:
                    await sched.stop()
                assert len(streams[name]) == n_new
                self.alone[name] = streams[name]

        asyncio.run(go())

    def take(self, *names, **budgets):
        """The named requests, a budget replaced where ``budgets`` says."""
        return {n: (self.requests[n][0], budgets.get(n, self.requests[n][1])) for n in names}


@functools.cache
def _refs(kind) -> Refs:
    return Refs(kind)


@pytest.fixture
def refs(kind) -> Refs:
    return _refs(kind)


def _nothing_leaked(sched):
    sched.allocator.check_invariants()
    assert sched.allocator.used_count == 0
    assert sorted(sched.free_slots) == list(range(SLOTS))
    assert not sched.decoding and not sched.prefilling and not sched.pending
    pager = sched.engine.window_pager
    if pager is not None:  # no head is registered: every window page is back
        assert pager.pages_in_use == 0


def _tenants(sched):
    """Record, at every decode dispatch, who holds each slot that rides it:
    ``[{slot: seq_id}]``."""
    seen = []
    real = sched.engine.decode

    def decode(active, *args, **kw):
        live = np.flatnonzero(np.asarray(active))
        seen.append({int(s): sched.decoding[int(s)].seq_id for s in live if int(s) in sched.decoding})
        return real(active, *args, **kw)

    sched.engine.decode = decode
    return seen


def _stale_at_consume(sched):
    """Record the rows that had ended by the time a step carrying them was
    consumed: the steps that ran ahead of a row's end."""
    stale = []
    real = sched._consume_step

    async def consume(step):
        stale.extend(h.seq_id for _slot, h, _epoch in step.members if h.finished or h.slot < 0)
        await real(step)

    sched._consume_step = consume
    return stale


def _after_a_dispatch(sched, when, act):
    """Run ``act`` right after the first decode dispatch for which ``when()``
    holds: that step is on the device and nothing has consumed it."""
    real = sched._dispatch_decode
    fired = []

    def dispatch(*args, **kw):
        step = real(*args, **kw)
        if not fired and when():
            fired.append(step)
            act()
        return step

    sched._dispatch_decode = dispatch
    return fired


async def _run(sched, body):
    await sched.start()
    try:
        return await body()
    finally:
        await sched.stop()


# --- (i) a sampled EOS under a step in flight --------------------------------------

async def test_a_row_that_samples_eos_leaves_nothing_to_its_slots_next_tenant(kind, refs):
    """``a`` samples EOS while the step dispatched ahead still carries it: that
    step writes ``a``'s state, ring and pages once more. ``c`` waits for a slot,
    takes ``a``'s, and streams what it streams alone; so does ``b`` beside them."""
    a, others = refs.alone["a"], set(refs.alone["b"]) | set(refs.alone["c"])
    at = next(k for k in range(3, len(a)) if a[k] not in a[:k] and a[k] not in others)
    sched = _scheduler(kind, eos_id=a[at])
    tenants, stale = _tenants(sched), _stale_at_consume(sched)

    _, streams = await _run(sched, lambda: _serve(sched, refs.take("a", "b", "c")))

    assert streams == {"a": a[:at], "b": refs.alone["b"], "c": refs.alone["c"]}
    assert "a" in stale, "no step ran ahead of the row's EOS"
    slot = next(s for ride in tenants for s, who in ride.items() if who == "a")
    assert any(ride.get(slot) == "c" for ride in tenants), "the slot had no next tenant"
    _nothing_leaked(sched)


# --- (ii) a cancel between dispatch and consume ------------------------------------

async def test_a_cancel_between_dispatch_and_consume_frees_everything(kind, refs):
    """``a`` is cancelled right after a decode step carrying it was dispatched:
    pages, window pages and the slot are free before that step is consumed,
    ``c`` takes the slot, and the survivors stream what they stream alone."""
    sched = _scheduler(kind)
    handles = {}
    stale = _stale_at_consume(sched)
    fired = _after_a_dispatch(
        sched, lambda: "a" in handles and handles["a"].generated >= 3 and handles["a"].slot >= 0,
        lambda: sched.cancel(handles["a"]))

    _, streams = await _run(
        sched, lambda: _serve(sched, refs.take("a", "b", "c", a=64), handles))

    assert fired and "a" in stale
    assert handles["a"].ended == "cancelled"
    assert streams["a"] == refs.alone["a"][:len(streams["a"])] and len(streams["a"]) < 16
    assert streams["b"] == refs.alone["b"] and streams["c"] == refs.alone["c"]
    _nothing_leaked(sched)


# --- (iii) the budget reached on the step ahead ------------------------------------

async def test_budgets_end_on_the_step_ahead_with_exact_counts(kind, refs):
    """``a`` ends on a budget that fills its last page to the last token while a
    step ahead carries it; ``d`` ends on 3 beside it, ``b`` and ``c`` behind them: every stream has exactly its budget, no token of a dead step reaches
    one, and no live row beside a dead one moves."""
    page = tiny_models.SHAPES[kind][0]
    fill = page - len(refs.requests["a"][0]) % page  # prompt + answer: whole pages
    fill += page if fill < 4 else 0
    budgets = {"a": fill, "d": 3, "b": 9, "c": 10}
    sched = _scheduler(kind)
    stale = _stale_at_consume(sched)

    handles, streams = await _run(
        sched, lambda: _serve(sched, refs.take("a", "d", "b", "c", **budgets)))

    assert streams == {name: refs.alone[name][:n] for name, n in budgets.items()}
    assert {h.ended for h in handles.values()} == {"length"}
    assert "a" in stale and len(set(stale)) >= 2, stale
    assert (len(refs.requests["a"][0]) + fill) % page == 0
    _nothing_leaked(sched)


# --- (iv) a constrained slot held out of the step ahead ----------------------------

class _Argmax:
    """A constraint that allows every token and picks the likeliest: the row
    streams its greedy stream, through the host pick's path."""

    def pick(self, row_logits, temperature, rng, remaining, top_p=1.0, top_k=0):
        return int(np.argmax(np.asarray(row_logits)))


async def test_a_constrained_slot_held_out_of_the_step_ahead_rejoins_where_it_was(kind, refs):
    """``a``'s next token is picked on the host when its step is consumed, so it
    sits out the step dispatched before that and rides the next: a step it is
    held out of must not move its context, its state or its ring. Picking the
    argmax, it streams its greedy stream; ``b`` rides every step."""
    sched = _scheduler(kind)
    tenants = _tenants(sched)

    _, streams = await _run(sched, lambda: _serve(
        sched, refs.take("a", "b"), a={"constraint": _Argmax()}))

    assert streams == {"a": refs.alone["a"], "b": refs.alone["b"]}
    both = [i for i, ride in enumerate(tenants) if len(ride) == 2]
    assert both, "the constrained row never rode a step with its bystander"
    held_out = [ride for ride in tenants[:both[-1]] if list(ride.values()) == ["b"]]
    assert held_out, "the constrained row was never held out of a step"
    _nothing_leaked(sched)


# --- (v) admission in waves through the ragged round -------------------------------

async def test_waves_admitted_through_the_ragged_round_end_leak_free(kind, refs):
    """Five requests through two slots: each later one is admitted when a row
    ends, and its prompt's chunks ride ragged rounds beside the row still
    decoding. Every stream is what it is alone, and nothing is left."""
    sched = _scheduler(kind)
    rounds = []
    real = sched.engine.ragged_round

    def ragged_round(tokens, tok_row, row_slot, row_start, row_len, row_from_device, *rest):
        rounds.append(int((np.asarray(row_len)[~np.asarray(row_from_device)] > 0).sum()))
        return real(tokens, tok_row, row_slot, row_start, row_len, row_from_device, *rest)

    sched.engine.ragged_round = ragged_round

    _, streams = await _run(sched, lambda: _serve(sched, refs.take("a", "b", "c", "long", "d")))

    assert streams == refs.alone
    assert sum(1 for n in rounds if n) >= 5, "the later prompts did not ride ragged rounds"
    _nothing_leaked(sched)


# --- (vi) a preemption under a step in flight --------------------------------------

async def test_a_row_preempted_under_a_step_in_flight_replays_exactly_once(kind, refs):
    """``a`` is preempted right after a decode step carrying it was dispatched:
    its slot and pages go, the tokens of the steps in flight are discarded at
    consume, and the replay prefills prompt and delivered tokens again — from a
    clean state — so its stream has no token twice and none missing."""
    sched = _scheduler(kind)
    handles = {}
    stale = _stale_at_consume(sched)
    fired = _after_a_dispatch(
        sched, lambda: "a" in handles and handles["a"].generated >= 4 and handles["a"].slot >= 0,
        lambda: sched._preempt(handles["a"]))

    _, streams = await _run(sched, lambda: _serve(sched, refs.take("a", "b"), handles))

    assert fired and "a" in stale and handles["a"].preempted == 1
    assert streams == {"a": refs.alone["a"], "b": refs.alone["b"]}
    _nothing_leaked(sched)


# --- (vii) a prompt that starts in ragged rounds and ends in prefill rounds --------

async def test_a_prompt_begun_in_ragged_rounds_ends_in_prefill_rounds(kind, refs):
    """The only decoding row is cancelled after ``long``'s first chunk rode a
    ragged round: no decode is left to ride with, and the rest of the prompt
    goes through ``prefill_step``. The row's state, ring and pages carry over
    from one program to the other, and it streams what it streams alone."""
    sched = _scheduler(kind)
    handles, at = {}, {}
    ragged, prefill = sched._ragged_round, sched._prefill_round

    async def ragged_round():
        await ragged()
        long = handles.get("long")
        if long is not None and not at and 0 < long.prefill_pos < len(long.prompt_ids):
            at["pos"] = long.prefill_pos
            sched.cancel(handles["a"])

    async def prefill_round():
        if at:
            at["prefill_rounds"] = at.get("prefill_rounds", 0) + 1
        await prefill()

    sched._ragged_round, sched._prefill_round = ragged_round, prefill_round

    async def body():
        handles["a"] = await sched.submit("a", refs.requests["a"][0], _greedy(64))
        first = asyncio.create_task(_drain(handles["a"]))
        while handles["a"].generated < 2:
            await asyncio.sleep(0.001)
        prompt, n_new = refs.requests["long"]
        handles["long"] = await sched.submit("long", prompt, _greedy(n_new))
        return await asyncio.gather(first, _drain(handles["long"]))

    _, long_stream = await _run(sched, body)

    assert at.get("prefill_rounds", 0) >= 1, at
    assert long_stream == refs.alone["long"]
    _nothing_leaked(sched)
