"""The ``sessions`` generator is a pure function of seed and parameters; the
percentile and gap arithmetic on a hand-made chunk log."""

import math

import pytest

from perfbench import reduce
from perfbench.cells import load_traffic
from perfbench.load import RequestLog
from perfbench.traffic_kinds import sessions


# ISSUE 23's advisor mix (an open loop over sessions), as a later PR would
# put it in a traffic file of its own
ADVISOR = {
    "kind": "sessions", "shape_seed": 20260927,
    "arrival": {"process": "poisson", "rate_per_s": 1.0},
    "turns": {"mean": 2.2, "max": 4},
    "think_s": {"median": 6.0, "sigma": 0.6, "cap": 15.0},
    "history": {"share": 0.4, "messages": [2, 8], "bytes": [100, 600]},
    "message_bytes": {"median": 80, "p99": 400, "min": 12, "max": 600},
    "users": 64, "rows_per_user": [20, 60], "row_bytes": [40, 80],
    "answer_cap": 128, "ingress": "kafka",
}


@pytest.mark.parametrize("name", ["advisor", "rehearsal-advisor", "report-backlog",
                                  "rehearsal-backlog"])
def test_sessions_is_pure_in_seed_and_parameters(name):
    params = ADVISOR if name == "advisor" else load_traffic(name)
    a = sessions.generate(params, 2 ** 31 + 11, 90.0)
    assert a == sessions.generate(dict(params), 2 ** 31 + 11, 90.0)
    b = sessions.generate(params, 12, 90.0)
    assert [u.rows for u in a.users] != [u.rows for u in b.users]
    assert a.answer_cap == params["answer_cap"] and a.sessions
    assert all(s.arrival_s <= t.arrival_s for s, t in zip(a.sessions, a.sessions[1:]))


def test_seeds_offer_the_same_work_in_another_order():
    params = ADVISOR
    a = sessions.generate(params, 1, 120.0, phases=(18.0, 69.0))
    b = sessions.generate(params, 2, 120.0, phases=(18.0, 69.0))
    # rows per user and their lengths come from the shape seed alone
    assert [[len(r["text"]) for r in u.rows] for u in a.users] == \
           [[len(r["text"]) for r in u.rows] for u in b.users]
    # the arrival instants are the same, and each phase (before, inside and
    # after the window) gets the same session shapes in another order
    assert [s.arrival_s for s in a.sessions] == [s.arrival_s for s in b.sessions]

    def shape(s):
        return (len(s.turns), tuple(len(t.message) for t in s.turns),
                tuple(round(t.think_s, 6) for t in s.turns), len(s.history))
    for lo, hi in ((0.0, 18.0), (18.0, 69.0), (69.0, 120.0)):
        sa = [shape(s) for s in a.sessions if lo <= s.arrival_s < hi]
        sb = [shape(s) for s in b.sessions if lo <= s.arrival_s < hi]
        assert sorted(sa) == sorted(sb) and len(sa) > 0
    window = [shape(s) for s in a.sessions if 18.0 <= s.arrival_s < 69.0]
    assert window != [shape(s) for s in b.sessions if 18.0 <= s.arrival_s < 69.0]


def test_advisor_parameters_hold():
    t = sessions.generate(ADVISOR, 5, 600.0)
    turns = [len(s.turns) for s in t.sessions]
    assert 1 <= min(turns) and max(turns) <= 4 and 1.6 < sum(turns) / len(turns) < 2.6
    share = sum(1 for s in t.sessions if s.history) / len(t.sessions)
    assert 0.3 < share < 0.5
    assert all(2 <= len(s.history) <= 8 for s in t.sessions if s.history)
    assert all(t_.think_s <= 15.0 for s in t.sessions for t_ in s.turns)
    assert len(t.users) == 64 and all(20 <= len(u.rows) <= 60 for u in t.users)
    assert all(40 <= len(r["text"]) <= 80 for u in t.users for r in u.rows)


def test_backlog_offers_every_report_at_once():
    params = load_traffic("report-backlog")
    t = sessions.generate(params, 3, 90.0)
    assert len(t.sessions) == 64 and {s.arrival_s for s in t.sessions} == {0.0}
    assert all(len(s.turns) == 1 and not s.history for s in t.sessions)
    assert t.answer_cap == 8192
    # one report per client, in the clients' order: every seed's first batch
    # is the same users with the same number of rows
    assert [s.user_id for s in t.sessions] == [u.user_id for u in t.users]
    u = sessions.generate(params, 4, 90.0)
    assert [len(x.rows) for x in t.users] == [len(x.rows) for x in u.users]


@pytest.mark.parametrize("values,q,want", [
    ([1.0], 95, 1.0), ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5),
    ([10, 20, 30, 40, 50, 60, 70, 80, 90, 100, 110], 90, 100.0),
    ([1, 2, math.inf], 50, 2.0), ([1, 2, math.inf], 90, math.inf),
])
def test_percentile_is_numpys_linear_method(values, q, want):
    assert reduce.percentile(values, q) == pytest.approx(want)


def _log(i, due, first, n, gap, ended="complete"):
    r = RequestLog(f"m{i}", f"s{i}", due=due, sent=due + 0.001)
    r.chunk_times = [due + first + k * gap for k in range(n)]
    r.done, r.ended = (due + first + n * gap, ended) if ended else (None, None)
    return r


def test_latency_is_anchored_at_the_due_instant_and_failures_miss_every_limit():
    cap = 10
    logs = [_log(i, due=100.0 + i, first=0.5 + 0.1 * i, n=cap, gap=0.02) for i in range(9)]
    logs.append(_log(9, due=109.0, first=0.5, n=cap, gap=0.02, ended="error"))
    logs.append(_log(10, due=99.0, first=0.5, n=cap, gap=0.02))   # due before the window
    logs.append(_log(11, due=111.0, first=0.5, n=cap, gap=0.02))  # due after it
    out = reduce.end_to_end(logs, 100.0, 110.0, answer_cap=cap, backlog=False, vocab=10 ** 9)
    assert (out["attempted"], out["failed"]) == (10, 1)
    assert out["metrics"]["ttft_p50_ms"] == pytest.approx(950.0)  # median of 500..1300 and inf
    assert out["metrics"]["ttft_p90_ms"] == math.inf or out["metrics"]["ttft_p90_ms"] > 1300
    assert out["n_gaps"] == 9 * (cap - 1) + 1  # the failed request adds one +inf gap
    assert out["metrics"]["token_gap_p95_ms"] == pytest.approx(20.0)
    # the generator's lateness is sent - due, over the requests due in the window
    assert reduce.gen_lag_ms(logs, 100.0, 110.0) == pytest.approx([1.0] * 10)


def test_short_answers_are_excused_as_sampled_eos_only_while_rare():
    cap = 100
    ok = [_log(i, 100.0 + 0.01 * i, 0.5, cap, 0.01) for i in range(97)]
    short = [_log(100 + i, 100.5, 0.5, 40, 0.01) for i in range(3)]
    few = reduce.end_to_end(ok + short[:2], 100, 110, answer_cap=cap, backlog=False, vocab=10 ** 9)
    assert (few["short_answers"], few["failed"]) == (2, 0)
    many = reduce.end_to_end(ok + short, 100, 110, answer_cap=cap, backlog=False, vocab=10 ** 9)
    assert (many["short_answers"], many["failed"]) == (3, 3)
    torn = reduce.verdict(_log(1, 100, 0.5, 95, 0.01), cap)  # the held-back torn bytes
    assert torn == "ok" and reduce.verdict(_log(1, 100, 0.5, 101, 0.01), cap) == "overlong"


def test_backlog_counts_what_the_window_saw_and_tokens_that_arrived_in_it():
    cap = 10
    logs = [_log(i, due=0.0, first=100.0 + i, n=cap, gap=0.1) for i in range(5)]
    out = reduce.end_to_end(logs, 100.0, 103.0, answer_cap=cap, backlog=True, vocab=10 ** 9)
    # the first two end inside the window, the third's chunks all arrive in
    # it and it ends at its close; the others start after it
    assert (out["attempted"], out["failed"]) == (3, 0)
    assert out["tokens_in_window"] == 10 + 10 + 10
    assert out["metrics"]["output_tok_s"] == pytest.approx(10.0)


def test_a_stream_thinner_than_the_batch_is_short_and_an_error_fails():
    cap = 1000
    # four answers stream all through the window 100..110, one token a round
    logs = [_log(i, due=0.0, first=90.0, n=400, gap=0.1, ended=None) for i in range(4)]
    stalled = _log(4, due=0.0, first=90.0, n=400, gap=0.1, ended=None)
    stalled.chunk_times = [t for t in stalled.chunk_times if not 103.0 <= t < 107.0]
    newcomer = _log(5, due=0.0, first=108.0, n=30, gap=0.1, ended=None)  # admitted late: fine
    eos = _log(6, due=0.0, first=90.0, n=150, gap=0.1)       # ended early inside: a sampled EOS
    out = reduce.end_to_end(logs + [newcomer, eos], 100.0, 110.0, answer_cap=cap,
                            backlog=True, vocab=10 ** 9)
    assert (out["attempted"], out["failed"], out["short_answers"]) == (6, 0, 1)
    out = reduce.end_to_end(logs + [stalled, stalled, stalled], 100.0, 110.0,
                            answer_cap=cap, backlog=True, vocab=10 ** 9)
    assert (out["attempted"], out["failed"]) == (7, 3)       # beyond what EOS excuses
    err = _log(7, due=0.0, first=90.0, n=150, gap=0.1, ended="error")
    out = reduce.end_to_end(logs + [err], 100.0, 110.0, answer_cap=cap, backlog=True,
                            vocab=10 ** 9)
    assert (out["attempted"], out["failed"]) == (5, 1)
