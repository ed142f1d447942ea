"""From the client's chunk log to the end-to-end metrics.

Which requests count: in an open-loop (paced) cell, those *due* inside the
window; in a ``backlog`` (saturated) cell, where every request is due at time
zero, those the window *saw*: an answer chunk arrived or the request ended
inside it. A counted request that ended in an error chunk, never ended within
the drain limit (open loop), or lost tokens is failed, and a failed request
misses every latency limit: it enters a latency percentile as +inf (so a run
with more failures than the percentile leaves room for reports inf, which is
no number, and is not correct).
"""

from __future__ import annotations

import math

# an answer runs to the cap (random weights never stop), less the rare byte
# the incremental decoder holds back as a torn UTF-8 sequence
MIN_CHUNK_SHARE = 0.95
EOS_ROOM = 4


def percentile(values: list[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between closest
    ranks (numpy's default method); +inf entries sort last."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    if pos == lo:
        return xs[lo]
    if xs[hi] == math.inf:
        return math.inf
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def chunks_in(r, w0: float, w1: float) -> int:
    return sum(1 for t in r.chunk_times if w0 <= t < w1)


def counted(requests, w0: float, w1: float, *, backlog: bool):
    if backlog:
        return [r for r in requests
                if chunks_in(r, w0, w1) or (r.done is not None and w0 <= r.done < w1)]
    return [r for r in requests if w0 <= r.due < w1]


def backlog_verdicts(window, w0: float, w1: float, answer_cap: int) -> list[str]:
    """Verdicts in a backlog cell. A request that ended inside the window is
    judged as any other (``verdict``). One still streaming at its close is
    ``ok``, unless it streamed all through the window and received under
    ``MIN_CHUNK_SHARE`` of what the fullest such stream did: every row of the
    batch gets one token a round, so a thinner stream stalled or lost tokens
    (``short``)."""
    through = [r for r in window if r.ended is None and r.chunk_times
               and r.chunk_times[0] < w0]
    full = max((chunks_in(r, w0, w1) for r in through), default=0)
    through_ids = {id(r) for r in through}
    out = []
    for r in window:
        if r.ended is not None and r.done < w1:
            out.append(verdict(r, answer_cap))
        elif id(r) in through_ids and chunks_in(r, w0, w1) < MIN_CHUNK_SHARE * full:
            out.append("short")
        else:
            out.append("ok")
    return out


def verdict(r, answer_cap: int) -> str:
    """``ok`` | ``short`` (complete, fewer chunks than the cap allows for: a
    sampled EOS or lost tokens) | ``error`` | ``unfinished`` | ``overlong``."""
    if r.ended is None:
        return "unfinished"
    if r.ended != "complete":
        return "error"
    if len(r.chunk_times) > answer_cap:
        return "overlong"
    if len(r.chunk_times) < math.ceil(MIN_CHUNK_SHARE * answer_cap):
        return "short"
    return "ok"


def end_to_end(requests, w0: float, w1: float, *, answer_cap: int, backlog: bool,
               vocab: int) -> dict:
    """Every end-to-end quantity the chunk log gives, by metric name, plus the
    counts. A sampled EOS (one id of the vocabulary) ends an answer early and
    legitimately, and the wire cannot tell it from lost tokens; so ``short``
    answers are excused while they are no more than ``EOS_ROOM`` times what
    the counted requests' tokens make likely (one in ``vocab`` each; at least
    2 are always allowed), and ALL count as failed beyond that — systematic
    loss is far above the EOS rate."""
    window = counted(requests, w0, w1, backlog=backlog)
    verdicts = (backlog_verdicts(window, w0, w1, answer_cap) if backlog
                else [verdict(r, answer_cap) for r in window])
    n_short = verdicts.count("short")
    likely_eos = sum(len(r.chunk_times) for r in window) / vocab
    excused = n_short <= max(2, math.ceil(EOS_ROOM * likely_eos))
    failed = [v not in ("ok",) and not (v == "short" and excused) for v in verdicts]
    ttft, gaps = [], []
    for r, bad in zip(window, failed):
        if bad or not r.chunk_times:
            ttft.append(math.inf)  # a failed request misses every limit
            gaps.append(math.inf)
            continue
        ttft.append((r.chunk_times[0] - r.due) * 1e3)
        gaps.extend((b - a) * 1e3 for a, b in zip(r.chunk_times, r.chunk_times[1:]))
    tokens_in_window = sum(chunks_in(r, w0, w1) for r in requests)
    out = {
        "attempted": len(window), "failed": sum(failed), "short_answers": n_short,
        "verdicts": {v: verdicts.count(v) for v in sorted(set(verdicts))},
        "n_gaps": len(gaps), "tokens_in_window": tokens_in_window,
        "metrics": {"output_tok_s": tokens_in_window / (w1 - w0)},
    }
    if ttft:
        out["metrics"]["ttft_p50_ms"] = percentile(ttft, 50)
        out["metrics"]["ttft_p90_ms"] = percentile(ttft, 90)
    if gaps:
        out["metrics"]["token_gap_p95_ms"] = percentile(gaps, 95)
    return out


def gen_lag_ms(requests, w0: float, w1: float) -> list[float]:
    return [(r.sent - r.due) * 1e3 for r in requests if w0 <= r.due < w1]
