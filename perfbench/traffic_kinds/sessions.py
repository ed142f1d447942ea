"""Traffic kind ``sessions``: users with transaction rows, and chat sessions
of one or more turns against the advisor. A pure function of the traffic
file's parameters and the seed.

So that two seeds offer the *same work in another order* (the check compares
runs of different seeds), the arrival instants and the shapes — how many turns,
how long each message, which sessions open with stored history, every think
time — are drawn from the file's ``shape_seed`` and are the same for every
``--seed``. The seed deals the shapes out to the arrival instants anew, but
only within a phase (before, inside and after the measured window, whose ends
the harness passes as ``phases``), so that every seed's window is offered the
same sessions at the same instants in another order; and it draws all text.

Parameters (all data, see ``perfbench/traffic/*.json``):

``arrival``  ``{"process": "poisson" | "gamma" | "backlog", "rate_per_s": r,
             "cv": c}`` — session arrivals a second; ``gamma`` has the
             coefficient of variation ``cv`` (> 1 is bursty); ``backlog`` needs
             ``sessions`` and offers them all at time zero, as an operator
             sends a batch.
``sessions`` number of sessions (``backlog``); otherwise as many as arrive in
             the horizon.
``turns``    ``{"mean": m, "max": k}`` geometric, at least 1.
``think_s``  ``{"median": s, "sigma": g, "cap": c}`` lognormal delay between an
             answer's end and the next turn.
``history``  ``{"share": p, "messages": [lo, hi], "bytes": [lo, hi]}`` stored
             earlier messages a session opens with.
``message_bytes`` ``{"median": b, "p99": b99, "min": lo, "max": hi}`` lognormal,
             or ``{"fixed": "text"}`` for one fixed message.
``users``    number of users; ``rows_per_user`` ``[lo, hi]``; ``row_bytes``
             ``[lo, hi]``.
``document_bytes`` optional ``[lo, hi]``: a block of text that opens each
             session's first message (a filing, a statement).
``answer_cap`` → ``engine.max_new_tokens``: with random weights the cap IS the
             output length. ``ingress``: ``kafka`` (``http`` is reserved).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

MERCHANTS = ["Blue Bottle Coffee", "Whole Foods Market", "Shell", "Payroll deposit",
             "Philz Coffee", "Trader Joe's", "City Parking", "Netflix", "Rent",
             "Electric utility", "Lyft ride", "Pharmacy", "Bookshop", "Gym membership",
             "Airline ticket", "Hardware store", "Farmers market", "Water utility"]
CATEGORIES = ["coffee", "groceries", "transport", "income", "housing", "utilities",
              "entertainment", "health", "travel", "shopping"]
WORDS = ("how much did I spend on my budget this month and what should change for "
         "savings goal groceries coffee rent income plan retire invest fund debt "
         "compare last quarter show chart of spending by category please explain "
         "why is it higher than usual can afford vacation emergency loan interest").split()


@dataclass
class Turn:
    message: str
    think_s: float  # delay after the previous answer's end (0 for the first)


@dataclass
class Session:
    session_id: str
    user_id: str
    arrival_s: float  # seconds after the traffic clock's zero
    history: list[tuple[str, str]] = field(default_factory=list)  # (sender, text)
    turns: list[Turn] = field(default_factory=list)


@dataclass
class User:
    user_id: str
    context: dict
    rows: list[dict]


@dataclass
class Traffic:
    users: list[User]
    sessions: list[Session]
    answer_cap: int
    ingress: str


def _text(rng: np.random.RandomState, n_bytes: int) -> str:
    out, size = [], 0
    while size <= n_bytes:
        w = WORDS[rng.randint(len(WORDS))]
        out.append(w)
        size += len(w) + 1
    return " ".join(out)[:max(1, n_bytes)]


def _lognormal(rng, median: float, sigma: float) -> float:
    return float(median * math.exp(sigma * rng.standard_normal()))


def _gaps(rng, arrival: dict, n: int) -> np.ndarray:
    rate = float(arrival["rate_per_s"])
    process = arrival["process"]
    if process == "poisson":
        return rng.exponential(1.0 / rate, size=n)
    if process == "gamma":
        shape = 1.0 / float(arrival["cv"]) ** 2
        return rng.gamma(shape, 1.0 / (rate * shape), size=n)
    raise ValueError(f"unknown arrival process {process!r}")


def generate(params: dict, seed: int, horizon_s: float,
             phases: tuple[float, ...] = ()) -> Traffic:
    """The traffic of one run: users, and sessions sorted by arrival, for
    ``horizon_s`` seconds of the traffic clock. ``phases`` are the instants
    that divide it (window open, window close)."""
    shape = np.random.RandomState(int(params.get("shape_seed", 0)) % (2 ** 32))
    rng = np.random.RandomState(int(seed) % (2 ** 32))

    # users and their rows: counts and lengths from the shape seed
    n_users = int(params["users"])
    lo, hi = params["rows_per_user"]
    blo, bhi = params["row_bytes"]
    users = []
    for u in range(n_users):
        rows = []
        for r in range(int(shape.randint(lo, hi + 1))):
            n_bytes = int(shape.randint(blo, bhi + 1))
            amount = round(float(rng.uniform(2, 900)), 2)
            merchant = MERCHANTS[rng.randint(len(MERCHANTS))]
            text = f"2026-{1 + rng.randint(12):02d}-{1 + rng.randint(28):02d} {merchant} ${amount:.2f} "
            text = (text + _text(rng, max(1, n_bytes - len(text))))[:n_bytes]
            rows.append({"text": text, "amount": -amount,
                         "category": CATEGORIES[rng.randint(len(CATEGORIES))]})
        users.append(User(f"user-{u:04d}",
                          {"name": f"Client {u}", "income": int(40_000 + 1000 * rng.randint(120)),
                           "savings_goal": int(5_000 + 1000 * rng.randint(60))}, rows))

    arrival = params["arrival"]
    if arrival["process"] == "backlog":
        n_sessions = int(params["sessions"])
        arrivals = np.zeros(n_sessions)
    else:
        n_gaps = int(math.ceil(float(arrival["rate_per_s"]) * horizon_s * 1.5)) + 8
        arrivals = np.cumsum(_gaps(shape, arrival, n_gaps))
        arrivals = arrivals[arrivals < horizon_s]
        n_sessions = len(arrivals)

    turns, think, hist, msg = (params["turns"], params["think_s"],
                               params["history"], params["message_bytes"])
    doc = params.get("document_bytes")
    sigma_msg = (math.log(msg["p99"] / msg["median"]) / 2.326) if "median" in msg else 0.0
    p_stop = 1.0 / float(turns["mean"])
    shapes = []
    for _ in range(n_sessions):
        n_turns = min(int(shape.geometric(p_stop)), int(turns["max"]))
        lengths = ([0] * n_turns if "fixed" in msg else
                   [int(min(max(_lognormal(shape, msg["median"], sigma_msg), msg["min"]), msg["max"]))
                    for _ in range(n_turns)])
        thinks = [0.0] + [min(_lognormal(shape, think["median"], think["sigma"]), think["cap"])
                          for _ in range(n_turns - 1)]
        n_hist = 0
        if shape.uniform() < hist["share"]:
            n_hist = int(shape.randint(hist["messages"][0], hist["messages"][1] + 1))
        hist_bytes = [int(shape.randint(hist["bytes"][0], hist["bytes"][1] + 1))
                      for _ in range(n_hist)]
        doc_bytes = int(shape.randint(doc[0], doc[1] + 1)) if doc else 0
        shapes.append((lengths, thinks, hist_bytes, doc_bytes))

    # deal the shapes out anew within each phase
    order = np.arange(n_sessions)
    phase_of = np.searchsorted(np.asarray(phases, float), arrivals, side="right")
    for ph in np.unique(phase_of):
        members = np.flatnonzero(phase_of == ph)
        order[members] = members[rng.permutation(len(members))]
    sessions = []
    for i, arrival_s in enumerate(arrivals):
        lengths, thinks, hist_bytes, doc_bytes = shapes[order[i]]
        user = users[(i + int(rng.randint(n_users))) % n_users] if arrival["process"] != "backlog" \
            else users[i % n_users]
        history = []
        for j, n_bytes in enumerate(hist_bytes):
            sender = "user" if j % 2 == 0 else "assistant"
            history.append((sender, _text(rng, n_bytes)))
        session_turns = []
        for j, (n_bytes, think_s) in enumerate(zip(lengths, thinks)):
            text = msg["fixed"] if "fixed" in msg else _text(rng, n_bytes)
            if j == 0 and doc_bytes:
                text = _text(rng, doc_bytes) + "\n" + text
            session_turns.append(Turn(text, float(think_s)))
        sessions.append(Session(f"s{int(seed)}-{i:05d}", user.user_id, float(arrival_s),
                                history, session_turns))
    return Traffic(users, sessions, int(params["answer_cap"]), params.get("ingress", "kafka"))
