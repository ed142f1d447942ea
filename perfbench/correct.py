"""The comparisons that decide ``correct``. All run outside the window.

(a) logits: a seeded prompt prefilled in two chunks through the paged cache
    and teacher-forced decode steps on the app's own engine, against
    ``perfbench/reference.py`` — logits, not tokens. Twice: through the split
    steps (``engine.prefill``, ``engine.decode``) and through the packed
    ragged step the serving path runs whenever prefill and decode share a
    round (``engine.ragged_mixed``: prefill-chunk rows, decode rows, and
    rounds that carry both).
(c) nothing compiled or broke inside the window (engine steps, rebuilds,
    breaker, sheds, dispatch failures, anomalies).
(d) the product's one guarantee: a retrieval returns only the asker's rows.
(b), the token counts per request, is in ``reduce.py``.
"""

from __future__ import annotations

import numpy as np

# bf16 serving against the float32 reference. Every matmul input and each of
# the 2 x n_layers residual additions rounds to bf16 (8 mantissa bits, eps
# 3.9e-3), and the kernels round unnormalised attention weights page by page,
# so the difference random-walks to a few percent of the logits' spread. The
# statistic is the RMS difference over the vocabulary relative to the
# reference logits' standard deviation: a wrong page, mask, head mapping or
# expert mix decorrelates the logits and puts it near 1; one expert swapped
# in one layer puts it at 0.4-0.6 (measured, below).
#
# The prompt is drawn from --seed, so the rule has to hold for every prompt.
# Measured on the chip over 70 seeds x 64 positions at Mixtral's widths, 3
# layers (PERF.md §6, "The logits check"): a prompt has a level of its own,
# the median over its positions, from 0.013 to 0.041 (median 0.022, 95th
# percentile 0.031; about 4 % of the context's tokens route differently in
# bf16, and every later position attends to them), and the worst single
# position with a stable routing read 0.060. Two limits, each about twice the
# worst seen and far under what a fault gives: the median over the compared
# positions (a loss of precision everywhere: int8 weights would be near 0.1)
# and the worst single position (one wrong page, row or expert).
LOGITS_RMS_TOL_MEDIAN = 0.06
LOGITS_RMS_TOL_MAX = 0.15
N_DECODE = 63
# Routed experts: where a token's routing margin (reference.py `_route`) is
# small in any layer, the bf16 engine can pick another expert than float32
# does, and with random weights one changed expert moves the logits by about
# half their spread. Such a position says nothing about the arithmetic, so it
# is not compared. Measured (same runs): 201 of 4,480 positions took another
# expert, 0.9 % of those with a margin of 0.08-0.1 router-logit standard
# deviations, the largest at 0.095, and none of the 2,508 above 0.1. The
# threshold is twice that largest; 30 % of positions pass it (44 % passed the
# 0.1 this file first had, under which 14 of the 70 prompts would have failed
# a check of 8 positions: fewer than 4 left, or a level over 0.05). At least
# MIN_STABLE must remain (of 64 the fewest seen was 12) or the check fails.
ROUTING_MARGIN_MIN = 0.2
MIN_STABLE = 4

MUST_NOT_MOVE = ("finchat_engine_rebuilds_total", "finchat_sheds_total",
                 "finchat_dispatch_failures_total",
                 "finchat_overload_rejections_total",
                 "finchat_quantmatmul_fallbacks_total")
ANOMALY_TRACK = "anomaly"


def check_logits(app, config: dict, seed: int) -> dict:
    """The relative RMS difference at the prefill's last position and at
    ``N_DECODE`` teacher-forced decode steps, judged by ``_judge`` on each of
    the two paths."""
    from perfbench.reference import forward_logits

    sched = app.scheduler
    engine = sched.engine
    prompt_len = engine.engine_cfg.prefill_chunk * 3 // 2  # more than one chunk
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    vocab = int(config["vocab_size"])
    tokens = [int(t) for t in rng.randint(0, vocab, size=prompt_len + N_DECODE)]
    prompt, forced = tokens[:prompt_len], tokens[prompt_len:]

    want, margins = forward_logits(
        engine.params, tokens, n_layers=int(config["num_hidden_layers"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        rope_theta=float(config["rope_theta"]), norm_eps=float(config["rms_norm_eps"]),
        n_experts=int(config.get("num_local_experts", 0)),
        top_k_experts=int(config.get("num_experts_per_tok", 2)),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        positions=list(range(prompt_len - 1, prompt_len + N_DECODE)),
        return_margins=True)
    want, margins = np.asarray(want, np.float32), np.asarray(margins, np.float32)

    split = _split_path_logits(sched, prompt, forced)
    ragged = _ragged_path_logits(sched, prompt, forced)

    def rel_rms(g, w) -> float:
        g = g.reshape(-1)[:vocab]
        return (float(np.sqrt(np.mean((g - w) ** 2)) / np.std(w))
                if np.isfinite(g).all() else float("inf"))

    # position index into `want` of each ragged reading: the prefill's last
    # position twice (two slots), then the forced tokens
    rel_ragged = [rel_rms(g, want[i]) for i, g in ragged]
    # split[0] follows the prefill (position P-1), split[i] the i-th forced token
    rel = [rel_rms(g, w) for g, w in zip(split, want)]
    judged = {"split": _judge(rel, margins),
              "ragged": _judge(rel_ragged, [margins[i] for i, _g in ragged])}
    return {"ok": all(j["ok"] for j in judged.values()), **judged,
            "tolerance": {"median": LOGITS_RMS_TOL_MEDIAN, "max": LOGITS_RMS_TOL_MAX,
                          "routing_margin_min": ROUTING_MARGIN_MIN,
                          "min_compared": MIN_STABLE},
            "prompt_len": prompt_len, "positions": len(rel),
            "per_step": [round(r, 4) for r in rel],
            "per_step_ragged": [round(r, 4) for r in rel_ragged],
            "margins": [round(float(m), 3) for m in margins]}


def _judge(rel: list[float], margins) -> dict:
    """The rule on one path's readings: those with a stable routing."""
    stable = sorted(r for r, m in zip(rel, margins) if m >= ROUTING_MARGIN_MIN)
    if len(stable) < MIN_STABLE:
        return {"ok": False, "compared": len(stable), "skipped_for_routing":
                len(rel) - len(stable), "median_rel_rms": None, "worst_rel_rms": None}
    median = float(np.median(stable))
    return {"ok": median <= LOGITS_RMS_TOL_MEDIAN and stable[-1] <= LOGITS_RMS_TOL_MAX,
            "compared": len(stable), "skipped_for_routing": len(rel) - len(stable),
            "median_rel_rms": median, "worst_rel_rms": stable[-1]}


def _split_path_logits(sched, prompt: list[int], forced: list[int]) -> list:
    """``engine.prefill`` then one ``engine.decode`` per forced token."""
    import jax.numpy as jnp

    from finchat_tpu.engine.kv_cache import pages_needed

    engine = sched.engine
    slot = sched.free_slots[-1]  # borrowed, not taken: the scheduler is not running
    owner = "perfbench-logits"
    pages = sched.allocator.allocate(
        owner, pages_needed(len(prompt) + len(forced) + 1, engine.page_size))
    got = []
    try:
        engine.set_page_table_row(slot, pages)
        got.append(np.asarray(engine.prefill(slot, prompt), np.float32))
        B = engine.engine_cfg.max_seqs
        active = jnp.zeros((B,), bool).at[slot].set(True)
        zeros, ones = jnp.zeros((B,), jnp.float32), jnp.ones((B,), jnp.float32)
        top_k = jnp.zeros((B,), jnp.int32)
        for token in forced:
            engine.set_last_token(slot, token)
            _, logits = engine.decode(active, zeros, ones, top_k, return_logits=True)
            got.append(np.asarray(logits[slot], np.float32))
    finally:
        engine.reset_slot(slot)
        sched.allocator.free(owner, pages)
    return got


def _ragged_path_logits(sched, prompt: list[int], forced: list[int]) -> list:
    """The same sequence through ``engine.ragged_mixed`` alone, packed as the
    scheduler packs a round (prefill rows first, then decode rows that read
    their token on the device). Slot A prefills in two rounds and then
    decodes the forced tokens; slot B prefills the same prompt in the rounds
    of A's first two decode steps, so those two rounds are mixed. Returns
    ``(index into the reference's positions, logits)`` pairs."""
    import jax.numpy as jnp

    from finchat_tpu.engine.kv_cache import pages_needed

    engine = sched.engine
    B = engine.engine_cfg.max_seqs
    chunk = engine.engine_cfg.prefill_chunk
    slot_a, slot_b = sched.free_slots[-1], sched.free_slots[-2]
    n_pages = pages_needed(len(prompt) + len(forced) + 1, engine.page_size)
    owners = {"perfbench-ragged-a": slot_a, "perfbench-ragged-b": slot_b}
    pages = {o: sched.allocator.allocate(o, n_pages) for o in owners}
    chunks = [(0, prompt[:chunk]), (chunk, prompt[chunk:])]

    def one_round(rows):
        """rows: (slot, start, tokens) for a prefill chunk, (slot, None, None)
        for a decode row. Returns the rows' sampling-position logits."""
        packed, tok_row = [], []
        row_slot = np.full((B,), rows[0][0], np.int32)  # padding rows: len 0
        row_start, row_len = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
        from_device = np.zeros((B,), bool)
        for i, (slot, start, toks) in enumerate(rows):
            row_slot[i] = slot
            if toks is None:
                from_device[i], toks = True, [0]
            else:
                row_start[i] = start
            row_len[i] = len(toks)
            packed += toks
            tok_row += [i] * len(toks)
        T = engine.ragged_bucket(len(packed))
        tok_row += [B] * (T - len(packed))
        packed += [0] * (T - len(packed))
        zeros, ones = jnp.zeros((B,), jnp.float32), jnp.ones((B,), jnp.float32)
        zeros_i = jnp.zeros((B,), jnp.int32)
        _e, _n, row_logits, _b = engine.ragged_mixed(
            jnp.asarray(np.asarray(packed, np.int32)),
            jnp.asarray(np.asarray(tok_row, np.int32)),
            jnp.asarray(row_slot), jnp.asarray(row_start), jnp.asarray(row_len),
            jnp.asarray(from_device), jnp.asarray(from_device), zeros_i,
            zeros, ones, zeros_i,              # per-row: greedy, nothing truncates
            jnp.zeros((B,), bool), zeros, ones, zeros_i, -1)  # no fused tail
        return np.asarray(row_logits, np.float32)

    got = []
    try:
        for owner, slot in owners.items():
            engine.set_page_table_row(slot, pages[owner])
        one_round([(slot_a, *chunks[0])])
        got.append((0, one_round([(slot_a, *chunks[1])])[0]))
        for k, token in enumerate(forced):
            engine.set_last_token(slot_a, token)
            if k < len(chunks):  # a mixed round: B's chunk rides with A's decode
                logits = one_round([(slot_b, *chunks[k]), (slot_a, None, None)])
                if k == len(chunks) - 1:
                    got.append((0, logits[0]))
                got.append((1 + k, logits[1]))
            else:
                got.append((1 + k, one_round([(slot_a, None, None)])[0]))
    finally:
        for owner, slot in owners.items():
            engine.reset_slot(slot)
            sched.allocator.free(owner, pages[owner])
    return got


async def check_isolation(app, traffic, n_users: int = 6) -> dict:
    """A sample of users each ask the retriever: every row returned is one of
    the asker's own, and a user with no rows gets none."""
    leaks, asked = [], 0
    step = max(1, len(traffic.users) // n_users)
    for user in traffic.users[::step][:n_users]:
        own = {r["text"] for r in user.rows}
        hits = await app.agent.retriever(
            {"search_query": "coffee and groceries this month", "user_id": user.user_id})
        asked += 1
        if not hits or any(h not in own for h in hits):
            leaks.append(user.user_id)
    stranger = await app.agent.retriever(
        {"search_query": "coffee", "user_id": "user-with-no-rows"})
    return {"ok": not leaks and stranger == [], "users_asked": asked,
            "leaks": leaks, "stranger_rows": len(stranger)}


def engine_step_cache_sizes() -> dict[str, int]:
    """Compiled-variant count of every jitted engine step: none may grow
    inside the window."""
    from finchat_tpu.engine import engine as engine_module

    return {name: fn._cache_size() for name, fn in vars(engine_module).items()
            if hasattr(fn, "_cache_size")}


def check_window(prom_before: dict, prom_after: dict, steps_before: dict,
                 steps_after: dict, tracer_events: list) -> dict:
    moved = {f: prom_after.get(f, 0.0) - prom_before.get(f, 0.0)
             for f in MUST_NOT_MOVE
             if prom_after.get(f, 0.0) != prom_before.get(f, 0.0)}
    grown = {n: [steps_before.get(n), steps_after[n]] for n in steps_after
             if steps_after[n] != steps_before.get(n)}
    anomalies = [ev[2] for ev in tracer_events if ev[4] == ANOMALY_TRACK]
    breaker = prom_after.get("finchat_breaker_state", 0.0)
    return {"ok": not moved and not grown and not anomalies and breaker == 0,
            "counters_moved": moved, "engine_steps_compiled": grown,
            "anomalies": anomalies, "breaker_state": breaker}
