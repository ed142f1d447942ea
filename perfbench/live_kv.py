"""The KV a decode step must read, counted by DISTINCT physical pages.

The yardstick's own, like ``costs.py``: the program says which rows decode and
what each row's page table references (``SequenceHandle.kv_ctx``, ``kv_gap``,
``shared_len``, ``prefix_entry``, ``page_list``: the bookkeeping its rounds
already keep), and the rule that turns it into a count lives here, where a
change to the program cannot move it.

The rule: a page that several rows' page tables reference is in the pool once,
so a step, and one layer's attention call, must read it once. A row's context
is ``kv_ctx - kv_gap`` tokens (what its next dispatched step reads: the
``kv_tokens`` stat of the program's dispatch annotation sums the same). Its
first ``shared_len`` tokens lie on the read-only pages of its
``prefix_entry``; those are counted once an entry — the longest reference to
it — and everything else once a row. A row without an entry adds its whole
context.
"""

from __future__ import annotations

LIVE_ANNOTATION = "perfbench_live"   # the harness's own event in a capture


def _count(handles) -> tuple[int, int, dict[int, int]]:
    """Σ rows' contexts, the tokens on the rows' own pages, and for each
    prefix entry referenced (by ``id``) the longest reference to it."""
    total = private = 0
    heads: dict[int, int] = {}
    for h in handles:
        ctx = h.kv_ctx - h.kv_gap
        total += ctx
        shared = min(h.shared_len, ctx) if h.prefix_entry is not None else 0
        private += ctx - shared
        if shared:
            key = id(h.prefix_entry)
            heads[key] = max(heads.get(key, 0), shared)
    return total, private, heads


def kv_tokens(handles) -> tuple[int, int]:
    """``(total, distinct)`` context tokens of the rows in ``handles``:
    Σ rows' contexts, and the tokens on distinct physical pages."""
    total, private, heads = _count(handles)
    return total, private + sum(heads.values())


def kv_pages(handles, page_size: int) -> int:
    """Distinct physical page ids under the rows' contexts: what the count
    above is a count OF. ``kv_pages x page_size`` bounds ``distinct`` from
    above and exceeds it by less than a page for each row and each entry
    (the last page of a context is part full)."""
    pages: set[int] = set()
    for h in handles:
        pages.update(h.page_list[:-(-(h.kv_ctx - h.kv_gap) // page_size)])
    return len(pages)


def sample(handles, page_size: int) -> dict:
    """One sample of the rows decoding now, as the harness keeps and notes it."""
    handles = list(handles)
    total, private, heads = _count(handles)
    return {"rows": len(handles), "kv_tokens": total,
            "kv_tokens_distinct": private + sum(heads.values()), "entries": len(heads),
            "page_tokens": kv_pages(handles, page_size) * page_size}
