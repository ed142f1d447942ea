"""Paged KV cache: device arrays + host-side page allocator.

The reference has no KV cache (inference is a remote API call); this is the
memory system that makes long RAG contexts (unbounded history + up to 10,000
retrieved transactions, reference qdrant_tool.py:145 / llm_agent.py:234-236)
servable on fixed TPU HBM:

- Device side: ``k_pages``/``v_pages`` shaped ``[n_layers, num_pages,
  page_size, n_kv_heads * head_dim]`` — token-major pages with the KV heads
  fused into the minor dim; each array as wide as ITS heads are (keys of 192
  over values of 128 are arrays of two widths) and a pool as wide as the heads
  of the KIND of layer that owns it (``LlamaConfig.kv_widths``: a model whose
  window layers keep 8 K/V heads and whose full layers keep 4 has pools of two
  page widths). This layout is chosen for Mosaic's DMA tiling
  rules (measured on v5e, round 4): a page's trailing dims
  ``(page_size, Hkv*hd)`` are tile-aligned, so the in-place decode append
  kernel (ops/kv_append.py) can RMW one whole page per sequence with legal
  full-extent DMAs, and the paged attention kernel (ops/paged_attention.py)
  copies whole pages, several a block, through the page table and
  value-slices per-head ``[block, hd]`` tiles out of the loaded block. The
  leading layer axis exists because the cache rides the model's layer scan
  as a CARRY (not xs→ys): XLA restacks xs→ys cache updates into a fresh
  buffer every step — a full-cache copy measured at ~22 ms/step for a 1.5 GB
  cache — while kernels with ``input_output_aliases`` update the carried
  buffer in place.
  Physical page 0 is a TRASH page — writes from padding lanes and inactive
  slots are redirected there, which keeps every jitted step a fixed-shape
  write with no host branching.
- Host side: ``PageAllocator`` — a free list with ownership tracking and the
  scheduler invariants of SURVEY §5.2 enforced at every call: a page is
  owned by at most one sequence; double-free and foreign-free raise.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from finchat_tpu.models.llama import WINDOW, LlamaConfig
from finchat_tpu.utils.logging import get_logger
from finchat_tpu.utils.metrics import METRICS

logger = get_logger(__name__)

TRASH_PAGE = 0


def scale_rows(n_kv: int) -> int:
    """Rows of the per-page scale block: KV heads padded to a sublane
    multiple so the ``[rows, page_size]`` trailing dims of the scale arrays
    are Mosaic-tile-aligned (fp32 tiles are (8, 128))."""
    return -(-n_kv // 8) * 8


@dataclass
class PagedKVCache:
    """Device-side paged cache tensors (a pytree; the leading layer axis is
    carried through the model's ``lax.scan`` and indexed per layer by the
    kernels via scalar prefetch).

    ``kv_quant="int8"`` stores pages as int8 with PER-TOKEN-PER-HEAD fp32
    scales in parallel ``[L, P, scale_rows, page_size]`` arrays (~6%
    overhead at head_dim 64): each token row is quantized independently at
    write time, so the append kernel's page RMW never requantizes existing
    rows — no drift — and per-step HBM traffic for the KV read halves.
    When off, the scale leaves are kept as (1,1,1,1) placeholders so the
    engine state pytree structure is identical in both modes."""

    # [L, P, page_size, Hkv * head_dim] and [.., Hkv * value_dim] (dtype or int8:
    # LlamaConfig.kv_widths); for latent attention
    # the latent rows [.., latent_row] and the indexer's key rows [.., Di]
    k_pages: Any
    v_pages: Any
    k_scales: Any  # [L, P, scale_rows(Hkv), page_size] fp32 (or (1,1,1,1))
    v_scales: Any
    page_size: int
    num_pages: int
    kv_quant: str = ""

    @classmethod
    def create(cls, config: LlamaConfig, num_pages: int, page_size: int,
               kv_quant: str = "") -> "PagedKVCache":
        # the pool has the depth of the layers that own pages: every layer,
        # or a layer pattern's full-attention layers alone; a row's width in
        # each of the two arrays is the model's (``kv_row_widths``: K and V
        # heads, or a latent row and the indexer's key row)
        k_row, v_row = config.kv_row_widths
        shape = (config.n_attn_layers, num_pages, page_size, k_row)
        if kv_quant:
            if kv_quant != "int8":
                raise ValueError(f"unknown kv_quant mode {kv_quant!r} (supported: 'int8')")
            if config.kv_lora_rank:
                raise ValueError("kv_quant is not supported with latent attention: the int8 "
                                 "pages keep a scale a KV head, and a latent row has none")
            sshape = (shape[0], num_pages, scale_rows(config.n_kv_heads), page_size)
            return cls(
                k_pages=jnp.zeros(shape, jnp.int8),
                v_pages=jnp.zeros(shape, jnp.int8),
                k_scales=jnp.zeros(sshape, jnp.float32),
                v_scales=jnp.zeros(sshape, jnp.float32),
                page_size=page_size, num_pages=num_pages, kv_quant=kv_quant,
            )
        return cls(
            k_pages=jnp.zeros(shape, config.dtype),
            v_pages=jnp.zeros((*shape[:3], v_row), config.dtype),
            k_scales=jnp.zeros((1, 1, 1, 1), jnp.float32),
            v_scales=jnp.zeros((1, 1, 1, 1), jnp.float32),
            page_size=page_size, num_pages=num_pages,
        )

    @classmethod
    def create_window(cls, config: LlamaConfig, num_pages: int,
                      page_size: int) -> "PagedKVCache":
        """The second pool of a model with sliding-window layers
        (``config.n_window_layers`` deep): rows as wide as the WINDOW layers'
        heads (``kv_widths``: the first pool's, unless the kinds differ), never
        quantized; a row's pages of it are the window's alone
        (``WindowPager``)."""
        k_row, v_row = config.kv_widths(WINDOW)
        shape = (config.n_window_layers, num_pages, page_size)
        return cls(
            k_pages=jnp.zeros((*shape, k_row), config.dtype),
            v_pages=jnp.zeros((*shape, v_row), config.dtype),
            k_scales=jnp.zeros((1, 1, 1, 1), jnp.float32),
            v_scales=jnp.zeros((1, 1, 1, 1), jnp.float32),
            page_size=page_size, num_pages=num_pages,
        )

    def layers_pytree(self) -> tuple[Any, Any, Any, Any]:
        """The (k, v, k_scales, v_scales) tuple carried through the model
        forward as the cache (scales are placeholders when kv_quant is
        off — the attention callbacks always unpack four)."""
        return (self.k_pages, self.v_pages, self.k_scales, self.v_scales)

    def hbm_bytes(self) -> int:
        return (self.k_pages.nbytes + self.v_pages.nbytes
                + self.k_scales.nbytes + self.v_scales.nbytes)


def page_hbm_bytes(config: LlamaConfig, page_size: int, kv_quant: str = "",
                   kind: str = "full") -> int:
    """HBM bytes ONE page costs across all layers that own pages (K+V, plus
    the int8 scale rows) — computed WITHOUT allocating, so harnesses can fit a KV
    pool to an HBM budget before engine construction. Mirrors
    ``PagedKVCache.create``'s shapes exactly (asserted in
    tests/test_kv_cache.py); with ``kind`` "window" a page of the second pool
    (``create_window``: the sliding-window layers', never quantized)."""
    import numpy as np

    if kind == "window":
        return (config.n_window_layers * page_size * sum(config.kv_widths(WINDOW))
                * np.dtype(config.dtype).itemsize)
    itemsize = 1 if kv_quant else np.dtype(config.dtype).itemsize
    per = config.n_attn_layers * page_size * sum(config.kv_row_widths) * itemsize
    if kv_quant:
        per += 2 * config.n_attn_layers * scale_rows(config.n_kv_heads) * page_size * 4
    return per


class PageAllocationError(RuntimeError):
    pass


class PageAllocator:
    """Host-side free-list allocator with ownership invariants.

    Page 0 is reserved as the trash page and never handed out.
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (one is the trash page)")
        self.num_pages = num_pages
        self._free: list[int] = list(range(num_pages - 1, 0, -1))  # pop() yields low ids first
        self._owner: dict[int, str] = {}  # page id -> sequence id

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return len(self._owner)

    def can_allocate(self, n: int) -> bool:
        return len(self._free) >= n

    def allocate(self, seq_id: str, n: int) -> list[int]:
        if n > len(self._free):
            raise PageAllocationError(
                f"requested {n} pages for {seq_id}, only {len(self._free)} free"
            )
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            assert p not in self._owner, f"invariant violation: page {p} already owned"
            self._owner[p] = seq_id
        METRICS.set_gauge("finchat_kv_pages_used", self.used_count)
        return pages

    def free(self, seq_id: str, pages: list[int]) -> None:
        for p in pages:
            owner = self._owner.get(p)
            if owner is None:
                raise PageAllocationError(f"double free of page {p} by {seq_id}")
            if owner != seq_id:
                raise PageAllocationError(
                    f"sequence {seq_id} freeing page {p} owned by {owner}"
                )
            del self._owner[p]
            self._free.append(p)
        METRICS.set_gauge("finchat_kv_pages_used", self.used_count)

    def owned_by(self, seq_id: str) -> list[int]:
        return [p for p, s in self._owner.items() if s == seq_id]

    def transfer(self, pages: list[int], seq_id: str, to: str) -> None:
        """Hand ``pages`` from one owner to another (a slot's window pages
        become a shared head's): nothing is freed in between."""
        for p in pages:
            if self._owner.get(p) != seq_id:
                raise PageAllocationError(f"page {p} is not {seq_id}'s to hand to {to}")
            self._owner[p] = to

    def reset(self) -> None:
        """Return EVERY page to the free list, dropping all ownership —
        the engine-rebuild path (scheduler breaker trip): the device KV
        pool was just torn down and recreated, so nothing the old owners
        pointed at exists anymore. Never valid while any owner still
        expects its pages to hold live KV."""
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._owner.clear()
        METRICS.set_gauge("finchat_kv_pages_used", 0)

    def check_invariants(self) -> None:
        """Every page is exactly one of {trash, free, owned-once}."""
        free_set = set(self._free)
        owned_set = set(self._owner)
        assert len(free_set) == len(self._free), "duplicate pages in free list"
        assert not (free_set & owned_set), "page both free and owned"
        assert TRASH_PAGE not in free_set and TRASH_PAGE not in owned_set
        assert free_set | owned_set | {TRASH_PAGE} == set(range(self.num_pages))


def pages_needed(n_tokens: int, page_size: int) -> int:
    return max(1, -(-n_tokens // page_size))


def window_pages_per_row(window: int, page_size: int) -> int:
    """The most pages of ONE sliding-window layer a row holds, whatever its
    length: the window's own and two more — the page the window's oldest token
    shares with tokens that slid out, and the page a dispatch's new tokens
    reach into."""
    return window // page_size + 2


@dataclass
class WindowHead:
    """A shared head's trailing window pages: what a row admitted from the
    head reads, by reference, until its own window slides past them."""

    owner: str
    first: int  # the logical page of ``pages[0]``
    pages: list[int]
    released: bool = False


class WindowPager:
    """Host-side page lists of the sliding-window layers' pool: a row (an
    engine slot) holds the pages that cover its window and nothing older.

    A row's list covers the CONTIGUOUS logical pages ``first .. first +
    len(pages) - 1``; the device sees it compacted — ``table[slot]`` the
    physical pages from column 0, ``gaps[slot] = first * page_size`` the tokens
    before them — which is the coordinate shift bounded KV already runs under
    (``DecodeState.kv_gaps``), applied to ONE kind of layer. ``advance`` runs
    before every dispatch: pages wholly behind the window of the dispatch's
    first query go back to the allocator (or, a shared head's, lose this row's
    reference), pages up to the dispatch's last token are allocated. A row
    never holds more than ``window_pages_per_row``; ``room`` is how many
    tokens a dispatch may carry for that to hold.

    A head's pages are read-only and counted by reference: the head's own and
    one a row. They return to the allocator when the head is released AND the
    last row slid past them."""

    def __init__(self, num_pages: int, max_seqs: int, window: int, page_size: int):
        import numpy as np

        if window % page_size:
            raise ValueError(f"window {window} is not a whole number of {page_size}-token pages")
        self.window, self.page_size = window, page_size
        self.per_row = window_pages_per_row(window, page_size)
        if num_pages < max_seqs * self.per_row + 1:
            raise ValueError(
                f"{num_pages} window pages cannot hold {max_seqs} rows of {self.per_row} "
                f"(and the trash page)")
        self.allocator = PageAllocator(num_pages)
        self.table = np.zeros((max_seqs, self.per_row), np.int32)
        self.gaps = np.zeros((max_seqs,), np.int32)
        self._first = [0] * max_seqs
        self._pages: list[list[int]] = [[] for _ in range(max_seqs)]
        self._refs: dict[int, int] = {}  # a head's page -> references (the head's own is one)
        self._heads: dict[int, WindowHead] = {}  # a head's page -> its head
        self._n_heads = 0
        self.dirty = False

    def _lowest(self, start: int) -> int:
        """The logical page of the oldest token a query at ``start`` sees."""
        return max(start - self.window + 1, 0) // self.page_size

    def room(self, start: int) -> int:
        """Tokens a dispatch starting at position ``start`` may carry."""
        return (self._lowest(start) + self.per_row) * self.page_size - start

    def pages_of(self, slot: int) -> list[int]:
        return list(self._pages[slot])

    @property
    def pages_in_use(self) -> int:
        return self.allocator.used_count

    def _drop(self, slot: int, page: int) -> int:
        """The row lets go of ``page``; returns 1 if it went back to the pool."""
        if page not in self._refs:
            self.allocator.free(f"w{slot}", [page])
            return 1
        return self._unref(page)

    def _unref(self, page: int) -> int:
        if page not in self._refs:  # a head that outlived a ``reset``
            return 0
        self._refs[page] -= 1
        if self._refs[page]:
            return 0
        del self._refs[page]
        self.allocator.free(self._heads.pop(page).owner, [page])
        return 1

    def advance(self, slot: int, start: int, incoming: int) -> int:
        """Before a dispatch of ``incoming`` tokens from position ``start``:
        returns the pages that went back to the allocator."""
        pages, ps = self._pages[slot], self.page_size
        lo, hi = self._lowest(start), (start + incoming - 1) // ps
        if hi - lo >= self.per_row:
            raise PageAllocationError(
                f"a dispatch of {incoming} tokens from {start} spans {hi - lo + 1} window "
                f"pages; a row holds {self.per_row} (see WindowPager.room)")
        freed, held = 0, len(pages)
        while pages and self._first[slot] < lo:
            freed += self._drop(slot, pages.pop(0))
            self._first[slot] += 1
        if not pages:
            self._first[slot] = lo
        need = hi + 1 - (self._first[slot] + len(pages))
        if need > 0:
            pages += self.allocator.allocate(f"w{slot}", need)
        if need > 0 or len(pages) != held:
            self.dirty = True
            self.table[slot] = 0
            self.table[slot, :len(pages)] = pages
            self.gaps[slot] = self._first[slot] * ps
        return freed

    def release(self, slot: int) -> None:
        """The row is over: its own pages go back, a head's lose its reference."""
        for page in self._pages[slot]:
            self._drop(slot, page)
        self._pages[slot] = []
        self._first[slot] = 0
        self.table[slot] = 0
        self.gaps[slot] = 0
        self.dirty = True

    def detach_head(self, slot: int, n_tokens: int) -> WindowHead:
        """The row just prefilled a shared head of ``n_tokens``: its pages
        that a row CONTINUING from there will read become the head's (the
        older ones go back), and the slot holds nothing."""
        lo = self._lowest(n_tokens)
        keep = self._pages[slot][max(lo - self._first[slot], 0):]
        for page in self._pages[slot][:len(self._pages[slot]) - len(keep)]:
            self._drop(slot, page)
        head = WindowHead(f"wh{self._n_heads}", max(lo, self._first[slot]), keep)
        self._n_heads += 1
        self.allocator.transfer(keep, f"w{slot}", head.owner)
        for page in keep:
            self._refs[page] = 1
            self._heads[page] = head
        self._pages[slot] = []
        self.release(slot)
        return head

    def room_for_head(self) -> bool:
        """One more head's pages fit beside every slot's bound: what heads
        hold now (retired ones that rows still read too) and one more."""
        reserve = self.allocator.num_pages - 1 - len(self._pages) * self.per_row
        return len(self._refs) + self.per_row <= reserve

    def share(self, slot: int, head: WindowHead) -> None:
        """Start the row from ``head``'s pages, by reference."""
        assert not self._pages[slot] and not head.released
        for page in head.pages:
            self._refs[page] += 1
        self._pages[slot] = list(head.pages)
        self._first[slot] = head.first
        self.table[slot] = 0
        self.table[slot, :len(head.pages)] = head.pages
        self.gaps[slot] = head.first * self.page_size
        self.dirty = True

    def release_head(self, head: WindowHead) -> None:
        """The head is retired: its own reference goes; a page returns to the
        allocator now, or when the last row slides past it."""
        if not head.released:
            head.released = True
            for page in head.pages:
                self._unref(page)

    def reset(self) -> None:
        """Every page back (the engine's state was rebuilt)."""
        self.allocator.reset()
        self.table[:] = 0
        self.gaps[:] = 0
        self._first = [0] * len(self._first)
        self._pages = [[] for _ in self._pages]
        self._refs.clear()
        self._heads.clear()
        self.dirty = True


@dataclass(frozen=True)
class BoundedKVPolicy:
    """SnapStream-style bounded-KV serving policy (ISSUE 15): the first
    ``sink_pages`` pages of a row are PINNED (the attention sink) and a
    sliding window of the ``window_pages`` most recent pages survives;
    everything in between is evicted back to the page pool as the context
    grows, so a live 100k-token session occupies at most
    ``sink_pages + window_pages`` pages and decodes at flat per-token cost.

    Eviction is pure host metadata riding the paged indirection: an evicted
    page leaves the row's logical→physical page list (later pages shift one
    logical slot down — physically nothing moves) and returns to the
    allocator. The row tracks ``kv_gap`` — evicted tokens, always a whole
    multiple of ``page_size`` — and every KV WRITE and attention MASK runs
    in COMPACTED coordinates (``absolute - kv_gap``) while positions/rotary
    stay ABSOLUTE (keys carry their original RoPE; relative distances to
    surviving tokens are exact). Compacted-coordinate masking is exact for
    the surviving set: a new token's q position always exceeds every
    evicted position, so ``c_kv <= c_q`` iff ``abs_kv <= abs_q`` for sink
    and window tokens alike (tests/test_bounded_kv.py pins this against the
    unbounded oracle while the context still fits).

    All methods are pure host-side integer math (no device work, no syncs)
    — the scheduler's eviction wave calls them between dispatches.
    """

    sink_pages: int
    window_pages: int
    page_size: int

    @property
    def enabled(self) -> bool:
        return self.sink_pages > 0 and self.window_pages > 0

    @property
    def budget_pages(self) -> int:
        """Max pages a bounded row ever occupies (its whole page list)."""
        return self.sink_pages + self.window_pages

    @property
    def sink_tokens(self) -> int:
        return self.sink_pages * self.page_size

    def validate(self, *, prefill_chunk: int, max_pages_per_seq: int,
                 spec_tokens: int = 0) -> None:
        """Feasibility at engine construction: the window must always be
        able to make room for the next dispatch's writes by evicting full
        post-sink pages — a chunk (prefill) or a spec burst (decode)
        plus one partial page of already-written tail must fit."""
        if not self.enabled:
            return
        if self.sink_pages < 1 or self.window_pages < 1:
            raise ValueError(
                "bounded KV needs kv_sink_pages >= 1 and kv_window_pages >= 1 "
                f"(got sink={self.sink_pages}, window={self.window_pages}); "
                "set both to 0 for unbounded serving"
            )
        burst = max(prefill_chunk, 1 + spec_tokens)
        need = -(-burst // self.page_size) + 2  # burst + partial tail + slack
        if self.window_pages < need:
            raise ValueError(
                f"kv_window_pages={self.window_pages} cannot hold a "
                f"{burst}-token dispatch burst between eviction waves; "
                f"need >= {need} pages of {self.page_size} tokens "
                "(grow the window or shrink prefill_chunk)"
            )
        if self.budget_pages > max_pages_per_seq:
            raise ValueError(
                f"bounded budget {self.budget_pages} pages exceeds "
                f"max_pages_per_seq={max_pages_per_seq}; grow max_seq_len "
                "or shrink the sink/window"
            )

    def row_pages(self, n_tokens: int) -> int:
        """Pages a bounded row needs for ``n_tokens`` of (compacted)
        context — the unbounded requirement capped at the budget."""
        return min(pages_needed(n_tokens, self.page_size), self.budget_pages)

    def plan_eviction(self, compacted_ctx: int, incoming: int,
                      capacity_pages: int, pinned_pages: int) -> int:
        """How many whole post-sink pages to evict so the next dispatch's
        ``incoming`` tokens fit the row's ``capacity_pages`` page list.
        ``compacted_ctx`` is the row's compacted written length (absolute
        minus kv_gap, INCLUDING tokens still in flight); ``pinned_pages``
        is the unevictable head (``max(sink_pages, shared head pages)`` —
        a shared-prefix head larger than the sink is pinned whole, an
        effectively larger sink for that row). Returns 0 when everything
        already fits. Deterministic in the written-token count alone — the
        preempt-replay identity leans on this."""
        need = -(-(compacted_ctx + incoming) // self.page_size)
        e = max(0, need - capacity_pages)
        if e == 0:
            return 0
        # only FULL post-sink pages are evictable (the newest, possibly
        # partial page holds the live tail; pinned head pages never move)
        evictable = max(0, compacted_ctx // self.page_size - pinned_pages)
        if e > evictable:
            raise PageAllocationError(
                f"bounded eviction infeasible: need {e} pages, only "
                f"{evictable} evictable (ctx={compacted_ctx}, "
                f"incoming={incoming}, capacity={capacity_pages}, "
                f"pinned={pinned_pages})"
            )
        return e


def scatter_kv_chunk(
    k_pages: Any,  # [L, P, page_size, Hkv*hd] full-depth cache
    v_pages: Any,
    k_new: Any,  # [B, C, Hkv, hd]
    v_new: Any,
    page_table: Any,  # [B, max_pages] int32 physical page ids (0 = trash)
    start_pos: Any,  # [B] int32 absolute position of chunk token 0
    n_valid: Any,  # [B] int32 how many of the C tokens are real
    page_size: int,
    layer: Any,  # scalar int32 — which layer's pages to write
) -> tuple[Any, Any]:
    """Scatter a chunk of new K/V into one layer's pages (fixed shapes).

    Token (b, i) lands at absolute position ``start_pos[b] + i`` →
    logical page ``pos // page_size``, offset ``pos % page_size``, physical
    page ``page_table[b, logical]``. Padding lanes (i >= n_valid[b]) are
    redirected to the trash page.

    This is the PREFILL write path (and the jnp reference path for decode):
    an XLA scatter, which costs a full-cache copy per call — fine amortized
    over a whole batched prefill chunk, ruinous per decode token. Decode
    uses the in-place Pallas append (ops/kv_append.py) instead.
    """
    B, C = k_new.shape[:2]
    hd_fused = k_pages.shape[-1]
    i = jnp.arange(C)[None, :]  # [1, C]
    pos = start_pos[:, None] + i  # [B, C]
    logical = pos // page_size
    offset = pos % page_size
    phys = jnp.take_along_axis(page_table, logical, axis=1)  # [B, C]
    valid = i < n_valid[:, None]
    phys = jnp.where(valid, phys, TRASH_PAGE)

    lay = jnp.broadcast_to(jnp.asarray(layer, jnp.int32), (B * C,))
    flat_phys = phys.reshape(-1)  # [B*C]
    flat_off = offset.reshape(-1)
    k_flat = k_new.reshape(B * C, hd_fused)  # token rows, heads fused
    v_flat = v_new.reshape(B * C, v_pages.shape[-1])
    k_pages = k_pages.at[lay, flat_phys, flat_off].set(k_flat, mode="drop")
    v_pages = v_pages.at[lay, flat_phys, flat_off].set(v_flat, mode="drop")
    return k_pages, v_pages


def gather_pages_host(
    k_pages: Any,
    v_pages: Any,
    k_scales: Any,
    v_scales: Any,
    page_ids: list[int],
) -> tuple[Any, Any, Any | None, Any | None]:
    """Copy a set of physical pages device→host across all layers: returns
    ``(k [L, n, PS, row], v, k_scales | None, v_scales | None)`` as numpy.

    Session-cache OFFLOAD path (engine/session_cache.py). Deliberately NOT
    jitted and deliberately synchronous: the gather rides the ordinary
    dispatch stream, so it serializes AFTER every already-dispatched step
    that might still append into these pages, and ``device_get`` blocks
    until the copy lands — the caller frees the pages immediately after,
    so returning before the read completed would race the next sequence's
    writes. Per-turn cost, never on the per-token hot path."""
    import numpy as np

    # the eager take is compiled by its shape: the page count is padded to a
    # power of two (the trash page repeated, dropped again on the host), so a
    # process meets log2(max_pages) + 1 shapes — which ``InferenceEngine.warmup``
    # runs once — and not one for every context length that ends: at a
    # vocabulary of 16k a window samples six EOS, each a page count no earlier
    # one had, and each cost 2.5 s of compiling on the loop (PERF.md section 6,
    # PR 40)
    n = len(page_ids)
    ids = jnp.asarray([*page_ids, *[TRASH_PAGE] * (gather_bucket(n) - n)], jnp.int32)

    def take(pages):  # a copy of the rows asked for: the padding goes with the buffer
        return np.ascontiguousarray(np.asarray(jax.device_get(jnp.take(pages, ids, axis=1)))[:, :n])

    quantized = k_pages.dtype == jnp.int8
    return (take(k_pages), take(v_pages),
            take(k_scales) if quantized else None, take(v_scales) if quantized else None)


def gather_bucket(n_pages: int) -> int:
    """The page count ``gather_pages_host`` pads ``n_pages`` to."""
    return 1 << max(n_pages - 1, 0).bit_length()


def scatter_pages_device(
    k_pages: Any,
    v_pages: Any,
    k_scales: Any,
    v_scales: Any,
    page_ids: list[int],
    host: tuple,
) -> tuple[Any, Any, Any, Any]:
    """Write host page snapshots (``gather_pages_host`` layout, possibly a
    leading slice of one) back into freshly allocated physical pages.

    Session-cache RESTORE path. An XLA scatter — one full-cache copy per
    restore, amortized over a whole turn (the same trade ``scatter_kv_chunk``
    makes per prefill chunk); never called from a jitted step."""
    import numpy as np

    ids = jnp.asarray(page_ids, jnp.int32)
    k, v, ks, vs = host
    n = len(page_ids)
    assert k.shape[1] >= n, f"snapshot holds {k.shape[1]} pages, need {n}"
    # cross-MODE snapshots must fail loudly, not cast silently: an int8
    # snapshot .set() into a bf16 pool (or a bf16 one into int8) would
    # value-cast into plausible-looking garbage KV. Callers refuse earlier
    # (session tier / import guards, counted); this is the last line.
    if np.dtype(k.dtype) != np.dtype(k_pages.dtype):
        raise ValueError(
            f"snapshot dtype {np.dtype(k.dtype).name} does not match the "
            f"page-pool dtype {np.dtype(k_pages.dtype).name} (cross-mode "
            "restore refused)"
        )
    k_pages = k_pages.at[:, ids].set(jnp.asarray(k[:, :n]))
    v_pages = v_pages.at[:, ids].set(jnp.asarray(v[:, :n]))
    if k_pages.dtype == jnp.int8:
        assert ks is not None and vs is not None, "int8 cache needs scale snapshots"
        k_scales = k_scales.at[:, ids].set(jnp.asarray(ks[:, :n]))
        v_scales = v_scales.at[:, ids].set(jnp.asarray(vs[:, :n]))
    return k_pages, v_pages, k_scales, v_scales


def quantize_kv_rows(x: Any, n_kv: int) -> tuple[Any, Any]:
    """Per-token-per-head symmetric int8 quantization of KV rows.

    ``x``: [..., Hkv*hd] float — returns (q8 [..., Hkv*hd] int8,
    scales [..., Hkv] fp32) with scale = amax over the head's channels /
    127 (1.0 for all-zero rows so dequant is exact).
    """
    lead = x.shape[:-1]
    hd = x.shape[-1] // n_kv
    xh = x.reshape(*lead, n_kv, hd).astype(jnp.float32)
    amax = jnp.max(jnp.abs(xh), axis=-1)  # [..., Hkv]
    scales = jnp.where(amax > 0, amax, 1.0) / 127.0
    q = jnp.clip(jnp.round(xh / scales[..., None]), -127, 127).astype(jnp.int8)
    return q.reshape(*lead, n_kv * hd), scales


def scatter_kv_chunk_q8(
    k_pages: Any,  # [L, P, page_size, Hkv*hd] int8
    v_pages: Any,
    k_scales: Any,  # [L, P, scale_rows, page_size] fp32
    v_scales: Any,
    k_new: Any,  # [B, C, Hkv, hd] float
    v_new: Any,
    page_table: Any,  # [B, max_pages]
    start_pos: Any,  # [B]
    n_valid: Any,  # [B]
    page_size: int,
    layer: Any,
    n_kv: int,
) -> tuple[Any, Any, Any, Any]:
    """Quantizing variant of ``scatter_kv_chunk``: int8 rows into the data
    pages, per-token-per-head scales into the scale pages. Same trash-page
    redirection; scale writes for trash lanes land in the trash page's
    scale block."""
    B, C = k_new.shape[:2]
    hd_fused = k_pages.shape[-1]
    i = jnp.arange(C)[None, :]
    pos = start_pos[:, None] + i
    logical = pos // page_size
    offset = pos % page_size
    phys = jnp.take_along_axis(page_table, logical, axis=1)
    valid = i < n_valid[:, None]
    phys = jnp.where(valid, phys, TRASH_PAGE)

    k_q, k_s = quantize_kv_rows(k_new.reshape(B, C, hd_fused), n_kv)
    v_q, v_s = quantize_kv_rows(v_new.reshape(B, C, hd_fused), n_kv)

    lay = jnp.broadcast_to(jnp.asarray(layer, jnp.int32), (B * C,))
    flat_phys = phys.reshape(-1)
    flat_off = offset.reshape(-1)
    k_pages = k_pages.at[lay, flat_phys, flat_off].set(
        k_q.reshape(B * C, hd_fused), mode="drop")
    v_pages = v_pages.at[lay, flat_phys, flat_off].set(
        v_q.reshape(B * C, hd_fused), mode="drop")
    # scale layout is [.., head_row, token_col]: ONE combined scatter per
    # array (a broadcast head-index column) — per-head scatters would each
    # rebuild the full scale buffer (the usual XLA scatter copy)
    heads = jnp.arange(n_kv)[None, :]  # [1, Hkv]
    k_scales = k_scales.at[lay[:, None], flat_phys[:, None], heads, flat_off[:, None]].set(
        k_s.reshape(-1, n_kv), mode="drop")
    v_scales = v_scales.at[lay[:, None], flat_phys[:, None], heads, flat_off[:, None]].set(
        v_s.reshape(-1, n_kv), mode="drop")
    return k_pages, v_pages, k_scales, v_scales


def gather_kv_any(
    k_pages: Any,
    v_pages: Any,
    k_scales: Any,
    v_scales: Any,
    page_table: Any,
    page_size: int,
    layer: Any,
    n_kv: int,
    dtype: Any = jnp.bfloat16,
) -> tuple[Any, Any]:
    """``gather_kv`` dispatching on the cache dtype — the ONE place the
    int8-vs-native READ choice lives for the jnp gather paths (the
    reference attention backend and the SP-segment prefix fold)."""
    if k_pages.dtype == jnp.int8:
        return gather_kv_q8(
            k_pages, v_pages, k_scales, v_scales, page_table, page_size,
            layer, n_kv, dtype=dtype,
        )
    return gather_kv(k_pages, v_pages, page_table, page_size, layer, n_kv)


def gather_kv_q8(
    k_pages: Any,  # [L, P, page_size, Hkv*hd] int8
    v_pages: Any,
    k_scales: Any,  # [L, P, scale_rows, page_size] fp32
    v_scales: Any,
    page_table: Any,  # [B, max_pages]
    page_size: int,
    layer: Any,
    n_kv: int,
    dtype: Any = jnp.bfloat16,
) -> tuple[Any, Any]:
    """Dequantizing variant of ``gather_kv`` (the jnp reference path for
    the int8 cache): returns dense [B, max_len, Hkv, hd] in ``dtype``."""
    B, max_pages = page_table.shape

    def deq(pages, scales):
        p_l = jax.lax.dynamic_index_in_dim(pages, layer, 0, keepdims=False)
        s_l = jax.lax.dynamic_index_in_dim(scales, layer, 0, keepdims=False)
        x = p_l[page_table]  # [B, MP, PS, Hkv*hd] int8
        s = s_l[page_table]  # [B, MP, SPAD, PS] fp32
        PS = x.shape[2]
        hd = x.shape[-1] // n_kv
        xh = x.reshape(B, max_pages, PS, n_kv, hd).astype(jnp.float32)
        s_t = s[:, :, :n_kv, :].transpose(0, 1, 3, 2)  # [B, MP, PS, Hkv]
        out = (xh * s_t[..., None]).astype(dtype)
        return out.reshape(B, max_pages * PS, n_kv, hd)

    return deq(k_pages, k_scales), deq(v_pages, v_scales)


def gather_kv(
    k_pages: Any,  # [L, P, page_size, Hkv*hd]
    v_pages: Any,
    page_table: Any,  # [B, max_pages]
    page_size: int,
    layer: Any,  # scalar int32
    n_kv: int,
) -> tuple[Any, Any]:
    """Gather one layer's pages for each sequence into a contiguous
    [B, max_len, Hkv, hd] view (max_len = max_pages * page_size). Reference
    path; the Pallas paged kernel reads pages in place instead."""
    B, max_pages = page_table.shape
    k_l = jax.lax.dynamic_index_in_dim(k_pages, layer, 0, keepdims=False)
    v_l = jax.lax.dynamic_index_in_dim(v_pages, layer, 0, keepdims=False)
    k = k_l[page_table]  # [B, max_pages, page_size, Hkv*hd]
    v = v_l[page_table]
    T = max_pages * page_size
    k = k.reshape(B, T, n_kv, k.shape[-1] // n_kv)
    v = v.reshape(B, T, n_kv, v.shape[-1] // n_kv)
    return k, v
