"""Latent (MLA) attention over the paged cache, with the indexer's selection.

A page of the first array holds one latent row a token, ``[c_kv | k_rope]``
(key and value at once, for every head); a page of the second the indexer's
key row. A query scores every context token with the indexer, keeps the exact
``topk`` largest (``select``: no approximation, no selection by page or block),
and runs the ABSORBED softmax over the kept rows alone (models/mla.py has the
algebra). Two forms realise the same selection:

- the GATHER form (one token a row): a stable sort of the scores with the
  tokens' pool addresses beside them, a gather of the first ``topk`` rows
  ``[B, topk, row]``, attention over them; plain ``jax.lax``;
- the MASK form: the k-th largest score by an exact bit search, a mask over a
  dense walk of the row's pages in blocks with a running softmax. A chunk of
  tokens a row (prefill, a ragged round's prompt rows) walks in plain
  ``jax.lax``, rows one after another, and a row without tokens costs
  nothing. One token a row on a kernel backend walks in ONE Pallas pass
  (``ops/paged_attention.py`` ``paged_latent_attention``: the paged kernel's
  walk — whole pages, the batch's shared head once for all rows' queries
  stacked), where the page table spans at most ``WALK_MAX_CONTEXTS``
  selections (``decode_form``: the walk's work grows with the context, the
  gather's with ``rows x topk``; PERF.md section 6, PR 41). The ``ref``
  backend, and a wider table, take the gather form. A ragged round's
  one-token rows take their form together (``packed_attention``).

The indexer's scores come in two forms too (``index_form``). STAGED: the index
keys of the whole table's pages copied out a row (``_take_pages``), then
``index_scores`` over them — the ``ref`` backend, the chunk forms, a ragged
round's rows. WALK (PR 43): one token a row of the decode step on a kernel
backend scores its row's LIVE index pages in ONE Pallas pass a layer
(``ops/paged_attention.py`` ``paged_index_scores``: the same walk with the
index keys as its source and the scores as its output, the shared head's keys
once); it fills nothing beyond a row's last live page, and ``select`` reads
scores only ``where(allowed, ., -inf)``. The selection is EXACT either way:
every token below the row's length scored by every head from the stored
values, float32 sums; the two differ by the order of a float32 head sum alone
(tests/test_latent_walk.py says how much that is).

Of tokens tied AT the k-th score both keep those at the lowest positions, so
the two select the same ``topk`` tokens always (tests/test_deepseek_v32.py,
tests/test_latent_walk.py; exact ties are everyday at a test's width — four
index heads are all negative under the ReLU one time in sixteen — and unheard
of at 64 heads).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import Array, lax

from finchat_tpu.ops.refs import NEG_INF

#: context tokens a step of the chunk form's walk brings in (whole pages)
WALK_BLOCK = 1024
#: the widest page table, in selections, whose one-token rows walk their pages
#: (``decode_form``). Alone on a v5e at 16 rows x 128 heads (PERF.md section 6, PR
#: 41): the gather form 0.743 ms a layer whatever the context; the walk 0.28-0.31
#: at contexts of 2.5-6 selections, 0.59 with EVERY row at 8; the lines cross
#: near 10. 8 is as far as it was measured (the served table's width)
WALK_MAX_CONTEXTS = 8


class LatentShape(NamedTuple):
    """The static widths a latent-attention call needs (``LlamaConfig``'s)."""
    kv_lora: int  # c_kv's width: the value part of a row
    topk: int  # the indexer's selection (0 = no indexer: every token)
    scale: float  # the softmax scale


def index_scores(idx_q: Array, idx_w: Array, keys: Array) -> Array:
    """``I[g, c, j] = sum_h w[g, c, h] ReLU(q[g, c, h] . k[g, j])`` in float32
    (``idx_w`` carries both scales). ``idx_q`` [G,C,Hi,Di], ``idx_w`` [G,C,Hi],
    ``keys`` [G,J,Di]."""
    s = jnp.einsum("gchd,gjd->gchj", idx_q, keys, preferred_element_type=jnp.float32)
    return jnp.einsum("gchj,gch->gcj", jax.nn.relu(s), idx_w)


def kth_largest(x: Array, k: int) -> Array:
    """The k-th largest value along the last axis of float32 ``x``, exact: a
    search over the 32 bits of the order-preserving integer key (32 counting
    passes; a sort of the axis costs 6 x as much at 256 x 16,384 on a v5e)."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    # float order as unsigned-integer order: flip all bits of a negative,
    # the sign bit of a positive
    key = lax.bitcast_convert_type(
        jnp.where(bits < 0, ~bits, bits | jnp.int32(-2 ** 31)), jnp.uint32)

    def bit(i, acc):
        cand = acc | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        count = jnp.sum((key >= cand[..., None]).astype(jnp.int32), axis=-1)
        return jnp.where(count >= k, cand, acc)

    kth = lax.fori_loop(0, 32, bit, jnp.zeros(x.shape[:-1], jnp.uint32))
    kth = lax.bitcast_convert_type(kth, jnp.int32)
    return lax.bitcast_convert_type(
        jnp.where(kth < 0, kth & jnp.int32(2 ** 31 - 1), ~kth), jnp.float32)


def select(scores: Array, allowed: Array, k: int) -> Array:
    """Of the ``allowed`` tokens, the ``k`` with the largest ``scores`` (all
    of them where fewer are allowed): a bool mask [..., J]."""
    if not k or k >= scores.shape[-1]:
        return allowed
    masked = jnp.where(allowed, scores, -jnp.inf)
    kth = kth_largest(masked, k)[..., None]
    above = masked > kth
    # of the tokens tied AT the k-th score, the lowest positions fill what is
    # left of k (the gather form's stable sort's rule): exactly k
    tied = allowed & (masked == kth)
    left = k - jnp.sum(above.astype(jnp.int32), axis=-1, keepdims=True)
    return above | (tied & (jnp.cumsum(tied.astype(jnp.int32), axis=-1) <= left))


def _pad_q(q: Array, width: int) -> Array:
    return jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, width - q.shape[-1])])


def attend_reference(q: Array, rows: Array, mask: Array, shape: LatentShape) -> Array:
    """Absorbed attention of ``q`` [G,C,H,R+r] over contiguous latent
    ``rows`` [G,J,W] where ``mask`` [G,C,J]: [G,C,H,R]. The dense reference."""
    s = jnp.einsum("gchr,gjr->gchj", _pad_q(q, rows.shape[-1]), rows,
                   preferred_element_type=jnp.float32) * shape.scale
    s = jnp.where(mask[:, :, None, :], s, NEG_INF)
    p = jnp.where(mask[:, :, None, :], jax.nn.softmax(s, axis=-1), 0.0)
    out = jnp.einsum("gchj,gjr->gchr", p.astype(rows.dtype), rows[..., :shape.kv_lora],
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _take_pages(pages: Array, layer: Array, ids: Array) -> Array:
    """``pages[layer, ids]`` with the tokens of the pages in a row: [..., n *
    page_size, W]."""
    got = pages[layer, ids]  # [..., n, page_size, W]
    return got.reshape(*ids.shape[:-1], -1, got.shape[-1])


def decode_form(backend: str, context: int, topk: int) -> str:
    """Which realisation a one-token call takes, read off the call: ``walk``
    (the mask form: ``paged_latent_attention`` walks the row's pages under the
    selection's mask) on a kernel backend where the page table spans at most
    ``WALK_MAX_CONTEXTS`` selections, else ``gather``. The walk's work grows
    with the context, the gather's with ``rows x topk``."""
    kept = min(topk or context, context)
    return "walk" if backend != "ref" and context <= WALK_MAX_CONTEXTS * kept else "gather"


def index_form(backend: str, packed: bool = False) -> str:
    """How a one-token call's indexer comes by its scores, read off the call:
    ``walk`` (``paged_index_scores``: the paged kernel's walk over the row's
    index pages, the shared head's keys once) on a kernel backend, else
    ``staged`` (``_take_pages`` + ``index_scores``: the keys of the whole
    table's pages copied out for every row, whatever the row's length). The
    walk is never the slower, so the table's width has no say. A ragged
    round's ``packed`` one-token rows stay staged with their chunk rows."""
    return "walk" if backend != "ref" and not packed else "staged"


def decode_attention(q: Array, idx_q: Array | None, idx_w: Array | None,
                     latent_pages: Array, index_pages: Array, layer: Array,
                     page_table: Array, kv_len: Array, live: Array, *,
                     page_size: int, shape: LatentShape, backend: str = "ref",
                     shared: tuple[Array, Array] | None = None,
                     packed: bool = False) -> tuple[Array, Array]:
    """One query a row (``q`` [B,H,R+r]) over the row's first ``kv_len``
    tokens (its own row already written), in the form ``decode_form`` reads
    off the call, the indexer's scores in the form ``index_form`` does
    (``shared``: ``shared_head``'s, for the walks; ``packed``: the rows are a
    ragged round's). Returns ``(o_latent [B,H,R], selected)``; ``live`` [B]
    rows count."""
    B = q.shape[0]
    J = page_table.shape[1] * page_size
    allowed = (jnp.arange(J)[None, :] < kv_len[:, None]) & live[:, None]
    k = min(shape.topk or J, J)
    selects = k < J
    if selects:
        with jax.named_scope("dsa_indexer"):
            if index_form(backend, packed) == "walk":
                from finchat_tpu.ops.paged_attention import paged_index_scores

                # beyond a row's tokens the walk leaves what it finds: only ``allowed`` scores count
                scores = paged_index_scores(
                    idx_q, idx_w, index_pages, page_table, jnp.where(live, kv_len, 0),
                    layer.reshape(1), shared, page_size=page_size,
                    interpret=backend == "pallas-interpret")
            else:
                keys = _take_pages(index_pages, layer, page_table)  # [B,J,Di]
                scores = index_scores(idx_q[:, None], idx_w[:, None], keys)[:, 0]
    if decode_form(backend, J, shape.topk) == "walk":
        from finchat_tpu.ops.paged_attention import paged_latent_attention

        kept = allowed
        if selects:
            with jax.named_scope("dsa_select"):
                kept = select(scores, allowed, k)
        with jax.named_scope("mla_attention"):
            out = paged_latent_attention(
                _pad_q(q, latent_pages.shape[-1]), latent_pages, kept, page_table,
                jnp.where(live, kv_len, 0), layer.reshape(1), shared, page_size=page_size,
                value_width=shape.kv_lora, scale=shape.scale,
                interpret=backend == "pallas-interpret")
        return out, jnp.sum(kept.astype(jnp.int32))
    if selects:
        with jax.named_scope("dsa_select"):
            # token j of a row lies in its page j // page_size at j % page_size:
            # its address in the layer's pool rides the sort as a second
            # operand (what lax.top_k's own iota does; a lookup of 32,768 page
            # ids behind the sort cost more than the sort: PERF.md section 5).
            # Stable, so of tied scores the lower positions come first
            where = (page_table[:, :, None] * page_size
                     + jnp.arange(page_size, dtype=page_table.dtype)).reshape(B, J)
            down, flat = lax.sort((-jnp.where(allowed, scores, -jnp.inf), where),
                                  dimension=1, num_keys=1, is_stable=True)
            kept, flat = down[:, :k] < jnp.inf, flat[:, :k]  # [B,k]
        with jax.named_scope("mla_attention"):  # reading the selected rows is attention's
            rows = latent_pages.reshape(latent_pages.shape[0], -1, latent_pages.shape[-1])[
                layer, flat]  # [B,k,W]
    else:
        kept = allowed
        with jax.named_scope("mla_attention"):
            rows = _take_pages(latent_pages, layer, page_table)
    with jax.named_scope("mla_attention"):
        out = attend_reference(q[:, None], rows, kept[:, None], shape)[:, 0]
    return out, jnp.sum(kept.astype(jnp.int32))


def chunk_attention(q: Array, idx_q: Array | None, idx_w: Array | None,
                    latent_pages: Array, index_pages: Array, layer: Array,
                    page_row: Array, q_pos: Array, q_valid: Array, *,
                    page_size: int, shape: LatentShape) -> tuple[Array, Array]:
    """A chunk of queries of ONE row (``q`` [C,H,R+r] at the row's compacted
    positions ``q_pos`` [C], ``q_valid`` [C]) over the row's pages
    ``page_row`` [max_pages]: the mask form, a walk in blocks of
    ``WALK_BLOCK`` tokens as far as the chunk's last position. Returns
    ``(o_latent [C,H,R], selected)``."""
    C, H = q.shape[:2]
    R = shape.kv_lora
    J = page_row.shape[0] * page_size
    per = max(1, min(WALK_BLOCK, J) // page_size)  # pages a block
    while page_row.shape[0] % per:
        per -= 1
    Jb = per * page_size
    n_blocks = (jnp.max(jnp.where(q_valid, q_pos, -1)) + Jb) // Jb  # to the last query
    allowed = (jnp.arange(J)[None, :] <= q_pos[:, None]) & q_valid[:, None]  # causal

    def block_pages(b: Array) -> Array:
        return lax.dynamic_slice_in_dim(page_row, b * per, per)

    if shape.topk and shape.topk < J:
        def score_block(b, scores):
            keys = _take_pages(index_pages, layer, block_pages(b))  # [Jb,Di]
            part = index_scores(idx_q[None], idx_w[None], keys[None])[0]
            return lax.dynamic_update_slice_in_dim(scores, part, b * Jb, axis=1)

        with jax.named_scope("dsa_indexer"):
            scores = lax.fori_loop(0, n_blocks, score_block,
                                   jnp.full((C, J), -jnp.inf, jnp.float32))
        with jax.named_scope("dsa_select"):
            mask = select(scores, allowed, shape.topk)
    else:
        mask = allowed
    q_wide = _pad_q(q, latent_pages.shape[-1])

    def walk_block(b, carry):
        m, norm, acc = carry
        rows = _take_pages(latent_pages, layer, block_pages(b))  # [Jb,W]
        on = lax.dynamic_slice_in_dim(mask, b * Jb, Jb, axis=1)[:, None, :]  # [C,1,Jb]
        s = jnp.einsum("chr,jr->chj", q_wide, rows,
                       preferred_element_type=jnp.float32) * shape.scale
        s = jnp.where(on, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(on, jnp.exp(s - m_new[..., None]), 0.0)
        fade = jnp.exp(m - m_new)
        acc = acc * fade[..., None] + jnp.einsum(
            "chj,jr->chr", p.astype(rows.dtype), rows[:, :R],
            preferred_element_type=jnp.float32)
        return m_new, norm * fade + jnp.sum(p, axis=-1), acc

    with jax.named_scope("mla_attention"):
        _, norm, acc = lax.fori_loop(
            0, n_blocks, walk_block,
            (jnp.full((C, H), NEG_INF, jnp.float32), jnp.zeros((C, H), jnp.float32),
             jnp.zeros((C, H, R), jnp.float32)))
        out = (acc / jnp.maximum(norm, 1e-30)[..., None]).astype(q.dtype)
    return out, jnp.sum(mask.astype(jnp.int32))


def rows_attention(q: Array, idx_q: Array | None, idx_w: Array | None,
                   latent_pages: Array, index_pages: Array, layer: Array,
                   page_rows: Array, start: Array, n_valid: Array, *,
                   page_size: int, shape: LatentShape, backend: str = "ref",
                   shared: tuple[Array, Array] | None = None) -> tuple[Array, Array]:
    """``q`` [N,C,H,R+r]: row ``n``'s ``n_valid[n]`` queries stand at the
    compacted positions ``start[n] ..``; their rows are written. One token a
    row takes ``decode_attention``'s form over all rows at once; a chunk the
    mask form a row at a time (a row without tokens is skipped). Returns
    ``(o_latent [N,C,H,R], selected)``."""
    N, C = q.shape[:2]
    kw = dict(page_size=page_size, shape=shape)
    if C == 1:
        out, selected = decode_attention(
            q[:, 0], None if idx_q is None else idx_q[:, 0],
            None if idx_w is None else idx_w[:, 0], latent_pages, index_pages, layer,
            page_rows, start + n_valid, n_valid > 0, backend=backend, shared=shared, **kw)
        return out[:, None], selected
    col = jnp.arange(C, dtype=jnp.int32)
    no_index = idx_q is None

    def one_row(args):
        q_n, iq_n, iw_n, pages_n, start_n, n = args
        return lax.cond(
            n > 0,
            lambda: chunk_attention(q_n, None if no_index else iq_n, None if no_index else iw_n,
                                    latent_pages, index_pages, layer, pages_n,
                                    start_n + col, col < n, **kw),
            lambda: (jnp.zeros((C, q.shape[2], shape.kv_lora), q.dtype), jnp.int32(0)))

    zeros = jnp.zeros((N,), jnp.int32)
    out, selected = lax.map(one_row, (q, zeros if no_index else idx_q,
                                      zeros if no_index else idx_w, page_rows, start, n_valid))
    return out, jnp.sum(selected)


def packed_attention(q: Array, idx_q: Array | None, idx_w: Array | None,
                     latent_pages: Array, index_pages: Array, layer: Array,
                     page_rows: Array, q_start: Array, start: Array, n_valid: Array, *,
                     width: int, page_size: int, shape: LatentShape,
                     backend: str = "ref") -> tuple[Array, Array]:
    """A ragged round's PACKED queries ``q`` [T,H,R+r]: row ``n``'s
    ``n_valid[n]`` (at most ``width``) tokens lie from ``q_start[n]`` on and
    stand at the compacted positions ``start[n] ..``. The rows of ONE token
    (the round's decode rows) take ``decode_attention``'s form together, off
    the packed buffer; only a row of more — a prompt's chunk — walks, a row at a time, on
    its ``width`` tokens sliced out of the buffer where they lie and written
    back there (regrouping every row to ``[rows, width]`` first costs a
    buffer of 0.6 GB a layer at 16 rows of 256 x 128 heads, most of it for
    rows of one token). Returns ``(o_latent [T,H,R], selected)``."""
    T, H = q.shape[:2]
    no_index = idx_q is None
    kw = dict(page_size=page_size, shape=shape)
    col = jnp.arange(width, dtype=jnp.int32)
    tail = lambda a: jnp.pad(a, [(0, width)] + [(0, 0)] * (a.ndim - 1))  # noqa: E731 — a slice of `width` never runs off the end
    q_pad, iq_pad, iw_pad = (None if a is None else tail(a) for a in (q, idx_q, idx_w))

    def walk(n, carry):
        def chunk(carry):
            out, selected = carry
            cut = lambda a: lax.dynamic_slice_in_dim(a, q_start[n], width)  # noqa: E731
            got, count = chunk_attention(
                cut(q_pad), None if no_index else cut(iq_pad), None if no_index else cut(iw_pad),
                latent_pages, index_pages, layer, page_rows[n], start[n] + col,
                col < n_valid[n], **kw)
            # the slice's tail belongs to the rows behind this one
            kept = jnp.where((col < n_valid[n])[:, None, None], got, cut(out))
            return lax.dynamic_update_slice_in_dim(out, kept, q_start[n], 0), selected + count

        return lax.cond(n_valid[n] > 1, chunk, lambda carry: carry, carry)

    out, selected = lax.fori_loop(
        0, page_rows.shape[0], walk,
        (jnp.zeros((T + width, H, shape.kv_lora), q.dtype), jnp.int32(0)))
    one = n_valid == 1
    first = jnp.minimum(q_start, T - 1)
    out_one, selected_one = decode_attention(
        q[first], None if no_index else idx_q[first], None if no_index else idx_w[first],
        latent_pages, index_pages, layer, page_rows, start + 1, one, backend=backend,
        packed=True, **kw)
    # a row that is not of one token writes into the padding behind the buffer
    out = out.at[jnp.where(one, first, T + width - 1)].set(out_one)
    return out[:T], selected + selected_one


def causal_attention(q: Array, rows: Array, idx_q: Array | None, idx_w: Array | None,
                     idx_k: Array | None, shape: LatentShape) -> tuple[Array, Array]:
    """The cache-less form over whole sequences (``forward`` without a
    cache: tests, a one-shot forward): ``q`` [B,S,H,R+r] over ``rows``
    [B,S,W], dense, the same selection. Returns ``(o_latent, selected)``."""
    S = q.shape[1]
    allowed = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool)), (q.shape[0], S, S))
    mask = allowed if idx_q is None else select(
        index_scores(idx_q, idx_w, idx_k), allowed, shape.topk)
    return attend_reference(q, rows, mask, shape), jnp.sum(mask.astype(jnp.int32))
