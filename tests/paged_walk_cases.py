"""Inputs for the tests of the paged attention walk (ops/paged_attention.py),
shared by the float cache's files (tests/test_pallas_attention.py: the plain
walk; test_paged_walk_shared_head.py; test_paged_walk_packed_tile.py;
test_paged_walk_engine.py) and the int8 cache's (tests/test_kv_quant.py,
test_kv_quant_shared_head.py, test_kv_quant_packed_tile.py,
test_kv_quant_engine.py).

One call holds every edge of the walk as a row: no token, one token, one
short of / exactly on / one past a page and a block boundary, live pages that
are not a multiple of the block, a row filling the whole table. The page
table is ``width`` entries wide whatever the rows hold, and every entry at or
beyond a row's live pages points at a page that poisons whatever reads it
(NaN values; for the int8 cache NaN scales): a dead page read is a failed
test, not a slower one.

``SHARED_CASES`` are decode batches (C = 1) in which rows hold the same
physical pages at the head of their tables, as rows admitted on one prefix
entry do: the kernel's first pass reads such a head once for all of its rows
(``ops.paged_attention.shared_head`` says which it takes).

``PACKED_SHAPES`` are head counts at which several KV heads' query rows share
one 8-row softmax tile of the decode kernel (``_heads_per_tile``: one or two
query heads a KV head), ``PACKED_CASES`` the shared-head cases they run on.

Nearly all of a case's time here is the compile of its program (the kernel in
interpret mode: 8-16 s against a run of milliseconds), and the program is its
shapes: cases that differ only in the pages their rows hold take a pool of
one size (``walk_case``'s ``pool``: ``SHARED_POOL`` for the shared-head
cases), so those with as many rows run one compiled program.
"""

import jax
import jax.numpy as jnp
import numpy as np

# the kernels compile on the chip (FINCHAT_TESTS_TPU=1) and are interpreted here
INTERPRET = jax.default_backend() != "tpu"
ATOL = RTOL = 2e-5 if INTERPRET else 2e-2

PAGE_SIZE = 64
WIDTH = 16  # table entries a row: 1,024 tokens
BLOCK = 512  # ops.paged_attention.BLOCK_TOKENS: 8 pages of 64
EDGE_CONTEXTS = [0, 1, PAGE_SIZE - 1, PAGE_SIZE, PAGE_SIZE + 1, BLOCK - 1, BLOCK,
                 BLOCK + 1, BLOCK + 5 * PAGE_SIZE + 3, WIDTH * PAGE_SIZE]
# (group, C): every group at decode, every chunk width at group 4
SHAPES = [(1, 1), (4, 1), (8, 1), (4, 3), (4, 8), (4, 32)]
DEAD_PAGE = 1  # physical page 0 is the writers' trash page; both are poisoned
# (group, KV heads) at decode: Olmo-Hybrid's 30 x 1 (three whole tiles of 8
# heads and one of 6), one partial tile alone, exactly one tile, and two query
# heads a KV head (4 heads a tile: one whole, one of a single head)
PACKED_SHAPES = [(1, 30), (1, 3), (1, 8), (2, 5)]
# rows of unequal length on one head with rows that are no member; no head at
# all; inactive slots beside a set; a head of more than one block
PACKED_CASES = ["a_subset", "no_sharing", "inactive_rows", "a_long_head"]

P = PAGE_SIZE
# name: (contexts, heads = [(rows, pages of the first row's that they all hold)],
#        the rows shared_head takes, the pages it takes)
SHARED_CASES = {
    "all_rows": ([5 * P + 3, 7 * P, 4 * P + 1, 9 * P + 17], [((0, 1, 2, 3), 4)],
                 (0, 1, 2, 3), 4),
    "a_subset": ([3 * P, 5 * P + 9, 70, 4 * P + 2, 6 * P, 1], [((1, 3, 4), 3)],
                 (1, 3, 4), 3),
    # 1 x 5 pages saved against 2 x 3: the larger set is taken, the other walks alone
    "two_heads": ([6 * P + 1, 7 * P + 5, 4 * P, 5 * P + 30, 3 * P + 1],
                  [((0, 1), 5), ((2, 3, 4), 3)], (2, 3, 4), 3),
    "no_sharing": ([3 * P + 1, 2 * P, 5 * P + 7], [], (), 0),
    # a member ending exactly on the last shared page, and one token past it
    "ends_on_the_head": ([8 * P + 11, 4 * P, 4 * P + 1], [((0, 1, 2), 4)], (0, 1, 2), 4),
    # a member shorter than the others' common run cuts the run to its whole pages
    "a_short_member": ([8 * P + 11, 6 * P + 2, 2 * P + 10], [((0, 1, 2), 5)],
                       (0, 1, 2), 2),
    # inactive rows (table row all 0, no token) beside a set
    "inactive_rows": ([0, 5 * P - 1, 0, 4 * P + 8, 3 * P + 40], [((1, 3, 4), 3)],
                      (1, 3, 4), 3),
    # a head longer than one block of 8 pages, its last block partial
    "a_long_head": ([12 * P + 5, 11 * P + 40, WIDTH * P], [((0, 1, 2), 11)],
                    (0, 1, 2), 11),
}


# physical pages of the largest shared-head case, and the two every pool leads with
SHARED_POOL = 2 + max(sum(-(-n // PAGE_SIZE) for n in case[0]) for case in SHARED_CASES.values())


def walk_case(group, C, *, quantized=False, contexts=EDGE_CONTEXTS, width=WIDTH,
              page_size=PAGE_SIZE, n_kv=2, head_dim=32, seed=0, heads=(), pool=0):
    """Returns ``(q, sources, page_table, q_offset, kv_len, layer, k_dense,
    v_dense)``: ``sources`` is ``(k_pages, v_pages)`` or, quantized, ``(k_pages,
    v_pages, k_scales, v_scales)`` with layer 1 of 2 filled; the dense pair
    ``[B, width*page_size, n_kv, head_dim]`` is what the oracle attends to
    (for the int8 cache: the dequantized values). ``heads`` lists ``(rows,
    pages)``: those rows hold the first row's first ``pages`` physical pages
    (as many as each has live) and so its tokens there; a row without a token
    then has a table row of zeros, as a slot that was never admitted. The
    pool holds ``pool`` physical pages where the rows need fewer."""
    rng = np.random.RandomState(seed)
    B, hd_fused = len(contexts), n_kv * head_dim
    live = [-(-n // page_size) for n in contexts]
    num_phys = max(2 + sum(live), pool)
    phys = rng.permutation(np.arange(2, num_phys))  # shuffled, as under churn
    table = np.full((B, width), DEAD_PAGE, np.int32)
    dense = np.zeros((2, B, width * page_size, hd_fused), np.float32)  # k, v
    pages = np.zeros((2, 2, num_phys, page_size, hd_fused), np.float32)  # k/v, layer
    used = 0
    for b, n in enumerate(contexts):
        table[b, :live[b]] = phys[used:used + live[b]]
        used += live[b]
        dense[:, b, :n] = rng.randn(2, n, hd_fused)
    if quantized:
        ints = rng.randint(-127, 128, size=dense.shape).astype(np.float32)
        tok_scales = rng.uniform(0.004, 0.012, size=(2, B, width * page_size, n_kv))
    own = np.ones((B, width), bool)  # the row fills this page of its table
    if heads:
        table[[b for b, n in enumerate(contexts) if n == 0]] = 0
    for rows, n_pages in heads:
        for b in rows[1:]:
            held, n = min(n_pages, live[b]), min(contexts[b], n_pages * page_size)
            table[b, :held], own[b, :held] = table[rows[0], :held], False
            for values in (dense, *((ints, tok_scales) if quantized else ())):
                values[:, b, :n] = values[:, rows[0], :n]
    if quantized:  # integers and per-token-per-head scales; dense = dequantized
        valid = np.arange(width * page_size)[None, :] < np.asarray(contexts)[:, None]
        ints *= valid[None, :, :, None]
        dense = (ints.reshape(2, B, -1, n_kv, head_dim)
                 * tok_scales[..., None]).reshape(dense.shape).astype(np.float32)
        spad = -(-n_kv // 8) * 8
        scales = np.ones((2, 2, num_phys, spad, page_size), np.float32)
        scales[:, :, :2] = np.nan
    stored = ints if quantized else dense
    for b in range(B):
        for p in np.flatnonzero(own[b, :live[b]]):
            span = slice(p * page_size, (p + 1) * page_size)
            pages[:, 1, table[b, p]] = stored[:, b, span]
            if quantized:  # [k/v, tokens, n_kv] -> [k/v, n_kv, tokens]
                scales[:, 1, table[b, p], :n_kv] = tok_scales[:, b, span].transpose(0, 2, 1)
    if quantized:
        pages[:, :, :2] = 127
        sources = (*jnp.asarray(pages, jnp.int8), *jnp.asarray(scales))
    else:
        pages[:, :, :2] = np.nan
        sources = tuple(jnp.asarray(pages))
    kv_len = np.asarray(contexts, np.int32)
    q = jnp.asarray(rng.randn(B, C, n_kv * group, head_dim), jnp.float32)
    k_dense, v_dense = (jnp.asarray(d.reshape(B, -1, n_kv, head_dim)) for d in dense)
    return (q, sources, jnp.asarray(table), jnp.asarray(np.maximum(kv_len - C, 0)),
            jnp.asarray(kv_len), jnp.asarray([1], jnp.int32), k_dense, v_dense)


def assert_matches_reference(out, ref, contexts=EDGE_CONTEXTS, atol=2e-5, rtol=2e-5):
    """Rows with tokens match the oracle; a row with none is exactly zero
    (the oracle's fully masked softmax averages V there instead)."""
    out, ref = np.asarray(out), np.asarray(ref)
    assert np.isfinite(out).all(), "a poisoned (dead) page was read"
    for b, n in enumerate(contexts):
        if n == 0:
            np.testing.assert_array_equal(out[b], 0.0)
        else:
            np.testing.assert_allclose(out[b], ref[b], atol=atol, rtol=rtol)


def pallas_eqn(jaxpr):
    """The first ``pallas_call`` equation in a (nested) jaxpr."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns") and (found := pallas_eqn(inner)) is not None:
                return found
    return None
