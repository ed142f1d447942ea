"""Latent attention's one-token call alone on the chip, at the shapes of the
cell ``deepseek-v32-report-saturated`` (PERF.md section 6, PR 41): what each
of ``ops/latent_attention.py`` ``decode_attention``'s two forms costs a layer.

16 rows, 128 heads, a latent row of 640 (576 padded), an index key of 128
under 64 index heads, ``index_topk`` 2,048, pages of 128, a page table of 128
entries (``max_seq_len`` 16,384), a pool of ``--layers`` layers (2) of 1,600
pages. Two batches: ``cell`` — contexts drawn between 5k and 12k tokens, the
first 31 pages (3,968 tokens) of every row physically shared, as in the
cell's capture — and ``full`` — every row at 16,384 tokens, the widest a row
of this table gets, where the walk's work is largest against the gather's.
Each form runs the whole call (the indexer's scores, the selection, the
attention) ``--steps`` times over the layers inside a ``jax.profiler``
capture; the times printed are device times by the scopes the benchmark
reads (``dsa_indexer``, ``dsa_select``, ``mla_attention``), ms a layer. The
``walk`` form is also priced at other blocks of the kernel (``--blocks``:
``tokens a block : bytes of the logit tile``), and the two ways to the k-th
score (32 counting passes, a values-only sort) alone. The crossover printed
is the context at which the walk's ``mla_attention + dsa_select`` passes the
gather's, by a line through the two batches. The INDEXER alone comes first,
its two forms side by side (``indexer``: ``staged`` — the whole table's keys
copied out, ``index_scores`` — and ``walk@<tokens a block>`` —
``paged_index_scores`` at the blocks of ``--index-blocks``): ``dsa_indexer``
ms a layer, the share of its bound (the keys on distinct pages once at 819
GB/s: what ``dsa_index_roofline.sat`` reads), and the walk's distance from
``index_scores`` on the columns below each row's length (PR 43).

    chiprun -- python3 benchmarks/latent_attention_bench.py [--seed N]

Runs on the chip only (off it: exit 2) and in no cell. It stays because the
rule ``decode_form`` chooses by (``WALK_MAX_CONTEXTS``) stands on its numbers.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

ROWS, HEADS, ROW, LATENT, ROPE, PAGE = 16, 128, 640, 512, 64, 128
INDEX_HEADS, INDEX_DIM, TOPK = 64, 128, 2048
SCOPES = ("dsa_indexer", "dsa_select", "mla_attention")


def scope_ms(trace_dir: str, calls: int) -> dict[str, float]:
    """Device ms a call by scope, from the capture."""
    from perfbench import xplane_scopes

    path = next(Path(trace_dir).rglob("*.xplane.pb"))
    paths = xplane_scopes.op_scope_paths(str(path))
    out = dict.fromkeys((*SCOPES, xplane_scopes.UNSCOPED), 0.0)
    loose: dict[str, float] = {}  # what runs under no scope, by operation
    for _dev, name, _kind, _start, dur in xplane_scopes.device_ops(path):
        scope = xplane_scopes.scope_of(paths.get(name), set(SCOPES))
        out[scope] += dur / 1e6 / calls
        if scope == xplane_scopes.UNSCOPED:
            loose[name[:80]] = loose.get(name[:80], 0.0) + dur / 1e6 / calls
    out["unscoped_ops"] = dict(sorted(loose.items(), key=lambda kv: -kv[1])[:4])
    return out


def report(result: dict, seed: int) -> int:
    print(json.dumps(result))
    out = Path("chiprun_out")
    out.mkdir(exist_ok=True)
    (out / f"latent_attention_bench_{seed}.json").write_text(json.dumps(result, indent=1))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--blocks", default="1024:1048576,512:1048576,1024:524288,2048:1048576",
                    help="the walk at LATENT_BLOCK_TOKENS:LATENT_TILE_BYTES, the first the tree's own")
    ap.add_argument("--index-blocks", default="2048,1024,512",
                    help="the indexer's walk at INDEX_BLOCK_TOKENS, the first the tree's own")
    ap.add_argument("--parts", default="indexer,forms,kth",
                    help="which of the script's three parts run")
    args = ap.parse_args()
    parts = args.parts.split(",")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.paged_attention_alone import (
        HBM_BYTES_PER_S, POOL_PAGES, contexts, page_table)
    from finchat_tpu.ops import latent_attention as la
    from finchat_tpu.ops import paged_attention as pa

    if jax.default_backend() != "tpu":
        print("latent_attention_bench: no TPU; a time comes only from the chip", file=sys.stderr)
        return 2

    layers, width = args.layers, 128
    keys = jax.random.split(jax.random.key(args.seed % (2 ** 31)), 5)
    latent = jax.random.normal(keys[0], (layers, POOL_PAGES, PAGE, ROW), jnp.bfloat16)
    latent = latent.at[..., LATENT + ROPE:].set(0)
    index = jax.random.normal(keys[1], (layers, POOL_PAGES, PAGE, INDEX_DIM), jnp.bfloat16)
    q = jax.random.normal(keys[2], (ROWS, HEADS, LATENT + ROPE), jnp.bfloat16)
    idx_q = jax.random.normal(keys[3], (ROWS, INDEX_HEADS, INDEX_DIM), jnp.bfloat16)
    idx_w = jax.random.normal(keys[4], (ROWS, INDEX_HEADS), jnp.float32)
    shape = la.LatentShape(LATENT, TOPK, (128 + ROPE) ** -0.5)
    batches = {"cell": contexts(args.seed), "full": np.full((ROWS,), width * PAGE)}

    def timed(fn, *operands, calls):
        fn(*operands).block_until_ready()
        with tempfile.TemporaryDirectory() as trace_dir:
            jax.profiler.start_trace(trace_dir)
            for _ in range(args.steps):
                out = fn(*operands)
            out.block_until_ready()
            jax.profiler.stop_trace()
            return scope_ms(trace_dir, calls), out

    def form(backend):
        @jax.jit
        def all_layers(latent, index, table, kv_len):
            def layer(i, acc):
                out, _n = la.decode_attention(
                    q, idx_q, idx_w, latent, index, i, table, kv_len,
                    jnp.ones((ROWS,), bool), page_size=PAGE, shape=shape, backend=backend)
                return acc + out.astype(jnp.float32)
            return jax.lax.fori_loop(0, layers, layer, jnp.zeros((ROWS, HEADS, LATENT)))
        return all_layers

    def indexer(backend):
        @jax.jit
        def all_layers(index, table, kv_len):
            def layer(i, acc):
                with jax.named_scope("dsa_indexer"):
                    if backend == "ref":
                        return acc + la.index_scores(
                            idx_q[:, None], idx_w[:, None], la._take_pages(index, i, table))[:, 0]
                    return acc + pa.paged_index_scores(
                        idx_q, idx_w, index, table, kv_len, i.reshape(1), page_size=PAGE)
            return jax.lax.fori_loop(0, layers, layer, jnp.zeros((ROWS, width * PAGE)))
        return all_layers

    result = {"seed": args.seed, "device": jax.devices()[0].device_kind, "layers": layers,
              "steps": args.steps, "batches": {}}
    calls = args.steps * layers
    for name, ctx in batches.items():
        table = jnp.asarray(page_table(ctx, width, args.seed, 31))
        kv_len = jnp.asarray(ctx, jnp.int32)
        entry = {"context_tokens": int(ctx.sum()),
                 "distinct_tokens": int(ctx.sum()) - (ROWS - 1) * 31 * PAGE}
        result["batches"][name] = entry
        if "indexer" in parts:
            bound_ms = entry["distinct_tokens"] * INDEX_DIM * 2 / HBM_BYTES_PER_S * 1e3
            below = jnp.arange(width * PAGE)[None] < kv_len[:, None]

            def priced(ms):
                return {"dsa_indexer_ms": ms["dsa_indexer"],
                        "share_of_bound": bound_ms / ms["dsa_indexer"]}

            ms, want = timed(indexer("ref"), index, table, kv_len, calls=calls)
            entry["index_bound_ms"], entry["indexer"] = bound_ms, {"staged": priced(ms)}
            own = pa.INDEX_BLOCK_TOKENS
            for block in args.index_blocks.split(","):
                pa.INDEX_BLOCK_TOKENS = int(block)
                jax.clear_caches()
                ms, got = timed(indexer("pallas"), index, table, kv_len, calls=calls)
                entry["indexer"][f"walk@{block}"] = {
                    **priced(ms),
                    "max_abs_diff_below_kv_len": float(jnp.abs(jnp.where(below, got - want, 0)).max()),
                    "largest_score": float(jnp.abs(jnp.where(below, want, 0)).max())}
            pa.INDEX_BLOCK_TOKENS = own
        if "forms" not in parts:
            continue
        entry["gather"], want = timed(form("ref"), latent, index, table, kv_len, calls=calls)
        for block in args.blocks.split(","):
            pa.LATENT_BLOCK_TOKENS, pa.LATENT_TILE_BYTES = (int(n) for n in block.split(":"))
            jax.clear_caches()
            try:
                entry[f"walk@{block}"], got = timed(form("pallas"), latent, index, table,
                                                    kv_len, calls=calls)
            except Exception as e:  # noqa: BLE001 — a block the compiler refuses is a finding
                entry[f"walk@{block}"] = {"refused": repr(e)[:400]}
                continue
            entry[f"walk@{block}"]["max_abs_diff_from_gather"] = float(jnp.abs(got - want).max())
        pa.LATENT_BLOCK_TOKENS, pa.LATENT_TILE_BYTES = (int(n) for n in
                                                args.blocks.split(",")[0].split(":"))

    # the k-th largest of [16, 16384] float32 alone: 32 counting passes, or a sort
    scores = jax.random.normal(keys[0], (ROWS, width * PAGE), jnp.float32)
    def scoped(kth):
        def fn(x):
            with jax.named_scope("dsa_select"):
                return kth(x)
        return jax.jit(fn)

    kth_ways = {
            "kth_by_bit_search": lambda x: la.kth_largest(x, TOPK),
            "kth_by_sort": lambda x: jax.lax.sort(x, dimension=1)[:, -TOPK],
            "kth_by_top_k": lambda x: jax.lax.top_k(x, TOPK)[0][:, -1],
            "select_whole": lambda x: la.select(x, x > -3.0, TOPK).astype(jnp.int32)}
    for name, kth in kth_ways.items() if "kth" in parts else ():
        result[name + "_ms"] = timed(scoped(kth), scores, calls=args.steps)[0]["dsa_select"]

    if "forms" not in parts:
        return report(result, args.seed)
    # the context at which the walk's attention + selection passes the gather's
    own = args.blocks.split(",")[0]
    cost = {f: [sum(result["batches"][b][f][s] for s in ("dsa_select", "mla_attention"))
                for b in ("cell", "full")] for f in ("gather", f"walk@{own}")}
    tokens = [result["batches"][b]["context_tokens"] / ROWS for b in ("cell", "full")]
    slope = [(c[1] - c[0]) / (tokens[1] - tokens[0]) for c in cost.values()]
    gap = cost["gather"][0] - cost[f"walk@{own}"][0]
    result["crossover_context_tokens"] = (
        tokens[0] + gap / (slope[1] - slope[0]) if slope[1] > slope[0] else None)
    result["cost_ms_attention_plus_select"] = cost
    return report(result, args.seed)


if __name__ == "__main__":
    sys.exit(main())
