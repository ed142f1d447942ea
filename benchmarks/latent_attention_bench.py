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
gather's, by a line through the two batches.

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


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--blocks", default="1024:1048576,512:1048576,1024:524288,2048:1048576",
                    help="the walk at LATENT_BLOCK_TOKENS:LATENT_TILE_BYTES, the first the tree's own")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.paged_attention_alone import POOL_PAGES, contexts, page_table
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

    result = {"seed": args.seed, "device": jax.devices()[0].device_kind, "layers": layers,
              "steps": args.steps, "batches": {}}
    calls = args.steps * layers
    for name, ctx in batches.items():
        table = jnp.asarray(page_table(ctx, width, args.seed, 31))
        kv_len = jnp.asarray(ctx, jnp.int32)
        entry = {"context_tokens": int(ctx.sum()),
                 "distinct_tokens": int(ctx.sum()) - (ROWS - 1) * 31 * PAGE}
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
        result["batches"][name] = entry

    # the k-th largest of [16, 16384] float32 alone: 32 counting passes, or a sort
    scores = jax.random.normal(keys[0], (ROWS, width * PAGE), jnp.float32)
    def scoped(kth):
        def fn(x):
            with jax.named_scope("dsa_select"):
                return kth(x)
        return jax.jit(fn)

    for name, kth in {
            "kth_by_bit_search": lambda x: la.kth_largest(x, TOPK),
            "kth_by_sort": lambda x: jax.lax.sort(x, dimension=1)[:, -TOPK],
            "kth_by_top_k": lambda x: jax.lax.top_k(x, TOPK)[0][:, -1],
            "select_whole": lambda x: la.select(x, x > -3.0, TOPK).astype(jnp.int32)}.items():
        result[name + "_ms"] = timed(scoped(kth), scores, calls=args.steps)[0]["dsa_select"]

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
    print(json.dumps(result))
    out = Path("chiprun_out")
    out.mkdir(exist_ok=True)
    (out / f"latent_attention_bench_{args.seed}.json").write_text(json.dumps(result, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
