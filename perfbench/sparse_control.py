#!/usr/bin/env python3
"""perfbench/sparse_control.py — the readings a sparse-attention selection is held by.

    python3 perfbench/sparse_control.py --workload <cell> --seeds 2 --prompt-len 3072

Never part of a benchmark run, as ``control.py`` and ``state_control.py`` are
not: the builder of a configuration whose attention SELECTS its context (an
indexer's top-k) runs it once on the chip, at the cell's own widths, and
writes the readings into the configuration file (``selection_check``) and
PERF.md. It exists because ``correct.py``'s sequence — a prompt of 1.5 chunks
and 63 forced tokens, 447 tokens at the cells' options — is shorter than
``index_topk`` (2,048): there the selection is every token, and no run's
``correct`` can see it.

One process: the engine as ``build_app`` builds it (the same artifacts and
engine options; no warm-up, scheduler or agent), then for each seed a seeded
prompt of ``--prompt-len`` tokens (over ``index_topk``) and 63 forced tokens,

* through the split path: the prompt in ``prefill_chunk`` pieces
  (``engine.prefill``), then ``engine.decode`` a forced token at a time;
* through the ragged path: a prompt chunk a round as one row of the packed
  buffer, then the forced tokens as one-token rows, a second slot's prompt
  chunks riding in the first of those rounds (mixed rounds);

each against the adapter's blocked reference by ``correct.py``'s own rule and
the file's ``selection_check`` limits (its ``logits_tolerance`` where it has
none). Two controls, the reference with its
selection broken, judged the same way; both have to come out as not correct:
``no_selection`` (every context token attended) and ``unrotated_index`` (the
selection made by index queries and keys that were not rotated). A third
reading explains and does not control: ``rounded_index``, the reference with
its indexer's inputs rounded to bfloat16.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

CONTROLS = ("no_selection", "unrotated_index")
# no fault, an explanation: the float32 reference with its indexer's inputs
# rounded to bfloat16 — the level that the selection's own sensitivity sets
EXPLAINS = ("rounded_index",)
MIXED_ROUNDS = 2  # decode rounds of slot A that carry a prompt chunk of slot B


def ragged_path_logits(sched, prompt: list[int], forced: list[int]) -> list:
    """``(index into the compared positions, logits)`` pairs: the prompt's
    last position, then each forced token, all through ``engine.ragged_mixed``
    packed as the scheduler packs a round."""
    import jax.numpy as jnp

    from finchat_tpu.engine.kv_cache import pages_needed

    engine = sched.engine
    B, chunk = engine.engine_cfg.max_seqs, engine.engine_cfg.prefill_chunk
    slot_a, slot_b = sched.free_slots[-1], sched.free_slots[-2]
    n_pages = pages_needed(len(prompt) + len(forced) + 1, engine.page_size)
    owners = {"perfbench-sparse-a": slot_a, "perfbench-sparse-b": slot_b}
    pages = {o: sched.allocator.allocate(o, n_pages) for o in owners}
    chunks = [(a, prompt[a:a + chunk]) for a in range(0, len(prompt), chunk)]

    def one_round(rows):
        """rows: (slot, start, tokens) for a prompt chunk, (slot, None, None)
        for a one-token row that reads its token on the device."""
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
            jnp.asarray(np.asarray(packed, np.int32)), jnp.asarray(np.asarray(tok_row, np.int32)),
            jnp.asarray(row_slot), jnp.asarray(row_start), jnp.asarray(row_len),
            jnp.asarray(from_device), jnp.asarray(from_device), zeros_i, zeros, ones, zeros_i,
            jnp.zeros((B,), bool), zeros, ones, zeros_i, -1)
        return np.asarray(row_logits, np.float32)

    got = []
    try:
        for owner, slot in owners.items():
            engine.set_page_table_row(slot, pages[owner])
        for start, toks in chunks:
            last = one_round([(slot_a, start, toks)])[0]
        got.append((0, last))
        for k, token in enumerate(forced):
            engine.set_last_token(slot_a, token)
            rows = [(slot_a, None, None)]
            if k < MIXED_ROUNDS:  # B's chunk rides in front, as the scheduler packs it
                rows = [(slot_b, *chunks[k]), *rows]
            got.append((1 + k, one_round(rows)[len(rows) - 1]))
    finally:
        for owner, slot in owners.items():
            engine.reset_slot(slot)
            sched.allocator.free(owner, pages[owner])
    return got


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--prompt-len", type=int, default=3072)
    ap.add_argument("--first-seed", type=int, default=2147485000)
    args = ap.parse_args()

    import jax

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.kv_cache import PageAllocator
    from finchat_tpu.serve.app import _load_model_artifacts
    from finchat_tpu.utils.runtime import enable_compile_cache
    from perfbench import correct
    from perfbench.cells import load_cell
    from perfbench.models import adapter
    from perfbench.server import app_config

    cell = load_cell(args.workload)
    model = adapter(cell.config)
    topk = int(cell.config.get("index_topk", 0))
    if not topk:
        print(f"perfbench: {cell.config_name} selects nothing (no index_topk)", file=sys.stderr)
        return 2
    if args.prompt_len <= topk:
        print(f"perfbench: --prompt-len {args.prompt_len} is not over index_topk {topk}: the "
              "selection would be every token", file=sys.stderr)
        return 2
    if not cell.rehearsal and jax.default_backend() != "tpu":
        print("perfbench: the control of a cell is read on the chip only", file=sys.stderr)
        return 2
    enable_compile_cache()
    work = ROOT / ".perfbench_work"
    work.mkdir(exist_ok=True)
    cfg = app_config(cell.config_name, cell.config, work_dir=work,
                     answer_cap=int(cell.traffic["answer_cap"]))
    config, params, _tokenizer, mesh = _load_model_artifacts(cfg)
    engine = InferenceEngine(config, params, cfg.engine, mesh=mesh)
    sched = SimpleNamespace(engine=engine, free_slots=list(range(cfg.engine.max_seqs)),
                            allocator=PageAllocator(cfg.engine.num_pages))
    # the limits of a context over index_topk are the file's `selection_check`
    # where it has one (they stand between this script's own two readings)
    tol = correct.tolerance({"logits_tolerance": cell.config.get(
        "selection_check", cell.config.get("logits_tolerance", {}))})

    program, controls = [], {name: [] for name in CONTROLS + EXPLAINS}
    for i in range(max(args.seeds, args.control_seeds)):
        seed = args.first_seed + i
        tokens, positions = correct.seeded_tokens(cell.config, seed, args.prompt_len)
        prompt, forced = tokens[:args.prompt_len], tokens[args.prompt_len:]
        want, margins = model.reference_logits(params, tokens, cell.config, positions=positions)
        want, margins = np.asarray(want, np.float32), np.asarray(margins, np.float32)
        if i < args.seeds:
            split = correct._split_path_logits(sched, prompt, forced)
            ragged = ragged_path_logits(sched, prompt, forced)
            for path, rel, m in (
                    ("split", [correct.rel_rms(g, w) for g, w in zip(split, want)], margins),
                    ("ragged", [correct.rel_rms(g, want[j]) for j, g in ragged],
                     [margins[j] for j, _g in ragged])):
                program.append(correct._judge(rel, m, tol))
                print(f"program seed {seed} {path}: {json.dumps(program[-1])}", flush=True)
        if i < args.control_seeds:
            for name in CONTROLS + EXPLAINS:
                got, _ = model.reference_logits(params, tokens, cell.config,
                                                positions=positions, variant=name)
                got = np.asarray(got, np.float32)
                controls[name].append(correct._judge(
                    [correct.rel_rms(g, w) for g, w in zip(got, want)], margins, tol))
                print(f"control {name} seed {seed}: {json.dumps(controls[name][-1])}", flush=True)

    def over(rows, key, pick):
        values = [r[key] for r in rows if r[key] is not None]
        return pick(values) if values else None

    print(json.dumps({
        "workload": cell.name, "device": jax.devices()[0].device_kind,
        "prompt_len": args.prompt_len, "index_topk": topk, "limits": tol,
        "program": {"readings": len(program), "all_ok": all(r["ok"] for r in program),
                    "largest_median": over(program, "median_rel_rms", max),
                    "largest_worst": over(program, "worst_rel_rms", max)},
        "controls": {name: {"readings": len(rows), "any_ok": any(r["ok"] for r in rows),
                            "smallest_median": over(rows, "median_rel_rms", min),
                            "smallest_worst": over(rows, "worst_rel_rms", min)}
                     for name, rows in controls.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
