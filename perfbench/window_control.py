#!/usr/bin/env python3
"""perfbench/window_control.py — the readings a sliding window and a shared cache are held by.

    python3 perfbench/window_control.py --workload <cell> --seeds 2 --prompt-len 1536

Never part of a benchmark run, as ``control.py``, ``state_control.py`` and
``sparse_control.py`` are not: the builder of a configuration with
sliding-window layers runs it once on the chip, at the cell's own widths, and
writes the readings into PERF.md. It exists because ``correct.py``'s sequence —
a prompt of 1.5 chunks and 63 forced tokens, 447 tokens at the cells' options —
is shorter than the window (512): there a window layer attends every token,
its pages never slide, and no run's ``correct`` can see either.

One process: the engine as ``build_app`` builds it (the same artifacts and
engine options; no warm-up, scheduler or agent), then for each seed a seeded
prompt of ``--prompt-len`` tokens (three windows) and 63 forced tokens through
the split path (``engine.prefill`` in ``prefill_chunk`` pieces, then
``engine.decode`` a token at a time) and through the ragged path
(``sparse_control.ragged_path_logits``: a prompt chunk a round, then one-token
rows, a second slot's chunks riding in the first of those rounds), each against
the adapter's reference by ``correct.py``'s own rule and the file's limits.
Two controls, the reference with a fault made on purpose, judged the same way;
both have to come out as not correct: ``window_off`` (the sliding layers attend
every token: a mask that keeps too much, a page list that never slides) and
``cross_own`` (each cross layer reads keys and values of its OWN input where
the model reads the ONE full-attention layer's).
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

CONTROLS = ("window_off", "cross_own")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--prompt-len", type=int, default=1536)
    ap.add_argument("--first-seed", type=int, default=2147486000)
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
    from perfbench.sparse_control import ragged_path_logits

    cell = load_cell(args.workload)
    model = adapter(cell.config)
    window = int(cell.config.get("sliding_window", 0))
    if not window:
        print(f"perfbench: {cell.config_name} has no sliding_window", file=sys.stderr)
        return 2
    if args.prompt_len < 3 * window:
        print(f"perfbench: --prompt-len {args.prompt_len} is under three windows of {window}: "
              "pages have to slide out more than once", file=sys.stderr)
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
    tol = correct.tolerance(cell.config)

    program, controls = [], {name: [] for name in CONTROLS}
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
            pager = engine.window_pager
            print(f"window pages in use after the check: {pager.pages_in_use} (a row's bound: "
                  f"{pager.per_row} a layer)", flush=True)
        if i < args.control_seeds:
            for name in CONTROLS:
                got, _ = model.reference_logits(params, tokens, cell.config,
                                                positions=positions, **{name: True})
                got = np.asarray(got, np.float32)
                controls[name].append(correct._judge(
                    [correct.rel_rms(g, w) for g, w in zip(got, want)], margins, tol))
                print(f"control {name} seed {seed}: {json.dumps(controls[name][-1])}", flush=True)

    def over(rows, key, pick):
        values = [r[key] for r in rows if r[key] is not None]
        return pick(values) if values else None

    print(json.dumps({
        "workload": cell.name, "device": jax.devices()[0].device_kind,
        "prompt_len": args.prompt_len, "sliding_window": window, "limits": tol,
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
