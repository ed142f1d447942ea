#!/usr/bin/env python3
"""perfbench/fault_control.py — a configuration's pieces, each left out on purpose.

    python3 perfbench/fault_control.py --workload <cell> --seeds 2

Never part of a benchmark run, as ``control.py`` and ``window_control.py`` are
not: the builder of a configuration whose adapter lists ``FAULTS`` (a piece of
the block left out or put where it does not belong: a sink, a partial
rotation, a rotation base, a scale) runs it once on the chip, at the cell's own
widths, and writes the readings into PERF.md. One process, no engine: the
seeded weights as ``build_app`` draws them, then for each seed
``correct.py``'s own sequence through the adapter's reference and through the
reference with each fault, judged by ``correct.py``'s rule and the file's
limits. Every fault has to come out as NOT correct: a limit that lets one
through does not hold the program to that piece. (``window_off`` needs a
sequence longer than the window to show at all cells' widths:
``window_control.py`` reads it on a prompt of three windows.)
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2147488000)
    args = ap.parse_args()

    import jax

    from finchat_tpu.serve.app import _load_model_artifacts
    from finchat_tpu.utils.runtime import enable_compile_cache
    from perfbench import correct
    from perfbench.cells import load_cell
    from perfbench.models import adapter
    from perfbench.server import app_config

    cell = load_cell(args.workload)
    model = adapter(cell.config)
    faults = getattr(model, "FAULTS", ())
    if not faults:
        print(f"perfbench: the adapter of {cell.config_name} lists no FAULTS", file=sys.stderr)
        return 2
    if not cell.rehearsal and jax.default_backend() != "tpu":
        print("perfbench: the control of a cell is read on the chip only", file=sys.stderr)
        return 2
    enable_compile_cache()
    work = ROOT / ".perfbench_work"
    work.mkdir(exist_ok=True)
    cfg = app_config(cell.config_name, cell.config, work_dir=work,
                     answer_cap=int(cell.traffic["answer_cap"]))
    _config, params, _tokenizer, _mesh = _load_model_artifacts(cfg)
    tol = correct.tolerance(cell.config)
    prompt_len = cfg.engine.prefill_chunk * 3 // 2  # correct.prompt_length's

    readings = {name: [] for name in faults}
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        tokens, positions = correct.seeded_tokens(cell.config, seed, prompt_len)
        want, margins = model.reference_logits(params, tokens, cell.config, positions=positions)
        want, margins = np.asarray(want, np.float32), np.asarray(margins, np.float32)
        for name in faults:
            got, _ = model.reference_logits(params, tokens, cell.config, positions=positions,
                                            fault=name)
            got = np.asarray(got, np.float32)
            readings[name].append(correct._judge(
                [correct.rel_rms(g, w) for g, w in zip(got, want)], margins, tol))
            print(f"fault {name} seed {seed}: {json.dumps(readings[name][-1])}", flush=True)

    def smallest(rows, key):
        values = [r[key] for r in rows if r[key] is not None]
        return min(values) if values else None

    print(json.dumps({
        "workload": cell.name, "device": jax.devices()[0].device_kind, "limits": tol,
        "prompt_len": prompt_len,
        "faults": {name: {"readings": len(rows), "any_ok": any(r["ok"] for r in rows),
                          "smallest_median": smallest(rows, "median_rel_rms"),
                          "smallest_worst": smallest(rows, "worst_rel_rms")}
                   for name, rows in readings.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
