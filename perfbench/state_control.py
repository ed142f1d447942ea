#!/usr/bin/env python3
"""perfbench/state_control.py — the readings a recurrent state is held by.

    python3 perfbench/state_control.py --workload <cell> --seeds 6 --control-seeds 6

Never part of a benchmark run, as ``control.py`` is not: the builder of a
configuration whose layers carry a recurrent state runs it once on the chip,
at the cell's own size, and writes the readings into PERF.md. It exists
because ``correct.py`` compares logits, and logits need not show the state's
precision (PERF.md section 4: in ``granite-4.0-h-small`` the reference with
its state rounded to bfloat16 after every token stands CLOSER to the float32
reference than the sound bfloat16 program does).

One process: the engine as ``build_app`` builds it (the same artifacts and
engine options; no warm-up, scheduler or agent), then for each seed the two
paths of ``correct.check_logits`` on the check's own sequence — unchanged:
``engine.reset_slot`` is wrapped on this one instance so that the slot's
state is read (``engine.ssm_snapshot``) before the path gives the slot back —
and, layer by layer, against the adapter's ``reference_state`` over the same
tokens:

* ``state_distance``: RMS difference over the reference state's RMS;
* ``kept_mantissa_bits``: how many of float32's 23 mantissa bits the kept
  values use — what ``ssm_state_dtype`` states, read off the state itself.

The control is the reference with its state rounded to bfloat16 after every
token (``reference_state(state_dtype=bfloat16)``), read the same way.
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


def program_states(sched, prompt: list[int], forced: list[int]) -> dict:
    """The recurrent state ``[state layers, ...]`` that the check's slot holds
    after ``prompt + forced`` on each of ``correct.py``'s two paths (the
    ragged path's slot A; its slot B has seen the prompt alone)."""
    from perfbench import correct

    engine = sched.engine
    seen: dict[int, np.ndarray] = {}
    give_back = engine.reset_slot

    def read_then_reset(slot: int) -> None:
        seen[slot] = np.asarray(engine.ssm_snapshot(slot)[0])
        give_back(slot)

    engine.reset_slot = read_then_reset
    try:
        correct._split_path_logits(sched, prompt, forced)
        split = seen[sched.free_slots[-1]]
        correct._ragged_path_logits(sched, prompt, forced)
        return {"split": split, "ragged": seen[sched.free_slots[-1]]}
    finally:
        del engine.reset_slot  # the instance's wrapper; the class's method is back


def readings(model, got, want) -> dict:
    distance = model.state_distance(got, want)
    return {"distance_median": float(np.median(distance)), "distance_worst": max(distance),
            "kept_bits_fewest": min(model.kept_mantissa_bits(got))}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--control-seeds", type=int, default=6)
    ap.add_argument("--first-seed", type=int, default=2147484000)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

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
    if not hasattr(model, "reference_state"):
        print(f"perfbench: the adapter of {cell.config_name} has no reference_state",
              file=sys.stderr)
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
    prompt_len = correct.prompt_length(engine)

    program, control = [], []
    for i in range(max(args.seeds, args.control_seeds)):
        seed = args.first_seed + i
        tokens, _positions = correct.seeded_tokens(cell.config, seed, prompt_len)
        want = model.reference_state(params, tokens, cell.config)
        if i < args.seeds:
            for path, got in program_states(sched, tokens[:prompt_len],
                                            tokens[prompt_len:]).items():
                program.append(readings(model, got, want))
                print(f"program seed {seed} {path}: {json.dumps(program[-1])}", flush=True)
        if i < args.control_seeds:
            got = model.reference_state(params, tokens, cell.config, state_dtype=jnp.bfloat16)
            control.append(readings(model, got, want))
            print(f"control seed {seed}: {json.dumps(control[-1])}", flush=True)

    def over(rows, key, pick):
        return pick(r[key] for r in rows) if rows else None

    # the file's `state_check`: the fewest mantissa bits a kept state may use
    limit = cell.config.get("state_check", {}).get("kept_mantissa_bits_min")
    print(json.dumps({
        "workload": cell.name, "device": jax.devices()[0].device_kind,
        "state_dtype_kept": str(engine.state.ssm_state.dtype),
        "program": {"readings": len(program),
                    "all_ok": limit is None or all(r["kept_bits_fewest"] >= limit for r in program),
                    "largest_distance_median": over(program, "distance_median", max),
                    "largest_distance_worst": over(program, "distance_worst", max),
                    "fewest_kept_bits": over(program, "kept_bits_fewest", min)},
        "control": {"readings": len(control),
                    "any_ok": limit is not None and any(r["kept_bits_fewest"] >= limit
                                                        for r in control),
                    "smallest_distance_median": over(control, "distance_median", min),
                    "smallest_distance_worst": over(control, "distance_worst", min),
                    "most_kept_bits": over(control, "kept_bits_fewest", max)},
        "limit": limit}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
