"""The touched-expert pass INSIDE a configuration's compiled decode step, by
how an expert is cut (PERF.md section 6, PR 48).

``ops/moe_step.py``'s pass alone (``chip_smoke.check_moe_at_cell_shape``) does
not predict what it reads inside the step: at Trinity-Mini's ``[2048, 2 x
1024]`` x 128 a tile of 512 columns read 3.4 % faster than 256 alone and 3.8 %
slower inside ``decode_step`` (and 6.8 % slower in the cell), the whole width
equal to 512 alone and 8.7 % under 256 in the step. So a tile is judged here:
``engine.decode_step`` of ``perfbench/configs/<configuration>.json`` at the
file's engine options on seeded weights, every slot active on a random last
token at a context of 40 (attention is small: what is timed is the pass among
the step's other operations and whatever XLA overlaps with it), ``--steps``
steps in one ``jax.profiler`` capture, once for each of ``--tiles`` (0 = what
``width_tile`` gives). One JSON line a tile: the step's device time, the mean
time of a ``moe_experts_step`` call, the rest of the step and the held experts
the step's routed layers touched (summed). Runs on the chip only:

    chiprun -- python3 benchmarks/moe_step_in_step.py trinity-mini --tiles 0,256,512
    chiprun -- python3 benchmarks/moe_step_in_step.py granite-4.0-h-small --tiles 0,128,768

The script runs in no cell. It stays because the next model of many small
experts is priced with it before it has a cell (ROADMAP S13 (i)).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("configuration",
                    help="a file's name under perfbench/configs, without .json (or a path to such a file)")
    ap.add_argument("--tiles", default="0", help="columns a grid step, comma-separated; 0 = the rule's")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--interpret", action="store_true",
                    help="a rehearsal off the chip (values only; give a tiny configuration)")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))

    import jax
    import jax.numpy as jnp
    import numpy as np

    import chip_smoke
    from finchat_tpu.engine import engine as E
    from finchat_tpu.models.llama import init_params
    from finchat_tpu.ops import moe_step
    from finchat_tpu.utils.config import EngineConfig
    from perfbench.models import adapter

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.interpret:
        print("moe_step_in_step: a time comes from the chip only (chiprun -- ...)", file=sys.stderr)
        return 2
    path = Path(args.configuration)
    if path.suffix != ".json":
        path = ROOT / "perfbench/configs" / f"{args.configuration}.json"
    file = json.loads(path.read_text())
    c = adapter(file).program_config(file)
    cfg = EngineConfig(**file["engine"])
    params = init_params(c, jax.random.key(int(file.get("weights_seed", 0))))
    B = cfg.max_seqs
    rows = (jnp.ones((B,), bool), jnp.ones((B,)), jnp.ones((B,)), jnp.zeros((B,), jnp.int32))
    the_rule = moe_step.width_tile
    for tile in (int(t) for t in args.tiles.split(",")):
        moe_step.width_tile = the_rule if not tile else (lambda *_shape, tile=tile: tile)
        jax.clear_caches()  # the tile is read while the step is traced
        state = dataclasses.replace(
            E.create_state(c, cfg, cfg.max_seq_len // cfg.page_size),
            last_tokens=jax.random.randint(jax.random.key(7), (B,), 0, c.vocab_size),
            context_lens=jnp.full((B,), 40, jnp.int32))
        box = {"state": state}

        def once():
            box["state"], tokens, _logits, box["experts"] = E.decode_step(
                params, box["state"], *rows, config=c, page_size=cfg.page_size,
                attn_backend="pallas" if on_chip else "pallas-interpret", qm_backend="ref")
            return tokens

        once().block_until_ready()
        line = {"configuration": args.configuration,
                "tile": tile or the_rule(c.hidden_dim, c.dim, jnp.dtype(c.dtype).itemsize),
                "experts_touched_a_step": int(box["experts"][0])}
        if on_chip:
            ops = chip_smoke.device_ops_us(once, args.steps)
            passes = [us for name, us in ops if "moe_experts_step" in name]
            total = sum(us for _name, us in ops)
            line.update(step_us=total / args.steps, pass_us=float(np.mean(passes)),
                        passes_a_step=len(passes) / args.steps,
                        rest_us=(total - sum(passes)) / args.steps,
                        experts_touched_a_step_at_the_end=int(box["experts"][0]))
        print(json.dumps(line), flush=True)
    moe_step.width_tile = the_rule
    return 0


if __name__ == "__main__":
    sys.exit(main())
