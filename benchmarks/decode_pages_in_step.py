"""A configuration's compiled decode step, a custom call at a time: the ONE
in-step probe (ROADMAP D16; PERF.md section 6, PRs 49 and 52).

A kernel alone does not predict what it reads inside the step (PR 48), so a
change to a one-token kernel — the append's slab and a window walk's block
(PR 49), the state update's tile (PR 52) — is judged here:
``engine.decode_step`` of ``perfbench/configs/<configuration>.json`` at the
file's engine options on seeded weights, every slot active at ``--context``
tokens on pages of its own — behind ``--shared-pages`` leading pages that every
row's table holds alike (the served system prompt's 31; 0, the default: no
shared head) — a window layer's table holding
the row's last window as the pager leaves it (``window / page + 1`` live pages
of ``+ 2`` columns, the coordinates compacted), ``--steps`` steps in one
``jax.profiler`` capture. One JSON line: the step's device time and, for every
custom call whose name holds ``--name`` (``paged``: the appends and the walks;
``ssm_state_step``, ``gdn_state_step``, ``moe_experts_step``), calls a step
and the mean time of one. ``--blocks`` gives a window walk's pages a block by
hand (comma-separated, a line each; 0 = the rule's), ``--tree`` times another
checkout's package (the parent commit unpacked somewhere under the repo) on
the same inputs. ``--context`` and ``--shared-pages`` take comma-separated
lists, a line a pair (the shorter list's last value repeats): ``--context
10780,14748 --shared-pages 0,31`` prices a shared head's pass as the difference
of two lines of one process. Runs on the chip only:

    chiprun -- python3 benchmarks/decode_pages_in_step.py phi-4-mini-flash-reasoning
    chiprun -- python3 benchmarks/decode_pages_in_step.py trinity-mini --blocks 0,6,18
    chiprun -- python3 benchmarks/decode_pages_in_step.py granite-4.0-h-small --name ssm_state_step

The script runs in no cell. It stays because the next change to a one-token
kernel is priced with it, parent against change, before a cell's runs are paid.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("configuration",
                    help="a file's name under perfbench/configs, without .json (or a path to such a file)")
    ap.add_argument("--name", default="paged",
                    help="the custom calls to time: those whose name holds this")
    ap.add_argument("--context", default="6000",
                    help="every row's tokens, the shared pages' included; a list: a line each")
    ap.add_argument("--shared-pages", default="0",
                    help="leading table columns on which every row holds the same pages")
    ap.add_argument("--blocks", default="0", help="a window walk's pages a block; 0 = the rule's")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--tree", default="", help="another checkout whose package is timed")
    ap.add_argument("--interpret", action="store_true",
                    help="a rehearsal off the chip (values only; give a tiny configuration)")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    if args.tree:
        sys.path.insert(0, str(Path(args.tree).resolve()))

    import jax
    import jax.numpy as jnp
    import numpy as np

    import chip_smoke
    from finchat_tpu.engine import engine as E
    from finchat_tpu.models.llama import init_params
    from finchat_tpu.ops import paged_attention
    from finchat_tpu.utils.config import EngineConfig
    from perfbench.models import adapter

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.interpret:
        print("decode_pages_in_step: a time comes from the chip only (chiprun -- ...)",
              file=sys.stderr)
        return 2
    path = Path(args.configuration)
    if path.suffix != ".json":
        path = ROOT / "perfbench/configs" / f"{args.configuration}.json"
    file = json.loads(path.read_text())
    c = adapter(file).program_config(file)
    cfg = EngineConfig(**file["engine"])
    params = init_params(c, jax.random.key(int(file.get("weights_seed", 0))))
    B, page = cfg.max_seqs, cfg.page_size
    rows = (jnp.ones((B,), bool), jnp.ones((B,)), jnp.ones((B,)), jnp.zeros((B,), jnp.int32))
    width = cfg.max_seq_len // page
    contexts = [int(x) for x in args.context.split(",")]
    shared_pages = [int(x) for x in args.shared_pages.split(",")]
    cases = [(contexts[min(i, len(contexts) - 1)], shared_pages[min(i, len(shared_pages) - 1)])
             for i in range(max(len(contexts), len(shared_pages)))]

    def leaves_of(context: int, shared: int) -> dict:
        """A state's host-made leaves: every row at ``context`` tokens, ``shared``
        leading pages the same for all of them (numpy: the step donates its state, so
        every line builds its own)."""
        own = -(-(context + args.steps + 2) // page) - shared
        assert shared * page <= context and shared + own <= width \
            and 1 + shared + B * own <= cfg.num_pages, "the contexts do not fit the pool"
        table = np.zeros((B, width), np.int32)
        table[:, :shared] = 1 + np.arange(shared)
        table[:, shared:shared + own] = 1 + shared + np.arange(B * own).reshape(B, own)
        leaves = dict(page_table=table,
                      last_tokens=np.random.RandomState(7).randint(0, c.vocab_size, size=B),
                      context_lens=np.full((B,), context, np.int32))
        if c.window:  # the row's last window: the token 20 into the last live page of window / page + 1
            held = c.window // page + 1
            at = (held - 1) * page + 20
            assert context >= at
            win = 1 + np.arange(B * held).reshape(B, held)
            leaves.update(win_table=np.pad(win, ((0, 0), (0, 1))).astype(np.int32),
                          win_gaps=np.full((B,), context - at, np.int32))
        return leaves

    the_rule = paged_attention._pages_per_block
    for pages in (int(p) for p in args.blocks.split(",")):
        if pages:
            paged_attention._pages_per_block = lambda *a, whole_table=False, pages=pages, **kw: (
                pages if whole_table else the_rule(*a, **kw))
        jax.clear_caches()  # the block is read while the step is traced
        for context, shared in cases:
            state = dataclasses.replace(
                E.create_state(c, cfg, width),
                **{k: jnp.asarray(v, jnp.int32) for k, v in leaves_of(context, shared).items()})
            box = {"state": state}

            def once():
                box["state"], tokens, *_ = E.decode_step(
                    params, box["state"], *rows, config=c, page_size=page,
                    attn_backend="pallas" if on_chip else "pallas-interpret", qm_backend="ref")
                return tokens

            once().block_until_ready()
            line = {"configuration": args.configuration, "tree": args.tree or ".",
                    "name": args.name, "context": context, "shared_pages": shared,
                    "window_block_pages": pages or "rule"}
            if on_chip:
                ops = chip_smoke.device_ops_us(once, args.steps)
                by_name = defaultdict(list)
                for name, us in ops:
                    if args.name in name:
                        by_name[name.lstrip("%")].append(us)
                started = time.perf_counter()
                for _ in range(args.steps):
                    tokens = once()
                tokens.block_until_ready()
                line.update(
                    wall_us_a_step=round((time.perf_counter() - started) / args.steps * 1e6, 1),
                    step_us=round(sum(us for _n, us in ops) / args.steps, 1),
                    calls_us_a_step=round(sum(sum(v) for v in by_name.values()) / args.steps, 1),
                    calls={name: [len(v) / args.steps, round(float(np.mean(v)), 2)]
                           for name, v in sorted(by_name.items())})
            print(json.dumps(line), flush=True)
        paged_attention._pages_per_block = the_rule
    return 0


if __name__ == "__main__":
    sys.exit(main())
