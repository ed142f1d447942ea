"""Does a ragged round hang the chip? (PR 32: with a `lax.scan` over a period's
run of same-kind layers NESTED in the scan over periods, `ragged_mixed_step`
of `olmo-hybrid-7b` hung a v5e about one round in ten, in rounds of 128 or 256
tokens; `decode_step` never. Cause not found; the program has no such loop
any more. Run this again after a compiler upgrade, and before any change
that puts a loop over layers back inside the period scan.)

    chiprun --timeout 900 -- python3 benchmarks/ragged_round_soak.py scan 120 600 [configuration]

(``configuration``: a file of perfbench/configs by name, `olmo-hybrid-7b` if
left out; PR 34 ran `granite-4.0-h-small`, ten layers unrolled in the period's
body: 120 iterations and no hang.)

One prompt's two rounds (256 tokens, then 128) at the cell's size, `iters`
times, under a watchdog that dumps every thread's stack and exits after
`seconds`. Variants, one a process:
  scan     the program as it is
  unroll   gdn._chunked's scan over blocks unrolled
  flat     every lax.scan of models/llama.py and models/gdn.py unrolled (no loop at all)
"""
import faulthandler, json, sys, time
from pathlib import Path
faulthandler.dump_traceback_later(int(sys.argv[3]) if len(sys.argv) > 3 else 200, exit=True)
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import jax, jax.numpy as jnp, numpy as np
from jax import lax
from finchat_tpu.models import gdn, llama
variant, iters = sys.argv[1], int(sys.argv[2])


class Shim:
    """``lax`` with every scan unrolled."""
    def __getattr__(self, name):
        return getattr(lax, name)

    def scan(self, f, init, xs, **kw):
        return lax.scan(f, init, xs, unroll=True, **kw)


if variant in ("unroll", "flat"):
    gdn.lax = Shim()
if variant == "flat":
    llama.lax = Shim()

from finchat_tpu.engine.engine import InferenceEngine
from finchat_tpu.models.llama import init_params
from finchat_tpu.utils.config import EngineConfig
from finchat_tpu.utils.runtime import enable_compile_cache
from perfbench.models import adapter
enable_compile_cache()
name = sys.argv[4] if len(sys.argv) > 4 else "olmo-hybrid-7b"
file = json.loads(Path(f"perfbench/configs/{name}.json").read_text())
c = adapter(file).program_config(file)
cfg = EngineConfig(**file["engine"])
t0 = time.time()
engine = InferenceEngine(c, init_params(c, jax.random.key(0)), cfg, attn_backend="pallas")
print(variant, "engine", round(time.time() - t0, 1), "s", flush=True)
B = cfg.max_seqs
rng = np.random.RandomState(0)
slot = B - 1
engine.set_page_table_row(slot, [7, 8, 9, 10])

def one_round(start, n):
    row_slot = np.full((B,), slot, np.int32)
    row_start, row_len = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
    row_start[0], row_len[0] = start, n
    packed = [int(t) for t in rng.randint(0, 100000, size=n)]
    T = engine.ragged_bucket(n)
    tok_row = [0] * n + [B] * (T - n); packed += [0] * (T - n)
    zeros, ones, zi = jnp.zeros((B,), jnp.float32), jnp.ones((B,), jnp.float32), jnp.zeros((B,), jnp.int32)
    no = jnp.zeros((B,), bool)
    out = engine.ragged_round(jnp.asarray(np.asarray(packed, np.int32)), jnp.asarray(np.asarray(tok_row, np.int32)),
        jnp.asarray(row_slot), jnp.asarray(row_start), jnp.asarray(row_len), no, no, zi, zeros, ones, zi)
    return np.asarray(out[2], np.float32)

for i in range(iters):
    one_round(0, 256)
    t = time.time()
    logits = one_round(256, 128)
    engine.reset_slot(slot)
    print(variant, "iteration", i, "T=128 round", round((time.time() - t) * 1e3, 1), "ms, finite", bool(np.isfinite(logits[0]).all()), flush=True)
print(variant, "PASSED", iters, flush=True)
