"""Headline benchmark: continuous-batch decode throughput (tok/s/chip).

Measures the paged inference engine end-to-end — chunked prefill into the
paged KV cache, then timed batched decode steps (attention over paged KV,
in-jit sampling) — against the BASELINE north star of 2,000 decode tok/s/chip
(BASELINE.md; reference publishes no numbers of its own, SURVEY §6).

Prints ONE JSON line:
  {"metric": "decode_tok_s_per_chip", "value": N, "unit": "tok/s/chip",
   "vs_baseline": N / 2000, ...detail fields}

One process for each chip: the top-level process parses args and
orchestrates WITHOUT importing jax (a parent that has touched JAX holds the
chip, and a child that needs it then fails or hangs); the measurement runs
in a child process with a faulthandler watchdog that dumps stacks and exits
instead of hanging. A TPU attempt that fails or times out is a failure:
exit code != 0 and no result line. A CPU run gives counts and correctness
for the CI smokes and is never substituted for a chip measurement.

Modes:
  python bench.py                      # measure on the TPU; no TPU = failure
  python bench.py --platform cpu       # CPU only (the CI count/identity smokes)
  python bench.py --worker ...         # internal: run one measurement
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

BASELINE_TOK_S_PER_CHIP = 2000.0  # BASELINE.md north star

# Per-platform default workloads. TPU: the largest BASELINE config that fits
# one chip's HBM, at the north-star concurrency (64 sessions). CPU: the
# "mini" debug config so a --platform cpu run finishes in seconds.
DEFAULTS = {
    # page_size 256: the decode attention grid is (B, 1, max_pages) per
    # layer — bigger pages halve the grid-iteration overhead (~1 µs each on
    # v5e) at the cost of coarser allocation granularity
    "tpu": dict(preset="tinyllama-1.1b", batch=64, prompt_len=128, steps=128,
                warmup=8, page_size=256, max_seq_len=1024),
    "cpu": dict(preset="mini", batch=8, prompt_len=128, steps=16,
                warmup=2, page_size=128, max_seq_len=1024),
}


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--platform", choices=("auto", "tpu", "cpu"), default="auto",
                   help="auto/tpu = measure on the TPU (no TPU is a failure, "
                        "never a CPU number); cpu = the CI smokes")
    p.add_argument("--preset", default=None)
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--prompt-len", type=int, default=None)
    p.add_argument("--steps", type=int, default=None)
    p.add_argument("--warmup", type=int, default=None)
    p.add_argument("--page-size", type=int, default=None)
    p.add_argument("--max-seq-len", type=int, default=None)
    p.add_argument("--attn", choices=("pallas", "ref", "pallas-interpret"),
                   default=None, help="attention backend (default: resolve "
                   "FINCHAT_ATTN / platform in the worker)")
    p.add_argument("--quant", choices=("int8", "int4"), default=None,
                   help="serve int8/int4 weight-only quantized params "
                        "(models/quant.py); default bf16")
    p.add_argument("--quant-group", type=int, default=None,
                   help="int4 scale group size along K (0 = per-channel)")
    p.add_argument("--kv-quant", choices=("int8",), default=None,
                   help="int8 paged-KV cache (per-token-per-head scales); "
                        "default: model dtype")
    p.add_argument("--spec-tokens", type=int, default=None,
                   help="also measure the speculative verify step at this "
                        "draft width (engine/spec.py): cost per step and "
                        "the full-acceptance throughput envelope")
    p.add_argument("--decode-loop-sweep", action="store_true",
                   help="sweep the fused multi-step decode loop "
                        "(engine decode_loop_step) over --decode-loop-depths "
                        "instead of the headline measurement: tok/s, "
                        "device dispatches per token, and inter-token p99 "
                        "jitter per depth")
    p.add_argument("--decode-loop-depths", default="1,4,8",
                   help="comma-separated depths for --decode-loop-sweep")
    p.add_argument("--session-sweep", action="store_true",
                   help="multi-turn conversation benchmark of the session "
                        "KV cache (engine/session_cache.py): per-turn "
                        "prefill chunks dispatched and TTFT with the cache "
                        "off (cold, re-prefill the whole history) vs on "
                        "(resume from the offloaded KV), plus a greedy "
                        "output identity check")
    p.add_argument("--session-turns", type=int, default=4,
                   help="conversation turns for --session-sweep")
    p.add_argument("--retrieval-sweep", action="store_true",
                   help="CPU-runnable sweep of the batched retrieval plane "
                        "(embed/batcher.py + embed/index.py + agent overlap): "
                        "embed dispatches/query and batch occupancy over "
                        "concurrency x wait-window, plus end-to-end TTFT "
                        "through the real agent+scheduler with "
                        "retrieval_overlap off vs on (greedy outputs "
                        "asserted byte-identical)")
    p.add_argument("--retrieval-concurrency", default="1,2,4,8",
                   help="comma-separated concurrent-request counts for "
                        "--retrieval-sweep")
    p.add_argument("--retrieval-windows-ms", default="0,2,5",
                   help="comma-separated embed wait-windows (ms) for "
                        "--retrieval-sweep")
    p.add_argument("--tool-overlap-sweep", action="store_true",
                   help="CPU-runnable sweep of the tool-streaming plane "
                        "(ISSUE 9): paced decision decode x controlled "
                        "tool latency; gates overlap-on retrieval within "
                        "15%% of max(decode, tool), byte-identical final "
                        "answers on vs off, eager launch before decode "
                        "ends, zero leaked holds/slots/pages")
    p.add_argument("--tool-overlap-smoke", action="store_true",
                   help="tiny --tool-overlap-sweep variant for CI: two "
                        "grid points, fewer repeats, same gates")
    p.add_argument("--retrieval-smoke", action="store_true",
                   help="tiny --retrieval-sweep variant for CI: fewer "
                        "rounds/repeats, coalescing+identity checks only")
    p.add_argument("--mixed-sweep", action="store_true",
                   help="CPU-runnable benchmark of the unified mixed "
                        "prefill+decode step (engine mixed_step): greedy "
                        "decode streams run while a long prompt is "
                        "admitted mid-decode, mixed off (split: prefill "
                        "round + decode dispatch per iteration) vs on "
                        "(one ragged dispatch). Reports model dispatches "
                        "per coexist-iteration (2→1), decode inter-token "
                        "p50/p99 during the admission window, and asserts "
                        "greedy outputs byte-identical")
    p.add_argument("--mixed-smoke", action="store_true",
                   help="tiny --mixed-sweep variant for CI: fewer "
                        "episodes, fusion+identity gates only")
    p.add_argument("--ragged-sweep", action="store_true",
                   help="CPU-runnable benchmark of the packed ragged step "
                        "(ISSUE 10): spec decode, decode_loop fused tails, a "
                        "grammar-constrained stream, and a short-tail long "
                        "prompt coexisting — previously ALL demoted to the "
                        "split path. Reports model dispatches per "
                        "coexist-iteration (>=2 split -> ~1 ragged), "
                        "per-dispatch feature coverage, byte-identity, "
                        "warmup-variant collapse, and a zero-leak audit")
    p.add_argument("--ragged-smoke", action="store_true",
                   help="tiny --ragged-sweep variant for CI: fewer episodes, "
                        "shorter prompts")
    p.add_argument("--longctx-sweep", action="store_true",
                   help="bounded-KV long-context serving (ISSUE 15): a "
                        "100k-token ingest through the real scheduler with "
                        "SnapStream-style sink+window eviction — flat "
                        "inter-token latency and bounded page occupancy vs "
                        "the unbounded control, identity while the context "
                        "fits, and ring-prefill promotion (one fused "
                        "dispatch per coexist round, zero ring demotions)")
    p.add_argument("--longctx-smoke", action="store_true",
                   help="CI-gated --longctx-sweep (same 100k ingest, "
                        "fewer decode samples)")
    p.add_argument("--longctx-tokens", type=int, default=100_000,
                   help="ingest length for the longctx scenario")
    p.add_argument("--freerun-sweep", action="store_true",
                   help="CPU-runnable benchmark of the free-running device "
                        "loop (ISSUE 13): a loaded mini engine (decode "
                        "streams + long prompts admitted mid-decode) at "
                        "freerun_rounds 1/4/8 — captured multi-round "
                        "dispatches vs host-stepped rounds. Reports model "
                        "dispatches per ROUND via the scheduler-attributed "
                        "coexist counters (1.0 -> <1 at rounds >= 4), "
                        "inter-token p99 delta during the admission window, "
                        "byte-identity across every level, and a zero-leak "
                        "audit")
    p.add_argument("--freerun-smoke", action="store_true",
                   help="tiny --freerun-sweep variant for CI: rounds 1/4, "
                        "fewer episodes, dispatch-ratio+identity gates")
    p.add_argument("--chaos-sweep", action="store_true",
                   help="CPU-runnable chaos benchmark of the resilience "
                        "plane (ISSUE 5): greedy streams under injected "
                        "dispatch faults — breaker trip + engine rebuild "
                        "with byte-identical survivors, page-pressure "
                        "recompute preemption with zero failed streams, "
                        "and a fault-rate sweep reporting goodput, "
                        "rebuilds, preemptions, and recovery latency")
    p.add_argument("--chaos-smoke", action="store_true",
                   help="tiny --chaos-sweep variant for CI: the two "
                        "acceptance gates only (streams survive a rebuild "
                        "byte-identically; preempt/replay byte-identity "
                        "with zero failed streams)")
    p.add_argument("--chaos-rates", default="0.05,0.2",
                   help="comma-separated decode-fault probabilities for "
                        "the --chaos-sweep rate section")
    p.add_argument("--fleet-sweep", action="store_true",
                   help="CPU-runnable fleet chaos drill (ISSUE 6): N "
                        "engine replicas under the conversation-affinity "
                        "router, one killed mid-stream — in-flight streams "
                        "must drain to siblings and complete byte-"
                        "identical, the victim goes OUT and is respawned, "
                        "goodput ≥ (N-1)/N during the outage and 1.0 "
                        "after, and a migrated conversation resumes from "
                        "its handed-off session-cache bytes")
    p.add_argument("--fleet-smoke", action="store_true",
                   help="tiny --fleet-sweep variant for CI: same gates, "
                        "same drill (the drill IS the smoke — it is "
                        "CPU-sized already)")
    p.add_argument("--pod-sweep", action="store_true",
                   help="pod-scale multi-host drill (ISSUE 20): 2 simulated "
                        "hosts x 2 replicas under the partition-assignment "
                        "router with liaison heartbeats, the shared warm "
                        "fabric, and per-partition journals; kill -9 one "
                        "whole host mid-stream — goodput >= the surviving "
                        "host's partition share during the detection gap "
                        "and 1.0 after adoption, migrated conversations "
                        "resume warm byte-identical (fabric record AND "
                        "live-peer liaison pull both exercised), the "
                        "adopted journals preload the dedupe ring (no "
                        "double answer), and a no-liaison single-host "
                        "control is byte-identical with zero pod-counter "
                        "movement")
    p.add_argument("--pod-smoke", action="store_true",
                   help="tiny --pod-sweep variant for CI: same gates, "
                        "smaller request waves")
    p.add_argument("--disagg-sweep", action="store_true",
                   help="disaggregated prefill/decode + warm-fabric drill "
                        "(ISSUE 17): a prefill storm against a 2+2 pool "
                        "split — steady decode streams' inter-token p99 "
                        "must stay flat vs the same run's pre-storm window, "
                        "storm outputs byte-identical vs a mixed fleet, "
                        "every handoff counted, zero leaked slots/pages; "
                        "then a fabric-warm resume on a never-seen replica "
                        "with lower TTFT, fewer prefill chunks, identical "
                        "greedy output")
    p.add_argument("--disagg-smoke", action="store_true",
                   help="tiny --disagg-sweep variant for CI: same gates, "
                        "smaller storm")
    p.add_argument("--durability-sweep", action="store_true",
                   help="crash-restart + graceful-drain drill (ISSUE 7): a "
                        "real App over the memory broker with the answered-"
                        "message journal and session disk tier on; kill it "
                        "mid-stream, restart, redeliver — zero double "
                        "answers, byte-identical final answers, next turn "
                        "resumed from disk; then SIGTERM-drain with zero "
                        "slot/page leaks")
    p.add_argument("--durability-smoke", action="store_true",
                   help="CI variant of --durability-sweep (same drill, "
                        "smoke-sized)")
    p.add_argument("--quant-sweep", action="store_true",
                   help="CPU-runnable benchmark of the quantized serving "
                        "plane (ISSUE 14): bf16 vs int8-w vs int8-w+int8-KV "
                        "vs int4-w through the REAL scheduler — decode "
                        "tok/s, TTFT, page-pool capacity per HBM byte "
                        "(~2x at int8-KV), prefill-logit quality envelope "
                        "per mode, session offload->restore byte-identity "
                        "including the int8 scale planes, resumed-vs-cold "
                        "greedy identity (exact at fp32 scales), and "
                        "dispatches/round < 1 with freerun + int8-KV "
                        "composed; zero-leak audit")
    p.add_argument("--quant-smoke", action="store_true",
                   help="tiny --quant-sweep variant for CI: same gates, "
                        "fewer tokens")
    p.add_argument("--quantmatmul-smoke", action="store_true",
                   help="CI gate for the fused dequant-matmul kernels "
                        "(ISSUE 16): interpret-mode kernel-vs-ref parity "
                        "across the int8/int4 layout matrix, fused-routing "
                        "greedy stream byte-identity vs the inline-dequant "
                        "reference at fp32 through the REAL scheduler, "
                        "zero new compiled variants from the backend knob, "
                        "fused-dispatch metric attribution, and a "
                        "zero-leak audit")
    p.add_argument("--trace-overhead", action="store_true",
                   help="tracing-plane gate (ISSUE 12): traced vs untraced "
                        "decode throughput (< 2%% overhead), a schema-valid "
                        "Perfetto export for one traced request, and an "
                        "injected breaker trip producing a checksummed "
                        "flight-recorder dump with the tripped round's "
                        "dispatch spans")
    p.add_argument("--fleet-replicas", type=int, default=4,
                   help="replica count for --fleet-sweep")
    p.add_argument("--tpu-timeout", type=float, default=180.0,
                   help="seconds allowed for TPU backend INIT before the "
                        "child is declared hung (measurement gets "
                        "--measure-budget on top)")
    p.add_argument("--measure-budget", type=float, default=420.0,
                   help="seconds allowed for the measurement itself once "
                        "the backend is up")
    p.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    return p


def resolve_workload(args: argparse.Namespace, platform: str) -> dict:
    d = DEFAULTS[platform]
    return {k: getattr(args, k) if getattr(args, k) is not None else v
            for k, v in d.items()}


# --------------------------------------------------------------------------
# Worker: the only code path that imports jax.
# --------------------------------------------------------------------------

def run_worker(args: argparse.Namespace) -> int:
    import faulthandler

    # Backstop against a backend init that never returns: dump all stacks
    # to stderr and exit instead of hanging forever. Re-armed below once
    # init succeeds.
    init_budget = max(30.0, args.tpu_timeout - 10.0)
    faulthandler.dump_traceback_later(init_budget, exit=True)

    import jax

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")

    t0 = time.perf_counter()
    devices = jax.devices()
    init_s = time.perf_counter() - t0
    platform = devices[0].platform
    print(f"[bench] backend up in {init_s:.1f}s: {devices[0]}", file=sys.stderr, flush=True)
    if args.platform == "tpu" and platform != "tpu":
        print(f"[bench] wanted tpu, backend resolved to {platform!r}", file=sys.stderr)
        return 3
    from finchat_tpu.utils.runtime import enable_compile_cache

    enable_compile_cache()

    # Measurement can legitimately take a while (first jit compile 20-40s);
    # keep the watchdog armed but give it the measurement budget.
    faulthandler.cancel_dump_traceback_later()
    faulthandler.dump_traceback_later(max(60.0, args.measure_budget - 10.0), exit=True)

    work = resolve_workload(args, "tpu" if platform == "tpu" else "cpu")
    if args.trace_overhead:
        result = measure_trace_overhead()
    elif args.quant_sweep or args.quant_smoke:
        result = measure_quant_sweep(smoke=args.quant_smoke)
    elif args.quantmatmul_smoke:
        result = measure_quantmatmul_smoke()
    elif args.durability_sweep or args.durability_smoke:
        result = measure_durability_sweep(smoke=args.durability_smoke)
    elif args.fleet_sweep or args.fleet_smoke:
        result = measure_fleet_sweep(
            smoke=args.fleet_smoke, replicas=args.fleet_replicas
        )
    elif args.pod_sweep or args.pod_smoke:
        result = measure_pod_sweep(smoke=args.pod_smoke)
    elif args.disagg_sweep or args.disagg_smoke:
        result = measure_disagg_sweep(smoke=args.disagg_smoke)
    elif args.chaos_sweep or args.chaos_smoke:
        result = measure_chaos_sweep(
            smoke=args.chaos_smoke,
            rates=tuple(float(r) for r in args.chaos_rates.split(",")),
        )
    elif args.ragged_sweep or args.ragged_smoke:
        result = measure_ragged_sweep(smoke=args.ragged_smoke)
    elif args.longctx_sweep or args.longctx_smoke:
        result = measure_longctx_sweep(smoke=args.longctx_smoke,
                                       tokens=args.longctx_tokens)
    elif args.freerun_sweep or args.freerun_smoke:
        result = measure_freerun_sweep(smoke=args.freerun_smoke)
    elif args.mixed_sweep:
        result = measure_mixed_sweep(smoke=args.mixed_smoke)
    elif args.tool_overlap_sweep or args.tool_overlap_smoke:
        result = measure_tool_overlap_sweep(smoke=args.tool_overlap_smoke)
    elif args.retrieval_sweep:
        result = measure_retrieval_sweep(
            concurrency=tuple(int(c) for c in args.retrieval_concurrency.split(",")),
            windows_ms=tuple(float(w) for w in args.retrieval_windows_ms.split(",")),
            smoke=args.retrieval_smoke,
        )
    elif args.session_sweep:
        if args.page_size is None:
            # page granularity is the resume resolution: the headline 128
            # would swallow a whole short turn per page at sweep scale
            work["page_size"] = 32
        result = measure_session_sweep(
            attn=args.attn, quant=args.quant or "",
            quant_group=args.quant_group or 0,
            kv_quant=args.kv_quant or "", turns=args.session_turns, **work)
    elif args.decode_loop_sweep:
        depths = tuple(int(d) for d in args.decode_loop_depths.split(","))
        result = measure_decode_loop_sweep(
            attn=args.attn, quant=args.quant or "",
            quant_group=args.quant_group or 0,
            kv_quant=args.kv_quant or "", depths=depths, **work)
    else:
        result = measure(attn=args.attn, quant=args.quant or "",
                         quant_group=args.quant_group or 0,
                         kv_quant=args.kv_quant or "",
                         spec_tokens=args.spec_tokens or 0, **work)
    result["backend_init_s"] = round(init_s, 1)
    # provenance stamp, so a stale record is visibly stale. The checkout
    # may not be a git repository (the chip tool's copy is not): then the
    # record simply carries no commit
    result.setdefault(
        "captured_at", time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    )
    try:
        result.setdefault("commit", subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)), text=True,
            stderr=subprocess.DEVNULL,
        ).strip())
    except (OSError, subprocess.CalledProcessError):
        pass
    print(json.dumps(result), flush=True)
    return 0


def measure(preset: str, batch: int, prompt_len: int, steps: int, warmup: int,
            page_size: int, max_seq_len: int, attn: str | None,
            quant: str = "", quant_group: int = 0, kv_quant: str = "",
            spec_tokens: int = 0) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.kv_cache import pages_needed
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.ops.dispatch import attention_backend
    from finchat_tpu.utils.config import EngineConfig

    config = PRESETS[preset]
    attn = attn or attention_backend()
    if spec_tokens > 0:
        # fail before ANY device time is spent, not after the main timed
        # sections (the spec section needs this much sequence room)
        spec_T = 10 * (spec_tokens + 1)  # (n_warm + n_timed) * (Kd + 1)
        assert prompt_len + spec_T <= max_seq_len, (
            f"spec bench needs prompt_len + {spec_T} <= max_seq_len "
            f"({prompt_len} + {spec_T} > {max_seq_len})"
        )
    pages_per_seq = pages_needed(max_seq_len, page_size)
    engine_cfg = EngineConfig(
        max_seqs=batch,
        page_size=page_size,
        # every slot fully paged + trash page, with some slack
        num_pages=batch * pages_per_seq + 8,
        max_seq_len=max_seq_len,
        prefill_chunk=max(prompt_len, 128),
        kv_quant=kv_quant,
    )

    if quant:
        # leaf-at-a-time quantized init: the full bf16 tree for llama3-8b
        # (16 GB) would not fit one v5e chip's HBM alongside anything else
        from finchat_tpu.models.quant import init_quantized_llama_params

        params = init_quantized_llama_params(
            config, jax.random.key(0), mode=quant, group_size=quant_group)
    else:
        params = init_params(config, jax.random.key(0))
    engine = InferenceEngine(config, params, engine_cfg, attn_backend=attn,
                             quant=quant)

    # assign pages + prefill a random prompt into every slot — all slots
    # batched into one prefill_step round (one weights-read per chunk round
    # for the WHOLE batch; the round-3 serial path took 8.6 s for 64x128).
    # A throwaway warmup round triggers the one-time XLA compile (serving
    # pays it at startup via Engine.warmup, not per request), then slots are
    # reset and the steady-state prefill is timed.
    rng = np.random.default_rng(0)
    rows = {
        slot: list(range(1 + slot * pages_per_seq, 1 + (slot + 1) * pages_per_seq))
        for slot in range(batch)
    }
    engine.set_page_table_rows(rows)
    items = [
        (slot, rng.integers(1, config.vocab_size, size=prompt_len).tolist())
        for slot in range(batch)
    ]
    t_compile0 = time.perf_counter()
    engine.prefill_batch(items)
    np.asarray(engine.state.context_lens)  # host fetch = execution barrier
    prefill_compile_s = time.perf_counter() - t_compile0
    engine.reset_slots(list(rows))
    engine.set_page_table_rows(rows)
    # barrier on BOTH updated arrays: reset must not leak into the timed
    # region (a dependent device->host copy forces the whole chain)
    np.asarray(engine.state.context_lens)
    np.asarray(engine.state.page_table.ravel()[:1])
    t_prefill0 = time.perf_counter()
    engine.prefill_batch(items)
    np.asarray(engine.state.context_lens)
    prefill_s = time.perf_counter() - t_prefill0
    print(f"[bench] prefill {batch}x{prompt_len} in {prefill_s:.2f}s "
          f"(first-call incl. compile {prefill_compile_s:.1f}s, attn={attn})",
          file=sys.stderr, flush=True)

    active = jnp.ones((batch,), bool)
    temperature = jnp.full((batch,), 0.5, jnp.float32)
    top_p = jnp.ones((batch,), jnp.float32)
    top_k = jnp.zeros((batch,), jnp.int32)

    def run_decode_barriered(n_steps: int) -> float:
        """Barriered decode loop, returns elapsed seconds. Sync via host
        fetch of the sampled tokens (a [batch] int32 array): a
        device→host copy of the step output forces the whole dependent
        chain."""
        t0 = time.perf_counter()
        for _ in range(n_steps):
            tokens = engine.decode(active, temperature, top_p, top_k)
        np.asarray(tokens)
        return time.perf_counter() - t0

    run_decode_barriered(max(warmup, 1))  # compile + steady-state warmup

    elapsed = run_decode_barriered(steps)

    tok_s = batch * steps / elapsed

    # long-context datum (verdict r3 weak #8: the RAG workload is long-
    # context, the bench only measured ctx <= prompt_len + steps): refill
    # every slot to ~3/4 of max_seq_len and time decode there. Prefill
    # variants for the longer chunk count compile here (excluded from the
    # timed region like the main prefill). The budget reserves room for
    # BOTH the warmup and timed decode steps, which all append KV.
    long_steps = max(steps // 2, 8)
    long_warmup = max(warmup // 2, 1)
    long_prompt_len = min(
        max_seq_len - long_steps - long_warmup, 3 * max_seq_len // 4
    )
    # Optional sections below must not kill the headline: the driver runs
    # this unattended at round end, and a failure in a secondary datum
    # (fresh compile variants) would otherwise discard the
    # already-measured decode number.
    longctx = {}
    if long_prompt_len > prompt_len:
        try:
            engine.reset_slots(list(rows))
            engine.set_page_table_rows(rows)
            long_items = [
                (slot, rng.integers(1, config.vocab_size, size=long_prompt_len).tolist())
                for slot in range(batch)
            ]
            engine.prefill_batch(long_items)
            np.asarray(engine.state.context_lens)  # barrier (incl. compiles)
            run_decode_barriered(long_warmup)
            long_elapsed = run_decode_barriered(long_steps)
            longctx = {
                "longctx_prompt_len": long_prompt_len,
                "longctx_decode_steps": long_steps,
                "longctx_step_ms": round(1000 * long_elapsed / long_steps, 2),
                "longctx_tok_s": round(batch * long_steps / long_elapsed, 1),
            }
        except Exception as e:  # pragma: no cover - defensive, driver-run path
            print(f"[bench] longctx section failed: {e}", file=sys.stderr, flush=True)
            longctx = {"longctx_error": str(e)[:200]}

    spec = {}
    if spec_tokens > 0:
        try:
            # Speculative verify-step cost: the step's compute is SHAPE-fixed
            # (acceptance changes which tokens commit, not what runs), so
            # timing verify steps with replayed rollout drafts gives both the
            # per-step cost and the full-acceptance throughput envelope
            # batch*(Kd+1)/step. Acceptance itself is reported informationally:
            # the replayed drafts mostly accept, but bf16 near-ties can round
            # differently under the C=Kd+1 chunk than the C=1 rollout, so 100%
            # is not numerically guaranteed. Prompt-lookup hit rate on the RAG
            # workload decides where real traffic lands between decode_tok_s
            # and the envelope.
            Kd = spec_tokens
            n_warm, n_timed = 2, 8
            T = (n_warm + n_timed) * (Kd + 1)  # must match the spec_T precheck
            engine.reset_slots(list(rows))
            engine.set_page_table_rows(rows)
            engine.prefill_batch(items)
            active = jnp.ones((batch,), bool)
            z = jnp.zeros((batch,), jnp.float32)  # greedy
            o, zk = jnp.ones((batch,), jnp.float32), jnp.zeros((batch,), jnp.int32)
            rec = np.stack(
                [np.asarray(engine.decode(active, z, o, zk)) for _ in range(T)],
                axis=1,
            )  # [batch, T] the greedy continuation, replayed as drafts below
            engine.reset_slots(list(rows))
            engine.set_page_table_rows(rows)
            engine.prefill_batch(items)
            np.asarray(engine.state.context_lens)  # barrier before timing

            def verify_rounds(t0_step: int, n_steps: int) -> tuple[float, list]:
                counts = []
                t_start = time.perf_counter()
                for s in range(t0_step, t0_step + n_steps):
                    t = s * (Kd + 1)
                    _, n_emitted = engine.decode_spec(
                        active, jnp.asarray(rec[:, t:t + Kd]),
                        jnp.full((batch,), Kd, jnp.int32), z, o, zk,
                    )
                    counts.append(n_emitted)  # device arrays; no sync in loop
                np.asarray(counts[-1])  # execution barrier
                return time.perf_counter() - t_start, counts

            verify_rounds(0, n_warm)  # compile + steady
            spec_elapsed, counts = verify_rounds(n_warm, n_timed)
            # acceptance is meaningful only while a slot is ALIGNED with the
            # replay schedule: after its first rejection the slot's context
            # falls behind rec's positions and every later step trivially
            # emits ~1 — include each slot's steps up to and INCLUDING its
            # first rejection, exclude the misaligned tail
            counts_np = np.stack([np.asarray(c) for c in counts])  # [n_timed, batch]
            emitted_vals = []
            for b in range(batch):
                col = counts_np[:, b]
                rejects = np.flatnonzero(col < Kd + 1)
                end = (rejects[0] + 1) if rejects.size else len(col)
                emitted_vals.extend(col[:end])
            spec_ms = 1000 * spec_elapsed / n_timed
            spec = {
                "spec_tokens": Kd,
                "spec_verify_step_ms": round(spec_ms, 2),
                "spec_tok_s_full_accept": round(batch * (Kd + 1) / (spec_elapsed / n_timed), 1),
                # mean over aligned steps only, of Kd+1 possible
                "spec_mean_emitted": round(float(np.mean(emitted_vals)), 2),
            }
        except Exception as e:  # pragma: no cover - defensive, driver-run path
            print(f"[bench] spec section failed: {e}", file=sys.stderr, flush=True)
            spec = {"spec_error": str(e)[:200]}

    # vs_baseline honesty (VERDICT r4 weak #3): the 2000 tok/s/chip target
    # is DEFINED for llama3-8b. On the target preset the ratio is direct;
    # on any other model it is normalized by parameter count against an 8B
    # AT THE SAME QUANT — decode is weight-bandwidth-bound, so at matching
    # bytes/param the params ratio IS the bytes ratio, and the figure
    # answers "this bandwidth spent on a same-quant 8B would hit what
    # fraction of 2000 tok/s" — a tinyllama-1.1b rate is ~0.14x its face
    # value as a bf16-8B-equivalent. (Cross-quant comparison is NOT
    # attempted; the basis label pins the quant.)
    from finchat_tpu.models.llama import n_params

    if preset == "llama3-8b":
        vs_baseline = tok_s / BASELINE_TOK_S_PER_CHIP
        basis = "direct (target model)"
    else:
        ratio = n_params(config) / n_params(PRESETS["llama3-8b"])
        vs_baseline = tok_s * ratio / BASELINE_TOK_S_PER_CHIP
        basis = (f"normalized to a llama3-8b at matching quant "
                 f"({quant or 'bf16'}): params x{ratio:.3f}")

    return {
        "metric": "decode_tok_s_per_chip",
        "value": round(tok_s, 1),
        "unit": "tok/s/chip",
        "vs_baseline": round(vs_baseline, 3),
        "vs_baseline_basis": basis,
        "baseline_model": "llama3-8b",
        "model": preset,
        "attn": attn,
        "quant": quant or "bf16",
        "kv_quant": kv_quant or "off",
        "batch": batch,
        "prompt_len": prompt_len,
        "decode_steps": steps,
        "step_ms": round(1000 * elapsed / steps, 2),
        "prefill_s": round(prefill_s, 2),
        "prefill_tok_s": round(batch * prompt_len / prefill_s, 1),
        "prefill_compile_s": round(prefill_compile_s, 1),
        **longctx,
        **spec,
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
    }


def measure_decode_loop_sweep(
    preset: str, batch: int, prompt_len: int, steps: int, warmup: int,
    page_size: int, max_seq_len: int, attn: str | None,
    quant: str = "", quant_group: int = 0, kv_quant: str = "",
    depths: tuple = (1, 4, 8),
) -> dict:
    """Sweep the fused multi-step decode loop: for each depth K, time
    blocks of K decode iterations per device dispatch and report tok/s,
    the MEASURED device-dispatch count per generated token (counted at the
    engine call site, not derived), and the host-observed inter-token p99
    jitter — the K-token burst is a real latency tradeoff: tokens within a
    block arrive together, so the p99 inter-token gap grows toward one
    block time as K grows while dispatch overhead amortizes ~K×."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.kv_cache import pages_needed
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.ops.dispatch import attention_backend
    from finchat_tpu.utils.config import EngineConfig

    config = PRESETS[preset]
    attn = attn or attention_backend()
    max_K = max(depths)
    # every depth decodes the same token budget (rounded up to whole
    # blocks) from the same prefilled state
    steps = max(steps, 2 * max_K)
    pages_per_seq = pages_needed(max_seq_len, page_size)
    engine_cfg = EngineConfig(
        max_seqs=batch,
        page_size=page_size,
        num_pages=batch * pages_per_seq + 8,
        max_seq_len=max_seq_len,
        prefill_chunk=max(prompt_len, 128),
        kv_quant=kv_quant,
        decode_loop_depth=max_K,
    )
    if quant:
        from finchat_tpu.models.quant import init_quantized_llama_params

        params = init_quantized_llama_params(
            config, jax.random.key(0), mode=quant, group_size=quant_group)
    else:
        params = init_params(config, jax.random.key(0))
    engine = InferenceEngine(config, params, engine_cfg, attn_backend=attn,
                             quant=quant)

    rng = np.random.default_rng(0)
    rows = {
        slot: list(range(1 + slot * pages_per_seq, 1 + (slot + 1) * pages_per_seq))
        for slot in range(batch)
    }
    items = [
        (slot, rng.integers(1, config.vocab_size, size=prompt_len).tolist())
        for slot in range(batch)
    ]

    active = jnp.ones((batch,), bool)
    temperature = jnp.zeros((batch,), jnp.float32)  # greedy: EOS-free replay
    top_p = jnp.ones((batch,), jnp.float32)
    top_k = jnp.zeros((batch,), jnp.int32)

    def reset_and_prefill() -> None:
        engine.reset_slots(list(rows))
        engine.set_page_table_rows(rows)
        engine.prefill_batch(items)
        np.asarray(engine.state.context_lens)  # execution barrier

    from finchat_tpu.utils.metrics import METRICS

    def run_blocks(K: int, n_blocks: int) -> tuple[float, list, int]:
        """Dispatch+fetch n_blocks blocks of K tokens; returns (elapsed,
        per-token host arrival times, dispatch count). The fetch per block
        is the point: ONE device→host [K, batch] copy replaces K [batch]
        copies, and the arrival timeline exposes the burst jitter. The
        dispatch count is read from the ENGINE's dispatch-seam counter
        (finchat_decode_dispatches_total, bumped once per enqueued device
        program) rather than this loop's iteration count — an engine
        regression that fell back to K host-side steps per 'block' would
        show up here instead of being assumed away."""
        before = METRICS.get("finchat_decode_dispatches_total")
        arrivals: list = []
        t0 = time.perf_counter()
        for _ in range(n_blocks):
            if K == 1:
                block = np.asarray(engine.decode(active, temperature, top_p, top_k))
            else:
                block = np.asarray(
                    engine.decode_loop(active, temperature, top_p, top_k,
                                       eos_id=-1, depth=K)
                )
            arrivals.extend([time.perf_counter()] * K)
            assert block.size  # keep the fetch live
        elapsed = time.perf_counter() - t0
        dispatches = int(METRICS.get("finchat_decode_dispatches_total") - before)
        return elapsed, arrivals, dispatches

    sweep = []
    for K in depths:
        n_blocks = -(-steps // K)
        reset_and_prefill()
        run_blocks(K, max(warmup // K, 1))  # compile + steady-state
        elapsed, arrivals, dispatches = run_blocks(K, n_blocks)
        tokens_per_slot = n_blocks * K
        gaps = np.diff(np.asarray(arrivals))
        sweep.append({
            "decode_loop_depth": K,
            "tok_s": round(batch * tokens_per_slot / elapsed, 1),
            "block_ms": round(1000 * elapsed / n_blocks, 2),
            "dispatches": dispatches,
            "tokens_per_slot": tokens_per_slot,
            "dispatches_per_token": round(dispatches / tokens_per_slot, 4),
            "intertoken_p99_ms": round(
                1000 * float(np.quantile(gaps, 0.99)) if gaps.size else 0.0, 3
            ),
        })
        print(f"[bench] decode_loop K={K}: {sweep[-1]['tok_s']} tok/s, "
              f"{sweep[-1]['dispatches_per_token']} dispatches/token, "
              f"p99 jitter {sweep[-1]['intertoken_p99_ms']} ms",
              file=sys.stderr, flush=True)

    return {
        "metric": "decode_loop_sweep",
        "unit": "tok/s/chip",
        "model": preset,
        "attn": attn,
        "quant": quant or "bf16",
        "kv_quant": kv_quant or "off",
        "batch": batch,
        "prompt_len": prompt_len,
        "sweep": sweep,
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
    }


def measure_session_sweep(
    preset: str, batch: int, prompt_len: int, steps: int, warmup: int,
    page_size: int, max_seq_len: int, attn: str | None,
    quant: str = "", quant_group: int = 0, kv_quant: str = "",
    turns: int = 4,
) -> dict:
    """Multi-turn conversation benchmark of the session KV cache: one
    conversation whose every turn's prompt extends the previous turn's
    prompt + response (the multi-turn chatbot shape — reference
    main.py re-fetches and re-prefills the whole history per message),
    measured twice through the REAL scheduler: cache off (cold — prefill
    from token zero every turn) vs on (resume from the offloaded KV).
    Reports per-turn prefill chunks dispatched (the metric the cache
    exists to shrink: cold grows linearly with history, resumed stays
    ~flat at the new-suffix size) and asserts the two runs' greedy token
    streams are identical."""
    import asyncio

    import jax
    import numpy as np

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.kv_cache import pages_needed
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.ops.dispatch import attention_backend
    from finchat_tpu.utils.config import EngineConfig
    from finchat_tpu.utils.metrics import METRICS

    config = PRESETS[preset]
    attn = attn or attention_backend()
    suffix_len, n_new = 48, 16  # new user tokens / response tokens per turn
    chunk = 64
    total_len = prompt_len + turns * (suffix_len + n_new) + n_new
    max_seq_len = max(max_seq_len, total_len + page_size)
    pages_per_seq = pages_needed(max_seq_len, page_size)

    def run_conversation(session_cache_bytes: int):
        engine_cfg = EngineConfig(
            max_seqs=2, page_size=page_size,
            num_pages=2 * pages_per_seq + 8, max_seq_len=max_seq_len,
            prefill_chunk=chunk,
            session_cache=session_cache_bytes > 0,
            session_cache_bytes=session_cache_bytes,
            kv_quant=kv_quant,
        )
        if quant:
            from finchat_tpu.models.quant import init_quantized_llama_params

            params = init_quantized_llama_params(
            config, jax.random.key(0), mode=quant, group_size=quant_group)
        else:
            params = init_params(config, jax.random.key(0))
        engine = InferenceEngine(config, params, engine_cfg, attn_backend=attn,
                                 quant=quant)
        # eos_id -1: random-weight greedy streams must never stop early, so
        # every turn generates exactly n_new tokens and runs are comparable
        scheduler = ContinuousBatchingScheduler(engine, eos_id=-1)
        rng = np.random.default_rng(0)
        history = rng.integers(1, config.vocab_size, size=prompt_len).tolist()
        per_turn: list[dict] = []
        streams: list[list[int]] = []

        async def go():
            nonlocal history
            await scheduler.start()
            try:
                for t in range(turns):
                    prompt = history + rng.integers(
                        1, config.vocab_size, size=suffix_len
                    ).tolist()
                    chunks0 = METRICS.snapshot().get("finchat_prefill_seconds_count", 0)
                    t0 = time.perf_counter()
                    handle = await scheduler.submit(
                        f"turn-{t}-{session_cache_bytes}", prompt,
                        SamplingParams(temperature=0.0, max_new_tokens=n_new),
                        conversation_id="bench-conv",
                    )
                    tokens, ttft = [], None
                    while True:
                        event = await handle.events.get()
                        if event["type"] == "token":
                            if ttft is None:
                                ttft = time.perf_counter() - t0
                            tokens.append(event["token_id"])
                        elif event["type"] == "done":
                            break
                        else:
                            raise RuntimeError(f"turn {t} errored: {event}")
                    chunks1 = METRICS.snapshot().get("finchat_prefill_seconds_count", 0)
                    per_turn.append({
                        "turn": t,
                        "prompt_tokens": len(prompt),
                        "prefill_chunks": int(chunks1 - chunks0),
                        "ttft_ms": round(1000 * ttft, 1),
                    })
                    streams.append(tokens)
                    history = prompt + tokens
            finally:
                await scheduler.stop()

        asyncio.run(go())
        return per_turn, streams

    cold_turns, cold_streams = run_conversation(0)
    restored0 = METRICS.get("finchat_session_cache_restored_tokens_total")
    warm_turns, warm_streams = run_conversation(64 << 20)
    restored = int(METRICS.get("finchat_session_cache_restored_tokens_total") - restored0)

    identical = warm_streams == cold_streams
    saved = [c["prefill_chunks"] - w["prefill_chunks"]
             for c, w in zip(cold_turns, warm_turns)]
    for c, w in zip(cold_turns, warm_turns):
        print(f"[bench] session turn {c['turn']}: prefill chunks "
              f"{c['prefill_chunks']} cold -> {w['prefill_chunks']} resumed "
              f"(ttft {c['ttft_ms']} -> {w['ttft_ms']} ms)",
              file=sys.stderr, flush=True)
    return {
        "metric": "session_cache_sweep",
        "unit": "prefill chunks/turn",
        "model": preset,
        "attn": attn,
        "quant": quant or "bf16",
        "kv_quant": kv_quant or "off",
        "page_size": page_size,
        "prefill_chunk": chunk,
        "turns": turns,
        "turn_suffix_tokens": suffix_len,
        "new_tokens_per_turn": n_new,
        "cold": cold_turns,
        "resumed": warm_turns,
        "chunks_saved_per_turn": saved,
        "restored_tokens_total": restored,
        # the acceptance gates: every turn after the first dispatches
        # strictly fewer prefill chunks resumed than cold, byte-identically
        "turn2_plus_strictly_fewer": all(s > 0 for s in saved[1:]),
        "greedy_outputs_identical": identical,
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
    }


def measure_retrieval_sweep(
    concurrency: tuple = (1, 2, 4, 8), windows_ms: tuple = (0.0, 2.0, 5.0),
    smoke: bool = False,
) -> dict:
    """Benchmark the batched retrieval plane (ISSUE 3), CPU-runnable.

    Part 1 — microbatcher: for each (concurrent requests, wait window),
    fire the requests together through the EmbedMicrobatcher and report
    embed DISPATCHES PER QUERY (the coalescing figure of merit: 1.0 means
    every request paid its own device dispatch, 1/c means perfect
    coalescing) and mean batch occupancy, both read from the metrics the
    serving plane exports.

    Part 2 — retrieval/prefill overlap: the REAL agent + scheduler +
    retriever stack (stub tool decision forcing retrieval; mini decoder),
    one warm run then timed runs of the streaming path with
    ``retrieval_overlap`` off vs on. Reports median TTFT each way and
    asserts the greedy streamed text is byte-identical — the overlap must
    be a pure latency optimization.
    """
    import asyncio

    import jax
    import numpy as np

    from finchat_tpu.agent.graph import LLMAgent
    from finchat_tpu.embed.batcher import EmbedMicrobatcher
    from finchat_tpu.embed.encoder import EMBED_PRESETS, EmbeddingEncoder, init_bert_params
    from finchat_tpu.embed.index import DeviceVectorIndex
    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.generator import EngineGenerator, StubGenerator
    from finchat_tpu.engine.kv_cache import pages_needed
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.io.schemas import ChatMessage
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.models.tokenizer import ByteTokenizer, get_tokenizer
    from finchat_tpu.tools.retrieval import TransactionRetriever
    from finchat_tpu.utils.config import EngineConfig
    from finchat_tpu.utils.metrics import METRICS

    embed_cfg = EMBED_PRESETS["bge-tiny"]
    encoder = EmbeddingEncoder(
        embed_cfg, init_bert_params(embed_cfg, jax.random.key(0)), ByteTokenizer()
    )
    encoder.embed_batch(["warm the encode_batch variants"])  # compile

    rounds = 2 if smoke else 6
    queries = [f"spending on category {i} last month" for i in range(64)]

    async def run_cell(conc: int, window_ms: float) -> dict:
        batcher = EmbedMicrobatcher(encoder, window_ms=window_ms, max_batch=32)
        d0 = METRICS.get("finchat_embed_batch_dispatches_total")
        r0 = METRICS.get("finchat_embed_requests_total")
        t0 = time.perf_counter()
        for r in range(rounds):
            await asyncio.gather(
                *[batcher.embed_one(queries[(r * conc + i) % len(queries)])
                  for i in range(conc)]
            )
        elapsed = time.perf_counter() - t0
        await batcher.close()
        dispatches = METRICS.get("finchat_embed_batch_dispatches_total") - d0
        requests = METRICS.get("finchat_embed_requests_total") - r0
        return {
            "concurrency": conc,
            "window_ms": window_ms,
            "dispatches_per_query": round(dispatches / max(requests, 1), 3),
            "mean_batch_occupancy": round(requests / max(dispatches, 1), 2),
            "mean_embed_latency_ms": round(1000 * elapsed / rounds, 2),
        }

    micro = [
        asyncio.run(run_cell(c, w)) for w in windows_ms for c in concurrency
    ]
    for cell in micro:
        print(f"[bench] embed microbatch c={cell['concurrency']} "
              f"w={cell['window_ms']}ms: {cell['dispatches_per_query']} "
              f"dispatches/query, occupancy {cell['mean_batch_occupancy']}",
              file=sys.stderr, flush=True)
    coalescing_ok = all(
        cell["dispatches_per_query"] < 1.0
        for cell in micro
        if cell["concurrency"] >= 4 and cell["window_ms"] > 0
    )

    # ---- part 2: retrieval/prefill overlap TTFT through the real stack --
    # Sized so the full prompt (system + context + history + retrieved
    # block + query, byte tokenizer) FITS the engine budget: history
    # windowing would change the static prefix after the hold was taken
    # and every overlap run would fall back serially (testing nothing).
    config = PRESETS["mini"]
    page_size = 32
    max_seq_len = 1024
    pps = pages_needed(max_seq_len, page_size)
    n_rows = 64 if smoke else 512
    repeats = 3 if smoke else 7
    history_turns = 4 if smoke else 8
    max_new = 8

    now = time.time()
    rng = np.random.default_rng(0)
    index = DeviceVectorIndex(dim=embed_cfg.dim)
    seed_retriever = TransactionRetriever(encoder, index, now=lambda: now)
    seed_retriever.upsert_transactions(
        "alice",
        [f"PURCHASE #{i} ${rng.integers(1, 500)}.{rng.integers(0, 99):02d} "
         f"merchant-{i % 13}" for i in range(n_rows)],
        dates=[now - 3600.0 * i for i in range(n_rows)],
    )
    history = [
        ChatMessage(
            sender="UserMessage" if i % 2 == 0 else "AIMessage",
            message=f"turn {i}: thinking about budget and savings",
        )
        for i in range(history_turns)
    ]

    async def run_stream(agent) -> tuple[float, str]:
        t0 = time.perf_counter()
        ttft, text = None, []
        async for ev in agent.stream_with_status(
            "what did I spend at merchant-3?", "alice", "Savings goal: $10k.",
            history, conversation_id=None,
        ):
            if ev["type"] == "response_chunk":
                if ttft is None:
                    ttft = time.perf_counter() - t0
                text.append(ev["content"])
        return ttft, "".join(text)

    async def run_modes():
        # ONE engine + scheduler serves both modes: identical compiled
        # variants and warmed state, so the off/on comparison measures the
        # overlap, not compile-cache luck
        ecfg = EngineConfig(
            max_seqs=4, page_size=page_size, num_pages=4 * pps + 8,
            max_seq_len=max_seq_len, prefill_chunk=64, session_cache=False,
        )
        engine = InferenceEngine(config, init_params(config, jax.random.key(0)), ecfg)
        scheduler = ContinuousBatchingScheduler(engine, eos_id=-1)
        await scheduler.start()
        batcher = EmbedMicrobatcher(encoder, window_ms=2.0, max_batch=32)
        try:
            retriever = TransactionRetriever(
                encoder, index, now=lambda: now, batcher=batcher
            )
            generator = EngineGenerator(scheduler, get_tokenizer())
            results = {}
            for overlap in (False, True):
                agent = LLMAgent(
                    StubGenerator(
                        default='retrieve_transactions({"search_query": '
                                '"spending at merchant-3", "num_transactions": 6})'
                    ),
                    generator, retriever, "You are Penny, a financial assistant.",
                    "Decide retrieval.",
                    response_sampling=SamplingParams(
                        temperature=0.0, max_new_tokens=max_new
                    ),
                    today=lambda: "2026-08-03",
                    retrieval_overlap=overlap,
                )
                ttfts, text = [], None
                for _ in range(repeats + 1):  # first run warms compiles
                    ttft, out = await run_stream(agent)
                    assert text is None or text == out, "nondeterministic greedy run"
                    text = out
                    ttfts.append(ttft)
                results[overlap] = (ttfts[1:], text)
            return results
        finally:
            await batcher.close()
            await scheduler.stop()

    g0 = METRICS.get("finchat_partial_grafts_total")
    results = asyncio.run(run_modes())
    off_ttfts, off_text = results[False]
    on_ttfts, on_text = results[True]
    grafts = int(METRICS.get("finchat_partial_grafts_total") - g0)
    ttft_off = float(np.median(off_ttfts))
    ttft_on = float(np.median(on_ttfts))
    print(f"[bench] retrieval overlap TTFT: off {1000*ttft_off:.1f} ms -> "
          f"on {1000*ttft_on:.1f} ms (grafts={grafts}, repeats={repeats})",
          file=sys.stderr, flush=True)

    return {
        "metric": "retrieval_sweep",
        "unit": "dispatches/query, ttft ms",
        "smoke": smoke,
        "embed_preset": "bge-tiny",
        "index_rows": n_rows,
        "history_turns": history_turns,
        "microbatch": micro,
        "coalescing_ok": coalescing_ok,
        "ttft_ms_overlap_off": round(1000 * ttft_off, 1),
        "ttft_ms_overlap_on": round(1000 * ttft_on, 1),
        "ttft_off_ms_all": [round(1000 * t, 1) for t in off_ttfts],
        "ttft_on_ms_all": [round(1000 * t, 1) for t in on_ttfts],
        "overlap_ttft_improved": ttft_on < ttft_off,
        "overlap_grafts": grafts,
        "greedy_outputs_identical": on_text == off_text,
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
    }


def measure_tool_overlap_sweep(smoke: bool = False) -> dict:
    """Benchmark the tool-streaming plane (ISSUE 9), CPU-runnable.

    Workload: tool-using agent turns through the REAL agent + scheduler +
    EngineGenerator stack. The tool-decision decode is a scripted, paced
    chunk stream (total duration = the point's decode_s; the search_query
    argument commits 25% in — the shape of a real constrained decode that
    spends its remaining budget on the later arguments), and the retriever
    is deterministic with a controlled latency (tool_s). Each (decode_s,
    tool_s) point measures time-to-retrieval-complete and full end-to-end
    with ``tool_streaming`` off (serial: decode + tool) vs on (eager
    launch at the search_query commit point + response-prefix hold at
    name-commit).

    Gates (the ISSUE 9 acceptance):
    - overlap-on retrieval latency within 15% of max(decode, tool) at
      every point (serial pays decode + tool);
    - final answers byte-identical overlap-on vs overlap-off;
    - at least one eager launch lands BEFORE the decision decode ends
      (first-launch timestamp + a nonzero overlap-saved histogram);
    - zero leaked holds/slots/pages after the sweep (sanitizer audit).
    """
    import asyncio

    import jax
    import numpy as np

    from finchat_tpu.agent.graph import LLMAgent
    from finchat_tpu.analysis import sanitizers
    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.generator import EngineGenerator
    from finchat_tpu.engine.kv_cache import pages_needed
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.io.schemas import ChatMessage
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.models.tokenizer import get_tokenizer
    from finchat_tpu.utils.config import EngineConfig
    from finchat_tpu.utils.metrics import METRICS

    # decision-decode script: search_query (the launch-required arg)
    # commits at the end of piece 2/8 (25% of decode); the remaining
    # pieces decode num_transactions — a REFINE key, so its late commit
    # refines the in-flight launch instead of cancelling it. This is the
    # commit-point profile the overlap win depends on.
    pieces = [
        'retrieve_transactions({"search_query": ',
        '"spending at merchant-3"',
        ', ',
        '"num_tra',
        'nsactions"',
        ': ',
        '6',
        '})',
    ]
    commit_fraction = 2 / len(pieces)

    class ScriptedToolGenerator:
        """Paced decision decode: the scripted pieces over ``total_s``."""

        def __init__(self, total_s: float):
            self.total_s = total_s
            self.stream_ended_at = None

        async def stream(self, prompt, sampling, conversation_id=None,
                         deadline=None):
            delay = self.total_s / len(pieces)
            for piece in pieces:
                await asyncio.sleep(delay)
                yield piece
            self.stream_ended_at = time.perf_counter()

        async def generate(self, prompt, sampling, conversation_id=None,
                           deadline=None):
            return "".join([p async for p in self.stream(prompt, sampling)])

    class DelayedRetriever:
        """Deterministic rows behind a controlled tool latency."""

        def __init__(self, delay_s: float):
            self.delay_s = delay_s
            self.first_called_at = None

        async def __call__(self, args):
            if self.first_called_at is None:
                self.first_called_at = time.perf_counter()
            await asyncio.sleep(self.delay_s)
            limit = int(args.get("num_transactions") or 10)
            return [f"PURCHASE #{i} $1{i}.00 merchant-3" for i in range(limit)]

    # (decode_s, tool_s) grid: decode-bound and tool-bound points, chosen
    # so the 15% gate leaves >= ~150 ms headroom over the commit-point
    # floor (overlap can never beat commit_fraction*decode + tool) — the
    # fixed per-turn overhead (event pacing, the hold's prefill dispatches
    # riding the same loop) measures ~100 ms on a CPU host
    points = [(1.00, 0.25), (0.30, 1.50)]
    if not smoke:
        points += [(1.20, 0.60), (0.40, 2.00)]
    repeats = 2 if smoke else 4

    # the "tiny" debug preset keeps every engine dispatch ms-scale on CPU
    # so the paced decode/tool durations dominate the measurement (the
    # gate compares against NOMINAL max(decode, tool))
    config = PRESETS["tiny"]
    page_size = 32
    max_seq_len = 1024
    pps = pages_needed(max_seq_len, page_size)
    history = [
        ChatMessage(sender="UserMessage" if i % 2 == 0 else "AIMessage",
                    message=f"turn {i}: thinking about budget and savings")
        for i in range(2)
    ]

    async def run_turn(agent, tool_gen, retriever):
        t0 = time.perf_counter()
        t_retr, text = None, []
        async for ev in agent.stream_with_status(
            "what did I spend at merchant-3?", "alice", "Savings goal: $10k.",
            history, conversation_id=None,
        ):
            if ev["type"] == "retrieval_complete":
                t_retr = time.perf_counter() - t0
            elif ev["type"] == "response_chunk":
                text.append(ev["content"])
        return t_retr, time.perf_counter() - t0, "".join(text)

    async def run_sweep():
        ecfg = EngineConfig(
            max_seqs=4, page_size=page_size, num_pages=4 * pps + 8,
            max_seq_len=max_seq_len, prefill_chunk=128, session_cache=False,
        )
        engine = InferenceEngine(config, init_params(config, jax.random.key(0)), ecfg)
        scheduler = ContinuousBatchingScheduler(engine, eos_id=-1)
        await scheduler.start()
        rows = []
        try:
            generator = EngineGenerator(scheduler, get_tokenizer())
            for decode_s, tool_s in points:
                cell = {"decode_ms": round(1000 * decode_s),
                        "tool_ms": round(1000 * tool_s)}
                for streaming in (False, True):
                    tool_gen = ScriptedToolGenerator(decode_s)
                    retriever = DelayedRetriever(tool_s)
                    agent = LLMAgent(
                        tool_gen, generator, retriever,
                        "You are Penny, a financial assistant.",
                        "Decide retrieval.",
                        response_sampling=SamplingParams(
                            temperature=0.0, max_new_tokens=8
                        ),
                        today=lambda: "2026-08-03",
                        tool_streaming=streaming,
                    )
                    saved0 = METRICS.snapshot().get(
                        "finchat_tool_overlap_saved_seconds_sum", 0.0)
                    t_retrs, t_totals, text = [], [], None
                    eager = False
                    for _ in range(repeats + 1):  # first run warms compiles
                        retriever.first_called_at = None
                        t_retr, t_total, out = await run_turn(
                            agent, tool_gen, retriever)
                        assert t_retr is not None, "turn never retrieved"
                        assert text is None or text == out, \
                            "nondeterministic greedy run"
                        text = out
                        t_retrs.append(t_retr)
                        t_totals.append(t_total)
                        if (retriever.first_called_at is not None
                                and tool_gen.stream_ended_at is not None
                                and retriever.first_called_at
                                < tool_gen.stream_ended_at):
                            eager = True
                    saved = METRICS.snapshot().get(
                        "finchat_tool_overlap_saved_seconds_sum", 0.0) - saved0
                    mode = "on" if streaming else "off"
                    cell[f"retrieval_ms_{mode}"] = round(
                        1000 * float(np.median(t_retrs[1:])), 1)
                    cell[f"e2e_ms_{mode}"] = round(
                        1000 * float(np.median(t_totals[1:])), 1)
                    cell[f"text_{mode}"] = text
                    cell[f"eager_launch_{mode}"] = eager
                    cell[f"overlap_saved_s_{mode}"] = round(saved, 3)
                bound_ms = 1150 * max(decode_s, tool_s)  # the 15% gate
                cell["bound_ms"] = round(bound_ms, 1)
                cell["overlap_ok"] = cell["retrieval_ms_on"] <= bound_ms
                cell["outputs_identical"] = cell.pop("text_on") == cell.pop("text_off")
                rows.append(cell)
                print(f"[bench] tool overlap d={cell['decode_ms']}ms "
                      f"t={cell['tool_ms']}ms: retrieval off "
                      f"{cell['retrieval_ms_off']} -> on "
                      f"{cell['retrieval_ms_on']} (bound {cell['bound_ms']}, "
                      f"eager={cell['eager_launch_on']})",
                      file=sys.stderr, flush=True)
        finally:
            await scheduler.stop()
        leaks = sanitizers.scheduler_leak_report(scheduler)
        return rows, leaks

    h0 = METRICS.get("finchat_partial_holds_total")
    l0 = METRICS.get("finchat_tool_launches_total")
    c0 = METRICS.get("finchat_tool_speculative_cancels_total")
    rows, leaks = asyncio.run(run_sweep())
    return {
        "metric": "tool_overlap_sweep",
        "unit": "ms to retrieval_complete",
        "smoke": smoke,
        "commit_fraction": round(commit_fraction, 3),
        "sweep": rows,
        "overlap_within_15pct_of_max": all(r["overlap_ok"] for r in rows),
        "outputs_identical": all(r["outputs_identical"] for r in rows),
        "eager_launch_before_decode_end": all(
            r["eager_launch_on"] and r["overlap_saved_s_on"] > 0 for r in rows
        ),
        "tool_launches": int(METRICS.get("finchat_tool_launches_total") - l0),
        "speculative_cancels": int(
            METRICS.get("finchat_tool_speculative_cancels_total") - c0),
        "partial_holds": int(METRICS.get("finchat_partial_holds_total") - h0),
        "zero_leaks": leaks == [],
        "leak_report": leaks,
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
    }


def measure_mixed_sweep(smoke: bool = False) -> dict:
    """Benchmark the unified mixed prefill+decode step (ISSUE 4),
    CPU-runnable through the REAL scheduler.

    Workload: greedy decode streams run steady-state; once each has
    emitted a couple of tokens, a long multi-chunk prompt is submitted so
    its prefill coexists with the live decodes (the admission-stall case).
    Each episode's window runs from the long prompt's submission to its
    first token. Measured once with ``engine.mixed_step`` off (split path:
    one prefill round + one decode dispatch per scheduler iteration) and
    once on (one ragged mixed dispatch per iteration):

    - model dispatches per coexist-iteration, counted at the engine
      dispatch seams (finchat_prefill_seconds_count +
      finchat_decode_dispatches_total + finchat_mixed_dispatches_total
      over finchat_coexist_iterations_total) — the 2→1 headline;
    - the decode streams' host-observed inter-token p50/p99 inside the
      admission window — the latency the fusion exists to cut;
    - greedy byte-identity of every stream across the two modes.

    The identity check runs at fp32: a decode row computes at the ragged
    [rows, chunk] shape in mixed mode vs [max_seqs, 1] in split mode, and
    under bf16 a last-ulp difference in the KV written during a mixed
    round can flip a LATER near-tie argmax (the same chunk-width caveat
    verify_step documents — either stream is a valid greedy decode). fp32
    pins the math identity so a structural bug cannot hide behind rounding.
    """
    import asyncio
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.kv_cache import pages_needed
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.utils.config import EngineConfig
    from finchat_tpu.utils.metrics import METRICS

    config = dataclasses.replace(PRESETS["mini"], dtype=jnp.float32)
    page_size = 16
    chunk = 32
    n_dec = 3
    long_chunks = 6 if smoke else 10
    long_len = chunk * long_chunks
    dec_budget = 48 if smoke else 72
    long_budget = 4
    episodes = 2 if smoke else 3  # measured episodes (plus one warm one)
    max_seq_len = long_len + 2 * page_size + long_budget
    pps = pages_needed(max_seq_len, page_size)
    rng = np.random.default_rng(0)
    dec_prompts = [
        rng.integers(1, config.vocab_size, size=12 + 3 * i).tolist()
        for i in range(n_dec)
    ]
    long_prompt = rng.integers(1, config.vocab_size, size=long_len).tolist()
    window_keys = (
        "finchat_prefill_seconds_count",
        "finchat_decode_dispatches_total",
        "finchat_mixed_dispatches_total",
        "finchat_coexist_iterations_total",
    )

    def run(mixed: bool) -> dict:
        ecfg = EngineConfig(
            max_seqs=n_dec + 2, page_size=page_size,
            num_pages=(n_dec + 2) * pps + 8, max_seq_len=max_seq_len,
            prefill_chunk=chunk, mixed_step=mixed, session_cache=False,
        )
        engine = InferenceEngine(config, init_params(config, jax.random.key(0)), ecfg)
        engine.warmup()  # compiles excluded from every episode's window
        sched = ContinuousBatchingScheduler(engine, eos_id=-1)
        gaps: list = []
        win = {k: 0.0 for k in window_keys}

        async def drain(handle, out):
            while True:
                ev = await handle.events.get()
                if ev["type"] == "token":
                    out.append((time.perf_counter(), ev["token_id"]))
                elif ev["type"] == "done":
                    return
                else:
                    raise RuntimeError(str(ev))

        async def go():
            all_streams = []
            await sched.start()
            try:
                for ep in range(episodes + 1):  # episode 0 warms steady state
                    handles = [
                        await sched.submit(
                            f"dec{ep}-{i}", dec_prompts[i],
                            SamplingParams(temperature=0.0, max_new_tokens=dec_budget),
                        )
                        for i in range(n_dec)
                    ]
                    outs = [[] for _ in handles]
                    tasks = [asyncio.create_task(drain(h, o))
                             for h, o in zip(handles, outs)]
                    while any(len(o) < 2 for o in outs):
                        await asyncio.sleep(0.002)
                    snap0 = METRICS.snapshot()
                    t_submit = time.perf_counter()
                    lh = await sched.submit(
                        f"long{ep}", long_prompt,
                        SamplingParams(temperature=0.0, max_new_tokens=long_budget),
                    )
                    lo: list = []
                    ltask = asyncio.create_task(drain(lh, lo))
                    while not lo:
                        await asyncio.sleep(0.001)
                    snap1 = METRICS.snapshot()
                    t_first = lo[0][0]
                    await asyncio.gather(*tasks, ltask)
                    if ep == 0:
                        continue
                    for k in window_keys:
                        win[k] += snap1.get(k, 0) - snap0.get(k, 0)
                    for o in outs:
                        ts = [t for t, _ in o if t_submit <= t <= t_first]
                        gaps.extend(np.diff(ts).tolist())
                    all_streams.append(
                        [[t for _, t in o] for o in outs] + [[t for _, t in lo]]
                    )
                return all_streams
            finally:
                await sched.stop()

        streams = asyncio.run(go())
        iters = max(win["finchat_coexist_iterations_total"], 1.0)
        dispatches = (win["finchat_prefill_seconds_count"]
                      + win["finchat_decode_dispatches_total"]
                      + win["finchat_mixed_dispatches_total"])
        return {
            "streams": streams,
            "dpi": dispatches / iters,
            "window": {k: int(v) for k, v in win.items()},
            "gaps": gaps,
        }

    split = run(False)
    mixed = run(True)

    def pct(gaps: list, q: float) -> float:
        if not gaps:
            return 0.0
        return round(1000 * float(np.quantile(np.asarray(gaps), q)), 3)

    p99_split, p99_mixed = pct(split["gaps"], 0.99), pct(mixed["gaps"], 0.99)
    print(f"[bench] mixed sweep: dispatches/iteration "
          f"{split['dpi']:.2f} split -> {mixed['dpi']:.2f} mixed; admission "
          f"inter-token p99 {p99_split} -> {p99_mixed} ms",
          file=sys.stderr, flush=True)

    return {
        "metric": "mixed_sweep",
        "unit": "dispatches/iteration, inter-token ms",
        "smoke": smoke,
        "model": "mini (fp32 — see identity note in measure_mixed_sweep)",
        "prefill_chunk": chunk,
        "long_prompt_chunks": long_chunks,
        "decode_streams": n_dec,
        "episodes": episodes,
        "dispatches_per_iteration_split": round(split["dpi"], 3),
        "dispatches_per_iteration_mixed": round(mixed["dpi"], 3),
        "window_split": split["window"],
        "window_mixed": mixed["window"],
        "admission_intertoken_p50_ms_split": pct(split["gaps"], 0.5),
        "admission_intertoken_p50_ms_mixed": pct(mixed["gaps"], 0.5),
        "admission_intertoken_p99_ms_split": p99_split,
        "admission_intertoken_p99_ms_mixed": p99_mixed,
        "admission_p99_improved": p99_mixed < p99_split,
        "greedy_outputs_identical": mixed["streams"] == split["streams"],
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
    }


def measure_ragged_sweep(smoke: bool = False) -> dict:
    """Benchmark the packed ragged step's demotion erasure (ISSUE 10),
    CPU-runnable through the REAL scheduler.

    Workload — the exact feature mix that demoted EVERY coexist iteration
    under PR 4's padded mixed step: spec decode on (a repetitive greedy
    stream whose prompt-lookup proposals fire), decode_loop on (fused
    K-token tails), a grammar-constrained stream, and a long prompt with a
    short tail admitted mid-decode. Each episode's window runs from the
    long prompt's submission to its first token, entered only once the
    spec stream has a LIVE proposal window (so the coexist iterations
    actually carry spec verify rows). Measured once with
    ``engine.mixed_step`` off (split path: a prefill round plus a
    spec/loop/decode dispatch per iteration — >= 2 dispatches) and once on
    (ONE packed ragged dispatch):

    - model dispatches per coexist-iteration at the engine dispatch seams
      — the >=2 → ~1 headline with every previously-demoting feature live;
    - per-dispatch feature coverage (spec rows, fused tails, constrained
      slots, short-tail prefill rows riding the SAME dispatch);
    - greedy/constrained byte-identity of every stream across the modes;
    - compiled-warmup-variant counts (the collapsed row×chunk×mode
      matrix), and a zero-leak audit of the stopped scheduler
      (analysis/sanitizers.scheduler_leak_report).

    The identity check runs at fp32 for the same reason as
    measure_mixed_sweep: pin the math identity so a structural bug cannot
    hide behind bf16 near-tie rounding.
    """
    import asyncio
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from finchat_tpu.agent.constrained import GrammarVocab, TokenConstraint
    from finchat_tpu.analysis.sanitizers import scheduler_leak_report
    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.kv_cache import pages_needed
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.models.tokenizer import ByteTokenizer
    from finchat_tpu.utils.config import EngineConfig
    from finchat_tpu.utils.metrics import METRICS

    config = dataclasses.replace(PRESETS["mini"], dtype=jnp.float32)
    page_size = 16
    chunk = 32
    long_chunks = 4 if smoke else 6
    long_len = chunk * long_chunks + 3  # short tail: a ragged 3-token row
    spec_budget = 40 if smoke else 56
    episodes = 1 if smoke else 2  # measured episodes (plus one warm one)
    max_seq_len = long_len + 4 * page_size
    pps = pages_needed(max_seq_len, page_size)
    tok = ByteTokenizer()
    rng = np.random.default_rng(0)
    base = rng.integers(1, config.vocab_size, size=4).tolist()
    spec_prompt = (base * 6)[:20]
    by_prompt = rng.integers(1, config.vocab_size, size=11).tolist()
    tool_prompt = tok.encode("decide", add_bos=True)
    long_prompt = rng.integers(1, config.vocab_size, size=long_len).tolist()
    window_keys = (
        "finchat_prefill_seconds_count",
        "finchat_decode_dispatches_total",
        "finchat_mixed_dispatches_total",
        "finchat_coexist_iterations_total",
        "finchat_coexist_dispatches_total",
        # the LAST demotion reason, erased by the ring promotion
        # (ISSUE 15) — pre-seeded, so zero is an assertion-ready value
        'finchat_mixed_demotions_total{reason="ring"}',
    )

    def run(mixed: bool) -> dict:
        ecfg = EngineConfig(
            max_seqs=6, page_size=page_size, num_pages=6 * pps + 8,
            max_seq_len=max_seq_len, prefill_chunk=chunk, mixed_step=mixed,
            session_cache=False, spec_tokens=3, decode_loop_depth=3,
        )
        engine = InferenceEngine(config, init_params(config, jax.random.key(0)), ecfg)
        engine.warmup()  # compiles excluded from every episode's window
        sched = ContinuousBatchingScheduler(engine, eos_id=-1)
        features: list = []
        if mixed:
            real = engine.ragged_mixed

            def spy(tokens, tok_row, row_slot, row_start, row_len,
                    row_from_device, row_arm, row_n_drafts, *rest):
                rl = np.asarray(row_len)
                fd = np.asarray(row_from_device)
                features.append({
                    "prefill": bool(((rl > 0) & ~fd).any()),
                    "spec": bool((np.asarray(row_n_drafts) > 0).any()),
                    "loop": bool(np.asarray(rest[3]).any()),
                    "constrained": any(
                        h.constraint is not None for h in sched.decoding.values()
                    ),
                    "short_tail": bool(((rl > 0) & ~fd & (rl < chunk)).any()),
                })
                return real(tokens, tok_row, row_slot, row_start, row_len,
                            row_from_device, row_arm, row_n_drafts, *rest)

            engine.ragged_mixed = spy
        win = {k: 0.0 for k in window_keys}

        async def drain(handle, out):
            while True:
                ev = await handle.events.get()
                if ev["type"] == "token":
                    out.append(ev["token_id"])
                elif ev["type"] == "done":
                    return
                else:
                    raise RuntimeError(str(ev))

        async def go():
            all_streams = []
            await sched.start()
            try:
                for ep in range(episodes + 1):  # episode 0 warms steady state
                    hs = await sched.submit(
                        f"spec{ep}", spec_prompt,
                        SamplingParams(temperature=0.0, max_new_tokens=spec_budget))
                    hb = await sched.submit(
                        f"by{ep}", by_prompt,
                        SamplingParams(temperature=0.0, max_new_tokens=spec_budget - 8))
                    hc = await sched.submit(
                        f"tool{ep}", tool_prompt,
                        SamplingParams(temperature=0.0, max_new_tokens=24),
                        constraint=TokenConstraint(GrammarVocab.for_tokenizer(tok)),
                    )
                    outs = {"spec": [], "by": [], "tool": [], "long": []}
                    tasks = [asyncio.create_task(drain(hs, outs["spec"])),
                             asyncio.create_task(drain(hb, outs["by"])),
                             asyncio.create_task(drain(hc, outs["tool"]))]
                    # admit the long prompt inside a live proposal window
                    # (timing only; greedy token values are unaffected)
                    for _ in range(30_000):
                        if hs.finished or (
                            sched._spec_cooldown == 0
                            and hs.ngram_index is not None
                            and hs.ngram_index.propose(2)
                        ):
                            break
                        await asyncio.sleep(0.001)
                    snap0 = METRICS.snapshot()
                    hl = await sched.submit(
                        f"long{ep}", long_prompt,
                        SamplingParams(temperature=0.0, max_new_tokens=4))
                    ltask = asyncio.create_task(drain(hl, outs["long"]))
                    for _ in range(300_000):  # bounded: a drain error must
                        if outs["long"] or hl.finished:  # fail, not hang
                            break
                        await asyncio.sleep(0.001)
                    await asyncio.gather(*tasks, ltask)
                    # snapshot AFTER the episode fully drains: the
                    # scheduler attributes a coexist iteration's
                    # dispatches at the NEXT iteration's start, so the
                    # exact numerator needs the post-episode tick
                    await asyncio.sleep(0.05)
                    snap1 = METRICS.snapshot()
                    if ep == 0:
                        continue
                    for k in window_keys:
                        win[k] += snap1.get(k, 0) - snap0.get(k, 0)
                    all_streams.append({k: list(v) for k, v in outs.items()})
                return all_streams
            finally:
                await sched.stop()

        streams = asyncio.run(go())
        leaks = scheduler_leak_report(sched)
        iters = max(win["finchat_coexist_iterations_total"], 1.0)
        # exact attribution: only dispatches booked to coexist iterations
        # (the scheduler's mark/attribute pair), immune to pure-decode
        # iterations straddling the window
        dispatches = win["finchat_coexist_dispatches_total"]
        return {
            "streams": streams,
            "dpi": dispatches / iters,
            "window": {k: int(v) for k, v in win.items()},
            "features": features,
            "leaks": leaks,
            "warmup_variants": engine.compiled_variants,
            "ragged_buckets": engine.ragged_token_buckets() if mixed else [],
        }

    split = run(False)
    ragged = run(True)

    feats = ragged["features"]
    all_in_one = sum(
        1 for f in feats
        if f["prefill"] and f["spec"] and f["loop"] and f["constrained"]
    )
    # the padded-mixed warmup matrix this PR collapses: pow-2 row buckets
    # × two chunk buckets (PR 4), vs the single packed-token bucket axis
    from finchat_tpu.engine.engine import round_up_pow2

    row_buckets = round_up_pow2(6).bit_length()  # 1..round_up_pow2(max_seqs)
    padded_matrix = row_buckets * 2
    print(f"[bench] ragged sweep: dispatches/coexist-iteration "
          f"{split['dpi']:.2f} split -> {ragged['dpi']:.2f} ragged with "
          f"spec+loop+constrained live ({all_in_one}/{len(feats)} fused "
          f"dispatches carried all features); warmup mixed-family variants "
          f"{padded_matrix} (padded row x chunk matrix) -> "
          f"{len(ragged['ragged_buckets'])} (packed-token buckets)",
          file=sys.stderr, flush=True)

    return {
        "metric": "ragged_sweep",
        "unit": "dispatches/coexist-iteration",
        "smoke": smoke,
        "model": "mini (fp32 — see identity note in measure_ragged_sweep)",
        "prefill_chunk": chunk,
        "long_prompt_chunks": long_chunks,
        "episodes": episodes,
        "spec_tokens": 3,
        "decode_loop_depth": 3,
        "dispatches_per_iteration_split": round(split["dpi"], 3),
        "dispatches_per_iteration_ragged": round(ragged["dpi"], 3),
        "window_split": split["window"],
        "window_ragged": ragged["window"],
        "fused_dispatches": len(feats),
        "fused_with_spec": sum(1 for f in feats if f["spec"]),
        "fused_with_loop_tail": sum(1 for f in feats if f["loop"]),
        "fused_with_constrained": sum(1 for f in feats if f["constrained"]),
        "fused_with_short_tail": sum(1 for f in feats if f["short_tail"]),
        "fused_with_all_features": all_in_one,
        "greedy_outputs_identical": ragged["streams"] == split["streams"],
        "zero_leaks": not split["leaks"] and not ragged["leaks"],
        "leak_report": split["leaks"] + ragged["leaks"],
        "warmup_variants_split": split["warmup_variants"],
        "warmup_variants_ragged": ragged["warmup_variants"],
        "padded_mixed_matrix_variants": padded_matrix,
        "ragged_bucket_variants": len(ragged["ragged_buckets"]),
        "warmup_matrix_collapsed": len(ragged["ragged_buckets"]) < padded_matrix,
        # ring rows are PROMOTED into the ragged round (ISSUE 15): the
        # reason="ring" label stays pre-seeded so its zero is a statement,
        # not an absence (tier1 gates it; the seq-sharded-row coverage
        # lives in --longctx-smoke, which has the mesh)
        "ring_demotions": int(ragged["window"].get(
            'finchat_mixed_demotions_total{reason="ring"}', 0)),
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
    }


def measure_longctx_sweep(smoke: bool = False, tokens: int = 100_000) -> dict:
    """Benchmark bounded-KV long-context serving (ISSUE 15; SnapStream-
    style sink+window with page-granular eviction), CPU-runnable through
    the REAL scheduler.

    Sections (mini fp32, page_size 16, prefill_chunk 64; sink 2 +
    window 30 pages → a 512-token bounded budget):

    - IDENTITY GUARD: a session whose prompt+budget fits the window is
      byte-identical to the unbounded engine's stream (the policy is
      inert until it evicts) — the fp32 contract the whole compacted-
      coordinate machinery hangs on.
    - LONG INGEST: ONE session ingests ``tokens`` prompt tokens (the
      100k-token 10-K-filing scenario of the acceptance criteria) and
      then decodes. Measured: peak page occupancy (must stay pinned at
      sink+window while the unbounded requirement is ~tokens/page_size
      pages), pages evicted, ingest throughput, and the decode
      inter-token median AT 100k context vs a ~1k-context bounded
      session — the flat-latency headline (bounded attention reads a
      constant sink+window token set per step, so context length drops
      out of the per-token cost entirely).
    - UNBOUNDED CONTROL: the same engine shape without the policy at 2k
      and 4k contexts — occupancy grows linearly with context and the
      decode inter-token cost grows with it (on CPU the attention read
      is compute-bound, so the growth is visible at small scale; on-chip
      it is an HBM-bandwidth term — same direction, steeper wall).
    - RING PROMOTION: a seq-sharded prefill row IN THE MIX with a live
      decode stream — the last mixed-path demotion reason is erased
      (``finchat_mixed_demotions_total{reason="ring"}`` stays 0) and the
      coexist iterations stay at EXACTLY one fused dispatch per round.
      Runs on a real ``seq=2`` mesh when the process has >= 2 devices
      (tier1 forces an 8-device host mesh); otherwise the ring routing
      predicate is forced and the record says so.
    """
    import asyncio
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from finchat_tpu.analysis.sanitizers import scheduler_leak_report
    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.utils.config import EngineConfig
    from finchat_tpu.utils.metrics import METRICS

    config = dataclasses.replace(PRESETS["mini"], dtype=jnp.float32)
    page_size, chunk = 16, 64
    sink, window = 2, 30
    budget_pages = sink + window
    params = init_params(config, jax.random.key(0))
    rng = np.random.default_rng(0)

    def build(bounded: bool, *, mesh=None, max_seqs=2, num_pages=0,
              ring_min=0):
        ecfg = EngineConfig(
            max_seqs=max_seqs, page_size=page_size,
            num_pages=num_pages or (max_seqs * budget_pages + 8),
            # max_seq_len only sizes the page-table row width; bounded
            # rows never occupy more than the budget
            max_seq_len=(budget_pages + 4) * page_size,
            prefill_chunk=chunk, session_cache=False,
            kv_sink_pages=sink if bounded else 0,
            kv_window_pages=window if bounded else 0,
            ring_prefill_min_tokens=ring_min or 4096,
            ring_prefill_chunk=chunk,
        )
        if not bounded:
            ecfg.max_seq_len = 8192
            ecfg.num_pages = num_pages or 600
        engine = InferenceEngine(config, params, ecfg, mesh=mesh)
        return ContinuousBatchingScheduler(engine, eos_id=-1)

    async def _drain_timed(handle, out, stamps):
        while True:
            ev = await handle.events.get()
            if ev["type"] == "token":
                out.append(ev["token_id"])
                stamps.append(time.perf_counter())
            elif ev["type"] == "done":
                return
            else:
                raise RuntimeError(str(ev))

    def run_session(sched, prompt, max_new, seq_id="s"):
        """One session through a fresh-started scheduler: returns
        (tokens, decode inter-token gaps, peak owned pages, wall)."""
        out, stamps = [], []
        peak = {"pages": 0}

        async def go():
            await sched.start()
            try:
                t0 = time.perf_counter()
                h = await sched.submit(
                    seq_id, prompt,
                    SamplingParams(temperature=0.0, max_new_tokens=max_new))
                task = asyncio.create_task(_drain_timed(h, out, stamps))
                while not h.finished:
                    peak["pages"] = max(
                        peak["pages"],
                        len(sched.allocator.owned_by(seq_id)))
                    await asyncio.sleep(0.002)
                await task
                wall = time.perf_counter() - t0
                sched.allocator.check_invariants()
                leaks = scheduler_leak_report(sched)
                assert not leaks, leaks
                return wall
            finally:
                await sched.stop()

        wall = asyncio.run(go())
        gaps = [b - a for a, b in zip(stamps, stamps[1:])]
        return out, gaps, peak["pages"], wall

    def median(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2] if xs else 0.0

    decode_n = 32 if smoke else 48

    # --- identity guard: inert inside the window ------------------------
    short = rng.integers(1, config.vocab_size, size=256).tolist()
    base_out, _, _, _ = run_session(build(False), short, 24)
    snap0 = METRICS.snapshot()
    bounded_out, _, short_peak, _ = run_session(build(True), short, 24)
    snap1 = METRICS.snapshot()
    identity_ok = bounded_out == base_out
    inert_ok = (snap1.get("finchat_boundedkv_evicted_pages_total", 0)
                == snap0.get("finchat_boundedkv_evicted_pages_total", 0))

    # --- bounded baseline at ~1k context --------------------------------
    short_ctx = rng.integers(1, config.vocab_size, size=1024).tolist()
    _, gaps_1k, _, _ = run_session(build(True), short_ctx, decode_n)

    # --- the long ingest -------------------------------------------------
    long_prompt = rng.integers(1, config.vocab_size, size=tokens).tolist()
    snap0 = METRICS.snapshot()
    long_out, gaps_long, long_peak, long_wall = run_session(
        build(True), long_prompt, decode_n)
    snap1 = METRICS.snapshot()
    evicted = (snap1.get("finchat_boundedkv_evicted_pages_total", 0)
               - snap0.get("finchat_boundedkv_evicted_pages_total", 0))
    from finchat_tpu.engine.kv_cache import pages_needed

    unbounded_pages_needed = pages_needed(tokens + decode_n, page_size)
    flat_ratio = (median(gaps_long) / median(gaps_1k)) if gaps_1k else 0.0

    # --- unbounded control: occupancy and latency grow with context -----
    ctrl = {}
    for n in (2048, 4096):
        p = rng.integers(1, config.vocab_size, size=n).tolist()
        _, gaps, peak_pages, _ = run_session(build(False), p, 24)
        ctrl[n] = {"peak_pages": peak_pages,
                   "inter_token_ms": round(1000 * median(gaps), 2)}
    # the control's CPU inter-token is SHAPE-bound, not context-bound: the
    # jax.lax reference gathers the row's whole max_pages allocation per
    # step, so the unbounded engine pays its 8192-token allocation on
    # every token while the bounded engine's gather is budget-sized —
    # the on-chip regime reads only live pages, where the growth is the
    # HBM term (PERF_longctx.md carries the honest regime analysis).
    # Occupancy growth is the directly-evidenced contrast here.
    ctrl_growth = (ctrl[4096]["peak_pages"] > ctrl[2048]["peak_pages"]
                   and ctrl[4096]["peak_pages"]
                   > budget_pages)

    # --- ring promotion: a seq-sharded row in the coexist mix ------------
    # its own tiny-config stack: the point is the SCHEDULE (one fused
    # dispatch per coexist round with a ring-routed row in the mix, zero
    # reason="ring" demotions), and GSPMD-compiling the mini shape over
    # an 8-virtual-device CPU mesh costs minutes for no extra signal
    ring_config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
    ring_params = init_params(ring_config, jax.random.key(0))
    ring_chunk = 32
    seq_mesh = None
    ring_mode = "forced-predicate"
    if jax.device_count() >= 2:
        from finchat_tpu.parallel.mesh import MeshSpec, build_mesh

        n_dev = jax.device_count()
        seq_mesh = build_mesh(
            MeshSpec(data=max(1, n_dev // 2), seq=2, expert=1, model=1))
        ring_mode = "seq=2 mesh"
    ring_prompt = rng.integers(
        1, ring_config.vocab_size, size=5 * ring_chunk).tolist()
    short8 = rng.integers(1, ring_config.vocab_size, size=8).tolist()

    def ring_run(promote: bool):
        ring_cfg = EngineConfig(
            max_seqs=2, page_size=page_size, num_pages=64, max_seq_len=512,
            prefill_chunk=ring_chunk, session_cache=False,
            ring_prefill_min_tokens=2 * ring_chunk,
            ring_prefill_chunk=ring_chunk,
        )
        engine = InferenceEngine(ring_config, ring_params, ring_cfg,
                                 mesh=seq_mesh if promote else None)
        sched = ContinuousBatchingScheduler(engine, eos_id=-1)
        if promote and seq_mesh is None:
            sched.engine._use_ring_prefill = lambda n: n >= 2 * ring_chunk

        async def go():
            snap0 = METRICS.snapshot()
            await sched.start()
            try:
                hs = await sched.submit(
                    "short", short8,
                    SamplingParams(temperature=0.0, max_new_tokens=28))
                outs = {"short": [], "long": []}
                stamps: list = []
                tasks = [asyncio.create_task(
                    _drain_timed(hs, outs["short"], stamps))]
                while len(outs["short"]) < 2 and not hs.finished:
                    await asyncio.sleep(0.002)
                if promote:
                    assert sched.engine._use_ring_prefill(len(ring_prompt))
                hl = await sched.submit(
                    "ring", ring_prompt,
                    SamplingParams(temperature=0.0, max_new_tokens=4))
                tasks.append(asyncio.create_task(
                    _drain_timed(hl, outs["long"], stamps)))
                await asyncio.gather(*tasks)
                await asyncio.sleep(0.05)  # attribution lands next tick
                snap1 = METRICS.snapshot()
                win = {k: snap1.get(k, 0) - snap0.get(k, 0) for k in (
                    "finchat_coexist_dispatches_total",
                    "finchat_coexist_rounds_total",
                    "finchat_coexist_iterations_total",
                )}
                win["ring_demotions"] = (
                    snap1.get('finchat_mixed_demotions_total{reason="ring"}', 0)
                    - snap0.get('finchat_mixed_demotions_total{reason="ring"}', 0))
                return outs, win
            finally:
                await sched.stop()

        return asyncio.run(go())

    plain_outs, _ = ring_run(False)
    ring_outs, ring_win = ring_run(True)
    ring_dpr = (ring_win["finchat_coexist_dispatches_total"]
                / max(1.0, ring_win["finchat_coexist_rounds_total"]))

    print(
        f"[bench] longctx: {tokens}-token bounded ingest in {long_wall:.0f}s "
        f"({tokens / long_wall:.0f} tok/s), peak {long_peak} pages vs "
        f"{unbounded_pages_needed} unbounded-required ({evicted:.0f} evicted); "
        f"inter-token median {1000 * median(gaps_long):.1f} ms at {tokens} ctx "
        f"vs {1000 * median(gaps_1k):.1f} ms at 1k (flat ratio "
        f"{flat_ratio:.2f}); ring promotion [{ring_mode}] dispatches/"
        f"coexist-round {ring_dpr:.2f}, ring demotions "
        f"{ring_win['ring_demotions']:.0f}", file=sys.stderr, flush=True)

    return {
        "metric": "longctx_sweep",
        "unit": "pages / ms-per-token",
        "smoke": smoke,
        "model": "mini (fp32 — identity contract, see measure_ragged_sweep)",
        "page_size": page_size,
        "prefill_chunk": chunk,
        "sink_pages": sink,
        "window_pages": window,
        "budget_pages": budget_pages,
        "ingest_tokens": tokens,
        "ingest_wall_s": round(long_wall, 1),
        "ingest_tok_s": round(tokens / long_wall, 1),
        "bounded_identical_while_fits": identity_ok,
        "policy_inert_inside_window": inert_ok and short_peak <= budget_pages,
        "peak_pages_longctx": int(long_peak),
        "unbounded_pages_required": int(unbounded_pages_needed),
        "occupancy_bounded": long_peak <= budget_pages,
        "evicted_pages": int(evicted),
        "decode_tokens": len(long_out),
        "inter_token_ms_at_1k": round(1000 * median(gaps_1k), 2),
        "inter_token_ms_at_longctx": round(1000 * median(gaps_long), 2),
        "flat_ratio": round(flat_ratio, 3),
        "inter_token_flat": bool(flat_ratio <= 1.5),
        "unbounded_control": {str(k): v for k, v in ctrl.items()},
        "unbounded_occupancy_grows": bool(ctrl_growth),
        "ring_mode": ring_mode,
        "ring_demotions": int(ring_win["ring_demotions"]),
        "ring_coexist_iterations": int(
            ring_win["finchat_coexist_iterations_total"]),
        "ring_dispatches_per_coexist_round": round(ring_dpr, 3),
        "ring_streams_identical": ring_outs == plain_outs,
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
    }


def measure_freerun_sweep(smoke: bool = False) -> dict:
    """Benchmark the free-running device loop (ISSUE 13), CPU-runnable
    through the REAL scheduler.

    Workload — a loaded engine where prefill and decode coexist for a
    sustained window: greedy decode streams with deep budgets, a
    multi-chunk long prompt admitted mid-decode per episode, fused loop
    tails on (decode_loop_depth 2). Measured at ``freerun_rounds`` 1
    (host-stepped: one ragged dispatch per round, the PR 10 state of the
    world) and 4/8 (captured multi-round programs):

    - model dispatches per ROUND via the scheduler-attributed coexist
      counters (finchat_coexist_dispatches_total over the new
      finchat_coexist_rounds_total — the ISSUE 13 headline: 1.0 at
      host-stepped, < 1 once captures engage, approaching 1/rounds);
    - the decode streams' host-observed inter-token p99 inside each
      admission window (captures trade per-token cadence for fewer
      syncs; the ring drains re-pace downstream);
    - greedy byte-identity of every stream across every level (fp32, the
      PR 4/10 contract — a staging bug cannot hide behind rounding);
    - a zero-leak audit of each stopped scheduler.
    """
    import asyncio
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from finchat_tpu.analysis.sanitizers import scheduler_leak_report
    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.kv_cache import pages_needed
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.utils.config import EngineConfig
    from finchat_tpu.utils.metrics import METRICS

    config = dataclasses.replace(PRESETS["mini"], dtype=jnp.float32)
    page_size = 16
    chunk = 32
    long_chunks = 4 if smoke else 8
    long_len = chunk * long_chunks + 3
    dec_budget = 40 if smoke else 72
    long_budget = 8
    n_dec = 2
    episodes = 1 if smoke else 2
    levels = (1, 4) if smoke else (1, 4, 8)
    max_seq_len = long_len + 8 * page_size
    pps = pages_needed(max_seq_len, page_size)
    rng = np.random.default_rng(0)
    dec_prompts = [
        rng.integers(1, config.vocab_size, size=n).tolist() for n in (12, 18)
    ]
    long_prompt = rng.integers(1, config.vocab_size, size=long_len).tolist()
    window_keys = (
        "finchat_coexist_iterations_total",
        "finchat_coexist_dispatches_total",
        "finchat_coexist_rounds_total",
        "finchat_freerun_dispatches_total",
        "finchat_mixed_dispatches_total",
    )

    def run(freerun: int) -> dict:
        ecfg = EngineConfig(
            max_seqs=4, page_size=page_size, num_pages=4 * pps + 8,
            max_seq_len=max_seq_len, prefill_chunk=chunk, mixed_step=True,
            session_cache=False, decode_loop_depth=2, freerun_rounds=freerun,
        )
        engine = InferenceEngine(config, init_params(config, jax.random.key(0)), ecfg)
        engine.warmup()  # compiles (incl. the capture) excluded from windows
        sched = ContinuousBatchingScheduler(engine, eos_id=-1)
        win = {k: 0.0 for k in window_keys}
        gaps: list = []

        async def drain(handle, out):
            while True:
                ev = await handle.events.get()
                if ev["type"] == "token":
                    out.append((time.perf_counter(), ev["token_id"]))
                elif ev["type"] == "done":
                    return
                else:
                    raise RuntimeError(str(ev))

        async def go():
            all_streams = []
            await sched.start()
            try:
                for ep in range(episodes + 1):  # episode 0 warms steady state
                    handles = [
                        await sched.submit(
                            f"dec{ep}-{i}", dec_prompts[i],
                            SamplingParams(temperature=0.0, max_new_tokens=dec_budget),
                        )
                        for i in range(n_dec)
                    ]
                    outs = [[] for _ in handles]
                    tasks = [asyncio.create_task(drain(h, o))
                             for h, o in zip(handles, outs)]
                    while any(len(o) < 2 for o in outs):
                        await asyncio.sleep(0.002)
                    snap0 = METRICS.snapshot()
                    t_submit = time.perf_counter()
                    lh = await sched.submit(
                        f"long{ep}", long_prompt,
                        SamplingParams(temperature=0.0, max_new_tokens=long_budget),
                    )
                    lo: list = []
                    ltask = asyncio.create_task(drain(lh, lo))
                    await asyncio.gather(*tasks, ltask)
                    # attribution lands at the NEXT loop tick (the PR 10
                    # mark/attribute pair) — give it one
                    await asyncio.sleep(0.05)
                    snap1 = METRICS.snapshot()
                    if ep == 0:
                        continue
                    for k in window_keys:
                        win[k] += snap1.get(k, 0) - snap0.get(k, 0)
                    t_first = lo[0][0] if lo else t_submit
                    for o in outs:
                        ts = [t for t, _ in o if t_submit <= t <= t_first]
                        gaps.extend(np.diff(ts).tolist())
                    all_streams.append(
                        [[t for _, t in o] for o in outs] + [[t for _, t in lo]]
                    )
                return all_streams
            finally:
                await sched.stop()

        streams = asyncio.run(go())
        leaks = scheduler_leak_report(sched)
        rounds = max(win["finchat_coexist_rounds_total"], 1.0)
        return {
            "streams": streams,
            "dpr": win["finchat_coexist_dispatches_total"] / rounds,
            "window": {k: int(v) for k, v in win.items()},
            "gaps": gaps,
            "leaks": leaks,
            "warmup_variants": engine.compiled_variants,
        }

    results = {f: run(f) for f in levels}

    def pct(gaps: list, q: float) -> float:
        if not gaps:
            return 0.0
        return round(1000 * float(np.quantile(np.asarray(gaps), q)), 3)

    base = results[levels[0]]
    top = results[levels[-1]]
    identical = all(r["streams"] == base["streams"] for r in results.values())
    sweep = [
        {
            "freerun_rounds": f,
            "dispatches_per_round": round(r["dpr"], 3),
            "freerun_dispatches": r["window"]["finchat_freerun_dispatches_total"],
            "coexist_rounds": r["window"]["finchat_coexist_rounds_total"],
            "coexist_dispatches": r["window"]["finchat_coexist_dispatches_total"],
            "intertoken_p50_ms": pct(r["gaps"], 0.5),
            "intertoken_p99_ms": pct(r["gaps"], 0.99),
        }
        for f, r in results.items()
    ]
    print(f"[bench] freerun sweep: dispatches/round "
          + " -> ".join(f"{s['dispatches_per_round']:.2f}@{s['freerun_rounds']}"
                        for s in sweep)
          + f"; admission inter-token p99 {pct(base['gaps'], 0.99)}"
          + f" -> {pct(top['gaps'], 0.99)} ms; identical={identical}",
          file=sys.stderr, flush=True)

    return {
        "metric": "freerun_sweep",
        "unit": "dispatches/round, inter-token ms",
        "smoke": smoke,
        "model": "mini (fp32 — the PR 4/10 identity contract)",
        "prefill_chunk": chunk,
        "long_prompt_chunks": long_chunks,
        "decode_streams": n_dec,
        "decode_budget": dec_budget,
        "decode_loop_depth": 2,
        "episodes": episodes,
        "sweep": sweep,
        "dispatches_per_round_base": round(base["dpr"], 3),
        "dispatches_per_round_top": round(top["dpr"], 3),
        "freerun_engaged": top["window"]["finchat_freerun_dispatches_total"] >= 1,
        "greedy_outputs_identical": identical,
        "zero_leaks": not any(r["leaks"] for r in results.values()),
        "leak_report": sum((r["leaks"] for r in results.values()), []),
        "warmup_variants": {f: r["warmup_variants"] for f, r in results.items()},
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
    }


def measure_quant_sweep(smoke: bool = False) -> dict:
    """Benchmark the quantized serving plane end-to-end (ISSUE 14),
    CPU-runnable through the REAL scheduler on the tiny fp32 config (fp32
    pins the greedy byte-identity gates the way every sweep here does).

    Mode grid — bf16 (unquantized), int8-w (weight-only), int8-w+int8-KV
    (the full quantized plane), int4-w (packed nibbles) — each measured
    for:

    - decode tok/s and turn-1 TTFT (reported; CPU is compute-bound, so
      weight-dequant ADDS work here — the HBM-traffic win is on-chip,
      PERF_quant.md regime analysis);
    - page-pool capacity per HBM byte (kv_cache.page_hbm_bytes): the
      int8-KV pool must fit >= 1.75x the bf16 pool's pages in the same
      budget (~2x minus the fp32 scale planes) — the deeper-batches lever;
    - a prefill-logit quality envelope vs the bf16 run (max relative
      logit delta on a fixed probe prompt; a mode past its bound bumps
      finchat_quant_envelope_exceeded_total and fails the gate);
    - session offload -> disk spill -> restore under each mode: turn 2
      resumes from restored KV and must be BYTE-IDENTICAL to a cold
      re-prefill of the same turn (exact by construction — int8 page
      ints and fp32 scale planes round-trip bit-exactly), and for the
      int8-KV mode the disk record's payload must equal the RAM entry's
      snapshot byte-for-byte INCLUDING the scale planes;
    - freerun composition: an int8-KV engine at freerun_rounds=4 must
      still capture (dispatches/round < 1 on the coexist counters) with
      streams byte-identical to its host-stepped twin;
    - a zero-leak audit of every stopped scheduler.
    """
    import asyncio
    import dataclasses
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from finchat_tpu.analysis.sanitizers import scheduler_leak_report
    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.kv_cache import page_hbm_bytes, pages_needed
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.models.quant import init_quantized_llama_params
    from finchat_tpu.utils.config import EngineConfig
    from finchat_tpu.utils.metrics import METRICS

    config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
    page_size = 16
    chunk = 32
    n_new = 16 if smoke else 24
    p1_len, suffix_len = 60, 20
    total_len = p1_len + suffix_len + 2 * n_new + page_size
    max_seq_len = total_len + 2 * page_size
    pps = pages_needed(max_seq_len, page_size)
    rng = np.random.default_rng(0)
    probe = rng.integers(1, config.vocab_size, size=40).tolist()
    p1 = rng.integers(1, config.vocab_size, size=p1_len).tolist()
    suffix = rng.integers(1, config.vocab_size, size=suffix_len).tolist()
    # envelope bounds per mode (relative max logit delta vs bf16 on the
    # probe prefill): int8 is per-channel weight rounding only; the KV
    # rounding adds on top; int4 is ~16x coarser than int8
    ENVELOPE = {"int8": 0.10, "int8+kv8": 0.25, "int4": 0.60}
    MODES = (("bf16", "", ""), ("int8", "int8", ""),
             ("int8+kv8", "int8", "int8"), ("int4", "int4", ""))

    def make_params(quant):
        if quant:
            return init_quantized_llama_params(config, jax.random.key(0),
                                               mode=quant)
        return init_params(config, jax.random.key(0))

    def build(quant, kv_quant, *, session_bytes=0, disk_path="", freerun=1,
              loop_depth=1):
        ecfg = EngineConfig(
            max_seqs=4, page_size=page_size, num_pages=4 * pps + 8,
            max_seq_len=max_seq_len, prefill_chunk=chunk,
            session_cache=session_bytes > 0, session_cache_bytes=session_bytes,
            session_cache_disk_path=disk_path, kv_quant=kv_quant,
            freerun_rounds=freerun, decode_loop_depth=loop_depth,
        )
        engine = InferenceEngine(config, make_params(quant), ecfg,
                                 quant=quant)
        return engine, ContinuousBatchingScheduler(engine, eos_id=-1)

    async def stream(sched, seq_id, prompt, conv=None):
        t0 = time.perf_counter()
        handle = await sched.submit(
            seq_id, prompt, SamplingParams(temperature=0.0, max_new_tokens=n_new),
            conversation_id=conv,
        )
        toks, ttft = [], None
        while True:
            ev = await handle.events.get()
            if ev["type"] == "token":
                if ttft is None:
                    ttft = time.perf_counter() - t0
                toks.append(ev["token_id"])
            elif ev["type"] == "done":
                return toks, ttft
            else:
                raise RuntimeError(str(ev))

    def run_mode(label, quant, kv_quant):
        """One mode's serving measurement; returns the per-mode record."""
        # quality envelope: a probe prefill's logits on a throwaway slot
        # (reset afterwards; the scheduler owns slots from here on)
        engine, sched = build(quant, kv_quant, session_bytes=32 << 20,
                              disk_path=tempfile.mkdtemp(prefix="quantskv-"))
        engine.set_page_table_row(0, list(range(1, pages_needed(len(probe), page_size) + 1)))
        probe_logits = np.asarray(engine.prefill(0, probe))
        engine.reset_slot(0)

        leaks: list = []
        rec: dict = {"mode": label}

        async def go():
            await sched.start()
            try:
                t0 = time.perf_counter()
                toks1, ttft1 = await stream(sched, f"{label}-t1", p1, "qconv")
                rec["ttft_ms_turn1"] = round(1000 * ttft1, 1)
                # decode rate: first token lands at ttft, the remaining
                # n_new-1 tokens span (elapsed - ttft) — excluding prefill,
                # which would otherwise dominate and mask per-mode decode
                # deltas (the column PERF_quant.md's regime analysis reads)
                decode_wall = max(time.perf_counter() - t0 - ttft1, 1e-9)
                rec["decode_tok_s"] = round((n_new - 1) / decode_wall, 1)
                history = p1 + toks1
                # scale-plane disk roundtrip (int8-KV): the RAM entry's
                # snapshot vs its landed disk record, byte-for-byte
                cache = sched.session_cache
                cache.disk.flush()
                entry, payload = cache.get("qconv"), cache.disk.load("qconv")
                rec["disk_roundtrip_identical"] = bool(
                    entry is not None and payload is not None
                    and np.array_equal(entry.token_ids, payload["token_ids"])
                    and all(
                        (a is None and b is None)
                        or (a is not None and b is not None and np.array_equal(a, b))
                        for a, b in zip(entry.snap, payload["snap"])
                    )
                )
                chunks0 = METRICS.snapshot().get("finchat_prefill_seconds_count", 0)
                toks2, _ = await stream(sched, f"{label}-t2", history + suffix, "qconv")
                rec["prefill_chunks_turn2_resumed"] = int(
                    METRICS.snapshot().get("finchat_prefill_seconds_count", 0) - chunks0
                )
                return history, toks2
            finally:
                await sched.stop()

        history, toks2_resumed = asyncio.run(go())
        leaks += scheduler_leak_report(sched)

        # cold twin: same turn 2, fresh engine, session cache OFF — the
        # byte-identity-where-exact gate (restored pages must decode
        # exactly like recomputed ones at fp32)
        engine_c, sched_c = build(quant, kv_quant)

        async def go_cold():
            await sched_c.start()
            try:
                await stream(sched_c, f"{label}-c1", p1)
                chunks0 = METRICS.snapshot().get("finchat_prefill_seconds_count", 0)
                toks, _ = await stream(sched_c, f"{label}-c2", history + suffix)
                return toks, int(
                    METRICS.snapshot().get("finchat_prefill_seconds_count", 0) - chunks0
                )
            finally:
                await sched_c.stop()

        toks2_cold, chunks_cold = asyncio.run(go_cold())
        leaks += scheduler_leak_report(sched_c)
        rec["prefill_chunks_turn2_cold"] = chunks_cold
        rec["resumed_vs_cold_identical"] = toks2_resumed == toks2_cold
        rec["resume_saved_chunks"] = chunks_cold - rec["prefill_chunks_turn2_resumed"]

        # page-pool accounting (the HBM lever, computed not allocated)
        pb = page_hbm_bytes(config, page_size, kv_quant)
        rec["page_bytes"] = pb
        conv_pages = pages_needed(len(history) + suffix_len + n_new, page_size)
        rec["pages_per_conversation"] = conv_pages
        rec["conversation_kv_bytes"] = conv_pages * pb
        rec["leaks"] = leaks
        return rec, probe_logits

    records, probe_by_mode = [], {}
    for label, quant, kv_quant in MODES:
        rec, lg = run_mode(label, quant, kv_quant)
        probe_by_mode[label] = lg
        records.append(rec)
        print(f"[bench] quant {label}: ttft {rec['ttft_ms_turn1']} ms, "
              f"turn-2 chunks {rec['prefill_chunks_turn2_cold']} cold -> "
              f"{rec['prefill_chunks_turn2_resumed']} resumed, "
              f"resumed==cold {rec['resumed_vs_cold_identical']}",
              file=sys.stderr, flush=True)

    base_logits = probe_by_mode["bf16"]
    denom = float(np.max(np.abs(base_logits)))
    envelope_ok = True
    for rec in records:
        if rec["mode"] == "bf16":
            rec["envelope_rel_delta"] = 0.0
            continue
        delta = float(np.max(np.abs(probe_by_mode[rec["mode"]] - base_logits)))
        rec["envelope_rel_delta"] = round(delta / denom, 4)
        rec["envelope_bound"] = ENVELOPE[rec["mode"]]
        if rec["envelope_rel_delta"] > rec["envelope_bound"]:
            METRICS.inc("finchat_quant_envelope_exceeded_total")
            envelope_ok = False

    by_mode = {r["mode"]: r for r in records}
    pool_ratio = by_mode["bf16"]["page_bytes"] / by_mode["int8+kv8"]["page_bytes"]
    # the sweep serves fp32 (identity discipline), which overstates the
    # KV saving; report the PRODUCT-shape ratio too — llama3-8b bf16 at
    # the on-chip page size, computed analytically (page_hbm_bytes):
    # ~1.94x (the fp32 scale planes cost ~3% there, vs ~50% at the tiny
    # sweep shapes where 2 KV heads pad to 8 scale rows)
    cfg_8b = PRESETS["llama3-8b"]
    pool_ratio_8b = (page_hbm_bytes(cfg_8b, 256)
                     / page_hbm_bytes(cfg_8b, 256, "int8"))

    # freerun composition: int8-KV at freerun_rounds 1 vs 4 — captures
    # must still engage (dispatches/round < 1) with identical streams.
    # Same loop depth and the SAME long prompt at both levels (the only
    # difference under test is the capture itself).
    fr_long_prompt = rng.integers(1, config.vocab_size, size=3 * chunk + 3).tolist()

    def run_freerun(freerun):
        engine, sched = build("int8", "int8", freerun=freerun, loop_depth=2)
        engine.warmup()
        long_prompt = fr_long_prompt
        win = {}

        async def go():
            await sched.start()
            try:
                outs = [[] for _ in range(2)]

                async def drain(h, o):
                    while True:
                        ev = await h.events.get()
                        if ev["type"] == "token":
                            o.append(ev["token_id"])
                        elif ev["type"] == "done":
                            return
                        else:
                            raise RuntimeError(str(ev))

                handles = [
                    await sched.submit(
                        f"fr{freerun}-d{i}", p1[: 12 + 6 * i],
                        SamplingParams(temperature=0.0, max_new_tokens=40),
                    )
                    for i in range(2)
                ]
                tasks = [asyncio.create_task(drain(h, o))
                         for h, o in zip(handles, outs)]
                while any(len(o) < 2 for o in outs):
                    await asyncio.sleep(0.002)
                snap0 = METRICS.snapshot()
                lh = await sched.submit(
                    f"fr{freerun}-long", long_prompt,
                    SamplingParams(temperature=0.0, max_new_tokens=8),
                )
                lo: list = []
                await asyncio.gather(*tasks, asyncio.create_task(drain(lh, lo)))
                await asyncio.sleep(0.05)  # attribution lands next tick
                snap1 = METRICS.snapshot()
                for k in ("finchat_coexist_dispatches_total",
                          "finchat_coexist_rounds_total",
                          "finchat_freerun_dispatches_total"):
                    win[k] = snap1.get(k, 0) - snap0.get(k, 0)
                return outs + [lo]
            finally:
                await sched.stop()

        streams = asyncio.run(go())
        leaks = scheduler_leak_report(sched)
        dpr = win["finchat_coexist_dispatches_total"] / max(
            win["finchat_coexist_rounds_total"], 1.0)
        return streams, dpr, win, leaks

    fr_streams_1, _dpr1, _w1, leaks1 = run_freerun(1)
    fr_streams_4, dpr4, win4, leaks4 = run_freerun(4)
    freerun_identical = fr_streams_1 == fr_streams_4
    print(f"[bench] quant freerun(int8-KV): dispatches/round {dpr4:.3f} @4 "
          f"(captures {win4['finchat_freerun_dispatches_total']}), "
          f"identical={freerun_identical}; kv8 pool ratio {pool_ratio:.2f}x",
          file=sys.stderr, flush=True)

    all_leaks = sum((r.pop("leaks") for r in records), []) + leaks1 + leaks4
    return {
        "metric": "quant_sweep",
        "unit": "tok/s, page bytes, rel logit delta",
        "smoke": smoke,
        "model": "tiny (fp32 — the identity-gate discipline)",
        "page_size": page_size,
        "prefill_chunk": chunk,
        "new_tokens_per_turn": n_new,
        "sweep": records,
        "kv8_pool_ratio": round(pool_ratio, 3),
        "kv8_pool_ratio_8b_bf16": round(pool_ratio_8b, 3),
        "kv8_pool_at_least_1_75x": pool_ratio >= 1.75 and pool_ratio_8b >= 1.9,
        "envelope_ok": envelope_ok,
        "resumed_identical_all_modes": all(
            r["resumed_vs_cold_identical"] for r in records
        ),
        "resume_saved_chunks_all_modes": all(
            r["resume_saved_chunks"] > 0 for r in records
        ),
        "scale_planes_roundtrip": by_mode["int8+kv8"]["disk_roundtrip_identical"],
        "freerun_dispatches_per_round_int8kv": round(dpr4, 3),
        "freerun_engaged": win4["finchat_freerun_dispatches_total"] >= 1,
        "freerun_outputs_identical": freerun_identical,
        "zero_leaks": not all_leaks,
        "leak_report": all_leaks,
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
    }


def measure_quantmatmul_smoke() -> dict:
    """CI gate for the fused dequant-matmul plane (ISSUE 16), CPU-runnable.

    Four gates, mirroring the attention-kernel dispatch discipline:

    1. ``quant_matmul_ref`` is BITWISE the historical inline-dequant math
       (``x @ dequantize(w)``) — the reference IS the tier-1 serving path,
       so routing every QTensor/Q4Tensor site through ops/dispatch.py
       cannot move a stream byte on the default CPU backend.
    2. Interpret-mode kernel-vs-ref parity on ragged int8 and per-group
       int4 shapes (fp32-accumulating tiles: allclose, not bitwise).
    3. Serving stream identity at fp32: an int8-quantized engine with the
       fused backend (``pallas-interpret`` on CPU) must produce greedy
       streams byte-identical to its inline-dequant twin through the REAL
       scheduler, engage the fused path (fused_dispatches_total > 0 only
       on the fused run), and compile EXACTLY as many warmup variants as
       the reference engine — the backend knob is resolved once at
       construction and multiplies nothing.
    4. A zero-leak audit of both stopped schedulers.
    """
    import asyncio
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from finchat_tpu.analysis.sanitizers import scheduler_leak_report
    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.kv_cache import pages_needed
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.models.llama import PRESETS
    from finchat_tpu.models.quant import (
        dequantize,
        init_quantized_llama_params,
        quantize,
        quantize_int4,
    )
    from finchat_tpu.ops.quant_matmul import (
        quant_matmul_int4,
        quant_matmul_int8,
        quant_matmul_ref,
    )
    from finchat_tpu.utils.config import EngineConfig
    from finchat_tpu.utils.metrics import METRICS

    rng = np.random.default_rng(0)

    # --- gate 1+2: op-level reference pin and kernel parity ----------------
    def _rand(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    parity: list[dict] = []
    ref_bitwise = True
    for name, (M, K, N), mode, group in (
        ("int8-ragged", (7, 130, 96), "int8", None),
        ("int4-per-group-ragged", (5, 192, 80), "int4", 32),
    ):
        x, w = _rand((M, K)), _rand((K, N))
        if mode == "int8":
            qt = quantize(w)
            out = quant_matmul_int8(x, qt.q, qt.scale, interpret=True)
        else:
            qt = quantize_int4(w, group_size=group)
            out = quant_matmul_int4(x, qt.q, qt.scale, interpret=True)
        ref = quant_matmul_ref(x, qt)
        ref_bitwise &= bool(
            np.array_equal(np.asarray(ref), np.asarray(x @ dequantize(qt, x.dtype)))
        )
        rel = float(np.max(np.abs(np.asarray(out) - np.asarray(ref)))
                    / max(float(np.max(np.abs(np.asarray(ref)))), 1e-9))
        parity.append({"case": name, "rel_err": round(rel, 9)})
    parity_ok = all(p["rel_err"] < 1e-4 for p in parity)

    # --- gate 3: fused vs inline-dequant serving streams at fp32 -----------
    config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
    params = init_quantized_llama_params(config, jax.random.key(0), mode="int8")
    page_size, n_new = 16, 12
    prompts = [rng.integers(1, config.vocab_size, size=n).tolist()
               for n in (44, 23)]
    max_seq_len = max(len(p) for p in prompts) + n_new + 2 * page_size
    pps = pages_needed(max_seq_len, page_size)

    def run_backend(qm_backend):
        ecfg = EngineConfig(max_seqs=2, page_size=page_size,
                            num_pages=2 * pps + 4, max_seq_len=max_seq_len,
                            prefill_chunk=32)
        engine = InferenceEngine(config, params, ecfg, quant="int8",
                                 qm_backend=qm_backend)
        engine.warmup()
        sched = ContinuousBatchingScheduler(engine, eos_id=-1)
        fused0 = METRICS.snapshot().get(
            "finchat_quantmatmul_fused_dispatches_total", 0)

        async def go():
            await sched.start()
            try:
                async def one(i, prompt):
                    handle = await sched.submit(
                        f"{qm_backend}-{i}", prompt,
                        SamplingParams(temperature=0.0, max_new_tokens=n_new))
                    toks = []
                    while True:
                        ev = await handle.events.get()
                        if ev["type"] == "token":
                            toks.append(ev["token_id"])
                        elif ev["type"] == "done":
                            return toks
                        else:
                            raise RuntimeError(str(ev))
                return list(await asyncio.gather(
                    *(one(i, p) for i, p in enumerate(prompts))))
            finally:
                await sched.stop()

        streams = asyncio.run(go())
        fused_d = METRICS.snapshot().get(
            "finchat_quantmatmul_fused_dispatches_total", 0) - fused0
        return streams, engine.compiled_variants, fused_d, \
            scheduler_leak_report(sched)

    ref_streams, ref_variants, ref_fused_d, leaks_r = run_backend("ref")
    fus_streams, fus_variants, fus_fused_d, leaks_f = run_backend(
        "pallas-interpret")
    identical = ref_streams == fus_streams
    print(f"[bench] quantmatmul: parity {parity}, streams identical="
          f"{identical}, variants ref={ref_variants} fused={fus_variants}, "
          f"fused dispatches {fus_fused_d}", file=sys.stderr, flush=True)

    all_leaks = leaks_r + leaks_f
    return {
        "metric": "quantmatmul_smoke",
        "unit": "rel logit delta, token streams",
        "model": "tiny (fp32 — the identity-gate discipline)",
        "parity": parity,
        "parity_ok": parity_ok,
        "ref_is_inline_dequant_bitwise": ref_bitwise,
        "streams_identical_fused_vs_ref": identical,
        "compiled_variants_ref": ref_variants,
        "compiled_variants_fused": fus_variants,
        "zero_new_compiled_variants": ref_variants == fus_variants,
        "fused_dispatches_ref_run": ref_fused_d,
        "fused_dispatches_fused_run": fus_fused_d,
        "fused_engaged": fus_fused_d > 0 and ref_fused_d == 0,
        "zero_leaks": not all_leaks,
        "leak_report": all_leaks,
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
    }


def measure_chaos_sweep(smoke: bool = False, rates: tuple = (0.05, 0.2)) -> dict:
    """Chaos benchmark of the resilience plane (ISSUE 5), CPU-runnable
    through the REAL scheduler on the tiny fp32 config (fp32 pins greedy
    byte-identity across the recompute-replay shapes).

    Section A — breaker: greedy streams decode while ``breaker_threshold``
    consecutive decode rounds are failed (utils.faults n_shot). The breaker
    must trip, the engine device state rebuild, and EVERY stream complete
    byte-identical to a fault-free run. Reports the rebuild count and the
    trip→recovery latency.

    Section B — page-pressure preemption: a deadline-less hog holds most of
    a deliberately small KV pool; an earlier-deadline request arrives at
    queue depth > free capacity. The hog must be recompute-preempted (not
    the candidate head-of-line-stalled), BOTH streams must complete, and
    the hog's replayed greedy stream must be byte-identical to an
    uncontended run — zero failed streams under nonzero preemptions.

    Section C (full sweep only) — fault-rate goodput: N requests per
    injected decode-fault probability; reports goodput (completed/
    submitted), wall time, preemptions, rebuilds, and sheds per rate.
    Under the preempt/replay discipline goodput should hold at 1.0 for
    moderate rates — faults cost re-prefills, not streams.
    """
    import asyncio
    import dataclasses

    import jax
    import jax.numpy as jnp

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.utils import faults
    from finchat_tpu.utils.config import EngineConfig
    from finchat_tpu.utils.metrics import METRICS

    config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
    params = init_params(config, jax.random.key(0))

    def make_scheduler(**over):
        cfg = dict(max_seqs=3, page_size=8, num_pages=96, max_seq_len=128,
                   prefill_chunk=16, session_cache=False)
        cfg.update(over)
        engine = InferenceEngine(config, params, EngineConfig(**cfg))
        return ContinuousBatchingScheduler(engine, eos_id=-1)

    async def drain(handle):
        tokens = []
        while True:
            ev = await handle.events.get()
            if ev["type"] == "token":
                tokens.append(ev["token_id"])
            elif ev["type"] == "done":
                return tokens, None
            else:
                return tokens, ev

    greedy = lambda n: SamplingParams(temperature=0.0, max_new_tokens=n)  # noqa: E731
    prompts = [list(range(1, 14)), list(range(20, 38)), list(range(50, 61))]

    # ---- section A: breaker trip + rebuild, streams survive -------------
    def run_breaker(fault: bool):
        async def go():
            sched = make_scheduler()
            await sched.start()
            try:
                handles = [await sched.submit(f"s{i}", p, greedy(10))
                           for i, p in enumerate(prompts)]
                tasks = [asyncio.create_task(drain(h)) for h in handles]
                if fault:
                    while any(h.generated < 2 for h in handles):
                        await asyncio.sleep(0.002)
                    faults.arm("scheduler.decode",
                               faults.n_shot(sched.breaker_threshold,
                                             RuntimeError("chaos: wedged dispatch")))
                results = [await asyncio.wait_for(t, timeout=300) for t in tasks]
                sched.allocator.check_invariants()
            finally:
                await sched.stop()
                faults.disarm_all()
            return results

        return asyncio.run(go())

    r0 = METRICS.get("finchat_engine_rebuilds_total")
    clean = run_breaker(False)
    t_fault = time.perf_counter()
    survived = run_breaker(True)
    breaker_wall_s = time.perf_counter() - t_fault
    rebuilds = int(METRICS.get("finchat_engine_rebuilds_total") - r0)
    streams_survive = all(err is None for _, err in survived)
    rebuild_identical = [t for t, _ in survived] == [t for t, _ in clean]
    recovery_p50_ms = round(
        1000 * METRICS.quantile("finchat_breaker_recovery_seconds", 0.5), 1
    )
    print(f"[bench] chaos breaker: rebuilds={rebuilds} survived={streams_survive} "
          f"identical={rebuild_identical} recovery_p50={recovery_p50_ms}ms",
          file=sys.stderr, flush=True)

    # ---- section B: page-pressure preemption, zero failed streams -------
    def run_pressure(contended: bool):
        async def go():
            # 7 usable pages; the hog takes 6, the urgent needs 3
            sched = make_scheduler(max_seqs=2, num_pages=8)
            await sched.start()
            try:
                hog = await sched.submit("hog", list(range(1, 24)), greedy(24))
                hog_task = asyncio.create_task(drain(hog))
                urgent_result = (None, None)
                if contended:
                    while hog.generated < 3:
                        await asyncio.sleep(0.002)
                    urgent = await sched.submit(
                        "urgent", list(range(40, 56)), greedy(8),
                        deadline=time.perf_counter() + 120.0,
                    )
                    urgent_result = await asyncio.wait_for(
                        asyncio.ensure_future(drain(urgent)), timeout=300
                    )
                hog_result = await asyncio.wait_for(hog_task, timeout=300)
                sched.allocator.check_invariants()
            finally:
                await sched.stop()
            return hog_result, urgent_result

        return asyncio.run(go())

    p0 = METRICS.get("finchat_preemptions_total")
    (clean_hog, _), _ = run_pressure(False)
    (hog_tokens, hog_err), (urgent_tokens, urgent_err) = run_pressure(True)
    preemptions = int(METRICS.get("finchat_preemptions_total") - p0)
    preempt_zero_failed = hog_err is None and urgent_err is None
    preempt_identical = hog_tokens == clean_hog
    print(f"[bench] chaos preemption: preemptions={preemptions} "
          f"zero_failed={preempt_zero_failed} identical={preempt_identical}",
          file=sys.stderr, flush=True)

    # ---- section C: fault-rate goodput sweep (full mode only) -----------
    rate_rows = []
    if not smoke:
        n_req = 6
        for rate in rates:
            async def go(rate=rate):
                sched = make_scheduler()
                await sched.start()
                try:
                    faults.arm("scheduler.decode",
                               faults.flaky(rate, RuntimeError("chaos flaky"), seed=7))
                    handles = [
                        await sched.submit(
                            f"r{rate}-{i}", prompts[i % len(prompts)], greedy(10),
                            deadline=time.perf_counter() + 600.0,
                        )
                        for i in range(n_req)
                    ]
                    return [
                        await asyncio.wait_for(asyncio.ensure_future(drain(h)), timeout=300)
                        for h in handles
                    ]
                finally:
                    await sched.stop()
                    faults.disarm_all()

            s0 = METRICS.snapshot()
            t0 = time.perf_counter()
            results = asyncio.run(go())
            wall = time.perf_counter() - t0
            s1 = METRICS.snapshot()
            completed = sum(1 for _, err in results if err is None)
            rate_rows.append({
                "fault_rate": rate,
                "submitted": n_req,
                "completed": completed,
                "goodput": round(completed / n_req, 3),
                "wall_s": round(wall, 2),
                "preemptions": int(s1.get("finchat_preemptions_total", 0)
                                   - s0.get("finchat_preemptions_total", 0)),
                "rebuilds": int(s1.get("finchat_engine_rebuilds_total", 0)
                                - s0.get("finchat_engine_rebuilds_total", 0)),
                "sheds": int(s1.get("finchat_sheds_total", 0)
                             - s0.get("finchat_sheds_total", 0)),
            })
            print(f"[bench] chaos rate {rate}: goodput "
                  f"{rate_rows[-1]['goodput']} ({completed}/{n_req}), "
                  f"preemptions {rate_rows[-1]['preemptions']}, "
                  f"rebuilds {rate_rows[-1]['rebuilds']}",
                  file=sys.stderr, flush=True)

    return {
        "metric": "chaos_sweep",
        "unit": "goodput, rebuilds, preemptions",
        "smoke": smoke,
        "model": "tiny (fp32 — identity contract, see measure_chaos_sweep)",
        # acceptance gates (tier1.yml --chaos-smoke)
        "streams_survive_rebuild": streams_survive,
        "rebuild_outputs_identical": rebuild_identical,
        "engine_rebuilds": rebuilds,
        "breaker_recovery_p50_ms": recovery_p50_ms,
        "breaker_wall_s": round(breaker_wall_s, 2),
        "preemptions": preemptions,
        "preempt_zero_failed": preempt_zero_failed,
        "preempt_outputs_identical": preempt_identical,
        "rate_sweep": rate_rows,
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
    }


def measure_fleet_sweep(smoke: bool = False, replicas: int = 4) -> dict:
    """Fleet chaos drill (ISSUE 6), CPU-runnable through REAL schedulers on
    the tiny fp32 config (fp32 pins greedy byte-identity across replicas —
    they share one params tree, so routing cannot change a greedy stream).

    With ``replicas`` engine replicas under one router, kill one mid-stream
    (wedge its decode dispatches until the breaker gives up):

    - every in-flight stream must COMPLETE BYTE-IDENTICAL on a sibling
      (breaker drain → adopt → recompute replay), zero user-visible errors;
    - the killed replica goes OUT (its partitions reassign) and the
      supervisor respawns it once the fault clears — replicas_live returns
      to N;
    - goodput for a request wave DURING the outage ≥ 3/4 (the router
      excludes the dead replica; survivors absorb), and 1.0 after respawn;
    - a conversation whose session-cache bytes lived on the killed replica
      gets them MIGRATED to the sibling its next turn routes to, and that
      turn admission-resumes from them (resumed, not cold, prefill
      profile: fewer prefill chunks than a cold start).
    """
    import asyncio
    import dataclasses

    import jax
    import jax.numpy as jnp

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.serve.fleet import LIVE, EngineFleet, EngineReplica
    from finchat_tpu.utils import faults
    from finchat_tpu.utils.config import EngineConfig, FleetConfig
    from finchat_tpu.utils.metrics import METRICS

    config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
    params = init_params(config, jax.random.key(0))
    PAGE, CHUNK = 8, 16

    def make_fleet() -> EngineFleet:
        reps = []
        for i in range(replicas):
            cfg = EngineConfig(
                max_seqs=3, page_size=PAGE, num_pages=96, max_seq_len=256,
                prefill_chunk=CHUNK, session_cache=True,
                session_cache_bytes=32 << 20, breaker_max_rebuilds=1,
            )
            engine = InferenceEngine(config, params, cfg)
            rid = str(i)
            reps.append(EngineReplica(
                replica_id=rid,
                scheduler=ContinuousBatchingScheduler(
                    engine, eos_id=-1,
                    metrics=METRICS.labeled(replica=rid), replica_id=rid,
                ),
            ))
        return EngineFleet(
            reps,
            FleetConfig(replicas=replicas, respawn_backoff_seconds=0.05,
                        supervisor_interval_seconds=0.05),
            num_partitions=32,
        )

    async def drain(handle):
        tokens = []
        while True:
            ev = await handle.events.get()
            if ev["type"] == "token":
                tokens.append(ev["token_id"])
            elif ev["type"] == "done":
                return tokens, None
            else:
                return tokens, ev

    greedy = lambda n: SamplingParams(temperature=0.0, max_new_tokens=n)  # noqa: E731
    t1_prompt = list(range(1, 14))
    stream_prompts = {f"fc{i}": list(range(10 * i + 1, 10 * i + 14))
                      for i in range(1, 5)}
    wave_n = 4 if smoke else 12

    async def turn(fleet, conv, prompt, n_new=10):
        rep = fleet.replica_for(conv)
        h = await rep.scheduler.submit(f"{conv}-t", prompt, greedy(n_new),
                                       conversation_id=conv)
        toks, err = await asyncio.wait_for(
            asyncio.ensure_future(drain(h)), timeout=300)
        return toks, err, h

    async def scenario(fault: bool) -> dict:
        fleet = make_fleet()
        await fleet.start()
        out: dict = {"errors": 0}
        try:
            # conversation "fmig": turn 1 retires a session entry on its
            # home replica — the one we will kill
            t1_tokens, err, _ = await turn(fleet, "fmig", t1_prompt)
            assert err is None, err
            out["t1_tokens"] = t1_tokens
            victim = fleet.replica_for("fmig")
            # in-flight streams spread over the fleet, plus one GUARANTEED
            # on the victim (the kill must be mid-stream there): scan conv
            # names until one routes to fmig's home replica
            prompts = dict(stream_prompts)
            conv_v = next(f"fv-{i}" for i in range(200)
                          if fleet.replica_for(f"fv-{i}") is victim)
            prompts[conv_v] = list(range(90, 104))
            handles = {}
            for conv, prompt in prompts.items():
                rep = fleet.replica_for(conv)
                handles[conv] = await rep.scheduler.submit(
                    conv + "-s", prompt, greedy(10), conversation_id=conv)
            tasks = {c: asyncio.create_task(drain(h)) for c, h in handles.items()}
            if fault:
                while any(h.generated < 2 for h in handles.values()):
                    await asyncio.sleep(0.002)
                dead = [True]

                def wedge(**ctx):
                    if dead[0] and ctx.get("replica") == victim.replica_id:
                        raise RuntimeError("fleet drill: dead replica")

                faults.arm("scheduler.decode", wedge)
                # a dead device fails its revive rebuild too: the victim
                # stays OUT (supervisor backing off) until the heal, so
                # the outage wave and the migration turn below really run
                # against the survivor set
                faults.arm("engine.rebuild", wedge)
            results = {c: await asyncio.wait_for(t, timeout=300)
                       for c, t in tasks.items()}
            out["stream_tokens"] = {c: toks for c, (toks, _e) in results.items()}
            out["errors"] += sum(1 for _toks, e in results.values() if e is not None)
            if fault:
                # keep poking the wedged replica until the breaker gives up
                # (probe streams drain to siblings and complete)
                for i in range(8):
                    if victim.scheduler.gave_up or victim.state != LIVE:
                        break
                    h = await victim.scheduler.submit(
                        f"probe{i}", list(range(200 + i, 212 + i)), greedy(4))
                    _toks, e = await asyncio.wait_for(
                        asyncio.ensure_future(drain(h)), timeout=300)
                    out["errors"] += 1 if e is not None else 0
                for _ in range(3000):
                    if victim.state != LIVE:
                        break
                    await asyncio.sleep(0.01)
                out["victim_out"] = victim.state != LIVE
                out["live_during"] = int(METRICS.get("finchat_fleet_replicas_live"))
                # outage wave: the router spreads over the survivors
                wave = []
                for i in range(wave_n):
                    conv = f"wave-{i}"
                    rep = fleet.replica_for(conv)
                    wave.append(await rep.scheduler.submit(
                        conv, list(range(60 + i, 74 + i)), greedy(6),
                        conversation_id=conv))
                wave_res = [await asyncio.wait_for(
                    asyncio.ensure_future(drain(h)), timeout=300) for h in wave]
                out["goodput_during"] = (
                    sum(1 for _t, e in wave_res if e is None) / wave_n)
            # turn 2 of fmig: during the outage it routes to a sibling,
            # which must MIGRATE the session bytes and resume from them
            t2_prompt = t1_prompt + t1_tokens + [7, 8, 9]
            t2_tokens, err, t2_handle = await turn(fleet, "fmig", t2_prompt)
            out["errors"] += 1 if err is not None else 0
            out["t2_tokens"] = t2_tokens
            out["t2_resumed_len"] = t2_handle.resumed_len
            if fault:
                # heal the device; the supervisor respawns the replica
                dead[0] = False
                for _ in range(3000):
                    if victim.state == LIVE:
                        break
                    await asyncio.sleep(0.01)
                out["victim_respawned"] = victim.state == LIVE
                out["live_after"] = int(METRICS.get("finchat_fleet_replicas_live"))
                wave = []
                for i in range(wave_n):
                    conv = f"after-{i}"
                    rep = fleet.replica_for(conv)
                    wave.append(await rep.scheduler.submit(
                        conv, list(range(120 + i, 134 + i)), greedy(6),
                        conversation_id=conv))
                wave_res = [await asyncio.wait_for(
                    asyncio.ensure_future(drain(h)), timeout=300) for h in wave]
                out["goodput_after"] = (
                    sum(1 for _t, e in wave_res if e is None) / wave_n)
            for rep in fleet.replicas:
                rep.scheduler.allocator.check_invariants()
        finally:
            await fleet.stop()
            faults.disarm_all()
        return out

    d0 = METRICS.get("finchat_fleet_drained_streams_total")
    m0 = METRICS.get("finchat_fleet_session_migrations_total")
    clean = asyncio.run(scenario(False))
    t0 = time.perf_counter()
    chaos = asyncio.run(scenario(True))
    wall = time.perf_counter() - t0
    drained = int(METRICS.get("finchat_fleet_drained_streams_total") - d0)
    migrations = int(METRICS.get("finchat_fleet_session_migrations_total") - m0)

    kill_identical = (
        chaos["stream_tokens"] == clean["stream_tokens"]
        and chaos["t2_tokens"] == clean["t2_tokens"]
    )
    resumed = int(chaos["t2_resumed_len"])
    t2_len = len(t1_prompt) + len(clean["t1_tokens"]) + 3
    chunks_cold = -(-t2_len // CHUNK)
    chunks_resumed = -(-(t2_len - resumed) // CHUNK)
    migrated_resume_ok = migrations >= 1 and resumed > 0 and chunks_resumed < chunks_cold
    print(f"[bench] fleet kill-one: drained={drained} errors={chaos['errors']} "
          f"identical={kill_identical} victim_out={chaos.get('victim_out')} "
          f"respawned={chaos.get('victim_respawned')}", file=sys.stderr, flush=True)
    print(f"[bench] fleet goodput: during={chaos.get('goodput_during')} "
          f"after={chaos.get('goodput_after')} live {chaos.get('live_during')}"
          f"→{chaos.get('live_after')}", file=sys.stderr, flush=True)
    print(f"[bench] fleet migration: migrations={migrations} resumed_len={resumed} "
          f"prefill_chunks {chunks_cold}→{chunks_resumed}", file=sys.stderr, flush=True)

    return {
        "metric": "fleet_sweep",
        "unit": "goodput, drained streams, migrations",
        "smoke": smoke,
        "replicas": replicas,
        "model": "tiny (fp32 — identity contract, see measure_fleet_sweep)",
        # acceptance gates (tier1.yml --fleet-smoke; ISSUE 6)
        "streams_survive_kill": chaos["errors"] == 0,
        "kill_outputs_identical": kill_identical,
        "drained_streams": drained,
        "victim_out": bool(chaos.get("victim_out")),
        "victim_respawned": bool(chaos.get("victim_respawned")),
        "replicas_live_during": chaos.get("live_during"),
        "replicas_live_after": chaos.get("live_after"),
        "goodput_during": chaos.get("goodput_during"),
        "goodput_after": chaos.get("goodput_after"),
        "session_migrations": migrations,
        "t2_resumed_len": resumed,
        "prefill_chunks_cold": chunks_cold,
        "prefill_chunks_resumed": chunks_resumed,
        "migrated_resume_ok": migrated_resume_ok,
        "wall_s": round(wall, 2),
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
    }


def measure_pod_sweep(smoke: bool = False) -> dict:
    """Pod-scale multi-host drill (ISSUE 20), CPU-runnable through REAL
    schedulers on the tiny fp32 config: 2 simulated hosts x 2 replicas,
    each host one Kafka consumer-group member (partition assignment IS
    the cross-host routing table), liaison channels between them, the
    warm-state fabric (ISSUE 17) as the shared disk tier, and one shared
    per-partition journal directory. kill -9 one whole host mid-stream:

    - the surviving host's streams COMPLETE BYTE-IDENTICAL to a clean
      run, zero user-visible errors;
    - goodput during the detection GAP (peer killed, death not yet
      declared) >= the surviving host's partition share, and 1.0 once
      the dead host's partitions are adopted;
    - a conversation homed on the dead host resumes on the adopter
      warm from the shared fabric record, byte-identical (and a second
      conversation exercises the live-peer liaison pull path, also
      byte-identical);
    - the adopter replays exactly the inherited per-partition journals
      into its dedupe ring — the dead host's already-answered id is a
      duplicate on the adopter (no double answer after the kill);
    - a no-liaison single-host control (pod attached, zero peers) is
      byte-identical to the plain fleet and never touches a pod counter.
    """
    import asyncio
    import dataclasses
    import os
    import tempfile

    import jax
    import jax.numpy as jnp

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.engine.warm_fabric import WarmFabric
    from finchat_tpu.io.journal import AnsweredJournal
    from finchat_tpu.io.kafka import InMemoryBroker, KafkaClient
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.serve.fleet import DedupeRing, EngineFleet, EngineReplica
    from finchat_tpu.serve.pod import PEER_DEAD, PodCoordinator
    from finchat_tpu.utils import faults
    from finchat_tpu.utils.config import (
        EngineConfig,
        FleetConfig,
        KafkaConfig,
        PodConfig,
    )
    from finchat_tpu.utils.metrics import METRICS

    config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
    params = init_params(config, jax.random.key(0))
    PAGE, CHUNK = 8, 16
    N_PARTS = 8
    wave_n = 4 if smoke else 8
    t1_prompt = list(range(1, 14))

    def make_fleet(host_tag: str, fabric) -> EngineFleet:
        reps = []
        for i in range(2):
            cfg = EngineConfig(
                max_seqs=3, page_size=PAGE, num_pages=96, max_seq_len=256,
                prefill_chunk=CHUNK, session_cache=True,
                session_cache_bytes=32 << 20, breaker_max_rebuilds=1,
            )
            engine = InferenceEngine(config, params, cfg)
            rid = f"{host_tag}{i}"
            reps.append(EngineReplica(
                replica_id=rid,
                scheduler=ContinuousBatchingScheduler(
                    engine, eos_id=-1,
                    metrics=METRICS.labeled(replica=rid), replica_id=rid,
                    fabric=fabric,
                ),
            ))
        return EngineFleet(
            reps,
            FleetConfig(replicas=2, respawn_backoff_seconds=0.05,
                        supervisor_interval_seconds=0.05),
            num_partitions=32,
        )

    def pod_cfg(host: str, listen: str = "", peers: str = "") -> PodConfig:
        return PodConfig(
            host_id=host, listen=listen, peers=peers,
            # the drill drives heartbeats by hand for determinism
            heartbeat_interval_seconds=60.0, heartbeat_miss_threshold=2,
            transfer_timeout_seconds=2.0, transfer_retries=1,
            retry_backoff_seconds=0.0, breaker_threshold=3,
            breaker_cooldown_seconds=0.05,
        )

    async def drain(handle):
        tokens = []
        while True:
            ev = await handle.events.get()
            if ev["type"] == "token":
                tokens.append(ev["token_id"])
            elif ev["type"] == "done":
                return tokens, None
            else:
                return tokens, ev

    greedy = lambda n: SamplingParams(temperature=0.0, max_new_tokens=n)  # noqa: E731
    seq_counter = [0]

    async def turn(fleet, conv, prompt, n_new=10):
        seq_counter[0] += 1
        rep = fleet.replica_for(conv)
        h = await rep.scheduler.submit(
            f"{conv}-t{seq_counter[0]}", prompt, greedy(n_new),
            conversation_id=conv,
        )
        toks, err = await asyncio.wait_for(
            asyncio.ensure_future(drain(h)), timeout=300)
        return toks, err, h

    async def scenario(chaos: bool, tag: str) -> dict:
        out: dict = {"errors": 0}
        base = tempfile.mkdtemp(prefix=f"finchat-pod-{tag}-")
        broker = InMemoryBroker(num_partitions=N_PARTS)
        ka = KafkaClient(KafkaConfig(num_partitions=N_PARTS), broker=broker)
        kb = KafkaClient(KafkaConfig(num_partitions=N_PARTS), broker=broker)
        # pin the member ids so the assignment (positional round-robin over
        # the SORTED member list) — and with it every conversation's owner
        # — is identical across the clean/chaos/control runs
        ka._member_id, kb._member_id = "member-hostA", "member-hostB"
        ka.setup_consumer()
        kb.setup_consumer()
        parts_a = {p for _t, p in ka.assignment()}
        parts_b = {p for _t, p in kb.assignment()}
        part_of = ka.partition_for
        # ONE fabric tier: simulated pods in one process share the tier
        # instance the way real hosts share the fabric directory
        fabric = WarmFabric(os.path.join(base, "fabric"), 1 << 30)
        jdir = os.path.join(base, "journal")
        ja = AnsweredJournal(jdir, num_partitions=N_PARTS)
        jb = AnsweredJournal(jdir, num_partitions=N_PARTS)
        ring_a, ring_b = DedupeRing(256), DedupeRing(256)
        fleet_a = make_fleet("a", fabric)
        fleet_b = make_fleet("b", fabric)
        coord_a = PodCoordinator(
            pod_cfg("hostA", listen=f"inproc:{tag}-hostA",
                    peers=f"hostB=inproc:{tag}-hostB"),
            fleet=fleet_a, kafka=ka, journal=ja, dedupe=ring_a,
        )
        coord_b = PodCoordinator(
            pod_cfg("hostB", listen=f"inproc:{tag}-hostB",
                    peers=f"hostA=inproc:{tag}-hostA"),
            fleet=fleet_b, kafka=kb, journal=jb, dedupe=ring_b,
        )
        for rep in fleet_a.replicas:
            rep.scheduler.pod = coord_a
        for rep in fleet_b.replicas:
            rep.scheduler.pod = coord_b

        def fleet_for(conv):
            return fleet_a if part_of(conv) in parts_a else fleet_b

        try:
            await fleet_a.start()
            await fleet_b.start()
            await coord_a.start()
            await coord_b.start()
            peer_a = coord_b.peers["hostA"]
            peer_b = coord_a.peers["hostB"]
            # first heartbeat exchange: each side learns the other's Kafka
            # member id (needed to evict the member on a death verdict)
            await coord_b._heartbeat(peer_a)
            await coord_a._heartbeat(peer_b)
            assert peer_a.member_id == ka.member_id

            # pmig: homed on host A — the fabric-migration conversation.
            # lmig: owned by host B but SERVED by A (the pre-rebalance
            # owner) — the liaison-pull conversation.
            pmig = next(f"pm-{i}" for i in range(200)
                        if part_of(f"pm-{i}") in parts_a)
            lmig = next(f"lm-{i}" for i in range(200)
                        if part_of(f"lm-{i}") in parts_b)
            out["pmig"], out["lmig"] = pmig, lmig
            out["pm1"], err, _ = await turn(fleet_a, pmig, t1_prompt)
            assert err is None, err
            out["lm1"], err, _ = await turn(fleet_a, lmig, t1_prompt)
            assert err is None, err
            # host A answered pmig: journal the id into its partition's
            # file (fsync-before-commit), dedupe-ring it locally
            ja.append(f"mid-{pmig}", partition=part_of(pmig))
            ring_a.seen(f"mid-{pmig}")
            # wait for the write-through records to land on the fabric
            for _ in range(2000):
                if pmig in fabric.tier and lmig in fabric.tier:
                    break
                await asyncio.sleep(0.005)
            assert pmig in fabric.tier
            # evict lmig's fabric record (stand-in for the tier's LRU):
            # its only warm copy is now host A's RAM, so the cross-host
            # turn below MUST come over the liaison
            fabric.tier.discard(lmig)
            await asyncio.to_thread(fabric.tier.flush)
            assert lmig not in fabric.tier

            # liaison migration while both hosts are live: lmig turn 2 on
            # its real owner B pulls the session bytes from A's RAM
            lm2_prompt = t1_prompt + out["lm1"] + [7, 8, 9]
            out["lm2"], err, h = await turn(fleet_b, lmig, lm2_prompt)
            out["errors"] += 1 if err is not None else 0
            out["lm2_resumed"] = h.resumed_len

            # in-flight streams, two per host, routed by partition owner
            streams: dict[str, list] = {}
            picked_a = picked_b = 0
            i = 0
            while picked_a < 2 or picked_b < 2:
                conv = f"ps-{i}"
                i += 1
                on_a = part_of(conv) in parts_a
                if on_a and picked_a < 2:
                    picked_a += 1
                elif not on_a and picked_b < 2:
                    picked_b += 1
                else:
                    continue
                streams[conv] = list(range(10 * i + 1, 10 * i + 15))
            out["streams"] = streams
            handles = {}
            for conv, prompt in streams.items():
                rep = fleet_for(conv).replica_for(conv)
                handles[conv] = await rep.scheduler.submit(
                    conv + "-s", prompt, greedy(10), conversation_id=conv)
            tasks = {c: asyncio.create_task(drain(h))
                     for c, h in handles.items()}

            if chaos:
                while any(h.generated < 2 for h in handles.values()):
                    await asyncio.sleep(0.002)
                # kill -9 the whole host: liaison off the wire with no
                # goodbye, heartbeat task dead mid-flight
                coord_a.kill()
                # the GAP: host A's share is ownerless until the failure
                # detector fires — only the survivor's share serves
                gap_served = 0
                gap_a = gap_b = 0
                j = 0
                while gap_a + gap_b < wave_n:
                    conv = f"gap-{j}"
                    j += 1
                    if part_of(conv) in parts_a:
                        if gap_a < wave_n // 2:
                            gap_a += 1  # dead owner, no adopter yet: lost
                        continue
                    if gap_b >= wave_n - wave_n // 2:
                        continue
                    gap_b += 1
                    _toks, e, _h = await turn(fleet_b, conv,
                                              list(range(60 + j, 74 + j)),
                                              n_new=6)
                    gap_served += 1 if e is None else 0
                out["goodput_during"] = gap_served / wave_n
                out["surviving_share"] = len(parts_b) / N_PARTS
                # failure detector: miss_threshold consecutive failed
                # heartbeats declare hostA dead -> evict its member ->
                # adopt its partitions -> replay its journals
                await coord_b._heartbeat(peer_a)
                await coord_b._heartbeat(peer_a)
                out["peer_dead"] = peer_a.state == PEER_DEAD
                out["hosts_live"] = int(METRICS.get("finchat_pod_hosts_live"))
                out["adopted_all"] = (
                    {p for _t, p in kb.assignment()} == parts_a | parts_b)
                # exactly-once across the kill: the id host A answered and
                # journaled is a DUPLICATE on the adopter
                out["dedupe_inherited"] = ring_b.seen(f"mid-{pmig}")
                # post-adoption wave: every partition has an owner again
                aft_served = 0
                for k in range(wave_n):
                    conv = f"aft-{k}"
                    _toks, e, _h = await turn(fleet_b, conv,
                                              list(range(120 + k, 134 + k)),
                                              n_new=6)
                    aft_served += 1 if e is None else 0
                out["goodput_after"] = aft_served / wave_n

            results = {c: await asyncio.wait_for(t, timeout=300)
                       for c, t in tasks.items()}
            out["stream_tokens"] = {c: toks
                                    for c, (toks, _e) in results.items()}
            out["errors"] += sum(
                1 for c, (_t, e) in results.items()
                if e is not None and not (chaos and part_of(c) in parts_a))

            # pmig turn 2: in the chaos run its partition now belongs to
            # the adopter, whose admission resumes warm from the shared
            # fabric record (host A's RAM died with it)
            pm2_prompt = t1_prompt + out["pm1"] + [7, 8, 9]
            out["pm2"], err, h = await turn(
                fleet_b if chaos else fleet_a, pmig, pm2_prompt)
            out["errors"] += 1 if err is not None else 0
            out["pm2_resumed"] = h.resumed_len

            for rep in (*fleet_a.replicas, *fleet_b.replicas):
                rep.scheduler.allocator.check_invariants()
        finally:
            await fleet_a.stop()
            await fleet_b.stop()
            await coord_b.stop()
            await coord_a.stop()
            ja.close()
            jb.close()
            await asyncio.to_thread(fabric.tier.close)
            faults.disarm_all()
        return out

    async def control(clean: dict) -> dict:
        """Single host, pod attached but ZERO peers: the no-liaison
        degradation — must be byte-identical to the plain fleet and
        never move a pod counter."""
        out: dict = {"errors": 0}
        fleet = make_fleet("c", None)
        solo = PodCoordinator(pod_cfg("solo"))
        for rep in fleet.replicas:
            rep.scheduler.pod = solo
        try:
            await fleet.start()
            await solo.start()
            pmig, lmig = clean["pmig"], clean["lmig"]
            out["pm1"], err, _ = await turn(fleet, pmig, t1_prompt)
            out["errors"] += 1 if err is not None else 0
            out["lm1"], err, _ = await turn(fleet, lmig, t1_prompt)
            out["errors"] += 1 if err is not None else 0
            lm2_prompt = t1_prompt + out["lm1"] + [7, 8, 9]
            out["lm2"], err, _ = await turn(fleet, lmig, lm2_prompt)
            out["errors"] += 1 if err is not None else 0
            handles = {}
            for conv, prompt in clean["streams"].items():
                rep = fleet.replica_for(conv)
                handles[conv] = await rep.scheduler.submit(
                    conv + "-s", prompt, greedy(10), conversation_id=conv)
            results = {c: await drain(h) for c, h in handles.items()}
            out["stream_tokens"] = {c: toks
                                    for c, (toks, _e) in results.items()}
            out["errors"] += sum(1 for _t, e in results.values()
                                 if e is not None)
            pm2_prompt = t1_prompt + out["pm1"] + [7, 8, 9]
            out["pm2"], err, _ = await turn(fleet, pmig, pm2_prompt)
            out["errors"] += 1 if err is not None else 0
            for rep in fleet.replicas:
                rep.scheduler.allocator.check_invariants()
        finally:
            await fleet.stop()
            await solo.stop()
        return out

    pulls0 = METRICS.get("finchat_pod_session_pulls_total")
    clean = asyncio.run(scenario(False, "clean"))
    clean_pulls = int(METRICS.get("finchat_pod_session_pulls_total") - pulls0)

    pulls0 = METRICS.get("finchat_pod_session_pulls_total")
    adopt0 = METRICS.get("finchat_pod_partition_adoptions_total")
    replay0 = METRICS.get("finchat_pod_adopted_ids_replayed_total")
    death0 = METRICS.get("finchat_pod_peer_deaths_total")
    t0 = time.perf_counter()
    chaos = asyncio.run(scenario(True, "chaos"))
    wall = time.perf_counter() - t0
    chaos_pulls = int(METRICS.get("finchat_pod_session_pulls_total") - pulls0)
    adoptions = int(METRICS.get("finchat_pod_partition_adoptions_total") - adopt0)
    replayed = int(METRICS.get("finchat_pod_adopted_ids_replayed_total") - replay0)
    deaths = int(METRICS.get("finchat_pod_peer_deaths_total") - death0)

    pod_counters = (
        "finchat_pod_session_pulls_total", "finchat_pod_pull_misses_total",
        "finchat_pod_heartbeats_total", "finchat_pod_peer_deaths_total",
    )
    ctr0 = {m: METRICS.get(m) for m in pod_counters}
    control_out = asyncio.run(control(clean))
    pod_silent = all(METRICS.get(m) == ctr0[m] for m in pod_counters)

    migrated_identical = (
        chaos["pm2"] == clean["pm2"] and chaos["lm2"] == clean["lm2"]
        and chaos["stream_tokens"] == clean["stream_tokens"]
    )
    control_identical = (
        control_out["pm2"] == clean["pm2"]
        and control_out["lm2"] == clean["lm2"]
        and control_out["stream_tokens"] == clean["stream_tokens"]
    )
    goodput_floor_ok = (
        chaos.get("goodput_during", 0.0) >= chaos.get("surviving_share", 1.0))
    print(f"[bench] pod kill-a-host: errors={chaos['errors']} "
          f"peer_dead={chaos.get('peer_dead')} adopted_all={chaos.get('adopted_all')} "
          f"adoptions={adoptions} replayed={replayed} deaths={deaths}",
          file=sys.stderr, flush=True)
    print(f"[bench] pod goodput: during={chaos.get('goodput_during')} "
          f"(share={chaos.get('surviving_share')}) "
          f"after={chaos.get('goodput_after')} hosts_live={chaos.get('hosts_live')}",
          file=sys.stderr, flush=True)
    print(f"[bench] pod migration: fabric_resumed={chaos.get('pm2_resumed')} "
          f"liaison_resumed={chaos.get('lm2_resumed')} "
          f"pulls clean={clean_pulls} chaos={chaos_pulls} "
          f"identical={migrated_identical} control_identical={control_identical} "
          f"dedupe_inherited={chaos.get('dedupe_inherited')}",
          file=sys.stderr, flush=True)

    return {
        "metric": "pod_sweep",
        "unit": "goodput, adopted partitions, replayed ids",
        "smoke": smoke,
        "hosts": 2,
        "replicas_per_host": 2,
        "partitions": N_PARTS,
        "model": "tiny (fp32 — identity contract, see measure_fleet_sweep)",
        # acceptance gates (tier1.yml --pod-smoke; ISSUE 20)
        "streams_survive_kill": chaos["errors"] == 0,
        "migrated_outputs_identical": migrated_identical,
        "peer_dead_detected": bool(chaos.get("peer_dead")),
        "adopted_all_partitions": bool(chaos.get("adopted_all")),
        "partition_adoptions": adoptions,
        "adopted_ids_replayed": replayed,
        "dedupe_inherited": bool(chaos.get("dedupe_inherited")),
        "goodput_during": chaos.get("goodput_during"),
        "surviving_share": chaos.get("surviving_share"),
        "goodput_floor_ok": goodput_floor_ok,
        "goodput_after": chaos.get("goodput_after"),
        "hosts_live_after_kill": chaos.get("hosts_live"),
        "fabric_resumed_len": int(chaos.get("pm2_resumed", 0)),
        "liaison_resumed_len": int(chaos.get("lm2_resumed", 0)),
        "session_pulls_clean": clean_pulls,
        "session_pulls_chaos": chaos_pulls,
        "control_identical": control_identical,
        "control_pod_plane_silent": pod_silent,
        "control_errors": control_out["errors"],
        "wall_s": round(wall, 2),
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
    }


def measure_disagg_sweep(smoke: bool = False) -> dict:
    """Disaggregated prefill/decode + warm-fabric drill (ISSUE 17),
    CPU-runnable through REAL schedulers on the tiny fp32 config.

    Section A — prefill storm against a 2+2 pool split: steady decode
    streams run on the decode pool while a wave of COLD long-prompt
    conversations arrives. With role-typed pools each cold prompt
    prefills on a prefill replica (whose dispatches run off-loop in
    worker threads) and only the finished KV crosses to the decode
    replica, so the steady streams' inter-token p99 inside the storm
    window must stay flat vs the pre-storm window of the SAME run
    (within 10%, plus an absolute CPU-scheduling-jitter allowance — the
    in-run baseline controls for machine load). The mixed-fleet control
    runs the same storm for comparison, and the storm conversations'
    greedy streams must be BYTE-IDENTICAL disagg vs mixed (the handoff
    cannot change a stream). Every handoff is counted; zero leaked
    slots/pages after the wave.

    Section B — warm-state fabric: a conversation retired by one
    scheduler resumes on a SECOND scheduler that never saw it, through
    the fabric's shared tier: TTFT strictly below the cold control's,
    strictly fewer prefill chunks, byte-identical greedy output.
    """
    import asyncio
    import dataclasses
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.engine.warm_fabric import WarmFabric
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.serve.disagg import ROLE_DECODE, ROLE_PREFILL
    from finchat_tpu.serve.fleet import EngineFleet, EngineReplica
    from finchat_tpu.utils.config import EngineConfig, FleetConfig
    from finchat_tpu.utils.metrics import METRICS

    config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
    params = init_params(config, jax.random.key(0))
    PAGE, CHUNK, MAX_SEQS = 8, 16, 4
    storm_n = 2 if smoke else 6
    steady_new = 300 if smoke else 600
    pre_storm_tokens = 24 if smoke else 48
    storm_prompt_len = 64

    def make_sched(rid: str, fabric=None) -> ContinuousBatchingScheduler:
        cfg = EngineConfig(
            max_seqs=MAX_SEQS, page_size=PAGE, num_pages=160,
            max_seq_len=512, prefill_chunk=CHUNK, session_cache=True,
            session_cache_bytes=32 << 20, breaker_max_rebuilds=1,
        )
        engine = InferenceEngine(config, params, cfg)
        return ContinuousBatchingScheduler(
            engine, eos_id=-1, metrics=METRICS.labeled(replica=rid),
            replica_id=rid, fabric=fabric,
        )

    def make_fleet(roles) -> EngineFleet:
        reps = [EngineReplica(replica_id=str(i), scheduler=make_sched(str(i)),
                              role=role)
                for i, role in enumerate(roles)]
        return EngineFleet(
            reps, FleetConfig(replicas=len(roles), respawn=False),
            num_partitions=32,
        )

    greedy = lambda n: SamplingParams(temperature=0.0, max_new_tokens=n)  # noqa: E731

    async def stamped_drain(handle):
        """(tokens, [(arrival_s, token)], error): per-token wall stamps."""
        tokens, stamps = [], []
        while True:
            ev = await asyncio.wait_for(handle.events.get(), timeout=600)
            if ev["type"] == "token":
                stamps.append((time.perf_counter(), ev["token_id"]))
                tokens.append(ev["token_id"])
            elif ev["type"] == "done":
                return tokens, stamps, None
            else:
                return tokens, stamps, ev

    def window_gaps(stamps, t_lo, t_hi):
        gaps = []
        for (ta, _), (tb, _) in zip(stamps, stamps[1:]):
            if t_lo <= tb <= t_hi:
                gaps.append(tb - ta)
        return gaps

    async def storm_scenario(roles) -> dict:
        fleet = make_fleet(roles)
        await fleet.start()
        out: dict = {"errors": 0}
        try:
            serving = [r for r in fleet.replicas if r.role != ROLE_PREFILL]
            # one steady decode stream pinned to each serving replica
            # (short prompt: under one chunk of cold work, so no handoff)
            steady = {}
            for rep in serving:
                conv = next(f"steady-{rep.replica_id}-{i}"
                            for i in range(300)
                            if fleet.replica_for(f"steady-{rep.replica_id}-{i}") is rep)
                steady[conv] = await rep.scheduler.submit(
                    conv, list(range(1, 14)), greedy(steady_new),
                    conversation_id=conv)
            steady_tasks = {c: asyncio.create_task(stamped_drain(h))
                            for c, h in steady.items()}

            async def one_cold(i: int, name: str = "storm"):
                conv = f"{name}-{i}"
                rep = fleet.replica_for(conv)
                prompt = [(37 * i + k) % 250 + 1
                          for k in range(storm_prompt_len)]
                h = await rep.scheduler.submit(
                    conv, prompt, greedy(8), conversation_id=conv)
                toks, _stamps, err = await stamped_drain(h)
                return conv, toks, err, h.resumed_len

            # warmup wave: the FIRST handoff import / resume-prefill on a
            # replica pays its one-time jit compile — run one cold conv
            # pinned to EACH serving replica outside the measured windows
            # so the storm measures steady-state cost, not compilation
            warm_ids = [next(100 + i for i in range(300)
                             if fleet.replica_for(f"warmup-{100 + i}") is rep)
                        for rep in serving]
            warm_wave = await asyncio.gather(
                *(one_cold(i, "warmup") for i in warm_ids))
            out["errors"] += sum(1 for _c, _t, e, _r in warm_wave
                                 if e is not None)
            # quiet pre-storm window: every steady stream generates
            # pre_storm_tokens more with no cold traffic in flight
            marks = {c: h.generated for c, h in steady.items()}
            t_settled = time.perf_counter()
            while any(h.generated - marks[c] < pre_storm_tokens
                      for c, h in steady.items()):
                await asyncio.sleep(0.002)

            t0 = time.perf_counter()
            storm = await asyncio.gather(
                *(one_cold(i) for i in range(storm_n)))
            t1 = time.perf_counter()
            out["errors"] += sum(1 for _c, _t, e, _r in storm
                                 if e is not None)
            out["storm_tokens"] = {c: t for c, t, _e, _r in sorted(storm)}
            out["storm_resumed"] = {c: r for c, _t, _e, r in sorted(storm)}
            steady_res = {c: await asyncio.wait_for(t, timeout=600)
                          for c, t in steady_tasks.items()}
            out["errors"] += sum(1 for _t, _s, e in steady_res.values()
                                 if e is not None)
            pre, during = [], []
            for _toks, stamps, _e in steady_res.values():
                pre += window_gaps(stamps, t_settled, t0)
                during += window_gaps(stamps, t0, t1)
            out["p99_pre"] = float(np.percentile(pre, 99)) if pre else 0.0
            out["p99_storm"] = (float(np.percentile(during, 99))
                                if during else 0.0)
            out["storm_wall"] = t1 - t0
            # zero-leak audit: every slot back, allocator invariants hold
            for rep in fleet.replicas:
                rep.scheduler.allocator.check_invariants()
                assert len(rep.scheduler.free_slots) == MAX_SEQS, (
                    rep.replica_id, rep.scheduler.free_slots)
            out["zero_leaks"] = True
        finally:
            await fleet.stop()
        return out

    h0 = sum(METRICS.get("finchat_disagg_handoffs_total", {"replica": rid})
             for rid in ("0", "1", "2", "3"))
    t_start = time.perf_counter()
    disagg = asyncio.run(storm_scenario(
        [ROLE_PREFILL, ROLE_PREFILL, ROLE_DECODE, ROLE_DECODE]))
    handoffs = int(
        sum(METRICS.get("finchat_disagg_handoffs_total", {"replica": rid})
            for rid in ("0", "1", "2", "3")) - h0)
    mixed = asyncio.run(storm_scenario(["mixed"] * 4))
    wall = time.perf_counter() - t_start

    storm_identical = disagg["storm_tokens"] == mixed["storm_tokens"]
    # flatness vs the SAME run's pre-storm window: 10% relative, plus an
    # absolute allowance on CPU hosts where BOTH pools share the same
    # cores (a storm necessarily steals decode cycles, and the handoff
    # admission round — page restore + residue chunk — serializes with
    # decode dispatch; ~50ms per concurrently-admitting storm conv).
    # On a real split-pool deployment the 10% relative term is the gate.
    p99_gate = max(1.10 * disagg["p99_pre"],
                   disagg["p99_pre"] + 0.050 * max(2, storm_n))
    p99_flat = disagg["p99_storm"] <= p99_gate
    resumed_all = all(r > 0 for r in disagg["storm_resumed"].values())
    print(f"[bench] disagg storm: handoffs={handoffs} errors={disagg['errors']} "
          f"identical={storm_identical} resumed={disagg['storm_resumed']}",
          file=sys.stderr, flush=True)
    print(f"[bench] disagg decode p99: pre={disagg['p99_pre'] * 1e3:.2f}ms "
          f"storm={disagg['p99_storm'] * 1e3:.2f}ms (gate {p99_gate * 1e3:.2f}ms) "
          f"mixed-storm={mixed['p99_storm'] * 1e3:.2f}ms",
          file=sys.stderr, flush=True)

    # --- Section B: warm-state fabric TTFT -----------------------------
    prompt1 = list(range(1, 65))
    prompt_wu = list(range(80, 144))

    async def fabric_turns(sched, turns):
        """Run [(seq, prompt, conv)] turns in order on a started
        scheduler; returns [(tokens, ttft_s, resumed_len)] per turn."""
        await sched.start()
        out = []
        try:
            for seq, prompt, conv in turns:
                t_sub = time.perf_counter()
                h = await sched.submit(seq, prompt, greedy(8),
                                       conversation_id=conv)
                toks, stamps, err = await stamped_drain(h)
                assert err is None, err
                out.append((toks, stamps[0][0] - t_sub, h.resumed_len))
            return out
        finally:
            await sched.stop()

    def fabric_scenario(tag: str, shared: bool):
        root = tempfile.mkdtemp(prefix=f"disagg_fabric_{tag}_")
        cold_root = None
        fabric = WarmFabric(root, 64 << 20)
        cold_fabric = None
        try:
            a = make_sched(f"f{tag}a", fabric=fabric)
            (wu1, _wt, _wr), (t1, _tt, _tr) = asyncio.run(fabric_turns(a, [
                ("w1", prompt_wu, "fwu"), ("t1", prompt1, "fconv")]))
            fabric.flush()
            if shared:
                b_fabric = fabric
            else:
                cold_root = tempfile.mkdtemp(
                    prefix=f"disagg_fabric_{tag}_cold_")
                cold_fabric = WarmFabric(cold_root, 64 << 20)
                b_fabric = cold_fabric
            b = make_sched(f"f{tag}b", fabric=b_fabric)
            # warmup turn first: compiles b's turn-2 code path (fabric
            # restore when shared, plain prefill when cold) OUTSIDE the
            # measured TTFT, so warm-vs-cold compares steady-state cost
            prompt_wu2 = prompt_wu + wu1 + [7, 8]
            prompt2 = prompt1 + t1 + [3, 4, 5]
            _wu, (t2, ttft2, resumed) = asyncio.run(fabric_turns(b, [
                ("w2", prompt_wu2, "fwu"), ("t2", prompt2, "fconv")]))
            return {"t2": t2, "ttft": ttft2, "resumed": int(resumed),
                    "len2": len(prompt2)}
        finally:
            fabric.close()
            if cold_fabric is not None:
                cold_fabric.close()
            shutil.rmtree(root, ignore_errors=True)
            if cold_root is not None:
                shutil.rmtree(cold_root, ignore_errors=True)

    hits0 = METRICS.get("finchat_fabric_hits_total", {"replica": "fwb"})
    warm = fabric_scenario("w", shared=True)
    fabric_hits = int(METRICS.get("finchat_fabric_hits_total",
                                  {"replica": "fwb"}) - hits0)
    cold = fabric_scenario("c", shared=False)
    chunks_cold = -(-cold["len2"] // CHUNK)
    chunks_warm = -(-(warm["len2"] - warm["resumed"]) // CHUNK)
    fabric_identical = warm["t2"] == cold["t2"]
    fabric_ttft_ok = warm["ttft"] < cold["ttft"]
    print(f"[bench] fabric warm resume: ttft {cold['ttft'] * 1e3:.1f}ms → "
          f"{warm['ttft'] * 1e3:.1f}ms, prefill chunks {chunks_cold}→"
          f"{chunks_warm}, hits={fabric_hits}, identical={fabric_identical}",
          file=sys.stderr, flush=True)

    return {
        "metric": "disagg_sweep",
        "unit": "inter-token p99 (s), handoffs, TTFT (s)",
        "smoke": smoke,
        "model": "tiny (fp32 — identity contract, see measure_disagg_sweep)",
        # acceptance gates (tier1.yml --disagg-smoke; ISSUE 17)
        "storm_streams_survive": disagg["errors"] == 0,
        "storm_outputs_identical": storm_identical,
        "handoffs": handoffs,
        "handoffs_ok": handoffs >= storm_n,
        "storm_resumed_all": resumed_all,
        "decode_p99_pre_s": round(disagg["p99_pre"], 5),
        "decode_p99_storm_s": round(disagg["p99_storm"], 5),
        "decode_p99_mixed_storm_s": round(mixed["p99_storm"], 5),
        "decode_p99_flat": p99_flat,
        "zero_leaks": bool(disagg.get("zero_leaks"))
        and bool(mixed.get("zero_leaks")),
        "fabric_ttft_warm_s": round(warm["ttft"], 5),
        "fabric_ttft_cold_s": round(cold["ttft"], 5),
        "fabric_ttft_ok": fabric_ttft_ok,
        "fabric_hits": fabric_hits,
        "prefill_chunks_cold": chunks_cold,
        "prefill_chunks_warm": chunks_warm,
        "fabric_chunks_ok": chunks_warm < chunks_cold,
        "fabric_outputs_identical": fabric_identical,
        "wall_s": round(wall, 2),
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
    }


def measure_trace_overhead() -> dict:
    """Tracing-plane gate (ISSUE 12), CPU-runnable through the REAL
    scheduler on the tiny fp32 config.

    Section A — overhead + identity: the same decode-dominated workload
    (3 greedy streams) runs in alternating traced/untraced reps on ONE
    warmed scheduler; throughput compares MEDIAN-of-reps walls on each
    side (the median absorbs one-sided scheduler-jitter outliers — the
    quantity under test is a deque append per event), gated < 2%, and
    the token streams must be byte-identical traced vs untraced (tracing
    must never change output).

    Section B — export: one traced request's ``TRACER.export`` must be a
    schema-valid Chrome/Perfetto trace containing admitted → dispatch
    (with the request's own rows) → first_token → done.

    Section C — flight recorder: ``breaker_threshold`` injected decode
    faults trip the breaker with a flight dir armed; the dump must load
    with a valid checksum and contain the trip anomaly plus dispatch
    spans carrying the tripped streams' trace ids.
    """
    import asyncio
    import dataclasses
    import tempfile

    import jax
    import jax.numpy as jnp

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.sampler import SamplingParams
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.utils import faults
    from finchat_tpu.utils.config import EngineConfig
    from finchat_tpu.utils.metrics import METRICS
    from finchat_tpu.utils.tracing import TRACER, load_flight_dump

    config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
    params = init_params(config, jax.random.key(0))

    def make_scheduler():
        engine = InferenceEngine(config, params, EngineConfig(
            max_seqs=4, page_size=8, num_pages=128, max_seq_len=256,
            prefill_chunk=16, session_cache=False,
        ))
        return ContinuousBatchingScheduler(engine, eos_id=-1)

    async def drain(handle):
        tokens = []
        while True:
            ev = await handle.events.get()
            if ev["type"] == "token":
                tokens.append(ev["token_id"])
            elif ev["type"] == "done":
                return tokens, None
            else:
                return tokens, ev

    prompts = [list(range(1, 14)), list(range(20, 38)), list(range(50, 61))]
    # decode-dominated and long enough that per-rep wall is ~0.3 s on the
    # tiny CPU config — median-of-7 alternating reps puts scheduler jitter
    # well under the 2% gate (the quantity under test is a deque append)
    TOKENS_PER_STREAM = 128
    greedy = SamplingParams(temperature=0.0, max_new_tokens=TOKENS_PER_STREAM)
    REPS = 7

    # ---- sections A + B: overhead, identity, export ---------------------
    async def run_reps(sched):
        async def rep(traced: bool, tag: str):
            TRACER.configure(enabled=traced)
            t0 = time.perf_counter()
            handles = [
                await sched.submit(
                    f"{tag}-{i}", p, greedy,
                    trace_id=f"trace-{tag}-{i}" if traced else None,
                )
                for i, p in enumerate(prompts)
            ]
            results = await asyncio.gather(*[drain(h) for h in handles])
            wall = time.perf_counter() - t0
            assert all(err is None for _t, err in results), results
            return wall, [t for t, _e in results]

        await rep(True, "warm")  # compiles + first-touch, discarded
        walls_off, walls_on = [], []
        tokens_off = tokens_on = None
        for r in range(REPS):
            w, tokens_off = await rep(False, f"off{r}")
            walls_off.append(w)
            w, tokens_on = await rep(True, f"on{r}")
            walls_on.append(w)
        return walls_off, walls_on, tokens_off, tokens_on

    async def section_ab():
        sched = make_scheduler()
        await sched.start()
        try:
            return await run_reps(sched)
        finally:
            await sched.stop()

    TRACER.clear()
    walls_off, walls_on, tokens_off, tokens_on = asyncio.run(section_ab())
    total_tokens = 3 * TOKENS_PER_STREAM

    def mid(walls):  # median absorbs one-sided scheduler-jitter outliers
        s = sorted(walls)
        return s[len(s) // 2]

    tput_off = total_tokens / mid(walls_off)
    tput_on = total_tokens / mid(walls_on)
    overhead_pct = (mid(walls_on) - mid(walls_off)) / mid(walls_off) * 100.0
    outputs_identical = tokens_off == tokens_on

    export = TRACER.export(f"trace-on{REPS - 1}-0")
    names = [e["name"] for e in export["traceEvents"]]
    own_dispatches = [
        e for e in export["traceEvents"]
        if e["name"] == "dispatch"
        and any(r[1] == f"trace-on{REPS - 1}-0" for r in e["args"]["rows"])
    ]
    export_valid = (
        all(n in names for n in ("admitted", "prefill_done", "first_token",
                                 "done", "request", "dispatch"))
        and len(own_dispatches) >= 2  # its prefill + decode rounds
        and all(e.get("ph") in ("X", "i") and "ts" in e and "tid" in e
                for e in export["traceEvents"])
        and bool(json.dumps(export))
    )
    print(f"[bench] trace overhead: off={mid(walls_off):.3f}s "
          f"on={mid(walls_on):.3f}s overhead={overhead_pct:+.2f}% "
          f"identical={outputs_identical} export_events={len(names)}",
          file=sys.stderr, flush=True)

    # ---- section C: breaker-trip flight dump ----------------------------
    flight_dir = tempfile.mkdtemp(prefix="finchat-flight-")
    rebuilds0 = METRICS.get("finchat_engine_rebuilds_total")

    async def section_c():
        TRACER.configure(enabled=True, flight_dir=flight_dir)
        TRACER.clear()
        sched = make_scheduler()
        await sched.start()
        try:
            handles = [
                await sched.submit(f"trip-{i}", p, greedy,
                                   trace_id=f"trace-trip-{i}")
                for i, p in enumerate(prompts)
            ]
            tasks = [asyncio.create_task(drain(h)) for h in handles]
            while any(h.generated < 2 for h in handles):
                await asyncio.sleep(0.002)
            faults.arm("scheduler.decode",
                       faults.n_shot(sched.breaker_threshold,
                                     RuntimeError("trace drill: wedged dispatch")))
            results = [await asyncio.wait_for(t, timeout=300) for t in tasks]
            return all(err is None for _t, err in results)
        finally:
            await sched.stop()
            faults.disarm_all()
            TRACER.configure(flight_dir="")

    streams_survived = asyncio.run(section_c())
    TRACER.flush_dumps()
    TRACER.configure(enabled=True)
    import glob as _glob

    dump_paths = sorted(_glob.glob(os.path.join(flight_dir, "flight-*.json")))
    flight_ok = flight_has_trip = flight_has_dispatch_rows = False
    if dump_paths:
        try:
            rec = load_flight_dump(dump_paths[0])
            flight_ok = True
            events = rec["trace"]["traceEvents"]
            flight_has_trip = (rec["reason"] == "breaker_trip"
                               and any(e["name"] == "breaker_trip" for e in events))
            flight_has_dispatch_rows = any(
                e["name"] == "dispatch"
                and any(str(r[1]).startswith("trace-trip-")
                        for r in e["args"]["rows"])
                for e in events
            )
        except ValueError as e:
            print(f"[bench] flight dump failed validation: {e}",
                  file=sys.stderr, flush=True)
    rebuilds = int(METRICS.get("finchat_engine_rebuilds_total") - rebuilds0)
    print(f"[bench] trace flight drill: dumps={len(dump_paths)} "
          f"checksum_ok={flight_ok} trip={flight_has_trip} "
          f"dispatch_rows={flight_has_dispatch_rows} rebuilds={rebuilds} "
          f"survived={streams_survived}", file=sys.stderr, flush=True)

    return {
        "metric": "trace_overhead",
        "model": "tiny-fp32",
        "tokens_per_rep": total_tokens,
        "reps": REPS,
        "walls_untraced_s": [round(w, 4) for w in walls_off],
        "walls_traced_s": [round(w, 4) for w in walls_on],
        "tput_untraced_tok_s": round(tput_off, 1),
        "tput_traced_tok_s": round(tput_on, 1),
        "overhead_pct": round(overhead_pct, 2),
        "overhead_under_2pct": overhead_pct < 2.0,
        "outputs_identical": outputs_identical,
        "export_valid": export_valid,
        "export_dispatches": len(own_dispatches),
        "flight_dumps": len(dump_paths),
        "flight_checksum_ok": flight_ok,
        "flight_has_trip": flight_has_trip,
        "flight_has_dispatch_rows": flight_has_dispatch_rows,
        "streams_survive_trip": streams_survived,
        "engine_rebuilds": rebuilds,
        "double_finish_total": int(METRICS.get("finchat_span_double_finish_total")),
    }


def measure_durability_sweep(smoke: bool = False) -> dict:
    """Crash-restart + graceful-drain drill (ISSUE 7), CPU-runnable through
    a REAL App over the memory Kafka broker on the tiny fp32 config (fp32
    pins greedy byte-identity across the restart — both processes share one
    params tree).

    Phase 1 (crash): with the answered-message journal, committed-offset
    persistence, and the session disk tier on — answer turn 1 of
    conversation A (journaled + committed), answer conversation B but
    CRASH before its offset commits (journaled, uncommitted — the exact
    fsync-before-commit window), and crash mid-stream on turn 2 of A.
    Restart over the same broker:

    - B redelivers and is SKIPPED (journal replay seeded the dedupe ring):
      zero double answers;
    - A's turn 2 redelivers and reprocesses to completion, and every
      final stored answer is byte-identical to an uninterrupted control
      run;
    - turn 2's admission RESUMES from the disk tier (restores >= 1,
      restored tokens > 0) — the restarted process is warm, not cold.

    Phase 2 (drain): SIGTERM-equivalent ``drain_and_stop`` with a message
    mid-stream — the stream COMPLETES within the deadline, the scheduler
    exits with zero slot/page leaks, and a post-restart turn resumes from
    the spilled session bytes.
    """
    import asyncio
    import dataclasses
    import os as _os
    import tempfile

    import jax
    import jax.numpy as jnp

    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.engine.generator import EngineGenerator, StubGenerator
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.io.kafka import InMemoryBroker, KafkaClient
    from finchat_tpu.io.store import InMemoryStore
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.models.tokenizer import ByteTokenizer
    from finchat_tpu.serve.app import build_app
    from finchat_tpu.utils.config import (
        AI_RESPONSE_TOPIC,
        USER_MESSAGE_TOPIC,
        EngineConfig,
        load_config,
    )
    from finchat_tpu.utils.metrics import METRICS

    config = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32)
    params = init_params(config, jax.random.key(0))
    tok = ByteTokenizer()
    root = tempfile.mkdtemp(prefix="finchat-durability-")
    n_new = 6 if smoke else 10

    def make_cfg(tag: str):
        cfg = load_config(overrides={"model.preset": "stub"})
        cfg.engine.temperature = 0.0
        cfg.engine.max_new_tokens = n_new
        cfg.kafka.commit_after_process = True
        cfg.journal.path = _os.path.join(root, tag, "journal")
        cfg.kafka.offsets_dir = cfg.journal.path
        cfg.engine.session_cache_disk_path = _os.path.join(root, tag, "disk")
        cfg.shutdown.deadline_seconds = 60.0
        return cfg

    def make_sched(cfg):
        ecfg = EngineConfig(
            max_seqs=4, page_size=8, num_pages=128, max_seq_len=256,
            prefill_chunk=16, session_cache=True, session_cache_bytes=32 << 20,
            session_cache_disk_path=cfg.engine.session_cache_disk_path,
            session_cache_disk_bytes=64 << 20,
        )
        return ContinuousBatchingScheduler(
            InferenceEngine(config, params, ecfg), eos_id=-1
        )

    class NullRetriever:
        async def __call__(self, args):
            return []

    def make_store():
        store = InMemoryStore()
        for conv in ("convA", "convB"):
            store.upsert_context(conv, {
                "user_id": "u1", "name": "Alex", "income": 5000,
                "savings_goal": 800,
            })
            store.add_user_message(conv, "hello", "u1")
        return store

    def make_app(cfg, broker, store, sched):
        app = build_app(
            cfg, store=store, kafka=KafkaClient(cfg.kafka, broker=broker),
            tool_generator=StubGenerator(default="No tool call"),
            response_generator=EngineGenerator(sched, tok),
            retriever=NullRetriever(),
        )
        app.scheduler = sched  # drain/stop manage the injected engine
        return app

    def produce(broker, cfg, conv, mid, text):
        KafkaClient(cfg.kafka, broker=broker).produce_message(
            USER_MESSAGE_TOPIC, conv,
            {"message": text, "conversation_id": conv, "user_id": "u1",
             "message_id": mid},
        )

    def chunks(broker):
        import json as _json

        return [_json.loads(m.value().decode())
                for m in broker.drain(AI_RESPONSE_TOPIC)]

    def n_complete(broker, mid):
        return sum(1 for c in chunks(broker)
                   if c.get("type") == "complete" and c.get("message_id") == mid)

    def n_chunks(broker, mid):
        return sum(1 for c in chunks(broker)
                   if c.get("type") == "response_chunk"
                   and c.get("message_id") == mid)

    async def wait_for(pred, timeout=240.0):
        import time as _time

        t0 = _time.perf_counter()
        while not pred():
            if _time.perf_counter() - t0 > timeout:
                raise TimeoutError("durability drill: condition not reached")
            await asyncio.sleep(0.01)

    async def crash(app, sched):
        """Process-kill emulation: no graceful drain, no commits, no
        journal close — just tear the tasks down and leave the group (a
        real crash ends in session-timeout eviction)."""
        app._running = False
        if app._consume_task:
            app._consume_task.cancel()
            try:
                await app._consume_task
            except asyncio.CancelledError:
                pass
        for t in list(app._inflight):
            t.cancel()
        if app._inflight:
            await asyncio.gather(*app._inflight, return_exceptions=True)
        await sched.stop()
        # the write-behind spill queue drains in milliseconds while a real
        # crash's restart takes seconds; flushing models that gap
        # deterministically, so the restart's directory sweep can't race
        # an in-flight record write from the dead scheduler's writer
        if sched.session_cache is not None and sched.session_cache.disk is not None:
            sched.session_cache.disk.flush()
        app.kafka.close()

    async def answered_texts(store):
        return {conv: [m.message for m in await store.get_history(conv)
                       if m.sender == "AIMessage"]
                for conv in ("convA", "convB")}

    async def control() -> dict:
        cfg = make_cfg("control")
        broker = InMemoryBroker(offsets_dir=cfg.kafka.offsets_dir)
        store = make_store()
        sched = make_sched(cfg)
        app = make_app(cfg, broker, store, sched)
        await app.start(serve_http=False)
        try:
            for mid, conv, text in (("mA1", "convA", "how am I doing?"),
                                    ("mB", "convB", "what changed?"),
                                    ("mA2", "convA", "and my savings?")):
                produce(broker, cfg, conv, mid, text)
                await wait_for(lambda mid=mid: n_complete(broker, mid) >= 1)
        finally:
            await app.stop()
        return {"answers": await answered_texts(store)}

    async def crash_restart() -> dict:
        cfg = make_cfg("crash")
        broker = InMemoryBroker(offsets_dir=cfg.kafka.offsets_dir)
        store = make_store()
        out: dict = {}
        sched1 = make_sched(cfg)
        app1 = make_app(cfg, broker, store, sched1)
        await app1.start(serve_http=False)
        # turn 1 of A: answered, journaled, COMMITTED (wait for the commit
        # itself — the done-callback runs a beat after the complete chunk)
        c0 = METRICS.get("finchat_kafka_commits_total")
        j0 = METRICS.get("finchat_durability_journal_appends_total")
        produce(broker, cfg, "convA", "mA1", "how am I doing?")
        await wait_for(lambda: n_complete(broker, "mA1") >= 1
                       and METRICS.get("finchat_kafka_commits_total") > c0)
        # from here the process "dies before committing": B answers (and
        # journals, fsync) but its offset commit is lost
        app1.kafka.commit_offset = lambda *a, **k: None
        produce(broker, cfg, "convB", "mB", "what changed?")
        await wait_for(lambda: n_complete(broker, "mB") >= 1 and
                       METRICS.get("finchat_durability_journal_appends_total")
                       >= j0 + 2)
        # turn 2 of A: crash MID-STREAM (some chunks out, no complete).
        # Slow decode while this turn streams so the crash lands
        # deterministically mid-stream — a 6-token turn can otherwise
        # finish inside one poll interval of the chunk watcher
        from finchat_tpu.utils import faults as _faults

        import time as _time

        _faults.arm("scheduler.decode", lambda **_: _time.sleep(0.02))
        try:
            produce(broker, cfg, "convA", "mA2", "and my savings?")
            await wait_for(lambda: n_chunks(broker, "mA2") >= 1)
            await crash(app1, sched1)
        finally:
            _faults.disarm("scheduler.decode")
        assert n_complete(broker, "mA2") == 0, (
            "drill setup: the crash was meant to land mid-stream"
        )
        out["completes_before_restart"] = {
            mid: n_complete(broker, mid) for mid in ("mA1", "mB", "mA2")
        }
        # restart: same broker (group rewinds to the committed watermark),
        # same journal + disk dirs — mB and mA2 redeliver
        r0 = METRICS.get("finchat_durability_disk_restores_total")
        rt0 = METRICS.get("finchat_session_cache_restored_tokens_total")
        d0 = METRICS.get("finchat_kafka_dedupe_skips_total")
        sched2 = make_sched(cfg)
        app2 = make_app(cfg, broker, store, sched2)
        await app2.start(serve_http=False)
        try:
            await wait_for(lambda: n_complete(broker, "mA2") >= 1)
            # give the redelivered-mB dedupe skip a beat to be counted
            await wait_for(lambda: METRICS.get("finchat_kafka_dedupe_skips_total") > d0)
        finally:
            await app2.stop()
        out["completes"] = {mid: n_complete(broker, mid)
                           for mid in ("mA1", "mB", "mA2")}
        out["dedupe_skips"] = int(
            METRICS.get("finchat_kafka_dedupe_skips_total") - d0)
        out["disk_restores"] = int(
            METRICS.get("finchat_durability_disk_restores_total") - r0)
        out["restored_tokens"] = int(
            METRICS.get("finchat_session_cache_restored_tokens_total") - rt0)
        out["answers"] = await answered_texts(store)
        return out

    async def drain_drill() -> dict:
        cfg = make_cfg("drain")
        broker = InMemoryBroker(offsets_dir=cfg.kafka.offsets_dir)
        store = make_store()
        out: dict = {}
        sched = make_sched(cfg)
        app = make_app(cfg, broker, store, sched)
        await app.start(serve_http=False)
        produce(broker, cfg, "convA", "mD1", "how am I doing?")
        await wait_for(lambda: n_chunks(broker, "mD1") >= 1)
        # SIGTERM: the in-flight stream must COMPLETE within the deadline
        await app.drain_and_stop()
        out["drain_completed"] = n_complete(broker, "mD1") >= 1
        out["zero_leaks"] = (
            sched.allocator.used_count == 0
            and len(sched.free_slots) == 4
            and not sched.decoding and not sched.prefilling and not sched.pending
        )
        # restart after the graceful drain: the next turn resumes from the
        # spilled session bytes
        r0 = METRICS.get("finchat_durability_disk_restores_total")
        sched2 = make_sched(cfg)
        app2 = make_app(cfg, broker, store, sched2)
        await app2.start(serve_http=False)
        try:
            produce(broker, cfg, "convA", "mD2", "and my savings?")
            await wait_for(lambda: n_complete(broker, "mD2") >= 1)
        finally:
            await app2.stop()
        out["restart_restores"] = int(
            METRICS.get("finchat_durability_disk_restores_total") - r0)
        return out

    t0 = time.perf_counter()
    clean = asyncio.run(control())
    chaos = asyncio.run(crash_restart())
    drain = asyncio.run(drain_drill())
    wall = time.perf_counter() - t0

    zero_double = all(n == 1 for n in chaos["completes"].values())
    identical = chaos["answers"] == clean["answers"]
    resumed = chaos["disk_restores"] >= 1 and chaos["restored_tokens"] > 0
    print(f"[bench] durability crash: completes={chaos['completes']} "
          f"dedupe_skips={chaos['dedupe_skips']} identical={identical} "
          f"disk_restores={chaos['disk_restores']} "
          f"restored_tokens={chaos['restored_tokens']}",
          file=sys.stderr, flush=True)
    print(f"[bench] durability drain: completed={drain['drain_completed']} "
          f"zero_leaks={drain['zero_leaks']} "
          f"restart_restores={drain['restart_restores']}",
          file=sys.stderr, flush=True)

    return {
        "metric": "durability_sweep",
        "unit": "crash/drain gates",
        "smoke": smoke,
        "model": "tiny (fp32 — identity contract, see measure_durability_sweep)",
        # acceptance gates (tier1.yml --durability-smoke; ISSUE 7)
        "zero_double_answers": zero_double,
        "answered_before_restart": chaos["completes_before_restart"],
        "completes_per_message": chaos["completes"],
        "journal_dedupe_skips": chaos["dedupe_skips"],
        "crash_outputs_identical": identical,
        "crash_restart_resumed": resumed,
        "disk_restores": chaos["disk_restores"],
        "restored_tokens": chaos["restored_tokens"],
        "drain_completed_inflight": drain["drain_completed"],
        "drain_zero_leaks": drain["zero_leaks"],
        "drained_restart_resumed": drain["restart_restores"] >= 1,
        "wall_s": round(wall, 2),
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
    }


# --------------------------------------------------------------------------
# Orchestrator: jax-free; spawns workers, never hangs, always prints JSON.
# --------------------------------------------------------------------------

def spawn_worker(args: argparse.Namespace, platform: str, timeout: float) -> dict | None:
    cmd = [sys.executable, os.path.abspath(__file__), "--worker",
           "--platform", platform, "--tpu-timeout", str(args.tpu_timeout),
           "--measure-budget", str(args.measure_budget)]
    for flag in ("preset", "batch", "prompt_len", "steps", "warmup",
                 "page_size", "max_seq_len", "attn", "quant", "quant_group",
                 "kv_quant", "spec_tokens"):
        v = getattr(args, flag)
        if v is not None:
            cmd += ["--" + flag.replace("_", "-"), str(v)]
    if args.decode_loop_sweep:
        cmd += ["--decode-loop-sweep",
                "--decode-loop-depths", args.decode_loop_depths]
    if args.session_sweep:
        cmd += ["--session-sweep", "--session-turns", str(args.session_turns)]
    if args.retrieval_sweep:
        cmd += ["--retrieval-sweep",
                "--retrieval-concurrency", args.retrieval_concurrency,
                "--retrieval-windows-ms", args.retrieval_windows_ms]
        if args.retrieval_smoke:
            cmd += ["--retrieval-smoke"]
    if args.mixed_sweep:
        cmd += ["--mixed-sweep"]
        if args.mixed_smoke:
            cmd += ["--mixed-smoke"]
    if args.ragged_sweep or args.ragged_smoke:
        cmd += (["--ragged-smoke"] if args.ragged_smoke
                else ["--ragged-sweep"])
    if args.freerun_sweep or args.freerun_smoke:
        cmd += (["--freerun-smoke"] if args.freerun_smoke
                else ["--freerun-sweep"])
    if args.longctx_sweep or args.longctx_smoke:
        cmd += (["--longctx-smoke"] if args.longctx_smoke
                else ["--longctx-sweep"])
        cmd += ["--longctx-tokens", str(args.longctx_tokens)]
    if args.tool_overlap_sweep or args.tool_overlap_smoke:
        cmd += (["--tool-overlap-smoke"] if args.tool_overlap_smoke
                else ["--tool-overlap-sweep"])
    if args.chaos_sweep or args.chaos_smoke:
        cmd += ["--chaos-rates", args.chaos_rates]
        cmd += ["--chaos-smoke"] if args.chaos_smoke else ["--chaos-sweep"]
    if args.durability_sweep or args.durability_smoke:
        cmd += (["--durability-smoke"] if args.durability_smoke
                else ["--durability-sweep"])
    if args.fleet_sweep or args.fleet_smoke:
        cmd += ["--fleet-replicas", str(args.fleet_replicas)]
        cmd += ["--fleet-smoke"] if args.fleet_smoke else ["--fleet-sweep"]
    if args.pod_sweep or args.pod_smoke:
        cmd += ["--pod-smoke"] if args.pod_smoke else ["--pod-sweep"]
    if args.disagg_sweep or args.disagg_smoke:
        cmd += (["--disagg-smoke"] if args.disagg_smoke
                else ["--disagg-sweep"])
    if args.quant_sweep or args.quant_smoke:
        cmd += (["--quant-smoke"] if args.quant_smoke else ["--quant-sweep"])
    if args.quantmatmul_smoke:
        cmd += ["--quantmatmul-smoke"]
    if args.trace_overhead:
        cmd += ["--trace-overhead"]
    print(f"[bench] spawning {platform} worker (timeout {timeout:.0f}s)",
          file=sys.stderr, flush=True)
    try:
        proc = subprocess.run(
            cmd, timeout=timeout, capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired as e:
        sys.stderr.write((e.stderr or b"").decode() if isinstance(e.stderr, bytes)
                         else (e.stderr or ""))
        print(f"[bench] {platform} worker timed out after {timeout:.0f}s (killed)",
              file=sys.stderr, flush=True)
        return None
    sys.stderr.write(proc.stderr or "")
    if proc.returncode != 0:
        print(f"[bench] {platform} worker exited rc={proc.returncode}",
              file=sys.stderr, flush=True)
        return None
    for line in reversed((proc.stdout or "").strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    print(f"[bench] {platform} worker produced no JSON line", file=sys.stderr)
    return None


def main() -> int:
    args = build_parser().parse_args()
    if args.worker:
        return run_worker(args)

    if args.platform in ("auto", "tpu"):
        # parent budget = init budget + measurement budget, so the child's
        # own watchdogs (which produce stack dumps) fire first
        result = spawn_worker(
            args, "tpu", timeout=args.tpu_timeout + args.measure_budget + 30.0
        )
        if result is None:
            print("[bench] no TPU measurement (no chip, failed start or "
                  "timeout); a CPU number is not a substitute",
                  file=sys.stderr)
            return 1
    else:
        result = spawn_worker(args, "cpu", timeout=600.0)
        if result is None:
            return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
