#!/usr/bin/env python3
"""perfbench/run.py — run one cell of the benchmark once.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process (the chip belongs to one process): it refuses any backend but
``tpu`` (a ``rehearsal`` cell of ``perfbench/rehearsal.json`` instead demands
``JAX_PLATFORMS=cpu`` and marks every number not-a-measurement), places the
compile cache inside the checkout, assembles the server as
``python -m finchat_tpu`` does and drives it through the reference's own
ingress (store + Kafka) from an asyncio task. Phases: set-up (weights from the
seed, warm-up, the logits check, ingest, stored histories, a lead-in of the
cell's own traffic) → the measured window → the drain of what was due in it →
one JSON line, the last of standard output.

``--sweep r1,r2,...`` is the mode the driver never calls: one set-up, then
the cell's traffic at each session rate for one window, printing per rate the
latency metrics, completions against due, and the backlog at the end.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import faulthandler  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import urllib.request  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

DRAIN_LIMIT_S = 20.0      # a counted request not ended by then is failed
                          # (a traffic file may set its own ``drain_limit_s``)
TRACE_SECONDS = 4.0       # the profiler's capture, in mid-window
HARD_DEADLINE_S = 1150    # dump every stack and exit rather than hang
LIVE_SAMPLE_S = 0.25


def say(message: str) -> None:
    print(message, flush=True)


def prom_totals(text: str) -> dict[str, float]:
    """Prometheus text → each series name's value summed over label sets
    (a histogram's ``_sum`` and ``_count`` are series of their own)."""
    totals: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        series, _, value = line.rpartition(" ")
        name = series.split("{", 1)[0]
        try:
            totals[name] = totals.get(name, 0.0) + float(value)
        except ValueError:
            continue
    return totals


def post_json(url: str, payload: dict, timeout: float = 300) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def device_object(jax, chips: int) -> dict:
    devices = jax.devices()[:chips]
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


class Window:
    """The bookkeeping of one measured window: snapshots at both ends."""

    def __init__(self, w0: float, seconds: float):
        self.w0, self.w1 = w0, w0 + seconds
        self.prom = [None, None]
        self.steps = [None, None]
        self.compiles = [0, 0]


async def measure_window(app, gen, seconds: float, lead_in_s: float, *,
                         trace_dir: Path | None, compile_count, backlog: bool,
                         drain_limit_s: float):
    """Run the lead-in, the window and the drain on a started generator.
    Returns the Window, the TRACER events inside it, the profiler's capture
    (or None) and the live-KV samples (``live_kv.sample``)."""
    import jax

    from finchat_tpu.utils.metrics import METRICS
    from finchat_tpu.utils.tracing import TRACER
    from perfbench import correct, live_kv

    win = Window(gen.t0 + lead_in_s, seconds)
    await asyncio.sleep(max(0.0, win.w0 - time.perf_counter()))
    win.w0 = time.perf_counter()
    win.w1 = win.w0 + seconds
    win.prom[0] = prom_totals(METRICS.render_prometheus())
    win.steps[0] = correct.engine_step_cache_sizes()
    win.compiles[0] = compile_count()

    live_samples: list[dict] = []
    sync = None

    async def sample_live() -> None:
        """The rows decoding now, counted by distinct physical pages; each
        sample also goes into a running capture as an event of its own, so
        the readers find it on the clock of the kernels."""
        sched = app.scheduler
        page = sched.engine.page_size
        while True:
            s = live_kv.sample(sched.decoding.values(), page)
            live_samples.append(s)
            with jax.profiler.TraceAnnotation(
                    live_kv.LIVE_ANNOTATION, rows=s["rows"], kv_tokens=s["kv_tokens"],
                    kv_tokens_distinct=s["kv_tokens_distinct"]):
                pass
            await asyncio.sleep(LIVE_SAMPLE_S)

    sampler = asyncio.create_task(sample_live()) if trace_dir is not None else None
    if trace_dir is not None:
        capture = min(TRACE_SECONDS, seconds / 3)
        await asyncio.sleep(max(0.0, win.w0 + 0.4 * seconds - time.perf_counter()))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        with jax.profiler.TraceAnnotation("perfbench_sync"):
            sync = time.perf_counter()
        await asyncio.sleep(capture)
        await asyncio.to_thread(jax.profiler.stop_trace)
    await asyncio.sleep(max(0.0, win.w1 - time.perf_counter()))
    win.prom[1] = prom_totals(METRICS.render_prometheus())
    win.steps[1] = correct.engine_step_cache_sizes()
    win.compiles[1] = compile_count()
    if sampler is not None:
        sampler.cancel()
    events = [ev for ev in TRACER.snapshot() if win.w0 <= ev[0] < win.w1]

    if not backlog:  # drain what was due in the window
        limit = win.w1 + drain_limit_s
        while time.perf_counter() < limit:
            pending = [r for r in gen.requests.values()
                       if win.w0 <= r.due < win.w1 and r.ended is None]
            if not pending:
                break
            await asyncio.sleep(0.05)
    return win, events, sync, live_samples


def host_label(events: list, offset: float):
    """What the host was doing in an idle gap of the device, from TRACER: the
    kind of the last dispatch before the gap began, or that nothing was in
    flight. ``offset`` maps the trace's clock (ns) onto perf_counter."""
    dispatches = sorted((ev[0], (ev[5] or {}).get("kind", "?"))
                        for ev in events if ev[2] == "dispatch")

    def label(start_ns: int, end_ns: int) -> str:
        t = start_ns / 1e9 + offset
        last = None
        for ts, kind in dispatches:
            if ts > t:
                break
            last = kind
        size = "short(<1ms)" if end_ns - start_ns < 1_000_000 else "long(>=1ms)"
        return f"after_dispatch:{last or 'none'} {size}"

    return label


async def run_cell(args, cell, jax) -> dict:
    from finchat_tpu.serve.app import build_app
    from perfbench import correct, reduce, trace_reduce
    from perfbench.layer_metrics import Context, read_metric
    from perfbench.load import LoadGenerator
    from perfbench.server import app_config

    traffic_kind = importlib.import_module(
        f"perfbench.traffic_kinds.{cell.traffic['kind']}")
    work = ROOT / ".perfbench_work"
    work.mkdir(exist_ok=True)
    lead_in = float(cell.traffic.get("lead_in_s", 18))
    backlog = cell.traffic["arrival"]["process"] == "backlog"
    rates = [float(r) for r in args.sweep.split(",")] if args.sweep else [None]

    compiles = 0

    def on_event(event: str, _duration: float, **_kw) -> None:
        nonlocal compiles
        if event == "/jax/core/compile/backend_compile_duration":
            compiles += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)

    answer_cap = int(cell.traffic["answer_cap"])
    cfg = app_config(cell.config_name, cell.config, answer_cap=answer_cap,
                     work_dir=work)
    t = time.perf_counter()
    app = build_app(cfg)
    engine = app.scheduler.engine
    say(f"set-up: build_app {time.perf_counter() - t:.1f} s, attn_backend="
        f"{engine.attn_backend}, {engine.compiled_variants} variants warmed, "
        f"{compiles} programs compiled or loaded")
    if not cell.rehearsal and engine.attn_backend != "pallas":
        raise SystemExit(f"perfbench: engine resolved attn_backend="
                         f"{engine.attn_backend!r}, not the compiled kernels")

    t = time.perf_counter()
    logits = correct.check_logits(app, cell.config, args.seed)
    say(f"correct(a) logits vs reference: {json.dumps(logits)} "
        f"({time.perf_counter() - t:.1f} s)")

    drain_limit = float(cell.traffic.get("drain_limit_s", DRAIN_LIMIT_S))
    horizon = lead_in + args.seconds + drain_limit + 5  # load runs on through the drain
    measured = None
    await app.start(serve_http=True)
    try:
        base = f"http://127.0.0.1:{cfg.serve.port}"
        traffic = None
        for i, rate in enumerate(rates):
            params = dict(cell.traffic)
            if rate is not None:
                params["arrival"] = {**params["arrival"], "rate_per_s": rate}
            traffic = traffic_kind.generate(params, args.seed + i, horizon,
                                            phases=(lead_in, lead_in + args.seconds))
            if i == 0:
                t = time.perf_counter()
                for user in traffic.users:
                    body = await asyncio.to_thread(
                        post_json, base + "/transactions",
                        {"user_id": user.user_id, "transactions": user.rows})
                    if body != {"upserted": len(user.rows)}:
                        raise SystemExit(f"perfbench: /transactions returned {body}")
                isolation = await correct.check_isolation(app, traffic)
                say(f"set-up: {sum(len(u.rows) for u in traffic.users)} rows of "
                    f"{len(traffic.users)} users ingested in "
                    f"{time.perf_counter() - t:.1f} s; correct(d) isolation: "
                    f"{json.dumps(isolation)}")
            gen = LoadGenerator(app, cfg, traffic)
            gen.store_sessions()
            gen.start()
            trace_dir = (work / "trace") if args.trace else None
            if trace_dir is not None:
                shutil.rmtree(trace_dir, ignore_errors=True)
            win, events, sync, live = await measure_window(
                app, gen, args.seconds, lead_in, trace_dir=trace_dir,
                compile_count=lambda: compiles, backlog=backlog,
                drain_limit_s=drain_limit)
            requests = list(gen.requests.values())
            backlog_end = sum(1 for r in requests if r.ended is None)
            await gen.stop()
            measured = (win, events, sync, live, requests)
            if rate is not None:
                e2e = reduce.end_to_end(requests, win.w0, win.w1, answer_cap=answer_cap,
                                        backlog=backlog, vocab=1)
                lag = reduce.gen_lag_ms(requests, win.w0, win.w1)
                say("sweep " + json.dumps({
                    "session_rate_per_s": rate, "due": e2e["attempted"],
                    "failed_or_unfinished": e2e["failed"], "verdicts": e2e["verdicts"],
                    "open_at_end": backlog_end, **e2e["metrics"],
                    "compiles_in_window": win.compiles[1] - win.compiles[0],
                    "gen_lag_p99_ms": reduce.percentile(lag, 99) if lag else None}))
                # let the system empty before the next rate
                quiet = time.perf_counter() + 120
                while app._inflight and time.perf_counter() < quiet:
                    await asyncio.sleep(0.2)
    finally:
        await app.stop()
    if args.sweep:
        return {}

    win, events, sync, live, requests = measured
    window_check = correct.check_window(win.prom[0], win.prom[1], win.steps[0],
                                        win.steps[1], events)
    say(f"correct(c) window: {json.dumps(window_check)}; programs compiled or "
        f"loaded inside the window: {win.compiles[1] - win.compiles[0]}")
    e2e = reduce.end_to_end(
        requests, win.w0, win.w1, answer_cap=answer_cap, backlog=backlog,
        vocab=int(cell.config["vocab_size"]))
    streaming_at_open = sum(1 for r in requests if r.chunk_times
                            and r.chunk_times[0] < win.w0 and (r.done or win.w1) > win.w0)
    ended_in_window = sum(1 for r in requests
                          if r.done is not None and win.w0 <= r.done < win.w1)
    say(f"correct(b) requests: attempted {e2e['attempted']}, failed {e2e['failed']}, "
        f"verdicts {json.dumps(e2e['verdicts'])}, short answers (sampled EOS) "
        f"{e2e['short_answers']}, gaps {e2e['n_gaps']}; answers streaming at the "
        f"window's open {streaming_at_open}, ended inside it {ended_in_window}, "
        f"preemptions inside it "
        f"{win.prom[1].get('finchat_preemptions_total', 0.0) - win.prom[0].get('finchat_preemptions_total', 0.0):.0f}"
        f"; client side: {json.dumps(e2e['metrics'])}")

    device = device_object(jax, cell.chips)
    setup_s = win.w0 - PROCESS_START
    checks = {"logits": bool(logits["ok"]), "isolation": bool(isolation["ok"]),
              "window": bool(window_check["ok"]),
              "requests": e2e["attempted"] > 0 and e2e["failed"] == 0}
    if not all(checks.values()):  # the standard-error tail is what a log keeps
        print(f"perfbench: correct is false: {json.dumps(checks)}; logits "
              f"{json.dumps({k: logits[k] for k in ('split', 'ragged')})}; isolation "
              f"{json.dumps(isolation)}; window {json.dumps(window_check)}; requests "
              f"{json.dumps(e2e['verdicts'])}", file=sys.stderr, flush=True)
    line: dict = {"correct": all(checks.values()), "attempted": e2e["attempted"],
                  "failed": e2e["failed"], "metrics": {}, "device": device,
                  "checks": checks}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if not args.trace:
        values = dict(e2e["metrics"], setup_s=setup_s)
        for m in cell.end_to_end:
            v = values.get(m["name"])
            if v is None or not math.isfinite(v):
                line["correct"] = False
                say(f"perfbench: end-to-end metric {m['name']} has no finite value ({v})")
                continue
            line["metrics"][m["name"]] = {"value": v, "unit": units[m["name"]]}
    else:
        xplane = trace_reduce.find_xplane(work / "trace")
        dtrace = trace_reduce.reduce_xplane(xplane) if xplane else None
        if dtrace is not None and dtrace.busy_s > 0:
            device["busy_s"] = dtrace.busy_s
            device["window_s"] = dtrace.window_s
            syncs = [s for name, s, _e in dtrace.host_events if name == "perfbench_sync"]
            offset = (sync - syncs[0] / 1e9) if (syncs and sync) else 0.0
            line["breakdown"] = {
                "device_ops": dtrace.top_ops(10),
                "idle_gaps": dtrace.idle_gaps(host_label(events, offset), 10)}
        if live:  # the count beside what it counts (distinct physical pages x the page
            # size): at the window's middle sample, and where they are closest and
            # farthest apart — from 0 to under a page a row and an entry
            def gap(s):
                return s["page_tokens"] - s["kv_tokens_distinct"]
            line["live_kv"] = {"samples": len(live), "middle": live[len(live) // 2],
                               "narrowest_gap": min(live, key=gap), "widest_gap": max(live, key=gap)}
            say(f"live KV, by distinct physical pages: {json.dumps(line['live_kv'])}")
        ctx = Context(
            w0=win.w0, w1=win.w1, requests=requests, tracer_events=events,
            prom_before=win.prom[0], prom_after=win.prom[1], device_trace=dtrace,
            device=device, model=cell.config,
            extra={"mean_live_kv_tokens": (sum(s["kv_tokens_distinct"] for s in live)
                                           / len(live)) if live else None})
        for m in cell.per_layer:
            v = read_metric(m["name"], ctx)
            if v is not None:
                line["metrics"][m["name"]] = {"value": v, "unit": units[m["name"]]}
    if cell.rehearsal:
        # a CPU run is never a measurement: no time-valued number goes out
        # under a metric's name
        line["rehearsal"] = True
        for name, entry in line["metrics"].items():
            entry["rehearsal_value"] = entry.pop("value")
            entry["value"] = None
            entry["note"] = "not a measurement (CPU rehearsal)"
        line.pop("breakdown", None)
    return line


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="", help="session rates, comma-separated")
    args = ap.parse_args()
    faulthandler.dump_traceback_later(HARD_DEADLINE_S, exit=True, file=sys.__stderr__)

    from perfbench.cells import benchmark, load_cell

    cell = load_cell(args.workload)
    if args.seconds is None:
        args.seconds = float(benchmark()["run_seconds"])
    try:
        import jax

        from finchat_tpu.utils.runtime import enable_compile_cache
    except ImportError as e:
        print(f"perfbench: cannot import the system under test ({e}); run from "
              "the root of a whole checkout", file=sys.stderr)
        return 2
    platform = jax.default_backend()
    if cell.rehearsal:
        if platform != "cpu" or not (jax.config.jax_platforms or "").startswith("cpu"):
            print("perfbench: a rehearsal cell runs with JAX_PLATFORMS=cpu only",
                  file=sys.stderr)
            return 2
        os.environ.setdefault("FINCHAT_ATTN", "ref")
    elif platform != "tpu":
        print(f"perfbench: no accelerator — jax.default_backend() is {platform!r}; "
              "a cell is measured on the chip only", file=sys.stderr)
        return 2
    if len(jax.devices()) < cell.chips:
        print(f"perfbench: the cell needs {cell.chips} chip(s), JAX finds "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    say(f"perfbench: cell {cell.name} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace}; device {jax.devices()[0].device_kind} x{len(jax.devices())}; "
        f"compile cache {enable_compile_cache()}")
    line = asyncio.run(run_cell(args, cell, jax))
    faulthandler.cancel_dump_traceback_later()
    if line:
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
