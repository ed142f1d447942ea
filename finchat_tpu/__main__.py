"""Process entrypoint: ``python -m finchat_tpu``.

The reference's process layer is gunicorn spawning N uvicorn workers
(gunicorn.conf.py:5-20, Dockerfile:42). A TPU worker is NOT replicable that
way — the chip is a singleton per process — so the equivalent here is one
process owning the engine, with concurrency supplied by the continuous-
batching scheduler instead of worker replication (SURVEY §2.3 DP note).
Scale-out has two layers: ``--fleet-replicas N`` stands up N engine
replicas INSIDE this process under one conversation-affinity router with
breaker drain-to-sibling and supervised respawn (serve/fleet.py —
ROBUSTNESS.md), and multi-host serving runs one such process per
chip/slice, each its own Kafka consumer-group member (the same
partition-spreading the reference relies on, kafka_client.py:17; the
router hashes the SAME partition ids, so affinity survives both layers).

Env compatibility: every reference env var keeps working (utils/config.py);
``FINCHAT_*`` adds the new surface. ``--watchdog`` mirrors the reference's
100 s per-message timeout (main.py:138).
"""

from __future__ import annotations

import argparse
import asyncio
import signal

from finchat_tpu.utils.config import load_config
from finchat_tpu.utils.logging import get_logger

logger = get_logger("finchat_tpu")


def main() -> None:
    p = argparse.ArgumentParser(prog="finchat_tpu", description=__doc__)
    p.add_argument("--config", default=None, help="JSON config file (see utils/config.py)")
    p.add_argument("--preset", default=None, help="model preset override")
    p.add_argument("--port", type=int, default=None)
    p.add_argument("--no-http", action="store_true", help="Kafka worker loop only")
    p.add_argument("--session-cache-bytes", type=int, default=None,
                   help="host-RAM byte budget for the session KV cache "
                        "(engine/session_cache.py); 0 disables cross-turn "
                        "KV resume — also FINCHAT_SESSION_CACHE_BYTES")
    p.add_argument("--request-deadline-seconds", type=float, default=None,
                   help="per-request deadline (Kafka producer timestamp + "
                        "this): past-deadline pending requests shed with a "
                        "retryable error and admission goes earliest-"
                        "deadline-first (ROBUSTNESS.md); 0 = off — also "
                        "FINCHAT_REQUEST_DEADLINE_SECONDS")
    p.add_argument("--fleet-replicas", type=int, default=None,
                   help="engine replicas under this worker's serving plane "
                        "(serve/fleet.py): conversation-affinity routing, "
                        "breaker drains to siblings, supervised respawn; "
                        "1 = single engine — also FINCHAT_FLEET_REPLICAS")
    p.add_argument("--fleet-roles", default=None,
                   help="comma-separated per-replica roles, e.g. "
                        "'prefill,decode,decode' (serve/disagg.py): prefill "
                        "replicas run cold prompts and hand the KV to the "
                        "decode/mixed serving pool over the drain-handoff "
                        "path; empty = all mixed — also FINCHAT_FLEET_ROLES")
    p.add_argument("--fabric-path", default=None,
                   help="cluster-wide warm-state fabric directory (engine/"
                        "warm_fabric.py): one shared session disk tier + "
                        "global index, so any replica resumes any "
                        "conversation warm and shared prompt heads prefill "
                        "once per fleet; implies fabric.enabled — also "
                        "FINCHAT_FABRIC_PATH")
    p.add_argument("--journal-dir", default=None,
                   help="durability directory (io/journal.py; ISSUE 7): "
                        "answered message ids journal here (fsync before "
                        "the Kafka commit) and replay into the dedupe ring "
                        "at restart; the memory broker's committed offsets "
                        "persist here too — also FINCHAT_JOURNAL_PATH")
    p.add_argument("--session-disk", default=None,
                   help="session-KV disk spill tier directory (engine/"
                        "session_cache.py SessionDiskTier): entries write "
                        "through to checksummed record files so a restarted "
                        "process resumes conversations warm — also "
                        "FINCHAT_SESSION_CACHE_DISK")
    p.add_argument("--flight-dir", default=None,
                   help="anomaly flight-recorder directory (utils/"
                        "tracing.py — OBSERVABILITY.md): on breaker trip/"
                        "watchdog fire/shed/give-up/quarantine/SIGTERM the "
                        "trace ring dumps to a checksummed file here — "
                        "also FINCHAT_TRACING_FLIGHT_DIR")
    p.add_argument("--no-tracing", action="store_true",
                   help="disable request tracing + the flight recorder "
                        "(tracing.enabled; also FINCHAT_TRACING=0)")
    p.add_argument("--shutdown-deadline-seconds", type=float, default=None,
                   help="graceful SIGTERM drain window: in-flight streams "
                        "may finish for this long before stragglers are "
                        "preempted to host with a retryable error — also "
                        "FINCHAT_SHUTDOWN_DEADLINE_SECONDS")
    args = p.parse_args()

    overrides: dict = {}
    if args.preset:
        overrides["model.preset"] = args.preset
    if args.port:
        overrides["serve.port"] = args.port
    if args.session_cache_bytes is not None:
        overrides["engine.session_cache_bytes"] = args.session_cache_bytes
    if args.request_deadline_seconds is not None:
        overrides["engine.request_deadline_seconds"] = args.request_deadline_seconds
    if args.fleet_replicas is not None:
        overrides["fleet.replicas"] = args.fleet_replicas
    if args.fleet_roles is not None:
        overrides["fleet.roles"] = args.fleet_roles
    if args.fabric_path is not None:
        overrides["fabric.path"] = args.fabric_path
        overrides["fabric.enabled"] = True
    if args.journal_dir is not None:
        overrides["journal.path"] = args.journal_dir
    if args.session_disk is not None:
        overrides["engine.session_cache_disk_path"] = args.session_disk
    if args.shutdown_deadline_seconds is not None:
        overrides["shutdown.deadline_seconds"] = args.shutdown_deadline_seconds
    if args.flight_dir is not None:
        overrides["tracing.flight_dir"] = args.flight_dir
    if args.no_tracing:
        overrides["tracing.enabled"] = False
    cfg = load_config(args.config, overrides)

    if cfg.model.preset != "stub":
        # the stub preset never compiles and runs without an accelerator
        from finchat_tpu.utils.runtime import (
            enable_compile_cache,
            require_accelerator_unless_cpu_requested,
        )

        require_accelerator_unless_cpu_requested()
        enable_compile_cache()

    from finchat_tpu.serve.app import build_app

    app = build_app(cfg)

    async def run() -> None:
        loop = asyncio.get_running_loop()
        import os

        if os.getenv("FINCHAT_DEV"):
            # SURVEY §5.2: the reference blocks its event loop (sync pymongo
            # in async defs, blocking consumer.poll); dev mode makes any such
            # regression here loudly visible instead of silently copied
            loop.set_debug(True)
            loop.slow_callback_duration = 0.1
            logger.info("dev diagnostics on: asyncio debug + slow-callback detection")
        stop = asyncio.Event()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        await app.start(serve_http=not args.no_http)
        logger.info(
            "worker up: preset=%s http=%s port=%d",
            cfg.model.preset, not args.no_http, cfg.serve.port,
        )
        await stop.wait()
        # graceful drain (ISSUE 7): stop admission, finish in-flight
        # streams within shutdown.deadline_seconds, preempt stragglers to
        # host with a retryable error, spill session bytes to the disk
        # tier, journal + commit, exit with zero slot/page leaks — the
        # restarted process resumes conversations warm
        logger.info("shutting down (graceful drain, deadline %.0fs)",
                    cfg.shutdown.deadline_seconds)
        await app.drain_and_stop()

    asyncio.run(run())


if __name__ == "__main__":
    main()
