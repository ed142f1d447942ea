"""finchat-lint rule fixtures (ISSUE 8).

Every rule gets positive (flags the bug) and negative (passes the fixed
form) fixtures, including a reproduction of each historical bug the rule
is derived from:

- R1: the inline breaker-trip device rebuild on the event loop (fixed in
  this PR by moving it behind ``asyncio.to_thread``),
- R3: the ``_fail_prefix_job`` slot leak — an unguarded device op on a
  cleanup path ahead of the releases (fixed in PR 6; R3 now pins the
  whole class),
- R5: the fleet counter emitted through a replica's labeled view (caught
  in PR 6 review; the unlabeled-fleet-family convention is now
  mechanical).

Plus the framework itself: suppressions (line + scope + mandatory
justification), the shrink-only baseline, and the runtime sanitizers
(stall + leak).
"""

from __future__ import annotations

import asyncio
import textwrap
import time
from pathlib import Path

import pytest

from finchat_tpu.analysis.core import Finding, load_baseline, run_analysis, write_baseline


def _lint(tmp_path: Path, files: dict[str, str], rules: set[str] | None = None):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return run_analysis(tmp_path, [tmp_path], rule_filter=rules)


def _messages(result) -> list[str]:
    return [f.message for f in result.findings]


# ---------------------------------------------------------------------------
# R1 event-loop-blocking
# ---------------------------------------------------------------------------

INLINE_REBUILD = """
    import asyncio

    class Sched:
        async def _loop(self):
            try:
                await self._round()
            except Exception as e:
                self._round_failed(str(e))

        async def _round(self):
            pass

        def _round_failed(self, error):
            self._trip_breaker(error)

        def _trip_breaker(self, error):
            self.allocator.reset()
            self.engine.rebuild_device_state()
"""

OFF_LOOP_REBUILD = """
    import asyncio

    class Sched:
        async def _loop(self):
            try:
                await self._round()
            except Exception as e:
                await self._round_failed(str(e))

        async def _round(self):
            pass

        async def _round_failed(self, error):
            await self._trip_breaker(error)

        async def _trip_breaker(self, error):
            self.allocator.reset()
            await asyncio.to_thread(self.engine.rebuild_device_state)
"""


def test_r1_flags_inline_rebuild_reachable_from_async(tmp_path):
    """The historical bug: a breaker trip rebuilt the device state INLINE
    on the event loop every sibling replica shares."""
    res = _lint(tmp_path, {"sched.py": INLINE_REBUILD}, {"event-loop-blocking"})
    assert len(res.findings) == 1
    f = res.findings[0]
    assert "rebuild" in f.message and "_trip_breaker" in f.symbol
    assert "_loop" in f.message  # the chain names the async root


def test_r1_passes_to_thread_rebuild(tmp_path):
    """The fixed form: the rebuild runs in a worker thread (the callable
    is passed by reference — never an on-loop call edge)."""
    res = _lint(tmp_path, {"sched.py": OFF_LOOP_REBUILD}, {"event-loop-blocking"})
    assert res.findings == []


def test_r1_primitives_sleep_fsync_and_executor_join(tmp_path):
    src = """
        import os
        import time

        class W:
            async def handler(self):
                time.sleep(0.5)
                os.fsync(3)
                self.pool.submit(len, "x").result()
    """
    res = _lint(tmp_path, {"w.py": src}, {"event-loop-blocking"})
    msgs = " | ".join(_messages(res))
    assert "time.sleep" in msgs and "os.fsync" in msgs and "executor join" in msgs
    assert len(res.findings) == 3


def test_r1_transitive_chain_through_sync_helpers(tmp_path):
    src = """
        import os

        class Journal:
            def append(self, mid):
                os.fsync(3)

        class App:
            def __init__(self):
                self.journal = Journal()

            async def done(self):
                self.journal.append("m")
    """
    res = _lint(tmp_path, {"app.py": src}, {"event-loop-blocking"})
    assert len(res.findings) == 1
    assert "Journal.append" in res.findings[0].symbol
    assert "App.done" in res.findings[0].message


def test_r1_loop_callback_registration_is_a_root(tmp_path):
    src = """
        import time

        class App:
            async def spawn(self, task):
                def _done(t):
                    time.sleep(1)
                task.add_done_callback(_done)
    """
    res = _lint(tmp_path, {"cb.py": src}, {"event-loop-blocking"})
    assert len(res.findings) == 1
    assert "_done" in res.findings[0].symbol


def test_r1_off_loop_lambda_and_thread_args_are_exempt(tmp_path):
    src = """
        import asyncio
        import time

        class W:
            async def fetch(self):
                return await asyncio.to_thread(lambda: time.sleep(1))
    """
    res = _lint(tmp_path, {"ok.py": src}, {"event-loop-blocking"})
    assert res.findings == []


def test_r1_blocking_socket_liaison_is_flagged(tmp_path):
    """A pod liaison built on raw sockets stalls every in-flight stream
    for a peer's RTT: create_connection / sendall / recv / accept on an
    async path are all primitives."""
    src = """
        import socket

        class Liaison:
            async def call(self, addr, frame):
                conn = socket.create_connection(addr)
                conn.sendall(frame)
                return conn.recv(65536)

            async def serve(self, srv):
                conn, _peer = srv.accept()
                return conn
    """
    res = _lint(tmp_path, {"liaison.py": src}, {"event-loop-blocking"})
    msgs = " | ".join(_messages(res))
    assert "socket.create_connection" in msgs
    assert ".sendall()" in msgs and ".recv()" in msgs and ".accept()" in msgs
    assert len(res.findings) == 4


def test_r1_asyncio_stream_liaison_is_clean(tmp_path):
    """The blessed transport (serve/pod.py): asyncio streams — awaited
    open_connection / readexactly / write+drain never hit the socket
    primitives."""
    src = """
        import asyncio

        class Liaison:
            async def call(self, host, port, frame):
                reader, writer = await asyncio.open_connection(host, port)
                writer.write(frame)
                await writer.drain()
                raw = await reader.readexactly(9)
                writer.close()
                return raw
    """
    res = _lint(tmp_path, {"liaison.py": src}, {"event-loop-blocking"})
    assert res.findings == []


# ---------------------------------------------------------------------------
# R2 hot-path-host-sync
# ---------------------------------------------------------------------------

HOT_ITEM = """
    import jax.numpy as jnp
    import numpy as np

    def dispatch(state, active):  # finchat-lint: hot
        logits = jnp.ones((4, 8))
        token = logits.argmax()
        t = int(np.asarray(token))
        if token:
            pass
        return t
"""

HOT_CLEAN = """
    import asyncio
    import jax.numpy as jnp
    import numpy as np

    async def dispatch(state, active):  # finchat-lint: hot
        logits = jnp.ones((4, 8))
        token = logits.argmax()
        host = await asyncio.to_thread(lambda: np.asarray(token))
        n = logits.shape[0]
        if token is not None:
            pass
        return host, n
"""


def test_r2_flags_host_sync_on_device_values(tmp_path):
    res = _lint(tmp_path, {"hot.py": HOT_ITEM}, {"hot-path-host-sync"})
    msgs = " | ".join(_messages(res))
    assert "D2H" in msgs  # np.asarray on the tainted token
    assert "__bool__" in msgs  # if token:
    assert len(res.findings) == 2


def test_r2_passes_off_loop_fetch_and_host_metadata(tmp_path):
    """The blessed pattern: the fetch rides to_thread; .shape and
    ``is not None`` are host-side and never flagged."""
    res = _lint(tmp_path, {"hot.py": HOT_CLEAN}, {"hot-path-host-sync"})
    assert res.findings == []


def test_r2_item_and_block_until_ready_always_flag(tmp_path):
    src = """
        def kern(x):  # finchat-lint: hot
            a = x.item()
            x.block_until_ready()
            return a
    """
    res = _lint(tmp_path, {"k.py": src}, {"hot-path-host-sync"})
    assert len(res.findings) == 2


def test_r2_host_helpers_do_not_taint(tmp_path):
    """A hot-module function returning a host scalar must not taint its
    callers (the ops/ backend-name helpers were the false-positive class
    the returns-device inference exists for)."""
    src = """
        def backend_name():
            return "ref"

        def kern(x):  # finchat-lint: hot
            b = backend_name()
            if b == "ref":
                return 1
            return 2
    """
    res = _lint(tmp_path, {"k.py": src}, {"hot-path-host-sync"})
    assert res.findings == []


def test_r2_scheduler_consume_seam_is_hot_by_name(tmp_path):
    """The scheduler's dispatch and consume functions are in the hot set BY
    NAME in engine/scheduler.py: a ``block_until_ready``, ``.item()``, D2H,
    or implicit ``__bool__`` on a step's tokens there serializes the host
    against the step the pipeline dispatched ahead. The blessed off-loop
    ``to_thread`` fetch stays clean."""
    bad = """
        import jax.numpy as jnp
        import numpy as np

        class Sched:
            async def _consume_step(self, step):
                tokens = jnp.ones((4, 4))
                tokens.block_until_ready()
                n = np.asarray(tokens)
                if tokens:
                    pass
                return n
    """
    res = _lint(tmp_path, {"engine/scheduler.py": bad}, {"hot-path-host-sync"})
    msgs = " | ".join(_messages(res))
    assert "block_until_ready" in msgs
    assert "D2H" in msgs
    assert "__bool__" in msgs
    assert len(res.findings) == 3
    good = """
        import asyncio
        import jax.numpy as jnp
        import numpy as np

        class Sched:
            async def _consume_step(self, step):
                tokens = jnp.ones((4, 4))
                host = await asyncio.to_thread(lambda: np.asarray(tokens))
                return host

            def _dispatch_decode(self, exclude):
                tokens = jnp.ones((4, 4))
                return tokens
    """
    res = _lint(tmp_path, {"engine/scheduler.py": good}, {"hot-path-host-sync"})
    assert res.findings == []


def test_r2_cold_functions_not_hot(tmp_path):
    src = """
        import numpy as np
        import jax.numpy as jnp

        def helper(x):
            v = jnp.ones(3)
            return np.asarray(v)
    """
    res = _lint(tmp_path, {"cold.py": src}, {"hot-path-host-sync"})
    assert res.findings == []


# ---------------------------------------------------------------------------
# R3 resource-pairing
# ---------------------------------------------------------------------------

FAIL_PREFIX_JOB_BUG = """
    class Sched:
        def _fail_prefix_job(self, job):
            self._prefix_jobs.remove(job)
            self.allocator.free(job.owner, job.pages)
            self.engine.reset_slot(job.slot)
            self.free_slots.append(job.slot)
            job.future.set_result(0)
"""

FAIL_PREFIX_JOB_FIXED = """
    class Sched:
        def _fail_prefix_job(self, job):
            self._prefix_jobs.remove(job)
            self.allocator.free(job.owner, job.pages)
            try:
                self.engine.reset_slot(job.slot)
            except Exception:
                pass
            self.free_slots.append(job.slot)
            job.future.set_result(0)
"""


def test_r3_flags_unguarded_device_op_before_releases(tmp_path):
    """The historical ``_fail_prefix_job`` bug: a raising reset_slot
    skipped the slot return and the future resolution, hanging the
    awaiter forever (PR 6 review catch)."""
    res = _lint(tmp_path, {"s.py": FAIL_PREFIX_JOB_BUG}, {"resource-pairing"})
    assert len(res.findings) == 1
    assert "reset_slot" in res.findings[0].message
    assert "_fail_prefix_job" in res.findings[0].symbol


def test_r3_passes_guarded_cleanup(tmp_path):
    res = _lint(tmp_path, {"s.py": FAIL_PREFIX_JOB_FIXED}, {"resource-pairing"})
    assert res.findings == []


def test_r3_flags_device_op_in_finally_before_release(tmp_path):
    src = """
        class Sched:
            def register(self, ids):
                try:
                    self.engine.prefill(0, ids)
                finally:
                    self.engine.reset_slot(0)
                    self.free_slots.append(0)
    """
    res = _lint(tmp_path, {"s.py": src}, {"resource-pairing"})
    assert len(res.findings) == 1
    assert "reset_slot" in res.findings[0].message


def test_r3_flags_acquire_leaked_on_early_raise(tmp_path):
    src = """
        class Sched:
            def admit(self, n):
                pages = self.allocator.allocate("s", n)
                if n > 4:
                    raise RuntimeError("too big")
                self.allocator.free("s", pages)
    """
    res = _lint(tmp_path, {"s.py": src}, {"resource-pairing"})
    assert len(res.findings) == 1
    assert "pages" in res.findings[0].message and "raise" in res.findings[0].message


def test_r3_passes_escaped_or_released_acquires(tmp_path):
    src = """
        class Sched:
            def admit(self, handle, n):
                pages = self.allocator.allocate("s", n)
                handle.page_list = pages  # ownership transferred
                return handle

            def probe(self, n):
                pages = self.allocator.allocate("s", n)
                try:
                    self.check(pages)
                finally:
                    self.allocator.free("s", pages)
    """
    res = _lint(tmp_path, {"s.py": src}, {"resource-pairing"})
    assert res.findings == []


# ---------------------------------------------------------------------------
# R4 knob-consistency
# ---------------------------------------------------------------------------

MINI_CONFIG = """
    from dataclasses import dataclass, field

    def _env(name, default=""):
        return default

    def _env_int(name, default=0):
        return default

    @dataclass
    class EngineConfig:
        max_seqs: int = 64
        secret_knob: int = 3{secret_suppress}

    @dataclass
    class AppConfig:
        engine: EngineConfig = field(default_factory=EngineConfig)

    def load_config():
        cfg = AppConfig()
        cfg.engine.max_seqs = _env_int("FINCHAT_MAX_SEQS", cfg.engine.max_seqs)
        return cfg
"""

MINI_MAIN = """
    overrides = {}
    overrides["engine.max_seqs"] = 1
    overrides["engine.not_a_knob"] = 2
"""


def test_r4_readme_env_and_field_drift(tmp_path):
    files = {
        "utils/config.py": MINI_CONFIG.format(secret_suppress=""),
        "__main__.py": MINI_MAIN,
        "README.md": "docs without the env var",
    }
    res = _lint(tmp_path, files, {"knob-consistency"})
    msgs = " | ".join(_messages(res))
    assert "FINCHAT_MAX_SEQS" in msgs  # wired but not in README
    assert "secret_knob" in msgs  # field without env wiring
    assert "engine.not_a_knob" in msgs  # CLI flag drift
    assert len(res.findings) == 3


def test_r4_clean_when_docs_and_wiring_agree(tmp_path):
    files = {
        "utils/config.py": MINI_CONFIG.format(
            secret_suppress="  # finchat-lint: disable=knob-consistency -- file-only by design"
        ),
        "__main__.py": 'overrides = {}\noverrides["engine.max_seqs"] = 1\n',
        "README.md": "set `FINCHAT_MAX_SEQS` to bound concurrency",
    }
    res = _lint(tmp_path, files, {"knob-consistency"})
    assert res.findings == []
    assert len(res.suppressed) == 1


# ---------------------------------------------------------------------------
# R5 metrics-discipline
# ---------------------------------------------------------------------------

FLEET_LABELED_BUG = """
    from finchat_tpu.utils.metrics import METRICS

    class Sched:
        def __init__(self, replica_id):
            self.metrics = METRICS.labeled(replica=str(replica_id))

        def drain_failed(self):
            self.metrics.inc("finchat_fleet_drain_failures_total")
"""

FLEET_UNLABELED_FIXED = """
    from finchat_tpu.utils.metrics import METRICS

    class Sched:
        def __init__(self, replica_id):
            self.metrics = METRICS.labeled(replica=str(replica_id))

        def drain_failed(self):
            METRICS.inc("finchat_fleet_drain_failures_total")
"""


def test_r5_flags_fleet_counter_through_labeled_view(tmp_path):
    """The historical PR 6 catch: a fleet-family counter emitted through
    a replica's labeled view splits into per-replica series no dashboard
    sums."""
    res = _lint(
        tmp_path,
        {"finchat_tpu/sched.py": FLEET_LABELED_BUG},
        {"metrics-discipline"},
    )
    assert len(res.findings) == 1
    assert "finchat_fleet_drain_failures_total" in res.findings[0].message


def test_r5_passes_fleet_counter_on_global_registry(tmp_path):
    res = _lint(
        tmp_path,
        {"finchat_tpu/sched.py": FLEET_UNLABELED_FIXED},
        {"metrics-discipline"},
    )
    assert res.findings == []


def test_r5_naming_and_suffix_conventions(tmp_path):
    src = """
        from finchat_tpu.utils.metrics import METRICS

        def emit():
            METRICS.inc("finchat_things")            # counter without _total
            METRICS.inc("bad_name_total")            # missing finchat_ prefix
            METRICS.observe("finchat_lat_ms")        # histogram without _seconds
            METRICS.set_gauge("finchat_depth_total") # gauge with counter suffix
            METRICS.inc("finchat_good_total")        # fine
            METRICS.set_gauge("finchat_depth")       # fine
            METRICS.observe("finchat_step_seconds")  # fine
    """
    res = _lint(tmp_path, {"finchat_tpu/m.py": src}, {"metrics-discipline"})
    assert len(res.findings) == 4


def test_r5_mixed_labeled_unlabeled_family(tmp_path):
    src = """
        from finchat_tpu.utils.metrics import METRICS

        def a():
            METRICS.inc("finchat_x_total", labels={"k": "v"})

        def b():
            METRICS.inc("finchat_x_total")
    """
    res = _lint(tmp_path, {"finchat_tpu/m.py": src}, {"metrics-discipline"})
    assert any("both with and without" in m for m in _messages(res))


# ---------------------------------------------------------------------------
# R5 span discipline (ISSUE 12)
# ---------------------------------------------------------------------------

_MINI_TRACING = """
    SPAN_MARKS = frozenset({"admitted", "first_token", "done"})
    TRACE_EVENTS = frozenset({"dispatch", "ingress"})
    ANOMALY_KINDS = frozenset({"breaker_trip", "shed"})
"""


def test_r5_span_mark_must_be_registered(tmp_path):
    """A typo'd mark name silently vanishes from every timeline — the
    span-discipline check catches it statically against SPAN_MARKS."""
    src = """
        class H:
            def go(self, handle):
                handle.span.mark("admited")      # typo: flagged
                handle.span.mark("admitted")     # registered: fine
                self.span.mark("first_token")    # registered: fine
    """
    res = _lint(
        tmp_path,
        {"finchat_tpu/utils/tracing.py": _MINI_TRACING,
         "finchat_tpu/sched.py": src},
        {"metrics-discipline"},
    )
    assert len(res.findings) == 1
    assert "admited" in res.findings[0].message
    assert "SPAN_MARKS" in res.findings[0].message


def test_r5_tracer_event_and_anomaly_names(tmp_path):
    src = """
        from finchat_tpu.utils.tracing import TRACER

        def go():
            TRACER.event("dispatch", "t1")        # registered event
            TRACER.event("admitted", "t1")        # span marks count too
            TRACER.event("dispach")               # typo: flagged
            TRACER.anomaly("breaker_trip")        # registered anomaly
            TRACER.anomaly("dispatch")            # not an ANOMALY kind: flagged
    """
    res = _lint(
        tmp_path,
        {"finchat_tpu/utils/tracing.py": _MINI_TRACING,
         "finchat_tpu/app.py": src},
        {"metrics-discipline"},
    )
    msgs = _messages(res)
    assert len(msgs) == 2
    assert any("dispach" in m for m in msgs)
    assert any("ANOMALY_KINDS" in m for m in msgs)


def test_r5_trace_forwarding_helper_literals_checked(tmp_path):
    """The agent's ``_trace(state, "name")`` forwarding convention: the
    literal is checked at the helper CALL site (the helper's own
    non-literal pass-through to TRACER.event is exempt by construction)."""
    src = """
        from finchat_tpu.utils.tracing import TRACER

        class Agent:
            def _trace(self, state, name, **args):
                TRACER.event(name, state.trace_id)   # non-literal: exempt

            def decide(self, state):
                self._trace(state, "admitted")       # registered: fine
                self._trace(state, "decide_startt")  # typo: flagged
    """
    res = _lint(
        tmp_path,
        {"finchat_tpu/utils/tracing.py": _MINI_TRACING,
         "finchat_tpu/agent.py": src},
        {"metrics-discipline"},
    )
    assert len(res.findings) == 1
    assert "decide_startt" in res.findings[0].message


def test_r5_span_checks_skip_without_tracing_module(tmp_path):
    src = """
        def go(handle):
            handle.span.mark("anything_goes")
    """
    res = _lint(tmp_path, {"finchat_tpu/x.py": src}, {"metrics-discipline"})
    assert res.findings == []


def test_r2_composes_with_tracing_calls_in_hot_regions(tmp_path):
    """ISSUE 12 satellite: tracing calls inside ``# finchat-lint: hot``
    regions must not smuggle device reads — a device value cast inside a
    TRACER.event args dict is exactly the hidden sync R2 exists for.
    Host-data-only tracing passes."""
    src = """
        import jax.numpy as jnp
        from finchat_tpu.utils.tracing import TRACER

        def dispatch_bad(active):  # finchat-lint: hot
            tokens = jnp.argmax(active)
            TRACER.event("dispatch", args={"tok": int(tokens)})

        def dispatch_ok(slot_list, tally):  # finchat-lint: hot
            TRACER.event("dispatch", args={"rows": slot_list, "n": tally})
    """
    res = _lint(
        tmp_path,
        {"finchat_tpu/hot.py": src},
        {"hot-path-host-sync"},
    )
    assert len(res.findings) == 1
    assert res.findings[0].symbol.endswith("dispatch_bad")


# ---------------------------------------------------------------------------
# suppressions + baseline + CLI
# ---------------------------------------------------------------------------


def test_suppression_requires_justification(tmp_path):
    src = """
        import time

        async def f():
            time.sleep(1)  # finchat-lint: disable=event-loop-blocking
    """
    res = _lint(tmp_path, {"x.py": src}, {"event-loop-blocking"})
    assert res.findings == []  # suppressed...
    assert len(res.suppressed) == 1
    assert any(  # ...but the bare suppression is itself a finding
        f.rule == "suppression-discipline" for f in res.meta_findings
    )


def test_scope_suppression_on_def_line(tmp_path):
    src = """
        import time

        async def f():  # finchat-lint: disable=event-loop-blocking -- fixture: scope form
            time.sleep(1)
            time.sleep(2)
    """
    res = _lint(tmp_path, {"x.py": src}, {"event-loop-blocking"})
    assert res.findings == [] and len(res.suppressed) == 2
    assert res.meta_findings == []


def test_unused_suppressions_reported(tmp_path):
    src = "x = 1  # finchat-lint: disable=event-loop-blocking -- nothing here\n"
    res = _lint(tmp_path, {"x.py": src}, {"event-loop-blocking"})
    assert res.unused_suppressions == [("x.py", 1)]


def test_baseline_gates_and_shrinks(tmp_path):
    f_old = Finding("event-loop-blocking", "a.py", 3, "f", "old message")
    f_new = Finding("event-loop-blocking", "a.py", 9, "g", "new message")
    path = tmp_path / "LINT_BASELINE.json"
    write_baseline(path, [f_old])
    baseline = load_baseline(path)
    assert f_old.fingerprint() in baseline
    assert f_new.fingerprint() not in baseline
    # fingerprints are line-stable: moving the finding keeps it baselined
    moved = Finding("event-loop-blocking", "a.py", 77, "f", "old message")
    assert moved.fingerprint() in baseline


def test_cli_exit_codes(tmp_path, monkeypatch):
    from finchat_tpu.analysis.__main__ import main

    bad = tmp_path / "bad.py"
    bad.write_text("import time\n\nasync def f():\n    time.sleep(1)\n")
    assert main([str(bad), "--root", str(tmp_path)]) == 1
    # baselining the finding turns the run green
    assert main([str(bad), "--root", str(tmp_path), "--update-baseline"]) == 0
    assert main([str(bad), "--root", str(tmp_path)]) == 0
    # fixing the finding leaves a stale entry (reported, not failing);
    # --update-baseline shrinks the file back to empty
    bad.write_text("async def f():\n    return 1\n")
    assert main([str(bad), "--root", str(tmp_path)]) == 0
    assert main([str(bad), "--root", str(tmp_path), "--update-baseline"]) == 0
    assert load_baseline(tmp_path / "LINT_BASELINE.json") == {}


def test_repo_is_lint_clean():
    """The ISSUE 8 acceptance gate, as a test: zero unsuppressed findings
    over the real tree (the baseline is empty — nothing grandfathered)."""
    root = Path(__file__).resolve().parent.parent
    res = run_analysis(root, [root / "finchat_tpu", root / "tests"])
    assert res.findings == [], "\n".join(f.render() for f in res.findings)
    assert res.meta_findings == [], "\n".join(
        f.render() for f in res.meta_findings
    )
    assert load_baseline(root / "LINT_BASELINE.json") == {}


# ---------------------------------------------------------------------------
# runtime sanitizers
# ---------------------------------------------------------------------------


def test_stall_sanitizer_catches_blocking_callback():
    from finchat_tpu.analysis.sanitizers import StallSanitizer

    async def blocker():
        time.sleep(0.25)  # finchat-lint: disable=event-loop-blocking -- fixture: the stall the sanitizer must catch

    san = StallSanitizer(threshold_s=0.1)
    with pytest.raises(RuntimeError, match="stall sanitizer"):
        san.run(blocker())


def test_stall_sanitizer_passes_off_loop_work():
    from finchat_tpu.analysis.sanitizers import StallSanitizer

    async def clean():
        await asyncio.to_thread(time.sleep, 0.25)

    san = StallSanitizer(threshold_s=0.1)
    san.run(clean())  # no raise
    assert san.violations() == []


def test_stall_sanitizer_allowlist():
    from finchat_tpu.analysis.sanitizers import StallSanitizer

    async def blocker():
        time.sleep(0.25)  # finchat-lint: disable=event-loop-blocking -- fixture: allowlisted stall

    san = StallSanitizer(threshold_s=0.1, allow=(r"blocker",))
    san.run(blocker())  # stall recorded but allowlisted
    assert san.stalls and san.violations() == []


class _FakeEngineCfg:
    max_seqs = 4


class _FakeEngine:
    engine_cfg = _FakeEngineCfg()


class _FakeSched:
    """The exact attribute surface scheduler_leak_report audits."""

    def __init__(self, allocator):
        self.allocator = allocator
        self.engine = _FakeEngine()
        self._prefixes = []
        self._prefix_jobs = []
        self.decoding = {}
        self.prefilling = []
        self.free_slots = [0, 1, 2, 3]
        self.session_cache = None
        self._running = False


def test_leak_report_clean_and_dirty():
    from finchat_tpu.analysis.sanitizers import scheduler_leak_report
    from finchat_tpu.engine.kv_cache import PageAllocator

    alloc = PageAllocator(8)
    sched = _FakeSched(alloc)
    assert scheduler_leak_report(sched) == []

    # a dead owner's pages (the cancel-delegation bug class)
    alloc.allocate("ghost", 2)
    report = scheduler_leak_report(sched)
    assert any("ghost" in p for p in report)
    alloc.free("ghost", alloc.owned_by("ghost"))

    # a slot that never came back (the _fail_prefix_job class)
    sched.free_slots = [0, 1, 2]
    report = scheduler_leak_report(sched)
    assert any("slot accounting" in p for p in report)


def test_leak_report_counts_live_prefix_entries_and_jobs():
    from finchat_tpu.analysis.sanitizers import scheduler_leak_report
    from finchat_tpu.engine.kv_cache import PageAllocator

    class _Entry:
        def __init__(self, owner, pages):
            self.owner = owner
            self.pages = pages
            self.refs = 0
            self.shared_len = 128

    alloc = PageAllocator(8)
    sched = _FakeSched(alloc)
    pages = alloc.allocate("__prefix_0__", 2)
    sched._prefixes = [_Entry("__prefix_0__", pages)]
    assert scheduler_leak_report(sched) == []  # accounted, not a leak

    # a refcount with no referent IS a leak
    sched._prefixes[0].refs = 1
    assert any("ref leak" in p for p in scheduler_leak_report(sched))


def test_update_baseline_scope_safety(tmp_path):
    """--update-baseline must not silently delete entries it did not
    re-analyze: rule filters are refused, and a narrowed-path run keeps
    entries for files outside the analyzed set."""
    from finchat_tpu.analysis.__main__ import main

    a = tmp_path / "a.py"
    b = tmp_path / "b.py"
    a.write_text("import time\n\nasync def f():\n    time.sleep(1)\n")
    b.write_text("import os\n\nasync def g():\n    os.fsync(3)\n")
    assert main([str(tmp_path), "--root", str(tmp_path), "--update-baseline"]) == 0
    full = load_baseline(tmp_path / "LINT_BASELINE.json")
    assert len(full) == 2
    # rule-filtered update refused (exit 2), baseline untouched
    assert main([str(tmp_path), "--root", str(tmp_path), "--rule", "R1",
                 "--update-baseline"]) == 2
    assert load_baseline(tmp_path / "LINT_BASELINE.json") == full
    # narrowed-path update: a.py fixed and re-baselined; b.py's entry kept
    a.write_text("async def f():\n    return 1\n")
    assert main([str(a), "--root", str(tmp_path), "--update-baseline"]) == 0
    kept = load_baseline(tmp_path / "LINT_BASELINE.json")
    assert len(kept) == 1
    assert next(iter(kept.values()))["path"] == "b.py"
    # and the full run is still green (b.py's finding stays baselined)
    assert main([str(tmp_path), "--root", str(tmp_path)]) == 0


def test_stall_sanitizer_run_cancels_pending_tasks():
    """StallSanitizer.run must mirror asyncio.run's teardown: a test that
    leaves a background task running gets it cancelled WITH its cleanup
    executed (a failing test that never stopped its scheduler must not
    strand the loop task or skip its finally blocks)."""
    from finchat_tpu.analysis.sanitizers import StallSanitizer

    cleaned = []

    async def background():
        try:
            await asyncio.sleep(60)
        finally:
            cleaned.append(True)

    async def body():
        asyncio.ensure_future(background())
        await asyncio.sleep(0.01)
        # exits with the background task still pending

    StallSanitizer(threshold_s=5.0).run(body())
    assert cleaned == [True]


def test_r1_plain_dotted_import_resolves_root_binding(tmp_path):
    """`import os.path` binds the name `os` — the import map must not
    alias it to `os.path`, which would resolve `os.fsync` to
    `os.path.fsync` and silently miss a real on-loop fsync."""
    src = """
        import os.path

        async def f(fh):
            os.fsync(fh.fileno())
    """
    res = _lint(tmp_path, {"x.py": src}, {"event-loop-blocking"})
    assert len(res.findings) == 1 and "os.fsync" in res.findings[0].message
