"""Test environment: force an 8-device CPU mesh BEFORE jax initializes.

SURVEY §4.3 — ``xla_force_host_platform_device_count=8`` lets TP/DP/SP
sharding, collective correctness, and scheduler tests run anywhere with no
TPU. Must happen before any ``import jax`` in the test process.

Escape hatch: ``FINCHAT_TESTS_TPU=1`` keeps the real backend so the kernel
parity matrix (tests/test_pallas_attention.py) can run ON-CHIP with
``interpret=False``. Single-device suites only; mesh-dependent tests skip
themselves.
"""

import os

_ON_TPU = bool(os.environ.get("FINCHAT_TESTS_TPU"))

_flags = os.environ.get("XLA_FLAGS", "")
if not _ON_TPU and "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

if not _ON_TPU:
    jax.config.update("jax_platforms", "cpu")
    assert jax.device_count() == 8, "tests require the virtual 8-device CPU mesh"
if os.environ.get("FINCHAT_REQUIRE_TPU"):
    # An on-chip run of the kernel tests sets this so that a silent CPU
    # fallback can never produce a passing "on-chip" parity record: the
    # kernel tests would run interpret=True on CPU and pass. Checked
    # UNCONDITIONALLY (not only under FINCHAT_TESTS_TPU): a run that sets
    # REQUIRE_TPU but loses the TESTS_TPU flag would otherwise run the
    # suite on the forced-CPU mesh with the guard silently disarmed.
    assert jax.default_backend() == "tpu", (
        f"FINCHAT_REQUIRE_TPU=1 but backend is {jax.default_backend()!r}"
    )

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402

# --- runtime sanitizers (ISSUE 8; finchat_tpu/analysis/sanitizers.py) ------
# The scheduler/fleet/durability suites run under two sanitizers:
# - STALL: async tests run on an asyncio-debug loop that FAILS the test
#   when any loop callback blocks past FINCHAT_STALL_THRESHOLD_S (default
#   1.0 s) — the dynamic form of finchat-lint R1 (the inline-rebuild /
#   sync-spill stall class). FINCHAT_STALL_SANITIZER=0 disables.
# - LEAK: after every test, each scheduler the test constructed and
#   stopped is audited — allocator pages, engine slots, prefix-head
#   refcounts, session-cache refs, in-flight prefix jobs — the dynamic
#   form of finchat-lint R3 (the _fail_prefix_job leak class). Leftover
#   open journal handles are closed (fd hygiene).
SANITIZED_MODULES = {
    "test_scheduler_pipeline",
    "test_fleet",
    "test_durability",
    "test_resilience",
    "test_session_cache",
    "test_mixed_step",
    "test_faults",
    "test_decode_pipeline",
    "test_prefix_cache",
    "test_spec_decode",
    "test_bounded_kv",
    "test_pod",
}

_SANITIZERS_ON = os.environ.get("FINCHAT_STALL_SANITIZER", "1") not in ("0", "false")


def _sanitized(module_name: str) -> bool:
    return _SANITIZERS_ON and module_name.rsplit(".", 1)[-1] in SANITIZED_MODULES


@pytest.fixture(autouse=True)
def _finchat_leak_sanitizer(request):
    """Track every scheduler/journal constructed during the test; audit
    the stopped schedulers afterwards (analysis/sanitizers.py)."""
    if not _sanitized(request.module.__name__):
        yield
        return
    from finchat_tpu.analysis import sanitizers
    from finchat_tpu.engine.scheduler import ContinuousBatchingScheduler
    from finchat_tpu.io.journal import AnsweredJournal

    sanitizers.clear_tracked()
    with sanitizers.track_constructions(ContinuousBatchingScheduler, "scheduler"):
        with sanitizers.track_constructions(AnsweredJournal, "journal"):
            yield
    problems: list[str] = []
    for sched in sanitizers.tracked_instances("scheduler"):
        task = getattr(sched, "_task", None)
        if getattr(sched, "_running", False) and not (task and task.done()):
            # genuinely still running (module-scoped fixture) — live
            # streams legitimately hold slots/pages. A scheduler whose
            # loop task was CANCELLED at loop teardown (test never called
            # stop()) keeps _running=True but IS quiescent — audit it:
            # the accounting invariants hold continuously, and skipping
            # it would hide exactly the leaks of tests that forgot stop()
            continue
        problems += [
            f"{type(sched).__name__}[{getattr(sched, 'replica_id', '?')}]: {p}"
            for p in sanitizers.scheduler_leak_report(sched)
        ]
    sanitizers.close_journals()
    sanitizers.clear_tracked()
    if problems:
        pytest.fail(
            "leak sanitizer (finchat-lint R3 class):\n  " + "\n  ".join(problems),
            pytrace=False,
        )


# The driver runs the suite on six workers under ``--dist loadfile``: a file is
# one unit of work, handed out in collection order, so the run is as long as
# its last file's start plus that file's own time — a four-minute file that
# starts last (tests/test_tpu_compile.py, alphabetically) costs the run more
# than the two eleven-minute files ROADMAP D10 split. The files that take over
# three quarters of a minute go to the workers first, longest first (seconds a file
# in the junit of the driver's run on PR 45's tree, the split files and
# test_decode_pipeline.py by PR 46's own run, test_ssm_step_kernel.py by PR 52's:
# 170 s with the pairs' cases); every other file keeps its place.
# A file that grows past the last of these belongs in the list.
LONGEST_FIRST = (
    "test_latent_walk", "test_tpu_compile", "test_kv_quant_packed_tile",
    "test_paged_walk_shared_head", "test_chip_smoke", "test_olmo_hybrid", "test_phi4_flash",
    "test_granite_hybrid", "test_ssm_step_kernel", "test_paged_walk_packed_tile",
    "test_kv_quant_shared_head",
    "test_deepseek_v32", "test_parallel", "test_gdn_step_kernel", "test_decode_pipeline",
    "test_flat_fence", "test_pallas_attention", "test_moe_step_kernel", "test_kv_quant",
    "test_falcon_h1", "test_kv_quant_engine", "test_paged_walk_engine",
    "test_quant_serving", "test_quant_matmul", "test_quant", "test_hf_loader",
    "test_ragged_attention", "test_checkpoint_io", "test_mixed_step",
    "test_kimi_linear_engine", "test_kimi_linear_model", "test_kimi_linear", "test_joyai",
    "test_latent_walk_head", "test_mimo_v2_flash", "test_mimo_v2_flash_serving",
)


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.module.__name__.rsplit(".", 1)[-1], len(rank)))


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Free compiled executables between test modules.

    The full suite runs ~600 distinct XLA CPU compilations in one
    process; at a deterministic point near the end (observed 4/4 at
    test_warmup, 2026-07-31) the NEXT compilation segfaults inside
    ``backend_compile_and_load`` — an XLA compiler crash on accumulated
    jit-cache state, not host OOM (RSS ~6 GB of 125 GB) and not stack
    (reproduced at ulimit -s 64 MB). Clearing caches per module keeps
    the executable count bounded; cross-module cache reuse is minimal
    anyway since shapes/configs differ per module."""
    yield
    jax.clear_caches()


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests with asyncio.run (pytest-asyncio isn't in the
    image). Sanitized modules run on an instrumented debug loop instead:
    any callback blocking past the threshold fails the test (the ISSUE 8
    stall sanitizer — asyncio debug mode stays on for these suites)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {name: pyfuncitem.funcargs[name] for name in pyfuncitem._fixtureinfo.argnames}
        exempt = pyfuncitem.get_closest_marker("no_stall_sanitizer") is not None
        if _sanitized(pyfuncitem.module.__name__) and not exempt:
            from finchat_tpu.analysis.sanitizers import StallSanitizer

            try:
                StallSanitizer.from_env().run(fn(**kwargs))
            except RuntimeError as e:
                if "stall sanitizer" not in str(e):
                    raise
                pytest.fail(str(e), pytrace=False)
        else:
            asyncio.run(fn(**kwargs))
        return True
    return None
