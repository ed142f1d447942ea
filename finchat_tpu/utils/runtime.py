"""Process-entry runtime checks: which device this process really got, and
where its compiled programs are kept.

Library code (an ``InferenceEngine`` built in a test) calls nothing here;
only a process entry that compiles does — ``python -m finchat_tpu``,
``chip_smoke.py``, ``perfbench/run.py``, ``perfbench/control.py`` — and it
does so before its first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax
from jax._src import cache_key

from finchat_tpu.utils import tracing
from finchat_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# <checkout>/.jax_cache, from this file's own location: the cache key covers
# the directory's path, so a temp name, pid or timestamp would never hit
_DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def _scope_registry_key() -> str:
    return "finchat-scopes:" + ",".join(sorted(tracing.DEVICE_SCOPES))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the operator has placed the
    cache (JAX reads the variable itself) and no directory is set in code;
    otherwise the cache lives at ``<checkout>/.jax_cache``. The size and
    compile-time floors drop to zero either way: warm-up's variants are
    exactly what a restarted process should find again, however small.

    The key also covers the names of ``tracing.DEVICE_SCOPES``. JAX keeps
    an op's metadata out of the key, so a restart after an edit that moved
    source lines stays warm; but then a program cached before a scope
    existed comes back without it, and the device-time-by-scope reduction
    reads scope paths from the profile. ``cache_key.custom_hook`` is JAX's
    seam for such an addition (tests/test_runtime.py holds it to that). A
    scope that moves while the registry and the program stay as they were
    keeps its old path until the entry is evicted."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cache_key.custom_hook = _scope_registry_key
    cache_dir = jax.config.jax_compilation_cache_dir
    logger.info("persistent compilation cache: %s", cache_dir)
    return cache_dir


def device_facts() -> dict:
    """What JAX reports for this process's devices — the ``device`` object
    every result that names a device carries."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_accelerator_unless_cpu_requested() -> dict:
    """Log the device facts and refuse a CPU backend nobody asked for.

    JAX falls back to the CPU when the accelerator runtime fails to
    initialise; the kernel dispatcher then picks the ``jax.numpy``
    references and the server would come up "healthy" at a fraction of
    its speed. A CPU run is legitimate only when requested."""
    facts = device_facts()
    logger.info("devices: platform=%s device_kind=%s count=%d",
                facts["platform"], facts["kind"], facts["count"])
    # the jax_platforms option (which JAX_PLATFORMS seeds) names cpu first
    requested = (jax.config.jax_platforms or "").split(",")[0].strip().lower()
    if facts["platform"] == "cpu" and requested != "cpu":
        raise RuntimeError(
            "JAX initialised the CPU backend but no CPU run was requested: "
            "the accelerator runtime is missing or failed to start. Set "
            "JAX_PLATFORMS=cpu to serve on the CPU deliberately."
        )
    return facts
