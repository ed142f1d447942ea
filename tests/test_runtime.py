"""Process-entry runtime checks (utils/runtime.py) and the no-silent-fallback
rules they back: where the compile cache lives, which backend an entry
accepts, and what a compiled kernel does off the chip."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parent.parent

_CACHE_PROBE = (
    "import jax; from finchat_tpu.utils.runtime import enable_compile_cache; "
    "first = enable_compile_cache(); second = enable_compile_cache(); "
    "assert first == second == jax.config.jax_compilation_cache_dir; "
    "print(first)"
)


def _run(code: str, env_changes: dict[str, str | None], cwd: Path = REPO):
    env = dict(os.environ)
    for key, value in env_changes.items():
        env.pop(key, None)
        if value is not None:
            env[key] = value
    env["PYTHONPATH"] = str(REPO)
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=120)


def test_compile_cache_defaults_to_checkout_dir(tmp_path):
    """Unset: <checkout>/.jax_cache — the same path on two calls, in two
    processes, from two working directories (the path is part of the
    cache's key, so one that moves never hits)."""
    a = _run(_CACHE_PROBE, {"JAX_COMPILATION_CACHE_DIR": None})
    b = _run(_CACHE_PROBE, {"JAX_COMPILATION_CACHE_DIR": None}, cwd=tmp_path)
    assert a.returncode == 0 and b.returncode == 0, a.stderr + b.stderr
    assert a.stdout.strip() == b.stdout.strip() == str(REPO / ".jax_cache")


def test_compile_cache_env_wins_and_no_directory_is_set_in_code(tmp_path):
    """Set: the operator placed the cache; the helper sets no directory."""
    placed = str(tmp_path / "placed")
    code = (
        "import jax; calls = []; real = jax.config.update; "
        "jax.config.update = lambda k, v: (calls.append(k), real(k, v))[1]; "
        "from finchat_tpu.utils.runtime import enable_compile_cache; "
        "print(enable_compile_cache()); "
        "assert 'jax_compilation_cache_dir' not in calls, calls"
    )
    out = _run(code, {"JAX_COMPILATION_CACHE_DIR": placed})
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == placed


_KEY_PROBE = (
    "import sys, jax; from finchat_tpu.utils import runtime, tracing; "
    "tracing.DEVICE_SCOPES = tracing.DEVICE_SCOPES | set(sys.argv[1:]); "
    "runtime.enable_compile_cache(); "
    "jax.jit(lambda x: x * 2 + 1)(1.0).block_until_ready()"
)


def test_compile_cache_key_covers_the_scope_registry_and_no_source_line(tmp_path):
    """A program cached under another set of DEVICE_SCOPES is not found
    again (its scope paths in a profile would be the old ones); one whose
    source lines moved is (a restart after a host-only edit stays warm).
    Fails when a JAX upgrade drops ``cache_key.custom_hook``."""
    def keys(code):  # one directory: its path is part of every key
        out = _run(code, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
        assert out.returncode == 0, out.stderr
        return {p.name.removesuffix("-atime").removesuffix("-cache")
                for p in tmp_path.iterdir() if p.name.startswith("jit__lambda")}

    first = keys(_KEY_PROBE)
    assert len(first) == 1
    assert keys("\n\n\n" + _KEY_PROBE) == first
    assert len(keys(_KEY_PROBE.replace("sys.argv[1:]", "['probe_scope']"))) == 2


def test_entry_refuses_a_cpu_backend_nobody_asked_for():
    """``python -m finchat_tpu`` with an engine preset: JAX fell back to the
    CPU (no accelerator here) and ``JAX_PLATFORMS`` did not ask for it."""
    code = ("import sys; sys.argv = ['finchat_tpu', '--preset', 'tiny', "
            "'--no-http']; from finchat_tpu.__main__ import main; main()")
    out = _run(code, {"JAX_PLATFORMS": None})
    assert out.returncode != 0
    assert "no CPU run was requested" in out.stderr


def test_cpu_backend_accepted_when_requested():
    from finchat_tpu.utils import runtime

    # conftest pinned jax_platforms=cpu: the CPU was asked for
    facts = runtime.require_accelerator_unless_cpu_requested()
    assert facts == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                     "count": jax.device_count()}


@pytest.mark.skipif(jax.default_backend() != "cpu", reason="CPU-only contract")
def test_compiled_kernel_raises_on_cpu_instead_of_interpreting():
    """Kernel wrappers default to compiled; off the chip that is an error,
    never a quiet switch to the interpreter."""
    from finchat_tpu.ops.flash_attention import flash_attention
    from finchat_tpu.ops.quant_matmul import quant_matmul_int8

    q = jnp.ones((1, 8, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="Only interpret mode is supported on CPU"):
        jax.block_until_ready(flash_attention(q, q, q))
    flash_attention(q, q, q, interpret=True)  # the explicit test path
    with pytest.raises(ValueError, match="Only interpret mode is supported on CPU"):
        jax.block_until_ready(quant_matmul_int8(
            jnp.ones((8, 128)), jnp.ones((128, 128), jnp.int8), jnp.ones((128,))))


def test_compiled_kernels_refuse_a_model_parallel_mesh():
    """The partitioner refuses a Mosaic call with sharded operands; under
    model>1 the engine says so at construction, never a quiet switch to
    ``ref``."""
    from finchat_tpu.engine.engine import InferenceEngine
    from finchat_tpu.models.llama import PRESETS, init_params
    from finchat_tpu.parallel.mesh import MeshSpec, build_mesh
    from finchat_tpu.utils.config import EngineConfig

    config = PRESETS["tiny"]
    params = init_params(config, jax.random.key(0))
    engine_cfg = EngineConfig(max_seqs=2, page_size=8, num_pages=16,
                              max_seq_len=64, prefill_chunk=8)
    mesh = build_mesh(MeshSpec(model=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="model>1"):
        InferenceEngine(config, params, engine_cfg, mesh=mesh,
                        attn_backend="pallas")
    InferenceEngine(config, params, engine_cfg, mesh=mesh, attn_backend="ref")
