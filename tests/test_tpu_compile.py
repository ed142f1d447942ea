"""The paged attention kernel compiled for a v5e that is described, not
attached (the TPU's compiler is installed beside the CPU backend): what
interpret mode cannot show — Mosaic's layout rules and the scoped-VMEM limit
— at the widths of the benchmark's cell. Nothing runs; a result or a time
comes only from the chip (chip_smoke.py, perfbench/).

All such compiles live in this one file: only one process may hold the TPU
library, and the worker that is given this file is that process.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from finchat_tpu.engine.kv_cache import scale_rows
from finchat_tpu.ops.paged_attention import (
    paged_flash_attention,
    paged_flash_attention_q8,
)

# mixtral-8x7b-v0.1 as perfbench/configs has it: 32 / 8 heads of 128, pages
# of 128 tokens, a table of max_seq_len / page = 128 entries, 1,600 pages
ROWS, HEADS, KV_HEADS, HEAD_DIM, PAGE, WIDTH, LAYERS, POOL = 16, 32, 8, 128, 128, 128, 3, 1600


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep it out
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


def _compiled_kernel_calls(one_chip, C, quantized, heads, kv_heads, layers):
    """The names of the custom calls in the paged kernel compiled for the
    described chip at 16 rows, pages of 128 and a table of 128 entries. At
    C = 1 the body holds the shared-head pass beside the rows' walks (the
    stacked query block, its m / l / acc scratch): ONE call all the same."""
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pages = shape((layers, POOL, PAGE, kv_heads * HEAD_DIM),
                  jnp.int8 if quantized else jnp.bfloat16)
    sources = (pages, pages)
    if quantized:
        scales = shape((layers, POOL, scale_rows(kv_heads), PAGE), jnp.float32)
        sources += (scales, scales)
    kernel = paged_flash_attention_q8 if quantized else paged_flash_attention
    compiled = jax.jit(
        lambda *args: kernel(*args, page_size=PAGE, n_kv=kv_heads)
    ).lower(
        shape((ROWS, C, heads, HEAD_DIM), jnp.bfloat16), *sources,
        shape((ROWS, WIDTH), jnp.int32), shape((ROWS,), jnp.int32),
        shape((ROWS,), jnp.int32), shape((1,), jnp.int32),
    ).compile()
    return [line.split(" = ")[0].strip() for line in compiled.as_text().splitlines()
            if "tpu_custom_call" in line and " custom-call(" in line]


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("C", [1, 3, 256], ids=["decode", "verify", "prefill"])
def test_paged_attention_compiles_for_v5e_at_the_cell_shape(one_chip, C, quantized):
    calls = _compiled_kernel_calls(one_chip, C, quantized, HEADS, KV_HEADS, LAYERS)
    # the benchmark's readers find the kernel by this name (attn_share.sat,
    # attn_kv_roofline.sat, which divides by the MEAN time of such calls): ONE
    # custom call named after the jitted wrapper
    assert len(calls) == 1 and calls[0].startswith("%paged_flash_attention")


# falcon-h1-34b-instruct as perfbench/configs has it: 20 / 4 heads of 128 (5
# query heads a KV head, pages 512 lanes wide), the same pool and table
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("C", [1, 256], ids=["decode", "prefill"])
def test_paged_attention_compiles_for_v5e_at_falcon_h1s_head_counts(one_chip, C, quantized):
    calls = _compiled_kernel_calls(one_chip, C, quantized, 20, 4, 5)
    assert len(calls) == 1 and calls[0].startswith("%paged_flash_attention")


def test_falcon_h1_decode_step_compiles_for_v5e_with_its_state_in_place(one_chip):
    """The whole decode step at the cell's size (5 layers, 16 slots, the
    1,600-page pool, the compiled kernels): the K/V pool AND the recurrent
    state [5, 16, 32, 128, 256] float32 are donated and updated in place —
    a copy of the state alone would be 0.34 GB of temporaries a step. The
    state's one-token update is ``ops/ssm_step.py``'s kernel (Mosaic's layout
    rules for its [rows, 32, 128, 256] float32 blocks are checked by this
    compile), and it is the ONLY operation under ``ssm_scan`` that touches
    the carried state: XLA's own two fusions both read it."""
    import json
    from pathlib import Path

    from finchat_tpu.engine import engine as E
    from finchat_tpu.models.llama import init_params
    from finchat_tpu.utils.config import EngineConfig
    from perfbench.models import falcon_h1

    file = json.loads((Path(__file__).resolve().parents[1]
                       / "perfbench/configs/falcon-h1-34b-instruct.json").read_text())
    c = falcon_h1.program_config(dict(file, num_hidden_layers=5))
    cfg = EngineConfig(**file["engine"])

    def described(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)

    params = described(jax.eval_shape(lambda: init_params(c, jax.random.key(0))))
    state = described(jax.eval_shape(lambda: E.create_state(c, cfg, WIDTH)))
    row = lambda dtype: jax.ShapeDtypeStruct((ROWS,), dtype, sharding=one_chip)  # noqa: E731
    compiled = E.decode_step.lower(
        params, state, row(bool), row(jnp.float32), row(jnp.float32), row(jnp.int32),
        config=c, page_size=PAGE, attn_backend="pallas", qm_backend="ref").compile()
    memory = compiled.memory_analysis()
    state_bytes = 5 * ROWS * 32 * 128 * 256 * 4
    assert state.ssm_state.shape == (5, ROWS, 32, 128, 256)
    assert memory.alias_size_in_bytes >= state_bytes + 2 * 5 * POOL * PAGE * 512 * 2
    assert memory.temp_size_in_bytes < state_bytes // 4
    text = compiled.as_text()
    scan = [line for line in text.splitlines()
            if "/ssm_scan/" in line and " = " in line and "op_name=" in line]
    kernels = [line for line in scan if 'custom_call_target="tpu_custom_call"' in line]
    # the benchmark's readers find the update by its scope (ssm_state_roofline.sat,
    # ssm_share.sat), and attn_share.sat must not: it takes custom calls by name
    assert len(kernels) == 1 and "ssm_state_step" in kernels[0].split(" = ")[0]
    assert "attention" not in kernels[0].split(" = ")[0]
    # and attention stays ONE custom call a layer under its scope, shared-head
    # pass and all: attn_kv_roofline.sat divides by the mean time of such calls
    attention = [line.split(" = ")[0] for line in text.splitlines()
                 if "/paged_attention/" in line and " = " in line
                 and 'custom_call_target="tpu_custom_call"' in line]
    assert len(attention) == 1 and "paged_flash_attention" in attention[0]
    # operands are printed by name: look each one's type up where it is defined
    types = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.-]+) = (\S+)", text, re.M))
    carried = "f32[5,16,32,128,256]"
    readers = [name for line in scan if " fusion(" in line
               for name, operands in [re.match(r"\s*(?:ROOT )?(%[\w.-]+) = .*? fusion\(([^)]*)\)",
                                               line).groups()]
               if any(types.get(operand.strip(), "").startswith(carried)
                      for operand in [name, *operands.split(",")])]
    assert readers == [], readers
