"""The paged attention kernel compiled for a v5e that is described, not
attached (the TPU's compiler is installed beside the CPU backend): what
interpret mode cannot show — Mosaic's layout rules and the scoped-VMEM limit
— at the widths of the benchmark's cell. Nothing runs; a result or a time
comes only from the chip (chip_smoke.py, perfbench/).

All such compiles live in this one file: only one process may hold the TPU
library, and the worker that is given this file is that process.
"""

import json
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks import relayout_probe
from finchat_tpu.engine.kv_cache import scale_rows
from finchat_tpu.ops.paged_attention import (
    paged_flash_attention,
    paged_flash_attention_q8,
)

# mixtral-8x7b-v0.1 as perfbench/configs has it: 32 / 8 heads of 128, pages
# of 128 tokens, a table of max_seq_len / page = 128 entries, 1,600 pages
ROWS, HEADS, KV_HEADS, HEAD_DIM, PAGE, WIDTH, LAYERS, POOL = 16, 32, 8, 128, 128, 128, 3, 1600
MIB = 1 << 20


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


_DECODE_STEPS = {}  # a configuration's compiled decode step, shared by the tests that read it


def _compiled_decode_step(one_chip, file):
    """``decode_step`` of ``file``'s configuration compiled for the described
    chip at the file's engine options, from shapes alone; returns ``(compiled,
    state shapes)``."""
    key = json.dumps(file, sort_keys=True)
    if key not in _DECODE_STEPS:
        _DECODE_STEPS[key] = relayout_probe.compiled_decode_step(file, one_chip)
    return _DECODE_STEPS[key]


_config_file = relayout_probe.config_file


def test_falcon_h1_decode_step_compiles_for_v5e_with_its_state_in_place(one_chip):
    """The whole decode step at the cell's size (5 layers, 16 slots, the
    1,600-page pool, the compiled kernels): the K/V pool AND the recurrent
    state [5, 16, 32, 128, 256] float32 are donated and updated in place —
    a copy of the state alone would be 0.34 GB of temporaries a step. The
    state's one-token update is ``ops/ssm_step.py``'s kernel (Mosaic's layout
    rules for its [rows, 32, 128, 256] float32 blocks are checked by this
    compile), and it is the ONLY operation under ``ssm_scan`` that touches
    the carried state: XLA's own two fusions both read it."""
    compiled, state = _compiled_decode_step(
        one_chip, dict(_config_file("falcon-h1-34b-instruct"), num_hidden_layers=5))
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


# olmo-hybrid-7b as perfbench/configs has it: 30 / 30 heads of 128 — ONE query
# head a KV head (at decode 8 KV heads share a softmax tile off block-diagonal
# queries 1,024 lanes wide, the last tile 6 heads: Mosaic's layout rules for
# that body are checked here) and a token row of K 3,840 wide, 7.5 KiB: the
# widest the kernel's blocks hold
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("C", [1, 256], ids=["decode", "prefill"])
def test_paged_attention_compiles_for_v5e_at_olmo_hybrids_head_counts(one_chip, C, quantized):
    calls = _compiled_kernel_calls(one_chip, C, quantized, 30, 30, 2)
    assert len(calls) == 1 and calls[0].startswith("%paged_flash_attention")


def test_kv_append_compiles_for_v5e_at_olmo_hybrids_row_width(one_chip):
    """The decode step's in-place append at a 3,840-wide row (a page of 128
    tokens is 0.94 MiB: since PR 49 a row's 16-token slab is read, patched and
    written, 120 KiB each of K and V): ONE custom call."""
    from finchat_tpu.ops.kv_append import paged_kv_append

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pages = shape((2, POOL, PAGE, 30 * HEAD_DIM), jnp.bfloat16)
    compiled = jax.jit(lambda *args: paged_kv_append(*args, page_size=PAGE)).lower(
        shape((ROWS, 1, 2 * 30 * HEAD_DIM), jnp.bfloat16), pages, pages,
        shape((ROWS, WIDTH), jnp.int32), shape((ROWS,), jnp.int32), shape((ROWS,), jnp.int32),
        shape((1,), jnp.int32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1


def test_olmo_hybrid_decode_step_compiles_for_v5e_with_pool_and_state_in_place(one_chip):
    """The whole decode step at the cell's size (two periods of three linear
    layers and a full one, 16 slots, the 1,600-page pool, the compiled
    kernels): the pool has the FULL layers' depth and the recurrent state the
    LINEAR layers', both are donated and updated in place, and a period's
    layers stand one after another in the ONE scan's body (no loop inside
    it: PERF.md section 6, PR 32). The state is 15 tiles of 96 x 384 a row —
    two heads side by side, no padded lane — and its one-token update is
    ``ops/gdn_step.py``'s kernel (Mosaic's layout rules for its [rows, 15, 96,
    384] float32 blocks are checked by this compile): three custom calls
    under ``gdn_scan``, a period's, which is what ``gdn_state_roofline.sat``
    times and the adapter counts, and no fusion under that scope touches the
    carried state."""
    file = _config_file("olmo-hybrid-7b")
    compiled, state = _compiled_decode_step(
        one_chip, dict(file, num_hidden_layers=8, layer_types=file["layer_types"][:4] * 2))
    assert state.k_pages.shape == (2, POOL, PAGE, 30 * HEAD_DIM)
    assert state.ssm_state.shape == (6, ROWS, 15, 96, 384)
    assert state.conv_state.shape == (6, ROWS, 3, 11520)
    memory = compiled.memory_analysis()
    state_bytes = 6 * ROWS * 30 * 96 * 192 * 4
    pool_bytes = 2 * 2 * POOL * PAGE * 30 * HEAD_DIM * 2
    assert memory.alias_size_in_bytes >= state_bytes + pool_bytes
    assert memory.temp_size_in_bytes < state_bytes // 4
    text = compiled.as_text()
    # one custom call a FULL layer under each scope, by the names the
    # benchmark's readers find them by
    for scope, name in (("paged_attention", "paged_flash_attention"), ("kv_append", "kv_append")):
        calls = [line.split(" = ")[0] for line in text.splitlines()
                 if f"/{scope}/" in line and " = " in line
                 and 'custom_call_target="tpu_custom_call"' in line]
        assert len(calls) == 1 and name in calls[0], (scope, calls)
    # the carried state is advanced three times in the program: a period's
    # linear layers, each by the kernel, in place
    scan = [line for line in text.splitlines()
            if "/gdn_scan/" in line and " = " in line and "op_name=" in line]
    kernels = [line.split(" = ")[0] for line in scan
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 3 and all("gdn_state_step" in name for name in kernels), kernels
    assert not any("attention" in name for name in kernels)  # attn_share.sat takes calls by name
    fusions = [line for line in scan if " fusion(" in line and "f32[6,16,15,96,384]" in line]
    assert fusions == [], fusions
    assert text.count(" while(") == 1  # the scan over periods and no other loop


def _loop_nest(text):
    """``(depth, op_name)`` of every ``while`` of a compiled module, from the
    entry computation down (a loop's depth is the number of loops around it)."""
    bodies, at = {}, None
    for line in text.splitlines():
        opened = re.match(r"^(?:ENTRY )?(%[\w.-]+) .*\{\s*$", line)
        if opened:
            at = opened.group(1)
            bodies[at] = []
        elif at is not None:
            bodies[at].append(line)
    entry = re.search(r"^ENTRY (%[\w.-]+)", text, re.M).group(1)
    found, seen = [], set()

    def walk(name, depth):
        for line in bodies.get(name, ()):
            if " while(" in line:
                op = re.search(r'op_name="([^"]*)"', line)
                found.append((depth, op.group(1) if op else ""))
                walk(re.search(r"body=(%[\w.-]+)", line).group(1), depth + 1)
                continue
            for callee in re.findall(r"(?:calls|to_apply|\w+_computations?)=\{?(%[\w.-]+)", line):
                if callee not in seen:
                    seen.add(callee)
                    walk(callee, depth)

    walk(entry, 0)
    return found


@pytest.mark.parametrize("T", [512])
def test_olmo_hybrid_ragged_round_has_no_loop_over_layers_inside_the_period_scan(one_chip, T):
    """``ragged_mixed_step`` at the cell's size and the window round's bucket
    (a prompt's 256-token chunk beside 15 decode rows). With a ``lax.scan``
    over a period's run of linear layers nested in the scan over periods this
    step hung a v5e one round in ten (PERF.md section 6, PR 32; cause not
    found; ``benchmarks/ragged_round_soak.py`` is the probe). What ran clean:
    ONE loop over periods, and inside it, a layer after another, only the
    chunked form's scan over blocks (the longest rows'; the other rows' one
    block stands outside any loop) and the loops XLA makes of the state's
    gathers by slot. So: one loop at the top, three block scans at depth 1,
    nothing deeper."""
    from finchat_tpu.engine import engine as E
    from finchat_tpu.models.llama import init_params
    from finchat_tpu.utils.config import EngineConfig
    from perfbench.models import olmo_hybrid

    file = _config_file("olmo-hybrid-7b")
    c = olmo_hybrid.program_config(file)
    cfg = EngineConfig(**file["engine"])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def described(tree):
        return jax.tree.map(lambda x: shape(x.shape, x.dtype), tree)

    rows = lambda dtype: shape((ROWS,), dtype)  # noqa: E731
    compiled = E.ragged_mixed_step.lower(
        described(jax.eval_shape(lambda: init_params(c, jax.random.key(0)))),
        described(jax.eval_shape(lambda: E.create_state(c, cfg, WIDTH))),
        shape((T,), jnp.int32), shape((T,), jnp.int32), rows(jnp.int32), rows(jnp.int32),
        rows(jnp.int32), rows(bool), rows(bool), rows(jnp.int32), rows(jnp.float32),
        rows(jnp.float32), rows(jnp.int32), config=c, page_size=PAGE, attn_backend="pallas",
        qm_backend="ref", max_row_tokens=cfg.prefill_chunk).compile()
    nest = _loop_nest(compiled.as_text())
    assert [op for depth, op in nest if depth == 0] == ["jit(ragged_mixed_step)/while"], nest
    assert max(depth for depth, _op in nest) == 1, nest
    assert sum(op.endswith("/gdn_scan/while") for _depth, op in nest) == 3, nest


# granite-4.0-h-small as perfbench/configs has it (PR 34): 128 mixer heads of
# 64 with 128 state channels, 32 / 8 attention heads with a softmax scale of
# 2^-7, 36 held experts of 768 (fused [gate | up]: 1,536) of a router of 72
def test_ssm_state_step_compiles_for_v5e_at_granites_heads(one_chip):
    """``ops/ssm_step.py`` at Granite's 128 heads of [64, 128]: the same 4 MiB
    a row as Falcon-H1's [32, 128, 256], stored as 64 PAIRS of heads with the
    state axis on sublanes, [9, 16, 64, 128, 128] (``stored_shape``; dt x and
    y lane-dense rows [r, 64, 128], B and C turned to columns in the kernel:
    a 128 x 128 transpose); Mosaic's layout rules and the VMEM limit for it
    are checked by this compile. ONE custom call, the state in place, and
    beside it only the small operands' fusions: nothing of the state's size."""
    from finchat_tpu.ops.ssm_step import rows_per_block, ssm_state_step, stored_shape

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    L, H, P, N = 9, 128, 64, 128
    assert stored_shape(H, P, N, 1) == (64, 128, 128)
    assert rows_per_block(ROWS, H * P * N * 4) == 2
    compiled = ssm_state_step.lower(
        shape((L, ROWS, 64, 128, 128)), shape((ROWS, H, P)), shape((ROWS, H)), shape((H,)),
        shape((ROWS, 1, N)), shape((ROWS, 1, N)), shape((H,)), shape((1,), jnp.int32)).compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= L * ROWS * H * P * N * 4  # in place
    assert memory.temp_size_in_bytes < 1024 * 1024
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 1 and "%ssm_state_step" in calls[0].split(" = ")[0]


def test_paged_attention_compiles_for_v5e_with_a_scale_of_its_own(one_chip):
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pages = shape((1, POOL, PAGE, KV_HEADS * HEAD_DIM), jnp.bfloat16)
    text = jax.jit(
        lambda *args: paged_flash_attention(*args, page_size=PAGE, n_kv=KV_HEADS, scale=2.0 ** -7)
    ).lower(
        shape((ROWS, 1, HEADS, HEAD_DIM), jnp.bfloat16), pages, pages,
        shape((ROWS, WIDTH), jnp.int32), shape((ROWS,), jnp.int32),
        shape((ROWS,), jnp.int32), shape((1,), jnp.int32),
    ).compile().as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 1 and calls[0].split(" = ")[0].strip().startswith("%paged_flash_attention")


def test_the_grouped_matmul_compiles_for_v5e_at_the_top_bucket(one_chip):
    """``moe_mlp``'s grouped form at the 4,096-token ragged bucket: 40,960
    (token, pick) pairs over the 36 held stacks; each grouped matmul is ONE
    operation whose FLOPs are the pairs', not pairs x experts."""
    from finchat_tpu.models.llama import moe_mlp
    from perfbench.models import granitemoehybrid

    c = granitemoehybrid.program_config(_config_file("granite-4.0-h-small"))

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    lp = {"router": shape((4096, 72), jnp.float32), "moe_in": shape((36, 4096, 1536)),
          "moe_out": shape((36, 768, 4096)), "shared_in": shape((4096, 3072)),
          "shared_out": shape((1536, 4096))}
    compiled = jax.jit(lambda h, lp: moe_mlp(h, lp, c)).lower(shape((1, 4096, 4096)), lp).compile()
    text = compiled.as_text()
    ragged = [line.split(" = ")[0].strip() for line in text.splitlines()
              if "ragged" in line.lower() and " = " in line
              and ("custom-call(" in line or "ragged-dot(" in line)]
    # the two grouped matmuls (they share one pass that lays out the groups)
    assert len([name for name in ragged if "metadata" not in name]) == 2, ragged
    assert "/moe_group/" in text and "/moe_shared/" in text and "/moe_experts/" in text
    pairs = 4096 * 10
    grouped_flops = 2 * pairs * 4096 * (1536 + 768)
    shared_flops = 2 * 4096 * 4096 * (3072 + 1536)
    assert compiled.cost_analysis()["flops"] < 1.5 * (grouped_flops + shared_flops)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


@pytest.mark.parametrize("tokens", [16, 64, 128])
def test_the_touched_expert_pass_compiles_for_v5e_at_granites_stacks(one_chip, tokens):
    """``ops/moe_step.py`` over the whole stacks [10, 36, 4096, 1536] and
    [10, 36, 768, 4096] in bf16, three tiles of 256 columns an expert (each of
    a step's three blocks 2 MiB, double-buffered under the VMEM limit it
    asks for): one custom call, nothing copied and no temporary beside it."""
    from finchat_tpu.ops.moe_step import moe_experts_step, width_tile

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    L, E, D, F = 10, 36, 4096, 768
    assert width_tile(F, D, 2) == 256
    compiled = moe_experts_step.lower(
        shape((tokens, D)), shape((E, tokens, 1), jnp.float32), shape((E,), jnp.int32),
        shape((1,), jnp.int32), shape((L, E, D, 2 * F)), shape((L, E, F, D)),
        shape((1,), jnp.int32)).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 1 and "%moe_experts_step" in calls[0].split(" = ")[0]
    assert compiled.memory_analysis().temp_size_in_bytes < 1024 * 1024


def test_granite_decode_step_compiles_for_v5e_with_pool_and_state_in_place(one_chip):
    """The whole decode step at the cell's size (one period of ten layers, 16
    slots, the 1,600-page pool of ONE layer, nine layers of [16, 128, 64, 128]
    state stored as pairs): pool and state donated and updated in place, the state's update
    ``ops/ssm_step.py``'s kernel in each of the nine mamba layers, attention
    one custom call, the routed experts ``ops/moe_step.py``'s pass in each of
    the ten layers (one custom call under ``moe_experts`` that takes the
    WHOLE stacks: no layer's slice is copied, no ``[16, 36, 1536]``
    intermediate; no grouped matmul at 16 tokens), and the counts of experts
    touched and read two int32 out."""
    file = _config_file("granite-4.0-h-small")
    compiled, state = _compiled_decode_step(one_chip, file)
    memory = compiled.memory_analysis()
    state_bytes = 9 * ROWS * 128 * 64 * 128 * 4
    # 128 heads of [64, 128] as 64 pairs [128, 2 x 64] (ops/ssm_step.py stored_shape)
    assert state.ssm_state.shape == (9, ROWS, 64, 128, 128)
    assert state.k_pages.shape == (1, POOL, PAGE, 8 * 128)
    assert memory.alias_size_in_bytes >= state_bytes + 2 * POOL * PAGE * 1024 * 2
    assert memory.temp_size_in_bytes < state_bytes // 4
    text = compiled.as_text()
    kernels = [line.split(" = ")[0] for line in text.splitlines()
               if "/ssm_scan/" in line and " = " in line and "op_name=" in line
               and 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 9 and all("ssm_state_step" in k for k in kernels)
    # ... and the ONLY operations that yield an array of the state's size:
    # mamba_state_roofline.sat sums every operation under ssm_scan, so a
    # transpose, a copy or a view of a layer's [16, 64, 128, 128] (or of the
    # heads' [16, 128, 64, 128]) left in the step would count against the pass
    state_sized = [line.split(" = ")[0].strip() for line in text.splitlines()
                   if re.search(r" = \(?[^=]*f32\[(9,)?16,(64,128,128|128,64,128)\]", line)
                   and " parameter(" not in line and "get-tuple-element(" not in line
                   and " tuple(" not in line and " while(" not in line]
    assert len(state_sized) == 9 and all("ssm_state_step" in k for k in state_sized), state_sized
    attention = [line.split(" = ")[0] for line in text.splitlines()
                 if "/paged_attention/" in line and " = " in line
                 and 'custom_call_target="tpu_custom_call"' in line]
    assert len(attention) == 1 and "paged_flash_attention" in attention[0]
    assert "ragged" not in text.lower()
    for scope in ("moe_router", "moe_experts", "moe_shared"):
        assert f"/{scope}/" in text, scope
    experts = [line.split(" = ")[0] for line in text.splitlines()
               if "/moe_experts/" in line and " = " in line
               and 'custom_call_target="tpu_custom_call"' in line]
    assert len(experts) == 10 and all("moe_experts_step" in k for k in experts)
    assert "bf16[16,36,1536]" not in text and "bf16[36,4096,1536]" not in text
    out_shapes = [x.shape for x in jax.tree.leaves(compiled.out_info)]
    assert out_shapes.count((2,)) == 1  # the counts (touched, read) beside the tokens


# --- deepseek-v3.2-exp (PR 40): latent pages, the indexer's selection -----------

def test_the_touched_expert_pass_compiles_for_v5e_at_deepseeks_stacks(one_chip):
    """``ops/moe_step.py`` over ``deepseek-v3.2-exp``'s stacks [4, 16, 7168,
    4096] and [4, 16, 2048, 7168]: a tile of 256 columns brings three blocks
    of 3.7 MB a grid step (7168 x 256 x 2 B), double-buffered under a VMEM
    limit of about 32 MB by its own formula, against Granite's 22: one custom
    call, nothing copied and no temporary beside it."""
    from finchat_tpu.ops.moe_step import moe_experts_step, width_tile

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    L, E, D, F = 4, 16, 7168, 2048
    assert width_tile(F, D, 2) == 256
    compiled = moe_experts_step.lower(
        shape((ROWS, D)), shape((E, ROWS, 1), jnp.float32), shape((E,), jnp.int32),
        shape((1,), jnp.int32), shape((L, E, D, 2 * F)), shape((L, E, F, D)),
        shape((1,), jnp.int32)).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 1 and "%moe_experts_step" in calls[0].split(" = ")[0]
    assert compiled.memory_analysis().temp_size_in_bytes < 1024 * 1024


# --- trinity-mini (PR 47): 128 small experts; the pass's tile by its bytes (PR 48) ---

@pytest.mark.parametrize("tokens", [32, 128])
def test_the_touched_expert_pass_compiles_for_v5e_at_trinity_minis_stacks(one_chip, tokens):
    """``ops/moe_step.py`` over ``trinity-mini``'s stacks [4, 128, 2048, 2048]
    and [4, 128, 1024, 2048]: an expert's three blocks are 12 MiB, so
    ``width_tile`` gives the whole width — ONE grid step an expert, ``W_in[e]``
    and ``W_out[e]`` read contiguously — at the decode step's 32 rows and a
    ragged round's 128 tokens: one custom call, nothing copied, no temporary
    beside it, and the VMEM it asks for (two buffers of an expert, the rows,
    8 MiB of room: 33 and 36 MiB) far under the chip's 128."""
    from finchat_tpu.ops.moe_step import moe_experts_step, width_tile

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    L, E, D, F = 4, 128, 2048, 1024
    assert width_tile(F, D, 2) == F
    compiled = moe_experts_step.lower(
        shape((tokens, D)), shape((E, tokens, 1), jnp.float32), shape((E,), jnp.int32),
        shape((1,), jnp.int32), shape((L, E, D, 2 * F)), shape((L, E, F, D)),
        shape((1,), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 1 and "%moe_experts_step" in calls[0].split(" = ")[0]
    assert compiled.memory_analysis().temp_size_in_bytes < 1024 * 1024
    asked = int(re.search(r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"0","size":"(\d+)"',
                          calls[0]).group(1))
    assert asked == 2 * (3 * D * F * 2) + 16 * tokens * D + 8 * MIB < 128 * MIB


def test_kv_append_compiles_for_v5e_with_a_latent_row_and_an_index_key(one_chip):
    """The decode step's in-place append where the pool's two arrays hold rows
    of different widths: a latent row of 640 columns and an index key of 128,
    one beside the other in ``kv_new``; each row's slab of both read, patched
    and written back by ONE custom call."""
    from finchat_tpu.ops.kv_append import paged_kv_append

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(lambda *args: paged_kv_append(*args, page_size=PAGE)).lower(
        shape((ROWS, 1, 640 + 128)), shape((5, POOL, PAGE, 640)), shape((5, POOL, PAGE, 128)),
        shape((ROWS, WIDTH), jnp.int32), shape((ROWS,), jnp.int32), shape((ROWS,), jnp.int32),
        shape((1,), jnp.int32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1024 * 1024


def test_the_one_token_latent_attention_compiles_for_v5e_at_the_cell_shape(one_chip):
    """``ops/latent_attention.py``'s gather form at ``deepseek-v3.2-exp``'s
    widths: 16 rows, 128 heads of 576 against pages of 640-column latent rows,
    an indexer of 64 heads of 128 over a table of 128 pages (16,384 tokens),
    the 2,048 best gathered: plain XLA — no custom call — under the three
    scopes the benchmark's readers time, with temporaries of about a quarter
    of a GB (the index keys of 16 x 16,384 tokens and their scores)."""
    from finchat_tpu.ops.latent_attention import LatentShape, decode_attention

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(lambda *args: decode_attention(
        *args, page_size=PAGE, shape=LatentShape(512, 2048, 0.135234))).lower(
        shape((ROWS, 128, 576)), shape((ROWS, 64, 128)), shape((ROWS, 64), jnp.float32),
        shape((5, POOL, PAGE, 640)), shape((5, POOL, PAGE, 128)), shape((), jnp.int32),
        shape((ROWS, WIDTH), jnp.int32), shape((ROWS,), jnp.int32), shape((ROWS,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    for scope in ("dsa_indexer", "dsa_select", "mla_attention"):
        assert f"/{scope}/" in text, scope
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def test_the_walked_latent_attention_compiles_for_v5e_at_the_cell_shape(one_chip):
    """The same call on a kernel backend (PR 41): a table of 128 pages is 8
    selections of 2,048, so the rows WALK their pages — ONE custom call under
    ``mla_attention`` (``paged_latent_attention``: the paged kernel's walk
    with one source, 128 query rows a KV "head", 16 x 128 stacked rows for
    the shared head, under a 48 MiB VMEM limit), the selection a mask made
    under ``dsa_select`` without a sort, no gathered ``[32768, 640]`` copy.
    Since PR 43 the indexer walks too: ONE custom call under ``dsa_indexer``
    (``paged_index_scores``), no staged ``[2048 pages, 128, 128]`` copy of the
    table's index keys and no ``[16, 64, 16384]`` products."""
    from finchat_tpu.ops.latent_attention import LatentShape, decode_attention

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(lambda *args: decode_attention(
        *args, page_size=PAGE, shape=LatentShape(512, 2048, 0.135234), backend="pallas")).lower(
        shape((ROWS, 128, 576)), shape((ROWS, 64, 128)), shape((ROWS, 64), jnp.float32),
        shape((5, POOL, PAGE, 640)), shape((5, POOL, PAGE, 128)), shape((), jnp.int32),
        shape((ROWS, WIDTH), jnp.int32), shape((ROWS,), jnp.int32), shape((ROWS,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    by_scope = {scope: [c.split(" = ")[0] for c in calls if f"/{scope}/" in c]
                for scope in ("dsa_indexer", "mla_attention")}
    assert len(calls) == 2 and [len(v) for v in by_scope.values()] == [1, 1], by_scope
    assert "paged_latent_attention" in by_scope["mla_attention"][0]
    assert "paged_index_scores" in by_scope["dsa_indexer"][0]
    assert "bf16[32768,640]" not in text and "bf16[16,2048,640]" not in text
    assert "bf16[2048,128,128]" not in text and "bf16[16,16384,128]" not in text
    assert "f32[16,64,16384]" not in text and "f32[16,1,64,16384]" not in text
    assert " sort(" not in text
    for scope in ("dsa_indexer", "dsa_select", "mla_attention"):
        assert f"/{scope}/" in text, scope
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


def test_the_indexers_walk_compiles_for_v5e_at_the_cell_shape(one_chip):
    """``paged_index_scores`` alone at the cell's shapes (16 rows, 64 index
    heads over keys of 128, a table of 128 columns over a pool five layers
    deep): ONE custom call — the paged kernel's walk in its index form,
    blocks of 2,048 tokens, the ``[16, 18304]`` float32 scores resident over
    the grid, inside the 16 MiB of scoped VMEM a kernel has without asking —
    and temporaries of the padded scores and the lane-wide weights alone."""
    from finchat_tpu.ops.paged_attention import INDEX_BLOCK_TOKENS, paged_index_scores

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(lambda *args: paged_index_scores(*args, page_size=PAGE)).lower(
        shape((ROWS, 64, 128)), shape((ROWS, 64), jnp.float32), shape((5, POOL, PAGE, 128)),
        shape((ROWS, WIDTH), jnp.int32), shape((ROWS,), jnp.int32), shape((1,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    calls = [line.split(" = ")[0] for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 1 and "paged_index_scores" in calls[0]
    padded = (WIDTH + INDEX_BLOCK_TOKENS // PAGE - 1) * PAGE
    assert f"f32[{ROWS},{padded}]" in text and "vmem_limit_bytes" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 1024 * 1024


def test_deepseek_decode_step_compiles_for_v5e_walking_its_latent_pages(one_chip):
    """The whole decode step at the cell's size (1 dense + 4 routed layers, 16
    slots, a table of 128 pages = 8 selections): the one-token latent
    attention is ONE custom call a layer under ``mla_attention`` (one in the
    scan's body, one in the leading dense layer), no gathered ``[32768, 640]``
    copy and no sort under ``dsa_select``; both paged arrays are updated in
    place, and the step's temporaries are a ninth of the gather form's."""
    compiled, state = _compiled_decode_step(one_chip, _config_file("deepseek-v3.2-exp"))
    text = compiled.as_text()
    walks = [line for line in text.splitlines()
             if "/mla_attention/" in line and 'custom_call_target="tpu_custom_call"' in line]
    assert len(walks) == 2 and all(
        "paged_latent_attention" in line.split(" = ")[0] for line in walks)
    assert "bf16[32768,640]" not in text
    assert not [line for line in text.splitlines() if "/dsa_select/" in line and " sort(" in line]
    # ... and the indexer its index pages (PR 43): one custom call a layer, nothing staged
    scored = [line for line in text.splitlines()
              if "/dsa_indexer/" in line and 'custom_call_target="tpu_custom_call"' in line]
    assert len(scored) == 2 and all(
        "paged_index_scores" in line.split(" = ")[0] for line in scored)
    assert "bf16[2048,128,128]" not in text and "f32[16,64,16384]" not in text
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= state.k_pages.size * 2 + state.v_pages.size * 2
    assert memory.temp_size_in_bytes < 64 * 1024 * 1024


# --- phi-4-mini-flash-reasoning (PR 42): one cache read by eight layers, window layers ---

def _phi4_step(one_chip, step, *extra, **static):
    """``step`` of ``phi-4-mini-flash-reasoning`` compiled for the described
    chip at the file's engine options (32 slots), from shapes alone."""
    from finchat_tpu.engine import engine as E
    from finchat_tpu.models.llama import init_params
    from finchat_tpu.utils.config import EngineConfig
    from perfbench.models import phi4flash

    file = _config_file("phi-4-mini-flash-reasoning")
    c = phi4flash.program_config(file)
    cfg = EngineConfig(**file["engine"])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def described(tree):
        return jax.tree.map(lambda x: shape(x.shape, x.dtype), tree)

    params = described(jax.eval_shape(lambda: init_params(c, jax.random.key(0))))
    state = described(jax.eval_shape(lambda: E.create_state(c, cfg, WIDTH)))
    rows = lambda dtype: shape((cfg.max_seqs,), dtype)  # noqa: E731
    args = extra[0](shape, rows) if extra else (
        rows(bool), rows(jnp.float32), rows(jnp.float32), rows(jnp.int32))
    compiled = getattr(E, step).lower(
        params, state, *args, config=c, page_size=PAGE, attn_backend="pallas", qm_backend="ref",
        **static).compile()
    return compiled, state, cfg


def test_phi4_flash_decode_step_compiles_for_v5e_walking_one_cache_from_eight_layers(one_chip):
    """The whole decode step at the cell's size (32 layers in three segments,
    32 slots): the paged kernel once under ``yoco_attention`` in the full
    layer's unrolled segment and once in the cross layers' scan (seven passes
    over the SAME pool at run time), once under ``swa_attention`` in the window
    layers' scan; the appends in place in BOTH pools (the cross layers write
    nothing: two appends in all), the Mamba-1 state in place; the step's
    temporaries a quarter of a GB."""
    compiled, state, _cfg = _phi4_step(one_chip, "decode_step")
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line and " custom-call(" in line]
    by_scope = {scope: [c for c in calls if f"/{scope}/" in c]
                for scope in ("yoco_attention", "swa_attention")}
    walks = {s: sum("paged_flash_attention" in c.split(" = ")[0] for c in cs)
             for s, cs in by_scope.items()}
    appends = {s: sum("kv_append" in c for c in cs) for s, cs in by_scope.items()}
    assert walks == {"yoco_attention": 2, "swa_attention": 1}, walks
    assert appends == {"yoco_attention": 1, "swa_attention": 1}, appends
    for scope in ("m1_scan", "m1_conv", "gmu", "attn_diff"):
        assert f"/{scope}/" in text, scope
    memory = compiled.memory_analysis()
    pools = sum(x.size * 2 for x in (state.k_pages, state.v_pages, state.win_k_pages,
                                     state.win_v_pages))
    assert memory.alias_size_in_bytes >= pools + state.ssm_state.size * 4
    assert memory.temp_size_in_bytes < 0.4e9


@pytest.mark.parametrize("T", [512, 8192], ids=["a chunk beside decode rows", "the top bucket"])
def test_phi4_flash_ragged_round_compiles_for_v5e_and_fits_beside_the_model(one_chip, T):
    """``ragged_mixed_step`` at the window round's bucket and at the top one
    (32 prompts' chunks at once): the ragged kernel under both scopes, the
    Mamba-1 scan a loop over TOKENS that carries ``[rows, 16, 5120]`` — nothing
    of ``[tokens, 5120, 16]`` is materialised (2.7 GB at 8,192 tokens) — and
    arguments and temporaries together under the chip's 15.75 GB."""
    def args(shape, rows):
        return (shape((T,), jnp.int32), shape((T,), jnp.int32), rows(jnp.int32), rows(jnp.int32),
                rows(jnp.int32), rows(bool), rows(bool), rows(jnp.int32), rows(jnp.float32),
                rows(jnp.float32), rows(jnp.int32))

    compiled, _state, cfg = _phi4_step(one_chip, "ragged_mixed_step", args,
                                       max_row_tokens=256)
    text = compiled.as_text()
    for scope in ("yoco_attention", "swa_attention"):
        assert [line for line in text.splitlines() if f"/{scope}/" in line
                and "ragged_flash_attention" in line.split(" = ")[0]], scope
    assert "/m1_scan/while" in text
    assert f"f32[{T},5120,16]" not in text and f"f32[{T},16,5120]" not in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1.5e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.75e9 * 0.85


def test_phi4_flash_prefill_chunk_compiles_for_v5e_at_forty_heads(one_chip):
    """``prefill_step`` of 32 rows: the chunk form of the paged kernel at 40
    query heads of 128 takes a query block of 64 (at 128 its blocks and
    softmax state are 13.1 MiB and the chip's compiler refused the call by
    1 MiB of VMEM, PR 42's first chip call)."""
    def args(shape, rows):
        return (shape((32, 256), jnp.int32), rows(jnp.int32), rows(jnp.int32), rows(jnp.int32))

    compiled, _state, _cfg = _phi4_step(one_chip, "prefill_step", args)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


# --- no weight is copied or staged before its matmul (PR 45) ---

LLAMA_BLOCK = ("mistral-7b-v0.3", "mixtral-8x7b-v0.1", "falcon-h1-34b-instruct",
               "olmo-hybrid-7b", "granite-4.0-h-small")


@pytest.mark.parametrize("name", [*LLAMA_BLOCK, "deepseek-v3.2-exp",
                                  "phi-4-mini-flash-reasoning"])
def test_decode_step_reads_every_projections_weight_where_it_lies(one_chip, name):
    """``decode_step`` of each of the seven configuration files at its cell's
    shapes, compiled for the described v5e: (a) no ``copy``, fused or not,
    whose result is a bfloat16 array of 2,000,000 elements or more (the
    smallest weight at stake, Falcon-H1's ``attn_k``, has 2.6 M; the largest
    re-tiling of an activation that remains, DeepSeek's ``bf16[16,128,512]``,
    1.05 M); (b) no ``dynamic-slice`` fusion of that size that leaves its
    result in on-chip memory (``S(1)``): a layer's weight staged out of its
    stack before the matmul can start; (c) in a llama-block file, a fusion
    under ``attn_qkv`` that takes the WHOLE ``attn_q`` stack ``bf16[L, D, N]``
    as an operand: the matmul reads its layer where it lies, as ``attn_v``'s
    and the MLP's always did. What keeps it so is ``models/quant.py``
    ``flat_fence`` round the product of a projection that is split into heads.
    On the parent of PR 45 this FAILS for Mistral, Mixtral, Falcon-H1 (2 copies
    + 2 staged slices each: ``attn_q`` and ``attn_k``, sliced and transposed a
    layer), Phi-4-flash (the whole ``bf16[16,2560,2560]`` stack copied in HBM
    once a step, ``bf16[2560,2560]`` and two staged slices) and DeepSeek (three
    staged up-projections of the q latent and a nested re-layout of
    ``attn_q_rope``), and passes for Olmo-Hybrid and Granite, which do not
    rotate; with the fence all seven pass.
    ``python3 benchmarks/relayout_probe.py <name>`` prints the listing."""
    from finchat_tpu.models.llama import init_params
    from perfbench.models import adapter

    probe, file = relayout_probe, _config_file(name)
    ops = probe.operations(_compiled_decode_step(one_chip, file)[0].as_text())
    copies, staged = probe.weight_relayouts(ops)
    assert not copies and not staged, [o.line() for o in copies + staged]
    if name in LLAMA_BLOCK:
        c = adapter(file).program_config(file)
        stack = jax.eval_shape(lambda: init_params(c, jax.random.key(0)))["layers"]["attn_q"]
        assert stack.dtype == jnp.bfloat16
        assert probe.reads_whole_stack(ops, "attn_qkv", tuple(stack.shape)), stack.shape


# --- trinity-mini: window layers in the llama block's pattern, 128 held experts (PR 47) ---

def test_trinity_mini_decode_step_compiles_for_v5e_with_both_pools_in_place(one_chip):
    """The whole decode step at the cell's size (1 dense + 4 routed layers, 32
    slots, 128 experts held): the paged kernel at 8 query heads a KV head under
    ``swa_attention`` over a table of 18 columns — once for the leading dense
    layer, outside the scan, and three times in its body — and once under the
    full layer's own ``paged_attention``; the touched-expert pass at ``[2048, 2
    x 1024] x 128`` four times; the appends in place in BOTH pools; no weight
    copied or staged before its matmul; temporaries under a tenth of a GB."""
    file = _config_file("trinity-mini")
    compiled, state = _compiled_decode_step(one_chip, file)
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line and " custom-call(" in line]
    window = [c for c in calls if "/swa_attention/" in c]
    full = [c for c in calls if "/swa_attention/" not in c and "/yoco_attention/" not in c]

    def count(cs, kernel, inside_scan=None):
        return sum(kernel in c.split(" = ")[0] and (inside_scan is None
                                                    or ("/while/body/" in c) == inside_scan)
                   for c in cs)

    for kernel in ("paged_flash_attention", "paged_kv_append"):  # (outside the scan, inside it)
        assert (count(window, kernel, False), count(window, kernel, True)) == (1, 3), kernel
    assert count(full, "paged_flash_attention") == 1 and count(full, "paged_kv_append") == 1
    assert count(calls, "moe_experts_step") == 4 and "/yoco_attention/" not in text
    assert state.win_table.shape == (32, 18) and state.win_k_pages.shape == (4, 649, 128, 512)
    memory = compiled.memory_analysis()
    pools = sum(x.size * 2 for x in (state.k_pages, state.v_pages, state.win_k_pages,
                                     state.win_v_pages))
    assert memory.alias_size_in_bytes >= pools and memory.temp_size_in_bytes < 0.1e9
    copies, staged = relayout_probe.weight_relayouts(relayout_probe.operations(text))
    assert not copies and not staged, [o.line() for o in copies + staged]


def test_trinity_mini_ragged_round_compiles_for_v5e_and_fits_beside_the_model(one_chip):
    """``ragged_mixed_step`` at the top bucket (32 prompts' chunks at once:
    65,536 (token, pick) pairs through the grouped matmul over 128 held
    experts): the ragged kernel under ``swa_attention`` and under the full
    layer's ``ragged_paged_attention``; arguments and temporaries together
    under the chip's 15.75 GB with room for the encoder."""
    from finchat_tpu.engine import engine as E
    from finchat_tpu.models.llama import init_params
    from finchat_tpu.utils.config import EngineConfig
    from perfbench.models import adapter

    file = _config_file("trinity-mini")
    c, cfg = adapter(file).program_config(file), EngineConfig(**file["engine"])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def described(tree):
        return jax.tree.map(lambda x: shape(x.shape, x.dtype), tree)

    params = described(jax.eval_shape(lambda: init_params(c, jax.random.key(0))))
    state = described(jax.eval_shape(lambda: E.create_state(c, cfg, WIDTH)))
    T, B = 8192, cfg.max_seqs
    compiled = E.ragged_mixed_step.lower(
        params, state, shape((T,), jnp.int32), shape((T,), jnp.int32), shape((B,), jnp.int32),
        shape((B,), jnp.int32), shape((B,), jnp.int32), shape((B,), bool), shape((B,), bool),
        shape((B,), jnp.int32), shape((B,), jnp.float32), shape((B,), jnp.float32),
        shape((B,), jnp.int32), config=c, page_size=PAGE, attn_backend="pallas",
        qm_backend="ref", spec_width=0).compile()
    text = compiled.as_text()
    walks = [line for line in text.splitlines()
             if "ragged_flash_attention" in line.split(" = ")[0] and " custom-call(" in line]
    assert [w for w in walks if "/swa_attention/" in w] and [
        w for w in walks if "/swa_attention/" not in w and "/ragged_paged_attention/" in w]
    assert "ragged-dot" in text or "ragged_dot" in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1.2e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.75e9 * 0.75


# --- the one-token step's page traffic: a token's slab, a window's pages (PR 49) ---

@pytest.mark.parametrize("rows, layers, pool, widths", [
    (32, 4, 649, (512, 512)),  # Trinity-Mini's window pool (4 KV heads)
    (16, 9, POOL, (1024, 1024)),  # Mistral, Mixtral, Granite (8)
    (32, 8, 217, (1280, 1280)),  # Phi-4-flash's window pool (10 of a pair's width)
    (16, 2, POOL, (3840, 3840)),  # Olmo-Hybrid (30)
    (16, 5, POOL, (640, 128)),  # DeepSeek: a latent row beside an index key
], ids=["512", "1024", "1280", "3840", "640+128"])
def test_the_append_moves_slabs_for_v5e_and_copies_no_pool(one_chip, rows, layers, pool, widths):
    """``paged_kv_append`` at the accepted pools' row widths, the pools donated
    as the decode step donates its state: ONE custom call whose VMEM scratch is
    ``[rows, 16, width]`` a pool (a token's packed tile of bfloat16, not the
    page), a dynamic 16-row slice of the pool's second-minor dimension that
    Mosaic takes where it lies — no operation but the call yields an array of
    a pool's shape, nothing is temporary, both pools are aliased."""
    from finchat_tpu.ops.kv_append import paged_kv_append
    from tests.paged_walk_cases import pallas_eqn

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    args = (shape((rows, 1, sum(widths))), shape((layers, pool, PAGE, widths[0])),
            shape((layers, pool, PAGE, widths[1])), shape((rows, WIDTH), jnp.int32),
            shape((rows,), jnp.int32), shape((rows,), jnp.int32), shape((1,), jnp.int32))
    step = jax.jit(lambda *a: paged_kv_append(*a, page_size=PAGE), donate_argnums=(1, 2))
    eqn = pallas_eqn(jax.make_jaxpr(step)(*args).jaxpr)
    n_scratch = eqn.params["grid_mapping"].num_scratch_operands
    scratch = [v.aval.shape for v in eqn.params["jaxpr"].invars[-n_scratch:]]
    assert scratch == [(rows, 16, widths[0]), (rows, 16, widths[1]), (2, rows)]
    compiled = step.lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    pools = [f"bf16[{layers},{pool},{PAGE},{w}]" for w in widths]
    makers = [line.split(" = ")[0].strip() for line in text.splitlines()
              if " = " in line and any(f" = {p}" in line or f" = ({p}" in line for p in pools)
              and " parameter(" not in line and " get-tuple-element(" not in line]
    assert len(makers) == 1 and "paged_kv_append" in makers[0], makers
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < MIB
    assert memory.alias_size_in_bytes >= sum(layers * pool * PAGE * w * 2 for w in widths)


@pytest.mark.parametrize("heads, kv_heads, layers, pool, columns, window, pages", [
    (40, 10, 8, 217, 6, 512, 6),  # Phi-4-flash: ONE block a row, 7.5 MiB of buffers
    (32, 4, 4, 649, 18, 2048, 9),  # Trinity-Mini: two blocks a row, 4.5 MiB
], ids=["phi4-flash", "trinity-mini"])
def test_a_window_walk_compiles_for_v5e_with_no_stacked_scratch(one_chip, heads, kv_heads, layers,
                                                                pool, columns, window, pages):
    """A window layer's one-token call at its cell's shape (32 rows): the
    table in the fewest blocks whose double-buffered K and V fit, under the 16
    MiB of scoped VMEM a kernel has without asking; no stacked queries among
    its operands, no second m / l / acc among its scratch — a row's own state,
    the chain's slot word, the two buffers, the semaphores."""
    from tests.paged_walk_cases import pallas_eqn

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pages_ = shape((layers, pool, PAGE, kv_heads * HEAD_DIM))
    args = (shape((32, 1, heads, HEAD_DIM)), pages_, pages_, shape((32, columns), jnp.int32),
            shape((32,), jnp.int32), shape((32,), jnp.int32), shape((1,), jnp.int32),
            (shape((32,), jnp.int32), shape((2,), jnp.int32)))  # the engine's: no page
    step = jax.jit(lambda *a: paged_flash_attention(*a, page_size=PAGE, n_kv=kv_heads,
                                                    window=window))
    eqn = pallas_eqn(jax.make_jaxpr(step)(*args).jaxpr)
    mapping = eqn.params["grid_mapping"]
    assert (mapping.num_index_operands, mapping.num_inputs) == (4, 3)
    scratch = [v.aval.shape for v in eqn.params["jaxpr"].invars[-mapping.num_scratch_operands:]]
    rows = heads  # a row's query heads: whole tiles of 8
    buffer = (2, pages, PAGE, kv_heads * HEAD_DIM)
    assert scratch == [(rows, 128), (rows, 128), (rows, HEAD_DIM), (1,), buffer, buffer, (2, 2)]
    text = step.lower(*args).compile().as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 1 and calls[0].split(" = ")[0].strip().startswith("%paged_flash_attention")
    assert "vmem_limit_bytes" not in calls[0]



# --- kimi-linear-48b-a3b (PR 51): the state step with a decay a key channel, latent pages walked
# --- with no selection, both in one decode step ---

def test_the_state_step_with_a_decay_a_key_channel_compiles_for_v5e_at_kimi_linears_tiles(one_chip):
    """``ops/gdn_step.py``'s second form at ``kimi-linear-report-saturated``'s
    shape: 32 rows of 32 heads of 128 x 128 float32 — a head a tile, 2 MiB a
    row, four rows a block — in layer 4 of 7: ONE custom call, the state
    aliased to its output (no copy of the 470 MB leaf), ``alpha`` arriving as a
    ``[dk, H]`` column beside k and q."""
    from finchat_tpu.ops.gdn_step import gdn_state_step

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    rows, heads, dk, dv = 32, 32, 128, 128
    compiled = gdn_state_step.lower(
        shape((7, rows, heads, dk, dv)), shape((rows, heads, dk)), shape((rows, heads, dk)),
        shape((rows, heads, dv)), shape((rows, heads, dk)), shape((rows, heads)),
        shape((1,), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [line.split(" = ")[0] for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 1 and "gdn_state_step" in calls[0]
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 7 * rows * heads * dk * dv * 4
    assert memory.temp_size_in_bytes < 8 * MIB
    # the scalar form at the same tiles is the program it was: alpha a lane vector
    scalar = gdn_state_step.lower(
        shape((7, rows, heads, dk, dv)), shape((rows, heads, dk)), shape((rows, heads, dk)),
        shape((rows, heads, dv)), shape((rows, heads)), shape((rows, heads)),
        shape((1,), jnp.int32)).compile()
    assert f"f32[{rows},4,{dk},{heads}]" in text and f"f32[{rows},2,{dk},{heads}]" in scalar.as_text()


def test_latent_pages_walk_with_no_selection_for_v5e_at_kimi_linears_shape(one_chip):
    """``decode_attention`` with ``topk`` 0 on a kernel backend at the cell's
    shape: 32 rows, 32 heads of 576 over pages of 640-column latent rows, a
    table of 256 pages (32,768 tokens), a pool two layers deep: every token is
    attended, so there is no indexer and no selection — ONE custom call under
    ``mla_attention`` (``paged_latent_attention``), nothing under
    ``dsa_indexer`` / ``dsa_select``, no gathered copy of the table's rows."""
    from finchat_tpu.ops.latent_attention import LatentShape, decode_attention, decode_form

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    rows, table, pool = 32, 256, 5120
    assert decode_form("pallas", table * PAGE, 0) == "walk"
    compiled = jax.jit(lambda q, pages, keys, layer, page_table, kv_len, live: decode_attention(
        q, None, None, pages, keys, layer, page_table, kv_len, live, page_size=PAGE,
        shape=LatentShape(512, 0, 192 ** -0.5), backend="pallas")).lower(
        shape((rows, 32, 576)), shape((2, pool, PAGE, 640)), shape((2, pool, PAGE, 128)),
        shape((), jnp.int32), shape((rows, table), jnp.int32), shape((rows,), jnp.int32),
        shape((rows,), jnp.bool_)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 1 and "/mla_attention/" in calls[0]
    assert "paged_latent_attention" in calls[0].split(" = ")[0]
    assert "/dsa_indexer/" not in text and "/dsa_select/" not in text and " sort(" not in text
    assert "bf16[32768,640]" not in text and f"bf16[{rows},32768,640]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


def test_kv_append_compiles_for_v5e_with_a_latent_row_and_a_lane_tile_nothing_reads(one_chip):
    """A latent model without an indexer: the pool's second array is ONE lane
    tile (``LlamaConfig.kv_row_widths``: Mosaic refuses a slab of 1 column —
    "must be aligned to tiling (128)" — and a minor dimension of 1 is padded to
    128 lanes in HBM anyway), written by the same ONE custom call."""
    from finchat_tpu.ops.kv_append import paged_kv_append

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    file = _config_file("kimi-linear-48b-a3b")
    from perfbench.models import adapter

    c = adapter(file).program_config(file)
    assert c.kv_row_widths == (640, 128) and not c.index_topk
    compiled = jax.jit(lambda *args: paged_kv_append(*args, page_size=PAGE)).lower(
        shape((32, 1, 640 + 128)), shape((2, 5120, PAGE, 640)), shape((2, 5120, PAGE, 128)),
        shape((32, 256), jnp.int32), shape((32,), jnp.int32), shape((32,), jnp.int32),
        shape((1,), jnp.int32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1024 * 1024


def test_kimi_linear_decode_step_compiles_for_v5e_with_pages_and_state_in_place(one_chip):
    """The whole decode step at the cell's size (1 dense KDA layer + 8 routed
    layers, 32 slots, 32 of 256 experts held, a table of 256 pages): the state
    step in its second form once for the leading layer outside the scan and
    three times in its body, ONE latent walk and ONE append in the body's
    latent layer, the touched-expert pass four times; the latent pool AND the
    recurrent state updated in place; temporaries under a tenth of a GB."""
    file = _config_file("kimi-linear-48b-a3b")
    compiled, state = _compiled_decode_step(one_chip, file)
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line and " custom-call(" in line]

    def count(kernel, inside_scan=None):
        return sum(kernel in c.split(" = ")[0] and (inside_scan is None
                                                    or ("/while/body/" in c) == inside_scan)
                   for c in calls)

    assert (count("gdn_state_step", False), count("gdn_state_step", True)) == (1, 3)
    assert (count("paged_latent_attention", False), count("paged_latent_attention", True)) == (0, 1)
    assert count("paged_kv_append") == 1 and count("moe_experts_step") == 4
    assert all("/gdn_scan/" in c for c in calls if "gdn_state_step" in c.split(" = ")[0])
    assert "/gdn_gate/" in text and "/dsa_indexer/" not in text and "/swa_attention/" not in text
    assert state.ssm_state.shape == (7, 32, 32, 128, 128) and state.k_pages.shape == (2, 5120, 128, 640)
    memory = compiled.memory_analysis()
    in_place = sum(x.size * x.dtype.itemsize for x in (state.k_pages, state.v_pages, state.ssm_state))
    assert memory.alias_size_in_bytes >= in_place and memory.temp_size_in_bytes < 0.1e9


# --- joyai-llm-flash (PR 55): a model that drafts — the width-2 latent walk, the module's step

def test_the_width_2_latent_walk_compiles_for_v5e_at_joyais_shape(one_chip):
    """``pair_attention`` on a kernel backend at the cell's shape: 32 rows of
    a token and its draft, 2 x 32 heads of 576 over pages of 640-column latent
    rows, a table of 256 pages, a pool six layers deep (the trunk's five and
    the module's block): ONE custom call under ``mla_attention`` — the walk at
    64 query rows a latent row, its log-sum-exp a lane tile behind the values
    of its ONE float32 output (a tuple would not read as a custom call in a
    capture) — and
    the second token's own row joined by a handful of small operations under
    the same scope; no gathered copy of the table's rows, no second walk."""
    from finchat_tpu.ops.latent_attention import LatentShape, rows_attention

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    rows, table, pool = 32, 256, 5120
    compiled = jax.jit(lambda q, own, pages, keys, layer, page_table, start, n: rows_attention(
        q, None, None, pages, keys, layer, page_table, start, n, page_size=PAGE,
        shape=LatentShape(512, 0, 192 ** -0.5, 1), backend="pallas", own=own)).lower(
        shape((rows, 2, 32, 576)), shape((rows, 2, 640)), shape((6, pool, PAGE, 640)),
        shape((6, pool, PAGE, 128)), shape((), jnp.int32), shape((rows, table), jnp.int32),
        shape((rows,), jnp.int32), shape((rows,), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 1 and "/mla_attention/" in calls[0]
    assert "paged_latent_attention" in calls[0].split(" = ")[0]
    produced = calls[0].split(" = ")[1]
    assert produced.startswith("f32[32,64,640]") and not produced.startswith("(")
    assert "bf16[32768,640]" not in text and f"bf16[{rows},32768,640]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


def _latent_walk(one_chip, rows, heads, table, pool, layers, with_lse):
    """``paged_latent_attention`` at a cell's shape: the kernel's VMEM and SMEM
    scratch by shape (read off the traced call), and its compile for a
    described v5e (which is what refuses a call over ``LATENT_VMEM_BYTES``)."""
    from finchat_tpu.ops import paged_attention as pa

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def call(q, pages, keep, page_table, kv_len, layer):
        return pa.paged_latent_attention(q, pages, keep, page_table, kv_len, layer, page_size=PAGE,
                                         value_width=512, scale=192 ** -0.5, with_lse=with_lse)

    args = (shape((rows, heads, 640)), shape((layers, pool, PAGE, 640)),
            shape((rows, table * PAGE), jnp.bool_), shape((rows, table), jnp.int32),
            shape((rows,), jnp.int32), shape((1,), jnp.int32))
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(call)(*args).jaxpr)
    [eqn] = found
    n_scratch = eqn.params["grid_mapping"].num_scratch_operands
    scratch = [tuple(v.aval.shape) for v in eqn.params["jaxpr"].invars[-n_scratch:]]
    jax.jit(call).lower(*args).compile()
    return scratch


@pytest.mark.parametrize("rows, heads, with_lse", [
    (32, 64, True),  # JoyAI-LLM-Flash: a token and its draft, 2 x 32 heads, the log-sum-exp
    (32, 32, False),  # Kimi-Linear
], ids=["joyai", "kimi-linear"])
def test_the_latent_walk_with_the_head_folded_compiles_for_v5e_inside_its_vmem(
        one_chip, rows, heads, with_lse):
    """The rule says ``folded`` at both shapes (PR 56): the call holds the
    head's 32 pages resident (5 MiB), walks through a ring of
    ``LATENT_RING_SLOTS`` blocks of 8 pages and keeps five SMEM words of ring
    state — beside the stacked rows' state — and the chip's compiler takes it
    under ``LATENT_VMEM_BYTES``."""
    from finchat_tpu.ops import paged_attention as pa

    assert pa.latent_head_form(rows, heads, 640, 512, 2) == "folded"
    scratch = _latent_walk(one_chip, rows, heads, 256, 5120, 6, with_lse)
    assert (32, PAGE, 640) in scratch and (pa.LATENT_RING_SLOTS, 8, PAGE, 640) in scratch
    assert (5,) in scratch and (pa.LATENT_RING_SLOTS + 1, 1) in scratch
    assert (rows * heads, 512) in scratch  # the stacked tiles stay: the MXU's better shape
    import math

    held = sum(math.prod(dims) * (2 if dims[-1] == 640 else 4) for dims in scratch if len(dims) > 1)
    assert held < pa.LATENT_VMEM_BYTES // 2, held  # blocks, the mask and the update's values beside it


def test_deepseeks_latent_walk_keeps_the_stacked_pass_and_its_scratch_for_v5e(one_chip):
    """128 query rows a latent row: the rule says ``stacked``, and the call's
    scratch is the listing it always had — own state, the stacked rows' state,
    the chain's one SMEM word, two slots of 8 pages, two semaphores; no
    resident head, no ring."""
    from finchat_tpu.ops import paged_attention as pa

    assert pa.latent_head_form(ROWS, 128, 640, 512, 2) == "stacked"
    assert _latent_walk(one_chip, ROWS, 128, WIDTH, POOL, 5, False) == [
        (128, 128), (128, 128), (128, 512), (2048, 128), (2048, 128), (2048, 512), (1,),
        (2, 8, PAGE, 640), (2, 1)]


def test_joyai_decode_step_compiles_for_v5e_drafting_and_verifying_in_one_program(one_chip):
    """The whole decode step at the cell's size (1 dense + 4 routed layers and
    the module, 32 slots, 64 of 256 experts held, a table of 256 pages): the
    XLA module is still ``jit_decode_step``; the width-2 walk once for the
    leading dense layer, once in the scan's body and once in the module's
    block; two appends (a token and its draft) in each; the touched-expert pass
    at 64 tokens in the body and in the module's block; the module's scopes and
    the verification's by name; the pool updated in place; temporaries and the
    draft state small."""
    file = _config_file("joyai-llm-flash")
    compiled, state = _compiled_decode_step(one_chip, file)
    text = compiled.as_text()
    assert text.startswith("HloModule jit_decode_step")
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line and " custom-call(" in line]

    def count(kernel, where=None):
        return sum(kernel in c.split(" = ")[0] and (where is None or where in c) for c in calls)

    assert count("paged_latent_attention") == 3 and count("paged_kv_append") == 6
    assert count("paged_latent_attention", "/while/body/") == 1
    assert count("paged_latent_attention", "/mtp_block/") == 1
    assert count("moe_experts_step") == 2 and count("moe_experts_step", "/mtp_block/") == 1
    for scope in ("mtp_project", "mtp_block", "mtp_head", "draft_verify", "mla_project",
                  "mla_attention", "moe_experts"):
        assert f"/{scope}/" in text, scope
    assert "/dsa_indexer/" not in text and "/dsa_select/" not in text
    assert state.k_pages.shape == (6, 5120, 128, 640)
    assert state.draft_probs.shape == (32, 129280) and state.mtp_hidden.shape == (32, 2048)
    memory = compiled.memory_analysis()
    in_place = sum(x.size * x.dtype.itemsize for x in (state.k_pages, state.v_pages))
    assert memory.alias_size_in_bytes >= in_place and memory.temp_size_in_bytes < 0.2e9


# --- mimo-v2-flash (PR 57): K and V of two widths, pools of two page widths, a sink ---

def test_mimo_v2_flash_decode_step_compiles_for_v5e_with_pools_of_two_page_widths(one_chip):
    """The whole decode step at the cell's size (1 dense + 6 routed layers, 32
    slots, 16 of 256 experts held): the paged kernel at keys of 192 over values
    of 128 — a head's keys cut as two lane tiles, Mosaic's layout rules held —
    twice under the full layers' own ``paged_attention`` (16 query rows a K/V
    head, the shared-head pass) and five times under ``swa_attention`` over a
    table of 3 columns with the sink block (no ``paged_attention`` inside it);
    the touched-expert pass at ``[4096, 2 x 2048] x 16`` six times; the appends
    in place in BOTH pools, K and V slabs of two widths; no weight copied or
    staged before its matmul; temporaries under a tenth of a GB."""
    file = _config_file("mimo-v2-flash")
    compiled, state = _compiled_decode_step(one_chip, file)
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line and " custom-call(" in line]
    window = [c for c in calls if "/swa_attention/" in c]
    full = [c for c in calls if "/swa_attention/" not in c]

    def count(cs, kernel, inside_scan=None):
        return sum(kernel in c.split(" = ")[0] and (inside_scan is None
                                                    or ("/while/body/" in c) == inside_scan)
                   for c in cs)

    for kernel in ("paged_flash_attention", "paged_kv_append"):  # (outside the scan, inside it)
        assert (count(window, kernel, False), count(window, kernel, True)) == (0, 5), kernel
        assert (count(full, kernel, False), count(full, kernel, True)) == (1, 1), kernel
    assert not [c for c in window if "/paged_attention/" in c]
    assert all("/paged_attention/" in c for c in full if "paged_flash_attention" in c.split(" = ")[0])
    assert count(calls, "moe_experts_step") == 6
    assert state.k_pages.shape == (2, 5120, 128, 768) and state.v_pages.shape == (2, 5120, 128, 512)
    assert state.win_table.shape == (32, 3) and state.win_k_pages.shape == (5, 109, 128, 1536)
    assert state.win_v_pages.shape == (5, 109, 128, 1024)
    memory = compiled.memory_analysis()
    pools = sum(x.size * 2 for x in (state.k_pages, state.v_pages, state.win_k_pages,
                                     state.win_v_pages))
    assert memory.alias_size_in_bytes >= pools and memory.temp_size_in_bytes < 0.1e9
    copies, staged = relayout_probe.weight_relayouts(relayout_probe.operations(text))
    assert not copies and not staged, [o.line() for o in copies + staged]


def test_mimo_v2_flash_ragged_round_compiles_for_v5e_and_fits_beside_the_model(one_chip):
    """``ragged_mixed_step`` at the top bucket (8,192 tokens): the ragged kernel
    at keys of 192 over values of 128 under ``swa_attention`` (with the sink)
    and under the full layers' ``ragged_paged_attention``; arguments and
    temporaries together 13.3 GB of the chip's 15.75 (the configuration's
    ``memory.compiled``; with the whole vocabulary 14.51: ISSUE 57's fallback)."""
    from finchat_tpu.engine import engine as E
    from finchat_tpu.models.llama import init_params
    from finchat_tpu.utils.config import EngineConfig
    from perfbench.models import adapter

    file = _config_file("mimo-v2-flash")
    c, cfg = adapter(file).program_config(file), EngineConfig(**file["engine"])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def described(tree):
        return jax.tree.map(lambda x: shape(x.shape, x.dtype), tree)

    params = described(jax.eval_shape(lambda: init_params(c, jax.random.key(0))))
    state = described(jax.eval_shape(lambda: E.create_state(c, cfg, cfg.max_seq_len // PAGE)))
    T, B = 8192, cfg.max_seqs
    compiled = E.ragged_mixed_step.lower(
        params, state, shape((T,), jnp.int32), shape((T,), jnp.int32), shape((B,), jnp.int32),
        shape((B,), jnp.int32), shape((B,), jnp.int32), shape((B,), bool), shape((B,), bool),
        shape((B,), jnp.int32), shape((B,), jnp.float32), shape((B,), jnp.float32),
        shape((B,), jnp.int32), config=c, page_size=PAGE, attn_backend="pallas",
        qm_backend="ref", spec_width=0).compile()
    text = compiled.as_text()
    walks = [line for line in text.splitlines()
             if "ragged_flash_attention" in line.split(" = ")[0] and " custom-call(" in line]
    assert [w for w in walks if "/swa_attention/" in w] and [
        w for w in walks if "/swa_attention/" not in w and "/ragged_paged_attention/" in w]
    assert "ragged-dot" in text or "ragged_dot" in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1.9e9
    assert 13.0e9 < memory.argument_size_in_bytes + memory.temp_size_in_bytes < 13.6e9
