"""Which weights a step program copies or stages before it multiplies by them,
read off the program compiled for a v5e that is described, not attached (no
chip: the TPU's compiler is installed beside the CPU backend; 3-15 s a
configuration at its cell's shapes).

    JAX_PLATFORMS=cpu python3 benchmarks/relayout_probe.py mistral-7b-v0.3
    JAX_PLATFORMS=cpu python3 benchmarks/relayout_probe.py phi-4-mini-flash-reasoning \
        --tree <checkout> --min-elements 2000000

lists every operation of ``perfbench/configs/<name>.json``'s ``decode_step``
whose result is a bfloat16 array of at least ``--min-elements`` elements and
that is (a) a ``copy`` — a re-layout, fused or not — or (b) a fusion that ends
in a ``dynamic-slice`` and leaves its result in on-chip memory (``S(1)`` in the
result's layout): a layer's weight taken out of its stack before the matmul can
start. One line each: operation, dtype, shape, memory space, ``op_name``. What
PR 45 found with it: a projection whose product is split into heads and rotated
has its weight staged AND transposed (XLA's layout assignment carries the heads'
layout back through the dot), ``attn_q`` and ``attn_k`` in five of the seven
configurations; ``models/quant.py`` ``flat_fence`` fences the product, and the
listing is empty (``tests/test_tpu_compile.py`` holds it so). ``--tree`` lists
another checkout's program (the parent unpacked under the repo). Nothing runs,
so nothing here is a time: that comes from the chip (PERF.md section 5).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
from pathlib import Path
from typing import Any, NamedTuple

WEIGHT_ELEMENTS_MIN = 2_000_000  # Falcon-H1's attn_k, the smallest weight at stake, has 2.6 M

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>[\w.-]+) = (?P<dtype>[a-z]+\d*)\[(?P<dims>[\d,]*)\]"
    r"(?:\{(?P<layout>[^}]*)\})? (?P<op>[\w-]+)\((?P<operands>[^)]*)\)(?P<rest>.*)$")


class Operation(NamedTuple):
    name: str
    op: str  # the HLO opcode: copy, fusion, dot, ...
    dtype: str
    shape: tuple[int, ...]
    space: str  # "S(1)" on-chip memory, "" HBM
    op_name: str  # the jax scope path of the metadata
    operands: tuple[str, ...]
    calls: str  # the fused computation, of a fusion
    fused_in: str  # the computation that holds it

    @property
    def elements(self) -> int:
        return math.prod(self.shape)

    def line(self) -> str:
        where = f" in {self.fused_in}" if self.fused_in.startswith("fused") else ""
        return (f"{self.name}{where}  {self.op}  {self.dtype}[{','.join(map(str, self.shape))}]  "
                f"{self.space or 'HBM'}  {self.op_name}")


def operations(text: str) -> list[Operation]:
    """Every instruction of a compiled program's text, with the computation
    that holds it."""
    found, computation = [], ""
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            computation = line.split(" ")[1 if line.startswith("ENTRY") else 0].lstrip("%")
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest, layout = m["rest"], m["layout"] or ""
        op_name = re.search(r'op_name="([^"]*)"', rest)
        calls = re.search(r"calls=%([\w.-]+)", rest)
        found.append(Operation(
            m["name"], m["op"], m["dtype"],
            tuple(int(d) for d in m["dims"].split(",") if d),
            "S(1)" if "S(1)" in layout else "", op_name[1] if op_name else "",
            tuple(o.split()[-1].lstrip("%") for o in m["operands"].split(",") if o.strip()),
            calls[1] if calls else "", computation))
    return found


def weight_relayouts(ops: list[Operation], min_elements: int = WEIGHT_ELEMENTS_MIN
                     ) -> tuple[list[Operation], list[Operation]]:
    """``(copies, staged)`` of bfloat16 results of at least ``min_elements``:
    every ``copy``, fused or not, and every fusion that ends in a
    ``dynamic-slice`` and leaves its result in on-chip memory."""
    roots = {o.fused_in: o for o in ops}  # a computation's last instruction is its ROOT
    big = [o for o in ops if o.dtype == "bf16" and o.elements >= min_elements]
    copies = [o for o in big if o.op == "copy"]
    staged = [o for o in big if o.op == "fusion" and o.space == "S(1)"
              and ("dynamic-slice" in o.name
                   or getattr(roots.get(o.calls), "op", "") == "dynamic-slice")]
    return copies, staged


def reads_whole_stack(ops: list[Operation], scope: str, stack: tuple[int, ...]) -> list[Operation]:
    """The fusions under ``scope`` with an operand of bfloat16 ``stack``: the
    matmul that reads a layer's weight where it lies."""
    shapes = {o.name: (o.dtype, o.shape) for o in ops}
    return [o for o in ops if o.op == "fusion" and f"/{scope}/" in o.op_name
            and any(shapes.get(x) == ("bf16", stack) for x in o.operands)]


def config_file(name: str, tree: str | Path | None = None) -> dict[str, Any]:
    root = Path(tree) if tree else Path(__file__).resolve().parents[1]
    return json.loads((root / f"perfbench/configs/{name}.json").read_text())


def compiled_decode_step(file: dict[str, Any], sharding: Any) -> tuple[Any, Any]:
    """``decode_step`` of the configuration ``file`` compiled for ``sharding``'s
    described chip at the file's engine options, from shapes alone; returns
    ``(compiled, state shapes)``."""
    import jax
    import jax.numpy as jnp

    from finchat_tpu.engine import engine as E
    from finchat_tpu.models.llama import init_params
    from finchat_tpu.utils.config import EngineConfig
    from perfbench.models import adapter

    c = adapter(file).program_config(file)
    cfg = EngineConfig(**file["engine"])

    def described(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)

    params = described(jax.eval_shape(lambda: init_params(c, jax.random.key(0))))
    state = described(jax.eval_shape(
        lambda: E.create_state(c, cfg, cfg.max_seq_len // cfg.page_size)))

    def row(dtype):
        return jax.ShapeDtypeStruct((cfg.max_seqs,), dtype, sharding=sharding)

    compiled = E.decode_step.lower(
        params, state, row(bool), row(jnp.float32), row(jnp.float32), row(jnp.int32),
        config=c, page_size=cfg.page_size, attn_backend="pallas", qm_backend="ref").compile()
    return compiled, state


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config", help="a file's name under perfbench/configs, without .json")
    ap.add_argument("--tree", default=str(Path(__file__).resolve().parents[1]))
    ap.add_argument("--min-elements", type=int, default=WEIGHT_ELEMENTS_MIN)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, args.tree)

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)  # unreadable without the chip
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    compiled, _state = compiled_decode_step(config_file(args.config, args.tree),
                                            SingleDeviceSharding(topo.devices[0]))
    copies, staged = weight_relayouts(operations(compiled.as_text()), args.min_elements)
    print(f"{args.config} decode_step for a described v5e ({args.tree}): "
          f"{len(copies)} copies, {len(staged)} staged slices of at least "
          f"{args.min_elements} bf16 elements")
    for o in copies + staged:
        print("  " + o.line())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
