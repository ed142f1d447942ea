"""Device mesh construction (SURVEY §5.8 model plane).

Axis convention (scaling-book style):
- ``data``   — batch/DP; gradients all-reduce here.
- ``pipe``   — pipeline parallelism; stages exchange activations point-to-
  point via the GPipe-style microbatch scheduler in parallel/pipeline.py
  (layer stack sharded by stage over this axis).
- ``model``  — tensor parallelism; attention heads + MLP hidden sharded.
- ``seq``    — sequence/context parallelism (ring attention rides this).
- ``expert`` — expert parallelism (MoE models; axis exposed, size 1 today).

ICI/DCN note: axis ORDER matters on real slices — ``jax.make_mesh`` puts the
fastest-varying (last) axis on the innermost ICI ring, so ``model`` (the
chattiest: 2 all-reduces/layer) is last; ``data`` (one gradient reduce per
step, DCN-tolerant) is first and lands across slices/hosts; ``pipe`` sits
right after ``data`` (stage hops are infrequent point-to-point sends and
tolerate DCN).

Multi-host: call ``initialize_distributed()`` once per process before
building the mesh; jax then sees the global device set.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
from jax.sharding import AxisType, Mesh

from finchat_tpu.utils.config import MeshConfig
from finchat_tpu.utils.logging import get_logger

logger = get_logger(__name__)

AXES = ("data", "pipe", "seq", "expert", "model")


@dataclass(frozen=True)
class MeshSpec:
    data: int = 1
    pipe: int = 1
    seq: int = 1
    expert: int = 1
    model: int = -1  # -1 = absorb all remaining devices

    @classmethod
    def from_config(cls, cfg: MeshConfig) -> "MeshSpec":
        return cls(data=cfg.data, pipe=cfg.pipe, seq=cfg.seq,
                   expert=cfg.expert, model=cfg.model)

    def resolve(self, n_devices: int) -> tuple[int, ...]:
        sizes = [self.data, self.pipe, self.seq, self.expert, self.model]
        free = [i for i, s in enumerate(sizes) if s == -1]
        fixed = 1
        for s in sizes:
            if s != -1:
                fixed *= s
        if len(free) > 1:
            raise ValueError("at most one mesh axis may be -1")
        if free:
            if n_devices % fixed:
                raise ValueError(f"{n_devices} devices not divisible by fixed axes {fixed}")
            sizes[free[0]] = n_devices // fixed
        total = 1
        for s in sizes:
            total *= s
        if total != n_devices:
            raise ValueError(f"mesh {dict(zip(AXES, sizes))} needs {total} devices, have {n_devices}")
        return tuple(sizes)


def build_mesh(spec: MeshSpec | None = None, devices=None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    spec = spec or MeshSpec()
    sizes = spec.resolve(len(devices))
    # Auto axis types = classic GSPMD propagation (the model code stays
    # sharding-agnostic; XLA infers intermediate shardings + collectives)
    mesh = jax.make_mesh(sizes, AXES, devices=devices,
                         axis_types=(AxisType.Auto,) * len(AXES))
    logger.info("mesh: %s over %d devices", dict(zip(AXES, sizes)), len(devices))
    return mesh


def initialize_distributed(coordinator: str | None = None, num_processes: int | None = None, process_id: int | None = None) -> None:
    """Multi-host init (jax.distributed); call before any backend use on
    every host of a multi-host slice/DCN job."""
    kwargs = {}
    if coordinator:
        kwargs = dict(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    jax.distributed.initialize(**kwargs)
    logger.info(
        "distributed initialized: process %d/%d, %d local / %d global devices",
        jax.process_index(), jax.process_count(),
        jax.local_device_count(), jax.device_count(),
    )
