"""Production mesh + per-arch parallelism policy.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state.  Target: TPU v5e pods —
one pod = a 16x16 (256-chip) mesh with axes (data, model); two pods add a
leading "pod" axis that data-parallelism spans (DP = pod x data).

``make_lane_mesh`` is the 1-D counterpart used by the sweep engine's
sharded execution layer (:mod:`repro.sweep.shard`): lanes of a batched
sweep are embarrassingly parallel, so a flat device list partitioned
along one ``"lanes"`` axis is the whole story.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax


def make_lane_mesh(devices: Optional[Sequence] = None):
    """1-D mesh over ``devices`` (default: all local) with axis ``lanes``.

    Used with ``NamedSharding(mesh, PartitionSpec("lanes"))`` to split the
    lane-leading arrays of a :class:`repro.sweep.batch.BatchedLanes` batch
    across devices; every per-lane computation then runs device-parallel
    under GSPMD with no cross-device traffic on the hot path (the only
    cross-lane reductions are scalar control-flow peeks).
    """
    import numpy as _np
    devs = list(jax.devices() if devices is None else devices)
    if not devs:
        raise ValueError("lane mesh needs at least one device")
    return jax.sharding.Mesh(_np.array(devs), ("lanes",))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as _np
    n = int(_np.prod(shape))
    devices = jax.devices()[:n]  # dry-run forces 512; single-pod uses 256
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)}; "
            "the dry-run must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before importing jax")
    # Auto axes: the model code places activations with
    # with_sharding_constraint, which make_mesh's default Explicit axes
    # reject
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_size(mesh) -> int:
    out = 1
    for a in dp_axes(mesh):
        out *= mesh.shape[a]
    return out


@dataclasses.dataclass(frozen=True)
class ParallelPolicy:
    """Per-arch distribution knobs (the §Perf hillclimb operates on these)."""

    fsdp: bool = False        # ZeRO-3 weight sharding over dp axes
    zero1: bool = True        # optimizer moments sharded over dp (ZeRO-1)
    remat: str = "dots"       # none | dots | full
    accum_steps: int = 1      # gradient accumulation microbatches
    param_dtype: str = "float32"  # bf16 + f32 master for the big archs


# Archs whose f32 params + moments exceed a v5e-256 pod without weight
# sharding; they default to FSDP + bf16 params.
_BIG = {"qwen2-72b", "deepseek-v2-236b"}
# Small archs have HBM headroom at train_4k: skip activation checkpointing
# (remat recompute cost ~20% FLOPs for zero capacity benefit; §Perf C3).
_SMALL = {"olmoe-1b-7b", "stablelm-1.6b", "mamba2-1.3b", "internvl2-2b",
          "zamba2-2.7b"}


def default_policy(arch: str) -> ParallelPolicy:
    if arch in _BIG:
        return ParallelPolicy(fsdp=True, param_dtype="bfloat16")
    if arch in _SMALL:
        return ParallelPolicy(remat="none")
    return ParallelPolicy()
