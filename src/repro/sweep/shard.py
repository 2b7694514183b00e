"""Sharded, chunked execution for the batched sweep engine.

:func:`repro.sweep.batch.simulate_lanes` materialises every lane of a grid
in one device-resident batch, which is what makes paper-scale runs (eagle
at scale 1.0 is 143k jobs x 81 cells) need "a beefier box".  This module
turns that into a *plan*: the lane axis is partitioned into fixed-width
chunks, and each chunk is

1. **streamed sequentially** on memory-bounded (single-device / CPU)
   boxes — the ``chunk_lanes`` budget caps how many lanes are resident at
   once, and every completed chunk is handed back to the caller *before*
   the next one starts, so the experiment backend can flush its cells into
   the engine-agnostic store (:mod:`repro.sweep.cache`) and an interrupted
   paper-scale run resumes chunk-by-chunk instead of all-or-nothing;

2. **lane-sharded across local devices** — chunk arrays are placed with a
   ``NamedSharding`` over a 1-D ``"lanes"`` device mesh
   (:func:`repro.launch.mesh.make_lane_mesh`), so GSPMD partitions every
   per-lane computation across the mesh with no cross-device traffic on
   the hot path (lanes are embarrassingly parallel; the only cross-lane
   reductions are scalar control-flow peeks).

Both are *execution* choices, never *experiment* choices: per-lane results
are independent of batch composition (padding lanes repeat an existing
lane, so every batch-level static — priority bounds, class gating, depth
cutoff — is unchanged), hence chunked/sharded runs are **bit-identical**
to the monolithic batch (``tests/test_shard.py``), and none of these knobs
may enter a spec or cell fingerprint (see
``src/repro/experiments/README.md``, "Execution knobs vs. the spec
fingerprint").

Every chunk in a plan executes at the same padded lane width, so chunks
share XLA compilations (one per engine structure and adaptive window
size) regardless of how many chunks the grid splits into.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterator, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.core.jobs import DONE

from .batch import (BatchedLanes, EngineConfig, lane_statics, pad_lanes,
                    simulate_lanes, take_lanes)


@dataclasses.dataclass(frozen=True)
class ShardConfig:
    """Results-neutral execution plan for one batched sweep.

    ``chunk_lanes``: the lane-width budget — at most this many lanes are
    device-resident at once (0 = the whole batch as one chunk, today's
    monolithic behaviour).  ``devices``: how many local devices to
    lane-shard each chunk across (0 = all local devices, 1 = no sharding).
    Neither knob can change any cell's result, so neither is ever part of
    a spec or cell fingerprint.
    """

    chunk_lanes: int = 0
    devices: int = 0

    def __post_init__(self) -> None:
        if self.chunk_lanes < 0:
            raise ValueError("chunk_lanes must be >= 0 (0 = unbounded)")
        if self.devices < 0:
            raise ValueError("devices must be >= 0 (0 = all local devices)")


class ChunkResult(NamedTuple):
    """One executed lane chunk of a :func:`simulate_lanes_chunked` stream.

    ``results`` is the :func:`repro.sweep.batch.simulate_lanes` dict sliced
    back to the chunk's real lanes ``[lo, hi)`` (padding rows dropped);
    ``lane_width`` is the padded width the chunk actually executed at (the
    peak device-resident lane count), ``wall_s`` its wall-clock.
    """

    lo: int
    hi: int
    results: Dict[str, np.ndarray]
    wall_s: float
    lane_width: int
    n_devices: int


def resolve_devices(n_devices: int) -> List:
    """The local devices a plan runs on (``n_devices=0`` = all of them)."""
    import jax

    devs = list(jax.devices())
    if n_devices == 0:
        return devs
    if n_devices > len(devs):
        raise ValueError(
            f"plan wants {n_devices} devices but only {len(devs)} are "
            "local (on CPU, XLA_FLAGS=--xla_force_host_platform_device_"
            "count=N forces N host devices)")
    return devs[:n_devices]


def chunk_plan(n_lanes: int, chunk_lanes: int,
               n_devices: int = 1) -> Tuple[int, List[Tuple[int, int]]]:
    """Partition ``n_lanes`` into ``[lo, hi)`` ranges plus their width.

    The executed width is the lane budget rounded **up** to a multiple of
    ``n_devices`` (a sharded chunk must split evenly over the mesh) and is
    identical for every chunk — short final chunks are padded up to it —
    so the whole stream reuses a single compilation per engine structure.
    """
    if n_lanes < 1:
        raise ValueError("a plan needs at least one lane")
    n_devices = max(1, n_devices)
    budget = chunk_lanes if chunk_lanes > 0 else n_lanes
    budget = min(budget, n_lanes)
    width = -(-budget // n_devices) * n_devices
    ranges = [(lo, min(lo + width, n_lanes))
              for lo in range(0, n_lanes, width)]
    return width, ranges


def lane_sharding(devices: Sequence):
    """``NamedSharding`` splitting lane-leading arrays over ``devices``."""
    import jax

    from repro.launch.mesh import make_lane_mesh

    return jax.sharding.NamedSharding(make_lane_mesh(devices),
                                      jax.sharding.PartitionSpec("lanes"))


def shard_lanes(batch: BatchedLanes, sharding) -> BatchedLanes:
    """Place every field of a lane batch with ``sharding`` (axis 0)."""
    import jax

    return BatchedLanes(*[jax.device_put(getattr(batch, name), sharding)
                          for name in BatchedLanes._fields])


def simulate_lanes_chunked(
    batch: BatchedLanes,
    cfg: EngineConfig,
    shard: ShardConfig = ShardConfig(),
    verbose: bool = False,
) -> Iterator[ChunkResult]:
    """Run ``batch`` as a stream of lane chunks; yield each as it finishes.

    With the default plan (``chunk_lanes=0`` on a single device) this is
    exactly one chunk covering the whole batch — the monolithic
    :func:`simulate_lanes` path.  Chunks execute in lane order; a consumer
    that persists each yielded chunk's cells before pulling the next one
    gets chunk-granular resume for free (the experiment backend does —
    :mod:`repro.experiments.backend_jax`).
    """
    devices = resolve_devices(shard.devices)
    width, ranges = chunk_plan(batch.n_lanes, shard.chunk_lanes,
                               len(devices))
    sharding = lane_sharding(devices) if len(devices) > 1 else None
    # compile parameters come from the FULL batch: every chunk shares one
    # compilation, and chunk composition cannot perturb any pass (the
    # balanced level bisection's iteration count follows span_max)
    with obs.span("sweep.statics", lanes=batch.n_lanes):
        statics = lane_statics(batch)
    for lo, hi in ranges:
        sub = pad_lanes(take_lanes(batch, lo, hi), width)
        if sharding is not None:
            sub = shard_lanes(sub, sharding)
        if verbose and (len(ranges) > 1 or sharding is not None):
            print(f"[sweep.shard] lanes [{lo}, {hi}) of {batch.n_lanes} "
                  f"at width {width} on {len(devices)} device(s)")
        t0 = time.monotonic()
        # the chunk span wraps the whole simulate_lanes call; the engine
        # emits nested sweep.compile / sweep.execute spans per window
        # chunk, so a trace shows the compile-vs-execute split per chunk
        with obs.span("sweep.chunk", lo=lo, hi=hi, width=width,
                      devices=len(devices)):
            res = simulate_lanes(sub, cfg, verbose=verbose, statics=statics)
        wall = time.monotonic() - t0
        m = hi - lo
        out = {k: (v[:m] if isinstance(v, np.ndarray) and v.ndim >= 1
                   and v.shape[0] == width else v)
               for k, v in res.items()}
        out["finished"] = bool(np.all(out["state"] == DONE))
        yield ChunkResult(lo, hi, out, wall, width, len(devices))


def describe_plan(n_lanes: int, shard: ShardConfig,
                  n_devices: Optional[int] = None) -> Dict[str, int]:
    """Plan summary (chunk count / width / devices) for logs and timing
    artifacts, without touching device state when ``n_devices`` is given."""
    if n_devices is None:
        n_devices = len(resolve_devices(shard.devices))
    width, ranges = chunk_plan(n_lanes, shard.chunk_lanes, n_devices)
    return {"n_lanes": n_lanes, "chunks": len(ranges),
            "lane_width": width, "devices": n_devices}
