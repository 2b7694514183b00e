# Batched device-resident sweep engine: the paper's (strategy x proportion
# x seed) grid evaluated as fixed-shape batched lanes on one device.
#
# - batch:       event-stepped, active-set-windowed batched simulator
# - shard:       chunked, resumable, multi-device execution plans over the
#                batch's lane axis (results-neutral by construction)
# - metrics_jax: on-device port of repro.core.metrics.run_metrics
# - cache:       engine-agnostic content-hash cell store (shared with the
#                DES backend of repro.experiments)
#
# Exports resolve lazily (PEP 562) so jax-free consumers — the cell store,
# the DES experiment backend — can import from this package without paying
# the jax import.
from typing import TYPE_CHECKING

_EXPORTS = {
    "BatchedLanes": "batch", "EngineConfig": "batch",
    "SweepEngineError": "batch", "build_lanes": "batch",
    "concat_lanes": "batch", "simulate_lanes": "batch",
    "lane_statics": "batch", "pad_lanes": "batch", "take_lanes": "batch",
    "ChunkResult": "shard", "ShardConfig": "shard",
    "chunk_plan": "shard", "describe_plan": "shard",
    "simulate_lanes_chunked": "shard",
    "SweepCache": "cache", "cell_fingerprint": "cache",
    "engine_version": "cache",
    "batched_metrics": "metrics_jax",
}

__all__ = sorted(_EXPORTS)

if TYPE_CHECKING:  # pragma: no cover
    from .batch import (BatchedLanes, EngineConfig, SweepEngineError,
                        build_lanes, concat_lanes, lane_statics, pad_lanes,
                        simulate_lanes, take_lanes)
    from .cache import SweepCache, cell_fingerprint, engine_version
    from .metrics_jax import batched_metrics
    from .shard import (ChunkResult, ShardConfig, chunk_plan, describe_plan,
                        simulate_lanes_chunked)


def __dir__():
    return sorted(set(globals()) | set(__all__))


def __getattr__(name):
    try:
        module = _EXPORTS[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}") from None
    import importlib
    return getattr(importlib.import_module(f".{module}", __name__), name)
