"""Engine-agnostic on-disk cell store for experiment grids.

A *cell* is one (trace spec, scenario, scale, strategy, proportion, seed)
simulation on one engine.  Its cache key is the SHA-256 of a canonical-JSON
fingerprint that includes everything that determines the metrics:

  * trace identity: generator name, trace seed, scale;
  * cluster: capacity, tick;
  * cell: strategy name, malleable proportion, transform seed;
  * transform configuration (efficiency thresholds and caps);
  * scenario axes (walltime accuracy, arrival compression, backfill depth
    — see :mod:`repro.core.scenario`);
  * engine identity: ``{des,jax}`` + that engine's version (bumped whenever
    its semantics change, so stale entries can never be replayed as fresh
    results).

Entries are one small JSON file per cell, sharded by the first two key hex
chars.  Both experiment backends (:mod:`repro.experiments.backend_des`,
:mod:`repro.experiments.backend_jax`) write completed cells through this
store as they finish — the jax backend flushes per completed *lane chunk*
(:mod:`repro.sweep.shard`), the DES per cell — so repeated sweeps skip
completed cells, an interrupted sweep resumes where it stopped (at chunk
granularity on the jax engine), and the DES crosscheck reads reference
cells (des-engine fingerprints) an earlier sweep or crosscheck already
paid for.  Execution-plan knobs (chunk width, device count, window sizes)
are never part of a fingerprint: a cell means the same thing however it
was computed.

This module never imports jax: the DES backend stays accelerator-free, and
the jax engine version is resolved lazily from :mod:`repro.sweep.batch`.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import pathlib
from typing import Dict, Optional

from repro import obs
from repro.core.scenario import ScenarioConfig
from repro.core.speedup import TransformConfig

# Version of the reference numpy DES substrate (core/simulator.py).  Bump
# whenever its event/scheduling semantics change so stored DES cells are
# invalidated alongside the jax ENGINE_VERSION mechanism.
# v2: workload-class queue priority (on-demand jobs outrank normal queued
# jobs) and the scenario schema gaining job_classes / walltime_dist.
# v3: data-parameterised strategy registry — pooled / stealing pass
# structures (pref_common_pool, steal_agreement) and the queue-order
# scenario axis (SJF insertion order; rigid_sjf pins it per strategy).
DES_ENGINE_VERSION = 3


def engine_version(engine: str) -> int:
    """Cache-invalidation version of ``engine`` (``des`` or ``jax``)."""
    if engine == "des":
        return DES_ENGINE_VERSION
    if engine == "jax":
        from .batch import ENGINE_VERSION  # lazy: keeps the DES path jax-free
        return ENGINE_VERSION
    raise ValueError(f"unknown engine {engine!r}; choose des or jax")


def cell_fingerprint(workload: str, trace_seed: int, scale: float,
                     capacity: int, tick: float, strategy: str,
                     proportion: float, seed: int, engine: str,
                     config: TransformConfig = TransformConfig(),
                     scenario: ScenarioConfig = ScenarioConfig()) -> Dict:
    """The canonical content of a cell's cache key (JSON-serializable)."""
    return {
        "workload": workload,
        "trace_seed": int(trace_seed),
        "scale": float(scale),
        "capacity": int(capacity),
        "tick": float(tick),
        "strategy": strategy,
        "proportion": float(proportion),
        "seed": int(seed),
        "engine": engine,
        "engine_version": engine_version(engine),
        "transform": dataclasses.asdict(config),
        # canonical form: a dead knob (e.g. walltime_seed at zero jitter)
        # must hash identically to its default
        "scenario": dataclasses.asdict(scenario.canonical()),
    }


class SweepCache:
    """Content-addressed store of per-cell metric dicts."""

    def __init__(self, root) -> None:
        self.root = pathlib.Path(root)
        self.hits = 0
        self.misses = 0

    @staticmethod
    def key(fingerprint: Dict) -> str:
        blob = json.dumps(fingerprint, sort_keys=True,
                          separators=(",", ":")).encode()
        return hashlib.sha256(blob).hexdigest()

    def _path(self, key: str) -> pathlib.Path:
        return self.root / key[:2] / f"{key}.json"

    def has(self, fingerprint: Dict) -> bool:
        """Whether a cell is stored, without reading or counting it
        (resume inspection: "how much of this grid is already paid for")."""
        return self._path(self.key(fingerprint)).exists()

    def get(self, fingerprint: Dict) -> Optional[Dict[str, float]]:
        path = self._path(self.key(fingerprint))
        if not path.exists():
            self.misses += 1
            obs.counter("store.miss")
            return None
        try:
            entry = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError):
            self.misses += 1
            obs.counter("store.miss")
            return None
        self.hits += 1
        obs.counter("store.hit")
        return entry["metrics"]

    def put(self, fingerprint: Dict, metrics: Dict[str, float]) -> None:
        obs.counter("store.put")
        with obs.span("store.put"):
            path = self._path(self.key(fingerprint))
            path.parent.mkdir(parents=True, exist_ok=True)
            tmp = path.with_suffix(".tmp")
            tmp.write_text(json.dumps(
                {"fingerprint": fingerprint, "metrics": metrics}, indent=1,
                default=float))
            tmp.replace(path)
