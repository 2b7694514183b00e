"""Back-compat layer: the jax-engine sweep as a declarative experiment.

Grid orchestration moved to :mod:`repro.experiments` (one spec -> backend
-> cell store -> artifact pipeline for both engines); this module keeps
the historical entry points alive:

  * ``python -m repro.sweep`` == ``python -m repro.experiments --engine
    jax`` (same flags, scenario axes and the chunked/sharded execution
    knobs included);
  * :func:`sweep_workload_jax` / :func:`sweep_workloads_jax` wrappers that
    build an :class:`repro.experiments.ExperimentSpec` and run it;
  * the :data:`CROSSCHECK_TOLERANCES` re-export (now owned by
    ``repro.experiments.crosscheck``).

CLI::

  PYTHONPATH=src python -m repro.sweep --workload haswell --scale 0.05 \
      --seeds 4 --crosscheck 4 --out artifacts/sweep-haswell-jax.json
  PYTHONPATH=src python -m repro.sweep \
      --workload haswell knl eagle theta --scale 0.02 --seeds 2
  PYTHONPATH=src python -m repro.sweep --workload eagle --scale 1.0 \
      --seeds 10 --chunk-lanes 16 --cache-dir artifacts/sweep_cache
"""
from __future__ import annotations

import sys
from typing import Dict, Optional, Sequence

from repro.core.strategies import (MALLEABLE_STRATEGY_NAMES,
                                   SWEEP_PROPORTIONS)
from repro.experiments import ExperimentSpec, run_experiment
from repro.experiments.crosscheck import CROSSCHECK_TOLERANCES  # noqa: F401 (re-export)

PROPORTIONS = SWEEP_PROPORTIONS
MALLEABLE_STRATEGIES = MALLEABLE_STRATEGY_NAMES

# Shown by ``python -m repro.sweep --help`` below the shared flag listing.
_CLI_EPILOG = """\
chunked / sharded execution (jax engine):
  --chunk-lanes N (alias --max-lane-width) caps how many grid lanes are
  device-resident at once: the batch streams as sequential chunks, and
  every completed chunk's cells are flushed to --cache-dir before the next
  chunk starts, so an interrupted paper-scale run resumes chunk-by-chunk
  (re-run the same command; --expect-cached asserts a finished grid).
  --devices N lane-shards each chunk across N local devices (0 = all).
  Both knobs are results-neutral and never part of a spec fingerprint:
  chunked/sharded cells are bit-identical to the monolithic batch.
  Sizing guidance and paper-scale commands: docs/paper-scale.md.
"""


def sweep_workloads_jax(
    names: Sequence[str],
    *,
    scale: float = 0.2,
    seeds: int = 3,
    proportions: Sequence[float] = PROPORTIONS,
    strategies: Sequence[str] = MALLEABLE_STRATEGIES,
    trace_seed: int = 0,
    crosscheck: int = 0,
    crosscheck_seed: int = 0,
    cache_dir: Optional[str] = None,
    window_slots: int = 0,
    chunk: int = 160,
    chunk_lanes: int = 0,
    devices: int = 0,
    expand_backend: str = "bisect",
    verbose: bool = True,
) -> Dict[str, Dict]:
    """Batched-engine sweep over one or more workloads.

    Historical wrapper kept for callers of the pre-experiment-layer API:
    it builds an :class:`repro.experiments.ExperimentSpec` (engine
    ``jax``) and delegates to :func:`repro.experiments.run_experiment` —
    new code should do that directly.  ``window_slots``, ``chunk``,
    ``chunk_lanes`` and ``devices`` are results-neutral execution knobs
    passed through as backend options (never spec fields).  Returns
    ``{workload: results}`` in the shared artifact schema.
    """
    spec = ExperimentSpec(
        workloads=tuple(names), scale=scale, trace_seed=trace_seed,
        seeds=seeds, proportions=tuple(proportions),
        strategies=tuple(strategies), engine="jax")
    return run_experiment(
        spec, cache_dir=cache_dir,
        backend_options={"window": window_slots, "chunk": chunk,
                         "chunk_lanes": chunk_lanes, "devices": devices,
                         "expand_backend": expand_backend},
        crosscheck=crosscheck, crosscheck_seed=crosscheck_seed,
        verbose=verbose)


def sweep_workload_jax(name: str, **kw) -> Dict:
    """Single-workload wrapper around :func:`sweep_workloads_jax`.

    Kept for ``benchmarks.sweep --engine jax`` era callers; like its
    plural sibling it is a thin shim over the declarative experiment
    layer (:mod:`repro.experiments`) with the engine pinned to ``jax``.
    """
    return sweep_workloads_jax([name], **kw)[name]


def main(argv=None) -> int:
    """Delegate to the canonical experiment CLI with the jax engine.

    The flags are exactly ``python -m repro.experiments``'s (scenario
    axes, crosscheck gates, chunking knobs); only the prog name and the
    chunked-execution epilogue differ.
    """
    from repro.experiments.__main__ import main as experiments_main
    argv = list(sys.argv[1:] if argv is None else argv)
    return experiments_main(["--engine", "jax"] + argv,
                            prog="python -m repro.sweep",
                            epilog=_CLI_EPILOG)


if __name__ == "__main__":
    raise SystemExit(main())
