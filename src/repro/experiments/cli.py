"""Shared argparse wiring: CLI flags <-> :class:`ExperimentSpec`.

Every grid CLI (``python -m repro.experiments``, ``python -m
benchmarks.sweep``, ``python -m benchmarks.run``, ``python -m repro.serve``)
builds its spec through these helpers, so the scenario axes and engine
choice are uniformly sweepable and no entry point grows a private grid
dialect.
"""
from __future__ import annotations

import argparse

from repro.core import CLUSTERS
from repro.core.scenario import (DEFAULT_BACKFILL_DEPTH,
                                 DEFAULT_WALLTIME_SEED, WALLTIME_DISTS,
                                 JobClasses, ScenarioConfig)
from repro.core.strategies import (MALLEABLE_STRATEGY_NAMES,
                                   SWEEP_PROPORTIONS,
                                   registered_strategy_names)

from .spec import ENGINES, ExperimentSpec


def add_spec_arguments(ap: argparse.ArgumentParser, *,
                       default_engine: str = "des",
                       default_scale: float = 0.2,
                       default_seeds: int = 3,
                       single_workload: bool = False) -> None:
    """Flags that define the experiment (everything in the fingerprint)."""
    if single_workload:
        ap.add_argument("--workload", required=True,
                        choices=sorted(CLUSTERS))
    else:
        ap.add_argument("--workload", required=True, nargs="+",
                        choices=sorted(CLUSTERS),
                        help="one workload, or several to run as one "
                             "experiment (the jax engine batches them "
                             "under a single compilation)")
    ap.add_argument("--scale", type=float, default=default_scale,
                    help="trace scale (1.0 = paper-size workloads)")
    ap.add_argument("--trace-seed", type=int, default=0,
                    help="trace-generator seed")
    ap.add_argument("--seeds", type=int, default=default_seeds,
                    help="transform seeds per (strategy, proportion)")
    ap.add_argument("--proportions", type=float, nargs="*",
                    default=list(SWEEP_PROPORTIONS))
    # choices follow the registry (ported ElastiSim policies included);
    # the *default* stays pinned to the explicit paper subset so
    # registering a strategy never silently changes the default grid
    ap.add_argument("--strategies", nargs="*",
                    default=list(MALLEABLE_STRATEGY_NAMES),
                    choices=list(registered_strategy_names(
                        sweepable_only=True)))
    ap.add_argument("--engine", choices=list(ENGINES),
                    default=default_engine,
                    help="des: reference numpy DES (cell-parallel); "
                         "jax: batched device-resident engine")
    add_scenario_arguments(ap)


def add_scenario_arguments(ap: argparse.ArgumentParser) -> None:
    """The scenario axes (see repro/core/scenario.py), one flag each.

    Kept separate so CLIs with their own grid flags (``benchmarks/run.py``)
    still expose every axis — a spec fingerprint covers the full
    :class:`ScenarioConfig`, so a CLI that hard-defaulted an axis could
    never reuse artifacts computed with it."""
    ap.add_argument("--walltime-factor", type=float, default=1.0,
                    help="scales walltime slack: 0 = exact estimates, "
                         "1 = the trace's padding, 4 = 4x padding")
    ap.add_argument("--walltime-jitter", type=float, default=0.0,
                    help="per-job spread of walltime slack (heterogeneous "
                         "estimate accuracy; 0 = uniform; distribution "
                         "set by --walltime-dist)")
    ap.add_argument("--walltime-dist", choices=list(WALLTIME_DISTS),
                    default="lognormal",
                    help="named per-job walltime-accuracy distribution "
                         "the jitter draws from")
    ap.add_argument("--walltime-seed", type=int,
                    default=DEFAULT_WALLTIME_SEED,
                    help="spec-seeded RNG for the jitter draw (part of "
                         "the scenario's identity)")
    ap.add_argument("--arrival-compression", type=float, default=1.0,
                    help="divides submission times: 2.0 doubles the "
                         "arrival rate at a fixed work mix")
    ap.add_argument("--backfill-depth", type=int,
                    default=DEFAULT_BACKFILL_DEPTH,
                    help="EASY backfill scan depth, honoured by every "
                         "engine (the policy core bounds the scan itself)")
    ap.add_argument("--queue-order", choices=["fcfs", "sjf"],
                    default="fcfs",
                    help="waiting-queue scan order: fcfs (default) or "
                         "sjf keyed on walltime estimates (composes with "
                         "the walltime-accuracy axes; strategies that pin "
                         "an order, e.g. rigid_sjf, override this)")
    ap.add_argument("--rigid-frac", type=float, default=0.0,
                    help="job-class mix: fraction pinned rigid (never "
                         "transformed, normal queue rank)")
    ap.add_argument("--on-demand-frac", type=float, default=0.0,
                    help="job-class mix: fraction on-demand (pinned rigid "
                         "+ queue priority, Fan & Lan)")
    ap.add_argument("--class-seed", type=int, default=0,
                    help="job-class assignment permutation seed")


def scenario_from_args(args: argparse.Namespace) -> ScenarioConfig:
    return ScenarioConfig(
        walltime_factor=args.walltime_factor,
        walltime_jitter=args.walltime_jitter,
        walltime_dist=args.walltime_dist,
        walltime_seed=args.walltime_seed,
        arrival_compression=args.arrival_compression,
        backfill_depth=args.backfill_depth,
        queue_order=getattr(args, "queue_order", "fcfs"),
        job_classes=JobClasses(
            rigid=args.rigid_frac,
            on_demand=args.on_demand_frac,
            malleable=1.0 - args.rigid_frac - args.on_demand_frac,
            seed=args.class_seed),
    )


def spec_from_args(args: argparse.Namespace) -> ExperimentSpec:
    workloads = args.workload
    if isinstance(workloads, str):
        workloads = [workloads]
    return ExperimentSpec(
        workloads=tuple(workloads),
        scale=args.scale,
        trace_seed=args.trace_seed,
        seeds=args.seeds,
        proportions=tuple(args.proportions),
        strategies=tuple(args.strategies),
        engine=args.engine,
        scenario=scenario_from_args(args),
    )


def add_execution_arguments(ap: argparse.ArgumentParser) -> None:
    """[jax] engine execution knobs — results-neutral by construction
    (chunked/sharded cells are bit-identical to the monolithic batch;
    ``tests/test_shard.py``), so none of them ever enters a spec or cell
    fingerprint.  Shared by every jax-capable grid CLI, including
    ``benchmarks/run.py`` which manages its own cache/worker flags.  A flag
    left out is absent from the parsed namespace, so the engine's own
    default holds (:func:`repro.experiments.backend_jax.engine_configs`)."""
    ap.add_argument("--window", type=int, default=argparse.SUPPRESS,
                    help="[jax] active-set window ladder floor (0, the "
                         "default: start at the statics-predicted bucket)")
    ap.add_argument("--chunk", type=int, default=argparse.SUPPRESS,
                    help="[jax] scan steps between window compactions")
    ap.add_argument("--chunk-lanes", type=int, default=argparse.SUPPRESS,
                    metavar="N",
                    help="[jax] max device-resident lanes per chunk; the "
                         "batch streams as sequential chunks, each flushed "
                         "to the cell store on completion so interrupted "
                         "runs resume chunk-by-chunk (0, the default: whole "
                         "batch at once; see docs/paper-scale.md)")
    ap.add_argument("--devices", type=int, default=argparse.SUPPRESS,
                    help="[jax] lane-shard each chunk across N local "
                         "devices over a 1-D mesh (0 = all local devices, "
                         "1 = no sharding)")
    ap.add_argument("--expand-backend", default=argparse.SUPPRESS,
                    choices=["bisect", "pallas", "pallas-interpret",
                             "fused", "fused-interpret"],
                    help="[jax] Step-3 greedy expand backend: sort-free "
                         "threshold bisection (default), the Pallas "
                         "prefix-waterfill kernel, or the fused Pallas "
                         "Steps-1..3 scheduling kernel (-interpret "
                         "variants run the kernels off-TPU)")


def add_backend_arguments(ap: argparse.ArgumentParser, *,
                          default_cache_dir: str = "artifacts/sweep_cache"
                          ) -> None:
    """Results-neutral execution knobs (never part of the fingerprint)."""
    ap.add_argument("--cache-dir", default=default_cache_dir,
                    help="shared per-cell result store ('' disables)")
    ap.add_argument("--workers", type=int, default=0,
                    help="[des] cell-parallel worker processes "
                         "(0/1 serial, -1 per CPU)")
    add_execution_arguments(ap)
    add_observability_arguments(ap)


def add_observability_arguments(ap: argparse.ArgumentParser) -> None:
    """Flight-recorder flags (:mod:`repro.obs`) — pure observability,
    results-neutral and never fingerprinted: a run with tracing on writes
    bit-identical cells to one with tracing off (``tests/test_obs.py``)."""
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="write a Chrome trace-event JSON of the run "
                         "(load in chrome://tracing or ui.perfetto.dev); "
                         "enables span recording pipeline-wide")
    ap.add_argument("--trace-jsonl", default="", metavar="PATH",
                    help="also write the spans + final counter snapshot "
                         "as JSON-lines (grep/jq-friendly)")
    ap.add_argument("--progress", action="store_true",
                    help="print a heartbeat line per chunk (jax) / cell "
                         "(des): done/total, cells flushed, ETA")


def configure_observability(args: argparse.Namespace) -> None:
    """Enable the process tracer when any ``--trace*`` flag asks for it."""
    from repro import obs

    if getattr(args, "trace", "") or getattr(args, "trace_jsonl", ""):
        obs.configure(enabled=True)


def flush_observability(args: argparse.Namespace,
                        verbose: bool = True) -> None:
    """Write the trace artifacts requested by the ``--trace*`` flags."""
    from repro import obs

    trace = getattr(args, "trace", "")
    jsonl = getattr(args, "trace_jsonl", "")
    if not (trace or jsonl):
        return
    obs.flush(trace_path=trace or None, jsonl_path=jsonl or None)
    if verbose:
        for p in (trace, jsonl):
            if p:
                print(f"[obs] wrote {p}")


EXECUTION_OPTIONS = ("window", "chunk", "chunk_lanes", "devices",
                     "expand_backend")


def execution_options_from_args(args: argparse.Namespace) -> dict:
    """The execution flags the user gave, and no others."""
    return {k: getattr(args, k) for k in EXECUTION_OPTIONS
            if hasattr(args, k)}


def backend_options_from_args(args: argparse.Namespace) -> dict:
    return {"workers": getattr(args, "workers", 0),
            "progress": bool(getattr(args, "progress", False)),
            **execution_options_from_args(args)}
