"""Malleability sweep: the paper's core experiment (Figs. 6-9).

For one workload: proportions 0..100% x strategies x seeds ->
per-(strategy, proportion) aggregated metrics with IQR, plus the
improvement-vs-rigid summary the paper's abstract quotes.

A thin CLI over the declarative experiment layer
(:mod:`repro.experiments`): the grid, the scenario axes (walltime
accuracy, arrival compression, backfill depth) and the engine choice all
live in one :class:`~repro.experiments.ExperimentSpec`, and both engines
share the per-cell result store (resume/incremental reuse):

  * ``--engine des`` (default): the reference numpy DES, one simulation
    per cell, optionally ``--workers N`` process-parallel;
  * ``--engine jax``: the batched device-resident engine, the whole grid
    as fixed-shape lanes — monolithic by default, or streamed as
    resumable lane chunks (``--chunk-lanes``) and sharded across local
    devices (``--devices``; see ``docs/paper-scale.md``) —
    ``--crosscheck``-able against the DES.

``--compare-engines`` runs both on the same grid and reports wall-clock.

CLI:  PYTHONPATH=src python -m benchmarks.sweep --workload haswell \
          --scale 0.2 --seeds 3 --out artifacts/sweep-haswell.json
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time
from typing import Dict, Optional

from repro.experiments import (ExperimentSpec, best_improvements,
                               run_experiment, write_artifact)
from repro.experiments.cli import (add_backend_arguments, add_spec_arguments,
                                   backend_options_from_args, spec_from_args)

__all__ = ["sweep_workload", "best_improvements", "compare_engines", "main"]


def sweep_workload(name: str, *, scale: float = 0.2, seeds: int = 3,
                   proportions=None, strategies=None,
                   backfill_depth: int = 256,
                   cache_dir: Optional[str] = None,
                   workers: int = 0,
                   verbose: bool = True) -> Dict:
    """Reference-DES sweep of one workload (spec-routed back-compat API).

    Returns the shared artifact schema: ``{"rigid": metrics,
    "<strat>@<pct>": aggregates, "_meta": ..., "_engine": ...}``.
    """
    from repro.core.scenario import ScenarioConfig
    kw = {}
    if proportions is not None:
        kw["proportions"] = tuple(proportions)
    if strategies is not None:
        kw["strategies"] = tuple(strategies)
    spec = ExperimentSpec(
        workloads=(name,), scale=scale, seeds=seeds, engine="des",
        scenario=ScenarioConfig(backfill_depth=backfill_depth), **kw)
    return run_experiment(spec, cache_dir=cache_dir,
                          backend_options={"workers": workers},
                          verbose=verbose)[name]


def compare_engines(spec: ExperimentSpec, *, crosscheck: int = 4) -> Dict:
    """Wall-clock comparison: looped DES vs. the batched JAX engine.

    Both legs run the *same* single-workload spec (scenario axes, trace
    seed and strategy set included) with the engine swapped.  The per-cell
    result store is never consulted, so every leg measures real
    simulation.  The JAX engine is timed twice — cold (first call in the
    process, XLA compilation included) and steady-state (compilations
    reused) — because compilation is a one-time cost that the persistent
    XLA cache carries across processes while the simulation cost recurs
    with every new grid.
    """
    import dataclasses
    name, = spec.workloads
    scale, seeds = spec.scale, spec.seeds
    des_spec = dataclasses.replace(spec, engine="des")
    jax_spec = dataclasses.replace(spec, engine="jax")

    t0 = time.monotonic()
    run_experiment(des_spec, verbose=False)
    des_wall = time.monotonic() - t0

    t0 = time.monotonic()
    jax_results = run_experiment(jax_spec,
                                 crosscheck=crosscheck, verbose=False)[name]
    # the crosscheck's DES re-runs are reference work, not engine time
    jax_cold_wall = time.monotonic() - t0 - \
        jax_results.get("_crosscheck", {}).get("seconds", 0.0)

    t0 = time.monotonic()
    run_experiment(jax_spec, verbose=False)
    jax_warm_wall = time.monotonic() - t0

    report = {
        "grid_cells": len(des_spec.cells()),
        "des_wall_s": des_wall,
        "jax_wall_cold_s": jax_cold_wall,
        "jax_wall_steady_s": jax_warm_wall,
        "speedup_cold": des_wall / max(jax_cold_wall, 1e-9),
        "speedup_steady": des_wall / max(jax_warm_wall, 1e-9),
        "crosscheck_ok": jax_results.get("_crosscheck", {}).get(
            "all_within_tolerance"),
    }
    print(f"[compare:{name}] {report['grid_cells']}-cell grid at "
          f"scale={scale} seeds={seeds}")
    print(f"[compare:{name}] looped DES      {des_wall:8.1f}s")
    print(f"[compare:{name}] batched JAX     {jax_cold_wall:8.1f}s cold "
          f"(incl. XLA compile)  -> {report['speedup_cold']:.1f}x")
    print(f"[compare:{name}] batched JAX     {jax_warm_wall:8.1f}s steady "
          f"state               -> {report['speedup_steady']:.1f}x")
    print(f"[compare:{name}] crosscheck within tolerance: "
          f"{report['crosscheck_ok']}")
    return report


def main(argv=None):
    ap = argparse.ArgumentParser()
    add_spec_arguments(ap, single_workload=True)
    add_backend_arguments(ap, default_cache_dir="artifacts/sweep_cache")
    ap.add_argument("--crosscheck", type=int, default=0,
                    help="[jax] re-run N sampled cells through the DES; "
                         "cells are drawn from a seeded RNG so reruns "
                         "check the same cells")
    ap.add_argument("--crosscheck-seed", type=int, default=0,
                    help="[jax] RNG seed for crosscheck cell sampling")
    ap.add_argument("--compare-engines", action="store_true",
                    help="time the same grid on both engines and report "
                         "the wall-clock ratio; the per-cell result store "
                         "is disabled so timings are real, and 4 cells are "
                         "crosschecked unless --crosscheck overrides")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if args.engine == "jax" or args.compare_engines:
        from repro.xla_cache import enable_compilation_cache
        enable_compilation_cache()

    if args.compare_engines:
        report = compare_engines(spec_from_args(args),
                                 crosscheck=args.crosscheck or 4)
        if args.out:
            path = pathlib.Path(args.out)
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_text(json.dumps(report, indent=1, default=float))
            print(f"[compare:{args.workload}] wrote {path}")
        return

    spec = spec_from_args(args)
    if args.crosscheck and spec.engine != "jax":
        ap.error("--crosscheck needs --engine jax "
                 "(the DES is the reference)")
    results = run_experiment(
        spec, cache_dir=args.cache_dir or None,
        backend_options=backend_options_from_args(args),
        crosscheck=args.crosscheck,
        crosscheck_seed=args.crosscheck_seed)[args.workload]
    summary = best_improvements(results)
    print(f"\n[sweep:{args.workload}] best-vs-rigid (100% malleable):")
    for metric, r in summary.items():
        print(f"  {metric}: {r['rigid']:,.1f} -> {r['best']:,.1f} "
              f"({r['improvement_pct']:+.1f}% via {r['strategy']})")
    if args.out:
        path = write_artifact(args.out, results, summary)
        print(f"[sweep:{args.workload}] wrote {path}")


if __name__ == "__main__":
    main()
