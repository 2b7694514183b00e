"""Benchmark orchestrator: one artifact per paper table/figure + roofline.

Default (CI-friendly) scale runs reduced traces; ``--full`` reproduces the
paper-scale sweeps (scale 1.0, 10 seeds).  Paper scale is a long run, not
a bigger box: ``--engine jax`` streams the grid as lane chunks sized by
``--chunk-lanes`` (optionally ``--devices``-sharded), flushing each
completed chunk into the shared cell store so an interrupted run resumes
where it stopped — commands, chunk sizing and expected wall-clock live in
``docs/paper-scale.md``.

Sweeps route through the declarative experiment layer
(:mod:`repro.experiments`): one :class:`~repro.experiments.ExperimentSpec`
covers all requested workloads, both engines share the per-cell result
store under ``artifacts/sweep_cache``, and whole-file sweep artifacts
(``artifacts/sweep-<name>.json``) are reused **only** when their recorded
spec fingerprint matches the requested experiment — a cached artifact from
a different scale, seed count, scenario, engine or engine version is
recomputed, never silently replayed.  Each sweep batch records wall-clock,
per-chunk timings and the peak device-resident lane width to
``artifacts/sweep-timing-{engine}.json``.

  PYTHONPATH=src python -m benchmarks.run [--scale 0.15] [--seeds 3]
  PYTHONPATH=src python -m benchmarks.run --engine jax --full \
      --chunk-lanes 16
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import time

from repro import xla_cache

from repro.experiments import (ExperimentSpec, best_improvements,
                               load_artifact_results, render_sweep_table,
                               run_experiment, write_artifact)
from repro.experiments.cli import (add_execution_arguments,
                                   add_observability_arguments,
                                   add_scenario_arguments,
                                   backend_options_from_args,
                                   configure_observability,
                                   flush_observability, scenario_from_args)

from . import figures, paper_tables, roofline

ARTIFACTS = pathlib.Path(__file__).resolve().parents[1] / "artifacts"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.15,
                    help="trace scale (1.0 = paper-size workloads)")
    ap.add_argument("--seeds", type=int, default=3,
                    help="seeds per (strategy, proportion); paper uses 10")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale: --scale 1.0 --seeds 10")
    ap.add_argument("--workloads", nargs="*",
                    default=["haswell", "knl", "eagle", "theta"])
    ap.add_argument("--engine", choices=["des", "jax"], default="des",
                    help="sweep engine: looped numpy DES or the batched "
                         "device-resident JAX engine")
    ap.add_argument("--workers", type=int, default=0,
                    help="[des] cell-parallel worker processes")
    add_execution_arguments(ap)
    add_scenario_arguments(ap)
    add_observability_arguments(ap)
    ap.add_argument("--skip-sweeps", action="store_true")
    ap.add_argument("--no-reuse", action="store_true",
                    help="recompute sweeps even if artifacts exist")
    ap.add_argument("--only-cached", action="store_true",
                    help="render sweeps only from existing artifacts "
                         "(skip, rather than recompute, missing ones)")
    ap.add_argument("--cold-xla-cache", action="store_true",
                    help="clear artifacts/xla_cache before the sweep so "
                         "compile_s measures a genuinely cold run (refused "
                         "when JAX_COMPILATION_CACHE_DIR places the cache)")
    ap.add_argument("--timing-tag", default="",
                    help="suffix for the wall-clock record "
                         "(sweep-timing-{engine}[-TAG].json) so a warm "
                         "rerun does not overwrite the cold record")
    args = ap.parse_args(argv)
    if args.full:
        args.scale, args.seeds = 1.0, 10
    if args.cold_xla_cache:
        if xla_cache.placed_from_outside():
            ap.error(f"--cold-xla-cache never deletes the "
                     f"{xla_cache.ENV_VAR} directory; unset the variable "
                     "or clear the directory yourself")
        shutil.rmtree(xla_cache.cache_dir(), ignore_errors=True)
    if args.engine == "jax":
        xla_cache.enable_compilation_cache()

    configure_observability(args)
    scenario = scenario_from_args(args)

    t0 = time.monotonic()
    print("#" * 72)
    print("# Paper tables")
    print("#" * 72)
    paper_tables.main(scale=min(args.scale, 0.3))
    print()

    print("#" * 72)
    print("# Figures 1-5 analogues (trace twins)")
    print("#" * 72)
    print(figures.fig_cleaning(scale=min(args.scale, 0.3)))
    for name in args.workloads:
        # eagle's 143k-job trace: keep the figure sim at the sweep's scale
        fscale = 0.06 if name == "eagle" else min(args.scale, 0.3)
        print(figures.fig_rigid_util(name, scale=fscale, scenario=scenario),
              flush=True)
        print(figures.fig_distributions(name, scale=fscale,
                                        scenario=scenario), flush=True)
    print()

    if not args.skip_sweeps:
        print("#" * 72)
        print(f"# Malleability sweeps (Figs. 6-9; scale={args.scale}, "
              f"seeds={args.seeds})")
        print("#" * 72)
        ARTIFACTS.mkdir(parents=True, exist_ok=True)
        spec = ExperimentSpec(
            workloads=tuple(args.workloads), scale=args.scale,
            seeds=args.seeds, engine=args.engine, scenario=scenario)
        all_results: dict = {}
        to_run = []
        for name in args.workloads:
            artifact = ARTIFACTS / f"sweep-{name}.json"
            cached = (None if args.no_reuse else
                      load_artifact_results(artifact, spec, name))
            if cached is not None:
                all_results[name] = cached
                print(f"[sweep:{name}] reusing {artifact} "
                      f"(spec {cached['_meta']['spec_key'][:12]})")
            elif args.only_cached:
                print(f"[sweep:{name}] no artifact for spec "
                      f"{spec.for_workload(name).key()[:12]}; skipping "
                      f"(re-run this command without --only-cached to "
                      f"compute it)")
            else:
                to_run.append(name)

        # classify the run for the perf gate *before* the sweep touches
        # the cache: cold = no persisted XLA compilations available
        xla_dir = xla_cache.cache_dir()
        xla_cache_state = ("warm" if xla_dir.exists()
                          and any(xla_dir.iterdir()) else "cold")

        batch_wall = None
        if to_run:
            run_spec = ExperimentSpec(
                workloads=tuple(to_run), scale=args.scale, seeds=args.seeds,
                engine=args.engine, scenario=scenario)
            t_sw = time.monotonic()
            computed = run_experiment(
                run_spec,
                # --no-reuse means recompute: bypass the cell store too —
                # but keep XLA compilations persistent (results-neutral)
                cache_dir=None if args.no_reuse
                else str(ARTIFACTS / "sweep_cache"),
                backend_options=backend_options_from_args(args))
            batch_wall = time.monotonic() - t_sw
            all_results.update(computed)

        for name in args.workloads:
            if name not in all_results:
                continue
            results = all_results[name]
            print()
            print(render_sweep_table(results))
            summary = best_improvements(results)
            print(f"\n  {name} best-vs-rigid at 100% malleable:")
            for metric, r in summary.items():
                print(f"    {metric:<12} {r['rigid']:>12,.1f} -> "
                      f"{r['best']:>12,.1f}  ({r['improvement_pct']:+6.1f}% "
                      f"via {r['strategy']})")
            write_artifact(ARTIFACTS / f"sweep-{name}.json", results,
                           summary)
            print()
        if batch_wall is not None:
            # wall-clock record per engine: running once with each of
            # --engine des / --engine jax leaves a comparable pair in
            # artifacts/ (see sweep/README.md "Performance").  Either
            # engine runs the remaining workloads as one experiment, so
            # only the batch total is real; the jax engine_info also
            # carries per-chunk wall-clock and the peak device-resident
            # lane width (the docs/paper-scale.md sizing inputs).
            tag = f"-{args.timing_tag}" if args.timing_tag else ""
            timing_path = ARTIFACTS / f"sweep-timing-{args.engine}{tag}.json"
            engine_info = {n: all_results[n].get("_engine", {})
                           for n in to_run}
            timing = {"schema_version": 2,  # docs/paper-scale.md
                      "engine": args.engine, "scale": args.scale,
                      "seeds": args.seeds, "batch_workloads": to_run,
                      "total_s": batch_wall,
                      "xla_cache_state": xla_cache_state,
                      "engine_info": engine_info}
            if args.engine == "jax" and to_run:
                # whole-batch achieved roofline: engine stats are
                # batch-scoped, so any one workload's _engine carries the
                # full chunk list (see backend_jax docstring)
                timing["roofline"] = roofline.sweep_roofline(
                    engine_info[to_run[0]])
            timing_path.write_text(json.dumps(timing, indent=1,
                                              default=float))
            print(f"[sweep] wall-clock record -> {timing_path}")

    print("#" * 72)
    print("# Roofline — BASELINE (paper-faithful + naive distribution)")
    print("#" * 72)
    roofline.main(["--artifacts", str(ARTIFACTS)])
    if list(ARTIFACTS.glob("dryrun-*-opt.json")):
        print()
        print("#" * 72)
        print("# Roofline — OPTIMIZED (post §Perf hillclimb; see "
              "EXPERIMENTS.md)")
        print("#" * 72)
        roofline.main(["--artifacts", str(ARTIFACTS), "--tag", "opt"])

    print(f"\n[benchmarks] total {time.monotonic()-t0:,.0f}s")
    flush_observability(args)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
