"""Traffic kind ``grid``: the paper grid as a user's script runs it.

A closed loop: one ``run_experiment(engine="jax")`` grid at a time, each
on its own empty cell store, back to back until the window's seconds
have passed; the window ends with the grid it is in.  The deployment's
job log is drawn from ``--seed`` (the program's ``trace_seed``), so each
seed is another log of the same deployment.  Set-up runs one whole grid
of the same spec on a throwaway store, so every program the window's
grids use is compiled, or loaded from the cache, before the window opens.
"""
from __future__ import annotations

import pathlib
import time
from typing import Dict, List


def spec_for(ctx):
    from repro.experiments import ExperimentSpec

    tr = ctx.traffic
    return ExperimentSpec(
        workloads=(ctx.cfg["deployment"],), scale=ctx.cfg["scale"],
        trace_seed=ctx.seed, seeds=tr["transform_seeds"],
        proportions=tuple(tr["proportions"]),
        strategies=tuple(tr["strategies"]), engine="jax")


def one_grid(ctx, spec, store: pathlib.Path) -> Dict:
    import jax

    from repro.experiments import run_experiment
    from repro.sweep.cache import SweepCache

    name = ctx.cfg["deployment"]
    t0 = time.monotonic()
    with jax.profiler.TraceAnnotation("bench.grid"):
        out = run_experiment(spec, cache_dir=str(store), verbose=False)
    wall = time.monotonic() - t0
    info = out[name]["_engine"]
    cache = SweepCache(str(store))
    answers = {c: cache.get(spec.cell_fingerprint(name, c))
               for c in spec.cells()}
    return {"store_hits": int(info["cache_hits"]), "answers": answers,
            "wall_s": wall}


def setup(ctx) -> None:
    spec = spec_for(ctx)
    ctx.state["spec"] = spec
    one_grid(ctx, spec, ctx.scratch / "warmup-store")


def window(ctx) -> Dict:
    """Run grids until ``ctx.seconds`` have passed; returns the window's
    end-to-end numbers and the answers to check."""
    spec = ctx.state["spec"]
    grids: List[Dict] = []
    errors: List[str] = []
    t0 = time.monotonic()
    while True:
        try:
            grids.append(one_grid(ctx, spec,
                                  ctx.scratch / f"store-{len(grids)}"))
        except Exception as exc:  # noqa: BLE001 — a failed grid fails the run
            errors.append(f"{type(exc).__name__}: {exc}")
            break
        if time.monotonic() - t0 >= ctx.seconds:
            break
    elapsed = time.monotonic() - t0
    n_cells = len(spec.cells())
    stored = sum(1 for g in grids for m in g["answers"].values()
                 if m is not None)
    return {
        "window_s": elapsed,
        "attempted": n_cells * (len(grids) + len(errors)),
        "failed": n_cells * (len(grids) + len(errors)) - stored,
        "errors": errors,
        "store_hits": sum(g["store_hits"] for g in grids),
        "metrics": {"sweep_cells_per_s": stored / elapsed},
        "notes": {"grid_s": [g["wall_s"] for g in grids]},
        "grids": grids,
        "cells": spec.cells(),
    }


def to_check(ctx, result: Dict):
    """``(cells to compute in the reference, answers per cell, missing)``.

    Every grid's stored answer for every cell is compared.  A grid that
    read a store hit has not computed its cells, so its hits count as
    missing."""
    cells = result["cells"]
    answers = {c: [g["answers"][c] for g in result["grids"]] for c in cells}
    missing = (sum(1 for g in result["grids"] for m in g["answers"].values()
                   if m is None)
               + result["store_hits"] + len(result["errors"]) * len(cells))
    return cells, answers, missing


def control_cells(ctx) -> List:
    """The cells a run with this seed compares."""
    return spec_for(ctx).cells()
