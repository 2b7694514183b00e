"""The paper's per-cell metrics (section 2.3) from a reference run.

Job metrics average over the jobs submitted inside the measurement
window ``[min(warm-up, 20% of the last submission), last submission]``;
utilization integrates busy nodes over that window.
"""
from __future__ import annotations

import concurrent.futures
import multiprocessing
import os
from typing import Dict, Sequence

import numpy as np

from . import sim, trace, transform


def cell_metrics(jobs: dict, res: dict, cfg: dict) -> dict:
    submit = jobs["submit"]
    last = float(np.max(submit))
    t0, t1 = min(cfg["policy"]["warmup_s"], 0.2 * last), last
    sel = (submit >= t0) & (submit <= t1) & np.isfinite(res["end"])
    unfinished = (submit >= t0) & (submit <= t1) & ~np.isfinite(res["end"])
    wait = res["start"][sel] - submit[sel]
    run = res["end"][sel] - res["start"][sel]
    ts = np.append(res["util_t"], max(res["t_end"], res["util_t"][-1]))
    seg = np.maximum(np.minimum(ts[1:], t1) - np.maximum(ts[:-1], t0), 0.0)
    busy = float(np.sum(seg * res["util_nodes"]))
    mall = sel & jobs.get("malleable", np.zeros(len(submit), bool))
    n_mall = int(np.sum(mall))
    return {
        "n_jobs": float(np.sum(sel)),
        "n_malleable": float(n_mall),
        "wait_mean": float(np.mean(wait)),
        "makespan_mean": float(np.mean(run)),
        "turnaround_mean": float(np.mean(res["end"][sel] - submit[sel])),
        "utilization": busy / (cfg["nodes"] * max(t1 - t0, 1e-9)),
        "expand_per_job": float(np.sum(res["expand_ops"][mall]))
        / max(n_mall, 1),
        "shrink_per_job": float(np.sum(res["shrink_ops"][mall]))
        / max(n_mall, 1),
        "unfinished": float(np.sum(unfinished)),
    }


def reference_cell(cfg: dict, trace_seed: int, strategy: str,
                   proportion: float, seed: int,
                   backfill: str = "easy", jobs: dict | None = None) -> dict:
    """Metrics of one cell, from the seed up."""
    rigid = trace.generate(cfg, trace_seed) if jobs is None else jobs
    cell = (transform.malleable_jobs(rigid, cfg, proportion, seed)
            if proportion > 0 and strategy != "easy" else rigid)
    res = sim.simulate(cell, cfg, strategy if proportion > 0 else "easy",
                       backfill=backfill)
    return cell_metrics(cell, res, cfg)


_JOBS: Dict = {}


def _one(args):
    cfg, trace_seed, cell, backfill = args
    key = (cfg["name"], cfg["scale"], trace_seed)
    if key not in _JOBS:
        _JOBS.clear()
        _JOBS[key] = trace.generate(cfg, trace_seed)
    return reference_cell(cfg, trace_seed, *cell, backfill=backfill,
                          jobs=_JOBS[key])


def reference_cells(cfg: dict, trace_seed: int, cells: Sequence,
                    backfill: str = "easy") -> Dict:
    """``{cell: metrics}`` for ``cells``, computed in worker processes
    (numpy only: no worker touches a device)."""
    workers = max(1, min(len(cells), (os.cpu_count() or 2) - 1, 12))
    tasks = [(cfg, trace_seed, c, backfill) for c in cells]
    if workers == 1:
        return {c: _one(t) for c, t in zip(cells, tasks)}
    ctx = multiprocessing.get_context("spawn")
    with concurrent.futures.ProcessPoolExecutor(workers,
                                                mp_context=ctx) as pool:
        return dict(zip(cells, pool.map(_one, tasks)))
