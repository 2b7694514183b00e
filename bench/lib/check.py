"""The comparison that decides ``correct``.

Each compared answer is one cell's metric dict as the program stored it;
the reference computes the same cell from the seed with
``bench/reference``.  The numbers compared, each with a limit of its own
from ``bench/limits/<cell>.json``:

* ``rigid_gap``: over the answers of the rigid cells (EASY with no
  malleable job), the widest gap of mean turnaround, mean wait and mean
  makespan, each measured against the reference's mean turnaround, and of
  utilization against the reference's utilization;
* ``count_gap``: over every answer, jobs counted in the window, malleable
  jobs among them, and jobs left unfinished must match exactly (limit 0);
* ``missing``: answers that never came or were never stored (limit 0).

``malleable_gap``, the same gap as ``rigid_gap`` over the malleable
cells, is printed beside them but not compared: a sub-second shift of one
arrival moves a malleable cell's means by up to a tenth in the reference
itself, as far as breaking the EASY reservation does (``PERF.md``).
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, Iterable, List, Optional, Tuple

TIME_KEYS = ("turnaround_mean", "wait_mean", "makespan_mean")
COUNT_KEYS = ("n_jobs", "n_malleable", "unfinished")


def is_rigid(cell: Tuple[str, float, int]) -> bool:
    strategy, proportion, _ = cell
    return strategy == "easy" or proportion == 0


def metric_gap(got: Dict[str, float], ref: Dict[str, float]) -> float:
    base = abs(ref["turnaround_mean"])
    gaps = [abs(got[k] - ref[k]) / base for k in TIME_KEYS]
    gaps.append(abs(got["utilization"] - ref["utilization"])
                / abs(ref["utilization"]))
    return max(gaps)


def count_gap(got: Dict[str, float], ref: Dict[str, float]) -> float:
    return sum(abs(got[k] - ref[k]) for k in COUNT_KEYS)


def load_limits(path: pathlib.Path) -> Dict[str, float]:
    """``{number: limit}`` of the numbers compared for a cell."""
    spec = json.loads(path.read_text())
    return {k: float(v["limit"]) for k, v in spec["numbers"].items()}


def readings(triples: Iterable[Tuple[Tuple, Dict, Dict]],
             missing: int) -> Dict[str, Optional[float]]:
    """Every number over ``(cell, got, ref)``; a gap over no answer is
    None."""
    triples = list(triples)
    rigid = [metric_gap(g, r) for c, g, r in triples if is_rigid(c)]
    mall = [metric_gap(g, r) for c, g, r in triples if not is_rigid(c)]
    return {
        "rigid_gap": max(rigid, default=None),
        "count_gap": max((count_gap(g, r) for _, g, r in triples),
                         default=None),
        "missing": float(missing),
        "malleable_gap": max(mall, default=None),
    }


def compare(values: Dict[str, Optional[float]],
            limits: Dict[str, float]) -> Tuple[bool, Dict]:
    """``(correct, {number: {"value", "limit"}})`` for the numbers that
    ``limits`` names; a number that reads None (nothing compared) fails."""
    out, ok = {}, True
    for name, limit in limits.items():
        value = values.get(name)
        out[name] = {"value": value, "limit": limit}
        ok = ok and value is not None and value <= limit
    return ok, out


def report_lines(compared: Dict, values: Dict) -> List[str]:
    lines = [f"not compared {name}: {value!r}"
             for name, value in values.items() if name not in compared]
    return lines + [f"compared {name}: {v['value']!r} limit {v['limit']!r}"
                    for name, v in compared.items()]
