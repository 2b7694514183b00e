#!/usr/bin/env python3
"""Readings of the correctness control for a cell, on given seeds.

The control is the reference with the configuration's EASY guarantee
broken (``backfill="reservationless"``: behind a blocked queue head, any
later job that fits starts, whether or not it delays the head), put in
the program's place: its answers for the cells that a run with the same
seed compares, from the same seed's job log, go through the same
comparison against the plain reference.  It has to read not correct.
Host only; it never touches a device.

  python3 bench/control.py --workload haswell.grid --seeds 1 2 3
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import check, harness  # noqa: E402
from bench.reference.metrics import reference_cells  # noqa: E402


def readings(workload: str, seed: int, backfill: str = "reservationless",
             scale=None, root=ROOT) -> dict:
    bench = harness.load_json(root / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = harness.load_json(root / entry["file"])
    if scale is not None:
        cfg = {**cfg, "scale": scale}
    traffic = harness.load_json(root / "bench" / "traffic"
                                / f"{cell['traffic']}.json")
    ctx = harness.Context(cell=cell, cfg=cfg, traffic=traffic, seed=seed,
                          seconds=0.0, scratch=root)
    cells = harness.KINDS[traffic["kind"]].control_cells(ctx)
    refs = reference_cells(cfg, seed, cells)
    got = reference_cells(cfg, seed, cells, backfill=backfill)
    values = check.readings([(c, got[c], refs[c]) for c in cells], 0)
    limits = check.load_limits(root / "bench" / "limits"
                               / f"{workload}.json")
    correct, compared = check.compare(values, limits)
    return {"seed": seed, "cells": len(cells), "correct": correct,
            "compared": compared, "values": values}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        t0 = time.monotonic()
        out = readings(args.workload, seed)
        out["host_s"] = time.monotonic() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
