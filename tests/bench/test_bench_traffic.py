"""The benchmark's traffic: the paper grid on a job log drawn from the seed."""
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import grid, harness  # noqa: E402
from bench.reference import trace as ref_trace  # noqa: E402

BIG_SEED = 2**31 + 12345


def context(seed, scale=0.02):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == "haswell.grid")
    cfg = json.loads((ROOT / "bench/configs/haswell.json").read_text())
    traffic = json.loads((ROOT / "bench/traffic/grid.json").read_text())
    return harness.Context(cell=cell, cfg={**cfg, "scale": scale},
                           traffic=traffic, seed=seed, seconds=1.0,
                           scratch=ROOT)


def test_grid_is_the_paper_grid_on_the_seeds_log():
    spec = grid.spec_for(context(BIG_SEED))
    assert spec.trace_seed == BIG_SEED and spec.engine == "jax"
    cells = spec.cells()
    assert len(cells) == 21 == len(set(cells))
    assert cells[0] == ("easy", 0.0, 0)
    assert {(s, p) for s, p, _ in cells[1:]} == {
        (s, p) for s in ("min", "pref", "avg", "keeppref")
        for p in (0.2, 0.4, 0.6, 0.8, 1.0)}


@pytest.mark.parametrize("seed", [BIG_SEED, 7])
def test_same_seed_gives_the_same_job_log(seed):
    cfg = context(seed).cfg
    a, b = ref_trace.generate(cfg, seed), ref_trace.generate(cfg, seed)
    c = ref_trace.generate(cfg, seed + 1)
    for key in ("submit", "runtime", "walltime", "req"):
        assert np.array_equal(a[key], b[key]), key
    assert not np.array_equal(a["submit"], c["submit"])


def test_every_seed_gives_the_deployments_size():
    cfg = context(0).cfg
    sizes = {len(ref_trace.generate(cfg, s)["submit"])
             for s in (0, 1, BIG_SEED)}
    assert sizes == {int(round(cfg["n_jobs"] * cfg["scale"]))}
