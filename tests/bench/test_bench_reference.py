"""The benchmark's plain reference against the repository's own DES.

The reference (``bench/reference``) imports nothing of the program; here,
at small sizes, it must agree with the numpy DES of the same semantics
(``repro.core.simulator``) to rounding, and its trace generator must give
the program's jobs for the same seed.
"""
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.reference import metrics as ref_metrics  # noqa: E402
from bench.reference import trace as ref_trace  # noqa: E402


def config(name, scale):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                     .read_text())
    return {**cfg, "scale": scale}


@pytest.mark.parametrize("name,scale,seed", [("haswell", 0.02, 2**31 + 7),
                                             ("haswell", 0.05, 4)])
def test_reference_trace_is_the_programs_trace(name, scale, seed):
    from repro.core import traces

    w = traces.generate(name, seed=seed, scale=scale)
    j = ref_trace.generate(config(name, scale), seed)
    assert np.array_equal(w.submit, j["submit"])
    assert np.array_equal(w.runtime, j["runtime"])
    assert np.array_equal(w.walltime, j["walltime"])
    assert np.array_equal(w.nodes_req, j["req"])


@pytest.mark.parametrize("cell", [("easy", 0.0, 0), ("min", 0.4, 0),
                                  ("pref", 0.6, 1), ("avg", 0.8, 0),
                                  ("keeppref", 1.0, 2)])
def test_reference_agrees_with_the_repo_des(cell):
    from repro.experiments import ExperimentSpec
    from repro.experiments.backend_des import simulate_cell

    cfg = config("haswell", 0.03)
    spec = ExperimentSpec(workloads=("haswell",), scale=0.03, trace_seed=5,
                          seeds=3, engine="des")
    des = simulate_cell(spec, "haswell", cell)
    ref = ref_metrics.reference_cell(cfg, 5, *cell)
    for key, value in ref.items():
        assert des[key] == pytest.approx(value, rel=1e-9, abs=1e-9), key


@pytest.mark.parametrize("seed", [2**31 + 3, 11])
def test_control_reads_not_correct(seed, tmp_path):
    """The reference with the EASY guarantee broken, in the program's
    place, fails the comparison that decides ``correct``."""
    from bench import control
    from benchtools import bench_copy

    out = control.readings("haswell.grid", seed, scale=0.25,
                           root=bench_copy(tmp_path))
    gap = out["compared"]["rigid_gap"]
    assert out["correct"] is False
    assert gap["value"] > gap["limit"]


def _two_job_queue(backfill):
    """Four nodes; a 2-node job runs, a 4-node head waits behind it, and
    a later 2-node job fits the free nodes but would outlast the head's
    reservation."""
    jobs = {"submit": np.array([0.0, 1.0, 2.0]),
            "runtime": np.array([100.0, 50.0, 400.0]),
            "walltime": np.array([125.0, 62.5, 500.0]),
            "req": np.array([2, 4, 2])}
    cfg = {"nodes": 4, "tick_s": 1.0, "policy": {"backfill_depth": 256}}
    from bench.reference import sim

    return sim.simulate(jobs, cfg, "easy", backfill=backfill)["start"]


def test_easy_backfill_never_delays_the_reserved_head():
    start = _two_job_queue("easy")
    assert start[1] == 100.0       # the head starts when the first job ends
    assert start[2] >= start[1]    # the later job waits behind it


def test_reservationless_control_delays_the_head():
    start = _two_job_queue("reservationless")
    assert start[2] == 2.0         # the later job jumps the blocked head
    assert start[1] > 400.0        # and the head waits for it to end
