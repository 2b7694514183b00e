"""The `theta.grid` cell: its configuration against the program, its
reference against the DES, and its check against sound and broken runs."""
import json
import math
import pathlib
import sys

import numpy as np
import pytest

from benchtools import FAULTS, bench_copy, rehearse

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import harness  # noqa: E402
from bench.reference import metrics as ref_metrics  # noqa: E402
from bench.reference import trace as ref_trace  # noqa: E402

SCALE = 0.1  # rehearsal size: 255 jobs over 2.8 days


def config(scale):
    cfg = json.loads((ROOT / "bench" / "configs" / "theta.json").read_text())
    return {**cfg, "scale": scale}


def test_configuration_is_the_programs_deployment():
    from repro.core.traces import THETA_SPEC

    cfg = config(1.0)
    harness.check_deployment(cfg)
    tr, spec = cfg["trace"], THETA_SPEC
    assert tr["node_values"] == list(spec.node_values)
    assert tr["node_probs"] == list(spec.node_probs)
    assert tr["runtime_mix"] == [list(c) for c in spec.runtime.components]
    assert (tr["rigid_util"], tr["load_factor"], tr["diurnal_amp"],
            tr["burst"]) == (spec.rigid_util, spec.load_factor,
                             spec.diurnal_amp, spec.burst)
    assert cfg["reduced"] == []


def test_traffic_is_the_paper_grid():
    """The cell runs the traffic ``haswell.grid`` runs, on a deployment
    of its own: a configuration that is not haswell's."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert cells["theta.grid"]["traffic"] == cells["haswell.grid"]["traffic"]
    theta, haswell = configs["theta"], configs["haswell"]
    assert theta["source"] != haswell["source"]
    assert theta["file"] != haswell["file"]


@pytest.mark.parametrize("scale,seed", [(1.0, 5200000001), (0.1, 2**31 + 9)])
def test_reference_trace_is_the_programs_trace(scale, seed):
    from repro.core import traces

    w = traces.generate("theta", seed=seed, scale=scale)
    j = ref_trace.generate(config(scale), seed)
    assert np.array_equal(w.submit, j["submit"])
    assert np.array_equal(w.runtime, j["runtime"])
    assert np.array_equal(w.walltime, j["walltime"])
    assert np.array_equal(w.nodes_req, j["req"])


@pytest.mark.parametrize("cell", [("easy", 0.0, 0), ("pref", 0.4, 0)])
def test_reference_agrees_with_the_repo_des(cell):
    from repro.experiments import ExperimentSpec
    from repro.experiments.backend_des import simulate_cell

    spec = ExperimentSpec(workloads=("theta",), scale=0.25, trace_seed=7,
                          seeds=1, engine="des")
    des = simulate_cell(spec, "theta", cell)
    ref = ref_metrics.reference_cell(config(0.25), 7, *cell)
    for key, value in ref.items():
        assert des[key] == pytest.approx(value, rel=1e-9, abs=1e-9), key


def _counting_cells(monkeypatch):
    """Record how many cells and answers the run compares."""
    seen = {}
    real = harness.reference_triples

    def counted(ctx, cells, answers):
        triples = real(ctx, cells, answers)
        seen.update(cells=len(cells), answers=len(triples))
        return triples

    monkeypatch.setattr(harness, "reference_triples", counted)
    return seen


def test_rehearsal_is_correct(tmp_path, monkeypatch, capsys):
    seen = _counting_cells(monkeypatch)
    rc, line = rehearse(bench_copy(tmp_path), monkeypatch, capsys,
                        "theta.grid", scale=SCALE)
    assert rc == 0 and line["correct"] is True, line["compared"]
    assert line["failed"] == 0
    assert seen["cells"] == 21 and seen["answers"] >= 21


@pytest.mark.parametrize("seed", [5200000001, 5200000003])
def test_control_reads_not_correct(seed, tmp_path):
    """The reference with the EASY reservation broken, in the program's
    place on the whole deployment, fails the comparison."""
    from bench import control

    out = control.readings("theta.grid", seed, scale=1.0,
                           root=bench_copy(tmp_path))
    gap = out["compared"]["rigid_gap"]
    assert out["cells"] == 21 and out["correct"] is False
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(fault, tmp_path, monkeypatch,
                                          capsys):
    plant, number = FAULTS[fault]
    plant(monkeypatch)
    rc, line = rehearse(bench_copy(tmp_path), monkeypatch, capsys,
                        "theta.grid", scale=SCALE)
    assert rc == 0 and line["correct"] is False
    got = line["compared"][number]
    assert got["value"] > got["limit"], line["compared"]


@pytest.mark.parametrize("cell,scale", [("haswell.grid", 0.01),
                                        ("theta.grid", SCALE)])
def test_traced_run_reads_the_window_fill(cell, scale, tmp_path,
                                          monkeypatch, capsys):
    """Each chunk call's first peek carries the active count, so the
    window's fill reads as a share of its slots."""
    rc, line = rehearse(bench_copy(tmp_path), monkeypatch, capsys, cell,
                        trace=1, scale=scale)
    assert rc == 0 and line["correct"] is True
    fill = line["rehearsal"]["engine_window_fill.sweep"]["value"]
    assert math.isfinite(fill) and 0 < fill <= 100
