"""The harness: result line, device refusal, cells found by name."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from benchtools import ROOT, bench_copy, rehearse

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture()
def root(tmp_path):
    return bench_copy(tmp_path)


def test_last_line_carries_the_contract_keys(root, monkeypatch, capsys):
    rc, line = rehearse(root, monkeypatch, capsys, "haswell.grid")
    assert rc == 0
    # rehearsal numbers stay out of "metrics"; "compared" comes last
    assert list(line) == CONTRACT_KEYS + ["rehearsal", "compared"]
    assert line["metrics"] == {}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["rehearsal"]) == {"setup_s", "sweep_cells_per_s"}
    assert set(line["compared"]) == {"rigid_gap", "count_gap", "missing"}
    assert all(set(v) == {"value", "limit"}
               for v in line["compared"].values())
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0


def test_no_grid_in_the_window_reads_a_store_hit(root, monkeypatch, capsys):
    from repro.experiments import run as run_mod

    hits = []
    real = run_mod.run_experiment

    def spy(spec, **kw):
        out = real(spec, **kw)
        hits.append(out[spec.workloads[0]]["_engine"]["cache_hits"])
        return out

    monkeypatch.setattr(run_mod, "run_experiment", spy)
    monkeypatch.setattr("repro.experiments.run_experiment", spy)
    rc, line = rehearse(root, monkeypatch, capsys, "haswell.grid",
                        seconds=3.0)
    assert rc == 0 and line["correct"] is True
    assert len(hits) >= 2  # the warm-up grid and at least one in the window
    assert hits == [0] * len(hits)


def test_measuring_run_without_a_tpu_exits_nonzero():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "haswell.grid", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    """Without the program beside it the harness fails and prints nothing
    on stdout."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "haswell.grid",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearsal",
         "--scale", "0.01"],
        cwd=tmp_path, capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_new_config_traffic_and_metric_are_found_by_name(root, monkeypatch,
                                                         capsys):
    """A cell, mix and metric added as new files plus BENCHMARK.json
    entries run with no edit to any existing file."""
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((root / "bench/configs/haswell.json").read_text())
    (root / "bench/configs/haswell_small.json").write_text(json.dumps(
        {**cfg, "name": "haswell_small"}))
    (root / "bench/traffic/grid.two.json").write_text(json.dumps(
        {"kind": "grid", "strategies": ["min", "avg"],
         "proportions": [0.0, 0.5, 1.0], "transform_seeds": 1,
         "profile_offset_s": 0.5, "profile_s": 0.5}))
    shutil.copy(root / "bench/limits/haswell.grid.json",
                root / "bench/limits/haswell_small.grid.json")
    (root / "bench/metrics/grid_count.test.py").write_text(
        "def read(ctx):\n    return float(ctx.result['attempted'])\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "haswell_small", "source": "x",
                             "file": "bench/configs/haswell_small.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "haswell_small.grid", "chips": 1,
                               "config": "haswell_small",
                               "traffic": "grid.two", "why": "test"})
    e2e = next(m for m in bench["end_to_end"]
               if m["name"] == "sweep_cells_per_s")
    e2e["workloads"].append("haswell_small.grid")
    bench["per_layer"].append({"name": "grid_count.test", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "sweep_cells_per_s",
                               "workloads": ["haswell_small.grid"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    rc, line = rehearse(root, monkeypatch, capsys, "haswell_small.grid",
                        trace=1, scale=0.01)
    assert rc == 0 and line["correct"] is True
    assert line["attempted"] >= 5
    assert line["rehearsal"]["grid_count.test"]["value"] == line["attempted"]
    assert "host_outside_engine_share.sweep" not in line["rehearsal"]
    for path, data in before.items():
        assert path.read_bytes() == data, path
