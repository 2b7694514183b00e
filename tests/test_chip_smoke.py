"""``chip_smoke.py`` off the chip.

Without a TPU the script must fail and print no result — from the repo,
and from a directory that holds nothing of the repo but the script.  Its
phase functions, steered here to a tiny scale with interpret kernels and
fresh stores, must compute every cell and agree with ``run_experiment``,
and the prefix sum must equal ``numpy.cumsum`` through ``jnp.cumsum``.
"""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_fails_without_a_tpu(tmp_path, where):
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = pathlib.Path(shutil.copy(script, tmp_path / script.name))
    res = subprocess.run(
        [sys.executable, str(script), "--out", str(tmp_path / "out")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=tmp_path,
        capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


def test_phases_compute_every_cell_and_agree(smoke, tmp_path):
    from repro.experiments import run_experiment
    from repro.sweep.cache import SweepCache

    checks = smoke.Checks()
    smoke.prefix_phase(checks, widths=(1024, 4099), rows=3)
    spec = smoke.smoke_spec(scale=0.005)
    bisect, _ = smoke.sweep_phase(spec, tmp_path / "bisect", checks)
    fused, _ = smoke.sweep_phase(spec, tmp_path / "fused", checks,
                                 expand_backend="fused-interpret")
    run_experiment(spec, cache_dir=str(tmp_path / "direct"), verbose=False)
    direct = SweepCache(str(tmp_path / "direct"))
    for c in spec.cells():
        ref = direct.get(spec.cell_fingerprint(smoke.WORKLOAD, c))
        assert smoke.same(bisect[c], ref), c
        assert smoke.same(fused[c], ref), c
    smoke.des_phase(spec, bisect, checks)
    smoke.serve_phase(spec, tmp_path / "serve", bisect, checks)
    assert checks.failed == []
