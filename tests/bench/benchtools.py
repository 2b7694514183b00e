"""Helpers for the benchmark's tests: a copy of the benchmark tree, and
in-process rehearsal runs of the harness on the CPU."""
import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import harness  # noqa: E402

# rehearsal size: a small trace of the deployment
SCALE = {"haswell.grid": 0.01}


def bench_copy(dest: pathlib.Path) -> pathlib.Path:
    """``BENCHMARK.json`` and ``bench/`` copied under ``dest``."""
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return dest


def rehearse(root: pathlib.Path, monkeypatch, capsys, workload: str, *,
             seed: int = 3, seconds: float = 2.0, trace: int = 0,
             scale: float = None):
    """Run the harness in-process on ``root``; returns (rc, last line)."""
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "BENCH", root / "bench")
    rc = harness.run(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace),
                      "--rehearsal", "--scale",
                      str(scale or SCALE[workload])], time.monotonic())
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


def fresh_engine_caches(monkeypatch) -> None:
    """Give the engine empty process-wide program caches for one test, so
    a program built on a broken path is never reused by a later test."""
    from repro.sweep import batch

    monkeypatch.setattr(batch, "_COMPILED_KEYS", set())
    monkeypatch.setattr(batch, "_WARM_EXECUTABLES", {})
    monkeypatch.setattr(batch, "_WARM_FUTURES", {})


# -- faults planted under the timed path --------------------------------
def fault_state_unchanged(monkeypatch) -> None:
    """Every engine chunk call hands back the state it was given."""
    import jax

    from repro.sweep import batch

    fresh_engine_caches(monkeypatch)
    real = batch._chunk_fn

    def broken(*args, **kw):
        fn = real(*args, **kw)

        @jax.jit
        def step(b, full, k, retrig, bf, nact, ncomp):
            out = fn(b, full, k, retrig, bf, nact, ncomp)
            return (full, k, retrig, bf, nact, ncomp) + tuple(out[6:])
        return step

    monkeypatch.setattr(batch, "_chunk_fn", broken)


def _patch_metrics(monkeypatch, wrap) -> None:
    from repro.experiments import backend_jax
    from repro.sweep import metrics_jax

    broken = wrap(metrics_jax.batched_metrics)
    monkeypatch.setattr(metrics_jax, "batched_metrics", broken)
    monkeypatch.setattr(backend_jax, "batched_metrics", broken)


def fault_half_left_out(monkeypatch) -> None:
    """Each cell's means are taken over the first half of its jobs."""
    import numpy as np

    def wrap(real):
        def broken(result, submit, malleable, window, capacity):
            submit = np.array(submit, dtype=np.float32)
            submit[..., submit.shape[-1] // 2:] = np.inf
            return real(result, submit, malleable, window, capacity)
        return broken

    _patch_metrics(monkeypatch, wrap)


def fault_answer_altered(monkeypatch) -> None:
    """Every answer's mean turnaround is doubled where it is produced."""
    def wrap(real):
        def broken(*args, **kw):
            out = real(*args, **kw)
            for m in out:
                m["turnaround_mean"] *= 2.0
            return out
        return broken

    _patch_metrics(monkeypatch, wrap)


FAULTS = {"state_unchanged": (fault_state_unchanged, "missing"),
          "half_left_out": (fault_half_left_out, "count_gap"),
          "answer_altered": (fault_answer_altered, "rigid_gap")}
