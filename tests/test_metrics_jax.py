"""Parity tests for the on-device batched metrics (repro.sweep.metrics_jax).

Two layers:

1. *Exact port parity*: feed identical simulation outputs through
   ``run_metrics`` (numpy) and ``batched_metrics`` (device) — the metric
   math itself must agree to float tolerance.
2. *Cross-engine parity*: batched-engine metrics vs. ``run_metrics`` on the
   numpy DES outputs for >= 2 traces x 2 strategies, within the documented
   tick-quantization / backfill-lite tolerances
   (``repro.experiments.crosscheck.CROSSCHECK_TOLERANCES``).
"""
import numpy as np
import pytest

from repro.core import (CLUSTERS, Window, get_strategy, run_metrics,
                        simulate, traces, transform_rigid_to_malleable)
from repro.core.simulator import SimResult
from repro.experiments.crosscheck import CROSSCHECK_TOLERANCES
from repro.sweep.batch import EngineConfig, build_lanes, simulate_lanes
from repro.sweep.metrics_jax import batched_metrics

CASES = [("haswell", "easy", 0.0), ("haswell", "min", 1.0),
         ("knl", "easy", 0.0), ("knl", "keeppref", 1.0)]


def _engine_run(name, strategy, prop, scale):
    cl = CLUSTERS[name]
    w = traces.generate(name, seed=0, scale=scale)
    lanes = [(get_strategy(strategy), prop, 0)]
    batch, order = build_lanes(w, cl.nodes, lanes, tick=cl.tick)
    cfg = EngineConfig(window=128, chunk=96)
    res = simulate_lanes(batch, cfg)
    return cl, w, Window.for_workload(w), batch, order, res


@pytest.mark.parametrize("name,strategy,prop", CASES[:2])
def test_metric_port_exact_parity(name, strategy, prop):
    """Same inputs -> run_metrics and batched_metrics agree to float tol."""
    cl, w, window, batch, order, res = _engine_run(name, strategy, prop,
                                                   scale=0.01)
    assert res["finished"]
    w_sorted = w.take(order)
    wm = (w_sorted if prop == 0.0 else w_sorted.copy())
    wm.malleable = np.asarray(batch.malleable[0])

    ref = run_metrics(
        SimResult(
            start=res["start_t"][0].astype(np.float64),
            end=res["end_t"][0].astype(np.float64),
            expand_ops=res["expand_ops"][0], shrink_ops=res["shrink_ops"][0],
            util_t=res["trace_t"][0].astype(np.float64),
            util_nodes=res["trace_busy"][0],
            n_sched_calls=res["steps"], sim_seconds=0.0, finished=True,
            end_time=float(np.nanmax(res["end_t"][0]))),
        wm, cl, window)
    dev = batched_metrics(res, batch.submit, batch.malleable, window,
                          cl.nodes)[0]
    for key, val in ref.items():
        if not np.isfinite(val):
            assert not np.isfinite(dev[key]), key
            continue
        assert dev[key] == pytest.approx(val, rel=1e-4, abs=1e-3), key


@pytest.mark.parametrize("name,strategy,prop", CASES)
def test_cross_engine_parity_with_des(name, strategy, prop):
    """Batched on-device metrics match run_metrics on the numpy DES within
    the documented tick-quantization / backfill-lite tolerances."""
    scale = 0.01 if name == "haswell" else 0.005
    cl, w, window, batch, order, res = _engine_run(name, strategy, prop,
                                                   scale=scale)
    assert res["finished"]
    wm = (w if prop == 0.0 else
          transform_rigid_to_malleable(w, prop, 0, cl.nodes))
    ref = run_metrics(simulate(wm, cl, get_strategy(strategy)),
                      wm, cl, window)
    dev = batched_metrics(res, batch.submit, batch.malleable, window,
                          cl.nodes)[0]
    assert dev["n_jobs"] == ref["n_jobs"]
    for key, (rtol, atol) in CROSSCHECK_TOLERANCES.items():
        a, b = ref[key], dev[key]
        if not np.isfinite(a):
            continue
        assert abs(b - a) <= max(rtol * abs(a), atol), (
            f"{key}: des={a} jax={b}")
