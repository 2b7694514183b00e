"""Tests for the batched device-resident sweep engine (repro.sweep)."""
import numpy as np
import pytest

from repro.core import (Cluster, STRATEGIES, Workload, simulate,
                        transform_rigid_to_malleable)
from repro.core.speedup import batched_malleable_params
from repro.sweep.batch import EngineConfig, build_lanes, simulate_lanes
from repro.sweep.cache import SweepCache, cell_fingerprint

TINY = Cluster("t", nodes=10, tick=1.0)


def _wl(seed=0, n=20, hi=150.0):
    rng = np.random.default_rng(seed)
    return Workload.rigid(submit=np.sort(rng.uniform(0, hi, n)),
                          runtime=rng.uniform(20, 120, n),
                          nodes_req=rng.choice([1, 2, 4, 8], n))


LANES = [(STRATEGIES["easy"], 0.0, 0), (STRATEGIES["min"], 0.6, 0),
         (STRATEGIES["pref"], 1.0, 1), (STRATEGIES["keeppref"], 0.6, 0)]
CFG = EngineConfig(window=16, chunk=64)


@pytest.fixture(scope="module")
def greedy_run():
    batch, order = build_lanes(_wl(), 10, LANES)
    return batch, order, simulate_lanes(batch, CFG)


def test_lane_construction_matches_looped_transform():
    w = _wl()
    batch, order = build_lanes(w, 10, LANES)
    inv = np.argsort(order)
    for b, (strat, prop, seed) in enumerate(LANES):
        wm = (w if prop == 0.0 else
              transform_rigid_to_malleable(w, prop, seed, 10))
        np.testing.assert_array_equal(
            np.asarray(batch.malleable[b])[inv], wm.malleable)
        np.testing.assert_allclose(
            np.asarray(batch.pfrac[b])[inv], wm.pfrac, rtol=1e-6)
        if strat.malleable:
            np.testing.assert_array_equal(
                np.asarray(batch.min_nodes[b])[inv], wm.min_nodes)
            np.testing.assert_array_equal(
                np.asarray(batch.max_nodes[b])[inv], wm.max_nodes)


def test_all_lanes_complete_and_capacity_respected(greedy_run):
    batch, order, res = greedy_run
    assert res["finished"]
    assert int(res["trace_busy"].max()) <= TINY.nodes
    submit = np.asarray(batch.submit)
    for b in range(len(LANES)):
        start, end = res["start_t"][b], res["end_t"][b]
        assert np.all(np.isfinite(start)) and np.all(np.isfinite(end))
        assert np.all(end > start)
        assert np.all(start >= submit - TINY.tick)


def test_rigid_lane_runtime_preserved(greedy_run):
    batch, order, res = greedy_run
    w = _wl().take(order)
    span = res["end_t"][0] - res["start_t"][0]  # lane 0 = EASY, 0% malleable
    assert np.all(span >= w.runtime - 1e-3)
    assert np.all(span <= w.runtime + 2 * TINY.tick)


def test_agreement_with_reference_des_low_contention():
    """Starts/ends track the DES within backfill-approximation tolerance on
    a low-contention workload (same regime as test_sim_jax)."""
    rng = np.random.default_rng(5)
    n = 12
    w = Workload.rigid(submit=np.sort(rng.uniform(0, 200, n)),
                       runtime=rng.uniform(20, 80, n),
                       nodes_req=rng.choice([1, 2], n))
    lanes = [(STRATEGIES["easy"], 0.0, 0), (STRATEGIES["min"], 0.5, 1)]
    batch, order = build_lanes(w, 10, lanes)
    res = simulate_lanes(batch, CFG)
    inv = np.argsort(order)
    for b, (strat, prop, seed) in enumerate(lanes):
        wm = (w if prop == 0.0 else
              transform_rigid_to_malleable(w, prop, seed, 10))
        ref = simulate(wm, TINY, strat)
        np.testing.assert_allclose(res["start_t"][b][inv], ref.start,
                                   atol=2.0)
        np.testing.assert_allclose(res["end_t"][b][inv], ref.end, atol=4.0)


def test_balanced_engine_runs_avg_lanes():
    batch, order, _ = (None, None, None)
    w = _wl(seed=3)
    lanes = [(STRATEGIES["avg"], 0.8, 0), (STRATEGIES["avg"], 1.0, 1)]
    batch, order = build_lanes(w, 10, lanes)
    cfg = EngineConfig(structure="balanced", window=16, chunk=64)
    res = simulate_lanes(batch, cfg)
    assert res["finished"]
    assert int(res["trace_busy"].max()) <= TINY.nodes


def test_mixed_engine_structures_rejected():
    with pytest.raises(ValueError):
        build_lanes(_wl(), 10, [(STRATEGIES["avg"], 0.5, 0),
                                (STRATEGIES["min"], 0.5, 0)])


def test_window_escalation_recovers_from_small_window():
    """A 4-slot window cannot hold the active set; the engine must escalate
    rather than stall or corrupt state."""
    w = _wl(n=30, hi=60.0)  # heavy burst -> deep queue
    batch, order = build_lanes(w, 10, [(STRATEGIES["easy"], 0.0, 0)])
    cfg = EngineConfig(window=4, chunk=32, reserve_slack=2)
    res = simulate_lanes(batch, cfg)
    assert res["finished"]
    assert res["window"] > 4
    ref = simulate(w, TINY, STRATEGIES["easy"])
    # escalation must not lose or duplicate work
    inv = np.argsort(order)
    assert np.all(np.isfinite(res["end_t"][0]))
    assert int(res["trace_busy"].max()) <= TINY.nodes
    del ref, inv


def test_batched_transform_grid_nests_across_proportions():
    """For one seed the malleable set at p1 < p2 must be a subset (the
    paper reuses the workload; only the malleable share grows)."""
    w = _wl()
    params = batched_malleable_params(w, [(0.3, 5), (0.9, 5)], 10)
    m30, m90 = params["malleable"]
    assert np.all(~m30 | m90)


# ------------------------------------------- compile budget / hot loop
def _bit_equal(res_a, res_b, keys=("start_t", "end_t", "state",
                                   "bf_starts", "shrink_ops",
                                   "expand_ops")):
    for key in keys:
        np.testing.assert_array_equal(
            np.asarray(res_a[key]), np.asarray(res_b[key]), err_msg=key)


def test_event_compression_is_results_neutral():
    """E=1 (one event per scan step) and E=4 (compressed) must be
    bit-identical: compression only merges no-op scheduling passes.

    The rigid lane's tail (queue drained, no expansion room) is all
    no-op completion events — the regime compression targets."""
    rng = np.random.default_rng(7)
    n = 20
    w = Workload.rigid(submit=np.sort(rng.uniform(0, 5.0, n)),
                       runtime=rng.uniform(20, 120, n),
                       nodes_req=np.ones(n, dtype=np.int64))
    batch, _order = build_lanes(w, 10, [(STRATEGIES["easy"], 0.0, 0),
                                        (STRATEGIES["min"], 0.6, 0)])
    res1 = simulate_lanes(batch, EngineConfig(window=16, chunk=64,
                                              events=1))
    res4 = simulate_lanes(batch, EngineConfig(window=16, chunk=64,
                                              events=4))
    assert res1["finished"] and res4["finished"]
    assert res4["compressed_events"] > 0  # E=4 actually compressed
    assert res1["compressed_events"] == 0
    _bit_equal(res1, res4)


def test_escalated_run_matches_fresh_larger_bucket():
    """A run forced through window escalation must produce the same cells
    as a fresh run started at the final bucket (execution-plan
    invariance: the ladder is a perf knob, not a semantics knob)."""
    w = _wl(n=30, hi=60.0)  # heavy burst -> forces escalation from 4
    batch, _order = build_lanes(w, 10, [(STRATEGIES["easy"], 0.0, 0),
                                        (STRATEGIES["min"], 0.8, 1)])
    forced = simulate_lanes(batch, EngineConfig(window=4, chunk=32,
                                                reserve_slack=2))
    assert forced["escalations"] > 0
    fresh = simulate_lanes(batch, EngineConfig(window=forced["window"],
                                               chunk=32, reserve_slack=2))
    assert fresh["escalations"] == 0
    _bit_equal(forced, fresh)


def test_chunk_fn_cache_is_unbounded_and_rerun_never_retraces():
    """Regression: ``_chunk_fn`` once sat behind an ``lru_cache`` whose
    eviction caused steady-state retraces on multi-variant sweeps.  The
    cache must be unbounded and a repeat run must re-trace nothing."""
    from repro.sweep.batch import _chunk_fn
    assert _chunk_fn.cache_info().maxsize is None
    batch, _order = build_lanes(_wl(), 10, LANES)
    cfg = EngineConfig(window=16, chunk=64)
    simulate_lanes(batch, cfg)
    rerun = simulate_lanes(batch, cfg)
    assert rerun["retraces"] == 0


def test_fused_backend_matches_bisect_engine():
    """The fused Pallas schedule_tick (interpret mode off-TPU) reproduces
    the reference pass bit-for-bit through a whole engine run."""
    batch, _order = build_lanes(_wl(n=25, hi=100.0), 10, LANES)
    ref = simulate_lanes(batch, EngineConfig(window=16, chunk=64))
    fused = simulate_lanes(batch, EngineConfig(
        window=16, chunk=64, expand_backend="fused-interpret"))
    assert fused["finished"]
    _bit_equal(ref, fused)
    np.testing.assert_array_equal(np.asarray(ref["sched_steps"]),
                                  np.asarray(fused["sched_steps"]))


@pytest.mark.parametrize("events", [1, 4])
def test_agreement_with_reference_des_under_compression(events):
    """DES parity holds with the compressed event loop at either depth."""
    rng = np.random.default_rng(5)
    n = 12
    w = Workload.rigid(submit=np.sort(rng.uniform(0, 200, n)),
                       runtime=rng.uniform(20, 80, n),
                       nodes_req=rng.choice([1, 2], n))
    lanes = [(STRATEGIES["easy"], 0.0, 0), (STRATEGIES["min"], 0.5, 1)]
    batch, order = build_lanes(w, 10, lanes)
    res = simulate_lanes(batch, EngineConfig(window=16, chunk=64,
                                             events=events))
    inv = np.argsort(order)
    for b, (strat, prop, seed) in enumerate(lanes):
        wm = (w if prop == 0.0 else
              transform_rigid_to_malleable(w, prop, seed, 10))
        ref = simulate(wm, TINY, strat)
        np.testing.assert_allclose(res["start_t"][b][inv], ref.start,
                                   atol=2.0)
        np.testing.assert_allclose(res["end_t"][b][inv], ref.end, atol=4.0)


# ---------------------------------------------------------------- cache
def test_cache_roundtrip_and_miss(tmp_path):
    cache = SweepCache(tmp_path)
    fp = cell_fingerprint("haswell", 0, 0.05, 2388, 1.0, "min", 0.6, 3,
                          engine="jax")
    assert cache.get(fp) is None
    cache.put(fp, {"turnaround_mean": 123.0})
    assert cache.get(fp) == {"turnaround_mean": 123.0}
    assert cache.hits == 1 and cache.misses == 1


def test_cache_key_sensitive_to_cell_identity(tmp_path):
    base = dict(workload="haswell", trace_seed=0, scale=0.05, capacity=2388,
                tick=1.0, strategy="min", proportion=0.6, seed=3,
                engine="jax")
    k0 = SweepCache.key(cell_fingerprint(**base))
    for field, value in [("strategy", "pref"), ("proportion", 0.8),
                         ("seed", 4), ("scale", 0.1), ("engine", "des")]:
        other = dict(base)
        other[field] = value
        assert SweepCache.key(cell_fingerprint(**other)) != k0, field


@pytest.mark.parametrize("structure", ["greedy", "pooled"])
def test_chunk_program_carries_named_scopes(structure):
    """The compiled chunk program names its phases and the scheduling
    pass's steps in op metadata, where a device trace can find them."""
    import re

    from repro.sweep.batch import _chunk_fn, chunk_arg_shapes

    n, B, W = 64, 2, 16
    fn = _chunk_fn(EngineConfig(structure=structure, chunk=4), n, B, W,
                   -64, 64, 64)
    text = fn.lower(*chunk_arg_shapes(n, B)).compile().as_text()
    scopes = {part for op in re.findall(r'op_name="([^"]*)"', text)
              for part in op.split("/")}
    want = {"chunk.compact", "chunk.advance", "chunk.account",
            "chunk.scatter", "sched.fcfs", "sched.backfill", "sched.shrink",
            "sched.expand"}
    if structure == "pooled":  # greedy has no Step 2b
        want.add("sched.extra")
    assert want <= scopes
