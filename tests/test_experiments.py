"""Tests for the declarative experiment layer (repro.experiments).

Covers the spec fingerprint (round-trip stability + invalidation on every
axis), the scenario workload transforms, the shared cell store (DES hit on
second run, incremental cross-spec reuse, parallel == serial determinism),
the stale-artifact guard for whole-file sweep reuse, and JAX-vs-DES parity
through the *same* spec entry point.
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.core import ScenarioConfig, apply_scenario, traces
from repro.core.jobs import (CLASS_NORMAL, CLASS_ON_DEMAND, CLASS_RIGID)
from repro.core.scenario import (DEFAULT_BACKFILL_DEPTH, JobClasses,
                                 assign_job_classes)
from repro.core.speedup import TransformConfig
from repro.experiments import (ExperimentSpec, load_artifact_results,
                               run_experiment, write_artifact)
from repro.experiments.cli import (add_backend_arguments,
                                   add_spec_arguments,
                                   backend_options_from_args,
                                   spec_from_args)
from repro.sweep import cache as cache_mod
from repro.sweep.cache import SweepCache

TINY = dict(workloads=("haswell",), scale=0.003, seeds=2,
            proportions=(0.0, 1.0), strategies=("min", "avg"))


def _results_equal(a, b):
    for k in a:
        if k.startswith("_"):
            continue
        assert a[k] == b[k], k


# ----------------------------------------------------------------------
# spec fingerprints
def test_spec_key_stable_across_instances():
    assert ExperimentSpec(**TINY).key() == ExperimentSpec(**TINY).key()
    # list inputs normalize to the same canonical spec
    lst = dict(TINY, workloads=["haswell"], proportions=[0.0, 1.0],
               strategies=["min", "avg"])
    assert ExperimentSpec(**lst).key() == ExperimentSpec(**TINY).key()


@pytest.mark.parametrize("change", [
    {"scale": 0.004},
    {"seeds": 3},
    {"trace_seed": 1},
    {"engine": "jax"},
    {"proportions": (0.0, 0.5, 1.0)},
    {"strategies": ("min",)},
    {"transform": TransformConfig(e_pref=0.8)},
    {"scenario": ScenarioConfig(walltime_factor=0.0)},
    {"scenario": ScenarioConfig(walltime_jitter=0.5)},
    {"scenario": ScenarioConfig(walltime_jitter=0.5,
                                walltime_dist="uniform")},
    {"scenario": ScenarioConfig(walltime_jitter=0.5, walltime_seed=7)},
    {"scenario": ScenarioConfig(arrival_compression=2.0)},
    {"scenario": ScenarioConfig(backfill_depth=16)},
    {"scenario": ScenarioConfig(queue_order="sjf")},
    {"strategies": ("min", "steal_agreement")},
    {"scenario": ScenarioConfig(job_classes=JobClasses(
        rigid=0.1, on_demand=0.2, malleable=0.7))},
    {"scenario": ScenarioConfig(job_classes=JobClasses(
        on_demand=0.2, malleable=0.8, seed=3))},
])
def test_spec_key_invalidation(change):
    base = ExperimentSpec(**TINY)
    other = dataclasses.replace(base, **change)
    assert other.key() != base.key(), change


def test_dead_scenario_knobs_do_not_invalidate():
    """Knobs that cannot reach the result (jitter seed/dist at zero
    jitter, class seed at default fractions, jitter under a zero factor)
    hash to the canonical default — stored cells stay valid."""
    base = ExperimentSpec(**TINY)
    for dead in (ScenarioConfig(walltime_seed=99),
                 ScenarioConfig(walltime_dist="uniform"),
                 ScenarioConfig(job_classes=JobClasses(seed=42))):
        same = dataclasses.replace(base, scenario=dead)
        assert same.key() == base.key(), dead
        cell = ("min", 1.0, 0)
        assert SweepCache.key(same.cell_fingerprint("haswell", cell)) == \
            SweepCache.key(base.cell_fingerprint("haswell", cell))
    a = dataclasses.replace(base,
                            scenario=ScenarioConfig(walltime_factor=0.0))
    b = dataclasses.replace(base, scenario=ScenarioConfig(
        walltime_factor=0.0, walltime_jitter=2.0, walltime_seed=5))
    assert a.key() == b.key()


def test_spec_key_tracks_engine_version(monkeypatch):
    base = ExperimentSpec(**TINY)
    k0 = base.key()
    monkeypatch.setattr(cache_mod, "DES_ENGINE_VERSION",
                        cache_mod.DES_ENGINE_VERSION + 1)
    assert base.key() != k0


@pytest.mark.parametrize("change", [
    {"scenario": ScenarioConfig(walltime_factor=4.0)},
    {"scenario": ScenarioConfig(arrival_compression=0.5)},
    {"scenario": ScenarioConfig(backfill_depth=8)},
    {"scenario": ScenarioConfig(queue_order="sjf")},
    {"trace_seed": 7},
])
def test_cell_fingerprint_tracks_scenario_axes(change):
    base = ExperimentSpec(**TINY)
    cell = ("min", 1.0, 0)
    k0 = SweepCache.key(base.cell_fingerprint("haswell", cell))
    other = dataclasses.replace(base, **change)
    assert SweepCache.key(other.cell_fingerprint("haswell", cell)) != k0


def test_spec_rejects_bad_inputs():
    with pytest.raises(ValueError):
        ExperimentSpec(workloads=("nope",))
    with pytest.raises(ValueError):
        ExperimentSpec(workloads=("knl",), engine="tpu")
    with pytest.raises(ValueError):
        ExperimentSpec(workloads=("knl",), strategies=("easy",))
    with pytest.raises(ValueError):
        ExperimentSpec(workloads=("knl",), proportions=(1.5,))
    with pytest.raises(ValueError):
        ScenarioConfig(arrival_compression=0.0)
    with pytest.raises(ValueError):  # crosscheck is jax-vs-DES only
        run_experiment(ExperimentSpec(**TINY, engine="des"), crosscheck=2)


def test_rigid_sjf_is_sweepable_and_contributes_one_cell():
    """rigid_sjf is accepted (its queue order distinguishes it from the
    implied rigid-EASY baseline) and, being proportion-invariant,
    contributes exactly one proportion-0 cell regardless of the
    proportion/seed grid."""
    spec = ExperimentSpec(workloads=("knl",), seeds=3,
                          proportions=(0.0, 0.5, 1.0),
                          strategies=("min", "rigid_sjf"))
    cells = spec.cells()
    sjf_cells = [c for c in cells if c[0] == "rigid_sjf"]
    assert sjf_cells == [("rigid_sjf", 0.0, 0)]
    # the malleable strategy still gets the full prop>0 x seed product
    assert len([c for c in cells if c[0] == "min"]) == 2 * 3


def test_registering_a_strategy_does_not_change_default_grid():
    """The sweep grid derives from the registry via an explicit
    paper-five subset: registering a new strategy must not silently grow
    the default grid or move any spec fingerprint (committed artifacts
    stay valid)."""
    from repro.core import strategies as strat_mod
    from repro.core.strategies import (StrategySpec, register_strategy,
                                       registered_strategy_names)

    base = ExperimentSpec(**TINY)
    k0, cells0 = base.key(), base.cells()
    fp0 = base.cell_fingerprint("haswell", ("min", 1.0, 0))
    probe = StrategySpec(name="probe_xyz", malleable=True,
                         structure="stealing", steal_margin=1)
    register_strategy(probe)
    try:
        assert "probe_xyz" in registered_strategy_names(sweepable_only=True)
        fresh = ExperimentSpec(**TINY)
        assert fresh.key() == k0
        assert fresh.cells() == cells0
        assert fresh.cell_fingerprint("haswell", ("min", 1.0, 0)) == fp0
        # defaults are the pinned paper grid, not "everything registered"
        assert fresh.strategies == ("min", "avg")
        assert "probe_xyz" not in ExperimentSpec(
            workloads=("haswell",)).strategies
        # but an explicit opt-in works end to end
        opted = ExperimentSpec(workloads=("haswell",), seeds=1,
                               proportions=(1.0,),
                               strategies=("probe_xyz",))
        assert ("probe_xyz", 1.0, 0) in opted.cells()
        # re-registering the same name is an error, not a silent replace
        with pytest.raises(ValueError):
            register_strategy(probe)
    finally:
        del strat_mod.STRATEGIES["probe_xyz"]


def test_engine_version_bump_is_per_cell_not_store_wide(tmp_path):
    """An engine-version bump must invalidate cells going forward while
    leaving cells stored under the old fingerprint readable — a stacked
    bump (new strategies added, version raised) cannot wipe the store."""
    spec = ExperimentSpec(**dict(TINY, seeds=1, strategies=("min",)))
    run_experiment(spec, cache_dir=tmp_path, verbose=False)
    store = SweepCache(tmp_path)
    cell = ("min", 1.0, 0)
    old_fp = spec.cell_fingerprint("haswell", cell)
    assert store.get(old_fp) is not None

    import unittest.mock as mock
    with mock.patch.object(cache_mod, "DES_ENGINE_VERSION",
                           cache_mod.DES_ENGINE_VERSION + 1):
        new_fp = spec.cell_fingerprint("haswell", cell)
        assert SweepCache.key(new_fp) != SweepCache.key(old_fp)
        # new-version cells miss (they must be recomputed) ...
        assert store.get(new_fp) is None
        # ... but the old-fingerprint cells remain readable in place
        assert store.get(old_fp) is not None


# ----------------------------------------------------------------------
# scenario workload transforms
def test_apply_scenario_axes():
    w = traces.generate("haswell", seed=0, scale=0.003)
    # identity: default scenario returns the same object (no copy)
    assert apply_scenario(w, ScenarioConfig()) is w
    sc = apply_scenario(w, ScenarioConfig(walltime_factor=0.0,
                                          arrival_compression=2.0))
    np.testing.assert_allclose(sc.submit, w.submit / 2.0)
    assert np.all(np.diff(sc.submit) >= 0)  # FCFS order preserved
    np.testing.assert_allclose(sc.walltime, sc.runtime)  # exact estimates
    sc.validate()
    wide = apply_scenario(w, ScenarioConfig(walltime_factor=4.0))
    np.testing.assert_allclose(wide.walltime / wide.runtime, 2.0)
    assert w.walltime[0] == pytest.approx(1.25 * w.runtime[0])  # untouched
    jit = apply_scenario(w, ScenarioConfig(walltime_jitter=1.0))
    jit.validate()
    ratios = jit.walltime / jit.runtime
    assert ratios.std() > 0  # heterogeneous estimates
    assert np.all(ratios >= 1.0)
    # deterministic: the jitter is part of the scenario identity
    again = apply_scenario(w, ScenarioConfig(walltime_jitter=1.0))
    np.testing.assert_array_equal(jit.walltime, again.walltime)


@pytest.mark.parametrize("fracs", [
    (0.0, 0.0), (0.3, 0.3), (0.25, 0.5), (1.0, 0.0), (0.0, 1.0),
    (0.123, 0.456),
])
def test_job_classes_fractions_partition_every_job_once(fracs):
    """Fractions summing to 1 place every job in exactly one class, with
    class sizes matching the rounded fractions."""
    rigid, od = fracs
    jc = JobClasses(rigid=rigid, on_demand=od,
                    malleable=1.0 - rigid - od, seed=11)
    for n in (1, 7, 100, 997):
        cls = assign_job_classes(n, jc)
        assert cls.shape == (n,)
        k_r = int(round(rigid * n))
        k_od = min(int(round(od * n)), n - k_r)
        counts = {c: int(np.sum(cls == c)) for c in
                  (CLASS_NORMAL, CLASS_RIGID, CLASS_ON_DEMAND)}
        assert counts[CLASS_RIGID] == k_r
        assert counts[CLASS_ON_DEMAND] == k_od
        # partition: the three classes cover every job exactly once
        assert sum(counts.values()) == n
        # deterministic: same seed, same assignment
        np.testing.assert_array_equal(cls, assign_job_classes(n, jc))


def test_job_classes_fractions_must_sum_to_one():
    with pytest.raises(ValueError):
        JobClasses(rigid=0.5, on_demand=0.2, malleable=0.5)
    with pytest.raises(ValueError):
        JobClasses(rigid=-0.1, on_demand=0.0, malleable=1.1)


def test_class_pinned_jobs_never_transformed():
    """Even at proportion 1.0, rigid/on-demand-class jobs stay rigid, and
    the batched transform agrees with the per-cell one bit-for-bit."""
    from repro.core import transform_rigid_to_malleable
    from repro.core.speedup import batched_malleable_params

    w = traces.generate("haswell", seed=0, scale=0.003)
    sc = ScenarioConfig(job_classes=JobClasses(
        rigid=0.2, on_demand=0.3, malleable=0.5, seed=5))
    wc = apply_scenario(w, sc)
    wm = transform_rigid_to_malleable(wc, 1.0, seed=0, cluster_nodes=512)
    assert not np.any(wm.malleable & (wc.job_class != CLASS_NORMAL))
    assert np.all(wm.malleable[wc.job_class == CLASS_NORMAL])
    wm.validate(512)
    params = batched_malleable_params(wc, [(1.0, 0)], 512)
    np.testing.assert_array_equal(params["malleable"][0], wm.malleable)
    np.testing.assert_array_equal(params["min_nodes"][0], wm.min_nodes)


def test_walltime_dist_named_distributions():
    w = traces.generate("haswell", seed=0, scale=0.003)
    for dist in ("lognormal", "uniform", "exact_frac"):
        sc = ScenarioConfig(walltime_jitter=0.5, walltime_dist=dist)
        out = apply_scenario(w, sc)
        out.validate()
        assert np.all(out.walltime >= out.runtime)
        # deterministic (spec-seeded), and seeds change the draw
        again = apply_scenario(w, sc)
        np.testing.assert_array_equal(out.walltime, again.walltime)
        other = apply_scenario(w, dataclasses.replace(
            sc, walltime_seed=123))
        assert np.any(out.walltime != other.walltime)
    # exact_frac: jitter is the fraction of jobs with exact estimates
    sc = ScenarioConfig(walltime_jitter=0.5, walltime_dist="exact_frac")
    out = apply_scenario(w, sc)
    frac = float(np.mean(out.walltime == out.runtime))
    assert 0.3 < frac < 0.7
    with pytest.raises(ValueError):
        ScenarioConfig(walltime_dist="cauchy")


_CONTENDED = dict(workloads=("theta",), scale=0.05, seeds=1,
                  proportions=(0.0,), strategies=("min",))


def test_uniform_walltime_factor_is_schedule_invariant():
    """The twins pad walltime uniformly (125% rule), and a global rescale
    of homogeneous slack cancels out of every EASY shadow/fit comparison
    — the schedule, and hence the metrics, are bit-identical."""
    base = ExperimentSpec(
        **_CONTENDED,
        scenario=ScenarioConfig(arrival_compression=6.0))
    wide = dataclasses.replace(base, scenario=ScenarioConfig(
        arrival_compression=6.0, walltime_factor=40.0))
    a = run_experiment(base, verbose=False)["theta"]["rigid"]
    b = run_experiment(wide, verbose=False)["theta"]["rigid"]
    assert a["wait_mean"] > 60.0  # the grid is actually contended
    assert a == b


def test_walltime_jitter_changes_backfill_schedule():
    """Heterogeneous estimates (some tight, some padded) change which
    candidates EASY backfills — the Chadha-style accuracy axis."""
    base = ExperimentSpec(
        **_CONTENDED,
        scenario=ScenarioConfig(arrival_compression=6.0))
    jit = dataclasses.replace(base, scenario=ScenarioConfig(
        arrival_compression=6.0, walltime_jitter=1.5))
    a = run_experiment(base, verbose=False)["theta"]["rigid"]
    b = run_experiment(jit, verbose=False)["theta"]["rigid"]
    assert a["wait_mean"] != b["wait_mean"]


# ----------------------------------------------------------------------
# cell store: resume, incremental reuse, determinism
def test_des_store_hit_on_second_run(tmp_path):
    spec = ExperimentSpec(**TINY)
    first = run_experiment(spec, cache_dir=tmp_path, verbose=False)
    again = run_experiment(spec, cache_dir=tmp_path, verbose=False)
    info = again["haswell"]["_engine"]
    assert info["computed_cells"] == 0
    assert info["cache_hits"] == len(spec.cells())
    _results_equal(first["haswell"], again["haswell"])


def test_store_shared_across_specs_incrementally(tmp_path):
    small = ExperimentSpec(**dict(TINY, strategies=("min",)))
    run_experiment(small, cache_dir=tmp_path, verbose=False)
    grown = ExperimentSpec(**TINY)  # adds the avg lanes
    info = run_experiment(grown, cache_dir=tmp_path,
                          verbose=False)["haswell"]["_engine"]
    assert info["cache_hits"] == len(small.cells())
    assert info["computed_cells"] == len(grown.cells()) - len(small.cells())


def test_parallel_des_matches_serial_bitwise():
    spec = ExperimentSpec(**TINY)
    serial = run_experiment(spec, verbose=False)["haswell"]
    par = run_experiment(spec, backend_options={"workers": 2},
                         verbose=False)["haswell"]
    _results_equal(serial, par)  # exact equality, not approx


# ----------------------------------------------------------------------
# whole-file artifact reuse (the benchmarks/run.py stale-artifact guard)
def test_stale_artifact_from_other_scale_not_reused(tmp_path):
    spec = ExperimentSpec(**TINY)
    results = run_experiment(spec, verbose=False)["haswell"]
    path = tmp_path / "sweep-haswell.json"
    write_artifact(path, results)

    assert load_artifact_results(path, spec, "haswell") is not None
    for stale in (dataclasses.replace(spec, scale=0.004),
                  dataclasses.replace(spec, seeds=3),
                  dataclasses.replace(spec, engine="jax"),
                  dataclasses.replace(
                      spec, scenario=ScenarioConfig(walltime_factor=0.0))):
        assert load_artifact_results(path, stale, "haswell") is None

    # legacy artifact without a spec fingerprint is never reused
    legacy = tmp_path / "sweep-legacy.json"
    payload = json.loads(path.read_text())
    del payload["results"]["_meta"]["spec_key"]
    legacy.write_text(json.dumps(payload))
    assert load_artifact_results(legacy, spec, "haswell") is None


def test_incomplete_artifact_never_reused(tmp_path):
    """Partial metrics (jax step-budget cutoff) must not be replayed."""
    spec = ExperimentSpec(**TINY)
    results = run_experiment(spec, verbose=False)["haswell"]
    assert results["_engine"]["incomplete_cells"] == 0
    results["_engine"]["incomplete_cells"] = 3  # as backend_jax reports
    path = tmp_path / "sweep-haswell.json"
    write_artifact(path, results)
    assert load_artifact_results(path, spec, "haswell") is None


def test_crosscheck_reads_des_cells_from_store(tmp_path):
    """The crosscheck reuses DES reference cells the store already holds
    (and writes the ones it computes)."""
    from repro.experiments.crosscheck import crosscheck_cells
    des_spec = ExperimentSpec(**TINY, engine="des")
    run_experiment(des_spec, cache_dir=tmp_path, verbose=False)
    store = SweepCache(tmp_path)
    jax_spec = dataclasses.replace(des_spec, engine="jax")
    # feed the DES metrics in as the "engine" results: deltas are zero,
    # and every reference must come from the store, not a re-simulation
    metrics = {cell: store.get(des_spec.cell_fingerprint("haswell", cell))
               for cell in des_spec.cells()}
    store.hits = 0
    report = crosscheck_cells(jax_spec, "haswell", metrics, n_cells=3,
                              store=store, verbose=False)
    assert report["store_hits"] == 3
    assert report["all_within_tolerance"]
    # an empty sample verified nothing: the gate must fail, not pass
    empty = crosscheck_cells(jax_spec, "haswell", {}, n_cells=3,
                             store=store, verbose=False)
    assert not empty["all_within_tolerance"]


# ----------------------------------------------------------------------
# CLI wiring: scenario axes sweepable on both engines
@pytest.mark.parametrize("engine", ["des", "jax"])
def test_cli_roundtrip_scenario_axes(engine):
    import argparse
    ap = argparse.ArgumentParser()
    add_spec_arguments(ap)
    add_backend_arguments(ap)
    args = ap.parse_args([
        "--workload", "knl", "--engine", engine, "--scale", "0.01",
        "--walltime-factor", "0.5", "--walltime-jitter", "0.8",
        "--arrival-compression", "3.0",
        "--backfill-depth", "64", "--workers", "2", "--window", "32"])
    spec = spec_from_args(args)
    assert spec.engine == engine
    assert spec.scenario == ScenarioConfig(walltime_factor=0.5,
                                           walltime_jitter=0.8,
                                           arrival_compression=3.0,
                                           backfill_depth=64)
    opts = backend_options_from_args(args)
    assert opts["workers"] == 2 and opts["window"] == 32
    # execution flags left out stay out: the engine's defaults hold
    assert not ({"chunk", "chunk_lanes", "devices", "expand_backend"}
                & set(opts))


def test_cli_default_backfill_depth_matches_des_default():
    import inspect
    from repro.core.simulator import Simulator
    sig = inspect.signature(Simulator.__init__)
    assert sig.parameters["backfill_depth"].default == DEFAULT_BACKFILL_DEPTH


# ----------------------------------------------------------------------
# backend parity through the same spec entry point
def test_jax_des_backend_parity_same_spec(tmp_path):
    from repro.experiments.crosscheck import CROSSCHECK_TOLERANCES
    base = dict(TINY, seeds=1, strategies=("min", "keeppref"))
    des = run_experiment(ExperimentSpec(**base, engine="des"),
                         cache_dir=tmp_path / "store",
                         verbose=False)["haswell"]
    jx = run_experiment(ExperimentSpec(**base, engine="jax"),
                        cache_dir=tmp_path / "store",
                        backend_options={"window": 32, "chunk": 64},
                        verbose=False)["haswell"]
    assert des["_meta"]["spec_key"] != jx["_meta"]["spec_key"]
    for cell_key in ("rigid", "min@100", "keeppref@100"):
        suffix = "" if cell_key == "rigid" else "_mean"
        for metric, (rtol, atol) in CROSSCHECK_TOLERANCES.items():
            a = des[cell_key][metric + suffix]
            b = jx[cell_key][metric + suffix]
            assert abs(b - a) <= max(rtol * abs(a), atol), (cell_key, metric)
    # both engines wrote their cells through the same store
    store = SweepCache(tmp_path / "store")
    spec_jax = ExperimentSpec(**base, engine="jax")
    spec_des = ExperimentSpec(**base, engine="des")
    for spec in (spec_des, spec_jax):
        for cell in spec.cells():
            assert store.get(spec.cell_fingerprint("haswell", cell)) \
                is not None, (spec.engine, cell)


@pytest.mark.parametrize("scenario", [
    ScenarioConfig(backfill_depth=2, arrival_compression=4.0),
    ScenarioConfig(job_classes=JobClasses(
        on_demand=0.3, malleable=0.7), arrival_compression=4.0),
    ScenarioConfig(queue_order="sjf", arrival_compression=4.0),
])
def test_jax_des_parity_on_scenario_axes(scenario):
    """The depth-bounded scan and the job-class queue priority stay within
    the documented engine tolerances on a contended depth-swept spec —
    the axes are engine-faithful, not DES-only."""
    from repro.experiments.crosscheck import CROSSCHECK_TOLERANCES
    base = dict(workloads=("haswell",), scale=0.003, seeds=1,
                proportions=(0.0, 1.0), strategies=("min",),
                scenario=scenario)
    des = run_experiment(ExperimentSpec(**base, engine="des"),
                         verbose=False)["haswell"]
    jx = run_experiment(ExperimentSpec(**base, engine="jax"),
                        backend_options={"window": 32, "chunk": 64},
                        verbose=False)["haswell"]
    for cell_key in ("rigid", "min@100"):
        suffix = "" if cell_key == "rigid" else "_mean"
        for metric, (rtol, atol) in CROSSCHECK_TOLERANCES.items():
            a = des[cell_key][metric + suffix]
            b = jx[cell_key][metric + suffix]
            assert abs(b - a) <= max(rtol * abs(a), atol), (cell_key,
                                                            metric)


def test_backfill_depth_changes_results_through_spec():
    """A depth-swept spec changes metrics on BOTH engines (regression:
    the batched engine used to ignore the axis)."""
    for engine in ("des", "jax"):
        base = ExperimentSpec(
            workloads=("theta",), scale=0.05, seeds=1, engine=engine,
            proportions=(0.0,), strategies=("min",),
            scenario=ScenarioConfig(arrival_compression=6.0))
        shallow = dataclasses.replace(base, scenario=ScenarioConfig(
            arrival_compression=6.0, backfill_depth=1))
        a = run_experiment(base, verbose=False)["theta"]["rigid"]
        b = run_experiment(shallow, verbose=False)["theta"]["rigid"]
        assert a["wait_mean"] != b["wait_mean"], engine


def test_incomplete_lanes_split_from_computed(monkeypatch, tmp_path):
    """Lanes cut off by the step budget count as incomplete, not
    computed, so resume summaries cannot overstate coverage."""
    from repro.core.jobs import DONE
    from repro.sweep import shard

    # the backend drives the engine through the chunked stream, whose
    # per-chunk engine entry is shard.simulate_lanes
    real = shard.simulate_lanes

    def cut_first_lane(batch, cfg, **kw):
        res = real(batch, cfg, **kw)
        res["state"] = np.array(res["state"])
        res["state"][0, -1] = 2  # pretend lane 0 never finished
        res["finished"] = bool(np.all(res["state"] == DONE))
        return res

    monkeypatch.setattr(shard, "simulate_lanes", cut_first_lane)
    spec = ExperimentSpec(**dict(TINY, seeds=1, strategies=("min",)),
                          engine="jax")
    results = run_experiment(spec, cache_dir=tmp_path,
                             verbose=False)["haswell"]
    info = results["_engine"]
    n_cells = len(spec.cells())
    assert info["incomplete_cells_total"] >= 1
    assert info["computed_cells"] == n_cells - \
        info["incomplete_cells_total"]
    assert info["incomplete_cells"] == info["incomplete_cells_total"]
    # incomplete cells were not written to the store
    store = SweepCache(tmp_path)
    stored = sum(store.get(spec.cell_fingerprint("haswell", c))
                 is not None for c in spec.cells())
    assert stored == info["computed_cells"]


def test_compare_scenarios_reporter(tmp_path, capsys):
    """--compare-scenarios sweeps one axis and renders the sensitivity
    table; the artifact holds one result set per value."""
    from repro.experiments import __main__ as exp_main

    out = tmp_path / "sens.json"
    rc = exp_main.main([
        "--workload", "haswell", "--scale", "0.003", "--seeds", "1",
        "--proportions", "0.0", "1.0", "--strategies", "min",
        "--engine", "des", "--cache-dir", str(tmp_path / "store"),
        "--compare-scenarios", "backfill_depth",
        "--scenario-values", "1", "256", "--out", str(out)])
    assert rc == 0
    text = capsys.readouterr().out
    assert "Scenario sensitivity" in text
    assert "backfill_depth=1" in text and "backfill_depth=256" in text
    payload = json.loads(out.read_text())
    assert payload["axis"] == "backfill_depth"
    assert set(payload["results"]) == {"1.0", "256.0"}
    for res in payload["results"].values():
        assert "rigid" in res["haswell"]


def test_scenario_variant_axes():
    from repro.experiments import scenario_variant
    base = ScenarioConfig()
    v = scenario_variant(base, "on_demand_frac", 0.4)
    assert v.job_classes == JobClasses(rigid=0.0, on_demand=0.4,
                                       malleable=0.6)
    v = scenario_variant(base, "backfill_depth", 4)
    assert v.backfill_depth == 4 and isinstance(v.backfill_depth, int)
    v = scenario_variant(base, "queue_order", "sjf")
    assert v.queue_order == "sjf"
    with pytest.raises(ValueError):
        scenario_variant(base, "nope", 1.0)


def test_compare_scenarios_categorical_axis(tmp_path, capsys):
    """The queue_order axis sweeps categorically: string keys survive the
    reporter and the artifact round-trip (numeric axes keep float keys —
    covered by test_compare_scenarios_reporter)."""
    from repro.experiments import __main__ as exp_main

    out = tmp_path / "sens-qo.json"
    rc = exp_main.main([
        "--workload", "haswell", "--scale", "0.003", "--seeds", "1",
        "--proportions", "0.0", "1.0", "--strategies", "min",
        "--engine", "des", "--cache-dir", str(tmp_path / "store"),
        "--compare-scenarios", "queue_order",
        "--scenario-values", "fcfs", "sjf", "--out", str(out)])
    assert rc == 0
    text = capsys.readouterr().out
    assert "queue_order=fcfs" in text and "queue_order=sjf" in text
    payload = json.loads(out.read_text())
    assert payload["axis"] == "queue_order"
    assert set(payload["results"]) == {"fcfs", "sjf"}
    for res in payload["results"].values():
        assert "rigid" in res["haswell"]


# ----------------------------------------------------------------------
# the persistent compilation cache: placed from outside, or a fixed path
def test_xla_cache_env_var_stands_else_fixed_repo_path(monkeypatch,
                                                       tmp_path):
    import pathlib

    import jax

    from repro import xla_cache

    repo = pathlib.Path(__file__).resolve().parents[1]
    assert xla_cache.DEFAULT_DIR == repo / "artifacts" / "xla_cache"
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv(xla_cache.ENV_VAR, str(tmp_path / "outside"))
    assert xla_cache.enable_compilation_cache() == tmp_path / "outside"
    assert updates == []  # JAX's own setting stands
    monkeypatch.delenv(xla_cache.ENV_VAR)
    monkeypatch.setattr(xla_cache, "DEFAULT_DIR", tmp_path / "fixed")
    assert xla_cache.enable_compilation_cache() == tmp_path / "fixed"
    assert updates == [("jax_compilation_cache_dir",
                        str(tmp_path / "fixed"))]


def test_cold_xla_cache_never_deletes_the_env_directory(monkeypatch,
                                                        tmp_path):
    from benchmarks import run as bench_run

    from repro import xla_cache

    outside = tmp_path / "outside"
    outside.mkdir()
    (outside / "entry").write_text("compiled")
    monkeypatch.setenv(xla_cache.ENV_VAR, str(outside))
    with pytest.raises(SystemExit):
        bench_run.main(["--cold-xla-cache", "--engine", "jax"])
    assert (outside / "entry").exists()
