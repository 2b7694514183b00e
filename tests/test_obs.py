"""Tests for the flight recorder (repro.obs) and its pipeline wiring.

Covers the span tracer (nesting, thread-safety, Chrome trace-event
validity), the counters registry, heartbeat ETA math, the guarantee that
observability never leaks into spec/cell fingerprints or results
(tracing-on == tracing-off bit-identity), the perf-regression gate
(tools/check_perf.py), and cross-engine agreement of the scheduling
counters (device-accumulated jax vs post-hoc DES).
"""
import importlib.util
import io
import json
import pathlib
import threading

import numpy as np
import pytest

from repro import obs
from repro.core.metrics import aggregate_seeds, backfill_starts
from repro.experiments import ExperimentSpec, run_experiment

REPO = pathlib.Path(__file__).resolve().parents[1]

TINY = dict(workloads=("haswell",), scale=0.003, seeds=2,
            proportions=(0.0, 1.0), strategies=("min", "avg"))


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts and ends with the disabled default tracer."""
    tracer = obs.get_tracer()
    tracer.reset()
    obs.configure(enabled=False)
    yield tracer
    tracer.reset()
    obs.configure(enabled=False)


# ----------------------------------------------------------------------
# span tracer
def test_disabled_tracer_records_nothing():
    with obs.span("outer"):
        obs.counter("hits")
    t = obs.get_tracer()
    assert t.events() == []
    assert t.counters.snapshot() == {"counters": {}, "gauges": {}}


def test_disabled_span_is_shared_noop_singleton():
    # the hot-path contract: no allocation per disabled span
    assert obs.span("a") is obs.span("b")


def test_span_nesting_records_parents():
    obs.configure(enabled=True)
    with obs.span("outer"):
        with obs.span("inner"):
            pass
    evs = obs.get_tracer().events()
    # inner exits (and records) first
    by_name = {e["name"]: e for e in evs}
    assert by_name["inner"]["args"]["parent"] == "outer"
    assert by_name["outer"]["args"]["parent"] is None
    assert by_name["outer"]["dur"] >= by_name["inner"]["dur"]


def test_span_thread_safety():
    obs.configure(enabled=True)
    n_threads, n_spans = 8, 50
    errors = []

    def work(tid):
        try:
            for i in range(n_spans):
                with obs.span("outer", thread=tid):
                    with obs.span("inner", i=i):
                        obs.counter("work")
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    evs = obs.get_tracer().events()
    assert len(evs) == n_threads * n_spans * 2
    # nesting is tracked per thread: every inner's parent is outer
    inner = [e for e in evs if e["name"] == "inner"]
    assert all(e["args"]["parent"] == "outer" for e in inner)
    assert obs.get_tracer().counters.get("work") == n_threads * n_spans


def test_chrome_trace_event_validity(tmp_path):
    obs.configure(enabled=True)
    with obs.span("a", detail=1):
        with obs.span("b"):
            pass
    trace = tmp_path / "trace.json"
    jsonl = tmp_path / "trace.jsonl"
    obs.flush(trace_path=trace, jsonl_path=jsonl)

    loaded = json.loads(trace.read_text())
    assert isinstance(loaded, list) and loaded
    for ev in loaded:
        assert ev["ph"] in ("B", "E", "X")
        assert isinstance(ev["name"], str)
        for k in ("ts", "dur"):
            assert isinstance(ev[k], (int, float)) and ev[k] >= 0
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)

    lines = [json.loads(line) for line in jsonl.read_text().splitlines()]
    assert [line["kind"] for line in lines] == ["span", "span", "counters"]


def test_counters_and_gauges():
    obs.configure(enabled=True)
    obs.counter("hits")
    obs.counter("hits", 2)
    obs.gauge("depth", 7.0)
    snap = obs.get_tracer().counters.snapshot()
    assert snap["counters"]["hits"] == 3
    assert snap["gauges"]["depth"] == 7.0
    obs.get_tracer().reset()
    assert obs.get_tracer().counters.snapshot() == {"counters": {},
                                                    "gauges": {}}


# ----------------------------------------------------------------------
# heartbeat
def test_eta_math():
    assert np.isnan(obs.eta_seconds(0, 10, 5.0))
    assert obs.eta_seconds(2, 10, 20.0) == pytest.approx(80.0)
    assert obs.eta_seconds(10, 10, 20.0) == 0.0
    assert obs.format_duration(float("nan")) == "--"
    assert obs.format_duration(12) == "12s"
    assert obs.format_duration(247) == "4m07s"
    assert obs.format_duration(3720) == "1h02m"


def test_heartbeat_lines_and_eta():
    now = [0.0]
    out = io.StringIO()
    hb = obs.Heartbeat(4, label="test", unit="chunk", enabled=True,
                       stream=out, clock=lambda: now[0])
    now[0] = 10.0
    hb.tick(cells_flushed=3)
    now[0] = 20.0
    hb.tick(cells_flushed=2)
    lines = out.getvalue().splitlines()
    assert len(lines) == 2
    assert "chunk 1/4" in lines[0] and "eta 30s" in lines[0]
    assert "chunk 2/4" in lines[1] and "cells 5" in lines[1]
    assert "eta 20s" in lines[1]


def test_heartbeat_disabled_prints_nothing():
    out = io.StringIO()
    hb = obs.Heartbeat(4, enabled=False, stream=out)
    hb.tick()
    assert out.getvalue() == ""


# ----------------------------------------------------------------------
# the profiler's clock and the engine loop's spans
def test_enabled_span_writes_a_profiler_host_event(tmp_path):
    import sys
    import time

    import jax

    sys.path.insert(0, str(REPO))
    from bench.lib import xplane

    jax.profiler.start_trace(str(tmp_path))
    try:
        obs.configure(enabled=True)
        with obs.span("x.y", detail=1):
            time.sleep(0.002)
        obs.configure(enabled=False)
        with obs.span("x.off"):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    profile = xplane.load(xplane.find_xplane(str(tmp_path)))
    names = [name for name, _, _ in xplane.host_events(profile)]
    # the bare span name, with no args folded into it
    assert names.count("x.y") == 1
    assert not any(n.startswith("x.off") or n.startswith("x.y#")
                   for n in names)


def test_obs_imports_and_traces_without_jax():
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from repro import obs\n"
        "obs.configure(enabled=True)\n"
        "with obs.span('a'):\n"
        "    pass\n"
        "assert [e['name'] for e in obs.get_tracer().events()] == ['a']\n"
        "assert 'jax' not in sys.modules\n")
    env = {"PYTHONPATH": str(REPO / "src"), "PATH": ""}
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr


def test_span_carries_the_pid_of_the_process_that_recorded_it():
    """The pid is read once per process, and again in a forked child."""
    import subprocess
    import sys

    code = (
        "import os\n"
        "from repro.obs.trace import Tracer\n"
        "t = Tracer(enabled=True)\n"
        "r, w = os.pipe()\n"
        "child = os.fork()\n"
        "if child == 0:\n"
        "    with t.span('x.y'):\n"
        "        pass\n"
        "    os.write(w, str(t.events()[-1]['pid']).encode())\n"
        "    os._exit(0)\n"
        "os.waitpid(child, 0)\n"
        "assert int(os.read(r, 64)) == child\n"
        "with t.span('x.y'):\n"
        "    pass\n"
        "assert t.events()[-1]['pid'] == os.getpid()\n")
    env = {"PYTHONPATH": str(REPO / "src"), "PATH": ""}
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr


def test_engine_loop_spans_bracket_each_chunk_call():
    from repro.core import STRATEGIES, Workload
    from repro.sweep.batch import EngineConfig, build_lanes, simulate_lanes

    rng = np.random.default_rng(0)
    w = Workload.rigid(submit=np.sort(rng.uniform(0, 150, 20)),
                       runtime=rng.uniform(20, 120, 20),
                       nodes_req=rng.choice([1, 2, 4, 8], 20))
    batch, _ = build_lanes(w, 10, [(STRATEGIES["easy"], 0.0, 0),
                                   (STRATEGIES["min"], 0.6, 0)])
    obs.configure(enabled=True)
    simulate_lanes(batch, EngineConfig(window=4, chunk=16))
    evs = obs.get_tracer().events()

    def intervals(*names):
        return [(e["ts"], e["ts"] + e["dur"]) for e in evs
                if e["name"] in names]

    calls = intervals("sweep.compile", "sweep.execute")
    peeks = intervals("sweep.peek")
    dispatches = intervals("sweep.dispatch")
    assert len(calls) >= 2
    assert len(dispatches) == len(calls)
    assert len(peeks) >= len(calls)
    for lo, hi in dispatches:
        assert any(a <= lo and hi <= b for a, b in calls)
    for lo, hi in peeks:
        assert all(hi <= a or b <= lo for a, b in calls)
    assert all(e["args"]["window"] >= 4 and e["args"]["lanes"] == 2
               for e in evs if e["name"] == "sweep.peek")


def test_wait_on_a_background_compile_is_compile_time(monkeypatch):
    """A chunk call that blocks on its rung's in-flight AOT compile counts
    the wait inside its ``sweep.compile`` span and in ``compile_s``."""
    import concurrent.futures
    import time

    from repro.core import STRATEGIES, Workload
    from repro.sweep import batch as batch_mod

    monkeypatch.setattr(batch_mod, "_COMPILED_KEYS", set())
    monkeypatch.setattr(batch_mod, "_WARM_EXECUTABLES", {})
    monkeypatch.setattr(batch_mod, "_WARM_FUTURES", {})

    class InFlight(concurrent.futures.Future):
        """A background compile still running when the engine asks."""

        def __init__(self, compile_fn):
            super().__init__()
            self.compile_fn = compile_fn

        def done(self):
            return False

        def result(self, timeout=None):
            time.sleep(0.3)
            return self.compile_fn()

    class Pool:
        def submit(self, fn):
            return InFlight(fn)

    monkeypatch.setattr(batch_mod, "_warm_pool", Pool)
    rng = np.random.default_rng(0)
    w = Workload.rigid(submit=np.sort(rng.uniform(0, 150, 20)),
                       runtime=rng.uniform(20, 120, 20),
                       nodes_req=rng.choice([1, 2, 4, 8], 20))
    batch, _ = batch_mod.build_lanes(w, 10, [(STRATEGIES["easy"], 0.0, 0)])
    obs.configure(enabled=True)
    # a pinned floor of 4 escalates at once to a rung warmed off-thread
    res = batch_mod.simulate_lanes(batch, batch_mod.EngineConfig(
        window=4, chunk=16))
    assert res["warm_hits"] >= 1
    waited = [e for e in obs.get_tracer().events()
              if e["name"] == "sweep.compile" and e["args"]["window"] > 4]
    assert waited and all(e["dur"] >= 0.3e6 for e in waited)
    assert res["compile_s"] >= 0.3 * len(waited)


# ----------------------------------------------------------------------
# observability never leaks into identity or results
def test_fingerprints_identical_with_tracing_on_and_off():
    spec = ExperimentSpec(**TINY)
    cells = spec.cells()
    off = {c: spec.cell_fingerprint("haswell", c) for c in cells}
    obs.configure(enabled=True)
    with obs.span("outer"):
        on = {c: spec.cell_fingerprint("haswell", c) for c in cells}
    assert on == off
    assert spec.key() == ExperimentSpec(**TINY).key()
    # scheduling counters are execution-side: never part of the identity
    assert "sched" not in json.dumps(next(iter(off.values())))


def test_des_results_identical_with_tracing_on_and_off():
    spec = ExperimentSpec(**TINY, engine="des")
    off = run_experiment(spec, verbose=False)
    obs.configure(enabled=True)
    on = run_experiment(spec, verbose=False)
    a, b = off["haswell"], on["haswell"]
    for label in a:
        if label.startswith("_"):
            continue
        assert a[label] == b[label], label
    # and the run actually traced something
    assert any(e["name"] == "des.cell" for e in obs.get_tracer().events())


def test_cell_metrics_carry_sched_counters():
    spec = ExperimentSpec(**TINY, engine="des")
    res = run_experiment(spec, verbose=False)["haswell"]
    for k in ("sched_backfill_starts", "sched_shrink_events",
              "sched_expand_events", "sched_invocations"):
        assert k in res["rigid"]
        assert f"{k}_mean" in res["min@100"]


def test_aggregate_seeds_tolerates_missing_sched_keys():
    # a cell replayed from an older store lacks the sched_ keys: the
    # aggregate must degrade that key to nan, not KeyError
    old = {"wait_mean": 1.0}
    new = {"wait_mean": 2.0, "sched_backfill_starts": 5.0}
    agg = aggregate_seeds([old, new])
    assert agg["wait_mean_mean"] == 1.5
    assert agg["sched_backfill_starts_mean"] == 5.0


# ----------------------------------------------------------------------
# backfill counter: definition + cross-engine agreement
def test_backfill_starts_definition():
    submit = np.array([0.0, 1.0, 2.0])
    # in-order starts: nothing jumped
    assert backfill_starts(submit, np.array([0.0, 5.0, 6.0])) == 0
    # job 2 starts while job 1 still waits
    assert backfill_starts(submit, np.array([0.0, 5.0, 3.0])) == 1
    # a never-started earlier job counts as +inf: both later jobs jumped it
    assert backfill_starts(submit, np.array([np.inf, 5.0, 3.0])) == 2
    # simultaneous starts are not jumps (strict <)
    assert backfill_starts(submit, np.array([0.0, 3.0, 3.0])) == 0


def test_scheduling_counter_parity_jax_vs_des():
    """Device-accumulated counters track the DES post-hoc definition.

    The engines' schedules are tolerance-close, not bit-identical (see
    CROSSCHECK_TOLERANCES), so the counters agree to a relative
    tolerance; the grid is chosen so backfill and reconfiguration are
    both nonzero (scale 0.05 is where haswell's queue first backs up).
    """
    base = dict(workloads=("haswell",), scale=0.05, seeds=1,
                proportions=(0.0, 0.5), strategies=("min",))
    jx = run_experiment(ExperimentSpec(**base, engine="jax"),
                        backend_options={"window": 0, "chunk": 160},
                        verbose=False)["haswell"]
    ds = run_experiment(ExperimentSpec(**base, engine="des"),
                        verbose=False)["haswell"]

    def val(res, label, key):
        r = res[label]
        return r.get(f"{key}_mean", r.get(key))

    # the rigid baseline backfills heavily at this scale
    assert val(ds, "rigid", "sched_backfill_starts") > 100
    assert val(ds, "min@50", "sched_shrink_events") > 100
    for label in ("rigid", "min@50"):
        for key in ("sched_backfill_starts", "sched_shrink_events",
                    "sched_expand_events"):
            a, b = val(jx, label, key), val(ds, label, key)
            assert a == pytest.approx(b, rel=0.15, abs=5.0), (label, key)


# ----------------------------------------------------------------------
# perf-regression gate
def _check_perf():
    spec = importlib.util.spec_from_file_location(
        "check_perf", REPO / "tools" / "check_perf.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _timing(tmp_path, name, total_s, **over):
    rec = {"schema_version": 2, "engine": "jax", "scale": 0.05,
           "seeds": 4, "batch_workloads": ["haswell"],
           "total_s": total_s,
           "roofline": {"compile_s": 10.0, "execute_s": total_s - 10.0,
                        "achieved_lane_steps_per_s": 1000.0}}
    rec.update(over)
    p = tmp_path / name
    p.write_text(json.dumps(rec))
    return p


def test_check_perf_pass_fail_tolerance(tmp_path):
    cp = _check_perf()
    base = _timing(tmp_path, "timing-base.json", 100.0)
    baseline = tmp_path / "BENCH.json"
    assert cp.main(["--timing", str(base), "--baseline", str(baseline),
                    "--write-baseline"]) == 0
    assert baseline.exists()

    ok = _timing(tmp_path, "timing-ok.json", 140.0)
    slow = _timing(tmp_path, "timing-slow.json", 200.0)
    very_slow = _timing(tmp_path, "timing-vslow.json", 400.0)
    argv = ["--baseline", str(baseline), "--tolerance", "1.5",
            "--hard-ratio", "3.0"]
    assert cp.main(["--timing", str(ok), *argv]) == 0
    assert cp.main(["--timing", str(slow), *argv]) == 1
    # --warn-only downgrades a tolerance breach ...
    assert cp.main(["--timing", str(slow), *argv, "--warn-only"]) == 0
    # ... but never a hard-ratio breach
    assert cp.main(["--timing", str(very_slow), *argv,
                    "--warn-only"]) == 1
    # a wider tolerance passes the same record (the component gates must
    # be widened too: slow's execute_s regressed along with its total_s)
    assert cp.main(["--timing", str(slow), "--baseline", str(baseline),
                    "--tolerance", "2.5", "--execute-tolerance",
                    "2.5"]) == 0


def test_check_perf_component_gates(tmp_path):
    """compile_s and execute_s are gated separately from total_s."""
    cp = _check_perf()
    base = _timing(tmp_path, "timing-base.json", 100.0)
    baseline = tmp_path / "BENCH.json"
    cp.main(["--timing", str(base), "--baseline", str(baseline),
             "--write-baseline"])
    # compile_s leaks 5x while total_s stays inside tolerance: a retrace
    # leak hidden by a faster execute must still fail the gate
    leak = _timing(tmp_path, "timing-leak.json", 110.0,
                   roofline={"compile_s": 50.0, "execute_s": 60.0})
    assert cp.main(["--timing", str(leak), "--baseline",
                    str(baseline)]) == 1
    assert cp.main(["--timing", str(leak), "--baseline", str(baseline),
                    "--compile-tolerance", "6.0", "--hard-ratio",
                    "8.0"]) == 0
    # execute_s regression with flat compile/total fails on its own gate
    slow_ex = _timing(tmp_path, "timing-slowex.json", 100.0,
                      roofline={"compile_s": 10.0, "execute_s": 140.0})
    assert cp.main(["--timing", str(slow_ex), "--baseline",
                    str(baseline)]) == 1


def test_check_perf_write_baseline_keeps_history(tmp_path):
    cp = _check_perf()
    baseline = tmp_path / "BENCH.json"
    for i, total in enumerate([100.0, 90.0, 80.0]):
        rec = _timing(tmp_path, f"timing-{i}.json", total)
        assert cp.main(["--timing", str(rec), "--baseline", str(baseline),
                        "--write-baseline"]) == 0
    final = json.loads(baseline.read_text())
    assert final["total_s"] == 80.0
    hist = final["history"]
    assert [h["total_s"] for h in hist] == [100.0, 90.0]
    # prior baselines enter history flattened, never nested
    assert all("history" not in h for h in hist)


def test_check_perf_compare_cold(tmp_path):
    """The warm-rerun gate asserts the compile budget collapsed."""
    cp = _check_perf()
    cold = _timing(tmp_path, "timing-cold.json", 60.0,
                   xla_cache_state="cold",
                   roofline={"compile_s": 50.0, "execute_s": 10.0})
    warm = _timing(tmp_path, "timing-warm.json", 13.0,
                   xla_cache_state="warm",
                   roofline={"compile_s": 3.0, "execute_s": 10.0})
    assert cp.main(["--timing", str(warm), "--compare-cold",
                    str(cold)]) == 0
    # a warm rerun that still recompiles most of the grid fails
    lukewarm = _timing(tmp_path, "timing-luke.json", 40.0,
                       xla_cache_state="warm",
                       roofline={"compile_s": 30.0, "execute_s": 10.0})
    assert cp.main(["--timing", str(lukewarm), "--compare-cold",
                    str(cold)]) == 1
    # a record not marked warm cannot pass as a warm rerun
    notwarm = _timing(tmp_path, "timing-notwarm.json", 13.0,
                      roofline={"compile_s": 3.0, "execute_s": 10.0})
    assert cp.main(["--timing", str(notwarm), "--compare-cold",
                    str(cold)]) == 2
    # mismatched grids refuse to compare
    other = _timing(tmp_path, "timing-other.json", 13.0, scale=0.2,
                    xla_cache_state="warm",
                    roofline={"compile_s": 3.0, "execute_s": 10.0})
    assert cp.main(["--timing", str(other), "--compare-cold",
                    str(cold)]) == 2


def test_check_perf_cache_state_is_part_of_the_grid(tmp_path):
    """A warm timing record never compares against a cold baseline."""
    cp = _check_perf()
    base = _timing(tmp_path, "timing-base.json", 100.0)
    baseline = tmp_path / "BENCH.json"
    cp.main(["--timing", str(base), "--baseline", str(baseline),
             "--write-baseline"])
    warm = _timing(tmp_path, "timing-warm.json", 50.0,
                   xla_cache_state="warm")
    assert cp.main(["--timing", str(warm), "--baseline",
                    str(baseline)]) == 2


def test_check_perf_grid_mismatch(tmp_path):
    cp = _check_perf()
    base = _timing(tmp_path, "timing-base.json", 100.0)
    baseline = tmp_path / "BENCH.json"
    cp.main(["--timing", str(base), "--baseline", str(baseline),
             "--write-baseline"])
    other = _timing(tmp_path, "timing-other.json", 100.0, scale=0.2)
    assert cp.main(["--timing", str(other), "--baseline",
                    str(baseline)]) == 2


def test_committed_baseline_matches_its_own_grid():
    """BENCH_sweep.json must stay a valid baseline for the CI grid."""
    baseline = REPO / "BENCH_sweep.json"
    rec = json.loads(baseline.read_text())
    assert rec["engine"] == "jax"
    assert rec["batch_workloads"] == ["haswell"]
    assert rec["total_s"] > 0


# ----------------------------------------------------------------------
# serve-layer observability: record_span + concurrent writers
def test_record_span_from_explicit_start():
    import time

    obs.configure(enabled=True)
    t0 = time.monotonic_ns()
    obs.record_span("serve.query", t0, path="memo")
    evs = obs.get_tracer().events()
    assert len(evs) == 1
    ev = evs[0]
    assert ev["name"] == "serve.query" and ev["ph"] == "X"
    assert ev["dur"] >= 0 and ev["args"]["path"] == "memo"
    # does not touch the per-thread nesting stack
    with obs.span("outer"):
        obs.record_span("serve.query", time.monotonic_ns())
        with obs.span("inner"):
            pass
    by_name = {e["name"]: e for e in obs.get_tracer().events()}
    assert by_name["inner"]["args"]["parent"] == "outer"


def test_record_span_disabled_is_noop():
    import time

    obs.record_span("serve.query", time.monotonic_ns())
    assert obs.get_tracer().events() == []


def test_counters_consistent_under_concurrent_writers():
    """The serve pattern: one dispatcher + N client threads mutating the
    same counters/gauges; totals must be exact, never torn."""
    obs.configure(enabled=True)
    n_clients, n_ops = 8, 200
    start = threading.Barrier(n_clients + 1)

    def client(tid):
        start.wait()
        for i in range(n_ops):
            obs.counter("serve.hit")
            obs.counter("serve.bytes", 3)
            obs.gauge("serve.queue_depth", float(i))

    def dispatcher():
        start.wait()
        for i in range(n_ops):
            obs.counter("serve.batches")
            obs.gauge("serve.coalesce_width", float(i % 16))

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(n_clients)]
    threads.append(threading.Thread(target=dispatcher))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = obs.get_tracer().counters.snapshot()
    assert snap["counters"]["serve.hit"] == n_clients * n_ops
    assert snap["counters"]["serve.bytes"] == 3 * n_clients * n_ops
    assert snap["counters"]["serve.batches"] == n_ops
    assert snap["gauges"]["serve.queue_depth"] == float(n_ops - 1)


def test_trace_export_valid_under_concurrent_span_writers(tmp_path):
    """Chrome-trace JSON stays well-formed when spans + record_span land
    from many threads at once (the dispatcher/client write pattern)."""
    import time

    obs.configure(enabled=True)
    n_threads, n_spans = 6, 40

    def work(tid):
        for i in range(n_spans):
            with obs.span("serve.batch", width=i):
                obs.counter("serve.computed")
            obs.record_span("serve.query", time.monotonic_ns(), tid=tid)

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    trace = tmp_path / "trace.json"
    obs.flush(trace_path=trace)
    loaded = json.loads(trace.read_text())
    assert len(loaded) == n_threads * n_spans * 2
    for ev in loaded:
        assert ev["ph"] == "X"
        assert isinstance(ev["ts"], (int, float))
        assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
    names = {e["name"] for e in loaded}
    assert names == {"serve.batch", "serve.query"}


def test_whatif_engine_obs_counters(tmp_path):
    """The serve engine's counter wiring end-to-end: hit/miss/dedup/
    batches all land in the registry (docs/observability.md)."""
    from repro.experiments.spec import ExperimentSpec
    from repro.serve.whatif import WhatIfEngine, WhatIfQuery

    obs.configure(enabled=True)
    spec = ExperimentSpec(**TINY, engine="des")
    eng = WhatIfEngine(spec, cache_dir=str(tmp_path / "store"),
                       max_batch=4, max_wait_s=0.05, start=False)
    q = WhatIfQuery(strategy="min", proportion=1.0, seed=0)
    f1, f2 = eng.submit(q), eng.submit(q)  # miss + dedup
    eng.start()
    f1.result(timeout=600)
    f2.result(timeout=600)
    eng.query(q, timeout=600)              # memo hit
    eng.close()
    got = obs.get_tracer().counters.snapshot()["counters"]
    assert got["serve.miss"] == 1
    assert got["serve.dedup"] == 1
    assert got["serve.memo_hit"] == 1 and got["serve.hit"] == 1
    assert got["serve.batches"] == 1 and got["serve.computed"] == 1
    spans = {e["name"] for e in obs.get_tracer().events()}
    assert {"serve.batch", "serve.query"} <= spans


# ----------------------------------------------------------------------
# perf gate: serve records (BENCH_serve.json)
def _serve_timing(tmp_path, name, *, total_s=2.0, **serve_over):
    serve = {"clients": 8, "queries": 64, "unique_cells": 40,
             "max_batch": 16, "max_wait_ms": 5.0,
             "cold_p50_ms": 250.0, "cold_p99_ms": 400.0, "cold_qps": 35.0,
             "warm_p50_ms": 0.2, "warm_p99_ms": 5.0, "warm_qps": 2000.0,
             "open_offered_qps": 200.0, "open_achieved_qps": 200.0,
             "open_p50_ms": 0.4, "open_p99_ms": 3.0}
    serve.update(serve_over)
    rec = {"schema_version": 1, "engine": "serve-des", "scale": 0.003,
           "seeds": 2, "batch_workloads": ["haswell"],
           "total_s": total_s, "serve": serve}
    p = tmp_path / name
    p.write_text(json.dumps(rec))
    return p


def test_check_perf_serve_gate(tmp_path):
    """Latency gated upward, throughput gated downward (inverted)."""
    cp = _check_perf()
    baseline = tmp_path / "BENCH_serve.json"
    base = _serve_timing(tmp_path, "serve-base.json")
    assert cp.main(["--timing", str(base), "--baseline", str(baseline),
                    "--write-baseline"]) == 0
    # baseline_from must carry the serve section into the committed file
    assert "serve" in json.loads(baseline.read_text())

    ok = _serve_timing(tmp_path, "serve-ok.json",
                       warm_p99_ms=7.0, warm_qps=1500.0)
    assert cp.main(["--timing", str(ok), "--baseline", str(baseline)]) == 0
    # p99 regression beyond --latency-tolerance fails
    slow = _serve_timing(tmp_path, "serve-slow.json", warm_p99_ms=12.0)
    assert cp.main(["--timing", str(slow), "--baseline",
                    str(baseline)]) == 1
    assert cp.main(["--timing", str(slow), "--baseline", str(baseline),
                    "--warn-only"]) == 0
    # throughput HALVING fails even though every latency got better:
    # the inverted ratio catches qps drops
    slow_tp = _serve_timing(tmp_path, "serve-slowtp.json",
                            warm_qps=800.0)
    assert cp.main(["--timing", str(slow_tp), "--baseline",
                    str(baseline)]) == 1
    # a faster record passes everything
    fast = _serve_timing(tmp_path, "serve-fast.json",
                         warm_p99_ms=2.0, warm_qps=4000.0,
                         cold_qps=70.0)
    assert cp.main(["--timing", str(fast), "--baseline",
                    str(baseline)]) == 0


def test_check_perf_serve_shape_mismatch(tmp_path):
    """Different client/storm shape refuses to compare (exit 2), and a
    serve record never compares against a sweep baseline."""
    cp = _check_perf()
    baseline = tmp_path / "BENCH_serve.json"
    cp.main(["--timing", str(_serve_timing(tmp_path, "serve-base.json")),
             "--baseline", str(baseline), "--write-baseline"])
    other = _serve_timing(tmp_path, "serve-16c.json", clients=16)
    assert cp.main(["--timing", str(other), "--baseline",
                    str(baseline)]) == 2
    # engine tag serve-des != jax: grid mismatch against a sweep baseline
    sweep_baseline = tmp_path / "BENCH_sweep.json"
    cp.main(["--timing", str(_timing(tmp_path, "sweep.json", 100.0)),
             "--baseline", str(sweep_baseline), "--write-baseline"])
    assert cp.main(["--timing",
                    str(_serve_timing(tmp_path, "serve-x.json")),
                    "--baseline", str(sweep_baseline)]) == 2


def test_committed_serve_baseline_matches_benchmark_grid():
    """BENCH_serve.json must stay valid for benchmarks/serve_load.py's
    default (CI serve-smoke) grid: >= 8 clients, p50/p99 + throughput."""
    rec = json.loads((REPO / "BENCH_serve.json").read_text())
    assert rec["engine"] == "serve-des"
    assert rec["batch_workloads"] == ["haswell"]
    serve = rec["serve"]
    assert serve["clients"] >= 8
    for key in ("warm_p50_ms", "warm_p99_ms", "open_p99_ms",
                "warm_qps", "cold_qps"):
        assert serve[key] > 0, key
