"""What-if service: deterministic concurrency harness + parity gates.

Three layers, none relying on real timing:

* **Coalescer mechanics** under an injectable :class:`FakeClock` and
  recording/gated fake executors — "N queries land in one batch",
  "max-wait fires with a partial batch", "mid-batch failure poisons only
  the failing query" are forced deterministically, without sleeps.
* **Query semantics** — delta parsing, cell normalization (proportion 0
  *is* the rigid baseline; non-malleable strategies collapse to their
  single cell), scenario override threading, admission-queue bounds,
  dedup of identical in-flight queries, close/cancel behaviour.
* **Parity** — results served through the engine (hit, single miss,
  coalesced miss, any submission order) are bit-identical to a direct
  :func:`repro.experiments.run.run_experiment` on the same spec, on both
  engines.  Random-interleaving order-independence is additionally
  property-tested in ``tests/test_serve_whatif_properties.py``.
"""
import threading

import pytest

from repro.experiments.spec import ExperimentSpec
from repro.serve.whatif import (EngineClosedError, QueryFailedError,
                                QueueFullError, WhatIfEngine, WhatIfQuery,
                                sample_queries)

BASE = dict(workloads=("haswell",), scale=0.003, seeds=2, engine="des")


def base_spec(**over) -> ExperimentSpec:
    return ExperimentSpec(**{**BASE, **over})


# ----------------------------------------------------------------------
# harness: fake clock + fake executors
class FakeClock:
    """Stepped fake time.  ``wait`` keeps a short *real* backstop so the
    dispatcher's condition loop stays live, but every admission decision
    keys on ``now()``, so test outcomes are deterministic."""

    def __init__(self) -> None:
        self._t = 0.0
        self._lock = threading.Lock()

    def now(self) -> float:
        with self._lock:
            return self._t

    def wait(self, cv, timeout) -> bool:
        return cv.wait(0.05)

    def advance(self, dt: float, engine: WhatIfEngine) -> None:
        with self._lock:
            self._t += dt
        engine.kick()


class RecordingExecutor:
    """Resolves every pending with a synthetic metric; records batches."""

    def __init__(self) -> None:
        self.batches = []
        self.started = threading.Event()

    def __call__(self, batch) -> None:
        self.batches.append([p.query for p in batch])
        self.started.set()
        for p in batch:
            p.resolve({"cell_tag": float(hash(p.key) % 1000)})

    @property
    def widths(self):
        return [len(b) for b in self.batches]


class GatedExecutor(RecordingExecutor):
    """Blocks mid-batch until the test opens the gate (in-flight dedup)."""

    def __init__(self) -> None:
        super().__init__()
        self.gate = threading.Event()

    def __call__(self, batch) -> None:
        self.started.set()
        assert self.gate.wait(10), "test forgot to open the gate"
        super().__call__(batch)


QA = WhatIfQuery(strategy="min", proportion=0.5, seed=0)
QB = WhatIfQuery(strategy="avg", proportion=0.5, seed=0)
QC = WhatIfQuery(strategy="min", proportion=1.0, seed=1)


def make_engine(executor, *, clock=None, start=False, **over):
    kw = dict(max_batch=16, max_wait_s=10.0)
    kw.update(over)
    return WhatIfEngine(base_spec(), cache_dir=None, executor=executor,
                        clock=clock, start=start, **kw)


# ----------------------------------------------------------------------
# coalescer mechanics (deterministic, no real sleeps)
@pytest.mark.parametrize("given,devices", [
    (None, 1), ({"devices": 0}, 1), ({"devices": 2}, 2)])
def test_query_batches_run_on_one_device_unless_asked(given, devices):
    eng = make_engine(RecordingExecutor(), backend_options=given)
    assert eng.backend_options["devices"] == devices
    eng.close()


def test_full_batch_dispatches_without_waiting():
    """N=max_batch queries land in ONE batch, no clock advance needed."""
    ex = RecordingExecutor()
    eng = make_engine(ex, clock=FakeClock(), max_batch=3)
    futs = [eng.submit(q) for q in (QA, QB, QC)]
    eng.start()
    results = [f.result(timeout=10) for f in futs]
    assert ex.widths == [3]
    assert [q.to_dict() for q in ex.batches[0]] == \
        [q.to_dict() for q in (QA, QB, QC)]
    assert all(isinstance(r["cell_tag"], float) for r in results)
    stats = eng.stats()
    assert stats["misses"] == 3 and stats["batches"] == 1
    assert stats["max_batch_width"] == 3
    eng.close()


def test_max_wait_fires_with_partial_batch():
    """Under max_batch, the batch dispatches only once the fake clock
    passes the oldest query's max-wait deadline."""
    ex = RecordingExecutor()
    clock = FakeClock()
    eng = make_engine(ex, clock=clock, max_batch=16, max_wait_s=10.0,
                      start=True)
    fa = eng.submit(QA)
    fb = eng.submit(QB)
    # fake time has not advanced: the dispatcher must hold the batch open
    assert not ex.started.wait(0.3)
    assert ex.batches == []
    clock.advance(10.1, eng)
    assert ex.started.wait(5)
    assert fa.result(timeout=10) and fb.result(timeout=10)
    assert ex.widths == [2]
    eng.close()


def test_overflow_spills_into_next_batch():
    """max_batch+1 queued queries drain as two batches, all answered."""
    ex = RecordingExecutor()
    eng = make_engine(ex, clock=FakeClock(), max_batch=2, max_wait_s=0.0)
    qs = [QA, QB, QC]
    futs = [eng.submit(q) for q in qs]
    eng.start()
    for f in futs:
        f.result(timeout=10)
    assert ex.widths == [2, 1]
    eng.close()


def test_midbatch_failure_poisons_only_the_failing_query():
    """resolve/reject/raise inside one batch: each query gets exactly its
    own outcome, and the dispatcher survives to serve the next batch."""
    class MixedExecutor(RecordingExecutor):
        def __call__(self, batch):
            self.batches.append([p.query for p in batch])
            batch[0].resolve({"ok": 1.0})
            batch[1].reject(RuntimeError("lane budget"))
            raise RuntimeError("executor blew up after item 2")

    ex = MixedExecutor()
    clock = FakeClock()
    eng = make_engine(ex, clock=clock, max_batch=3)
    fa, fb, fc = (eng.submit(q) for q in (QA, QB, QC))
    eng.start()
    assert fa.result(timeout=10) == {"ok": 1.0}
    with pytest.raises(QueryFailedError, match="lane budget"):
        fb.result(timeout=10)
    with pytest.raises(QueryFailedError, match="blew up"):
        fc.result(timeout=10)
    # a rejected query is NOT memoized — resubmitting retries it; and the
    # dispatcher survived, so the retry is served normally
    ex.__class__ = RecordingExecutor  # stop failing
    fb2 = eng.submit(QB)
    clock.advance(10.1, eng)  # a lone miss dispatches at the deadline
    assert fb2.result(timeout=10)["cell_tag"] >= 0
    # the successful in-batch resolve WAS memoized: no recompute
    assert eng.submit(QA).result(timeout=10) == {"ok": 1.0}
    stats = eng.stats()
    assert stats["failed"] == 2 and stats["computed"] == 2
    assert stats["memo_hits"] == 1
    eng.close()


def test_unresolved_items_are_rejected_not_hung():
    """An executor that silently drops an item must not hang its future."""
    class ForgetfulExecutor(RecordingExecutor):
        def __call__(self, batch):
            batch[0].resolve({"ok": 1.0})  # forgets the rest

    eng = make_engine(ForgetfulExecutor(), clock=FakeClock(), max_batch=2)
    fa, fb = eng.submit(QA), eng.submit(QB)
    eng.start()
    assert fa.result(timeout=10) == {"ok": 1.0}
    with pytest.raises(QueryFailedError, match="without resolving"):
        fb.result(timeout=10)
    eng.close()


def test_identical_inflight_queries_deduplicate():
    """The same query queued AND executing attaches, never recomputes."""
    ex = GatedExecutor()
    eng = make_engine(ex, clock=None, max_batch=1, max_wait_s=0.0,
                      start=False)
    f1 = eng.submit(QA)
    f2 = eng.submit(QA)          # dedup against the queued pending
    eng.start()
    assert ex.started.wait(5)    # batch is now executing, gate closed
    f3 = eng.submit(QA)          # dedup against the *executing* pending
    ex.gate.set()
    r1, r2, r3 = (f.result(timeout=10) for f in (f1, f2, f3))
    assert r1 == r2 == r3
    stats = eng.stats()
    assert stats["dedup"] == 2 and stats["computed"] == 1
    assert ex.widths == [1]
    eng.close()


def test_bounded_queue_rejects_overflow():
    eng = make_engine(RecordingExecutor(), max_queue=2, start=False)
    eng.submit(QA)
    eng.submit(QB)
    with pytest.raises(QueueFullError):
        eng.submit(QC)
    eng.start()
    eng.close()


def test_close_cancels_pending_and_rejects_new_queries():
    eng = make_engine(RecordingExecutor(), start=False)
    fut = eng.submit(QA)
    eng.close(cancel_pending=True)
    with pytest.raises(QueryFailedError):
        fut.result(timeout=10)
    with pytest.raises(EngineClosedError):
        eng.submit(QB)


def test_close_drains_by_default():
    ex = RecordingExecutor()
    eng = make_engine(ex, max_batch=4, max_wait_s=0.0, start=False)
    futs = [eng.submit(q) for q in (QA, QB, QC)]
    eng.start()
    eng.close()  # drain, don't cancel
    for f in futs:
        assert f.result(timeout=10)


# ----------------------------------------------------------------------
# query semantics
def test_query_parse_and_roundtrip():
    q = WhatIfQuery.parse(
        "strategy=avg,proportion=0.5,seed=1,backfill_depth=4,"
        "queue_order=sjf")
    assert q == WhatIfQuery(strategy="avg", proportion=0.5, seed=1,
                            backfill_depth=4, queue_order="sjf")
    assert WhatIfQuery.from_dict(q.to_dict()) == q


def test_query_validation():
    with pytest.raises(ValueError, match="unknown strategy"):
        WhatIfQuery(strategy="nope")
    with pytest.raises(ValueError, match="proportion"):
        WhatIfQuery(proportion=1.5)
    with pytest.raises(ValueError, match="unknown workload"):
        WhatIfQuery(workload="nope")
    with pytest.raises(ValueError, match="queue_order"):
        WhatIfQuery(queue_order="lifo")
    with pytest.raises(ValueError, match="unknown query field"):
        WhatIfQuery.from_dict({"strategy": "min", "bogus": 1})


def test_cell_normalization_matches_grid_semantics():
    # proportion 0 is the rigid baseline whatever the strategy
    assert WhatIfQuery(strategy="avg", proportion=0.0, seed=1).cell() == \
        ("easy", 0.0, 0)
    # non-malleable sweepable strategies have a single canonical cell
    assert WhatIfQuery(strategy="rigid_sjf", proportion=0.7).cell() == \
        ("rigid_sjf", 0.0, 0)
    assert WhatIfQuery(strategy="min", proportion=0.4, seed=1).cell() == \
        ("min", 0.4, 1)


def test_spec_for_threads_scenario_overrides():
    base = base_spec()
    spec = WhatIfQuery(strategy="min", backfill_depth=4, queue_order="sjf",
                       rigid_frac=0.2, arrival_compression=2.0
                       ).spec_for(base)
    assert spec.scenario.backfill_depth == 4
    assert spec.scenario.queue_order == "sjf"
    assert spec.scenario.arrival_compression == 2.0
    assert spec.scenario.job_classes.rigid == 0.2
    assert spec.scenario.job_classes.malleable == pytest.approx(0.8)
    # None fields inherit; base is untouched
    assert spec.scenario.walltime_factor == base.scenario.walltime_factor
    assert base.scenario.backfill_depth != 4
    # no overrides -> the base scenario object itself
    assert WhatIfQuery(strategy="min").spec_for(base).scenario \
        is base.scenario


def test_sample_queries_is_seeded():
    a = sample_queries(3, 8, workloads=("haswell",), seeds=2)
    b = sample_queries(3, 8, workloads=("haswell",), seeds=2)
    c = sample_queries(4, 8, workloads=("haswell",), seeds=2)
    assert a == b and a != c and len(a) == 8


# ----------------------------------------------------------------------
# hit paths + parity vs run_experiment (real engines, tiny workloads)
def _serve_all(engine, queries):
    futs = [(q, engine.submit(q)) for q in queries]
    return [(q, f.result(timeout=600)) for q, f in futs]


def _cells_for(spec):
    """(query, fingerprint) covering the whole tiny grid of ``spec``."""
    out = []
    for strat in spec.strategies:
        for prop in spec.proportions:
            for seed in range(spec.seeds):
                q = WhatIfQuery(strategy=strat, proportion=prop, seed=seed)
                out.append((q, q.spec_for(spec).cell_fingerprint(
                    spec.workloads[0], q.cell())))
    return out


def test_des_parity_with_run_experiment(tmp_path):
    """Cells served through the coalescer (miss path, concurrent storm)
    are bit-identical to run_experiment's store writes — same spec, two
    independent stores compared fingerprint-by-fingerprint."""
    from repro.experiments.run import run_experiment
    from repro.sweep.cache import SweepCache

    spec = base_spec(proportions=(0.0, 0.5), strategies=("min", "avg"))
    run_experiment(spec, cache_dir=str(tmp_path / "direct"), verbose=False)

    eng = WhatIfEngine(spec, cache_dir=str(tmp_path / "served"),
                       max_batch=8, max_wait_s=0.05, start=False)
    rows = _cells_for(spec)
    futs = [eng.submit(q) for q, _ in rows]
    eng.start()
    for f in futs:
        f.result(timeout=600)
    stats = eng.stats()
    eng.close()

    direct = SweepCache(str(tmp_path / "direct"))
    served = SweepCache(str(tmp_path / "served"))
    for q, fp in rows:
        a, b = direct.get(fp), served.get(fp)
        assert a is not None and b is not None, q
        assert a == b, f"serve path diverged from run_experiment for {q}"
    # the storm coalesced: every unique cell computed exactly once
    unique = len({SweepCache.key(fp) for _, fp in rows})
    assert stats["computed"] == unique
    assert stats["dedup"] == len(rows) - unique


def test_hit_paths_and_single_miss(tmp_path):
    """store hit (fresh engine, shared store), memo hit (same engine),
    single-miss compute — all three return the identical metrics."""
    spec = base_spec()
    q = WhatIfQuery(strategy="min", proportion=0.5, seed=0)

    eng1 = WhatIfEngine(spec, cache_dir=str(tmp_path / "c"),
                        max_batch=4, max_wait_s=0.0)
    computed = eng1.query(q, timeout=600)
    assert eng1.stats()["misses"] == 1
    eng1.close()

    eng2 = WhatIfEngine(spec, cache_dir=str(tmp_path / "c"),
                        max_batch=4, max_wait_s=0.0)
    from_store = eng2.query(q, timeout=600)
    assert eng2.stats() ["store_hits"] == 1
    from_memo = eng2.query(q, timeout=600)
    assert eng2.stats()["memo_hits"] == 1
    eng2.close()
    assert computed == from_store == from_memo


@pytest.mark.parametrize("strategies", [
    ("min", "avg"),                              # greedy + balanced
    ("pref_common_pool", "steal_agreement"),     # pooled + stealing
])
def test_jax_coalesced_parity_with_run_experiment(tmp_path, strategies):
    """The padded-device-batch miss path (two pass structures in one
    storm) is bit-identical to the jax run_experiment backend."""
    from repro.experiments.run import run_experiment
    from repro.sweep.cache import SweepCache

    spec = base_spec(engine="jax", proportions=(0.0, 0.5),
                     strategies=strategies, seeds=1)
    run_experiment(spec, cache_dir=str(tmp_path / "direct"), verbose=False)

    eng = WhatIfEngine(spec, cache_dir=str(tmp_path / "served"),
                       max_batch=8, max_wait_s=0.05, start=False,
                       backend_options={"devices": 1})
    rows = _cells_for(spec)
    futs = [eng.submit(q) for q, _ in rows]
    eng.start()
    for f in futs:
        f.result(timeout=600)
    eng.close()

    direct = SweepCache(str(tmp_path / "direct"))
    served = SweepCache(str(tmp_path / "served"))
    for q, fp in rows:
        a, b = direct.get(fp), served.get(fp)
        assert a is not None and b is not None, q
        assert a == b, f"jax serve path diverged for {q}"


def test_jax_realization_failure_poisons_only_its_query():
    """A query whose workload fails to realise is rejected alone; the
    rest of its batch still runs through the lane executor."""
    eng = WhatIfEngine(base_spec(engine="jax"), cache_dir=None, max_batch=2,
                       max_wait_s=10.0, start=False)
    realized, calls = eng._realized, []

    def first_call_fails(spec, name):
        calls.append(name)
        if len(calls) == 1:
            raise RuntimeError("trace unreadable")
        return realized(spec, name)

    eng._realized = first_call_fails
    fa, fc = eng.submit(QA), eng.submit(QC)
    eng.start()
    with pytest.raises(QueryFailedError, match="trace unreadable"):
        fa.result(timeout=600)
    assert "sched_invocations" in fc.result(timeout=600)
    eng.close()


def test_seeded_interleaving_order_independence(tmp_path):
    """Shuffled submission order + varying batch widths never change any
    query's answer (the non-hypothesis half of the order-independence
    property; see tests/test_serve_whatif_properties.py)."""
    import random

    spec = base_spec(proportions=(0.0, 0.5), strategies=("min",))
    rows = _cells_for(spec)
    reference = None
    rng = random.Random(0)
    for trial, max_batch in enumerate((1, 2, 8)):
        order = list(range(len(rows)))
        rng.shuffle(order)
        eng = WhatIfEngine(spec, cache_dir=None, max_batch=max_batch,
                           max_wait_s=0.05, start=False)
        futs = {i: eng.submit(rows[i][0]) for i in order}
        eng.start()
        got = {i: futs[i].result(timeout=600) for i in order}
        eng.close()
        if reference is None:
            reference = got
        else:
            assert got == reference, \
                f"trial {trial} (max_batch={max_batch}) changed results"
