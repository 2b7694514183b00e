"""The extracted scheduling-policy core (repro.core.passes).

Three layers of coverage, matching the module's three implementation
families:

  * numpy-vs-jnp parity of the exact argsort-based Steps 2-3 passes;
  * shadow-time EASY reservation units (sort-free bisection vs. the exact
    oracle; the reserved head is never delayed by backfill; backfill that
    fits under the shadow still happens);
  * a small-grid three-way engine parity check (numpy DES vs. dense-tick
    ``sim_jax`` vs. the event-stepped batched engine), plus bit-parity of
    the multi-cluster padded batch against per-workload runs.
"""
import numpy as np
import pytest

from repro.core import (STRATEGIES, Cluster, Workload, simulate,
                        transform_rigid_to_malleable)
from repro.core import passes
from repro.core.sim_jax import simulate_jax
from repro.sweep.batch import (EngineConfig, build_lanes, concat_lanes,
                               simulate_lanes)

jnp = pytest.importorskip("jax.numpy")

TINY = Cluster("t", nodes=10, tick=1.0)


def _wl(seed=0, n=20, hi=150.0, prop=0.0, nodes=10):
    rng = np.random.default_rng(seed)
    w = Workload.rigid(submit=np.sort(rng.uniform(0, hi, n)),
                       runtime=rng.uniform(20, 120, n),
                       nodes_req=rng.choice([1, 2, 4, 8], n))
    if prop > 0:
        w = transform_rigid_to_malleable(w, prop, seed=seed,
                                         cluster_nodes=nodes)
    return w


# ------------------------------------------------------- numpy/jnp parity
def _random_case(rng, n=12, span=16):
    mn = rng.integers(1, 4, n)
    mx = mn + rng.integers(0, span, n)
    alloc = rng.integers(0, span + 4, n).clip(mn, mx)
    prio = rng.integers(-5, 6, n)
    return alloc, mn, mx, prio


@pytest.mark.parametrize("trial", range(8))
def test_greedy_passes_numpy_jnp_parity(trial):
    rng = np.random.default_rng(trial)
    alloc, mn, mx, prio = _random_case(rng)
    need = int(rng.integers(0, np.sum(alloc - mn) + 3))
    idle = int(rng.integers(0, np.sum(mx - alloc) + 3))
    shr_np = passes.greedy_shrink(alloc, mn, prio, need, xp=np)
    shr_j = passes.greedy_shrink(jnp.asarray(alloc), jnp.asarray(mn),
                                 jnp.asarray(prio), need, xp=jnp)
    np.testing.assert_array_equal(shr_np, np.asarray(shr_j))
    exp_np = passes.greedy_expand(alloc, mx, prio, idle, xp=np)
    exp_j = passes.greedy_expand(jnp.asarray(alloc), jnp.asarray(mx),
                                 jnp.asarray(prio), idle, xp=jnp)
    np.testing.assert_array_equal(exp_np, np.asarray(exp_j))


@pytest.mark.parametrize("trial", range(8))
def test_balanced_passes_numpy_jnp_parity(trial):
    rng = np.random.default_rng(100 + trial)
    alloc, mn, mx, _ = _random_case(rng)
    need = int(rng.integers(0, np.sum(alloc - mn) + 3))
    idle = int(rng.integers(0, np.sum(mx - alloc) + 3))
    shr_np = passes.balanced_shrink(alloc, mn, mx, need, xp=np)
    shr_j = passes.balanced_shrink(jnp.asarray(alloc), jnp.asarray(mn),
                                   jnp.asarray(mx), need, xp=jnp)
    np.testing.assert_array_equal(np.asarray(shr_np), np.asarray(shr_j))
    exp_np = passes.balanced_expand(alloc, mn, mx, idle, xp=np)
    exp_j = passes.balanced_expand(jnp.asarray(alloc), jnp.asarray(mn),
                                   jnp.asarray(mx), idle, xp=jnp)
    np.testing.assert_array_equal(np.asarray(exp_np), np.asarray(exp_j))


# ------------------------------------------- shadow-time reservation units
@pytest.mark.parametrize("trial", range(6))
def test_shadow_reservation_matches_exact_oracle(trial):
    """The sort-free time bisection lands on the exact oracle's shadow."""
    rng = np.random.default_rng(trial)
    k = int(rng.integers(2, 9))
    # distinct end estimates: the snapped bisection bound is unambiguous
    ests = np.sort(rng.uniform(10.0, 500.0, k)).astype(np.float32)
    release = rng.integers(1, 5, k)
    head_floor = int(release.sum()) + int(rng.integers(-3, 1))
    head_floor = max(head_floor, int(release[0]) + 1)
    free = 0  # blocked head
    shadow_ref, extra_ref = passes.easy_reservation_exact(
        ests, release, free, head_floor)

    W = 16  # pad to fixed shape with non-running (+inf) slots
    est = np.full(W, np.inf, np.float32)
    rel = np.zeros(W, np.int32)
    est[:k], rel[:k] = ests, release
    shadow, extra = passes.shadow_reservation(
        jnp.asarray(est), jnp.asarray(rel), jnp.int32(free),
        jnp.int32(head_floor))
    np.testing.assert_allclose(float(shadow), shadow_ref, rtol=1e-5)
    assert int(extra) == extra_ref


def _head_blocking_workload():
    """A running 8-node job, a 10-node head, and two backfill candidates.

    * job 0 (runtime 50): running, releases the cluster at t=50;
    * job 1 (10 nodes): the blocked head — its reservation is t=62.5
      (walltime-padded estimate of job 0);
    * job 2 (2 nodes, runtime 200): would hold nodes far past the
      reservation — starting it would delay the head;
    * job 3 (2 nodes, runtime 10): finishes before the reservation —
      legitimate backfill.
    """
    return Workload.rigid(
        submit=np.array([0.0, 1.0, 2.0, 3.0]),
        runtime=np.array([50.0, 30.0, 200.0, 10.0]),
        nodes_req=np.array([8, 10, 2, 2]))


def _starts(name, w):
    if name == "des":
        return simulate(w, TINY, STRATEGIES["easy"]).start
    if name == "sim_jax":
        st, _ = simulate_jax(w, TINY.nodes, TINY.tick, 400,
                             STRATEGIES["easy"])
        return np.asarray(st.start_t)
    batch, order = build_lanes(w, TINY.nodes, [(STRATEGIES["easy"], 0.0, 0)])
    res = simulate_lanes(batch, EngineConfig(window=8, chunk=32))
    return res["start_t"][0][np.argsort(order)]


@pytest.mark.parametrize("engine", ["des", "sim_jax", "batch"])
def test_backfill_never_delays_reserved_head(engine):
    """The long candidate must not start before the head (no spare pool),
    so the head starts as soon as the running job completes."""
    start = _starts(engine, _head_blocking_workload())
    # head starts right when job 0 releases its 8 nodes (t=50)
    assert start[1] == pytest.approx(50.0, abs=2 * TINY.tick)
    # the reservation-violating candidate waits for the head to finish
    assert start[2] >= start[1] + 1.0


@pytest.mark.parametrize("engine", ["des", "sim_jax", "batch"])
def test_backfill_under_shadow_still_happens(engine):
    """The short candidate fits under the shadow: it backfills immediately
    and the head is still never starved."""
    start = _starts(engine, _head_blocking_workload())
    assert start[3] <= 5.0 + 2 * TINY.tick   # backfilled at submit
    assert start[1] == pytest.approx(50.0, abs=2 * TINY.tick)


@pytest.mark.parametrize("engine", ["des", "sim_jax", "batch"])
def test_backfill_scans_candidates_in_queue_order(engine):
    """Backfill is one first-fit scan in queue order: an earlier candidate
    that runs past the reservation inside the spare-node pool starts
    before a later one that would finish before it, and the later one,
    left without free nodes, waits.

    Job 0 (6 nodes) runs until t=100; the 8-node head reserves t=100 with
    2 spare nodes beyond its need.  Jobs 2 (2 nodes, long) and 3 (4 nodes,
    short) arrive together: job 2 takes the spare pool, which leaves 2
    free nodes, too few for job 3."""
    w = Workload.rigid(
        submit=np.array([0.0, 1.0, 2.0, 2.0]),
        runtime=np.array([100.0, 50.0, 1000.0, 10.0]),
        nodes_req=np.array([6, 8, 2, 4]))
    start = _starts(engine, w)
    assert start[2] == pytest.approx(2.0, abs=TINY.tick)
    assert start[1] == pytest.approx(100.0, abs=2 * TINY.tick)
    assert start[3] >= start[1] + 1.0


@pytest.mark.parametrize("engine", ["des", "batch"])
def test_long_job_ends_at_the_tick_after_its_end(engine):
    """A job frees its nodes at the first tick at or after its end, however
    long it ran: job 0 ends at t=150000.4, so the full-width job 2 starts
    at t=150001, though job 1's end brings a tick at t=150000 when less
    than 1e-5 of job 0's work is left."""
    w = Workload.rigid(submit=np.array([0.0, 0.0, 1.0]),
                       runtime=np.array([150000.4, 149999.8, 10.0]),
                       nodes_req=np.array([5, 5, 10]))
    assert _starts(engine, w)[2] == 150001.0


def test_engine_starts_equal_des_on_a_theta_log():
    """On a seeded Theta log, where capability jobs queue behind wide
    heads, the batched engine starts every rigid EASY job at the DES's
    start (within the one tick an arrival may be admitted early)."""
    from repro.core import CLUSTERS, get_strategy
    from repro.core.traces import generate

    w, cl = generate("theta", seed=2, scale=0.5), CLUSTERS["theta"]
    strat = get_strategy("easy")
    ref = simulate(w, cl, strat)
    batch, order = build_lanes(w, cl.nodes, [(strat, 0.0, 0)], tick=cl.tick)
    got = simulate_lanes(batch, EngineConfig())["start_t"][0][
        np.argsort(order)]
    np.testing.assert_allclose(got, ref.start, atol=cl.tick)
    wait_ref = np.mean(ref.start - w.submit)
    assert np.mean(got - w.submit) == pytest.approx(wait_ref, rel=1e-3)


# ----------------------------------------------- three-way engine parity
@pytest.mark.parametrize("name,prop", [("easy", 0.0), ("min", 0.5),
                                       ("avg", 0.5)])
def test_three_way_engine_parity_small_grid(name, prop):
    """DES, sim_jax and the batched engine agree on starts/ends within the
    documented tick-quantization tolerance on a low-contention workload."""
    rng = np.random.default_rng(5)
    n = 12
    w = Workload.rigid(submit=np.sort(rng.uniform(0, 200, n)),
                       runtime=rng.uniform(20, 80, n),
                       nodes_req=rng.choice([1, 2], n))
    wm = (w if prop == 0.0 else
          transform_rigid_to_malleable(w, prop, seed=1, cluster_nodes=10))
    strat = STRATEGIES[name]

    ref = simulate(wm, TINY, strat)
    st, _ = simulate_jax(wm, TINY.nodes, TINY.tick, 600, strat)
    batch, order = build_lanes(w, TINY.nodes,
                               [(strat, prop, 1)])
    res = simulate_lanes(batch, EngineConfig(structure=strat.structure,
                                             window=16, chunk=64))
    inv = np.argsort(order)

    np.testing.assert_allclose(np.asarray(st.start_t), ref.start, atol=2.0)
    np.testing.assert_allclose(np.asarray(st.end_t), ref.end, atol=4.0)
    np.testing.assert_allclose(res["start_t"][0][inv], ref.start, atol=2.0)
    np.testing.assert_allclose(res["end_t"][0][inv], ref.end, atol=4.0)


# ------------------------------------------------- backfill depth (bound)
def _depth_workload():
    """Depth-sensitive trace: the first candidate behind the blocked head
    cannot backfill (it would outlive the reservation with no spare pool),
    the second can.  With ``backfill_depth=1`` the scan stops before the
    fitting candidate; any deeper scan admits it at submit time.
    """
    return Workload.rigid(
        submit=np.array([0.0, 1.0, 2.0, 3.0]),
        runtime=np.array([50.0, 30.0, 200.0, 10.0]),
        nodes_req=np.array([8, 10, 2, 2]))


def _depth_starts(engine, w, depth):
    if engine == "des":
        return simulate(w, TINY, STRATEGIES["easy"],
                        backfill_depth=depth).start
    if engine == "sim_jax":
        st, _ = simulate_jax(w, TINY.nodes, TINY.tick, 400,
                             STRATEGIES["easy"], backfill_depth=depth)
        return np.asarray(st.start_t)
    batch, order = build_lanes(w, TINY.nodes,
                               [(STRATEGIES["easy"], 0.0, 0)],
                               backfill_depth=depth)
    res = simulate_lanes(batch, EngineConfig(window=8, chunk=32))
    return res["start_t"][0][np.argsort(order)]


@pytest.mark.parametrize("engine", ["des", "sim_jax", "batch"])
def test_backfill_depth_changes_schedule(engine):
    """backfill_depth=1 vs. the default produce *different* schedules in
    every engine: the axis bounds the scan itself, engine-faithfully."""
    w = _depth_workload()
    shallow = _depth_starts(engine, w, 1)
    deep = _depth_starts(engine, w, 256)
    # the fitting candidate backfills only when the scan reaches it
    assert deep[3] <= 5.0 + 2 * TINY.tick
    assert shallow[3] >= shallow[1] + 1.0  # waited for the head instead
    assert np.any(shallow != deep)


def test_backfill_depth_consistent_across_engines():
    """All three engines agree on the depth-bounded schedule within the
    documented tick quantization, at every depth."""
    w = _depth_workload()
    for depth in (1, 2, 256):
        ref = _depth_starts("des", w, depth)
        for engine in ("sim_jax", "batch"):
            np.testing.assert_allclose(
                _depth_starts(engine, w, depth), ref,
                atol=2 * TINY.tick, err_msg=f"{engine} depth={depth}")


def test_batched_depth_swept_lanes_share_one_batch():
    """backfill_depth is per-lane data: depth-swept lanes in one batch
    reproduce the per-depth solo runs bit-for-bit."""
    from repro.sweep.batch import BatchedLanes

    w = _depth_workload()
    cfg = EngineConfig(window=8, chunk=32)
    solo = {}
    batches = []
    for depth in (1, 256):
        batch, _order = build_lanes(w, TINY.nodes,
                                    [(STRATEGIES["easy"], 0.0, 0)],
                                    backfill_depth=depth)
        solo[depth] = simulate_lanes(batch, cfg)
        batches.append(batch)
    both = BatchedLanes(*[
        jnp.concatenate([getattr(b, name) for b in batches])
        for name in BatchedLanes._fields])
    res = simulate_lanes(both, cfg)
    np.testing.assert_array_equal(res["start_t"][0], solo[1]["start_t"][0])
    np.testing.assert_array_equal(res["start_t"][1],
                                  solo[256]["start_t"][0])


# ------------------------------------------------ on-demand queue priority
def _od_workload():
    """A running 8-node job; a normal 6-node job queues first; a 6-node
    on-demand job arrives later and must start first."""
    from repro.core.jobs import CLASS_ON_DEMAND
    w = Workload.rigid(
        submit=np.array([0.0, 1.0, 2.0]),
        runtime=np.array([50.0, 40.0, 40.0]),
        nodes_req=np.array([8, 6, 6]))
    w.job_class[2] = CLASS_ON_DEMAND
    return w


@pytest.mark.parametrize("engine", ["des", "sim_jax", "batch"])
def test_on_demand_outranks_earlier_normal_job(engine):
    w = _od_workload()
    start = _depth_starts(engine, w, 256)
    # the on-demand job takes the release at t=50; the earlier-submitted
    # normal job waits behind it
    assert start[2] == pytest.approx(50.0, abs=2 * TINY.tick)
    assert start[1] >= start[2] + 30.0


@pytest.mark.parametrize("engine", ["des", "sim_jax", "batch"])
def test_on_demand_backfills_before_earlier_normal_candidate(engine):
    """Backfill admission follows (class, submit) order too: with budget
    for one candidate, the on-demand one backfills and the
    earlier-submitted normal one waits — in every engine."""
    from repro.core.jobs import CLASS_ON_DEMAND
    # jobs 0-1 fill the cluster until t=20, when 2 nodes free up; by then
    # the od head (job 2) and BOTH candidates are queued, and the 2 free
    # nodes admit exactly one backfill candidate
    w = Workload.rigid(
        submit=np.array([0.0, 0.0, 2.0, 3.0, 4.0]),
        runtime=np.array([60.0, 20.0, 30.0, 10.0, 10.0]),
        nodes_req=np.array([8, 2, 10, 2, 2]))
    w.job_class[2] = CLASS_ON_DEMAND  # blocked head (od outranks all)
    w.job_class[4] = CLASS_ON_DEMAND  # the late od candidate
    start = _depth_starts(engine, w, 256)
    assert start[4] == pytest.approx(20.0, abs=2 * TINY.tick)  # od first
    assert start[3] >= start[4] + 5.0        # normal candidate waits


# -------------------------------------------------- pallas expand backend
@pytest.mark.parametrize("trial", range(4))
def test_pallas_give_matches_bisection_give(trial):
    """The Pallas prefix-waterfill expand backend (interpret mode) agrees
    with the sort-free threshold bisection slot-for-slot."""
    rng = np.random.default_rng(200 + trial)
    B, W = 3, 10
    prio = jnp.asarray(rng.integers(-4, 5, (B, W)), jnp.int32)
    room = jnp.asarray(rng.integers(0, 6, (B, W)), jnp.int32)
    idle = jnp.asarray(rng.integers(0, 25, B), jnp.int32)
    ref = passes.give_asc_prefix(prio, room, idle, -5, 5)
    got = passes._pallas_give(prio, room, idle, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


# ---------------------------------------------- fused schedule_tick kernel
def _random_tick_case(rng, B=4, W=24):
    """A plausible mid-simulation slot state for one schedule_tick call."""
    from repro.core.jobs import QUEUED, RUNNING
    mn = rng.integers(1, 3, (B, W)).astype(np.int32)
    mx = (mn + rng.integers(0, 6, (B, W))).astype(np.int32)
    want = np.clip(rng.integers(1, 7, (B, W)), mn, mx).astype(np.int32)
    state = rng.choice(4, size=(B, W), p=[0.2, 0.4, 0.3, 0.1])
    alloc = np.where(state == RUNNING, want, 0).astype(np.int32)
    p = passes.PassParams(
        malleable=jnp.asarray(rng.random((B, W)) < 0.7),
        min_nodes=jnp.asarray(mn), max_nodes=jnp.asarray(mx),
        want=jnp.asarray(want), floor=jnp.asarray(mn),
        shrink_floor=jnp.asarray(mn),
        prio_ref=jnp.asarray(rng.integers(0, 3, (B, W)), jnp.int32),
        pfrac=jnp.asarray(rng.uniform(0.3, 1.0, (B, W)), jnp.float32),
        wall_work=jnp.asarray(rng.uniform(20.0, 200.0, (B, W)),
                              jnp.float32))
    args = (p, jnp.asarray(state, jnp.int32), jnp.asarray(alloc),
            jnp.asarray(rng.uniform(1.0, 80.0, (B, W)), jnp.float32),
            jnp.asarray(np.where(state == RUNNING,
                                 rng.uniform(0.0, 40.0, (B, W)), 0.0),
                        jnp.float32),
            jnp.asarray(rng.random(B) < 0.8)[:, None],
            jnp.asarray(rng.integers(8, 16, B), jnp.int32),
            jnp.asarray(rng.uniform(30.0, 60.0, B), jnp.float32))
    del QUEUED
    return args


@pytest.mark.parametrize("trial", range(6))
@pytest.mark.parametrize("depth", [None, 2])
def test_fused_schedule_tick_matches_reference(trial, depth):
    """The fused Pallas Steps-1..3 kernel (interpret mode) is bit-equal to
    the reference pass on random slot states, bounded depth included."""
    rng = np.random.default_rng(500 + trial)
    args = _random_tick_case(rng)
    B = args[1].shape[0]
    kw = dict(structure="greedy", fill_rounds=2, prio_lo=-4, prio_hi=12,
              span_max=8,
              backfill_depth=None if depth is None
              else jnp.full((B,), depth, jnp.int32))
    ref = passes.schedule_tick(*args, expand_backend="bisect", **kw)
    got = passes.schedule_tick(*args, expand_backend="fused-interpret",
                               **kw)
    for r, g, name in zip(ref, got, ("state", "alloc", "start_t")):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g),
                                      err_msg=name)


# ------------------------------------------- multi-cluster padded batching
def test_concat_lanes_matches_per_workload_runs():
    """Lanes of different workloads/clusters stacked into one padded batch
    reproduce each workload's solo batch result exactly."""
    cfg = EngineConfig(window=16, chunk=64)
    w_a = _wl(seed=0, n=20)
    w_b = _wl(seed=9, n=13, hi=100.0)
    lanes_a = [(STRATEGIES["easy"], 0.0, 0), (STRATEGIES["min"], 0.6, 0)]
    lanes_b = [(STRATEGIES["pref"], 1.0, 1)]
    b_a, _ = build_lanes(w_a, 10, lanes_a, tick=1.0)
    b_b, _ = build_lanes(w_b, 6, lanes_b, tick=2.0)

    big = concat_lanes([b_a, b_b])
    assert big.n_lanes == 3 and big.n_jobs == 20
    res = simulate_lanes(big, cfg)
    res_a = simulate_lanes(b_a, cfg)
    res_b = simulate_lanes(b_b, cfg)

    for key in ("start_t", "end_t", "expand_ops", "shrink_ops"):
        np.testing.assert_array_equal(res[key][:2], res_a[key])
        np.testing.assert_array_equal(res[key][2:, :13], res_b[key])
    # padding slots never ran
    assert np.all(np.isnan(res["start_t"][2:, 13:]))


# ------------------------------------- ported ElastiSim strategy parity
@pytest.mark.parametrize("name,prop", [("steal_agreement", 0.8),
                                       ("pref_common_pool", 0.8),
                                       ("rigid_sjf", 0.0)])
def test_ported_strategies_three_way_parity(name, prop):
    """The ported registry policies (stealing / pooled / pinned-SJF
    structures) agree across the three engines.  The stealing pass
    reallocates *running* jobs, so the event-stepped engine's quantized
    pass timing compounds into end times — hence its wider (documented)
    end tolerance; aggregate metrics stay inside CROSSCHECK_TOLERANCES.
    """
    rng = np.random.default_rng(5)
    n = 14
    w = Workload.rigid(submit=np.sort(rng.uniform(0, 200, n)),
                       runtime=rng.uniform(20, 80, n),
                       nodes_req=rng.choice([1, 2, 4], n))
    strat = STRATEGIES[name]
    wm = (w if prop == 0.0 else
          transform_rigid_to_malleable(w, prop, seed=1, cluster_nodes=10))

    ref = simulate(wm, TINY, strat)
    st, _ = simulate_jax(wm, TINY.nodes, TINY.tick, 600, strat)
    batch, order = build_lanes(w, TINY.nodes, [(strat, prop, 1)])
    res = simulate_lanes(batch, EngineConfig(
        structure=strat.structure if strat.malleable else "greedy",
        window=16, chunk=64))
    inv = np.argsort(order)

    np.testing.assert_allclose(np.asarray(st.start_t), ref.start, atol=2.0)
    np.testing.assert_allclose(np.asarray(st.end_t), ref.end, atol=4.0)
    np.testing.assert_allclose(res["start_t"][0][inv], ref.start, atol=2.0)
    np.testing.assert_allclose(res["end_t"][0][inv], ref.end, atol=10.0)


def test_pooled_pass_conserves_capacity_and_draws_only_surplus():
    """The common-pool start pass never over-commits the cluster and only
    shrinks donors that were above their preferred allocation."""
    rng = np.random.default_rng(11)
    n = 16
    w = Workload.rigid(submit=np.sort(rng.uniform(0, 120, n)),
                       runtime=rng.uniform(20, 80, n),
                       nodes_req=rng.choice([2, 4], n))
    wm = transform_rigid_to_malleable(w, 1.0, seed=0, cluster_nodes=10)
    strat = STRATEGIES["pref_common_pool"]
    batch, _ = build_lanes(w, TINY.nodes, [(strat, 1.0, 0)])
    res = simulate_lanes(batch, EngineConfig(structure="pooled",
                                             window=16, chunk=64))
    assert res["finished"]
    assert int(res["trace_busy"].max()) <= TINY.nodes
    ref = simulate(wm, TINY, strat)
    # running allocations never fell below the malleable floor
    assert np.all(ref.end >= ref.start)


def test_stealing_pass_conserves_capacity():
    rng = np.random.default_rng(13)
    n = 16
    w = Workload.rigid(submit=np.sort(rng.uniform(0, 120, n)),
                       runtime=rng.uniform(20, 80, n),
                       nodes_req=rng.choice([2, 4], n))
    strat = STRATEGIES["steal_agreement"]
    batch, _ = build_lanes(w, TINY.nodes, [(strat, 1.0, 0)])
    res = simulate_lanes(batch, EngineConfig(structure="stealing",
                                             window=16, chunk=64))
    assert res["finished"]
    assert int(res["trace_busy"].max()) <= TINY.nodes


# --------------------------------------------- SJF queue ordering (axis)
def _sjf_depth_workload():
    """SJF-sensitive depth trace: the head (job 1) stays the head under
    both orders (shortest walltime), but the two backfill candidates have
    *inverted* walltime order — FCFS scans the non-fitting long job (2)
    first, SJF ranks the fitting short job (3) first.  With
    ``backfill_depth=1`` only the first-ranked candidate is scanned, so
    the depth bound must apply to the *reordered* queue.

    Submits are spaced > one tick apart so the dense-tick engine starts
    job 0 before job 1 arrives (same-tick arrivals would let SJF reorder
    them — a legitimate but distracting quantization effect).
    """
    return Workload.rigid(
        submit=np.array([0.0, 3.0, 4.0, 5.0]),
        runtime=np.array([50.0, 20.0, 200.0, 30.0]),
        nodes_req=np.array([8, 10, 2, 2]))


def _qorder_starts(engine, w, depth, queue_order):
    if engine == "des":
        return simulate(w, TINY, STRATEGIES["easy"], backfill_depth=depth,
                        queue_order=queue_order).start
    if engine == "sim_jax":
        st, _ = simulate_jax(w, TINY.nodes, TINY.tick, 400,
                             STRATEGIES["easy"], backfill_depth=depth,
                             queue_order=queue_order)
        return np.asarray(st.start_t)
    batch, order = build_lanes(w, TINY.nodes,
                               [(STRATEGIES["easy"], 0.0, 0)],
                               backfill_depth=depth,
                               queue_order=queue_order)
    res = simulate_lanes(batch, EngineConfig(window=8, chunk=32))
    return res["start_t"][0][np.argsort(order)]


@pytest.mark.parametrize("engine", ["des", "sim_jax", "batch"])
def test_sjf_depth_bound_scans_reordered_queue(engine):
    """With backfill_depth=1, FCFS scans only the long non-fitting
    candidate (job 3 waits), while SJF's reordered queue puts the short
    fitting candidate first (job 3 backfills at submit) — identically in
    every engine."""
    w = _sjf_depth_workload()
    fcfs = _qorder_starts(engine, w, 1, "fcfs")
    sjf = _qorder_starts(engine, w, 1, "sjf")
    # FCFS@depth=1: the scan stops at the long job; job 3 waits for the
    # head chain (>= the head's release at t=50)
    assert fcfs[3] >= 50.0 - 2 * TINY.tick, engine
    # SJF@depth=1: job 3 is the first-ranked candidate and backfills
    assert sjf[3] <= 5.0 + 2 * TINY.tick, engine
    # the head is reserved (never starved) under both orders
    assert fcfs[1] == pytest.approx(50.0, abs=2 * TINY.tick)
    assert sjf[1] == pytest.approx(50.0, abs=2 * TINY.tick)


@pytest.mark.parametrize("engine", ["sim_jax", "batch"])
def test_sjf_engine_parity_vs_des(engine):
    """A contended random workload under queue_order=sjf: the vectorized
    engines match the reference DES within the usual quantization
    tolerance (the permutation wrapper is schedule-faithful)."""
    rng = np.random.default_rng(7)
    n = 14
    w = Workload.rigid(submit=np.sort(rng.uniform(0, 150, n)),
                       runtime=rng.uniform(20, 100, n),
                       nodes_req=rng.choice([1, 2, 4, 8], n))
    ref = _qorder_starts("des", w, 256, "sjf")
    got = _qorder_starts(engine, w, 256, "sjf")
    np.testing.assert_allclose(got, ref, atol=2.0)


def test_fcfs_lane_inside_sjf_batch_is_bit_identical():
    """A with_sjf compilation must not disturb FCFS lanes: their monotone
    sort keys yield the identity permutation, so a mixed fcfs+sjf batch
    reproduces the solo-FCFS lane bit-for-bit."""
    w = _wl(seed=3, n=18)
    solo, order_a = build_lanes(w, 10, [(STRATEGIES["easy"], 0.0, 0)])
    mixed, order_b = build_lanes(
        w, 10, [(STRATEGIES["easy"], 0.0, 0),
                (STRATEGIES["rigid_sjf"], 0.0, 0)])
    cfg = EngineConfig(window=16, chunk=64)
    res_solo = simulate_lanes(solo, cfg)
    res_mixed = simulate_lanes(mixed, cfg)
    np.testing.assert_array_equal(res_mixed["start_t"][0],
                                  res_solo["start_t"][0])
    np.testing.assert_array_equal(res_mixed["end_t"][0],
                                  res_solo["end_t"][0])
    # and the SJF lane actually differs somewhere (the axis is live)
    assert np.any(res_mixed["start_t"][1] != res_solo["start_t"][0])


# ------------------------------------------------- blocked prefix sums
@pytest.mark.parametrize("w", [1, 127, 128, 129, 4096, 16384, 28259])
@pytest.mark.parametrize("lanes", [(), (16,)], ids=["row", "lanes"])
@pytest.mark.parametrize("dtype", ["int32", "bool"])
def test_blocked_prefix_sum_equals_cumsum(w, lanes, dtype):
    """The MXU form is bitwise ``jnp.cumsum``: int32 over the full range,
    wraparound included, and bool, whatever the padding to blocks."""
    import jax
    rng = np.random.default_rng(w)
    shape = lanes + (w,)
    if dtype == "bool":
        x = rng.random(shape) < 0.5
    else:
        x = rng.integers(-2**31, 2**31, shape).astype(np.int32)
        x[..., ::3] = np.int32(2**31 - 1)  # long runs that wrap
    got = jax.jit(passes.blocked_prefix_sum)(x)
    want = jnp.cumsum(jnp.asarray(x), axis=-1)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("structure,lanes", [
    ("greedy", [("easy", 0.0), ("min", 0.6), ("keeppref", 1.0),
                ("rigid_sjf", 0.0)]),
    ("balanced", [("avg", 0.5), ("avg", 1.0)]),
], ids=["greedy_sjf", "balanced"])
def test_engine_bitwise_with_blocked_prefix_sums(structure, lanes,
                                                 monkeypatch):
    """A batch whose every prefix sum takes the blocked form, as where the
    chunk program is lowered for TPU, returns the same bits as the
    ``jnp.cumsum`` program.  Some jobs are on-demand (class lanes), and
    the queue outgrows one 128-slot block."""
    import functools

    from repro.core.jobs import CLASS_ON_DEMAND
    from repro.sweep import batch as sb

    w = _wl(seed=5, n=200, hi=300.0)
    w.job_class[::4] = CLASS_ON_DEMAND
    batch, _ = build_lanes(w, TINY.nodes,
                           [(STRATEGIES[s], p, 0) for s, p in lanes])
    cfg = EngineConfig(structure=structure, window=16, chunk=32,
                       aot_warmup=False)
    ref = simulate_lanes(batch, cfg)

    monkeypatch.setattr(passes, "_platform_prefix_sum",
                        passes.blocked_prefix_sum)
    # trace the chunk programs afresh under the patch
    monkeypatch.setattr(sb, "_chunk_fn",
                        functools.cache(sb._chunk_fn.__wrapped__))
    for name in ("_COMPILED_KEYS", "_WARM_EXECUTABLES", "_WARM_FUTURES"):
        monkeypatch.setattr(sb, name, type(getattr(sb, name))())
    got = simulate_lanes(batch, cfg)

    assert ref["finished"] and got["finished"]
    assert ref["compile_variants"] > 0 and got["compile_variants"] > 0
    for key, val in ref.items():
        if isinstance(val, np.ndarray):
            assert got[key].dtype == val.dtype, key
            np.testing.assert_array_equal(got[key], val, err_msg=key)
