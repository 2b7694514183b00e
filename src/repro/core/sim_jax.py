"""Fully-jittable malleable-scheduling simulator (``jax.lax.scan`` over ticks).

This is the paper's scheduling technique expressed as a composable JAX
module: fixed-size job arrays, one scan step per tick, and the exact same
scheduling passes (:func:`repro.core.passes.schedule_tick`) as the batched
sweep engine — this module is the dense-per-tick *driver* around the shared
policy core, nothing more.  Because every step is pure and fixed-shape it
can be jitted, vmapped over seeds/proportions, and differentiated through
(the speedup model is smooth in the allocation).

Fidelity differences vs. the reference DES (``simulator.py``), documented
and property-tested:

  * completions are quantized to tick boundaries (the DES completes jobs at
    exact event times);
  * EASY backfill uses the shared vectorized shadow-time reservation
    (:func:`repro.core.passes.shadow_reservation`): candidates start in
    rounds of the DES's first-fit scan, each up to the first candidate it
    skips, and the reserved queue head is never delayed — same as the DES;
  * Step 2 shrink is applied once per tick rather than to fixpoint — the
    schedule converges over subsequent ticks (the JAX engine runs *every*
    tick, so the paper's tick semantics still hold).

For paper-figure numbers use the numpy DES; use this engine for jit/vmap
sweeps, property tests and the elastic-training manager.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .jobs import DONE, PENDING, QUEUED, RUNNING, Workload
from .passes import (PassParams, _speedup_f32 as _speedup, schedule_tick,
                     start_policies)
from .scenario import DEFAULT_BACKFILL_DEPTH
from .strategies import StrategySpec, effective_queue_order


class JobArrays(NamedTuple):
    """Device-resident SoA mirror of :class:`repro.core.jobs.Workload`."""

    submit: jax.Array      # f32 (n,)
    runtime: jax.Array     # f32 (n,)
    walltime: jax.Array    # f32 (n,) reservation estimates use this
    nodes_req: jax.Array   # i32 (n,)
    malleable: jax.Array   # bool (n,)
    min_nodes: jax.Array   # i32 (n,)
    max_nodes: jax.Array   # i32 (n,)
    pref_nodes: jax.Array  # i32 (n,)
    pfrac: jax.Array       # f32 (n,)
    rank: jax.Array        # i32 (n,) FCFS order (argsort of submit)
    on_demand: jax.Array   # bool (n,) queue-priority class

    @staticmethod
    def from_workload(w: Workload) -> "JobArrays":
        order = np.argsort(w.submit, kind="stable")
        rank = np.empty(w.n_jobs, dtype=np.int32)
        rank[order] = np.arange(w.n_jobs, dtype=np.int32)
        return JobArrays(
            submit=jnp.asarray(w.submit, jnp.float32),
            runtime=jnp.asarray(w.runtime, jnp.float32),
            walltime=jnp.asarray(w.walltime, jnp.float32),
            nodes_req=jnp.asarray(w.nodes_req, jnp.int32),
            malleable=jnp.asarray(w.malleable, jnp.bool_),
            min_nodes=jnp.asarray(w.min_nodes, jnp.int32),
            max_nodes=jnp.asarray(w.max_nodes, jnp.int32),
            pref_nodes=jnp.asarray(w.pref_nodes, jnp.int32),
            pfrac=jnp.asarray(w.pfrac, jnp.float32),
            rank=jnp.asarray(rank, jnp.int32),
            on_demand=jnp.asarray(w.on_demand, jnp.bool_),
        )

    @staticmethod
    def stack(variants: Sequence["JobArrays"]) -> "JobArrays":
        """Stack same-length variants into batched (B, n) arrays."""
        return JobArrays(*[jnp.stack(a) for a in zip(*variants)])


class SimState(NamedTuple):
    state: jax.Array      # i32 (n,) PENDING/QUEUED/RUNNING/DONE
    alloc: jax.Array      # i32 (n,)
    remaining: jax.Array  # f32 (n,) fraction of work left
    start_t: jax.Array    # f32 (n,)
    end_t: jax.Array      # f32 (n,)
    expand_ops: jax.Array  # i32 (n,)
    shrink_ops: jax.Array  # i32 (n,)


class SimTrace(NamedTuple):
    busy: jax.Array        # i32 (T,) busy nodes after each tick's schedule
    queue_len: jax.Array   # i32 (T,)


@functools.partial(
    jax.jit,
    static_argnames=("strategy", "capacity", "tick", "n_ticks",
                     "with_classes", "queue_order"),
)
def simulate_scan(
    jobs: JobArrays,
    strategy: StrategySpec,
    capacity: int,
    tick: float,
    n_ticks: int,
    backfill_depth: int = DEFAULT_BACKFILL_DEPTH,
    with_classes: bool = False,
    queue_order: str = "fcfs",
) -> Tuple[SimState, SimTrace]:
    """Run ``n_ticks`` scheduler ticks; returns final state + per-tick trace."""
    n = jobs.submit.shape[0]
    # The shared passes want slots in FCFS order: simulate in submit-rank
    # order and scatter results back to the caller's job order at the end.
    order = jnp.argsort(jobs.rank)
    sj = JobArrays(*[a[order] for a in jobs])
    want, floor, sfloor, prio_ref = start_policies(
        strategy, sj.malleable, sj.min_nodes, sj.pref_nodes, sj.nodes_req,
        xp=jnp)
    s_ref = _speedup(sj.nodes_req, sj.pfrac)
    with_sjf = effective_queue_order(strategy, queue_order) == "sjf"
    params = PassParams(
        malleable=sj.malleable & bool(strategy.malleable),
        min_nodes=sj.min_nodes, max_nodes=sj.max_nodes,
        want=want, floor=floor, shrink_floor=sfloor, prio_ref=prio_ref,
        pfrac=sj.pfrac, wall_work=sj.walltime * s_ref,
        on_demand=sj.on_demand,
        pref_nodes=sj.pref_nodes,
        sort_key=sj.walltime if with_sjf else None,
    )
    depth = jnp.asarray(backfill_depth, jnp.int32)
    # conservative static pass bounds: every allocation and priority
    # reference lies within a few multiples of the cluster size
    prio_lo, prio_hi = -4 * int(capacity), 4 * int(capacity)
    span_max = 4 * int(capacity)

    init = SimState(
        state=jnp.full((n,), PENDING, jnp.int32),
        alloc=jnp.zeros((n,), jnp.int32),
        remaining=jnp.ones((n,), jnp.float32),
        start_t=jnp.full((n,), jnp.nan, jnp.float32),
        end_t=jnp.full((n,), jnp.nan, jnp.float32),
        expand_ops=jnp.zeros((n,), jnp.int32),
        shrink_ops=jnp.zeros((n,), jnp.int32),
    )

    def step(st: SimState, k):
        t = (k.astype(jnp.float32) + 1.0) * tick  # schedule at end of tick k
        # 1. progress running jobs over this tick
        running = st.state == RUNNING
        rate = _speedup(st.alloc, sj.pfrac) / (s_ref * sj.runtime)
        remaining = jnp.where(running, st.remaining - tick * rate, st.remaining)
        # 2. completions (quantized to tick end)
        done_now = running & (remaining <= 1e-6)
        state = jnp.where(done_now, DONE, st.state)
        end_t = jnp.where(done_now, t, st.end_t)
        alloc = jnp.where(done_now, 0, st.alloc)
        remaining = jnp.where(done_now, 0.0, remaining)
        # 3. arrivals
        arrived = (state == PENDING) & (sj.submit <= t)
        state = jnp.where(arrived, QUEUED, state)

        running0 = state == RUNNING
        alloc0 = alloc

        # 4. shared Steps 1-3 scheduling pass (policy core)
        state, alloc, start_t = schedule_tick(
            params, state, alloc, remaining, st.start_t, True,
            jnp.int32(capacity), t,
            structure=(strategy.structure if strategy.malleable
                       else "greedy"),
            fill_rounds=2, prio_lo=prio_lo, prio_hi=prio_hi,
            span_max=span_max, backfill_depth=depth,
            with_classes=with_classes, with_sjf=with_sjf,
            pool_share=jnp.float32(strategy.pool_share),
            steal_margin=jnp.int32(strategy.steal_margin))

        # 5. net per-tick op accounting (jobs running before & after)
        still = running0 & (state == RUNNING)
        d = alloc - alloc0
        expand_ops = st.expand_ops + (still & (d > 0)).astype(jnp.int32)
        shrink_ops = st.shrink_ops + (still & (d < 0)).astype(jnp.int32)

        busy = jnp.sum(jnp.where(state == RUNNING, alloc, 0))
        qlen = jnp.sum(state == QUEUED)
        new = SimState(state, alloc, remaining, start_t, end_t,
                       expand_ops, shrink_ops)
        return new, (busy.astype(jnp.int32), qlen.astype(jnp.int32))

    final, (busy, qlen) = jax.lax.scan(init=init, xs=jnp.arange(n_ticks), f=step)
    final = SimState(*[a[jobs.rank] for a in final])  # back to caller order
    return final, SimTrace(busy=busy, queue_len=qlen)


def simulate_jax(workload: Workload, capacity: int, tick: float,
                 n_ticks: int, strategy: StrategySpec,
                 backfill_depth: int = DEFAULT_BACKFILL_DEPTH,
                 queue_order: str = "fcfs",
                 ) -> Tuple[SimState, SimTrace]:
    """Convenience wrapper: Workload -> device arrays -> scan."""
    return simulate_scan(JobArrays.from_workload(workload), strategy,
                         int(capacity), float(tick), int(n_ticks),
                         backfill_depth,
                         with_classes=bool(np.any(workload.on_demand)),
                         queue_order=queue_order)


@functools.lru_cache(maxsize=None)
def _batched_sim(strategy: StrategySpec, capacity: int, tick: float,
                 n_ticks: int, with_classes: bool, queue_order: str):
    """One jitted vmap of :func:`simulate_scan` per static configuration."""
    return jax.jit(jax.vmap(
        lambda jobs, depth: simulate_scan(jobs, strategy, capacity, tick,
                                          n_ticks, depth,
                                          with_classes=with_classes,
                                          queue_order=queue_order)))


def simulate_scan_batch(jobs: JobArrays, strategy: StrategySpec,
                        capacity: int, tick: float, n_ticks: int,
                        backfill_depth=None,
                        queue_order: str = "fcfs") -> Tuple[SimState, SimTrace]:
    """Batched entry point: ``jobs`` fields are (B, n); one lane per variant.

    The strategy axis stays static (one jit per strategy); proportion/seed
    variants ride the leading batch axis.  ``backfill_depth`` may be a
    scalar or a (B,) array (per-lane depths share the compilation).  For
    the high-throughput event-stepped engine use :mod:`repro.sweep.batch`
    instead — this wrapper runs the dense per-tick scan and is intended
    for moderate grids and property tests.
    """
    B = jobs.submit.shape[0]
    if backfill_depth is None:
        backfill_depth = DEFAULT_BACKFILL_DEPTH
    depth = jnp.broadcast_to(
        jnp.asarray(backfill_depth, jnp.int32), (B,))
    with_classes = bool(jnp.any(jobs.on_demand))
    return _batched_sim(strategy, int(capacity), float(tick),
                        int(n_ticks), with_classes,
                        str(queue_order))(jobs, depth)
