"""Event-stepped batched scheduling engine for sweep grids.

Evaluates many (strategy-policy, proportion, seed — and, since engine v2,
*workload/cluster*) lanes of the paper's sweep in lockstep on one device.
The scheduling passes themselves (Steps 1-3, EASY shadow-time backfill,
greedy/balanced shrink-expand) live in :mod:`repro.core.passes` — the
single policy core shared with the numpy DES and the dense-tick
``sim_jax`` engine.  This module owns only the simulation substrate:

1. **Event-quantized steps, not ticks.**  Like the reference DES
   (``core/simulator.py``), scheduler state only changes on the first tick
   after a job submission or completion, so each ``lax.scan`` step jumps to
   the next event's tick instead of walking every tick (~2 steps/job vs.
   tens of thousands of ticks per trace).  When a scheduling pass changed
   state while jobs stayed queued, the next step is clamped to ``t + tick``
   so the pass converges over subsequent ticks exactly like dense per-tick
   ElastiSim (the documented ``sim_jax`` fidelity model).

2. **Active-set windowing over a bucketed ladder.**  Per-step work is
   O(window), not O(jobs): each lane's queued+running jobs (plus a prefetch
   reserve of upcoming arrivals) are compacted into a fixed ``W``-slot
   buffer every ``chunk`` steps.  Buffer slots stay in FCFS (submit-rank)
   order, so the FCFS start pass is a masked cumulative sum with no
   sorting.  A lane that would advance past its last prefetched arrival
   freezes until the next compaction; if no lane can advance at all the
   driver escalates the window.  Window sizes come from a small static
   menu of power-of-two buckets (:func:`window_ladder`), and the starting
   bucket is picked from a lane-statics lower bound on the peak active set
   (:func:`lane_statics`), so a whole sweep compiles at most
   ``len(buckets)`` chunk kernels — typically exactly one — instead of one
   per 2x escalation step.  Buckets above the start can be pre-compiled on
   a background thread (``EngineConfig.aot_warmup``) so an escalation hits
   a warm executable instead of stalling the run.

2b. **Event compression.**  Each scan step retires up to
   ``EngineConfig.events`` per-lane events instead of exactly one: a lane
   keeps advancing through consecutive events whose scheduling pass is
   provably a no-op (no queued jobs and no expansion possible), and the
   single :func:`~repro.core.passes.schedule_tick` per step runs only for
   lanes whose last event needs it.  Every micro-advance replays the exact
   per-event arithmetic of the one-event step and skipped passes are
   bitwise no-ops, so results are bit-identical for any ``events`` setting
   while completion-dominated tails shrink their scan trip count.

3. **Multi-trace padded batching.**  ``capacity`` and ``tick`` are per-lane
   *data* and shorter traces are padded with never-arriving jobs
   (:func:`concat_lanes`), so lanes of *different* workloads and clusters
   stack into one batch and a single compilation serves all four
   supercomputer grids.  Per-lane results are bit-identical to running each
   workload's batch alone (padding contributes zeros to every reduction).

Strategy *structure* is static per compiled engine (greedy / balanced /
pooled / stealing, plus the ``with_sjf`` queue-order flag — see
``docs/strategies.md``); strategy *parameters* (start want/floor, shrink
floor, priority reference, preferred allocation, pool share, steal
margin, queue-order sort key) are data, so all registry strategies of one
structure share one compilation and one batch.  FCFS lanes carry a
monotone sort key, so an all-FCFS batch compiles ``with_sjf`` away
entirely and mixed FCFS+SJF batches share the permuted pass.

Because per-lane results are independent of batch composition, a batch can
also be *split* along the lane axis (:func:`take_lanes` / :func:`pad_lanes`)
and executed as smaller chunks — sequentially on memory-bounded boxes, or
sharded across local devices — without changing any lane's result; that
execution layer lives in :mod:`repro.sweep.shard`.

Fidelity vs. the reference DES (documented in ``sweep/README.md``):
completions and starts quantized to tick boundaries; EASY backfill honours
the head's shadow-time reservation (:func:`repro.core.passes.
shadow_reservation`) and scans candidates in queue order as the DES does,
but a pass stops after ``fill_rounds`` skipped candidates and the scan goes
on at the next tick; shrink/expand tie-break in FCFS order
rather than the DES running-set insertion order; scheduling converges over
subsequent ticks instead of an in-tick fixpoint.  ``python -m
repro.experiments --engine jax --crosscheck`` quantifies the resulting
metric deltas against the DES per cell.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.jobs import DONE, PENDING, QUEUED, RUNNING, Workload
from repro.core.passes import (PassParams, prefix_sum, schedule_tick,
                               start_policies)
from repro.core.scenario import DEFAULT_BACKFILL_DEPTH
from repro.core.speedup import (TransformConfig, amdahl_speedup,
                                batched_malleable_params)
from repro.core.strategies import StrategySpec, effective_queue_order
from repro.sweep.compact import window_in, window_out

# Bump when engine semantics change: invalidates sweep-cache entries.
# v2: shadow-time EASY backfill (head reservation) via the shared policy
# core; per-lane capacity/tick; multi-trace padded batching.
# v3: the EASY scan is bounded by backfill_depth (per-lane data, same
# rank cutoff as the DES queue slice) instead of scanning the whole
# active window; workload-class queue priority (on-demand lanes).
# v4: data-parameterised strategy registry — pooled / stealing pass
# structures (pref_common_pool, steal_agreement), per-lane pool-share /
# steal-margin / preferred-allocation data, and the queue-order axis
# (per-lane SJF sort keys permuting the slot-window queue order).
# v5: each EASY fill round is one first-fit pass in queue order, as the
# DES scans, in place of three passes (finishes-before-the-reservation
# candidates first, then the spare-pool ones at want, then at floor);
# a job completes at a tick only if at most _REM_EPS of a tick of its
# work is left, as the DES ends it there (the threshold was a fraction
# of the job, which let a job of more than 1e5 ticks end a tick early).
ENGINE_VERSION = 5

_TICK_EPS = 1e-6   # ceil guard, matches the DES event quantization
_REM_EPS = 1e-3    # completion threshold: remaining run time, in ticks


class SweepEngineError(RuntimeError):
    """The engine cannot make progress even at the maximum window size."""


class BatchedLanes(NamedTuple):
    """Fixed-shape lane batch: one lane per (workload, strategy, prop, seed).

    Jobs are pre-sorted by submission time so array index == FCFS rank.
    Padding slots (from :func:`concat_lanes`) carry ``submit == +inf`` and
    never arrive.  ``capacity``/``tick`` are per-lane so lanes of different
    clusters share one compilation.
    """

    submit: jax.Array        # f32 (B, n) ascending; +inf on padding
    malleable: jax.Array     # bool (B, n)
    min_nodes: jax.Array     # i32 (B, n)
    max_nodes: jax.Array     # i32 (B, n)
    pfrac: jax.Array         # f32 (B, n)
    inv_ref: jax.Array       # f32 (B, n): 1 / (S(nodes_req) * runtime)
    wall_work: jax.Array     # f32 (B, n): walltime * S(nodes_req)
    want: jax.Array          # i32 (B, n) start-pass target allocation
    floor: jax.Array         # i32 (B, n) smallest start allocation
    shrink_floor: jax.Array  # i32 (B, n) smallest Step-2 allocation
    prio_ref: jax.Array      # i32 (B, n): greedy priority = alloc - prio_ref
    on_demand: jax.Array     # bool (B, n) queue-priority class
    pref_nodes: jax.Array    # i32 (B, n) preferred allocation ([pooled])
    sort_key: jax.Array      # f32 (B, n) queue-order key (submit rank
                             # under FCFS — monotone — walltime under SJF)
    capacity: jax.Array      # i32 (B,) cluster nodes of the lane
    tick: jax.Array          # f32 (B,) scheduling granularity of the lane
    backfill_depth: jax.Array  # i32 (B,) EASY scan bound of the lane
    pool_share: jax.Array    # f32 (B,) shared-pool fraction ([pooled])
    steal_margin: jax.Array  # i32 (B,) slack above average ([stealing])

    @property
    def n_lanes(self) -> int:
        return self.malleable.shape[0]

    @property
    def n_jobs(self) -> int:
        return self.malleable.shape[1]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    structure: str = "greedy"  # static pass structure of the batch's
                               # lanes: greedy|balanced|pooled|stealing
    window: int = 0           # ladder floor (starting bucket); 0 = auto:
                              # pick the bucket covering the lane-statics
                              # peak-active bound (128-slot ladder floor)
    chunk: int = 160          # scan steps between compactions
    fill_rounds: int = 2      # shadow-backfill fill rounds per pass
    reserve_slack: int = 64   # min arrival-prefetch slots kept in the window
    max_steps_factor: int = 16  # step budget = factor * n_jobs + 2048
    expand_backend: str = "bisect"  # bisect | pallas | pallas-interpret |
                                    # fused | fused-interpret
    events: int = 4           # max per-lane events retired per scan step
                              # (results-neutral; 1 = one event per step)
    aot_warmup: bool = True   # pre-compile upper ladder buckets on a
                              # background thread (results-neutral)


def build_lanes(
    workload: Workload,
    cluster_nodes: int,
    lanes: Sequence[Tuple[StrategySpec, float, int]],
    config: TransformConfig = TransformConfig(),
    tick: float = 1.0,
    backfill_depth: int = DEFAULT_BACKFILL_DEPTH,
    queue_order: str = "fcfs",
) -> Tuple[BatchedLanes, np.ndarray]:
    """Stack (strategy, proportion, seed) lanes into device arrays.

    All strategies in ``lanes`` must share the same engine pass structure
    (``strategy.structure``; non-malleable lanes run any structure as
    data).  ``queue_order`` is the scenario's queue order — a strategy
    that pins its own (``rigid_sjf``) overrides it per lane
    (:func:`repro.core.strategies.effective_queue_order`); FCFS lanes get
    a monotone (submit-rank) sort key, SJF lanes their walltime
    estimates.  Returns the batch plus ``order``, the submit-sort
    permutation (results come back in sorted order; apply
    ``np.argsort(order)`` to recover original job order).
    """
    if len({s.structure for s, _, _ in lanes if s.malleable}) > 1:
        raise ValueError(
            "lanes mix engine pass structures (greedy/balanced/pooled/"
            "stealing); group lanes by strategy.structure")
    order = np.argsort(workload.submit, kind="stable")
    w = workload.take(order)
    params = batched_malleable_params(
        w, [(prop, seed) for _, prop, seed in lanes], cluster_nodes, config)

    B = len(lanes)
    n = w.n_jobs
    req = np.tile(w.nodes_req, (B, 1))
    mall = params["malleable"]
    mn, mx = params["min_nodes"], params["max_nodes"]
    pref, pfrac = params["pref_nodes"], params["pfrac"]

    want = np.empty_like(req)
    floor = np.empty_like(req)
    sfloor = np.empty_like(req)
    prio_ref = np.empty_like(req)
    sort_key = np.empty((B, n), np.float32)
    pool_share = np.empty((B,), np.float32)
    steal_margin = np.empty((B,), np.int32)
    fcfs_key = np.arange(n, dtype=np.float32)  # monotone: identity perm
    for b, (strat, _, _) in enumerate(lanes):
        if not strat.malleable:
            mall[b] = False
            mn[b] = mx[b] = req[b]
        want[b], floor[b], sfloor[b], prio_ref[b] = start_policies(
            strat, mall[b], mn[b], pref[b], req[b])
        sjf = effective_queue_order(strat, queue_order) == "sjf"
        sort_key[b] = w.walltime if sjf else fcfs_key
        pool_share[b] = strat.pool_share
        steal_margin[b] = strat.steal_margin

    s_ref = amdahl_speedup(req, pfrac)
    batch = BatchedLanes(
        submit=jnp.asarray(np.tile(w.submit, (B, 1)), jnp.float32),
        malleable=jnp.asarray(mall),
        min_nodes=jnp.asarray(mn, jnp.int32),
        max_nodes=jnp.asarray(mx, jnp.int32),
        pfrac=jnp.asarray(pfrac, jnp.float32),
        inv_ref=jnp.asarray(1.0 / (s_ref * w.runtime[None, :]), jnp.float32),
        wall_work=jnp.asarray(w.walltime[None, :] * s_ref, jnp.float32),
        want=jnp.asarray(want, jnp.int32),
        floor=jnp.asarray(floor, jnp.int32),
        shrink_floor=jnp.asarray(sfloor, jnp.int32),
        prio_ref=jnp.asarray(prio_ref, jnp.int32),
        on_demand=jnp.asarray(np.tile(w.on_demand, (B, 1))),
        pref_nodes=jnp.asarray(pref, jnp.int32),
        sort_key=jnp.asarray(sort_key, jnp.float32),
        capacity=jnp.full((B,), int(cluster_nodes), jnp.int32),
        tick=jnp.full((B,), float(tick), jnp.float32),
        backfill_depth=jnp.full((B,), int(backfill_depth), jnp.int32),
        pool_share=jnp.asarray(pool_share, jnp.float32),
        steal_margin=jnp.asarray(steal_margin, jnp.int32),
    )
    return batch, order


def concat_lanes(batches: Sequence[BatchedLanes]) -> BatchedLanes:
    """Concatenate lane batches of *different* workloads into one batch.

    Shorter traces are right-padded with never-arriving jobs
    (``submit = +inf``); :func:`simulate_lanes` marks padding DONE at
    initialization, so it contributes zeros to every masked reduction and
    per-lane results are bit-identical to the unpadded single-workload run.
    """
    n_max = max(b.n_jobs for b in batches)
    pad_fill = {
        "submit": jnp.float32(jnp.inf), "malleable": False, "min_nodes": 1, "max_nodes": 1,
        "pfrac": jnp.float32(0.0), "inv_ref": jnp.float32(1.0),
        "wall_work": jnp.float32(1.0), "want": 1, "floor": 1,
        "shrink_floor": 1, "prio_ref": 0, "on_demand": False,
        "pref_nodes": 1,
        # padding must sort behind every real job in the permuted queue
        "sort_key": jnp.float32(jnp.inf),
    }

    def pad(name, arr, n):
        if arr.ndim == 1 or n == n_max:  # (B,) per-lane fields need no pad
            return arr
        return jnp.pad(arr, ((0, 0), (0, n_max - n)),
                       constant_values=pad_fill[name])

    return BatchedLanes(*[
        jnp.concatenate([pad(name, getattr(b, name), b.n_jobs)
                         for b in batches], axis=0)
        for name in BatchedLanes._fields
    ])


def take_lanes(batch: BatchedLanes, lo: int, hi: int) -> BatchedLanes:
    """Slice a contiguous lane range ``[lo, hi)`` out of a batch.

    Every field of :class:`BatchedLanes` is lane-leading (``(B, n)`` or
    ``(B,)``), so the slice is uniform.  Per-lane results are independent
    of batch composition (the multi-trace bit-parity property), which is
    what lets :mod:`repro.sweep.shard` stream a big batch as smaller lane
    chunks without changing any cell.
    """
    return BatchedLanes(*[getattr(batch, name)[lo:hi]
                          for name in BatchedLanes._fields])


def pad_lanes(batch: BatchedLanes, width: int) -> BatchedLanes:
    """Right-pad a batch to ``width`` lanes by repeating its first lane.

    Repeating an existing lane keeps every batch-level static derived from
    lane maxima/minima (priority bounds, class gating, depth cutoff,
    window peeks) unchanged, so padded lanes cannot perturb the real ones;
    callers discard the padding rows from the result.
    """
    b = batch.n_lanes
    if width < b:
        raise ValueError(f"cannot pad {b} lanes down to {width}")
    if width == b:
        return batch
    idx = np.concatenate([np.arange(b), np.zeros(width - b, np.int64)])
    return BatchedLanes(*[jnp.take(getattr(batch, name), idx, axis=0)
                          for name in BatchedLanes._fields])


def _peak_active_bound(batch: BatchedLanes) -> int:
    """Lower bound on the largest per-lane peak active (queued+running) set.

    Two O(n log n) numpy bounds per lane, both provable lower bounds of
    the true peak (a job is active on ``[submit, end_t]`` and
    ``end_t >= submit + minimal service duration``), combined by max:

    * **no-wait interval peak** — overlap count of the minimal-duration
      intervals ``[submit, submit + dur(max_nodes)]``;
    * **fluid backlog peak** — arrivals minus the most completions the
      cluster's node-seconds budget ``capacity * (t - t0)`` could possibly
      have served by each arrival instant (each job costs at least
      ``1 / inv_ref`` node-seconds, its single-node work).

    The bound only *guides* the starting window bucket — the window is
    results-neutral and escalation corrects any under-estimate — but a
    good guess is what collapses the compile ladder to one variant.
    """
    submit = np.asarray(batch.submit, np.float64)
    finite = np.isfinite(submit)
    if not np.any(finite):
        return 0
    inv_ref = np.asarray(batch.inv_ref, np.float64)
    pfrac = np.asarray(batch.pfrac, np.float64)
    mx = np.maximum(np.asarray(batch.max_nodes, np.float64), 1.0)
    s_max = 1.0 / ((1.0 - pfrac) + pfrac / mx)
    dur_min = 1.0 / np.maximum(inv_ref * s_max, 1e-30)

    # (a) no-wait interval overlap peak (+1 at submit, -1 at earliest end)
    t_pts = np.concatenate(
        [np.where(finite, submit, np.inf),
         np.where(finite, submit + dur_min, np.inf)], axis=1)
    delta = np.concatenate(
        [finite.astype(np.int64), -finite.astype(np.int64)], axis=1)
    order = np.argsort(t_pts, axis=1, kind="stable")
    overlap = int(np.max(np.cumsum(
        np.take_along_axis(delta, order, axis=1), axis=1)))

    # (b) fluid backlog: active(t_i) >= arrivals(t_i) - max completions,
    # where completions by t_i are capped by the node-seconds budget spent
    # on the cheapest jobs (1/inv_ref node-seconds each, served at most
    # capacity nodes at once from the first submission on)
    cap = np.asarray(batch.capacity, np.float64)[:, None]
    ns_min = np.where(finite, 1.0 / np.maximum(inv_ref, 1e-30), np.inf)
    ns_sorted = np.sort(ns_min, axis=1)
    cum_ns = np.cumsum(np.where(np.isfinite(ns_sorted), ns_sorted, 0.0),
                       axis=1)
    sub_sorted = np.sort(np.where(finite, submit, np.inf), axis=1)
    t0 = sub_sorted[:, :1]
    budget = np.where(np.isfinite(sub_sorted),
                      cap * (sub_sorted - t0), np.inf)
    backlog = 0
    arrived = np.arange(1, budget.shape[1] + 1)
    for b in range(budget.shape[0]):
        real = np.isfinite(sub_sorted[b])
        if not np.any(real):
            continue
        done_max = np.searchsorted(cum_ns[b], budget[b], side="right")
        backlog = max(backlog, int(np.max((arrived - done_max)[real])))
    return max(overlap, backlog)


def lane_statics(batch: BatchedLanes) -> Dict[str, int]:
    """Batch-level static compile parameters derived from lane data.

    ``prio_lo``/``prio_hi``/``span_max`` bound the greedy/balanced passes'
    integer and level bisections, ``with_classes`` gates the on-demand
    queue-priority passes, ``with_sjf`` gates the queue-order permutation
    (an all-FCFS batch carries monotone sort keys and compiles the flag
    away), ``min_depth`` decides whether the EASY rank cutoff can bind,
    and ``peak_active`` (a lower bound on the largest per-lane active
    set, :func:`_peak_active_bound`) picks the starting window bucket.  They only need to *cover* the lanes actually run, so
    a chunked execution (:mod:`repro.sweep.shard`) computes them once on
    the **full** batch and reuses them for every chunk — keeping each
    chunk's compiled pass (notably the balanced level bisection, whose
    iteration count follows ``span_max``) bit-identical to the monolithic
    batch's, every chunk on one compilation, and every chunk on the same
    window bucket.
    """
    sk = np.asarray(batch.sort_key, np.float64)
    sk = np.where(np.isfinite(sk), sk, np.finfo(np.float64).max)
    return {
        "prio_lo": -int(np.max(np.asarray(batch.prio_ref))),
        "prio_hi": int(np.max(np.asarray(batch.max_nodes
                                         - batch.prio_ref))),
        "span_max": int(np.max(np.asarray(batch.max_nodes
                                          - batch.min_nodes))),
        "with_classes": bool(np.any(np.asarray(batch.on_demand))),
        # non-monotone sort keys are exactly the lanes whose queue-order
        # permutation is not the identity (inf padding maps to the float
        # max, so trailing padding never forces the flag on)
        "with_sjf": bool(np.any(np.diff(sk, axis=-1) < 0)),
        "min_depth": int(np.min(np.asarray(batch.backfill_depth))),
        "peak_active": _peak_active_bound(batch),
    }


@jax.jit
def _peek_active(state):
    """Largest per-lane queued+running count — the window lower bound."""
    active = (state == QUEUED) | (state == RUNNING)
    return jnp.max(jnp.sum(active, axis=-1))


# Compile keys (the full static configuration of `_chunk_fn`) already seen
# in this process.  The first `run_chunk` call at a key traces + compiles;
# later calls replay the jitted executable — so "first seen here" is
# exactly "this call paid the compile" (module-level like jit's own cache,
# so a second in-process run correctly reports zero retraces).
_COMPILED_KEYS: set = set()

# Background-AOT state: executables compiled off-thread via
# `jit(...).lower(...).compile()`, keyed like `_COMPILED_KEYS`.  Module
# level on purpose: a later chunk (or run) at the same key must call the
# warm executable, not re-trace through jit's dispatch cache.
_WARM_EXECUTABLES: Dict = {}
_WARM_FUTURES: Dict = {}
_WARM_POOL = None


def _warm_pool():
    global _WARM_POOL
    if _WARM_POOL is None:
        import concurrent.futures
        _WARM_POOL = concurrent.futures.ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="sweep-aot")
    return _WARM_POOL


def window_ladder(floor: int, n: int) -> Tuple[int, ...]:
    """The static window-bucket menu: ``floor * 2^k`` capped at ``n``.

    Every window the engine ever runs at is a rung of this ladder, so a
    whole sweep compiles at most ``len(ladder)`` chunk kernels per engine
    structure — and in practice exactly one, because the starting rung is
    picked from the lane-statics peak-active bound.
    """
    floor = max(1, min(floor, n))
    rungs = [floor]
    while rungs[-1] < n:
        rungs.append(min(2 * rungs[-1], n))
    return tuple(rungs)


def _ladder_cover(ladder: Tuple[int, ...], need: int) -> int:
    """Smallest rung >= ``need`` (the top rung when none is)."""
    for w in ladder:
        if w >= need:
            return w
    return ladder[-1]


def simulate_lanes(batch: BatchedLanes, cfg: EngineConfig,
                   verbose: bool = False,
                   statics: Optional[Dict[str, int]] = None
                   ) -> Dict[str, np.ndarray]:
    """Run every lane to completion; returns per-job outcomes + event trace.

    Output dict (numpy, job axes in submit-sorted order):
      ``state, alloc, start_t, end_t, expand_ops, shrink_ops`` (B, n);
      ``trace_t, trace_busy, trace_qlen`` (B, S) event-step timeline
      (``trace_busy[k]`` holds on ``[trace_t[k], trace_t[k+1])``; repeated
      timestamps are zero-width — event compression emits them);
      ``bf_starts, sched_steps`` (B,) device-accumulated scheduling
      counters (out-of-order EASY starts / processed scheduling ticks per
      lane — invariant under chunking, sharding, window size and event
      compression, so they may ride in cell metrics without breaking
      execution-plan parity); ``steps, window, finished``; and
      execution-only observability scalars ``compile_s, execute_s,
      compile_variants, retraces, aot_rejits, warm_hits, escalations,
      compressed_events`` (wall-clock split by whether the chunk call
      paid a trace+compile, the distinct static chunk configurations this
      run dispatched — the compile-ladder width ``tools/check_perf.py``
      gates — the number of fresh foreground compile variants, warm AOT
      executables that rejected their arguments and were re-jitted, warm
      AOT executables used, window escalations, and per-lane events retired
      beyond the first of their scan step — these describe *this
      execution*, never the cells, and must stay out of metrics).

    The window walks a static bucket ladder (:func:`window_ladder`): the
    starting rung covers the lane-statics peak-active bound (or the
    explicit ``cfg.window`` floor), before each chunk the largest active
    set is peeked and ``W`` escalates straight to the covering rung
    whenever active + arrival slack would not fit (or no lane advanced in
    the previous chunk), and it de-escalates with hysteresis — but only
    onto rungs that already have a compiled executable, so de-escalation
    can never pay a fresh compile.  With ``cfg.aot_warmup`` the rungs
    between the start and the predicted bucket (plus the next rung after
    any escalation) are lowered + compiled on a background thread, so an
    escalation hits a warm executable instead of stalling.  Simulation
    state lives in full-size arrays between chunks, so window switches
    continue the run instead of restarting it.

    If lanes are still unfinished when the step budget runs out, their
    jobs keep ``end_t = nan`` and ``finished`` is False (metrics report
    them as unfinished).

    ``statics`` overrides the batch-derived compile parameters
    (:func:`lane_statics`); chunked execution passes the full batch's so
    every chunk shares one compilation and the monolithic bit-parity.
    """
    n, B = batch.n_jobs, batch.n_lanes
    if statics is None:
        with obs.span("sweep.statics", lanes=B):
            statics = lane_statics(batch)
    st = statics
    # static greedy-priority bounds: every alloc lies in [0, max_nodes]
    prio_lo, prio_hi = st["prio_lo"], st["prio_hi"]
    span_max = st["span_max"]
    # static: class-free batches compile the class-free pass (no overhead)
    with_classes = st["with_classes"]
    # static: all-FCFS batches compile the queue-order permutation away
    with_sjf = bool(st.get("with_sjf", False))
    # queue ranks never exceed the window's queued count, so a depth >= W
    # cannot cut the scan: such compilations skip the rank mask entirely
    # (the default-depth grid pays nothing for the axis)
    min_depth = st["min_depth"]
    ladder = window_ladder(int(cfg.window or 128), n)
    # the rung the statics bound predicts the run will need; an explicit
    # cfg.window pins the *start* to the ladder floor instead (that is
    # how tests force escalation), with the predicted rungs warmed
    predicted = _ladder_cover(
        ladder, min(int(st.get("peak_active", 0)) + cfg.reserve_slack, n))
    W0 = ladder[0] if cfg.window else predicted
    W = W0

    def key_for(w):
        return (cfg, n, B, w, prio_lo, prio_hi, span_max, with_classes,
                with_sjf, min_depth < w)

    def fn_for(w):
        # module-level cache: one trace/compile per static configuration
        return _chunk_fn(cfg, n, B, w, prio_lo, prio_hi, span_max,
                         with_classes, with_sjf=with_sjf,
                         depth_bounded=min_depth < w)

    real = jnp.isfinite(batch.submit)  # padding slots are born DONE
    full = dict(
        state=jnp.where(real, PENDING, DONE).astype(jnp.int32),
        alloc=jnp.zeros((B, n), jnp.int32),
        remaining=jnp.where(real, 1.0, 0.0).astype(jnp.float32),
        start_t=jnp.full((B, n), jnp.nan, jnp.float32),
        end_t=jnp.full((B, n), jnp.nan, jnp.float32),
        expand_ops=jnp.zeros((B, n), jnp.int32),
        shrink_ops=jnp.zeros((B, n), jnp.int32),
    )
    k = jnp.full((B,), -1, jnp.int32)  # last processed tick index
    retrig = jnp.zeros((B,), bool)
    # device-side scheduling counters, accumulated across chunks
    bf = jnp.zeros((B,), jnp.int32)      # out-of-order (backfill) starts
    nact = jnp.zeros((B,), jnp.int32)    # processed scheduling ticks
    ncomp = jnp.zeros((B,), jnp.int32)   # events compressed into steps

    def submit_warm(w):
        """Queue a background lower+compile of rung ``w`` (idempotent)."""
        ckey = key_for(w)
        if (not cfg.aot_warmup or ckey in _COMPILED_KEYS
                or ckey in _WARM_EXECUTABLES or ckey in _WARM_FUTURES):
            return
        fn = fn_for(w)
        args = (batch, full, k, retrig, bf, nact, ncomp)
        _WARM_FUTURES[ckey] = _warm_pool().submit(
            lambda: fn.lower(*args).compile())

    for w in ladder:  # warm the rungs a pinned-start run will escalate to
        if W0 < w <= predicted:
            submit_warm(w)

    traces: List[Tuple[np.ndarray, ...]] = []
    steps = 0
    w_peak = W
    low_streak = 0
    escalations = 0
    retraces = 0
    aot_rejits = 0
    warm_hits = 0
    compile_s = 0.0
    execute_s = 0.0
    used_keys: set = set()  # distinct static configs this run dispatched

    def escalate(need):
        nonlocal W, low_streak, escalations
        W = _ladder_cover(ladder, min(need, n))
        low_streak = 0
        escalations += 1
        obs.counter("sweep.escalations")
        nxt = _ladder_cover(ladder, min(2 * W, n))
        if nxt > W:  # anticipate another escalation off-thread
            submit_warm(nxt)

    max_steps = cfg.max_steps_factor * n + 2048
    while steps < max_steps:
        # host control flow between chunk calls: the device idles through
        # each read back (sweep.peek)
        with obs.span("sweep.peek", window=W, lanes=B) as peek:
            n_active = int(_peek_active(full["state"]))
            peek.set(active=n_active)
            need = n_active + cfg.reserve_slack
            if need > W and W < n:
                escalate(need)
                if verbose:
                    print(f"[sweep.batch] active={n_active} -> window W={W}")
            elif W > W0 and need <= W // 2:
                low_streak += 1
                if low_streak >= 2:
                    # smallest covering rung that already has an executable:
                    # de-escalation never pays a fresh compile
                    down = [w for w in ladder
                            if W0 <= w < W and w >= need
                            and (key_for(w) in _COMPILED_KEYS
                                 or key_for(w) in _WARM_EXECUTABLES)]
                    if down:
                        W, low_streak = min(down), 0
            else:
                low_streak = 0
            w_peak = max(w_peak, W)

            ckey = key_for(W)
            used_keys.add(ckey)
            fn, fut, is_warm, first = None, None, False, False
            if ckey in _WARM_EXECUTABLES:
                fn, is_warm = _WARM_EXECUTABLES[ckey], True
            elif ckey in _WARM_FUTURES:
                fut = _WARM_FUTURES.pop(ckey)
                # blocking on an in-flight background compile is compile
                # time: the wait runs inside the sweep.compile span below
                first = not fut.done()
                is_warm = True
                warm_hits += 1
            else:
                fn = fn_for(W)
                if ckey not in _COMPILED_KEYS:
                    _COMPILED_KEYS.add(ckey)
                    first = True
                    retraces += 1
                    obs.counter("sweep.retraces")
            k_before = np.asarray(k)
        t_call = time.monotonic()
        with obs.span("sweep.compile" if first else "sweep.execute",
                      window=W, lanes=B, scan_steps=cfg.chunk):
            if fut is not None:
                # a failed background compile raises here — the
                # foreground jit would only fail the same way, or hide
                # what the device refused
                fn = _WARM_EXECUTABLES[ckey] = fut.result()
                _COMPILED_KEYS.add(ckey)
            try:
                with obs.span("sweep.dispatch"):
                    out = fn(batch, full, k, retrig, bf, nact, ncomp)
            except Exception:
                if not is_warm:
                    raise
                # an AOT executable can reject its arguments at call time
                # (e.g. sharded inputs); re-jit once, counted and said
                _WARM_EXECUTABLES.pop(ckey, None)
                first = True
                retraces += 1
                aot_rejits += 1
                obs.counter("sweep.retraces")
                obs.counter("sweep.aot_rejits")
                print(f"[sweep.batch] AOT executable for window W={W} "
                      "rejected its arguments; re-jitted", flush=True)
                out = fn_for(W)(batch, full, k, retrig, bf, nact, ncomp)
            full, k, retrig, bf, nact, ncomp, ys, all_done = out
            # host conversion blocks on the device work, so the span (and
            # the compile/execute wall split) covers the real cost
            traces.append(tuple(np.asarray(y) for y in ys))
            done_now = bool(all_done)
        dt_call = time.monotonic() - t_call
        if first:
            compile_s += dt_call
        else:
            execute_s += dt_call
        steps += cfg.chunk
        if done_now:
            break
        with obs.span("sweep.peek", window=W, lanes=B):
            if np.array_equal(k_before, np.asarray(k)):
                # nothing advanced: every lane is frozen waiting for arrivals
                # that do not fit -> the window must grow
                if W >= n:
                    raise SweepEngineError(
                        "engine stalled with the window at the full job count")
                escalate(2 * W)

    # the final state and step timelines read back to the host
    with obs.span("sweep.collect", lanes=B):
        out = {kk: np.asarray(v) for kk, v in full.items()}
        out["trace_t"] = np.concatenate([t for t, _, _ in traces], axis=1)
        out["trace_busy"] = np.concatenate([b for _, b, _ in traces], axis=1)
        out["trace_qlen"] = np.concatenate([q for _, _, q in traces], axis=1)
        out["bf_starts"] = np.asarray(bf)
        out["sched_steps"] = np.asarray(nact)
    out["steps"] = steps
    out["window"] = w_peak
    out["finished"] = bool(np.all(out["state"] == DONE))
    out["compile_s"] = compile_s
    out["execute_s"] = execute_s
    out["compile_variants"] = len(used_keys)
    out["retraces"] = retraces
    out["aot_rejits"] = aot_rejits
    out["warm_hits"] = warm_hits
    out["escalations"] = escalations
    out["compressed_events"] = int(np.sum(np.asarray(ncomp)))
    return out


# The per-job fields a chunk's window carries, and what an empty slot
# holds: job fields in, job state in and back out.
_WINDOW_FIELDS = ("submit", "malleable", "min_nodes", "max_nodes", "pfrac",
                  "inv_ref", "wall_work", "want", "floor", "shrink_floor",
                  "prio_ref", "on_demand", "pref_nodes", "sort_key")
_WINDOW_FILLS = (np.float32(np.inf), False, 1, 1, np.float32(0.0),
                 np.float32(1.0), np.float32(1.0), 1, 1, 1, 0, False, 1,
                 np.float32(np.inf))  # padding sorts last
_STATE_FIELDS = ("state", "alloc", "remaining", "start_t", "end_t",
                 "expand_ops", "shrink_ops")
_STATE_FILLS = (np.int32(DONE), 0, np.float32(0.0), np.float32(np.nan),
                np.float32(np.nan), 0, 0)


@functools.cache  # unbounded on purpose: see the eviction note in the doc
def _chunk_fn(cfg: EngineConfig, n: int, B: int, W: int,
              prio_lo: int, prio_hi: int, span_max: int,
              with_classes: bool = False, with_sjf: bool = False,
              depth_bounded: bool = True):
    """Compile the compaction + K-step scan + scatter-back chunk kernel.

    ``capacity``, ``tick`` and ``backfill_depth`` are lane data (fields of
    the batch), not part of the compile key — one compilation serves every
    cluster (and every depth-swept lane) at a given shape, which is what
    makes the multi-trace batch a single compile.  ``with_classes`` and
    ``with_sjf`` are the lane-derived statics: they gate the on-demand
    queue-priority passes and the queue-order permutation so class-free /
    all-FCFS batches pay nothing for either axis.

    The cache is **unbounded** (`functools.cache`, not an lru_cache with a
    maxsize): an evicted entry would silently recompile mid-sweep on
    variant-heavy grids (depth x classes x ladder rungs x chunk widths),
    and a traced chunk fn is small — the XLA executable it holds is the
    thing worth pinning.  ``_COMPILED_KEYS``/``retraces`` assert on this.

    Each scan step retires up to ``cfg.events`` per-lane events before the
    single Steps-1..3 scheduling pass (event compression, module doc §2b);
    the micro-advances past the first only take events whose scheduling
    pass is provably a bitwise no-op, so results are invariant in
    ``cfg.events`` and the emitted timeline only gains zero-width entries.
    """
    K = cfg.chunk
    E = max(1, int(cfg.events))
    INF = jnp.float32(jnp.inf)

    def step(bj, capacity, tick, depth, arrival_limit, carry, _):
        (bstate, balloc, brem, bstart, bend, beops, bsops,
         k, retrig, frozen, bf, nact, ncomp) = carry

        def micro(st_):
            """Retire one per-lane event (phases 1-4 of the classic step).

            Lanes halt (and stop micro-advancing) at the first event whose
            post-advance state needs a real scheduling pass; events whose
            pass would be a bitwise no-op — nothing queued AND (no free
            nodes OR no expand headroom) — advance straight through.
            """
            (bstate, balloc, brem, bstart, bend,
             k, retrig, frozen, halted, n_adv, nact) = st_
            t = k.astype(jnp.float32) * tick
            running = bstate == RUNNING
            alloc_f = jnp.maximum(balloc.astype(jnp.float32), 1.0)
            s_cur = 1.0 / ((1.0 - bj.pfrac) + bj.pfrac / alloc_f)
            rate = s_cur * bj.inv_ref
            pending = bstate == PENDING
            # one fused reduction over completions and arrivals
            ev = jnp.where(running, t[:, None] + brem / rate,
                           jnp.where(pending, bj.submit, INF))
            t_event = jnp.min(ev, axis=-1)
            t_event = jnp.minimum(t_event,
                                  jnp.where(retrig, t + tick, INF))

            # strictly-future tick: <= k*tick was already processed
            k_cand = jnp.maximum(
                jnp.ceil(t_event / tick - _TICK_EPS).astype(jnp.int32),
                k + 1)
            t_cand = k_cand.astype(jnp.float32) * tick
            # freeze before swallowing an arrival that was not prefetched;
            # halted lanes re-check after their pending scheduling pass
            # (next scan step), exactly where the classic loop checks
            newly_frozen = (t_cand + 0.5 * tick >= arrival_limit) \
                & ~halted & ~frozen
            act = ~frozen & ~halted & ~newly_frozen & jnp.isfinite(t_event)
            k = jnp.where(act, k_cand, k)
            t_next = k.astype(jnp.float32) * tick
            dt = jnp.maximum(t_next - t, 0.0)

            # progress + tick-quantized completions (dt = 0 lanes advance
            # by exactly 0.0: bit-exact identity on brem)
            brem = jnp.where(running, brem - dt[:, None] * rate, brem)
            done_now = running & (brem <= _REM_EPS * tick[:, None] * rate) \
                & act[:, None]
            bstate = jnp.where(done_now, DONE, bstate)
            bend = jnp.where(done_now, t_next[:, None], bend)
            balloc = jnp.where(done_now, 0, balloc)
            brem = jnp.where(done_now, 0.0, brem)

            # arrivals (half-tick slack absorbs f32 rounding of the ceil)
            arrived = pending & act[:, None] & \
                (bj.submit <= (t_next + 0.5 * tick)[:, None])
            bstate = jnp.where(arrived, QUEUED, bstate)

            # halting predicate: the Steps-1..3 pass is a bitwise no-op
            # iff nothing is queued (no starts, no head -> no backfill,
            # no shrink) and expand has no free nodes or no headroom
            run_now = bstate == RUNNING
            queued_ct = jnp.sum((bstate == QUEUED).astype(jnp.int32),
                                axis=-1)
            free_now = capacity - jnp.sum(
                jnp.where(run_now, balloc, 0), axis=-1)
            room_tot = jnp.sum(
                jnp.where(run_now & bj.malleable,
                          jnp.maximum(bj.max_nodes - balloc, 0), 0),
                axis=-1)
            noop = (queued_ct == 0) & ((free_now <= 0) | (room_tot == 0))
            # the classic loop clears retrig after a no-op pass
            retrig = jnp.where(act & noop, False, retrig)
            halted = halted | (act & ~noop)
            frozen = frozen | newly_frozen
            nact = nact + act.astype(jnp.int32)
            n_adv = n_adv + act.astype(jnp.int32)

            busy = jnp.sum(jnp.where(run_now, balloc, 0), axis=-1)
            st_ = (bstate, balloc, brem, bstart, bend,
                   k, retrig, frozen, halted, n_adv, nact)
            return st_, (t_next, busy.astype(jnp.int32), queued_ct)

        def dup(st_):
            # every lane halted/frozen: emit a zero-width duplicate entry
            bstate, balloc = st_[0], st_[1]
            t_now = st_[5].astype(jnp.float32) * tick
            busy = jnp.sum(jnp.where(bstate == RUNNING, balloc, 0),
                           axis=-1)
            qlen = jnp.sum((bstate == QUEUED).astype(jnp.int32), axis=-1)
            return st_, (t_now, busy.astype(jnp.int32), qlen)

        with jax.named_scope("chunk.advance"):
            halted = jnp.zeros_like(frozen)
            n_adv = jnp.zeros((B,), jnp.int32)
            st_ = (bstate, balloc, brem, bstart, bend,
                   k, retrig, frozen, halted, n_adv, nact)
            st_, emit = micro(st_)
            emits = [emit]
            for _ in range(E - 1):
                live = jnp.any(~st_[8] & ~st_[7])  # ~halted & ~frozen
                st_, emit = jax.lax.cond(live, micro, dup, st_)
                emits.append(emit)
            (bstate, balloc, brem, bstart, bend,
             k, retrig, frozen, halted, n_adv, nact) = st_

        running0 = bstate == RUNNING
        alloc0 = balloc
        state0 = bstate
        t_now = k.astype(jnp.float32) * tick
        # shared Steps 1-3 scheduling pass (policy core), once per scan
        # step, on the lanes that halted at an event that needs it
        params = PassParams(
            malleable=bj.malleable, min_nodes=bj.min_nodes,
            max_nodes=bj.max_nodes, want=bj.want, floor=bj.floor,
            shrink_floor=bj.shrink_floor, prio_ref=bj.prio_ref,
            pfrac=bj.pfrac, wall_work=bj.wall_work,
            on_demand=bj.on_demand, pref_nodes=bj.pref_nodes,
            sort_key=bj.sort_key if with_sjf else None)
        bstate, balloc, bstart = schedule_tick(
            params, bstate, balloc, brem, bstart, halted[:, None],
            capacity, t_now, structure=cfg.structure,
            fill_rounds=cfg.fill_rounds, prio_lo=prio_lo, prio_hi=prio_hi,
            span_max=span_max, expand_backend=cfg.expand_backend,
            backfill_depth=depth if depth_bounded else None,
            with_classes=with_classes, with_sjf=with_sjf,
            pool_share=bj.pool_share, steal_margin=bj.steal_margin)

        # per-step accounting: op counts, counters, the timeline fixup
        with jax.named_scope("chunk.account"):
            # net per-invocation op accounting (jobs running before & after)
            still = running0 & (bstate == RUNNING)
            d = balloc - alloc0
            beops = beops + (still & (d > 0)).astype(jnp.int32)
            bsops = bsops + (still & (d < 0)).astype(jnp.int32)

            # scheduling counters (buffer slots are in FCFS submit-rank order,
            # so "an earlier job is still queued after the pass" is an
            # exclusive prefix count).  A start with an earlier job left
            # waiting is exactly an out-of-order (EASY backfill / shrink-
            # admitted) start — the tick-quantized equivalent of the DES's
            # post-hoc rule (core.metrics.backfill_starts), so the counters
            # agree across engines and are execution-plan-invariant.
            started_now = (state0 == QUEUED) & (bstate == RUNNING)
            q_after = bstate == QUEUED
            earlier_q = prefix_sum(q_after) - q_after
            bf = bf + jnp.sum(started_now & (earlier_q > 0),
                              axis=-1).astype(jnp.int32)
            ncomp = ncomp + jnp.maximum(n_adv - 1, 0)

            busy = jnp.sum(jnp.where(bstate == RUNNING, balloc, 0), axis=-1)
            qlen = jnp.sum((bstate == QUEUED).astype(jnp.int32), axis=-1)
            # rerun next tick while a pass changed state and jobs stayed
            # queued (no-op passes were cleared in the micro-advance already).
            # Only lanes whose halting event got a real pass may rewrite the
            # flag: a lane frozen with a retrig pending (its re-tick would
            # swallow an unprefetched arrival) must carry it through the
            # trailing no-op steps and resume the cascade after compaction —
            # overwriting it here would drop a scheduling invocation and shift
            # starts by a tick whenever a freeze lands mid-cascade.
            changed = jnp.any((balloc != alloc0) | (bstate != state0), axis=-1)
            retrig = jnp.where(halted, changed & (qlen > 0), retrig)

            # timeline fixup: the halting event's entry (index n_adv - 1, and
            # every zero-width duplicate after it) was emitted pre-schedule;
            # the classic loop emits post-schedule values at that timestamp
            ts = jnp.stack([e[0] for e in emits])        # (E, B)
            busy_e = jnp.stack([e[1] for e in emits])
            qlen_e = jnp.stack([e[2] for e in emits])
            fix = jnp.arange(E)[:, None] >= jnp.maximum(n_adv - 1, 0)[None, :]
            busy_e = jnp.where(fix, busy.astype(jnp.int32)[None, :], busy_e)
            qlen_e = jnp.where(fix, qlen[None, :], qlen_e)

        carry = (bstate, balloc, brem, bstart, bend, beops, bsops,
                 k, retrig, frozen, bf, nact, ncomp)
        return carry, (ts, busy_e, qlen_e)

    @jax.jit
    def run_chunk(batch, full, k, retrig, bf, nact, ncomp):
        with jax.named_scope("chunk.compact"):
            state = full["state"]
            active = (state == QUEUED) | (state == RUNNING)
            n_active = jnp.sum(active, axis=-1)
            pending = state == PENDING
            ar = jnp.arange(n)[None, :]
            # first still-pending slot (padding is DONE, so this stays within
            # the lane's real jobs; n when everything arrived)
            aptr = jnp.min(jnp.where(pending, ar, n), axis=-1)

            # -- compact active + arrival reserve into W slots (FCFS order) ---
            reserve = jnp.maximum(W - n_active, 0)
            sel = active | (pending & (ar < (aptr + reserve)[:, None]))
            pos = prefix_sum(sel) - 1
            live = sel & (pos < W)
            per_job = [getattr(batch, f) for f in _WINDOW_FIELDS] + \
                [full[f] for f in _STATE_FIELDS]
            win, back = window_in(per_job, live, pos,
                                  _WINDOW_FILLS + _STATE_FILLS, W)
            bj = batch._replace(**dict(zip(_WINDOW_FIELDS, win)))
            n_prefetch = jnp.sum(sel & pending, axis=-1)
            lim_idx = aptr + n_prefetch
            # the submit time of job lim_idx (INF past the last job)
            arrival_limit = jnp.min(
                jnp.where(ar == lim_idx[:, None], batch.submit, INF), axis=-1)

            carry = (*win[len(_WINDOW_FIELDS):],
                     k, retrig, jnp.zeros((B,), bool), bf, nact, ncomp)
        carry, ys = jax.lax.scan(
            lambda c, x: step(bj, batch.capacity, batch.tick,
                              batch.backfill_depth, arrival_limit, c, x),
            carry, None, length=K)
        k, retrig, _frozen, bf, nact, ncomp = carry[len(_STATE_FIELDS):]

        with jax.named_scope("chunk.scatter"):
            full = dict(zip(_STATE_FIELDS, window_out(
                [full[f] for f in _STATE_FIELDS],
                carry[:len(_STATE_FIELDS)], live, back)))
            all_done = jnp.all(full["state"] == DONE)
        ts, busy, qlen = ys  # (K, E, B): E compressed entries per step
        KE = K * E

        def flat(a):
            return a.reshape(KE, B).T

        return (full, k, retrig, bf, nact, ncomp,
                (flat(ts), flat(busy), flat(qlen)), all_done)

    return run_chunk


def chunk_arg_shapes(n: int, B: int, sharding=None) -> tuple:
    """Abstract arguments of one :func:`_chunk_fn` call on ``B`` lanes of
    ``n`` jobs — ``(batch, full, k, retrig, bf, nact, ncomp)`` as
    ``jax.ShapeDtypeStruct`` — so a chunk program can be lowered or
    compiled ahead of time without arrays (the TPU compile tests, the
    chip smoke's kernel check)."""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    f32, i32, b = jnp.float32, jnp.int32, jnp.bool_
    per_job = dict(submit=f32, malleable=b, min_nodes=i32, max_nodes=i32,
                   pfrac=f32, inv_ref=f32, wall_work=f32, want=i32,
                   floor=i32, shrink_floor=i32, prio_ref=i32, on_demand=b,
                   pref_nodes=i32, sort_key=f32)
    per_lane = dict(capacity=i32, tick=f32, backfill_depth=i32,
                    pool_share=f32, steal_margin=i32)
    batch = BatchedLanes(**{k: s((B, n), t) for k, t in per_job.items()},
                         **{k: s((B,), t) for k, t in per_lane.items()})
    full = dict(state=s((B, n), i32), alloc=s((B, n), i32),
                remaining=s((B, n), f32), start_t=s((B, n), f32),
                end_t=s((B, n), f32), expand_ops=s((B, n), i32),
                shrink_ops=s((B, n), i32))
    return (batch, full, s((B,), i32), s((B,), b), s((B,), i32),
            s((B,), i32), s((B,), i32))
