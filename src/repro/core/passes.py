"""The scheduling-policy core: single source of truth for Steps 1-3.

Every simulator in this repo — the exact-event numpy DES
(:mod:`repro.core.simulator`), the dense-tick ``lax.scan`` engine
(:mod:`repro.core.sim_jax`) and the event-stepped batched sweep engine
(:mod:`repro.sweep.batch`) — consumes the paper's scheduling passes
(§2.1 Steps 1-3, Eqs. 1-3) from this module.  No simulator carries a
private copy of start / backfill / shrink / expand logic; fidelity
differences between engines are confined to the *simulation substrate*
(exact event times vs. tick quantization, fixpoint vs. converge-over-ticks)
and documented in ``sweep/README.md``.

Three implementation families live here, matching the three substrates:

1. **Exact argsort-based redistribution** (:func:`greedy_shrink`,
   :func:`greedy_expand`, :func:`balanced_shrink`, :func:`balanced_expand`)
   — pure, vectorized, ``xp``-agnostic (pass ``numpy`` or ``jax.numpy``).
   These are the reference semantics of Steps 2-3 and the oracles the
   sort-free variants are property-tested against.

2. **Exact sequential EASY-backfill** (:func:`fcfs_prefix_exact`,
   :func:`easy_reservation_exact`, :func:`easy_backfill_scan_exact`) —
   the Step-1 start pass with head-reservation shadow time, in the exact
   first-fit order ElastiSim uses.  Consumed by the numpy DES.

3. **Masked fixed-shape vectorized passes** (:func:`schedule_tick` and its
   building blocks) — jit/vmap-friendly, batch-axis agnostic (arrays are
   ``(..., W)`` with slots in FCFS order), sort-free (cumulative sums and
   threshold bisection instead of ``argsort``), including a bisected
   **shadow-time reservation** (:func:`shadow_reservation`) so EASY
   backfill never delays the reserved queue head.  Consumed by ``sim_jax``
   (lane shape ``()``) and the batched sweep engine (lane shape ``(B,)``).

Strategy *structure* (``greedy`` / ``balanced`` / ``pooled`` /
``stealing``, plus the ``with_sjf`` queue-order flag) is a static
argument; strategy *parameters* (start want/floor, shrink floor,
priority reference, preferred allocation, pool share, steal margin,
queue-order sort key) are data (:class:`PassParams` + per-lane scalars),
so all registry strategies share one compiled pass per structure bucket
(``docs/strategies.md``).  The greedy Step-3 expand optionally runs
through the Pallas prefix-waterfill kernel (``repro.kernels.waterfill``)
when ``expand_backend`` is set — see :func:`schedule_tick`.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import numpy as np

from .jobs import QUEUED, RUNNING
from .speedup import amdahl_speedup

_BISECT_ITERS = 24  # 2^-24 level resolution; exact after integer rounding
                    # (max span handled exactly: 2^24 >> any cluster size)

# Shadow-time bisection iterations: trace spans are <= ~2.4e6 s and the
# engines keep time in f32 (ulp ~0.25 s at that magnitude), so 26 halvings
# of [0, t_max] separate any two distinct f32 event estimates.
SHADOW_ITERS = 26
_SHADOW_EPS = 1e-3  # absolute slack on "finishes before the reservation"


def _jnp():
    import jax.numpy as jnp
    return jnp


# ======================================================================
# Start policies (paper §2.1 Step 1 parameters, per strategy)
# ======================================================================
def start_policies(strategy, malleable, mn, pref, req, xp=np):
    """Per-job ``(want, floor, shrink_floor, prio_ref)`` policy arrays.

    ``want``/``floor`` parameterize the Step-1 start pass, ``shrink_floor``
    Step 2, and ``prio_ref`` the greedy priority ``alloc - prio_ref``
    (Eqs. 1-2; AVG's Eq. 3 is the balanced pass structure instead).
    Non-malleable jobs (and every job under a rigid strategy) use their
    rigid request for all four.
    """
    if not strategy.malleable:
        return req, req, req, req

    def pick(which):
        return strategy.pick(which, mn, pref, req)

    want = xp.where(malleable, pick(strategy.start_want), req)
    floor = xp.where(malleable, pick(strategy.start_floor), req)
    sfloor = xp.where(malleable, pick(strategy.shrink_floor), req)
    prio_ref = pick("min" if strategy.priority == "min" else "pref")
    return want, floor, sfloor, prio_ref


# ======================================================================
# 1. Exact argsort-based redistribution (Steps 2-3 reference semantics)
# ======================================================================
def _stable_argsort(key, xp):
    # numpy needs kind="stable"; jax.numpy argsort is stable by default.
    if xp is np:
        return np.argsort(key, kind="stable")
    return xp.argsort(key)


def greedy_shrink(alloc, floor, priority, need, xp=np):
    """Shrink jobs to ``floor`` in descending priority until >= need freed.

    Returns the new allocation array.  Shrinks the *smallest number of jobs*:
    jobs are fully lowered to floor in priority order; the marginal job is
    lowered only as far as needed.  If total surplus < need, frees what it can.
    """
    alloc = xp.asarray(alloc)
    surplus = xp.maximum(alloc - floor, 0)
    order = _stable_argsort(-xp.asarray(priority), xp)
    s_sorted = surplus[order]
    cum = xp.cumsum(s_sorted)
    target = xp.minimum(xp.asarray(need, dtype=cum.dtype), cum[-1] if cum.shape[0] else 0)
    prev = cum - s_sorted
    amt_sorted = xp.clip(target - prev, 0, s_sorted)
    if xp is np:
        amt = np.empty_like(np.asarray(s_sorted))
        amt[np.asarray(order)] = amt_sorted
    else:
        amt = xp.zeros_like(s_sorted).at[order].set(amt_sorted)
    return alloc - amt.astype(alloc.dtype)


def greedy_expand(alloc, cap, priority, idle, xp=np):
    """Expand jobs to ``cap`` in ascending priority until idle exhausted."""
    alloc = xp.asarray(alloc)
    room = xp.maximum(cap - alloc, 0)
    order = _stable_argsort(xp.asarray(priority), xp)
    r_sorted = room[order]
    cum = xp.cumsum(r_sorted)
    target = xp.minimum(xp.asarray(idle, dtype=cum.dtype), cum[-1] if cum.shape[0] else 0)
    prev = cum - r_sorted
    amt_sorted = xp.clip(target - prev, 0, r_sorted)
    if xp is np:
        amt = np.empty_like(np.asarray(r_sorted))
        amt[np.asarray(order)] = amt_sorted
    else:
        amt = xp.zeros_like(r_sorted).at[order].set(amt_sorted)
    return alloc + amt.astype(alloc.dtype)


def _level_targets_xp(level, mn, mx, xp):
    """Integer allocation at relative level ``level`` in [0, 1]."""
    span = (mx - mn) * 1.0  # promote to the backend's default float
    return mn + xp.floor(level * span + 1e-9).astype(mn.dtype)


def balanced_shrink(alloc, mn, mx, need, xp=np):
    """AVG shrink: lower all jobs toward a common relative level.

    Finds the largest level ``r`` such that shrinking every job to
    ``min(alloc, mn + r (mx - mn))`` frees at least ``need`` nodes, then
    returns excess (integer-rounding) capacity back to the jobs shrunk the
    deepest, so exactly ``min(need, freeable)`` is freed.
    """
    alloc = xp.asarray(alloc)
    freeable = xp.sum(xp.maximum(alloc - mn, 0))
    need_eff = xp.minimum(xp.asarray(need, dtype=freeable.dtype), freeable)

    lo = xp.zeros(()); hi = xp.ones(())
    for _ in range(_BISECT_ITERS):
        mid = 0.5 * (lo + hi)
        t = xp.minimum(alloc, _level_targets_xp(mid, mn, mx, xp))
        freed = xp.sum(alloc - t)
        ok = freed >= need_eff           # level low enough to free need
        lo = xp.where(ok, mid, lo)
        hi = xp.where(ok, hi, mid)
    t = xp.minimum(alloc, _level_targets_xp(lo, mn, mx, xp))
    freed = xp.sum(alloc - t)
    # Return integer-rounding excess to the most-shrunk jobs (largest delta).
    excess = freed - need_eff
    delta = alloc - t
    giveback = greedy_expand(t, alloc, -delta, excess, xp=xp)
    return giveback


def balanced_expand(alloc, mn, mx, idle, xp=np):
    """AVG expand: raise all jobs toward a common relative level."""
    alloc = xp.asarray(alloc)
    room = xp.sum(xp.maximum(mx - alloc, 0))
    idle_eff = xp.minimum(xp.asarray(idle, dtype=room.dtype), room)

    lo = xp.zeros(()); hi = xp.ones(())
    for _ in range(_BISECT_ITERS):
        mid = 0.5 * (lo + hi)
        t = xp.maximum(alloc, xp.minimum(_level_targets_xp(mid, mn, mx, xp), mx))
        used = xp.sum(t - alloc)
        ok = used <= idle_eff
        lo = xp.where(ok, mid, lo)
        hi = xp.where(ok, hi, mid)
    t = xp.maximum(alloc, xp.minimum(_level_targets_xp(lo, mn, mx, xp), mx))
    used = xp.sum(t - alloc)
    # Hand out the remaining few nodes to the least-utilized jobs first.
    leftover = idle_eff - used
    span = xp.maximum(mx - mn, 1)
    balance = (t - mn) / span
    return greedy_expand(t, mx, balance, leftover, xp=xp)


# ======================================================================
# 2. Exact sequential EASY backfill (Step 1, consumed by the numpy DES)
# ======================================================================
def fcfs_prefix_exact(want, floor, free: int):
    """Start the FCFS queue prefix; each job takes ``min(want, free)``.

    Stops at the first job whose ``floor`` does not fit.  Returns the
    per-position allocations of started jobs and the remaining free nodes.
    """
    allocs = []
    for w_, f_ in zip(want, floor):
        if int(f_) > free:
            break
        a = int(min(int(w_), free))
        allocs.append(a)
        free -= a
    return allocs, free


def easy_reservation_exact(ests, release, free: int, head_floor: int
                           ) -> Tuple[float, int]:
    """EASY head reservation: ``(shadow, extra)`` from exact end estimates.

    ``shadow`` is the earliest time the blocked head's ``head_floor`` nodes
    accumulate (walltime-padded estimates, ascending-finish order);
    ``extra`` is how many nodes beyond the head's need are free at that
    moment — the pool backfill jobs running past ``shadow`` may draw from.
    """
    srt = np.argsort(ests, kind="stable")
    cumfree = free + np.cumsum(np.asarray(release)[srt])
    k = int(np.searchsorted(cumfree, head_floor))
    k = min(k, len(ests) - 1)
    return float(np.asarray(ests)[srt][k]), int(cumfree[k]) - int(head_floor)


def easy_backfill_scan_exact(want, floor, wall_work, pfrac, t: float,
                             shadow: float, extra: int, free: int,
                             eps: float = 1e-9):
    """EASY backfill scan over queued candidates (head excluded), in order.

    A candidate is started at ``a = min(want, free)`` (falling back to
    ``floor``) when it either finishes before ``shadow`` at that allocation
    or fits inside the ``extra`` spare-node pool — the head's reservation
    is never delayed.  Returns ``(starts, free, extra)`` where ``starts``
    is a list of ``(candidate_index, alloc)``.
    """
    starts = []
    for i in range(len(want)):
        if free == 0:
            break
        floor_i = int(floor[i])
        if floor_i > free:
            continue
        want_i = int(want[i])
        for a_try in dict.fromkeys([min(want_i, free), floor_i]):
            est = wall_work[i] / amdahl_speedup(float(a_try), pfrac[i])
            if t + est <= shadow + eps:
                pass  # finishes before the reservation
            elif a_try <= extra:
                extra -= a_try  # runs past shadow inside spare nodes
            else:
                continue
            starts.append((i, a_try))
            free -= a_try
            break
    return starts, free, extra


# ======================================================================
# 3. Masked fixed-shape vectorized passes (sim_jax + sweep/batch)
# ======================================================================
class PassParams(NamedTuple):
    """Per-slot job/policy data for :func:`schedule_tick`.

    All arrays are ``(..., W)`` with slots in FCFS (submit-rank) order;
    leading axes are lanes (``()`` for a single simulation, ``(B,)`` for a
    batched sweep).  ``wall_work`` is ``walltime * S(nodes_req)`` so the
    walltime-padded remaining-duration estimate at allocation ``a`` is
    ``remaining * wall_work / S(a)`` (the DES's ``_est_duration``).
    ``on_demand`` marks queue-priority jobs (Fan & Lan hybrid workloads):
    any queued on-demand job outranks every non-on-demand queued job,
    regardless of submit order; it is only consulted when
    :func:`schedule_tick` runs with ``with_classes=True``.  ``pref_nodes``
    (the preferred allocation) is only consulted by the ``pooled``
    structure, and ``sort_key`` (the queue-order key: submit rank under
    FCFS, walltime estimate under SJF) only under ``with_sjf=True``.
    """

    malleable: object   # bool — resizable under the lane's strategy
    min_nodes: object   # i32
    max_nodes: object   # i32
    want: object        # i32 Step-1 target allocation
    floor: object       # i32 smallest start allocation
    shrink_floor: object  # i32 smallest Step-2 allocation
    prio_ref: object    # i32 greedy priority = alloc - prio_ref (Eqs. 1-2)
    pfrac: object       # f32 Amdahl parallel fraction
    wall_work: object   # f32 walltime * S(nodes_req)
    on_demand: object = None   # bool — queue-priority class (optional)
    pref_nodes: object = None  # i32 preferred allocation ([pooled] only)
    sort_key: object = None    # f32 queue-order key ([with_sjf] only)


def _speedup_f32(n, p):
    jnp = _jnp()
    n = jnp.maximum(n.astype(jnp.float32), 1.0)
    return 1.0 / ((1.0 - p) + p / n)


def first_true(mask):
    """Mask of the first True slot per lane (all-False lanes stay empty)."""
    jnp = _jnp()
    head = jnp.argmax(mask, axis=-1)
    return mask & (jnp.arange(mask.shape[-1]) == head[..., None])


def priority_head(queued, on_demand):
    """Mask of the queue head under class priority.

    The head is the first queued on-demand slot when any exists, else the
    first queued slot — i.e. ``first_true`` over the (class, submit-rank)
    queue order without materializing a sort.
    """
    jnp = _jnp()
    q_od = queued & on_demand
    return jnp.where(jnp.any(q_od, axis=-1)[..., None],
                     first_true(q_od), first_true(queued & ~on_demand))


def queue_ranks(queued, on_demand=None):
    """1-based per-slot queue position (head == 1) in queue order.

    Without classes the queue order is slot (FCFS) order; with classes
    every queued on-demand slot ranks ahead of every non-on-demand one.
    Non-queued slots get arbitrary ranks — callers mask with ``queued``.
    """
    jnp = _jnp()
    if on_demand is None:
        return jnp.cumsum(queued, axis=-1)
    q_od = queued & on_demand
    n_od = jnp.sum(q_od, axis=-1)
    return jnp.where(on_demand, jnp.cumsum(q_od, axis=-1),
                     n_od[..., None] + jnp.cumsum(queued & ~on_demand,
                                                  axis=-1))


def queue_cumsum(amount, mask, on_demand=None):
    """Cumulative ``amount`` over ``mask`` slots in *queue order*.

    Without classes the queue order is slot (FCFS/permuted-SJF) order;
    with classes every on-demand slot accumulates before any normal one,
    so cumulative-fit admission follows the same (class, queue-rank)
    order the DES scans (prefix semantics within that order).
    """
    jnp = _jnp()
    if on_demand is None:
        return jnp.cumsum(jnp.where(mask, amount, 0), axis=-1)
    a_od = jnp.where(mask & on_demand, amount, 0)
    a_n = jnp.where(mask & ~on_demand, amount, 0)
    return jnp.where(
        on_demand, jnp.cumsum(a_od, axis=-1),
        jnp.sum(a_od, axis=-1, keepdims=True) + jnp.cumsum(a_n, axis=-1))


def take_desc_prefix(prio, amount, need, lo0: int, hi0: int):
    """Per-slot take with sum == min(need, sum(amount)), highest-prio first.

    ``lo0``/``hi0`` are static priority bounds: every slot with
    ``amount > 0`` must satisfy ``lo0 < prio <= hi0``.  Equivalent to
    :func:`greedy_shrink`'s take with ties broken in slot (FCFS) order,
    with the threshold found by integer bisection instead of a sort.
    """
    jnp = _jnp()
    lanes = prio.shape[:-1]
    lo = jnp.full(lanes, lo0, jnp.int32)    # invariant: S(lo) > need or lo0
    hi = jnp.full(lanes, hi0, jnp.int32)    # invariant: S(hi) <= need
    s_hi = jnp.zeros_like(need)
    for _ in range(int(math.ceil(math.log2(max(hi0 - lo0, 1)))) + 1):
        mid = (lo + hi) // 2
        s = jnp.sum(jnp.where(prio > mid[..., None], amount, 0), axis=-1)
        ok = s <= need
        hi = jnp.where(ok, mid, hi)
        s_hi = jnp.where(ok, s, s_hi)
        lo = jnp.where(ok, lo, mid)
    theta = hi  # smallest threshold whose above-take fits within need
    rem = need - s_hi
    tie = prio == theta[..., None]
    before = jnp.cumsum(jnp.where(tie, amount, 0), axis=-1)
    tie_take = jnp.clip(rem[..., None] - (before - amount), 0, amount)
    return jnp.where(prio > theta[..., None], amount,
                     jnp.where(tie, tie_take, 0))


def give_asc_prefix(prio, room, idle, lo0: int, hi0: int):
    """Per-slot give with sum == min(idle, sum(room)), lowest-prio first."""
    return take_desc_prefix(-prio, room, idle, -hi0 - 1, -lo0 + 1)


def level_targets(level, mn, mx):
    """Integer allocation at relative level ``level`` in [0, 1] (jnp)."""
    return _level_targets_xp(level, mn, mx, _jnp())


def shadow_reservation(est, release, free, head_floor,
                       iters: int = SHADOW_ITERS):
    """Sort-free EASY head reservation: ``(shadow, extra)`` per lane.

    ``est`` holds the running slots' walltime-padded end estimates
    (``+inf`` on non-running slots), ``release`` their allocations.
    ``shadow`` is the smallest estimate value at which
    ``free + released-by-then >= head_floor`` — found by bisecting time and
    snapping the upper bound onto actual estimate values, so no sort enters
    the hot loop.  Callers must guarantee ``free < head_floor`` (a blocked
    head) and at least one running slot per lane; lanes violating that are
    expected to mask the result away.
    """
    jnp = _jnp()
    NEG = jnp.float32(-jnp.inf)
    finite = jnp.isfinite(est)
    rel = jnp.where(finite, release, 0)
    need = head_floor - free

    def released(tau):
        return jnp.sum(jnp.where(finite & (est <= tau[..., None]), rel, 0),
                       axis=-1)

    hi = jnp.max(jnp.where(finite, est, NEG), axis=-1)  # all released: >= need
    lo = jnp.zeros_like(hi)
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        ok = released(mid) >= need
        snap = jnp.max(jnp.where(finite & (est <= mid[..., None]), est, NEG),
                       axis=-1)
        hi = jnp.where(ok, snap, hi)
        lo = jnp.where(ok, lo, mid)
    extra = free + released(hi) - head_floor
    return hi, extra


def schedule_tick(p: PassParams, state, alloc, remaining, start_t, act,
                  capacity, t_now, *, structure: str = "greedy",
                  fill_rounds: int,
                  prio_lo: int, prio_hi: int, span_max: int,
                  shadow_iters: int = SHADOW_ITERS,
                  expand_backend: str = "bisect",
                  backfill_depth=None, with_classes: bool = False,
                  with_sjf: bool = False, pool_share=None,
                  steal_margin=None):
    """One Steps-1..3 scheduling pass on queue-ordered slot arrays.

    Pure and fixed-shape: works under jit/vmap/scan for lane shapes ``()``
    (sim_jax) and ``(B,)`` (the batched sweep engine).  ``act`` masks slots
    eligible for state changes this tick (frozen lanes / padding); running
    slots are always live.  ``capacity`` and ``t_now`` are per-lane data so
    lanes of *different clusters* share one compilation.

    Steps (paper §2.1):
      1. FCFS-prefix start (head may fall back to ``floor``), then EASY
         backfill under a **shadow-time head reservation**
         (:func:`shadow_reservation`): a backfill candidate starts only if
         it finishes before the reservation or fits the spare-node pool —
         the blocked head is never delayed by backfill.  The scan only
         considers the first ``backfill_depth`` queued candidates behind
         the head (per-lane data, a masked rank cutoff over the queue
         snapshot at scan entry — the same bound the DES applies by
         slicing its queue); ``None`` leaves the scan unbounded.
      2. Shrink running malleable jobs (greedy highest-priority-first, or
         AVG-balanced when ``structure == 'balanced'``) to admit the head.
      2b. Structure-specific extra pass (``docs/strategies.md``):
         ``pooled`` starts queued malleable candidates from the shared
         surplus-above-preferred pool; ``stealing`` transfers nodes from
         over-average running jobs to under-average ones.
      3. Expand running malleable jobs into remaining idle nodes (greedy
         lowest-priority-first or balanced).  With
         ``expand_backend='pallas'`` (or ``'pallas-interpret'`` off-TPU)
         the greedy give runs through the Pallas prefix-waterfill kernel
         in sorted priority order instead of the threshold bisection.

    ``with_sjf`` (static) enables queue-order generality: slots are
    permuted by ``p.sort_key`` (stable argsort) before the pass and
    unpermuted after, so the FCFS-prefix/backfill/head machinery above
    runs over the *reordered* queue — SJF lanes key on walltime
    estimates, FCFS lanes on submit rank.  An FCFS lane's key is
    monotone over its slots, so its permutation is the identity and an
    FCFS lane inside a ``with_sjf`` compilation is bit-identical to the
    ``with_sjf=False`` pass (mixed batches share one compilation; an
    all-FCFS batch compiles the flag away entirely).

    ``with_classes`` (static) enables workload-class queue priority:
    ``p.on_demand`` slots outrank every non-on-demand queued slot, so the
    Step-1 prefix starts all queued on-demand jobs first, the head (the
    reservation owner Steps 2's shrink admits) is the first *on-demand*
    queued job when one exists, and backfill ranks follow the same
    (class, submit-rank) order.  The flag is static so class-free lanes
    compile to exactly the class-free pass (zero overhead when off).

    Static ints ``prio_lo``/``prio_hi`` must bound ``alloc - prio_ref`` on
    every slot with shrink surplus / expand room (values outside are
    clipped), and ``span_max`` must bound ``max_nodes - min_nodes``.
    Head bookkeeping uses first-true masks and masked sums instead of
    per-lane gathers/scatters, and the backfill / shrink / expand passes
    are skipped via ``lax.cond`` on whole-batch predicates — both matter:
    XLA:CPU pays far more for gather/scatter/cumsum kernels than for fused
    elementwise work.

    Returns ``(state, alloc, start_t)``.
    """
    import jax
    jnp = _jnp()
    if structure not in ("greedy", "balanced", "pooled", "stealing"):
        raise ValueError(f"unknown pass structure {structure!r}")
    balanced = structure == "balanced"
    if with_sjf:
        # Queue-order permutation wrapper: run the pass over slots sorted
        # by the per-slot queue key, then restore slot order.  The stable
        # argsort keeps ties in slot (submit) order, matching the DES's
        # stable insertion.
        perm = jnp.argsort(p.sort_key, axis=-1)
        inv = jnp.argsort(perm, axis=-1)

        def fwd(a):
            return jnp.take_along_axis(a, perm, axis=-1)

        p_q = PassParams(*(fwd(f) if f is not None else None for f in p))
        st_q, al_q, s0_q = schedule_tick(
            p_q, fwd(state), fwd(alloc), fwd(remaining), fwd(start_t),
            fwd(jnp.broadcast_to(act, state.shape)), capacity, t_now,
            structure=structure, fill_rounds=fill_rounds,
            prio_lo=prio_lo, prio_hi=prio_hi, span_max=span_max,
            shadow_iters=shadow_iters, expand_backend=expand_backend,
            backfill_depth=backfill_depth, with_classes=with_classes,
            with_sjf=False, pool_share=pool_share,
            steal_margin=steal_margin)

        def rev(a):
            return jnp.take_along_axis(a, inv, axis=-1)

        return rev(st_q), rev(al_q), rev(s0_q)
    if (expand_backend in ("fused", "fused-interpret")
            and structure == "greedy" and not with_classes):
        # the whole greedy/class-free pass as one VMEM-resident Pallas
        # kernel (repro.kernels.schedule_tick); balanced / class lanes
        # keep the reference pass below
        from repro.kernels.schedule_tick import fused_schedule_tick
        return fused_schedule_tick(
            p, state, alloc, remaining, start_t,
            jnp.broadcast_to(act, state.shape), capacity, t_now,
            fill_rounds=fill_rounds, prio_lo=prio_lo, prio_hi=prio_hi,
            shadow_iters=shadow_iters, backfill_depth=backfill_depth,
            interpret=expand_backend == "fused-interpret")
    INF = jnp.float32(jnp.inf)
    level_iters = int(math.ceil(math.log2(span_max + 2))) + 1
    od = p.on_demand if with_classes else None

    with jax.named_scope("sched.fcfs"):
        running = state == RUNNING
        free = capacity - jnp.sum(jnp.where(running, alloc, 0), axis=-1)

        # -- Step 1: FCFS prefix (slots are in FCFS order) --------------------
        queued = (state == QUEUED) & act
        if with_classes:
            # class-priority prefix: queued on-demand slots start first (in
            # submit order); non-on-demand slots may only join the prefix when
            # every queued on-demand job started.
            q_od = queued & od
            cumw_od = jnp.cumsum(jnp.where(q_od, p.want, 0), axis=-1)
            s1o = q_od & (cumw_od <= free[..., None])
            used_od = jnp.max(jnp.where(s1o, cumw_od, 0), axis=-1)
            all_od = ~jnp.any(q_od & ~s1o, axis=-1)
            rem = free - used_od
            q_n = queued & ~od
            cumw_n = jnp.cumsum(jnp.where(q_n, p.want, 0), axis=-1)
            s1 = s1o | (q_n & (cumw_n <= rem[..., None]) & all_od[..., None])
            leftover = rem - jnp.max(
                jnp.where(s1 & ~od, cumw_n, 0), axis=-1)
            h_mask = priority_head(queued & ~s1, od)
        else:
            cumw = jnp.cumsum(jnp.where(queued, p.want, 0), axis=-1)
            s1 = queued & (cumw <= free[..., None])
            used = jnp.max(jnp.where(s1, cumw, 0), axis=-1)
            leftover = free - used
            # head fallback: first queued job not started, floor fits leftover
            h_mask = first_true(queued & ~s1)
        hfloor = jnp.sum(jnp.where(h_mask, p.floor, 0), axis=-1)
        hwant = jnp.sum(jnp.where(h_mask, p.want, 0), axis=-1)
        h_ok = (hfloor > 0) & (hfloor <= leftover)  # floor >= 1 on real jobs
        h_alloc = jnp.clip(leftover, hfloor, hwant)

        h_upd = h_mask & h_ok[..., None]
        started = s1 | h_upd
        alloc = jnp.where(s1, p.want, alloc)
        alloc = jnp.where(h_upd, h_alloc[..., None], alloc)
        state = jnp.where(started, RUNNING, state)
        start_t = jnp.where(started, t_now[..., None], start_t)
        free = leftover - jnp.where(h_ok, h_alloc, 0)

    with jax.named_scope("sched.backfill"):
        # -- EASY backfill under the head's shadow-time reservation -----------
        queued = (state == QUEUED) & act
        h_mask = priority_head(queued, od) if with_classes else \
            first_true(queued)
        hfloor = jnp.sum(jnp.where(h_mask, p.floor, 0), axis=-1)
        hwant = jnp.sum(jnp.where(h_mask, p.want, 0), axis=-1)
        has_head = hfloor > 0

        def backfill(args):
            state, alloc, start_t, free = args
            if backfill_depth is None:
                depth_ok = True
            else:
                # rank cutoff over the queue snapshot at scan entry: the head
                # holds rank 1, so candidates 1..depth behind it are ranks
                # 2..depth+1 (the DES's ``queue[1 : 1 + depth]`` slice)
                ranks = queue_ranks((state == QUEUED) & act, od)
                depth_ok = ranks <= backfill_depth[..., None] + 1
            run = state == RUNNING
            est = jnp.where(
                run,
                t_now[..., None] + remaining * p.wall_work
                / _speedup_f32(alloc, p.pfrac),
                INF)
            sh_b, ex_b = shadow_reservation(est, alloc, free, hfloor,
                                            iters=shadow_iters)
            blocked = has_head & (hfloor > free)
            # head fits free: reservation starts now; no head: unconstrained
            shadow = jnp.where(blocked, sh_b, jnp.where(has_head, t_now, INF))
            extra = jnp.where(blocked, ex_b,
                              jnp.where(has_head, free - hfloor, free))

            def qcumsum(amount, mask):
                return queue_cumsum(amount, mask, od)

            tfit = t_now[..., None] + p.wall_work / _speedup_f32(
                p.want, p.pfrac) <= shadow[..., None] + _SHADOW_EPS
            for _ in range(fill_rounds):
                cand = (state == QUEUED) & act & ~h_mask & depth_ok
                # (a) finishes before the reservation: free nodes only
                c = cand & tfit & (p.want <= free[..., None])
                cum = qcumsum(p.want, c)
                s = c & (cum <= free[..., None])
                free = free - jnp.max(jnp.where(s, cum, 0), axis=-1)
                # (b) runs past the reservation: spare-node pool, at want
                lim = jnp.minimum(free, extra)
                c2 = cand & ~s & ~tfit & (p.want <= lim[..., None])
                cum2 = qcumsum(p.want, c2)
                s2 = c2 & (cum2 <= lim[..., None])
                take2 = jnp.max(jnp.where(s2, cum2, 0), axis=-1)
                # (c) spare-node pool at floor (want did not fit)
                lim3 = jnp.minimum(free - take2, extra - take2)
                c3 = cand & ~s & ~s2 & ~tfit & (p.floor <= lim3[..., None])
                cum3 = qcumsum(p.floor, c3)
                s3 = c3 & (cum3 <= lim3[..., None])
                take3 = jnp.max(jnp.where(s3, cum3, 0), axis=-1)

                free = free - take2 - take3
                extra = extra - take2 - take3
                new = s | s2 | s3
                alloc = jnp.where(s | s2, p.want,
                                  jnp.where(s3, p.floor, alloc))
                state = jnp.where(new, RUNNING, state)
                start_t = jnp.where(new, t_now[..., None], start_t)
            return state, alloc, start_t, free

        state, alloc, start_t, free = jax.lax.cond(
            jnp.any(has_head), backfill, lambda a: a,
            (state, alloc, start_t, free))

    with jax.named_scope("sched.shrink"):
        # -- Step 2: shrink running malleable jobs to admit the head ----------
        deficit = jnp.where(has_head, hfloor - free, 0)
        shrinkable = (state == RUNNING) & p.malleable
        fl = jnp.where(shrinkable, jnp.minimum(p.shrink_floor, alloc), alloc)
        surplus = jnp.maximum(alloc - fl, 0)
        tot_surplus = jnp.sum(surplus, axis=-1)
        need = jnp.where((deficit > 0) & (tot_surplus >= deficit), deficit, 0)

        prio = jnp.clip(alloc - p.prio_ref, prio_lo, prio_hi)

        if balanced:
            def shrink(alloc):
                mn_eff = jnp.where(shrinkable, fl, alloc)
                mx_eff = jnp.where(shrinkable, p.max_nodes, alloc)
                lanes = need.shape
                lo = jnp.zeros(lanes, jnp.float32)
                hi = jnp.ones(lanes, jnp.float32)
                freed_lo = tot_surplus
                for _ in range(level_iters):
                    mid = 0.5 * (lo + hi)
                    tgt = jnp.minimum(
                        alloc, level_targets(mid[..., None], mn_eff, mx_eff))
                    freed = jnp.sum(alloc - tgt, axis=-1)
                    ok = freed >= need
                    lo = jnp.where(ok, mid, lo)
                    hi = jnp.where(ok, hi, mid)
                    freed_lo = jnp.where(ok, freed, freed_lo)
                tgt = jnp.minimum(
                    alloc, level_targets(lo[..., None], mn_eff, mx_eff))
                # return integer-rounding excess to the most-shrunk jobs
                delta = alloc - tgt
                give = give_asc_prefix(-delta, delta, freed_lo - need,
                                       -span_max - 1, 0)
                return alloc - (delta - give)
        else:
            def shrink(alloc):
                return alloc - take_desc_prefix(prio, surplus, need,
                                                prio_lo - 1, prio_hi)

        alloc = jax.lax.cond(jnp.any(need > 0), shrink, lambda a: a, alloc)
        free = free + need  # the take sums to exactly `need` by construction

        h_ok = has_head & (hfloor <= free)
        h_alloc = jnp.clip(free, hfloor, hwant)
        h_upd = h_mask & h_ok[..., None]
        alloc = jnp.where(h_upd, h_alloc[..., None], alloc)
        state = jnp.where(h_upd, RUNNING, state)
        start_t = jnp.where(h_upd, t_now[..., None], start_t)
        free = free - jnp.where(h_ok, h_alloc, 0)

    with jax.named_scope("sched.extra"):
        # -- Step 2b: structure-specific extra pass ---------------------------
        if structure == "pooled":
            # Common-pool start pass (docs/strategies.md § pref_common_pool):
            # running malleable jobs' surplus above their preferred
            # allocation forms a shared pool; queued malleable candidates
            # behind the head draw their floor from it in queue order
            # (prefix semantics: the first non-fitting malleable candidate
            # blocks the rest, like the DES scan).  The pool never touches
            # free nodes, so the head's shadow reservation is unaffected,
            # and every pool start is paid for by shrinking donors back
            # toward preferred — busy is conserved by construction.
            run_m = (state == RUNNING) & p.malleable
            over_pref = jnp.where(run_m,
                                  jnp.maximum(alloc - p.pref_nodes, 0), 0)
            pool_amt = jnp.sum(over_pref, axis=-1)
            share = pool_share if pool_share is not None else 1.0
            budget = jnp.minimum((share * pool_amt).astype(pool_amt.dtype),
                                 pool_amt)
            q_pool = (state == QUEUED) & act
            h_pool = priority_head(q_pool, od) if with_classes else \
                first_true(q_pool)
            cand = q_pool & p.malleable & ~h_pool
            cumf = queue_cumsum(p.floor, cand, od)
            sp = cand & (cumf <= budget[..., None])
            taken = jnp.max(jnp.where(sp, cumf, 0), axis=-1)

            def pool_start(args):
                state, alloc, start_t = args
                pr = jnp.clip(alloc - p.prio_ref, prio_lo, prio_hi)
                take = take_desc_prefix(pr, over_pref, taken,
                                        prio_lo - 1, prio_hi)
                alloc = alloc - take
                alloc = jnp.where(sp, p.floor, alloc)
                state = jnp.where(sp, RUNNING, state)
                start_t = jnp.where(sp, t_now[..., None], start_t)
                return state, alloc, start_t

            state, alloc, start_t = jax.lax.cond(
                jnp.any(taken > 0), pool_start, lambda a: a,
                (state, alloc, start_t))

        if structure == "stealing":
            # Steal-agreement pass (docs/strategies.md § steal_agreement):
            # running malleable jobs above the average running allocation
            # (plus the per-lane steal margin) donate their surplus above
            # max(average, shrink floor); starved under-average jobs steal
            # up to min(average, max_nodes).  The transfer is min(donatable,
            # stealable), taken highest-priority-first and given
            # lowest-priority-first — busy is conserved, and repeated
            # application converges (donors land on the average).
            run_m = (state == RUNNING) & p.malleable
            n_run = jnp.sum(run_m, axis=-1)
            avg = (jnp.sum(jnp.where(run_m, alloc, 0), axis=-1)
                   // jnp.maximum(n_run, 1))
            margin = steal_margin if steal_margin is not None else 0
            sfl = jnp.where(run_m, jnp.minimum(p.shrink_floor, alloc), alloc)
            donor = run_m & (alloc > (avg + margin)[..., None])
            donor_amt = jnp.where(
                donor,
                jnp.maximum(alloc - jnp.maximum(avg[..., None], sfl), 0), 0)
            taker_room = jnp.where(
                run_m,
                jnp.maximum(jnp.minimum(avg[..., None], p.max_nodes) - alloc,
                            0), 0)
            transfer = jnp.minimum(jnp.sum(donor_amt, axis=-1),
                                   jnp.sum(taker_room, axis=-1))

            def steal(alloc):
                pr = jnp.clip(alloc - p.prio_ref, prio_lo, prio_hi)
                take = take_desc_prefix(pr, donor_amt, transfer,
                                        prio_lo - 1, prio_hi)
                give = give_asc_prefix(pr, taker_room, transfer,
                                       prio_lo - 1, prio_hi)
                return alloc - take + give

            alloc = jax.lax.cond(jnp.any(transfer > 0), steal, lambda a: a,
                                 alloc)

    with jax.named_scope("sched.expand"):
        # -- Step 3: expand into remaining idle nodes -------------------------
        expandable = (state == RUNNING) & p.malleable
        idle = jnp.maximum(
            jnp.where(jnp.any(expandable, axis=-1), free, 0), 0)
        if balanced:
            def expand(alloc):
                mn_eff = jnp.where(expandable, p.min_nodes, alloc)
                cap_eff = jnp.where(expandable, p.max_nodes, alloc)
                room_tot = jnp.sum(jnp.maximum(cap_eff - alloc, 0), axis=-1)
                idle_eff = jnp.minimum(idle, room_tot)
                lanes = idle.shape
                lo = jnp.zeros(lanes, jnp.float32)
                hi = jnp.ones(lanes, jnp.float32)
                used_lo = jnp.zeros_like(idle_eff)
                for _ in range(level_iters):
                    mid = 0.5 * (lo + hi)
                    tgt = jnp.maximum(alloc, jnp.minimum(
                        level_targets(mid[..., None], mn_eff, cap_eff),
                        cap_eff))
                    spent = jnp.sum(tgt - alloc, axis=-1)
                    ok = spent <= idle_eff
                    lo = jnp.where(ok, mid, lo)
                    hi = jnp.where(ok, hi, mid)
                    used_lo = jnp.where(ok, spent, used_lo)
                tgt = jnp.maximum(alloc, jnp.minimum(
                    level_targets(lo[..., None], mn_eff, cap_eff), cap_eff))
                # hand the leftover to the least-utilized jobs (2^-16 levels)
                span = jnp.maximum(cap_eff - mn_eff, 1)
                balance_q = ((tgt - mn_eff) * 65536) // span
                room = jnp.maximum(cap_eff - tgt, 0)
                give = give_asc_prefix(balance_q, room, idle_eff - used_lo,
                                       -1, 65537)
                return tgt + give
        else:
            def expand(alloc):
                room = jnp.where(expandable,
                                 jnp.maximum(p.max_nodes - alloc, 0), 0)
                pr = jnp.clip(alloc - p.prio_ref, prio_lo, prio_hi)
                if expand_backend in ("pallas", "pallas-interpret"):
                    give = _pallas_give(pr, room, idle,
                                        interpret=expand_backend
                                        == "pallas-interpret")
                else:  # bisect, and the fused backends' unfused statics
                    give = give_asc_prefix(pr, room, idle, prio_lo - 1,
                                           prio_hi)
                return alloc + give

        alloc = jax.lax.cond(jnp.any(idle > 0), expand, lambda a: a, alloc)
    return state, alloc, start_t


def _pallas_give(prio, room, idle, *, interpret: bool):
    """Greedy ascending-priority give via the Pallas prefix-waterfill kernel.

    Sorts slots by ``(prio, slot)`` — same tie-break as the bisection path —
    and waterfills the sorted room.  TPU-targeted; ``interpret=True`` runs
    the kernel in interpreter mode elsewhere (parity tests, CPU smoke).
    """
    import jax
    jnp = _jnp()
    from repro.kernels.waterfill import waterfill

    def one(prio1, room1, idle1):
        order = jnp.argsort(prio1)  # stable: FCFS tie-break preserved
        give_sorted = waterfill(room1[order], idle1, interpret=interpret)
        return jnp.zeros_like(room1).at[order].set(give_sorted)

    if prio.ndim == 1:
        return one(prio, room, idle)
    flat = prio.reshape(-1, prio.shape[-1])
    give = jax.vmap(one)(flat, room.reshape(flat.shape),
                         idle.reshape(-1).astype(jnp.int32))
    return give.reshape(prio.shape)
