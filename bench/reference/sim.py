"""Event-driven reference scheduler (paper section 2.1, ElastiSim semantics).

Jobs complete at exact times; the scheduler runs on the first tick after
each submission or completion, and within one invocation repeats its
passes until nothing changes:

1. EASY backfill start pass: the queue's FCFS prefix starts while it fits;
   for a blocked head, one reservation is made from the running jobs'
   walltime-padded end estimates, and later jobs (up to the backfill
   depth) start only if they end before it or fit in the nodes it leaves
   spare, so the head is never delayed.
2. Malleable strategies shrink running malleable jobs to admit a blocked
   head, greedily by priority (MIN, PREF, KEEPPREF) or towards a common
   relative level (AVG).
3. Idle nodes go to running malleable jobs, lowest priority first (or
   levelled, for AVG).

A job's work is 1; at ``a`` nodes it runs at ``S(a) / (S(req) * runtime)``
with Amdahl's ``S(n) = 1 / ((1 - p) + p / n)``.

``backfill="reservationless"`` breaks the EASY guarantee on purpose, as
a scheduler that forgets the head's reservation would: behind a blocked
head, every later job that fits the free nodes starts, whether or not it
delays the head.  It is the control that the comparison has to reject.
"""
from __future__ import annotations

import numpy as np

EPS = 1e-9
LEVEL_ITERS = 24

# start want / start floor / shrink floor / priority / balanced, per
# strategy (paper Eqs. 1-3 and the KEEPPREF rule)
STRATEGIES = {
    "easy": None,
    "min": ("mn", "mn", "mn", "min", False),
    "pref": ("pref", "mn", "mn", "pref", False),
    "avg": ("mn", "mn", "mn", "avg", True),
    "keeppref": ("pref", "pref", "pref", "pref", False),
}
BACKFILL = ("easy", "reservationless")


def speedup(n, p):
    return 1.0 / ((1.0 - p) + p / np.maximum(np.asarray(n, np.float64), 1.0))


def _take_in_order(amount, order, total):
    """Take ``min(total, sum(amount))`` from ``amount`` in ``order``."""
    a = amount[order]
    cum = np.cumsum(a)
    target = min(total, cum[-1]) if len(cum) else 0
    out = np.empty_like(a)
    out[order] = np.clip(target - (cum - a), 0, a)
    return out


def greedy_shrink(alloc, floor, prio, need):
    """Free ``need`` nodes from the highest-priority jobs first."""
    surplus = np.maximum(alloc - floor, 0)
    return alloc - _take_in_order(surplus, np.argsort(-prio, kind="stable"),
                                  need)


def greedy_expand(alloc, cap, prio, idle):
    """Give ``idle`` nodes to the lowest-priority jobs first."""
    room = np.maximum(cap - alloc, 0)
    return alloc + _take_in_order(room, np.argsort(prio, kind="stable"),
                                  idle)


def _level(level, mn, mx):
    return mn + np.floor(level * ((mx - mn) * 1.0) + 1e-9).astype(mn.dtype)


def level_shrink(alloc, mn, mx, need):
    """AVG: lower every job towards one relative level in [mn, mx] until
    ``need`` nodes are free; rounding surplus goes back to the jobs that
    lost most."""
    need = min(need, int(np.sum(np.maximum(alloc - mn, 0))))
    lo, hi = 0.0, 1.0
    for _ in range(LEVEL_ITERS):
        mid = 0.5 * (lo + hi)
        if np.sum(alloc - np.minimum(alloc, _level(mid, mn, mx))) >= need:
            lo = mid
        else:
            hi = mid
    t = np.minimum(alloc, _level(lo, mn, mx))
    excess = int(np.sum(alloc - t)) - need
    return greedy_expand(t, alloc, -(alloc - t), excess)


def level_expand(alloc, mn, mx, idle):
    """AVG: raise every job towards one relative level with ``idle``
    nodes; the last few go to the least utilized jobs."""
    idle = min(idle, int(np.sum(np.maximum(mx - alloc, 0))))
    lo, hi = 0.0, 1.0
    for _ in range(LEVEL_ITERS):
        mid = 0.5 * (lo + hi)
        t = np.maximum(alloc, np.minimum(_level(mid, mn, mx), mx))
        if np.sum(t - alloc) <= idle:
            lo = mid
        else:
            hi = mid
    t = np.maximum(alloc, np.minimum(_level(lo, mn, mx), mx))
    left = idle - int(np.sum(t - alloc))
    return greedy_expand(t, mx, (t - mn) / np.maximum(mx - mn, 1), left)


def simulate(jobs: dict, cfg: dict, strategy: str,
             backfill: str = "easy") -> dict:
    """Per-job ``start, end, expand_ops, shrink_ops`` and the busy-node
    timeline ``(util_t, util_nodes, t_end)`` of one cell."""
    if backfill not in BACKFILL:
        raise ValueError(f"backfill must be one of {BACKFILL}")
    pol = STRATEGIES[strategy]
    nodes, tick = int(cfg["nodes"]), float(cfg["tick_s"])
    depth = int(cfg["policy"]["backfill_depth"])
    submit, runtime = jobs["submit"], jobs["runtime"]
    req = jobs["req"]
    n = len(submit)
    if pol is None:
        mall = np.zeros(n, dtype=bool)
        pfrac = np.full(n, 0.9) if "pfrac" not in jobs else jobs["pfrac"]
        mn = mx = pref = req
        want = floor = sfloor = req
    else:
        mall, pfrac = jobs["malleable"], jobs["pfrac"]
        mn, mx, pref = jobs["mn"], jobs["mx"], jobs["pref"]
        pick = {"mn": mn, "pref": pref}
        want = np.where(mall, pick[pol[0]], req)
        floor = np.where(mall, pick[pol[1]], req)
        sfloor = np.where(mall, pick[pol[2]], req)
    prio_kind = None if pol is None else pol[3]
    balanced = pol is not None and pol[4]
    s_ref = speedup(req, pfrac)
    wall_work = jobs["walltime"] * s_ref
    denom = s_ref * runtime

    alloc = np.zeros(n, dtype=np.int64)
    rem = np.ones(n)
    start = np.full(n, np.nan)
    end = np.full(n, np.nan)
    eops = np.zeros(n, dtype=np.int64)
    sops = np.zeros(n, dtype=np.int64)
    order = np.argsort(submit, kind="stable")
    queue: list = []
    run = np.zeros(0, dtype=np.int64)  # running jobs, in start order
    st = {"t": 0.0, "busy": 0, "run": run, "changed": False}
    util_t, util_n = [0.0], [0]

    def record():
        util_t.append(st["t"])
        util_n.append(st["busy"])

    def rates(ids):
        return speedup(alloc[ids], pfrac[ids]) / denom[ids]

    def advance(t_to):
        while True:
            ids = st["run"]
            if len(ids) == 0:
                st["t"] = t_to
                return
            r = rates(ids)
            t = st["t"]
            t_fin = (t + rem[ids] / r).min()
            if t_fin > t_to + EPS:
                rem[ids] -= (t_to - t) * r
                st["t"] = t_to
                return
            rem[ids] -= max(t_fin - t, 0.0) * r
            st["t"] = t_fin
            done = rem[ids] <= EPS
            gone = ids[done]
            end[gone] = t_fin
            rem[gone] = 0.0
            st["busy"] -= int(alloc[gone].sum())
            st["run"] = ids[~done]
            record()

    def begin(j, a):
        alloc[j] = a
        start[j] = st["t"]
        st["run"] = np.append(st["run"], j)
        st["busy"] += a
        st["changed"] = True

    def resize(ids, new):
        delta = new - alloc[ids]
        if np.any(delta != 0):
            st["changed"] = True
        alloc[ids] = new
        st["busy"] += int(delta.sum())

    def priority(ids):
        a = alloc[ids]
        if prio_kind == "min":
            return a - mn[ids]
        return a - pref[ids]

    def start_pass():
        free = nodes - st["busy"]
        k = 0
        while k < len(queue) and floor[queue[k]] <= free:
            a = int(min(want[queue[k]], free))
            begin(queue[k], a)
            free -= a
            k += 1
        del queue[:k]
        ids = st["run"]
        if not queue or len(ids) == 0:
            return
        t = st["t"]
        head_floor = int(floor[queue[0]])
        ends = t + rem[ids] * wall_work[ids] / speedup(alloc[ids], pfrac[ids])
        by_end = np.argsort(ends, kind="stable")
        cum_free = free + np.cumsum(alloc[ids][by_end])
        i = min(int(np.searchsorted(cum_free, head_floor)), len(ids) - 1)
        shadow = float(ends[by_end][i])
        extra = int(cum_free[i]) - head_floor
        started = []
        for j in queue[1:1 + depth]:
            if free == 0:
                break
            f = int(floor[j])
            if f > free:
                continue
            first = int(min(want[j], free))
            for a in ((first,) if first == f else (first, f)):
                fin = t + wall_work[j] / (1.0 / ((1.0 - pfrac[j])
                                                 + pfrac[j] / float(a)))
                if fin > shadow + EPS and backfill == "easy":
                    if a > extra:
                        continue
                    extra -= a
                started.append((j, a))
                free -= a
                break
        if started:
            gone = set()
            for j, a in started:
                begin(j, a)
                gone.add(j)
            queue[:] = [j for j in queue if j not in gone]

    def schedule_once():
        start_pass()
        if pol is None:
            return
        while queue:
            deficit = int(floor[queue[0]]) - (nodes - st["busy"])
            ids = st["run"]
            m = ids[mall[ids]]
            if deficit <= 0 or len(m) == 0:
                break
            fl = np.minimum(sfloor[m], alloc[m])
            if int(np.sum(alloc[m] - fl)) < deficit:
                break
            if balanced:
                new = level_shrink(alloc[m], fl, mx[m], deficit)
            else:
                new = greedy_shrink(alloc[m], fl, priority(m), deficit)
            resize(m, new)
            start_pass()
        free = nodes - st["busy"]
        ids = st["run"]
        m = ids[mall[ids]]
        if free > 0 and len(m) and np.any(alloc[m] < mx[m]):
            if balanced:
                new = level_expand(alloc[m], mn[m], mx[m], free)
            else:
                new = greedy_expand(alloc[m], mx[m], priority(m), free)
            resize(m, new)

    def schedule():
        ids = st["run"]
        m0 = ids[mall[ids]]
        a0 = alloc[m0].copy()
        for _ in range(10_000):
            st["changed"] = False
            schedule_once()
            if not st["changed"]:
                break
        else:
            raise RuntimeError("scheduler found no fixpoint")
        d = alloc[m0] - a0
        eops[m0[d > 0]] += 1
        sops[m0[d < 0]] += 1
        record()

    nxt = 0
    sub_sorted = submit[order]
    while nxt < n or len(st["run"]):
        ids = st["run"]
        t_fin = (st["t"] + rem[ids] / rates(ids)).min() if len(ids) \
            else np.inf
        t_sub = sub_sorted[nxt] if nxt < n else np.inf
        t_ev = min(t_fin, t_sub)
        advance(max(float(np.ceil(t_ev / tick - EPS) * tick), 0.0))
        while nxt < n and sub_sorted[nxt] <= st["t"] + EPS:
            queue.append(int(order[nxt]))
            nxt += 1
        schedule()
    return {"start": start, "end": end, "expand_ops": eops,
            "shrink_ops": sops, "util_t": np.asarray(util_t),
            "util_nodes": np.asarray(util_n), "t_end": st["t"]}
