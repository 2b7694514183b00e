"""Fused Steps 1-3 scheduling pass as a single Pallas TPU kernel.

:func:`repro.core.passes.schedule_tick` is the per-event hot loop of the
batched sweep engine: FCFS-prefix start, EASY backfill under the head's
shadow-time reservation, greedy shrink, and waterfill expand.  As XLA ops
each phase round-trips the active window through HBM several times (the
shadow bisection alone is ~26 masked reductions).  But the window is small
by construction — the ladder buckets are 128..2048 slots — so one lane's
entire window fits in VMEM.

This kernel exploits exactly that: a 1-D grid over lanes, each grid step
loads its lane's whole window once, runs **all** of Steps 1-3 on the
VMEM-resident row (the bisections become register-level loops over loaded
vectors), and writes the three outputs once — one HBM read and one HBM
write per element for the entire scheduling pass.

Bit-parity contract: the kernel body is an op-for-op transcription of the
masked vectorized pass in :mod:`repro.core.passes` (greedy structure,
class-free), restricted to one lane.  The ``lax.cond`` phase skips of the
reference are value-level identities per lane (a lane with no head admits
nothing, ``need == 0`` takes nothing, ``idle == 0`` gives nothing), so
running every phase unconditionally yields bitwise-identical outputs —
asserted by the interpret-mode parity tests in ``tests/test_passes.py``
and the engine-level crosscheck (``--expand-backend fused-interpret``).
That Mosaic accepts the kernel for a TPU v5e is asserted separately, by
compiling it for a described chip in ``tests/test_tpu_compile.py``; the
in-kernel prefix sums are :func:`repro.kernels.waterfill.lane_cumsum`.

Balanced (AVG) structure and workload-class queue priority are not fused;
:func:`repro.core.passes.schedule_tick` falls back to the reference pass
for those statics.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.jobs import DONE, QUEUED, RUNNING
from repro.kernels.waterfill import lane_cumsum

_SHADOW_EPS = 1e-3  # must match repro.core.passes._SHADOW_EPS


def _first_true(mask):
    """``passes.first_true`` without argmax: the slot where the inclusive
    prefix count first hits 1."""
    return mask & (lane_cumsum(mask.astype(jnp.int32)) == 1)


def _speedup_f32(a, p):
    af = jnp.maximum(a.astype(jnp.float32), 1.0)
    return 1.0 / ((1.0 - p) + p / af)


def _take_desc_prefix(prio, amount, need, lo0: int, hi0: int):
    """``passes.take_desc_prefix`` on a (1, W) row with (1, 1) lane scalars."""
    lo = jnp.full((1, 1), lo0, jnp.int32)
    hi = jnp.full((1, 1), hi0, jnp.int32)
    s_hi = jnp.zeros_like(need)
    for _ in range(int(math.ceil(math.log2(max(hi0 - lo0, 1)))) + 1):
        mid = (lo + hi) // 2
        s = jnp.sum(jnp.where(prio > mid, amount, 0), axis=-1,
                    keepdims=True)
        ok = s <= need
        hi = jnp.where(ok, mid, hi)
        s_hi = jnp.where(ok, s, s_hi)
        lo = jnp.where(ok, lo, mid)
    theta = hi
    rem = need - s_hi
    tie = prio == theta
    before = lane_cumsum(jnp.where(tie, amount, 0))
    tie_take = jnp.clip(rem - (before - amount), 0, amount)
    return jnp.where(prio > theta, amount, jnp.where(tie, tie_take, 0))


def _give_asc_prefix(prio, room, idle, lo0: int, hi0: int):
    return _take_desc_prefix(-prio, room, idle, -hi0 - 1, -lo0 + 1)


def _shadow_reservation(est, release, free, head_floor, iters: int):
    """``passes.shadow_reservation`` on a (1, W) row -> (1, 1) scalars."""
    NEG = jnp.float32(-jnp.inf)
    finite = jnp.isfinite(est)
    rel = jnp.where(finite, release, 0)
    need = head_floor - free

    def released(tau):
        return jnp.sum(jnp.where(finite & (est <= tau), rel, 0), axis=-1,
                       keepdims=True)

    hi = jnp.max(jnp.where(finite, est, NEG), axis=-1, keepdims=True)
    lo = jnp.zeros_like(hi)
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        ok = released(mid) >= need
        snap = jnp.max(jnp.where(finite & (est <= mid), est, NEG),
                       axis=-1, keepdims=True)
        hi = jnp.where(ok, snap, hi)
        lo = jnp.where(ok, lo, mid)
    extra = free + released(hi) - head_floor
    return hi, extra


def _tick_kernel(state_ref, alloc_ref, remaining_ref, start_ref, act_ref,
                 mall_ref, want_ref, floor_ref, sfloor_ref, pref_ref,
                 mx_ref, pfrac_ref, wall_ref, cap_ref, tnow_ref, depth_ref,
                 out_state_ref, out_alloc_ref, out_start_ref, *,
                 fill_rounds: int, prio_lo: int, prio_hi: int,
                 shadow_iters: int, depth_bounded: bool):
    INF = jnp.float32(jnp.inf)
    state = state_ref[...]                       # (1, W) i32
    alloc = alloc_ref[...]                       # (1, W) i32
    remaining = remaining_ref[...]               # (1, W) f32
    start_t = start_ref[...]                     # (1, W) f32
    act = act_ref[...] != 0                      # (1, W)
    mall = mall_ref[...] != 0                    # (1, W)
    want, floor = want_ref[...], floor_ref[...]  # (1, W) i32
    sfloor, pref = sfloor_ref[...], pref_ref[...]
    mx = mx_ref[...]
    pfrac, wall = pfrac_ref[...], wall_ref[...]  # (1, W) f32
    capacity = cap_ref[...]                      # (1, 1) lane scalars
    t_now = tnow_ref[...]
    depth = depth_ref[...]

    running = state == RUNNING
    free = capacity - jnp.sum(jnp.where(running, alloc, 0), axis=-1,
                              keepdims=True)

    # -- Step 1: FCFS prefix + head fallback ------------------------------
    queued = (state == QUEUED) & act
    cumw = lane_cumsum(jnp.where(queued, want, 0))
    s1 = queued & (cumw <= free)
    used = jnp.max(jnp.where(s1, cumw, 0), axis=-1, keepdims=True)
    leftover = free - used
    h_mask = _first_true(queued & ~s1)
    hfloor = jnp.sum(jnp.where(h_mask, floor, 0), axis=-1, keepdims=True)
    hwant = jnp.sum(jnp.where(h_mask, want, 0), axis=-1, keepdims=True)
    h_ok = (hfloor > 0) & (hfloor <= leftover)
    h_alloc = jnp.clip(leftover, hfloor, hwant)

    h_upd = h_mask & h_ok
    started = s1 | h_upd
    alloc = jnp.where(s1, want, alloc)
    alloc = jnp.where(h_upd, h_alloc, alloc)
    state = jnp.where(started, RUNNING, state)
    start_t = jnp.where(started, t_now, start_t)
    free = leftover - jnp.where(h_ok, h_alloc, 0)

    # -- EASY backfill under the head's shadow-time reservation -----------
    queued = (state == QUEUED) & act
    h_mask = _first_true(queued)
    hfloor = jnp.sum(jnp.where(h_mask, floor, 0), axis=-1, keepdims=True)
    hwant = jnp.sum(jnp.where(h_mask, want, 0), axis=-1, keepdims=True)
    has_head = hfloor > 0

    behind_head = act & ~h_mask
    if depth_bounded:
        ranks = lane_cumsum(queued.astype(jnp.int32))
        behind_head = behind_head & (ranks <= depth + 1)
    run = state == RUNNING
    est = jnp.where(run,
                    t_now + remaining * wall / _speedup_f32(alloc, pfrac),
                    INF)
    sh_b, ex_b = _shadow_reservation(est, alloc, free, hfloor,
                                     iters=shadow_iters)
    blocked = has_head & (hfloor > free)
    shadow = jnp.where(blocked, sh_b, jnp.where(has_head, t_now, INF))
    extra = jnp.where(blocked, ex_b,
                      jnp.where(has_head, free - hfloor, free))

    tfit = t_now + wall / _speedup_f32(want, pfrac) <= shadow + _SHADOW_EPS
    for _ in range(fill_rounds):
        cand = (state == QUEUED) & behind_head
        c = cand & tfit & (want <= free)
        cum = lane_cumsum(jnp.where(c, want, 0))
        s = c & (cum <= free)
        free = free - jnp.max(jnp.where(s, cum, 0), axis=-1, keepdims=True)
        lim = jnp.minimum(free, extra)
        c2 = cand & ~s & ~tfit & (want <= lim)
        cum2 = lane_cumsum(jnp.where(c2, want, 0))
        s2 = c2 & (cum2 <= lim)
        take2 = jnp.max(jnp.where(s2, cum2, 0), axis=-1, keepdims=True)
        lim3 = jnp.minimum(free - take2, extra - take2)
        c3 = cand & ~s & ~s2 & ~tfit & (floor <= lim3)
        cum3 = lane_cumsum(jnp.where(c3, floor, 0))
        s3 = c3 & (cum3 <= lim3)
        take3 = jnp.max(jnp.where(s3, cum3, 0), axis=-1, keepdims=True)

        free = free - take2 - take3
        extra = extra - take2 - take3
        new = s | s2 | s3
        alloc = jnp.where(s | s2, want, jnp.where(s3, floor, alloc))
        state = jnp.where(new, RUNNING, state)
        start_t = jnp.where(new, t_now, start_t)

    # -- Step 2: greedy shrink to admit the head --------------------------
    deficit = jnp.where(has_head, hfloor - free, 0)
    shrinkable = (state == RUNNING) & mall
    fl = jnp.where(shrinkable, jnp.minimum(sfloor, alloc), alloc)
    surplus = jnp.maximum(alloc - fl, 0)
    tot_surplus = jnp.sum(surplus, axis=-1, keepdims=True)
    need = jnp.where((deficit > 0) & (tot_surplus >= deficit), deficit, 0)
    prio = jnp.clip(alloc - pref, prio_lo, prio_hi)
    alloc = alloc - _take_desc_prefix(prio, surplus, need,
                                      prio_lo - 1, prio_hi)
    free = free + need

    h_ok = has_head & (hfloor <= free)
    h_alloc = jnp.clip(free, hfloor, hwant)
    h_upd = h_mask & h_ok
    alloc = jnp.where(h_upd, h_alloc, alloc)
    state = jnp.where(h_upd, RUNNING, state)
    start_t = jnp.where(h_upd, t_now, start_t)
    free = free - jnp.where(h_ok, h_alloc, 0)

    # -- Step 3: greedy waterfill expand ----------------------------------
    expandable = (state == RUNNING) & mall
    idle = jnp.maximum(
        jnp.where(jnp.any(expandable, axis=-1, keepdims=True), free, 0), 0)
    room = jnp.where(expandable, jnp.maximum(mx - alloc, 0), 0)
    pr = jnp.clip(alloc - pref, prio_lo, prio_hi)
    alloc = alloc + _give_asc_prefix(pr, room, idle, prio_lo - 1, prio_hi)

    out_state_ref[...] = state
    out_alloc_ref[...] = alloc
    out_start_ref[...] = start_t


def fused_schedule_tick(p, state, alloc, remaining, start_t, act,
                        capacity, t_now, *, fill_rounds: int, prio_lo: int,
                        prio_hi: int, shadow_iters: int,
                        backfill_depth=None, interpret: bool = False):
    """Run the fused greedy/class-free Steps 1-3 kernel over all lanes.

    Accepts the same array layout as :func:`repro.core.passes.
    schedule_tick` (lane shape ``()`` or ``(B,)``, slot arrays
    ``(..., W)``); pads the window to a lane-block multiple of 128 with
    inert slots.  Returns ``(state, alloc, start_t)``.
    """
    lane_shape = state.shape[:-1]
    W0 = state.shape[-1]
    B = 1
    for d in lane_shape:
        B *= d

    def row_i32(a, fill=0):
        a = jnp.broadcast_to(jnp.asarray(a), lane_shape + (W0,))
        return a.reshape(B, 1, W0).astype(jnp.int32), jnp.int32(fill)

    def row_f32(a, fill=0.0):
        a = jnp.broadcast_to(jnp.asarray(a), lane_shape + (W0,))
        return a.reshape(B, 1, W0).astype(jnp.float32), jnp.float32(fill)

    rows = [row_i32(state, DONE),
            row_i32(alloc), row_f32(remaining), row_f32(start_t),
            row_i32(act), row_i32(p.malleable), row_i32(p.want),
            row_i32(p.floor), row_i32(p.shrink_floor), row_i32(p.prio_ref),
            row_i32(p.max_nodes), row_f32(p.pfrac),
            row_f32(p.wall_work, 1.0)]
    # pad the window so the lane block is TPU-lane aligned; padding slots
    # are DONE, zero-alloc and non-malleable: they contribute zero to
    # every reduction and are sliced off on return
    W = max(128, -(-W0 // 128) * 128)
    pad = W - W0
    if pad:
        rows = [(jnp.pad(a, ((0, 0), (0, 0), (0, pad)), constant_values=f),
                 f)
                for a, f in rows]
    arrs = [a for a, _ in rows]

    def scal(v, dtype):
        v = jnp.broadcast_to(jnp.asarray(v), lane_shape)
        return v.reshape(B, 1, 1).astype(dtype)

    arrs.append(scal(capacity, jnp.int32))
    arrs.append(scal(t_now, jnp.float32))
    depth_bounded = backfill_depth is not None
    arrs.append(scal(backfill_depth if depth_bounded else 0, jnp.int32))

    # one lane per grid step: (B, 1, W) rows with a squeezed lane dim, so
    # the block's last two dims equal the array's (Mosaic's tiling rule)
    row_spec = pl.BlockSpec((None, 1, W), lambda b: (b, 0, 0))
    scal_spec = pl.BlockSpec((None, 1, 1), lambda b: (b, 0, 0))
    out = pl.pallas_call(
        functools.partial(_tick_kernel, fill_rounds=fill_rounds,
                          prio_lo=prio_lo, prio_hi=prio_hi,
                          shadow_iters=shadow_iters,
                          depth_bounded=depth_bounded),
        grid=(B,),
        in_specs=[row_spec] * 13 + [scal_spec] * 3,
        out_specs=[row_spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((B, 1, W), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1, W), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1, W), jnp.float32)],
        interpret=interpret,
        name="schedule_tick",
    )(*arrs)
    state2, alloc2, start2 = (a[:, 0, :W0] for a in out)
    return (state2.reshape(lane_shape + (W0,)),
            alloc2.reshape(lane_shape + (W0,)),
            start2.reshape(lane_shape + (W0,)))
