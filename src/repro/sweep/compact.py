"""Moving a chunk's window of job slots in and out of the full job arrays.

Before each chunk call's scan, every lane's selected jobs (its active set
plus an arrival reserve, in FCFS order) are packed into the first ``W``
slots of a window; after the scan the window's state is written back to
the jobs it came from.  The selection is a stable partition: selected job
``m`` of a lane sits at ``i_m`` and goes to slot ``m``.  Two forms move the
same bits:

* ``gather``: a scatter builds each slot's job index, a
  ``take_along_axis`` per field reads the window, and an ``.at[].set`` per
  field writes it back.  Each costs an addressed access per element.
* ``shift``: a stable compaction network of log-step shifts.  Selected job
  ``m`` moves left by ``d_m = i_m - m``, which never decreases with ``m``,
  one bit of ``d_m`` per stage, least significant first, so no two
  selected jobs ever land on one slot.  The write-back runs the same
  stages in reverse, most significant bit first and shifting right, with
  the displacement each slot carried in.  A stage is one select between
  the fields and a shift of them, ``n * ceil(log2 n)`` element-stages a
  move, with no addressed access.

Both are exact: a select moves bits, NaN payloads included.  A chunk
program lowered for TPU runs the network, and one lowered for any other
platform the gathers (``tools/compact_bench.py``, one move of 16 lanes,
21 fields in and 7 back, PERF.md section 5).  On a TPU v5e the network
took 0.16-0.20 ms at 2,550 jobs and 0.73-0.89 ms at 28,259 whatever the
window, and the gathers 0.89-10.4 ms and 3.35-71.6 ms, growing with the
window from 128 to 16,384 slots: the network wins at every rung, and
both grow with the job count (the gathers through their index scatter
over every job), so no shape on the ladder crosses over.  On the CPU
(8 cores of a Xeon) the gathers took 0.80-1.54 ms at 2,550 jobs and
3.72-9.84 ms at 28,259, the network 35.7-48.7 ms and 512-554 ms; a
haswell grid at scale 0.25 took 131.8 and 135.1 s with the gathers,
144.1 and 144.7 s with the network.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp


def _stages(n: int) -> int:
    """Bits of the largest displacement, ``n - 1``."""
    return max(n - 1, 0).bit_length()


def _as_i32(a):
    """``a``'s bits as int32 (a bool as 0 or 1)."""
    if a.dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(a, jnp.int32)
    return a.astype(jnp.int32)


def _from_i32(x, dtype):
    """The inverse of :func:`_as_i32`."""
    if dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(x, jnp.float32)
    return x.astype(dtype)


def _network(arrays: List, disp, left: bool) -> Tuple[List, object]:
    """Move each slot whose displacement is non-zero by that many slots,
    to the left or to the right, one bit a stage: least significant bit
    first to the left, most significant first to the right.  Two moving
    slots never land on one slot only because their displacements never
    decrease along the axis (module doc); a slot a mover lands on takes
    the mover's values.  The fields travel as one int32 stack with the
    displacement last, and the stages are one loop, so a program holds
    one stage.  Returns the moved arrays and the displacement each slot
    now holds (0 where no moved slot landed)."""
    n = disp.shape[-1]
    last = _stages(n) - 1

    def stage(i, st):
        s = jnp.left_shift(1, i if left else last - i)
        pad = jnp.zeros_like(st)
        moved = (jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([st, pad], -1), s, n, -1) if left else
            jax.lax.dynamic_slice_in_dim(
                jnp.concatenate([pad, st], -1), n - s, n, -1))
        arrive = (moved[-1] & s) != 0
        left_behind = ~arrive & ((st[-1] & s) != 0)
        st = jnp.where(arrive, moved, st)
        return st.at[-1].set(jnp.where(left_behind, 0, st[-1]))

    st = jax.lax.fori_loop(0, last + 1, stage, jnp.stack(
        [_as_i32(a) for a in arrays] + [disp]))
    return [_from_i32(x, a.dtype) for x, a in zip(st, arrays)], st[-1]


def gather_in(arrays: Sequence, live, pos, fills: Sequence, W: int):
    """The window by per-element gathers; returns ``(window fields, idx)``
    where ``idx`` is each slot's job index (``n`` on an empty slot)."""
    B, n = live.shape
    rows = jnp.arange(B)[:, None]
    ar = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (B, n))
    idx = jnp.full((B, W), n, jnp.int32).at[
        rows, jnp.where(live, pos, W)].set(ar)  # W: dropped
    slot_ok = idx < n
    gidx = jnp.minimum(idx, n - 1)
    return [jnp.where(slot_ok, jnp.take_along_axis(a, gidx, -1), f)
            for a, f in zip(arrays, fills)], idx


def gather_out(fulls: Sequence, bufs: Sequence, live, idx) -> List:
    """Write the window back by per-element scatters (``idx == n`` slots
    are dropped)."""
    rows = jnp.arange(live.shape[0])[:, None]
    return [a.at[rows, idx].set(buf) for a, buf in zip(fulls, bufs)]


def shift_in(arrays: Sequence, live, pos, fills: Sequence, W: int):
    """The window by the compaction network; returns ``(window fields,
    disp)`` where ``disp`` is each filled slot's displacement from its
    job (0 on an empty slot)."""
    n = live.shape[-1]
    ar = jnp.arange(n, dtype=jnp.int32)
    moved, disp = _network(list(arrays), jnp.where(live, ar - pos, 0),
                           left=True)
    filled = jnp.arange(W) < jnp.sum(live, axis=-1, dtype=jnp.int32)[:, None]
    return [jnp.where(filled, a[:, :W], f)
            for a, f in zip(moved, fills)], disp[:, :W]


def shift_out(fulls: Sequence, bufs: Sequence, live, disp) -> List:
    """Write the window back by the network run in reverse."""
    n = live.shape[-1]
    W = disp.shape[-1]
    grow = [(0, 0), (0, n - W)]
    moved, _ = _network([jnp.pad(b, grow) for b in bufs],
                        jnp.pad(disp, grow), left=False)
    return [jnp.where(live, m, a) for a, m in zip(fulls, moved)]


def window_in(arrays: Sequence, live, pos, fills: Sequence, W: int):
    """Pack each lane's ``live`` jobs into ``W`` slots in FCFS order: slot
    ``pos[i]`` takes job ``i``, slots past the lane's live count take
    ``fills``.  ``live`` is a stable selection of at most ``W`` jobs a
    lane and ``pos`` its exclusive prefix count.  Returns the window's
    fields and what :func:`window_out` needs to write it back."""
    return jax.lax.platform_dependent(
        list(arrays), live, pos,
        tpu=functools.partial(shift_in, fills=fills, W=W),
        default=functools.partial(gather_in, fills=fills, W=W))


def window_out(fulls: Sequence, bufs: Sequence, live, back) -> List:
    """Each ``live`` job of ``fulls`` takes its window slot's value from
    ``bufs``; every other job keeps its own."""
    return jax.lax.platform_dependent(list(fulls), list(bufs), live, back,
                                      tpu=shift_out, default=gather_out)
