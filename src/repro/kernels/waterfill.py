"""Greedy prefix waterfill as a Pallas TPU kernel — the scheduler hot loop.

The paper's Step-2/Step-3 redistribution (shrink highest-priority-first /
expand lowest-priority-first) reduces, after priority sorting, to a *prefix
waterfill*: walk the capacity array in order, take from each slot until the
target is met.  At Eagle scale (143k jobs x one scheduler invocation per
event) this is the simulator's dominant vector op.

Kernel structure: 1-D sequential grid over job blocks; the running
prefix total is a (1, 1) VMEM scalar carried across grid steps.  Each
block does an in-VMEM log-step prefix sum (:func:`lane_cumsum`), clips
against the remaining target, and writes its take — one HBM read and
one HBM write per element, the memory
roofline for this op (XLA's global cumsum materializes the full prefix
array through HBM twice).

Capacities are int32 node counts; targets fit int32 (cluster sizes <= 10k
nodes, Table 2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def lane_cumsum(x):
    """Inclusive int32 prefix sum along the last (lane) axis, in-kernel.

    Mosaic has no ``cumsum`` lowering, so this is a log-step
    (Hillis-Steele) scan: ``ceil(log2 W)`` lane rotations, each masked by
    a lane iota so nothing wraps around.  Integer adds are exact (and
    wrap identically on overflow), so the result is bit-equal to
    ``jnp.cumsum`` on the same row.
    """
    axis = x.ndim - 1
    n = x.shape[axis]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    shift = 1
    while shift < n:
        x = x + jnp.where(lane >= shift, pltpu.roll(x, shift, axis), 0)
        shift *= 2
    return x


def _waterfill_kernel(target_ref, cap_ref, take_ref, carry_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    cap = cap_ref[...]                              # (1, blk) int32
    prev = carry_ref[...]                           # (1, 1)
    before = prev + lane_cumsum(cap) - cap          # prefix sum before slot
    remaining = target_ref[...] - before
    take_ref[...] = jnp.clip(remaining, 0, cap)
    carry_ref[...] = prev + jnp.sum(cap, axis=-1, keepdims=True)


def waterfill(capacity: jax.Array, target, *, block: int = 2048,
              interpret: bool = False) -> jax.Array:
    """Per-slot take, in order, with sum == min(target, sum(capacity)).

    capacity: (N,) int32 >= 0, already in priority order; target: scalar.
    """
    cap = jnp.asarray(capacity, jnp.int32)
    n = cap.shape[0]
    # lane-aligned block; zero-capacity padding takes nothing
    block = min(block, max(-(-n // 128) * 128, 128))
    pad = (-n) % block
    if pad:
        cap = jnp.pad(cap, (0, pad))
    n_blocks = cap.shape[0] // block
    # (n_blocks, 1, block) with a squeezed block-index dim, so the block's
    # last two dims equal the array's (Mosaic's tiling rule)
    cap3 = cap.reshape(n_blocks, 1, block)
    blk = pl.BlockSpec((None, 1, block), lambda i: (i, 0, 0))

    out = pl.pallas_call(
        _waterfill_kernel,
        grid=(n_blocks,),
        # the target is a (1, 1) block too, so a vmapped call (one target
        # per lane) keeps a tileable layout
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)), blk],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct(cap3.shape, jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.int32)],
        interpret=interpret,
    )(jnp.asarray(target, jnp.int32).reshape(1, 1), cap3)
    return out.reshape(-1)[:n]


def greedy_shrink_pallas(alloc, floor, priority, need, *,
                         interpret: bool = False):
    """Pallas-accelerated :func:`repro.core.passes.greedy_shrink`."""
    alloc = jnp.asarray(alloc, jnp.int32)
    surplus = jnp.maximum(alloc - jnp.asarray(floor, jnp.int32), 0)
    order = jnp.argsort(-jnp.asarray(priority))
    take_sorted = waterfill(surplus[order], need, interpret=interpret)
    take = jnp.zeros_like(surplus).at[order].set(take_sorted)
    return alloc - take


def greedy_expand_pallas(alloc, cap, priority, idle, *,
                         interpret: bool = False):
    """Pallas-accelerated :func:`repro.core.passes.greedy_expand`."""
    alloc = jnp.asarray(alloc, jnp.int32)
    room = jnp.maximum(jnp.asarray(cap, jnp.int32) - alloc, 0)
    order = jnp.argsort(jnp.asarray(priority))
    give_sorted = waterfill(room[order], idle, interpret=interpret)
    give = jnp.zeros_like(room).at[order].set(give_sorted)
    return alloc + give
