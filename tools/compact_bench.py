#!/usr/bin/env python3
"""Time one chunk-window move on the device: gathers against the shift network.

A move is what a chunk call does around its scan: the 14 job fields and
7 state fields of ``--rows`` lanes of ``n`` jobs packed into a ``W``-slot
window (``repro.sweep.compact`` ``*_in``), and the 7 state fields written
back (``*_out``).  For each ``n`` and each ladder rung ``W`` the gather form
and the shift form run inside one jitted loop of ``--iters`` moves, on a
selection of about ``W`` jobs a lane that changes every iteration; a loop
that slices the first ``W`` slots and writes them back with one select
(``copy``, the least a move can cost) is timed too.  Both forms are first
checked against each other bit for bit, on f32 fields that hold NaNs.  One
JSON line per shape, times in microseconds per move.

  python3 tools/compact_bench.py [--jobs 2550 28259] [--iters 10]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

JOBS = (2550, 28259)


def inputs(rng, B: int, n: int, W: int):
    """The engine's per-job fields (NaN payloads in the f32 ones) and two
    selections of about ``W`` jobs a lane in a stretch of ``2W``."""
    import jax.numpy as jnp
    import numpy as np

    from repro.sweep import batch as sb

    shapes, full = sb.chunk_arg_shapes(n, B)[:2]
    names = [(shapes, f) for f in sb._WINDOW_FIELDS] + \
        [(full, f) for f in sb._STATE_FIELDS]

    def field(dtype):
        if dtype == jnp.bool_:
            return rng.random((B, n)) < 0.5
        if dtype == jnp.int32:
            return rng.integers(-2**31, 2**31, (B, n)).astype(np.int32)
        x = rng.standard_normal((B, n)).astype(np.float32)
        x[rng.random((B, n)) < 0.1] = np.uint32(0x7FC00001).view(np.float32)
        return x

    arrays = [field((getattr(s, f) if isinstance(s, tuple) else s[f]).dtype)
              for s, f in names]

    def selection():
        span = min(n, 2 * W)
        start = rng.integers(0, n - span + 1, B)
        ar = np.arange(n)
        inside = (ar >= start[:, None]) & (ar < (start + span)[:, None])
        return inside & (rng.random((B, n)) < 0.5)

    return arrays, selection(), selection()


def copy_in(arrays, live, pos, fills, W):
    import jax.numpy as jnp
    return [a[:, :W] for a in arrays], jnp.zeros(pos[:, :W].shape, pos.dtype)


def copy_out(fulls, bufs, live, back):
    import jax.numpy as jnp
    n, W = live.shape[-1], back.shape[-1]
    return [jnp.where(live, jnp.pad(b, [(0, 0), (0, n - W)]), a)
            for a, b in zip(fulls, bufs)]


def mover(form_in, form_out, W, fills, n_jobs_fields):
    """One move: select, pack, write the state back; returns the new
    state fields and a checksum of the job fields' window."""
    import jax
    import jax.numpy as jnp

    from repro.core.passes import prefix_sum

    def move(arrays, sel):
        pos = prefix_sum(sel) - 1
        live = sel & (pos < W)
        win, back = form_in(arrays, live, pos, fills, W)
        out = form_out(arrays[n_jobs_fields:], win[n_jobs_fields:], live,
                       back)
        check = sum(jnp.sum(jax.lax.bitcast_convert_type(w, jnp.int32)
                            if w.dtype == jnp.float32
                            else w.astype(jnp.int32), axis=-1)
                    for w in win[:n_jobs_fields])
        return out, check, win, back

    return move


def loop_time(move, arrays, sel_a, sel_b, iters: int, reps: int, nj: int):
    """Best wall time of ``iters`` moves, the state carried through."""
    import jax
    import jax.numpy as jnp

    def body(i, carry):
        state, acc = carry
        sel = jnp.where(i % 2 == 0, sel_a, sel_b)
        out, check, _, _ = move(list(arrays[:nj]) + list(state), sel)
        return out, acc ^ check

    run = jax.jit(lambda: jax.lax.fori_loop(
        0, iters, body, (list(arrays[nj:]),
                         jnp.zeros(sel_a.shape[:1], jnp.int32))))
    jax.block_until_ready(run())
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        best = min(best, time.perf_counter() - t0)
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--jobs", type=int, nargs="+", default=JOBS)
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.sweep import batch as sb
    from repro.sweep import compact
    from repro.sweep.batch import window_ladder

    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    fills = sb._WINDOW_FILLS + sb._STATE_FILLS
    nj = len(sb._WINDOW_FIELDS)
    forms = {"gather": (compact.gather_in, compact.gather_out),
             "shift": (compact.shift_in, compact.shift_out),
             "copy": (copy_in, copy_out)}
    ok = True
    for n in args.jobs:
        for W in window_ladder(128, n):
            if W > 16384:
                continue
            arrays, sel_a, sel_b = inputs(rng, args.rows, n, W)
            arrays = [jnp.asarray(a) for a in arrays]
            sel_a, sel_b = jnp.asarray(sel_a), jnp.asarray(sel_b)
            row = {"device": dev.device_kind, "rows": args.rows, "n": n,
                   "W": W, "fields": len(arrays)}
            outs = {}
            for name, (f_in, f_out) in forms.items():
                move = mover(f_in, f_out, W, fills, nj)
                if name != "copy":
                    out, _, win, _ = jax.jit(move)(arrays, sel_a)
                    outs[name] = [np.asarray(x) for x in
                                  jax.tree_util.tree_leaves((out, win))]
                t = loop_time(move, arrays, sel_a, sel_b, args.iters,
                              args.reps, nj)
                row[f"{name}_us"] = t / args.iters * 1e6
            same = all(
                np.array_equal(a.view(np.uint32) if a.dtype == np.float32
                               else a,
                               b.view(np.uint32) if b.dtype == np.float32
                               else b)
                for a, b in zip(outs["gather"], outs["shift"]))
            row["exact"] = same
            ok &= same
            print(json.dumps(row), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
