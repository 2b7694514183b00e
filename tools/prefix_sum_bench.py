#!/usr/bin/env python3
"""Time one prefix sum on the device: ``jnp.cumsum`` against the blocked form.

For each width ``W`` a ``(rows, W)`` int32 array (the full int32 range) and
a bool array run through ``jnp.cumsum`` and through
``repro.core.passes.blocked_prefix_sum``, each inside one jitted loop of
``--iters`` sums on inputs that change every iteration; a loop with no sum
in it is subtracted.  Both forms are checked against ``numpy.cumsum``
first.  One JSON line per width and dtype, times in microseconds per sum.

  python3 tools/prefix_sum_bench.py [--widths 128 4096 ...] [--iters 400]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

WIDTHS = (128, 256, 512, 1024, 2048, 4096, 8192, 16384, 28259)


def loop_time(f, x, iters: int, reps: int) -> float:
    """Best wall time of ``iters`` sums ``acc ^= f(x ^ i)`` in one call."""
    import jax
    import jax.numpy as jnp

    def body(i, acc):
        flip = (i % 2 == 1) if x.dtype == jnp.bool_ else i
        return acc ^ f(x ^ flip).astype(jnp.int32)

    run = jax.jit(lambda x: jax.lax.fori_loop(
        0, iters, body, jnp.zeros(x.shape, jnp.int32)))
    run(x).block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        run(x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--widths", type=int, nargs="+", default=WIDTHS)
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--iters", type=int, default=400)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.passes import blocked_prefix_sum

    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    forms = {"cumsum": lambda x: jnp.cumsum(x, axis=-1),
             "blocked": blocked_prefix_sum}
    ok = True
    for w in args.widths:
        ints = rng.integers(-2**31, 2**31, (args.rows, w)).astype(np.int32)
        for x in (ints, rng.random((args.rows, w)) < 0.5):
            ref = np.cumsum(x, axis=-1, dtype=np.int64).astype(np.int32)
            row = {"device": dev.device_kind, "rows": args.rows, "W": w,
                   "dtype": str(x.dtype)}
            xd = jnp.asarray(x)
            base = loop_time(lambda v: v, xd, args.iters, args.reps)
            for name, f in forms.items():
                same = np.array_equal(np.asarray(jax.jit(f)(xd)), ref)
                ok &= same
                t = loop_time(f, xd, args.iters, args.reps)
                row[f"{name}_us"] = (t - base) / args.iters * 1e6
                row[f"{name}_exact"] = same
            print(json.dumps(row), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
