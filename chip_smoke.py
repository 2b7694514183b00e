#!/usr/bin/env python3
"""Bring-up smoke: the batched sweep and what-if paths on a TPU.

Deployment: Haswell at paper Table 2 size (``scale=1.0``: 28,259 jobs on
2,388 nodes, 1 s tick, 5 days), trace seed 0, one transform seed.  The
trace is generated from the seed.  Every phase runs in this one process
(a chip belongs to one process):

1. sweep, twice: ``ExperimentSpec -> run_experiment -> backend_jax`` on a
   fresh cell store each, once with the reference ``bisect`` pass and
   once with the fused ``schedule_tick`` Pallas kernel (``fused``);
2. DES reference: ``easy@0`` and ``keeppref@50`` through the numpy DES,
   in-process, held to ``CROSSCHECK_TOLERANCES``;
3. serve: what-if queries, two of them identical and concurrent, through
   ``WhatIfEngine(engine="jax")`` on its own fresh store.

Before them, the scheduling pass's prefix sum (``passes.prefix_sum``) runs
on ``(16, W)`` int32 (the full range) and bool arrays at the engine's
window widths and the whole log.

Checks: the prefix sum equals ``numpy.cumsum`` and its program holds the
blocked form's matmul; every cell computed fresh (no store hit, no lane
cut by the step budget); greedy cells bit-identical between ``bisect``
and ``fused``, with the kernel (``tpu_custom_call``) in the fused chunk
programs; DES agreement; served answers equal to the sweep's.  A failed check, or no
TPU, exits non-zero.  The last line of stdout is the JSON result.

  python3 chip_smoke.py                # one chip
  python3 chip_smoke.py --four-chips   # lane-sharded sweep: 4 devices vs 1
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

WORKLOAD = "haswell"
SCALE = 1.0  # paper Table 2: 28,259 jobs, 2,388 nodes
# one cell per pass structure (plus the rigid baseline easy@0):
# greedy keeppref/min, balanced avg, pooled pref_common_pool, stealing
# steal_agreement — at 50% and 100% malleable
STRATEGIES = ("keeppref", "min", "avg", "pref_common_pool",
              "steal_agreement")
PROPORTIONS = (0.5, 1.0)
DES_CELLS = (("easy", 0.0, 0), ("keeppref", 0.5, 0))
PREFIX_WIDTHS = (4096, 8192, 16384, 28259)  # window rungs and the whole log


def smoke_spec(scale: float = SCALE, strategies=STRATEGIES,
               proportions=PROPORTIONS):
    from repro.experiments import ExperimentSpec

    return ExperimentSpec(workloads=(WORKLOAD,), scale=scale, trace_seed=0,
                          seeds=1, proportions=proportions,
                          strategies=strategies, engine="jax")


def smoke_queries():
    """Three distinct what-if cells; keeppref@50 is asked twice."""
    from repro.serve.whatif import WhatIfQuery

    return [WhatIfQuery(strategy="keeppref", proportion=0.5),
            WhatIfQuery(strategy="keeppref", proportion=0.5),
            WhatIfQuery(strategy="min", proportion=1.0),
            WhatIfQuery(strategy="easy", proportion=0.0)]


class Checks:
    """Named pass/fail records; any failure fails the run."""

    def __init__(self) -> None:
        self.failed: list = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"[check] {'ok  ' if ok else 'FAIL'} {name}"
              + (f": {detail}" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)
        return ok


def same(a, b) -> bool:
    """Bitwise equality of two metric dicts (NaN equals NaN)."""
    return json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


def fresh_dir(path: pathlib.Path) -> pathlib.Path:
    shutil.rmtree(path, ignore_errors=True)
    path.mkdir(parents=True)
    return path


def engine_stats() -> dict:
    """Compile/execute split and counters of the engine calls since the
    last reset, read from the flight recorder's spans (every engine
    chunk call is one ``sweep.compile`` or ``sweep.execute`` span)."""
    from repro import obs

    tracer = obs.get_tracer()
    events = tracer.events()
    counts = tracer.counters.snapshot()["counters"]

    def total(name):
        return sum(e["dur"] for e in events if e["name"] == name) / 1e6

    windows = [e["args"].get("window", 0) for e in events
               if e["name"] in ("sweep.compile", "sweep.execute")]
    return {"compile_s": total("sweep.compile"),
            "execute_s": total("sweep.execute"),
            "retraces": int(counts.get("sweep.retraces", 0)),
            "escalations": int(counts.get("sweep.escalations", 0)),
            "aot_rejits": int(counts.get("sweep.aot_rejits", 0)),
            "window_peak": max(windows, default=0)}


def prefix_phase(checks: Checks, widths=PREFIX_WIDTHS, rows: int = 16):
    """``passes.prefix_sum`` on this platform against ``numpy.cumsum``;
    the blocked form is taken on TPU only."""
    import jax
    import numpy as np

    from repro.core.passes import prefix_sum

    rng = np.random.default_rng(0)
    fn = jax.jit(prefix_sum)
    on_tpu = jax.devices()[0].platform == "tpu"
    for w in widths:
        ints = rng.integers(-2**31, 2**31, (rows, w)).astype(np.int32)
        for x in (ints, rng.random((rows, w)) < 0.5):
            ref = np.cumsum(x, axis=-1, dtype=np.int64).astype(np.int32)
            blocked = "dot_general" in fn.lower(x).as_text()
            checks(f"prefix_sum {x.dtype} ({rows}, {w}): equals "
                   "numpy.cumsum", np.array_equal(np.asarray(fn(x)), ref))
            checks(f"prefix_sum {x.dtype} ({rows}, {w}): "
                   + ("blocked form" if on_tpu else "jnp.cumsum"),
                   blocked == on_tpu)


def sweep_phase(spec, store_dir: pathlib.Path, checks: Checks, *,
                expand_backend: str = "bisect", devices: int = 1,
                label: str = "sweep"):
    """``run_experiment`` on a fresh store; returns ``{cell: metrics}``
    read back from the store, and the run's engine info."""
    from repro.experiments import run_experiment
    from repro.sweep.cache import SweepCache

    fresh_dir(store_dir)
    out = run_experiment(
        spec, cache_dir=str(store_dir), verbose=False,
        backend_options={"expand_backend": expand_backend,
                         "devices": devices})
    info = out[WORKLOAD]["_engine"]
    store = SweepCache(str(store_dir))
    cells = {c: store.get(spec.cell_fingerprint(WORKLOAD, c))
             for c in spec.cells()}
    checks(f"{label}: no store hit", info["cache_hits"] == 0,
           f"{info['cache_hits']} hits")
    checks(f"{label}: every cell computed",
           info["computed_cells"] == len(cells)
           and all(m is not None for m in cells.values()),
           f"{info['computed_cells']}/{len(cells)}")
    checks(f"{label}: no lane cut by the step budget",
           info["incomplete_cells_total"] == 0,
           f"{info['incomplete_cells_total']} incomplete")
    return cells, info


def des_phase(spec, jax_cells, checks: Checks):
    """The DES reference cells, in-process, against the jax cells."""
    from repro.experiments.crosscheck import crosscheck_cells

    rep = crosscheck_cells(spec, WORKLOAD,
                           {c: jax_cells[c] for c in DES_CELLS},
                           n_cells=len(DES_CELLS), verbose=True)
    for rec in rep["cells"]:
        checks(f"des: {rec['cell']} within CROSSCHECK_TOLERANCES",
               rec["within_tolerance"],
               ", ".join(f"{k} des={d['des']:.6g} jax={d['jax']:.6g}"
                         for k, d in rec["deltas"].items()))
    checks("des: every reference cell compared",
           len(rep["cells"]) == len(DES_CELLS))
    return rep


def serve_phase(spec, store_dir: pathlib.Path, sweep_cells, checks: Checks,
                *, expand_backend: str = "bisect"):
    """Coalesced what-if queries on a fresh store, against the sweep."""
    from repro.serve.__main__ import run_storm
    from repro.serve.whatif import WhatIfEngine

    fresh_dir(store_dir)
    queries = smoke_queries()
    engine = WhatIfEngine(spec, cache_dir=str(store_dir), max_batch=8,
                          max_wait_s=0.05, start=False,
                          backend_options={"devices": 1,
                                           "expand_backend": expand_backend})
    rows = run_storm(engine, queries, clients=2)
    stats = engine.stats()
    engine.close()
    unique = len({q.cell() for q in queries})
    checks("serve: no query failed", not any("error" in r for r in rows),
           "; ".join(r["error"] for r in rows if "error" in r))
    checks("serve: fresh store (no hits)", stats["hits"] == 0,
           f"{stats['hits']} hits")
    checks("serve: identical queries deduplicated",
           stats["computed"] == unique
           and stats["dedup"] == len(queries) - unique,
           f"computed={stats['computed']} dedup={stats['dedup']}")
    for q, row in zip(queries, rows):
        checks(f"serve: {q.strategy}@{int(q.proportion * 100)} equals the "
               "sweep cell",
               "metrics" in row and same(row["metrics"],
                                         sweep_cells[q.cell()]))
    return stats


def kernel_programs(keys) -> dict:
    """``{(structure, window): kernel present}`` for chunk programs, read
    from the lowered program (a Pallas TPU kernel lowers to a
    ``tpu_custom_call``)."""
    from repro.sweep import batch as sb

    out = {}
    for key in sorted(keys, key=repr):
        cfg, n, B, W, lo, hi, span, classes, sjf, depth = key
        fn = sb._chunk_fn(cfg, n, B, W, lo, hi, span, classes,
                          with_sjf=sjf, depth_bounded=depth)
        text = fn.lower(*sb.chunk_arg_shapes(n, B)).as_text()
        out[(cfg.structure, W)] = "tpu_custom_call" in text
    return out


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def run_phase(name: str, fn, report: dict, dev):
    """Run one phase under a fresh flight recorder; print its numbers."""
    from repro import obs

    obs.get_tracer().reset()
    t0 = time.monotonic()
    result = fn()
    stats = {"wall_s": time.monotonic() - t0, **engine_stats(),
             "peak_bytes_in_use": peak_bytes(dev)}
    report[name] = stats
    print(f"[phase] {name}: " + " ".join(f"{k}={v}" for k, v in
                                          stats.items()), flush=True)
    return result


def one_chip(spec, out: pathlib.Path, dev, checks: Checks,
             report: dict) -> None:
    from repro.core import get_strategy
    from repro.sweep import batch as sb

    run_phase("prefix", lambda: prefix_phase(checks), report, dev)
    bisect, _ = run_phase(
        "sweep_bisect", lambda: sweep_phase(spec, out / "store-bisect",
                                            checks, label="sweep bisect"),
        report, dev)
    keys_before = set(sb._COMPILED_KEYS)
    fused, _ = run_phase(
        "sweep_fused", lambda: sweep_phase(spec, out / "store-fused", checks,
                                           expand_backend="fused",
                                           label="sweep fused"),
        report, dev)
    fused_keys = [k for k in sb._COMPILED_KEYS - keys_before
                  if k[0].expand_backend == "fused"]
    programs = kernel_programs(fused_keys)
    for (structure, W), kernel in sorted(programs.items()):
        print(f"[fused] {structure} W={W}: "
              + ("fused schedule_tick kernel" if kernel
                 else "reference pass (no kernel)"), flush=True)
    report["fused_programs"] = {f"{s}@W{w}": k
                                for (s, w), k in programs.items()}
    checks("fused: the kernel is in every greedy chunk program",
           any(s == "greedy" for s, _ in programs)
           and all(k for (s, _), k in programs.items() if s == "greedy"))
    greedy = [c for c in spec.cells()
              if get_strategy(c[0]).structure == "greedy"]
    for c in spec.cells():
        bitwise = same(bisect[c], fused[c])
        if c in greedy:
            checks(f"greedy {c[0]}@{int(c[1] * 100)}: bisect == fused "
                   "bitwise", bitwise)
        else:
            print(f"[fused] {c[0]}@{int(c[1] * 100)}: bisect == fused "
                  f"bitwise: {bitwise}", flush=True)
    run_phase("des", lambda: des_phase(spec, bisect, checks), report, dev)
    report["serve_stats"] = run_phase(
        "serve", lambda: serve_phase(spec, out / "store-serve", bisect,
                                     checks), report, dev)


def four_chips(spec, out: pathlib.Path, dev, checks: Checks,
               report: dict) -> None:
    one, _ = run_phase(
        "sweep_1_device", lambda: sweep_phase(spec, out / "store-1dev",
                                              checks, devices=1,
                                              label="1 device"),
        report, dev)
    four, _ = run_phase(
        "sweep_4_devices", lambda: sweep_phase(spec, out / "store-4dev",
                                               checks, devices=4,
                                               label="4 devices"),
        report, dev)
    for c in spec.cells():
        checks(f"{c[0]}@{int(c[1] * 100)}: 4 devices == 1 device bitwise",
               same(one[c], four[c]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the lane-sharded sweep, on 4 devices "
                         "and on 1, and compare them")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                    help="output directory (cell stores, trace, report)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    need = 4 if args.four_chips else 1
    if dev.platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU device(s); JAX found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 2
    print(f"[device] platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)

    from repro import obs
    from repro.xla_cache import enable_compilation_cache

    print(f"[xla_cache] {enable_compilation_cache()}", flush=True)
    obs.configure(enabled=True)
    out = fresh_dir(pathlib.Path(args.out))
    checks = Checks()
    report: dict = {"device_kind": dev.device_kind, "count": len(devices),
                    "workload": WORKLOAD, "scale": SCALE}
    t0 = time.monotonic()
    (four_chips if args.four_chips else one_chip)(smoke_spec(), out, dev,
                                                  checks, report)
    report["total_s"] = time.monotonic() - t0
    report["failed_checks"] = checks.failed
    (out / "report.json").write_text(json.dumps(report, indent=1,
                                                default=str))
    print(f"[total] {report['total_s']:.1f}s, "
          f"{len(checks.failed)} failed check(s)", flush=True)
    if checks.failed:
        print("chip_smoke: failed: " + ", ".join(checks.failed),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
