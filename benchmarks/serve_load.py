"""Serve-layer load benchmark: p50/p99 latency + throughput under storms.

The traffic-scaling scoreboard for the what-if service
(:mod:`repro.serve.whatif`, ``docs/serving.md``).  Three measured phases
against one engine + one shared cell store:

1. **cold closed-loop** — N client threads, each submitting its share of
   the query storm one-at-a-time (next query leaves when the previous
   answer lands).  Every unique cell is a cache miss, so this measures
   the request-coalescing compute path: batch width, throughput, and
   miss latency under concurrency.
2. **warm closed-loop** — the identical storm replayed against the now
   populated store/memo: every query is a hit, measuring the
   memory-speed answer path's p50/p99.
3. **warm open-loop** — queries arrive on a fixed schedule at
   ``--offered-qps`` regardless of completions (no coordinated
   omission: latency is measured from the *scheduled* arrival, so a
   stalled engine accrues queueing delay instead of hiding it).

The record (``artifacts/serve-timing-{engine}.json``) is gateable by
``tools/check_perf.py`` against the committed ``BENCH_serve.json``::

  PYTHONPATH=src python -m benchmarks.serve_load
  python tools/check_perf.py --timing artifacts/serve-timing-des.json \\
      --baseline BENCH_serve.json --warn-only
  python tools/check_perf.py --timing artifacts/serve-timing-des.json \\
      --baseline BENCH_serve.json --write-baseline   # reference box only

Defaults are the committed-baseline grid (haswell, scale 0.003, 8
clients, 64 queries, DES engine — stable on shared runners); CI's
``serve-smoke`` job runs exactly this grid warn-only.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time
from typing import Dict, List

REPO = pathlib.Path(__file__).resolve().parents[1]
ARTIFACTS = REPO / "artifacts"


def percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile of an already-sorted sample."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def latency_summary(lat_s: List[float], wall_s: float) -> Dict[str, float]:
    s = sorted(lat_s)
    return {"p50_ms": percentile(s, 0.50) * 1e3,
            "p99_ms": percentile(s, 0.99) * 1e3,
            "mean_ms": (sum(s) / len(s)) * 1e3 if s else 0.0,
            "qps": len(s) / wall_s if wall_s > 0 else 0.0,
            "wall_s": wall_s, "n": len(s)}


def run_closed_loop(engine, queries, clients: int,
                    timeout: float) -> Dict[str, float]:
    """Each client thread plays its share of the storm back-to-back."""
    import threading

    lat: List[List[float]] = [[] for _ in range(clients)]
    shares = [queries[i::clients] for i in range(clients)]

    def client(cid: int) -> None:
        for q in shares[cid]:
            t0 = time.perf_counter()
            engine.query(q, timeout=timeout)
            lat[cid].append(time.perf_counter() - t0)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients) if shares[i]]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return latency_summary([x for ls in lat for x in ls], wall)


def run_open_loop(engine, queries, offered_qps: float,
                  timeout: float) -> Dict[str, float]:
    """Fixed-schedule arrivals; latency from the *scheduled* arrival."""
    import threading

    interval = 1.0 / offered_qps
    lat: List[float] = []
    lock = threading.Lock()
    t0 = time.perf_counter()
    waiters = []

    def on_done(scheduled_at: float, fut) -> None:
        fut.result(timeout)  # re-raise per-query failures
        with lock:
            lat.append(time.perf_counter() - scheduled_at)

    for i, q in enumerate(queries):
        scheduled_at = t0 + i * interval
        delay = scheduled_at - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        fut = engine.submit(q)
        th = threading.Thread(target=on_done, args=(scheduled_at, fut))
        th.start()
        waiters.append(th)
    for th in waiters:
        th.join()
    wall = time.perf_counter() - t0
    out = latency_summary(lat, wall)
    out["offered_qps"] = offered_qps
    return out


def main(argv=None) -> int:
    from repro.experiments.spec import ENGINES, ExperimentSpec
    from repro.serve.whatif import WhatIfEngine, sample_queries

    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", nargs="+", default=["haswell"])
    ap.add_argument("--scale", type=float, default=0.003)
    ap.add_argument("--trace-seed", type=int, default=0)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--engine", choices=list(ENGINES), default="des")
    ap.add_argument("--clients", type=int, default=8,
                    help="concurrent client threads (closed-loop phases)")
    ap.add_argument("--queries", type=int, default=64,
                    help="size of the seeded query storm")
    ap.add_argument("--query-seed", type=int, default=0)
    ap.add_argument("--offered-qps", type=float, default=200.0,
                    help="open-loop arrival rate (phase 3, warm store)")
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="per-query result timeout (seconds)")
    ap.add_argument("--cache-dir", default="",
                    help="cell store; default: a fresh temp dir so the "
                         "cold phase is genuinely cold")
    ap.add_argument("--out", default="",
                    help="timing record path (default: "
                         "artifacts/serve-timing-{engine}.json)")
    args = ap.parse_args(argv)
    if args.engine == "jax":
        from repro.xla_cache import enable_compilation_cache
        enable_compilation_cache()

    if args.cache_dir:
        cache_dir = args.cache_dir
    else:
        import tempfile

        cache_dir = tempfile.mkdtemp(prefix="serve-load-")
    base = ExperimentSpec(
        workloads=tuple(args.workload), scale=args.scale,
        trace_seed=args.trace_seed, seeds=args.seeds, engine=args.engine)
    queries = sample_queries(args.query_seed, args.queries,
                             workloads=args.workload, seeds=args.seeds,
                             depths=(None, 4), orders=(None, "sjf"))
    unique = len({q.spec_for(base).cell_fingerprint(
        q.workload or args.workload[0], q.cell()).__str__()
        for q in queries})

    def fresh_engine() -> WhatIfEngine:
        return WhatIfEngine(base, cache_dir=cache_dir,
                            max_batch=args.max_batch,
                            max_wait_s=args.max_wait_ms / 1000.0,
                            backend_options={"devices": 1})

    bench_t0 = time.perf_counter()
    print(f"[serve_load] storm: {len(queries)} queries ({unique} unique "
          f"cells) x {args.clients} clients, engine={args.engine}, "
          f"max_batch={args.max_batch}, max_wait={args.max_wait_ms}ms")

    engine = fresh_engine()
    cold = run_closed_loop(engine, queries, args.clients, args.timeout)
    cold_stats = engine.stats()
    engine.close()
    print(f"[serve_load] cold closed-loop: p50 {cold['p50_ms']:.1f}ms "
          f"p99 {cold['p99_ms']:.1f}ms, {cold['qps']:.1f} qps "
          f"({cold_stats['batches']} batches, max width "
          f"{cold_stats['max_batch_width']}, {cold_stats['dedup']} deduped)")

    # fresh engine: warm numbers measure the *store* path, not the memo
    engine = fresh_engine()
    warm = run_closed_loop(engine, queries, args.clients, args.timeout)
    warm_stats = engine.stats()
    print(f"[serve_load] warm closed-loop: p50 {warm['p50_ms']:.2f}ms "
          f"p99 {warm['p99_ms']:.2f}ms, {warm['qps']:.0f} qps "
          f"({warm_stats['hits']}/{warm_stats['queries']} hits)")
    if warm_stats["misses"]:
        print(f"[serve_load] WARNING: {warm_stats['misses']} misses in "
              "the warm phase (failed cells from the cold phase?)")

    open_loop = run_open_loop(engine, queries, args.offered_qps,
                              args.timeout)
    engine.close()
    print(f"[serve_load] warm open-loop @ {args.offered_qps:.0f} qps "
          f"offered: p50 {open_loop['p50_ms']:.2f}ms "
          f"p99 {open_loop['p99_ms']:.2f}ms, achieved "
          f"{open_loop['qps']:.0f} qps")

    total_s = time.perf_counter() - bench_t0
    record = {
        "schema_version": 1,
        # grid identity: the serve-{engine} tag keeps check_perf from ever
        # cross-comparing this record with a sweep BENCH baseline
        "engine": f"serve-{args.engine}",
        "scale": args.scale, "seeds": args.seeds,
        "batch_workloads": list(args.workload),
        "total_s": total_s,
        "serve": {
            "clients": args.clients, "queries": len(queries),
            "unique_cells": unique,
            "max_batch": args.max_batch, "max_wait_ms": args.max_wait_ms,
            "cold_p50_ms": cold["p50_ms"], "cold_p99_ms": cold["p99_ms"],
            "cold_qps": cold["qps"], "cold_wall_s": cold["wall_s"],
            "cold_batches": cold_stats["batches"],
            "cold_max_batch_width": cold_stats["max_batch_width"],
            "cold_dedup": cold_stats["dedup"],
            "warm_p50_ms": warm["p50_ms"], "warm_p99_ms": warm["p99_ms"],
            "warm_qps": warm["qps"], "warm_wall_s": warm["wall_s"],
            "warm_hit_rate": (warm_stats["hits"] /
                              max(1, warm_stats["queries"])),
            "open_offered_qps": open_loop["offered_qps"],
            "open_achieved_qps": open_loop["qps"],
            "open_p50_ms": open_loop["p50_ms"],
            "open_p99_ms": open_loop["p99_ms"],
        },
    }
    out = pathlib.Path(args.out) if args.out else (
        ARTIFACTS / f"serve-timing-{args.engine}.json")
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(record, indent=1, default=float) + "\n")
    print(f"[serve_load] wall-clock record -> {out} "
          f"(total {total_s:.1f}s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
