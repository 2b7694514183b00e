"""One benchmark run: set-up, a measured window, the check, one result line.

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration (the ``file`` of its ``configs`` entry), its traffic mix
(``bench/traffic/<traffic>.json``, whose ``kind`` names the module that runs it), its
correctness limits (``bench/limits/<cell>.json``) and a reader per
per-layer metric (``bench/metrics/<metric>.py``, a ``read(ctx)`` that
returns a number or None).  A new cell, mix or metric is new files and
entries; no file here changes.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import threading
import time
import traceback
from typing import Dict, List, Optional

from . import check, grid, xplane

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
KINDS = {"grid": grid}


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Context:
    """What a traffic kind and a metric reader see of one run."""

    cell: Dict
    cfg: Dict
    traffic: Dict
    seed: int
    seconds: float
    scratch: pathlib.Path
    state: Dict = dataclasses.field(default_factory=dict)
    # filled once the window has closed
    result: Optional[Dict] = None
    spans: List[Dict] = dataclasses.field(default_factory=list)
    device_trace: Optional[Dict] = None


def load_json(path: pathlib.Path) -> Dict:
    return json.loads(path.read_text())


def reader(name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: Dict, cell: str, section: str) -> List[Dict]:
    """The entries of ``section`` that this cell reports."""
    e2e = {m["name"]: m for m in bench["end_to_end"]}

    def reports(m):
        if "workloads" in m:
            return cell in m["workloads"]
        if section == "per_layer":
            return cell in e2e[m["moves"]].get("workloads", [cell])
        return True

    return [m for m in bench[section] if reports(m)]


def check_devices(chips: int, rehearsal: bool):
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if not rehearsal and (platform != "tpu" or len(devices) < chips):
        raise NoDevice(f"the cell needs {chips} TPU chip(s); JAX found "
                       f"{len(devices)} {platform} device(s)")
    return devices


def check_deployment(cfg: Dict) -> None:
    """The program's deployment must be the one the configuration states."""
    from repro.configs.workloads import WORKLOADS

    w = WORKLOADS[cfg["deployment"]]
    got = (w.cluster.nodes, w.tick, w.n_jobs, w.duration_days)
    want = (cfg["nodes"], cfg["tick_s"], cfg["n_jobs"], cfg["duration_days"])
    if got != want:
        raise SystemExit(f"deployment {cfg['deployment']!r} in the program "
                         f"is {got}, the configuration states {want}")


class Profiler:
    """Profiles ``length`` seconds starting ``offset`` seconds after
    :meth:`start`, on a thread of its own, into ``log_dir``."""

    def __init__(self, log_dir: pathlib.Path, offset: float, length: float):
        self.log_dir, self.offset, self.length = log_dir, offset, length
        self.stop = threading.Event()
        self.thread: Optional[threading.Thread] = None
        self.error: Optional[str] = None

    def _run(self) -> None:
        import jax

        if self.stop.wait(self.offset):
            return
        # no Python function tracing: it slows the host inside the traced
        # stretch and so inflates the device's idle share; JAX's own host
        # events and the benchmark's annotations still label the gaps
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        try:
            jax.profiler.start_trace(str(self.log_dir),
                                     profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench.profiled"):
                    self.stop.wait(self.length)
            finally:
                jax.profiler.stop_trace()
        except Exception as exc:  # noqa: BLE001 — reported, run goes on
            self.error = f"{type(exc).__name__}: {exc}"

    def start(self) -> None:
        self.thread = threading.Thread(target=self._run, name="bench-prof")
        self.thread.start()

    def finish(self) -> Optional[Dict]:
        self.stop.set()
        self.thread.join()
        if self.error:
            print(f"[bench] profiler: {self.error}", file=sys.stderr)
        path = xplane.find_xplane(str(self.log_dir))
        if path is None:
            return None
        t0 = time.monotonic()
        profile = xplane.load(path)
        bounds = xplane.annotation_bounds(profile, "bench.profiled")
        out = xplane.reduce(profile, *(bounds or (None, None)),
                            ignore=("bench.profiled",))
        print(f"[bench] trace: {os.path.getsize(path)} bytes reduced in "
              f"{time.monotonic() - t0:.1f} s", file=sys.stderr)
        return out


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def window_spans(tracer, t_open_ns: int, t_close_ns: int) -> List[Dict]:
    """Program spans that start inside the window, times in seconds from
    its start (the tracer's epoch is reset when the window opens)."""
    width = (t_close_ns - t_open_ns) / 1e9
    out = []
    for ev in tracer.events():
        ts, dur = ev["ts"] / 1e6, ev["dur"] / 1e6
        if 0.0 <= ts <= width:
            out.append({"name": ev["name"], "ts": ts, "dur": dur,
                        "args": ev.get("args", {})})
    return out


def reference_triples(ctx: Context, cells, answers) -> List:
    """``(cell, answer, reference)`` for every answer that came; the
    reference draws the same job log from the run's seed."""
    from bench.reference.metrics import reference_cells

    refs = reference_cells(ctx.cfg, ctx.seed, cells)
    return [(c, got, refs[c]) for c in cells for got in answers[c]
            if got is not None]


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tests only: run on any platform at --scale; the "
                         "numbers go under 'rehearsal', never 'metrics'")
    ap.add_argument("--scale", type=float, default=None,
                    help="with --rehearsal: trace scale in place of the "
                         "configuration's")
    args = ap.parse_args(argv)
    if args.scale is not None and not args.rehearsal:
        ap.error("--scale is for --rehearsal runs only")
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    return args


def run(argv, t_process: float) -> int:
    args = parse(argv)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload),
                None)
    if cell is None:
        print(f"[bench] no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(ROOT / cfg_entry["file"])
    if args.scale is not None:
        cfg = {**cfg, "scale": args.scale}
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    kind = KINDS[traffic["kind"]]
    limits = check.load_limits(BENCH / "limits" / f"{cell['name']}.json")
    e2e = cell_metrics(bench, cell["name"], "end_to_end")
    layer = cell_metrics(bench, cell["name"], "per_layer")

    try:
        devices = check_devices(cell["chips"], args.rehearsal)
    except NoDevice as exc:
        print(f"[bench] {exc}", file=sys.stderr)
        return 3
    import jax

    from repro import obs

    check_deployment(cfg)
    if not args.rehearsal:
        from repro.xla_cache import enable_compilation_cache

        # every program goes to the persistent cache, however fast it
        # compiled, so a second run of the cell compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        print(f"[bench] compile cache: {enable_compilation_cache()}",
              file=sys.stderr)
    tracer = obs.configure(enabled=bool(args.trace))

    scratch = pathlib.Path(tempfile.mkdtemp(prefix="bench-"))
    ctx = Context(cell=cell, cfg=cfg, traffic=traffic, seed=args.seed,
                  seconds=args.seconds, scratch=scratch)
    setup_s, memory, ref_s, cells, triples, missing = 0.0, 0, 0.0, [], [], 0
    prof = None
    try:
        kind.setup(ctx)
        tracer.reset()
        t_open_ns = time.monotonic_ns()
        setup_s = time.monotonic() - t_process
        if args.trace:
            prof = Profiler(scratch / "profile",
                            traffic["profile_offset_s"],
                            traffic["profile_s"])
            prof.start()
        result = kind.window(ctx)
        t_close_ns = time.monotonic_ns()
        ctx.result = result
        if prof is not None:
            ctx.device_trace, prof = prof.finish(), None
        ctx.spans = window_spans(tracer, t_open_ns, t_close_ns)
        memory = peak_bytes(devices[:cell["chips"]])
        cells, answers, missing = kind.to_check(ctx, result)
        t_ref = time.monotonic()
        triples = reference_triples(ctx, cells, answers)
        ref_s = time.monotonic() - t_ref
    except Exception as exc:  # noqa: BLE001 — a broken run is not correct
        traceback.print_exc()
        result = {"window_s": 0.0, "attempted": 1, "failed": 1,
                  "errors": [f"{type(exc).__name__}: {exc}"], "metrics": {}}
        missing = 1
    finally:
        if prof is not None:  # the window broke while profiling
            prof.stop.set()
            prof.thread.join()
        shutil.rmtree(scratch, ignore_errors=True)

    readings = check.readings(triples, missing)
    correct, compared = check.compare(readings, limits)
    metrics = {}
    if ctx.result is not None and args.trace:
        for m in layer:
            value = reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    elif ctx.result is not None:
        values = {"setup_s": setup_s, **result["metrics"]}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in e2e}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory}
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"]}
    if args.rehearsal:
        line.update(metrics={}, device=device, rehearsal=metrics)
    else:
        line.update(metrics=metrics, device=device)
    if args.trace and ctx.device_trace is not None:
        tr = ctx.device_trace
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["compared"] = compared

    notes = {"setup_s": setup_s, "window_s": result["window_s"],
             "reference_s": ref_s,
             "cells_checked": len(cells), "answers_checked": len(triples),
             "errors": result["errors"], **result.get("notes", {})}
    print(f"[bench] {json.dumps(notes, default=str)}", file=sys.stderr)
    for text in check.report_lines(compared, readings):
        print(text, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
