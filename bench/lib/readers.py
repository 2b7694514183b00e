"""Shared arithmetic of the per-layer metric readers in ``bench/metrics``.

A reader gets the run's :class:`bench.lib.harness.Context` once the window
has closed: ``spans`` (the program's ``repro.obs`` spans that started in
the window, seconds from its start), ``result`` and ``device_trace`` (the
reduced profiler trace of the traced stretch, or None).  It returns a
number, or None when the run holds nothing for it to read.
"""
from __future__ import annotations

from typing import Optional

ENGINE_SPANS = ("sweep.compile", "sweep.execute")


def spans(ctx, *names):
    return [s for s in ctx.spans if s["name"] in names]


def count(ctx, name: str) -> Optional[float]:
    return float(len(spans(ctx, name)))


def outside_share(ctx, names=ENGINE_SPANS) -> Optional[float]:
    """Percent of the window's wall time that no ``names`` span covers."""
    width = ctx.result["window_s"]
    if width <= 0:
        return None
    covered, at = 0.0, 0.0
    for s in sorted(spans(ctx, *names), key=lambda s: s["ts"]):
        lo, hi = max(s["ts"], at), min(s["ts"] + s["dur"], width)
        if hi > lo:
            covered += hi - lo
            at = hi
    return 100.0 * (1.0 - covered / width)


def per_step_us(ctx, name: str = "sweep.execute") -> Optional[float]:
    """Microseconds of ``name`` span time per scan step it covered."""
    ss = spans(ctx, name)
    steps = sum(int(s["args"].get("scan_steps", 0)) for s in ss)
    if not steps:
        return None
    return 1e6 * sum(s["dur"] for s in ss) / steps


def idle_share(ctx) -> Optional[float]:
    """Percent of the traced stretch in which no operation ran on the
    device (averaged over the chips used)."""
    tr = ctx.device_trace
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
