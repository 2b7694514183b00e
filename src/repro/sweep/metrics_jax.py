"""On-device batched port of :func:`repro.core.metrics.run_metrics`.

Computes the paper's per-run metrics (wait / makespan / turnaround /
utilization / ops-per-job inside the measurement window) for every lane of
a batched sweep at once, entirely on device — only the final (B,)-shaped
metric table is transferred to host.

Matches the numpy reference key-for-key so :func:`aggregate_seeds` works on
the per-lane dicts unchanged.  Utilization integrates the event-step busy
timeline (``busy[k]`` holds on ``[t[k], t[k+1])``), which is exact for the
event-stepped engine's piecewise-constant busy level.

Windows and capacities are **per-lane data** so one call covers a
multi-workload batch (:func:`repro.sweep.batch.concat_lanes`): each lane
carries its own measurement window ``[t0, t1]`` and cluster size, and
padding jobs (``submit = +inf``) fall outside every window.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
@jax.named_scope("metrics.cells")
def _batched_metrics_device(start, end, expand_ops, shrink_ops, submit,
                            malleable, trace_t, trace_busy, t0, t1, capacity):
    B = start.shape[0]
    done = jnp.isfinite(end)
    in_win = (submit >= t0[:, None]) & (submit <= t1[:, None])
    sel = in_win & done
    n_sel = jnp.sum(sel, axis=-1)
    some = jnp.maximum(n_sel, 1)

    wait = start - submit
    makespan = end - start
    turnaround = end - submit

    def mean(x):
        m = jnp.sum(jnp.where(sel, x, 0.0), axis=-1) / some
        return jnp.where(n_sel > 0, m, jnp.nan)

    def p50(x):
        xs = jnp.sort(jnp.where(sel, x, jnp.inf), axis=-1)
        i1 = jnp.maximum((n_sel - 1) // 2, 0)
        i2 = n_sel // 2
        v1 = jnp.take_along_axis(xs, i1[:, None], axis=-1)[:, 0]
        v2 = jnp.take_along_axis(xs, jnp.minimum(i2, xs.shape[-1] - 1)[:, None],
                                 axis=-1)[:, 0]
        return jnp.where(n_sel > 0, 0.5 * (v1 + v2), jnp.nan)

    # busy integral over the window from the event timeline
    t_next = jnp.concatenate(
        [trace_t[:, 1:], jnp.full((B, 1), jnp.inf, trace_t.dtype)], axis=-1)
    seg = jnp.clip(jnp.minimum(t_next, t1[:, None])
                   - jnp.maximum(trace_t, t0[:, None]), 0.0, None)
    integral = jnp.sum(trace_busy.astype(jnp.float32) * seg, axis=-1)
    util = integral / (capacity.astype(jnp.float32)
                       * jnp.maximum(t1 - t0, 1e-9))

    msel = sel & malleable
    n_mall = jnp.sum(msel, axis=-1)
    mall_some = jnp.maximum(n_mall, 1)
    expand = jnp.sum(jnp.where(msel, expand_ops, 0), axis=-1) / mall_some
    shrink = jnp.sum(jnp.where(msel, shrink_ops, 0), axis=-1) / mall_some

    return {
        "n_jobs": n_sel.astype(jnp.float32),
        "n_malleable": n_mall.astype(jnp.float32),
        "wait_mean": mean(wait),
        "wait_p50": p50(wait),
        "makespan_mean": mean(makespan),
        "turnaround_mean": mean(turnaround),
        "turnaround_p50": p50(turnaround),
        "utilization": util,
        "expand_per_job": expand.astype(jnp.float32),
        "shrink_per_job": shrink.astype(jnp.float32),
        "unfinished": jnp.sum(in_win & ~done, axis=-1).astype(jnp.float32),
    }


def batched_metrics(result: Dict[str, np.ndarray], submit, malleable,
                    window, capacity) -> List[Dict[str, float]]:
    """Per-lane metric dicts for a :func:`simulate_lanes` result.

    ``submit`` ((n,) or (B, n)) and ``malleable`` (B, n) must be in the same
    (submit-sorted) job order as the engine result.  ``window`` is either a
    :class:`repro.core.metrics.Window` shared by every lane or a
    ``(t0, t1)`` pair of per-lane arrays; ``capacity`` is a shared int or a
    per-lane array.  Returns one plain-float dict per lane, key-compatible
    with :func:`repro.core.metrics.run_metrics`.
    """
    malleable = jnp.asarray(malleable)
    B = malleable.shape[0]
    submit = jnp.asarray(submit, jnp.float32)
    if submit.ndim == 1:
        submit = jnp.broadcast_to(submit, (B, submit.shape[0]))
    if hasattr(window, "t0"):
        t0, t1 = window.t0, window.t1
    else:
        t0, t1 = window
    t0 = jnp.broadcast_to(jnp.asarray(t0, jnp.float32), (B,))
    t1 = jnp.broadcast_to(jnp.asarray(t1, jnp.float32), (B,))
    capacity = jnp.broadcast_to(jnp.asarray(capacity, jnp.float32), (B,))
    dev = _batched_metrics_device(
        jnp.asarray(result["start_t"]), jnp.asarray(result["end_t"]),
        jnp.asarray(result["expand_ops"]), jnp.asarray(result["shrink_ops"]),
        submit, malleable,
        jnp.asarray(result["trace_t"]), jnp.asarray(result["trace_busy"]),
        t0, t1, capacity)
    host = {k: np.asarray(v) for k, v in dev.items()}
    keys = list(host)
    return [{k: float(host[k][b]) for k in keys} for b in range(B)]
