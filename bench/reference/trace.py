"""Job traces of a deployment, generated from a seed.

The published job logs are not redistributable, so a deployment's jobs
are a synthetic twin drawn from the distributions its configuration file
states (node counts, a lognormal runtime mixture, diurnal arrivals with
an optional burst) and calibrated so that the offered load matches the
paper's rigid utilization.  The draws follow the same numpy sequence as
the program's generator, so one seed gives both the same jobs; nothing
is taken from the program.
"""
from __future__ import annotations

import numpy as np

DAY = 86400.0


def _arrivals(tr: dict, rng, n: int, duration: float,
              full_duration: float) -> np.ndarray:
    grid = np.linspace(0.0, duration, 2048)
    lam = 1.0 + tr["diurnal_amp"] * np.sin(2 * np.pi * grid / DAY - np.pi / 2)
    if tr["burst"] is not None:
        rel = duration / full_duration
        center, width, weight = tr["burst"]
        center, width = center * rel, width * rel
        if center < duration:
            lam = lam + weight * len(grid) * np.exp(
                -0.5 * ((grid - center) / width) ** 2) / np.sqrt(2 * np.pi)
    cdf = np.cumsum(lam)
    cdf = cdf / cdf[-1]
    u = np.sort(rng.uniform(0, 1, size=n))
    t = np.interp(u, cdf, grid)
    t = np.sort(t + rng.uniform(0, duration / 2048, size=n))
    return np.clip(t, 0.0, duration)


def _runtimes(tr: dict, rng, n: int) -> np.ndarray:
    mix = tr["runtime_mix"]
    ws = np.array([c[0] for c in mix])
    ws = ws / ws.sum()
    comp = rng.choice(len(ws), size=n, p=ws)
    med = np.array([c[1] for c in mix])[comp]
    sig = np.array([c[2] for c in mix])[comp]
    out = med * np.exp(sig * rng.standard_normal(n))
    return np.clip(out, 30.0, 7 * DAY)


def _calibrate(runtime, nodes, rate_per_s: float, capacity: int,
               target_util: float) -> np.ndarray:
    """Scale runtimes by ``nodes**gamma`` (then one global factor) so that
    the offered node-seconds per second equal ``target_util * capacity``."""
    target_ns = target_util * capacity / rate_per_s

    def offered(gamma):
        return float(np.mean(runtime * nodes ** (1.0 + gamma)))

    lo, hi = 0.0, 1.5
    if offered(hi) < target_ns:
        gamma = hi
    elif offered(lo) > target_ns:
        gamma = lo
    else:
        for _ in range(50):
            mid = 0.5 * (lo + hi)
            if offered(mid) < target_ns:
                lo = mid
            else:
                hi = mid
        gamma = 0.5 * (lo + hi)
    rt = runtime * nodes ** gamma
    rt *= target_ns / float(np.mean(rt * nodes))
    return np.clip(rt, 30.0, 14 * DAY)


def generate(cfg: dict, seed: int) -> dict:
    """Rigid jobs of the deployment ``cfg`` for trace seed ``seed``:
    ``{"submit", "runtime", "walltime", "req"}`` in submission order."""
    tr = cfg["trace"]
    rng = np.random.default_rng(seed + 0xC0FFEE)
    full_duration = cfg["duration_days"] * DAY
    n = max(int(round(cfg["n_jobs"] * cfg["scale"])), 10)
    duration = full_duration * cfg["scale"]
    submit = _arrivals(tr, rng, n, duration, full_duration)
    probs = np.asarray(tr["node_probs"], dtype=np.float64)
    probs = probs / probs.sum()
    req = rng.choice(np.asarray(tr["node_values"]), size=n, p=probs)
    runtime = _runtimes(tr, rng, n)
    runtime = _calibrate(runtime, req,
                         rate_per_s=cfg["n_jobs"] / full_duration,
                         capacity=cfg["nodes"],
                         target_util=tr["rigid_util"] * tr["load_factor"])
    return {"submit": submit, "runtime": runtime,
            "walltime": tr["walltime_over_runtime"] * runtime,
            "req": np.asarray(req, dtype=np.int64)}
