"""The paper's rigid->malleable transform (section 2.2), per cell.

Each job gets an Amdahl parallel fraction calibrated so that its rigid
request runs at a reference efficiency drawn from ``e_ref_range``; its
preferred and largest allocations are where the efficiency falls to
``e_pref`` and ``e_min``, its smallest half the request.  A seed picks
which jobs become malleable; the selection nests across proportions.
"""
from __future__ import annotations

import numpy as np

RIGID_PFRAC = 0.9


def _nodes_at(p, e):
    n = (1.0 / e - p) / np.maximum(1.0 - p, 1e-12)
    return np.maximum(np.floor(n + 1e-9).astype(np.int64), 1)


def malleable_jobs(jobs: dict, cfg: dict, proportion: float,
                   seed: int) -> dict:
    """``jobs`` plus ``malleable, pfrac, mn, mx, pref`` for one cell."""
    tc = cfg["transform"]
    req = jobs["req"]
    n = len(req)
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    e_ref = rng.uniform(*tc["e_ref_range"], size=n)

    nf = req.astype(np.float64)
    p = np.where(nf > 1.0, (nf - 1.0 / e_ref) / np.maximum(nf - 1.0, 1e-12),
                 2.0 - 1.0 / e_ref)
    p = np.clip(p, 0.0, 1.0 - 1e-9)
    pref = _nodes_at(p, tc["e_pref"])
    mx = _nodes_at(p, tc["e_min"])
    mn = np.maximum(1, req // tc["min_divisor"])
    pref = np.minimum(pref, tc["pref_cap_factor"] * req)
    mx = np.minimum(mx, tc["max_cap_factor"] * req)
    mx = np.minimum(mx, cfg["nodes"])
    pref = np.minimum(pref, mx)
    pref = np.maximum(pref, mn)
    mx = np.maximum(mx, pref)
    mn = np.minimum(mn, pref)

    chosen = np.zeros(n, dtype=bool)
    chosen[perm[:int(round(proportion * n))]] = True
    return {**jobs, "malleable": chosen,
            "pfrac": np.where(chosen, p, RIGID_PFRAC),
            "mn": np.where(chosen, mn, req),
            "mx": np.where(chosen, mx, req),
            "pref": np.where(chosen, pref, req)}
