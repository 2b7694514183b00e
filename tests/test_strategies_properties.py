"""Property tests over the data-parameterised strategy registry.

Draws arbitrary valid :class:`StrategySpec` knob combinations — not just
the eight registered strategies — and asserts the three engines (numpy
DES, dense-tick ``sim_jax``, event-stepped batched) stay in parity.
This is the registry's core guarantee: *any* spec expressible in the
data layer is faithfully executed by every engine, so registering a new
strategy never requires engine changes.

Parity has two documented layers (see docs/strategies.md):

* the two vectorized engines agree *per job* within a few ticks — they
  share the pass code but use entirely different time stepping, so this
  is a strong cross-implementation check;
* every engine agrees with the reference DES within the aggregate
  ``CROSSCHECK_TOLERANCES``.  Per-job tightness vs the DES is *not* a
  property of arbitrary specs: alloc-dependent priorities (``avg``) can
  flip reallocation order on a one-tick quantization difference and
  cascade individual start times, while aggregates stay put.

Skipped (not failed) when hypothesis is unavailable: the CI image has
it, minimal local envs may not.
"""
import numpy as np
import pytest

pytest.importorskip("jax.numpy")
pytest.importorskip("hypothesis")

from hypothesis import example, given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.core import (Cluster, Workload, simulate,
                        transform_rigid_to_malleable)
from repro.core.strategies import StrategySpec
from repro.core.sim_jax import simulate_jax
from repro.sweep.batch import EngineConfig, build_lanes, simulate_lanes

TINY = Cluster("t", nodes=10, tick=1.0)

# Low-contention workload: enough queueing for the passes to fire, small
# enough that one example stays ~1 s.  Fixed across examples so only the
# strategy knobs vary (hypothesis shrinks in knob space, not trace space).
_RNG = np.random.default_rng(21)
_N = 12
_W = Workload.rigid(submit=np.sort(_RNG.uniform(0, 200, _N)),
                    runtime=_RNG.uniform(20, 80, _N),
                    nodes_req=_RNG.choice([1, 2, 4], _N))

spec_st = st.builds(
    StrategySpec,
    name=st.just("prop"),
    malleable=st.just(True),
    start_want=st.sampled_from(("req", "min", "pref")),
    start_floor=st.sampled_from(("req", "min", "pref")),
    shrink_floor=st.sampled_from(("min", "pref")),
    structure=st.sampled_from(("greedy", "balanced", "pooled", "stealing")),
    priority=st.sampled_from(("min", "pref", "avg")),
    queue_order=st.sampled_from(("fcfs", "sjf")),
    pool_share=st.floats(min_value=0.25, max_value=1.0),
    steal_margin=st.integers(min_value=0, max_value=3),
)


# The aggregate contract (mirrors experiments.crosscheck tolerances).
_AGG_TOL = {"turnaround": (0.08, 45.0), "wait": (0.20, 90.0),
            "makespan": (0.08, 45.0)}


def _agg(start, end, submit):
    return {"turnaround": float(np.mean(end - submit)),
            "wait": float(np.mean(start - submit)),
            "makespan": float(np.max(end))}


@settings(max_examples=15, deadline=None, derandomize=True)
@given(spec=spec_st, prop=st.sampled_from((0.0, 0.6, 1.0)))
# the batched engine starts job 6 at 136 s and job 7 at 135 s where the
# DES and sim_jax start them at 135 s and 147 s (SJF, start at req)
@example(spec=StrategySpec(name="prop", malleable=True, start_want="req",
                           start_floor="req", shrink_floor="min",
                           structure="greedy", priority="min",
                           queue_order="sjf", pool_share=1.0,
                           steal_margin=0),
         prop=0.6)
def test_any_registry_spec_keeps_engines_in_parity(spec, prop):
    wm = (_W if prop == 0.0 else
          transform_rigid_to_malleable(_W, prop, seed=1, cluster_nodes=10))
    ref = simulate(wm, TINY, spec)
    st_j, _ = simulate_jax(wm, TINY.nodes, TINY.tick, 600, spec)
    batch, order = build_lanes(_W, TINY.nodes, [(spec, prop, 1)])
    res = simulate_lanes(batch, EngineConfig(structure=spec.structure,
                                             window=16, chunk=64))
    inv = np.argsort(order)
    assert res["finished"]
    js, je = np.asarray(st_j.start_t), np.asarray(st_j.end_t)
    bs, be = res["start_t"][0][inv], res["end_t"][0][inv]
    # vectorized engines agree per job (measured worst: 1.0 / 4.0)
    np.testing.assert_allclose(bs, js, atol=2.5)
    np.testing.assert_allclose(be, je, atol=6.0)
    # every engine agrees with the DES on the aggregate contract
    # (measured worst uses < 10% of the budget)
    m_ref = _agg(ref.start, ref.end, _W.submit)
    for s, e in ((js, je), (bs, be)):
        m = _agg(s, e, _W.submit)
        for k, (rel, atol) in _AGG_TOL.items():
            assert abs(m[k] - m_ref[k]) <= rel * abs(m_ref[k]) + atol, (
                k, m[k], m_ref[k], spec)


@settings(max_examples=10, deadline=None, derandomize=True)
@given(spec=spec_st)
def test_spec_validation_is_total(spec):
    """Any spec hypothesis can build is fully valid: the registry's
    validation accepts it and its derived properties resolve."""
    assert spec.structure in ("greedy", "balanced", "pooled", "stealing")
    assert callable(spec.priority_fn)
    mn, pref, req = np.array([1, 2]), np.array([4, 8]), np.array([2, 4])
    for which, want in (("min", mn), ("pref", pref), ("req", req)):
        assert spec.pick(which, mn, pref, req) is want
