"""Reduction of a profiler trace to device busy time and idle gaps."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import xplane  # noqa: E402

# one device with a while op holding a fusion, and a later fusion; one
# host thread holding
# the stretch's annotation and one host event inside a device gap
SMALL = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 500000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 8000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "while.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_run_chunk" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 7 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 4500000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.profiled" } }
  event_metadata { key: 2 value { id: 2 name: "host.wait" } }
}
'''


def small():
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(SMALL)


def test_busy_union_idle_gaps_and_top_ops():
    prof = small()
    lo, hi = xplane.annotation_bounds(prof, "bench.profiled")
    assert (lo, hi) == (0, 9000)
    out = xplane.reduce(prof, lo, hi, ignore=("bench.profiled",))
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(9e-6)
    # ops cover [1000, 4000] and [6000, 7000]: 4 us busy of 9; ops by
    # self time: the while op less the fusion inside it
    assert out["busy_s"] == pytest.approx(4e-6)
    assert out["device_ops"] == [["while.2", pytest.approx(2.5e-6)],
                                 ["fusion.1", pytest.approx(1.5e-6)]]
    # gaps [4000, 6000], [0, 1000], [7000, 9000]; the first is covered
    # by host.wait at its middle, the others by no host event
    names = dict((round(s * 1e9), n) for n, s in out["idle_gaps"])
    assert sorted(round(s * 1e9) for _, s in out["idle_gaps"]) == [
        1000, 2000, 2000]
    assert "host.wait" in [n for n, _ in out["idle_gaps"]]
    assert names[1000] == "no host event"


def test_clipping_to_a_stretch():
    out = xplane.reduce(small(), 2000, 6500)
    assert out["window_s"] == pytest.approx(4.5e-6)
    assert out["busy_s"] == pytest.approx(2.5e-6)


def test_self_times_of_nested_ops():
    ops = [("while.2", 0, 100), ("cond.1", 10, 50), ("%fusion.3 = f32[8]", 20,
                                                    30),
           ("%fusion.3 = f32[8]", 60, 70), ("copy", 120, 130)]
    assert xplane.self_times(ops, 0, 200) == {
        "while.2": 50, "cond.1": 30, "fusion.3": 20, "copy": 10}
    assert xplane.self_times(ops, 25, 65) == {
        "while.2": 10, "cond.1": 20, "fusion.3": 10}


def test_union_and_gaps():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xplane.gaps([(0, 3), (5, 8)], 0, 10) == [(3, 5), (8, 10)]


def test_trace_without_device_ops_reads_nothing():
    from jax.profiler import ProfileData

    host_only = SMALL[SMALL.index("planes {\n  id: 2"):]
    assert xplane.reduce(ProfileData.from_text_proto(host_only)) is None


RECORDED = ROOT / "tests" / "bench" / "data" / "tpu_small.xplane.pb"


def test_recorded_tpu_trace():
    """A trace recorded on a TPU v5e: three steps of a jitted matmul, each
    under a ``host.step`` annotation, with sleeps between them."""
    prof = xplane.load(str(RECORDED))
    lo, hi = xplane.annotation_bounds(prof, "bench.profiled")
    out = xplane.reduce(prof, lo, hi, ignore=("bench.profiled",))
    assert out["devices"] == 1
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["device_ops"] and out["idle_gaps"]
    assert all(s > 0 for _, s in out["idle_gaps"])
