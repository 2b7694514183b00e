"""The chunk window's two forms move the same bits.

``repro.sweep.compact`` packs each lane's selected jobs into a ``W``-slot
window and writes the window back, either by per-element gathers and
scatters or by a network of log-step shifts.  These tests hold the network
to the gathers bit for bit (and to a numpy packing), over job counts at
and around a power of two, windows below, at and above the selected count,
and f32 fields that hold NaN payloads, infinities and negative zero; then
a whole engine run with the network forced equals the gather run.
"""
import functools

import jax
import numpy as np
import pytest

from repro.core import STRATEGIES, Workload
from repro.sweep import compact
from repro.sweep.batch import EngineConfig, build_lanes, simulate_lanes

# f32 bit patterns a select must carry unchanged: quiet and signalling NaNs
# with payloads, both infinities, negative zero, the smallest subnormal
SPECIAL = np.array([0x7FC00001, 0xFFC00000, 0x7F800001, 0x7F800000,
                    0xFF800000, 0x80000000, 0x00000001], np.uint32)


def _runs(rng, n, mean):
    """A selection of alternating on/off runs of geometric lengths."""
    out = np.zeros(n, bool)
    i, on = 0, bool(rng.integers(2))
    while i < n:
        step = int(rng.geometric(1.0 / mean))
        out[i:i + step] = on
        i, on = i + step, not on
    return out


def _selections(rng, n):
    """Lanes: none, all, long runs, short runs, and one job alone at each
    power of two and at the end (a displacement of every bit)."""
    lanes = [np.zeros(n, bool), np.ones(n, bool), _runs(rng, n, 40.0),
             _runs(rng, n, 3.0)]
    for i in sorted({min(1 << b, n - 1) for b in range(n.bit_length())}
                    | {n - 1}):
        one = np.zeros(n, bool)
        one[i] = True
        lanes.append(one)
    return np.stack(lanes)


def _fields(rng, B, n):
    f32 = rng.standard_normal((B, n)).astype(np.float32)
    spots = rng.random((B, n)) < 0.3
    f32[spots] = SPECIAL[rng.integers(len(SPECIAL), size=spots.sum())].view(
        np.float32)
    i32 = rng.integers(-2**31, 2**31, (B, n)).astype(np.int32)
    return [f32, i32, rng.random((B, n)) < 0.5]


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint32) if a.dtype == np.float32 else a


FILLS = (np.float32(np.nan), 7, False)


def _windows(n):
    rng = np.random.default_rng(n)
    sel = _selections(rng, n)
    c = int(sel[2].sum())  # the long-run lane's selected count
    return [(n, W) for W in sorted({max(c - 1, 1), max(c, 1),
                                    min(c + 7, n)})]


@pytest.mark.parametrize(
    "n,W", [case for n in (1, 127, 128, 129, 2550, 28259)
            for case in _windows(n)])
def test_shift_network_equals_gathers_bitwise(n, W):
    rng = np.random.default_rng(n)
    sel = _selections(rng, n)
    B = sel.shape[0]
    arrays = _fields(rng, B, n)
    pos = np.cumsum(sel, axis=-1, dtype=np.int32) - 1
    live = sel & (pos < W)

    def move_in(form):
        f = compact.shift_in if form == "shift" else compact.gather_in
        return jax.jit(lambda a, l, p: f(a, l, p, FILLS, W))(
            arrays, live, pos)

    (g_win, idx), (s_win, disp) = move_in("gather"), move_in("shift")
    for g, s, a, fill in zip(g_win, s_win, arrays, FILLS):
        np.testing.assert_array_equal(_bits(s), _bits(g))
        for b in range(B):  # numpy packing of the same lane
            want = np.full(W, fill, a.dtype)
            got = a[b, live[b]]
            want[:len(got)] = got
            np.testing.assert_array_equal(_bits(s[b]), _bits(want))
    filled = np.arange(W) < live.sum(-1, keepdims=True)
    np.testing.assert_array_equal(
        np.where(filled, np.asarray(disp) + np.arange(W), n), idx)

    fulls, bufs = _fields(rng, B, n), _fields(rng, B, W)
    g_full = jax.jit(compact.gather_out)(fulls, bufs, live, idx)
    s_full = jax.jit(compact.shift_out)(fulls, bufs, live, disp)
    for g, s in zip(g_full, s_full):
        np.testing.assert_array_equal(_bits(s), _bits(g))


@pytest.mark.parametrize("form", ["gather", "shift", "platform"])
def test_a_window_moved_in_and_straight_back_changes_nothing(form):
    """Writing the window back unchanged restores every job's bits; the
    platform's own pair (``window_in``/``window_out``) included."""
    f_in, f_out = {"gather": (compact.gather_in, compact.gather_out),
                   "shift": (compact.shift_in, compact.shift_out),
                   "platform": (compact.window_in, compact.window_out)}[form]
    n, W = 2550, 600
    rng = np.random.default_rng(5)
    sel = _selections(rng, n)
    arrays = _fields(rng, sel.shape[0], n)
    pos = np.cumsum(sel, axis=-1, dtype=np.int32) - 1
    live = sel & (pos < W)

    @jax.jit
    def there_and_back(a, l, p):
        win, back = f_in(a, l, p, FILLS, W)
        return f_out(a, win, l, back)

    for got, want in zip(there_and_back(arrays, live, pos), arrays):
        np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("structure,lanes", [
    ("greedy", [("easy", 0.0), ("min", 0.6), ("keeppref", 1.0),
                ("rigid_sjf", 0.0)]),
    ("balanced", [("avg", 0.5), ("avg", 1.0)]),
    ("pooled", [("pref_common_pool", 0.5), ("pref_common_pool", 1.0)]),
    ("stealing", [("steal_agreement", 0.5), ("steal_agreement", 1.0)]),
], ids=["greedy", "balanced", "pooled", "stealing"])
def test_engine_bitwise_with_the_shift_network(structure, lanes,
                                               monkeypatch):
    """A batch whose every chunk program moves its window by the network,
    as the programs lowered for TPU do, returns the same bits as the
    gather programs the CPU runs, through window escalations."""
    from repro.sweep import batch as sb

    rng = np.random.default_rng(3)
    n = 300
    w = Workload.rigid(submit=np.sort(rng.uniform(0, 400.0, n)),
                       runtime=rng.uniform(20, 120, n),
                       nodes_req=rng.choice([1, 2, 4, 8], n))
    batch, _ = build_lanes(w, 10, [(STRATEGIES[s], p, 0) for s, p in lanes])
    cfg = EngineConfig(structure=structure, window=16, chunk=32,
                       aot_warmup=False)

    def fresh_programs():
        monkeypatch.setattr(sb, "_chunk_fn",
                            functools.cache(sb._chunk_fn.__wrapped__))
        for name in ("_COMPILED_KEYS", "_WARM_EXECUTABLES", "_WARM_FUTURES"):
            monkeypatch.setattr(sb, name, type(getattr(sb, name))())

    fresh_programs()
    ref = simulate_lanes(batch, cfg)
    monkeypatch.setattr(sb, "window_in", compact.shift_in)
    monkeypatch.setattr(sb, "window_out", compact.shift_out)
    fresh_programs()
    got = simulate_lanes(batch, cfg)

    assert ref["finished"] and got["finished"]
    assert ref["escalations"] >= 1 and got["escalations"] >= 1
    for key, val in ref.items():
        if isinstance(val, np.ndarray):
            assert got[key].dtype == val.dtype, key
            np.testing.assert_array_equal(_bits(got[key]), _bits(val),
                                          err_msg=key)
