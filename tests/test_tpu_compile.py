"""The Pallas kernels and an engine chunk program compile for a TPU v5e.

Interpret-mode parity (``tests/test_passes.py``, ``tests/test_kernels.py``)
shows that the kernels compute the reference values; it cannot show that
Mosaic accepts them.  These tests compile for a *described* v5e chip (no
chip attached): the fused ``schedule_tick`` kernel at window-ladder widths,
the waterfill kernel, one engine chunk program with the fused backend, and
one with the reference pass, whose prefix sums must take the MXU form.

The topology is described inside a module fixture — never at import — so
every xdist worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""
import re

import pytest

W_LADDER = (128, 512, 2048)


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip's executables cannot be read back from the
        # persistent cache without the chip: keep them out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _shapes(sharding, B, W):
    import jax
    import jax.numpy as jnp

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    i32, f32 = jnp.int32, jnp.float32
    row = [s((B, W), jnp.bool_)] + [s((B, W), i32)] * 6 + \
        [s((B, W), f32)] * 2 + [s((B, W), i32)] * 2 + \
        [s((B, W), f32)] * 2 + [s((B, W), jnp.bool_)]
    return row + [s((B,), i32), s((B,), f32), s((B,), i32)]


@pytest.mark.parametrize("W", W_LADDER)
@pytest.mark.parametrize("depth_bounded", [False, True])
def test_fused_schedule_tick_compiles(one_chip, W, depth_bounded):
    import jax

    from repro.core.passes import PassParams
    from repro.kernels.schedule_tick import fused_schedule_tick

    def tick(mall, mn, mx, want, fl, sfl, pref, pfrac, ww, state, alloc,
             rem, start, act, cap, t_now, depth):
        p = PassParams(mall, mn, mx, want, fl, sfl, pref, pfrac, ww)
        return fused_schedule_tick(
            p, state, alloc, rem, start, act, cap, t_now, fill_rounds=2,
            prio_lo=-1024, prio_hi=1792, shadow_iters=26,
            backfill_depth=depth if depth_bounded else None)

    compiled = jax.jit(tick).lower(*_shapes(one_chip, 8, W)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [2048, 10000])
def test_waterfill_compiles(one_chip, n):
    import jax
    import jax.numpy as jnp

    from repro.kernels.waterfill import waterfill

    compiled = jax.jit(waterfill).lower(
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_engine_chunk_compiles_with_fused_kernel(one_chip):
    from repro.sweep.batch import EngineConfig, _chunk_fn, chunk_arg_shapes

    n, B, W = 2048, 8, 512
    fn = _chunk_fn(EngineConfig(expand_backend="fused"), n, B, W,
                   -1024, 1792, 1792, False, with_sjf=False,
                   depth_bounded=True)
    compiled = fn.lower(*chunk_arg_shapes(n, B, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_engine_chunk_prefix_sums_run_on_the_mxu(one_chip):
    """The reference pass's chunk program, lowered for TPU, takes the
    blocked prefix sums: matmuls, and no window-wide ``reduce-window``."""
    from repro.sweep.batch import EngineConfig, _chunk_fn, chunk_arg_shapes

    n, B, W = 2048, 8, 512
    fn = _chunk_fn(EngineConfig(), n, B, W, -1024, 1792, 1792, False,
                   with_sjf=False, depth_bounded=True)
    text = fn.lower(*chunk_arg_shapes(n, B, one_chip)).compile().as_text()
    convs = [ln for ln in text.splitlines() if " convolution(" in ln]
    assert convs
    assert "reduce-window" not in text
    # every matmul is the helper's, named by its scope
    for ln in convs:
        assert "/pass.prefix/" in re.search(r'op_name="([^"]*)"', ln)[1]


def _window_moves(sharding, W):
    """Lines of the haswell-size chunk program, compiled for the device of
    ``sharding``, that gather or scatter under ``chunk.compact`` or
    ``chunk.scatter``."""
    from repro.sweep.batch import EngineConfig, _chunk_fn, chunk_arg_shapes

    n, B = 28259, 16
    fn = _chunk_fn(EngineConfig(), n, B, W, -2388, 2388, 2388, False,
                   with_sjf=False, depth_bounded=False)
    text = fn.lower(*chunk_arg_shapes(n, B, sharding)).compile().as_text()
    return [ln for ln in text.splitlines()
            if re.search(r"= \S+ (gather|scatter)\(", ln)
            and re.search(r'op_name="[^"]*chunk\.(compact|scatter)', ln)]


@pytest.mark.parametrize("W", [128, 16384])
def test_engine_chunk_moves_its_window_without_gathers(one_chip, W):
    """At the bottom and the top window rung of the haswell deployment,
    the chunk program lowered for TPU moves its window by shifts and
    selects: no gather or scatter op carries ``chunk.compact`` or
    ``chunk.scatter``."""
    assert not _window_moves(one_chip, W)


def test_the_gather_form_shows_its_gathers():
    """The check above sees the gathers where the program has them: the
    same program compiled for the CPU, which keeps the gather form."""
    import jax
    from jax.sharding import SingleDeviceSharding

    assert _window_moves(SingleDeviceSharding(jax.devices("cpu")[0]), 16384)
