"""Where JAX's persistent compilation cache lives, for every jax entry point.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and stands:
nothing here sets another directory.  Otherwise the cache goes to the
fixed path ``<repo>/artifacts/xla_cache`` — fixed because a later run
only finds its compilations again under the same path, so it is never
derived from a temp dir, a pid or the time.

Entry points (``python -m repro.sweep`` / ``repro.experiments`` /
``repro.serve``, ``benchmarks/run.py``, ``benchmarks/serve_load.py``,
``chip_smoke.py``) call :func:`enable_compilation_cache` once, before
their first compilation; library code never does.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / "artifacts" / \
    "xla_cache"


def placed_from_outside() -> bool:
    """True when ``JAX_COMPILATION_CACHE_DIR`` names the cache."""
    return bool(os.environ.get(ENV_VAR))


def cache_dir() -> pathlib.Path:
    """The directory the persistent compilation cache uses."""
    return (pathlib.Path(os.environ[ENV_VAR]) if placed_from_outside()
            else DEFAULT_DIR)


def enable_compilation_cache() -> pathlib.Path:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = cache_dir()
    if not placed_from_outside():
        path.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
