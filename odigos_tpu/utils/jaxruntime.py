"""Process-level JAX runtime facts and set-up.

Two things every process that runs this package has to get right on a
machine whose accelerator belongs to ONE process at a time:

* ``backend_initialized()`` — whether THIS process has initialised a
  JAX backend (and so owns the chip). Telemetry that wants device facts
  asks this first: with ``model: remote`` the chip belongs to the
  scoring sidecar, and a collector that called ``jax.devices()`` to
  look would make itself a second owner. ``"jax" in sys.modules`` is
  not the same question — it says jax was imported, not that a backend
  exists.
* ``configure_compile_cache()`` — where the persistent XLA compile
  cache lives. Called once by every process entry point that will
  compile (``chip_smoke.py``, ``python -m odigos_tpu.pipeline``,
  ``python -m odigos_tpu.serving.sidecar``, ``benchmark/run.py``).
  ``JAX_COMPILATION_CACHE_DIR`` wins when set (jax reads it into
  ``jax_compilation_cache_dir`` itself, so nothing is set here);
  otherwise the cache is one fixed directory inside the checkout. The
  path is part of the cache key, so it is never derived from a pid, a
  clock or ``tempfile``.

Importing this module imports nothing from jax.
"""

from __future__ import annotations

import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the fallback cache directory (listed in .gitignore and .chiprunignore)
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def backend_initialized() -> bool:
    """True once this process has initialised a JAX backend. Never
    imports jax and never initialises anything."""
    bridge = sys.modules.get("jax._src.xla_bridge")
    return bridge is not None and bool(bridge.backends_are_initialized())


def configure_compile_cache() -> str:
    """Place the persistent compile cache; returns the directory in use.

    Also lowers ``jax_persistent_cache_min_compile_time_secs`` to 0: at
    its default of 1 s the fused route's small sub-jits and the floor
    ladder rung compile under the threshold and are never stored, so a
    restarted process would pay them again every time. Like the
    directory, a floor the operator set from outside
    (``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``, which jax reads
    itself) is left alone."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    if not os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
