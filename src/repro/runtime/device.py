"""The device decisions of the whole repo, in one module.

* :func:`pallas_interpret` — how the Pallas kernels run on a backend:
  compiled by Mosaic on ``tpu``, through the Pallas interpreter on
  ``cpu`` (the test and rehearsal path).  Any other backend raises: the
  kernels use TPU memory spaces, so there is no silent fallback that
  would turn a device run into an interpreter run.
* :func:`enable_compile_cache` — JAX's persistent compilation cache for
  the entry points (``chip_smoke.py``, ``viem``, ``repro.launch.serve``,
  ``benchmarks/run.py``).  Library import never calls it.
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/src/repro/runtime/device.py → <checkout>/.jax_cache
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def pallas_interpret(backend: str | None = None) -> bool:
    """``False`` (compiled kernels) on ``tpu``, ``True`` (interpreter)
    on ``cpu``; ``backend`` defaults to ``jax.default_backend()``.
    Raises ``RuntimeError`` for any other backend."""
    if backend is None:
        import jax
        backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"no Pallas path for backend {backend!r}: the kernels compile "
        f"for 'tpu' and run interpreted on 'cpu' only")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and no other directory is set; otherwise the cache lives at
    the fixed ``<checkout>/.jax_cache`` (a stable path, so later runs in
    the same checkout hit it)."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
