"""What the program builds at run time stays inside its checkout.

``CACHE_ROOT`` is ``<checkout>/.cache``, which ``.gitignore`` lists. It
holds JAX's persistent compilation cache (``jax/``) and the planner's
calibration profiles (``calibration/``). No path under ``$HOME`` or a
temporary directory is used, so a fresh checkout on a fresh machine starts
empty, and a rerun in the same checkout finds its compiled programs again.

Import-side-effect free: jax is imported only when the cache is enabled.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_ROOT = Path(__file__).resolve().parents[2] / ".cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and nothing is set here. Otherwise the cache goes to the fixed
    path ``CACHE_ROOT / "jax"``. Call it once, before the first compile,
    from each entry point (the CLI mains and ``chip_smoke.py``).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CACHE_ROOT / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
