"""Where JAX keeps its persistent compilation cache for this checkout.

Every entry point that runs on the chip (``chip_smoke.py``,
``repro.launch.ingest``, ``repro.launch.serve``) calls
:func:`enable_compile_cache` before its first compile.  A cache hits only
when its directory stays put, so the path is fixed: the directory that
``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads that
variable itself), and ``<checkout>/.jax_cache`` otherwise.
"""

from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
