"""Where JAX's persistent compilation cache lives for the entry points.

Every command-line entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, the ``benchmarks/*_sweep.py`` drivers) calls
:func:`use_compile_cache` once, before its first compile.  Tests do not:
a test session compiles with whatever the environment says.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing else
  is set here.
* Not set: the cache goes to ``<repo>/.jax_cache``, a fixed directory
  (the path is part of what a later run must find again, so it is never
  built from a temporary name, a process id or the time), which
  ``.gitignore`` lists.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the checkout's root (src/repro/runtime/ -> repo)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
