"""JAX's persistent compilation cache, placed for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it. Otherwise, when ``repro`` runs from a checkout's
``src/``, ``use_compile_cache`` points the cache at ``.jax_cache/`` in the
root of that checkout (gitignored): a fixed path, so a later run in the same
checkout finds what an earlier one compiled. An installed ``repro`` has no
checkout, and there the cache stays off unless the variable names one.

Only entry points call this (``chip_smoke.py``, ``examples/*.py``);
importing ``repro`` sets no cache, so tests run without one unless their
environment names a directory.
"""

from __future__ import annotations

import os
import pathlib

import jax

_ROOT = pathlib.Path(__file__).resolve().parents[2]

#: ``<checkout>/.jax_cache`` when this file is ``<checkout>/src/repro/``
#: of this repository, else None.
DEFAULT_DIR = (_ROOT / ".jax_cache"
               if (_ROOT / "pyproject.toml").is_file()
               and (_ROOT / "src" / "repro").is_dir() else None)


def use_compile_cache() -> str | None:
    """Turn on the persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``DEFAULT_DIR``. Returns
    None, and leaves the cache off, when neither exists."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path and DEFAULT_DIR is not None:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path or None


__all__ = ["DEFAULT_DIR", "use_compile_cache"]
