"""Where JAX keeps its persistent compilation cache.

A cache hits only when its directory is the same from one run to the
next: the path is part of what JAX keys on.  So the rule is fixed:
``JAX_COMPILATION_CACHE_DIR``, when the environment sets it (JAX reads
the variable itself and nothing here overrides it), else ``.jax_cache/``
under the checkout the entry point runs from -- never a path built from a
temporary name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(root: str | os.PathLike) -> str:
    """Point JAX's persistent compilation cache at its one place and
    return that directory.  ``root`` is the checkout's root directory.
    Call before the first compile."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax
    path = str(Path(root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
