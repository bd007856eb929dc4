"""JAX's persistent compilation cache, turned on by the program's entry points.

Entry points (`launch.serve`, `launch.train`, the `serve.worker` subprocess,
`chip_smoke.py` and the benchmark scripts) call `enable_compile_cache` before
their first compile. Library modules never call it, so importing them — as
the tests do — leaves JAX's cache settings as they were.
"""
from __future__ import annotations

import os
from pathlib import Path

#: In-checkout default, listed in .gitignore. The path is fixed because it
#: is part of what lets a later process find an entry again.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads the
    variable itself). Otherwise the cache goes to `DEFAULT_DIR`, exported
    through that variable so worker subprocesses inherit it.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
        jax.config.update("jax_compilation_cache_dir", path)
    return path
