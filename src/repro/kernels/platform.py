"""Where the Pallas kernels run: compiled on a TPU, interpreted elsewhere.

The one place that decides it.  Every kernel resolves its ``interpret``
flag here, so a TPU host always runs the compiled kernels and the CPU test
suite always runs the same kernel bodies in interpret mode.  Only
``flash_attention`` also takes an explicit ``interpret=`` (to compile for
a described chip with no chip attached).  Nothing here catches errors: a JAX runtime that cannot
start raises, rather than silently turning a broken chip into "not a TPU".
"""
from __future__ import annotations

import os
import pathlib
from typing import Optional

import jax

#: the compile cache's fixed home inside the checkout (git-ignored)
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else interpret mode exactly when off-TPU."""
    return (not on_tpu()) if interpret is None else bool(interpret)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own choice and is
    left alone; otherwise the cache lives at the fixed :data:`CACHE_DIR`
    (a fixed path: the directory is part of every entry's key)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
