"""Where JAX keeps its persistent compilation cache.

The cache's key includes its path, so a directory that moves never hits:
every entry point (``chip_smoke.py``, ``launch/train.py``,
``launch/worker.py``) calls :func:`use_compile_cache` from ``main`` — never
at import — and so shares one fixed directory per checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache(root: Path = CHECKOUT) -> str:
    """Point JAX's persistent compilation cache at ``root/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` is set, in which case JAX already
    uses that directory and nothing else is set.  Returns the directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
