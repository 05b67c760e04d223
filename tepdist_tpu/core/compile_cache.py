"""Placement of JAX's persistent compilation cache.

Called from process entry points only (the server binary, ``chip_smoke.py``
children, ``benchmark/run.py`` and the example mains) — never on import, so a
library user and the test suite keep whatever they configured.

The directory is part of a cache entry's key, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` wins when the environment sets it (jax
reads the variable itself, nothing is set in code), otherwise the fixed
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir() -> str:
    """Where an accelerator process keeps its compiled programs."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR


def configure_compile_cache() -> Optional[str]:
    """Point jax at the persistent cache; returns the directory in use.

    Call once the platform is chosen and before the first compile. A
    process on the CPU backend (tests, rehearsals, accelerator-less
    clients) caches nothing and gets None: no one deploys that backend,
    and XLA:CPU reloads each entry with a page of machine-feature
    warnings on stderr."""
    if jax.default_backend() == "cpu":
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return compile_cache_dir()
