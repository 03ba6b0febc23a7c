"""Compilation plumbing for the entry points: the persistent compile
cache and a counter of backend compiles.

The cache is turned on by the entry points (``chip_smoke.py``,
``launch/serve.py``, ``launch/train.py``, ``benchmarks/run.py``), never
when the package is imported, and never by the tests.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here; otherwise the cache lives in ``.jax_cache/`` at the root
of the checkout.  The path is part of each entry's key, so it is fixed:
never a temporary directory, a process id or a time.
"""

from __future__ import annotations

import contextlib
import os
import pathlib
from typing import Iterator, Mapping, Optional

ENV = "JAX_COMPILATION_CACHE_DIR"
#: the checkout root: src/repro/launch/compilation.py -> parents[3]
ROOT = pathlib.Path(__file__).resolve().parents[3]
#: the backend-compile event JAX records once per compiled program
#: (a persistent-cache hit is recorded under it too)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def cache_dir(environ: Optional[Mapping[str, str]] = None) -> str:
    """Where the persistent compile cache lives: ``$JAX_COMPILATION_
    CACHE_DIR`` when set, else ``<checkout>/.jax_cache``."""
    environ = os.environ if environ is None else environ
    return environ.get(ENV) or str(ROOT / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on for this process; returns
    its directory.  Sets the directory only when the environment does
    not name one."""
    import jax
    path = cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path


@contextlib.contextmanager
def count_compiles() -> Iterator[list]:
    """Count backend compiles inside the block: yields a one-element
    list whose entry is the running count."""
    from jax import monitoring
    count = [0]

    def on_event(event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            count[0] += 1

    monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield count
    finally:
        monitoring.unregister_event_duration_listener(on_event)
