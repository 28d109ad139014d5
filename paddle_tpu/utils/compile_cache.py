"""JAX's persistent compilation cache for the entry points (``bench.py``,
``chip_smoke.py``, ``__graft_entry__.py``): placed from outside through
``JAX_COMPILATION_CACHE_DIR``, else at one fixed path inside the checkout.

A directory that moves never hits, so no temporary name, pid or time.  On
the CPU backend the cache stays off, as it does for the tests
(``tests/conftest.py``): jaxlib's CPU backend crashes deserializing entries
an earlier process wrote.
"""
from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")

_counts = {"hits": 0, "misses": 0}
_listening = False


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _counts["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _counts["misses"] += 1


def enable_compile_cache() -> str:
    """Place the persistent cache before the first compile and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and nothing is set here; otherwise the cache goes to
    ``REPO_CACHE_DIR``.  Call it once the platform is chosen: it asks for
    the backend."""
    import jax

    global _listening
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    if jax.default_backend() == "cpu":
        jax.config.update("jax_enable_compilation_cache", False)
        return path
    # JAX keeps only programs that took a second to compile; the step and
    # the engine programs pass that, the hundreds of small ones a model's
    # set-up compiles do not, and together they are the longer wait
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return path


def cache_counts() -> dict:
    """Persistent-cache hits and misses seen since ``enable_compile_cache``."""
    return dict(_counts)
