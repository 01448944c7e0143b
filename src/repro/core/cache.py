"""Persistent (on-disk) XLA compilation cache: cold starts stop paying compile.

The in-memory program caches (``montecarlo._PROGRAM_CACHE`` and the sweep
engine's twin, keyed on ``GridSignature`` + ``source.cache_token()`` + static
shapes) die with the process — a cold start re-traces AND re-runs XLA for
every program.  ``setup_compilation_cache`` turns on jax's persistent
compilation cache so a fresh process loads compiled executables from disk.

Where the cache lives is decided outside the program: if
``JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it and that is the
directory (nothing here sets another); otherwise it is the fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``).  The path is part of
jax's cache key, so a fixed path is what lets a later process hit.

Key convention — how disk entries line up with the in-memory keys: jax keys
the disk cache on a fingerprint of the *traced program* (HLO + compile
options + backend/jax versions).  The sweep engine's traced program is a pure
function of its in-memory cache key — ``(source.cache_token(), GridSignature,
partition, mesh shape, static iteration/slot shapes)`` — plus the dispatch's
array shapes/dtypes, so:

* same grid signature + shapes in a fresh process  -> disk HIT (no XLA),
* any change that would retrace in-process (new ``GridSignature``, different
  ``cache_token``, new mesh shape) -> disk MISS, compiled exactly once, then
  persisted for every later process.

Tracing itself (python -> jaxpr) still runs per process — it is the XLA
compile (the dominant cost) that the disk cache removes.  Entries are
backend- and jax-version-scoped by jax's fingerprint, so one directory is
safe to share across heterogeneous hosts; stale entries are simply never hit.

The entry points (``launch/train.py``, ``chip_smoke.py``, the benchmark
CLIs) call ``setup_compilation_cache()`` before their first compile; nothing
calls it at import, so library users and the tests keep jax's defaults.

``setup_compilation_cache()`` also starts the process-wide compile counter
that ``compile_stats()`` reads, from jax's own monitoring events: a retrace
or recompile shows there even where the disk cache hides it from
``cache_entries``.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

__all__ = ["ENV_VAR", "DEFAULT_DIR", "setup_compilation_cache", "cache_entries",
           "compile_stats"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


# jax.monitoring event -> compile_stats() key.  Backend compiles count every
# executable XLA was asked for, those the persistent cache served included.
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "traces",
    "/jax/core/compile/backend_compile_duration": "compiles",
    "/jax/compilation_cache/cache_hits": "cache_hits",
}
_COMPILE_COUNTS = dict.fromkeys(_COMPILE_EVENTS.values(), 0)
_counting = False


def _count_compile_event(event: str, *_, **__) -> None:
    key = _COMPILE_EVENTS.get(event)
    if key is not None:
        _COMPILE_COUNTS[key] += 1


def _count_compiles() -> None:
    global _counting
    if not _counting:
        jax.monitoring.register_event_listener(_count_compile_event)
        jax.monitoring.register_event_duration_secs_listener(_count_compile_event)
        _counting = True


def compile_stats() -> dict:
    """Process-wide counts since ``setup_compilation_cache()``: jaxpr
    ``traces``, backend ``compiles`` (every executable requested of XLA) and
    the persistent-cache ``cache_hits`` among them.  A warm loop adds 0 to
    each; the difference of two snapshots says which steps recompiled."""
    return dict(_COMPILE_COUNTS)


def setup_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache; return its directory.

    Uses ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``DEFAULT_DIR``.
    Removes jax's default size/time floors (min entry size, min compile
    seconds) so EVERY executable persists — the sweep grids this repo
    compiles are seconds-scale programs, but the floors would silently skip
    the small auxiliary executables and leave a fresh process still paying
    a compile.  Also enables the XLA-level sub-caches (autotune results
    etc.) where the backend supports them.  Idempotent; must run before the
    process's first compile to take effect.  Starts ``compile_stats()``'s
    counter.
    """
    _count_compiles()
    cache_dir = os.environ.get(ENV_VAR) or DEFAULT_DIR
    os.makedirs(cache_dir, exist_ok=True)
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    return cache_dir


def cache_entries(cache_dir: Optional[str] = None) -> int:
    """Number of persisted entries (files) under ``cache_dir`` (default: the
    active directory).  The entry *delta* across a run is the observable
    compile count: a fully-warmed process adds exactly 0, a changed
    ``GridSignature`` adds exactly the newly-compiled executables."""
    if cache_dir is None:
        cache_dir = jax.config.jax_compilation_cache_dir
    if cache_dir is None or not os.path.isdir(cache_dir):
        return 0
    n = 0
    for _, _, files in os.walk(cache_dir):
        n += len(files)
    return n
