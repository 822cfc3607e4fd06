"""Persistent XLA compilation cache: on by default, placed from outside.

A BERT-base ``compile_step`` costs most of a minute of XLA time and is
paid again by every benchmark round, test-driver rerun and restarted
worker, even though the program is byte-identical. JAX ships a
persistent compilation cache keyed on the serialized HLO, the compile
options and the cache directory's own path, so the directory must not
move between runs:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own handling of the
  variable decides the directory and this module sets no other;
- where it is not, the cache lives at :data:`DEFAULT_CACHE_DIR` — one
  fixed, git-ignored directory inside the package, never a temporary
  name, a process id or a time.

``enable_compile_cache()`` (called at ``tpudl.runtime`` import) also
zeroes the min-compile-time / min-entry-size gates so every executable
is eligible — the repo's test-sized programs compile in milliseconds
and would otherwise never be cached.

Observability: the ``jax.monitoring`` hook-up of
``tpudl.analysis.dispatch`` (installed here, at import) turns the
cache's hit/miss events into ``compile_cache_hits`` /
``compile_cache_misses`` counters, a ``compile_cache_hit`` event in the
span stream when a span recorder is active, and the ``cache_hit`` /
``cache_read_s`` attributes of each program's ``program.compile``
record, so a report shows whether a run's compiles were served from
disk and how long the reads took.
"""

from __future__ import annotations

import os
import pathlib

#: The variable JAX itself reads into ``jax_compilation_cache_dir``.
JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: Where the cache lives when that variable is unset (``.gitignore`` and
#: ``.chiprunignore`` list it).
DEFAULT_CACHE_DIR = (
    pathlib.Path(__file__).resolve().parents[1] / ".compile_cache"
)


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory (see the
    module docstring for which) and return that directory. Idempotent;
    the monitoring listeners install once per process."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if not os.environ.get(JAX_CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    # The repo's programs range from millisecond test jits to minute
    # BERT compiles; cache all of them — the gates exist for shared
    # multi-tenant caches, not an operator-owned directory.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax latches its used/checked verdict at the FIRST compile of the
    # process; without the reset, enabling after any jit has run is a
    # silent no-op.
    compilation_cache.reset_cache()
    from tpudl.analysis.dispatch import install_listeners

    install_listeners()
    return jax.config.jax_compilation_cache_dir
