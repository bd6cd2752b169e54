"""Where JAX keeps its persistent compilation cache.

One rule, applied by every entry point before its first compile
(``train.main``, ``serve.main``, ``chip_smoke.py``, ``benchmark/run.py``
and the process-actor worker entry):

  * ``JAX_COMPILATION_CACHE_DIR`` set — the cache lives there.  JAX reads
    the variable itself; nothing in this repo sets another directory.
  * not set — ``<checkout>/.jax_cache`` (gitignored), computed from this
    file's own location.  The path is part of the cache key, so it is
    never derived from a temp dir, a pid or a clock: two processes, or two
    runs of the same checkout, land on the same directory and hit.

jax is imported inside the function so the module stays import-light
(analysis/import_light.py).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# Persist every compile, not only those over jax's 1.0 s default: a run
# compiles dozens of sub-second programs (ring adds, param copies, one
# greedy apply per serving bucket), and a process that starts beside a warm
# cache should compile none of them.
_MIN_COMPILE_TIME_S = 0.0


def cache_dir() -> str:
    """The directory the cache uses under the rule above."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def enable_compile_cache() -> str:
    """Apply the rule to this process; returns the directory in use.

    Call before the first compile: jax decides once per process whether the
    cache is on.  The launch log starts listening to jax's compile events
    here (``profiling.listen``) and takes a mark: in a process whose entry
    point touched the backend itself, chip start-up ends at it."""
    import jax

    from ape_x_dqn_tpu.utils import profiling

    profiling.listen()
    profiling.launch.mark("compile_cache")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", _MIN_COMPILE_TIME_S
    )
    return cache_dir()
