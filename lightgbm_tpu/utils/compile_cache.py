"""The one compile-cache rule (r21).

Where JAX's persistent compilation cache lives, and what its keys are
made of, is decided here and nowhere else:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads the variable itself and
  this program sets no other directory, in any code path;
* not set — ``<checkout>/.jaxcache`` (gitignored), a fixed path because
  the path is part of what a restarted process must find again: never a
  temp name, a pid or a time;
* either way, no Python source location goes into the IR JAX emits
  (``jax_traceback_in_locations_limit = 0``).  A Pallas kernel is
  serialized into its program WITH its locations and that payload is
  part of the cache key (the outer program's locations are stripped
  before hashing, the payload's are not).  With locations in, the key of
  every program that holds a kernel depends on the call path it was
  traced from and on which line first traced a ``jnp`` helper the kernel
  shares with other code, so a second ``lgb.train`` of the same shape
  from another line, script or trace order recompiled from nothing.
  One frame (``jax_include_full_tracebacks_in_locations = False``) is
  not enough: the shared-helper case remains.  The price: HLO metadata
  and profiles name ops by their name stack only, not by file and line.

``import lightgbm_tpu`` puts the rule in force, so ``lgb.train``,
``lgb.cv``, the serving stack and ``chip_smoke.py`` share one cache.  ``serving.enable_persistent_cache``, ``ModelBank(cache_dir=)``
and the serve CLI's ``compile_cache_dir`` key only report the directory
in force.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def checkout_cache_dir() -> str:
    """``<checkout>/.jaxcache`` — beside the ``lightgbm_tpu`` package."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package), ".jaxcache")


def compile_cache_dir() -> str:
    """Put the rule in force; return the directory in force."""
    import jax

    jax.config.update("jax_traceback_in_locations_limit", 0)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = checkout_cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
