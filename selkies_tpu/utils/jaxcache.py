"""Persistent XLA compilation cache for fast encoder (re)builds.

The resilience ladder's RESTART and RECYCLE rungs (resilience/
supervisor.py) rebuild encoders and fleet services; without a
compilation cache every rebuild pays the full XLA compile again — tens
of seconds of dead air exactly when a session is trying to recover. With
the disk cache, a rebuilt program with identical HLO loads in a fraction
of the time, and a restarted *process* (supervisor-level recovery, CI
reruns) warm-starts too.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory. Otherwise the cache lives at
``.jax_cache/`` in the checkout root: a fixed path, because the path is
part of what makes a later run find the entries again.
"""

from __future__ import annotations

import logging
import os
from pathlib import Path

logger = logging.getLogger("utils.jaxcache")

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_done = False


def cache_dir(environ=os.environ) -> str:
    """The directory the persistent cache uses under ``environ``."""
    return environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)


def enable_persistent_compilation_cache() -> None:
    """Idempotent; call before building jitted programs."""
    global _done
    if _done:
        return
    _done = True
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    # cache everything that takes real time; tiny programs stay
    # in-memory only
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    logger.info("persistent compilation cache at %s",
                jax.config.jax_compilation_cache_dir)
