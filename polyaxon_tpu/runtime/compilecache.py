"""Persistent XLA compile cache: one directory, placed from outside.

Every gang member, serving replica and hpsearch trial would otherwise
pay the full XLA compile bill fresh (the goodput ledger attributes it as
``xla_compile_s``).  JAX's persistent compilation cache removes the
repeat cost, and its directory is part of the cache key — a directory
that moves never hits.  So there is exactly one rule for where it lives:

- ``JAX_COMPILATION_CACHE_DIR`` set from outside → the cache is there.
  Nothing in this package writes another directory into the environment
  or into ``jax.config``; child processes inherit the variable.
- not set → :data:`DEFAULT_CACHE_DIR`, one fixed git-ignored path at the
  root of the checkout.  Not a function of ``--base-dir``, a temporary
  name, a pid or the time.

JAX's own variables stay the interface for everything else
(``JAX_ENABLE_COMPILATION_CACHE=0`` turns the cache off,
``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` sets a persist
threshold); this module only defaults the thresholds to "persist every
compile" where the outside left them unset — the CPU smoke
configurations compile in milliseconds and cross-process reuse is the
point.

Never imports jax when it isn't already loaded — the worker defers the
jax import deliberately, so the boot path arms the cache through the
environment jax reads at import time.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Optional, Tuple

__all__ = [
    "CacheStatus",
    "DEFAULT_CACHE_DIR",
    "cache_dir",
    "enable_compile_cache",
    "cache_status",
    "aot_compile",
]

#: JAX's own variable: the only way to place the cache.
ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

#: Where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is not set:
#: ``<checkout>/.compile_cache`` (listed in ``.gitignore``).
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".compile_cache"

#: Persist every compile unless the outside says otherwise (-1 = no
#: entry-size floor).
_THRESHOLD_DEFAULTS = {
    "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1",
}


@dataclass(frozen=True)
class CacheStatus:
    """Outcome of the most recent :func:`enable_compile_cache` attempt."""

    enabled: bool
    cache_dir: Optional[str]
    reason: str


_lock = threading.Lock()
_status: Optional[CacheStatus] = None


def cache_dir() -> str:
    """The directory this process's compile cache lives in."""
    return os.environ.get(ENV_DIR) or str(DEFAULT_CACHE_DIR)


def enable_compile_cache() -> CacheStatus:
    """Arm JAX's persistent compilation cache for this process and the
    children that inherit its environment.

    With ``JAX_COMPILATION_CACHE_DIR`` exported this only creates the
    directory and defaults the persist thresholds; the variable and
    ``jax_compilation_cache_dir`` are left exactly as the outside set
    them.  Without it the fixed default is exported (before jax is
    imported on the worker boot path, so jax reads it at import).
    An unusable directory comes back as a disabled status with the
    reason (surfaced by ``checks/health.py:check_compile_cache``).
    """
    global _status
    with _lock:
        placed = bool(os.environ.get(ENV_DIR))
        resolved = cache_dir()
        try:
            os.makedirs(resolved, exist_ok=True)
            if not os.access(resolved, os.W_OK):
                raise OSError("not writable")
        except OSError as e:
            _status = CacheStatus(
                False, resolved, f"cache dir {resolved} unusable: {e}"
            )
            return _status
        for var, default in _THRESHOLD_DEFAULTS.items():
            os.environ.setdefault(var, default)
        if not placed:
            os.environ[ENV_DIR] = resolved
        if "jax" in sys.modules:
            # jax read its flags from the environment at import; bring an
            # already-imported jax in line, then reset the cache singleton
            # — it latches on first compile, so a process that compiled
            # anything before this call would otherwise never read or
            # write the cache.
            import jax
            from jax._src import compilation_cache as _cc

            from polyaxon_tpu.tracking.ledger import install_compile_hooks

            if not placed:
                jax.config.update("jax_compilation_cache_dir", resolved)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs",
                float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]),
            )
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes",
                int(os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"]),
            )
            _cc.reset_cache()
            install_compile_hooks()
        _status = CacheStatus(
            True,
            resolved,
            f"placed by {ENV_DIR}" if placed else "default path in the checkout",
        )
        return _status


def cache_status() -> CacheStatus:
    """The last :func:`enable_compile_cache` outcome for this process
    (a disabled placeholder when it was never called — e.g. the control
    plane, which never compiles)."""
    with _lock:
        if _status is not None:
            return _status
        return CacheStatus(False, None, "not enabled in this process")


def _reset_for_tests() -> None:
    global _status
    with _lock:
        _status = None


def aot_compile(jitted: Callable, *args: Any) -> Tuple[Any, float]:
    """AOT-compile a jitted fn: ``(executable, compile_seconds)``.

    The returned executable must be *called directly* — ``lower().
    compile()`` does not populate the jit dispatch cache, so calling the
    original ``jitted`` afterwards would compile a second time.  A
    lowering or compile error (a Mosaic refusal, an HBM overflow)
    propagates from here, where it happened.  Donation declared on the
    jit is preserved through the AOT path.
    """
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    return compiled, time.perf_counter() - t0
