"""Watchdog-guarded JAX backend initialization.

JAX initializes its PJRT client lazily on the first ``jax.default_backend()``
/ ``jnp`` call, and a chip that another process holds, or a runtime that
fails to come up, can block or fail that call. The reference engine never
has this problem because its backend is the CPU it is already running on;
for a device-tiered engine the backend is a *fallible external resource*
and must be probed exactly once, under a timeout, from a single thread —
never raced from N scan workers (cf. the frozen-per-query config bootstrap
discipline in the reference, ``src/common/daft-config/src/lib.rs:40-68``).

Semantics:
- :func:`probe` starts (once) a daemon thread that touches the backend.
- :func:`backend_name` / :func:`device_ready` wait up to the configured
  timeout for that probe; on timeout or error the device tier is marked
  unavailable for the life of the process and the engine pins itself to the
  host tier. The failure is logged ONCE at WARNING with its text and kept
  in :func:`probe_error` — a chip that failed to initialise is visible.
  The stuck thread is left to its fate (daemon).
- ``DAFT_TPU_BACKEND_TIMEOUT`` (seconds, default 60) bounds the wait.

Compile cache: ONE rule. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and this module sets no directory (only the two size/time
thresholds, so every program is cached). Otherwise, on a non-CPU
backend, the persistent XLA cache lives at ``<repo>/.cache/jax`` — a fixed
path (the path is part of the cache key, so a directory that moves never
hits). CPU backends get no persistent cache: their AOT artifacts are
machine-feature-pinned and reload with SIGILL-risk warnings across hosts.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional

logger = logging.getLogger(__name__)

_lock = threading.Lock()
_probe_thread: Optional[threading.Thread] = None
_done = threading.Event()
_backend: Optional[str] = None
_failed = False
_error: Optional[str] = None

#: the checkout root: ``.cache/`` under it (git-ignored) holds everything
#: the engine persists on its own — compile cache, link profile, datasets
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_root() -> str:
    """``<repo>/.cache`` — the one place the engine persists state."""
    return os.path.join(REPO_ROOT, ".cache")


def _timeout() -> float:
    from ..analysis import knobs
    return knobs.env_float("DAFT_TPU_BACKEND_TIMEOUT")


def configure_compile_cache(backend: str) -> Optional[str]:
    """Apply the one compile-cache rule (module docstring); returns the
    directory in effect, or None when there is no persistent cache."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if backend == "cpu":
            return None
        path = os.path.join(cache_root(), "jax")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # else: JAX already honours the env var; no directory is set in code.
    # Either way cache every program, however quick its compile: a warm
    # process then compiles nothing at all, which a second run can assert
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def _probe_body() -> None:
    global _backend, _failed
    try:
        import jax

        # daft-lint: allow(unguarded-global-mutation) -- the _done Event is
        # the sync point: readers wait on it, this write happens-before set()
        _backend = jax.default_backend()
        configure_compile_cache(_backend)
    except Exception as exc:
        # daft-lint: allow(unguarded-global-mutation) -- Event-synchronized
        # with readers (see _backend above)
        _failed = True
        _note_failure(f"{type(exc).__name__}: {exc}")
    finally:
        _done.set()


def probe() -> None:
    """Kick off backend initialization in the background (idempotent)."""
    global _probe_thread
    with _lock:
        if _probe_thread is None:
            _probe_thread = threading.Thread(
                target=_probe_body, name="daft-tpu-backend-probe", daemon=True)
            _probe_thread.start()


def backend_name(wait: bool = True) -> Optional[str]:
    """The initialized backend name, or None if unavailable/timed out."""
    global _failed
    if _failed:
        return None
    probe()
    if wait and not _done.is_set():
        _done.wait(_timeout())
        if not _done.is_set():
            # timed out: permanently mark the device tier unusable so later
            # callers don't re-block for another full timeout.
            # daft-lint: allow(unguarded-global-mutation) -- worst case two
            # timed-out threads both store True; probe never clears it
            _failed = True
            _note_failure(f"backend probe did not finish within "
                          f"{_timeout():g}s (DAFT_TPU_BACKEND_TIMEOUT)")
            return None
    if not _done.is_set():
        return None  # non-waiting peek while the probe is in flight
    return None if _failed else _backend


def _note_failure(text: str) -> None:
    """Keep the probe's failure text and log it — once per process."""
    global _error
    with _lock:
        if _error is not None:
            return
        _error = text
    logger.warning(
        "daft-tpu: device backend failed to initialise — the process is "
        "pinned to the host tier: %s", text)


def probe_error() -> Optional[str]:
    """Text of the probe's exception or timeout, or None if it came up
    (or is still in flight)."""
    return _error


def device_ready() -> bool:
    """True once the JAX backend initialized successfully within timeout."""
    return backend_name() is not None


def is_accelerator() -> bool:
    """True when the initialized backend is an accelerator, not the CPU
    tier — the SINGLE predicate for buffer donation. New backend
    strings get classified here once, not at every dispatch site."""
    return (backend_name() or "cpu") != "cpu"


def reset_for_tests() -> None:
    global _probe_thread, _backend, _failed, _error
    with _lock:
        _probe_thread = None
        _backend = None
        _failed = False
        _error = None
        _done.clear()
