"""JIT recompile guard: count XLA backend compiles at runtime.

A steady-state serving process must not recompile: every serve-path
program is precompiled by warmup (daemon._warmup, tests/test_warmup.py)
and batch shapes are pinned by the columnar layout (pad ladders).  A
recompile in the serve path is a multi-second p99 spike — the exact
failure mode guberlint's trace pass exists to keep out of the code.
This module closes the loop at RUNTIME: it counts actual backend
compiles via jax's monitoring events and exports the count as the
``gubernator_jit_recompiles`` metric, so a soak (tests/
test_recompile_guard.py) or a production scrape can assert the count
stays flat after warmup.

The hook is jax's semi-private ``jax._src.monitoring`` listener API.
The '/jax/core/compile/backend_compile_duration' duration event fires
once per program the backend is asked for, never on in-memory jit
cache hits — pinned by a test — but it wraps the persistent-cache
lookup too, so a program loaded from the persistent compilation cache
still counts.  Three plain events tell those apart: every lookup
records '/jax/compilation_cache/compile_requests_use_cache', every hit
'/jax/compilation_cache/cache_hits', and every executable the backend
really compiled and stored '/jax/compilation_cache/cache_misses' (a
compile the backend refused is a request that is neither).  A restarted
daemon on a warm cache shows zero misses (chip_smoke.py asserts it).
"""

from __future__ import annotations

import threading

_lock = threading.Lock()
_count = 0  # guberlint: guarded-by _lock
_by_name: dict = {}  # guberlint: guarded-by _lock
_installed = False
_available = False

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# Persistent-cache events by the name cache_stats() reports them under.
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_cache = dict.fromkeys(_CACHE_EVENTS.values(), 0)  # guberlint: guarded-by _lock


def _on_event_duration(event: str, duration: float, **kwargs) -> None:
    global _count
    if event == _COMPILE_EVENT:
        name = kwargs.get("fun_name", "")
        with _lock:
            _count += 1
            _by_name[name] = _by_name.get(name, 0) + 1


def _on_event(event: str, **kwargs) -> None:
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        with _lock:
            _cache[name] += 1


def install() -> bool:
    """Register the compile-event listener (idempotent).  Returns
    whether the counter is live."""
    global _installed, _available
    with _lock:
        if _installed:
            return _available
        _installed = True
    try:
        from jax._src import monitoring
    except Exception:  # noqa: BLE001 — private API moved; degrade
        from gubernator_tpu.utils.metrics import record_swallowed

        record_swallowed("jit_guard.install")
        return False
    monitoring.register_event_duration_secs_listener(_on_event_duration)
    monitoring.register_event_listener(_on_event)
    with _lock:
        _available = True
    return True


def available() -> bool:
    with _lock:
        return _available


def compile_count() -> int:
    """Backend compiles observed since install() (0 if unavailable)."""
    with _lock:
        return _count


def cache_stats() -> dict:
    """Persistent-compilation-cache lookups, hits and misses since
    install(); `misses` is what the backend really compiled."""
    with _lock:
        return dict(_cache)


def compiled_programs() -> dict:
    """Backend compile requests by jitted function name since
    install() — which program families this process has built."""
    with _lock:
        return dict(_by_name)
