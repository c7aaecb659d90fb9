"""What an engine is serving on: the `device` block of /debug/vars and
the daemon's start-up log line.

Everything here is read from what the engine already holds (its
devices, the step form it selected, the compile probes' verdicts, its
counters) — there is no setting behind it.  An operator, a client or
`chip_smoke.py` reads this to learn which platform and which step
program answer requests, instead of inferring it from timings.
"""

from __future__ import annotations

from typing import List


def _devices(engine) -> List:
    """The jax devices an engine's state lives on."""
    mesh = getattr(engine, "mesh", None)
    if mesh is not None:
        return list(mesh.devices.flat)
    import jax

    return [engine._device if engine._device is not None else jax.devices()[0]]


def describe(engine) -> dict:
    from gubernator_tpu.core.native import NativeInternTable
    from gubernator_tpu.net import wire_codec
    from gubernator_tpu.platform_guard import cpu_was_requested
    from gubernator_tpu.utils import jit_guard

    devices = _devices(engine)
    d0 = devices[0]
    pump = getattr(engine, "_pump", None)
    tables = getattr(engine, "tables", None) or [engine.table]
    memory = []
    for d in devices:
        stats = d.memory_stats() or {}  # None on the CPU backend
        memory.append(
            {
                "id": d.id,
                "bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
            }
        )
    return {
        "platform": d0.platform,
        "device_kind": d0.device_kind,
        "device_count": len(devices),
        # True only when the backend resolved to the CPU without
        # anything having asked for it by name.
        "cpu_unrequested": d0.platform == "cpu" and not cpu_was_requested(),
        "engine": type(engine).__name__,
        "rows": engine.capacity,
        "rows_occupied": engine.cache_size(),
        "pump": pump is not None,
        "pump_scan": bool(pump is not None and pump._scan_ok),
        "probes": {
            name: {"ok": v.ok, "reason": v.reason}
            for name, v in engine.probes.items()
        },
        "native": {
            "intern_table": all(
                isinstance(t, NativeInternTable) for t in tables
            ),
            "wire_codec": wire_codec.load() is not None,
        },
        "memory": memory,
        "counters": {
            "requests_total": engine.requests_total,
            "batches_total": engine.batches_total,
            "rounds_total": engine.rounds_total,
            "dispatches_total": engine.dispatches_total,
            "over_limit_total": engine.over_limit_total,
            "evictions_total": sum(t.evictions for t in tables),
            "unexpired_evictions_total": sum(
                t.unexpired_evictions for t in tables
            ),
            "rows_loaded_total": engine.rows_loaded_total,
            "pump_fused_rounds": pump.fused_rounds if pump else 0,
            "pump_flushes": pump.flushes if pump else 0,
        },
        "compiles": {
            "backend_compiles": jit_guard.compile_count(),
            "persistent_cache": jit_guard.cache_stats(),
            "programs": jit_guard.compiled_programs(),
        },
    }
