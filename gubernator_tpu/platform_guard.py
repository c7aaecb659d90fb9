"""Pin jax to the CPU platform for host-side runs.

Tier-1 tests, the multi-chip dry run and the host-side scripts run on
the CPU backend — the tests on an 8-device virtual mesh — whatever
accelerator the machine has.  `force_cpu_platform` is the one place
that does it (`tests/conftest.py`, `__graft_entry__.dryrun_multichip`,
the daemon under `GUBER_PLATFORM=cpu`, `scripts/`); keep the logic
here so it cannot drift.

Must be called before any jax backend initializes (first array op /
`jax.devices()`): `XLA_FLAGS` is read at backend-init time, and the
platform switch cannot evict an already-initialized backend.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def force_cpu_platform(n_devices: int | None = None) -> None:
    """Pin jax to the CPU platform, with ≥ `n_devices` virtual devices.

    Safe to call repeatedly; raises the virtual device count to the max
    ever requested (a pre-existing smaller count in `XLA_FLAGS` is
    rewritten, not trusted)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
        if m is None:
            flags = (flags + f" {_COUNT_FLAG}={n_devices}").strip()
        elif int(m.group(1)) < n_devices:
            flags = flags.replace(m.group(0), f"{_COUNT_FLAG}={n_devices}")
        os.environ["XLA_FLAGS"] = flags

    import jax

    jax.config.update("jax_platforms", "cpu")
    disable_cpu_persistent_cache()


def cpu_was_requested() -> bool:
    """Whether something told this process, by name, to run on the CPU
    backend (`JAX_PLATFORMS=cpu` in the environment, `GUBER_PLATFORM=cpu`
    or `force_cpu_platform`, both of which land in `jax_platforms`)."""
    import jax

    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def disable_cpu_persistent_cache() -> None:
    """Turn the persistent compile cache OFF when the effective
    backend is CPU.

    Serializing certain XLA:CPU executables (the pump's donated
    lax.scan programs) SEGFAULTS in jaxlib's AOT export, and loading
    entries written by a different CPU model is a fatal abort — both
    hit this build mid-suite.  The cache exists for the accelerator's
    compiles; CPU compiles are cheap, so the safe configuration is
    cache-off whenever the effective backend is CPU.  Called by
    force_cpu_platform and by engine construction.  It only disables:
    the configured directory is left as it is.

    Updating the config alone is NOT enough once anything compiled:
    jax memoizes the cache-enabled decision — reset it too."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if jax.default_backend() != "cpu":
        return
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
