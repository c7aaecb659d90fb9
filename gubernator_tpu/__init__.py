"""gubernator_tpu — a TPU-native distributed rate-limiting framework.

A ground-up rebuild of the capabilities of mailgun/gubernator (the Go
reference lives at /root/reference; see SURVEY.md) designed for TPU
hardware: per-key token/leaky-bucket state lives as device-sharded
struct-of-arrays in HBM, every ~500µs request batch is applied by one
jit-compiled XLA kernel (`gubernator_tpu.ops.bucket_kernel`), GLOBAL
aggregation maps to collectives over the ICI mesh, and consistent
hashing maps keys to hosts (cluster tier) and device shards (mesh tier).

Public API mirrors the reference's gRPC/HTTP contract
(reference: proto/gubernator.proto, proto/peers.proto).
"""

import os

# Bucket timestamps are unix-epoch milliseconds and counters are int64 on
# the wire (reference: proto/gubernator.proto:142-161), so the device
# kernel needs 64-bit integer arithmetic.  x64 must be enabled before the
# first JAX computation runs.  Opt out with GUBERNATOR_TPU_X64=0 (the
# engine will refuse to start without x64, but other subpackages remain
# importable).
if os.environ.get("GUBERNATOR_TPU_X64", "1") != "0":  # pragma: no branch
    import jax

    jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: daemon warmup precompiles a ladder
# of batch widths (engine.warmup), so every process after the first
# starts from cached executables.  Where JAX_COMPILATION_CACHE_DIR is
# set, jax has already read it and no directory is set here; otherwise
# the cache lives at <checkout>/.jax_cache, computed from this file's
# location (the path is part of what makes a cache findable again, so
# it never depends on $HOME, a pid or the time).  Every program is
# cached, however quickly it compiled: the warmup ladder is ~75 small
# programs and a restart should recompile none of them.  On the CPU
# backend the cache is switched off
# (platform_guard.disable_cpu_persistent_cache).
# Opt out with GUBERNATOR_TPU_COMPILE_CACHE=0.
if os.environ.get("GUBERNATOR_TPU_COMPILE_CACHE", "1") != "0":
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                ".jax_cache",
            ),
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

from gubernator_tpu._version import __version__
from gubernator_tpu.types import (
    Algorithm,
    Behavior,
    Status,
    RateLimitReq,
    RateLimitResp,
    HealthCheckReq,
    HealthCheckResp,
    GetRateLimitsReq,
    GetRateLimitsResp,
    PeerInfo,
    has_behavior,
)

__all__ = [
    "__version__",
    "Algorithm",
    "Behavior",
    "Status",
    "RateLimitReq",
    "RateLimitResp",
    "HealthCheckReq",
    "HealthCheckResp",
    "GetRateLimitsReq",
    "GetRateLimitsResp",
    "PeerInfo",
    "has_behavior",
]
