"""Runtime companion to guberlint's trace pass: the recompile guard.

The trace pass keeps unpinned shapes out of the jit surface statically;
these tests close the loop at runtime — a warmed engine serving
steady-state traffic must trigger ZERO XLA backend compiles, across
every wire width the serving paths produce, and the count is exported
as the ``gubernator_jit_recompiles`` metric.
"""

import numpy as np
import pytest

from gubernator_tpu.clock import Clock
from gubernator_tpu.core.engine import DecisionEngine
from gubernator_tpu.types import Algorithm, RateLimitReq


def _columns(n, start=0, name="soak"):
    return dict(
        keys=[b"%s_k%d" % (name.encode(), start + i) for i in range(n)],
        algo=np.asarray([i % 2 for i in range(n)], dtype=np.int32),
        behavior=np.zeros(n, dtype=np.int32),
        hits=np.ones(n, dtype=np.int64),
        limit=np.full(n, 100, dtype=np.int64),
        duration=np.full(n, 60_000, dtype=np.int64),
        burst=np.full(n, 100, dtype=np.int64),
    )


def test_monitoring_hook_counts_compiles_not_cache_hits(jit_recompile_guard):
    """Pin the event semantics the guard depends on: a fresh shape
    compiles (count moves), a repeated shape is a cache hit (flat)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2 + 1)
    before = jit_recompile_guard.count()
    f(jnp.ones(7)).block_until_ready()
    after_first = jit_recompile_guard.count()
    assert after_first > before, "first call must compile"
    jit_recompile_guard.snapshot()
    f(jnp.ones(7)).block_until_ready()
    jit_recompile_guard.assert_flat("jit cache hit")
    f(jnp.ones(9)).block_until_ready()  # new shape -> recompile
    assert jit_recompile_guard.count() > after_first


def test_steady_state_serve_soak_zero_recompiles(
    frozen_clock, jit_recompile_guard
):
    """The acceptance soak: after warmup, a steady-state mix of every
    serving width (dataclass + columnar + duplicate-key collapse) runs
    with a flat compile count."""
    engine = DecisionEngine(
        capacity=8192, clock=frozen_clock, max_kernel_width=1024
    )
    engine.warmup(max_width=1024)

    jit_recompile_guard.snapshot()
    for round_no in range(3):
        for width in (1, 63, 64, 65, 500, 1000, 1024):
            engine.apply_columnar(
                **_columns(width, start=round_no * 10_000 + width * 7)
            )
        # Dataclass path at a couple of widths.
        for width in (3, 100):
            engine.get_rate_limits(
                [
                    RateLimitReq(
                        name="soak2",
                        unique_key=str(i),
                        hits=1,
                        limit=100,
                        duration=60_000,
                        algorithm=Algorithm.TOKEN_BUCKET,
                    )
                    for i in range(width)
                ]
            )
        # Hot-key collapse path (duplicate keys in one batch).
        engine.apply_columnar(
            keys=[b"soak_hot" for _ in range(200)],
            algo=np.zeros(200, dtype=np.int32),
            behavior=np.zeros(200, dtype=np.int32),
            hits=np.ones(200, dtype=np.int64),
            limit=np.full(200, 1_000_000, dtype=np.int64),
            duration=np.full(200, 60_000, dtype=np.int64),
            burst=np.full(200, 1_000_000, dtype=np.int64),
        )
    jit_recompile_guard.assert_flat("steady-state serve soak")


def test_recompile_metric_exported(frozen_clock, jit_recompile_guard):
    """gubernator_jit_recompiles rides the /metrics collector."""
    from gubernator_tpu.config import BehaviorConfig, Config
    from gubernator_tpu.service import V1Instance
    from gubernator_tpu.utils.metrics import build_registry

    engine = DecisionEngine(capacity=1024, clock=frozen_clock)
    inst = V1Instance(Config(behaviors=BehaviorConfig()), engine)
    try:
        reg = build_registry(inst)
        sample = reg.get_sample_value("gubernator_jit_recompiles_total")
        assert sample is not None
        assert sample == jit_recompile_guard.count()
    finally:
        inst.close()


def _algo_columns(n, algo, start=0, name="fz"):
    return dict(
        keys=[b"%s_%d_%d" % (name.encode(), algo, start + i) for i in range(n)],
        algo=np.full(n, algo, dtype=np.int32),
        behavior=np.zeros(n, dtype=np.int32),
        hits=np.ones(n, dtype=np.int64),
        limit=np.full(n, 1000, dtype=np.int64),
        duration=np.full(n, 60_000, dtype=np.int64),
        burst=np.full(n, 1000, dtype=np.int64),
    )


def test_fused_step_soak_zero_recompiles_both_algorithms(
    frozen_clock, jit_recompile_guard
):
    """ISSUE 10 satellite: the decision step (single dispatch per
    batch) stays recompile-flat across every wire
    width and BOTH algorithms — token and leaky exercise different
    jnp.where arms of the same compiled program, so a flat count here
    pins that the algorithm mix cannot fork the compile cache."""
    engine = DecisionEngine(
        capacity=8192, clock=frozen_clock, max_kernel_width=1024
    )
    engine.warmup(max_width=1024)

    jit_recompile_guard.snapshot()
    for round_no in range(2):
        for width in (1, 64, 65, 500, 1000, 1024):
            for algo in (0, 1):
                engine.apply_columnar(
                    **_algo_columns(
                        width, algo, start=round_no * 5_000 + width
                    )
                )
    jit_recompile_guard.assert_flat("fused-step width x algorithm soak")


def test_sharded_psum_merge_soak_zero_recompiles(
    frozen_clock, jit_recompile_guard
):
    """Review regression (ISSUE 10): the psum-merge program universe
    — every pow2 (n_pad, width) pair with width <= n_pad <=
    pad(n_shards*width), WITH the serve path's input shardings — is
    warmed by ShardedDecisionEngine.warmup; arbitrary whole-batch
    sizes then serve with a flat compile count (a host-committed
    warmup dummy used to warm a program the serve path never hit)."""
    from gubernator_tpu.parallel.sharded_engine import ShardedDecisionEngine

    engine = ShardedDecisionEngine(
        shard_capacity=1024, clock=frozen_clock
    )
    if not engine._use_psum_merge:
        pytest.skip("psum merge disabled on this mesh")
    engine.warmup(max_width=256)
    assert engine.dispatches_total == 0  # warmup restores the counter

    jit_recompile_guard.snapshot()
    for n in (1, 57, 100, 200, 250, 256):
        engine.apply_columnar(
            **_columns(n, start=n * 13, name="psmk")
        )
    jit_recompile_guard.assert_flat("sharded psum-merge width soak")
