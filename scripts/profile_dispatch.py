"""Dispatch-overhead experiments for the attached accelerator backend.

Answers, with real numbers:
  A. blocking round-trip latency of a tiny kernel (sync floor)
  B. async enqueue throughput (ops/sec) when chaining without blocking
  C. whether a FUSED donated read-modify-write program pays
     O(capacity) copy-insertion (step time vs capacity)
  D. pipelined throughput of the packed 4-op step
     (h2d + compute + scatter + async d2h) at several batch widths
Prints one JSON dict.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("GUBERNATOR_TPU_X64", "1")
import gubernator_tpu  # noqa: F401
import jax
import jax.numpy as jnp
import numpy as np

res: dict = {}


def report(k, v):
    res[k] = v
    print(f"{k}: {v}", file=sys.stderr, flush=True)


def main():
    dev = jax.devices()[0]
    report("platform", dev.platform)

    x = jax.device_put(jnp.ones(8, jnp.float32), dev)

    @jax.jit
    def tiny(a):
        return a + 1

    tiny(x).block_until_ready()

    # A. sync round-trip floor
    t0 = time.perf_counter()
    for _ in range(50):
        tiny(x).block_until_ready()
    report("sync_roundtrip_ms", (time.perf_counter() - t0) / 50 * 1e3)

    # B. async chained enqueue rate
    t0 = time.perf_counter()
    o = x
    for _ in range(200):
        o = tiny(o)
    o.block_until_ready()
    report("async_chain_op_ms", (time.perf_counter() - t0) / 200 * 1e3)

    # C. fused donated RMW: gather+math+scatter in ONE program, donated
    # state, at two capacities — if time scales with capacity, XLA's
    # copy-insertion is cloning the state.
    B = 8192

    def fused(state, slot, hits):
        g = [a.at[slot].get(mode="fill", fill_value=0,
                            indices_are_sorted=True, unique_indices=True)
             for a in state]
        upd = [v + hits.astype(v.dtype) for v in g]
        return [a.at[slot].set(v, mode="drop", indices_are_sorted=True,
                               unique_indices=True)
                for a, v in zip(state, upd)]

    fused_j = jax.jit(fused, donate_argnums=(0,))
    rng = np.random.default_rng(0)
    for cap in (1 << 17, 1 << 21):
        state = [jax.device_put(jnp.zeros(cap, jnp.int32), dev)
                 for _ in range(19)]
        slot = jax.device_put(
            jnp.asarray(np.sort(rng.choice(cap, B, replace=False)).astype(np.int32)), dev)
        hits = jax.device_put(jnp.ones(B, jnp.int32), dev)
        state = fused_j(state, slot, hits)  # warm
        t0 = time.perf_counter()
        for _ in range(20):
            state = fused_j(state, slot, hits)
        jax.block_until_ready(state)
        report(f"fused_rmw_cap{cap}_ms", (time.perf_counter() - t0) / 20 * 1e3)

    # D. packed pipelined step at several widths: one h2d int32 [15,B],
    # one fused RMW kernel (donated packed state [cap,20]), one packed
    # int32 [5,B] output with async d2h, pipeline depth 3.
    cap = 1 << 21

    def step(stmat, pin):
        slot = pin[0]
        rows = stmat.at[slot].get(mode="fill", fill_value=0,
                                  indices_are_sorted=True, unique_indices=True)
        upd = rows + pin[3][:, None]
        newm = stmat.at[slot].set(upd, mode="drop", indices_are_sorted=True,
                                  unique_indices=True)
        out = jnp.stack([upd[:, 0], upd[:, 1], upd[:, 2], upd[:, 3], upd[:, 4]])
        return newm, out

    step_j = jax.jit(step, donate_argnums=(0,))
    for B2 in (1024, 8192, 32768):
        stmat = jax.device_put(jnp.zeros((cap, 20), jnp.int32), dev)
        host_in = np.zeros((15, B2), np.int32)
        host_in[0] = np.sort(rng.choice(cap, B2, replace=False)).astype(np.int32)
        host_in[3] = 1
        stmat, out = step_j(stmat, jnp.asarray(host_in))  # warm
        np.asarray(out)
        pend = []
        t0 = time.perf_counter()
        NIT = 50
        for _ in range(NIT):
            stmat, out = step_j(stmat, jnp.asarray(host_in))
            out.copy_to_host_async()
            pend.append(out)
            if len(pend) > 3:
                np.asarray(pend.pop(0))
        for p in pend:
            np.asarray(p)
        dt = (time.perf_counter() - t0) / NIT
        report(f"packed_step_B{B2}_ms", dt * 1e3)
        report(f"packed_step_B{B2}_decs_per_s", B2 / dt)

    print(json.dumps(res))


if __name__ == "__main__":
    main()
