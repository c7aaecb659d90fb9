"""Host interning ceiling (VERDICT r3 #5): native table schedule()
throughput vs capacity — 131k / 8M / 100M slots — for both the
miss/insert and the steady-state hit case, plus the share of a full
packed-step dispatch the intern pass costs at batch 8192.

Prints one JSON line; PERF.md carries the table.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("GUBERNATOR_TPU_X64", "1")

# Host-side benchmark: the intern table is pure C++/numpy, so pin jax
# to the CPU and leave any chip to whoever needs it.
from gubernator_tpu.platform_guard import force_cpu_platform

force_cpu_platform(1)

import numpy as np

from gubernator_tpu.core.native import make_intern_table

B = 8192
res = {}

for cap in (1 << 17, 1 << 23, 100_000_000):
    table = make_intern_table(cap)
    if not hasattr(table, "schedule"):
        res[f"cap{cap}"] = "python-fallback"
        continue
    # Fill to ~60% of capacity or 2M keys, whichever is smaller
    # (bounded run time; probe batches then measure against the
    # populated table).
    fill = min(int(cap * 0.6), 2_000_000)
    t_fill0 = time.perf_counter()
    for lo in range(0, fill, B):
        keys = [b"ik%d" % i for i in range(lo, min(lo + B, fill))]
        table.schedule(keys, 1_000_000)
    fill_dt = time.perf_counter() - t_fill0
    res[f"cap{cap}_fill_keys_per_s"] = round(fill / fill_dt, 0)

    # Steady-state HIT case: re-schedule known keys.
    rng = np.random.default_rng(0)
    batches = [
        [b"ik%d" % i for i in rng.integers(0, fill, B)] for _ in range(8)
    ]
    t0 = time.perf_counter()
    n_it = 24
    for i in range(n_it):
        table.schedule(batches[i % 8], 2_000_000)
    hit_dt = (time.perf_counter() - t0) / n_it
    res[f"cap{cap}_hit_us_per_key"] = round(hit_dt / B * 1e6, 3)
    res[f"cap{cap}_hit_keys_per_s"] = round(B / hit_dt, 0)

# Intern share of the serving step at the default bench shape:
# measured packed-step wall (BENCH/PROFILE artifacts) vs intern pass.
# Only meaningful when the NATIVE table was measured — the Python
# fallback records no timing and must not masquerade as free.
if "cap131072_hit_us_per_key" in res:
    intern_ms = res["cap131072_hit_us_per_key"] * B / 1e3
    res["intern_ms_per_8192_batch_cap131072"] = round(intern_ms, 3)

print(json.dumps(res))
