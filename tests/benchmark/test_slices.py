"""run.py's per-slice line on made-up RPCs: no daemon, no jax."""

import numpy as np
import run as harness


def test_slices_bin_by_completion_and_read_rate_p50_p95():
    t0 = 1000.0
    # 25 s window, 10-item RPCs: 100 complete in the first slice at
    # 20 ms, 50 in the second at 40 ms, 10 in the last (5 s long) at 30 ms
    done = np.concatenate([
        np.linspace(0.1, 9.9, 100), np.linspace(10.1, 19.9, 50),
        np.linspace(20.1, 24.9, 10),
    ])
    took = np.concatenate([np.full(100, 0.020), np.full(50, 0.040),
                           np.full(10, 0.030)])
    took[0] = 0.5  # one slow RPC: the p95 of 100 does not see it
    sent_at = t0 + done - took
    rows = harness.slices_of(sent_at, took, t0, 25.0, 10, [])
    assert [r["from_s"] for r in rows] == [0.0, 10.0, 20.0]
    assert [r["rpcs"] for r in rows] == [100, 50, 10]
    assert [r["decisions_per_s"] for r in rows] == [100.0, 50.0, 20.0]
    assert [round(r["rpc_p50_ms"], 6) for r in rows] == [20.0, 40.0, 30.0]
    assert round(rows[0]["rpc_p95_ms"], 6) == 20.0
    assert sum(r["rpcs"] for r in rows) == done.size
    assert all(r["pauses_ms"] == [] for r in rows)


def test_slices_carry_the_pauses_that_began_in_them():
    t0 = 50.0
    gaps = [(t0 + 3.2, 0.12), (t0 + 14.0, 0.3), (t0 + 14.5, 0.06)]
    took = np.full(4, 0.01)
    sent_at = t0 + np.array([1.0, 2.0, 12.0, 13.0])
    rows = harness.slices_of(sent_at, took, t0, 20.0, 1, gaps)
    assert [r["pauses_ms"] for r in rows] == [[120], [300, 60]]


def test_an_empty_slice_reports_its_rate_and_no_latency():
    rows = harness.slices_of(np.array([100.5]), np.array([0.1]), 100.0, 20.0,
                             1000, [])
    assert rows[0]["rpcs"] == 1 and rows[0]["decisions_per_s"] == 100.0
    assert rows[1]["rpcs"] == 0 and rows[1]["decisions_per_s"] == 0.0
    assert "rpc_p50_ms" not in rows[1]
