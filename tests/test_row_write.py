"""The step's write by rows (ops/row_write.py): one Pallas kernel that
DMAs each 128-word row a step touched, once, over every state column.

The oracle is `column.at[slot].set(word, mode="drop")`, on random
column words so that a word written to the wrong place cannot hide
behind zeros.  The kernel runs here in Pallas' interpreter; what the
chip's compiler makes of it is pinned in tests/test_state_access.py,
and what it costs is read on the chip alone
(scripts/probe_state_access.py)."""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gubernator_tpu.ops import bucket_kernel as bk
from gubernator_tpu.ops import row_write

NOW = 1_700_000_000_000
# the rule's edge at 64 lanes: the smallest table the row write takes
EDGE_ROWS = 8192 * 64


def random_columns(rows: int, seed: int = 0) -> tuple:
    """Twelve columns with `BucketState`'s dtypes, random words."""
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.integers(0, 2**31 - 1, size=rows).astype(c.dtype))
        for c in bk.make_state(1)
    )


def padded(live, width: int, rows: int) -> np.ndarray:
    """Sorted unique slots, then distinct ascending out-of-range padding
    (what every host packer sends)."""
    live = np.unique(np.asarray(live, dtype=np.int64)).astype(np.int32)
    pad = np.arange(rows, rows + width - len(live), dtype=np.int32)
    return np.concatenate([live, pad])


def live_slots(width: int, rows: int) -> np.ndarray:
    """Lanes sharing one row (a whole row, and a run across a row
    boundary), the first and the last row's end words, the rest
    scattered; a quarter of the width left as padding."""
    rng = np.random.default_rng(width)
    fixed = [0, 1, 127, 128, rows - 128, rows - 2, rows - 1]
    fixed += list(range(4096, 4096 + 128))[: width // 4]
    fixed += list(range(9000 - 5, 9000 + 5))
    rest = rng.choice(rows, size=width, replace=False)
    return np.unique(np.concatenate([fixed, rest]))[: width * 3 // 4]


@pytest.mark.parametrize("width", [64, 256])
def test_the_row_write_writes_the_words_the_scatter_writes(width):
    columns = random_columns(EDGE_ROWS)
    slot = padded(live_slots(width, EDGE_ROWS), width, EDGE_ROWS)
    rng = np.random.default_rng(1)
    words = tuple(
        jnp.asarray(rng.integers(0, 2**31 - 1, size=width).astype(c.dtype))
        for c in columns
    )
    got = jax.jit(
        functools.partial(row_write.write_rows, interpret=True)
    )(columns, jnp.asarray(slot), words)
    assert len(got) == 12
    for col, new, w in zip(columns, got, words):
        want = col.at[jnp.asarray(slot)].set(w, mode="drop")
        assert new.dtype == col.dtype
        np.testing.assert_array_equal(np.asarray(new), np.asarray(want))


def test_every_lane_padding_writes_nothing():
    columns = random_columns(EDGE_ROWS)[:2]
    slot = padded([], 64, EDGE_ROWS)
    words = tuple(jnp.zeros(64, c.dtype) for c in columns)
    got = row_write.write_rows(columns, jnp.asarray(slot), words,
                               interpret=True)
    for col, new in zip(columns, got):
        np.testing.assert_array_equal(np.asarray(new), np.asarray(col))


def test_a_row_has_one_writer_its_first_lane():
    rows = 1024
    slot = jnp.asarray(padded([3, 5, 127, 128, 300, 1023], 8, rows))
    writer, dst = row_write.writers(slot, rows)
    # lanes 0-2 share row 0, lane 3 starts row 1, 4 row 2, 5 row 7
    np.testing.assert_array_equal(np.asarray(writer), [0, 0, 0, 3, 4, 5, 8, 8])
    np.testing.assert_array_equal(np.asarray(dst), [0, -1, -1, 1, 2, 7, -1, -1])


@pytest.mark.parametrize(
    "rows,lanes,platform,form",
    [
        # under 8,192 rows a lane: the pass, on every platform
        (EDGE_ROWS - 128, 64, "tpu", "pass"),
        (2_000_000, 1024, "tpu", "pass"),
        (100_000_000, 16384, "tpu", "pass"),
        (4, 64, "cpu", "pass"),
        # at or above it, whole rows, lowered for a TPU: the rows
        (EDGE_ROWS, 64, "tpu", "rows"),
        (100_000_000, 64, "tpu", "rows"),
        (100_000_000, 4096, "tpu", "rows"),
        (100_000_000, 8192, "tpu", "rows"),
        # otherwise the loop: another platform, or a partial last row
        # (the mesh's 25 M rows a shard)
        (100_000_000, 1024, "cpu", "loop"),
        (EDGE_ROWS, 64, "cpu", "loop"),
        (25_000_000, 256, "tpu", "loop"),
        (EDGE_ROWS + 1, 64, "tpu", "loop"),
    ],
)
def test_the_write_form_follows_the_shapes_and_the_platform(
    rows, lanes, platform, form
):
    assert bk._scatter_form(rows, lanes, platform) == form
    # the clear and the restore have no row form: hinted for the pass
    hinted = form == "pass"
    assert bk._scatter_hints(rows, lanes) == dict(
        indices_are_sorted=hinted, unique_indices=hinted
    )


# -- a step with the row write answers as the same step with the pass --------


def fresh(fn):
    """jax caches a trace by the function traced: a form set after the
    first trace of `fn` itself would never be seen."""
    return lambda *args: fn(*args)


def collapsed_pin(rng, width, cap, n):
    s = np.unique(rng.choice(cap, size=max(1, n // 2), replace=False))
    s = s.astype(np.int32)
    counts = rng.integers(1, 3, len(s)).astype(np.int64)
    seg = np.repeat(np.arange(len(s), dtype=np.int32), counts)[:width]
    pos = np.concatenate([np.arange(c, dtype=np.int32) for c in counts])
    k = len(s)
    fields = (
        rng.integers(0, 2, k), np.zeros(k, np.int64), np.ones(k, np.int64),
        np.full(k, 100, np.int64), np.full(k, 60_000, np.int64),
        np.full(k, 100, np.int64), np.zeros(k, np.int64),
        np.zeros(k, np.int64),
    )
    return bk.pack_collapsed_host(
        width, NOW, cap, s, counts, fields, seg, pos[:width]
    )


def packed_pin(rng, width, cap, n):
    s = np.unique(rng.choice(cap, size=n, replace=False)).astype(np.int32)
    n = len(s)
    return bk.pack_batch_host(
        width, NOW, cap, s,
        rng.integers(0, 2, n), np.zeros(n, np.int64),
        rng.integers(0, 3, n), np.full(n, 100, np.int64),
        np.full(n, 60_000, np.int64), np.full(n, 100, np.int64),
        np.zeros(n, np.int64), np.zeros(n, np.int64),
    )


STEPS = {
    "collapsed_step": (bk._collapsed_step_core, collapsed_pin, False),
    "fused_step": (bk._fused_step_core, packed_pin, False),
    "multi_fused_step": (bk._fused_step_core, packed_pin, True),
}


@pytest.mark.parametrize("name", sorted(STEPS))
def test_a_step_with_the_row_write_answers_as_with_the_pass(
    name, monkeypatch
):
    """The step's own write, lowered here as a TPU would lower it: the
    kernel (interpreted) in place of XLA's scatter."""
    core, make_pin, scanned = STEPS[name]
    cap, width = 128 * 24, 64
    rng = np.random.default_rng(len(name))
    pins = np.stack([make_pin(rng, width, cap, 40) for _ in range(2)])
    pin = jnp.asarray(pins if scanned else pins[0])
    state = bk.BucketState(*random_columns(cap, seed=7))
    program = (
        (lambda c: lambda st, p: jax.lax.scan(fresh(c), st, p))(core)
        if scanned
        else core
    )

    def run(form):
        monkeypatch.setattr(bk, "_scatter_form", lambda *a: form)
        return jax.jit(fresh(program))(state, pin)

    monkeypatch.setattr(
        jax.lax, "platform_dependent",
        lambda *args, tpu, default: functools.partial(
            row_write.write_rows, interpret=True)(*args),
    )
    rows_state, rows_out = run("rows")
    pass_state, pass_out = run("pass")
    np.testing.assert_array_equal(np.asarray(rows_out), np.asarray(pass_out))
    for a, b in zip(rows_state, pass_state):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- the counter the benchmark reads ------------------------------------------


def test_the_engine_counts_the_dispatches_that_write_by_rows(monkeypatch):
    """`device.counters.row_write_dispatches_total` moves with each step
    dispatch whose program the rule gives the rows, and the benchmark's
    `step.row_write_pct` reads it over every dispatch; `probes` names
    the form at each width of the ladder."""
    from gubernator_tpu import RateLimitReq
    from gubernator_tpu.core import device_info
    from gubernator_tpu.core.engine import DecisionEngine

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from lib.manifest import read_metric

    with open(os.path.join(
            bench, "layer_metrics", "step.row_write_pct.json")) as fh:
        metric = json.load(fh)

    eng = DecisionEngine(capacity=1024, max_kernel_width=256)
    assert eng.probes["write_form"].reason == (
        "64:pass 128:pass 256:pass"
    )

    def serve(n):
        eng.get_rate_limits([
            RateLimitReq(name="rw", unique_key=f"k{i}", hits=1, limit=10,
                         duration=60_000)
            for i in range(n)
        ])

    def snapshot():
        eng._flush_pump()
        return {"device": device_info.describe(eng)}

    before = snapshot()
    serve(3)
    after = snapshot()
    assert after["device"]["counters"]["row_write_dispatches_total"] == 0
    assert read_metric(metric, {"vars_before": before,
                                "vars_after": after}) == 0.0

    # the same engine, told it serves a TPU table the rule gives the rows
    monkeypatch.setattr(bk, "_SCATTER_PASS_ROWS_PER_LANE", 1)
    eng._platform = "tpu"
    eng._record_write_forms()
    assert eng.probes["write_form"].reason == "64:rows 128:rows 256:rows"
    before = snapshot()
    serve(3)
    serve(70)
    after = snapshot()
    moved = (after["device"]["counters"]["row_write_dispatches_total"]
             - before["device"]["counters"]["row_write_dispatches_total"])
    steps = (after["device"]["counters"]["dispatches_total"]
             - before["device"]["counters"]["dispatches_total"])
    assert moved >= 2 and moved <= steps
    assert read_metric(metric, {"vars_before": before,
                                "vars_after": after}) == pytest.approx(
        100.0 * moved / steps)
    # a program without the counter (the parent's): nothing, no raise
    for doc in (before, after):
        del doc["device"]["counters"]["row_write_dispatches_total"]
    assert read_metric(metric, {"vars_before": before,
                                "vars_after": after}) is None
