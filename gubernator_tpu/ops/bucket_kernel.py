"""The vectorized bucket-update kernel: one XLA call per request batch.

This is the TPU-native replacement for the reference's entire local
execution engine — the worker-pool channel hop plus the per-key
`tokenBucket`/`leakyBucket` call (reference: gubernator_pool.go:250-336,
algorithms.go:31-516).  Bucket state is a struct-of-arrays in device
memory; a batch of requests is applied as gather → branch-free update
(`jnp.where` chains over the algorithm/behavior flags) → scatter.

Semantics are defined by the scalar spec in
`gubernator_tpu.models.spec` (bit-equivalence is fuzz-tested); see that
module's docstring for the preserved reference quirks.

Duplicate slots within one call are NOT allowed (scatter order would be
unspecified); the engine splits a batch into rounds so each slot appears
at most once per call, which reproduces the reference's per-key
serialization (reference: gubernator_pool.go:19-37) while keeping every
round a single vectorized device step.

`now_ms` is an explicit input — the device never reads time — so frozen
clock conformance tests drive the kernel directly (SURVEY.md §4.5).

Names are part of the measurement.  The benchmark finds a step program
in the device trace by its module name — `jit_` + the name of the
function handed to `jax.jit` — against the glob patterns of
`benchmarks/layer_metrics/step.kernel_us_per_dispatch.json` (and
`mesh.step_us_per_dispatch.json` for the sharded programs):
`_fused_step_core`, `_multi_fused_core`, `_uniform_step_core`,
`_multi_uniform_core`, `_collapsed_step_core` here, `local_*_fused` /
`flat_*_fused` in parallel/sharded_engine.py.  A step that is renamed
or rewritten under another name turns those metrics to nothing;
tests/test_step_names.py pins every dispatchable step to a pattern.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from gubernator_tpu.ops import row_write
from gubernator_tpu.types import Algorithm, Behavior, Status

_I64 = jnp.int64
_I32 = jnp.int32
_F64 = jnp.float64

# numpy scalars (not jnp): they inline as jaxpr literals instead of
# materializing device constants at import.
_OVER = np.int32(int(Status.OVER_LIMIT))
_UNDER = np.int32(int(Status.UNDER_LIMIT))


class BucketState(NamedTuple):
    """Struct-of-arrays bucket state, 48 bytes/slot (VERDICT r4 #6;
    the round-4 layout was 19 plain arrays at 73 B/slot — 7.6 GB at
    100 M keys).

    The fields of TokenBucketItem/LeakyBucketItem (reference:
    store.go:29-43) plus cache-item metadata (reference: cache.go:30-42):
    `t0` = CreatedAt (token) / UpdatedAt (leaky); expire/invalid mirror
    CacheItem.ExpireAt / InvalidAt.

    64-bit logical fields travel as (hi: int32, lo: uint32) word pairs
    because the TPU runtime has no native 64-bit arrays (JAX's x64 shim
    would otherwise split/recombine every capacity-sized array at the
    jit boundary — O(state) per step).  Three packings shrink the slot:

    - `meta` folds occupied (bit 0), the algorithm (bit 1, normalized
      to {0,1} — every non-zero wire value means LEAKY_BUCKET, the
      documented divergence for out-of-enum algorithm ints), the
      sticky token status (bits 2-3), and the HI WORDS of t0 and
      invalid_at (11 bits each at bits 4-14 / 15-25): millisecond
      timestamps fit 43 bits until the year 2248, so their hi words
      fit 11.  Values clamp to [0, 2^43) at encode.
    - `hi2` likewise folds the expire and duration hi words (duration
      clamps at 2^43 ms ≈ 278 years; negative durations clamp to 0 —
      both documented divergences at absurd inputs only).
    - `rem` merges the token remaining (int64 words) and the leaky
      32.32 fixed-point remaining (whole:int32, frac:uint32): a slot
      runs one algorithm at a time, so the pair is interpreted through
      the meta algo bit (`models/spec.py quantize_remf` defines the
      leaky quantization; bit-equality stays fuzz-pinned either way).
    """

    meta: jax.Array  # int32 — see docstring bit layout
    hi2: jax.Array  # int32 — expire hi (bits 0-10) | duration hi (11-21)
    t0_lo: jax.Array  # uint32
    expire_lo: jax.Array  # uint32
    invalid_lo: jax.Array  # uint32
    duration_lo: jax.Array  # uint32
    limit_hi: jax.Array  # int32
    limit_lo: jax.Array  # uint32
    rem_hi: jax.Array  # int32   (token int64 hi / leaky whole)
    rem_lo: jax.Array  # uint32  (token int64 lo / leaky fraction)
    burst_hi: jax.Array  # int32
    burst_lo: jax.Array  # uint32


# Millisecond-timestamp clamp bound for the packed 11-bit hi words.
TS_CLAMP_MAX = (1 << 43) - 1
_HI11 = 0x7FF


class BatchInput(NamedTuple):
    """One request batch, shape [B] per field.

    Padding lanes MUST use distinct, ascending, out-of-range slots
    (capacity + lane) — the kernel declares its gather/scatter indices
    sorted and unique, and -1 padding would both defeat the
    `slot < capacity` mask and violate the uniqueness contract.

    `greg_duration`/`greg_expire` are host-precomputed per request when
    DURATION_IS_GREGORIAN is set (reference: interval.go:84-148 — the
    calendar math never runs on device)."""

    slot: jax.Array  # int32; padding = capacity + lane (see above)
    algo: jax.Array  # int32
    behavior: jax.Array  # int32
    hits: jax.Array  # int64
    limit: jax.Array  # int64
    duration: jax.Array  # int64
    burst: jax.Array  # int64
    greg_duration: jax.Array  # int64
    greg_expire: jax.Array  # int64


_U32 = jnp.uint32


def make_state(capacity: int) -> BucketState:
    """Allocate an empty state of `capacity` slots.

    Every field gets its own buffer — every step donates the whole
    state, and aliased buffers cannot be donated twice."""

    def z(dt):
        return jnp.zeros((capacity,), dtype=dt)

    return BucketState(
        meta=z(_I32),
        hi2=z(_I32),
        t0_lo=z(_U32),
        expire_lo=z(_U32),
        invalid_lo=z(_U32),
        duration_lo=z(_U32),
        limit_hi=z(_I32),
        limit_lo=z(_U32),
        rem_hi=z(_I32),
        rem_lo=z(_U32),
        burst_hi=z(_I32),
        burst_lo=z(_U32),
    )


def clamp_ts(v):
    """Clamp a millisecond value into the packed-hi-word range (works
    on jnp and np arrays alike)."""
    return jnp.clip(v, 0, TS_CLAMP_MAX)


def pack_meta(occ, algo_norm, status, t0c, invc):
    """occupied/algo/status/t0/invalid → the meta word (values already
    normalized/clamped; t0c/invc int64 in [0, 2^43))."""
    return (
        occ.astype(_I32)
        | (algo_norm.astype(_I32) << 1)
        | ((status & 3).astype(_I32) << 2)
        | ((t0c >> 32).astype(_I32) << 4)
        | ((invc >> 32).astype(_I32) << 15)
    )


def meta_occupied(meta):
    return (meta & 1) != 0


def meta_algo(meta):
    return ((meta >> 1) & 1).astype(_I32)


def meta_status(meta):
    return ((meta >> 2) & 3).astype(_I32)


def meta_t0(meta, t0_lo):
    return (((meta >> 4) & _HI11).astype(_I64) << 32) | t0_lo.astype(_I64)


def meta_invalid(meta, inv_lo):
    return (((meta >> 15) & _HI11).astype(_I64) << 32) | inv_lo.astype(_I64)


def pack_hi2(expc, durc):
    """expire/duration (clamped int64) → the hi2 word."""
    return ((expc >> 32).astype(_I32)) | (((durc >> 32).astype(_I32)) << 11)


def hi2_expire(hi2, exp_lo):
    return ((hi2 & _HI11).astype(_I64) << 32) | exp_lo.astype(_I64)


def hi2_duration(hi2, dur_lo):
    return (((hi2 >> 11) & _HI11).astype(_I64) << 32) | dur_lo.astype(_I64)


def pack_state_host(logical: dict) -> dict:
    """Encode logical numpy columns (keys as in `unpack_state_host`,
    with the leaky remaining given as remf_hi/remf_lo words) into the
    packed BucketState field arrays — bulk load/restore paths only."""
    occ = np.asarray(logical["occupied"]).astype(bool)
    algo = (np.asarray(logical["algo"]) != 0).astype(np.int32)
    status = np.asarray(logical["status"]).astype(np.int64)
    t0c = np.clip(np.asarray(logical["t0"]), 0, TS_CLAMP_MAX)
    invc = np.clip(np.asarray(logical["invalid"]), 0, TS_CLAMP_MAX)
    expc = np.clip(np.asarray(logical["expire"]), 0, TS_CLAMP_MAX)
    durc = np.clip(np.asarray(logical["duration"]), 0, TS_CLAMP_MAX)
    meta = (
        occ.astype(np.int32)
        | (algo << 1)
        | ((status & 3).astype(np.int32) << 2)
        | ((t0c >> 32).astype(np.int32) << 4)
        | ((invc >> 32).astype(np.int32) << 15)
    )
    hi2 = ((expc >> 32).astype(np.int32)) | (
        (durc >> 32).astype(np.int32) << 11
    )
    rem64 = np.asarray(logical["remaining"]).astype(np.int64)
    leaky = algo == 1
    rem_hi = np.where(
        leaky, np.asarray(logical["remf_hi"]).astype(np.int32),
        (rem64 >> 32).astype(np.int32),
    )
    rem_lo = np.where(
        leaky, np.asarray(logical["remf_lo"]).astype(np.uint32),
        (rem64 & 0xFFFFFFFF).astype(np.uint32),
    )
    limit64 = np.asarray(logical["limit"]).astype(np.int64)
    burst64 = np.asarray(logical["burst"]).astype(np.int64)
    return {
        "meta": meta,
        "hi2": hi2,
        "t0_lo": (t0c & 0xFFFFFFFF).astype(np.uint32),
        "expire_lo": (expc & 0xFFFFFFFF).astype(np.uint32),
        "invalid_lo": (invc & 0xFFFFFFFF).astype(np.uint32),
        "duration_lo": (durc & 0xFFFFFFFF).astype(np.uint32),
        "limit_hi": (limit64 >> 32).astype(np.int32),
        "limit_lo": (limit64 & 0xFFFFFFFF).astype(np.uint32),
        "rem_hi": rem_hi,
        "rem_lo": rem_lo,
        "burst_hi": (burst64 >> 32).astype(np.int32),
        "burst_lo": (burst64 & 0xFFFFFFFF).astype(np.uint32),
    }


def unpack_state_host(state) -> dict:
    """Decode a full state into logical numpy columns (export /
    checkpoint / inspection — full-state host ops, never the hot
    path).  Keys: occupied, algo, status, t0, invalid, expire,
    duration, limit, remaining (token view), remf_hi/remf_lo (leaky
    words), burst."""
    meta = np.asarray(state.meta)
    hi2 = np.asarray(state.hi2)
    t0_lo = np.asarray(state.t0_lo)
    inv_lo = np.asarray(state.invalid_lo)
    exp_lo = np.asarray(state.expire_lo)
    dur_lo = np.asarray(state.duration_lo)

    def c64(hi, lo):
        return (np.asarray(hi).astype(np.int64) << 32) | np.asarray(
            lo
        ).astype(np.int64)

    rem_hi = np.asarray(state.rem_hi)
    rem_lo = np.asarray(state.rem_lo)
    return {
        "occupied": (meta & 1) != 0,
        "algo": (meta >> 1) & 1,
        "status": (meta >> 2) & 3,
        "t0": (((meta >> 4) & _HI11).astype(np.int64) << 32)
        | t0_lo.astype(np.int64),
        "invalid": (((meta >> 15) & _HI11).astype(np.int64) << 32)
        | inv_lo.astype(np.int64),
        "expire": ((hi2 & _HI11).astype(np.int64) << 32)
        | exp_lo.astype(np.int64),
        "duration": (((hi2 >> 11) & _HI11).astype(np.int64) << 32)
        | dur_lo.astype(np.int64),
        "limit": c64(state.limit_hi, state.limit_lo),
        "remaining": c64(rem_hi, rem_lo),
        "remf_hi": rem_hi,
        "remf_lo": rem_lo,
        "burst": c64(state.burst_hi, state.burst_lo),
    }


def combine_i64(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """(hi:int32, lo:uint32) → int64 (two's complement)."""
    return (hi.astype(_I64) << 32) | lo.astype(_I64)


def split_i64(v: jax.Array) -> tuple[jax.Array, jax.Array]:
    """int64 → (hi:int32, lo:uint32)."""
    return (v >> 32).astype(_I32), (v & 0xFFFFFFFF).astype(_U32)


def trunc_i64(v: jax.Array) -> jax.Array:
    """int64(v) for float64 `v`, truncating toward zero (Go's
    `int64(float64)`), safe on accelerators.

    A v5e's float64 is a pair of float32 (PERF.md, "Bring-up on the
    chip"), and its float→int conversion truncates the two halves
    separately: 3.9999999998 is held as (4.0, -2e-10) and converts to
    4.  `jnp.trunc` is exact there, and converting an integer-valued
    float is too, so truncate in float first.  On the CPU this is the
    same conversion it always was."""
    return jnp.trunc(v).astype(_I64)


def combine_remf(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """(whole:int32, frac:uint32) fixed-point → float64.

    The leaky remaining (float64 in the reference, store.go:36) is
    persisted as 32.32 fixed point: the backend's X64 rewriter cannot
    bitcast f64 words, so the value is quantized to 2^-32 on store.
    The scalar spec applies the identical quantization
    (models/spec.py `quantize_remf`), keeping spec↔kernel bit-equality.
    Whole parts saturate at ±2^31 (far beyond any observable behavior
    in the reference test suite)."""
    return hi.astype(_F64) + lo.astype(_F64) * (2.0**-32)


def split_remf(v: jax.Array) -> tuple[jax.Array, jax.Array]:
    """float64 → (whole:int32, frac:uint32) with floor quantization."""
    w = jnp.floor(v)
    wc = jnp.clip(w, -(2.0**31), 2.0**31 - 1)
    # floor before the conversion: see trunc_i64.
    return wc.astype(_I32), jnp.floor((v - w) * (2.0**32)).astype(_U32)


# How a step writes its slots into a state column: one of three forms,
# chosen by the two shapes the program is compiled for and the platform
# it is lowered for — no setting.
#
# * **pass** — `column.at[slots].set` with `indices_are_sorted` +
#   `unique_indices`: XLA:TPU merges the updates into ONE STREAMING
#   PASS over the whole column, ~12 ps a row of the table whatever the
#   width: 29-69 µs at 1 M rows, where round 2 measured it "~200x
#   faster" than the loop (PERF_HISTORY §4), and 1.18-1.24 ms a column,
#   14.3 ms a twelve-column step, at 100 M rows on a v5e (PERF.md).
# * **loop** — the same scatter without the hints: on a large table an
#   in-place loop over the lanes, padding included: 15 / 109 / 748 µs at
#   64 / 1,024 / 8,192 lanes and 100 M rows, twelve times a step; on a
#   small one XLA finds the pass's price by itself (1 M rows: equal at
#   every width).
# * **rows** — `row_write.write_rows`: each 128-word row the step
#   touched is written once, by one DMA, from one Pallas kernel for all
#   twelve columns: 16.6 / 63.6 / 184.7 µs a column at 64 / 1,024 /
#   4,096 lanes against the loop's 18.1 / 108.1 / 384.7 on one 100 M-row
#   column of a v5e (scripts/probe_state_access.py; PERF.md §6).
#
# The pass where `rows < 8,192 × lanes` — the cheaper form, or within
# 3 µs of the loop, at all twelve points of 1 M-100 M rows × 64-8,192
# lanes on a v5e (scripts/probe_state_access.py; PERF.md §6) —
# which is every table of the tests and of round 2.  Above it the rows
# where the column is whole rows (`rows % 128 == 0`) and the program is
# lowered for a TPU (`lax.platform_dependent`: the kernel is traced
# everywhere and lowered there alone), the loop otherwise.  Gathers are
# no pass in either form (15-31 µs) and keep the hints.
_SCATTER_PASS_ROWS_PER_LANE = 8192


def _scatter_form(rows: int, lanes: int, platform: str = "tpu") -> str:
    """"pass", "rows" or "loop": how a step writes `lanes` sorted,
    unique slots into a column of `rows` rows, lowered for `platform`."""
    # guberlint: ok trace — two shapes and a platform's name: static under trace
    if rows < _SCATTER_PASS_ROWS_PER_LANE * lanes:
        return "pass"
    # guberlint: ok trace — two shapes and a platform's name: static under trace
    if rows % row_write.ROW == 0 and platform == "tpu":
        return "rows"
    return "loop"


def _scatter_hints(rows: int, lanes: int) -> dict:
    """Keyword hints for `column.at[slots].set(...)`, `slots` sorted
    and unique: the pass or the loop of `_scatter_form` (the clear and
    the restore have no row form)."""
    hinted = _scatter_form(rows, lanes) == "pass"
    return dict(indices_are_sorted=hinted, unique_indices=hinted)


# guberlint: shapes meta [capacity] fixed at engine build; slots [C], C in the pow2 clear ladder (warmup)
def _clear_occupied_impl(meta: jax.Array, slots: jax.Array) -> jax.Array:
    """Mark evicted slots unoccupied (host eviction executed on device).

    Split out of the apply kernel so the compile cache is one shape per
    clear width instead of a (batch width × clear width) matrix —
    eviction bursts then never trigger apply-kernel recompiles.
    Padding lanes use distinct ascending out-of-range slots.  With the
    packed layout this is a sparse read-modify-write of the meta word
    (clear bit 0).  The gather touches O(clears) cells; the scatter
    does too only where `_scatter_hints` withholds the hints — with
    them it is a pass over the meta column."""
    s = jnp.sort(slots)
    cur = meta.at[s].get(
        mode="fill", fill_value=0, indices_are_sorted=True,
        unique_indices=True,
    )
    return meta.at[s].set(
        cur & ~1, mode="drop", **_scatter_hints(meta.shape[0], s.shape[0])
    )


# Donated: write-only scatter, compiles in place (no occupancy-array
# copy).  Callers must treat the input buffer as consumed.  Inside
# shard_map/jit tracing use `_clear_occupied_impl` (inner donation has
# no effect there).
clear_occupied = jax.jit(_clear_occupied_impl, donate_argnums=(0,))


def _apply_core(state: BucketState, slot: jax.Array, *args):
    """gather → update → scatter in ONE program: the body of the
    packed and the uniform step.  With the state donated it must
    compile in place — `fused_step_ok` checks that it does."""
    vals, resp_status, resp_rem, resp_reset = _compute_update(
        state, slot, *args
    )
    new_state = _scatter_values(state, slot, vals)
    return new_state, resp_status, resp_rem, resp_reset


class GatheredSlots(NamedTuple):
    """Raw per-lane state words after the gather — the packed column
    values for each request lane's slot, still encoded (meta/hi2 bit
    packings, hi/lo word pairs).  Shape [B] per field.

    This is the seam between the two halves of the decision step:
    `gather_slots` produces it (one sorted/unique gather per column)
    and `update_lanes` is the math over it."""

    meta: jax.Array  # int32
    hi2: jax.Array  # int32
    t0_lo: jax.Array  # uint32
    expire_lo: jax.Array  # uint32
    invalid_lo: jax.Array  # uint32
    duration_lo: jax.Array  # uint32
    limit_hi: jax.Array  # int32
    limit_lo: jax.Array  # uint32
    rem_hi: jax.Array  # int32
    rem_lo: jax.Array  # uint32
    burst_hi: jax.Array  # int32
    burst_lo: jax.Array  # uint32


def gather_slots(state: BucketState, slot: jax.Array) -> GatheredSlots:
    """Gather the raw state words for slot-sorted lanes (fill 0 for
    out-of-range padding lanes).  Field order tracks BucketState (the
    gather zips the two)."""

    def g(arr):
        return arr.at[slot].get(
            mode="fill",
            fill_value=0,
            indices_are_sorted=True,
            unique_indices=True,
        )

    return GatheredSlots(*(g(arr) for arr in state))


def _compute_update(
    state: BucketState,
    slot: jax.Array,  # int32 [B] SORTED ascending, unique; padding = cap+i
    r_algo: jax.Array,
    r_beh: jax.Array,
    r_hits: jax.Array,
    r_limit: jax.Array,
    r_dur: jax.Array,
    r_burst: jax.Array,
    r_gdur: jax.Array,
    r_gexp: jax.Array,
    now: jax.Array,
):
    """The READ-ONLY half of the branch-free bucket update over
    slot-sorted lanes: gather → update.  Returns (SlotValues, status,
    remaining, reset_time) with everything in the SORTED lane order."""
    cap = state.meta.shape[0]
    mask = slot < cap
    g = gather_slots(state, slot)
    return update_lanes(
        g, mask, r_algo, r_beh, r_hits, r_limit, r_dur, r_burst,
        r_gdur, r_gexp, now,
    )


def rate_int(D: jax.Array, L: jax.Array, finite: jax.Array) -> jax.Array:
    """int64(D / L) — the leaky rate in whole ms per token, as the
    reference computes it (a float64 quotient truncated toward zero;
    0 where the conceptual rate is +inf) — by exact integer division.

    An accelerator's float64 is not IEEE double (a v5e emulates it as
    a pair of float32: ~48 mantissa bits, and its float→int conversion
    truncates the two halves separately), so `(D / L).astype(int64)`
    came out one too large there for quotients above 2^24 with a
    fractional part — 7 per 30 days is one (PERF.md, "Bring-up on the
    chip").  Truncating integer division is exact on every backend and
    equals the truncated IEEE quotient whenever |D| < 2^52 (an exact
    quotient is representable; an inexact one is further than half an
    ulp from the next integer).  `L` must be ≥ 1."""
    return jnp.where(finite, jax.lax.div(D, L), 0)


def update_lanes(
    g: GatheredSlots,
    mask: jax.Array,  # bool [B]: lane in range (padding lanes False)
    r_algo: jax.Array,
    r_beh: jax.Array,
    r_hits: jax.Array,
    r_limit: jax.Array,
    r_dur: jax.Array,
    r_burst: jax.Array,
    r_gdur: jax.Array,
    r_gexp: jax.Array,
    now: jax.Array,
):
    """The branch-free bucket update over already-gathered lanes: the
    pure vector math between gather and scatter, shared by every step
    program (see GatheredSlots)."""
    s_meta = g.meta
    s_occ = meta_occupied(s_meta) & mask
    s_algo = meta_algo(s_meta)
    s_status = meta_status(s_meta)
    s_t0 = meta_t0(s_meta, g.t0_lo)
    s_inv = meta_invalid(s_meta, g.invalid_lo)
    s_hi2 = g.hi2
    s_exp = hi2_expire(s_hi2, g.expire_lo)
    s_dur = hi2_duration(s_hi2, g.duration_lo)
    s_limit = combine_i64(g.limit_hi, g.limit_lo)
    # The merged remaining words: int64 for token slots, 32.32 fixed
    # point for leaky — both views computed, the algo paths pick.
    _rem_hi, _rem_lo = g.rem_hi, g.rem_lo
    s_rem = combine_i64(_rem_hi, _rem_lo)
    s_rem_f = combine_remf(_rem_hi, _rem_lo)
    s_burst = combine_i64(g.burst_hi, g.burst_lo)

    # Normalize the request algorithm to the stored 1-bit domain (see
    # BucketState docstring).
    r_algo = (r_algo != 0).astype(_I32)

    greg = (r_beh & int(Behavior.DURATION_IS_GREGORIAN)) != 0
    rst = (r_beh & int(Behavior.RESET_REMAINING)) != 0

    # Cache-hit check (reference: lrucache.go:112-138): strict
    # `expire_at < now` / non-zero `invalid_at < now` are misses.
    live = s_occ & ~((s_inv != 0) & (s_inv < now)) & (s_exp >= now)
    same = live & (s_algo == r_algo)
    is_tok = r_algo == int(Algorithm.TOKEN_BUCKET)

    p_tok_reset = same & is_tok & rst
    p_tok_ex = same & is_tok & ~rst
    p_leak_ex = same & ~is_tok
    p_tok_new = ~same & is_tok
    p_leak_new = ~same & ~is_tok

    zero64 = jnp.zeros_like(r_limit)

    # ---------------- token bucket, existing item (algorithms.go:79-208)
    limit_changed = s_limit != r_limit
    te_rem0 = jnp.where(
        limit_changed, jnp.maximum(s_rem + (r_limit - s_limit), 0), s_rem
    )
    dur_changed = s_dur != r_dur
    te_new_exp = jnp.where(greg, r_gexp, s_t0 + r_dur)
    te_renew = dur_changed & (te_new_exp <= now)
    te_exp = jnp.where(dur_changed, jnp.where(te_renew, now + r_dur, te_new_exp), s_exp)
    te_created = jnp.where(te_renew, now, s_t0)
    te_rem_store = jnp.where(te_renew, r_limit, te_rem0)

    # Branch chain — priority: query > empty > exact > over > consume
    # (sequential ifs at algorithms.go:173-207).  `te_rem0` is the
    # response snapshot, `te_rem_store` the stored value (they differ
    # only on renewal; see models/spec.py docstring).
    te_q = r_hits == 0
    te_e = (te_rem0 == 0) & (r_hits > 0)
    te_x = te_rem_store == r_hits
    te_o = r_hits > te_rem_store

    te_rem_out = te_rem_store - r_hits  # consume
    te_rem_out = jnp.where(te_o, te_rem_store, te_rem_out)
    te_rem_out = jnp.where(te_x, zero64, te_rem_out)
    te_rem_out = jnp.where(te_e, te_rem_store, te_rem_out)
    te_rem_out = jnp.where(te_q, te_rem_store, te_rem_out)

    te_resp_rem = te_rem_store - r_hits
    te_resp_rem = jnp.where(te_o, te_rem0, te_resp_rem)
    te_resp_rem = jnp.where(te_x, zero64, te_resp_rem)
    te_resp_rem = jnp.where(te_e, te_rem0, te_resp_rem)
    te_resp_rem = jnp.where(te_q, te_rem0, te_resp_rem)

    te_resp_status = jnp.where(
        te_q, s_status, jnp.where(te_e | (~te_x & te_o), _OVER, s_status)
    )
    te_status_store = jnp.where(te_e & ~te_q, _OVER, s_status)

    # ---------------- token bucket, new item (algorithms.go:215-272)
    tn_exp = jnp.where(greg, r_gexp, now + r_dur)
    tn_over = r_hits > r_limit
    tn_rem = jnp.where(tn_over, r_limit, r_limit - r_hits)
    tn_resp_status = jnp.where(tn_over, _OVER, _UNDER)

    # ---------------- leaky bucket shared
    # `rate` = D/L is conceptually +inf when limit<=0 and 0 when D==0
    # (Go divides by zero and carries ±inf); instead of materializing
    # infinities we track the classification with integer masks and
    # only divide safe operands.
    burst_eff = jnp.where(r_burst == 0, r_limit, r_burst)
    limit_pos = r_limit > 0
    lk_D = jnp.where(greg, r_gdur, r_dur)  # rate numerator (ms)
    rate_finite = limit_pos  # else conceptual rate = +inf
    rate_zero = limit_pos & (lk_D == 0)
    lk_L = jnp.where(limit_pos, r_limit, 1)
    lk_rate = jnp.where(
        rate_finite, lk_D.astype(_F64) / lk_L.astype(_F64), 0.0
    )
    lk_rate_i = rate_int(lk_D, lk_L, rate_finite)

    # ---------------- leaky bucket, existing item (algorithms.go:329-448)
    le_rem = jnp.where(rst, burst_eff.astype(_F64), s_rem_f)
    burst_changed = s_burst != burst_eff
    le_rem = jnp.where(
        burst_changed & (burst_eff > trunc_i64(le_rem)),
        burst_eff.astype(_F64),
        le_rem,
    )
    le_eff_dur = jnp.where(greg, r_gexp - now, r_dur)
    le_exp = jnp.where(r_hits != 0, now + le_eff_dur, s_exp)

    elapsed = (now - s_t0).astype(_F64)
    rate_pos = rate_finite & ~rate_zero
    le_leak = jnp.where(
        rate_pos, elapsed / jnp.where(rate_pos, lk_rate, 1.0), 0.0
    )
    # Conceptual leak = +inf (rate==0, elapsed>0) refills to burst
    # (Go: elapsed/0.0 = +Inf; int64(+inf) is platform-defined, so
    # model "huge leak" explicitly instead of casting it).
    leak_inf = rate_zero & (elapsed > 0)
    leak_applies = (trunc_i64(le_leak) > 0) | leak_inf
    le_rem = jnp.where(leak_applies, le_rem + le_leak, le_rem)
    le_rem = jnp.where(leak_inf, burst_eff.astype(_F64), le_rem)
    le_t0 = jnp.where(leak_applies, now, s_t0)
    le_rem = jnp.where(trunc_i64(le_rem) > burst_eff, burst_eff.astype(_F64), le_rem)

    le_rem_i = trunc_i64(le_rem)
    le_rate_i = lk_rate_i
    le_reset0 = now + (r_limit - le_rem_i) * le_rate_i

    # Branch chain — priority: empty > exact > over > query > consume
    # (sequential ifs at algorithms.go:416-447; order differs from token).
    le_e = (le_rem_i == 0) & (r_hits > 0)
    le_x = le_rem_i == r_hits
    le_o = r_hits > le_rem_i
    le_q = r_hits == 0

    le_consume = le_rem - r_hits.astype(_F64)
    le_rem_out = le_consume
    le_rem_out = jnp.where(le_q, le_rem, le_rem_out)
    le_rem_out = jnp.where(le_o, le_rem, le_rem_out)
    le_rem_out = jnp.where(le_x, le_consume, le_rem_out)
    le_rem_out = jnp.where(le_e, le_rem, le_rem_out)

    le_consume_i = trunc_i64(le_consume)
    le_resp_rem = le_consume_i
    le_resp_rem = jnp.where(le_q, le_rem_i, le_resp_rem)
    le_resp_rem = jnp.where(le_o, le_rem_i, le_resp_rem)
    le_resp_rem = jnp.where(le_x, zero64, le_resp_rem)
    le_resp_rem = jnp.where(le_e, le_rem_i, le_resp_rem)

    le_resp_status = jnp.where(
        le_e | (~le_x & le_o), _OVER, _UNDER
    )
    le_reset = now + (r_limit - le_consume_i) * le_rate_i
    le_reset = jnp.where(le_q, le_reset0, le_reset)
    le_reset = jnp.where(le_o, le_reset0, le_reset)
    le_reset = jnp.where(le_x, now + r_limit * le_rate_i, le_reset)
    le_reset = jnp.where(le_e, le_reset0, le_reset)

    # ---------------- leaky bucket, new item (algorithms.go:454-516)
    # Shares lk_rate with the existing-item path (identical formula).
    ln_dur = jnp.where(greg, r_gexp - now, r_dur)
    ln_rate_i = lk_rate_i
    ln_over = r_hits > burst_eff
    ln_rem = burst_eff - r_hits
    ln_resp_rem = jnp.where(ln_over, zero64, ln_rem)
    ln_rem_f = jnp.where(ln_over, 0.0, ln_rem.astype(_F64))
    ln_resp_status = jnp.where(ln_over, _OVER, _UNDER)
    ln_reset = now + (r_limit - ln_resp_rem) * ln_rate_i

    # ---------------- combine paths → responses
    def pick(tok_reset, tok_ex, tok_new, leak_ex, leak_new):
        out = jnp.where(p_leak_new, leak_new, 0)
        out = jnp.where(p_leak_ex, leak_ex, out)
        out = jnp.where(p_tok_new, tok_new, out)
        out = jnp.where(p_tok_ex, tok_ex, out)
        out = jnp.where(p_tok_reset, tok_reset, out)
        return out

    resp_status = pick(_UNDER, te_resp_status, tn_resp_status, le_resp_status, ln_resp_status)
    resp_rem = pick(r_limit, te_resp_rem, tn_rem, le_resp_rem, ln_resp_rem)
    resp_reset = pick(zero64, te_exp, tn_exp, le_reset, ln_reset)

    # ---------------- combine paths → stored state, then scatter
    n_occ = ~p_tok_reset
    n_algo = r_algo
    n_limit = r_limit
    n_rem = pick(zero64, te_rem_out, tn_rem, zero64, zero64)
    n_rem_f = pick(jnp.zeros_like(le_rem), jnp.zeros_like(le_rem), jnp.zeros_like(le_rem), le_rem_out, ln_rem_f)
    # Stored duration: leaky-existing keeps the *raw* request duration
    # (algorithms.go:360) but leaky-new stores the Gregorian remainder
    # (algorithms.go:472,479); token paths store the request duration.
    n_dur = pick(r_dur, r_dur, r_dur, r_dur, ln_dur)
    n_t0 = pick(zero64, te_created, now, le_t0, now)
    n_exp = pick(zero64, te_exp, tn_exp, le_exp, now + ln_dur)
    n_burst = pick(zero64, zero64, zero64, burst_eff, burst_eff)
    n_status = pick(_UNDER, te_status_store, _UNDER, _UNDER, _UNDER)

    vals = SlotValues(
        occ=n_occ,
        algo=n_algo,
        status=n_status,
        limit=n_limit,
        remaining=n_rem,
        rem_f=n_rem_f,
        duration=n_dur,
        t0=n_t0,
        expire=n_exp,
        burst=n_burst,
    )
    return vals, resp_status, resp_rem, resp_reset


class SlotValues(NamedTuple):
    """Per-lane values to store after an update, shape [B] per field
    (combined int64; `encode_slot_values` splits them into hi/lo
    words for the scatter)."""

    occ: jax.Array  # bool
    algo: jax.Array  # int32
    status: jax.Array  # int32
    limit: jax.Array  # int64
    remaining: jax.Array  # int64
    rem_f: jax.Array  # float64 (leaky 32.32 source)
    duration: jax.Array  # int64
    t0: jax.Array  # int64
    expire: jax.Array  # int64
    burst: jax.Array  # int64


class StoredWords(NamedTuple):
    """Per-lane encoded column words to store — field-for-field aligned
    with BucketState so the scatter can zip the two.  Shape [B] per
    field; dtypes are the logical pre-cast ones (the store casts to
    each column's dtype)."""

    meta: jax.Array
    hi2: jax.Array
    t0_lo: jax.Array
    expire_lo: jax.Array
    invalid_lo: jax.Array
    duration_lo: jax.Array
    limit_hi: jax.Array
    limit_lo: jax.Array
    rem_hi: jax.Array
    rem_lo: jax.Array
    burst_hi: jax.Array
    burst_lo: jax.Array


def encode_slot_values(vals: SlotValues) -> StoredWords:
    """Encode computed slot values into the packed column words — the
    pure half of `_scatter_values` (update always clears invalid_at)."""
    algo_norm = (vals.algo != 0).astype(_I32)
    t0c = clamp_ts(vals.t0)
    invc = jnp.zeros_like(t0c)  # updates always clear invalid_at
    expc = clamp_ts(vals.expire)
    durc = clamp_ts(vals.duration)
    meta_v = pack_meta(vals.occ, algo_norm, vals.status, t0c, invc)
    hi2_v = pack_hi2(expc, durc)
    # Merged remaining: token int64 words vs leaky 32.32 words.
    tok_hi, tok_lo = split_i64(vals.remaining)
    remf_hi_v, remf_lo_v = split_remf(vals.rem_f)
    leaky = algo_norm == 1
    limit_hi, limit_lo = split_i64(vals.limit)
    burst_hi, burst_lo = split_i64(vals.burst)
    return StoredWords(
        meta=meta_v,
        hi2=hi2_v,
        t0_lo=t0c & 0xFFFFFFFF,
        expire_lo=expc & 0xFFFFFFFF,
        invalid_lo=jnp.zeros_like(meta_v),
        duration_lo=durc & 0xFFFFFFFF,
        limit_hi=limit_hi,
        limit_lo=limit_lo,
        rem_hi=jnp.where(leaky, remf_hi_v, tok_hi),
        rem_lo=jnp.where(leaky, remf_lo_v, tok_lo),
        burst_hi=burst_hi,
        burst_lo=burst_lo,
    )


def _scatter_values(
    state: BucketState, slot: jax.Array, vals: SlotValues
) -> BucketState:
    """Scatter of computed slot values into the state — the write half
    of every step program.

    `slot` is sorted with distinct out-of-range padding, which is
    dropped.  The form is `_scatter_form`'s: on a small table the
    hinted pass a column; on a large one, lowered for a TPU, the row
    write (`row_write.write_rows`: one DMA a touched row a column, one
    kernel for the twelve), else the unhinted loop over the lanes.

    The steps gather from and write into the same donated buffers.
    Where XLA's copy-insertion answers that by cloning every state
    array (measured once, on an early backend: 18 full-capacity copies,
    ~41 ms at 2 M slots, O(capacity) a batch) a step is unusable, which
    is why the engines compile `fused_step_ok`'s probe at start and, on
    an accelerator, refuse to serve on a no.
    """
    words = tuple(
        w.astype(c.dtype) for c, w in zip(state, encode_slot_values(vals))
    )

    def scatter(hinted: bool):
        def run(columns, slot, words):
            return tuple(
                c.at[slot].set(
                    w, mode="drop", indices_are_sorted=hinted,
                    unique_indices=hinted,
                )
                for c, w in zip(columns, words)
            )

        return run

    form = _scatter_form(state.meta.shape[0], slot.shape[0])
    if form == "rows":
        return BucketState(
            *jax.lax.platform_dependent(
                tuple(state), slot, words,
                tpu=row_write.write_rows, default=scatter(False),
            )
        )
    return BucketState(*scatter(form == "pass")(tuple(state), slot, words))


# ---------------------------------------------------------------------------
# Packed single-transfer step — the serving fast path.
#
# Every device operation — transfer or kernel — carries a fixed
# dispatch cost next to which the HBM/compute time of an 8k-lane step
# is small, so the design reason is fewer transfers and dispatches per
# decision.  The columnar path packs the WHOLE request round into ONE
# int32 [PACKED_IN_ROWS, B] host buffer (one h2d op), runs ONE
# program, and reads back ONE int32 [PACKED_OUT_ROWS, B] buffer.
# Layout:
#
#   row 0      header: [now_hi, now_lo, 0, ...]   (now_ms int64 words)
#   row 1      slot    (int32; sorted ascending; padding = cap + lane)
#   row 2      algo    row 3   behavior
#   rows 4-5   hits    rows 6-7   limit     rows 8-9  duration
#   rows 10-11 burst   rows 12-13 greg_dur  rows 14-15 greg_exp
#   (64-bit fields as (hi, lo) int32 word rows)
#
# Output rows: 0 status, 1-2 remaining (hi, lo), 3-4 reset_time.
# The request `limit` is echoed host-side (the kernel's limit output
# is always the request limit), so it is not read back.

PACKED_IN_ROWS = 16
PACKED_OUT_ROWS = 5


def _row64(pin: jax.Array, hi_row: int, lo_row: int) -> jax.Array:
    """Recombine (hi, lo) int32 word rows into int64 (two's complement)."""
    return (pin[hi_row].astype(_I64) << 32) | (pin[lo_row].astype(_I64) & 0xFFFFFFFF)


def _unpack_in(pin: jax.Array) -> tuple[BatchInput, jax.Array]:
    batch = BatchInput(
        slot=pin[1],
        algo=pin[2],
        behavior=pin[3],
        hits=_row64(pin, 4, 5),
        limit=_row64(pin, 6, 7),
        duration=_row64(pin, 8, 9),
        burst=_row64(pin, 10, 11),
        greg_duration=_row64(pin, 12, 13),
        greg_expire=_row64(pin, 14, 15),
    )
    now = (pin[0, 0].astype(_I64) << 32) | (pin[0, 1].astype(_I64) & 0xFFFFFFFF)
    return batch, now


def _pack_out(status: jax.Array, rem: jax.Array, reset: jax.Array) -> jax.Array:
    # int64→int32 astype truncates to the low word (numpy/XLA C-cast
    # semantics) — exactly the bit split the host recombines.
    return jnp.stack(
        [
            status.astype(_I32),
            (rem >> 32).astype(_I32),
            rem.astype(_I32),
            (reset >> 32).astype(_I32),
            reset.astype(_I32),
        ]
    )


def pack_batch_host(
    size: int,
    now_ms: int,
    capacity: int,
    slot_sorted: np.ndarray,  # int32 [m] sorted ascending
    algo: np.ndarray,
    behavior: np.ndarray,
    hits: np.ndarray,
    limit: np.ndarray,
    duration: np.ndarray,
    burst: np.ndarray,
    greg_duration: np.ndarray,
    greg_expire: np.ndarray,
    out: np.ndarray | None = None,  # reusable [PACKED_IN_ROWS, size] int32
) -> np.ndarray:
    """Build the packed input buffer on the host (vectorized numpy).

    Lanes beyond `len(slot_sorted)` are padding: distinct ascending
    out-of-range slots, zero fields."""
    m = len(slot_sorted)
    if out is None:
        out = np.zeros((PACKED_IN_ROWS, size), dtype=np.int32)
    else:
        out[:, m:] = 0
    out[0, 0] = (np.int64(now_ms) >> 32).astype(np.int32)
    out[0, 1] = np.int64(now_ms).astype(np.int32)  # low-word bit pattern
    out[1, :m] = slot_sorted
    if size > m:
        out[1, m:] = (
            np.arange(capacity, capacity + (size - m), dtype=np.int64)
            .astype(np.int32)
        )
    out[2, :m] = algo
    out[3, :m] = behavior

    def w64(hi_row, lo_row, col):
        c = col.astype(np.int64, copy=False)
        out[hi_row, :m] = (c >> 32).astype(np.int32)
        out[lo_row, :m] = c.astype(np.int32)  # low-word bit pattern

    w64(4, 5, hits)
    w64(6, 7, limit)
    w64(8, 9, duration)
    w64(10, 11, burst)
    w64(12, 13, greg_duration)
    w64(14, 15, greg_expire)
    return out


def unpack_out_host(arr: np.ndarray, m: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Packed output rows → (status int32[m], remaining i64[m], reset i64[m])."""
    status = arr[0, :m]
    rem = (arr[1, :m].astype(np.int64) << 32) | (
        arr[2, :m].astype(np.int64) & 0xFFFFFFFF
    )
    reset = (arr[3, :m].astype(np.int64) << 32) | (
        arr[4, :m].astype(np.int64) & 0xFFFFFFFF
    )
    return status, rem, reset


# guberlint: shapes pin [PACKED_IN_ROWS, W] int32, W on the pow2 width ladder; state fixed at capacity
def _fused_step_core(state: BucketState, pin: jax.Array):
    batch, now = _unpack_in(pin)
    new_state, resp_status, resp_rem, resp_reset = _apply_core(
        state,
        batch.slot,
        batch.algo,
        batch.behavior,
        batch.hits,
        batch.limit,
        batch.duration,
        batch.burst,
        batch.greg_duration,
        batch.greg_expire,
        now,
    )
    return new_state, _pack_out(resp_status, resp_rem, resp_reset)


# Gather→update→scatter with donated state: ONE device op per round.
# Whether XLA compiles the in-place RMW without cloning the state is
# platform-dependent — both engines check `fused_step_ok()`
# (memory_analysis probe) at construction (core/engine.py
# `require_in_place`).
fused_step = jax.jit(_fused_step_core, donate_argnums=(0,))


# guberlint: shapes pins [R, PACKED_IN_ROWS, W], R in {2,4,8,16} (pump rounds up), W on the width ladder
def _multi_fused_core(state: BucketState, pins: jax.Array):
    """R packed rounds applied SEQUENTIALLY in one device program.

    pins int32 [R, PACKED_IN_ROWS, W] → outputs [R, PACKED_OUT_ROWS, W].
    lax.scan preserves the per-slot sequential semantics the rounds
    scheme guarantees per step, while collapsing R dispatches + R
    readbacks into ONE of each (fewer transfers and dispatches per
    decision).  Padding rounds (all lanes out of range) are no-ops by
    the same mechanism as padding lanes."""

    def body(st, pin):
        return _fused_step_core(st, pin)

    state, pouts = jax.lax.scan(body, state, pins)
    return state, pouts


multi_fused_step = jax.jit(_multi_fused_core, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Uniform-batch narrow format.
#
# The 16-row packed input costs 64B/decision up and the 5-row output
# 20B/decision down.  Real traffic is overwhelmingly "one limit
# config, many keys" (the reference's request shape too: same
# name/limit/duration across a client's batch), and such batches need
# only THE SLOT per lane uphill and status+remaining+reset downhill
# (fewer bytes per decision across the host↔device link):
#
#   pin  int32 [2, W]: row0 header
#        [now_hi, now_lo, algo, behavior, hits_hi, hits_lo,
#         limit, duration_lo, burst, duration_hi]  (scalars, W >= 64)
#        row1 slot (sorted; padding = cap + lane)
#   pout int32 [2, W]: row0 = (status << 31) | remaining
#        (remaining < 2^31 — guaranteed by the uniformity gate
#         limit, burst < 2^31), row1 = reset_time - now (< duration
#        < 2^31 by the gate).
#
# 4B up + 8B down per decision.
# Host-side gating (engine._uniform_cols): no Gregorian, all config
# columns constant, limit/duration/burst < 2^31.

UNIFORM_IN_ROWS = 2
UNIFORM_OUT_ROWS = 2


def pack_uniform_host(
    size: int,
    now_ms: int,
    capacity: int,
    slot_sorted: np.ndarray,  # int32 [m] sorted ascending
    algo: int,
    behavior: int,
    hits: int,
    limit: int,
    duration: int,
    burst: int,
    out: np.ndarray | None = None,
) -> np.ndarray:
    m = len(slot_sorted)
    if out is None:
        out = np.zeros((UNIFORM_IN_ROWS, size), dtype=np.int32)
    else:
        out[:, m:] = 0
    hdr = out[0]
    hdr[0] = (np.int64(now_ms) >> 32).astype(np.int32)
    hdr[1] = np.int64(now_ms).astype(np.int32)
    hdr[2] = algo
    hdr[3] = behavior
    hdr[4] = (np.int64(hits) >> 32).astype(np.int32)
    hdr[5] = np.int64(hits).astype(np.int32)
    hdr[6] = limit
    hdr[7] = np.int64(duration).astype(np.int32)
    hdr[8] = burst
    hdr[9] = (np.int64(duration) >> 32).astype(np.int32)
    out[1, :m] = slot_sorted
    if size > m:
        out[1, m:] = (
            np.arange(capacity, capacity + (size - m), dtype=np.int64)
            .astype(np.int32)
        )
    return out


# guberlint: shapes pin [UNIFORM_IN_ROWS, W] int32, W on the pow2 width ladder; state fixed at capacity
def _uniform_step_core(state: BucketState, pin: jax.Array):
    hdr = pin[0]
    now = (hdr[0].astype(_I64) << 32) | (hdr[1].astype(_I64) & 0xFFFFFFFF)
    w = pin.shape[1]
    slot = pin[1]

    def bc(x):
        return jnp.full((w,), x)

    algo = bc(hdr[2])
    behavior = bc(hdr[3])
    hits = bc((hdr[4].astype(_I64) << 32) | (hdr[5].astype(_I64) & 0xFFFFFFFF))
    limit = bc(hdr[6].astype(_I64))
    duration = bc(
        (hdr[9].astype(_I64) << 32) | (hdr[7].astype(_I64) & 0xFFFFFFFF)
    )
    burst = bc(hdr[8].astype(_I64))
    zeros = jnp.zeros((w,), dtype=_I64)
    new_state, status, rem, reset = _apply_core(
        state, slot, algo, behavior, hits, limit,
        duration, burst, zeros, zeros, now,
    )
    pout = jnp.stack(
        [
            (
                (status.astype(_I64) << 31) | (rem & 0x7FFFFFFF)
            ).astype(_I32),
            (reset - now).astype(_I32),
        ]
    )
    return new_state, pout


uniform_step = jax.jit(_uniform_step_core, donate_argnums=(0,))


# guberlint: shapes pins [R, UNIFORM_IN_ROWS, W], R in {2,4,8,16}; W on the width ladder
def _multi_uniform_core(state: BucketState, pins: jax.Array):
    def body(st, pin):
        return _uniform_step_core(st, pin)

    state, pouts = jax.lax.scan(body, state, pins)
    return state, pouts


multi_uniform_step = jax.jit(_multi_uniform_core, donate_argnums=(0,))


def unpack_uniform_out_host(
    arr: np.ndarray, m: int, now_ms: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Narrow output rows → (status, remaining, reset) like
    unpack_out_host (the status/remaining packing is sign-safe via a
    uint32 view)."""
    u = arr[0, :m].view(np.uint32)
    status = (u >> 31).astype(np.int32)
    rem = (u & 0x7FFFFFFF).astype(np.int64)
    reset = arr[1, :m].astype(np.int64) + now_ms
    return status, rem, reset


class ProbeVerdict(NamedTuple):
    """A compile probe's answer and what decided it: the memory-
    analysis numbers, or the first line of the compiler's refusal.
    The engine logs a "no" and /debug/vars carries both fields."""

    ok: bool
    reason: str


def first_line(e: BaseException) -> str:
    """`Type: first line of message` — a probe's reason for a no."""
    lines = str(e).strip().splitlines()
    return f"{type(e).__name__}: {lines[0] if lines else ''}"[:400]


def compiled_temp_bytes(step, *shapes) -> int:
    """Compile jitted `step` for these shapes (with their shardings,
    where they carry any) and return XLA's temp allocation on a
    device; raises the compiler's refusal."""
    ma = step.lower(*shapes).compile().memory_analysis()
    if ma is None:
        raise RuntimeError("backend reports no memory analysis")
    return int(ma.temp_size_in_bytes)


def in_place_verdict(temp: int, state_bytes: int) -> ProbeVerdict:
    """A donated step's temp against the state a device holds: temp
    allocations a fraction of the state size mean the donation aliased
    the buffers and no O(capacity) copy was inserted (copy-insertion
    cloning the state shows as temp ≈ state size)."""
    bound = max(state_bytes // 4, 1 << 20)
    ok = temp < bound
    return ProbeVerdict(
        ok,
        f"temp {temp} B {'<' if ok else '>='} bound {bound} B "
        f"(state {state_bytes} B)",
    )


def _in_place_probe(step, capacity: int, in_shape: tuple) -> ProbeVerdict:
    """Compile donated `step` at this capacity and read XLA's memory
    analysis (`in_place_verdict`)."""
    state_sds = jax.eval_shape(lambda: make_state(capacity))
    in_sds = jax.ShapeDtypeStruct(in_shape, jnp.int32)
    try:
        temp = compiled_temp_bytes(step, state_sds, in_sds)
    except Exception as e:  # noqa: BLE001 — the refusal is the verdict
        return ProbeVerdict(False, first_line(e))
    state_bytes = sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree.leaves(state_sds)
    )
    return in_place_verdict(temp, state_bytes)


@functools.lru_cache(maxsize=None)
def multi_step_ok(
    capacity: int, rounds: int = 2, width: int = 64
) -> ProbeVerdict:
    """Probe whether the scanned multi-round program keeps the donated
    state in place (see fused_step_ok — a scan that clones the state
    per iteration would be O(R·capacity) memory)."""
    return _in_place_probe(
        multi_fused_step, capacity, (rounds, PACKED_IN_ROWS, width)
    )


# ---------------------------------------------------------------------------
# Collapsed duplicate-segment step.
#
# A batch with a hot key repeated m times would cost m serialization
# rounds (m device dispatches) under the rounds scheme — Zipf traffic
# measured 12k dec/s on the zipf bench config because of exactly this.
# When every occurrence of a key in the batch carries IDENTICAL request
# fields (the overwhelmingly common case — same limit/duration/hits per
# client), the sequential semantics have a CLOSED FORM:
#
#   After the first application (handled by the full _compute_update),
#   the remaining m-1 occurrences see an existing item with unchanged
#   config and zero elapsed time (same `now`), so each either consumes
#   `h` or is rejected without consuming.  With R1 = remaining after
#   the first application, the number of accepted extras is
#   a2 = clip(R1 // h, 0, m-1) (all, for h <= 0), occurrence p
#   (0-based among extras) responds
#     accepted (p < a2):  remaining R1-(p+1)h, sticky/UNDER status
#     rejected:           remaining R1-a2·h, OVER
#   and the stored remaining is R1 - a2·h.  The token bucket's sticky
#   status flips to OVER iff some extra actually saw remaining==0
#   (h > 0, R1-a2·h == 0, a2 < m-1).  The leaky bucket is identical
#   over floor(rem_f) with reset_time = now + (limit - rem_resp)·rate.
#
# One dispatch therefore serves ALL duplicates exactly; the kernel
# fuzz (tests/test_collapse.py) pins equality with the sequential
# scalar spec.  Segments with RESET_REMAINING, mid-batch slot reuse
# (eviction rounds > 0), or non-uniform fields fall back to rounds.
#
# Packed layout (int32 [COLLAPSED_IN_ROWS, W]):
#   row 0   header [now_hi, now_lo]
#   rows 1-16   SEGMENT level (first S lanes real; padding = m 0 +
#               ascending out-of-range slots): slot, m, algo, behavior,
#               hits, limit, duration, burst, greg_dur, greg_exp
#               (64-bit as hi/lo pairs)
#   row 17  lane → segment index;  row 18  lane → position in segment
# Output rows are PACKED_OUT_ROWS, lane order.

COLLAPSED_IN_ROWS = 19


def _collapsed_values(state: BucketState, pin: jax.Array):
    now = (pin[0, 0].astype(_I64) << 32) | (pin[0, 1].astype(_I64) & 0xFFFFFFFF)
    slot = pin[1]
    m = pin[2].astype(_I64)
    s_algo = pin[3]
    s_beh = pin[4]

    def r64(hi, lo):
        return (pin[hi].astype(_I64) << 32) | (pin[lo].astype(_I64) & 0xFFFFFFFF)

    s_hits = r64(5, 6)
    s_limit = r64(7, 8)
    s_dur = r64(9, 10)
    s_burst = r64(11, 12)
    s_gdur = r64(13, 14)
    s_gexp = r64(15, 16)
    seg = pin[17]
    pos = pin[18].astype(_I64)

    # First application per segment: the full bucket update.
    vals, st1, rem1, rst1 = _compute_update(
        state, slot, s_algo, s_beh, s_hits, s_limit,
        s_dur, s_burst, s_gdur, s_gexp, now,
    )

    extras = jnp.maximum(m - 1, 0)
    h = s_hits
    h_safe = jnp.maximum(h, 1)
    is_tok = s_algo == int(Algorithm.TOKEN_BUCKET)

    # Token extras.
    R1 = vals.remaining
    a2_tok = jnp.where(h > 0, jnp.clip(R1 // h_safe, 0, extras), extras)
    rem2_tok = R1 - a2_tok * h
    sticky_over = (h > 0) & (rem2_tok == 0) & (a2_tok < extras)
    status2 = jnp.where(sticky_over & is_tok, _OVER, vals.status).astype(_I32)

    # Leaky extras (over floor of the fixed-point remaining).
    W1f = vals.rem_f
    W1 = trunc_i64(W1f)
    a2_lk = jnp.where(h > 0, jnp.clip(W1 // h_safe, 0, extras), extras)
    rem2_lkf = W1f - (a2_lk * h).astype(_F64)

    vals2 = vals._replace(
        remaining=jnp.where(is_tok, rem2_tok, vals.remaining),
        status=status2,
        rem_f=jnp.where(is_tok, vals.rem_f, rem2_lkf),
    )

    # Leaky reset slope (same formula as update_lanes' lk_rate_i).
    lk_D = jnp.where((s_beh & int(Behavior.DURATION_IS_GREGORIAN)) != 0, s_gdur, s_dur)
    limit_pos = s_limit > 0
    lk_rate_i = rate_int(lk_D, jnp.where(limit_pos, s_limit, 1), limit_pos)

    # Lane-level responses.
    def g(x):
        return x[seg]

    p = jnp.maximum(pos - 1, 0)
    first = pos == 0
    l_tok = g(is_tok)
    l_h = g(h)

    acc_tok = p < g(a2_tok)
    rem_tok = jnp.where(acc_tok, g(R1) - (p + 1) * l_h, g(rem2_tok))
    st_tok = jnp.where(acc_tok, g(vals.status), _OVER)
    rst_tok = g(vals.expire)

    acc_lk = p < g(a2_lk)
    rem_lk = jnp.where(acc_lk, g(W1) - (p + 1) * l_h, g(W1 - a2_lk * h))
    st_lk = jnp.where(acc_lk, _UNDER, _OVER)
    rst_lk = now + (g(s_limit) - rem_lk) * g(lk_rate_i)

    o_status = jnp.where(first, g(st1), jnp.where(l_tok, st_tok, st_lk))
    o_rem = jnp.where(first, g(rem1), jnp.where(l_tok, rem_tok, rem_lk))
    o_reset = jnp.where(first, g(rst1), jnp.where(l_tok, rst_tok, rst_lk))
    return slot, vals2, _pack_out(o_status.astype(_I32), o_rem, o_reset)


def token_extras_host(R1: int, h: int, extras: int) -> tuple[int, int, bool]:
    """Host-scalar twin of the token branch of `_collapsed_values`:
    given remaining R1 after the first application, `extras` further
    occurrences each consuming `h` admit
    a2 = clip(R1 // h, 0, extras) of them (all, for h <= 0), leaving
    rem2 = R1 - a2*h, with the sticky status flipping OVER iff some
    extra actually saw remaining==0.  Returns (a2, rem2, sticky_over).

    The decision ledger (core/ledger.py) drains its credit leases with
    this same algebra — one source of truth for the closed form the
    kernel fuzz pins (tests/test_collapse.py, tests/test_ledger.py)."""
    if h > 0:
        a2 = min(max(R1 // h, 0), extras)
    else:
        a2 = extras
    rem2 = R1 - a2 * h
    sticky = h > 0 and rem2 == 0 and a2 < extras
    return a2, rem2, sticky


# guberlint: shapes pin [COLLAPSED_IN_ROWS, W] int32, W on the pow2 width ladder; state fixed at capacity
def _collapsed_step_core(state: BucketState, pin: jax.Array):
    slot, vals2, packed = _collapsed_values(state, pin)
    return _scatter_values(state, slot, vals2), packed


collapsed_step = jax.jit(_collapsed_step_core, donate_argnums=(0,))


def pack_collapsed_host(
    size: int,
    now_ms: int,
    capacity: int,
    uniq_slots: np.ndarray,  # int32 [S] sorted unique
    counts: np.ndarray,  # int64 [S]
    seg_fields: tuple,  # (algo, behavior, hits, limit, duration, burst,
    #                      greg_dur, greg_exp) per segment, [S]
    seg_idx: np.ndarray,  # int32 [m_lanes]
    pos: np.ndarray,  # int32 [m_lanes]
    out: np.ndarray | None = None,  # reusable [COLLAPSED_IN_ROWS, size]
) -> np.ndarray:
    """Host packer for the collapsed step (layout above)."""
    s_count = len(uniq_slots)
    n_lanes = len(seg_idx)
    if out is None:
        out = np.zeros((COLLAPSED_IN_ROWS, size), dtype=np.int32)
    else:
        out[:] = 0
    out[0, 0] = (np.int64(now_ms) >> 32).astype(np.int32)
    out[0, 1] = np.int64(now_ms).astype(np.int32)
    out[1, :s_count] = uniq_slots
    if size > s_count:
        out[1, s_count:] = np.arange(
            capacity, capacity + (size - s_count), dtype=np.int64
        ).astype(np.int32)
    out[2, :s_count] = counts.astype(np.int32)
    algo, behavior, hits, limit, duration, burst, gdur, gexp = seg_fields
    out[3, :s_count] = algo
    out[4, :s_count] = behavior

    def w64(hi_row, lo_row, col):
        c = col.astype(np.int64, copy=False)
        out[hi_row, :s_count] = (c >> 32).astype(np.int32)
        out[lo_row, :s_count] = c.astype(np.int32)

    w64(5, 6, hits)
    w64(7, 8, limit)
    w64(9, 10, duration)
    w64(11, 12, burst)
    w64(13, 14, gdur)
    w64(15, 16, gexp)
    out[17, :n_lanes] = seg_idx
    # Padding lanes point at the last padding segment (m=0, harmless).
    if size > n_lanes:
        out[17, n_lanes:] = size - 1
    out[18, :n_lanes] = pos
    return out


@functools.lru_cache(maxsize=None)
def fused_step_ok(capacity: int, width: int = 64) -> ProbeVerdict:
    """Probe whether `fused_step` compiles to a true in-place update
    at this capacity (tiny width).  A no is a fault, not a choice: a
    step that clones the state would copy the whole table every
    dispatch, so on an accelerator both engines refuse to start on it
    (core/engine.py `require_in_place`)."""
    return _in_place_probe(fused_step, capacity, (PACKED_IN_ROWS, width))


class SlotRecord(NamedTuple):
    """Persisted bucket values for restoring slots (Store.get /
    Loader.load hydration), shape [C] per field.

    `remf` carries the leaky remaining as 32.32 fixed point words so a
    Loader snapshot round-trips bit-exactly."""

    slot: jax.Array  # int32; padding = out-of-range ascending
    algo: jax.Array  # int32
    status: jax.Array  # int32
    limit: jax.Array  # int64
    remaining: jax.Array  # int64   (token)
    remf_hi: jax.Array  # int32    (leaky whole)
    remf_lo: jax.Array  # uint32   (leaky fraction)
    duration: jax.Array  # int64
    t0: jax.Array  # int64
    expire_at: jax.Array  # int64
    burst: jax.Array  # int64
    invalid_at: jax.Array  # int64


# guberlint: shapes rec columns padded to pow2 (build_restore_record _pad_size); state fixed at capacity
def _load_slots_impl(state: BucketState, rec: SlotRecord) -> BucketState:
    """Hydrate persisted bucket values into their slots.

    The scatter contract matches the apply kernel: `rec.slot` sorted,
    unique, padding out-of-range (dropped).  `_scatter_hints` keeps
    the pass a column for a bulk load (more than a lane per 8,192
    rows) and spares it a single key's hydration."""

    def put(arr, vals):
        return arr.at[rec.slot].set(
            vals,
            mode="drop",
            **_scatter_hints(arr.shape[0], rec.slot.shape[0]),
        )

    def put64(hi, lo, v):
        vh, vl = split_i64(v)
        return put(hi, vh), put(lo, vl)

    cap = state.meta.shape[0]
    algo_norm = (rec.algo != 0).astype(_I32)
    t0c = clamp_ts(rec.t0)
    invc = clamp_ts(rec.invalid_at)
    expc = clamp_ts(rec.expire_at)
    durc = clamp_ts(rec.duration)
    meta_v = pack_meta(
        (rec.slot < cap), algo_norm, rec.status, t0c, invc
    )
    hi2_v = pack_hi2(expc, durc)
    tok_hi, tok_lo = split_i64(rec.remaining)
    leaky = algo_norm == 1
    rem_hi_v = jnp.where(leaky, rec.remf_hi, tok_hi)
    rem_lo_v = jnp.where(leaky, rec.remf_lo, tok_lo)
    limit_hi, limit_lo = put64(state.limit_hi, state.limit_lo, rec.limit)
    burst_hi, burst_lo = put64(state.burst_hi, state.burst_lo, rec.burst)
    return state._replace(
        meta=put(state.meta, meta_v),
        hi2=put(state.hi2, hi2_v),
        t0_lo=put(state.t0_lo, (t0c & 0xFFFFFFFF).astype(_U32)),
        expire_lo=put(state.expire_lo, (expc & 0xFFFFFFFF).astype(_U32)),
        invalid_lo=put(state.invalid_lo, (invc & 0xFFFFFFFF).astype(_U32)),
        duration_lo=put(
            state.duration_lo, (durc & 0xFFFFFFFF).astype(_U32)
        ),
        limit_hi=limit_hi,
        limit_lo=limit_lo,
        rem_hi=put(state.rem_hi, rem_hi_v),
        rem_lo=put(state.rem_lo, rem_lo_v.astype(_U32)),
        burst_hi=burst_hi,
        burst_lo=burst_lo,
    )


load_slots = jax.jit(_load_slots_impl, donate_argnums=(0,))


# ----------------------------------------------------------------------
# Paged-state page transfer helpers (core/paging.py; PERF.md §30).
#
# A page is `page_size` consecutive rows of every state column.  Spill
# and refill move the RAW packed words — the same 12 int32/uint32
# columns the kernels read — so an evict→spill→refill roundtrip is
# bit-exact by construction (including the leaky 32.32 remaining and
# the folded hi-word packings; no decode/re-encode on the path).  One
# [PAGE_WORD_ROWS, page_size] int32 block per page keeps it to ONE d2h
# (spill, via the readback combiner) or one h2d + one donated in-place
# update (refill).  `start` is a traced device-row scalar, so each
# page size compiles exactly one gather and one load program.

PAGE_WORD_ROWS = len(BucketState._fields)  # 12 — one row per column


# guberlint: shapes state fixed at device capacity; start scalar device row; page_size static — one program per page size
@functools.partial(jax.jit, static_argnums=(2,))
def gather_page_words(
    state: BucketState, start: jax.Array, page_size: int
) -> jax.Array:
    """One page's raw column words as [PAGE_WORD_ROWS, page_size]
    int32 (uint32 columns bitcast, not converted)."""
    rows = []
    for name in BucketState._fields:
        col = getattr(state, name)
        sl = jax.lax.dynamic_slice_in_dim(col, start, page_size)
        if sl.dtype != jnp.int32:
            sl = jax.lax.bitcast_convert_type(sl, jnp.int32)
        rows.append(sl)
    return jnp.stack(rows)


# guberlint: shapes words fixed [PAGE_WORD_ROWS, page_size] per plane; state fixed at device capacity
def _load_page_words_impl(
    state: BucketState, start: jax.Array, words: jax.Array
) -> BucketState:
    """Write a page's raw words back into the state columns at device
    row `start` — the refill half of the spill roundtrip."""
    new = {}
    for i, name in enumerate(BucketState._fields):
        col = getattr(state, name)
        row = words[i]
        if col.dtype != jnp.int32:
            row = jax.lax.bitcast_convert_type(row, col.dtype)
        new[name] = jax.lax.dynamic_update_slice_in_dim(
            col, row, start, axis=0
        )
    return BucketState(**new)


load_page_words = jax.jit(_load_page_words_impl, donate_argnums=(0,))

