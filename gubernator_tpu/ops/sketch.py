"""Count-min-sketch rate limiting: approximate decisions at unbounded
key cardinality (BASELINE config 5 stretch; no reference counterpart —
the reference caps state at its LRU size and evicts, store.go/lrucache
.go, while a sketch answers for EVERY key in O(1) memory with a
one-sided overcount error).

TPU-first design:

- Sketch state: int32 `[depth, width]` counters in HBM, one sketch per
  fixed window duration.  Sliding behavior comes from TWO alternating
  epochs (current + previous) with linear interpolation — the classic
  sliding-window approximation, all branch-free arithmetic.
- Hashing: the host computes one fnv1a-64 per key (it already has the
  bytes); the device derives the `depth` row indexes via
  Kirsch-Mitzenmacher double hashing (h1 + r·h2) mod width — no
  per-row string hashing anywhere.
- Duplicate handling: scatter-add with arbitrary duplicate indexes
  lowers to a serial per-element loop on TPU, so the HOST pre-combines
  each row's duplicates (sort + reduce) and the device runs only
  sorted-unique gathers/scatter-adds — the same fast-path contract as
  the bucket kernel (ops/bucket_kernel.py).
- One packed int32 input `[2 + 3*depth, B]` per step (header, hits
  row, then per-row sorted unique indexes / summed hits / gather
  positions), one packed int32 output `[1, B]` (the estimate), so the
  step costs 3 device ops like the exact engine (PERF.md §4).

Estimate semantics: `est = min_r sketch[r][idx_r]` AFTER adding this
batch's hits, interpolated across the two epochs; OVER_LIMIT when
`est > limit`.  Errors are one-sided (never under-counts), matching a
rate limiter's fail-closed preference.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_I32 = jnp.int32
_I64 = jnp.int64
_U64 = np.uint64


class SketchState(NamedTuple):
    """Two-epoch count-min sketch, shape [2, depth, width] int32."""

    counts: jax.Array  # int32 [2, depth, width]
    epoch: jax.Array  # int64 scalar — window index of counts[cur]
    cur: jax.Array  # int32 scalar — which plane is the current epoch


def make_sketch(depth: int = 4, width: int = 1 << 20) -> SketchState:
    return SketchState(
        counts=jnp.zeros((2, depth, width), dtype=_I32),
        epoch=jnp.asarray(0, dtype=_I64),
        cur=jnp.asarray(0, dtype=_I32),
    )


# guberlint: shapes state planes [depth, width] fixed at sketch build; epoch_now scalar
def _rotate(state: SketchState, epoch_now: jax.Array) -> SketchState:
    """Advance to `epoch_now`: one step rotates planes (previous ←
    current, current ← zeros); a gap ≥ 2 windows zeroes both.

    The rotation is gated behind lax.cond so the COMMON step (same
    window as the last one, delta == 0) never touches the full
    [2, depth, width] state: an unconditional where-chain here cost an
    O(state) rewrite per batch — 32MB at the default shape, ~85ms per
    step on the CPU backend and pure wasted HBM bandwidth on TPU."""
    delta = epoch_now - state.epoch
    cur = state.cur

    def unchanged(counts):
        return counts, cur

    def rotate(counts):
        def one(c):
            other = 1 - cur
            return c.at[other].set(0), other.astype(_I32)

        def gap(c):
            # Both planes stale: zero everything, keep the plane index.
            return jnp.zeros_like(c), cur

        return jax.lax.cond(delta == 1, one, gap, counts)

    counts, cur2 = jax.lax.cond(
        delta <= 0, unchanged, rotate, state.counts
    )
    return SketchState(
        counts=counts,
        epoch=jnp.maximum(state.epoch, epoch_now),
        cur=cur2,
    )


def _sketch_step_impl(
    state: SketchState,
    pin: jax.Array,  # int32 [2 + 3*depth, B] (see host packer)
    depth: int,
    cur: int,
):
    # Header row 0: [epoch_hi, epoch_lo, frac_q16, ...].  Rotation is
    # NOT part of this program: the host mirrors the window epoch and
    # runs the (rare) rotate program first (SketchLimiter.apply) — an
    # in-program rotation, even lax.cond-gated, made XLA:CPU
    # materialize O(state) copies on every step (measured 69ms/step at
    # the default 32MB shape).  `cur` is STATIC (host-mirrored, two
    # compiled variants) for the same reason: a traced plane index in
    # the scatters also defeated in-place donation and kept the step
    # O(state); with static plane/row starts the program is O(batch).
    frac_q16 = pin[0, 2].astype(_I64)  # elapsed fraction of window, Q16
    width = state.counts.shape[2]
    size = pin.shape[1]
    prev = 1 - cur

    # ONE flat gather + ONE flat scatter + ONE flat gather over
    # globalized indexes (plane*depth + row)*width + idx — per-row
    # chained scatters interleaved with prev-plane gathers defeated
    # XLA:CPU's in-place donation analysis and copied the whole state
    # per step (measured 63ms at the default 32MB shape; this form
    # runs at ~0.09ms, and on TPU it is also the minimal-pass layout).
    flat = state.counts.reshape(-1)
    total = 2 * depth * width
    lanes = jnp.arange(size, dtype=_I64)
    rows64 = jnp.arange(depth, dtype=_I64)[:, None]
    idx_rows = jnp.stack(
        [pin[2 + 3 * r] for r in range(depth)]
    ).astype(_I64)  # [depth, size]; padding lanes hold width + lane
    add_rows = jnp.stack(
        [pin[2 + 3 * r + 1] for r in range(depth)]
    ).astype(_I64)
    valid = idx_rows < width
    # Padding indexes must stay unique ACROSS rows after flattening
    # (per-row `width + lane` repeats row to row), so they relocate to
    # total + row*size + lane, past every real cell.
    pad = total + rows64 * size + lanes[None, :]
    g_cur_idx = jnp.where(
        valid, (cur * depth + rows64) * width + idx_rows, pad
    ).reshape(-1)
    g_prev_idx = jnp.where(
        valid, (prev * depth + rows64) * width + idx_rows, pad
    ).reshape(-1)

    # Saturating add: gather current counters, add in int64, clamp to
    # the int32 range, scatter-set.  A plain int32 scatter-add would
    # wrap a saturated counter negative and silently turn the one-sided
    # "never under-counts" guarantee into under-counting.
    g0 = flat.at[g_cur_idx].get(
        mode="fill", fill_value=0, unique_indices=True
    )
    new_vals = jnp.clip(
        g0.astype(_I64) + add_rows.reshape(-1),
        -(2**31), 2**31 - 1,
    ).astype(_I32)
    flat = flat.at[g_cur_idx].set(
        new_vals, mode="drop", unique_indices=True
    )
    g_prev = flat.at[g_prev_idx].get(
        mode="fill", fill_value=0, unique_indices=True
    )
    # Sliding-window interpolation: prev·(1−f) + cur, in Q16.
    row_est = (
        g_prev.astype(_I64) * (65536 - frac_q16) // 65536
        + new_vals.astype(_I64)
    ).reshape(depth, size)
    est = jnp.full(size, jnp.iinfo(jnp.int64).max, dtype=_I64)
    for r in range(depth):
        pos = pin[2 + 3 * r + 2]  # lane → position into this row
        est = jnp.minimum(est, row_est[r][pos])

    new_state = SketchState(
        counts=flat.reshape(2, depth, width),
        epoch=state.epoch,
        cur=jnp.asarray(cur, dtype=_I32),
    )
    out = jnp.stack(
        [(est >> 32).astype(_I32), est.astype(_I32)]
    )  # int64 estimate as hi/lo rows
    return new_state, out


class SketchLimiter:
    """Approximate per-key rate limiter over a count-min sketch.

    One limiter = one (window_ms, depth, width) sketch; keys are
    unbounded.  `apply(keys, hits, limit)` returns (over_limit bool
    array, estimate array).  Overcounting is possible (collisions) at
    a rate bounded by ~batch_hits/width per row; undercounting is not.
    """

    def __init__(
        self,
        window_ms: int = 1_000,
        depth: int = 4,
        width: int = 1 << 20,
        *,
        seed: int = 0x9E3779B97F4A7C15,
    ):
        if depth < 1 or width < 2:
            raise ValueError("depth >= 1 and width >= 2 required")
        self.window_ms = int(window_ms)
        self.depth = depth
        self.width = width
        self._seed = np.uint64(seed)
        self._state = make_sketch(depth, width)
        # Serializes concurrent apply() calls: the step DONATES the
        # state, so two racing callers would hand the same deleted
        # buffer to the device (and even without donation the
        # read-modify-write of self._state would drop updates,
        # breaking the never-under-count contract).
        import threading

        self._lock = threading.Lock()
        # guberlint: shapes pin [rows, W] with W on the sketch pad ladder; depth static
        def sketch_step(s, pin, cur):
            return _sketch_step_impl(s, pin, depth, cur)

        self._step = jax.jit(
            sketch_step, donate_argnums=(0,), static_argnums=(2,)
        )
        # Host mirrors of the state's window epoch and current plane:
        # apply() triggers the rotation program only when the window
        # actually advances, and passes the plane statically (see
        # _sketch_step_impl).
        self._epoch_host = 0
        self._cur_host = 0
        self._rotate_jit = jax.jit(_rotate, donate_argnums=(0,))

    # -- host packing --------------------------------------------------

    def _indexes(self, keys) -> np.ndarray:
        """[depth, B] int64 row indexes via double hashing."""
        from gubernator_tpu.hashing import fnv1a_64_batch, pack_keys

        padded, lengths = pack_keys(keys)
        return self._indexes_hashed(fnv1a_64_batch(padded, lengths))

    def _indexes_hashed(self, h1: np.ndarray) -> np.ndarray:
        """Row indexes from precomputed fnv1a-64 key hashes (the wire
        codec already hashed every key — no re-hash, no key
        materialization on the served path)."""
        h1 = np.asarray(h1, dtype=np.uint64)
        # Second hash: one multiply-xor over h1 (splitmix-style).
        h2 = (h1 ^ (h1 >> np.uint64(33))) * self._seed
        rows = np.empty((self.depth, len(h1)), dtype=np.int64)
        for r in range(self.depth):
            rows[r] = (
                (h1 + np.uint64(r) * h2) % np.uint64(self.width)
            ).astype(np.int64)
        return rows

    def apply(
        self,
        keys,
        hits: np.ndarray,
        limit: np.ndarray,
        now_ms: int,
        *,
        key_hashes: Optional[np.ndarray] = None,  # fnv1a-64 per key
    ) -> Tuple[np.ndarray, np.ndarray]:
        n = len(key_hashes) if key_hashes is not None else len(keys)
        if n == 0:
            return np.zeros(0, dtype=bool), np.zeros(0, dtype=np.int64)
        rows = (
            self._indexes_hashed(key_hashes)
            if key_hashes is not None
            else self._indexes(keys)
        )
        hits64 = np.asarray(hits, dtype=np.int64)

        size = 64
        while size < n:
            size *= 2
        pin = np.zeros((2 + 3 * self.depth, size), dtype=np.int32)
        epoch = now_ms // self.window_ms
        frac = (now_ms % self.window_ms) * 65536 // self.window_ms
        pin[0, 0] = np.int32(epoch >> 32)
        pin[0, 1] = np.int64(epoch).astype(np.int32)
        pin[0, 2] = frac
        pin[1, :n] = np.clip(hits64, -(2**31), 2**31 - 1).astype(np.int32)
        for r in range(self.depth):
            idx = rows[r]
            # Host pre-combine: unique sorted indexes + summed hits,
            # plus each lane's position into the unique array.
            uniq, inv = np.unique(idx, return_inverse=True)
            m = len(uniq)
            # Exact int64 per-index sums, clamped to int32: a hot key's
            # combined hits must not wrap negative in the int32 lane
            # (that would decrement the counter — under-counting, which
            # the one-sided error contract forbids).
            sums = np.zeros(m, dtype=np.int64)
            np.add.at(sums, inv, hits64)
            sums = np.clip(sums, -(2**31), 2**31 - 1)
            pin[2 + 3 * r, :m] = uniq.astype(np.int32)
            if size > m:
                pin[2 + 3 * r, m:] = (
                    np.arange(self.width, self.width + (size - m), dtype=np.int64)
                    .astype(np.int32)
                )
            pin[2 + 3 * r + 1, :m] = sums.astype(np.int32)
            pin[2 + 3 * r + 2, :n] = inv.astype(np.int32)

        with self._lock:
            if epoch > self._epoch_host:
                # Window advanced: run the (rare) rotation program —
                # see _sketch_step_impl for why it is not in-step.
                if epoch - self._epoch_host == 1:
                    self._cur_host ^= 1  # mirror _rotate's plane flip
                self._state = self._rotate_jit(
                    self._state, jnp.asarray(epoch, dtype=jnp.int64)
                )
                self._epoch_host = epoch
            self._state, out = self._step(
                self._state, jnp.asarray(pin), self._cur_host
            )
            arr = np.asarray(out)
        est = (arr[0, :n].astype(np.int64) << 32) | (
            arr[1, :n].astype(np.int64) & 0xFFFFFFFF
        )
        over = est > np.asarray(limit, dtype=np.int64)
        return over, est
