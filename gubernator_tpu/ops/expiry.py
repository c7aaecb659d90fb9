"""Periodic device-side expiry sweep.

The reference's LRU expires items lazily on read and evicts on overflow
(reference: lrucache.go:112-159).  With device-resident state, lazy
expiry is already handled by the kernel's liveness check; this sweep
reclaims slots of expired buckets in bulk so the host intern table can
reuse them (SURVEY.md §7.3 item 6).

Scaling (VERDICT r1 item 4): the round-1 sweep returned the full freed
MASK, forcing an O(capacity) device→host transfer per sweep (~100MB at
100M slots).  `sweep_window_scan` instead processes a fixed-width
window and compacts freed indices ON DEVICE (stable argsort puts freed
lanes first), so the host pulls one count scalar per window and then
only `count` indices — transfer is O(freed), not O(capacity).  The
meta buffer is donated on commit, so the windowed update is in-place:
device work per call is O(window).

With the packed layout (BucketState docstring) occupancy is meta bit 0
and the expire hi word is hi2 bits 0-10; the 64-bit `expire_at < now`
compare runs on the (hi-word, lo-word) pair directly — combining to
int64 across the window would reintroduce the O(capacity) x64 boundary
shim the split layout exists to avoid.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

# Layout constants live in ONE place (bucket_kernel); masking with a
# local copy would silently free wrong slots if the packing ever moved.
from gubernator_tpu.ops.bucket_kernel import _HI11


# guberlint: shapes columns [..., capacity] fixed at engine build; window static (SWEEP_WINDOW)
@partial(jax.jit, static_argnames=("window",))
def sweep_window_scan(
    meta: jax.Array,  # int32 [..., capacity]
    hi2: jax.Array,  # int32 [..., capacity]
    expire_lo: jax.Array,  # uint32 [..., capacity]
    now_hi: jax.Array,  # int32 scalar (now_ms >> 32; fits 11 bits)
    now_lo: jax.Array,  # uint32 scalar
    start: jax.Array,  # int32 scalar, window start (pre-clamped by host)
    *,
    window: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """READ-ONLY scan of `[start, start+window)` along the capacity axis.

    Returns (meta_window_new, freed_order, count): `meta_window_new`
    is the window's meta words with freed slots' occupied bit cleared;
    `freed_order[..., :count]` are the window-local indices of freed
    slots in ascending order (stable argsort compaction); entries
    beyond `count` are arbitrary non-freed lanes and must be ignored.
    Pair with `sweep_window_commit` — the read/write split keeps the
    donated commit copy-free (the fused slice+update variant forced a
    full meta copy per window).
    """
    axis = meta.ndim - 1
    meta_w = lax.dynamic_slice_in_dim(meta, start, window, axis)
    hi2_w = lax.dynamic_slice_in_dim(hi2, start, window, axis)
    elo_w = lax.dynamic_slice_in_dim(expire_lo, start, window, axis)
    occ_w = (meta_w & 1) != 0
    ehi_w = hi2_w & _HI11
    lt = (ehi_w < now_hi) | ((ehi_w == now_hi) & (elo_w < now_lo))
    freed = occ_w & lt
    count = jnp.sum(freed, axis=axis, dtype=jnp.int32)
    # Compaction: freed lanes (True) sort before kept lanes, stable →
    # ascending window-local index order.
    order = jnp.argsort(~freed, axis=axis, stable=True).astype(jnp.int32)
    return jnp.where(freed, meta_w & ~1, meta_w), order, count


# guberlint: shapes meta [..., capacity] fixed; meta_window [..., SWEEP_WINDOW] fixed per capacity
@partial(jax.jit, donate_argnums=(0,))
def sweep_window_commit(
    meta: jax.Array,  # int32 [..., capacity] (donated)
    meta_window: jax.Array,  # int32 [..., window]
    start: jax.Array,  # int32 scalar
) -> jax.Array:
    """WRITE-ONLY in-place commit of a scanned window's meta words."""
    return lax.dynamic_update_slice_in_dim(
        meta, meta_window, start, meta.ndim - 1
    )


def windowed_sweep(
    engine, cap: int, now_ms: int, max_windows, release,
    scan=sweep_window_scan, commit=sweep_window_commit,
) -> int:
    """Drive scan/commit windows over an engine's state.

    Shared by DecisionEngine.sweep and ShardedDecisionEngine.sweep (the
    clamp/overlap/cursor-wrap logic is subtle enough to exist once).
    `engine` supplies `_state`, `_sweep_cursor`, `SWEEP_WINDOW`; the
    caller holds the engine lock.  `release(order, count, start) -> n`
    frees the compacted slots in the host table(s) and returns how many.
    `scan` / `commit` default to the two programs above; the mesh
    engine passes its own, which run them per shard on its flat
    columns.
    """
    window = min(cap, engine.SWEEP_WINDOW)
    n_windows = (cap + window - 1) // window
    if max_windows is not None:
        n_windows = min(n_windows, max_windows)
    now_hi = jnp.asarray(now_ms >> 32, dtype=jnp.int32)
    now_lo = jnp.asarray(now_ms & 0xFFFFFFFF, dtype=jnp.uint32)
    freed_total = 0
    for _ in range(n_windows):
        # Clamp the tail window; overlap is idempotent (slots freed
        # earlier in this pass are no longer occupied).
        start = min(engine._sweep_cursor, cap - window)
        start_dev = jnp.asarray(start, dtype=jnp.int32)
        meta_w, order, count = scan(
            engine._state.meta,
            engine._state.hi2,
            engine._state.expire_lo,
            now_hi,
            now_lo,
            start_dev,
            window=window,
        )
        engine._state = engine._state._replace(
            meta=commit(engine._state.meta, meta_w, start_dev)
        )
        freed_total += release(order, count, start)
        engine._sweep_cursor += window
        if engine._sweep_cursor >= cap:
            engine._sweep_cursor = 0
    return freed_total


# guberlint: shapes full-capacity columns fixed at engine build (legacy one-shot sweep)
@jax.jit
def sweep_expired(
    meta: jax.Array,  # int32
    hi2: jax.Array,  # int32
    expire_lo: jax.Array,  # uint32
    now_hi: jax.Array,  # int32 scalar
    now_lo: jax.Array,  # uint32 scalar
) -> tuple[jax.Array, jax.Array]:
    """Full-capacity sweep returning (new_meta, freed_mask).

    Kept for small-capacity callers and tests; production engines use
    the windowed compaction above."""
    occ = (meta & 1) != 0
    ehi = hi2 & _HI11
    lt = (ehi < now_hi) | ((ehi == now_hi) & (expire_lo < now_lo))
    freed = occ & lt
    return jnp.where(freed, meta & ~1, meta), freed
