"""Persistence interfaces: write-through Store + bulk Loader.

reference: store.go — `Store` gets OnChange/Get/Remove called inline by
the algorithms (:49-65, call sites algorithms.go:46-54,164-169,266-269);
`Loader` streams the whole cache in at startup and out at shutdown
(:69-78, driven by gubernator_pool.go:341-531).  The bucket value
structs mirror store.go:29-43.

TPU adaptation: bucket state lives on device, so
- `Store.get` hydrates a freshly interned slot via a batched device
  scatter (`ops.bucket_kernel.load_slots`) instead of a cache insert;
- `Store.on_change` receives values derived from the kernel's response
  (for LEAKY_BUCKET the sub-integer remainder is quantized to the
  response's integer `remaining` — the reference hands the store its
  float64; a restored bucket may therefore leak up to one hit of
  precision per save/restore cycle);
- `Loader.save`/`load` use full-fidelity device snapshots (exact hi/lo
  words, including the leaky fixed-point fraction);
- a Loader may also hand the cache over in columns (`load_columns`,
  `ItemColumns`): the state is columns on the device and on disk, and
  a restart that walks 1e8 `CacheItem`s in Python takes tens of minutes.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable, Iterator, List, Optional, Protocol, Union

import numpy as np

from gubernator_tpu.types import Algorithm, RateLimitReq


@dataclass
class TokenBucketItem:
    """reference: store.go:29-35."""

    status: int = 0
    limit: int = 0
    duration: int = 0
    remaining: int = 0
    created_at: int = 0  # unix ms


@dataclass
class LeakyBucketItem:
    """reference: store.go:37-43."""

    limit: int = 0
    duration: int = 0
    remaining: float = 0.0
    updated_at: int = 0  # unix ms
    burst: int = 0
    # Exact 32.32 fixed-point (whole, frac) words of `remaining` — set
    # by engine snapshots so Loader round-trips are bit-exact even when
    # the float64 mirror would round (whole part ≥ 2^21); restores
    # prefer these over `remaining` when present.
    remaining_words: Optional[tuple] = None


@dataclass
class CacheItem:
    """reference: cache.go:30-42."""

    key: str = ""
    value: Union[TokenBucketItem, LeakyBucketItem, None] = None
    expire_at: int = 0  # unix ms
    algorithm: int = Algorithm.TOKEN_BUCKET
    # A store may set this to force the cache to treat the item as
    # invalid after this time (reference: cache.go:37-41).
    invalid_at: int = 0


def words_from_float(v: float) -> tuple:
    """float remaining → exact-as-possible 32.32 fixed-point words."""
    import math

    whole = math.floor(v)
    frac = min((v - whole) * (2.0**32), 2.0**32 - 1)
    return (int(whole), int(frac))


def item_from_record(
    key: str,
    algorithm: int,
    status: int,
    limit: int,
    remaining: int,
    remf_hi: int,
    remf_lo: int,
    duration: int,
    t0: int,
    expire_at: int,
    burst: int,
    invalid_at: int,
) -> CacheItem:
    """Build a CacheItem from raw engine-state words — the ONE place
    that knows how snapshot columns map onto bucket value structs
    (used by both engines' export_items)."""
    if algorithm == int(Algorithm.TOKEN_BUCKET):
        value: Union[TokenBucketItem, LeakyBucketItem] = TokenBucketItem(
            status=status,
            limit=limit,
            duration=duration,
            remaining=remaining,
            created_at=t0,
        )
    else:
        value = LeakyBucketItem(
            limit=limit,
            duration=duration,
            # Float mirror rounds at whole ≥ 2^21; words are exact.
            remaining=float(remf_hi) + float(remf_lo) * 2.0**-32,
            updated_at=t0,
            burst=burst,
            remaining_words=(remf_hi, remf_lo),
        )
    return CacheItem(
        key=key,
        value=value,
        expire_at=expire_at,
        algorithm=algorithm,
        invalid_at=invalid_at,
    )


def pack_keys(keys: List[bytes]) -> tuple:
    """Keys end to end in one uint8 buffer, and their int64 [n + 1]
    boundaries: how `ItemColumns` carries them."""
    offsets = np.zeros(len(keys) + 1, dtype=np.int64)
    np.cumsum([len(k) for k in keys], out=offsets[1:])
    return np.frombuffer(b"".join(keys), dtype=np.uint8), offsets


@dataclass
class ItemColumns:
    """Rows of the cache as columns, in the stream's order: what
    `Loader.load_columns` yields a chunk at a time.  Row i's key is
    `key_buf[key_offsets[i]:key_offsets[i + 1]]` (UTF-8); the state
    columns carry the raw engine-state words of `item_from_record`,
    under `ops.bucket_kernel.SlotRecord`'s names."""

    key_buf: np.ndarray  # uint8, the keys' bytes end to end
    key_offsets: np.ndarray  # int64 [n + 1]
    algo: np.ndarray  # int32
    status: np.ndarray  # int32 (token)
    limit: np.ndarray  # int64
    remaining: np.ndarray  # int64 (token)
    remf_hi: np.ndarray  # int32 (leaky whole)
    remf_lo: np.ndarray  # uint32 (leaky fraction)
    duration: np.ndarray  # int64
    t0: np.ndarray  # int64: created_at (token) / updated_at (leaky)
    expire_at: np.ndarray  # int64
    burst: np.ndarray  # int64 (leaky)
    invalid_at: np.ndarray  # int64

    def __len__(self) -> int:
        return len(self.key_offsets) - 1

    def keys(self) -> List[str]:
        raw, off = self.key_buf.tobytes(), self.key_offsets
        return [raw[off[i]:off[i + 1]].decode() for i in range(len(self))]

    @classmethod
    def from_items(cls, items: Iterable["CacheItem"]) -> "ItemColumns":
        """Columns of the items that carry a value, in their order."""
        rows = []
        keys = []
        for it in items:
            v = it.value
            if isinstance(v, TokenBucketItem):
                row = (int(Algorithm.TOKEN_BUCKET), v.status, v.limit,
                       v.remaining, 0, 0, v.duration, v.created_at,
                       it.expire_at, 0)
            elif isinstance(v, LeakyBucketItem):
                hi, lo = (
                    v.remaining_words if v.remaining_words is not None
                    else words_from_float(v.remaining)
                )
                row = (int(Algorithm.LEAKY_BUCKET), 0, v.limit, 0, hi, lo,
                       v.duration, v.updated_at, it.expire_at, v.burst)
            else:
                continue
            keys.append(it.key.encode())
            rows.append(row + (it.invalid_at,))
        cols = list(zip(*rows)) or [()] * len(COLUMN_DTYPES)
        return cls(
            *pack_keys(keys),
            *(np.asarray(c, dtype=dt)
              for c, dt in zip(cols, COLUMN_DTYPES.values())),
        )

    def items(self) -> Iterator["CacheItem"]:
        """The rows as CacheItems, for an engine that restores per item."""
        for i, key in enumerate(self.keys()):
            yield item_from_record(
                key=key, algorithm=int(self.algo[i]),
                status=int(self.status[i]), limit=int(self.limit[i]),
                remaining=int(self.remaining[i]),
                remf_hi=int(self.remf_hi[i]), remf_lo=int(self.remf_lo[i]),
                duration=int(self.duration[i]), t0=int(self.t0[i]),
                expire_at=int(self.expire_at[i]), burst=int(self.burst[i]),
                invalid_at=int(self.invalid_at[i]),
            )


# The state columns of ItemColumns, in its field order.
COLUMN_DTYPES = {
    "algo": np.int32, "status": np.int32, "limit": np.int64,
    "remaining": np.int64, "remf_hi": np.int32, "remf_lo": np.uint32,
    "duration": np.int64, "t0": np.int64, "expire_at": np.int64,
    "burst": np.int64, "invalid_at": np.int64,
}


class Store(Protocol):
    """Write-through hooks, called by the engine per touched key.

    reference: store.go:49-65.
    """

    def on_change(self, req: RateLimitReq, item: CacheItem) -> None: ...

    def get(self, req: RateLimitReq) -> Optional[CacheItem]: ...

    def remove(self, key: str) -> None: ...


class Loader(Protocol):
    """Bulk restore/persist at startup/shutdown.

    reference: store.go:69-78.
    """

    def load(self) -> Iterable[CacheItem]: ...

    def save(self, items: Iterator[CacheItem]) -> None: ...

    # Optional: `load_columns() -> Iterable[ItemColumns]`, the same
    # stream a chunk of columns at a time.  An engine with a columnar
    # restore (`DecisionEngine.load`) takes it where the loader has it;
    # `load()` stays the path of every other engine and loader.


class MemoryStore:
    """Dict-backed Store (reference: MockStore, store.go:80-112)."""

    def __init__(self) -> None:
        self.data: Dict[str, CacheItem] = {}
        self.on_change_calls = 0
        self.get_calls = 0
        self.remove_calls = 0

    def on_change(self, req: RateLimitReq, item: CacheItem) -> None:
        self.on_change_calls += 1
        self.data[item.key] = item

    def get(self, req: RateLimitReq) -> Optional[CacheItem]:
        self.get_calls += 1
        return self.data.get(req.hash_key())

    def remove(self, key: str) -> None:
        self.remove_calls += 1
        self.data.pop(key, None)


class MemoryLoader:
    """List-backed Loader (reference: MockLoader, store.go:114-150)."""

    def __init__(self, items: Optional[List[CacheItem]] = None) -> None:
        self.items: List[CacheItem] = list(items or [])
        self.load_calls = 0
        self.save_calls = 0

    def load(self) -> Iterable[CacheItem]:
        self.load_calls += 1
        return list(self.items)

    def save(self, items: Iterator[CacheItem]) -> None:
        self.save_calls += 1
        self.items = list(items)
