"""Checkpoint/resume: file-backed Loader for device-state snapshots.

The Loader interface (store.py) IS the checkpoint system, exactly as in
the reference (SURVEY.md §5.4): `engine.save(loader)` streams a
full-fidelity device→host snapshot out, `engine.load(loader)` streams
it back in before serving.  `NpzFileLoader` persists the stream as one
compressed npz of columnar arrays — the struct-of-arrays layout on
disk mirrors the layout in HBM — and hands it back as columns
(`load_columns`), so a restore is one numpy read, one bulk insert and
one device scatter a chunk, not a per-key walk.
"""

from __future__ import annotations

import os
from typing import Iterable, Iterator

import numpy as np

from gubernator_tpu.store import CacheItem, ItemColumns, pack_keys
from gubernator_tpu.types import Algorithm


class NpzFileLoader:
    """Loader that persists CacheItems to an .npz file."""

    def __init__(self, path: str):
        self.path = path

    def save(self, items: Iterator[CacheItem]) -> None:
        cols = ItemColumns.from_items(items)
        leaky = cols.algo != int(Algorithm.TOKEN_BUCKET)
        # .npz-suffixed temp name (savez would append the suffix
        # otherwise), swapped in atomically so a crash mid-save never
        # clobbers the previous checkpoint.
        tmp = self.path + ".tmp.npz"
        np.savez_compressed(
            tmp,
            keys=np.asarray(cols.keys(), dtype=object),
            algo=cols.algo,
            status=cols.status,
            limit=cols.limit,
            remaining_i=cols.remaining,
            # The float64 mirror of the exact 32.32 words below (it
            # rounds once whole parts exceed 2^21).
            remaining_f=np.where(
                leaky, cols.remf_hi + cols.remf_lo * 2.0**-32, 0.0
            ),
            remf_hi=cols.remf_hi,
            remf_lo=cols.remf_lo,
            duration=cols.duration,
            t0=cols.t0,
            expire=cols.expire_at,
            burst=cols.burst,
            invalid=cols.invalid_at,
        )
        os.replace(tmp, self.path)

    def load_columns(self) -> Iterable[ItemColumns]:
        """The file's arrays as one chunk; no CacheItem is built."""
        if not os.path.exists(self.path):
            return
        with np.load(self.path, allow_pickle=True) as z:
            key_buf, key_offsets = pack_keys(
                [k.encode() for k in z["keys"].tolist()])
            if "remf_hi" in z:
                remf_hi, remf_lo = z["remf_hi"], z["remf_lo"]
            else:
                # A file from before the exact words were kept: the
                # float mirror's words, as store.words_from_float's.
                whole = np.floor(z["remaining_f"])
                remf_hi = whole.astype(np.int32)
                remf_lo = np.minimum(
                    (z["remaining_f"] - whole) * 2.0**32, 2.0**32 - 1
                ).astype(np.uint32)
            yield ItemColumns(
                key_buf=key_buf, key_offsets=key_offsets,
                algo=z["algo"], status=z["status"], limit=z["limit"],
                remaining=z["remaining_i"], remf_hi=remf_hi,
                remf_lo=remf_lo, duration=z["duration"], t0=z["t0"],
                expire_at=z["expire"], burst=z["burst"],
                invalid_at=z["invalid"],
            )

    def load(self) -> Iterable[CacheItem]:
        for cols in self.load_columns():
            yield from cols.items()
