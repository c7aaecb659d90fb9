"""The snapshot a filled node restores at start: what a long-running
node's `Loader.Save()` would have written, made from a seed.

Row `r` of the snapshot is a pure function of (the configuration's
`snapshot` block, `r`, the time the snapshot is dated): its key
`fill_f<r, 8 digits>` — another limit name and key prefix than the
traffic's `mix_*` / `uni` and `k<id>`, so no key the traffic sends is
ever a restored one — its algorithm, limit and duration by a hash of
`r` (as `traffic.LimitTable` draws the mix's), a bucket partly spent,
last touched less than a quarter of its duration before the date and
therefore live for three quarters of it after: no row expires inside
a run.  The order of the rows is the order they are loaded in, which
is the LRU order: row 0 is the oldest and goes first.

Columns come for any rows asked (`columns`; `chunks` cuts the snapshot
into pieces), named as the program's `Loader.load_columns` wants them; `states` gives the same rows to the
benchmark's reference (lib/lru_reference.py over lib/spec.py).  Nothing
of the program is imported here.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

import numpy as np

from . import spec
from .traffic import LEAKY, TOKEN, _ALGORITHMS, id_hash

CHUNK_ROWS = 1 << 20  # rows handed over at a time
KEY_DIGITS = 8  # rows < 1e8 → 14-byte keys, inside a short string's buffer


# "0000".."9999" as ASCII rows: a key's eight digits are two lookups.
_FOUR_DIGITS = (
    np.arange(10_000)[:, None] // 10 ** np.arange(3, -1, -1) % 10 + 48
).astype(np.uint8)


class Snapshot:
    def __init__(self, block: dict, rows: int, dated_ms: int):
        """`block`: the configuration's `snapshot`; `rows`: how many
        rows this table holds; `dated_ms`: the snapshot's date (the
        launcher's start)."""
        if rows > 10 ** KEY_DIGITS:
            raise ValueError(f"{rows} rows do not fit {KEY_DIGITS} digits")
        self.rows, self.dated_ms = int(rows), int(dated_ms)
        self.seed = int(block["seed"])
        self.name = block["name"]
        self._prefix = (
            self.name + "_" + block["unique_key"].split("<")[0]
        ).encode()
        self._algos = np.array(
            [_ALGORITHMS[a] for a in block["algorithms"]], dtype=np.int32)
        self._limits = np.array(block["limits"], dtype=np.int64)
        self._durations = np.array(block["durations_ms"], dtype=np.int64)
        self._sample = block["sample"]

    def _draws(self, r: np.ndarray) -> Tuple[np.ndarray, ...]:
        """Three independent 32-bit draws a row."""
        salt = np.int64((self.seed * 2654435761 + 97) & 0x7FFFFFFF)
        return tuple(
            id_hash((r * 3 + k) ^ salt) for k in range(3)
        )

    def columns(self, r: np.ndarray) -> Dict[str, np.ndarray]:
        """The rows numbered `r` (int64), in that order."""
        h, g, f = self._draws(r)
        u = np.uint64
        algo = self._algos[((h >> u(4)) % u(len(self._algos))).astype(np.int64)]
        limit = self._limits[((h >> u(8)) % u(len(self._limits))).astype(np.int64)]
        duration = self._durations[
            ((h >> u(16)) % u(len(self._durations))).astype(np.int64)]
        leaky = algo == LEAKY
        age = (g.astype(np.int64) % (duration // 4))
        t0 = self.dated_ms - age
        # token: 0..limit spent; leaky: a whole part of 0..limit-1 and
        # a 32-bit fraction
        spent = (g >> u(7)).astype(np.int64)
        keys = np.empty((len(r), len(self._prefix) + KEY_DIGITS), np.uint8)
        keys[:, : len(self._prefix)] = np.frombuffer(self._prefix, np.uint8)
        keys[:, -8:-4] = _FOUR_DIGITS[r // 10_000]
        keys[:, -4:] = _FOUR_DIGITS[r % 10_000]
        zero = np.zeros(len(r), dtype=np.int64)
        return {
            "key_buf": keys.reshape(-1),
            "key_offsets": np.arange(len(r) + 1, dtype=np.int64) * keys.shape[1],
            "algo": algo,
            "status": np.zeros(len(r), dtype=np.int32),
            "limit": limit,
            "remaining": np.where(leaky, 0, limit - spent % (limit + 1)),
            "remf_hi": np.where(leaky, spent % limit, 0).astype(np.int32),
            "remf_lo": np.where(leaky, f, 0).astype(np.uint32),
            "duration": duration,
            "t0": t0,
            "expire_at": t0 + duration,
            "burst": np.where(leaky, limit, 0),
            "invalid_at": zero,
        }

    def chunks(self, rows: int) -> Iterator[np.ndarray]:
        """The numbers of the first `rows` rows, CHUNK_ROWS at a time."""
        for lo in range(0, rows, CHUNK_ROWS):
            yield np.arange(lo, min(lo + CHUNK_ROWS, rows), dtype=np.int64)

    def sample(self) -> np.ndarray:
        """The rows the launcher asks the node about: drawn from the
        seed among the newest-loaded share of the snapshot, which the
        run's evictions (oldest first, ~1.5 M of 100 M) never reach."""
        newest = max(1, int(self.rows * float(self._sample["newest_share"])))
        n = min(int(self._sample["rows"]), newest)
        rng = np.random.default_rng([self.seed, self.rows])
        picked = rng.choice(newest, size=n, replace=False)
        return np.sort(self.rows - newest + picked)

    def states(self, rows: np.ndarray) -> List[Tuple[str, spec.SlotState]]:
        """(key, bucket state) of the given rows, for the reference."""
        rows = np.asarray(rows, dtype=np.int64)
        c = {k: v.tolist() for k, v in self.columns(rows).items()
             if k not in ("key_buf", "key_offsets")}
        out = []
        for i, r in enumerate(rows.tolist()):
            leaky = c["algo"][i] == LEAKY
            out.append((self.key(r), spec.SlotState(
                algorithm=LEAKY if leaky else TOKEN,
                limit=c["limit"][i], remaining=c["remaining"][i],
                remaining_f=(
                    c["remf_hi"][i] + c["remf_lo"][i] * 2.0**-32
                    if leaky else 0.0
                ),
                duration=c["duration"][i], t0=c["t0"][i],
                expire_at=c["expire_at"][i], burst=c["burst"][i],
                status=c["status"][i],
            )))
        return out

    def key(self, r: int) -> str:
        return f"{self._prefix.decode()}{r:0{KEY_DIGITS}d}"

    def unique_key(self, r: int) -> bytes:
        return self.key(r)[len(self.name) + 1:].encode()
