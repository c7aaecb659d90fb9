"""The one general traffic generator.  A mix is a data file of
parameters (`benchmarks/mixes/<name>.json`); this module turns a mix, a
seed and a caller's index into that caller's pool of pre-encoded
`GetRateLimitsReq` payloads, with the ids and limit configurations
kept beside each payload for the judge.

Keys: ranks drawn from one bounded Zipf law over `keys.ids` ids, the
same law for every caller, the rank scrambled by an affine bijection so
that hot ids are spread over the id space.  Callers therefore share
keys: the hottest id is ~4.8 % of every caller's items at exponent 0.99
over 1e8 ids (~48 of an RPC's 1,000), so concurrent RPCs contend for
the same buckets and the order in which the node applied their hits is
not known to any one caller (lib/judge.py finds one).  A mixed caller's
bucket of an id is `mix_<n>_k<id>`, a single-limit caller's `uni_k<id>`:
the name is part of the key.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List

import numpy as np

from . import wire

TOKEN, LEAKY = 0, 1
_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


class BoundedZipf:
    """P(rank k) ∝ k^-s for k = 1..n, any s > 0.

    The first `head` ranks come from an exact cumulative table; the
    rest from the inverse of ∫ x^-s dx over [k-½, k+½), whose relative
    error against k^-s is below s(s+1)/(24 k²) — under 1e-10 past the
    default head."""

    def __init__(self, n: int, s: float, head: int = 1 << 16):
        if n < 1 or s <= 0:
            raise ValueError(f"bounded Zipf needs n >= 1 and s > 0: {n}, {s}")
        self.n, self.s = int(n), float(s)
        k = min(self.n, int(head))
        self._head_cum = np.cumsum(
            np.arange(1, k + 1, dtype=np.float64) ** -self.s
        )
        self._head_mass = float(self._head_cum[-1])
        self._a = k + 0.5
        self._tail_mass = (
            self._integral(self.n + 0.5) - self._integral(self._a)
            if self.n > k else 0.0
        )
        self.total = self._head_mass + self._tail_mass

    def _integral(self, x: float) -> float:
        if self.s == 1.0:
            return math.log(x)
        return x ** (1.0 - self.s) / (1.0 - self.s)

    def draw(self, rng: np.random.Generator, size: int) -> np.ndarray:
        """`size` ranks, 0-based (0 is the hottest)."""
        u = rng.random(size) * self.total
        out = np.empty(size, dtype=np.int64)
        head = u < self._head_mass
        out[head] = np.searchsorted(self._head_cum, u[head], side="right")
        v = u[~head] - self._head_mass + self._integral(self._a)
        if self.s == 1.0:
            x = np.exp(v)
        else:
            x = (v * (1.0 - self.s)) ** (1.0 / (1.0 - self.s))
        out[~head] = np.floor(x + 0.5).astype(np.int64) - 1
        return np.clip(out, 0, self.n - 1)

    def pmf(self, rank: int) -> float:
        """Analytic probability of the 0-based rank."""
        return (rank + 1) ** -self.s / self.total


def scramble_multiplier(n: int) -> int:
    """An odd multiplier coprime with n, for the affine rank → id map."""
    a = (2654435761 % n) | 1
    while math.gcd(a, n) != 1:
        a += 2
    return a


def scramble(ranks: np.ndarray, n: int) -> np.ndarray:
    """A bijection on [0, n): (rank · a + b) mod n."""
    a = scramble_multiplier(n)
    b = 0x5BD1E995 % n
    return (ranks * a + b) % n


def id_hash(ids: np.ndarray) -> np.ndarray:
    return (ids.astype(np.uint64) * _GOLDEN) >> np.uint64(32)


@dataclass(frozen=True)
class LimitConfig:
    """What a request carries about its limit.  A request repeats its
    own configuration, so it has to be a pure function of the key."""

    name: str
    algorithm: int
    limit: int
    duration: int
    burst: int
    hits: int


class LimitTable:
    """Every limit configuration a mix can send, by index.  Index 0 is
    the single-limit caller's; the mixed callers' follow, one for each
    (name, algorithm, limit, duration)."""

    def __init__(self, mix: dict):
        u, m = mix["uniform"], mix["mixed"]
        self.configs: List[LimitConfig] = [self._one(
            u["name"], u["algorithm"], u["limit"], u["duration_ms"],
            u["hits"],
        )]
        self._names = len(m["names"])
        self._algos = [_ALGORITHMS[a] for a in m["algorithms"]]
        self._limits, self._durations = m["limits"], m["durations_ms"]
        for name in m["names"]:
            for algo in m["algorithms"]:
                for limit in self._limits:
                    for dur in self._durations:
                        self.configs.append(
                            self._one(name, algo, limit, dur, m["hits"])
                        )
        self.heads = []  # per config: {key length: leading bytes}
        self.suffixes = []
        for c in self.configs:
            prefix = wire.name_prefix(c.name)
            suffix = wire.item_suffix(
                c.hits, c.limit, c.duration, c.algorithm, 0, c.burst
            )
            by_len = {}
            for klen in range(1, 24):
                body = len(prefix) + 2 + klen + len(suffix)
                by_len[klen] = (
                    b"\x0a" + wire.varint(body) + prefix + b"\x12"
                    + wire.varint(klen)
                )
            self.heads.append(by_len)
            self.suffixes.append(suffix)

    @staticmethod
    def _one(name, algo, limit, duration, hits) -> LimitConfig:
        algorithm = _ALGORITHMS[algo]
        return LimitConfig(
            name=name, algorithm=algorithm, limit=int(limit),
            duration=int(duration),
            burst=int(limit) if algorithm == LEAKY else 0, hits=int(hits),
        )

    def mixed_index(self, ids: np.ndarray) -> np.ndarray:
        """The mixed callers' configuration of each id: a pure function
        of the id."""
        h = id_hash(ids)
        name = (h % np.uint64(self._names)).astype(np.int64)
        algo = ((h >> np.uint64(4)) % np.uint64(len(self._algos))).astype(np.int64)
        limit = ((h >> np.uint64(8)) % np.uint64(len(self._limits))).astype(np.int64)
        dur = ((h >> np.uint64(16)) % np.uint64(len(self._durations))).astype(np.int64)
        n_a, n_l, n_d = len(self._algos), len(self._limits), len(self._durations)
        return 1 + ((name * n_a + algo) * n_l + limit) * n_d + dur


_ALGORITHMS = {"token": TOKEN, "leaky": LEAKY}


def is_uniform_caller(mix: dict, caller: int) -> bool:
    every = int(mix.get("uniform_caller_every", 0))
    return bool(every) and caller % every == every - 1


@dataclass
class Pool:
    """One caller's payloads, in the order it sends them."""

    caller: int
    payloads: List[bytes]
    ids: List[np.ndarray]      # per payload: the id of each item
    configs: List[np.ndarray]  # per payload: LimitTable index of each item


def build_pool(mix: dict, seed: int, caller: int, n_rpcs: int,
               table: LimitTable = None) -> Pool:
    """The caller's first `n_rpcs` payloads.  The same (mix, seed,
    caller) gives the same bytes; a longer pool starts with the
    shorter one's payloads."""
    table = table or LimitTable(mix)
    keys = mix["keys"]
    n_ids = int(keys["ids"])
    items = int(mix["items_per_rpc"])
    zipf = BoundedZipf(n_ids, float(keys["exponent"]))
    uniform = is_uniform_caller(mix, caller)
    pool = Pool(caller, [], [], [])
    heads, suffixes = table.heads, table.suffixes
    # One generator per RPC, so that a pool's length never changes
    # what its earlier payloads hold.
    for j in range(n_rpcs):
        rng = np.random.default_rng([int(seed), caller, j])
        ids = scramble(zipf.draw(rng, items), n_ids)
        cfg = (
            np.zeros(items, dtype=np.int64) if uniform
            else table.mixed_index(ids)
        )
        parts = []
        for i, c in zip(ids.tolist(), cfg.tolist()):
            k = b"k%d" % i
            parts.append(heads[c][len(k)] + k + suffixes[c])
        pool.payloads.append(b"".join(parts))
        pool.ids.append(ids)
        pool.configs.append(cfg.astype(np.uint8))
    return pool

