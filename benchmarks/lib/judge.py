"""The comparison that decides `correct`: every answer on a sampled
key, warm-up and window alike, held to the benchmark's own reference.

Callers share keys (lib/traffic.py) and their RPCs overlap, so no
caller knows the order in which the node applied the hits on a key.
The configuration states that it applied them one after another, each
exactly as the reference would.  A key is therefore judged by finding
such an order: a sequence of all its answers that

  - keeps each caller's own order (its RPCs are sequential, and the
    items of one RPC are applied in item order),
  - keeps real time (an answer received before another request was
    sent comes before it), and
  - has the reference, started from an empty bucket and run down the
    sequence, give every answer exactly — status, limit, remaining and
    reset_time — at a clock that lies between that RPC's send and
    receive.

The node's clock is not known either, but the answers give it away
where it matters: a leaky bucket answers `reset_time = now + (limit -
remaining) · rate` every time, and a fresh token bucket `reset_time =
now + duration`; a later hit on a live token bucket does not depend on
the clock at all.  The node reads its clock before it takes its turn
(`core/engine.py`: `now_ms` is read outside the engine lock), so the
order of the clocks is only a hint for the order of the hits: the
search tries the hinted answer first and backs up where it fails.

Compared: the number of keys for which no such sequence exists
(`mismatched`, limit 0), the RPCs that were never answered in full
(limit 0), and the answers checked (at least the cell's floor).
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from . import spec
from .traffic import LEAKY, LimitTable, id_hash, scramble

HOT_RANKS = 16  # the law's hottest ids are always judged
SEND_SLACK_MS, RECV_SLACK_MS = 1, 2  # the two clocks tick apart

# One row for each judged answer, as the clients hand them over.
COLUMNS = (
    "id", "cfg", "caller", "seq", "t0", "t1",
    "status", "limit", "remaining", "reset", "error",
)


class Record:
    """One RPC as its caller saw it."""

    __slots__ = ("pool_index", "t_send_ms", "t_recv_ms", "raw", "error")

    def __init__(self, pool_index, t_send_ms, t_recv_ms, raw, error):
        self.pool_index = pool_index
        self.t_send_ms = t_send_ms
        self.t_recv_ms = t_recv_ms
        self.raw = raw
        self.error = error


def hot_ids(n_ids: int) -> np.ndarray:
    return scramble(np.arange(min(HOT_RANKS, n_ids)), n_ids)


def sampled_mask(ids: np.ndarray, seed: int, share: float,
                 always: np.ndarray) -> np.ndarray:
    """Which ids are judged: a share of the id space drawn from the
    seed, and the hottest ids whatever the draw.  A key is judged with
    all of its answers or with none."""
    if share >= 1.0:
        return np.ones(len(ids), dtype=bool)
    salt = np.uint64((int(seed) * 2654435761 + 97) & 0xFFFFFFFF)
    draw = (id_hash(ids ^ salt.astype(np.int64)) % np.uint64(10000))
    return (draw < np.uint64(int(share * 10000))) | np.isin(ids, always)


def collect(decode, pools: dict, records: dict, items_per_rpc: int,
            seed: int, share: float, always: np.ndarray) -> dict:
    """What one client process hands to the judge: a row for every
    answer on a sampled key (`COLUMNS`), the keys of RPCs that were not
    answered in full (whether their hits were applied is unknown), and
    the counts over all answers.  `pools` and `records` are by caller;
    `decode` turns response bytes into answers."""
    rows = {name: [] for name in COLUMNS}
    unknown_id, unknown_cfg = [], []
    counts = {"answered_items": 0, "failed_items": 0, "over": 0,
              "unanswered_rpcs": 0}
    for caller, recs in records.items():
        pool = pools[caller]
        for ordinal, rec in enumerate(recs):
            ids = pool.ids[rec.pool_index]
            cfgs = pool.configs[rec.pool_index]
            answers = [] if rec.raw is None else decode(rec.raw)
            mask = sampled_mask(ids, seed, share, always)
            if len(answers) != len(ids):
                counts["unanswered_rpcs"] += 1
                counts["failed_items"] += len(ids)
                unknown_id.append(ids[mask])
                unknown_cfg.append(cfgs[mask])
                continue
            errors = sum(1 for a in answers if a.error)
            counts["failed_items"] += errors
            counts["answered_items"] += len(ids) - errors
            counts["over"] += sum(1 for a in answers if a.status == 1)
            picked = np.flatnonzero(mask)
            if not picked.size:
                continue
            got = [answers[i] for i in picked.tolist()]
            rows["id"].append(ids[picked])
            rows["cfg"].append(cfgs[picked])
            rows["caller"].append(np.full(picked.size, caller))
            rows["seq"].append(ordinal * items_per_rpc + picked)
            rows["t0"].append(
                np.full(picked.size, int(rec.t_send_ms) - SEND_SLACK_MS))
            rows["t1"].append(
                np.full(picked.size, int(rec.t_recv_ms) + RECV_SLACK_MS))
            rows["status"].append([a.status for a in got])
            rows["limit"].append([a.limit for a in got])
            rows["remaining"].append([a.remaining for a in got])
            rows["reset"].append([a.reset_time for a in got])
            rows["error"].append([bool(a.error) for a in got])
    out = {
        name: (np.concatenate([np.asarray(p, dtype=np.int64) for p in parts])
               if parts else np.zeros(0, dtype=np.int64))
        for name, parts in rows.items()
    }
    empty = [np.zeros(0, dtype=np.int64)]
    out["unknown_id"] = np.concatenate(unknown_id or empty).astype(np.int64)
    out["unknown_cfg"] = np.concatenate(unknown_cfg or empty).astype(np.int64)
    out["counts"] = counts
    return out


class KeySearch:
    """All answers on one key → a sequence the reference agrees with,
    or the point past which there is none."""

    def __init__(self, cfg, inp, caller, seq, t0, t1, got, clocks, rank):
        self.cfg, self.inp = cfg, inp
        self.t0, self.t1, self.got, self.clocks = t0, t1, got, clocks
        self.rank = rank
        self.n = len(got)
        by_caller: Dict[int, List[int]] = {}
        for r in sorted(range(self.n), key=seq.__getitem__):
            by_caller.setdefault(caller[r], []).append(r)
        self.queues = list(by_caller.values())
        self.queue_of = [0] * self.n
        for q, rows in enumerate(self.queues):
            for r in rows:
                self.queue_of[r] = q
        self.heads = [0] * len(self.queues)
        self.placed: List[int] = []
        self.deepest = 0
        self.stuck: Optional[dict] = None
        self.tries = 0

    def _step(self, state, r):
        """The reference's state after answer `r`, if it gives it."""
        got = self.got[r]
        for now in self.clocks[r]:
            self.tries += 1
            new_state, out = spec.apply_spec(state, self.inp, now)
            if got == (out.status, out.limit, out.remaining, out.reset_time):
                return new_state
        return None

    def _candidates(self, last: Optional[int]) -> List[int]:
        """The answers that may come next: each caller's earliest one
        not yet placed, unless another pending answer had been
        received before it was sent.  First the next item of the RPC
        whose item was placed last (the program applies an RPC's items
        on a key together), then the hinted order."""
        pending = [
            rows[h] for rows, h in zip(self.queues, self.heads)
            if h < len(rows)
        ]
        closes = min(self.t1[r] for r in pending)
        follows = None
        if last is not None:
            q = self.queue_of[last]
            rows, h = self.queues[q], self.heads[q]
            if h < len(rows) and self.t0[rows[h]] == self.t0[last]:
                follows = rows[h]
        rank = self.rank
        return sorted(
            (r for r in pending if self.t0[r] <= closes),
            key=lambda r: -1 if r == follows else rank[r],
        )

    def _node(self, state) -> tuple:
        """What the rest of the search depends on: which answers are
        placed, and the bucket."""
        if state is None:
            return (tuple(self.heads),)
        return (tuple(self.heads), state.remaining, state.remaining_f,
                state.t0, state.expire_at, state.status)

    def _note_stuck(self, state, cands) -> None:
        r = cands[0]
        clocks = self.clocks[r] or [self.t0[r]]
        _, want = spec.apply_spec(state, self.inp, clocks[0])
        self.stuck = {
            "key": f"{self.cfg.name}_k?", "placed": len(self.placed),
            "of": self.n, "got": list(self.got[r]), "want_at": clocks[0],
            "want": [want.status, want.limit, want.remaining,
                     want.reset_time],
            "window_ms": [self.t0[r], self.t1[r]],
            "pending_next": [list(self.got[c]) for c in cands[:4]],
            "last_placed": [
                [self.t0[p], self.got[p][0], self.got[p][2]]
                for p in self.placed[-6:]
            ],
        }

    def run(self) -> bool:
        """Depth first, the likeliest answer first.  A point of the
        search (the answers placed and the bucket's state) from which
        no sequence was found is remembered, so that it is not searched
        again by another way of reaching it."""
        budget = 16 * (len(self.queues) + 2) * self.n + 50_000
        frames = [[self._candidates(None), 0, None]]
        dead = set()
        while frames:
            frame = frames[-1]
            cands, i, state = frame
            placed_one = False
            while i < len(cands):
                r = cands[i]
                i += 1
                new_state = self._step(state, r)
                if new_state is None:
                    continue
                q = self.queue_of[r]
                self.heads[q] += 1
                if dead and self._node(new_state) in dead:
                    self.heads[q] -= 1
                    continue
                frame[1] = i
                self.placed.append(r)
                if len(self.placed) == self.n:
                    self.deepest = self.n
                    return True
                frames.append([self._candidates(r), 0, new_state])
                placed_one = True
                break
            if placed_one:
                continue
            if len(self.placed) >= self.deepest:
                self.deepest = len(self.placed)
                self._note_stuck(state, cands)
            dead.add(self._node(state))
            frames.pop()
            if self.placed:
                self.heads[self.queue_of[self.placed.pop()]] -= 1
            if self.tries > budget:
                self.stuck["search"] = "gave up"
                break
        return False


def derived_clock(cfg, got) -> int:
    """The node's clock, as the answer's reset_time gives it away: for
    a leaky bucket always, for a token bucket if it was fresh."""
    if cfg.algorithm == LEAKY:
        return got[3] - (cfg.limit - got[2]) * (cfg.duration // cfg.limit)
    return got[3] - cfg.duration


def judge_answers(table: LimitTable, cols: dict) -> dict:
    """Judge every key of the merged rows.  Returns the counts compared
    and the first few keys that found no sequence."""
    inputs = [
        spec.SpecInput(hits=c.hits, limit=c.limit, duration=c.duration,
                       burst=c.burst, algorithm=c.algorithm)
        for c in table.configs
    ]
    out = {"checked": 0, "mismatched": 0, "keys": 0, "shared_keys": 0,
           "reordered_keys": 0, "first_mismatches": []}
    n = len(cols["id"])
    if n == 0:
        return out
    bucket = bucket_of(cols)
    unknown = np.unique(cols["unknown_id"] * 256 + cols["unknown_cfg"])
    order = np.lexsort((cols["seq"], cols["caller"], bucket))
    bucket = bucket[order]
    c = {name: cols[name][order] for name in COLUMNS}
    starts = np.flatnonzero(np.r_[True, bucket[1:] != bucket[:-1]])
    ends = np.r_[starts[1:], n]
    skip = np.isin(bucket[starts], unknown)

    # Keys answered once: the answer is a fresh bucket's, at a clock
    # inside the RPC.  Most keys of the law's tail; done in one pass.
    single = (ends - starts == 1) & ~skip
    s = starts[single]
    cfg_i = c["cfg"][s]
    lim = np.asarray([x.limit for x in table.configs])[cfg_i]
    dur = np.asarray([x.duration for x in table.configs])[cfg_i]
    leaky = np.asarray([x.algorithm == LEAKY for x in table.configs])[cfg_i]
    clock = c["reset"][s] - np.where(leaky, dur // lim, dur)
    fresh = (
        (c["error"][s] == 0) & (c["status"][s] == 0) & (c["limit"][s] == lim)
        & (c["remaining"][s] == lim - 1)
        & (clock >= c["t0"][s]) & (clock <= c["t1"][s])
    )
    out["checked"] += int(single.sum())
    out["keys"] += int(single.sum())
    out["mismatched"] += int((~fresh).sum())
    for j in np.flatnonzero(~fresh)[:5].tolist():
        r = int(s[j])
        out["first_mismatches"].append({
            "key": f"{table.configs[int(cfg_i[j])].name}_k{int(c['id'][r])}",
            "placed": 0, "of": 1,
            "got": [int(c[k][r]) for k in ("status", "limit", "remaining",
                                           "reset")],
            "want": [0, int(lim[j]), int(lim[j]) - 1, "clock + rate|duration"],
            "window_ms": [int(c["t0"][r]), int(c["t1"][r])],
        })

    lists = {name: c[name].tolist() for name in COLUMNS}
    for a, b in zip(starts[~single & ~skip].tolist(),
                    ends[~single & ~skip].tolist()):
        cfg = table.configs[lists["cfg"][a]]
        got = list(zip(lists["status"][a:b], lists["limit"][a:b],
                       lists["remaining"][a:b], lists["reset"][a:b]))
        t0, t1 = lists["t0"][a:b], lists["t1"][a:b]
        caller, seq = lists["caller"][a:b], lists["seq"][a:b]
        out["keys"] += 1
        out["shared_keys"] += len(set(caller)) > 1
        if any(lists["error"][a:b]):
            out["checked"] += b - a
            out["mismatched"] += 1
            continue
        clocks, hint = [], []
        for g, lo, hi in zip(got, t0, t1):
            d = derived_clock(cfg, g)
            inside = [d] if lo <= d <= hi else []
            if cfg.algorithm == LEAKY:
                clocks.append(inside)
                hint.append((d, g[0], -g[2]))
            else:
                # a later hit on a live bucket: the earliest clock the
                # RPC allows is the one most likely to find it live
                clocks.append(inside + [lo])
                hint.append((g[3], g[0], -g[2]))
        by_hint = sorted(
            range(b - a), key=lambda r: (hint[r], t0[r], caller[r], seq[r]))
        rank = [0] * (b - a)
        for position, r in enumerate(by_hint):
            rank[r] = position
        search = KeySearch(cfg, inputs[lists["cfg"][a]], caller, seq, t0, t1,
                           got, clocks, rank)
        ok = search.run()
        out["checked"] += search.deepest
        out["reordered_keys"] += ok and search.placed != by_hint
        if not ok:
            out["mismatched"] += 1
            if len(out["first_mismatches"]) < 5 and search.stuck:
                search.stuck["key"] = f"{cfg.name}_k{lists['id'][a]}"
                search.stuck["tries"] = search.tries
                out["first_mismatches"].append(search.stuck)
    out["first_mismatches"] = out["first_mismatches"][:5]
    return out


def bucket_of(cols: dict) -> np.ndarray:
    """A bucket is (name, id), and the name follows from the
    configuration's index."""
    return cols["id"] * 256 + cols["cfg"]


def merge_columns(parts: List[dict]) -> dict:
    """The clients' hand-overs as one: rows joined, counts added."""
    cols = {
        name: np.concatenate([p[name] for p in parts])
        for name in COLUMNS + ("unknown_id", "unknown_cfg")
    }
    cols["counts"] = {
        k: sum(p["counts"][k] for p in parts) for k in parts[0]["counts"]
    }
    return cols


def verdict(total: dict, min_checked: int) -> dict:
    """Each number compared, beside its limit; `correct` is their and."""
    compared = {
        "mismatched": {"value": total["mismatched"], "limit": 0},
        "unanswered_rpcs": {"value": total["unanswered_rpcs"], "limit": 0},
        "checked": {"value": total["checked"], "at_least": min_checked},
    }
    ok = (
        total["mismatched"] == 0 and total["unanswered_rpcs"] == 0
        and total["checked"] >= min_checked
    )
    return {"correct": ok, "compared": compared}
