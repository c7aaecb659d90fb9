"""The generator, the wire format, the reference's copy, and the judge
with its control."""

import json
import os
import random

import numpy as np
import pytest

from conftest import BENCH
from lib import judge, spec, traffic, wire


def load_mix(name, **keys):
    with open(os.path.join(BENCH, "mixes", f"{name}.json")) as f:
        mix = json.load(f)
    mix["keys"] = dict(mix["keys"], **keys)
    return mix


@pytest.fixture(scope="module")
def mix():
    return load_mix("batch1000_zipf", ids=4000)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_same_seed_same_bytes_other_seed_other_bytes(mix, seed):
    a = traffic.build_pool(mix, seed, 2, 6)
    b = traffic.build_pool(mix, seed, 2, 6)
    assert a.payloads == b.payloads
    assert all((x == y).all() for x, y in zip(a.ids, b.ids))
    other = traffic.build_pool(mix, seed + 1, 2, 6)
    assert other.payloads != a.payloads
    # a longer pool starts with the shorter one's payloads
    assert traffic.build_pool(mix, seed, 2, 3).payloads == a.payloads[:3]
    # no payload repeats inside a pool
    assert len(set(a.payloads)) == len(a.payloads)


def test_callers_share_the_one_law(mix):
    """Every caller draws from the same Zipf law over the same ids, so
    concurrent callers hit the same buckets; the single-limit callers'
    bucket of an id is another one (the name is part of the key)."""
    pools = [traffic.build_pool(mix, 5, c, 3) for c in range(mix["callers"])]
    ids = [np.concatenate(p.ids) for p in pools]
    for x in ids:
        assert x.min() >= 0 and x.max() < mix["keys"]["ids"]
    hottest = traffic.scramble(np.arange(1), mix["keys"]["ids"])[0]
    shares = [(x == hottest).mean() for x in ids]
    law = traffic.BoundedZipf(mix["keys"]["ids"], mix["keys"]["exponent"]).pmf(0)
    assert all(abs(s_ - law) < 0.03 for s_ in shares), (shares, law)
    assert len(set(ids[0].tolist()) & set(ids[1].tolist())) > 100
    assert pools[0].payloads[0] != pools[1].payloads[0]


@pytest.mark.parametrize("n,head", [(1000, 1 << 16), (1000, 50), (50_000, 64)])
def test_bounded_zipf_rank_frequency_matches_the_law(n, head):
    z = traffic.BoundedZipf(n, 0.99, head=head)
    draws = 400_000
    ranks = z.draw(np.random.default_rng(1), draws)
    assert ranks.min() >= 0 and ranks.max() < n
    counts = np.bincount(ranks, minlength=n)
    for r in (0, 1, 2, 9, 99):
        want = draws * z.pmf(r)
        assert abs(counts[r] - want) < 5 * np.sqrt(want) + 1, (r, counts[r], want)
    # and in aggregate across the boundary between table and tail
    for lo, hi in ((0, 10), (10, 100), (100, n)):
        want = draws * sum(z.pmf(r) for r in range(lo, min(hi, 2000)))
        if hi <= 2000:
            assert abs(counts[lo:hi].sum() - want) < 5 * np.sqrt(want)
    total = sum(z.pmf(r) for r in range(n)) if n <= 1000 else 1.0
    assert total == pytest.approx(1.0, abs=1e-5)  # the tail is an integral


def test_scramble_is_a_bijection_and_spreads_hot_ranks():
    n = 4000
    ids = traffic.scramble(np.arange(n), n)
    assert sorted(ids.tolist()) == list(range(n))
    assert np.ptp(ids[:8]) > n // 4


def test_limit_config_is_a_pure_function_of_the_id(mix):
    table = traffic.LimitTable(mix)
    ids = np.arange(0, 4000)
    first, again = table.mixed_index(ids), table.mixed_index(ids.copy())
    assert (first == again).all() and first.min() >= 1
    assert len(set(first.tolist())) == len(table.configs) - 1  # every combination
    pool_a = traffic.build_pool(mix, 1, 0, 4, table)
    pool_b = traffic.build_pool(mix, 99, 0, 4, table)
    seen = {}
    for pool in (pool_a, pool_b):
        for ids_, cfgs in zip(pool.ids, pool.configs):
            for i, c in zip(ids_.tolist(), cfgs.tolist()):
                assert seen.setdefault(i, c) == c
    # one caller in four sends a single limit
    uni = traffic.build_pool(mix, 1, 3, 2, table)
    assert all((c == 0).all() for c in uni.configs)
    assert table.configs[0].name == "uni"
    for c in table.configs:
        assert c.burst == (c.limit if c.algorithm == traffic.LEAKY else 0)


def test_payload_is_the_wire_format_the_program_parses(mix):
    from gubernator_tpu.net.pb import gubernator_pb2 as pb

    table = traffic.LimitTable(mix)
    pool = traffic.build_pool(mix, 3, 1, 1, table)
    theirs = pb.GetRateLimitsReq.FromString(pool.payloads[0]).requests
    ours = wire.decode_request(pool.payloads[0])
    assert len(theirs) == len(ours) == mix["items_per_rpc"]
    for t, o, i, c in zip(theirs, ours, pool.ids[0].tolist(),
                          pool.configs[0].tolist()):
        cfg = table.configs[c]
        assert (t.name, t.unique_key) == (cfg.name, f"k{i}") == (o.name, o.unique_key)
        assert (t.hits, t.limit, t.duration, int(t.algorithm), t.burst) == (
            cfg.hits, cfg.limit, cfg.duration, cfg.algorithm, cfg.burst)
        assert int(t.behavior) == 0
    resp = pb.GetRateLimitsResp(responses=[
        pb.RateLimitResp(status=1, limit=10, remaining=0, reset_time=1 << 41),
        pb.RateLimitResp(limit=5, remaining=4, reset_time=77, error="boom",
                         metadata={"a": "b"}),
    ])
    got = wire.decode_response(resp.SerializeToString())
    assert [(a.status, a.limit, a.remaining, a.reset_time, a.error) for a in got] == [
        (1, 10, 0, 1 << 41, ""), (0, 5, 4, 77, "boom")]
    rows = [(1, 10, 0, 1 << 41), (0, 5, 4, 77)]
    back = pb.GetRateLimitsResp.FromString(wire.encode_response(rows)).responses
    assert [(int(a.status), a.limit, a.remaining, a.reset_time) for a in back] == rows


def test_the_reference_copy_agrees_with_the_programs_spec():
    from gubernator_tpu.models import spec as theirs

    rng = random.Random(23)
    keys = [f"k{i}" for i in range(24)]
    ours_s, theirs_s, now = {}, {}, 1_700_000_000_000
    for _ in range(6000):
        key = rng.choice(keys)
        behavior = 8 if rng.random() < 0.1 else 0
        fields = dict(
            hits=rng.choice([-1, 0, 1, 1, 1, 2, 5, 100]),
            limit=rng.choice([0, 1, 2, 10, 100, 1000]),
            duration=rng.choice([1, 5, 1000, 60000, 3_600_000]),
            burst=rng.choice([0, 0, 5, 20]), algorithm=rng.choice([0, 1]),
            behavior=behavior,
        )
        if rng.random() < 0.1:
            fields.update(behavior=behavior | 4, greg_duration=86_400_000,
                          greg_expire=now - now % 86_400_000 + 86_400_000)
        a_state, a_out = spec.apply_spec(
            ours_s.get(key), spec.SpecInput(**fields), now)
        b_state, b_out = theirs.apply_spec(
            theirs_s.get(key), theirs.SpecInput(**fields), now)
        assert (a_out.status, a_out.limit, a_out.remaining, a_out.reset_time) == (
            int(b_out.status), b_out.limit, b_out.remaining, b_out.reset_time)
        for states, state in ((ours_s, a_state), (theirs_s, b_state)):
            if state is None:
                states.pop(key, None)
            else:
                states[key] = state
        now += rng.choice([0, 0, 1, 3, 7, 100, 1000, 40000])


class Served:
    """Several closed-loop callers through the control's reference in
    rounds: in a round every caller has one RPC in flight, the node
    reads each RPC's clock when it arrives and applies the RPCs in
    another order, as the program does (clock read outside the lock)."""

    def __init__(self, mix, seed, fault="none", callers=4, rounds=8,
                 step_ms=40, share=1.0, lose_updates_in_round=None,
                 late_ms=2):
        from lib import control_server as cs

        self.mix, self.seed, self.share = mix, seed, share
        self.table = traffic.LimitTable(mix)
        self.pools = {c: traffic.build_pool(mix, seed, c, rounds, self.table)
                      for c in range(callers)}
        self.records = {c: [] for c in range(callers)}
        ref, rng = cs.Reference(fault), random.Random(seed)
        clock = [0]
        real, cs.time.time_ns = cs.time.time_ns, lambda: clock[0] * 1_000_000
        try:
            for r in range(rounds):
                base = 1_790_000_000_000 + r * step_ms
                order = list(range(callers))
                rng.shuffle(order)
                before = dict(ref.states)
                for turn, c in enumerate(order):
                    t_send = base + rng.randint(0, 3)
                    clock[0] = t_send + rng.randint(0, late_ms)
                    if r == lose_updates_in_round and turn == 1:
                        ref.states = dict(before)  # the first RPC's writes are lost
                    raw = ref.serve(self.pools[c].payloads[r])
                    self.records[c].append(
                        judge.Record(r, t_send, base + late_ms + 6 + turn, raw, ""))
        finally:
            cs.time.time_ns = real

    def judged(self, records=None):
        handed = judge.collect(
            wire.decode_response, self.pools, records or self.records,
            self.mix["items_per_rpc"], self.seed, self.share,
            judge.hot_ids(self.mix["keys"]["ids"]))
        cols = judge.merge_columns([handed])
        return dict(cols["counts"], **judge.judge_answers(self.table, cols))


@pytest.mark.parametrize("seed", [1, 2, 3000000019])
def test_the_reference_in_the_programs_place_is_correct(mix, seed):
    s = Served(mix, seed).judged()
    assert s["mismatched"] == 0 and s["checked"] == 4 * 8 * 1000
    assert s["shared_keys"] > 100, "callers share keys"
    assert s["reordered_keys"] > 0, "some keys were not applied in clock order"
    assert s["over"] > 0, "finite limits: hot ids go OVER_LIMIT"
    assert judge.verdict(s, 1000)["correct"] is True


@pytest.mark.parametrize("seed", [1, 2, 3000000019])
def test_control_stale_answers_come_out_not_correct(mix, seed):
    s = Served(mix, seed, "stale").judged()
    assert s["mismatched"] > 0
    v = judge.verdict(s, 1000)
    assert v["correct"] is False
    assert v["compared"]["mismatched"] == {"value": s["mismatched"], "limit": 0}


@pytest.mark.parametrize("seed", [11, 12])
def test_clocks_far_out_of_order_are_still_placed(mix, seed):
    """Eight callers whose clocks were read up to 25 ms before their
    turn: the hot one-token-a-millisecond leaky buckets then admit many
    wrong beginnings, which the search has to back out of."""
    s = Served(mix, seed, callers=8, rounds=12, step_ms=60, late_ms=25).judged()
    assert s["mismatched"] == 0 and s["checked"] == 8 * 12 * 1000
    assert s["reordered_keys"] > 20


def test_control_fails_the_single_item_mix_too():
    herd = load_mix("herd100", ids=400)
    s = Served(herd, 4, "stale", callers=20, rounds=150, step_ms=30).judged()
    assert s["mismatched"] > 0
    s = Served(herd, 4, "none", callers=20, rounds=150, step_ms=30).judged()
    assert s["mismatched"] == 0 and s["checked"] == 3000


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_a_write_lost_between_two_callers_is_caught(mix, seed):
    """Per-key atomicity across callers: two RPCs in flight together,
    the second applied to the state from before the first."""
    assert Served(mix, seed).judged()["mismatched"] == 0
    s = Served(mix, seed, lose_updates_in_round=3).judged()
    assert s["mismatched"] > 0
    assert judge.verdict(s, 1)["correct"] is False


def altered_record(rec, rows):
    return judge.Record(rec.pool_index, rec.t_send_ms, rec.t_recv_ms,
                        wire.encode_response(rows), "")


def test_an_altered_answer_an_error_and_a_lost_rpc_are_caught(mix):
    run = Served(mix, 5, rounds=6)
    assert run.judged()["mismatched"] == 0
    victim = run.records[2][3]
    rows = [(a.status, a.limit, a.remaining, a.reset_time)
            for a in wire.decode_response(victim.raw)]
    under = next(i for i, r in enumerate(rows) if r[0] == 0 and r[2] > 0)
    st, lim, rem, rst = rows[under]

    def with_record(new):
        records = {c: list(r) for c, r in run.records.items()}
        records[2][3] = new
        return run.judged(records)

    for wrong in ((st, lim, rem + 1, rst), (1, lim, rem, rst),
                  (st, lim, rem, rst + 5000), (st, lim + 1, rem, rst)):
        altered = list(rows)
        altered[under] = wrong
        assert with_record(altered_record(victim, altered))["mismatched"] >= 1, wrong
    s = with_record(judge.Record(3, victim.t_send_ms, victim.t_recv_ms, None,
                                 "DEADLINE_EXCEEDED"))
    assert s["unanswered_rpcs"] == 1 and s["failed_items"] == 1000
    assert s["mismatched"] == 0, "its keys are not followed, and not blamed"
    assert judge.verdict(s, 1)["correct"] is False
    s = with_record(altered_record(victim, rows[:10]))
    assert s["unanswered_rpcs"] == 1


def hand_made(table, cfg_i, rows):
    """Columns for one key from (caller, seq, t0, t1, status, remaining,
    reset) rows."""
    cols = {name: [] for name in judge.COLUMNS}
    for caller, seq, t0, t1, status, remaining, reset in rows:
        for name, v in zip(judge.COLUMNS, (
                7, cfg_i, caller, seq, t0, t1, status,
                table.configs[cfg_i].limit, remaining, reset, 0)):
            cols[name].append(v)
    cols = {k: np.asarray(v, dtype=np.int64) for k, v in cols.items()}
    cols["unknown_id"] = cols["unknown_cfg"] = np.zeros(0, dtype=np.int64)
    return cols


@pytest.mark.parametrize("third,ok", [
    ((2, 0, 112, 125, 0, 97, 60105), True),    # overlaps the second: may follow it
    ((2, 0, 112, 118, 0, 97, 60105), False),   # answered before the second was sent
    ((1, 0, 131, 140, 0, 97, 60105), False),   # the second's own caller, sent first
    ((2, 0, 131, 140, 0, 96, 60105), False),   # a value skipped
    ((2, 0, 131, 140, 1, 0, 60105), False),    # OVER_LIMIT with tokens left
    ((2, 0, 60131, 60140, 0, 97, 60105), False),  # after the bucket's reset
])
def test_a_sequence_has_to_keep_real_time_and_each_callers_order(mix, third, ok):
    table = traffic.LimitTable(mix)
    assert table.configs[0].algorithm == traffic.TOKEN
    assert (table.configs[0].limit, table.configs[0].duration) == (100, 60000)
    rows = [(0, 0, 100, 110, 0, 99, 60105), (1, 1, 120, 130, 0, 98, 60105), third]
    out = judge.judge_answers(table, hand_made(table, 0, rows))
    assert (out["mismatched"] == 0) is ok
    assert out["keys"] == 1 and out["shared_keys"] == 1


def test_a_state_never_written_is_caught(mix):
    """The step that returns its state unchanged: every answer is a
    fresh bucket's."""
    run = Served(mix, 6, callers=2, rounds=4)
    for c, recs in run.records.items():
        for i, rec in enumerate(recs):
            rows = []
            for r in wire.decode_request(run.pools[c].payloads[i]):
                _, out = spec.apply_spec(None, spec.SpecInput(
                    hits=r.hits, limit=r.limit, duration=r.duration,
                    burst=r.burst, algorithm=r.algorithm), int(rec.t_send_ms) + 1)
                rows.append((out.status, out.limit, out.remaining, out.reset_time))
            recs[i] = altered_record(rec, rows)
    assert run.judged()["mismatched"] > 0


def test_the_sample_is_drawn_from_the_seed_and_keeps_the_hot_ids(mix):
    ids = np.arange(0, 4000, 8)
    hot = ids[:3]
    a = judge.sampled_mask(ids, 1, 0.25, hot)
    b = judge.sampled_mask(ids, 2, 0.25, hot)
    assert a[:3].all() and b[:3].all()
    assert (a != b).any()
    assert 0.15 < a.mean() < 0.35
    assert (judge.sampled_mask(ids, 1, 0.25, hot) == a).all()
    assert judge.sampled_mask(ids, 1, 1.0, hot).all()
    s = Served(mix, 8, rounds=5, share=0.25).judged()
    assert 0 < s["checked"] < 4 * 5000 and s["mismatched"] == 0
    assert set(judge.hot_ids(4000).tolist()) == set(
        traffic.scramble(np.arange(judge.HOT_RANKS), 4000).tolist())


def test_too_few_checked_answers_is_not_correct():
    total = {"mismatched": 0, "unanswered_rpcs": 0, "checked": 10}
    assert judge.verdict(total, 11)["correct"] is False
    assert judge.verdict(total, 10)["correct"] is True
