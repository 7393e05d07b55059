"""The traffic generator: deterministic per seed, the same work for every
seed at the same times, and within the mix's stated ranges, shares and
rate. CPU only; nothing here touches an accelerator."""
import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import schedule  # noqa: E402

MIXES = ["chat-prefix", "chat-burst"]
SEEDS = [3, 2**31 + 17]


def load(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule(mix):
    a, pa = schedule.schedule(load(mix), SEEDS[1], 40, 2048, 152064)
    b, pb = schedule.schedule(load(mix), SEEDS[1], 40, 2048, 152064)
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert all(np.array_equal(x, y) for x, y in zip(pa, pb))


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_offer_the_same_work_at_the_same_times(mix):
    a, _ = schedule.schedule(load(mix), SEEDS[0], 40, 2048, 152064)
    b, _ = schedule.schedule(load(mix), SEEDS[1], 40, 2048, 152064)
    assert [(r.due, r.kind, r.prompt.size, r.max_tokens) for r in a] == \
        [(r.due, r.kind, r.prompt.size, r.max_tokens) for r in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)


@pytest.mark.parametrize("mix", MIXES)
def test_ranges_shares_and_rate(mix):
    m = load(mix)
    seconds, max_len = 40, 2048
    reqs, prefixes = schedule.schedule(m, SEEDS[0], seconds, max_len,
                                       65024)
    rate = m["arrivals"]["rate_per_s"]
    assert len(reqs) == round(rate * seconds)
    due = [r.due for r in reqs]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < seconds
    kinds = Counter(r.kind.startswith("prefix") for r in reqs)
    for c in m["classes"]:
        shared = "prefix" in c
        assert abs(kinds[shared] - c["share"] * len(reqs)) <= 1
        ours = [r for r in reqs if r.kind.startswith("prefix") == shared]
        extra = c["prefix"]["tokens"] if shared else 0
        lens = [r.prompt.size - extra for r in ours]
        assert min(lens) >= c["prompt"]["min"]
        assert max(lens) <= c["prompt"]["max"]
        assert all(r.max_tokens >= 1 for r in ours)
        assert all(r.max_tokens <= c["output"]["max"] for r in ours)
        if shared:
            for r in ours:
                k = int(r.kind[6:])
                assert np.array_equal(r.prompt[:extra], prefixes[k])
            # a few clients send most traffic: the first prefix leads
            per = Counter(r.kind for r in ours)
            assert per["prefix0"] == max(per.values())
    assert all(r.prompt.size + r.max_tokens <= max_len for r in reqs)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 65024 for r in reqs)


def test_bursts_arrive_in_bursts():
    m = load("chat-burst")
    reqs, _ = schedule.schedule(m, SEEDS[0], 40, 2048, 65024)
    due = np.array([r.due for r in reqs])
    lo, hi = m["arrivals"]["burst"]
    # group arrivals closer than the spread: each group is one burst
    groups, size = [], 1
    for gap in np.diff(due):
        if gap <= m["arrivals"]["spread_s"]:
            size += 1
        else:
            groups.append(size)
            size = 1
    groups.append(size)
    assert max(groups) >= lo
    assert np.median(groups) >= lo / 2


@pytest.mark.parametrize("mix", MIXES)
def test_warmup_reaches_every_bucket(mix):
    m = load(mix)
    buckets = [64, 128, 256, 512, 1024, 2048]
    reqs, prefixes = schedule.schedule(m, SEEDS[0], 40, 2048, 65024)
    cold, hits = schedule.warmup(m, prefixes, buckets, 5, 65024, 1)

    def bucket(n):
        return min(b for b in buckets if b >= n)

    wanted = set()
    for r in reqs:
        wanted.add(bucket(r.prompt.size))
    got = {bucket(r.prompt.size) for r in cold}
    assert wanted <= got
    if prefixes:
        tails = {bucket(r.prompt.size - len(prefixes[0])) for r in reqs
                 if r.kind.startswith("prefix")}
        assert tails <= {bucket(r.prompt.size - len(prefixes[0]))
                         for r in hits}
