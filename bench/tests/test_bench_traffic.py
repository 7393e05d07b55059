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


# -- closed loops -----------------------------------------------------------

CLOSED = "long-decode"
MAX_LEN = 10240


def test_closed_mix_is_the_same_work_under_every_seed():
    m = load(CLOSED)
    a = schedule.Clients(m, SEEDS[0], MAX_LEN, 152064)
    b = schedule.Clients(m, SEEDS[1], MAX_LEN, 152064)
    # b asks in another order: a client's k-th request is still the same
    order_b = [(c, k) for k in range(3) for c in reversed(range(a.n))]
    got_b = {(c, k): b.next(c, 0.0) for c, k in order_b}
    for c in range(a.n):
        for k in range(3):
            ra, rb = a.next(c, 0.0), got_b[(c, k)]
            assert (ra.kind, ra.prompt.size, ra.max_tokens, ra.client) == \
                (rb.kind, rb.prompt.size, rb.max_tokens, rb.client) != \
                ("", 0, 0, -1)
            assert not np.array_equal(ra.prompt, rb.prompt)
    first = schedule.Clients(m, SEEDS[0], MAX_LEN, 152064).first()
    assert [r.client for r in first] == list(range(m["clients"]))


def test_closed_mix_fills_max_len_from_a_uniform_prompt():
    m = load(CLOSED)
    cl = schedule.Clients(m, SEEDS[1], MAX_LEN, 152064)
    lo, hi = m["classes"][0]["prompt"]["uniform"]
    for c in range(cl.n):
        for _ in range(4):
            r = cl.next(c, 0.0)
            assert lo <= r.prompt.size <= hi
            assert r.prompt.size + r.max_tokens == MAX_LEN
            assert 0 <= r.prompt.min() and r.prompt.max() < 152064


def test_closed_mix_refuses_a_shared_prefix():
    m = load(CLOSED)
    m["classes"] = [dict(m["classes"][0],
                         prefix={"count": 2, "tokens": 64, "zipf": 1.0})]
    with pytest.raises(ValueError, match="prefix"):
        schedule.Clients(m, SEEDS[0], MAX_LEN, 152064)


def test_uniform_law_stays_inside_its_range():
    rng = np.random.default_rng(4)
    x = schedule._lengths(rng, {"uniform": [3, 7]}, 2000, 0)
    assert set(x.tolist()) == {3, 4, 5, 6, 7}
    assert schedule.law_range({"uniform": [3, 7]}) == (3, 7)
    assert list(schedule._lengths(rng, {"fill": True}, 2, 99)) == [99, 99]


def test_warmup_leaves_out_buckets_the_preload_compiles():
    m = load(CLOSED)
    buckets = [4608, 5120, 5632, 6144]
    cold, hits = schedule.warmup(m, [], buckets, 5, 152064, 1)
    assert sorted(r.prompt.size for r in cold) == buckets and not hits
    cold, _ = schedule.warmup(m, [], buckets, 5, 152064, 1,
                              covered=[4100, 5000])
    assert sorted(r.prompt.size for r in cold) == [5632, 6144]


class _Handle:
    def __init__(self, prompt, max_tokens):
        from repro.serve import RequestStatus

        self.status = RequestStatus.QUEUED
        self.max_tokens, self.tokens_ready = max_tokens, 0

    def tokens_so_far(self):
        return list(range(self.tokens_ready))


class _Session:
    """Admits every queued request in one step (its first token out), and
    otherwise decodes a segment of 2 tokens for every live one."""

    def __init__(self):
        self.handles, self.steps = [], []

    def submit(self, prompt, params):
        h = _Handle(prompt, params.max_tokens)
        self.handles.append(h)
        return h

    @property
    def idle(self):
        import program as P

        return all(P.terminal(h) for h in self.handles)

    def stats(self):
        return {"prefix": None}

    def step(self):
        from repro.serve import RequestStatus

        queued = [h for h in self.handles
                  if h.status is RequestStatus.QUEUED]
        for h in queued:
            h.status, h.tokens_ready = RequestStatus.DECODING, 1
        self.steps.append("admit" if queued else "decode")
        if queued:
            return
        for h in self.handles:
            if h.status is RequestStatus.DECODING:
                h.tokens_ready = min(h.tokens_ready + 2, h.max_tokens)
                if h.tokens_ready == h.max_tokens:
                    h.status = RequestStatus.DONE


def _clock():
    t = [0.0]

    def now():
        t[0] += 1e-3
        return t[0]
    return now


def _short_mix():
    m = load(CLOSED)
    m["clients"] = 3
    m["classes"] = [{"share": 1.0, "prompt": {"uniform": [4, 9]},
                     "output": {"uniform": [3, 12]}}]
    return m


def test_closed_loop_next_request_waits_for_the_last():
    import harness

    cl = schedule.Clients(_short_mix(), SEEDS[0], 64, 100)
    sess = _Session()
    clock = _clock()
    recs, steps, _ = harness.drive(sess, cl.first(0.0), 0.5, clock,
                                   0.0, clients=cl)
    by = {}
    for r in recs:
        by.setdefault(r.client, []).append(r)
    assert set(by) == {0, 1, 2} and all(len(v) > 3 for v in by.values())
    for mine in by.values():
        for prev, nxt in zip(mine, mine[1:]):
            assert prev.times and len(prev.times) == prev.max_tokens
            assert nxt.due >= prev.times[-1]         # due once it is done
            assert nxt.submitted >= nxt.due
    # the last request of each client is the only one left unfinished
    assert sum(len(v[-1].times) < v[-1].max_tokens for v in by.values()) \
        <= 3


def test_preloaded_requests_are_admitted_before_the_window():
    import harness
    import program as P

    m = _short_mix()
    m["classes"][0]["output"] = {"fill": True}
    cl = schedule.Clients(m, SEEDS[1], 400, 100)
    sess = _Session()
    clock = _clock()
    first = cl.first()
    pre = harness.preload(sess, first, clock)
    t0 = clock()
    assert [r.client for r in pre] == [0, 1, 2]
    assert sess.steps == ["admit", "decode"]   # and a segment compiled
    assert not any(P.queued(r.handle) for r in pre)
    assert all(r.submitted < t0 and len(r.times) == 3 for r in pre)
    for r in pre:
        r.due = r.submitted = r.submitted - t0
        r.times = [x - t0 for x in r.times]
    recs, steps, _ = harness.drive(sess, [], 0.05, clock, t0, clients=cl,
                                   preloaded=pre)
    assert recs[:3] == pre and all(r.due < 0 for r in pre)
    assert all(s["kind"] == "decode" for s in steps.values())
