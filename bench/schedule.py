"""The one traffic generator: turns a traffic file (``traffic/<mix>.json``)
into seeded requests, on a schedule (open loop) or from clients that each
wait for their last answer (closed loop).

A mix is data. ``loop`` is ``open`` (the default) or ``closed``.

* Open: ``arrivals`` is ``poisson`` (``rate_per_s``) or ``bursts``
  (``rate_per_s`` on average, bursts of ``burst`` = [lo, hi] requests
  spread over ``spread_s``, exponential gaps between bursts).
* Closed: ``clients`` clients each send one request at a time; when it
  ends, the client's next is due at once. With ``preload`` true each
  client's first request is admitted and prefilled during set-up, before
  the window opens. ``in_flight_min_tokens`` (optional) lets the check
  sample requests still in flight at the close that have served at least
  that many tokens (``harness.py``).

``classes`` split the requests by ``share``; each has a ``prompt`` and an
``output`` length law and, in an open loop, may have a ``prefix``:
``count`` shared prompts of ``tokens`` tokens, used with Zipf skew
``zipf`` (a few clients send most traffic), ahead of the unique tail that
``prompt`` then describes. A length law is lognormal (``median``,
``sigma``, clipped to [``min``, ``max``]), ``uniform``: [lo, hi] (whole
numbers, both ends included), or, for an output, ``fill``: true (up to
``max_len``).

The sizes, classes, prefix choices and arrival times come from the file's
``shape_seed`` alone (in a closed loop, per client and in order: a
client's k-th request is the same under every seed); the run's ``--seed``
draws only the token ids (and, elsewhere, the weights). So every seed
offers the same work, and runs of different seeds spread no wider than
runs of one seed: the spread left is the system's own. A request's output
is cut so that prompt plus output fits ``max_len``.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Request:
    due: float                 # seconds after the window opens
    prompt: np.ndarray         # int32 token ids
    max_tokens: int
    kind: str                  # "prefix<k>" or "unshared"
    client: int = -1           # closed loop: the client that sent it


def _lognormal(rng, law: dict, n: int) -> np.ndarray:
    x = rng.lognormal(math.log(law["median"]), law["sigma"], n)
    return np.clip(np.rint(x), law["min"], law["max"]).astype(np.int64)


def _lengths(rng, law: dict, n: int, fill: int) -> np.ndarray:
    """n lengths of a law; ``fill`` is what a ``fill`` law gives."""
    if law.get("fill"):
        return np.full(n, fill, np.int64)
    if "uniform" in law:
        lo, hi = law["uniform"]
        return rng.integers(lo, hi + 1, n).astype(np.int64)
    return _lognormal(rng, law, n)


def law_range(law: dict):
    """The least and the most length of a prompt law."""
    if "uniform" in law:
        return tuple(law["uniform"])
    return law["min"], law["max"]


def _prefixes(classes, rng_r, vocab: int) -> list:
    """The shared prompts of every class that has them, from the seed."""
    return [rng_r.integers(0, vocab, c["prefix"]["tokens"])
            for c in classes if "prefix" in c
            for _ in range(c["prefix"]["count"])]


def _request(due, kind, tail_len, out, prefixes, rng_r, vocab, max_len,
             client=-1):
    tail = rng_r.integers(0, vocab, int(tail_len))
    prompt = (np.concatenate([prefixes[int(kind[6:])], tail])
              if kind.startswith("prefix") else tail)
    out = int(min(out, max_len - prompt.size))
    if out < 1:
        raise ValueError(f"prompt of {prompt.size} leaves no output "
                         f"within max_len {max_len}")
    return Request(float(due), prompt.astype(np.int32), out, kind, client)


def _apportion(weights, n: int) -> np.ndarray:
    """Integer counts summing to n, in proportion to weights (largest
    remainder)."""
    w = np.asarray(weights, np.float64)
    exact = w / w.sum() * n
    base = np.floor(exact).astype(np.int64)
    order = np.argsort(-(exact - base), kind="stable")
    base[order[:n - base.sum()]] += 1
    return base


def _arrivals(rng, arr: dict, n: int, seconds: float):
    """n due times in [0, seconds)."""
    if arr["kind"] == "poisson":
        t = np.cumsum(rng.exponential(size=n + 1))
        return t[:n] / t[-1] * seconds
    if arr["kind"] == "bursts":
        lo, hi = arr["burst"]
        spread = float(arr["spread_s"])
        sizes = []
        while sum(sizes) < n:
            sizes.append(int(rng.integers(lo, hi + 1)))
        sizes[-1] -= sum(sizes) - n
        starts = np.cumsum(rng.exponential(size=len(sizes) + 1))
        starts = (starts - starts[0]) / starts[-1] * (seconds - spread)
        return np.sort(np.concatenate(
            [a + rng.uniform(0, spread, k) for a, k in zip(starts, sizes)]))
    raise ValueError(f"no arrival kind {arr['kind']!r}")


def schedule(mix: dict, seed: int, seconds: float, max_len: int,
             vocab: int):
    """The requests due in a window of ``seconds``, sorted by due time."""
    if mix.get("loop", "open") != "open":
        raise ValueError("a closed-loop mix is served by Clients")
    rng_s = np.random.default_rng(mix["shape_seed"])
    rng_r = np.random.default_rng(seed)
    n = max(1, int(round(mix["arrivals"]["rate_per_s"] * seconds)))
    classes = mix["classes"]
    counts = _apportion([c["share"] for c in classes], n)
    specs = []                              # (kind, prompt/tail len, out)
    prefixes = _prefixes(classes, rng_r, vocab)
    base = 0
    for c, m in zip(classes, counts):
        lens = _lengths(rng_s, c["prompt"], m, max_len)
        outs = _lengths(rng_s, c["output"], m, max_len)
        if "prefix" in c:
            p = c["prefix"]
            zipf = [1.0 / (k + 1) ** p["zipf"] for k in range(p["count"])]
            ids = np.repeat(np.arange(p["count"]), _apportion(zipf, m))
            kinds = [f"prefix{base + k}" for k in ids]
            base += p["count"]
        else:
            kinds = ["unshared"] * m
        specs += list(zip(kinds, lens, outs))
    due = _arrivals(rng_s, mix["arrivals"], n, seconds)
    reqs = [_request(t, *specs[i], prefixes, rng_r, vocab, max_len)
            for t, i in zip(due, rng_s.permutation(n))]
    return reqs, [p.astype(np.int32) for p in prefixes]


class Clients:
    """The clients of a closed-loop mix. ``next(c, due)`` is client c's
    next request: its class and sizes from (``shape_seed``, c), its token
    ids from (``--seed``, c), each in the client's own order, so a
    client's k-th request is the same work whenever it falls due. Its
    classes have no shared prefix."""

    def __init__(self, mix: dict, seed: int, max_len: int, vocab: int):
        if mix.get("loop") != "closed":
            raise ValueError("an open-loop mix is served by schedule()")
        if any("prefix" in c for c in mix["classes"]):
            raise ValueError("a closed-loop mix has no shared prefix")
        self.mix, self.max_len, self.vocab = mix, max_len, vocab
        self.n = int(mix["clients"])
        self.preload = bool(mix.get("preload", False))
        self._rng_s = [np.random.default_rng([mix["shape_seed"], c])
                       for c in range(self.n)]
        self._rng_r = [np.random.default_rng([seed, 3, c])
                       for c in range(self.n)]

    def next(self, client: int, due: float) -> Request:
        rng = self._rng_s[client]
        classes = self.mix["classes"]
        share = np.array([c["share"] for c in classes], np.float64)
        i = int(rng.choice(len(classes), p=share / share.sum()))
        c = classes[i]
        ln = int(_lengths(rng, c["prompt"], 1, self.max_len)[0])
        out = int(_lengths(rng, c["output"], 1, self.max_len)[0])
        return _request(due, "unshared", ln, out, [], self._rng_r[client],
                        self.vocab, self.max_len, client)

    def first(self, due: float = 0.0) -> list:
        """Every client's first request."""
        return [self.next(c, due) for c in range(self.n)]


def _bucket_lengths(lo: int, hi: int, buckets) -> list:
    """One length per prefill bucket that lengths in [lo, hi] reach: the
    longest such length in the bucket."""
    out, prev = [], 0
    for b in sorted(buckets):
        if b >= lo and prev < hi:
            out.append(min(b, hi))
        prev = b
    return out


def _bucket(n: int, buckets) -> int:
    return min(b for b in buckets if b >= n)


def warmup(mix: dict, prefixes, buckets, tokens: int, vocab: int,
           seed: int, covered=()):
    """Two waves of requests that compile every program the mix's window
    uses: each prefill bucket its prompts reach, cold; and, behind the
    first wave's cached prefixes, each bucket their tails reach. Each
    request decodes ``tokens`` tokens, enough for a decode segment.
    ``covered``: prompt lengths the set-up serves anyway (a closed loop's
    preloaded requests); an unshared bucket one of them reaches is left
    out."""
    rng = np.random.default_rng([seed, 1])
    cold, hits = [], []
    base = 0
    done = {_bucket(n, buckets) for n in covered}
    for c in mix["classes"]:
        lo, hi = law_range(c["prompt"])
        if "prefix" not in c:
            for ln in _bucket_lengths(lo, hi, buckets):
                if _bucket(ln, buckets) not in done:
                    cold.append(rng.integers(0, vocab, ln))
            continue
        count, ptok = c["prefix"]["count"], c["prefix"]["tokens"]
        full = _bucket_lengths(ptok + lo, ptok + hi, buckets)
        for k in range(max(count, len(full))):
            ln = full[k % len(full)] - ptok
            cold.append(np.concatenate([prefixes[base + k % count],
                                        rng.integers(0, vocab, ln)]))
        for ln in _bucket_lengths(lo, hi, buckets):
            hits.append(np.concatenate([prefixes[base],
                                        rng.integers(0, vocab, ln)]))
        base += count
    return ([Request(0.0, p.astype(np.int32), tokens, "warmup")
             for p in cold],
            [Request(0.0, p.astype(np.int32), tokens, "warmup")
             for p in hits])
