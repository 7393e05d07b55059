"""The one traffic generator: turns a traffic file (``traffic/<mix>.json``)
into a seeded open-loop schedule of requests.

A mix is data. ``arrivals`` is ``poisson`` (``rate_per_s``) or ``bursts``
(``rate_per_s`` on average, bursts of ``burst`` = [lo, hi] requests spread
over ``spread_s``, exponential gaps between bursts). ``classes`` split the
requests by ``share``; each has a ``prompt`` and an ``output`` length law
(lognormal by ``median`` and ``sigma``, clipped to [``min``, ``max``]) and
may have a ``prefix``: ``count`` shared prompts of ``tokens`` tokens, used
with Zipf skew ``zipf`` (a few clients send most traffic), ahead of the
unique tail that ``prompt`` then describes.

The sizes, prefix choices and arrival times come from the file's
``shape_seed`` alone; the run's ``--seed`` draws only the token ids (and,
elsewhere, the weights). So every seed offers the same work at the same
moments, and runs of different seeds spread no wider than runs of one
seed: the spread left is the system's own. A request's output is cut so
that prompt plus output fits ``max_len``.
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


def _lognormal(rng, law: dict, n: int) -> np.ndarray:
    x = rng.lognormal(math.log(law["median"]), law["sigma"], n)
    return np.clip(np.rint(x), law["min"], law["max"]).astype(np.int64)


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
        raise ValueError("only open-loop mixes are generated")
    rng_s = np.random.default_rng(mix["shape_seed"])
    rng_r = np.random.default_rng(seed)
    n = max(1, int(round(mix["arrivals"]["rate_per_s"] * seconds)))
    classes = mix["classes"]
    counts = _apportion([c["share"] for c in classes], n)
    specs = []                              # (kind, prompt/tail len, out)
    prefixes = []
    for c, m in zip(classes, counts):
        lens = _lognormal(rng_s, c["prompt"], m)
        outs = _lognormal(rng_s, c["output"], m)
        if "prefix" in c:
            p = c["prefix"]
            base = len(prefixes)
            prefixes += [rng_r.integers(0, vocab, p["tokens"])
                         for _ in range(p["count"])]
            zipf = [1.0 / (k + 1) ** p["zipf"] for k in range(p["count"])]
            ids = np.repeat(np.arange(p["count"]), _apportion(zipf, m))
            kinds = [f"prefix{base + k}" for k in ids]
        else:
            kinds = ["unshared"] * m
        specs += list(zip(kinds, lens, outs))
    due = _arrivals(rng_s, mix["arrivals"], n, seconds)
    reqs = []
    for t, i in zip(due, rng_s.permutation(n)):
        kind, ln, out = specs[i]
        tail = rng_r.integers(0, vocab, int(ln))
        prompt = (np.concatenate([prefixes[int(kind[6:])], tail])
                  if kind.startswith("prefix") else tail)
        out = int(min(out, max_len - prompt.size))
        if out < 1:
            raise ValueError(f"prompt of {prompt.size} leaves no output "
                             f"within max_len {max_len}")
        reqs.append(Request(float(t), prompt.astype(np.int32), out, kind))
    return reqs, [p.astype(np.int32) for p in prefixes]


def _bucket_lengths(lo: int, hi: int, buckets) -> list:
    """One length per prefill bucket that lengths in [lo, hi] reach: the
    longest such length in the bucket."""
    out, prev = [], 0
    for b in sorted(buckets):
        if b >= lo and prev < hi:
            out.append(min(b, hi))
        prev = b
    return out


def warmup(mix: dict, prefixes, buckets, tokens: int, vocab: int,
           seed: int):
    """Two waves of requests that compile every program the mix's window
    uses: each prefill bucket its prompts reach, cold; and, behind the
    first wave's cached prefixes, each bucket their tails reach. Each
    request decodes ``tokens`` tokens, enough for a decode segment."""
    rng = np.random.default_rng([seed, 1])
    cold, hits = [], []
    base = 0
    for c in mix["classes"]:
        law = c["prompt"]
        if "prefix" not in c:
            for ln in _bucket_lengths(law["min"], law["max"], buckets):
                cold.append(rng.integers(0, vocab, ln))
            continue
        count, ptok = c["prefix"]["count"], c["prefix"]["tokens"]
        full = _bucket_lengths(ptok + law["min"], ptok + law["max"], buckets)
        for k in range(max(count, len(full))):
            ln = full[k % len(full)] - ptok
            cold.append(np.concatenate([prefixes[base + k % count],
                                        rng.integers(0, vocab, ln)]))
        for ln in _bucket_lengths(law["min"], law["max"], buckets):
            hits.append(np.concatenate([prefixes[base],
                                        rng.integers(0, vocab, ln)]))
        base += count
    return ([Request(0.0, p.astype(np.int32), tokens, "warmup")
             for p in cold],
            [Request(0.0, p.astype(np.int32), tokens, "warmup")
             for p in hits])
