"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished (and, where the mix says so, of those
still in flight with enough served tokens: ``harness.py``), drawn from the
seed and always holding the longest, is run through the float32 reference (``reference.py``) over
its prompt and the tokens the program served. The number compared is the
widest gap, over every served token of the sample, by which the served
token's reference logit lies below the reference's best logit at that
position (served tokens are greedy, so a sound run reads a rounding-sized
gap). Its limit is the configuration's ``correct.max_logit_gap``, set from
sound runs and the float8 control as ``PERF.md`` records.
"""
from __future__ import annotations

import numpy as np

import reference as R

SAMPLE = 4          # requests compared per run, unless the configuration's
                    # ``correct.sample`` says otherwise


def sample(finished, seed: int, k: int = SAMPLE) -> list:
    """``k`` of the finished requests, from the seed, the longest
    (prompt plus served tokens) always among them."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(finished[i][0].size + len(finished[i][1])))
    rest = order[1:]
    rng = np.random.default_rng([seed, 2])
    pick = list(rng.choice(rest, min(k - 1, len(rest)), replace=False)) \
        if rest else []
    return [finished[order[0]]] + [finished[i] for i in pick]


def compare(cfg: dict, seed: int, finished, pad_to: int, fam=None,
            devices=None) -> dict:
    """Numbers compared: {name: (value, "<=" or ">=", limit)}. ``devices``
    spreads the sampled requests over the cell's chips (``reference.py``).
    """
    arch = R.Arch(cfg, fam)
    reqs = sample(finished, seed, cfg["correct"].get("sample", SAMPLE))
    served = sum(len(t) for _, t in reqs)
    bad = sum(int(np.sum((np.asarray(t) < 0) | (np.asarray(t) >= arch.vocab)))
              for _, t in reqs)
    out = {"served_tokens_compared": (float(served), ">=", 1.0),
           "tokens_out_of_vocab": (float(bad), "<=", 0.0)}
    if reqs and not bad:
        gaps = R.served_gaps(arch, seed, reqs, pad_to=pad_to,
                             devices=devices)
        out["max_logit_gap"] = (float(max(g.max() for g in gaps)), "<=",
                                float(cfg["correct"]["max_logit_gap"]))
    return out


def control_gap(cfg: dict, seed: int, finished, pad_to: int, fam=None,
                devices=None) -> float:
    """The float8 control's reading on the same sample: the widest gap of
    the token the control puts first at each served position. Run by the
    calibration (``control.py``) and the tests, never by a benchmark run."""
    reqs = sample(finished, seed, cfg["correct"].get("sample", SAMPLE))
    gaps = R.served_gaps(R.Arch(cfg, fam), seed, reqs, control=True,
                         pad_to=pad_to, devices=devices)
    return float(max(g.max() for g in gaps))


def verdict(numbers: dict) -> bool:
    """Every number within its limit, and the gap was measured."""
    return "max_logit_gap" in numbers and all(
        v <= lim if op == "<=" else v >= lim
        for v, op, lim in numbers.values())
