"""The serving session's own spans and the device's program events, read
from the same profile as ``trace.py``, and what they measure.

The session wraps each phase of a step in a ``TraceAnnotation`` named
``serve.*`` (``serve.schedule``, ``serve.prefill``, ``serve.hit_admit``,
``serve.first_token``, ``serve.segment``, ``serve.segment_wait``,
``serve.drain``); a request's spans carry its ``rid`` as metadata, and
``serve.prefill`` also the ``tokens`` it prefilled and the prefix ``hit``.
Each serve program has a name of its own (``serve_prefill``,
``serve_tail_prefill``, ``serve_hit_admit``, ``serve_decode_segment``),
which the device plane's module line shows as ``jit_<name>(<id>)``.

``load`` adds two keys to the neutral form of ``trace.py``:

    {"program": [[name, start_ns, dur_ns, {metadata}], ...],  # serve.* spans
     "modules": {base: [[start_ns, dur_ns], ...]}}   # first device's programs

Host spans and device events share the profiler's clock, so device time
can be placed inside a span. Everything here is measured on the first
device, as ``trace.View.idle_gaps`` is. A reader returns None where the
slice holds nothing to read (a program without these spans and names).
"""
from __future__ import annotations

import glob
import os
import re

import trace as TR

PROGRAM_PREFIX = "serve."
MODULE_LINES = ("XLA Modules",)
PREFILL_PROGRAMS = ("serve_prefill", "serve_tail_prefill")
SEGMENT_PROGRAM = "serve_decode_segment"


def module_base(text: str) -> str:
    """``jit_serve_decode_segment(1234)`` -> ``serve_decode_segment``."""
    name = re.sub(r"\(\d+\)$", "", text.strip())
    name = TR.base_name(name)
    return name[4:] if name.startswith("jit_") else name


def load(logdir: str, lo: int, hi: int) -> dict:
    """The ``serve.*`` host spans and the first device's module events of
    the newest ``.xplane.pb`` under ``logdir`` that overlap [lo, hi],
    uncut: a reader decides what lies wholly inside the slice."""
    import jax

    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no profile under {logdir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    program, per_device = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                program.extend(
                    [e.name, e.start_ns, e.duration_ns, dict(e.stats)]
                    for e in line.events
                    if e.name.startswith(PROGRAM_PREFIX)
                    and e.start_ns < hi and e.start_ns + e.duration_ns > lo)
        elif plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in MODULE_LINES:
                    mods = per_device.setdefault(plane.name, {})
                    for e in line.events:
                        if e.start_ns < hi and e.start_ns + e.duration_ns > lo:
                            mods.setdefault(module_base(e.name), []).append(
                                [e.start_ns, e.duration_ns])
    program.sort(key=lambda p: p[1])
    modules = per_device[min(per_device)] if per_device else {}
    return {"program": program, "modules": modules}


def _ops(data: dict) -> list:
    """The first device's operations as (start, end) intervals."""
    devs = [ev for _, ev in sorted(data["devices"].items()) if ev]
    return [(s, s + d) for _, s, d in devs[0]] if devs else []


def _inside(data: dict, s, d) -> bool:
    lo, hi = data["window"]
    return lo <= s and s + d <= hi


def _idle_ns(ops, a, b) -> float:
    return max(0, b - a) - TR.covered(ops, [(a, b)]) if b > a else 0.0


def queue_wait_ms(c0: dict, c1: dict):
    """Mean queue wait of the admissions between two counter snapshots:
    Δ``queue_wait_ms`` over Δ``queue_waited``."""
    if "queue_waited" not in c0 or "queue_waited" not in c1:
        return None
    n = c1["queue_waited"] - c0["queue_waited"]
    if n <= 0:
        return None
    return (c1["queue_wait_ms"] - c0["queue_wait_ms"]) / n


def prefill_program_ms_per_ktok(data: dict):
    """Device-busy time inside the prefill programs that lie wholly in the
    slice, per 1,000 tokens their ``serve.prefill`` spans prefilled. A
    program belongs to the last ``serve.prefill`` span that began before
    it: admissions are serial, each ends in its first token's host sync."""
    spans = [p for p in data["program"] if p[0] == "serve.prefill"]
    mods = sorted((s, s + d) for base in PREFILL_PROGRAMS
                  for s, d in data["modules"].get(base, [])
                  if _inside(data, s, d))
    ops = _ops(data)
    busy, owners = 0.0, {}
    for a, b in mods:
        before = [p for p in spans if p[1] <= a]
        if not before:
            continue
        owners[id(before[-1])] = before[-1][3]["tokens"]
        busy += TR.covered(ops, [(a, b)])
    tokens = sum(owners.values())
    if tokens <= 0 or busy <= 0:
        return None
    return busy * 1e-6 / (tokens / 1e3)


def admit_gap_ms(data: dict):
    """Device-idle time from the start of each request's admission span
    (``serve.prefill`` or ``serve.hit_admit``) to the end of its
    ``serve.first_token`` span, both wholly in the slice, per request.
    Swap resumes emit no first token and are left out."""
    adm = [p for p in data["program"]
           if p[0] in ("serve.prefill", "serve.hit_admit")]
    ops = _ops(data)
    gaps = []
    for name, s, d, meta in data["program"]:
        if name != "serve.first_token" or not _inside(data, s, d):
            continue
        mine = [p for p in adm if p[3].get("rid") == meta.get("rid")
                and p[1] <= s]
        if not mine or not _inside(data, mine[-1][1], mine[-1][2]):
            continue
        gaps.append(_idle_ns(ops, mine[-1][1], s + d))
    if not gaps or not ops:
        return None
    return sum(gaps) / len(gaps) * 1e-6


def segment_gap_ms(data: dict):
    """Device-idle time between consecutive decode-segment programs with
    no other serve program between them, per such pair in the slice."""
    mods = sorted((s, s + d, base) for base, ev in data["modules"].items()
                  if base.startswith("serve_") for s, d in ev
                  if _inside(data, s, d))
    ops = _ops(data)
    gaps = [_idle_ns(ops, a[1], b[0]) for a, b in zip(mods, mods[1:])
            if a[2] == b[2] == SEGMENT_PROGRAM]
    if not gaps or not ops:
        return None
    return sum(gaps) / len(gaps) * 1e-6


def idle_gaps(data: dict, k: int = 10) -> list:
    """``trace.View.idle_gaps`` with program labels: the k longest gaps with
    no device operation (first device), each named by the ``serve.*`` span
    that covers most of it (the innermost, where spans cover it equally),
    else by the benchmark span that covers most of it, as there."""
    ops = _ops(data)
    if not ops:
        return []
    lo, hi = data["window"]
    busy = TR.union(ops)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    harness = [(s, s + d, n.split(":")[0]) for n, s, d in data["host"]
               if n != "trace-window"]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        label, best = None, (0, 0)
        for name, s, d, _ in data["program"]:
            c = min(b, s + d) - max(a, s)
            if c > 0 and (c, -d) > best:
                label, best = name, (c, -d)
        if label is None:
            cover = {}
            for s, e, n in harness:
                cover[n] = cover.get(n, 0) + max(0, min(b, e) - max(a, s))
            label = max(cover, key=cover.get) if cover else None
            if label is None or cover[label] <= 0:
                label = "outside-spans"
        out.append([label, (b - a) * 1e-9])
    return out
