"""Device traces: capture with the JAX profiler, reduce to a small neutral
form, and read busy time, kernel time and idle gaps from it.

The neutral form is a dict, the same whether it comes from a fresh
profile or from a file a test committed:

    {"window": [lo_ns, hi_ns],            # the traced slice
     "host": [[name, start_ns, dur_ns], ...],   # the benchmark's spans
     "devices": {plane: [[name, start_ns, dur_ns], ...]}}   # device ops

Host spans are the benchmark's own ``TraceAnnotation`` names:
``trace-window``, ``arrival-wait``, ``submit``, ``step:<n>``, ``record``.
A device event is named by its HLO instruction (``packed_xnor_gemv.82``):
the profiler's name is the instruction's whole text, whose operand list
names other instructions, so it is cut to the part before `` = ``.
A kernel is found by its instruction's base name (``packed_xnor_gemv``),
never by a substring of the text.
"""
from __future__ import annotations

import glob
import os
import re

OUR_SPANS = ("trace-window", "arrival-wait", "submit", "step:", "record")
# collective operations, by base name: ``all-gather``, and the two halves
# of an asynchronous one, ``all-gather-start`` and ``all-gather-done``
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
# device lines that hold one event per executed operation
OP_LINES = ("XLA Ops",)


def op_name(text: str) -> str:
    """``%packed_xnor_gemv.82 = f32[16,5120]{...} custom-call(...)`` ->
    ``packed_xnor_gemv.82``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def base_name(op: str) -> str:
    """``packed_xnor_gemv.82`` -> ``packed_xnor_gemv``."""
    return re.sub(r"(\.\d+)+$", "", op)


def is_collective(op: str) -> bool:
    """A collective operation's event, by its instruction's base name."""
    base = base_name(op)
    return any(base == c or base in (c + "-start", c + "-done")
               for c in COLLECTIVES)


def leaves(events) -> list:
    """The events that hold no other event of the same line: an XLA
    ``while`` spans every operation of its body."""
    out, ev = [], sorted(events, key=lambda e: (e[1], -e[2]))
    for i, (name, s, d) in enumerate(ev):
        if i + 1 < len(ev) and ev[i + 1][1] < s + d:
            continue
        out.append([name, s, d])
    return out


def load_profile(logdir: str) -> dict:
    """Reduce the newest ``.xplane.pb`` under ``logdir`` to the neutral
    form, cut to the ``trace-window`` span."""
    import jax

    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no profile under {logdir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    host, devices, lines = [], {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                lines.setdefault(plane.name, []).append(line.name)
                if line.name in OP_LINES:
                    devices.setdefault(plane.name, []).extend(
                        [op_name(e.name), e.start_ns, e.duration_ns]
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith(OUR_SPANS))
    win = [h for h in host if h[0] == "trace-window"]
    if not win:
        raise ValueError("profile holds no trace-window span")
    lo, hi = win[0][1], win[0][1] + win[0][2]
    return {"window": [lo, hi], "host": host,
            "devices": {d: clip(ev, lo, hi) for d, ev in devices.items()},
            "lines": lines}


def clip(events, lo, hi) -> list:
    """Events cut to [lo, hi]; those wholly outside dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append([name, a, b - a])
    return out


def union(intervals) -> list:
    """Sorted disjoint union of (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def covered(intervals, spans) -> float:
    """Length of the union of ``intervals`` inside the union of
    ``spans``."""
    iv, sp = union(intervals), union(spans)
    total, j = 0.0, 0
    for a, b in iv:
        while j < len(sp) and sp[j][1] <= a:
            j += 1
        k = j
        while k < len(sp) and sp[k][0] < b:
            total += max(0.0, min(b, sp[k][1]) - max(a, sp[k][0]))
            k += 1
    return total


class View:
    """One traced slice of a run: device events beside the harness's
    spans and its record of each step."""

    def __init__(self, data: dict, steps: dict):
        self.lo, self.hi = data["window"]
        self.devices = {d: ev for d, ev in sorted(data["devices"].items())
                        if ev}
        self.host = data["host"]
        self.steps = steps            # step number -> harness step record
        self.step_spans = []          # (start, end, record), inside window
        for name, s, d in self.host:
            if name.startswith("step:") and s >= self.lo \
                    and s + d <= self.hi:
                n = int(name.split(":")[1])
                if n in steps:
                    self.step_spans.append((s, s + d, steps[n]))

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        per = [covered([(s, s + d) for _, s, d in ev], [(self.lo, self.hi)])
               for ev in self.devices.values()]
        return sum(per) / len(per) * 1e-9

    def spans_of(self, kind: str) -> list:
        return [(a, b) for a, b, rec in self.step_spans
                if rec["kind"] == kind]

    def steps_of(self, kind: str) -> list:
        return [rec for _, _, rec in self.step_spans if rec["kind"] == kind]

    def busy_in_s(self, kind: str) -> float:
        """Device-busy seconds inside the steps of ``kind``, averaged over
        devices."""
        spans = self.spans_of(kind)
        if not self.devices or not spans:
            return 0.0
        per = [covered([(s, s + d) for _, s, d in ev], spans)
               for ev in self.devices.values()]
        return sum(per) / len(per) * 1e-9

    def kernel_s(self, names, kind: str = None) -> float:
        """Device time of the events whose base name is one of ``names``,
        inside steps of ``kind`` (all of the window if None), averaged over
        devices."""
        return self.ops_s(lambda n: base_name(n) in names, kind)

    def ops_s(self, pick, kind: str = None) -> float:
        """Device time of the events whose name ``pick`` takes, inside
        steps of ``kind`` (all of the window if None), averaged over
        devices."""
        spans = self.spans_of(kind) if kind else [(self.lo, self.hi)]
        if not self.devices or not spans:
            return 0.0
        per = []
        for ev in self.devices.values():
            hits = [(s, s + d) for n, s, d in ev if pick(n)]
            per.append(covered(hits, spans))   # one device runs one op
        return sum(per) / len(per) * 1e-9

    def top_ops(self, k: int = 10) -> list:
        """The k device operations that took most time (first device),
        counting only operations that hold no other: a loop's time is its
        body's."""
        if not self.devices:
            return []
        tot = {}
        for n, _, d in leaves(next(iter(self.devices.values()))):
            tot[n] = tot.get(n, 0) + d
        best = sorted(tot.items(), key=lambda x: -x[1])[:k]
        return [[n, d * 1e-9] for n, d in best]

    def idle_gaps(self, k: int = 10) -> list:
        """The k longest gaps with no device operation (first device),
        each named by the benchmark span that covers most of it."""
        if not self.devices:
            return []
        ev = next(iter(self.devices.values()))
        busy = union([(s, s + d) for _, s, d in ev])
        edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        spans = [(s, s + d, n.split(":")[0]) for n, s, d in self.host
                 if n != "trace-window"]
        out = []
        for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
            cover = {}
            for s, e, n in spans:
                cover[n] = cover.get(n, 0) + max(0, min(b, e) - max(a, s))
            label = max(cover, key=cover.get) if cover else None
            if label is None or cover[label] <= 0:
                label = "outside-spans"
            out.append([label, (b - a) * 1e-9])
        return out
