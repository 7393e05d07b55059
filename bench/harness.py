"""One run of one cell: set up, warm up, measure a window, check.

Everything that belongs to one configuration, traffic mix, cell, model
family or per-layer metric lives in a file of its own, found by the name
``BENCHMARK.json`` or a configuration gives it:

* ``configs/<config>.json`` (the entry's ``file``): the model's sizes, its
  ``program`` (family and what the program needs besides), the ``serve``
  settings and the ``correct`` limits;
* ``families/<family>.py`` (``family.py``): the family's ``ModelConfig``
  fields, the reference's leaves and equations, what a decoded token asks
  of each projection, and weight recipes for leaves of its own;
* ``traffic/<mix>.json`` (``schedule.py``): an open loop (Poisson or
  bursts) or a closed loop of clients, preloaded or not;
* ``cells/<workload>.json`` (optional): a ``serve`` block laid over the
  configuration's, key by key, so one configuration serves several
  deployments;
* ``metrics/<metric>.py``: a ``read(run)`` that returns a number, or None
  where it finds nothing to read.

A metric named ``<base>.<qualifier>`` is computed as ``<base>`` is (its
reader is ``metrics/<base>.py``): the qualifier makes the same quantity a
metric of its own for cells that need their own bound, or whose per-layer
metrics move it.

A cell's ``chips`` above 1 serves it on ``make_serve_mesh(chips)``. So a
new family, configuration, mix, cell (on one chip or four) or per-layer
metric is new files and new entries; no file here changes.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

import check
import family
import program as P
import schedule
import stats
import trace as TR
from peaks import peaks

BENCH = Path(__file__).resolve().parent
DRAIN_S = 60.0         # longest wait past the window for due first tokens
TRACE_S = 4.0          # least length of the traced slice, mid-window


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(bench: dict, workload: str, root: Path,
            traffic_dir: Path = BENCH / "traffic",
            cells_dir: Path = BENCH / "cells",
            families_dir: Path = family.FAMILIES) -> dict:
    """The cell's entry, configuration (with the cell's own ``serve`` laid
    over it), mix, family and metrics, by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(root / conf["file"])
    own = cells_dir / f"{workload}.json"
    if own.is_file():
        over = load_json(own)
        if set(over) - {"why", "serve"}:
            raise ValueError(f"{own} may hold only 'why' and 'serve'")
        cfg["serve"] = {**cfg["serve"], **over.get("serve", {})}
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}

    def applies(m):
        if "workloads" in m:
            return workload in m["workloads"]
        return m["moves"] in names

    layer = [m for m in bench["per_layer"] if applies(m)]
    return {"cell": cell, "config": cfg,
            "traffic": load_json(traffic_dir / f"{cell['traffic']}.json"),
            "family": family.of(cfg, families_dir),
            "end_to_end": e2e, "per_layer": layer}


def base(name: str) -> str:
    """The quantity a metric's name computes: the part before any ``.``."""
    return name.split(".")[0]


def reader(name: str, metrics_dir: Path = BENCH / "metrics"):
    """``read`` of ``metrics/<base>.py``."""
    name = base(name)
    path = metrics_dir / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Rec:
    """One request of the window, as the client saw it."""

    def __init__(self, req: schedule.Request):
        self.due = req.due
        self.prompt = req.prompt
        self.max_tokens = req.max_tokens
        self.kind = req.kind
        self.client = req.client
        self.handle = None
        self.submitted = None
        self.times = []          # arrival of each token, window seconds


class Tracer:
    """Profiles a slice of the window, in window seconds: from the first
    poll at or after ``start`` until ``length`` seconds have passed and
    the slice has held at least one step of each of ``kinds`` (an
    admission and a decode step in an open loop; a decode step in a closed
    one, whose window may admit nothing), or until ``end``. Polled between
    steps only, so a slice always holds whole steps, however long one step
    lasts."""

    def __init__(self, logdir: Path, start: float, length: float,
                 end: float, kinds=("admit", "decode")):
        self.logdir, self.start, self.length, self.end = (logdir, start,
                                                          length, end)
        self.needs = set(kinds)
        self.state = 0
        self.began = None
        self.kinds = set()
        self._ann = None

    def poll(self, now: float) -> None:
        import jax

        if self.state == 0 and now >= self.start:
            jax.profiler.start_trace(str(self.logdir))
            self._ann = annotate("trace-window")
            self._ann.__enter__()
            self.state, self.began = 1, now
        elif self.state == 1 and (now >= self.end or (
                now >= self.began + self.length
                and self.needs <= self.kinds)):
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = 2

    def note(self, kind: str) -> None:
        if self.state == 1:
            self.kinds.add(kind)


def submit(session, r: Rec, now: float) -> None:
    with annotate("submit"):
        r.handle = session.submit(r.prompt, P.sampling(r.max_tokens))
    r.submitted = now


def preload(session, reqs, clock) -> list:
    """A closed loop's first requests (one a client), admitted and
    prefilled before the window, then one decode segment (which compiles
    it). Times are left on ``clock``: the caller makes them window seconds
    once the window's origin is known."""
    recs = [Rec(r) for r in reqs]
    t = clock()
    for r in recs:
        submit(session, r, t)
    while any(P.queued(r.handle) for r in recs):
        session.step()
    session.step()
    t = clock()
    for r in recs:
        r.times = [t] * r.handle.tokens_ready
    return recs


def drive(session, reqs, seconds: float, clock, t0: float, tracer=None,
          drain: float = DRAIN_S, clients=None, preloaded=()):
    """Serve from one thread: submit each request when it is due (between
    steps: the session is not re-entrant), step, and record when each
    token reached the client. ``reqs`` is an open loop's schedule; with
    ``clients`` (a closed loop) each request that ends makes its client's
    next one due at once, and ``preloaded`` holds the records submitted
    during set-up. Returns the records and, per step, what it was: an
    admission or a decode segment."""
    recs = list(preloaded) + [Rec(r) for r in reqs]
    nxt, live, steps, n = len(preloaded), list(preloaded), {}, 0
    while True:
        now = clock() - t0
        if tracer is not None:
            tracer.poll(now)
        while nxt < len(recs) and recs[nxt].due <= now:
            submit(session, recs[nxt], now)
            live.append(recs[nxt])
            nxt += 1
        if now >= seconds:
            waiting = [r for r in recs if r.due < seconds and (
                r.handle is None
                or (not r.times and not P.terminal(r.handle)))]
            if not waiting or now >= seconds + drain:
                break
        if session.idle:
            until = recs[nxt].due if nxt < len(recs) else seconds
            with annotate("arrival-wait"):
                time.sleep(min(max(until - now, 0.0), 0.05))
            continue
        queued = [r for r in live if P.queued(r.handle)]
        ctx = [r.prompt.size + len(r.times) for r in live
               if r.times and not P.terminal(r.handle)]
        before = P.counters(session)
        t_step = clock() - t0
        with annotate(f"step:{n}"):
            session.step()
        t = clock() - t0
        admitted = [r for r in queued if not P.queued(r.handle)]
        after = P.counters(session)
        kind = "admit" if admitted else "decode"
        if tracer is not None:
            tracer.note(kind)
        steps[n] = {"kind": kind,
                    "prompt_tokens": sum(r.prompt.size for r in admitted),
                    "hit_tokens": after.get("hit_tokens", 0)
                    - before.get("hit_tokens", 0),
                    "contexts": ctx, "at": t_step, "took": t - t_step}
        n += 1
        with annotate("record"):
            for r in live:
                k = r.handle.tokens_ready
                if k > len(r.times):
                    r.times += [t] * (k - len(r.times))
                if clients is not None and P.terminal(r.handle) \
                        and t < seconds:
                    recs.append(Rec(clients.next(r.client, t)))
            live = [r for r in live if not P.terminal(r.handle)]
    return recs, steps, clock() - t0


def warm(session, waves) -> None:
    """Serve each warm-up wave to idle: every program the window will run
    compiles (or loads from the cache) here."""
    for wave in waves:
        for r in wave:
            session.submit(r.prompt, P.sampling(r.max_tokens))
        session.run_until_idle()


class Run:
    """What a per-layer reader may read of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             traced: bool, root: Path, t_start: float, *,
             require_chip: bool = True, log=None,
             traffic_dir: Path = BENCH / "traffic",
             cells_dir: Path = BENCH / "cells",
             families_dir: Path = family.FAMILIES,
             control: bool = False) -> dict:
    """One run; returns the result line's object. ``require_chip=False``
    and the directories are for tests: the first skips the look for an
    accelerator, the others find mixes, cells and families elsewhere.
    ``control`` (the calibration and tests only) also reads the float8
    control on the same sample, as ``control_max_logit_gap``."""
    import jax

    log = log or (lambda m: print(f"[bench] {m}", file=sys.stderr,
                                  flush=True))
    spec = resolve(bench, workload, root, traffic_dir, cells_dir,
                   families_dir)
    cfg, serve, mix = spec["config"], spec["config"]["serve"], spec["traffic"]
    fam = spec["family"]
    chips = spec["cell"]["chips"]
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise SystemExit(f"no TPU: JAX found {devs[0].platform}")
        if len(devs) < chips:
            raise SystemExit(f"cell needs {chips} chips, found {len(devs)}")
        peak = peaks(devs[0].device_kind)
    else:
        peak = peaks("TPU v5 lite")
    devs = devs[:chips]
    compiles, hits = [], []       # programs compiled or loaded; cache hits
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **kw: compiles.append(time.perf_counter())
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    jax.monitoring.register_event_listener(
        lambda ev, **kw: hits.append(time.perf_counter())
        if ev == "/jax/compilation_cache/cache_hits" else None)

    mc = P.model_config(cfg, fam)
    params = P.make_params(mc, seed, fam)
    jax.block_until_ready(params)
    t_weights = time.perf_counter() - t_start
    engine, session = P.open_session(mc, params, serve, chips)
    del params
    vocab, max_len = cfg["vocab_size"], serve["max_len"]
    clients, first, preloaded = None, [], []
    if mix.get("loop", "open") == "closed":
        clients = schedule.Clients(mix, seed, max_len, vocab)
        reqs, prefixes = clients.first(0.0), []
        if clients.preload:
            first, reqs = reqs, []
    else:
        reqs, prefixes = schedule.schedule(mix, seed, seconds, max_len,
                                           vocab)
    warm(session, schedule.warmup(mix, prefixes, serve["buckets"],
                                  serve["segment"] + 1, vocab, seed,
                                  covered=[r.prompt.size for r in first]))
    if first:
        preloaded = preload(session, first, time.perf_counter)
    t0 = time.perf_counter()
    for r in preloaded:
        r.due = r.submitted = r.submitted - t0
        r.times = [x - t0 for x in r.times]
    setup_s = t0 - t_start
    log(f"set-up {setup_s:.2f}s (weights {t_weights:.2f}s), "
        f"{len(compiles)} programs built, {len(hits)} of them from the "
        f"persistent cache; " + (
            f"{clients.n} clients in a closed loop, {len(preloaded)} "
            f"requests preloaded" if clients is not None else
            f"{len(reqs)} requests due in {seconds}s"))
    tracer = None
    logdir = root / ".bench_runs" / "trace"
    if traced:
        shutil.rmtree(logdir, ignore_errors=True)
        logdir.mkdir(parents=True)
        tracer = Tracer(logdir, max(0.0, seconds / 2 - TRACE_S / 2),
                        TRACE_S, seconds,
                        ("decode",) if clients is not None
                        else ("admit", "decode"))
    c0 = P.counters(session)
    n_comp0 = len(compiles)
    pauses = []                   # (seconds, generation, window second)

    def on_gc(phase, info, began=[0.0]):
        if phase == "start":
            began[0] = time.perf_counter()
        else:
            pauses.append((time.perf_counter() - began[0],
                           info["generation"], began[0] - t0))

    gc.callbacks.append(on_gc)
    try:
        recs, steps, end = drive(session, reqs, seconds, time.perf_counter,
                                 t0, tracer, clients=clients,
                                 preloaded=preloaded)
    finally:
        gc.callbacks.remove(on_gc)
    if tracer is not None and tracer.state == 1:
        tracer.poll(float("inf"))
    c1 = P.counters(session)
    in_window = sum(t0 <= c < t0 + seconds for c in compiles[n_comp0:])
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs)

    due = [r for r in recs if r.due < seconds]
    failed = [r for r in due if r.handle is None or
              (P.terminal(r.handle) and not P.done(r.handle))]
    # what the check may sample: finished requests, and where the mix says
    # so those still in flight with enough served tokens
    least = mix.get("in_flight_min_tokens")
    answers = [(r.prompt, np.asarray(r.handle.tokens_so_far(), np.int64))
               for r in recs if r.handle is not None and (
                   P.done(r.handle) or (least is not None
                                        and not P.terminal(r.handle)
                                        and r.handle.tokens_ready >= least))]
    late = [r.submitted - r.due for r in recs if r.submitted is not None]
    session.close()
    for r in recs:
        r.handle = None          # handles hold the session, and so the engine
    del engine, session
    gc.collect()

    tt = stats.ttfts(due, seconds, end)
    gp = stats.gaps(recs, seconds)
    e2e = {"ttft_p90_ms": lambda: stats.percentile(tt, 90) * 1e3,
           "itl_p95_ms": lambda: stats.percentile(gp, 95) * 1e3,
           "tokens_per_s": lambda: stats.tokens_in_window(recs, seconds)
           / seconds,
           "peak_hbm_gib": lambda: mem / 2**30,
           "setup_s": lambda: setup_s}
    timed = [r for r in due if r.due >= 0]           # as ``tt`` counts
    half = [[x for r, x in zip(timed, tt) if (r.due < seconds / 2) == h]
            for h in (True, False)]
    backlog = sum(1 for r in due if not r.times or r.times[0] >= seconds)
    log(f"backlog at close {backlog}; median TTFT of requests due in the "
        f"first / second half {np.median(half[0] or [0]) * 1e3:.0f} / "
        f"{np.median(half[1] or [0]) * 1e3:.0f} ms")
    nz = [g for g in gp if g > 0]
    n_adm = sum(s["kind"] == "admit" for s in steps.values())
    log(f"token gaps: {len(nz)} of {len(gp)} above 0; p90 / p93 / p95 / "
        f"p97 / p99 " + " / ".join(
            f"{stats.percentile(gp, q) * 1e3:.1f}" for q in (90, 93, 95, 97,
                                                              99))
        + f" ms; {len(steps)} steps, {n_adm} admissions")
    worst = max(pauses, default=(0.0, -1, 0.0))
    log(f"garbage collections in the window: {len(pauses)}, "
        f"{sum(p[0] for p in pauses) * 1e3:.0f} ms in all; longest "
        f"{worst[0] * 1e3:.0f} ms (generation {worst[1]}, at {worst[2]:.2f}s)")
    slow = sorted(steps.values(), key=lambda s: -s["took"])[:3]
    log("longest steps: " + "; ".join(
        f"{s['kind']} at {s['at']:.2f}s took {s['took'] * 1e3:.0f} ms "
        f"({s['prompt_tokens']} prompt, {s['hit_tokens']} hit tokens)"
        for s in slow))
    log(f"{len(due)} due, {len(answers)} to sample from, {len(failed)} "
        f"failed; "
        f"{len(tt)} first tokens, {len(gp)} token gaps; generator late "
        f"median {np.median(late) * 1e3:.1f} ms, max "
        f"{max(late) * 1e3:.1f} ms; {in_window} compiles in the window; "
        f"peak {mem} B")
    metrics = {}
    device = dict(_device_info(devs), memory_peak_bytes=int(mem))
    breakdown = None
    if not traced:
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": float(e2e[base(m["name"])]()),
                                  "unit": m["unit"]}
    else:
        data = TR.load_profile(str(logdir))
        view = TR.View(data, steps)
        shutil.rmtree(logdir, ignore_errors=True)
        run = Run(view=view, steps=steps, records=recs, seconds=seconds,
                  counters=(c0, c1), config=cfg, serve=serve, peak=peak,
                  chips=chips, family=fam)
        for m in spec["per_layer"]:
            v = reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=view.busy_s(), window_s=view.window_s)
        breakdown = {"device_ops": view.top_ops(), "idle_gaps":
                     view.idle_gaps()}
        log(f"trace lines: {json.dumps(data['lines'])}")

    t_c = time.perf_counter()
    n_c, n_h = len(compiles), len(hits)
    spread = devs if chips > 1 else None      # the reference's devices
    numbers = check.compare(cfg, seed, answers, max_len, fam, spread)
    log(f"check took {time.perf_counter() - t_c:.1f}s, "
        f"{len(compiles) - n_c} programs built, {len(hits) - n_h} of them "
        f"from the persistent cache")
    correct = check.verdict(numbers)
    if control and "max_logit_gap" in numbers:
        numbers["control_max_logit_gap"] = (
            check.control_gap(cfg, seed, answers, max_len, fam, spread), ">=",
            numbers["max_logit_gap"][2])
    out = {"correct": correct, "attempted": len(due),
           "failed": len(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = {k: {"value": v, "limit": lim, "must_be": op}
                    for k, (v, op, lim) in numbers.items()}
    for k, (v, op, lim) in numbers.items():
        log(f"check {k} = {v!r} (must be {op} {lim!r})")
    return out
