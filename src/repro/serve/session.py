"""Async-style streaming serve API: request handles over a re-entrant core.

``ServeSession`` turns the closed batch loop (hand over a request pool,
wait for the pool to drain) into an explicit request lifecycle:

    session = engine.session(lanes=4, page_size=16, segment=2)
    h = session.submit(prompt, SamplingParams(max_tokens=64, stop_token=2))
    for tok in h.tokens():         # yields as decode segments complete
        ...
    h2 = session.submit(other)     # mid-flight: admitted as lanes free up
    h.cancel()                     # frees the lane + pages immediately
    session.run_until_idle()

The session drives ONE scheduler/pool through three composable phases —
``_admit_and_prefill`` (pop pending requests into free lanes, bucketed
prefill — or, with ``prefix_cache=True``, a tail-only / zero prefill off
the radix index — commit pages, EMIT the prefill-sampled first token),
``_decode_segment`` (one fused ``segment``-step scan over the fixed lane
pool), ``_drain_finished`` (harvest emitted tokens, stop-token early
finish, release lanes) — so callers can interleave submissions, token
reads, and cancellations between segments. A ``step()`` that admitted
returns before decoding: streaming TTFT equals prefill latency.
``ServeEngine.generate_batch`` is a thin wrapper: submit all, run until
idle, collect.

Prefill compiles are BUCKETED by padded prompt length: a prompt of length
S is right-padded to the smallest bucket >= S (powers of two by default,
or an explicit ``buckets=`` tuple) and prefilled with the true length as a
traced position mask (``lm_prefill(length=...)``), so a live stream of
ragged prompts reuses a handful of compiled prefill fns instead of one per
distinct length. Pool bytes after the masked commit are identical to an
unpadded prefill, so greedy tokens stay bit-identical to ``generate``.

Sampling state is per-request (``SamplingParams``): temperature, optional
seed (else the session key folded with the request id), token budget, stop
token. A request's sampled stream is a function of its own key and step
only — independent of lane placement, co-tenants, and submission timing.

Overload + fault hardening (PR 6): the session is the CONTAINMENT
boundary. Deadlines are swept at the top of every ``step()`` (unmeetable
pending → SHED before any compute; past-deadline active → EXPIRED, lane +
pages freed like cancel). Injected faults (serve/faults.py) are polled
host-side BEFORE the pool is taken for a donating dispatch, so a fault
never costs the pool: admission faults fail only the victim request;
an injected kernel-dispatch fault serves the segment through the
bitwise-identical XLA gather graph (no victim at all); detected
prefix-index corruption quarantines the index (cold admission). A REAL
dispatch failure after donation loses the pool — ``_contain_pool_loss``
fails every active request terminally, flushes the index (its pages were
in the lost bytes), and the next admission starts over on a fresh pool.
A segment graph that fails to lower or compile is NOT contained: it is
compiled before the pool moves and its error propagates out of
``step()``.
``audit=True`` cross-checks every allocator refcount and index pin
against the holders' own books after each step.
"""
from __future__ import annotations

import time
from collections import Counter
from contextlib import nullcontext
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import block_roles
from repro.models.attention import paged_kernel_enabled, paged_kernel_override

from .faults import FaultInjector, InjectedFault, corrupt_prefix_index
from . import reasons
from .paged_cache import pages_for
from .prefix_cache import IndexCorruption, PrefixCache, snapshot_stride
from .sampling import logits_all_finite, sample_tokens
from .scheduler import (TERMINAL, Request, RequestStatus, SamplingParams,
                        Scheduler)
from .swap import SwapBridge, SwapManager


class _PoolLost(Exception):
    """A segment dispatch failed after the pool was donated (the original
    error is ``__cause__``)."""


def _default_bucket(S: int, floor: int = 8) -> int:
    b = floor
    while b < S:
        b <<= 1
    return b


def _lane_state_bytes(cfg) -> int:
    """Bytes of one lane's SSM state over every mamba layer (float32 h
    and conv history): what one boundary snapshot holds."""
    layers = cfg.n_groups * sum(r["mixer"] == "mamba"
                                for r in block_roles(cfg))
    return layers * 4 * cfg.d_inner_ * (cfg.ssm_state + cfg.conv_width - 1)


def _raw_key(key):
    """Normalize a PRNG key to the raw (2,) uint32 form the lane mirrors
    store: modern typed keys (``jax.random.key``) pass through
    ``key_data``, legacy ``PRNGKey`` arrays pass through unchanged — both
    work everywhere ``generate`` accepts a key, so they must here too."""
    if jnp.issubdtype(jnp.asarray(key).dtype, jax.dtypes.prng_key):
        return jax.random.key_data(key)
    return key


class RequestHandle:
    """Caller-facing view of one submitted request."""

    def __init__(self, session: "ServeSession", req: Request):
        self._session = session
        self._req = req

    @property
    def rid(self) -> int:
        return self._req.rid

    @property
    def status(self) -> RequestStatus:
        return self._req.status

    @property
    def tokens_ready(self) -> int:
        """Tokens already emitted and readable without further stepping."""
        return len(self._req.emitted)

    def tokens_so_far(self) -> list:
        """Snapshot of the tokens emitted so far, WITHOUT driving the
        session — the non-blocking read for poll-style consumers (the
        ``--stream`` launcher, an HTTP/SSE front-end) that interleave
        their own ``session.step()`` calls with reads."""
        return list(self._req.emitted)

    @property
    def error(self) -> Optional[str]:
        """Why the request left the session abnormally (``SHED`` /
        ``EXPIRED`` / ``FAILED``): the machine-readable reason string
        (``queue-full``, ``deadline``, ``injected:page_alloc``, ...);
        None for normal lifecycles."""
        return self._req.fail_reason

    @property
    def preemptions(self) -> int:
        """Total times this request was evicted (swap + recompute)."""
        return self._req.preemptions

    @property
    def preempt_swap(self) -> int:
        """Evictions resumed by host-RAM page swap: the restored bytes
        are identical, so the stream stays BIT-identical to an
        uninterrupted run — swap-resumed streams need no special
        handling from identity consumers."""
        return self._req.preempt_swap

    @property
    def preempt_recompute(self) -> int:
        """Evictions resumed by recompute — nonzero means the stream is
        oracle-consistent for the EFFECTIVE prompt at each resume, not
        bit-equal to an uninterrupted run (the documented recompute
        contract). Stream-identity consumers (traffic replay) skip
        such requests."""
        return self._req.preempt_recompute

    def tokens(self) -> Iterator[int]:
        """Yield this request's tokens as decode segments complete.

        Drains whatever is already buffered, then drives ``session.step()``
        (admitting/decoding EVERY live request, not just this one) until
        the request reaches a terminal status — done, cancelled, or the
        hardened-lifecycle exits (shed/expired/failed: the stream simply
        ends after the partial tokens; ``status``/``error`` say why). Safe
        to interleave with other handles' iterators — progress is shared.
        """
        i = 0
        while True:
            while i < len(self._req.emitted):
                yield self._req.emitted[i]
                i += 1
            if self._req.status in TERMINAL:
                return
            if not self._session.step():
                raise RuntimeError(
                    f"session idle but request {self._req.rid} is "
                    f"{self._req.status.name}")

    def result(self) -> jax.Array:
        """Drive the session until this request reaches a terminal status;
        returns its tokens as a (n,) int32 array (partial if cancelled /
        shed mid-queue / expired / failed — check ``status``/``error``)."""
        while self._req.status not in TERMINAL:
            if not self._session.step():
                raise RuntimeError(
                    f"session idle but request {self._req.rid} is "
                    f"{self._req.status.name}")
        return jnp.asarray(self._req.emitted, jnp.int32)

    def cancel(self) -> bool:
        """Drop the request now. An active request releases its lane and
        pages immediately (reusable by the next admit); already-emitted
        tokens stay readable. Returns False if it already reached a
        terminal status."""
        req = self._req
        if req.status in TERMINAL:
            return False
        lane = req.lane
        ok = self._session.sched.cancel(req)
        if ok and lane >= 0:
            self._session._reset_lane(lane)
        if ok:
            self._session._handles.pop(req.rid, None)
        return ok


class ServeSession:
    """One live serving context: a scheduler + paged pool + host mirrors.

    Compiled fns are cached on the ENGINE (keyed by pool geometry), so
    sessions of the same shape share compiles; the paged pool is taken
    from the engine's donation-safe cache pool lazily at first admission
    and returned by ``close()`` (or the context manager).
    """

    def __init__(self, engine, *, lanes: int = 4, page_size: int = 16,
                 n_pages: Optional[int] = None, segment: int = 1,
                 key: Optional[jax.Array] = None,
                 buckets: Optional[Sequence[int]] = None,
                 prefix_cache: Optional[bool] = None,
                 max_pending: Optional[int] = None,
                 tenant_page_quota: Optional[int] = None,
                 tenant_lane_quota: Optional[int] = None,
                 faults: Optional[FaultInjector] = None,
                 audit: bool = False, clock=None,
                 hit_first: bool = True,
                 host_page_budget: Optional[int] = None):
        """Overload/robustness knobs (all default off — the pre-hardening
        behavior): ``max_pending`` bounds the submit queue (overflow sheds
        with ``ShedError``), ``tenant_*_quota`` bound each tenant's
        worst-case footprint, ``faults`` arms the injection registry (or
        set ``REPRO_FAULTS`` in the env — chaos mode), ``audit=True`` runs
        the allocator + prefix-index invariant audit after every step,
        ``clock`` (→ wall milliseconds, default ``time.monotonic``) is the
        deadline clock — injectable so tests drive time by hand.
        ``host_page_budget`` attaches the host-RAM swap tier
        (serve/swap.py): that many host page slots back swap-out
        preemption (bit-exact resume), prefix-cache demotion, and index
        persistence across ``close()`` — and admission accounts BOTH
        tiers (``host-budget`` sheds)."""
        if segment < 1 or page_size < 1 or lanes < 1:
            raise ValueError("segment, page_size and lanes must be >= 1")
        self.engine = engine
        self.cfg = engine.cfg
        self.lanes = lanes
        self.page_size = page_size
        self.segment = segment
        self._table_cols = -(-engine.max_len // page_size)
        if n_pages is None:    # full residency for every lane + garbage page
            n_pages = lanes * self._table_cols + 1
        self.n_pages = n_pages
        if prefix_cache is None:
            prefix_cache = engine.prefix_cache
        self._has_ssm = any(r["mixer"] == "mamba"
                            for r in block_roles(engine.cfg))
        self.prefix = PrefixCache(page_size, snapshot_stride=snapshot_stride(
            _lane_state_bytes(engine.cfg), page_size)) \
            if prefix_cache else None
        self.faults = faults if faults is not None else FaultInjector.from_env()
        self.audit_mode = audit
        self._clock = clock if clock is not None \
            else (lambda: time.monotonic() * 1000.0)
        self._est_admit_ms = 0.0    # EMA of admission+prefill wall time
        self.swap_mgr = None
        self._swap = None
        self._store_key = None
        if host_page_budget is not None:
            if getattr(engine, "mesh", None) is not None:
                raise NotImplementedError(
                    "host_page_budget under a serve mesh is not supported "
                    "yet: sharded attention leaves need per-shard host "
                    "slices (ROADMAP follow-up)")
            if host_page_budget < 0:
                raise ValueError("host_page_budget must be >= 0")
            # a same-geometry index parked by a previous session's close()
            # is ADOPTED — its host-resident entries (and their slots)
            # carry over; the bridge below rebinds it to this session
            self._store_key = ("pfx", page_size, int(host_page_budget))
            parked = engine._prefix_store.pop(self._store_key, None) \
                if self.prefix is not None else None
            if parked is not None:
                self.prefix, self.swap_mgr = parked
                self.swap_mgr.faults = self.faults
            else:
                self.swap_mgr = SwapManager(engine.cfg,
                                            int(host_page_budget),
                                            faults=self.faults)
            self._swap = SwapBridge(self, self.swap_mgr)
            if self.prefix is not None:
                self.prefix.swap = self._swap
        self.sched = Scheduler(lanes, n_pages, page_size,
                               prefix_cache=self.prefix,
                               max_pending=max_pending,
                               tenant_page_quota=tenant_page_quota,
                               tenant_lane_quota=tenant_lane_quota,
                               faults=self.faults, hit_first=hit_first,
                               swap=self._swap, clock=self._clock)
        self.key = _raw_key(key) if key is not None else jax.random.PRNGKey(0)
        self.buckets = tuple(sorted(int(b) for b in buckets)) \
            if buckets else None
        self._pool = None
        self._pool_key = ("paged", lanes, page_size, n_pages)
        self._closed = False
        # shard-loss drill history (mesh sessions only): shard ids whose
        # simulated drop was contained by a fail-fast lane drain. Surfaced
        # via stats()["mesh"] so operators see the events.
        self._lost_shards: list = []
        self._next_rid = 0
        self._handles = {}
        self._last_toks = None
        # host-side device mirror of the lane state (tiny, re-uploaded per
        # segment; the multi-MiB pool itself only moves via donation)
        self._bt = np.zeros((lanes, self._table_cols), np.int32)
        self._pos = np.zeros((lanes,), np.int32)
        self._cur = np.zeros((lanes, 1), np.int32)
        self._steps = np.zeros((lanes,), np.int32)
        self._temps = np.zeros((lanes,), np.float32)
        self._keys = np.zeros((lanes, 2), np.uint32)

    # -- lifecycle -----------------------------------------------------------
    def submit(self, prompt, params: Optional[SamplingParams] = None
               ) -> RequestHandle:
        """Enqueue a request at any time — before, between, or after decode
        segments. Validates the FULL capacity story up front: an empty
        prompt, a zero budget, a prompt+budget past ``max_len``, or a page
        budget the pool can never satisfy raise ``ValueError`` here, before
        any compute is spent (and before other requests' tokens are at
        risk). Returns a handle for streaming/result/cancel."""
        if self._closed:
            raise RuntimeError("session is closed")
        p = np.asarray(prompt, np.int32).reshape(-1)
        if params is None:
            params = SamplingParams()
        rid = self._next_rid
        if params.max_tokens < 1 or p.size < 1:
            raise ValueError(f"request {rid}: empty prompt or zero "
                             "token budget")
        if p.size + params.max_tokens > self.engine.max_len:
            raise ValueError(
                f"request {rid}: {p.size}+{params.max_tokens} tokens "
                f"exceeds max_len={self.engine.max_len} (would need "
                f"{pages_for(p.size, params.max_tokens, self.page_size)} "
                f"pages; {self.sched.alloc.n_free} free now)")
        req = Request(rid=rid, prompt=p, params=params)
        self.sched.check_fits(req)          # never-fitting page budget
        self._bucket_len(p.size)            # custom buckets must cover it
        if params.deadline_ms is not None:  # relative budget → absolute ms
            req.deadline = self._clock() + params.deadline_ms
        self._next_rid += 1
        self.sched.submit(req)              # may shed (queue/quota bounds)
        handle = RequestHandle(self, req)
        self._handles[rid] = handle
        return handle

    def step(self) -> bool:
        """Drive one scheduling round. EMISSION-BEFORE-DECODE: an admission
        round (admit + prefill + emit each new request's prefill-sampled
        first token) returns immediately, so streaming consumers observe
        TTFT = prefill latency — first tokens never wait out a decode
        segment. Rounds with nothing to admit decode ONE fused segment over
        the lane pool and drain finished lanes. Returns False (and does
        nothing) once the session is idle.

        Each phase runs inside a profiler span named ``serve.*``
        (``serve.schedule``, ``serve.prefill``, ``serve.hit_admit``,
        ``serve.first_token``, ``serve.segment``, ``serve.segment_wait``,
        ``serve.drain``); a request's spans carry its ``rid``. A span
        records only while a profiler session is active and adds no sync."""
        if self._closed:
            raise RuntimeError("session is closed")
        if self.sched.idle:
            return False
        self._sweep_deadlines()
        if self.faults is not None and self.prefix is not None \
                and self.faults.should_fire("prefix_index"):
            # the corruption stand-in: flip bytes in a live index node;
            # detection + quarantine happen at the next lookup (or audit)
            corrupt_prefix_index(self.prefix)
        if not self.sched.idle:
            if self._admit_and_prefill():
                pass                         # TTFT: return before decoding
            elif self._decode_segment():
                with TraceAnnotation("serve.drain"):
                    self._drain_finished()
        if self.audit_mode:
            try:
                self.audit()
            except IndexCorruption:
                # the post-step audit is a DETECTOR, same as the lookup
                # walk: corruption it finds quarantines the index (cold
                # admission — always correct) instead of crashing the
                # session; the re-audit below must then come back clean
                self.prefix.quarantine(self.sched.alloc)
                self.audit()
        return True

    def run_until_idle(self) -> None:
        while self.step():
            pass

    def preempt(self, handle: RequestHandle) -> bool:
        """Evict a live request: its lane and pages free immediately and
        the request requeues at the FRONT of the queue (status PREEMPTED).
        With the swap tier (``host_page_budget=``) its page bytes + lane
        state park on host and re-admission restores them — the resumed
        greedy stream is BIT-identical to the uninterrupted one. Without
        the tier (or when it cannot take the pages) re-admission
        recomputes the cache by prefilling prompt+emitted; the resumed
        tail is exactly the stream the engine would serve for that
        effective prompt fresh (see scheduler.py on why recompute is
        oracle-consistent rather than bit-equal under Boolean
        numerics)."""
        req = handle._req
        if req.lane < 0 or self.sched.active.get(req.lane) is not req:
            return False
        lane = req.lane
        self.sched.evict(lane)
        self._reset_lane(lane)
        return True

    def _sweep_deadlines(self) -> None:
        """Deadline enforcement, both ends: shed pending requests whose
        deadline cannot be met (now + estimated admission latency past it
        — no compute wasted on doomed work), and expire active requests
        already past theirs (lane + pages free immediately, like cancel;
        partial tokens stay readable)."""
        now = self._clock()
        self.sched.shed_expired(now, self._est_admit_ms)
        for lane, req in self.sched.expire(now):
            self._reset_lane(lane)
        for req in self.sched.drain_shed():
            self._handles.pop(req.rid, None)

    def audit(self) -> dict:
        """Zero-leak oracle: rebuild the page-refcount and node-pin census
        from the holders' OWN books (active requests' page lists + CoW
        holds, the prefix index's owned pages and records) and cross-check
        the allocator and index against it. Raises on any leak, double
        count, or orphan; returns summary stats. O(pool + index) host work
        — run after every step under ``audit=True`` and by the fault
        suite after drain."""
        holds: Counter = Counter()
        pins: Counter = Counter()           # id(node) -> live-request pins
        for req in self.sched.active.values():
            for p in req.pages:
                holds[p] += 1
            if req.hit is not None:
                for node in self.prefix._chain(req.hit.node):
                    pins[id(node)] += 1     # pins are transitive to root
                if req.hit.exact and req.hit.record.page is not None:
                    holds[req.hit.record.page] += 1     # CoW-source hold
        out = {}
        if self.prefix is not None:
            for p in self.prefix._owned_page_iter():
                holds[p] += 1               # index ownership refs
            out["prefix"] = self.prefix.audit(self.sched.alloc,
                                              external_pins=dict(pins))
        out["alloc"] = self.sched.alloc.audit(holds=dict(holds))
        out["sched"] = dict(self.sched.stats)
        if self.swap_mgr is not None:
            slots: Counter = Counter()  # slot -> holders, from their books
            for req in self.sched.pending:
                if req.swap is not None:
                    for sl in req.swap.slots:
                        slots[sl] += 1
            if self.prefix is not None:
                for sl in self.prefix._host_slot_iter():
                    slots[sl] += 1
            out["swap"] = self.swap_mgr.audit(dict(slots))
        return out

    def stats(self) -> dict:
        """One flat host-side snapshot of every serving counter — the
        surface the HTTP gateway's ``/metrics`` endpoint renders into
        Prometheus text (gateway/metrics.py): scheduler lifecycle
        counters (with ``queue_wait_ms``, the session-clock time admitted
        requests spent queued, summed over ``queue_waited`` admissions),
        queue/lane occupancy, pool-page occupancy, the engine's count of
        programs built, and (when enabled) the prefix-cache counters. Pure
        reads, no device sync."""
        alloc = self.sched.alloc
        return {
            "sched": dict(self.sched.stats),
            "engine": {"programs_built": self.engine.programs_built},
            "pending": len(self.sched.pending),
            "active": len(self.sched.active),
            "lanes": self.lanes,
            "pool": {"n_pages": alloc.n_pages, "n_free": alloc.n_free,
                     "n_owned": alloc.n_pages - 1 - alloc.n_free},
            "prefix": dict(self.prefix.stats)
            if self.prefix is not None else None,
            "swap": self.swap_mgr.stats_dict()
            if self.swap_mgr is not None else None,
            "mesh": self._mesh_stats(),
        }

    def _mesh_stats(self) -> Optional[dict]:
        """Mesh health snapshot (None single-device). ``healthy`` goes —
        and stays — False after a contained shard-loss event: in a real
        deployment the mesh must be rebuilt before the instance is fully
        trusted again, so the flag is conservative even though this
        simulation keeps serving on the (actually intact) devices."""
        if getattr(self.engine, "mesh", None) is None:
            return None
        return {"shards": int(getattr(self.engine, "tp", 1)),
                "shard_loss_events": len(self._lost_shards),
                "lost": list(self._lost_shards),
                "healthy": not self._lost_shards}

    @property
    def idle(self) -> bool:
        return self.sched.idle

    def close(self) -> None:
        """Cancel anything outstanding and return the paged pool to the
        engine's cache pool for the next session of this geometry. With
        the swap tier + prefix cache, the index is first demoted WHOLE to
        host and parked on the engine — the next same-geometry session
        adopts it, so the prefix cache survives pool hand-back."""
        if self._closed:
            return
        for h in list(self._handles.values()):
            h.cancel()
        if self._swap is not None and self.prefix is not None \
                and not self.prefix.quarantined:
            self.prefix.demote_all(self.sched.alloc)
            self.engine._prefix_store[self._store_key] = (self.prefix,
                                                          self.swap_mgr)
        if self._pool is not None:
            self.engine._caches.put(self._pool_key, self._pool)
            self._pool = None
        self._closed = True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- phases composed by step() -------------------------------------------
    def _bucket_len(self, S: int, strict: bool = True) -> int:
        if self.buckets is not None:
            for b in self.buckets:
                if b >= S:
                    return b
            if strict:
                raise ValueError(f"no prefill bucket >= prompt length {S} "
                                 f"(buckets={self.buckets})")
        # admission never hard-fails mid-serve: a preempted request whose
        # effective prompt (prompt+emitted) outgrew an explicit bucket set
        # takes one extra pow-2 compile instead of crashing the session
        return _default_bucket(S)

    def _lane_key(self, req: Request) -> np.ndarray:
        if req.params.seed is not None:
            k = _raw_key(jax.random.PRNGKey(req.params.seed))
        else:
            k = jax.random.fold_in(self.key, req.rid)
        return np.asarray(k, np.uint32)

    def _ensure_pool(self):
        if self._pool is None:
            self._pool = self.engine._caches.take(self._pool_key)
            if self._pool is None:
                # engine hook: mesh engines device_put the fresh pool with
                # its attention leaves sharded on the KVp axis
                self._pool = self.engine.init_pool(self.lanes, self.n_pages,
                                                   self.page_size)

    def placement(self):
        """Lane→shard placement under the mesh-wide scheduler.

        Tensor-parallel serving places every lane on ONE shard group
        spanning the whole ("model",) mesh: each device holds that lane's
        head-local page slice, so the host scheduler core makes every
        admission/quota/priority/deadline decision once, mesh-wide —
        PR 6 semantics are placement-invariant (pinned by the multidevice
        suite). Returns {lane: shard_group}; all lanes map to group 0
        until data-parallel replica routing adds more groups (ROADMAP).
        """
        return {lane: 0 for lane in range(self.lanes)}

    def _take_pool(self):
        """Detach the pool before a donating dispatch: donation invalidates
        the buffers even when the dispatch later fails, so on an exception
        ``self._pool`` must be None — ``close()`` then skips the put and the
        engine cache never sees a poisoned tree (CachePool.take contract)."""
        self._ensure_pool()
        pool, self._pool = self._pool, None
        return pool

    def _reset_lane(self, lane: int) -> None:
        """Point a released lane at the garbage page: its in-flight segment
        writes land on page 0 and its position masks every read."""
        self._bt[lane] = 0
        self._pos[lane] = self._cur[lane] = self._steps[lane] = 0
        self._temps[lane] = 0.0
        self._keys[lane] = 0

    def _prefix_page_bucket(self, n: int) -> int:
        """Pow-2 bucket for the prefix-gather page count — bounds tail
        prefill compiles by O(log pool) instead of one per hit length."""
        return _default_bucket(n, floor=1)

    def _admit_exact(self, req, S: int):
        """Exact-record admission: ZERO prefill. Shared full pages enter
        the block table as-is; a partially-filled boundary page is CoW-
        forked onto the request's first private page (its decode rows land
        there); the stored mamba end state is written into the lane. The
        first token comes from the record's stored end-of-prompt logits —
        the same bytes the cold run sampled from, which (with decode then
        re-reading identical page bytes) makes the whole cache-hit stream
        bit-identical to the cold run."""
        rec = req.hit.record
        fork = rec.page is not None
        if fork and self.faults is not None \
                and self.faults.should_fire("fork_page"):
            # polled host-side BEFORE _take_pool(): the pool is untouched,
            # so containment costs only this request
            raise InjectedFault("fork_page", f"rid={req.rid}")
        if fork or self._has_ssm:
            fn = self.engine._get_fn(
                ("hit_admit", self._pool_key, fork, self._has_ssm),
                lambda: self.engine._build_hit_admit(fork, self._has_ssm))
            # fork dst = the request's logical page S // page_size, which
            # scheduler page ordering puts first among its private pages
            self._pool = fn(
                self._take_pool(),
                jnp.asarray(rec.page if fork else 0, jnp.int32),
                jnp.asarray(req.private_pages[0] if fork else 0, jnp.int32),
                jnp.asarray(req.lane, jnp.int32),
                rec.end_ssm if self._has_ssm else {})
            if fork:
                self.prefix.stats["cow_forks"] += 1
        req.cache_extras = None         # index already holds this prompt
        return rec.logits

    def _count_queue_wait(self, req: Request) -> None:
        """Add the time since ``req`` last entered the queue to
        ``stats()["sched"]["queue_wait_ms"]``, once per admission: a request
        admitted behind others in the same step counts their prefills."""
        if req.queued_at is not None:
            self.sched.stats["queue_wait_ms"] += self._clock() - req.queued_at
            self.sched.stats["queue_waited"] += 1
            req.queued_at = None

    def _admit_prefill(self, req, eff, o: int):
        """Cold / partial-hit admission: prefill ONLY the uncached tail
        through its length bucket (pad to the bucket, true length as a
        traced mask), scatter the masked rows into the request's tail
        pages, and — when the prefix index is on — capture the device
        payload a finish donates to it (end logits, mamba end state,
        kept boundary snapshots). A partial hit threads the position
        offset, the gathered prefix K/V pages, and the boundary SSM state
        through ``lm_prefill`` so the tail is computed exactly as a
        continuation of the cached prefix."""
        T = int(eff.shape[0]) - o
        o_pages = o // self.page_size
        bucket = self._bucket_len(T, strict=False)
        npp_b = -(-bucket // self.page_size)
        npp_t = -(-T // self.page_size)
        page_ids = np.zeros((npp_b,), np.int32)
        page_ids[:npp_t] = req.pages[o_pages:o_pages + npp_t]
        padded = np.zeros((bucket,), np.int32)
        padded[:T] = eff[o:]
        if self.prefix is None:
            pfn = self.engine._get_fn(
                ("prefill_commit", self._pool_key, bucket),
                lambda: self.engine._build_prefill_commit(self.page_size))
            logits, self._pool = pfn(
                self.engine.params, self._take_pool(),
                jnp.asarray(padded[None]), jnp.asarray(T, jnp.int32),
                jnp.asarray(page_ids), jnp.asarray(req.lane, jnp.int32))
            return logits
        ppb = self._prefix_page_bucket(o_pages) if o_pages else 0
        prefix_ids = np.zeros((ppb,), np.int32)
        prefix_ids[:o_pages] = req.pages[:o_pages]
        # the kernel flag is part of the key: REPRO_PAGED_KERNEL is read at
        # trace time, so a mid-process flip must recompile, not serve the
        # other path's cached graph
        pfn = self.engine._get_fn(
            ("pfx_prefill", self._pool_key, bucket, ppb,
             paged_kernel_enabled()),
            lambda: self.engine._build_pfx_prefill(self.page_size,
                                                   tail=ppb > 0))
        snap_pages = self.prefix.snapshot_pages(o, o + T)
        snap_at = np.zeros((self.prefix.snapshot_slots(bucket),), np.int32)
        snap_at[:len(snap_pages)] = [b * self.page_size - o
                                     for b in snap_pages]
        ssm_init = {}
        if ppb > 0:
            args = (jnp.asarray(o, jnp.int32), jnp.asarray(prefix_ids),
                    jnp.asarray(o, jnp.int32))
            if self._has_ssm:
                ssm_init = req.hit.ssm
        else:
            args = ()
        resume = TraceAnnotation("serve.snapshot_restore", tokens=o,
                                 gap=req.hit.gap) \
            if ppb > 0 and self._has_ssm else nullcontext()
        with resume:
            logits, self._pool, end_ssm, snaps = pfn(
                self.engine.params, self._take_pool(),
                jnp.asarray(padded[None]), jnp.asarray(T, jnp.int32), *args,
                jnp.asarray(page_ids), jnp.asarray(req.lane, jnp.int32),
                jnp.asarray(snap_at), *((ssm_init,) if ppb > 0 else ()))
        req.cache_extras = {"tokens": np.array(eff, np.int32), "offset": o,
                            "logits": logits, "end_ssm": end_ssm,
                            "snaps": snaps, "snap_pages": snap_pages,
                            # exact records promise bit-identity with a
                            # COLD run; a kv-quant tail prefill computes
                            # over DEQUANTIZED prefix rows, so its end
                            # state is serve-over-cache, not cold-faithful
                            # — donate its tail pages to the trie (partial
                            # hits are documented as serve-over-cache) but
                            # never as an exact record
                            "record_ok": not (self.cfg.kv_cache_quant
                                              and o > 0)}
        return logits

    def _resume_swapped(self, req: Request) -> bool:
        """Swap-resume a re-admitted preempted request: scatter its host
        slots into the freshly granted pages, restore the lane mirrors
        captured at eviction, and continue decoding — the resumed stream
        is bit-identical to the uninterrupted one. False = an injected
        ``swap_in`` fault fired: the record is discarded (host slots
        freed), the preemption reclassified as recompute, and the caller
        falls through to the recompute prefill path, which is always
        correct."""
        rec, req.swap = req.swap, None
        if self.faults is not None and self.faults.should_fire("swap_in"):
            self._swap.discard(rec)
            self.sched.swap_resume_failed(req)
            return False
        self._swap.restore(req, rec)
        lane = req.lane
        self._bt[lane] = 0
        self._bt[lane, :len(req.pages)] = req.pages
        self._pos[lane] = rec.pos
        self._cur[lane, 0] = rec.cur
        self._steps[lane] = rec.steps
        self._temps[lane] = req.params.temperature
        self._keys[lane] = self._lane_key(req)
        req.status = RequestStatus.DECODING
        return True

    def _admit_and_prefill(self):
        """Pop pending requests into free lanes, produce each one's
        end-of-prompt logits (full prefill, tail prefill, or an exact-hit
        record read), arm the lane mirrors, and EMIT the prefill-sampled
        first token immediately — streaming TTFT equals prefill latency,
        and a budget-1 (or instant stop-token) request finishes without
        ever occupying a decode segment."""
        t0 = self._clock()
        with TraceAnnotation("serve.schedule"):
            admitted = self.sched.admit()
            # reset lanes freed by admission-time preemption/faults BEFORE
            # arming new lanes — a reset must never clobber a fresh admit
            for lane in self.sched.drain_freed_lanes():
                self._reset_lane(lane)
            for req in self.sched.drain_faulted() + self.sched.drain_shed():
                self._handles.pop(req.rid, None)
        for req in admitted:
            if req.swap is not None:
                with TraceAnnotation("serve.hit_admit", rid=req.rid):
                    self._count_queue_wait(req)
                    resumed = self._resume_swapped(req)
                if resumed:
                    # bytes + lane state restored; decode continues exactly
                    # where it stopped — no prefill, no token emitted here
                    continue
            eff = req.effective_prompt
            S = int(eff.shape[0])
            try:
                if req.hit is not None and req.hit.exact:
                    with TraceAnnotation("serve.hit_admit", rid=req.rid):
                        self._count_queue_wait(req)
                        logits = self._admit_exact(req, S)
                else:
                    o = req.hit.hit_len if req.hit is not None else 0
                    with TraceAnnotation("serve.prefill", rid=req.rid,
                                         tokens=S - o, hit=o):
                        self._count_queue_wait(req)
                        logits = self._admit_prefill(req, eff, o)
            except InjectedFault as e:
                # fired before the pool was taken (host-side poll), so the
                # pool is intact: fail ONLY the victim, free its resources
                self.sched.fail(req.lane, reasons.format_reason(
                    reasons.INJECTED, e.site))
                for lane in self.sched.drain_freed_lanes():
                    self._reset_lane(lane)
                self._handles.pop(req.rid, None)
                continue
            if self.audit_mode and not logits_all_finite(logits[:, -1]):
                self.sched.fail(req.lane, reasons.format_reason(
                    reasons.BAD_LOGITS, "non-finite prefill logits"))
                for lane in self.sched.drain_freed_lanes():
                    self._reset_lane(lane)
                self._handles.pop(req.rid, None)
                continue
            with TraceAnnotation("serve.first_token", rid=req.rid):
                lane_key = self._lane_key(req)
                e = len(req.emitted)
                first = sample_tokens(
                    self.cfg, logits[:, -1], req.params.temperature,
                    jnp.asarray(lane_key) if req.params.temperature > 0
                    else None, e)
                tok0 = int(first[0, 0])
            lane = req.lane
            self._bt[lane] = 0
            self._bt[lane, :len(req.pages)] = req.pages
            self._pos[lane] = S
            self._cur[lane, 0] = tok0
            self._steps[lane] = e
            self._temps[lane] = req.params.temperature
            self._keys[lane] = lane_key
            req.status = RequestStatus.DECODING
            if req.params.stop_token is not None \
                    and tok0 == req.params.stop_token:
                req.stopped = True
            req.emitted.append(tok0)
            if req.done:                 # budget 1 / instant stop token
                self.sched.finish(lane)
                self._reset_lane(lane)
                self._handles.pop(req.rid, None)
        if admitted:
            dt = self._clock() - t0      # feeds the deadline-shed estimate
            self._est_admit_ms = dt if self._est_admit_ms == 0.0 \
                else 0.5 * (self._est_admit_ms + dt)
        return admitted

    def _dispatch_segment(self, sampled: bool, kernel_on: bool) -> None:
        """Compile/fetch the segment graph for the given kernel choice and
        run it, updating the lane mirrors. The kernel flag is pinned in
        BOTH the compile key and the trace-time override, so the fallback
        graph is cached under — and only under — its own key.

        The graph is lowered and compiled BEFORE the pool is taken: an
        error there is a program error and propagates out of ``step()``
        with the pool and every request intact. Only a failure of the
        dispatch itself (after donation) raises ``_PoolLost``."""
        key = ("segment", self._pool_key, self.segment, sampled, kernel_on)
        with TraceAnnotation("serve.segment"):
            self._ensure_pool()
            lanes = (jnp.asarray(self._bt), jnp.asarray(self._pos),
                     jnp.asarray(self._cur), jnp.asarray(self._steps),
                     jnp.asarray(self._temps), jnp.asarray(self._keys))
            sfn = self.engine._get_fn(
                key,
                lambda: self.engine._build_batch_segment(self.segment,
                                                         sampled)
                .lower(self.engine.params, self._pool, *lanes).compile())
            pool = self._take_pool()
            try:
                toks, cur_d, pool = sfn(self.engine.params, pool, *lanes)
            except Exception as e:
                raise self._segment_lost(key, e) from e
        with TraceAnnotation("serve.segment_wait"):
            try:
                toks, cur = np.asarray(toks), np.array(cur_d)
            except Exception as e:
                raise self._segment_lost(key, e) from e
        self._pool = pool
        self._last_toks = toks
        self._cur = cur                 # a copy: the host mirror stays writable
        self._pos += self.segment
        self._steps += self.segment

    def _segment_lost(self, key, exc: Exception) -> _PoolLost:
        """The segment's executable may be poisoned (dead device buffers):
        evict it so recovery compiles fresh."""
        self.engine._fns.pop(key, None)
        return _PoolLost(exc)

    def _contain_pool_loss(self, exc: Exception) -> None:
        """A dispatch failed AFTER the pool was donated: the buffers are
        invalid (CachePool.take contract — ``self._pool`` is already None),
        so every active request's cache state is gone. Containment: fail
        them all terminally (partial tokens kept), flush the prefix index
        — its page ids point into the lost bytes, and the replacement pool
        is zero-initialized — and let the next admission allocate fresh.
        Pending requests are untouched; the session keeps serving."""
        for lane in list(self.sched.active):
            req = self.sched.fail(
                lane, reasons.format_reason(
                    reasons.POOL_LOST, f"{type(exc).__name__}: {exc}"))
            self._handles.pop(req.rid, None)
        for lane in self.sched.drain_freed_lanes():
            self._reset_lane(lane)
        if self.prefix is not None:
            self.prefix.flush(self.sched.alloc)

    def _contain_oom(self) -> None:
        """Simulated RESOURCE_EXHAUSTED at the decode-segment dispatch,
        polled host-side BEFORE ``_take_pool()`` — the pool never moves.
        Containment fails ONE victim: the newest active request (freeing
        its pages models the headroom the dispatch retry needs, and the
        oldest streams — the ones a client has waited longest on — keep
        their bit-identical decode)."""
        lane = self.sched.oom_victim()
        if lane is None:
            return
        req = self.sched.fail(lane, reasons.format_reason(
            reasons.OOM, "decode-segment"))
        self._handles.pop(req.rid, None)
        for freed in self.sched.drain_freed_lanes():
            self._reset_lane(freed)

    def _contain_shard_loss(self) -> None:
        """A mesh device dropped mid-segment. TP shards every head across
        the mesh axis, so EVERY active lane's next segment would need the
        lost shard: fail-fast drain them all with the typed ``shard-lost``
        reason rather than stream bytes computed from a partial mesh.
        Pending requests are untouched; the session keeps admitting (the
        simulated mesh still dispatches), but ``stats()["mesh"]`` stays
        degraded so operators see the event."""
        shard = len(self._lost_shards) % max(
            int(getattr(self.engine, "tp", 1)), 1)
        reason = reasons.format_reason(reasons.SHARD_LOST, f"shard{shard}")
        for lane in list(self.sched.active):
            req = self.sched.fail(lane, reason)
            self._handles.pop(req.rid, None)
        for lane in self.sched.drain_freed_lanes():
            self._reset_lane(lane)
        self._lost_shards.append(shard)

    def _decode_segment(self) -> bool:
        """One fused ``segment``-step scan over the full lane pool; lanes
        whose request finished or was cancelled compute into the garbage
        page until the boundary. Returns False when no lane is live.

        Fault handling, two tiers: an INJECTED ``kernel_dispatch`` fault
        is polled host-side before the pool moves and served through the
        XLA gather graph (``REPRO_PAGED_KERNEL=0`` path) for this segment
        — bitwise-identical tokens, no victim; a REAL dispatch exception
        surfaces after donation and is contained by ``_contain_pool_loss``
        (the pool is unrecoverable by then). A graph that fails to lower
        or compile is not contained: it raises out of ``step()``."""
        if not self.sched.active:
            if self.sched.pending:   # unreachable given check_fits at submit
                raise RuntimeError("scheduler deadlock: pending requests "
                                   "but nothing admissible")
            return False
        if self.faults is not None \
                and getattr(self.engine, "mesh", None) is not None \
                and self.faults.should_fire("shard_loss"):
            self._contain_shard_loss()
            return False
        if self.faults is not None \
                and self.faults.should_fire("device_oom"):
            # polled host-side BEFORE _take_pool(), like kernel_dispatch:
            # the pool never moves, so containment costs one victim
            self._contain_oom()
            if not self.sched.active:
                return False
        # the sampled/greedy split is per SEGMENT, from the lanes actually
        # live in it — all-greedy traffic never pays the per-step RNG work,
        # and both variants stay cached for a mixed session
        sampled = any(r.params.temperature > 0
                      for r in self.sched.active.values())
        try:
            if self.faults is not None \
                    and self.faults.should_fire("kernel_dispatch"):
                with paged_kernel_override(False):
                    self._dispatch_segment(sampled, False)
            else:
                self._dispatch_segment(sampled, paged_kernel_enabled())
        except _PoolLost as e:
            self._contain_pool_loss(e.__cause__)
            return False
        return True

    def _drain_finished(self):
        """Harvest the segment's tokens into each live request, apply
        stop-token early finish, and release completed lanes (freed pages
        are admissible in the next step's admit)."""
        finished = []
        for lane, req in list(self.sched.active.items()):
            take = min(self.segment,
                       req.params.max_tokens - len(req.emitted))
            new = [int(t) for t in self._last_toks[:take, lane]]
            stop = req.params.stop_token
            if stop is not None and stop in new:
                new = new[:new.index(stop) + 1]
                req.stopped = True
            req.emitted.extend(new)
            if req.done:
                self.sched.finish(lane)
                self._reset_lane(lane)
                # handles stay valid (they hold the Request directly); the
                # session just stops tracking finished work, so a long-lived
                # session doesn't accumulate every request it ever served
                self._handles.pop(req.rid, None)
                finished.append(req)
        return finished
