"""Fault-injection containment suite (``-m faultinject``).

The PR-6 acceptance contract, verified per fault class: every injected
fault (allocator failure, CoW-fork failure, kernel dispatch error, prefix
index corruption, deadline expiry, queue overflow) resolves to a TERMINAL
request status; ``PageAllocator.audit()`` / ``PrefixCache.audit()`` are
clean after drain (zero leaked pages — the session composes the holder
census itself); and every co-resident uninjected request's greedy tokens
are bit-identical to a fault-free run. Sessions here run with
``audit=True``, so the invariants are additionally re-checked after EVERY
step, not just at drain.

Faults are armed per call-index (``FaultInjector.arm(site, at=...)``), so
each test pins its fault to an exact admission round or decode segment —
the suite is deterministic, no chaos-monkey flakiness.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import io_callback

from repro.configs import get_smoke
from repro.models import lm_init
from repro.serve import (FaultInjector, RequestStatus, SamplingParams,
                         ServeEngine, ShedError)

pytestmark = pytest.mark.faultinject

RNG = np.random.default_rng(7)


@pytest.fixture(scope="module")
def engine():
    cfg = get_smoke("gemma2-2b")
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    return ServeEngine(cfg, params, max_len=32), cfg


def _prompts(cfg, lens):
    return [RNG.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
            for L in lens]


def _ref(eng, p, n):
    return np.asarray(eng.generate(jnp.asarray(p[None]), n)[0])


def _assert_drained_clean(sess):
    """Zero-leak oracle after drain: every lane free, every page back
    (or index-owned), census-exact refcounts."""
    assert sess.idle
    report = sess.audit()
    assert report["alloc"]["n_pages"] - 1 \
        == report["alloc"]["n_free"] + report.get("prefix", {}).get(
            "pages", 0) + len(
                [r for r in sess.prefix.records.values()
                 if r.page is not None] if sess.prefix is not None else [])


# ---------------------------------------------------------------------------
# allocator failure at admission
# ---------------------------------------------------------------------------
def test_alloc_fault_fails_only_the_victim(engine):
    eng, cfg = engine
    prompts = _prompts(cfg, [9, 11, 7])
    # polls count allocs with n>0: admissions are polls 0, 1, 2 in
    # submit order — arm poll 1 so the SECOND admission fails
    inj = FaultInjector({"page_alloc": [1]})
    with eng.session(lanes=2, page_size=8, segment=2, audit=True,
                     faults=inj, prefix_cache=False) as sess:
        hs = [sess.submit(p, SamplingParams(max_tokens=6)) for p in prompts]
        sess.run_until_idle()
        assert inj.fired == [("page_alloc", 1)]
        assert hs[1].status is RequestStatus.FAILED
        assert hs[1].error == "injected:page_alloc"
        assert hs[1].tokens_so_far() == []
        # co-resident requests: bit-identical to the sequential oracle
        for h, p in [(hs[0], prompts[0]), (hs[2], prompts[2])]:
            assert h.status is RequestStatus.DONE
            np.testing.assert_array_equal(h.tokens_so_far(),
                                          _ref(eng, p, 6))
        _assert_drained_clean(sess)


# ---------------------------------------------------------------------------
# CoW fork failure on an exact-hit admission
# ---------------------------------------------------------------------------
def test_fork_fault_contained_and_next_hit_serves(engine):
    eng, cfg = engine
    p = _prompts(cfg, [13])[0]           # 13 % 8 != 0: boundary page fork
    inj = FaultInjector({"fork_page": [0]})
    with eng.session(lanes=2, page_size=8, segment=2, audit=True,
                     faults=inj, prefix_cache=True) as sess:
        cold = sess.submit(p, SamplingParams(max_tokens=6))
        sess.run_until_idle()            # populates an exact record
        victim = sess.submit(p, SamplingParams(max_tokens=6))
        sess.run_until_idle()            # exact hit -> fork -> injected
        assert victim.status is RequestStatus.FAILED
        assert victim.error == "injected:fork_page"
        retry = sess.submit(p, SamplingParams(max_tokens=6))
        sess.run_until_idle()            # poll 1 unarmed: hit serves
        assert retry.status is RequestStatus.DONE
        np.testing.assert_array_equal(retry.tokens_so_far(),
                                      cold.tokens_so_far())
        assert inj.fired == [("fork_page", 0)]
        _assert_drained_clean(sess)


# ---------------------------------------------------------------------------
# kernel dispatch fault -> gather-path fallback, no victim at all
# ---------------------------------------------------------------------------
def test_kernel_dispatch_fault_falls_back_bit_identically(engine):
    eng, cfg = engine
    prompts = _prompts(cfg, [10, 12])
    inj = FaultInjector({"kernel_dispatch": [0, 1]})   # first two segments
    with eng.session(lanes=2, page_size=8, segment=2, audit=True,
                     faults=inj, prefix_cache=False) as sess:
        hs = [sess.submit(p, SamplingParams(max_tokens=8)) for p in prompts]
        sess.run_until_idle()
        assert [s for s, _ in inj.fired] == ["kernel_dispatch"] * 2
        for h, p in zip(hs, prompts):    # graceful degradation: NO victim
            assert h.status is RequestStatus.DONE
            np.testing.assert_array_equal(h.tokens_so_far(),
                                          _ref(eng, p, 8))
        _assert_drained_clean(sess)


# ---------------------------------------------------------------------------
# prefix-index corruption -> detection -> quarantine -> cold correctness
# ---------------------------------------------------------------------------
def test_index_corruption_quarantines_and_serves_cold(engine):
    eng, cfg = engine
    p = _prompts(cfg, [12])[0]
    inj = FaultInjector()
    with eng.session(lanes=2, page_size=8, segment=2, audit=False,
                     faults=inj, prefix_cache=True) as sess:
        first = sess.submit(p, SamplingParams(max_tokens=6))
        sess.run_until_idle()            # index now holds the prompt
        assert sess.prefix.owned_pages > 0
        # arm the NEXT prefix_index poll: the upcoming step corrupts a
        # node in place, and the admission lookup must detect it
        inj.arm("prefix_index", at=inj._count.get("prefix_index", 0))
        second = sess.submit(p, SamplingParams(max_tokens=6))
        sess.run_until_idle()
        assert sess.prefix.quarantined
        assert sess.prefix.stats["quarantines"] == 1
        assert sess.prefix.owned_pages == 0          # flushed, zero leaks
        # the victim of corruption is... nobody: cold admission is correct
        assert second.status is RequestStatus.DONE
        np.testing.assert_array_equal(second.tokens_so_far(),
                                      first.tokens_so_far())
        # bypass mode: later identical prompts still serve, still cold
        third = sess.submit(p, SamplingParams(max_tokens=6))
        sess.run_until_idle()
        np.testing.assert_array_equal(third.tokens_so_far(),
                                      first.tokens_so_far())
        _assert_drained_clean(sess)


def test_reclaim_sweep_contains_index_corruption(engine):
    """Corruption nobody has looked up yet must not crash the RECLAIM
    sweep (found by chaos seed 1016): ``prefix_index`` fires in the same
    step as an admission whose lookup MISSES the corrupted node (cold
    prompt, different first page) but whose reclaim sweep walks it to
    free pages. The sweep must detect the bad node (not KeyError out of
    the keyed eviction), quarantine, and admit cold against the flushed
    pool — request served, tokens oracle-identical, zero leaks."""
    eng, cfg = engine
    a, b = _prompts(cfg, [12, 12])
    b[0] = (a[0] + 1) % cfg.vocab_size   # different first page: no walk
    inj = FaultInjector()
    with eng.session(lanes=1, page_size=8, n_pages=4, segment=2,
                     audit=True, faults=inj, prefix_cache=True) as sess:
        first = sess.submit(a, SamplingParams(max_tokens=6))
        sess.run_until_idle()            # index now holds a's prompt page
        assert sess.prefix.owned_pages > 0
        # b needs 3 pages; the index holds the pool's slack, so admission
        # MUST reclaim (the path that crashed pre-fix)
        assert sess.sched.alloc.n_free < 3
        inj.arm("prefix_index", at=inj._count.get("prefix_index", 0))
        second = sess.submit(b, SamplingParams(max_tokens=6))
        sess.run_until_idle()
        assert any(site == "prefix_index" for site, _ in inj.fired)
        assert sess.prefix.quarantined
        assert sess.prefix.stats["quarantines"] == 1
        assert sess.prefix.owned_pages == 0          # flushed, zero leaks
        assert second.status is RequestStatus.DONE
        np.testing.assert_array_equal(second.tokens_so_far(),
                                      _ref(eng, b, 6))
        assert first.status is RequestStatus.DONE
        _assert_drained_clean(sess)


# ---------------------------------------------------------------------------
# deadlines (fake clock drives time by hand)
# ---------------------------------------------------------------------------
def test_deadline_expires_mid_flight_and_frees_resources(engine):
    eng, cfg = engine
    now = [0.0]
    with eng.session(lanes=2, page_size=8, segment=2, audit=True,
                     prefix_cache=False, clock=lambda: now[0]) as sess:
        doomed = sess.submit(_prompts(cfg, [9])[0],
                             SamplingParams(max_tokens=12, deadline_ms=100.0))
        fine = sess.submit(_prompts(cfg, [9])[0],
                           SamplingParams(max_tokens=6))
        sess.step()                      # admit both (first tokens emitted)
        sess.step()                      # one decode segment
        assert doomed.status is RequestStatus.DECODING
        partial = doomed.tokens_ready
        assert partial >= 1
        now[0] = 101.0                   # wall time passes the deadline
        sess.run_until_idle()
        assert doomed.status is RequestStatus.EXPIRED
        assert doomed.error == "deadline"
        assert doomed.tokens_ready >= partial        # partial tokens kept
        assert len(doomed.tokens_so_far()) < 12
        assert fine.status is RequestStatus.DONE     # co-resident finishes
        assert len(fine.tokens_so_far()) == 6
        _assert_drained_clean(sess)


def test_unmeetable_deadline_sheds_without_compute(engine):
    eng, cfg = engine
    now = [0.0]
    with eng.session(lanes=1, page_size=8, segment=2, audit=True,
                     prefix_cache=False, clock=lambda: now[0]) as sess:
        blocker = sess.submit(_prompts(cfg, [9])[0],
                              SamplingParams(max_tokens=6))
        late = sess.submit(_prompts(cfg, [9])[0],
                           SamplingParams(max_tokens=6, deadline_ms=50.0))
        sess.step()                      # blocker takes the only lane
        now[0] = 60.0                    # late's deadline passes in queue
        sess.run_until_idle()
        assert late.status is RequestStatus.SHED
        assert late.error == "deadline"
        assert late.tokens_so_far() == []            # zero compute spent
        assert blocker.status is RequestStatus.DONE
        _assert_drained_clean(sess)


# ---------------------------------------------------------------------------
# queue overflow through the session API
# ---------------------------------------------------------------------------
def test_queue_overflow_sheds_in_admission_time(engine):
    eng, cfg = engine
    with eng.session(lanes=1, page_size=8, segment=2, audit=True,
                     prefix_cache=False, max_pending=1) as sess:
        ok = sess.submit(_prompts(cfg, [9])[0], SamplingParams(max_tokens=4))
        sess.step()                      # ok admitted; the queue is empty
        queued = sess.submit(_prompts(cfg, [9])[0],
                             SamplingParams(max_tokens=4))
        with pytest.raises(ShedError) as ei:
            sess.submit(_prompts(cfg, [9])[0], SamplingParams(max_tokens=4))
        assert ei.value.reason == "queue-full"
        sess.run_until_idle()            # bounded queue still drains fully
        assert ok.status is RequestStatus.DONE
        assert queued.status is RequestStatus.DONE
        _assert_drained_clean(sess)


# ---------------------------------------------------------------------------
# REAL dispatch failure after donation: pool-loss containment
# ---------------------------------------------------------------------------
def test_pool_loss_contains_all_actives_then_recovers(engine, monkeypatch):
    eng, cfg = engine
    prompts = _prompts(cfg, [9, 11])
    build = eng._build_batch_segment

    def device_lost():
        raise RuntimeError("device lost")

    def broken_builder(segment, sampled):
        # compiles fine; the executable fails while it runs, i.e. after
        # the pool buffers were donated to it
        good = build(segment, sampled)

        def fn(params, pool, *lanes):
            io_callback(device_lost, None)
            return good(params, pool, *lanes)
        return jax.jit(fn, donate_argnums=(1,))

    # segment=3 gives this test its own compile-cache keys, so the broken
    # builder is what the first decode resolves
    with eng.session(lanes=2, page_size=8, segment=3, audit=True,
                     prefix_cache=False) as sess:
        hs = [sess.submit(p, SamplingParams(max_tokens=6)) for p in prompts]
        sess.step()                      # admissions only
        monkeypatch.setattr(eng, "_build_batch_segment", broken_builder)
        sess.step()                      # decode -> dispatch fails post-take
        for h in hs:
            assert h.status is RequestStatus.FAILED
            assert h.error.startswith("pool-lost:")
            assert len(h.tokens_so_far()) == 1       # prefill token kept
        _assert_drained_clean(sess)
        monkeypatch.undo()
        # the session keeps serving: fresh pool, correct tokens
        again = sess.submit(prompts[0], SamplingParams(max_tokens=6))
        sess.run_until_idle()
        assert again.status is RequestStatus.DONE
        np.testing.assert_array_equal(again.tokens_so_far(),
                                      _ref(eng, prompts[0], 6))
        _assert_drained_clean(sess)


def test_segment_lowering_error_raises_out_of_step(engine, monkeypatch):
    """A segment graph that cannot be lowered (a kernel the compiler
    refuses, say) is a program error: it propagates out of ``step()``
    before the pool is donated, and no request is turned into a FAILED
    pool-loss victim."""
    eng, cfg = engine
    prompts = _prompts(cfg, [9, 11])

    def unlowerable(segment, sampled):
        def fn(params, pool, *lanes):
            raise NotImplementedError("kernel refused by the compiler")
        return jax.jit(fn, donate_argnums=(1,))

    # segment=5: a compile-cache key of its own, so the broken builder is
    # what the first decode resolves
    with eng.session(lanes=2, page_size=8, segment=5, audit=True,
                     prefix_cache=False) as sess:
        hs = [sess.submit(p, SamplingParams(max_tokens=8)) for p in prompts]
        sess.step()                      # admissions only
        monkeypatch.setattr(eng, "_build_batch_segment", unlowerable)
        with pytest.raises(NotImplementedError, match="refused"):
            sess.step()
        assert sess.sched.stats["failed"] == 0
        for h in hs:
            assert h.status is RequestStatus.DECODING
            assert h.error is None
        assert sess._pool is not None    # never donated
        monkeypatch.undo()
        sess.run_until_idle()            # the same requests finish intact
        for h, p in zip(hs, prompts):
            assert h.status is RequestStatus.DONE
            np.testing.assert_array_equal(h.tokens_so_far(), _ref(eng, p, 8))
        _assert_drained_clean(sess)


# ---------------------------------------------------------------------------
# device OOM at decode dispatch: newest victim, co-residents bit-identical
# ---------------------------------------------------------------------------
def test_device_oom_fails_newest_keeps_coresidents_identical(engine):
    eng, cfg = engine
    prompts = _prompts(cfg, [9, 11])
    inj = FaultInjector({"device_oom": [0]})
    with eng.session(lanes=2, page_size=8, segment=2, audit=True,
                     faults=inj, prefix_cache=False) as sess:
        first = sess.submit(prompts[0], SamplingParams(max_tokens=6))
        newest = sess.submit(prompts[1], SamplingParams(max_tokens=6))
        sess.run_until_idle()
        assert inj.fired == [("device_oom", 0)]
        # victim policy: the NEWEST active request (its freed pages model
        # the headroom a real RESOURCE_EXHAUSTED retry needs)
        assert newest.status is RequestStatus.FAILED
        assert newest.error == "oom:decode-segment"
        assert len(newest.tokens_so_far()) == 1      # prefill token kept
        # co-resident stream: bit-identical to the sequential oracle
        assert first.status is RequestStatus.DONE
        np.testing.assert_array_equal(first.tokens_so_far(),
                                      _ref(eng, prompts[0], 6))
        _assert_drained_clean(sess)
        # the session keeps serving after containment
        again = sess.submit(prompts[1], SamplingParams(max_tokens=6))
        sess.run_until_idle()
        assert again.status is RequestStatus.DONE
        np.testing.assert_array_equal(again.tokens_so_far(),
                                      _ref(eng, prompts[1], 6))
        _assert_drained_clean(sess)


def test_device_oom_sole_request_terminal_and_clean(engine):
    eng, cfg = engine
    (p,) = _prompts(cfg, [9])
    inj = FaultInjector({"device_oom": [0]})
    with eng.session(lanes=2, page_size=8, segment=2, audit=True,
                     faults=inj, prefix_cache=False) as sess:
        h = sess.submit(p, SamplingParams(max_tokens=6))
        sess.run_until_idle()
        assert h.status is RequestStatus.FAILED
        assert h.error == "oom:decode-segment"
        assert sess.idle
        _assert_drained_clean(sess)
    # never polled on the mesh-only site single-device
    assert all(site != "shard_loss" for site, _ in inj.fired)


# ---------------------------------------------------------------------------
# strict REPRO_FAULTS parsing: a typo'd chaos plan must not silently no-op
# ---------------------------------------------------------------------------
def test_from_env_rejects_unknown_sites_and_bad_indices():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultInjector.from_env("page_alloc@1,not_a_site@0")
    with pytest.raises(ValueError, match="empty entry"):
        FaultInjector.from_env("page_alloc@1,,kernel_dispatch@0")
    with pytest.raises(ValueError, match="bad poll index"):
        FaultInjector.from_env("page_alloc@x")
    with pytest.raises(ValueError, match="negative"):
        FaultInjector.from_env("page_alloc@-3")
    assert FaultInjector.from_env("") is None
    assert FaultInjector.from_env("   ") is None
    # the documented shorthand still parses: bare site means poll 0,
    # whitespace around entries is tolerated
    inj = FaultInjector.from_env(" kernel_dispatch , page_alloc@2 ")
    assert inj.should_fire("kernel_dispatch")
    assert not inj.should_fire("page_alloc")
    assert not inj.should_fire("page_alloc")
    assert inj.should_fire("page_alloc")


def test_constructor_and_arm_reject_unknown_sites():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultInjector({"not_a_site": [0]})
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultInjector().arm("also_bad", at=1)


# ---------------------------------------------------------------------------
# fault-free hardened traffic: audits stay clean through churn
# ---------------------------------------------------------------------------
def test_audit_clean_under_churn(engine):
    eng, cfg = engine
    prompts = _prompts(cfg, [9, 11, 7, 13, 10])
    with eng.session(lanes=2, page_size=8, segment=2, audit=True,
                     prefix_cache=True) as sess:
        hs = [sess.submit(p, SamplingParams(max_tokens=5))
              for p in prompts[:3]]
        sess.step()
        sess.step()
        victim = next(h for h in hs if h.status is RequestStatus.DECODING)
        victim.cancel()                  # mid-decode cancel under audit
        hs += [sess.submit(p, SamplingParams(max_tokens=5))
               for p in prompts[3:]]
        sess.run_until_idle()            # every step audits internally
        for h in hs:
            if h is not victim:
                assert h.status is RequestStatus.DONE
        _assert_drained_clean(sess)
