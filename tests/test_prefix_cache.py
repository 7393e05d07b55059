"""Prefix-cache subsystem validation.

Six layers, mirroring the PR contract:
  1. ACCEPTANCE — cache-hit generation is BIT-IDENTICAL (greedy) to a
     cold-cache run of the same prompt across dense, packed, kv-quant,
     ssm and hybrid configs, with zero prefill compiles on the hit path;
  2. partial hits — tail-only prefill (position-offset attention over
     gathered prefix pages + SSM boundary-state resumption) matches the
     cold oracle bit-for-bit on non-quant configs; under kv_cache_quant
     the tail attends over the DEQUANTIZED prefix rows (the same bytes
     decode reads), so the pinned contract is determinism + validity,
     not bit-equality with the pre-quant cold prefill;
  3. refcount/pressure edges — concurrent sharing, cancel of queued and
     active requests over pinned prefixes, LRU reclaim under page
     pressure, zero-free-pages waiting (no deadlock), page conservation;
  4. host-only radix/allocator units — split, dedup-on-insert, LRU
     eviction order, refcount-never-negative (hypothesis-based);
  5. satellites — emission-before-decode schedule (TTFT = prefill, the
     tightened pages_for bound), CachePool donation-safety + limit
     plumbing;
  6. SSM snapshots — kept every ``snapshot_stride`` pages and at each
     stored prompt's last full page, hits rounded down to one (the
     tokens given up counted), the state held bounded.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_smoke
from repro.models import cache_init, lm_init
from repro.serve import (CachePool, PageAllocator, PrefixCache, Request,
                         RequestStatus, SamplingParams, Scheduler,
                         ServeEngine, pages_for)
from repro.serve.session import _lane_state_bytes

RNG = np.random.default_rng(0)


def _prompt(cfg, L, rng=None):
    return (rng or RNG).integers(0, cfg.vocab_size, (L,)).astype(np.int32)


def _engine(arch="gemma2-2b", packed=False, quant=False, max_len=32):
    cfg = get_smoke(arch)
    if quant:
        cfg = cfg.scaled(kv_cache_quant=True)
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    return ServeEngine(cfg, params, max_len=max_len, packed=packed,
                       prefix_cache=True), cfg


def _ref(engine, p, n):
    return np.asarray(engine.generate(jnp.asarray(p[None]), n)[0])


def _assert_conserved(sess):
    """Every page is exactly one of: garbage, free, index-owned, or a live
    request's private page — and free pages carry refcount zero."""
    alloc = sess.sched.alloc
    assert alloc.refs[0] == 1
    for p in alloc.free_pages:
        assert alloc.refs[p] == 0
    owned = sess.prefix.owned_pages if sess.prefix else 0
    priv = sum(len(r.private_pages) for r in sess.sched.active.values())
    assert owned + priv + alloc.n_free == alloc.n_pages - 1


# ---------------------------------------------------------------------------
# 1. acceptance: cache-hit == cold-cache, bit-identical, zero prefill
# ---------------------------------------------------------------------------
def _assert_exact_hit_bit_identical(engine, cfg, page, S, n):
    p = _prompt(cfg, S)
    ref = _ref(engine, p, n)
    with engine.session(lanes=2, page_size=page, segment=2) as sess:
        cold = np.asarray(sess.submit(p, SamplingParams(max_tokens=n))
                          .result())
        pf_before = [k for k in engine._fns if k[0] == "pfx_prefill"]
        hit = np.asarray(sess.submit(p, SamplingParams(max_tokens=n))
                         .result())
        pf_after = [k for k in engine._fns if k[0] == "pfx_prefill"]
        assert sess.prefix.stats["exact_hits"] == 1
        _assert_conserved(sess)
    np.testing.assert_array_equal(cold, ref)      # cold path == oracle
    np.testing.assert_array_equal(hit, ref)       # THE acceptance criterion
    # a hit re-reads stored bytes — it must not compile (or run) a prefill
    assert pf_before == pf_after
    assert any(k[0] == "hit_admit" for k in engine._fns)


@pytest.mark.parametrize("packed", [False, True])
def test_exact_hit_bit_identical_dense(packed):
    engine, cfg = _engine(packed=packed)
    _assert_exact_hit_bit_identical(engine, cfg, page=4, S=11, n=6)


def test_exact_hit_bit_identical_kv_quant():
    engine, cfg = _engine(quant=True)
    _assert_exact_hit_bit_identical(engine, cfg, page=4, S=11, n=6)


@pytest.mark.parametrize("arch", ["falcon-mamba-7b", "jamba-1.5-large-398b"])
def test_exact_hit_bit_identical_ssm_hybrid(arch):
    """The SSM end state stored on the exact record must restore the lane
    recurrence bit-exactly (and for hybrid, compose with paged attention
    + MoE blocks)."""
    engine, cfg = _engine(arch)
    _assert_exact_hit_bit_identical(engine, cfg, page=8, S=13, n=5)


def test_exact_hit_page_aligned_prompt_skips_cow():
    """A page-aligned prompt leaves no partial boundary page: the exact
    hit needs no copy-on-write fork and is still bit-identical."""
    engine, cfg = _engine()
    p = _prompt(cfg, 8)
    ref = _ref(engine, p, 5)
    with engine.session(lanes=2, page_size=4) as sess:
        sess.submit(p, SamplingParams(max_tokens=5)).result()
        hit = np.asarray(sess.submit(p, SamplingParams(max_tokens=5))
                         .result())
        assert sess.prefix.stats["cow_forks"] == 0
        _assert_conserved(sess)
    np.testing.assert_array_equal(hit, ref)


# ---------------------------------------------------------------------------
# 2. partial hits: tail-only prefill over the shared page-aligned prefix
# ---------------------------------------------------------------------------
def _partial_pair(cfg, S, rng):
    """Two prompts sharing all full pages, diverging in the last rows."""
    p1 = _prompt(cfg, S, rng)
    p2 = p1.copy()
    p2[-2:] = (p2[-2:] + 1) % cfg.vocab_size
    return p1, p2


@pytest.mark.parametrize("arch,page,S,n", [
    ("gemma2-2b", 4, 11, 6),
    ("falcon-mamba-7b", 8, 13, 5),
    ("jamba-1.5-large-398b", 8, 13, 5),
])
def test_partial_hit_matches_cold_oracle(arch, page, S, n):
    """Tail prefill (offset positions + prefix K/V gather + SSM boundary
    state) serves the same tokens as a cold run of the full prompt —
    bit-for-bit on non-quant configs, where the stored prefix rows are the
    exact bf16 bytes the cold prefill produced."""
    engine, cfg = _engine(arch)
    rng = np.random.default_rng(7)
    p1, p2 = _partial_pair(cfg, S, rng)
    ref2 = _ref(engine, p2, n)
    with engine.session(lanes=2, page_size=page, segment=2) as sess:
        sess.submit(p1, SamplingParams(max_tokens=n)).result()
        out2 = np.asarray(sess.submit(p2, SamplingParams(max_tokens=n))
                          .result())
        assert sess.prefix.stats["partial_hits"] == 1
        assert sess.prefix.stats["hit_tokens"] >= page
        _assert_conserved(sess)
    np.testing.assert_array_equal(out2, ref2)


def test_partial_hit_kv_quant_deterministic_contract():
    """Under kv_cache_quant a partial-hit tail attends over DEQUANTIZED
    prefix rows — the same bytes decode reads — so its stream follows the
    serve-over-cache semantics rather than the pre-quant cold prefill.
    The pinned contract: the hit stream is deterministic (same cache
    state -> same tokens), in-vocab, and the shared-prefix lookup really
    happened."""
    outs = []
    for _ in range(2):
        engine, cfg = _engine(quant=True)
        rng = np.random.default_rng(9)
        p1, p2 = _partial_pair(cfg, 11, rng)
        with engine.session(lanes=2, page_size=4, segment=2) as sess:
            sess.submit(p1, SamplingParams(max_tokens=6)).result()
            outs.append(np.asarray(
                sess.submit(p2, SamplingParams(max_tokens=6)).result()))
            assert sess.prefix.stats["partial_hits"] == 1
            _assert_conserved(sess)
    np.testing.assert_array_equal(outs[0], outs[1])
    assert (outs[0] >= 0).all() and (outs[0] < cfg.vocab_size).all()


# ---------------------------------------------------------------------------
# 3. refcounts and pressure through live sessions
# ---------------------------------------------------------------------------
def test_concurrent_shared_prefix_and_cancel_keeps_cotenant_exact():
    """Two requests decode simultaneously off the SAME cached prefix
    pages; cancelling one mid-decode must not disturb the other or leak
    refs (the shared pages keep the survivor's + the index's refs)."""
    engine, cfg = _engine(max_len=64)
    p = _prompt(cfg, 9)
    ref = _ref(engine, p, 8)
    with engine.session(lanes=3, page_size=4, segment=2) as sess:
        sess.submit(p, SamplingParams(max_tokens=8)).result()   # seeds cache
        a = sess.submit(p, SamplingParams(max_tokens=8))
        b = sess.submit(p, SamplingParams(max_tokens=8))
        assert sess.step()                        # admission round (hits)
        assert sess.prefix.stats["exact_hits"] == 2
        shared = set(sess.sched.active[a._req.lane].shared_pages)
        assert shared and shared == set(sess.sched.active[b._req.lane]
                                        .shared_pages)
        for pg in shared:                         # index + two live users
            assert sess.sched.alloc.refs[pg] == 3
        assert sess.step() and a.cancel()
        for pg in shared:
            assert sess.sched.alloc.refs[pg] == 2
        out_b = np.asarray(b.result())
        _assert_conserved(sess)
    np.testing.assert_array_equal(out_b, ref)
    got_a = np.asarray(a.tokens_so_far(), np.int32)
    np.testing.assert_array_equal(got_a, ref[:len(got_a)])


def test_admission_reclaims_lru_under_page_pressure():
    """When the free list cannot cover a request's unshared tail, the LRU
    sweep evicts unpinned index entries until it fits — and the admitted
    request still serves oracle-identical tokens."""
    engine, cfg = _engine()
    pa, pb = _prompt(cfg, 10), _prompt(cfg, 12)
    ref_b = _ref(engine, pb, 6)
    with engine.session(lanes=2, page_size=4, n_pages=7) as sess:
        sess.submit(pa, SamplingParams(max_tokens=4)).result()
        assert sess.prefix.owned_pages > 0        # index holds pa's pages
        out_b = np.asarray(sess.submit(pb, SamplingParams(max_tokens=6))
                           .result())             # needs 5 of 6 pages
        assert sess.prefix.stats["evicted_pages"] >= 1
        _assert_conserved(sess)
    np.testing.assert_array_equal(out_b, ref_b)


def test_zero_free_pages_with_live_shared_pages_waits_not_deadlocks():
    """An exact-hit request holds every free page; a queued request whose
    tail cannot be covered (the remaining pages are pinned by the live
    hit) must WAIT — never crash, never reclaim pinned pages — and admit
    as soon as the hit finishes."""
    engine, cfg = _engine()
    pa, pb = _prompt(cfg, 8), _prompt(cfg, 4)
    ref_b = _ref(engine, pb, 4)
    with engine.session(lanes=2, page_size=4, n_pages=5, segment=2) as sess:
        sess.submit(pa, SamplingParams(max_tokens=9)).result()  # 4 pages
        a = sess.submit(pa, SamplingParams(max_tokens=9))       # exact hit
        assert sess.step()
        assert a.status == RequestStatus.DECODING
        assert sess.sched.alloc.n_free == 0
        b = sess.submit(pb, SamplingParams(max_tokens=4))       # needs 2
        assert sess.step()
        assert b.status == RequestStatus.QUEUED   # waiting on pinned pages
        out_b = np.asarray(b.result())            # drives until idle
        assert a.status == RequestStatus.DONE
        _assert_conserved(sess)
    np.testing.assert_array_equal(out_b, ref_b)


def test_cancel_queued_request_over_pinned_prefix():
    """Cancelling a QUEUED request whose looked-up prefix is pinned by a
    live co-tenant must not touch any refcount (queued requests hold
    nothing); the live request and a later identical submit are unharmed."""
    engine, cfg = _engine()
    p = _prompt(cfg, 8)
    ref = _ref(engine, p, 8)
    with engine.session(lanes=1, page_size=4, n_pages=5, segment=2) as sess:
        sess.submit(p, SamplingParams(max_tokens=8)).result()
        a = sess.submit(p, SamplingParams(max_tokens=8))        # takes lane
        assert sess.step()
        b = sess.submit(p, SamplingParams(max_tokens=8))        # queued
        assert sess.step() and b.status == RequestStatus.QUEUED
        refs_before = list(sess.sched.alloc.refs)
        assert b.cancel()
        assert sess.sched.alloc.refs == refs_before
        out_a = np.asarray(a.result())
        c = sess.submit(p, SamplingParams(max_tokens=8))
        out_c = np.asarray(c.result())
        _assert_conserved(sess)
    np.testing.assert_array_equal(out_a, ref)
    np.testing.assert_array_equal(out_c, ref)


# ---------------------------------------------------------------------------
# 4. host-only radix / allocator units (no device work)
# ---------------------------------------------------------------------------
def _host_sched(lanes=2, n_pages=12, page=2):
    cache = PrefixCache(page)
    return Scheduler(lanes, n_pages, page, prefix_cache=cache), cache


def _finish_with_extras(sched, req):
    """Stand in for the session: attach the device payload a prefill would
    have captured (host test — opaque objects suffice) and finish."""
    req.cache_extras = {"tokens": np.asarray(req.effective_prompt, np.int32),
                        "offset": req.hit.hit_len if req.hit else 0,
                        "logits": object(), "end_ssm": {}, "snaps": {}}
    sched.finish(req.lane)


def test_radix_insert_dedup_frees_duplicate_pages():
    """Two requests with the same prompt admitted cold TOGETHER: the
    second finish walks into the first's nodes and its duplicate pages
    free instead of leaking."""
    sched, cache = _host_sched()
    a = Request(0, np.arange(6, dtype=np.int32), n_tokens=3)
    b = Request(1, np.arange(6, dtype=np.int32), n_tokens=3)
    sched.submit(a), sched.submit(b)
    assert len(sched.admit()) == 2                # both cold (no hit yet)
    free0 = sched.alloc.n_free
    n_a, n_b = len(a.pages), len(b.pages)
    _finish_with_extras(sched, a)
    _finish_with_extras(sched, b)
    # a's 3 full pages + boundary-less record stay cached; ALL of b's
    # pages freed as duplicates (its prompt pages dedup, decode pages free)
    assert cache.owned_pages == 3
    assert sched.alloc.n_free == free0 + n_a + n_b - 3
    assert cache.stats["inserted_pages"] == 3


def test_radix_split_preserves_pins_and_lru_evicts_leaf_first():
    sched, cache = _host_sched(n_pages=20)
    a = Request(0, np.arange(8, dtype=np.int32), n_tokens=3)
    sched.submit(a)
    sched.admit()
    _finish_with_extras(sched, a)                 # one 4-page node chain
    # a shorter shared prompt forces a mid-node SPLIT at page 2
    b = Request(1, np.concatenate([np.arange(4), [9, 9]]).astype(np.int32),
                n_tokens=3)
    sched.submit(b)
    sched.admit()
    assert b.hit is not None and b.hit.hit_len == 4 and not b.hit.exact
    # pins: b's path (head node) 1 + a's record path pin on head AND tail
    head = b.hit.node
    assert head.ref == 2 and len(head.pages) == 2
    (tail,) = head.children.values()
    assert tail.ref == 1 and len(tail.pages) == 2
    _finish_with_extras(sched, b)
    # evict: only unpinned leaves are reclaimable, records go LRU-first
    owned0 = cache.owned_pages
    assert cache.reclaim(sched.alloc, owned0)     # drain the whole index
    assert cache.owned_pages == 0 and not cache.records
    assert sched.alloc.n_free == sched.alloc.n_pages - 1


def test_reclaim_refuses_pinned_paths():
    sched, cache = _host_sched(n_pages=8)
    a = Request(0, np.arange(6, dtype=np.int32), n_tokens=3)
    sched.submit(a)
    sched.admit()
    _finish_with_extras(sched, a)
    b = Request(1, np.arange(6, dtype=np.int32), n_tokens=3)
    sched.submit(b)
    sched.admit()                                 # exact hit, pins path
    assert b.hit is not None and b.hit.exact
    assert not cache.reclaim(sched.alloc, 100)    # pinned: can't drain
    assert cache.owned_pages > 0
    sched.cancel(b)                               # unpin
    assert cache.reclaim(sched.alloc, cache.owned_pages)
    assert cache.owned_pages == 0


def test_segment_overrun_never_corrupts_donated_pages():
    """A request whose page count fills EVERY block-table column finishes
    early in a segment; the lane's overrun steps must spill to the
    garbage page, not wrap onto its last real page (clipped column) —
    donation makes those prompt bytes load-bearing, so a wrap would make
    the later exact hit diverge from the cold run."""
    engine, cfg = _engine()
    for seed in range(20):                     # need t0 != t1 so the stop
        p = _prompt(cfg, 29, np.random.default_rng(seed))   # fires MID-seg
        ref = _ref(engine, p, 3)               # pages_for(29,3,8)=4 == cols
        if ref[0] != ref[1]:
            break
    # n_pages leaves free headroom: at pool minimum the exact hit would
    # (correctly) fall back to cold instead of exercising the CoW fork
    with engine.session(lanes=1, page_size=8, n_pages=9, segment=4) as sess:
        a = sess.submit(p, SamplingParams(max_tokens=3,
                                          stop_token=int(ref[1])))
        assert sess.step()                     # admission: pages committed
        bpage = sess.sched.active[0].pages[3]  # boundary page (rows 24..31)
        before = np.asarray(sess._pool["b0"]["k"])[:, bpage, :5]
        sess.run_until_idle()                  # overruns to pos 32 mid-seg
        assert a.status == RequestStatus.DONE and a.tokens_ready == 2
        # the overrun write at pos 32 must land on the garbage page, not
        # wrap onto in-page offset 0 (= prompt row 24) of the real page
        after = np.asarray(sess._pool["b0"]["k"])[:, bpage, :5]
        np.testing.assert_array_equal(after, before)
        hit = np.asarray(sess.submit(p, SamplingParams(max_tokens=3))
                         .result())
        assert sess.prefix.stats["exact_hits"] == 1
    np.testing.assert_array_equal(hit, ref)    # and the hit serves cold's


def test_kv_quant_partial_hit_never_seeds_exact_record():
    """Under kv_cache_quant a partial-hit tail computes over dequantized
    prefix rows — its end state is serve-over-cache, not cold-faithful —
    so finishing must NOT create an exact record: resubmitting the same
    prompt partial-hits again (deterministically) instead of replaying a
    record that would violate the exact-hit bit-identity contract."""
    engine, cfg = _engine(quant=True)
    rng = np.random.default_rng(9)
    p1, p2 = _partial_pair(cfg, 11, rng)
    with engine.session(lanes=2, page_size=4, segment=2) as sess:
        sess.submit(p1, SamplingParams(max_tokens=6)).result()   # cold
        first = np.asarray(sess.submit(p2, SamplingParams(max_tokens=6))
                           .result())          # partial hit off p1
        again = np.asarray(sess.submit(p2, SamplingParams(max_tokens=6))
                           .result())          # must partial-hit AGAIN
        assert sess.prefix.stats["partial_hits"] == 2
        assert sess.prefix.stats["exact_hits"] == 0
        _assert_conserved(sess)
    np.testing.assert_array_equal(first, again)


def test_record_map_is_count_bounded_lru():
    """Distinct-prompt traffic must not grow the record map (device
    logits + SSM states) without bound: the oldest record LRU-evicts at
    the cap, its boundary page returning to the pool."""
    sched, cache = _host_sched(lanes=2, n_pages=40, page=2)
    cache.max_records = 2
    for rid in range(4):
        req = Request(rid, np.asarray([rid] * 5, np.int32), n_tokens=2)
        sched.submit(req)
        sched.admit()
        _finish_with_extras(sched, req)
    assert len(cache.records) == 2
    # the two NEWEST survive; evicting released the old boundary pages
    for rid in (2, 3):
        assert np.asarray([rid] * 5, np.int32).tobytes() in cache.records
    assert cache.stats["evicted_pages"] >= 2
    priv = sum(len(r.private_pages) for r in sched.active.values())
    assert cache.owned_pages + priv + sched.alloc.n_free \
        == sched.alloc.n_pages - 1


def test_exact_hit_at_minimum_pool_falls_back_to_cold():
    """Minimum-capacity pool where the exact hit's own CoW fork source is
    the only reclaimable page: holding the hit would livelock (the fork
    source can't be both preserved and reclaimed), so admission must drop
    the hit and admit COLD after reclaiming the index — never crash on an
    incref of a freed page, never wedge an otherwise-idle pool."""
    engine, cfg = _engine(max_len=16)
    p = _prompt(cfg, 6)
    ref = _ref(engine, p, 7)
    with engine.session(lanes=2, page_size=4, n_pages=4) as sess:
        cold = np.asarray(sess.submit(p, SamplingParams(max_tokens=7))
                          .result())                  # 3 pages = whole pool
        assert sess.prefix.owned_pages == 2           # 1 node + boundary
        again = np.asarray(sess.submit(p, SamplingParams(max_tokens=7))
                           .result())                 # exact hit can't fit
        assert sess.prefix.stats["misses"] == 2       # fell back to cold
        _assert_conserved(sess)
    np.testing.assert_array_equal(cold, ref)
    np.testing.assert_array_equal(again, ref)         # still oracle-exact


def test_page_allocator_refcount_discipline():
    alloc = PageAllocator(6)
    pages = alloc.alloc(3)
    assert alloc.n_free == 2 and all(alloc.refs[p] == 1 for p in pages)
    alloc.incref(pages[0])
    alloc.decref(pages[0])
    assert alloc.refs[pages[0]] == 1              # still owned
    alloc.decref(pages[0])
    assert alloc.refs[pages[0]] == 0 and pages[0] in alloc.free_pages
    with pytest.raises(ValueError, match="decref"):
        alloc.decref(pages[0])                    # never-negative, loudly
    with pytest.raises(ValueError, match="incref"):
        alloc.incref(0)                           # garbage page is pinned
    with pytest.raises(ValueError, match="alloc"):
        alloc.alloc(alloc.n_free + 1)


def test_pages_for_emission_schedule_bound():
    """First token rides the prefill: a request writes prompt+n-1 rows, so
    a budget-1 request needs only its prompt pages and S+n == page*k + 1
    no longer rounds up an extra page."""
    assert pages_for(8, 1, 4) == 2
    assert pages_for(5, 4, 4) == 2                # 8 rows, not 9
    assert pages_for(8, 9, 4) == 4


@given(st.lists(st.tuples(st.integers(2, 8), st.integers(1, 6)),
                min_size=1, max_size=12),
       st.integers(0, 2**32 - 1))
@settings(max_examples=25, deadline=None)
def test_refcounts_never_negative_under_random_traffic(sizes, seed):
    """Random submit/admit/cancel/finish traffic over a host-only
    scheduler+index: refcounts stay non-negative (the allocator raises
    otherwise) and page conservation holds at every quiescent point."""
    import random

    rnd = random.Random(seed)
    sched, cache = _host_sched(lanes=3, n_pages=24, page=2)
    rid = 0
    live = []
    for S, n in sizes:
        toks = np.asarray([rnd.randrange(4) for _ in range(S)], np.int32)
        req = Request(rid, toks, n_tokens=n)
        rid += 1
        sched.submit(req)
        live.append(req)
        sched.admit()
        for r in list(live):
            if r.lane >= 0 and rnd.random() < 0.4:
                if rnd.random() < 0.5:
                    _finish_with_extras(sched, r)
                else:
                    sched.cancel(r)
                live.remove(r)
    for r in live:
        if r.lane >= 0:
            _finish_with_extras(sched, r)
        else:
            sched.cancel(r)
        sched.admit()
    priv = sum(len(r.private_pages) for r in sched.active.values())
    assert cache.owned_pages + priv + sched.alloc.n_free \
        == sched.alloc.n_pages - 1
    assert all(r >= 0 for r in sched.alloc.refs)


# ---------------------------------------------------------------------------
# 5. satellites: emission schedule + CachePool donation safety
# ---------------------------------------------------------------------------
def test_first_token_emitted_at_admission_round():
    """TTFT == prefill: one step() (the admission round, no decode
    segment) already yields the prefill-sampled token, and it equals the
    sequential oracle's first token."""
    engine, cfg = _engine()
    p = _prompt(cfg, 6)
    ref = _ref(engine, p, 4)
    with engine.session(lanes=2, page_size=4, segment=2,
                        prefix_cache=False) as sess:
        h = sess.submit(p, SamplingParams(max_tokens=4))
        assert sess.step()
        assert h.tokens_ready == 1 and h.tokens_so_far()[0] == ref[0]
        sess.run_until_idle()
        np.testing.assert_array_equal(np.asarray(h.result()), ref)


def test_budget_one_and_instant_stop_finish_without_decode():
    engine, cfg = _engine()
    p = _prompt(cfg, 6)
    ref = _ref(engine, p, 2)
    with engine.session(lanes=2, page_size=4) as sess:
        h1 = sess.submit(p, SamplingParams(max_tokens=1))
        assert sess.step()                         # admission round only
        assert h1.status == RequestStatus.DONE
        assert not sess.sched.active               # lane already released
        np.testing.assert_array_equal(np.asarray(h1.result()), ref[:1])
        h2 = sess.submit(p, SamplingParams(max_tokens=8,
                                           stop_token=int(ref[0])))
        sess.run_until_idle()
        assert h2.status == RequestStatus.DONE
        np.testing.assert_array_equal(np.asarray(h2.result()), ref[:1])
    seg_keys = [k for k in engine._fns if k[0] == "segment"]
    assert not seg_keys                            # never decoded a segment


def test_cache_pool_failed_donating_dispatch_drops_entry():
    """A dispatch that dies AFTER the pool entry was taken must leave the
    pool without the (donation-invalidated) entry — the next request
    allocates fresh instead of inheriting poisoned buffers."""
    engine, cfg = _engine(max_len=16)
    prompts = jnp.asarray(_prompt(cfg, 6)[None])
    ref = np.asarray(engine.generate(prompts, 4))
    assert 1 in engine._caches                     # batch-1 cache parked
    key = (1, 6, 4, False)
    good_fn = engine._fns[key]

    def boom(*a, **k):
        raise RuntimeError("injected dispatch failure")

    engine._fns[key] = boom
    with pytest.raises(RuntimeError, match="injected"):
        engine.generate(prompts, 4)
    assert 1 not in engine._caches                 # dropped, not poisoned
    engine._fns[key] = good_fn
    np.testing.assert_array_equal(np.asarray(engine.generate(prompts, 4)),
                                  ref)


def test_cache_pool_fifo_eviction_order_and_engine_limit():
    pool = CachePool(limit=2)
    pool.put("a", 1), pool.put("b", 2), pool.put("c", 3)
    assert "a" not in pool and "b" in pool and "c" in pool   # FIFO: a first
    pool.put("d", 4)
    assert "b" not in pool and len(pool) == 2
    pool.put("c", 99)                              # re-put refreshes value
    assert pool.take("c") == 99
    # limit surfaces through the engine instead of the hardcoded 8
    cfg = get_smoke("gemma2-2b")
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, max_len=16, cache_pool_limit=3)
    assert eng._caches.limit == 3


# ---------------------------------------------------------------------------
# 6. SSM snapshots the index can hold: one every ``snapshot_stride`` pages
#    plus the last full page of each stored prompt, hits rounded down to
#    the deepest one, and the state held kept under its budget
# ---------------------------------------------------------------------------
def _stride_engine(monkeypatch, cfg, stride, page=4, max_len=48):
    """An engine whose sessions keep a snapshot every ``stride`` pages:
    the per-token allowance is set so ``snapshot_stride`` derives it from
    the model's own lane state."""
    import repro.serve.prefix_cache as PC

    per_token = _lane_state_bytes(cfg) // (stride * page)
    monkeypatch.setattr(PC, "SNAPSHOT_BYTES_PER_TOKEN", per_token)
    assert PC.snapshot_stride(_lane_state_bytes(cfg), page) == stride
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    return ServeEngine(cfg, params, max_len=max_len, prefix_cache=True)


def _boundary_snapshots(cache) -> int:
    n, stack = 0, [cache.root]
    while stack:
        node = stack.pop()
        stack.extend(node.children.values())
        n += sum(s is not None for s in node.snaps)
    return n


def _hybrid_mqa():
    """jamba's block without experts: one KV head, no RoPE, dt/B/C norms."""
    return get_smoke("jamba-1.5-large-398b").scaled(
        n_experts=0, top_k=0, n_kv_heads=1, use_rope=False, ssm_norms=True,
        dtype=jnp.float32)


def test_partial_hit_rounded_to_a_stride_snapshot_matches_cold(monkeypatch):
    """Two prompts share 6 pages; snapshots sit at pages 4 and 7 of the
    first, so the second resumes from page 4 and recomputes pages 5-6:
    its prefill logits and tokens are those of a cold full prefill."""
    cfg = _hybrid_mqa()
    engine = _stride_engine(monkeypatch, cfg, stride=4)
    rng = np.random.default_rng(11)
    p1 = _prompt(cfg, 30, rng)
    p2 = p1.copy()
    p2[24:] = (p2[24:] + 1) % cfg.vocab_size
    ref2 = _ref(engine, p2, 5)
    cold_logits, _ = engine._prefill(engine.params,
                                     {"tokens": jnp.asarray(p2[None])},
                                     cache_init(cfg, 1, engine.max_len)[0])
    with engine.session(lanes=2, page_size=4, segment=2) as sess:
        assert sess.prefix.snapshot_stride == 4
        sess.submit(p1, SamplingParams(max_tokens=5)).result()
        out2 = np.asarray(sess.submit(p2, SamplingParams(max_tokens=5))
                          .result())
        st = sess.prefix.stats
        assert (st["partial_hits"], st["hit_tokens"],
                st["resume_gap_tokens"]) == (1, 16, 8)
        rec = sess.prefix.records[p2.tobytes()]
        _assert_conserved(sess)
    np.testing.assert_array_equal(out2, ref2)
    got, want = np.asarray(rec.logits)[0, -1], np.asarray(cold_logits)[0, -1]
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * np.abs(want).max())


def test_snapshots_held_are_bounded_by_the_stride(monkeypatch):
    """After N prompts of L tokens the index holds at most
    N * (L // (page * K) + 1) boundary snapshots, and its gauges count
    them, and the records' end states, to the byte."""
    import repro.serve.prefix_cache as PC

    cfg = get_smoke("falcon-mamba-7b")
    engine = _stride_engine(monkeypatch, cfg, stride=4)
    rng = np.random.default_rng(12)
    N, L = 4, 37
    with engine.session(lanes=2, page_size=4, segment=2) as sess:
        for _ in range(N):
            sess.submit(_prompt(cfg, L, rng), SamplingParams(max_tokens=3))
        sess.run_until_idle()
        cache = sess.prefix
        held = _boundary_snapshots(cache)
        assert 0 < held <= N * (L // (4 * 4) + 1)
        ends = [r.end_ssm for r in cache.records.values()]
        assert cache.stats["ssm_snapshots"] == held + len(ends)
        nodes, stack = [], [cache.root]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            nodes += [s for s in n.snaps if s is not None]
        assert cache.stats["ssm_snapshot_bytes"] == sum(
            PC._state_bytes(t) for t in nodes + ends)


def test_resume_gap_tokens_counts_what_a_hit_gave_up(monkeypatch):
    """A match below the first kept snapshot is a miss that still counts
    its matched tokens as given up; an attention-only model never rounds."""
    cfg = get_smoke("falcon-mamba-7b")
    engine = _stride_engine(monkeypatch, cfg, stride=4)
    rng = np.random.default_rng(13)
    p1 = _prompt(cfg, 10, rng)                  # snapshot at page 2 only
    p2 = np.concatenate([p1[:4], _prompt(cfg, 6, rng)])   # shares page 1
    with engine.session(lanes=2, page_size=4, segment=2) as sess:
        sess.submit(p1, SamplingParams(max_tokens=3)).result()
        sess.submit(p2, SamplingParams(max_tokens=3)).result()
        st = sess.prefix.stats
        assert (st["misses"], st["partial_hits"], st["hit_tokens"],
                st["resume_gap_tokens"]) == (2, 0, 0, 4)
    dense, dcfg = _engine()
    with dense.session(lanes=2, page_size=4) as sess:
        assert sess.prefix.snapshot_stride == 0
        sess.submit(p1 % dcfg.vocab_size,
                    SamplingParams(max_tokens=3)).result()
        sess.submit(p2 % dcfg.vocab_size,
                    SamplingParams(max_tokens=3)).result()
        st = sess.prefix.stats
        assert (st["partial_hits"], st["hit_tokens"],
                st["resume_gap_tokens"]) == (1, 4, 0)


def test_state_budget_evicts_lru_state_with_its_entry(monkeypatch):
    """Distinct prompts past the budget: inserts LRU-evict records and
    leaves that hold state, so what the index holds stays under it, and
    pages and pins stay consistent."""
    cfg = get_smoke("falcon-mamba-7b")
    engine = _stride_engine(monkeypatch, cfg, stride=2)
    rng = np.random.default_rng(14)
    with engine.session(lanes=2, page_size=4, segment=2) as sess:
        one = _lane_state_bytes(cfg)
        sess.prefix.state_budget = 5 * one
        for _ in range(6):
            sess.submit(_prompt(cfg, 21, rng), SamplingParams(max_tokens=3))
            sess.run_until_idle()
            assert sess.prefix.stats["ssm_snapshot_bytes"] <= 5 * one
        assert sess.prefix.stats["evicted_pages"] > 0
        sess.prefix.audit(sess.sched.alloc)
        _assert_conserved(sess)
