"""Pallas paged-attention kernel validation (interpret mode).

The serve decode/prefix paths dispatch to kernels/paged_attention.py by
default (``REPRO_PAGED_KERNEL=1``); the XLA block-table gather survives as
the reference fallback. Because Boolean sign() amplifies reduction-order
ulps into different tokens, the pinned contract is BITWISE equality of the
kernel against the gather reference — not allclose — which in turn makes
every serve-level stream token-identical across the two paths.

Five layers:
  1. kernel-level bit parity of ``paged_flash_decode`` vs the gather +
     ``_flash_decode_local`` oracle — ragged lane positions, idle
     garbage-page lanes, table-overrun lanes, sliding window + softcap,
     multi-chunk, int8 kv-quant, and CoW-forked boundary pages;
  2. kernel-level bit parity of ``paged_prefix_attention`` vs
     ``gather_prefix_kv`` + ``flash_attention_abs`` (the prefix-cache tail);
  3. model-level: one paged decode step and one partial-hit session produce
     bit-identical logits/streams with the kernel on vs the fallback
     (``REPRO_PAGED_KERNEL=0``), across dense / packed / kv-quant /
     ssm-hybrid configs;
  4. serve-level CI gate: greedy ``generate_batch`` token streams are
     unchanged by the kernel across the config matrix, and the prefix-cache
     exact/partial-hit bit-identity holds with the kernel enabled;
  5. the decode step over the stacked pool, addressed by layer index, is
     bit-identical to the layer scan that sliced each group's pool out of
     the carry and wrote it back whole (kept below as the reference), and
     writes no pool row but the new token's.
"""
import types
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.kernels.paged_attention import (paged_flash_decode,
                                           paged_prefix_attention)
from repro.models import attention as A
from repro.models import lm as LM
from repro.models import lm_decode_step_paged, lm_init, lm_prefill
from repro.serve import SamplingParams, ServeEngine, commit_prefill, \
    pack_weights, paged_pool_init
from repro.serve.paged_cache import fork_page

RNG = np.random.default_rng(0)


def _prompts(cfg, lens):
    return [RNG.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
            for L in lens]


# ---------------------------------------------------------------------------
# 1. paged_flash_decode ≡ gather + _flash_decode_local, bit for bit
# ---------------------------------------------------------------------------
def _rand_pool(key, n_pages, page, KV, hd, quant, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 4)
    if quant:
        kp = jax.random.randint(ks[0], (n_pages, page, KV, hd), -127, 127,
                                jnp.int8)
        vp = jax.random.randint(ks[1], (n_pages, page, KV, hd), -127, 127,
                                jnp.int8)
        kscale = jax.random.uniform(ks[2], (n_pages, page, KV), jnp.float32,
                                    1e-3, 0.1)
        vscale = jax.random.uniform(ks[3], (n_pages, page, KV), jnp.float32,
                                    1e-3, 0.1)
        return kp, vp, kscale, vscale
    kp = jax.random.normal(ks[0], (n_pages, page, KV, hd),
                           jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[1], (n_pages, page, KV, hd),
                           jnp.float32).astype(dtype)
    return kp, vp, None, None


def _gather_decode_ref(cfg, q, kp, vp, bt, pos, ks, vs, local):
    """The XLA fallback: block-table gather + _flash_decode_local."""
    L, C = bt.shape
    page, KV, hd = kp.shape[1], kp.shape[2], kp.shape[3]
    k = kp[bt].reshape(L, C * page, KV, hd)
    v = vp[bt].reshape(L, C * page, KV, hd)
    kss = ks[bt].reshape(L, C * page, KV) if ks is not None else None
    vss = vs[bt].reshape(L, C * page, KV) if vs is not None else None
    m, l, acc = A._flash_decode_local(cfg, q, k, v, pos, 0, local=local,
                                      k_scale=kss, v_scale=vss)
    return acc / jnp.maximum(l[..., None], 1e-30)


@pytest.mark.parametrize("quant,window,softcap,chunk", [
    (False, 0, 0.0, 2048),      # global attention, single chunk
    (False, 9, 50.0, 8),        # sliding window + softcap, multi-chunk
    (True, 0, 0.0, 2048),       # int8 kv-quant
    (True, 7, 30.0, 16),        # quant + window + softcap, multi-chunk
])
def test_decode_kernel_bit_parity(quant, window, softcap, chunk):
    """Ragged positions, an idle garbage-page lane, and a table-overrun
    lane: the kernel's in-place page reads reproduce the gather reference
    bit for bit."""
    cfg = types.SimpleNamespace(decode_chunk=chunk,
                                attn_logit_softcap=softcap,
                                sliding_window=window)
    L, C, page, KV, R, hd = 4, 5, 4, 2, 8, 16
    key = jax.random.PRNGKey(0)
    kq, kpool = jax.random.split(key)
    q = jax.random.normal(kq, (L, KV, R, hd), jnp.float32).astype(
        jnp.bfloat16)
    # three stacked layers; the kernel reads layer 1 in place
    stacked = [_rand_pool(k, 12, page, KV, hd, quant)
               for k in jax.random.split(kpool, 3)]
    kp, vp, ks, vs = [None if x[0] is None else jnp.stack(x)
                      for x in zip(*stacked)]
    bt = jnp.asarray([[3, 1, 7, 0, 0],
                      [2, 5, 9, 11, 4],
                      [0, 0, 0, 0, 0],       # idle lane: garbage page only
                      [6, 8, 0, 0, 0]], jnp.int32)
    pos = jnp.asarray([6, 19, 0, 35], jnp.int32)   # 35 overruns the table
    ref = _gather_decode_ref(cfg, q, kp[1], vp[1], bt, pos,
                             None if ks is None else ks[1],
                             None if vs is None else vs[1], window > 0)
    out = paged_flash_decode(q, kp, vp, bt, pos, 1, ks, vs, window=window,
                             softcap_val=softcap, chunk=chunk,
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(ref, np.float32),
                                  np.asarray(out, np.float32))


def test_decode_kernel_bit_parity_after_cow_fork():
    """A CoW-forked boundary page (prefix-cache exact-hit admission) is
    just another physical page: decode over the forked copy matches the
    gather reference bit for bit, and differs from decoding the stale
    source page once the fork diverges."""
    cfg = get_smoke("gemma2-2b")
    ns = types.SimpleNamespace(decode_chunk=cfg.decode_chunk,
                               attn_logit_softcap=cfg.attn_logit_softcap,
                               sliding_window=0)
    page, KV, hd = 4, cfg.kv_heads_padded(), cfg.head_dim_
    pool = paged_pool_init(cfg, lanes=1, n_pages=8, page_size=page)
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    S = 7
    prompts = jnp.asarray(_prompts(cfg, [S])[0][None])
    _, pcache = lm_prefill(cfg, params, {"tokens": prompts})
    pool = commit_prefill(cfg, pool, pcache["blocks"], jnp.asarray(0),
                          jnp.asarray([2, 5], jnp.int32), page)
    pool = fork_page(cfg, pool, jnp.asarray(5), jnp.asarray(3))   # CoW copy
    g = pool["b0"]["k"].shape[0] - 1                 # the last group
    b0 = jax.tree.map(lambda x: x[g], pool["b0"])    # its slice: reference
    q = jax.random.normal(jax.random.PRNGKey(1), (1, KV, 8, hd),
                          jnp.float32).astype(cfg.dtype)
    for table in ([[2, 3, 0, 0]], [[2, 5, 0, 0]]):   # forked vs source page
        bt = jnp.asarray(table, jnp.int32)
        pos = jnp.asarray([S], jnp.int32)
        ref = _gather_decode_ref(ns, q, b0["k"], b0["v"], bt, pos,
                                 b0.get("k_scale"), b0.get("v_scale"), False)
        out = paged_flash_decode(q, pool["b0"]["k"], pool["b0"]["v"], bt,
                                 pos, g, pool["b0"].get("k_scale"),
                                 pool["b0"].get("v_scale"),
                                 chunk=ns.decode_chunk,
                                 softcap_val=ns.attn_logit_softcap,
                                 interpret=True)
        np.testing.assert_array_equal(np.asarray(ref, np.float32),
                                      np.asarray(out, np.float32))


# ---------------------------------------------------------------------------
# 2. paged_prefix_attention ≡ gather_prefix_kv + flash_attention_abs
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("quant,window,softcap,chunk", [
    (False, 0, 0.0, 1024),
    (False, 11, 50.0, 16),      # window + softcap, multi-chunk
    (True, 0, 30.0, 1024),
    (True, 13, 0.0, 8),
])
def test_prefix_kernel_bit_parity(quant, window, softcap, chunk):
    """Tail queries over [pool prefix pages ; tail K/V]: the in-place
    kernel reproduces the gathered-rows reference bit for bit, including
    the garbage-page bucket padding and a partially-live last page."""
    cfg = types.SimpleNamespace(kv_cache_quant=quant)
    npp, page, KV, n_rep, hd = 4, 4, 2, 8, 16
    H, S = KV * n_rep, 8
    key = jax.random.PRNGKey(1)
    ks_ = jax.random.split(key, 4)
    xdtype = jnp.bfloat16
    q = jax.random.normal(ks_[0], (1, S, H, hd), jnp.float32).astype(xdtype)
    kt = jax.random.normal(ks_[1], (1, S, KV, hd), jnp.float32).astype(xdtype)
    vt = jax.random.normal(ks_[2], (1, S, KV, hd), jnp.float32).astype(xdtype)
    kp, vp, kscale, vscale = _rand_pool(ks_[3], 10, page, KV, hd, quant,
                                        xdtype)
    bcache = {"k": kp[None], "v": vp[None]}
    if quant:
        bcache.update(k_scale=kscale[None], v_scale=vscale[None])
    page_ids = jnp.asarray([3, 7, 0, 0], jnp.int32)   # bucketed, garbage pad
    prefix_len = jnp.asarray(7, jnp.int32)            # partial last live page
    offset = jnp.asarray(7, jnp.int32)
    length = jnp.asarray(5, jnp.int32)                # true tail < bucket

    prefix = A.gather_prefix_kv(cfg, bcache, page_ids)
    pk = A._repeat_kv(prefix["k"][0].astype(xdtype), n_rep)
    pv = A._repeat_kv(prefix["v"][0].astype(xdtype), n_rep)
    P = npp * page
    positions = jnp.arange(S, dtype=jnp.int32) + offset
    ref = A.flash_attention_abs(
        q, jnp.concatenate([pk, A._repeat_kv(kt, n_rep)], axis=1),
        jnp.concatenate([pv, A._repeat_kv(vt, n_rep)], axis=1),
        q_pos=positions,
        k_pos=jnp.concatenate([jnp.arange(P, dtype=jnp.int32), positions]),
        k_valid=jnp.concatenate([jnp.arange(P) < prefix_len,
                                 jnp.arange(S) < length]),
        window=window, softcap_val=softcap, chunk=chunk)

    out = paged_prefix_attention(
        q[0].transpose(1, 0, 2), kt[0], vt[0], kp, vp, page_ids, offset,
        prefix_len, length, kscale, vscale, n_rep=n_rep, window=window,
        softcap_val=softcap, chunk=chunk, interpret=True)
    out = out.transpose(1, 0, 2)[None].astype(q.dtype)
    np.testing.assert_array_equal(np.asarray(ref, np.float32),
                                  np.asarray(out, np.float32))


# ---------------------------------------------------------------------------
# 3. model-level: kernel vs REPRO_PAGED_KERNEL=0 fallback, bit-identical
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("quant", [False, True])
def test_fallback_parity_decode_step(monkeypatch, quant):
    """One paged decode step (the real model graph, local+global gemma2
    blocks) produces bit-identical logits with the kernel on and off."""
    cfg = get_smoke("gemma2-2b")
    if quant:
        cfg = cfg.scaled(kv_cache_quant=True)
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    S, page = 9, 4
    prompts = jnp.asarray(_prompts(cfg, [S])[0][None])
    tok = jnp.asarray([[7]], jnp.int32)
    _, pcache = lm_prefill(cfg, params, {"tokens": prompts})
    pool = paged_pool_init(cfg, lanes=1, n_pages=6, page_size=page)
    pool = commit_prefill(cfg, pool, pcache["blocks"], jnp.asarray(0),
                          jnp.asarray([2, 4, 1], jnp.int32), page)
    paged = {"blocks": pool,
             "block_table": jnp.asarray([[2, 4, 1, 0]], jnp.int32),
             "pos": jnp.asarray([S], jnp.int32)}

    monkeypatch.setenv("REPRO_PAGED_KERNEL", "1")
    on, _ = lm_decode_step_paged(cfg, params, paged, tok)
    monkeypatch.setenv("REPRO_PAGED_KERNEL", "0")
    off, _ = lm_decode_step_paged(cfg, params, paged, tok)
    np.testing.assert_array_equal(np.asarray(on, np.float32),
                                  np.asarray(off, np.float32))


# ---------------------------------------------------------------------------
# 4. serve-level gate: token streams unchanged with the kernel enabled
# ---------------------------------------------------------------------------
SERVE_CONFIGS = [
    ("gemma2-2b", False, False),
    ("gemma2-2b", True, False),          # packed XNOR weight serving
    ("gemma2-2b", False, True),          # int8 kv-quant cache
    ("falcon-mamba-7b", False, False),   # pure SSM (lane-indexed state)
    ("jamba-1.5-large-398b", False, False),   # hybrid mamba+attn+MoE
]


@pytest.mark.parametrize("arch,packed,quant", SERVE_CONFIGS)
def test_serve_tokens_unchanged_by_kernel(monkeypatch, arch, packed, quant):
    """THE CI smoke gate: greedy ``generate_batch`` streams are identical
    with REPRO_PAGED_KERNEL=1 and =0 — and both match the sequential
    ``generate`` oracle — across the serve config matrix."""
    cfg = get_smoke(arch)
    if quant:
        cfg = cfg.scaled(kv_cache_quant=True)
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(cfg, [5, 8, 6])
    ntoks = [4, 3, 5]

    monkeypatch.setenv("REPRO_PAGED_KERNEL", "1")
    eng_on = ServeEngine(cfg, params, max_len=32, packed=packed)
    on = eng_on.generate_batch(prompts, ntoks, lanes=2, page_size=4,
                               segment=2)
    refs = [np.asarray(eng_on.generate(jnp.asarray(p[None]), n)[0])
            for p, n in zip(prompts, ntoks)]
    monkeypatch.setenv("REPRO_PAGED_KERNEL", "0")
    eng_off = ServeEngine(cfg, params, max_len=32, packed=packed)
    off = eng_off.generate_batch(prompts, ntoks, lanes=2, page_size=4,
                                 segment=2)
    for a, b, r in zip(on, off, refs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), r)


@pytest.mark.parametrize("quant", [False, True])
def test_prefix_partial_hit_parity_with_kernel(monkeypatch, quant):
    """Prefix-cache sessions with the kernel on: the exact hit and the
    partial-hit tail (paged_prefix_attention through the real engine)
    yield the same streams as the REPRO_PAGED_KERNEL=0 fallback — and, on
    the non-quant config, as the cold oracle."""
    cfg = get_smoke("gemma2-2b")
    if quant:
        cfg = cfg.scaled(kv_cache_quant=True)
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    base = _prompts(cfg, [12])[0]
    ext = np.concatenate([base,
                          _prompts(cfg, [5])[0]]).astype(np.int32)

    def serve(flag):
        monkeypatch.setenv("REPRO_PAGED_KERNEL", flag)
        eng = ServeEngine(cfg, params, max_len=32, prefix_cache=True)
        with eng.session(lanes=2, page_size=4, segment=2) as sess:
            cold = np.asarray(sess.submit(
                base, SamplingParams(max_tokens=5)).result())
            hit = np.asarray(sess.submit(
                base, SamplingParams(max_tokens=5)).result())
            partial = np.asarray(sess.submit(
                ext, SamplingParams(max_tokens=4)).result())
        oracle = np.asarray(eng.generate(jnp.asarray(ext[None]), 4)[0])
        return cold, hit, partial, oracle

    on = serve("1")
    off = serve("0")
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(on[0], on[1])      # exact hit == cold
    if not quant:                                    # kv-quant: serve-over-
        np.testing.assert_array_equal(on[2], on[3])  # cache, not cold-equal


# ---------------------------------------------------------------------------
# 5. stacked-pool decode ≡ the per-layer slice path, bit for bit
# ---------------------------------------------------------------------------
def _write_cells(block_table, pos, page):
    """(page id, offset) of each lane's new row: past the table, the
    garbage page 0."""
    C = block_table.shape[1]
    col = pos // page
    page_id = jnp.where(col < C, jnp.take_along_axis(
        block_table, jnp.clip(col, 0, C - 1)[:, None], axis=1)[:, 0], 0)
    return page_id, pos % page


def _slice_attention(cfg, p, x, cache, block_table, pos, layer=None, *,
                     local):
    """Paged decode attention over ONE layer's pool leaves (n_pages, page,
    KVp, hd): scatter the new row at (page, offset), then the block-table
    gather + ``_flash_decode_local``."""
    B, hd = x.shape[0], cfg.head_dim_
    hp, kvp = A._tp_heads(cfg)
    page, C = cache["k"].shape[1], block_table.shape[1]
    q, k_new, v_new = A._qkv(cfg, p, x, pos[:, None])
    qg = q[:, 0].reshape(B, kvp, hp // kvp, hd)
    rows = {"k": k_new, "v": v_new}
    if cache["k"].dtype == jnp.int8:
        rows["k"], rows["k_scale"] = A.kv_quant(k_new)
        rows["v"], rows["v_scale"] = A.kv_quant(v_new)
    page_id, off = _write_cells(block_table, pos, page)
    new = {n: cache[n].at[page_id, off].set(r[:, 0].astype(cache[n].dtype))
           for n, r in rows.items()}
    got = {n: a[block_table].reshape((B, C * page) + a.shape[2:])
           for n, a in new.items()}
    _, l, acc = A._flash_decode_local(
        cfg, qg, got["k"], got["v"], pos, 0, local=local,
        k_scale=got.get("k_scale"), v_scale=got.get("v_scale"))
    out = (acc / jnp.maximum(l[..., None], 1e-30)).reshape(
        B, 1, hp, hd).astype(x.dtype)
    out = A._head_mask(cfg, out).reshape(B, 1, hp * hd)
    return A._wo_project(cfg, p["wo"], out), new


def _slice_decode_step(cfg, params, cache, tokens):
    """The reference layer scan: slice every group's cache out of the
    stacked carry, decode each block on its slice (attention through
    ``_slice_attention``), write the whole slice back."""
    pos, bt = cache["pos"], cache["block_table"]
    h = LM.embed_apply(cfg, params["embed"], tokens).astype(cfg.dtype)

    def body(carry, gparams):
        x, blocks, g = carry
        gcache = jax.tree.map(
            lambda c: jax.lax.dynamic_index_in_dim(c, g, 0, keepdims=False),
            blocks)
        new = {}
        for i, role in enumerate(LM.block_roles(cfg)):
            x, new[f"b{i}"] = LM._apply_block_decode(
                cfg, gparams[f"b{i}"], role, gcache[f"b{i}"], x, pos,
                block_table=bt)
        blocks = jax.tree.map(
            lambda full, nc: jax.lax.dynamic_update_index_in_dim(
                full, nc.astype(full.dtype), g, 0), blocks, new)
        return (x, blocks, g + 1), None

    with mock.patch.object(A, "attention_decode_paged", _slice_attention):
        (h, blocks, _), _ = jax.lax.scan(
            body, (h, cache["blocks"], jnp.zeros((), jnp.int32)),
            params["blocks"])
    h = LM.rmsnorm_apply(params["final_norm"], h)
    return LM.head_apply(cfg, params["head"], h), {
        "blocks": blocks, "block_table": bt, "pos": pos + 1}


def _random_pool(cfg, lanes, n_pages, page, key):
    """A paged pool whose every byte is random (scales positive)."""
    pool = paged_pool_init(cfg, lanes, n_pages, page)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(pool)
    out = []
    for k, (path, a) in zip(jax.random.split(key, len(leaves)), leaves):
        if a.dtype == jnp.int8:
            out.append(jax.random.randint(k, a.shape, -127, 127, jnp.int8))
        elif "scale" in jax.tree_util.keystr(path):
            out.append(jax.random.uniform(k, a.shape, a.dtype, 1e-3, 0.1))
        else:
            out.append(jax.random.normal(k, a.shape, jnp.float32)
                       .astype(a.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _attn_ids(cfg):
    return [f"b{i}" for i, r in enumerate(LM.block_roles(cfg))
            if r["mixer"] != "mamba"]


@pytest.mark.parametrize("arch,packed,quant", [
    ("gemma2-2b", False, False),
    ("gemma2-2b", True, False),
    ("gemma2-2b", False, True),
    ("jamba-1.5-large-398b", False, False),   # attention inside mamba groups
], ids=["dense", "packed", "kv-quant", "hybrid"])
def test_stacked_pool_decode_matches_layer_slices(monkeypatch, arch, packed,
                                                  quant):
    """Three decode steps over a random pool: with the kernel and with the
    gather fallback, the stacked-pool step gives the reference's logits and
    pool bit for bit, with an idle lane on the garbage page and a lane that
    overruns its block table on the second step; every pool row but the
    new tokens' stays byte-identical."""
    cfg = get_smoke(arch)
    if quant:
        cfg = cfg.scaled(kv_cache_quant=True)
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    if packed:
        params = pack_weights(params)
    page, C = 4, 3
    pool = _random_pool(cfg, 4, 13, page, jax.random.PRNGKey(1))
    bt = jnp.asarray([[1, 2, 3],
                      [4, 5, 0],
                      [0, 0, 0],        # idle lane: garbage page only
                      [7, 8, 9]], jnp.int32)
    pos0 = jnp.asarray([5, 4, 0, C * page - 1], jnp.int32)  # lane 3 overruns
    toks = jax.random.randint(jax.random.PRNGKey(2), (3, 4, 1), 0,
                              cfg.vocab_size)

    def run(step):
        cache = {"blocks": pool, "block_table": bt, "pos": pos0}
        out = []
        for t in toks:
            logits, cache = step(params, cache, t)
            out.append((logits, cache["blocks"]))
        return out

    ref = run(jax.jit(lambda p, c, t: _slice_decode_step(cfg, p, c, t)))
    for flag in ("1", "0"):
        monkeypatch.setenv("REPRO_PAGED_KERNEL", flag)
        got = run(jax.jit(lambda p, c, t: lm_decode_step_paged(cfg, p, c, t)))
        before = pool
        for s, ((lr, pr), (lg, pg)) in enumerate(zip(ref, got)):
            np.testing.assert_array_equal(np.asarray(lr, np.float32),
                                          np.asarray(lg, np.float32))
            jax.tree.map(np.testing.assert_array_equal, pr, pg)
            page_id, off = _write_cells(bt, pos0 + s, page)
            kept = np.ones((13, page), bool)
            kept[np.asarray(page_id), np.asarray(off)] = False
            for b in _attn_ids(cfg):
                for n in pg[b]:
                    np.testing.assert_array_equal(
                        np.asarray(pg[b][n])[:, kept],
                        np.asarray(before[b][n])[:, kept])
            before = pg


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_write_touches_only_its_layer(quant):
    """One attention block's decode at layer g writes the new rows into
    group g of the stacked leaves: every other group's pool stays
    byte-identical, and group g changes only at the new rows' cells."""
    cfg = get_smoke("gemma2-2b").scaled(n_layers=6, kv_cache_quant=quant)
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    page, g = 4, 1                  # the middle one of three groups
    pool = _random_pool(cfg, 2, 7, page, jax.random.PRNGKey(3))["b1"]
    G = pool["k"].shape[0]
    assert G == 3
    p = jax.tree.map(lambda a: a[g], params["blocks"]["b1"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 1, cfg.d_model),
                          jnp.float32).astype(cfg.dtype)
    bt = jnp.asarray([[2, 5, 0], [3, 0, 0]], jnp.int32)
    pos = jnp.asarray([6, 1], jnp.int32)
    _, new = A.attention_decode_paged(cfg, p, x, pool, bt, pos, g)
    page_id, off = _write_cells(bt, pos, page)
    kept = np.ones(pool["k"].shape[1:3], bool)
    kept[np.asarray(page_id), np.asarray(off)] = False
    for n in pool:
        old, cur = np.asarray(pool[n]), np.asarray(new[n])
        others = np.arange(G) != g
        np.testing.assert_array_equal(cur[others], old[others])
        np.testing.assert_array_equal(cur[g][kept], old[g][kept])
        assert not np.array_equal(cur[g][~kept], old[g][~kept])
