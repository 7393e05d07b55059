"""Attention substrate: GQA with Boolean projections, chunked flash attention
(32k-ready), sliding-window + global alternation (gemma2), logit softcap,
QKV bias (qwen), and a KV-cache decode path (flash-decode-ready).

TP scheme: Q heads sharded over "model" (padded up to a multiple of the axis;
padded head outputs are *masked to zero* before the o-projection because
Boolean ±1 weights cannot encode zero rows). KV heads with n_kv < axis are
replicated; larger kv counts are padded+sharded like Q.
"""
from __future__ import annotations

import math
import os
from contextlib import contextmanager
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .modules import (FSDP_AXIS, MODEL_AXIS, ModelConfig, proj_apply,
                      proj_init, rope, softcap)


_PAGED_OVERRIDE: Optional[bool] = None


def paged_kernel_enabled() -> bool:
    """Whether paged serve attention runs the Pallas in-place-page kernels
    (kernels/paged_attention.py) instead of the XLA block-table gather.

    Checked at TRACE time — compiled serve fns bake the choice in, so the
    session/engine compile-cache keys include this flag and flipping
    ``REPRO_PAGED_KERNEL`` mid-process recompiles instead of serving stale
    graphs. ``REPRO_PAGED_KERNEL=0`` keeps the gather path as the reference
    fallback (bitwise-identical outputs — tests/test_paged_kernel.py).

    A live ``paged_kernel_override`` context takes precedence over the
    environment — the serve session's kernel-fault containment path traces
    the gather graph under ``override(False)`` without mutating global env
    state other sessions/threads read.
    """
    if _PAGED_OVERRIDE is not None:
        return _PAGED_OVERRIDE
    return os.environ.get("REPRO_PAGED_KERNEL", "1") != "0"


@contextmanager
def paged_kernel_override(enabled: Optional[bool]):
    """Scoped override of ``paged_kernel_enabled`` (None = defer to env).
    Used with a compile key pinning the same value, so the graph traced
    inside the context is cached under — and only under — that choice."""
    global _PAGED_OVERRIDE
    prev = _PAGED_OVERRIDE
    _PAGED_OVERRIDE = enabled
    try:
        yield
    finally:
        _PAGED_OVERRIDE = prev


def attention_init(key, cfg: ModelConfig, axis_size: int = 16):
    hd = cfg.head_dim_
    hp = cfg.heads_padded(axis_size)
    kvp = cfg.kv_heads_padded(axis_size)
    kv_spec = (P(FSDP_AXIS, MODEL_AXIS) if cfg.n_kv_heads >= axis_size
               else P(FSDP_AXIS, None))
    ks = jax.random.split(key, 4)
    return {
        "wq": proj_init(ks[0], cfg, cfg.d_model, hp * hd,
                        P(FSDP_AXIS, MODEL_AXIS), bias=cfg.qkv_bias),
        "wk": proj_init(ks[1], cfg, cfg.d_model, kvp * hd, kv_spec,
                        bias=cfg.qkv_bias),
        "wv": proj_init(ks[2], cfg, cfg.d_model, kvp * hd, kv_spec,
                        bias=cfg.qkv_bias),
        "wo": proj_init(ks[3], cfg, hp * hd, cfg.d_model,
                        P(MODEL_AXIS, FSDP_AXIS)),
    }


def _tp_heads(cfg: ModelConfig, axis_size: int = 16):
    """(hp_local, kvp_local): per-device head counts under serve-time TP.

    ``serve_tp == 1`` (everything except mesh serving) returns the global
    padded counts unchanged. Under TP the engine shards q/k/v weight
    columns and the KV page pools on the head axis, so every projection
    and cache shape inside the shard_map body is head-local. GQA grouping
    survives because heads are group-major: kvp % tp == 0 is validated at
    engine construction, and hp_l // kvp_l == the global n_rep.
    """
    hp = cfg.heads_padded(axis_size)
    kvp = cfg.kv_heads_padded(axis_size)
    return hp // cfg.serve_tp, kvp // cfg.serve_tp


def _qkv(cfg: ModelConfig, p, x, positions, axis_size: int = 16):
    """Project to (B,S,Hp,hd) q and (B,S,KVp,hd) k/v with RoPE applied.

    Under serve-TP the weights are column-sharded (shard-major for the
    fused wqkv), so the shapes here are the LOCAL head counts.
    """
    B, S, _ = x.shape
    hd = cfg.head_dim_
    hp, kvp = _tp_heads(cfg, axis_size)
    if "wqkv" in p:
        # Packed serving layout (pack_weights): q/k/v fused into a single
        # GEMV so the decode token makes ONE pass over the activations and
        # one packed weight stream instead of three.
        qkv = proj_apply(cfg, p["wqkv"], x)
        q, k, v = jnp.split(qkv, [hp * hd, (hp + kvp) * hd], axis=-1)
        q = q.reshape(B, S, hp, hd)
        k = k.reshape(B, S, kvp, hd)
        v = v.reshape(B, S, kvp, hd)
    else:
        q = proj_apply(cfg, p["wq"], x).reshape(B, S, hp, hd)
        k = proj_apply(cfg, p["wk"], x).reshape(B, S, kvp, hd)
        v = proj_apply(cfg, p["wv"], x).reshape(B, S, kvp, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _head_mask(cfg: ModelConfig, out, axis_size: int = 16):
    """Zero the padded q-head outputs (Boolean wo rows are ±1, not 0).

    Under serve-TP ``out`` carries this shard's local head slice, so the
    real-head test compares GLOBAL head indices (shard offset via
    ``axis_index``) against ``n_heads``.
    """
    hp = cfg.heads_padded(axis_size)
    if hp == cfg.n_heads:
        return out
    hp_l = hp // cfg.serve_tp
    idx = jnp.arange(hp_l)
    if cfg.serve_tp > 1:
        idx = idx + jax.lax.axis_index(MODEL_AXIS) * hp_l
    mask = (idx < cfg.n_heads).astype(out.dtype)
    return out * mask[None, None, :, None]


def _wo_project(cfg: ModelConfig, p_wo, out):
    """o-projection, TP-aware: the head-axis reduce of the decode segment.

    ``out`` is (B, S, hp_local*hd). Under serve-TP the heads are
    all-gathered (shard-major == global head order) and the REPLICATED wo
    is applied to the full activation — NOT a partial-wo psum. Summing
    per-shard wo partials would reassociate the fan-in reduction, and
    B⊕LD's sign() activations amplify those ulps into token flips (the
    psum variant measurably diverges on 8-device CPU meshes); gathering
    the tiny (B,1,hp*hd) per-step activation instead keeps the projection
    arithmetic IDENTICAL to the unsharded graph, so greedy streams stay
    token-identical across shard counts. The gathered bytes are O(B·hp·hd)
    per step — noise next to the per-device O(tokens-attended) pool reads
    that sharding exists to cut — and the replicated wo is 1-bit packed,
    so the weight-byte cost of replication is 32× discounted.
    serve_tp == 1 takes the exact pre-TP code path."""
    if cfg.serve_tp == 1:
        return proj_apply(cfg, p_wo, out)
    full = jax.lax.all_gather(out, MODEL_AXIS, axis=2, tiled=True)
    return proj_apply(cfg, p_wo, full)


def _repeat_kv(x, n_rep: int):
    """(B,S,KV,hd) -> (B,S,KV*n_rep,hd) — GQA group broadcast."""
    if n_rep == 1:
        return x
    B, S, KV, hd = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (B, S, KV, n_rep, hd)) \
              .reshape(B, S, KV * n_rep, hd)


# ---------------------------------------------------------------------------
# Chunked flash attention (training / prefill)
# ---------------------------------------------------------------------------
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap_val: float = 0.0, chunk: int = 1024):
    """Online-softmax attention, scanning KV in chunks of ``chunk``.

    q,k,v: (B, S, H, hd) with identical H (kv already group-broadcast).
    window > 0 limits attention to the last ``window`` positions (sliding).
    Never materializes the (S,S) score matrix: peak extra memory is
    (B, H, Cq, Ck) per chunk pair.
    """
    B, S, H, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    cq = min(chunk, S)
    ck = min(chunk, S)
    nq, nk = -(-S // cq), -(-S // ck)
    Sp_q, Sp_k = nq * cq, nk * ck
    qp = jnp.pad(q, ((0, 0), (0, Sp_q - S), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sp_k - S), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sp_k - S), (0, 0), (0, 0)))

    # (B, n, C, H, hd) -> scan-friendly (n, B, H, C, hd)
    qb = qp.reshape(B, nq, cq, H, hd).transpose(1, 0, 3, 2, 4)
    kb = kp.reshape(B, nk, ck, H, hd).transpose(1, 0, 3, 2, 4)
    vb = vp.reshape(B, nk, ck, H, hd).transpose(1, 0, 3, 2, 4)

    q_pos = jnp.arange(Sp_q).reshape(nq, cq)
    k_pos = jnp.arange(Sp_k).reshape(nk, ck)

    def per_q_chunk(qi, q_chunk):
        qpos = q_pos[qi]                       # (cq,)

        def kv_step(carry, inp):
            m, l, acc = carry
            k_chunk, v_chunk, kpos = inp
            s = jnp.einsum("bhqd,bhkd->bhqk", q_chunk, k_chunk,
                           preferred_element_type=jnp.float32) * scale
            s = softcap(s, softcap_val)
            valid = jnp.ones((cq, ck), bool)
            if causal:
                valid &= qpos[:, None] >= kpos[None, :]
            if window > 0:
                valid &= qpos[:, None] - kpos[None, :] < window
            valid &= (kpos < S)[None, :]
            s = jnp.where(valid[None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(v_chunk.dtype), v_chunk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, cq), -1e30, jnp.float32)
        l0 = jnp.zeros((B, H, cq), jnp.float32)
        a0 = jnp.zeros((B, H, cq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (kb, vb, k_pos))
        return acc / jnp.maximum(l[..., None], 1e-30)

    out = jax.lax.map(lambda args: per_q_chunk(*args),
                      (jnp.arange(nq), qb))          # (nq, B, H, cq, hd)
    out = out.transpose(1, 0, 3, 2, 4).reshape(B, Sp_q, H, hd)[:, :S]
    return out.astype(q.dtype)


def flash_attention_abs(q, k, v, q_pos, k_pos, k_valid, *, window: int = 0,
                        softcap_val: float = 0.0, chunk: int = 1024):
    """Online-softmax attention with EXPLICIT absolute positions.

    The prefix-cache tail prefill attends queries at absolute positions
    ``q_pos`` (offset + tail index) over keys at ``k_pos`` — a cached
    prefix gathered from pool pages concatenated with the tail's own keys
    — so index-based causality (``flash_attention``) no longer applies:
    masking is ``k_valid & (k_pos <= q_pos)`` (& the sliding window),
    entirely in position space.

    q: (B, T, H, hd); k/v: (B, K, H, hd) (kv already group-broadcast);
    q_pos: (T,) int32; k_pos: (K,) int32; k_valid: (K,) bool (traced —
    masks prefix-pad and bucket-pad rows). Scans KV in ``chunk``-row
    chunks like ``flash_attention``; never materializes (T, K).
    """
    B, T, H, hd = q.shape
    K = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    ck = min(chunk, K)
    nk = -(-K // ck)
    pad = ((0, 0), (0, nk * ck - K), (0, 0), (0, 0))
    kb = jnp.pad(k, pad).reshape(B, nk, ck, H, hd).transpose(1, 0, 3, 2, 4)
    vb = jnp.pad(v, pad).reshape(B, nk, ck, H, hd).transpose(1, 0, 3, 2, 4)
    kpb = jnp.pad(k_pos, (0, nk * ck - K)).reshape(nk, ck)
    kvb = jnp.pad(k_valid, (0, nk * ck - K)).reshape(nk, ck)
    qt = q.transpose(0, 2, 1, 3)                    # (B, H, T, hd)

    def kv_step(carry, inp):
        m, l, acc = carry
        k_chunk, v_chunk, kpos, kval = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", qt, k_chunk,
                       preferred_element_type=jnp.float32) * scale
        s = softcap(s, softcap_val)
        valid = kval[None, :] & (kpos[None, :] <= q_pos[:, None])
        if window > 0:
            valid &= q_pos[:, None] - kpos[None, :] < window
        s = jnp.where(valid[None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(v_chunk.dtype), v_chunk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, T), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, T), jnp.float32)
    a0 = jnp.zeros((B, H, T, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kb, vb, kpb, kvb))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def gather_prefix_kv(cfg: ModelConfig, bcache, page_ids):
    """Gather a cached prefix's K/V rows out of one block's page pool.

    bcache: a stacked-groups pool block ({"k"/"v": (G, n_pages, page, KVp,
    hd)} plus scale pools under kv_cache_quant); page_ids: (npp,) int32
    physical pages (garbage-page 0 padding allowed — rows masked by the
    caller's ``prefix_len``). Returns {"k"/"v": (G, 1, npp*page, KVp, hd)}
    fp32-dequantized — EXACTLY the bytes decode would read for those rows,
    which is what makes a tail prefill consistent with decoding over the
    same pages. The leading G axis lets the result ride the block scan as
    xs alongside the stacked params.
    """
    npp = page_ids.shape[0]
    page = bcache["k"].shape[2]

    def rows(name):
        g = bcache[name][:, page_ids]            # (G, npp, page, ...)
        g = g.reshape((g.shape[0], npp * page) + g.shape[3:])
        return g[:, None]                        # (G, 1, npp*page, ...)

    k, v = rows("k"), rows("v")
    if cfg.kv_cache_quant:
        return {"k": kv_dequant(k, rows("k_scale")),
                "v": kv_dequant(v, rows("v_scale"))}
    return {"k": kv_dequant(k), "v": kv_dequant(v)}


def flash_prefix_attention_paged(cfg: ModelConfig, bcache, page_ids, q, k, v,
                                 positions, prefix_len, length, *,
                                 local: bool = False):
    """Tail-prefill attention over a cached prefix read IN PLACE from pool
    pages — the Pallas replacement for ``gather_prefix_kv`` +
    ``flash_attention_abs`` (bitwise-identical; see
    kernels/paged_attention.py for the parity contract).

    bcache: one block's group-sliced pool leaves ({"k"/"v": (n_pages, page,
    KVp, hd)} plus scale pools under kv_cache_quant); page_ids: (npp,) int32
    physical prefix pages (garbage-page padding allowed); q: (1, S, Hp, hd)
    tail queries; k/v: (1, S, KVp, hd) the tail's own rows (pre-GQA-repeat);
    positions: (1, S) absolute tail positions (offset + i); prefix_len /
    ``length``: traced int32 live-row bounds (``length`` None ⇒ S).
    Returns (1, S, Hp, hd) in q.dtype.
    """
    from repro.kernels import ops as kops

    B, S, hp, hd = q.shape
    if B != 1:
        raise ValueError("paged prefix attention serves batch-1 admission "
                         f"prefills; got batch {B}")
    kvp = k.shape[2]
    out = kops.paged_prefix_attention(
        q[0].transpose(1, 0, 2), k[0], v[0], bcache["k"], bcache["v"],
        page_ids, positions[0, 0], prefix_len,
        jnp.asarray(S if length is None else length, jnp.int32),
        bcache.get("k_scale"), bcache.get("v_scale"),
        n_rep=hp // kvp, window=cfg.sliding_window if local else 0,
        softcap_val=cfg.attn_logit_softcap, chunk=cfg.attn_chunk)
    return out.transpose(1, 0, 2)[None].astype(q.dtype)


def attention_apply(cfg: ModelConfig, p, x, positions, *,
                    local: bool = False, axis_size: int = 16):
    """Full training/prefill attention block body (no residual/norm)."""
    B, S, _ = x.shape
    hd = cfg.head_dim_
    hp = cfg.heads_padded(axis_size)
    kvp = cfg.kv_heads_padded(axis_size)
    q, k, v = _qkv(cfg, p, x, positions, axis_size)
    k = _repeat_kv(k, hp // kvp)
    v = _repeat_kv(v, hp // kvp)
    window = cfg.sliding_window if local else 0
    out = flash_attention(q, k, v, causal=True, window=window,
                          softcap_val=cfg.attn_logit_softcap,
                          chunk=cfg.attn_chunk)
    out = _head_mask(cfg, out, axis_size)
    out = out.reshape(B, S, hp * hd)
    return proj_apply(cfg, p["wo"], out)


# ---------------------------------------------------------------------------
# KV cache + decode
# ---------------------------------------------------------------------------
# int8 cache rows carry a PER-(token, head) fp32 scale computed at write
# time: scale = max|row| / 127, stored alongside the k/v blocks. A fixed
# global step (the old KV_QUANT_SCALE=16) either clips outlier rows or
# wastes step granularity on quiet ones — with per-row scales every row
# spans its own full int8 range and clipping disappears by construction.
def kv_quant(x):
    """x: (..., hd) -> (int8 rows, fp32 per-row scale (...,))."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-6) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def kv_dequant(x, scale=None):
    if x.dtype == jnp.int8:
        return x.astype(jnp.float32) * scale[..., None]
    return x.astype(jnp.float32)


def kv_cache_entry(cfg: ModelConfig, k, v):
    """The prefill write payload for one attention block: quantized rows +
    their scales when cfg.kv_cache_quant, plain cfg.dtype rows otherwise.
    Structure matches ``attention_cache_init`` so prefill can tree-map
    ``cache_write`` over (cache, entry)."""
    if cfg.kv_cache_quant:
        kq, ks = kv_quant(k)
        vq, vs = kv_quant(v)
        return {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    return {"k": k.astype(cfg.dtype), "v": v.astype(cfg.dtype)}


def attention_cache_init(cfg: ModelConfig, batch: int, max_len: int,
                         axis_size: int = 16, *, shard_seq: bool = False):
    """Returns (cache, specs).

    Default decode layout: batch over cfg.batch_axes, cache sequence over
    cfg.cache_seq_axes (the launcher picks per shape — see
    launch/shardings.py), kv heads over "model" only when n_kv >= axis.
    With cfg.kv_cache_quant the int8 k/v leaves are joined by fp32
    per-(token, head) scale leaves sharing the (batch, seq, kv) layout.
    """
    hd = cfg.head_dim_
    # LOCAL kv head count under serve-TP (shard_map prefill bodies allocate
    # their scratch cache at the shard's slice); global when serve_tp == 1.
    kvp = _tp_heads(cfg, axis_size)[1]
    seq_axes = cfg.cache_seq_axes if (shard_seq or cfg.cache_seq_axes) else None
    # seq-sharded decode layout keeps kv heads unsharded; otherwise kv heads
    # shard over model when wide enough.
    kv_axis = (MODEL_AXIS if (cfg.n_kv_heads >= axis_size and not seq_axes)
               else None)
    batch_axis = cfg.batch_axes if cfg.batch_axes else None
    spec = P(batch_axis, seq_axes if seq_axes else None, kv_axis, None)
    dtype = jnp.int8 if cfg.kv_cache_quant else cfg.dtype
    shape = (batch, max_len, kvp, hd)
    cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    specs = {"k": spec, "v": spec}
    if cfg.kv_cache_quant:
        sspec = P(batch_axis, seq_axes if seq_axes else None, kv_axis)
        for n in ("k_scale", "v_scale"):
            cache[n] = jnp.zeros((batch, max_len, kvp), jnp.float32)
            specs[n] = sspec
    return cache, specs


def cache_write(full, new):
    """Write ``new`` (a prompt prefix along the seq axis, or a full-state
    leaf) into the preallocated cache leaf ``full``. Quantization happens
    upstream in ``kv_cache_entry`` (per-row scales ride as their own
    leaves), so this is a pure prefix write. Replaces the grown-per-prompt
    caches: buffers are allocated at max_len once and only ever updated in
    place. Prefill-only: writes start at position 0 (decode writes at
    ``pos`` via ``attention_decode`` directly)."""
    new = new.astype(full.dtype)
    if full.shape == new.shape:
        return new
    return jax.lax.dynamic_update_slice(full, new, (0,) * full.ndim)


def _flash_decode_local(cfg: ModelConfig, q, k_cache, v_cache, pos,
                        seq_offset, *, local: bool,
                        k_scale=None, v_scale=None):
    """Partial flash-decode over a LOCAL cache slab.

    q: (B, KVg, R, hd) grouped queries; k/v_cache: (B, S_loc, KVg, hd)
    (bf16 or int8 — dequantized chunk-by-chunk via the per-(token, head)
    ``k_scale``/``v_scale`` leaves (B, S_loc, KVg)); pos: global position —
    a scalar, or a (B,) vector of per-lane positions (continuous batching:
    every lane sits at its own depth in the cache); seq_offset: global
    index of this slab's first row.
    Returns (m, l, acc): softmax stats + unnormalized value accumulator.
    """
    B, S_loc, KV, hd = k_cache.shape
    R = q.shape[2]
    scale = 1.0 / math.sqrt(hd)
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    C = min(cfg.decode_chunk, S_loc)
    n = -(-S_loc // C)
    if n * C != S_loc:
        pad = ((0, 0), (0, n * C - S_loc), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)
        if k_scale is not None:
            k_scale = jnp.pad(k_scale, pad[:3])
            v_scale = jnp.pad(v_scale, pad[:3])

    kb = k_cache.reshape(B, n, C, KV, hd).transpose(1, 0, 2, 3, 4)
    vb = v_cache.reshape(B, n, C, KV, hd).transpose(1, 0, 2, 3, 4)
    xs = (kb, vb, jnp.arange(n))
    if k_scale is not None:
        xs += (k_scale.reshape(B, n, C, KV).transpose(1, 0, 2, 3),
               v_scale.reshape(B, n, C, KV).transpose(1, 0, 2, 3))

    def step(carry, inp):
        m, l, acc = carry
        kc, vc, ci = inp[:3]
        ks, vs = inp[3:] if len(inp) > 3 else (None, None)
        kf = kv_dequant(kc, ks)                       # (B,C,KV,hd) fp32
        s = jnp.einsum("bgrd,bcgd->bgrc", q.astype(jnp.float32), kf,
                       preferred_element_type=jnp.float32) * scale
        s = softcap(s, cfg.attn_logit_softcap)
        lrow = ci * C + jnp.arange(C)
        kpos = seq_offset + lrow
        valid = (kpos[None, :] <= posv[:, None]) & (lrow < S_loc)[None, :]
        if local and cfg.sliding_window > 0:
            valid &= kpos[None, :] > posv[:, None] - cfg.sliding_window
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        pexp = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(pexp, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bgrc,bcgd->bgrd", pexp, kv_dequant(vc, vs),
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, R), -1e30, jnp.float32)
    l0 = jnp.zeros((B, KV, R), jnp.float32)
    a0 = jnp.zeros((B, KV, R, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), xs)
    return m, l, acc


def attention_decode(cfg: ModelConfig, p, x, cache, pos, *,
                     local: bool = False, axis_size: int = 16):
    """One-token decode. x: (B,1,D); cache{k,v}: (B,Smax,KVp,hd); pos scalar.

    When the launcher installs a seq-sharded cache layout
    (cfg.cache_seq_axes), the cache update + flash-decode run inside a fully
    manual shard_map: each device scans only its local cache slab, then the
    softmax stats combine with one tiny psum over the seq axes — the
    collective payload is O(B·H·hd), independent of context length.
    """
    B, _, _ = x.shape
    hd = cfg.head_dim_
    hp = cfg.heads_padded(axis_size)
    kvp = cfg.kv_heads_padded(axis_size)
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k_new, v_new = _qkv(cfg, p, x, positions, axis_size)
    n_rep = hp // kvp
    qg = q[:, 0].reshape(B, kvp, n_rep, hd)
    quant = cache["k"].dtype == jnp.int8
    if quant:
        k_new, ks_new = kv_quant(k_new)               # (B,1,KV,hd),(B,1,KV)
        v_new, vs_new = kv_quant(v_new)
    else:
        k_new = k_new.astype(cache["k"].dtype)
        v_new = v_new.astype(cache["v"].dtype)
        ks_new = vs_new = None

    new_cache = dict(cache)
    if cfg.use_sharding_constraints and cfg.cache_seq_axes:
        out, written = _decode_shardmap(
            cfg, qg, k_new[:, 0], v_new[:, 0], cache, pos,
            ks_new[:, 0] if quant else None,
            vs_new[:, 0] if quant else None, local=local)
        new_cache.update(written)
    else:
        new_cache["k"] = jax.lax.dynamic_update_slice(cache["k"], k_new,
                                                      (0, pos, 0, 0))
        new_cache["v"] = jax.lax.dynamic_update_slice(cache["v"], v_new,
                                                      (0, pos, 0, 0))
        if quant:
            new_cache["k_scale"] = jax.lax.dynamic_update_slice(
                cache["k_scale"], ks_new, (0, pos, 0))
            new_cache["v_scale"] = jax.lax.dynamic_update_slice(
                cache["v_scale"], vs_new, (0, pos, 0))
        m, l, acc = _flash_decode_local(
            cfg, qg, new_cache["k"], new_cache["v"], pos, 0, local=local,
            k_scale=new_cache.get("k_scale"),
            v_scale=new_cache.get("v_scale"))
        out = acc / jnp.maximum(l[..., None], 1e-30)

    out = out.reshape(B, 1, hp, hd).astype(x.dtype)
    out = _head_mask(cfg, out, axis_size)
    out = out.reshape(B, 1, hp * hd)
    return proj_apply(cfg, p["wo"], out), new_cache


def attention_decode_paged(cfg: ModelConfig, p, x, cache, block_table, pos,
                           layer, *, local: bool = False):
    """One-token decode over a block-table PAGED cache (continuous batching).

    x: (L,1,D) with L scheduler lanes; cache{k,v}: (G, n_pages, page, KVp,
    hd) pool blocks of EVERY layer group, stacked (plus per-row scale pools
    (G, n_pages, page, KVp) under kv_cache_quant); layer: int32 scalar, the
    group ``g`` this block belongs to; block_table: (L, C) int32 mapping
    lane-logical page j -> physical page; pos: (L,) int32 per-lane
    positions. Logical cache row r of lane l in group g lives at
    ``pool[g, block_table[l, r // page], r % page]`` — the write scatters
    the new token into that (g, page, offset) cell of the stacked leaf,
    which XLA performs in place on the decode scan's carry; the read walks
    the lane's live pages of layer g IN PLACE inside the Pallas
    flash-decode kernel (kernels/paged_attention.py — O(tokens-attended)
    pool bytes per step), or, under ``REPRO_PAGED_KERNEL=0``, gathers them
    from layer g into a contiguous (L, C·page, ...) window and runs the
    same flash-decode with per-lane position masking (the
    bitwise-identical XLA reference). No layer-sized slice of the pool is
    taken or written back on either path. Physical page 0 is the reserved
    garbage page: idle/overrun lanes point at it, so their writes never
    touch pages owned by live requests. Returns (out, the stacked cache
    with the new rows written).

    Under serve-TP (``cfg.serve_tp > 1``, engine mesh mode) this body runs
    inside ``shard_map`` on a 1-D ("model",) mesh: the cache pool leaves
    are the shard's KVp-local slices, q/k/v projections produce local
    heads, and both the Pallas kernel and the gather fallback read only
    head-local pages — the O(tokens-attended) pool-byte bound holds PER
    DEVICE. The o-projection all-gathers the head activations first
    (``_wo_project`` — a gather, not a psum, for bit-stability).
    """
    B = x.shape[0]
    hd = cfg.head_dim_
    hp, kvp = _tp_heads(cfg)
    page = cache["k"].shape[2]
    C = block_table.shape[1]
    q, k_new, v_new = _qkv(cfg, p, x, pos[:, None])
    n_rep = hp // kvp
    qg = q[:, 0].reshape(B, kvp, n_rep, hd)

    # (page, offset) of each lane's write; lanes past their allocation land
    # on table entries that are 0 (the garbage page) by construction, and
    # lanes past the TABLE itself (segment overrun of a request whose page
    # count fills every column) are routed to the garbage page explicitly —
    # clipping the column would WRAP the write onto the lane's last real
    # page, corrupting prompt rows that prefix caching later re-serves.
    col = pos // page
    page_id = jnp.where(
        col < C,
        jnp.take_along_axis(block_table, jnp.clip(col, 0, C - 1)[:, None],
                            axis=1)[:, 0],
        0)
    off = pos % page

    quant = cache["k"].dtype == jnp.int8
    new_cache = dict(cache)
    if quant:
        k_new, ks_new = kv_quant(k_new)
        v_new, vs_new = kv_quant(v_new)
        new_cache["k_scale"] = cache["k_scale"].at[layer, page_id, off].set(
            ks_new[:, 0])
        new_cache["v_scale"] = cache["v_scale"].at[layer, page_id, off].set(
            vs_new[:, 0])
    else:
        k_new = k_new.astype(cache["k"].dtype)
        v_new = v_new.astype(cache["v"].dtype)
    new_cache["k"] = cache["k"].at[layer, page_id, off].set(k_new[:, 0])
    new_cache["v"] = cache["v"].at[layer, page_id, off].set(v_new[:, 0])

    if paged_kernel_enabled():
        # Pallas kernel: the lane's block table is walked in-kernel and only
        # its live pages are DMA'd from the pool refs — no (L, C*page, ...)
        # slab ever materializes in HBM.
        from repro.kernels import ops as kops

        out = kops.paged_flash_decode(
            qg, new_cache["k"], new_cache["v"], block_table, pos, layer,
            new_cache.get("k_scale"), new_cache.get("v_scale"),
            window=cfg.sliding_window if local else 0,
            softcap_val=cfg.attn_logit_softcap, chunk=cfg.decode_chunk)
    else:
        # XLA reference: lane-contiguous (L, C*page, KVp, hd) gather of
        # layer g's pages, one gather from the stacked leaf
        def lanes(leaf):
            rows = leaf[layer, block_table]
            return rows.reshape((B, C * page) + rows.shape[3:])

        k, v = lanes(new_cache["k"]), lanes(new_cache["v"])
        ks = lanes(new_cache["k_scale"]) if quant else None
        vs = lanes(new_cache["v_scale"]) if quant else None
        m, l, acc = _flash_decode_local(cfg, qg, k, v, pos, 0, local=local,
                                        k_scale=ks, v_scale=vs)
        out = acc / jnp.maximum(l[..., None], 1e-30)

    out = out.reshape(B, 1, hp, hd).astype(x.dtype)
    out = _head_mask(cfg, out)
    out = out.reshape(B, 1, hp * hd)
    return _wo_project(cfg, p["wo"], out), new_cache


def _decode_shardmap(cfg: ModelConfig, qg, k_new, v_new, cache, pos,
                     ks_new=None, vs_new=None, *, local: bool):
    """Manual seq-sharded flash-decode (see attention_decode docstring).

    Returns (out, written) where ``written`` holds the updated cache leaves
    (k/v, plus k_scale/v_scale under kv_cache_quant — the per-row scales
    shard along the same seq axes as the rows they describe).
    """
    from repro.distributed import get_mesh

    mesh = get_mesh()
    seq_axes = cfg.cache_seq_axes
    b_ax = cfg.batch_axes if cfg.batch_axes else None
    S = cache["k"].shape[1]
    n_shards = 1
    for a in seq_axes:
        n_shards *= mesh.shape[a]
    S_loc = S // n_shards
    quant = ks_new is not None

    def local_fn(qg, k_new, v_new, kc, vc, *scales):
        # global offset of this device's slab
        idx = jnp.zeros((), jnp.int32)
        for a in seq_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        offset = idx * S_loc
        # write the new token iff it lands in this slab
        lpos = jnp.clip(pos - offset, 0, S_loc - 1)
        here = (pos >= offset) & (pos < offset + S_loc)
        kc_new = jax.lax.dynamic_update_slice(kc, k_new[:, None], (0, lpos, 0, 0))
        vc_new = jax.lax.dynamic_update_slice(vc, v_new[:, None], (0, lpos, 0, 0))
        kc = jnp.where(here, kc_new, kc)
        vc = jnp.where(here, vc_new, vc)
        ksc = vsc = None
        if quant:
            ks_tok, vs_tok, ksc, vsc = scales
            ksc = jnp.where(here, jax.lax.dynamic_update_slice(
                ksc, ks_tok[:, None], (0, lpos, 0)), ksc)
            vsc = jnp.where(here, jax.lax.dynamic_update_slice(
                vsc, vs_tok[:, None], (0, lpos, 0)), vsc)
        m, l, acc = _flash_decode_local(cfg, qg, kc, vc, pos, offset,
                                        local=local, k_scale=ksc, v_scale=vsc)
        # combine softmax stats across seq shards — O(B·H·hd) payload
        m_g = jax.lax.pmax(m, seq_axes)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, seq_axes)
        acc_g = jax.lax.psum(acc * corr[..., None], seq_axes)
        out = acc_g / jnp.maximum(l_g[..., None], 1e-30)
        if quant:
            return out, kc, vc, ksc, vsc
        return out, kc, vc

    rep = P(b_ax, None, None, None)
    cache_spec = P(b_ax, seq_axes, None, None)
    scale_spec = P(b_ax, seq_axes, None)
    in_specs = (rep, P(b_ax, None, None), P(b_ax, None, None),
                cache_spec, cache_spec)
    out_specs = (rep, cache_spec, cache_spec)
    args = (qg, k_new, v_new, cache["k"], cache["v"])
    if quant:
        in_specs += (P(b_ax, None), P(b_ax, None), scale_spec, scale_spec)
        out_specs += (scale_spec, scale_spec)
        args += (ks_new, vs_new, cache["k_scale"], cache["v_scale"])
    res = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=False)(*args)
    written = {"k": res[1], "v": res[2]}
    if quant:
        written["k_scale"], written["v_scale"] = res[3], res[4]
    return res[0], written
