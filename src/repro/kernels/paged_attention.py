"""Pallas paged-attention decode kernels: in-place page reads, no pool gather.

The serve decode path keeps K/V in a block-table paged pool
(serve/paged_cache.py). The portable XLA path reads it by materializing a
lane-contiguous ``(L, C*page, KVp, hd)`` gather via fancy indexing — per-token
HBM traffic scales with the whole pool slab, throwing away the very
data-movement win B⊕LD packing buys. These kernels walk each lane's block
table *inside* the kernel (``PrefetchScalarGridSpec`` scalar prefetch) and DMA
only the pages the lane actually attends over — O(tokens-attended) pool bytes
per step — straight from the pool refs (``pl.ANY``) into VMEM scratch.

Two entry points:

  * ``paged_flash_decode`` — one-token decode over L lanes, reading one
    layer of the decode scan's stacked pool by its index. Grid is per-lane;
    the lane's live pages (``ceil((pos+1)/page)``, clamped to the table) are
    staged one softmax chunk at a time; int8 KV rows dequantize in-kernel
    from the per-(token, head) scale pools; garbage-page-0 rows and rows
    beyond ``pos`` are masked.
  * ``paged_prefix_attention`` — the prefix-cache tail prefill: tail queries
    attend over [cached prefix pages ; the tail's own K/V] without ever
    materializing the gathered prefix rows (``gather_prefix_kv``'s job on the
    fallback path). Grid is (KV head group, tail-row block).

BIT-IDENTITY CONTRACT (interpret mode): Boolean sign() amplifies
reduction-order ulps into different tokens, so greedy parity between the
kernel and the XLA fallback (``REPRO_PAGED_KERNEL=0``) requires the kernels
to replicate their XLA references' op sequence — the same chunk sizes
(``decode_chunk`` / ``attn_chunk``), the same dequant-then-astype chain, the
same per-(head, row) dot products, the same masking constants — and only
replace the HBM gather with in-place page DMA. tests/test_paged_kernel.py
pins bitwise equality under the CPU interpreter. Compiled by Mosaic, the
matmuls run on the MXU with their own accumulation order, so on a TPU the
kernels agree with the references to float32 rounding, not bitwise.

VMEM model: each kernel stages ONE softmax chunk of K/V rows at a time
(``chunk`` rows × every KV head), walked by a ``fori_loop`` — the footprint
is fixed by the chunk size, not by the context, and the trace does not
grow with the page count. Staged rows a chunk does not fill hold finite
bytes (scratch is zeroed once per call) and are masked to weight 0.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _softcap(x, cap: float):
    # mirror of models/modules.softcap (kept local: kernels must not import
    # models — the dependency points the other way)
    return cap * jnp.tanh(x / cap) if cap > 0 else x


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


# Mosaic gets plain 2-D matmuls. Under the CPU interpreter each product is
# issued in its XLA reference's operand layout instead (unit batch dims
# standing in for the reference's lane/head dims): XLA:CPU picks a dot's
# summation order from the operand layout, and the parity tests are bitwise.
_REF_LAYOUT = {
    # _flash_decode_local: kv rows are (rows, KV, hd), heads in the middle
    "decode": ("grd,cgd->grc", "grc,cgd->grd",
               lambda q: q[None], lambda kv: kv[:, None]),
    # flash_attention_abs: every operand head-major
    "prefix": ("hqd,hkd->hqk", "hqk,hkd->hqd",
               lambda q: q[None], lambda kv: kv[None]),
}


def _qk(q, k, layout, interpret):
    """q (M, d) · k (N, d)ᵀ -> (M, N) fp32."""
    if interpret:
        spec, _, lq, lk = _REF_LAYOUT[layout]
        return jnp.einsum(spec, lq(q), lk(k),
                          preferred_element_type=jnp.float32)[0]
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _pv(p, v, layout, interpret):
    """p (M, N) · v (N, d) -> (M, d) fp32."""
    if interpret:
        _, spec, lq, lk = _REF_LAYOUT[layout]
        return jnp.einsum(spec, lq(p), lk(v),
                          preferred_element_type=jnp.float32)[0]
    return jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _copies(pairs, sem):
    return [pltpu.make_async_copy(src, dst, sem.at[i])
            for i, (src, dst) in enumerate(pairs)]


def _page_scales(scale_pool):
    """(n_pages, page, KV) per-(token, head) scales -> (n_pages, 1,
    page*KV): DMA moves whole lane-dense rows only, so each page's scales
    travel as one row (``_scale_col`` unpacks them in VMEM)."""
    n_pages, page, KV = scale_pool.shape
    return scale_pool.reshape(n_pages, 1, page * KV)


def _scale_col(sbuf, g: int, n: int, KV: int):
    """Head g's scales for the first n staged rows as an (n, 1) column.

    ``sbuf`` (pages, 1, page*KV) holds one lane-dense row per staged
    page; each row is repeated over its page's rows, and a one-hot lane
    select picks lane ``(row % page) * KV + g``. The sum adds one scale
    to zeros, so the column is exact."""
    npg, _, PK = sbuf.shape
    page = PK // KV
    rows = jnp.broadcast_to(sbuf[...], (npg, page, PK)).reshape(npg * page,
                                                                PK)[:n]
    r = _iota((n, PK), 0)
    pick = _iota((n, PK), 1) == jax.lax.rem(r, page) * KV + g
    return jnp.sum(jnp.where(pick, rows, 0.0), axis=1, keepdims=True)


def _chunk_rows(chunk: int, S: int, page: int) -> int:
    """Softmax chunk rows over an S-row sequence: ``min(chunk, S)``, the
    XLA references' rule. A chunk shorter than the sequence must hold
    whole pages, since pages are what the kernels DMA."""
    Cc = min(chunk, S)
    if Cc < S and Cc % page:
        raise ValueError(f"attention chunk {chunk} must be a multiple of "
                         f"the page size {page} when it splits a "
                         f"{S}-row sequence")
    return Cc


# ---------------------------------------------------------------------------
# Decode: one token per lane over the lane's block-table pages
# ---------------------------------------------------------------------------
def _decode_kernel(bt_ref, pos_ref, layer_ref, q_ref, kpool_ref, vpool_ref,
                   *rest, page: int, C: int, Cc: int, window: int,
                   softcap_val: float, scale: float, quant: bool,
                   interpret: bool):
    if quant:
        kspool_ref, vspool_ref, o_ref, kbuf, vbuf, ksbuf, vsbuf, sems = rest
        bufs = (kbuf, vbuf, ksbuf, vsbuf)
    else:
        o_ref, kbuf, vbuf, sems = rest
        bufs = (kbuf, vbuf)

    lane = pl.program_id(0)
    pos = pos_ref[lane]
    layer = layer_ref[0]
    S_loc = C * page
    ppc = Cc // page                     # pages per chunk
    n_all = -(-S_loc // Cc)
    tail_rows = S_loc - (n_all - 1) * Cc
    tail_rows = tail_rows if tail_rows < Cc else 0
    _, KV, R, hd = q_ref.shape

    @pl.when(lane == 0)
    def _zero():
        # rows a chunk does not fill still ride the einsums at weight 0.0;
        # uninitialized VMEM could hold NaN bits and 0*NaN would poison
        # the lane. Later lanes only ever see finite staged bytes.
        for b in bufs:
            b[...] = jnp.zeros_like(b)

    # live pages: rows 0..pos inclusive (the new token is already scattered
    # at ``pos``); an overrun lane (pos past its table) clamps to the full
    # table, exactly the row set the XLA gather reads and masks. Chunks
    # past the live pages are skipped: in the reference they add weight 0
    # to a real running max (x*1 + 0 — the same bits, ±0 at worst).
    n_live = jnp.minimum(C, (pos + page) // page)
    n_chunks = (n_live + ppc - 1) // ppc

    def page_copies(first, j):
        pid = bt_ref[lane, first + j]
        rows = pl.ds(j * page, page)
        pairs = [(kpool_ref.at[layer, pid], kbuf.at[rows]),
                 (vpool_ref.at[layer, pid], vbuf.at[rows])]
        if quant:           # the layer's scales, one row per page
            pairs += [(kspool_ref.at[pid], ksbuf.at[j]),
                      (vspool_ref.at[pid], vsbuf.at[j])]
        return _copies(pairs, sems)

    q = [q_ref[0, g].astype(jnp.float32) for g in range(KV)]   # (R, hd)

    def chunk_step(ci, carry):
        first = ci * ppc
        n_here = jnp.minimum(ppc, n_live - first)

        def start(j, _):
            for cp in page_copies(first, j):
                cp.start()
            return 0

        def wait(j, _):
            for cp in page_copies(first, j):
                cp.wait()
            return 0

        jax.lax.fori_loop(0, n_here, start, 0)
        jax.lax.fori_loop(0, n_here, wait, 0)
        if tail_rows:
            # the table's last chunk is short: the reference pads it with
            # zero rows, which a fully masked (overrun) lane still weighs
            @pl.when(ci == n_all - 1)
            def _pad():
                for i, b in enumerate(bufs):
                    lo = tail_rows if i < 2 else tail_rows // page
                    b[pl.ds(lo, b.shape[0] - lo)] = jnp.zeros(
                        (b.shape[0] - lo,) + b.shape[1:], b.dtype)

        # _flash_decode_local's chunk step, one KV head at a time
        lrow = ci * Cc + _iota((1, Cc), 1)
        valid = (lrow <= pos) & (lrow < S_loc)
        if window > 0:
            valid &= lrow > pos - window
        out = []
        for g in range(KV):
            m, l, acc = carry[g]
            kf = kbuf[:, g, :].astype(jnp.float32)           # (Cc, hd)
            vf = vbuf[:, g, :].astype(jnp.float32)
            if quant:
                kf = kf * _scale_col(ksbuf, g, Cc, KV)
                vf = vf * _scale_col(vsbuf, g, Cc, KV)
            s = _qk(q[g], kf, "decode", interpret) * scale    # (R, Cc)
            s = _softcap(s, softcap_val)
            s = jnp.where(valid, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            pexp = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(pexp, axis=-1, keepdims=True)
            acc = acc * corr + _pv(pexp, vf, "decode", interpret)
            out.append((m_new, l, acc))
        return tuple(out)

    init = tuple((jnp.full((R, 1), -1e30, jnp.float32),
                  jnp.zeros((R, 1), jnp.float32),
                  jnp.zeros((R, hd), jnp.float32)) for _ in range(KV))
    res = jax.lax.fori_loop(0, n_chunks, chunk_step, init)
    for g in range(KV):
        _, l, acc = res[g]
        o_ref[0, g] = acc / jnp.maximum(l, 1e-30)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap_val", "chunk", "interpret"),
)
def paged_flash_decode(q, k_pool, v_pool, block_table, pos, layer,
                       k_scale=None, v_scale=None, *, window: int = 0,
                       softcap_val: float = 0.0, chunk: int = 512,
                       interpret: bool = False):
    """Flash-decode over one layer of a stacked paged pool, pages read in
    place per lane.

    Args:
      q: (L, KV, R, hd) grouped queries (R = GQA group size).
      k_pool/v_pool: (G, n_pages, page, KV, hd) pool blocks of every layer
        group, stacked (cfg.dtype or int8).
      block_table: (L, C) int32 lane-logical page -> physical page.
      pos: (L,) int32 per-lane positions (new token already written at pos).
      layer: int32 scalar — the group ``g`` whose pages are read. It rides
        as a scalar-prefetch operand beside the block table and the
        positions, and every DMA reads ``pool.at[g, page]``: no layer-sized
        slice of the pool is ever taken, so the decode scan can keep the
        stacked pool as its carry and write the new rows in place.
      k_scale/v_scale: (G, n_pages, page, KV) fp32 per-(token, head) scales,
        required iff the pools are int8.
      chunk: softmax chunk rows (``cfg.decode_chunk``) — also the VMEM
        staging size.

    Returns (L, KV, R, hd) fp32 — the block-table gather of layer ``g`` +
    ``_flash_decode_local`` reference (bitwise in interpret mode).

    HEAD-LOCAL CONTRACT (serve-TP): every shape here comes from the
    operands, never from a config — under shard_map each device passes its
    KV-local q slice and KV-local pool leaves, so the kernel's per-lane
    DMA loop touches ONLY head-local pages and the O(tokens-attended)
    pool-byte bound divides by the shard count per device. The same holds
    for the gather fallback (it indexes the same local pool leaves), which
    is what keeps kernel-vs-gather parity shard-by-shard.
    """
    L, KV, R, hd = q.shape
    page = k_pool.shape[2]
    C = block_table.shape[1]
    Cc = _chunk_rows(chunk, C * page, page)
    quant = k_pool.dtype == jnp.int8

    kernel = functools.partial(
        _decode_kernel, page=page, C=C, Cc=Cc, window=window,
        softcap_val=softcap_val, scale=1.0 / math.sqrt(hd), quant=quant,
        interpret=interpret)
    scratch = [pltpu.VMEM((Cc, KV, hd), k_pool.dtype),
               pltpu.VMEM((Cc, KV, hd), v_pool.dtype)]
    in_specs = [
        pl.BlockSpec((1, KV, R, hd), lambda lane, *_: (lane, 0, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    args = [block_table, pos, jnp.reshape(layer, (1,)).astype(jnp.int32),
            q, k_pool, v_pool]
    if quant:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        # a (page, KV) scale block is too narrow for a DMA: the layer's
        # scales are relaid out as one lane-dense row per page (a copy of
        # that layer's scale pool; the K/V pages stay where they are)
        args += [_page_scales(k_scale[layer]), _page_scales(v_scale[layer])]
        scratch += [pltpu.VMEM((Cc // page, 1, page * KV), jnp.float32)] * 2
    scratch.append(pltpu.SemaphoreType.DMA((4,)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(L,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, R, hd),
                               lambda lane, *_: (lane, 0, 0, 0)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        name="paged_flash_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((L, KV, R, hd), jnp.float32),
        # sequential: lane 0 zeroes the scratch every later lane reuses
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*args)


# ---------------------------------------------------------------------------
# Prefix-cache tail prefill: tail queries over [prefix pages ; tail K/V]
# ---------------------------------------------------------------------------
_PREFIX_BLOCK_T = 128     # tail rows per grid step (one MXU-height tile)

def _prefix_kernel(page_ids_ref, lens_ref, q_ref, kt_ref, vt_ref,
                   kpool_ref, vpool_ref, *rest, page: int, npp: int, S: int,
                   ck: int, nk: int, n_tail_units: int, n_rep: int, KV: int,
                   window: int, softcap_val: float, scale: float,
                   quant: bool, interpret: bool):
    if quant:
        (kspool_ref, vspool_ref, o_ref, kst, vst, m_ref, l_ref, acc_ref,
         kq, vq, ks, vs, sems) = rest
        pbufs = (kq, vq, ks, vs)
        ppools = (kpool_ref, vpool_ref, kspool_ref, vspool_ref)
        zero = (kst, vst) + pbufs
    else:
        o_ref, kst, vst, m_ref, l_ref, acc_ref, sems = rest
        pbufs = (kst, vst)
        ppools = (kpool_ref, vpool_ref)
        zero = (kst, vst)

    g = pl.program_id(0)
    ti = pl.program_id(1)
    prefix_len = lens_ref[0]
    length = lens_ref[1]
    offset = lens_ref[2]
    P = npp * page
    K = P + S
    upc = ck // page                      # page-sized units per chunk
    n_units = npp + n_tail_units
    bt = q_ref.shape[1]

    @pl.when((g == 0) & (ti == 0))
    def _zero():
        for b in zero:
            b[...] = jnp.zeros_like(b)

    m_ref[...] = jnp.full_like(m_ref, -1e30)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    # live prefix pages only: the rest of the page bucket is garbage-page
    # padding the reference gathers and masks (rows past prefix_len)
    n_live = jnp.minimum(npp, (prefix_len + page - 1) // page)
    qpos = offset + ti * bt + _iota((bt, 1), 0)      # absolute tail positions

    def unit_copies(u, j):
        """The DMAs staging unit u (a prefix page or ``page`` tail rows)
        into row block j of the chunk buffers."""
        dst = pl.ds(j * page, page)
        pid = page_ids_ref[jnp.minimum(u, npp - 1)]
        prefix = _copies([(p.at[pid], b.at[d]) for p, b, d in
                          zip(ppools, pbufs, [dst] * 2 + [j] * 2)], sems)
        row = pl.ds(jnp.maximum(u - npp, 0) * page, page)
        tail = _copies([(kt_ref.at[row], kst.at[dst]),
                        (vt_ref.at[row], vst.at[dst])], sems)
        return prefix, tail

    def chunk_step(ci, _):
        u0 = ci * upc
        n_here = jnp.minimum(upc, n_units - u0)

        def each(op):
            def body(j, _):
                u = u0 + j
                prefix, tail = unit_copies(u, j)

                @pl.when(u < n_live)
                def _():
                    for cp in prefix:
                        getattr(cp, op)()

                @pl.when(u >= npp)
                def _():
                    for cp in tail:
                        getattr(cp, op)()
                return 0
            jax.lax.fori_loop(0, n_here, body, 0)

        each("start")
        each("wait")

        # flash_attention_abs's chunk step over rows r of [prefix ; tail]
        r = ci * ck + _iota((1, ck), 1)
        in_prefix = r < P
        # prefix rows sit at absolute positions 0..P-1; tail row j sits at
        # offset + j (the tail's own RoPE positions). Boolean selects are
        # spelled as and/or: Mosaic cannot select between i1 vectors.
        kpos = jnp.where(in_prefix, r, offset + (r - P))
        kval = ((in_prefix & (r < prefix_len))
                | (~in_prefix & ((r - P) < length) & (r < K)))
        valid = kval & (kpos <= qpos)                     # (bt, ck)
        if window > 0:
            valid &= qpos - kpos < window

        def group(gi):
            kc, vc = kst[:ck, gi, :], vst[:ck, gi, :]     # (ck, hd)
            if quant:
                # gather_prefix_kv's chain: int8 rows -> fp32 * scale ->
                # the tail's dtype, for the prefix rows only
                pre = ci * ck + _iota((ck, 1), 0) < P
                kc = jnp.where(pre, (kq[:ck, gi, :].astype(jnp.float32)
                                     * _scale_col(ks, gi, ck, KV)
                                     ).astype(kc.dtype), kc)
                vc = jnp.where(pre, (vq[:ck, gi, :].astype(jnp.float32)
                                     * _scale_col(vs, gi, ck, KV)
                                     ).astype(vc.dtype), vc)
            for h in range(n_rep):
                s = _qk(q_ref[h], kc, "prefix", interpret) * scale
                s = _softcap(s, softcap_val)
                s = jnp.where(valid, s, -1e30)
                m = m_ref[h]
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                pexp = jnp.exp(s - m_new)
                corr = jnp.exp(m - m_new)
                l_ref[h] = l_ref[h] * corr + jnp.sum(pexp, axis=-1,
                                                     keepdims=True)
                acc_ref[h] = acc_ref[h] * corr + _pv(
                    pexp.astype(vc.dtype), vc, "prefix", interpret)
                m_ref[h] = m_new

        # Mosaic only slices a head out of a bf16/int8 (rows, KV, hd)
        # buffer at a static index: one static branch per KV group
        for gi in range(KV):
            pl.when(g == gi)(functools.partial(group, gi))
        return 0

    last_rows = n_units * page - (nk - 1) * ck
    if last_rows < ck:
        # the last chunk's rows past the staged units are the reference's
        # zero padding; clear what earlier chunks left there first
        def run(ci, c):
            @pl.when(ci == nk - 1)
            def _():
                for b in (kst, vst):
                    b[pl.ds(last_rows, ck - last_rows)] = jnp.zeros(
                        (ck - last_rows,) + b.shape[1:], b.dtype)
            return chunk_step(ci, c)
    else:
        run = chunk_step
    jax.lax.fori_loop(0, nk, run, 0)
    o_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(
    jax.jit,
    static_argnames=("n_rep", "window", "softcap_val", "chunk", "interpret"),
)
def paged_prefix_attention(q, k_tail, v_tail, k_pool, v_pool, page_ids,
                           offset, prefix_len, length,
                           k_scale=None, v_scale=None, *, n_rep: int,
                           window: int = 0, softcap_val: float = 0.0,
                           chunk: int = 1024, interpret: bool = False):
    """Tail-prefill attention over in-place prefix pages + the tail's K/V.

    Args:
      q: (H, T, hd) tail queries, H = KV * n_rep (GQA broadcast order).
      k_tail/v_tail: (S, KV, hd) the tail's own K/V rows (S = T bucket).
      k_pool/v_pool: (n_pages, page, KV, hd) pool blocks; page_ids: (npp,)
        int32 physical pages of the cached prefix (garbage-page padding ok).
      offset: traced int32 — absolute position of tail row 0 (= hit length).
      prefix_len/length: traced int32 — live prefix rows / true tail length.
      k_scale/v_scale: scale pools, required iff the pool is int8.
      chunk: softmax chunk rows (``cfg.attn_chunk``) — also the VMEM
        staging size.

    Returns (H, T, hd) fp32 — gather_prefix_kv + ``flash_attention_abs``
    over the concatenated rows (bitwise in interpret mode).
    """
    H, T, hd = q.shape
    S, KV, _ = k_tail.shape
    npp = page_ids.shape[0]
    page = k_pool.shape[1]
    quant = k_pool.dtype == jnp.int8
    K = npp * page + S
    ck = _chunk_rows(chunk, K, page)
    nk = -(-K // ck)
    # the tail DMAs in page-sized units: pad it to whole pages (zero rows
    # past K are the reference's own chunk padding, masked either way)
    Sp = -(-S // page) * page
    k_tail = jnp.pad(k_tail, ((0, Sp - S), (0, 0), (0, 0)))
    v_tail = jnp.pad(v_tail, ((0, Sp - S), (0, 0), (0, 0)))
    rows = max(ck, npp * page + Sp) if nk == 1 else ck
    bt = min(T, _PREFIX_BLOCK_T)
    if T % bt:
        raise ValueError(f"tail bucket {T} is not a multiple of {bt}")

    kernel = functools.partial(
        _prefix_kernel, page=page, npp=npp, S=S, ck=ck, nk=nk,
        n_tail_units=Sp // page, n_rep=n_rep, KV=KV, window=window,
        softcap_val=softcap_val, scale=1.0 / math.sqrt(hd), quant=quant,
        interpret=interpret)
    scratch = [pltpu.VMEM((rows, KV, hd), k_tail.dtype),
               pltpu.VMEM((rows, KV, hd), v_tail.dtype),
               pltpu.VMEM((n_rep, bt, 1), jnp.float32),
               pltpu.VMEM((n_rep, bt, 1), jnp.float32),
               pltpu.VMEM((n_rep, bt, hd), jnp.float32)]
    in_specs = [pl.BlockSpec((n_rep, bt, hd),
                             lambda g, t, pid, ln: (g, t, 0))] + \
               [pl.BlockSpec(memory_space=pl.ANY)] * 4
    args = [page_ids,
            jnp.stack([jnp.asarray(prefix_len, jnp.int32),
                       jnp.asarray(length, jnp.int32),
                       jnp.asarray(offset, jnp.int32)]),
            q, k_tail, v_tail, k_pool, v_pool]
    if quant:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        args += [_page_scales(k_scale), _page_scales(v_scale)]
        scratch += [pltpu.VMEM((rows, KV, hd), jnp.int8)] * 2 + \
                   [pltpu.VMEM((rows // page, 1, page * KV), jnp.float32)] * 2
    scratch.append(pltpu.SemaphoreType.DMA((4,)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(KV, T // bt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((n_rep, bt, hd),
                               lambda g, t, pid, ln: (g, t, 0)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        name="paged_prefix_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, T, hd), jnp.float32),
        # sequential: step (0, 0) zeroes the scratch every later step reuses
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*args)
