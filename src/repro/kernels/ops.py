"""Jitted public wrappers for the B⊕LD Pallas kernels.

Each wrapper picks the kernel mode from the backend at call time: on a TPU
the kernels compile to Mosaic, anywhere else (the CPU test suite) they run
in the Pallas interpreter. The kernel functions themselves default to
compiled mode; pass ``interpret=`` to override.
"""
from __future__ import annotations

import jax

from . import boolean_matmul as _bm
from . import packed_xnor as _px
from . import boolean_bwd as _bb


def interpret_mode() -> bool:
    """True unless the default backend is a TPU."""
    return jax.default_backend() != "tpu"


def boolean_matmul(x, w, *, fuse_threshold=False, tau=0.0, **kw):
    kw.setdefault("interpret", interpret_mode())
    return _bm.boolean_matmul(x, w, fuse_threshold=fuse_threshold, tau=tau, **kw)


def packed_xnor_matmul(x_packed, w_packed, *, k_valid, **kw):
    kw.setdefault("interpret", interpret_mode())
    return _px.packed_xnor_matmul(x_packed, w_packed, k_valid=k_valid, **kw)


def packed_xnor_gemv(x, w_packed, *, k_valid, **kw):
    """Thin-M decode GEMV: real activations × bit-packed Boolean weights."""
    kw.setdefault("interpret", interpret_mode())
    return _px.packed_xnor_gemv(x, w_packed, k_valid=k_valid, **kw)


def boolean_weight_bwd(x, z, d, *, alpha=0.0, **kw):
    kw.setdefault("interpret", interpret_mode())
    return _bb.boolean_weight_bwd(x, z, d, alpha=alpha, **kw)


pack_bits = _px.pack_bits
unpack_bits = _px.unpack_bits


def flash_attention_tpu(q, k, v, **kw):
    from . import flash_attention as _fa

    kw.setdefault("interpret", interpret_mode())
    return _fa.flash_attention_tpu(q, k, v, **kw)


def paged_flash_decode(q, k_pool, v_pool, block_table, pos, layer,
                       k_scale=None, v_scale=None, **kw):
    """Serve decode over layer ``layer`` of a stacked block-table paged
    pool, pages read in place."""
    from . import paged_attention as _pa

    kw.setdefault("interpret", interpret_mode())
    return _pa.paged_flash_decode(q, k_pool, v_pool, block_table, pos,
                                  layer, k_scale, v_scale, **kw)


def paged_prefix_attention(q, k_tail, v_tail, k_pool, v_pool, page_ids,
                           offset, prefix_len, length,
                           k_scale=None, v_scale=None, **kw):
    """Prefix-cache tail prefill over in-place prefix pages."""
    from . import paged_attention as _pa

    kw.setdefault("interpret", interpret_mode())
    return _pa.paged_prefix_attention(q, k_tail, v_tail, k_pool, v_pool,
                                      page_ids, offset, prefix_len, length,
                                      k_scale, v_scale, **kw)
