"""Pallas kernel: bit-packed XNOR-popcount GEMM — the 1-bit dataflow floor.

The closest TPU analogue of the paper's "native Boolean accelerator": the K
dimension is packed 32 Booleans per uint32 word (bit=1 ⇔ T), and the Boolean
dot product becomes
    s = Σ_i e(x_i)·e(w_i) = K_valid − 2·popcount(x_bits XOR w_bits)
computed on the VPU (xor + population_count + integer adds) — no MXU at all.

On real v5e this loses to the int8 MXU path for square GEMMs (VPU peak is
~2 orders below the MXU) but it moves 32× fewer weight bytes, so it wins on
the *memory-bound* thin GEMMs of decode (arithmetic intensity < 1 MAC/byte),
and it is the faithful model of the paper's data-movement claims.

Tiling: grid (M/bm, N/bn, Kw/bkw) over packed words; int32 accumulator in
VMEM. popcount via jax.lax.population_count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# GEMV tile autotune table
# ---------------------------------------------------------------------------
# Mosaic-real tiling for the thin-M serving GEMV: the weight tile (bkw, bn)
# wants sublane-aligned bkw (uint32 tiles are (8, 128)) and lane-full bn
# (multiples of 128); the in-VMEM unpacked ±1 view is (bkw*32, bn) fp32, so
# bkw also bounds the transient VMEM footprint (bkw=16, bn=256 -> 512 KiB,
# the cap every entry must respect). Entries are (block_n, block_kw), keyed
# by the GEMV shape signature (N, Kw, activation dtype) and populated from
# the tile sweep in benchmarks/bench_kernels.py (``python
# benchmarks/bench_kernels.py --sweep-gemv`` prints entries in this literal
# form; re-sweep on real TPU — the checked-in entries come from the
# interpret harness and encode layout, not silicon, preferences). All tile
# candidates come from the sweep grid (bn ∈ {128, 256}, bkw ∈ {8, 16}) so
# a re-sweep can reproduce or overturn any entry.
GEMV_TILE_TABLE = {
    # the decode GEMVs the packed smoke serve configs actually issue
    # (fused wqkv/wgu thin projections + wo/wd down projections)
    (320, 2, "float32"): (128, 8),
    (256, 2, "float32"): (128, 8),
    (64, 4, "float32"): (128, 8),
    (64, 8, "float32"): (128, 8),
    # square serving shapes (bench_kernels trajectory points)
    (512, 16, "float32"): (256, 8),
    (1024, 32, "float32"): (256, 16),
    (4096, 128, "float32"): (256, 16),
}


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def gemv_tile_config(N: int, Kw: int, dtype=jnp.float32):
    """(block_n, block_kw) for a (M thin, Kw packed words, N) GEMV.

    Table hit wins; otherwise a Mosaic-aligned heuristic: lane-full
    ``block_n`` (128, or 256 once N spans multiple lanes of tiles) and an
    8-sublane-aligned ``block_kw`` capped so the transient unpacked weight
    tile stays ≲ 512 KiB of VMEM.

    ``dtype`` is the caller's activation dtype. The kernel unpacks and
    accumulates in fp32 regardless (activations are cast before the grid —
    see ``packed_xnor_gemv``), so a miss on the exact dtype falls back to
    the shape's ``float32`` entry before the heuristic; the dtype stays in
    the key for a future in-kernel bf16 variant whose tiles WILL differ.
    bf16 serving (cfg.dtype default) therefore hits the fp32-swept entries.
    """
    N, Kw = int(N), int(Kw)
    name = jnp.dtype(dtype).name
    for key in ((N, Kw, name), (N, Kw, "float32")):
        if key in GEMV_TILE_TABLE:
            return GEMV_TILE_TABLE[key]
    bn = 128 if N <= 128 else 256
    bkw = min(_round_up(max(Kw, 1), 8), 16)
    return bn, bkw


# ---------------------------------------------------------------------------
# Packing helpers (pure jnp; used by callers and the reference oracle).
# Packing layout: bit b of word j along K encodes element k = j*32 + b.
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("axis",))
def pack_bits(x_pm1: jax.Array, axis: int = -1) -> jax.Array:
    """Pack a ±1 int8 array into uint32 words along ``axis`` (pad with F).

    Jitted so the (…, K/32, 32) bit planes fuse into the reduction instead
    of materializing at 4 bytes per weight (gigabytes at serving widths)."""
    x = jnp.moveaxis(x_pm1, axis, -1)
    K = x.shape[-1]
    Kp = -(-K // 32) * 32
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, Kp - K)], constant_values=-1)
    bits = (x > 0).astype(jnp.uint32).reshape(*x.shape[:-1], Kp // 32, 32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    words = jnp.sum(bits * weights, axis=-1, dtype=jnp.uint32)
    return jnp.moveaxis(words, -1, axis)


def unpack_bits(words: jax.Array, k: int, axis: int = -1) -> jax.Array:
    """Inverse of pack_bits -> ±1 int8 of length k along ``axis``."""
    w = jnp.moveaxis(words, axis, -1)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (w[..., :, None] >> shifts) & jnp.uint32(1)
    x = jnp.where(bits == 1, 1, -1).astype(jnp.int8)
    x = x.reshape(*w.shape[:-1], w.shape[-1] * 32)[..., :k]
    return jnp.moveaxis(x, -1, axis)


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------
def _xnor_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_kw: int, k_valid: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xw = x_ref[...]          # (bm, bkw) uint32
    ww = w_ref[...]          # (bkw, bn) uint32
    # disagreements per word: popcount(x ^ w), broadcast outer product shape.
    diff = jax.lax.population_count(xw[:, None, :] ^ ww.T[None, :, :])
    acc_ref[...] += jnp.sum(diff.astype(jnp.int32), axis=-1)

    @pl.when(pl.program_id(2) == n_kw - 1)
    def _done():
        # Pad bits are F(0) on BOTH operands -> xor 0 -> contribute nothing
        # to the disagreement count, so s = K_valid - 2*popcount holds.
        o_ref[...] = (k_valid - 2 * acc_ref[...]).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# Thin-M serving GEMV: real activations × bit-packed Boolean weights.
#
# Decode GEMMs have M = batch (a handful of rows) and real-valued (bf16)
# activations, so the fully-Boolean popcount form above does not apply
# directly. The mixed-type rule (paper Def 3.5: xnor(w, x) = e(w)·x for real
# x) still lets the *weights* stay bit-packed: only uint32 words stream from
# HBM (32× fewer weight bytes — the whole point on memory-bound decode) and
# the ±1 view is reconstructed in VMEM right before the fp32 MAC.
# ---------------------------------------------------------------------------
def _xnor_gemv_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_kw: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)         # (M, bkw*32)
    wbits = w_ref[...]                         # (bkw, bn) uint32
    bkw, bn = wbits.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (wbits[:, None, :] >> shifts[None, :, None]) & jnp.uint32(1)
    wpm = jnp.where(bits == 1, 1.0, -1.0).reshape(bkw * 32, bn)
    acc_ref[...] += jnp.dot(x, wpm, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == n_kw - 1)
    def _done():
        # x rows are zero-padded past k_valid, so garbage pad bits in the
        # unpacked weight tile contribute exactly nothing.
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("k_valid", "block_n", "block_kw", "interpret"),
)
def packed_xnor_gemv(x: jax.Array, w_packed: jax.Array, *,
                     k_valid: int,
                     block_n: int = None, block_kw: int = None,
                     interpret: bool = False) -> jax.Array:
    """y[i,j] = Σ_k x[i,k]·e(w[k,j]) with only the weights bit-packed.

    Args:
      x: (M, K) real (or ±1 int8) activations, M thin (decode batch).
      w_packed: (Kw, N) uint32 — K packed along axis 0 (``pack_bits`` layout).
      k_valid: the true contraction length K (= x.shape[1]).
      block_n/block_kw: tile override; None consults the autotune table
        (``gemv_tile_config``, keyed by (N, Kw, x.dtype)).

    Returns (M, N) float32 counting outputs (exact: ±1·x accumulated fp32).
    """
    M, K = x.shape
    Kw, N = w_packed.shape
    if K != k_valid or Kw * 32 < K:
        raise ValueError(
            f"packed gemv mismatch: x {x.shape}, w {w_packed.shape}, "
            f"k_valid={k_valid}")
    if block_n is None or block_kw is None:
        tn, tkw = gemv_tile_config(N, Kw, x.dtype)
        block_n = tn if block_n is None else block_n
        block_kw = tkw if block_kw is None else block_kw

    # Mosaic alignment: bkw on uint32 sublane tiles (8), bn on full lanes
    # (128), M on fp32 sublanes (8) — padded compute over aligned tiles
    # beats Mosaic relayouts of ragged ones; pads are sliced off below.
    bkw = min(block_kw, _round_up(Kw, 8))
    bn = min(block_n, _round_up(N, 128))
    Kwp, Np = _round_up(Kw, bkw), _round_up(N, bn)
    Mp = _round_up(M, 8)
    n_kw = Kwp // bkw
    xp = jnp.pad(x.astype(jnp.float32), ((0, Mp - M), (0, Kwp * 32 - K)))
    wp = jnp.pad(w_packed, ((0, Kwp - Kw), (0, Np - N)))

    kernel = functools.partial(_xnor_gemv_kernel, n_kw=n_kw)
    cost = pl.CostEstimate(
        # the MAC work after the in-VMEM unpack, and the HBM bytes that
        # actually move: fp32 activations + PACKED weight words + fp32 out
        flops=2 * Mp * Kwp * 32 * Np,
        bytes_accessed=xp.nbytes + wp.nbytes + Mp * Np * 4,
        transcendentals=0,
    )
    yp = pl.pallas_call(
        kernel,
        name="packed_xnor_gemv",
        grid=(Np // bn, n_kw),
        in_specs=[
            pl.BlockSpec((Mp, bkw * 32), lambda j, k: (0, k)),
            pl.BlockSpec((bkw, bn), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((Mp, bn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((Mp, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        cost_estimate=cost,
    )(xp, wp)
    return yp[:M, :N]


@functools.partial(
    jax.jit,
    static_argnames=("k_valid", "block_m", "block_n", "block_kw", "interpret"),
)
def packed_xnor_matmul(x_packed: jax.Array, w_packed: jax.Array, *,
                       k_valid: int,
                       block_m: int = 128, block_n: int = 128,
                       block_kw: int = 64, interpret: bool = False) -> jax.Array:
    """y[i,j] = Σ_k e(x[i,k])·e(w[k,j]) from bit-packed operands.

    Args:
      x_packed: (M, Kw) uint32 — K packed along axis 1 (Kw = ceil(K/32)).
      w_packed: (Kw, N) uint32 — K packed along axis 0.
      k_valid: the true (unpadded) K; pad bits must be F (=0) on both sides.
    """
    M, Kw = x_packed.shape
    Kw2, N = w_packed.shape
    if Kw != Kw2:
        raise ValueError(f"packed contraction mismatch {x_packed.shape} @ {w_packed.shape}")

    bm, bn, bkw = min(block_m, M), min(block_n, N), min(block_kw, Kw)
    Mp, Np, Kwp = -(-M // bm) * bm, -(-N // bn) * bn, -(-Kw // bkw) * bkw
    # Zero-pad: pad words are all-F on both operands -> zero disagreements.
    xp = jnp.pad(x_packed, ((0, Mp - M), (0, Kwp - Kw)))
    wp = jnp.pad(w_packed, ((0, Kwp - Kw), (0, Np - N)))
    n_kw = Kwp // bkw

    kernel = functools.partial(_xnor_kernel, n_kw=n_kw, k_valid=k_valid)
    yp = pl.pallas_call(
        kernel,
        name="packed_xnor_matmul",
        grid=(Mp // bm, Np // bn, n_kw),
        in_specs=[
            pl.BlockSpec((bm, bkw), lambda i, j, k: (i, k)),
            pl.BlockSpec((bkw, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xp, wp)
    return yp[:M, :N]
