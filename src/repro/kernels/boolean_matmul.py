"""Pallas TPU kernel: Boolean (±1 int8) GEMM with fused threshold activation.

This is the forward hot-spot of every B⊕LD layer: the counting-of-TRUEs
neuron (paper Eq 1) under the ±1 embedding is an int8×int8→int32 MAC, which
the TPU MXU executes natively at 2× bf16 throughput. The fused threshold
(paper §3.1 Forward Activation) emits int8 ±1 directly from VMEM, removing
the int32 pre-activation round-trip through HBM — data movement is the
paper's dominant energy term, so the fusion is the point, not a nicety.

Tiling: grid (M/bm, N/bn, K/bk), K innermost ("arbitrary" semantics), int32
accumulator tile in VMEM scratch. MXU alignment: bm multiple of 8 (sublane),
bn/bk multiples of 128 (lane); defaults (256, 256, 512) keep the working set
x(bm,bk) + w(bk,bn) + acc(bm,bn) = 128K + 128K + 256K ≈ 0.5 MB ≪ 16 MB VMEM
with headroom for double-buffered pipelines.

Validated on CPU in the Pallas interpreter against ``ref.py``; the TPU path
is identical code.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bool_matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int,
                        fuse_threshold: bool, tau: float):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 ±1 blocks -> MXU int8 path with int32 accumulation.
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        acc = acc_ref[...]
        if fuse_threshold:
            # y = T(+1) iff s >= tau — int8 out, never materializes s in HBM.
            o_ref[...] = jnp.where(acc >= tau, 1, -1).astype(o_ref.dtype)
        else:
            o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "fuse_threshold",
                     "tau", "interpret"),
)
def boolean_matmul(x: jax.Array, w: jax.Array, *,
                   block_m: int = 256, block_n: int = 256, block_k: int = 512,
                   fuse_threshold: bool = False, tau: float = 0.0,
                   interpret: bool = False) -> jax.Array:
    """y = x @ w for ±1 int8 operands; int32 counting output (or fused ±1 int8).

    Args:
      x: (M, K) int8 ±1.   w: (K, N) int8 ±1.
      fuse_threshold: emit int8 ±1 = [s >= tau] instead of int32 counts.
      interpret: run the kernel body in the Pallas interpreter (CPU);
        ``ops.boolean_matmul`` decides it from the backend.
    """
    if x.ndim != 2 or w.ndim != 2:
        raise ValueError("boolean_matmul expects 2-D operands")
    M, K = x.shape
    K2, N = w.shape
    if K != K2:
        raise ValueError(f"contraction mismatch {x.shape} @ {w.shape}")

    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    # Pad to block multiples. K-padding with +1/-1 pairs would bias the count,
    # so pad x with zeros (int8 0 contributes nothing to the MAC).
    Mp, Np, Kp = (-(-M // bm) * bm), (-(-N // bn) * bn), (-(-K // bk) * bk)
    xp = jnp.pad(x, ((0, Mp - M), (0, Kp - K)))
    wp = jnp.pad(w, ((0, Kp - K), (0, Np - N)))
    n_k = Kp // bk

    out_dtype = jnp.int8 if fuse_threshold else jnp.int32
    kernel = functools.partial(_bool_matmul_kernel, n_k=n_k,
                               fuse_threshold=fuse_threshold, tau=tau)
    yp = pl.pallas_call(
        kernel,
        name="boolean_matmul",
        grid=(Mp // bm, Np // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xp, wp)
    return yp[:M, :N]
