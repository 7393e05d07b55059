"""Pallas kernel: fused Boolean-variation weight backward (paper Eq 5/7).

Computes the vote aggregation  G_W = Xᵀ·Z  with the tanh'(αΔ) activation
re-weighting (App C) fused into the same pass:

    G_W[i,j] = Σ_k e(x[k,i]) · z[k,j] · tanh'(α·(s[k,j] − τ))

Fusing the mask avoids materializing the masked upstream signal Z̃ in HBM —
on a (B·S, n) signal at 32k context that is gigabytes of traffic per layer.

x is ±1 int8 (Boolean input activations), z/s are bf16/f32; accumulation is
fp32 (vote counts need exact-ish summation over the batch dimension).
Tiling: grid (M/bm, N/bn, B/bb) with the batch dim innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bwd_kernel(x_ref, z_ref, d_ref, o_ref, acc_ref, *, n_b: int,
                alpha: float):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    zf = z_ref[...].astype(jnp.float32)
    if alpha > 0.0:
        t = jnp.tanh(alpha * d_ref[...].astype(jnp.float32))
        zf = zf * (1.0 - t * t)
    xf = x_ref[...].astype(jnp.float32)          # (bb, bm) ±1
    acc_ref[...] += jax.lax.dot_general(
        xf, zf, (((0,), (0,)), ((), ())),         # contract batch dim
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == n_b - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("alpha", "block_m", "block_n", "block_b", "interpret"),
)
def boolean_weight_bwd(x: jax.Array, z: jax.Array, d: jax.Array, *,
                       alpha: float = 0.0,
                       block_m: int = 256, block_n: int = 256,
                       block_b: int = 256, interpret: bool = False) -> jax.Array:
    """G_W = Σ_k x[k,:]ᵀ ⊗ (z[k,:]·tanh'(α·d[k,:])).

    Args:
      x: (B, M) ±1 (int8 or float).  z: (B, N) upstream signal.
      d: (B, N) pre-activation minus threshold (ignored when alpha == 0).
    Returns (M, N) fp32 vote counts.
    """
    B, M = x.shape
    B2, N = z.shape
    if B != B2 or d.shape != z.shape:
        raise ValueError(f"shape mismatch x{x.shape} z{z.shape} d{d.shape}")

    bm, bn, bb = min(block_m, M), min(block_n, N), min(block_b, B)
    Mp, Np, Bp = -(-M // bm) * bm, -(-N // bn) * bn, -(-B // bb) * bb
    xp = jnp.pad(x, ((0, Bp - B), (0, Mp - M)))
    zp = jnp.pad(z, ((0, Bp - B), (0, Np - N)))
    dp = jnp.pad(d, ((0, Bp - B), (0, Np - N)))
    n_b = Bp // bb

    kernel = functools.partial(_bwd_kernel, n_b=n_b, alpha=alpha)
    yp = pl.pallas_call(
        kernel,
        name="boolean_weight_bwd",
        grid=(Mp // bm, Np // bn, n_b),
        in_specs=[
            pl.BlockSpec((bb, bm), lambda i, j, k: (k, i)),
            pl.BlockSpec((bb, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bb, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xp, zp, dp)
    return yp[:M, :N]
