"""1-bit gradient compression with error feedback (EF-SignSGD).

The paper's own convergence abstraction (Appendix A.2) IS EF-SignSGD:
Q₀ = stochastic sign, Q₁ = flip-threshold, e_t = error accumulator. At pod
scale the Boolean vote aggregation (Eq 7) distributes naturally: each data
shard contributes a ±1 **vote per weight**, so the cross-replica all-reduce
can carry int8 signs instead of fp32 partial sums — 4× less DP traffic
before bit-packing (32× packed; the int8 payload is what XLA's all-reduce
supports natively).

Usage: wrap the hybrid optimizer —
    opt = ef_signsgd_compressed(hybrid_optimizer(...), cfg.batch_axes)
and compute per-shard gradients with pmean DISABLED on the boolean subtree
(shard_map region). The error-feedback residual lives in the optimizer
state, bounding the compression bias (Lemma A.9: E‖e_t‖² ≤ 2γ/(1−γ)²·η²σ²).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.optimizer import Optimizer, is_boolean_leaf


class EFState(NamedTuple):
    inner: object
    error: object          # per-leaf error feedback residual (bf16)


def compress_votes(g, error, axes: Tuple[str, ...]):
    """Inside shard_map: e-corrected sign + int8 psum + residual update."""
    corrected = g + error.astype(g.dtype)
    sign = jnp.where(corrected >= 0, 1, -1).astype(jnp.int8)
    # vote count across replicas (Boolean aggregation, Eq 7)
    votes = jax.lax.psum(sign.astype(jnp.int32), axes)
    n = jax.lax.axis_size(axes)  # replica count over the reduced axes
    decoded = votes.astype(jnp.float32) / n
    scale = jnp.mean(jnp.abs(corrected))          # per-leaf magnitude
    decoded = decoded * scale
    new_error = (corrected - sign.astype(g.dtype) * scale).astype(jnp.bfloat16)
    return decoded, new_error


def ef_signsgd_compressed(inner: Optimizer, axes: Tuple[str, ...],
                          mesh=None) -> Optimizer:
    """Optimizer wrapper: boolean-leaf gradients arrive UN-reduced per data
    shard; this wrapper compresses + vote-reduces them (int8 payload) with
    error feedback, then delegates to the inner optimizer."""

    def init(params):
        err = jax.tree.map(
            lambda p: (jnp.zeros(p.shape, jnp.bfloat16)
                       if is_boolean_leaf(p) else None), params)
        return EFState(inner.init(params), err)

    def update(grads, state, params):
        from repro.distributed import get_mesh
        m = mesh or get_mesh()

        def leaf(g, e, p):
            if e is None:
                return g, None
            spec = jax.sharding.PartitionSpec(*([None] * g.ndim))
            dec, new_e = jax.shard_map(
                lambda gg, ee: compress_votes(gg, ee, axes),
                mesh=m, in_specs=(spec, spec), out_specs=(spec, spec),
                check_vma=False)(g, e)
            return dec, new_e

        out = jax.tree.map(
            leaf, grads, state.error, params,
            is_leaf=lambda x: x is None)
        dec = jax.tree.map(lambda t: t[0], out,
                           is_leaf=lambda x: isinstance(x, tuple))
        err = jax.tree.map(lambda t: t[1], out,
                           is_leaf=lambda x: isinstance(x, tuple))
        new_params, inner_state = inner.update(dec, state.inner, params)
        return new_params, EFState(inner_state, err)

    return Optimizer(init, update)
