"""Pallas TPU kernel: causal flash attention (the 32k-prefill hot spot).

The pure-JAX chunked flash in models/attention.py is the portable path;
this kernel is the TPU-native version: one (batch·head, q-block) program
scans KV blocks with the online-softmax recurrence entirely in VMEM, and
SKIPS fully-masked blocks structurally (k-grid iterates only j ≤ i via
masking at block granularity — the 2× causal waste of the masked-full
portable path disappears on the wall clock because masked blocks emit no
MXU work... on TPU; in interpret mode both paths compute).

Layout: q,k,v (BH, S, hd); blocks (bq, hd)/(bk, hd); fp32 m/l/acc scratch.
Optional sliding window and logit softcap (gemma2).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  n_k: int, bq: int, bk: int, scale: float, causal: bool,
                  window: int, softcap: float, seq_len: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = kj * bk

    # block-level causal/window skip: fully-masked blocks do no MXU work
    live = jnp.asarray(True)
    if causal:
        live &= k_start <= q_start + bq - 1
        if window > 0:
            live &= k_start + bk - 1 >= q_start - window + 1

    @pl.when(live)
    def _work():
        q = q_ref[0].astype(jnp.float32) * scale        # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = kpos < seq_len
        if causal:
            valid &= qpos >= kpos
        if window > 0:
            valid &= qpos - kpos < window
        s = jnp.where(valid, s, -1e30)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == n_k - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                     "interpret"),
)
def flash_attention_tpu(q, k, v, *, causal: bool = True, window: int = 0,
                        softcap: float = 0.0, block_q: int = 512,
                        block_k: int = 512, interpret: bool = False):
    """q,k,v: (BH, S, hd) -> (BH, S, hd). Causal online-softmax attention."""
    BH, S, hd = q.shape
    bq, bk = min(block_q, S), min(block_k, S)
    nq, nk = -(-S // bq), -(-S // bk)
    Sq, Sk = nq * bq, nk * bk
    qp = jnp.pad(q, ((0, 0), (0, Sq - S), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sk - S), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sk - S), (0, 0)))
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _flash_kernel, n_k=nk, bq=bq, bk=bk, scale=scale, causal=causal,
        window=window, softcap=softcap, seq_len=S)
    out = pl.pallas_call(
        kernel,
        name="flash_attention_tpu",
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :S]
