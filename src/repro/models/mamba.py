"""Mamba-1 block (falcon-mamba, jamba) with Boolean projections.

The selective-scan recurrence itself stays FP (DESIGN.md
§Arch-applicability: it is an elementwise gated recurrence, not a counting
GEMM); the four projections around it — in_proj, x_proj, dt_proj, out_proj,
≈97% of block FLOPs — carry Boolean weights.

Train/prefill: chunked selective scan — ``lax.scan`` over sequence chunks
carrying the (B, d_inner, N) state, ``associative_scan`` within a chunk.
TP: d_inner sharded over "model"; the recurrence is elementwise over
d_inner, so shards scan independently (zero comm inside the recurrence).

Decode: O(1) single-step state update (this is why falcon-mamba/jamba are
the long_500k-eligible architectures).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .modules import (FSDP_AXIS, MODEL_AXIS, ModelConfig, batch_spec,
                      constrain, fp_weight, fp_zeros, proj_apply, proj_init,
                      rmsnorm_apply)

SSM_CHUNK = 128


def mamba_init(key, cfg: ModelConfig):
    D, DI, N, R = cfg.d_model, cfg.d_inner_, cfg.ssm_state, cfg.dt_rank_
    ks = jax.random.split(key, 8)
    # S4D-real initialization for A; dt bias so softplus(dt) spans
    # [1e-3, 1e-1] (standard mamba init).
    A = jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (DI, N))
    dt = jnp.exp(jax.random.uniform(ks[0], (DI,), jnp.float32)
                 * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))  # inverse softplus
    return {
        # separate x / z halves keep each output dim cleanly TP-sharded
        "in_x": proj_init(ks[1], cfg, D, DI, P(FSDP_AXIS, MODEL_AXIS)),
        "in_z": proj_init(ks[6], cfg, D, DI, P(FSDP_AXIS, MODEL_AXIS)),
        "conv_w": fp_weight(ks[2], (cfg.conv_width, DI), P(None, MODEL_AXIS),
                            scale=1.0 / math.sqrt(cfg.conv_width)),
        "conv_b": fp_zeros((DI,), P(MODEL_AXIS)),
        "x_proj": proj_init(ks[3], cfg, DI, R + 2 * N,
                            P(MODEL_AXIS, None)),
        "dt_proj": proj_init(ks[4], cfg, R, DI, P(FSDP_AXIS, MODEL_AXIS)),
        "dt_bias": (dt_bias, P(MODEL_AXIS)),
        "A_log": (jnp.log(A), P(MODEL_AXIS, None)),
        "D": fp_ones_di(DI),
        "out_proj": proj_init(ks[5], cfg, DI, D, P(MODEL_AXIS, FSDP_AXIS)),
        **({"dt_norm": (jnp.zeros((R,), jnp.float32), P(None)),
            "b_norm": (jnp.zeros((N,), jnp.float32), P(None)),
            "c_norm": (jnp.zeros((N,), jnp.float32), P(None))}
           if cfg.ssm_norms else {}),
    }


def fp_ones_di(di):
    return (jnp.ones((di,), jnp.float32), P(MODEL_AXIS))


def _causal_conv(x, w, b, conv0=None):
    """Depthwise causal conv over seq. x: (B,S,DI); w: (W,DI).

    ``conv0`` (B,W-1,DI), optional: the last W-1 inputs BEFORE this
    sequence (a cached-prefix boundary state) — they replace the zero
    left-padding so a tail continues the conv exactly where the prefix
    left off."""
    W = w.shape[0]
    if conv0 is None:
        xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([conv0.astype(x.dtype), x], axis=1)
    out = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None, :]
              for i in range(W))
    return out + b[None, None, :]


def _ssm_params(cfg: ModelConfig, p, xc):
    """xc: (..., DI) conv-activated input -> (dt, Bmat, Cmat).

    With ``cfg.ssm_norms`` (jamba) the dt rank slice, B and C are each
    RMS-normed with a learned gain (``dt_norm``/``b_norm``/``c_norm``,
    applied as ``rmsnorm_apply``'s 1 + scale) before ``dt_proj`` and the
    scan, in prefill and decode alike."""
    N, R = cfg.ssm_state, cfg.dt_rank_
    dbc = proj_apply(cfg, p["x_proj"], xc)
    dt_r, Bm, Cm = jnp.split(dbc, [R, R + N], axis=-1)
    if cfg.ssm_norms:
        dt_r = rmsnorm_apply({"scale": p["dt_norm"]}, dt_r)
        Bm = rmsnorm_apply({"scale": p["b_norm"]}, Bm)
        Cm = rmsnorm_apply({"scale": p["c_norm"]}, Cm)
    dt = proj_apply(cfg, p["dt_proj"], dt_r)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    return dt, Bm.astype(jnp.float32), Cm.astype(jnp.float32)


def _scan_chunk(carry, chunk):
    """One chunk of the selective scan.

    carry: h (B, DI, N) fp32.
    chunk: (decay (B,Q,DI,N), xbar (B,Q,DI,N)) where
           decay = exp(dt·A), xbar = dt·B·x.
    """
    h0 = carry
    decay, xbar = chunk

    def op(a, b):
        (d1, x1), (d2, x2) = a, b
        return (d1 * d2, d2 * x1 + x2)

    dcum, xcum = jax.lax.associative_scan(op, (decay, xbar), axis=1)
    h = dcum * h0[:, None] + xcum             # (B,Q,DI,N)
    return h[:, -1], h


def mamba_ssm(cfg: ModelConfig, p, xc, dt, Bm, Cm, h0=None,
              chunk: int = SSM_CHUNK, return_hs: bool = False):
    """Selective scan. xc: (B,S,DI); dt: (B,S,DI); Bm/Cm: (B,S,N).

    Returns (y (B,S,DI), h_final (B,DI,N)); with ``return_hs`` also the
    per-position states hs (B,S,DI,N) — ``hs[:, t]`` is the state after
    consuming token t (already materialized for the y einsum, so exposing
    it costs nothing).
    """
    Bsz, S, DI = xc.shape
    N = cfg.ssm_state
    A = -jnp.exp(p["A_log"].astype(jnp.float32))          # (DI,N)
    # keep the (B,S,DI,N) scan tensors batch×DI sharded — the elementwise
    # mix of batch-sharded dt and 2D-sharded A otherwise resolves to
    # replicated DI under SPMD (4 GB/tensor/device at jamba scale — §Perf)
    spec4 = batch_spec(cfg, None, MODEL_AXIS, None)
    decay = constrain(cfg, jnp.exp(dt[..., None] * A[None, None]), spec4)
    xbar = constrain(
        cfg, (dt * xc.astype(jnp.float32))[..., None] * Bm[:, :, None, :],
        spec4)

    Q = min(chunk, S)
    nq = -(-S // Q)
    Sp = nq * Q
    pad = ((0, 0), (0, Sp - S), (0, 0), (0, 0))
    # decay=1, xbar=0 padding keeps the state unchanged on padded steps.
    decay = jnp.pad(decay, pad, constant_values=1.0)
    xbar = jnp.pad(xbar, pad)
    decay = decay.reshape(Bsz, nq, Q, DI, N).transpose(1, 0, 2, 3, 4)
    xbar = xbar.reshape(Bsz, nq, Q, DI, N).transpose(1, 0, 2, 3, 4)

    if h0 is None:
        h0 = jnp.zeros((Bsz, DI, N), jnp.float32)
    h_last, hs = jax.lax.scan(_scan_chunk, h0, (decay, xbar))
    hs = constrain(cfg,
                   hs.transpose(1, 0, 2, 3, 4).reshape(Bsz, Sp, DI, N)[:, :S],
                   spec4)
    y = jnp.einsum("bsdn,bsn->bsd", hs, Cm,
                   preferred_element_type=jnp.float32)
    y = y + p["D"].astype(jnp.float32)[None, None] * xc.astype(jnp.float32)
    if return_hs:
        return y.astype(xc.dtype), h_last, hs
    return y.astype(xc.dtype), h_last


def mamba_apply(cfg: ModelConfig, p, x, h0=None, conv0=None,
                return_state: bool = False, length=None, state_at=None):
    """Train/prefill mamba block body. x: (B,S,D).

    ``length`` (traced scalar, optional): true sequence length when ``x`` is
    right-padded to a compile bucket. Padded steps are frozen out of the
    recurrence (dt=0 => decay=1, input=0 — the same identity element
    ``mamba_ssm`` already pads chunks with), and the returned conv state is
    sliced at ``length`` instead of the padded tail, so the state tuple is
    bit-identical to running the unpadded sequence.

    ``h0``/``conv0``: initial recurrence state and conv history (the
    boundary state of a cached prefix) — the sequence then continues
    exactly where the prefix left off instead of from zeros.

    ``state_at`` ((n,) int32 token counts, optional, may be traced): also
    return ``{"h": (B,n,DI,N), "conv": (B,n,W-1,DI)}`` — the state after
    consuming the first ``b`` tokens, for each ``b`` in ``state_at``
    (prefix-cache boundary snapshots). Positions past ``length`` hold the
    frozen state at ``length`` (the recurrence identity) and garbage conv
    rows, and a ``b`` of 0 pads the vector; callers discard them. Free
    beyond the gathers: the per-position states already exist for the
    output einsum.
    """
    DI = cfg.d_inner_
    W = cfg.conv_width
    xin = proj_apply(cfg, p["in_x"], x)
    z = proj_apply(cfg, p["in_z"], x)
    xconv = _causal_conv(xin, p["conv_w"].astype(jnp.float32),
                         p["conv_b"], conv0=conv0).astype(x.dtype)
    xc = jax.nn.silu(xconv.astype(jnp.float32)).astype(x.dtype)
    dt, Bm, Cm = _ssm_params(cfg, p, xc)
    if length is not None:
        live = jnp.arange(x.shape[1]) < jnp.asarray(length, jnp.int32)
        dt = jnp.where(live[None, :, None], dt, 0.0)
    y, h_last, *hs = mamba_ssm(cfg, p, xc, dt, Bm, Cm, h0,
                               chunk=cfg.ssm_chunk,
                               return_hs=state_at is not None)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    out = proj_apply(cfg, p["out_proj"], y)
    if not return_state:
        return out
    # conv history: conv0 (or zeros) prepended, so rows [b-W+1, b) of the
    # full input stream live at xp[:, b : b+W-1] for ANY b, including the
    # dynamic ``length`` slice and the static ``state_at`` snapshots.
    if conv0 is None:
        xp = jnp.pad(xin, ((0, 0), (W - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([conv0.astype(xin.dtype), xin], axis=1)
    if length is None:
        conv_state = xp[:, x.shape[1]:x.shape[1] + W - 1, :]
    else:
        conv_state = jax.lax.dynamic_slice_in_dim(
            xp, jnp.asarray(length, jnp.int32), W - 1, axis=1)
    if state_at is None:
        return out, (h_last, conv_state)
    at = jnp.asarray(state_at, jnp.int32)
    snaps = {
        "h": jnp.take(hs[0], jnp.maximum(at - 1, 0), axis=1),
        "conv": jnp.take(xp, at[:, None] + jnp.arange(W - 1), axis=1),
    }
    return out, (h_last, conv_state), snaps


def mamba_cache_init(cfg: ModelConfig, batch: int):
    DI, N, W = cfg.d_inner_, cfg.ssm_state, cfg.conv_width
    b_ax = cfg.batch_axes if cfg.batch_axes else None
    return ({"h": jnp.zeros((batch, DI, N), jnp.float32),
             "conv": jnp.zeros((batch, W - 1, DI), jnp.float32)},
            {"h": P(b_ax, MODEL_AXIS, None),
             "conv": P(b_ax, None, MODEL_AXIS)})


def mamba_cache_lane_write(pool, state, lane):
    """Write one request's prefilled SSM state into scheduler lane ``lane``
    of the lane-indexed pool (continuous batching; SSM state is O(1) per
    lane so it is never paged — admission is a single lane write, eviction
    just abandons the lane).

    pool leaves: (n_groups, lanes, ...); state leaves: (n_groups, 1, ...)
    from a batch-1 prefill.
    """
    return jax.tree.map(
        lambda full, s: full.at[:, lane].set(s[:, 0].astype(full.dtype)),
        pool, state)


def mamba_decode(cfg: ModelConfig, p, x, cache):
    """One-token decode. x: (B,1,D); cache: {h (B,DI,N), conv (B,W-1,DI)}."""
    B = x.shape[0]
    DI, N, W = cfg.d_inner_, cfg.ssm_state, cfg.conv_width
    xin = proj_apply(cfg, p["in_x"], x)[:, 0]             # (B,DI)
    z = proj_apply(cfg, p["in_z"], x)[:, 0]

    conv_hist = jnp.concatenate(
        [cache["conv"], xin[:, None].astype(cache["conv"].dtype)], axis=1)
    w = p["conv_w"].astype(jnp.float32)                   # (W,DI)
    xconv = jnp.sum(conv_hist * w[None], axis=1) + p["conv_b"][None]
    xc = jax.nn.silu(xconv).astype(x.dtype)               # (B,DI)

    dt, Bm, Cm = _ssm_params(cfg, p, xc[:, None])
    dt, Bm, Cm = dt[:, 0], Bm[:, 0], Cm[:, 0]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    decay = jnp.exp(dt[..., None] * A[None])              # (B,DI,N)
    h = decay * cache["h"] + (dt * xc.astype(jnp.float32))[..., None] \
        * Bm[:, None, :]
    y = jnp.einsum("bdn,bn->bd", h, Cm) \
        + p["D"].astype(jnp.float32)[None] * xc.astype(jnp.float32)
    y = y.astype(x.dtype) * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    out = proj_apply(cfg, p["out_proj"], y[:, None])
    new_cache = {"h": h, "conv": conv_hist[:, 1:]}
    return out, new_cache
