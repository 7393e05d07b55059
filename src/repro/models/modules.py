"""Model substrate: config dataclass, functional param system, shared modules.

No flax: parameters are nested dicts of arrays; every init helper returns a
``(param, PartitionSpec)`` pair and ``unzip`` splits a tree of such pairs
into a params tree + a sharding-spec tree of identical structure. Boolean
weights are int8 ±1 leaves (that is also the optimizer's routing rule).

Mesh axes referenced by specs: "pod", "data", "model" (see launch/mesh.py).
Logical use: batch → ("pod","data");  TP dims (heads, d_ff, experts,
d_inner, vocab) → "model".
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import (PackedBool, boolean_activation, boolean_dense,
                        boolean_dense_inference, random_boolean)

MODEL_AXIS = "model"
# FSDP: the non-TP dimension of every large weight shards over "data" —
# XLA all-gathers per layer inside the scan (freed after use) and
# reduce-scatters the per-layer grads. Weights stay replicated across
# "pod" (hybrid FSDP: no DCN gathers on the critical path).
FSDP_AXIS = "data"


def batch_spec(cfg, *rest) -> P:
    """PartitionSpec with dim0 = the config's batch axes."""
    axes = cfg.batch_axes if cfg.batch_axes else None
    return P(axes, *rest)


def constrain(cfg, x, spec: P):
    """with_sharding_constraint against the launcher-installed mesh;
    disabled outside a mesh (smoke tests)."""
    if not cfg.use_sharding_constraints:
        return x
    from jax.sharding import NamedSharding

    from repro.distributed import get_mesh

    return jax.lax.with_sharding_constraint(x, NamedSharding(get_mesh(), spec))


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | ssm | moe | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // n_heads

    # attention flavour
    qkv_bias: bool = False
    sliding_window: int = 0        # >0 enables local attention layers
    alt_local_global: bool = False # gemma2: alternate local/global
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    rope_theta: float = 10_000.0

    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_dense_residual: bool = False   # arctic: dense FFN in parallel w/ MoE
    moe_every: int = 1                 # apply MoE FFN on blocks with idx % moe_every == moe_offset
    moe_offset: int = 0
    capacity_factor: float = 1.25
    moe_impl: str = "einsum"           # einsum (GShard baseline) | scatter (hillclimbed)
    dense_ff: int = 0                  # width of the non-MoE dense FFN (hybrid/arctic)

    # SSM (mamba-1)
    ssm_state: int = 0
    d_inner: int = 0               # 0 -> 2*d_model
    conv_width: int = 4
    dt_rank: int = 0               # 0 -> d_model // 16

    # hybrid (jamba)
    group_size: int = 1            # layers scanned per group
    attn_index: int = -1           # which in-group index is attention (jamba)

    # B⊕LD knobs
    boolean: bool = True           # Boolean projections (int8 weights)
    act_boolean: bool = True       # threshold activation in FFN hidden
    sign_backward: bool = False    # 1-bit inter-layer backprop signal
    bwd_norm: bool = True          # App-C.4 variance normalization

    # frontend
    frontend: str = "tokens"       # tokens | embeddings (audio/vlm stub)

    # numerics / memory
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "full"     # full | save_block_outs (§Perf: skips
    # re-running the forward TP psums during backward recompute, at
    # 2·(B,S,D)/layer of extra saved activations)
    long_context: bool = False     # eligible for long_500k (ssm/hybrid)
    attn_chunk: int = 1024         # flash-attention KV chunk

    # distribution (set by the launcher; defaults run mesh-free on CPU)
    batch_axes: Tuple[str, ...] = ("data",)
    cache_seq_axes: Tuple[str, ...] = ()   # decode cells: cache seq sharding
    use_sharding_constraints: bool = False
    moe_groups: int = 1            # routing groups (= batch shards) for MoE capacity
    kv_cache_quant: bool = False   # int8 KV cache (BOLD-quantized dataflow)
    serve_tp: int = 1              # serve-time tensor parallelism over the
    # head axis: the paged decode/prefill graphs run under shard_map on a
    # 1-D ("model",) mesh with hp/kvp divided by serve_tp per device, a
    # shard-offset head mask, and an all-gather of the head activations
    # before the REPLICATED o-projection (attention._wo_project — a
    # gather, not a row-shard psum, so the fan-in reduction order matches
    # the unsharded graph exactly; sign() amplifies reassociation ulps
    # into token flips). serve_tp == 1 is bit-identical to the unsharded
    # graph — the TP branches are skipped entirely at trace time.
    decode_chunk: int = 512        # flash-decode inner chunk over local seq
    # (also the paged decode kernel's VMEM staging rows: 512 keeps a
    # v5e lane at 8 KV heads x hd 128 well inside the scoped VMEM limit)
    ssm_chunk: int = 128           # selective-scan chunk (train/prefill)
    reduce_bf16: bool = False      # bf16 cross-shard matmul partials (§Perf)
    block_grad_barriers: bool = False  # barrier between in-group blocks:
    # the transposed barrier splits backward grad all-reduces per block so
    # XLA's AllReduceCombiner cannot keep every block's full-D fp32 weight
    # grads live simultaneously (§Perf: jamba train memory)

    # -- derived -----------------------------------------------------------
    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def heads_padded(self, axis_size: int = 16) -> int:
        """Q heads padded up to a multiple of the TP axis (padded heads are
        masked to zero post-attention; Boolean weights cannot be zeroed)."""
        return -(-self.n_heads // axis_size) * axis_size

    def kv_heads_padded(self, axis_size: int = 16) -> int:
        if self.n_kv_heads >= axis_size:
            return -(-self.n_kv_heads // axis_size) * axis_size
        return self.n_kv_heads  # replicated over model axis instead

    @property
    def vocab_padded(self) -> int:
        return -(-self.vocab_size // 256) * 256

    @property
    def d_inner_(self) -> int:
        return self.d_inner or 2 * self.d_model

    @property
    def dt_rank_(self) -> int:
        return self.dt_rank or max(self.d_model // 16, 1)

    @property
    def n_groups(self) -> int:
        assert self.n_layers % self.group_size == 0
        return self.n_layers // self.group_size

    @property
    def dense_ff_(self) -> int:
        return self.dense_ff or self.d_ff

    def scaled(self, **overrides) -> "ModelConfig":
        return dataclasses.replace(self, **overrides)


# ---------------------------------------------------------------------------
# (param, spec) tree plumbing
# ---------------------------------------------------------------------------
def _is_pair(x):
    return (isinstance(x, tuple) and len(x) == 2
            and isinstance(x[1], (P, type(None))))


def unzip(tree):
    """Tree of (array, PartitionSpec) -> (params, specs)."""
    params = jax.tree.map(lambda t: t[0], tree, is_leaf=_is_pair)
    specs = jax.tree.map(lambda t: t[1] if t[1] is not None else P(),
                         tree, is_leaf=_is_pair)
    return params, specs


def bool_weight(key, shape, spec: P):
    """Native Boolean int8 ±1 weight (paper's randint init, Alg 4)."""
    return (random_boolean(key, shape), spec)


def fp_weight(key, shape, spec: P, scale: float = 1.0, dtype=jnp.float32):
    w = jax.random.normal(key, shape, jnp.float32) * scale
    return (w.astype(dtype), spec)


def fp_zeros(shape, spec: P, dtype=jnp.float32):
    return (jnp.zeros(shape, dtype), spec)


def fp_ones(shape, spec: P, dtype=jnp.float32):
    return (jnp.ones(shape, dtype), spec)


# ---------------------------------------------------------------------------
# Projection dispatch: Boolean (paper) or FP (baseline) — one call site.
# ---------------------------------------------------------------------------
def proj_init(key, cfg: ModelConfig, d_in: int, d_out: int, spec: P,
              bias: bool = False):
    """A linear projection: Boolean int8 (B⊕LD) or bf16 FP (baseline)."""
    p = {}
    if cfg.boolean:
        p["w"] = bool_weight(key, (d_in, d_out), spec)
    else:
        p["w"] = fp_weight(key, (d_in, d_out), spec,
                           scale=1.0 / math.sqrt(d_in), dtype=cfg.dtype)
    if bias:
        bias_spec = P(spec[-1]) if len(spec) else P()
        p["b"] = fp_zeros((d_out,), bias_spec, dtype=jnp.float32)
    return p


def proj_apply(cfg: ModelConfig, p, x, *, scale: Optional[float] = None):
    """Apply a projection. Boolean path: mixed-type counting GEMM via the
    B⊕LD custom-vjp, then the deterministic 1/√fan_in pre-activation
    normalizer (App C.3 — one scalar per tensor, no FP latents)."""
    w = p["w"]
    b = p.get("b")
    if isinstance(w, PackedBool):
        # Serving fast path: bit-packed weight words stream from HBM and the
        # GEMV kernel reconstructs the ±1 view in VMEM (no int8 copy
        # resident). fp32 counting output, then the same 1/√fan_in scale.
        y = boolean_dense_inference(x, w).astype(x.dtype)
        s = (1.0 / math.sqrt(w.k)) if scale is None else scale
        y = y * jnp.asarray(s, y.dtype)
        if b is not None:
            y = y + b.astype(y.dtype)
        return y
    if w.dtype == jnp.int8:
        # bf16 ±1 view is produced by train_step; if we are called with the
        # raw int8 leaf (eval/serve), view it here.
        w = w.astype(cfg.dtype)
    if w.dtype != x.dtype:
        w = w.astype(x.dtype)
    if cfg.boolean:
        y = boolean_dense(x, w, None, cfg.bwd_norm, cfg.sign_backward,
                          cfg.reduce_bf16)
        s = (1.0 / math.sqrt(w.shape[0])) if scale is None else scale
        y = y * jnp.asarray(s, y.dtype)
        if b is not None:
            y = y + b.astype(y.dtype)
        return y
    pref = x.dtype if cfg.reduce_bf16 else jnp.float32
    y = jnp.dot(x, w, preferred_element_type=pref).astype(x.dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Norms / rotary / embeddings
# ---------------------------------------------------------------------------
def rmsnorm_init(d: int):
    return {"scale": fp_ones((d,), P(None))}


def rmsnorm_apply(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + p["scale"].astype(jnp.float32))).astype(x.dtype)


def rope(x, positions, theta: float):
    """Rotary embedding. x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freq  # (..., S, half)
    ang = ang[..., None, :]                                   # (..., S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def embed_init(key, cfg: ModelConfig):
    return {"table": fp_weight(key, (cfg.vocab_padded, cfg.d_model),
                               P(MODEL_AXIS, FSDP_AXIS), scale=0.02,
                               dtype=cfg.dtype)}


def embed_apply(cfg: ModelConfig, p, tokens):
    return jnp.take(p["table"], tokens, axis=0) * math.sqrt(cfg.d_model)


def head_init(key, cfg: ModelConfig):
    # Last layer stays FP (paper's standard setup).
    return {"w": fp_weight(key, (cfg.d_model, cfg.vocab_padded),
                           P(FSDP_AXIS, MODEL_AXIS),
                           scale=1.0 / math.sqrt(cfg.d_model),
                           dtype=cfg.dtype)}


def head_apply(cfg: ModelConfig, p, x):
    logits = jnp.dot(x, p["w"], preferred_element_type=jnp.float32)
    if cfg.final_logit_softcap > 0:
        c = cfg.final_logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits  # fp32 (B, S, vocab_padded)


def softcap(x, cap: float):
    return cap * jnp.tanh(x / cap) if cap > 0 else x
