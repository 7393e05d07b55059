"""Plain float32 reference of the served Boolean-weight (B⊕LD) decoders.

Written from the layer equations with jax.numpy alone: no kernel, no
cache, no batching, no bucketing. It imports nothing of the program and
takes no array the program made: it makes every weight again, layer by
layer, from the seed (``weights.py``), and decodes the packed words itself.

The equations are the program's model definition (the paper's Boolean
linear layer inside each architecture), which departs from the published
float models in these places, all noted in the configuration files:

* every projection is ``(x @ e(W)) / sqrt(fan_in) (+ b)`` with ±1 weights;
* the FFN is gated by the Boolean threshold activation: ``sign(g - tau)``
  (+1 at 0) times ``u``, in place of SiLU(g) * u;
* RMSNorm multiplies by ``(1 + scale)`` with eps 1e-6;
* qwen2.5-14b: the 40 query heads are laid out as 48 (the program pads to
  a multiple of 16); head h reads KV head h // 6, heads 40..47 are zero
  before the o-projection, whose fan-in (and scale) is 48 * 128;
* falcon-mamba-7b: Mamba-1 mixing, without Falcon-Mamba's extra RMS norms
  on B, C and dt.

``mode="f32"`` is the reference (float32 operands, HIGHEST matmul
precision). ``mode="fp8"`` is the control: the same mathematics with every
matmul operand rounded to float8_e4m3fn, the precision one step below the
configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

EPS = 1e-6
HI = jax.lax.Precision.HIGHEST


def unpack_pm1(words: jax.Array, k: int) -> jax.Array:
    """(Kw, N) uint32 -> (k, N) float32 of ±1; bit b of word j is row
    j*32+b, and a set bit is +1."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:, None, :] >> shifts[None, :, None]) & jnp.uint32(1)
    pm1 = bits.astype(jnp.float32) * 2.0 - 1.0
    return pm1.reshape(-1, words.shape[-1])[:k]


def fp8(x):
    """Round to the float8 e4m3 grid (3 mantissa bits, subnormal step
    2^-9, saturating at 448), kept in float32."""
    a = jnp.abs(x)
    step = 2.0 ** (jnp.floor(jnp.log2(jnp.maximum(a, 2.0 ** -6))) - 3)
    return jnp.sign(x) * jnp.minimum(jnp.round(a / step) * step, 448.0)


def _quant(x, mode):
    return fp8(x) if mode == "fp8" else x


def _mm(x, w, mode):
    return jnp.dot(_quant(x, mode), _quant(w, mode), precision=HI,
                   preferred_element_type=jnp.float32)


def _rms(x, scale):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * (1.0 + scale)


def _proj(x, words, k, mode, b=None):
    y = _mm(x, unpack_pm1(words, k), mode) / math.sqrt(k)
    return y if b is None else y + b


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class Arch:
    """The sizes the reference needs, read from a configuration file."""

    def __init__(self, cfg: dict):
        prog = cfg["program"]
        self.family = prog["family"]
        self.d = cfg["hidden_size"]
        self.layers = cfg["num_hidden_layers"]
        self.vocab = cfg["vocab_size"]
        self.vocab_rows = prog.get("vocab_padded", self.vocab)
        # the type the embedding and head are served (and so made) in
        self.table_dtype = jnp.dtype(prog.get("overrides", {}).get(
            "dtype", "bfloat16"))
        if self.family == "dense":
            self.heads = cfg["num_attention_heads"]
            self.kv = cfg["num_key_value_heads"]
            self.hd = prog.get("head_dim", self.d // self.heads)
            self.hp = prog["heads_padded"]
            self.ff = cfg["intermediate_size"]
            self.theta = float(cfg["rope_theta"])
        elif self.family == "ssm":
            self.di = cfg["intermediate_size"]
            self.n = cfg["state_size"]
            self.r = cfg["time_step_rank"]
            self.w = cfg["conv_kernel"]
        else:
            raise ValueError(f"reference has no family {self.family!r}")

    def layer_shapes(self):
        """{path: (per-layer shape, dtype, packed fan-in or 0)}."""
        d, blk = self.d, "['blocks']['b0']"
        f32 = jnp.float32
        if self.family == "dense":
            hq, hkv = self.hp * self.hd, self.kv * self.hd
            a, f = blk + "['attn']", blk + "['ffn']"
            return {
                blk + "['norm1']['scale']": ((d,), f32, 0),
                blk + "['norm2']['scale']": ((d,), f32, 0),
                a + "['wq']['w']": ((d // 32, hq), jnp.uint32, d),
                a + "['wq']['b']": ((hq,), f32, 0),
                a + "['wk']['w']": ((d // 32, hkv), jnp.uint32, d),
                a + "['wk']['b']": ((hkv,), f32, 0),
                a + "['wv']['w']": ((d // 32, hkv), jnp.uint32, d),
                a + "['wv']['b']": ((hkv,), f32, 0),
                a + "['wo']['w']": ((hq // 32, d), jnp.uint32, hq),
                f + "['wgu']['w']": ((d // 32, 2 * self.ff), jnp.uint32, d),
                f + "['wd']['w']": ((self.ff // 32, d), jnp.uint32,
                                    self.ff),
                f + "['tau']": ((self.ff,), f32, 0),
            }
        m = blk + "['mamba']"
        di, n, r = self.di, self.n, self.r
        return {
            blk + "['norm1']['scale']": ((d,), f32, 0),
            m + "['in_x']['w']": ((d // 32, di), jnp.uint32, d),
            m + "['in_z']['w']": ((d // 32, di), jnp.uint32, d),
            m + "['conv_w']": ((self.w, di), f32, 0),
            m + "['conv_b']": ((di,), f32, 0),
            m + "['x_proj']['w']": ((di // 32, r + 2 * n), jnp.uint32, di),
            m + "['dt_proj']['w']": ((-(-r // 32), di), jnp.uint32, r),
            m + "['dt_bias']": ((di,), f32, 0),
            m + "['A_log']": ((di, n), f32, 0),
            m + "['D']": ((di,), f32, 0),
            m + "['out_proj']['w']": ((di // 32, d), jnp.uint32, di),
        }


def _short(path: str) -> str:
    """``['blocks']['b0']['attn']['wq']['w']`` -> ``wq.w``."""
    parts = [p.strip("[]'") for p in path.split("][")]
    return ".".join(parts[-2:]) if parts[-1] in ("w", "b", "scale") \
        else parts[-1]


def _dense_layer(arch: Arch, w, h, pos, mode):
    d, hd, hp, kv = arch.d, arch.hd, arch.hp, arch.kv
    S = h.shape[0]
    x = _rms(h, w["norm1.scale"])
    q = _proj(x, w["wq.w"], d, mode, w["wq.b"]).reshape(S, hp, hd)
    k = _proj(x, w["wk.w"], d, mode, w["wk.b"]).reshape(S, kv, hd)
    v = _proj(x, w["wv.w"], d, mode, w["wv.b"]).reshape(S, kv, hd)
    q = _rope(q, pos, arch.theta)[:, :arch.heads]
    k = _rope(k, pos, arch.theta)
    group = jnp.arange(arch.heads) // (hp // kv)     # KV head of each head
    kh, vh = k[:, group], v[:, group]                 # (S, heads, hd)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.einsum("qhd,khd->hqk", _quant(q, mode), _quant(kh, mode),
                   precision=HI) / math.sqrt(hd)
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", _quant(p, mode), _quant(vh, mode),
                   precision=HI).reshape(S, arch.heads * hd)
    wo = unpack_pm1(w["wo.w"], hp * hd)[:arch.heads * hd]
    h = h + _mm(o, wo, mode) / math.sqrt(hp * hd)
    x = _rms(h, w["norm2.scale"])
    gu = _proj(x, w["wgu.w"], d, mode)
    g, u = gu[:, :arch.ff], gu[:, arch.ff:]
    a = jnp.where(g - w["tau"] >= 0, 1.0, -1.0)
    return h + _proj(a * u, w["wd.w"], arch.ff, mode)


def _ssm_layer(arch: Arch, w, h, pos, mode):
    del pos
    d, di, n, r = arch.d, arch.di, arch.n, arch.r
    S = h.shape[0]
    x = _rms(h, w["norm1.scale"])
    xin = _proj(x, w["in_x.w"], d, mode)
    z = _proj(x, w["in_z.w"], d, mode)
    cw = w["conv_w"]
    xp = jnp.concatenate([jnp.zeros((arch.w - 1, di), jnp.float32), xin])
    xconv = sum(xp[i:i + S] * cw[i] for i in range(arch.w)) + w["conv_b"]
    xc = jax.nn.silu(xconv)
    dbc = _proj(xc, w["x_proj.w"], di, mode)
    dt_r, bm, cm = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    dt = jax.nn.softplus(_proj(dt_r, w["dt_proj.w"], r, mode)
                         + w["dt_bias"])
    a = -jnp.exp(w["A_log"])                          # (di, n)

    def step(state, inp):
        dt_t, x_t, b_t, c_t = inp
        state = jnp.exp(dt_t[:, None] * a) * state \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return state, state @ c_t

    _, y = jax.lax.scan(step, jnp.zeros((di, n), jnp.float32),
                        (dt, xc, bm, cm))
    y = (y + w["D"] * xc) * jax.nn.silu(z)
    return h + _proj(y, w["out_proj.w"], di, mode)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _make_layer(arch_key, shapes_key, seed_k, layer):
    del arch_key
    return {_short(p): W.layer_leaf(seed_k, p, layer, s, dt, 0, k)
            for p, (s, dt, k) in shapes_key}


def _hashable_shapes(arch: Arch):
    return tuple((p, (tuple(s), dt, k))
                 for p, (s, dt, k) in sorted(arch.layer_shapes().items()))


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(arch: Arch, w, h, pos, mode):
    f = _dense_layer if arch.family == "dense" else _ssm_layer
    return f(arch, w, h, pos, mode)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(arch: Arch, seed_k, tokens):
    table = W.top_leaf(seed_k, "['embed']['table']",
                       (arch.vocab_rows, arch.d), arch.table_dtype, arch.d)
    return table[tokens].astype(jnp.float32) * math.sqrt(arch.d)


def final_hidden(arch: Arch, seed: int, seqs, mode: str = "f32",
                 pad_to: int = 0):
    """Normed last hidden states of each token sequence (lists of ids),
    run one layer at a time over every sequence. Sequences are padded at
    the end to ``pad_to`` positions (one compiled shape); causality keeps
    the padding out of every real position."""
    key = W.seed_key(seed)
    n = max(pad_to, max(len(s) for s in seqs))
    toks = np.zeros((len(seqs), n), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    emb = _embed(arch, key, jnp.asarray(toks))
    hs = [emb[i] for i in range(len(seqs))]
    pos = jnp.arange(n, dtype=jnp.int32)
    shapes = _hashable_shapes(arch)
    for layer in range(arch.layers):
        w = _make_layer(id(arch), shapes, key, layer)
        hs = [_layer(arch, w, h, pos, mode) for h in hs]
    fn = W.top_leaf(key, "['final_norm']['scale']", (arch.d,),
                    jnp.float32, arch.d)
    return [_rms(h, fn) for h in hs]


@functools.partial(jax.jit, static_argnums=(0,))
def _head(arch: Arch, seed_k):
    return W.top_leaf(seed_k, "['head']['w']", (arch.d, arch.vocab_rows),
                      arch.table_dtype, arch.d).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _logits(arch: Arch, head, h, mode):
    return _mm(h, head, mode)[:, :arch.vocab]


def served_gaps(arch: Arch, seed: int, requests, *, control: bool = False,
                block: int = 256, pad_to: int = 0):
    """For each (prompt, served tokens) pair: the gap, at every served
    position, by which a token lies below the reference's best logit.

    ``control=False``: the token is the one the program served.
    ``control=True``: the token is the one the fp8 control puts first at
    that position (same prompt and served tokens as context).
    Returns one float32 numpy array of gaps per request.
    """
    seqs = [list(p) + list(t[:-1]) for p, t in requests]
    starts = [len(p) - 1 for p, _ in requests]
    ref = final_hidden(arch, seed, seqs, "f32", pad_to)
    ctl = final_hidden(arch, seed, seqs, "fp8", pad_to) if control else None
    head = _head(arch, W.seed_key(seed))
    out = []
    for i, (p, t) in enumerate(requests):
        gaps = []
        for b0 in range(0, len(t), block):
            rows = jnp.arange(block) + starts[i] + b0     # fixed shape
            n = min(block, len(t) - b0)
            lg = _logits(arch, head, jnp.take(ref[i], rows, axis=0,
                                              mode="clip"), "f32")[:n]
            best = jnp.max(lg, axis=-1)
            if control:
                pick = jnp.argmax(_logits(
                    arch, head, jnp.take(ctl[i], rows, axis=0, mode="clip"),
                    "fp8")[:n], axis=-1)
            else:
                pick = jnp.asarray(t[b0:b0 + n], jnp.int32)
            got = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
            gaps.append(np.asarray(best - got))
        out.append(np.concatenate(gaps))
    return out
