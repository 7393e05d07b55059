"""Plain float32 reference of the served Boolean-weight (B⊕LD) decoders.

Written from the layer equations with jax.numpy alone: no kernel, no
cache, no batching, no bucketing. It imports nothing of the program and
takes no array the program made: it makes every weight again, layer by
layer, from the seed (``weights.py``), and decodes the packed words itself.

The equations are the program's model definition (the paper's Boolean
linear layer inside each architecture), written once per family in
``families/<family>.py`` (found through ``family.py``); this module holds
what every family shares. They depart from the published float models in
these places, all noted in the configuration files:

* every projection is ``(x @ e(W)) / sqrt(fan_in) (+ b)`` with ±1 weights;
* the FFN is gated by the Boolean threshold activation: ``sign(g - tau)``
  (+1 at 0) times ``u``, in place of SiLU(g) * u;
* RMSNorm multiplies by ``(1 + scale)`` with eps 1e-6;
* each family's own departures, in its module.

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

import family
import weights as W

EPS = 1e-6
HI = jax.lax.Precision.HIGHEST


def unpack_pm1(words: jax.Array, k: int) -> jax.Array:
    """(..., Kw, N) uint32 -> (..., k, N) float32 of ±1; bit b of word j
    is row j*32+b, and a set bit is +1. Leading axes (experts) stay."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., :, None, :] >> shifts[:, None]) & jnp.uint32(1)
    pm1 = bits.astype(jnp.float32) * 2.0 - 1.0
    return pm1.reshape(*words.shape[:-2], -1, words.shape[-1])[..., :k, :]


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
    """The sizes the reference needs, read from a configuration file; the
    family's own sizes (``family.py``) are set on it beside the shared
    ones."""

    def __init__(self, cfg: dict, fam=None):
        prog = cfg["program"]
        self.fam = fam if fam is not None else family.of(cfg)
        self.recipes = family.recipes(self.fam)
        self.d = cfg["hidden_size"]
        self.layers = cfg["num_hidden_layers"]
        self.vocab = cfg["vocab_size"]
        self.vocab_rows = prog.get("vocab_padded", self.vocab)
        # the type the embedding and head are served (and so made) in
        self.table_dtype = jnp.dtype(prog.get("overrides", {}).get(
            "dtype", "bfloat16"))
        self.__dict__.update(self.fam.sizes(cfg))
        # stacked indices: one per layer unless the family stacks groups
        self.stack = getattr(self, "stack", self.layers)

    def layer_shapes(self):
        """{path: (one stacked index's shape, dtype, packed fan-in or 0)}."""
        return self.fam.layer_shapes(self)


def _short(path: str) -> str:
    """``['blocks']['b0']['attn']['wq']['w']`` -> ``wq.w``; a grouped
    family's other blocks keep their block: ``b1.wq.w``."""
    parts = [p.strip("[]'") for p in path.split("][")]
    name = ".".join(parts[-2:]) if parts[-1] in ("w", "b", "scale") \
        else parts[-1]
    if len(parts) > 2 and parts[0] == "blocks" and parts[1] != "b0":
        name = f"{parts[1]}.{name}"
    return name


@functools.partial(jax.jit, static_argnums=(0,))
def _make_layer(arch: Arch, seed_k, layer):
    return {_short(p): W.layer_leaf(seed_k, p, layer, s, dt, 0, k,
                                    arch.recipes)
            for p, (s, dt, k) in sorted(arch.layer_shapes().items())}


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(arch: Arch, w, h, pos, mode):
    return arch.fam.layer(arch, w, h, pos, mode)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(arch: Arch, seed_k, tokens):
    table = W.top_leaf(seed_k, "['embed']['table']",
                       (arch.vocab_rows, arch.d), arch.table_dtype, arch.d,
                       arch.recipes)
    return table[tokens].astype(jnp.float32) * math.sqrt(arch.d)


def final_hidden(arch: Arch, seed: int, seqs, mode: str = "f32",
                 pad_to: int = 0, devices=None):
    """Normed last hidden states of each token sequence (lists of ids),
    run one stacked index at a time over every sequence. Sequences are
    padded at the end to ``pad_to`` positions (one compiled shape);
    causality keeps the padding out of every real position. With
    ``devices``, sequence i runs on ``devices[i % len(devices)]`` (each
    index's weights are made on the first and copied), so the sequences
    run side by side; its states come back to the first device."""
    key = W.seed_key(seed)
    n = max(pad_to, max(len(s) for s in seqs))
    toks = np.zeros((len(seqs), n), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    emb = _embed(arch, key, jnp.asarray(toks))
    hs = [emb[i] for i in range(len(seqs))]
    pos = jnp.arange(n, dtype=jnp.int32)
    if devices:
        home = [devices[i % len(devices)] for i in range(len(seqs))]
        hs = [jax.device_put(h, d) for h, d in zip(hs, home)]
        pos_on = {d: jax.device_put(pos, d) for d in devices}
    for layer in range(arch.stack):
        w = _make_layer(arch, key, layer)
        if devices:
            w_on = {d: jax.device_put(w, d) for d in devices}
            hs = [_layer(arch, w_on[d], h, pos_on[d], mode)
                  for h, d in zip(hs, home)]
        else:
            hs = [_layer(arch, w, h, pos, mode) for h in hs]
    if devices:
        hs = [jax.device_put(h, devices[0]) for h in hs]
    fn = W.top_leaf(key, "['final_norm']['scale']", (arch.d,),
                    jnp.float32, arch.d, arch.recipes)
    return [_rms(h, fn) for h in hs]


@functools.partial(jax.jit, static_argnums=(0,))
def _head(arch: Arch, seed_k):
    return W.top_leaf(seed_k, "['head']['w']", (arch.d, arch.vocab_rows),
                      arch.table_dtype, arch.d, arch.recipes
                      ).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _logits(arch: Arch, head, h, mode):
    return _mm(h, head, mode)[:, :arch.vocab]


def served_gaps(arch: Arch, seed: int, requests, *, control: bool = False,
                block: int = 256, pad_to: int = 0, devices=None):
    """For each (prompt, served tokens) pair: the gap, at every served
    position, by which a token lies below the reference's best logit.

    ``control=False``: the token is the one the program served.
    ``control=True``: the token is the one the fp8 control puts first at
    that position (same prompt and served tokens as context).
    Returns one float32 numpy array of gaps per request. ``devices``:
    as in ``final_hidden``.
    """
    seqs = [list(p) + list(t[:-1]) for p, t in requests]
    starts = [len(p) - 1 for p, _ in requests]
    ref = final_hidden(arch, seed, seqs, "f32", pad_to, devices)
    ctl = final_hidden(arch, seed, seqs, "fp8", pad_to, devices) \
        if control else None
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
