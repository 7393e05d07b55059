"""Random Boolean weights made from ``--seed``, on the device.

Every weight is named by its path in the served parameter tree (for
example ``['blocks']['b0']['attn']['wq']['w']``) and, for a stacked block
leaf, by its layer. Its values are a function of (seed, path, layer) alone,
so the whole tree can be made in one jitted call for the program, and one
layer at a time for the reference, and both see the same numbers.

Boolean projections are made directly as packed uint32 words, 32 weights
to a word along the fan-in (bit b of word j is weight j*32+b, 1 is +1):
each bit is a fair coin, which is the ±1 weight of the paper's random
init. No int8 tree is ever built. Real leaves (norm scales, biases,
thresholds, SSM parameters, the bf16 embedding and head) follow the
recipes in ``RECIPES``, keyed by the last name of the path; a family adds
its own (``family.recipes``). A packed leaf may have axes before its
``(Kw, N)`` words, such as experts, drawn with the rest of the leaf.

The gated Boolean FFN gets tied weights: the gate and up halves of the
fused ``wgu`` leaf are the same random words and the threshold ``tau`` is
0, so the FFN computes ``sign(g) * g = |g|``. With independent halves the
activation ``sign(g - tau) * u`` jumps wherever ``g`` crosses ``tau``; a
rounding error e then moves the output by about sqrt(e), and after a few
of 48 layers the bfloat16 program, the float32 reference and any control
disagree completely, so no comparison of logits could tell a sound run
from a broken one. Tied, the FFN is continuous and the comparison
separates them. The program does the same work on these weights as on any
others: every word is still a fair coin to the kernels.

This module imports nothing of the program.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (PRNGKey keeps only 32)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)


def leaf_key(key: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def packed_bits(key, shape, k: int) -> jax.Array:
    """(..., Kw, N) uint32 words of fair-coin weights; bits past ``k`` are
    0."""
    words = jax.random.bits(key, shape, jnp.uint32)
    if k % 32:
        kw = jnp.arange(shape[-2])[:, None]
        valid = jnp.clip(k - kw * 32, 0, 32).astype(jnp.uint32)
        mask = jnp.where(valid >= 32, jnp.uint32(0xFFFFFFFF),
                         (jnp.uint32(1) << valid) - jnp.uint32(1))
        words = words & mask
    return words


def _normal(scale, dtype=jnp.float32):
    def make(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * scale
                ).astype(dtype)
    return make


def _dt_bias(key, shape):
    # softplus(dt_bias) log-uniform in [1e-3, 1e-1], the usual mamba init
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    return dt + jnp.log(-jnp.expm1(-dt))


def _a_log(key, shape):
    # S4D-real A = -(1..N) per channel, perturbed so channels differ
    n = shape[-1]
    base = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
    return base + 0.1 * jax.random.normal(key, shape, jnp.float32)


def _conv_w(key, shape):
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[0])


RECIPES = {
    "scale": _normal(0.1),              # RMSNorm gain is (1 + scale)
    "b": _normal(0.1),                  # projection bias
    "tau": lambda k, s: jnp.zeros(s, jnp.float32),   # see module doc
    "conv_b": _normal(0.1),
    "D": lambda k, s: 1.0 + 0.1 * jax.random.normal(k, s, jnp.float32),
    "dt_bias": _dt_bias,
    "A_log": _a_log,
    "conv_w": _conv_w,
}


TIED = "['wgu']['w']"      # fused [gate | up] columns of the gated FFN


def real_leaf(key, path: str, shape, dtype, d_model: int,
              recipes=None) -> jax.Array:
    """One real-valued leaf (one layer's slice for a stacked leaf), by the
    recipe of its last name in ``recipes`` (``RECIPES`` if None)."""
    recipes = RECIPES if recipes is None else recipes
    name = path.rsplit("['", 1)[-1].rstrip("']")
    if path.startswith("['embed']"):
        return _normal(0.02, dtype)(key, shape)
    if path.startswith("['head']"):
        return _normal(1.0 / math.sqrt(d_model), dtype)(key, shape)
    if name not in recipes:
        raise KeyError(f"no weight recipe for leaf {path}")
    return recipes[name](key, shape).astype(dtype)


def layer_leaf(seed_k, path: str, layer, shape, dtype, d_model: int,
               k: int = 0, recipes=None) -> jax.Array:
    """Layer ``layer`` of a stacked leaf: packed words when ``k`` > 0."""
    key = jax.random.fold_in(leaf_key(seed_k, path), layer)
    if k and path.endswith(TIED):
        half = packed_bits(key, tuple(shape[:-1]) + (shape[-1] // 2,), k)
        return jnp.concatenate([half, half], axis=-1)
    if k:
        return packed_bits(key, shape, k)
    return real_leaf(key, path, shape, dtype, d_model, recipes)


def top_leaf(seed_k, path: str, shape, dtype, d_model: int,
             recipes=None) -> jax.Array:
    """A leaf outside the layer stack (embedding, head, final norm)."""
    return real_leaf(leaf_key(seed_k, path), path, shape, dtype, d_model,
                     recipes)


def make_stacked(seed_k, path: str, n_layers: int, shape, dtype,
                 d_model: int, k: int = 0, recipes=None) -> jax.Array:
    """All layers of a stacked leaf, equal to ``layer_leaf`` layer by
    layer (random draws under vmap match the unbatched ones)."""
    return jax.vmap(lambda l: layer_leaf(seed_k, path, l, shape, dtype,
                                         d_model, k, recipes)
                    )(jnp.arange(n_layers))
