"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(the ops wrappers run the kernel bodies in the interpreter on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import random_boolean
from repro.kernels import ops, ref
from repro.kernels.packed_xnor import pack_bits, unpack_bits


def _bool(key, shape):
    return random_boolean(jax.random.PRNGKey(key), shape)


# ---------------------------------------------------------------------------
# boolean_matmul (int8 MXU GEMM)
# ---------------------------------------------------------------------------
SHAPES = [
    (8, 16, 8),           # tiny, sub-block
    (128, 128, 128),      # exactly one block
    (256, 512, 384),      # multi-block K
    (100, 130, 70),       # ragged, forces padding
    (1, 256, 8),          # decode-like thin M
]


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_boolean_matmul_matches_ref(m, k, n):
    x = _bool(m * 3 + n, (m, k))
    w = _bool(k + 1, (k, n))
    y = ops.boolean_matmul(x, w, block_m=128, block_n=128, block_k=128)
    y_ref = ref.boolean_matmul_ref(x, w)
    assert y.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


@pytest.mark.parametrize("tau", [0.0, 3.0, -5.0])
def test_boolean_matmul_fused_threshold(tau):
    x = _bool(0, (64, 96))
    w = _bool(1, (96, 48))
    y = ops.boolean_matmul(x, w, fuse_threshold=True, tau=tau,
                           block_m=64, block_n=64, block_k=64)
    y_ref = ref.boolean_matmul_ref(x, w, fuse_threshold=True, tau=tau)
    assert y.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


@settings(max_examples=10)
@given(st.integers(1, 64), st.integers(1, 96), st.integers(1, 64),
       st.integers(0, 2 ** 16))
def test_boolean_matmul_hypothesis(m, k, n, seed):
    x = _bool(seed, (m, k))
    w = _bool(seed + 1, (k, n))
    y = ops.boolean_matmul(x, w, block_m=32, block_n=32, block_k=32)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(ref.boolean_matmul_ref(x, w)))


def test_boolean_matmul_output_range():
    # Counting outputs lie in [-K, K] with parity of K.
    m, k, n = 16, 33, 16
    x, w = _bool(5, (m, k)), _bool(6, (k, n))
    y = np.asarray(ops.boolean_matmul(x, w, block_m=16, block_n=16, block_k=32))
    assert np.all(np.abs(y) <= k)
    assert np.all((y - k) % 2 == 0)


# ---------------------------------------------------------------------------
# pack/unpack + packed XNOR popcount GEMM
# ---------------------------------------------------------------------------
@settings(max_examples=10)
@given(st.integers(1, 130), st.integers(0, 2 ** 16))
def test_pack_unpack_roundtrip(k, seed):
    x = _bool(seed, (4, k))
    packed = pack_bits(x, axis=-1)
    assert packed.dtype == jnp.uint32
    assert packed.shape == (4, -(-k // 32))
    back = unpack_bits(packed, k, axis=-1)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_pack_bits_axis0():
    x = _bool(3, (40, 6))
    packed = pack_bits(x, axis=0)
    assert packed.shape == (2, 6)
    back = unpack_bits(packed, 40, axis=0)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


PACKED_SHAPES = [(16, 32, 16), (64, 256, 64), (33, 70, 9), (1, 512, 128)]


@pytest.mark.parametrize("m,k,n", PACKED_SHAPES)
def test_packed_xnor_matches_ref(m, k, n):
    x = _bool(m + k, (m, k))
    w = _bool(n + k, (k, n))
    xp = pack_bits(x, axis=-1)
    wp = pack_bits(w, axis=0)
    y = ops.packed_xnor_matmul(xp, wp, k_valid=k,
                               block_m=32, block_n=32, block_kw=4)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(ref.packed_xnor_matmul_ref(x, w)))


def test_packed_equals_int8_kernel():
    # The two kernel families implement the same Boolean algebra.
    m, k, n = 24, 100, 20
    x, w = _bool(11, (m, k)), _bool(12, (k, n))
    y8 = ops.boolean_matmul(x, w, block_m=32, block_n=32, block_k=64)
    yp = ops.packed_xnor_matmul(pack_bits(x, -1), pack_bits(w, 0), k_valid=k,
                                block_m=32, block_n=32, block_kw=2)
    np.testing.assert_array_equal(np.asarray(y8), np.asarray(yp))


# ---------------------------------------------------------------------------
# fused weight-backward kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,m,n,alpha", [
    (32, 16, 24, 0.0),
    (100, 64, 32, 0.05),
    (7, 130, 5, 0.2),
])
def test_boolean_weight_bwd_matches_ref(b, m, n, alpha):
    x = _bool(b, (b, m))
    z = jax.random.normal(jax.random.PRNGKey(b + 1), (b, n), jnp.float32)
    d = jax.random.normal(jax.random.PRNGKey(b + 2), (b, n), jnp.float32) * 10
    y = ops.boolean_weight_bwd(x, z, d, alpha=alpha,
                               block_m=64, block_n=64, block_b=64)
    y_ref = ref.boolean_weight_bwd_ref(x, z, d, alpha=alpha)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)


def test_weight_bwd_equals_autodiff_votes():
    # The kernel computes the same votes as the custom_vjp path (Eq 5/7).
    from repro.core import boolean_dense
    b, m, n = 16, 32, 8
    x = _bool(0, (b, m)).astype(jnp.float32)
    w = _bool(1, (m, n)).astype(jnp.float32)
    z = jax.random.normal(jax.random.PRNGKey(2), (b, n))
    _, pb = jax.vjp(lambda w_: boolean_dense(x, w_, None, bwd_norm=False), w)
    gw, = pb(z)
    y = ops.boolean_weight_bwd(x.astype(jnp.int8), z, jnp.zeros_like(z),
                               alpha=0.0, block_m=32, block_n=32, block_b=16)
    np.testing.assert_allclose(np.asarray(y), np.asarray(gw), rtol=1e-4,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# Pallas flash attention (TPU-native prefill hot spot)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("s,hd,causal,window,softcap", [
    (128, 64, True, 0, 0.0),
    (256, 64, True, 0, 50.0),       # gemma2 softcap
    (256, 64, True, 64, 0.0),       # sliding window
    (96, 32, False, 0, 0.0),        # ragged, non-causal
])
def test_flash_attention_kernel_matches_ref(s, hd, causal, window, softcap):
    key = jax.random.PRNGKey(s + hd)
    q = jax.random.normal(key, (2, s, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, s, hd), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, s, hd), jnp.float32)
    out = ops.flash_attention_tpu(q, k, v, causal=causal, window=window,
                                  softcap=softcap, block_q=64, block_k=64)
    expected = ref.flash_attention_ref(q, k, v, causal=causal,
                                       window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_kernel_matches_model_flash():
    """Kernel == the portable pure-JAX chunked flash in models/attention."""
    from repro.models.attention import flash_attention as jnp_flash
    B, S, H, hd = 2, 128, 2, 32
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, S, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, hd), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, hd), jnp.float32)
    portable = jnp_flash(q, k, v, causal=True, chunk=64)
    fused = ops.flash_attention_tpu(
        q.transpose(0, 2, 1, 3).reshape(B * H, S, hd),
        k.transpose(0, 2, 1, 3).reshape(B * H, S, hd),
        v.transpose(0, 2, 1, 3).reshape(B * H, S, hd),
        causal=True, block_q=64, block_k=64)
    fused = fused.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(portable),
                               rtol=2e-4, atol=2e-4)


def test_kernel_mode_follows_the_backend(monkeypatch):
    """The ops wrappers interpret on the CPU backend and compile on a TPU
    one, decided at call time from ``jax.default_backend()`` — no
    environment variable takes part."""
    import inspect

    from repro.kernels import packed_xnor as px

    seen = {}

    def spy(x, w, *, k_valid, **kw):
        seen.update(kw)
        return x

    monkeypatch.setattr(px, "packed_xnor_gemv", spy)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")     # no longer read
    assert jax.default_backend() == "cpu"
    ops.packed_xnor_gemv(jnp.zeros((1, 32)), jnp.zeros((1, 8), jnp.uint32),
                         k_valid=32)
    assert seen["interpret"] is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ops.packed_xnor_gemv(jnp.zeros((1, 32)), jnp.zeros((1, 8), jnp.uint32),
                         k_valid=32)
    assert seen["interpret"] is False
    from repro.kernels import (boolean_bwd, boolean_matmul, flash_attention,
                               packed_xnor, paged_attention)
    for mod in (ops, boolean_bwd, boolean_matmul, flash_attention,
                packed_xnor, paged_attention):
        src = inspect.getsource(mod)
        assert "environ" not in src and "interpret: bool = True" not in src
