"""Compile the serving kernels for a described TPU v5e, without a chip.

Each test lowers one Pallas kernel at qwen2.5-14b serving widths for one
chip of a described ``v5e:2x2`` topology, and asserts the compiled program
calls it as a Mosaic kernel (``tpu_custom_call``). This catches what the
interpreter-mode tests cannot: a tiling the compiler refuses, a kernel
that overflows its scoped VMEM, an op Mosaic cannot lower.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, so the worker that runs
this file loads it and every other worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.packed_xnor import packed_xnor_gemv
from repro.kernels.paged_attention import (paged_flash_decode,
                                           paged_prefix_attention)

CFG = get_config("qwen2.5-14b")
LANES, MAX_LEN, PAGE = 8, 4096, 16
HP, KVP, HD = CFG.heads_padded(), CFG.kv_heads_padded(), CFG.head_dim_
D, FF = CFG.d_model, CFG.d_ff

# the packed GEMVs one decode step issues, as (K, N): q heads are padded
# from 40 to 48 (the Boolean TP axis), q/k/v keep their own leaves (their
# biases keep pack_weights from fusing them), gate/up fuse into one
GEMV_SHAPES = {
    "wq": (D, HP * HD),
    "wk_wv": (D, KVP * HD),
    "wo": (HP * HD, D),
    "wgu": (D, 2 * FF),
    "wd": (FF, D),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args, **kw) -> str:
    return fn.lower(*args, **kw).compile().as_text()


@pytest.mark.parametrize("name", sorted(GEMV_SHAPES))
def test_packed_gemv_compiles(one_chip, name):
    K, N = GEMV_SHAPES[name]
    x = jax.ShapeDtypeStruct((LANES, K), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((-(-K // 32), N), jnp.uint32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(packed_xnor_gemv, x, w,
                                               k_valid=K)


def _pools(one_chip, quant: bool, layers=()):
    """Pool leaves of one layer, or stacked over ``layers`` = (G,)."""
    n_pages = LANES * (MAX_LEN // PAGE) + 1
    dt = jnp.int8 if quant else CFG.dtype
    pool = jax.ShapeDtypeStruct(layers + (n_pages, PAGE, KVP, HD), dt,
                                sharding=one_chip)
    scale = jax.ShapeDtypeStruct(layers + (n_pages, PAGE, KVP), jnp.float32,
                                 sharding=one_chip)
    return pool, (scale if quant else None)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_decode_compiles_at_4096_positions(one_chip, quant):
    pool, scale = _pools(one_chip, quant, layers=(CFG.n_groups,))
    q = jax.ShapeDtypeStruct((LANES, KVP, HP // KVP, HD), CFG.dtype,
                             sharding=one_chip)
    bt = jax.ShapeDtypeStruct((LANES, MAX_LEN // PAGE), jnp.int32,
                              sharding=one_chip)
    pos = jax.ShapeDtypeStruct((LANES,), jnp.int32, sharding=one_chip)
    layer = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(
        paged_flash_decode, q, pool, pool, bt, pos, layer, scale, scale,
        chunk=CFG.decode_chunk)


def test_paged_prefix_compiles_at_1024_prefix_512_tail(one_chip):
    pool, _ = _pools(one_chip, False)
    T, npp = 512, 1024 // PAGE

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    scalar = sds((), jnp.int32)
    assert "tpu_custom_call" in _compiled_text(
        paged_prefix_attention, sds((HP, T, HD), CFG.dtype),
        sds((T, KVP, HD), CFG.dtype), sds((T, KVP, HD), CFG.dtype),
        pool, pool, sds((npp,), jnp.int32), scalar, scalar, scalar,
        n_rep=HP // KVP, chunk=CFG.attn_chunk)


# jamba2-3b (AI21-Jamba2-3B) at its published widths: d 2560, Mamba d_inner
# 5120, state 16, dt rank 160; 20 query heads of 128 laid out as 32 over
# one KV head, which attention holds as two copies (16 query heads each);
# FFN 8192; 32 lanes x 6,144 positions, two attention layers
J_D, J_DI, J_FF, J_LANES, J_LEN = 2560, 5120, 8192, 32, 6144
J_HP, J_KV = 32, 2
J_GEMVS = {"in_x": (J_D, J_DI), "x_proj": (J_DI, 160 + 2 * 16),
           "dt_proj": (160, J_DI), "out_proj": (J_DI, J_D),
           "wqkv": (J_D, (J_HP + 2) * HD), "wo": (J_HP * HD, J_D),
           "wgu": (J_D, 2 * J_FF), "wd": (J_FF, J_D)}


@pytest.mark.parametrize("name", sorted(J_GEMVS))
def test_jamba_gemv_compiles(one_chip, name):
    K, N = J_GEMVS[name]
    x = jax.ShapeDtypeStruct((J_LANES, K), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((-(-K // 32), N), jnp.uint32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(packed_xnor_gemv, x, w,
                                               k_valid=K)


def _jamba_pool(one_chip, layers=()):
    n_pages = J_LANES * (J_LEN // PAGE) + 1
    return jax.ShapeDtypeStruct(layers + (n_pages, PAGE, J_KV, HD),
                                jnp.bfloat16, sharding=one_chip)


def test_jamba_mqa_paged_decode_compiles(one_chip):
    """One KV head held twice: each copy's rows stage as a tile-aligned
    (page, 2, hd) block (a single head's (page, 1, hd) is refused)."""
    pool = _jamba_pool(one_chip, layers=(2,))

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q = sds((J_LANES, J_KV, J_HP // J_KV, HD), jnp.bfloat16)
    assert "tpu_custom_call" in _compiled_text(
        paged_flash_decode, q, pool, pool, sds((J_LANES, J_LEN // PAGE)),
        sds((J_LANES,)), sds(()), chunk=512)


def test_jamba_mqa_paged_prefix_compiles(one_chip):
    """A doc-qa hit: 4,096 cached positions and a 1,024-row tail, 16 query
    heads a KV copy, in 512-row chunks (1,024 overruns scoped VMEM)."""
    pool = _jamba_pool(one_chip)
    T, npp = 1024, 4096 // PAGE

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    assert "tpu_custom_call" in _compiled_text(
        paged_prefix_attention, sds((J_HP, T, HD), jnp.bfloat16),
        sds((T, J_KV, HD), jnp.bfloat16), sds((T, J_KV, HD), jnp.bfloat16),
        pool, pool, sds((npp,)), sds(()), sds(()), sds(()),
        n_rep=J_HP // J_KV, chunk=512)
