"""Decode fast-path validation.

Four layers of evidence:
  1. pack_bits/unpack_bits round-trip (deterministic, no hypothesis needed);
  2. the thin-M packed-XNOR GEMV kernel against the pure-jnp oracles —
     exact counting parity on ±1 inputs, fp32 parity on real inputs;
  3. the fused scan-decode engine against the seed per-token loop
     (token-identical greedy + temperature outputs), plus the packed-weight
     serving mode against the int8 path on a dense arch (bit-exact there;
     SSM/MoE archs amplify 1-ulp bf16 reduction-order flips through the
     recurrence/top-k routing, so they get oracle coverage at kernel level
     instead);
  4. the paged decode step's structure: its jaxpr takes no layer-sized
     slice of the stacked KV pool and writes none back.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core import PackedBool, random_boolean
from repro.kernels import ops, ref
from repro.kernels.packed_xnor import pack_bits, unpack_bits
from repro.models import block_roles, lm_decode_step_paged, lm_init
from repro.serve import ServeEngine, pack_weights, paged_pool_init


# ---------------------------------------------------------------------------
# 1. packing round-trip
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("k", [1, 31, 32, 33, 64, 130])
def test_pack_unpack_roundtrip_axis_last(k):
    x = random_boolean(jax.random.PRNGKey(k), (4, k))
    packed = pack_bits(x, axis=-1)
    assert packed.dtype == jnp.uint32
    assert packed.shape == (4, -(-k // 32))
    np.testing.assert_array_equal(np.asarray(unpack_bits(packed, k, axis=-1)),
                                  np.asarray(x))


@pytest.mark.parametrize("k", [16, 40, 96])
def test_pack_unpack_roundtrip_contraction_axis(k):
    # axis=-2 is the layout pack_weights serves from: (k, n) -> (ceil(k/32), n)
    x = random_boolean(jax.random.PRNGKey(k), (k, 6))
    packed = pack_bits(x, axis=-2)
    assert packed.shape == (-(-k // 32), 6)
    np.testing.assert_array_equal(np.asarray(unpack_bits(packed, k, axis=-2)),
                                  np.asarray(x))


# ---------------------------------------------------------------------------
# 2. packed GEMV kernel vs oracle
# ---------------------------------------------------------------------------
GEMV_SHAPES = [(1, 64, 128), (2, 70, 9), (8, 512, 256), (3, 33, 130)]


@pytest.mark.parametrize("m,k,n", GEMV_SHAPES)
def test_packed_gemv_boolean_inputs_exact(m, k, n):
    """±1 activations: the GEMV must reproduce the XNOR counting oracle
    EXACTLY (integer counting embedded in fp32)."""
    x = random_boolean(jax.random.PRNGKey(m + k), (m, k))
    w = random_boolean(jax.random.PRNGKey(n + k), (k, n))
    y = ops.packed_xnor_gemv(x, pack_bits(w, axis=0), k_valid=k)
    np.testing.assert_array_equal(
        np.asarray(y).astype(np.int32),
        np.asarray(ref.packed_xnor_matmul_ref(x, w)))


@pytest.mark.parametrize("m,k,n", GEMV_SHAPES)
def test_packed_gemv_real_inputs(m, k, n):
    """Real activations (mixed-type Def 3.5): fp32 parity with x @ e(w)."""
    x = jax.random.normal(jax.random.PRNGKey(m), (m, k), jnp.float32)
    w = random_boolean(jax.random.PRNGKey(n), (k, n))
    y = ops.packed_xnor_gemv(x, pack_bits(w, axis=0), k_valid=k)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.packed_xnor_gemv_ref(x, w)),
                               rtol=1e-5, atol=1e-4)


def test_packed_wide_m_routes_to_dense_path_same_result():
    """Prefill-sized (wide-M) packed contractions unpack to the MXU dense
    path; result must match the thin-M GEMV kernel numerics-for-numerics."""
    from repro.core import boolean_dense_inference, pack_boolean_weight
    from repro.core.boolean_linear import PACKED_GEMV_MAX_M

    k, n = 64, 48
    w = random_boolean(jax.random.PRNGKey(0), (k, n))
    pw = pack_boolean_weight(w)
    x_wide = jax.random.normal(jax.random.PRNGKey(1),
                               (PACKED_GEMV_MAX_M + 8, k), jnp.float32)
    y_wide = boolean_dense_inference(x_wide, pw)
    np.testing.assert_allclose(np.asarray(y_wide),
                               np.asarray(ref.packed_xnor_gemv_ref(x_wide, w)),
                               rtol=1e-5, atol=1e-4)
    # thin slice through the kernel path agrees with the wide dense path
    y_thin = boolean_dense_inference(x_wide[:4], pw)
    np.testing.assert_allclose(np.asarray(y_thin), np.asarray(y_wide[:4]),
                               rtol=1e-5, atol=1e-4)


def test_packed_gemv_rejects_mismatched_k():
    x = jnp.zeros((2, 64), jnp.float32)
    w = jnp.zeros((2, 8), jnp.uint32)
    with pytest.raises(ValueError):
        ops.packed_xnor_gemv(x, w, k_valid=32)


def test_pack_weights_structure():
    cfg = get_smoke("gemma2-2b")
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    packed = pack_weights(params)
    b0 = jax.tree.map(lambda x: x, packed["blocks"]["b0"],
                      is_leaf=lambda x: isinstance(x, PackedBool))
    # q/k/v fused into one packed leaf; gate/up likewise
    assert "wqkv" in b0["attn"] and "wq" not in b0["attn"]
    assert isinstance(b0["attn"]["wqkv"]["w"], PackedBool)
    assert "wgu" in b0["ffn"] and "wg" not in b0["ffn"]
    assert isinstance(b0["ffn"]["wd"]["w"], PackedBool)
    # FP leaves (embed/head/norms) untouched
    assert packed["embed"]["table"].dtype == cfg.dtype
    assert packed["head"]["w"].dtype == cfg.dtype
    # packing density: 32 Booleans per uint32 word = 8× fewer bytes than the
    # int8 store (32× fewer than an fp32 view)
    pb = b0["attn"]["wqkv"]["w"]
    assert pb.bits.dtype == jnp.uint32
    assert pb.bits.shape[-2] == -(-cfg.d_model // 32)
    int8_bytes = sum(params["blocks"]["b0"]["attn"][n]["w"].nbytes
                     for n in ("wq", "wk", "wv"))
    assert int8_bytes // pb.bits.nbytes == 8


# ---------------------------------------------------------------------------
# 3. engine: fused scan decode vs the seed per-token loop
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["gemma2-2b", "falcon-mamba-7b"])
def test_scan_decode_matches_eager_greedy(arch):
    cfg = get_smoke(arch)
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, max_len=24)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab_size)
    out_scan = engine.generate(prompts, 8)
    out_eager = engine.generate_eager(prompts, 8)
    assert out_scan.shape == (2, 8)
    np.testing.assert_array_equal(np.asarray(out_scan), np.asarray(out_eager))


def test_scan_decode_matches_eager_temperature():
    """Sampled decode folds the key per step identically in both paths."""
    cfg = get_smoke("gemma2-2b")
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, max_len=20)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                                 cfg.vocab_size)
    key = jax.random.PRNGKey(7)
    out_scan = engine.generate(prompts, 6, temperature=0.8, key=key)
    out_eager = engine.generate_eager(prompts, 6, temperature=0.8, key=key)
    np.testing.assert_array_equal(np.asarray(out_scan), np.asarray(out_eager))


def test_temperature_is_traced_not_a_compile_key():
    """Per-request temperatures must reuse one compiled fn (only the
    greedy/sampled branch is static)."""
    cfg = get_smoke("gemma2-2b")
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, max_len=16)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                                 cfg.vocab_size)
    key = jax.random.PRNGKey(3)
    engine.generate(prompts, 4, temperature=0.7, key=key)
    engine.generate(prompts, 4, temperature=0.9, key=key)
    engine.generate(prompts, 4, temperature=1.3, key=key)
    assert len(engine._fns) == 1
    engine.generate(prompts, 4)             # greedy: one more variant only
    assert len(engine._fns) == 2


def test_donated_cache_reused_across_requests():
    """Back-to-back requests reuse (donate + return) the preallocated cache
    and stay deterministic — no per-request cache growth."""
    cfg = get_smoke("gemma2-2b")
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, max_len=24)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab_size)
    out1 = engine.generate(prompts, 8)
    assert 2 in engine._caches
    out2 = engine.generate(prompts, 8)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    # a different prompt after a long generation is unaffected by stale slots
    prompts2 = jax.random.randint(jax.random.PRNGKey(5), (2, 4), 0,
                                  cfg.vocab_size)
    out3 = engine.generate(prompts2, 4)
    out3_again = engine.generate(prompts2, 4)
    np.testing.assert_array_equal(np.asarray(out3), np.asarray(out3_again))


def test_packed_engine_matches_int8_on_dense_arch():
    """gemma2 smoke is reduction-order benign: packed-XNOR serving must be
    token-identical with the int8 path end to end."""
    cfg = get_smoke("gemma2-2b")
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab_size)
    out_int8 = ServeEngine(cfg, params, max_len=20).generate(prompts, 6)
    out_packed = ServeEngine(cfg, params, max_len=20,
                             packed=True).generate(prompts, 6)
    np.testing.assert_array_equal(np.asarray(out_int8), np.asarray(out_packed))


@pytest.mark.parametrize("arch", ["falcon-mamba-7b", "jamba-1.5-large-398b"])
def test_packed_engine_runs_on_ssm_and_hybrid(arch):
    """SSM/hybrid archs: packed serving must produce valid tokens (bitwise
    parity is not required — see module docstring)."""
    cfg = get_smoke(arch)
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                                 cfg.vocab_size)
    out = ServeEngine(cfg, params, max_len=16, packed=True).generate(prompts, 4)
    assert out.shape == (2, 4)
    assert (np.asarray(out) >= 0).all()
    assert (np.asarray(out) < cfg.vocab_size).all()


# ---------------------------------------------------------------------------
# 4. paged decode addresses the stacked pool in place
# ---------------------------------------------------------------------------
def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it (scan and
    jit bodies, cond branches, kernel bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("kernel,quant", [("1", False), ("0", False),
                                          ("1", True)],
                         ids=["kernel", "fallback", "kernel-int8"])
def test_paged_decode_takes_no_layer_slice_of_the_pool(monkeypatch, kernel,
                                                        quant):
    """The decode step's layer scan keeps the paged K/V pool stacked in its
    carry: no ``dynamic_slice`` takes a whole layer of a pool leaf, no
    ``dynamic_update_slice`` writes one back, and the new rows reach the
    stacked leaves as scatters (two per attention block: K and V)."""
    monkeypatch.setenv("REPRO_PAGED_KERNEL", kernel)
    cfg = get_smoke("gemma2-2b")
    if quant:
        cfg = cfg.scaled(kv_cache_quant=True)
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    lanes, n_pages, page = 4, 9, 4
    pool = paged_pool_init(cfg, lanes, n_pages, page)
    cache = {"blocks": pool,
             "block_table": jnp.zeros((lanes, 3), jnp.int32),
             "pos": jnp.zeros((lanes,), jnp.int32)}
    tok = jnp.zeros((lanes, 1), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, c, t: lm_decode_step_paged(cfg, p, c, t))(
            params, cache, tok).jaxpr

    stacked = pool["b0"]["k"].shape            # (G, n_pages, page, KVp, hd)
    layer = (1,) + stacked[1:]
    eqns = list(_eqns(jaxpr))
    slices = [e for e in eqns if e.primitive.name == "dynamic_slice"
              and tuple(e.params["slice_sizes"]) == layer]
    updates = [e for e in eqns if e.primitive.name == "dynamic_update_slice"
               and e.invars[1].aval.shape == layer]
    assert (len(slices), len(updates)) == (0, 0)
    scatters = [e for e in eqns if e.primitive.name == "scatter"
                and e.invars[0].aval.shape == stacked]
    assert len(scatters) == 2 * len(block_roles(cfg))
