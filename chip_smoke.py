"""Smoke run of the packed-Boolean paged serving path on a TPU.

    python chip_smoke.py              # one chip: the full check below
    python chip_smoke.py --shards 4   # four chips: tensor-parallel session
                                      # vs the unsharded engine

One process drives qwen2.5-14b at its published widths, cut to 8 of its 48
layers, with random Boolean weights from ``--seed``, through the entry
points a user calls: ``ServeEngine(packed=True, prefix_cache=True)`` and
``engine.session()`` — the scheduler, the paged pools, the packed-XNOR GEMV
and the paged-attention kernels. It checks:

  * every request ends DONE with its full budget of in-vocab tokens, at
    least one request is a partial prefix-cache hit (its tail prefill runs
    ``paged_prefix_attention``), and a second pass over the same traffic
    reproduces every stream;
  * each Pallas kernel against its own XLA reference, on the chip, at the
    run's shapes, within the tolerance stated beside the check;
  * the compiled decode segment calls Mosaic kernels (``tpu_custom_call``),
    so no kernel ran in the interpreter.

With ``--shards 4`` it runs only the tensor-parallel session on a 4-chip
serve mesh (no prefix cache: the mesh refuses it) and requires its greedy
streams to equal the unsharded engine's on one chip of the same process.

The last line of stdout is one JSON object, ``{"ok": true, "device": ...}``;
any failed check raises, so the process exits non-zero and prints no result.
A machine without a TPU fails at once.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2.5-14b"
N_LAYERS = 8              # of 48: what one 16 GB chip holds beside the pool
MAX_LEN = 4096
LANES = 8
PAGE = 16
SEGMENT = 4
GEN = 32
N_REQUESTS = 16
SHARED_PREFIX = 1024      # tokens shared by half the prompts (64 pages)
BUCKETS = (512, 1024, 2048)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def make_traffic(vocab: int, n: int = N_REQUESTS, *,
                 shared_len: int = SHARED_PREFIX, lo: int = 512,
                 hi: int = 2048, tail: tuple = (257, 512),
                 seed: int = 0) -> list:
    """Seeded prompts: even requests share one ``shared_len`` prefix plus a
    unique tail of ``tail`` tokens, odd ones are unique lengths in
    [lo, hi]. The first lane wave holds shared request 0; the shared
    requests admitted after it finishes are partial prefix hits."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, (shared_len,))
    prompts = []
    for i in range(n):
        if i % 2 == 0:
            t = rng.integers(0, vocab, (int(rng.integers(*tail,
                                                         endpoint=True)),))
            p = np.concatenate([shared, t])
        else:
            p = rng.integers(0, vocab, (int(rng.integers(lo, hi,
                                                         endpoint=True)),))
        prompts.append(p.astype(np.int32))
    return prompts


def serve(engine, prompts, *, gen: int = GEN, lanes: int = LANES,
          page_size: int = PAGE, segment: int = SEGMENT,
          buckets=BUCKETS, prefix_cache: bool = True) -> dict:
    """Run one session to idle over ``prompts`` (greedy, ``gen`` tokens
    each) and check every request finished with ``gen`` in-vocab tokens.
    Returns the streams, the wall time and the prefix-cache counters."""
    from repro.serve import RequestStatus, SamplingParams

    t0 = time.perf_counter()
    with engine.session(lanes=lanes, page_size=page_size, segment=segment,
                        buckets=buckets, prefix_cache=prefix_cache) as sess:
        handles = [sess.submit(p, SamplingParams(max_tokens=gen))
                   for p in prompts]
        sess.run_until_idle()
        streams = [np.asarray(h.tokens_so_far(), np.int64) for h in handles]
        prefix = dict(sess.prefix.stats) if sess.prefix is not None else None
        pool_key = sess._pool_key
    seconds = time.perf_counter() - t0
    bad = [(i, h.status.name, h.error) for i, h in enumerate(handles)
           if h.status is not RequestStatus.DONE]
    if bad:
        raise AssertionError(f"requests not DONE: {bad}")
    vocab = engine.cfg.vocab_size
    for i, s in enumerate(streams):
        if s.shape != (gen,) or s.min() < 0 or s.max() >= vocab:
            raise AssertionError(f"request {i}: stream {s.tolist()} is not "
                                 f"{gen} tokens in [0, {vocab})")
    return {"streams": streams, "seconds": seconds, "prefix": prefix,
            "pool_key": pool_key}


def _max_err(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float32)
                               - np.asarray(want, np.float32))))


def _check(name: str, err: float, tol: float) -> None:
    log(f"kernel check {name}: max |kernel - reference| = {err:.3e} "
        f"(tolerance {tol:.3e})")
    if not err <= tol:
        raise AssertionError(f"{name}: error {err} above tolerance {tol}")


def check_kernels(engine, *, lanes: int = LANES, max_len: int = MAX_LEN,
                  page: int = PAGE, prefix_len: int = SHARED_PREFIX,
                  tail: int = 512, seed: int = 0) -> None:
    """Each kernel against its own XLA reference, on the default device,
    at the serving run's shapes. The references run at float32 matmul
    precision; the tolerances bound what the kernels' MXU arithmetic may
    legitimately differ by, and each sits well below the error of one
    wrong weight bit, page or mask row."""
    import jax
    import jax.numpy as jnp

    from repro.core import PackedBool
    from repro.kernels import ops, unpack_bits
    from repro.models import attention as A

    cfg = engine.cfg
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))
    hi = jax.default_matmul_precision("highest")

    # packed-XNOR GEMV vs the unpacked dot, at every packed projection of
    # block 0. Products of ±1 weights and bf16 activations are exact, so
    # only the float32 summation order differs: ~sqrt(K)·2^-24 of the row's
    # L1 norm in practice. 2^-13·L1 sits under the change one wrong weight
    # bit makes (2|x_k| ≈ 2·L1/K ≥ 1.4e-4·L1 at K ≤ 13824).
    blk = engine.params["blocks"]["b0"]
    leaves = jax.tree_util.tree_flatten_with_path(
        blk, is_leaf=lambda x: isinstance(x, PackedBool))[0]
    packed = [(jax.tree_util.keystr(p), w) for p, w in leaves
              if isinstance(w, PackedBool)]
    if not packed:
        raise AssertionError("engine holds no packed weights")
    for name, w in packed:
        bits = w.bits[0]                              # layer group 0
        x = jax.random.normal(next(keys), (lanes, w.k), jnp.bfloat16)
        got = ops.packed_xnor_gemv(x, bits, k_valid=w.k)
        with hi:
            want = jnp.dot(x.astype(jnp.float32),
                           unpack_bits(bits, w.k, axis=0).astype(jnp.float32))
        l1 = jnp.sum(jnp.abs(x.astype(jnp.float32)), axis=1, keepdims=True)
        err = float(jnp.max(jnp.abs(got - want) / l1))
        _check(f"packed_xnor_gemv {name} {w.k}->{bits.shape[1]} "
               "(error / row L1)", err, 2.0 ** -13)

    # random pools at the run's geometry: lanes × max_len rows of pages
    hp, kvp, hd = cfg.heads_padded(), cfg.kv_heads_padded(), cfg.head_dim_
    R = hp // kvp
    C = max_len // page
    n_pages = lanes * C + 1
    kp = jax.random.normal(next(keys), (n_pages, page, kvp, hd), jnp.bfloat16)
    vp = jax.random.normal(next(keys), (n_pages, page, kvp, hd), jnp.bfloat16)
    vmax = float(jnp.max(jnp.abs(vp.astype(jnp.float32))))

    # paged_flash_decode vs the block-table gather + _flash_decode_local.
    # The kernel may round the softmax weights to bf16 for the MXU
    # (relative 2^-9 each); the output is a convex combination of v rows,
    # so it may move by 2^-9·max|v|. Allow 2^-8·max|v| for the score and
    # exp roundings on top.
    bt = (jax.random.permutation(next(keys), n_pages - 1)[:lanes * C] + 1
          ).reshape(lanes, C).astype(jnp.int32)
    pos = jnp.asarray(np.linspace(max_len - 1, 0, lanes).astype(np.int32))
    q = jax.random.normal(next(keys), (lanes, kvp, R, hd), jnp.bfloat16)
    # the kernel reads layer 1 of a two-layer stack whose other layer
    # holds the other pool, so a wrong layer index reads wrong rows
    got = ops.paged_flash_decode(q, jnp.stack([vp, kp]), jnp.stack([kp, vp]),
                                 bt, pos, 1, chunk=cfg.decode_chunk)

    @jax.jit
    def decode_ref(q, kp, vp, bt, pos):
        k = kp[bt].reshape(lanes, C * page, kvp, hd)
        v = vp[bt].reshape(lanes, C * page, kvp, hd)
        _, l, acc = A._flash_decode_local(cfg, q, k, v, pos, 0, local=False)
        return acc / jnp.maximum(l[..., None], 1e-30)

    with hi:
        want = decode_ref(q, kp, vp, bt, pos)
    _check(f"paged_flash_decode {lanes} lanes x {max_len} positions",
           _max_err(got, want), 2.0 ** -8 * vmax)

    # paged_prefix_attention vs gather_prefix_kv + flash_attention_abs,
    # both in the serving dtype (bf16 in, bf16 out, as lm_prefill uses
    # them). Both round the softmax weights and the output to bf16, so a
    # one-ulp flip at the largest output is allowed: 2^-7·max|out|.
    npp = prefix_len // page
    page_ids = bt[0, :npp]
    qt = jax.random.normal(next(keys), (1, tail, hp, hd), jnp.bfloat16)
    kt = jax.random.normal(next(keys), (1, tail, kvp, hd), jnp.bfloat16)
    vt = jax.random.normal(next(keys), (1, tail, kvp, hd), jnp.bfloat16)
    length = tail - 12            # the tail bucket is padded past its end
    got = ops.paged_prefix_attention(
        qt[0].transpose(1, 0, 2), kt[0], vt[0], kp, vp, page_ids,
        prefix_len, prefix_len, length, n_rep=R, chunk=cfg.attn_chunk)
    got = got.transpose(1, 0, 2)[None].astype(jnp.bfloat16)

    @jax.jit
    def prefix_ref(qt, kt, vt, kp, vp, page_ids):
        pre = A.gather_prefix_kv(cfg, {"k": kp[None], "v": vp[None]},
                                 page_ids)
        pk = A._repeat_kv(pre["k"][0].astype(qt.dtype), R)
        pv = A._repeat_kv(pre["v"][0].astype(qt.dtype), R)
        positions = prefix_len + jnp.arange(tail, dtype=jnp.int32)
        return A.flash_attention_abs(
            qt, jnp.concatenate([pk, A._repeat_kv(kt, R)], axis=1),
            jnp.concatenate([pv, A._repeat_kv(vt, R)], axis=1),
            q_pos=positions,
            k_pos=jnp.concatenate(
                [jnp.arange(npp * page, dtype=jnp.int32), positions]),
            k_valid=jnp.concatenate([jnp.arange(npp * page) < prefix_len,
                                     jnp.arange(tail) < length]),
            chunk=cfg.attn_chunk)

    with hi:
        want = prefix_ref(qt, kt, vt, kp, vp, page_ids)
    omax = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
    _check(f"paged_prefix_attention prefix {prefix_len} + tail {tail}",
           _max_err(got, want), 2.0 ** -7 * omax)


def _segment_hlo(engine, pool_key) -> str:
    keys = [k for k in engine._fns
            if k[0] == "segment" and k[1] == pool_key]
    if not keys:
        raise AssertionError("no compiled decode segment")
    return engine._fns[keys[0]].as_text()


def _gib(n) -> str:
    return f"{n / 2**30:.2f} GiB"


def _nbytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def build(cfg, seed: int, mesh=None):
    """Random Boolean weights from ``seed``, packed into a serving engine
    (the int8 tree is dropped once packed). Prints the memory reckoning."""
    import jax

    from repro.core import PackedBool
    from repro.models import lm_init
    from repro.serve import ServeEngine, paged_pool_init

    params = jax.jit(lambda k: lm_init(k, cfg)[0])(jax.random.PRNGKey(seed))
    fp = _nbytes({k: params[k] for k in ("embed", "head")})
    blocks = _nbytes(params["blocks"])
    engine = ServeEngine(cfg, params, max_len=MAX_LEN, packed=True,
                         prefix_cache=mesh is None, mesh=mesh)
    del params
    packed = sum(w.bits.size * 4 for w in jax.tree.leaves(
        engine.params, is_leaf=lambda x: isinstance(x, PackedBool))
        if isinstance(w, PackedBool))
    pool = _nbytes(jax.eval_shape(
        lambda: paged_pool_init(cfg, LANES, LANES * (MAX_LEN // PAGE) + 1,
                                PAGE)))
    log(f"memory reckoning: bf16 embed+head {_gib(fp)}, int8 Boolean "
        f"blocks {_gib(blocks)} (dropped after packing), packed blocks "
        f"{_gib(packed)}, paged pool {LANES} lanes x {MAX_LEN} positions "
        f"{_gib(pool)}")
    return engine


def run_one_chip(cfg, seed: int) -> None:
    import jax

    t0 = time.perf_counter()
    engine = build(cfg, seed)
    jax.block_until_ready(engine.params)
    t_build = time.perf_counter() - t0
    prompts = make_traffic(cfg.vocab_size, seed=seed)
    lens = [len(p) for p in prompts]
    log(f"traffic: {len(prompts)} requests, prompts {min(lens)}-{max(lens)}"
        f" tokens, even ones share a {SHARED_PREFIX}-token prefix, "
        f"{GEN} greedy tokens each")

    # first pass compiles every prefill bucket and the decode segment
    warm = serve(engine, prompts)
    log(f"set-up: weights {t_build:.1f}s, first pass (compiles) "
        f"{warm['seconds']:.1f}s")
    run = serve(engine, prompts)
    n_tok = GEN * len(prompts)
    log(f"serving (compiled): {len(prompts)} requests, {n_tok} tokens in "
        f"{run['seconds']:.2f}s on {LANES} lanes")
    pf = run["prefix"]
    log(f"prefix cache: {pf['partial_hits']} partial + {pf['exact_hits']} "
        f"exact hits / {pf['lookups']} lookups, {pf['hit_tokens']} prompt "
        f"tokens served from cache")
    if pf["partial_hits"] < 1:
        raise AssertionError("no partial prefix hit: paged_prefix_attention "
                             "never ran")
    for i, (a, b) in enumerate(zip(warm["streams"], run["streams"])):
        if not np.array_equal(a, b):
            raise AssertionError(f"request {i}: second pass stream differs")
    log(f"all {len(prompts)} requests DONE with {GEN} in-vocab tokens; both "
        "passes agree token for token")

    hlo = _segment_hlo(engine, run["pool_key"])
    n_calls = hlo.count("tpu_custom_call")
    log(f"decode segment HLO: {n_calls} tpu_custom_call sites")
    if n_calls < 2:      # the packed GEMVs and the paged decode kernel
        raise AssertionError("decode segment runs no Mosaic kernel")

    check_kernels(engine, seed=seed)


def run_shards(cfg, seed: int, shards: int) -> None:
    import jax

    from repro.distributed import set_mesh
    from repro.launch.mesh import make_serve_mesh

    prompts = make_traffic(cfg.vocab_size, seed=seed)
    mesh = make_serve_mesh(shards)
    set_mesh(mesh)
    t0 = time.perf_counter()
    ref_engine = build(cfg, seed)                  # one chip, unsharded
    ref = serve(ref_engine, prompts, prefix_cache=False)
    log(f"unsharded engine on {jax.devices()[0]}: {ref['seconds']:.1f}s "
        "(compiles included)")
    del ref_engine
    tp_engine = build(cfg, seed, mesh=mesh)
    tp = serve(tp_engine, prompts, prefix_cache=False)
    tp2 = serve(tp_engine, prompts, prefix_cache=False)
    for i, (a, b) in enumerate(zip(tp["streams"], tp2["streams"])):
        if not np.array_equal(a, b):
            raise AssertionError(f"request {i}: second pass stream differs")
    log(f"tensor-parallel engine on {shards} chips: first pass "
        f"{tp['seconds']:.1f}s (compiles), second {tp2['seconds']:.2f}s; "
        f"total {time.perf_counter() - t0:.1f}s")
    diff = [i for i, (a, b) in enumerate(zip(ref["streams"], tp["streams"]))
            if not np.array_equal(a, b)]
    if diff:
        raise AssertionError(f"tensor-parallel greedy streams differ from "
                             f"the unsharded engine for requests {diff}")
    log(f"all {len(prompts)} greedy streams identical to the unsharded "
        "engine")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--shards", type=int, default=0,
                    help="run only the tensor-parallel session on this "
                         "many chips and compare it with one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_PAGED_KERNEL") is not None:
        raise SystemExit("chip_smoke: unset REPRO_PAGED_KERNEL — the run "
                         "must take the Pallas kernel path")

    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()
    if dev[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {dev[0].platform})")
    need = args.shards or 1
    if len(dev) < need:
        raise SystemExit(f"chip_smoke: needs {need} chips, found {len(dev)}")
    log(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH).scaled(n_layers=N_LAYERS)
    log(f"{cfg.name}: d_model {cfg.d_model}, {cfg.n_heads} heads "
        f"({cfg.heads_padded()} padded) / {cfg.n_kv_heads} kv, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.n_layers} of 48 layers; "
        f"device {dev[0].device_kind} x {len(dev)}")
    t0 = time.perf_counter()
    if args.shards:
        run_shards(cfg, args.seed, args.shards)
    else:
        run_one_chip(cfg, args.seed)
    stats = dev[0].memory_stats() or {}
    log(f"peak_bytes_in_use on {dev[0]}: "
        f"{stats.get('peak_bytes_in_use', 'not reported')}; "
        f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}), flush=True)


if __name__ == "__main__":
    main()
