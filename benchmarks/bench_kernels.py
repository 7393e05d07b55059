"""Kernel microbenchmarks.

On this CPU container Pallas runs in interpret mode (functional, not
performant), so the wall-clock numbers that matter here are the XLA-compiled
equivalents of the kernels' MATH: int8 counting GEMM vs fp32 GEMM, and the
bit-packing density. The Pallas kernels themselves are timed once for
regression tracking (interpret-mode latency).

Two serve-path sections feed the perf trajectory:

  * paged attention — the Pallas in-place-page decode kernel vs the XLA
    block-table gather across (lanes × pool pages × page size × kv-quant),
    with the MODELED per-step pool-byte traffic of each path: the kernel
    reads O(tokens-attended) pool bytes (live pages only), the gather
    materializes the whole (L, C·page, ...) slab. Interpret-mode wall
    clocks track regressions only; the byte model is the hardware claim.
  * packed-GEMV tile sweep (``--sweep-gemv`` or always in smoke) — times
    the thin-M XNOR GEMV across sublane/lane-aligned (block_n, block_kw)
    candidates and prints the chosen autotune entry in
    ``kernels.GEMV_TILE_TABLE`` form.

Results are also written to ``BENCH_kernels.json`` at the repo root.
"""
from __future__ import annotations

import json
import os
import sys
import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.core import random_boolean
from repro.kernels import ops
from repro.kernels.packed_xnor import gemv_tile_config, pack_bits

SMOKE = os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"


def _time(fn, *args, reps=5):
    fn(*args).block_until_ready()
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.time() - t0) / reps * 1e6


# ---------------------------------------------------------------------------
# Paged-attention microbench: kernel (in-place pages) vs XLA gather
# ---------------------------------------------------------------------------
def _paged_case(key, lanes, n_pages, page, quant, KV=2, R=8, hd=16):
    from repro.models import attention as A

    C = (n_pages - 1) // max(lanes, 1)
    C = max(C, 1)
    cfg = types.SimpleNamespace(decode_chunk=2048, attn_logit_softcap=0.0,
                                sliding_window=0)
    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (lanes, KV, R, hd), jnp.float32).astype(
        jnp.bfloat16)
    if quant:
        kp = jax.random.randint(ks[1], (n_pages, page, KV, hd), -127, 127,
                                jnp.int8)
        vp = jax.random.randint(ks[2], (n_pages, page, KV, hd), -127, 127,
                                jnp.int8)
        kss = jax.random.uniform(ks[3], (n_pages, page, KV), jnp.float32,
                                 1e-3, 0.1)
        vss = jax.random.uniform(ks[4], (n_pages, page, KV), jnp.float32,
                                 1e-3, 0.1)
    else:
        kp = jax.random.normal(ks[1], (n_pages, page, KV, hd),
                               jnp.float32).astype(jnp.bfloat16)
        vp = jax.random.normal(ks[2], (n_pages, page, KV, hd),
                               jnp.float32).astype(jnp.bfloat16)
        kss = vss = None
    # ragged occupancy: lane i holds ~(i+1)/L of its window, lane 0 idle
    import numpy as np

    bt = np.zeros((lanes, C), np.int32)
    pos = np.zeros((lanes,), np.int32)
    nxt = 1
    for i in range(1, lanes):
        depth = max(1, ((i + 1) * C * page) // (lanes + 1))
        npg = -(-depth // page)
        for c in range(min(npg, C)):
            if nxt < n_pages:
                bt[i, c] = nxt
                nxt += 1
        pos[i] = depth - 1
    bt, pos = jnp.asarray(bt), jnp.asarray(pos)

    def kernel():
        return ops.paged_flash_decode(
            q, kp[None], vp[None], bt, pos, 0,
            None if kss is None else kss[None],
            None if vss is None else vss[None], chunk=cfg.decode_chunk)

    def gather():
        k = kp[bt].reshape(lanes, C * page, KV, hd)
        v = vp[bt].reshape(lanes, C * page, KV, hd)
        ksg = kss[bt].reshape(lanes, C * page, KV) if quant else None
        vsg = vss[bt].reshape(lanes, C * page, KV) if quant else None
        m, l, acc = A._flash_decode_local(cfg, q, k, v, pos, 0, local=False,
                                          k_scale=ksg, v_scale=vsg)
        return acc / jnp.maximum(l[..., None], 1e-30)

    gather = jax.jit(gather)

    row_b = KV * hd * kp.dtype.itemsize + (KV * 4 * 2 if quant else 0) \
        + KV * hd * vp.dtype.itemsize
    live_rows = int(sum(min(C, (int(p) + page) // page) * page
                        for p in pos))
    return kernel, gather, {
        "kernel_pool_bytes": live_rows * row_b,          # live pages only
        "gather_pool_bytes": lanes * C * page * row_b,   # the full slab
        "tokens_attended": int(jnp.sum(pos + 1)),
    }


def bench_paged_attention():
    rows, cases = [], []
    sweep = [(4, 33, 8, False), (4, 33, 8, True)] if SMOKE else [
        (2, 17, 8, False), (4, 33, 8, False), (8, 65, 8, False),
        (4, 17, 4, False), (4, 65, 16, False),
        (4, 33, 8, True), (8, 65, 8, True),
    ]
    key = jax.random.PRNGKey(0)
    for lanes, n_pages, page, quant in sweep:
        kernel, gather, model = _paged_case(key, lanes, n_pages, page, quant)
        t_k = _time(kernel, reps=2)
        t_g = _time(gather, reps=2)
        tag = f"L{lanes}_p{n_pages}x{page}" + ("_q" if quant else "")
        ratio = model["gather_pool_bytes"] / max(model["kernel_pool_bytes"],
                                                 1)
        rows.append((f"kernels/paged_attn_kernel_{tag}", t_k,
                     f"pool_bytes={model['kernel_pool_bytes']}"))
        rows.append((f"kernels/paged_attn_gather_{tag}", t_g,
                     f"pool_bytes={model['gather_pool_bytes']}"
                     f";kernel_reads_{ratio:.1f}x_less"))
        cases.append({"lanes": lanes, "n_pages": n_pages, "page": page,
                      "kv_quant": quant, "kernel_us": t_k, "gather_us": t_g,
                      **model})
    return rows, cases


# ---------------------------------------------------------------------------
# Packed-GEMV tile sweep -> autotune entry
# ---------------------------------------------------------------------------
def sweep_gemv(shapes=None):
    rows, chosen = [], {}
    if shapes is None:
        shapes = [(8, 512, 512)] if SMOKE else [
            (8, 512, 512), (8, 1024, 1024), (4, 4096, 4096)]
    for M, K, N in shapes:
        x = jax.random.normal(jax.random.PRNGKey(2), (M, K), jnp.float32)
        w = pack_bits(random_boolean(jax.random.PRNGKey(3), (K, N)), axis=0)
        Kw = w.shape[0]
        best = None
        for bn in (128, 256):
            for bkw in (8, 16):
                t = _time(lambda a, b, bn=bn, bkw=bkw: ops.packed_xnor_gemv(
                    a, b, k_valid=K, block_n=bn, block_kw=bkw), x, w, reps=2)
                rows.append((f"kernels/gemv_sweep_{N}x{Kw}_bn{bn}_bkw{bkw}",
                             t, "interpret-mode"))
                if best is None or t < best[0]:
                    best = (t, bn, bkw)
        table_bn, table_bkw = gemv_tile_config(N, Kw, x.dtype)
        # printed in GEMV_TILE_TABLE literal form so a silicon re-sweep
        # can be pasted straight into kernels/packed_xnor.py
        chosen[f"({N}, {Kw}, '{x.dtype.name}')"] = {
            "swept_best": (best[1], best[2]), "table": (table_bn, table_bkw),
            "best_us": best[0]}
        rows.append((f"kernels/gemv_autotune_{N}x{Kw}", best[0],
                     f"chosen=(bn={best[1]},bkw={best[2]})"
                     f";table=(bn={table_bn},bkw={table_bkw})"))
    return rows, chosen


def run():
    rows = []
    M = K = N = 512
    x8 = random_boolean(jax.random.PRNGKey(0), (M, K))
    w8 = random_boolean(jax.random.PRNGKey(1), (K, N))
    xf = x8.astype(jnp.float32)
    wf = w8.astype(jnp.float32)

    f_int8 = jax.jit(lambda a, b: jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32))
    f_fp32 = jax.jit(lambda a, b: a @ b)
    t_int8 = _time(f_int8, x8, w8)
    t_fp32 = _time(f_fp32, xf, wf)
    rows.append(("kernels/xla_int8_counting_gemm_512", t_int8,
                 f"speedup_vs_fp32={t_fp32/t_int8:.2f}x"))
    rows.append(("kernels/xla_fp32_gemm_512", t_fp32, ""))

    # bit-packing density (weights bytes on the wire / in HBM)
    packed = pack_bits(w8, axis=0)
    rows.append(("kernels/pack_density", 0.0,
                 f"{w8.size / packed.nbytes:.1f}bool_per_byte"))

    # Pallas interpret-mode latencies (regression tracking only)
    t_pal = _time(lambda a, b: ops.boolean_matmul(
        a, b, block_m=128, block_n=128, block_k=128), x8, w8, reps=2)
    rows.append(("kernels/pallas_boolean_matmul_interp", t_pal,
                 "interpret-mode"))
    t_px = _time(lambda a, b: ops.packed_xnor_matmul(
        a, b, k_valid=K, block_m=128, block_n=128, block_kw=16),
        pack_bits(x8, -1), pack_bits(w8, 0), reps=2)
    rows.append(("kernels/pallas_packed_xnor_interp", t_px,
                 "interpret-mode"))

    pa_rows, pa_cases = bench_paged_attention()
    rows += pa_rows
    gemv_rows, gemv_chosen = sweep_gemv()
    rows += gemv_rows

    out = {"rows": [list(r) for r in rows],
           "paged_attention": pa_cases,
           "gemv_autotune": gemv_chosen,
           "smoke": SMOKE}
    path = Path(__file__).resolve().parent.parent / "BENCH_kernels.json"
    path.write_text(json.dumps(out, indent=1))
    rows.append(("kernels/bench_json", 0.0, str(path.name)))
    return rows


if __name__ == "__main__":
    if "--sweep-gemv" in sys.argv:
        for r in sweep_gemv()[0]:
            print(",".join(str(x) for x in r))
    else:
        for r in run():
            print(",".join(str(x) for x in r))
