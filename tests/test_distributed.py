"""Distribution tests on real (forced) multi-device CPU.

These tests require >1 device, so each spawns a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count set (the conftest keeps
the main pytest process single-device on purpose — smoke tests and benches
must see one device).
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def _run(src: str, n_dev: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", src], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return out.stdout


def test_pjit_train_step_matches_single_device():
    """One sharded train step on a 4x2 mesh == the unsharded step (the
    distribution layer must not change the math)."""
    out = _run(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke
        from repro.core import hybrid_optimizer
        from repro.distributed import set_mesh
        from repro.launch.mesh import auto_mesh
        from repro.launch.shardings import named
        from repro.models import lm_init
        from repro.train.step import make_train_step

        cfg0 = get_smoke("qwen2.5-14b").scaled(dtype=jnp.float32)
        key = jax.random.PRNGKey(0)
        batch = {
            "tokens": jax.random.randint(key, (8, 32), 0, cfg0.vocab_size),
            "labels": jax.random.randint(key, (8, 32), 0, cfg0.vocab_size),
        }

        def one(cfg, shard):
            params, specs = lm_init(jax.random.PRNGKey(1), cfg)
            opt = hybrid_optimizer(eta=4.0, fp_lr=1e-3)
            state = opt.init(params)
            step = make_train_step(cfg, opt, microbatches=2)
            if shard:
                mesh = auto_mesh((4, 2), ("data", "model"))
                set_mesh(mesh)
                sh = named(mesh, specs)
                params = jax.device_put(params, sh)
                step = jax.jit(step, in_shardings=(sh, None, None))
            else:
                step = jax.jit(step)
            new_params, new_state, metrics = step(params, state, batch)
            return new_params, float(metrics["loss"])

        p1, l1 = one(cfg0, shard=False)
        p2, l2 = one(cfg0.scaled(use_sharding_constraints=True), shard=True)
        assert abs(l1 - l2) < 1e-3, (l1, l2)
        mism = 0
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            if a.dtype == np.int8 if hasattr(a, 'dtype') else False:
                mism += int((a != b).sum())
            else:
                np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-3)
        print("OK", l1, l2)
    """))
    assert "OK" in out


def test_shardmap_flash_decode_matches_local():
    """Seq-sharded shard_map flash-decode == single-device decode."""
    out = _run(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke
        from repro.distributed import set_mesh
        from repro.launch.mesh import auto_mesh
        from repro.launch.shardings import named
        from repro.models import cache_init, lm_decode_step, lm_init

        cfg0 = get_smoke("gemma2-2b").scaled(dtype=jnp.float32)
        params, specs = lm_init(jax.random.PRNGKey(0), cfg0)
        pf = jax.tree.map(
            lambda p: p.astype(jnp.float32) if p.dtype == jnp.int8 else p,
            params)
        B, S = 2, 64
        tok = jax.random.randint(jax.random.PRNGKey(1), (B, 1), 0,
                                 cfg0.vocab_size)

        # reference: plain decode at pos 5 with prefilled random cache
        cache, _ = cache_init(cfg0, B, S)
        kfill = jax.random.normal(jax.random.PRNGKey(2), (1,)) # det fill below
        def fill(c):
            return jax.tree.map(
                lambda x: jax.random.normal(
                    jax.random.PRNGKey(x.size % 97), x.shape, jnp.float32
                ).astype(x.dtype) * 0.1 if x.ndim >= 3 else x, c)
        cache = {"blocks": fill(cache["blocks"]),
                 "pos": jnp.asarray(5, jnp.int32)}
        ref_logits, _ = jax.jit(
            lambda p, c, t: lm_decode_step(cfg0, p, c, t))(pf, cache, tok)

        # sharded: cache seq over "model" (4), batch over "data" (2)
        mesh = auto_mesh((2, 4), ("data", "model"))
        set_mesh(mesh)
        cfg = cfg0.scaled(use_sharding_constraints=True,
                          batch_axes=("data",), cache_seq_axes=("model",))
        _, cspecs = cache_init(cfg, B, S)
        csh = named(mesh, cspecs)
        cache_sh = jax.device_put(cache, csh)
        sh_logits, _ = jax.jit(
            lambda p, c, t: lm_decode_step(cfg, p, c, t))(pf, cache_sh, tok)
        np.testing.assert_allclose(np.asarray(ref_logits, np.float32),
                                   np.asarray(sh_logits, np.float32),
                                   rtol=1e-3, atol=1e-3)
        print("OK")
    """))
    assert "OK" in out


def test_ef_signsgd_compression_roundtrip():
    """1-bit EF all-reduce: votes decode to ~the mean gradient; error
    feedback keeps the residual bounded."""
    out = _run(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import compress_votes
        from repro.launch.mesh import auto_mesh

        mesh = auto_mesh((8,), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        e = jnp.zeros((8, 64), jnp.bfloat16)

        dec, new_e = jax.jit(jax.shard_map(
            lambda gg, ee: compress_votes(gg, ee, ("data",)),
            mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=(P(None), P("data")), check_vma=False))(g, e)
        # decoded votes correlate with the true mean gradient
        true = np.asarray(g.mean(0))
        d = np.asarray(dec[0], np.float32)
        corr = np.corrcoef(true.ravel(), d.ravel())[0, 1]
        assert corr > 0.4, corr
        # residual bounded by the per-shard magnitude
        assert float(jnp.abs(new_e).max()) < float(jnp.abs(g).max()) * 2
        print("OK", corr)
    """))
    assert "OK" in out


@pytest.mark.slow
def test_dryrun_cell_end_to_end():
    """The dry-run machinery itself (512 fake devices, production mesh,
    lower+compile+analysis) — one small cell."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "gemma2-2b", "--shape", "decode_32k", "--mesh", "single",
         "--tag", "pytest"],
        capture_output=True, text=True, timeout=1800, env=env,
        cwd=str(REPO))
    assert out.returncode == 0, out.stderr[-2000:]
    assert ": ok" in out.stdout
    rec = json.loads((REPO / "results/dryrun/"
                      "gemma2-2b__decode_32k__single__pytest.json")
                     .read_text())
    assert rec["status"] == "ok"
    assert rec["roofline"]["bottleneck"] in ("compute", "memory",
                                             "collective")
    assert rec["peak_bytes_per_device"] < 16 * 2 ** 30


def test_elastic_restore_across_mesh_sizes(tmp_path):
    """Checkpoint under a (2,2) mesh, restore onto a (4,2) mesh — the
    elastic-scaling contract (full-array leaves re-shard onto whatever
    topology is live)."""
    script = textwrap.dedent(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.checkpoint import save_pytree, restore_pytree
        from repro.configs import get_smoke
        from repro.distributed import set_mesh
        from repro.launch.mesh import auto_mesh
        from repro.launch.shardings import named
        from repro.models import lm_init

        ckpt = {str(repr(str(tmp_path)))}
        cfg = get_smoke("gemma2-2b")
        params, specs = lm_init(jax.random.PRNGKey(0), cfg)

        # phase 1: shard on (2,2), checkpoint
        mesh1 = auto_mesh((2, 2), ("data", "model"))
        p1 = jax.device_put(params, named(mesh1, specs))
        save_pytree(p1, ckpt, step=3, sync=True)

        # phase 2: 'the fleet grew' — restore onto (4,2)
        mesh2 = auto_mesh((4, 2), ("data", "model"))
        restored, step = restore_pytree(params, ckpt,
                                        shardings=named(mesh2, specs))
        assert step == 3
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        # restored leaves actually live on the new mesh
        leaf = jax.tree.leaves(restored)[0]
        assert leaf.sharding.mesh.devices.size == 8
        print("OK")
    """)
    out = _run(script, n_dev=8)
    assert "OK" in out
