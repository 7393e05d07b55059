"""Training launcher.

Small-scale (this container): real training on the local mesh —
    PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b \
        --smoke --steps 50 --batch 8 --seq 128

Pod-scale (real TPU fleet): the same entry point with --mesh single|multi
builds the production mesh, shards params/opt-state per the model's
PartitionSpecs, and runs the identical loop. On multi-host runs
``jax.distributed.initialize()`` is called first; XLA latency-hiding
scheduler flags are applied for compute/collective overlap.
"""
from __future__ import annotations

import argparse
import os


def _perf_flags():
    # collective/compute overlap on real TPU runtimes (no-op on CPU)
    flags = os.environ.get("XLA_FLAGS", "")
    for f in ("--xla_tpu_enable_latency_hiding_scheduler=true",
              "--xla_tpu_enable_async_collective_fusion=true"):
        if f not in flags:
            flags += " " + f
    os.environ["XLA_FLAGS"] = flags


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--eta", type=float, default=4.0, help="Boolean lr η")
    ap.add_argument("--fp-lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--data", default=None, help=".bin token file (else synthetic)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default="local", choices=["local", "single", "multi"])
    ap.add_argument("--model-axis", type=int, default=1)
    args = ap.parse_args()

    _perf_flags()
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, get_smoke
    from repro.core import cosine_schedule, hybrid_optimizer
    from repro.data import make_pipeline
    from repro.distributed import set_mesh
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import (make_local_mesh, make_production_mesh,
                                   mesh_batch_axes)
    from repro.launch.shardings import named
    from repro.models import lm_init
    from repro.train.loop import TrainLoop
    from repro.train.step import make_train_step

    enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if args.mesh == "local":
        mesh = make_local_mesh(args.model_axis)
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    set_mesh(mesh)
    cfg = cfg.scaled(batch_axes=mesh_batch_axes(mesh),
                     use_sharding_constraints=len(jax.devices()) > 1)

    key = jax.random.PRNGKey(0)
    params, specs = lm_init(key, cfg)
    shardings = named(mesh, specs)
    if len(jax.devices()) > 1:
        params = jax.device_put(params, shardings)

    opt = hybrid_optimizer(
        eta=cosine_schedule(args.eta, args.steps, warmup=args.steps // 20),
        fp_lr=cosine_schedule(args.fp_lr, args.steps, warmup=args.steps // 20))
    opt_state = opt.init(params)

    step_fn = jax.jit(make_train_step(cfg, opt, args.microbatches),
                      donate_argnums=(0, 1))
    pipeline = make_pipeline(cfg, args.seq, args.batch, path=args.data)

    loop = TrainLoop(step_fn, params, opt_state, pipeline,
                     ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    history = loop.run(args.steps)
    if history:
        k = max(len(history) // 10, 1)
        print(f"[train] loss first{k}-avg {sum(history[:k])/k:.4f} -> "
              f"last{k}-avg {sum(history[-k:])/k:.4f}")
    print(f"[train] stragglers observed: {len(loop.stragglers)}")


if __name__ == "__main__":
    main()
