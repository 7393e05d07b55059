"""Serving launcher: batched prefill + decode on int8 Boolean weights.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b --smoke \
        --batch 4 --prompt-len 32 --gen 16

Continuous batching (mixed-length request pool over the paged-cache lane
scheduler instead of one fixed-shape batch):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b --smoke \
        --continuous --requests 8 --lanes 4 --gen 16

Streaming session (tokens printed as decode segments complete, requests
submitted mid-flight — the async serve API):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b --smoke \
        --stream --requests 8 --lanes 4 --gen 16

Prefix caching (requests share a system prompt; cache hits prefill only
their unique tail — hit-rate/CoW/eviction stats printed at drain):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b --smoke \
        --stream --prefix-cache --requests 8 --lanes 4 --gen 16

Overload hardening (bounded admission + deadlines + post-step invariant
audits; sheds print with their typed reason, audit stats at drain; arm
``REPRO_FAULTS=site@idx,...`` in the env for chaos-mode fault injection):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b --smoke \
        --stream --requests 8 --lanes 4 --gen 16 \
        --max-pending 4 --deadline-ms 5000 --audit

Serving over HTTP (the gateway: POST /v1/generate streams tokens as SSE,
GET /metrics is Prometheus text, /healthz flips to 503 at drain; SIGTERM
drains gracefully — in-flight streams finish, new work gets 503):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b --smoke \
        --http 8080 --lanes 4 --max-pending 16 --prefix-cache

    curl -N localhost:8080/v1/generate \
        -d '{"prompt": [3, 1, 4, 1, 5], "max_tokens": 8}'
    curl localhost:8080/metrics

Chaos soak (N seeded random fault schedules run to drain against fresh
sessions with post-step audits; a failing schedule prints its seed and
plan JSON and replays byte-for-byte):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b --smoke \
        --chaos-soak 25 --requests 6 --lanes 2 --gen 8
"""
from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache with per-(token,head) dynamic scales")
    ap.add_argument("--packed", action="store_true",
                    help="bit-packed XNOR weight serving (32 weights/word)")
    ap.add_argument("--eager", action="store_true",
                    help="seed per-token loop instead of the fused scan "
                         "fast path (baseline/debug)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching: a mixed-length request pool "
                         "through the paged-cache lane scheduler")
    ap.add_argument("--stream", action="store_true",
                    help="streaming session: submit/stream/cancel request "
                         "lifecycle, tokens printed as segments complete")
    ap.add_argument("--requests", type=int, default=8,
                    help="(--continuous/--stream) request pool size")
    ap.add_argument("--lanes", type=int, default=4,
                    help="(--continuous/--stream) fixed decode lane count")
    ap.add_argument("--page-size", type=int, default=16,
                    help="(--continuous/--stream) cache page size in tokens")
    ap.add_argument("--segment", type=int, default=2,
                    help="(--stream) decode steps between scheduling points")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="(--stream) radix-indexed prompt-page sharing: "
                         "requests share a system prompt; cache hits "
                         "prefill only their unique tail")
    ap.add_argument("--audit", action="store_true",
                    help="(--stream) run the allocator/prefix-index "
                         "invariant audit after every step and print the "
                         "robustness stats at drain")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="(--stream) bounded submit queue: overflow sheds "
                         "with a typed ShedError instead of queueing "
                         "without bound")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="(--stream) per-request deadline budget in wall "
                         "ms: unmeetable at admission sheds, passing it "
                         "mid-flight expires the request")
    ap.add_argument("--host-pages", type=int, default=None,
                    help="(--stream/--http) host-RAM swap tier budget in "
                         "pages: preempted lanes and cold prefix pages "
                         "migrate to pinned host buffers instead of being "
                         "recomputed/freed, and fault back in bit-identical")
    ap.add_argument("--metrics-tenants", type=int, default=None,
                    help="(--http) per-tenant /metrics label budget: first "
                         "N distinct tenants get their own label, the rest "
                         "aggregate under tenant=\"other\"")
    ap.add_argument("--http", type=int, default=None, metavar="PORT",
                    help="serve over HTTP on this port: POST /v1/generate "
                         "(JSON body; tokens stream back as SSE), GET "
                         "/metrics (Prometheus text), GET /healthz. "
                         "Honors --lanes/--page-size/--segment/"
                         "--prefix-cache/--max-pending/--audit; SIGTERM "
                         "drains gracefully")
    ap.add_argument("--watchdog-timeout", type=float, default=300.0,
                    help="(--http) seconds one session.step() round may "
                         "run before the gateway watchdog declares the "
                         "step driver stalled: /healthz flips to degraded "
                         "and live SSE streams end with a typed 'watchdog' "
                         "error instead of hanging")
    ap.add_argument("--chaos-soak", type=int, default=0, metavar="N",
                    help="run N seeded random fault schedules against "
                         "fresh sessions (serve/chaos.py) instead of "
                         "serving; prints each schedule's report and exits "
                         "nonzero if any containment check fails — a "
                         "failing seed reproduces byte-for-byte via "
                         "--chaos-seed")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="(--chaos-soak) base seed: schedule i uses "
                         "seed+i; pass a failing run's printed seed with "
                         "--chaos-soak 1 to replay it exactly")
    ap.add_argument("--chaos-rate", type=float, default=None,
                    help="(--chaos-soak) override every default per-site "
                         "firing probability with one value in [0,1]")
    ap.add_argument("--host", default="127.0.0.1",
                    help="(--http) bind address")
    ap.add_argument("--shards", type=int, default=0,
                    help="tensor-parallel serve mesh over N devices "
                         "(head-axis sharded weights + KV page pools, one "
                         "mesh-wide scheduler; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N). "
                         "0 = single-device, no mesh")
    args = ap.parse_args()

    import jax

    from repro.configs import get_config, get_smoke
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import lm_init
    from repro.serve.engine import ServeEngine

    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if cfg.frontend == "embeddings":
        print(f"[serve] {args.arch} uses an embeddings frontend stub; "
              "serving decodes tokens after an embedded prompt.")
    cfg = cfg.scaled(kv_cache_quant=args.kv_quant)

    key = jax.random.PRNGKey(0)
    params, _ = lm_init(key, cfg)
    mesh = None
    if args.shards:
        from repro.distributed import set_mesh
        from repro.launch.mesh import make_serve_mesh

        mesh = make_serve_mesh(args.shards)
        set_mesh(mesh)
        print(f"[serve] tensor-parallel mesh: {args.shards} shards "
              f"(head-axis sharded weights + KV page pools)")
        if not (args.stream or args.continuous):
            raise SystemExit("--shards requires --stream or --continuous "
                             "(the paged serve path; generate() is "
                             "single-device)")
    engine = ServeEngine(cfg, params, max_len=args.prompt_len + args.gen,
                         packed=args.packed, mesh=mesh)

    if args.chaos_soak:
        import numpy as np

        from repro.serve import (DEFAULT_RATES, FaultSchedule,
                                 SamplingParams, soak_session)

        rates = dict(DEFAULT_RATES) if args.chaos_rate is None else \
            {s: args.chaos_rate for s in DEFAULT_RATES}
        rng = np.random.default_rng(12345)
        prompts = [rng.integers(0, cfg.vocab_size,
                                (int(rng.integers(4, args.prompt_len + 1)),)
                                ).astype(np.int32)
                   for _ in range(args.requests)]

        def make(inj):
            return engine.session(lanes=args.lanes,
                                  page_size=args.page_size,
                                  segment=args.segment,
                                  prefix_cache=args.prefix_cache,
                                  audit=True, faults=inj)

        failed = 0
        for i in range(args.chaos_soak):
            seed = args.chaos_seed + i
            sched = FaultSchedule.random(seed, rates)
            rep = soak_session(
                make, prompts, sched,
                params_for=lambda i: SamplingParams(max_tokens=args.gen),
                preempt_period=7)
            print(f"[chaos] {rep.summary()}")
            if not rep.ok:
                failed += 1
                print(f"[chaos] FAILING SCHEDULE seed={seed} — replay with "
                      f"--chaos-soak 1 --chaos-seed {seed}")
                print(f"[chaos] plan: {sched.to_json()}")
                for f in rep.failures:
                    print(f"[chaos]   {f}")
        if failed:
            raise SystemExit(
                f"[chaos] {failed}/{args.chaos_soak} schedules FAILED")
        print(f"[chaos] {args.chaos_soak} schedules drained clean "
              "(audit, terminal statuses, bit-identity)")
        return

    if args.http is not None:
        from repro.gateway import run_gateway

        print(f"[serve] gateway listening on http://{args.host}:{args.http} "
              f"({args.lanes} lanes, page_size={args.page_size}, "
              f"segment={args.segment}"
              + (", prefix-cache" if args.prefix_cache else "")
              + (f", max_pending={args.max_pending}"
                 if args.max_pending is not None else "")
              + ") — SIGTERM/Ctrl-C drains gracefully")
        run_gateway(engine, host=args.host, port=args.http,
                    lanes=args.lanes, page_size=args.page_size,
                    segment=args.segment, prefix_cache=args.prefix_cache,
                    max_pending=args.max_pending, audit=args.audit,
                    host_page_budget=args.host_pages,
                    metrics_tenants=args.metrics_tenants,
                    watchdog_timeout=args.watchdog_timeout)
        print("[serve] gateway drained; exiting")
        return

    if args.stream or args.continuous:
        # one request-pool builder for both traffic-shaped modes
        import numpy as np

        rng = np.random.default_rng(1)
        if args.prefix_cache:
            # the traffic shape prefix caching exists for: one shared
            # system prompt, short unique tails — capped at --prompt-len
            # so every prompt fits the engine's max_len
            sys_len = min(max(args.prompt_len * 3 // 4, 1),
                          max(args.prompt_len - 1, 1))
            sys_p = rng.integers(0, cfg.vocab_size, (sys_len,)
                                 ).astype(np.int32)
            prompts = [np.concatenate([sys_p, rng.integers(
                0, cfg.vocab_size,
                (int(rng.integers(1, max(args.prompt_len - sys_len, 1)
                                  + 1)),)).astype(np.int32)]
                )[:args.prompt_len]
                       for _ in range(args.requests)]
        else:
            prompts = [rng.integers(
                0, cfg.vocab_size,
                (int(rng.integers(4, args.prompt_len + 1)),)
            ).astype(np.int32) for _ in range(args.requests)]
        gens = [int(rng.integers(max(args.gen // 2, 1), args.gen + 1))
                for _ in range(args.requests)]

    if args.stream:
        from repro.serve import RequestStatus, SamplingParams, ShedError

        with engine.session(lanes=args.lanes, page_size=args.page_size,
                            segment=args.segment,
                            prefix_cache=args.prefix_cache,
                            max_pending=args.max_pending,
                            audit=args.audit,
                            host_page_budget=args.host_pages) as sess:
            def _submit(p, g):
                try:
                    return sess.submit(p, SamplingParams(
                        max_tokens=g, deadline_ms=args.deadline_ms))
                except ShedError as e:
                    print(f"[serve] shed rid={e.rid} ({e.reason}): {e}")
                    return None

            # submit half up front, inject the rest mid-flight — the
            # scheduler is re-entrant, admission happens between segments
            handles = [_submit(p, g)
                       for p, g in zip(prompts[: args.requests // 2],
                                       gens[: args.requests // 2])]
            printed = [0] * args.requests
            t0 = time.time()
            ttft = None
            injected = args.requests // 2
            while not sess.idle or injected < args.requests:
                if injected < args.requests:    # one mid-flight submit/step
                    handles.append(_submit(prompts[injected],
                                           gens[injected]))
                    injected += 1
                sess.step()
                for i, h in enumerate(handles):
                    if h is not None and h.tokens_ready > printed[i]:
                        if ttft is None:
                            ttft = time.time() - t0
                        new = h.tokens_so_far()[printed[i]:]
                        print(f"[serve] req{i} +{new} "
                              f"({h.tokens_ready}/{gens[i]} "
                              f"{h.status.name.lower()})")
                        printed[i] = h.tokens_ready
            dt = time.time() - t0
            total = sum(h.tokens_ready for h in handles if h is not None)
            # shed at submit (None) or left the session other than DONE or
            # CANCELLED: the run failed, and the exit code says so below
            abnormal = [i for i, h in enumerate(handles)
                        if h is None or h.status not in (
                            RequestStatus.DONE, RequestStatus.CANCELLED)]
            for i in abnormal:
                h = handles[i]
                if h is not None:
                    print(f"[serve] req{i} left abnormally: "
                          f"{h.status.name} ({h.error})")
            if args.audit:
                a = sess.audit()
                st = sess.sched.stats
                print(f"[serve] audit clean at drain: "
                      f"{a['alloc']['n_owned']} pages owned / "
                      f"{a['alloc']['n_free']} free; "
                      f"admitted={st['admitted']} shed={st['shed']} "
                      f"expired={st['expired']} failed={st['failed']} "
                      f"preemptions={st['preemptions']}")
            if args.prefix_cache:
                st = sess.prefix.stats
                print(f"[serve] prefix cache: {st['exact_hits']} exact + "
                      f"{st['partial_hits']} partial hits / "
                      f"{st['lookups']} lookups "
                      f"({100 * sess.prefix.hit_rate:.0f}% hit rate, "
                      f"{st['hit_tokens']} prompt tokens served from cache,"
                      f" {st['cow_forks']} CoW forks, "
                      f"{st['evicted_pages']} pages LRU-evicted)")
            if args.host_pages is not None:
                sw = sess.swap_mgr.stats_dict()
                st = sess.sched.stats
                print(f"[serve] swap tier: {sw['swap_outs']} captures / "
                      f"{sw['swap_ins']} restores "
                      f"({sw['swap_out_bytes']}B out, "
                      f"{sw['swap_in_bytes']}B in; "
                      f"{sw['host_used']}/{sw['host_pages']} host pages "
                      f"used; preempt swap={st['preempt_swap']} "
                      f"recompute={st['preempt_recompute']})")
        print(f"[serve] stream: {args.requests} requests over {args.lanes} "
              f"lanes in {dt:.2f}s ({total/dt:.1f} tok/s aggregate, "
              f"first tokens after {ttft:.2f}s — no wait for pool drain)")
        if abnormal:
            raise SystemExit(f"[serve] {len(abnormal)}/{args.requests} "
                             "requests did not finish")
        return

    if args.continuous:
        engine.generate_batch(prompts, gens, lanes=args.lanes,
                              page_size=args.page_size)   # warmup/compile
        t0 = time.time()
        outs = engine.generate_batch(prompts, gens, lanes=args.lanes,
                                     page_size=args.page_size)
        dt = time.time() - t0
        total = sum(gens)
        mode = "continuous" + ("+packed" if args.packed else "")
        print(f"[serve] {mode}: {args.requests} mixed-length requests "
              f"(prompts {min(map(len, prompts))}-{max(map(len, prompts))}, "
              f"gens {min(gens)}-{max(gens)}) over {args.lanes} lanes in "
              f"{dt:.2f}s ({total/dt:.1f} tok/s aggregate)")
        print("[serve] request 0:", outs[0][:12].tolist())
        return

    gen = engine.generate_eager if args.eager else engine.generate
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    gen(prompts, args.gen)      # warmup: compile the fused fast path
    t0 = time.time()
    out = gen(prompts, args.gen)
    dt = time.time() - t0
    toks = args.batch * args.gen
    mode = ("eager" if args.eager else "scan") + \
        ("+packed" if args.packed else "")
    print(f"[serve] {mode}: generated {out.shape} in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s batched)")
    print("[serve] sample:", out[0, :16].tolist())


if __name__ == "__main__":
    main()
