"""Batched serving engine: one fused jitted fast path per request shape,
plus continuous batching over a paged cache pool.

The decode hot path is a single compiled computation — prefill, a
``jax.lax.scan`` over decode steps, and sampling all live inside one
``generate_fn`` — instead of the seed's per-token Python loop (one dispatch
per token). The KV/SSM cache is preallocated at ``max_len`` by
``cache_init``, written in place with ``lax.dynamic_update_slice``, and
DONATED into every call: XLA aliases the multi-MiB cache buffers across
requests rather than re-materializing them per token.

``ServeEngine.session`` is the traffic-shaped entry point: an explicit
submit/stream/cancel request lifecycle (serve/session.py) over a
re-entrant continuous-batching scheduler (serve/scheduler.py) and
block-table paged caches carved from one preallocated pool
(serve/paged_cache.py). The decode batch is padded to a fixed LANE count
so the fused decode-segment scan compiles once per (segment, lanes) and
never retraces as requests come and go, and prefill compiles are bucketed
by padded prompt length. ``generate_batch`` survives as a thin wrapper
over a session (submit all, run until idle, collect); greedy decoding is
token-identical to per-request ``generate``, which — with
``generate_eager`` — survives as the parity oracle.

Weight serving modes:
  * default — stored int8 Boolean weights, per-layer transient ±1 views
    (no FP weight copy is ever resident);
  * ``packed=True`` — every Boolean projection is bit-packed once at engine
    init (32 weights per uint32 word) and decode contractions stream the
    packed words through the thin-M packed-XNOR GEMV kernel: ~32× fewer
    resident weight bytes and per-token HBM weight traffic, which is the
    B⊕LD dataflow win on memory-bound decode (q/k/v and gate/up are also
    fused into single GEMVs) — and under continuous batching those packed
    words stream ONCE per step for the whole lane pool. MoE expert tensors
    stay int8 (they are routed einsums, not proj leaves).

Each session program takes its name in its ``_build_*`` method —
``serve_prefill``, ``serve_tail_prefill``, ``serve_hit_admit``,
``serve_decode_segment`` — so a device trace's module line shows
``jit_serve_decode_segment`` (under the mesh's shard_map too), and each
Pallas kernel passes its own ``name``.

Optional int8-quantized KV cache (cfg.kv_cache_quant) quantizes at both
prefill and decode writes with per-(token, head) dynamic scales stored
alongside the cache rows (models/attention.py: kv_quant).
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro.core import pack_boolean_weight
from repro.models import (ModelConfig, block_roles, cache_init,
                          lm_decode_step, lm_decode_step_paged, lm_prefill)
from repro.models import attention as A
from repro.models import mamba as M

from .paged_cache import CachePool, commit_prefill, fork_page
from .sampling import sample_tokens
from .scheduler import SamplingParams
from .session import ServeSession


def _fusable(*projs) -> bool:
    """Boolean bias-free proj dicts over the same input dim can fuse."""
    return all(isinstance(p, dict) and "b" not in p
               and isinstance(p.get("w"), jax.Array)
               and p["w"].dtype == jnp.int8
               and p["w"].shape[:-1] == projs[0]["w"].shape[:-1]
               for p in projs)


def pack_weights(params, tp: int = 1):
    """Bit-pack every Boolean int8 projection leaf for serving.

    q/k/v (and FFN gate/up) projections sharing an input dim fuse into one
    packed leaf (``wqkv`` / ``wgu``) so a decode token makes one pass per
    block over activations and packed weight words. Everything FP (embed,
    head, norms, router, biases) and MoE expert tensors pass through
    untouched.

    ``tp > 1`` (engine mesh mode) lays the fused wqkv columns out
    SHARD-MAJOR ``[q_0|k_0|v_0 | q_1|k_1|v_1 | ...]`` so a plain last-axis
    PartitionSpec hands shard s exactly its local ``[q_s|k_s|v_s]`` fused
    block (the plain ``[q|k|v]`` concat layout cannot be column-sharded
    without a permutation). wo packs normally — it stays replicated under
    the mesh (launch/shardings.py explains why).
    """
    def shard_major(*ws):
        if tp == 1:
            return jnp.concatenate(ws, axis=-1)
        slices = [[w[..., s * (w.shape[-1] // tp):(s + 1)
                     * (w.shape[-1] // tp)] for w in ws]
                  for s in range(tp)]
        return jnp.concatenate([w for sl in slices for w in sl], axis=-1)

    def walk(node):
        if not isinstance(node, dict):
            return node
        node = dict(node)
        if {"wq", "wk", "wv"} <= node.keys() \
                and _fusable(node["wq"], node["wk"], node["wv"]):
            w = shard_major(node.pop("wq")["w"], node.pop("wk")["w"],
                            node.pop("wv")["w"])
            node["wqkv"] = {"w": pack_boolean_weight(w)}
        if {"wg", "wu"} <= node.keys() \
                and _fusable(node["wg"], node["wu"]):
            w = jnp.concatenate([node.pop("wg")["w"], node.pop("wu")["w"]],
                                axis=-1)
            node["wgu"] = {"w": pack_boolean_weight(w)}
        out = {}
        for k, v in node.items():
            if k == "w" and isinstance(v, jax.Array) \
                    and v.dtype == jnp.int8 and v.ndim >= 2:
                out[k] = pack_boolean_weight(v)
            else:
                out[k] = walk(v)
        return out

    return walk(params)


class ServeEngine:
    # Compiled generate fns are shape-specialized; bound the cache so novel
    # (S, n_tokens) traffic can't grow host/device memory forever. (Session
    # prefills are bucketed by padded prompt length, so steady traffic sits
    # well under this; the bound protects against one-off generate shapes.)
    MAX_COMPILED_FNS = 64

    def __init__(self, cfg: ModelConfig, params, max_len: int,
                 packed: bool = False, prefix_cache: bool = False,
                 cache_pool_limit: int = 8, mesh=None):
        """``mesh``: a 1-D ("model",) mesh (launch/mesh.make_serve_mesh)
        enables tensor-parallel serving — q/k/v weights column-sharded on
        the head axis (packed wqkv repacked shard-major), the KV page
        pools split on the KVp dim, and the paged prefill / decode-segment
        graphs traced under shard_map with an all-gather of the head
        activations before the replicated o-projection (the head-axis
        reduce — see attention._wo_project for why it is a gather, not a
        psum). The scheduler/session API is unchanged for callers; on a
        1-device mesh token streams are BITWISE identical to the unsharded
        engine, and multi-device greedy streams are token-identical to the
        single-device path (per-head arithmetic is untouched by sharding;
        tests/test_mesh_serve.py pins both)."""
        self.mesh = mesh
        self.tp = 1
        if mesh is not None:
            if tuple(mesh.axis_names) != ("model",):
                raise ValueError(
                    f"ServeEngine mesh must be 1-D ('model',) — got axes "
                    f"{tuple(mesh.axis_names)}; build it with "
                    "launch.mesh.make_serve_mesh (data-parallel replica "
                    "routing is a scheduler concern, not a mesh axis)")
            self.tp = int(mesh.shape["model"])
            self._validate_tp(cfg)
            if prefix_cache:
                raise NotImplementedError(
                    "prefix_cache under a serve mesh is not implemented "
                    "(the radix index would need shard-symmetric CoW "
                    "forks; ROADMAP follow-up)")
        self.cfg = cfg
        # the config the sharded graphs trace with: serve_tp switches the
        # model body to local head counts + the all-gather head reduce
        # before wo. tp == 1 leaves cfg untouched, so the traced graph is
        # the unsharded one.
        self._serve_cfg = cfg.scaled(serve_tp=self.tp) if self.tp > 1 else cfg
        self.max_len = max_len
        self.packed = packed
        # default for sessions (overridable per session): radix-indexed
        # cross-request prompt-page sharing — see serve/prefix_cache.py
        self.prefix_cache = prefix_cache
        if packed:
            from repro.core import PackedBool

            self.params = pack_weights(params, tp=self.tp)
            n_packed = sum(isinstance(l, PackedBool) for l in jax.tree.leaves(
                self.params, is_leaf=lambda x: isinstance(x, PackedBool)))
            if n_packed == 0:
                raise ValueError(
                    "packed=True but no Boolean int8 projection leaves were "
                    "found to pack (FP baseline model?) — packed serving "
                    "would silently serve full-precision weights")
        else:
            self.params = params
        if mesh is not None:
            from repro.launch.shardings import (named, serve_param_specs,
                                                serve_pool_specs)
            from .paged_cache import paged_pool_init

            self._param_specs = serve_param_specs(self.params)
            self.params = jax.device_put(self.params,
                                         named(mesh, self._param_specs))
            # pool SPEC tree depends only on the block roles + quant layout,
            # not geometry — build it once from a throwaway template
            self._pool_specs = serve_pool_specs(
                cfg, paged_pool_init(cfg, 1, 2, 1))
        # preallocated cache trees, donated per call: contiguous oracle
        # caches keyed by batch size, paged pools keyed by pool geometry —
        # one bounded pool abstraction instead of an unbounded per-shape dict
        self._caches = CachePool(limit=cache_pool_limit)
        # host-parked prefix indexes (serve/swap.py): close() demotes a
        # session's whole index to host and parks (PrefixCache, SwapManager)
        # here keyed by geometry; the next same-key session adopts it
        self._prefix_store = {}
        self._fns = {}      # compile-shape key -> jitted fn (FIFO-bounded)
        self.programs_built = 0     # _get_fn misses: a recompile shows here
        # (temperature is a TRACED argument, deliberately not a compile key)
        self._prefill = jax.jit(
            lambda p, b, c: lm_prefill(cfg, p, b, cache=c))
        self._decode = jax.jit(lambda p, c, t: lm_decode_step(cfg, p, c, t))

    def _validate_tp(self, cfg: ModelConfig) -> None:
        """Shardability: every attention role must split its KV heads (and
        hence, group-major GQA, its q heads) evenly over the mesh."""
        tp = self.tp
        has_attn = any(r["mixer"] != "mamba" for r in block_roles(cfg))
        if not has_attn:
            return  # pure-SSM: state is lane-indexed and replicated
        hp, kvp = cfg.heads_padded(), cfg.kv_heads_padded()
        if kvp % tp or hp % tp:
            raise ValueError(
                f"serve mesh of {tp} shards cannot split heads evenly: "
                f"padded q heads {hp}, padded kv heads {kvp} (scale "
                f"n_kv_heads so tp divides it)")

    def init_pool(self, lanes: int, n_pages: int, page_size: int):
        """Allocate one paged pool for a session — mesh mode device_puts it
        with the attention leaves sharded on the KVp axis (each device then
        holds its head-local page bytes; page IDs stay symmetric across
        shards, so ONE host allocator places every shard's pages)."""
        from .paged_cache import paged_pool_init

        pool = paged_pool_init(self.cfg, lanes, n_pages, page_size)
        if self.mesh is not None:
            from repro.launch.shardings import named

            pool = jax.device_put(pool, named(self.mesh, self._pool_specs))
        return pool

    def _shard_serve_fn(self, fn, n_plain: int, n_outs: int):
        """jit ``fn(params, pool, *plain)``, traced under shard_map when the
        engine has a mesh. ``n_plain``: replicated operand count after
        (params, pool); outputs are replicated except the LAST, the pool.
        The pool is donated either way — under the mesh its sharded buffers
        alias in place per device."""
        if self.mesh is None:
            return jax.jit(fn, donate_argnums=(1,))
        from jax.sharding import PartitionSpec as P

        sm = jax.shard_map(fn, mesh=self.mesh,
                           in_specs=(self._param_specs, self._pool_specs)
                           + tuple([P()] * n_plain),
                           out_specs=tuple([P()] * (n_outs - 1))
                           + (self._pool_specs,),
                           check_vma=False)
        return jax.jit(sm, donate_argnums=(1,))

    def _get_fn(self, key, build):
        """Shape-keyed compiled-fn cache, LRU-evicted: a hit refreshes the
        entry so steady traffic (the per-segment decode fn) can't be pushed
        out by a parade of cold one-off shapes (per-prompt-length prefills).
        Each miss builds a program and counts in ``programs_built``."""
        if key in self._fns:
            self._fns[key] = fn = self._fns.pop(key)   # move to MRU end
            return fn
        if len(self._fns) >= self.MAX_COMPILED_FNS:
            self._fns.pop(next(iter(self._fns)))
        self._fns[key] = fn = build()
        self.programs_built += 1
        return fn

    # -- shared plumbing ----------------------------------------------------
    def _inputs(self, params, prompts):
        if self.cfg.frontend == "embeddings":
            table = params["embed"]["table"]
            emb = jnp.take(table, prompts, axis=0).astype(self.cfg.dtype)
            return {"embeddings": emb}
        return {"tokens": prompts}

    # -- fused fast path ----------------------------------------------------
    def _build_fn(self, n_tokens: int, sampled: bool):
        """Only the greedy-vs-sampled branch is static; the temperature
        itself rides in as a traced scalar so per-request temperatures
        never retrace the fused graph."""
        cfg = self.cfg

        def gen(params, cache, prompts, key, temperature):
            k = key if sampled else None
            t = temperature if sampled else 0.0
            logits, cache = lm_prefill(cfg, params,
                                       self._inputs(params, prompts),
                                       cache=cache)
            tok = sample_tokens(cfg, logits[:, -1], t, k, 0)

            def step(carry, i):
                tok, cache = carry
                logits, cache = lm_decode_step(cfg, params, cache, tok)
                nxt = sample_tokens(cfg, logits[:, -1], t, k, i + 1)
                return (nxt, cache), tok[:, 0]

            (_, cache), toks = jax.lax.scan(
                step, (tok, cache), jnp.arange(n_tokens))
            return toks.T, cache

        return jax.jit(gen, donate_argnums=(1,))

    def generate(self, prompts: jax.Array, n_tokens: int,
                 temperature: float = 0.0,
                 key: Optional[jax.Array] = None) -> jax.Array:
        """prompts: (B, S) int32 -> (B, n_tokens) int32 (greedy/temperature).

        One jitted call: prefill + n_tokens-step decode scan + sampling,
        with the preallocated cache donated in and returned for the next
        request of the same batch size.
        """
        B, S = prompts.shape
        assert S + n_tokens <= self.max_len
        if self.tp > 1:
            raise NotImplementedError(
                "generate() uses the contiguous-cache path, which is not "
                "mesh-sharded — use session()/generate_batch() on a serve "
                "mesh (tp=1 meshes are fine)")
        sampled = temperature > 0.0 and key is not None
        fn = self._get_fn((B, S, n_tokens, sampled),
                          lambda: self._build_fn(n_tokens, sampled))
        k = key if key is not None else jax.random.PRNGKey(0)
        # Take before the call: donation invalidates the buffers even when
        # the dispatch later fails, so a kept reference would poison every
        # future request of this batch size. On failure the pool entry is
        # simply gone and the next call allocates fresh.
        cache = self._caches.take(B)
        if cache is None:
            cache = cache_init(self.cfg, B, self.max_len)[0]
        toks, cache = fn(self.params, cache, prompts, k,
                         jnp.asarray(temperature, jnp.float32))
        self._caches.put(B, cache)
        return toks

    # -- continuous batching over paged caches ------------------------------
    def _build_prefill_commit(self, page_size: int):
        """jitted (per prompt-length BUCKET): batch-1 prefill of the padded
        prompt with the true ``length`` as a traced position mask, then a
        masked scatter of the prompt's cache rows / SSM state into the
        lane's pages (tail page ids point at the garbage page). The pool is
        donated — admission writes in place. One compile serves every
        prompt length in the bucket.

        Mesh mode: traced under shard_map (``_shard_serve_fn``) — the body
        sees head-local params and pool slices, and the commit scatter
        writes each shard's own KVp slice of the request's pages."""
        cfg = self._serve_cfg

        def serve_prefill(params, pool, prompt, length, page_ids, lane):
            logits, pcache = lm_prefill(cfg, params,
                                        self._inputs(params, prompt),
                                        length=length)
            pool = commit_prefill(cfg, pool, pcache["blocks"], lane,
                                  page_ids, page_size, length=length)
            return logits, pool

        return self._shard_serve_fn(serve_prefill, n_plain=4, n_outs=2)

    def _build_batch_segment(self, segment: int, sampled: bool):
        """jitted fused scan of ``segment`` decode steps over the full lane
        pool. Compiled once per (segment, pool geometry): admission and
        finish only rewrite the block table / pos / token / key vectors
        between calls, never the graph. The session emits each request's
        prefill-sampled first token AT ADMISSION, so the scan emits the
        NEWLY sampled token of every step (the carried token was already
        reported) — matching ``generate``'s [prefill sample, decode
        samples...] stream so greedy outputs stay token-identical.
        Sampling state rides per lane: each lane folds its own per-request
        step into its own per-request key (SamplingParams threaded through
        the lanes by the session).

        Mesh mode: the whole segment scan runs under shard_map — every
        device decodes ITS head slice of every lane against its local page
        pool (O(tokens-attended)/tp pool bytes per device per step), the
        o-projection psums, and sampling runs replicated on identical
        logits, so every shard carries the same token stream."""
        cfg = self._serve_cfg

        def serve_decode_segment(params, pool, block_table, pos, tok, steps,
                                 temps, keys):
            def step(carry, _):
                tok, pool, pos, steps = carry
                logits, nc = lm_decode_step_paged(
                    cfg, params,
                    {"blocks": pool, "block_table": block_table, "pos": pos},
                    tok)
                nxt = sample_tokens(cfg, logits[:, -1], temps,
                                    keys if sampled else None, steps + 1)
                return (nxt, nc["blocks"], nc["pos"], steps + 1), nxt[:, 0]

            (tok, pool, _, _), toks = jax.lax.scan(
                step, (tok, pool, pos, steps), None, length=segment)
            return toks, tok, pool

        return self._shard_serve_fn(serve_decode_segment, n_plain=6,
                                    n_outs=3)

    def _role_ids(self, mixer_is_mamba: bool):
        return [i for i, r in enumerate(block_roles(self.cfg))
                if (r["mixer"] == "mamba") == mixer_is_mamba]

    def _build_pfx_prefill(self, page_size: int, tail: bool):
        """jitted prefill for prefix-cached sessions (per prompt-length
        bucket × prefix-page bucket). ``tail=False`` is the cold miss: the
        same masked prefill-commit as ``_build_prefill_commit`` but ALSO
        returning the device payload a finish donates to the index — the
        mamba end state, the page-boundary state snapshots (static slice
        positions per bucket; free — the per-position states already exist
        for the scan's output einsum), and the end logits the exact record
        stores. ``tail=True`` prefills ONLY the uncached tail of a partial
        hit: positions offset by the hit length, tail queries attending
        over the prefix K/V — read IN PLACE from the pool pages by the
        Pallas paged kernel, or (``REPRO_PAGED_KERNEL=0``) materialized via
        ``gather_prefix_kv`` (garbage-page padding masked by ``prefix_len``
        either way; bitwise-identical outputs) — and each mamba recurrence
        resumed from the hit's boundary state."""
        cfg = self.cfg
        attn_ids = self._role_ids(False)
        mamba_ids = self._role_ids(True)

        def run(params, pool, prompt, length, offset, prefix_ids,
                prefix_len, page_ids, lane, ssm_init):
            S = prompt.shape[1]
            boundaries = tuple(range(page_size, S + 1, page_size))
            kw = {}
            if tail and A.paged_kernel_enabled():
                kw = dict(offset=offset, prefix_len=prefix_len,
                          ssm_init=ssm_init, prefix_ids=prefix_ids,
                          prefix_pages={f"b{i}": pool[f"b{i}"]
                                        for i in attn_ids})
            elif tail:
                kw = dict(offset=offset, prefix_len=prefix_len,
                          ssm_init=ssm_init,
                          prefix={f"b{i}": A.gather_prefix_kv(
                              cfg, pool[f"b{i}"], prefix_ids)
                              for i in attn_ids})
            res = lm_prefill(cfg, params, self._inputs(params, prompt),
                             length=length, state_at=boundaries or None,
                             **kw)
            logits, pcache = res[0], res[1]
            snaps = res[2] if boundaries else {}
            pool = commit_prefill(cfg, pool, pcache["blocks"], lane,
                                  page_ids, page_size, length=length)
            end_ssm = {f"b{i}": pcache["blocks"][f"b{i}"]
                       for i in mamba_ids}
            return logits, pool, end_ssm, snaps

        if tail:
            def serve_tail_prefill(params, pool, prompt, length, offset,
                                   prefix_ids, prefix_len, page_ids, lane,
                                   ssm_init):
                return run(params, pool, prompt, length, offset, prefix_ids,
                           prefix_len, page_ids, lane, ssm_init)
            return jax.jit(serve_tail_prefill, donate_argnums=(1,))

        def serve_prefill(params, pool, prompt, length, page_ids, lane):
            return run(params, pool, prompt, length, None, None, None,
                       page_ids, lane, None)
        return jax.jit(serve_prefill, donate_argnums=(1,))

    def _build_hit_admit(self, fork: bool, has_ssm: bool):
        """jitted exact-hit admission: CoW-fork the record's partially-
        filled boundary page onto the request's private page (src → dst)
        and/or write the stored mamba end state into the request's lane.
        The only device work a bit-identical cache hit pays — no prefill."""
        cfg = self.cfg
        mamba_ids = self._role_ids(True)

        def serve_hit_admit(pool, src, dst, lane, end_ssm):
            if fork:
                pool = fork_page(cfg, pool, src, dst)
            if has_ssm:
                pool = dict(pool)
                for i in mamba_ids:
                    pool[f"b{i}"] = M.mamba_cache_lane_write(
                        pool[f"b{i}"], end_ssm[f"b{i}"], lane)
            return pool

        return jax.jit(serve_hit_admit, donate_argnums=(0,))

    def session(self, *, lanes: int = 4, page_size: int = 16,
                n_pages: Optional[int] = None, segment: int = 1,
                key: Optional[jax.Array] = None,
                buckets: Optional[Sequence[int]] = None,
                prefix_cache: Optional[bool] = None,
                **robustness) -> ServeSession:
        """Open a streaming serve session: submit/stream/cancel requests at
        any time over one paged pool (see serve/session.py).
        ``prefix_cache`` overrides the engine default (radix-indexed
        cross-request prompt-page sharing — serve/prefix_cache.py).
        ``**robustness`` forwards the overload/fault knobs (``max_pending``,
        ``tenant_page_quota``, ``tenant_lane_quota``, ``faults``,
        ``audit``, ``clock``, ``host_page_budget`` — see ServeSession)."""
        use_pfx = self.prefix_cache if prefix_cache is None else prefix_cache
        if use_pfx and self.mesh is not None:
            raise NotImplementedError(
                "prefix_cache under a serve mesh is not implemented "
                "(ROADMAP follow-up)")
        return ServeSession(self, lanes=lanes, page_size=page_size,
                            n_pages=n_pages, segment=segment, key=key,
                            buckets=buckets, prefix_cache=prefix_cache,
                            **robustness)

    def generate_batch(self,
                       prompts: Sequence,
                       n_tokens: Union[int, Sequence[int]],
                       temperatures=None,
                       key: Optional[jax.Array] = None, *,
                       lanes: int = 4,
                       page_size: int = 16,
                       n_pages: Optional[int] = None,
                       segment: int = 1,
                       prefix_cache: Optional[bool] = None):
        """Continuous-batching generation over a paged cache pool — a thin
        wrapper over ``session()``: submit every request, run the segment
        loop until idle, collect results in request order.

        prompts: sequence of 1-D int32 token arrays (mixed lengths);
        n_tokens: per-request token budget (int broadcasts). Returns a list
        of (n_tokens_i,) int32 arrays in request order.

        GREEDY decode is token-identical to per-request ``generate`` (the
        parity oracle); sampled decode (``key`` given) folds (request id,
        step) into ``key`` per lane, so a request's stream doesn't depend
        on lane placement or co-tenants (but differs from the
        single-request path's batch-level stream). ``temperatures`` without
        a ``key`` decodes greedily, as before the session redesign.
        """
        n = len(prompts)
        n_tok = ([int(n_tokens)] * n if isinstance(n_tokens, int)
                 else [int(t) for t in n_tokens])
        temps = ([0.0] * n if temperatures is None
                 else [float(t) for t in temperatures])
        if len(n_tok) != n or len(temps) != n:
            raise ValueError(f"{n} prompts but {len(n_tok)} n_tokens / "
                             f"{len(temps)} temperatures")
        if key is None:
            temps = [0.0] * n
        sess = self.session(lanes=lanes, page_size=page_size,
                            n_pages=n_pages, segment=segment, key=key,
                            prefix_cache=prefix_cache)
        try:
            # submit everything BEFORE stepping: a never-fitting request
            # fails here, before any compute is spent on its pool-mates
            handles = [sess.submit(p, SamplingParams(max_tokens=nt,
                                                     temperature=t))
                       for p, nt, t in zip(prompts, n_tok, temps)]
            sess.run_until_idle()
            return [h.result() for h in handles]
        finally:
            sess.close()

    # -- seed per-token loop: parity oracle / benchmark baseline ------------
    def generate_eager(self, prompts: jax.Array, n_tokens: int,
                       temperature: float = 0.0,
                       key: Optional[jax.Array] = None) -> jax.Array:
        """The seed decode path: one jitted dispatch per token. Kept only to
        prove the fused scan path is token-identical (tests) and to anchor
        the tokens/sec trajectory (benchmarks)."""
        B, S = prompts.shape
        assert S + n_tokens <= self.max_len
        if self.tp > 1:
            raise NotImplementedError(
                "generate_eager() uses the contiguous-cache path, which is "
                "not mesh-sharded — use session()/generate_batch()")
        cache, _ = cache_init(self.cfg, B, self.max_len)
        logits, cache = self._prefill(self.params,
                                      self._inputs(self.params, prompts),
                                      cache)
        out = []
        tok = sample_tokens(self.cfg, logits[:, -1], temperature, key, 0)
        for i in range(n_tokens):
            out.append(tok)
            logits, cache = self._decode(self.params, cache, tok)
            tok = sample_tokens(self.cfg, logits[:, -1], temperature, key,
                                i + 1)
        return jnp.concatenate(out, axis=1)
