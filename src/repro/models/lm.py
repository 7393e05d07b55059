"""Unified decoder LM: every assigned architecture is an instance of this
module (family-dispatched block roles), with B⊕LD Boolean projections as the
first-class weight type.

Layer stack is scanned (``lax.scan`` over parameter leaves stacked on a
leading ``n_groups`` axis) — compile time and HLO size stay O(1) in depth,
which is what makes the 80-layer/480B dry-runs tractable.

Heterogeneous stacks (gemma2 local/global pairs, jamba 1:7 mamba:attn groups
with alternating MoE) are expressed as a ``group`` of ``group_size`` blocks
with static in-group roles; the scan runs over groups.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import attention as A
from . import ffn as F
from . import mamba as M
from . import moe as MOE
from .modules import (MODEL_AXIS, ModelConfig, batch_spec, constrain,
                      embed_apply, embed_init, head_apply, head_init,
                      rmsnorm_apply, rmsnorm_init, unzip)


# ---------------------------------------------------------------------------
# Block roles
# ---------------------------------------------------------------------------
def block_roles(cfg: ModelConfig) -> List[Dict[str, Optional[str]]]:
    """Static per-in-group-index roles: mixer in {attn, attn_local, mamba},
    ffn in {dense, moe, moe+dense, None}."""
    if cfg.family == "ssm":
        return [{"mixer": "mamba", "ffn": None}]
    if cfg.family == "hybrid":
        roles = []
        for i in range(cfg.group_size):
            mixer = "attn" if i == cfg.attn_index else "mamba"
            ffn = "moe" if (i % 2 == 1 and cfg.n_experts > 0) else "dense"
            roles.append({"mixer": mixer, "ffn": ffn})
        return roles
    if cfg.alt_local_global:
        return [{"mixer": "attn_local", "ffn": "dense"},
                {"mixer": "attn", "ffn": "dense"}]
    if cfg.n_experts > 0:
        ffn = "moe+dense" if cfg.moe_dense_residual else "moe"
        return [{"mixer": "attn", "ffn": ffn}]
    return [{"mixer": "attn", "ffn": "dense"}]


def _block_init(key, cfg: ModelConfig, role) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": rmsnorm_init(cfg.d_model)}
    if role["mixer"] == "mamba":
        p["mamba"] = M.mamba_init(ks[0], cfg)
    else:
        p["attn"] = A.attention_init(ks[0], cfg)
    if role["ffn"] is not None:
        p["norm2"] = rmsnorm_init(cfg.d_model)
        if "moe" in role["ffn"]:
            p["moe"] = MOE.moe_init(ks[1], cfg)
        if "dense" in role["ffn"]:
            p["ffn"] = F.ffn_init(ks[2], cfg, cfg.dense_ff_
                                  if role["ffn"] != "dense" else cfg.d_ff)
    return p


def _group_init(key, cfg: ModelConfig):
    roles = block_roles(cfg)
    ks = jax.random.split(key, len(roles))
    return {f"b{i}": _block_init(ks[i], cfg, r) for i, r in enumerate(roles)}


def _stack_groups(key, cfg: ModelConfig):
    """Loop-stack per-group params onto a leading (n_groups,) axis and
    prepend None to every PartitionSpec."""
    keys = jax.random.split(key, cfg.n_groups)
    trees = [unzip(_group_init(k, cfg)) for k in keys]
    params = jax.tree.map(lambda *xs: jnp.stack(xs), *[t[0] for t in trees])
    specs = jax.tree.map(lambda s: P(None, *s), trees[0][1],
                         is_leaf=lambda x: isinstance(x, P))
    return params, specs


def lm_init(key, cfg: ModelConfig):
    """Returns (params, specs) — trees of identical structure."""
    ks = jax.random.split(key, 3)
    blocks, block_specs = _stack_groups(ks[0], cfg)
    embed_p, embed_s = unzip(embed_init(ks[1], cfg))
    head_p, head_s = unzip(head_init(ks[2], cfg))
    fn_p, fn_s = unzip(rmsnorm_init(cfg.d_model))
    params = {"embed": embed_p, "blocks": blocks, "final_norm": fn_p,
              "head": head_p}
    specs = {"embed": embed_s, "blocks": block_specs, "final_norm": fn_s,
             "head": head_s}
    return params, specs


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------
def _ckpt_name(cfg, x):
    if cfg.remat_policy == "save_block_outs":
        from jax.ad_checkpoint import checkpoint_name

        return checkpoint_name(x, "blk_out")
    return x


def _apply_block(cfg: ModelConfig, bp, role, x, positions):
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm_apply(bp["norm1"], x)
    if role["mixer"] == "mamba":
        mix = M.mamba_apply(cfg, bp["mamba"], h)
    else:
        mix = A.attention_apply(cfg, bp["attn"], h, positions,
                                local=(role["mixer"] == "attn_local"))
    x = x + _ckpt_name(cfg, mix)
    if role["ffn"] is not None:
        h = rmsnorm_apply(bp["norm2"], x)
        out = jnp.zeros_like(x)
        if "moe" in role["ffn"]:
            moe_out, moe_aux = MOE.moe_apply(cfg, bp["moe"], h)
            out = out + moe_out
            aux = aux + moe_aux
        if "dense" in role["ffn"]:
            out = out + F.ffn_apply(cfg, bp["ffn"], h)
        x = x + _ckpt_name(cfg, out)
    return x, aux


def _scan_blocks(cfg: ModelConfig, blocks, x, positions):
    roles = block_roles(cfg)

    def body(carry, gparams):
        x, aux = carry
        for i, role in enumerate(roles):
            x, a = _apply_block(cfg, gparams[f"b{i}"], role, x, positions)
            aux = aux + a
            if cfg.block_grad_barriers and i + 1 < len(roles):
                x, aux = jax.lax.optimization_barrier((x, aux))
        x = constrain(cfg, x, batch_spec(cfg, None, None))
        return (x, aux), None

    if cfg.remat:
        policy = None
        if cfg.remat_policy == "save_block_outs":
            policy = jax.checkpoint_policies.save_only_these_names("blk_out")
        body = jax.checkpoint(body, prevent_cse=False, policy=policy)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), blocks)
    return x, aux


def _inputs_to_h(cfg: ModelConfig, params, batch):
    if cfg.frontend == "embeddings":
        # Modality frontend STUB: precomputed frame/patch embeddings.
        h = batch["embeddings"].astype(cfg.dtype)
    else:
        h = embed_apply(cfg, params["embed"], batch["tokens"]).astype(cfg.dtype)
    return h


def lm_forward(cfg: ModelConfig, params, batch):
    """-> (logits fp32 (B,S,Vp), aux_loss scalar)."""
    h = _inputs_to_h(cfg, params, batch)
    B, S = h.shape[0], h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    h, aux = _scan_blocks(cfg, params["blocks"], h, positions)
    h = rmsnorm_apply(params["final_norm"], h)
    logits = head_apply(cfg, params["head"], h)
    return logits, aux


def lm_loss(cfg: ModelConfig, params, batch):
    """Cross-entropy over the (padded) vocab with padded-slot masking.

    The vocab dim stays sharded over "model" through the softmax (the
    reductions cross the shard boundary as tiny (B,S) stats) — a 256k-vocab
    logits tensor must never be gathered per device.
    """
    logits, aux = lm_forward(cfg, params, batch)
    logits = constrain(cfg, logits, batch_spec(cfg, None, MODEL_AXIS))
    labels = batch["labels"]
    pad_mask = jnp.arange(cfg.vocab_padded) < cfg.vocab_size
    logits = jnp.where(pad_mask[None, None, :], logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - picked
    weights = batch.get("loss_mask", jnp.ones_like(labels, jnp.float32))
    loss = jnp.sum(nll * weights) / jnp.maximum(jnp.sum(weights), 1.0)
    return loss + 0.01 * aux, {"nll": loss, "aux": aux}


# ---------------------------------------------------------------------------
# KV / SSM caches + decode
# ---------------------------------------------------------------------------
def _block_cache_init(cfg: ModelConfig, role, batch, max_len,
                      shard_seq: bool):
    if role["mixer"] == "mamba":
        return M.mamba_cache_init(cfg, batch)
    return A.attention_cache_init(cfg, batch, max_len, shard_seq=shard_seq)


def cache_init(cfg: ModelConfig, batch: int, max_len: int,
               shard_seq: bool = False):
    """Stacked (n_groups, ...) cache + specs + pos scalar."""
    roles = block_roles(cfg)
    caches, specs = {}, {}
    for i, role in enumerate(roles):
        c, s = _block_cache_init(cfg, role, batch, max_len, shard_seq)
        caches[f"b{i}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_groups,) + a.shape), c)
        specs[f"b{i}"] = jax.tree.map(
            lambda sp: P(None, *sp), s, is_leaf=lambda x: isinstance(x, P))
    return ({"blocks": caches, "pos": jnp.zeros((), jnp.int32)},
            {"blocks": specs, "pos": P()})


def _apply_block_decode(cfg: ModelConfig, bp, role, bcache, x, pos,
                        block_table=None, layer=None):
    """One block of decode. ``bcache`` is the block's cache for this layer
    group, except for a paged attention block (``block_table`` given),
    whose ``bcache`` is the stacked pool of every group, addressed at
    group ``layer``."""
    h = rmsnorm_apply(bp["norm1"], x)
    if role["mixer"] == "mamba":
        # SSM state is O(1) per lane — lane-indexed directly, never paged.
        mix, new_c = M.mamba_decode(cfg, bp["mamba"], h, bcache)
    elif block_table is not None:
        mix, new_c = A.attention_decode_paged(
            cfg, bp["attn"], h, bcache, block_table, pos, layer,
            local=(role["mixer"] == "attn_local"))
    else:
        mix, new_c = A.attention_decode(cfg, bp["attn"], h, bcache, pos,
                                        local=(role["mixer"] == "attn_local"))
    x = x + mix
    if role["ffn"] is not None:
        h = rmsnorm_apply(bp["norm2"], x)
        out = jnp.zeros_like(x)
        if "moe" in role["ffn"]:
            moe_out, _ = MOE.moe_apply(cfg, bp["moe"], h)
            out = out + moe_out
        if "dense" in role["ffn"]:
            out = out + F.ffn_apply(cfg, bp["ffn"], h)
        x = x + out
    return x, new_c


def lm_decode_step(cfg: ModelConfig, params, cache, tokens):
    """One-token decode. tokens: (B,1) int32. Returns (logits, new_cache).

    The stacked (n_groups, ...) cache rides the layer scan as CARRY (not
    xs→ys), so the while-loop aliases the donated cache buffers instead of
    double-buffering the multi-GiB KV stack (§Perf: decode-cache-carry).
    How each block reaches its group ``g`` of the carry depends on what it
    is:

    * a paged attention block (the cache has a ``block_table``: see
      ``lm_decode_step_paged``) keeps its pool leaves stacked and addresses
      them at ``g`` — the new rows are scattered into ``(g, page, offset)``
      in place and the kernel reads layer ``g``'s pages where they lie, so
      no layer-sized slice of the pool is taken or written back;
    * every other block (mamba state, and the contiguous cache that
      carries a scalar ``pos``) slices group ``g`` out of the carry, and
      writes the whole slice back after the block.
    """
    pos = cache["pos"]
    block_table = cache.get("block_table")
    h = embed_apply(cfg, params["embed"], tokens).astype(cfg.dtype)
    roles = block_roles(cfg)

    def body(carry, gparams):
        x, blocks, g = carry
        blocks = dict(blocks)
        for i, role in enumerate(roles):
            bp, b = gparams[f"b{i}"], blocks[f"b{i}"]
            if block_table is not None and role["mixer"] != "mamba":
                x, blocks[f"b{i}"] = _apply_block_decode(
                    cfg, bp, role, b, x, pos, block_table, layer=g)
                continue
            c = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, g, 0,
                                                       keepdims=False), b)
            x, c = _apply_block_decode(cfg, bp, role, c, x, pos)
            blocks[f"b{i}"] = jax.tree.map(
                lambda full, nc: jax.lax.dynamic_update_index_in_dim(
                    full, nc.astype(full.dtype), g, 0), b, c)
        return (x, blocks, g + 1), None

    (h, new_blocks, _), _ = jax.lax.scan(
        body, (h, cache["blocks"], jnp.zeros((), jnp.int32)),
        params["blocks"])
    h = rmsnorm_apply(params["final_norm"], h)
    logits = head_apply(cfg, params["head"], h)
    new_cache = {"blocks": new_blocks, "pos": pos + 1}
    if block_table is not None:
        new_cache["block_table"] = block_table
    return logits, new_cache


def lm_decode_step_paged(cfg: ModelConfig, params, cache, tokens):
    """One decode step over L scheduler lanes with a block-table paged cache.

    cache: {"blocks": paged pool (serve/paged_cache.py layout),
            "block_table": (L, C) int32, "pos": (L,) int32}; tokens (L,1).
    Identical math to ``lm_decode_step`` per lane, but every lane sits at
    its own position: attention writes scatter into the shared page pool
    at (layer group, page, offset) and reads walk the lane's pages of that
    group in place; SSM state is lane-indexed.
    The lane count L is the jit-stable batch shape — admission/eviction
    only rewrites the (tiny) block table and pos vector, never the graph.
    """
    return lm_decode_step(cfg, params, cache, tokens)


# ---------------------------------------------------------------------------
# Prefill: forward over the full prompt, writing the prompt's KV/SSM state
# into a cache PREALLOCATED at max_len (lax.dynamic_update_slice at offset 0)
# — no prompt-length-sized caches ever exist, so decode never re-materializes
# or pads them and the whole (prefill + decode scan) jit can alias a donated
# cache buffer end to end.
# ---------------------------------------------------------------------------
def _apply_block_prefill(cfg: ModelConfig, bp, role, x, positions,
                         length=None, prefix=None, prefix_len=None,
                         prefix_pages=None, prefix_ids=None,
                         ssm_init=None, state_at=None):
    """One block of (possibly tail-) prefill. Returns (x, cache entry,
    snap) — ``snap`` is the mamba page-boundary state snapshots when
    ``state_at`` is set (None otherwise / for attention blocks).

    ``prefix`` ({"k"/"v": (1, P, KVp, hd)} fp32, rows valid below
    ``prefix_len``): a cached prefix's K/V gathered from pool pages —
    queries attend over prefix + tail with absolute-position masking.
    ``prefix_pages``/``prefix_ids`` (the in-place alternative — default
    when the paged kernel is enabled): the block's RAW pool leaves plus the
    prefix's physical page ids; the Pallas kernel reads the pages straight
    from the pool, so the gathered prefix rows never materialize.
    ``ssm_init``: the prefix-boundary mamba state the recurrence resumes
    from. All None ⇒ exactly the cold prefill graph.
    """
    snap = None
    h = rmsnorm_apply(bp["norm1"], x)
    if role["mixer"] == "mamba":
        h0 = conv0 = None
        if ssm_init is not None:
            h0, conv0 = ssm_init["h"], ssm_init["conv"]
        res = M.mamba_apply(cfg, bp["mamba"], h, h0=h0, conv0=conv0,
                            return_state=True, length=length,
                            state_at=state_at)
        if state_at is not None:
            mix, (h_last, conv_state), snap = res
        else:
            mix, (h_last, conv_state) = res
        new_c = {"h": h_last, "conv": conv_state.astype(jnp.float32)}
    else:
        local = role["mixer"] == "attn_local"
        B, S, _ = x.shape
        q, k, v = A._qkv(cfg, bp["attn"], h, positions)
        # LOCAL head counts under serve-TP (global when serve_tp == 1);
        # hp // kvp is the global GQA group size either way.
        hp, kvp = A._tp_heads(cfg)
        kk = A._repeat_kv(k, hp // kvp)
        vv = A._repeat_kv(v, hp // kvp)
        window = cfg.sliding_window if local else 0
        if prefix_pages is not None:
            out = A.flash_prefix_attention_paged(
                cfg, prefix_pages, prefix_ids, q, k, v, positions,
                prefix_len, length, local=local)
        elif prefix is None:
            out = A.flash_attention(q, kk, vv, causal=True, window=window,
                                    softcap_val=cfg.attn_logit_softcap,
                                    chunk=cfg.attn_chunk)
        else:
            P = prefix["k"].shape[1]
            pk = A._repeat_kv(prefix["k"].astype(x.dtype), hp // kvp)
            pv = A._repeat_kv(prefix["v"].astype(x.dtype), hp // kvp)
            live = (jnp.arange(S) < jnp.asarray(length, jnp.int32)
                    if length is not None else jnp.ones((S,), bool))
            out = A.flash_attention_abs(
                q, jnp.concatenate([pk, kk], axis=1),
                jnp.concatenate([pv, vv], axis=1),
                q_pos=positions[0],
                k_pos=jnp.concatenate([jnp.arange(P, dtype=jnp.int32),
                                       positions[0]]),
                k_valid=jnp.concatenate(
                    [jnp.arange(P) < jnp.asarray(prefix_len, jnp.int32),
                     live]),
                window=window, softcap_val=cfg.attn_logit_softcap,
                chunk=cfg.attn_chunk)
        out = A._head_mask(cfg, out)
        mix = A._wo_project(cfg, bp["attn"]["wo"],
                            out.reshape(B, S, hp * cfg.head_dim_))
        new_c = A.kv_cache_entry(cfg, k, v)
    x = x + mix
    if role["ffn"] is not None:
        hh = rmsnorm_apply(bp["norm2"], x)
        out = jnp.zeros_like(x)
        if "moe" in role["ffn"]:
            moe_out, _ = MOE.moe_apply(cfg, bp["moe"], hh)
            out = out + moe_out
        if "dense" in role["ffn"]:
            out = out + F.ffn_apply(cfg, bp["ffn"], hh)
        x = x + out
    return x, new_c, snap


def lm_prefill(cfg: ModelConfig, params, batch, cache=None,
               max_len: Optional[int] = None, length=None, offset=None,
               prefix=None, prefix_len=None, prefix_pages=None,
               prefix_ids=None, ssm_init=None, state_at=None):
    """Prefill over (B,S) inputs -> (last-position logits, populated cache).

    ``cache`` is a preallocated ``cache_init`` tree (sized max_len) that the
    prompt state is written into; pass one to reuse/donate buffers across
    requests. When omitted, one is allocated at ``max_len`` (default S).

    ``length`` (traced int32 scalar, optional) marks the true prompt length
    when the inputs are right-padded to a compile bucket: logits come from
    position ``length-1`` instead of ``S-1``, and the SSM recurrence freezes
    on positions >= length (decay=1, input=0) so the returned state is
    exactly the state after the true prompt. Attention rows < length are
    already pad-invariant under the causal mask; their cache rows are
    masked/committed by the caller (serve/paged_cache.commit_prefill). One
    compiled prefill then serves every prompt length in the bucket.

    Prefix-cache TAIL prefill (serve/prefix_cache.py): the inputs are the
    UNCACHED tail of a prompt whose first ``offset`` tokens already live in
    pool pages. ``offset`` (traced scalar) shifts positions (RoPE is
    absolute); ``prefix`` ({bi: {"k"/"v": (G, 1, P, KVp, hd)}} gathered via
    ``gather_prefix_kv``, rows valid below ``prefix_len``) lets tail
    queries attend over the cached rows — or, when the Pallas paged kernel
    is on, ``prefix_pages`` ({bi: the block's RAW pool leaves, leading G})
    plus ``prefix_ids`` ((npp,) int32 physical pages) reads them IN PLACE
    from the pool so the gathered rows never materialize (bitwise-identical
    outputs); ``ssm_init`` ({bi: {"h", "conv"}},
    leading G) resumes each mamba recurrence from the prefix-boundary
    state. ``state_at`` (STATIC position tuple) additionally returns mamba
    state snapshots at those tail-relative positions — the page-boundary
    states a finished request donates to the prefix index — as a third
    result {bi: {"h": (G, B, len(state_at), DI, N), "conv": ...}}.
    All four default to None ⇒ the exact cold-prefill graph.
    """
    h = _inputs_to_h(cfg, params, batch)
    B, S = h.shape[0], h.shape[1]
    if cache is None:
        cache, _ = cache_init(cfg, B, max_len or S)
    pos_row = jnp.arange(S, dtype=jnp.int32)
    if offset is not None:
        pos_row = pos_row + jnp.asarray(offset, jnp.int32)
    positions = jnp.broadcast_to(pos_row, (B, S))
    roles = block_roles(cfg)

    def body(carry, xs):
        gparams, gprefix, gpages, gssm = xs
        x, blocks, g = carry
        gcache = jax.tree.map(
            lambda c: jax.lax.dynamic_index_in_dim(c, g, 0, keepdims=False),
            blocks)
        snaps = {}
        for i, role in enumerate(roles):
            x, c, snap = _apply_block_prefill(
                cfg, gparams[f"b{i}"], role, x, positions, length=length,
                prefix=None if gprefix is None else gprefix.get(f"b{i}"),
                prefix_len=prefix_len,
                prefix_pages=None if gpages is None else gpages.get(f"b{i}"),
                prefix_ids=prefix_ids,
                ssm_init=None if gssm is None else gssm.get(f"b{i}"),
                state_at=state_at)
            if snap is not None:
                snaps[f"b{i}"] = snap
            gcache[f"b{i}"] = jax.tree.map(A.cache_write, gcache[f"b{i}"], c)
        blocks = jax.tree.map(
            lambda full, nc: jax.lax.dynamic_update_index_in_dim(
                full, nc.astype(full.dtype), g, 0),
            blocks, gcache)
        return (x, blocks, g + 1), snaps

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (h, new_blocks, _), snaps = jax.lax.scan(
        body, (h, cache["blocks"], jnp.zeros((), jnp.int32)),
        (params["blocks"], prefix, prefix_pages, ssm_init))
    h = rmsnorm_apply(params["final_norm"], h)
    if length is None:
        last = h[:, -1:]
        pos = jnp.asarray(S, jnp.int32)
    else:
        last = jax.lax.dynamic_slice_in_dim(
            h, jnp.asarray(length, jnp.int32) - 1, 1, axis=1)
        pos = jnp.asarray(length, jnp.int32)
    if offset is not None:
        pos = pos + jnp.asarray(offset, jnp.int32)
    logits = head_apply(cfg, params["head"], last)
    new_cache = {"blocks": new_blocks, "pos": pos}
    if state_at is not None:
        return logits, new_cache, snaps
    return logits, new_cache
