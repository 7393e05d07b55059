"""Jamba's hybrid decoder without experts (AI21-Jamba2-3B), as the
program's B⊕LD model defines it. One stacked index is one period of
``attn_layer_period`` blocks ``b0..b{period-1}``, read from the published
offset and period as HF's ``JambaConfig`` lays them out: block
``attn_layer_offset`` attends, every other block is Mamba-1. Every block
is pre-norm mixing, then the gated Boolean FFN (``num_experts`` 1 is a
plain MLP).

* Attention: multi-query (one KV head), no positional encoding, no bias.
  The query heads are laid out as ``heads_padded`` (a multiple of 16); the
  padded heads are zero before the o-projection, whose fan-in (and scale)
  is heads_padded * head_dim. The program packs q, k and v as one fused
  ``wqkv`` leaf, columns [q | k | v].
* Mamba: as ``families/ssm.py``, with Jamba's RMS norms on the dt rank
  slice, B and C (gain 1 + scale, eps 1e-6) before ``dt_proj`` and the
  scan; conv with bias, projections without.
"""
from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp

import family
import reference as R

dense = family.load("dense")


def _norm_gain(key, shape):
    return 0.1 * jax.random.normal(key, shape, jnp.float32)


# the dt/B/C norms' scales, by the leaf names the program gives them
RECIPES = {"dt_norm": _norm_gain, "b_norm": _norm_gain,
           "c_norm": _norm_gain}


def model_config(cfg: dict) -> dict:
    prog = cfg["program"]
    return dict(family="hybrid",
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                head_dim=prog["head_dim"],
                d_ff=cfg["intermediate_size"],
                ssm_state=cfg["mamba_d_state"],
                d_inner=cfg["mamba_expand"] * cfg["hidden_size"],
                dt_rank=cfg["mamba_dt_rank"],
                conv_width=cfg["mamba_d_conv"],
                group_size=cfg["attn_layer_period"],
                attn_index=cfg["attn_layer_offset"],
                use_rope=False, ssm_norms=True)


def validate(mc, cfg: dict) -> None:
    dense.validate(mc, cfg)
    if cfg["num_experts"] != 1 or cfg["mamba_proj_bias"] \
            or not cfg["mamba_conv_bias"]:
        raise ValueError("the jamba family serves one dense MLP a layer, "
                         "Mamba projections without bias, conv with bias")
    if cfg["num_hidden_layers"] % cfg["attn_layer_period"]:
        raise ValueError("depth must be whole attention periods")


def sizes(cfg: dict) -> dict:
    prog = cfg["program"]
    return dict(heads=cfg["num_attention_heads"],
                kv=cfg["num_key_value_heads"], hd=prog["head_dim"],
                hp=prog["heads_padded"], ff=cfg["intermediate_size"],
                di=cfg["mamba_expand"] * cfg["hidden_size"],
                n=cfg["mamba_d_state"], r=cfg["mamba_dt_rank"],
                w=cfg["mamba_d_conv"], period=cfg["attn_layer_period"],
                attn=cfg["attn_layer_offset"],
                stack=cfg["num_hidden_layers"] // cfg["attn_layer_period"],
                score_bytes=dense.SCORE_BYTES)


def layer_shapes(arch) -> dict:
    d, di, n, r = arch.d, arch.di, arch.n, arch.r
    f32, u32 = jnp.float32, jnp.uint32
    out = {}
    for i in range(arch.period):
        blk = f"['blocks']['b{i}']"
        f = blk + "['ffn']"
        out.update({
            blk + "['norm1']['scale']": ((d,), f32, 0),
            blk + "['norm2']['scale']": ((d,), f32, 0),
            f + "['wgu']['w']": ((d // 32, 2 * arch.ff), u32, d),
            f + "['wd']['w']": ((arch.ff // 32, d), u32, arch.ff),
            f + "['tau']": ((arch.ff,), f32, 0),
        })
        if i == arch.attn:
            a, hq = blk + "['attn']", arch.hp * arch.hd
            out.update({
                a + "['wqkv']['w']": ((d // 32, hq + 2 * arch.kv * arch.hd),
                                      u32, d),
                a + "['wo']['w']": ((hq // 32, d), u32, hq),
            })
            continue
        m = blk + "['mamba']"
        out.update({
            m + "['in_x']['w']": ((d // 32, di), u32, d),
            m + "['in_z']['w']": ((d // 32, di), u32, d),
            m + "['conv_w']": ((arch.w, di), f32, 0),
            m + "['conv_b']": ((di,), f32, 0),
            m + "['x_proj']['w']": ((di // 32, r + 2 * n), u32, di),
            m + "['dt_proj']['w']": ((-(-r // 32), di), u32, r),
            m + "['dt_bias']": ((di,), f32, 0),
            m + "['A_log']": ((di, n), f32, 0),
            m + "['D']": ((di,), f32, 0),
            m + "['out_proj']['w']": ((di // 32, d), u32, di),
            m + "['dt_norm']": ((r,), f32, 0),
            m + "['b_norm']": ((n,), f32, 0),
            m + "['c_norm']": ((n,), f32, 0),
        })
    return out


def _attention(arch, w, h, pos, mode):
    d, hd, hp, kv = arch.d, arch.hd, arch.hp, arch.kv
    S = h.shape[0]
    x = R._rms(h, w["norm1.scale"])
    qkv = R._proj(x, w["wqkv.w"], d, mode)
    q = qkv[:, :hp * hd].reshape(S, hp, hd)[:, :arch.heads]
    k = qkv[:, hp * hd:(hp + kv) * hd].reshape(S, kv, hd)
    v = qkv[:, (hp + kv) * hd:].reshape(S, kv, hd)
    group = jnp.arange(arch.heads) // (hp // kv)     # KV head of each head
    o = dense.attend(arch, q, k[:, group], v[:, group], pos, mode)
    wo = R.unpack_pm1(w["wo.w"], hp * hd)[:arch.heads * hd]
    return h + R._mm(o, wo, mode) / math.sqrt(hp * hd)


def _mamba(arch, w, h, mode):
    d, di, n, r = arch.d, arch.di, arch.n, arch.r
    S = h.shape[0]
    x = R._rms(h, w["norm1.scale"])
    xin = R._proj(x, w["in_x.w"], d, mode)
    z = R._proj(x, w["in_z.w"], d, mode)
    cw = w["conv_w"]
    xp = jnp.concatenate([jnp.zeros((arch.w - 1, di), jnp.float32), xin])
    xc = jax.nn.silu(sum(xp[i:i + S] * cw[i] for i in range(arch.w))
                     + w["conv_b"])
    dbc = R._proj(xc, w["x_proj.w"], di, mode)
    dt_r = R._rms(dbc[:, :r], w["dt_norm"])
    bm = R._rms(dbc[:, r:r + n], w["b_norm"])
    cm = R._rms(dbc[:, r + n:], w["c_norm"])
    dt = jax.nn.softplus(R._proj(dt_r, w["dt_proj.w"], r, mode)
                         + w["dt_bias"])
    a = -jnp.exp(w["A_log"])                          # (di, n)

    def step(state, inp):
        dt_t, x_t, b_t, c_t = inp
        state = jnp.exp(dt_t[:, None] * a) * state \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return state, state @ c_t

    _, y = jax.lax.scan(step, jnp.zeros((di, n), jnp.float32),
                        (dt, xc, bm, cm))
    y = (y + w["D"] * xc) * jax.nn.silu(z)
    return h + R._proj(y, w["out_proj.w"], di, mode)


def _ffn(arch, w, h, mode):
    x = R._rms(h, w["norm2.scale"])
    gu = R._proj(x, w["wgu.w"], arch.d, mode)
    g, u = gu[:, :arch.ff], gu[:, arch.ff:]
    a = jnp.where(g - w["tau"] >= 0, 1.0, -1.0)
    return h + R._proj(a * u, w["wd.w"], arch.ff, mode)


def _block(w, i: int) -> dict:
    """Block i's leaves by their short names (``reference._short`` gives
    block 0's without a ``b0.`` prefix)."""
    if i:
        pre = f"b{i}."
        return {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
    return {k: v for k, v in w.items() if not re.match(r"b\d+\.", k)}


def layer(arch, w, h, pos, mode):
    """One period: its blocks in order, each mixing then its FFN."""
    for i in range(arch.period):
        bw = _block(w, i)
        h = _attention(arch, bw, h, pos, mode) if i == arch.attn \
            else _mamba(arch, bw, h, mode)
        h = _ffn(arch, bw, h, mode)
    return h


def decode_work(cfg: dict) -> dict:
    """Published widths: 26 Mamba layers' projections, 2 attention layers
    of 20 query heads and 1 KV head of 128 (not the 32 heads the program
    lays out), and every layer's FFN."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    n_attn = layers // cfg["attn_layer_period"]
    n_mamba = layers - n_attn
    hd = cfg["program"]["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    di = cfg["mamba_expand"] * d
    r, n = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
    ff = cfg["intermediate_size"]
    return {"projections": [(d, di, n_mamba), (d, di, n_mamba),
                            (di, r + 2 * n, n_mamba), (r, di, n_mamba),
                            (di, d, n_mamba),
                            (d, q, n_attn), (d, kv, n_attn),
                            (d, kv, n_attn), (q, d, n_attn),
                            (d, 2 * ff, layers), (ff, d, layers)],
            "attention": {"layers": n_attn,
                          "heads": cfg["num_attention_heads"],
                          "kv_heads": cfg["num_key_value_heads"],
                          "head_dim": hd}}
