"""Dense GQA decoder with QKV bias (qwen2.5), as the program's B⊕LD model
defines it: pre-norm attention with RoPE, then the gated Boolean FFN.

One stacked index is one layer. The query heads are laid out as
``heads_padded`` (the program pads to a multiple of 16): head h reads KV
head h // (heads_padded / kv_heads), the padded heads are zero before the
o-projection, whose fan-in (and scale) is heads_padded * head_dim.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import reference as R

# Score bytes (float32, all heads) one block of query rows may hold: above
# it the reference attends over blocks of rows, so that it fits a chip at
# long contexts; at or below it, it attends over all rows at once.
SCORE_BYTES = 2**30


def model_config(cfg: dict) -> dict:
    prog = cfg["program"]
    return dict(n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                d_ff=cfg["intermediate_size"],
                rope_theta=float(cfg["rope_theta"]),
                qkv_bias=bool(prog["qkv_bias"]),
                head_dim=prog.get("head_dim", 0))


def validate(mc, cfg: dict) -> None:
    if mc.heads_padded() != cfg["program"]["heads_padded"]:
        raise ValueError(f"program pads {mc.n_heads} heads to "
                         f"{mc.heads_padded()}, the file says "
                         f"{cfg['program']['heads_padded']}")


def sizes(cfg: dict) -> dict:
    prog = cfg["program"]
    return dict(heads=cfg["num_attention_heads"],
                kv=cfg["num_key_value_heads"],
                hd=prog.get("head_dim",
                            cfg["hidden_size"] // cfg["num_attention_heads"]),
                hp=prog["heads_padded"], ff=cfg["intermediate_size"],
                theta=float(cfg["rope_theta"]), score_bytes=SCORE_BYTES)


def layer_shapes(arch) -> dict:
    d, blk = arch.d, "['blocks']['b0']"
    f32 = jnp.float32
    hq, hkv = arch.hp * arch.hd, arch.kv * arch.hd
    a, f = blk + "['attn']", blk + "['ffn']"
    return {
        blk + "['norm1']['scale']": ((d,), f32, 0),
        blk + "['norm2']['scale']": ((d,), f32, 0),
        a + "['wq']['w']": ((d // 32, hq), jnp.uint32, d),
        a + "['wq']['b']": ((hq,), f32, 0),
        a + "['wk']['w']": ((d // 32, hkv), jnp.uint32, d),
        a + "['wk']['b']": ((hkv,), f32, 0),
        a + "['wv']['w']": ((d // 32, hkv), jnp.uint32, d),
        a + "['wv']['b']": ((hkv,), f32, 0),
        a + "['wo']['w']": ((hq // 32, d), jnp.uint32, hq),
        f + "['wgu']['w']": ((d // 32, 2 * arch.ff), jnp.uint32, d),
        f + "['wd']['w']": ((arch.ff // 32, d), jnp.uint32, arch.ff),
        f + "['tau']": ((arch.ff,), f32, 0),
    }


def query_rows(S: int, heads: int, budget: int) -> int:
    """Rows of one block of queries: all S where their scores fit
    ``budget`` bytes, else the largest divisor of S whose scores do."""
    if heads * S * S * 4 <= budget:
        return S
    most = max(1, budget // (heads * S * 4))
    return max(r for r in range(1, most + 1) if S % r == 0)


def attend(arch, q, kh, vh, pos, mode):
    """Causal softmax attention of q (S, heads, hd) over kh, vh (S, heads,
    hd), in blocks of query rows (``query_rows``); each row's scores and
    weights are those of the unblocked attention."""
    S, H, hd = q.shape

    def block(qb, pb):
        causal = pb[:, None] >= pos[None, :]
        s = jnp.einsum("qhd,khd->hqk", R._quant(qb, mode),
                       R._quant(kh, mode), precision=R.HI) / math.sqrt(hd)
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", R._quant(p, mode),
                          R._quant(vh, mode), precision=R.HI)

    rows = query_rows(S, H, arch.score_bytes)
    if rows == S:
        return block(q, pos).reshape(S, H * hd)
    nb = S // rows
    o = jax.lax.map(lambda a: block(*a),
                    (q.reshape(nb, rows, H, hd), pos.reshape(nb, rows)))
    return o.reshape(S, H * hd)


def layer(arch, w, h, pos, mode):
    d, hd, hp, kv = arch.d, arch.hd, arch.hp, arch.kv
    S = h.shape[0]
    x = R._rms(h, w["norm1.scale"])
    q = R._proj(x, w["wq.w"], d, mode, w["wq.b"]).reshape(S, hp, hd)
    k = R._proj(x, w["wk.w"], d, mode, w["wk.b"]).reshape(S, kv, hd)
    v = R._proj(x, w["wv.w"], d, mode, w["wv.b"]).reshape(S, kv, hd)
    q = R._rope(q, pos, arch.theta)[:, :arch.heads]
    k = R._rope(k, pos, arch.theta)
    group = jnp.arange(arch.heads) // (hp // kv)     # KV head of each head
    kh, vh = k[:, group], v[:, group]                 # (S, heads, hd)
    o = attend(arch, q, kh, vh, pos, mode)
    wo = R.unpack_pm1(w["wo.w"], hp * hd)[:arch.heads * hd]
    h = h + R._mm(o, wo, mode) / math.sqrt(hp * hd)
    x = R._rms(h, w["norm2.scale"])
    gu = R._proj(x, w["wgu.w"], d, mode)
    g, u = gu[:, :arch.ff], gu[:, arch.ff:]
    a = jnp.where(g - w["tau"] >= 0, 1.0, -1.0)
    return h + R._proj(a * u, w["wd.w"], arch.ff, mode)


def decode_work(cfg: dict) -> dict:
    """Published widths (40 query heads for qwen2.5-14b, not the 48 the
    program pads to)."""
    d = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    hd = cfg["program"].get("head_dim", d // cfg["num_attention_heads"])
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    ff = cfg["intermediate_size"]
    return {"projections": [(d, q, layers), (d, kv, layers),
                            (d, kv, layers), (q, d, layers),
                            (d, 2 * ff, layers), (ff, d, layers)],
            "attention": {"layers": layers,
                          "heads": cfg["num_attention_heads"],
                          "kv_heads": cfg["num_key_value_heads"],
                          "head_dim": hd}}
