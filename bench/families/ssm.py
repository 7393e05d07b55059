"""Attention-free Mamba-1 decoder (falcon-mamba), as the program's B⊕LD
model defines it: pre-norm Mamba mixing with Boolean in, x, dt and out
projections and a real selective scan, without Falcon-Mamba's RMS norms on
B, C and dt. One stacked index is one layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import reference as R


def model_config(cfg: dict) -> dict:
    return dict(n_heads=1, n_kv_heads=1, d_ff=0,
                ssm_state=cfg["state_size"],
                d_inner=cfg["intermediate_size"],
                dt_rank=cfg["time_step_rank"],
                conv_width=cfg["conv_kernel"])


def sizes(cfg: dict) -> dict:
    return dict(di=cfg["intermediate_size"], n=cfg["state_size"],
                r=cfg["time_step_rank"], w=cfg["conv_kernel"])


def layer_shapes(arch) -> dict:
    d, blk = arch.d, "['blocks']['b0']"
    f32 = jnp.float32
    m = blk + "['mamba']"
    di, n, r = arch.di, arch.n, arch.r
    return {
        blk + "['norm1']['scale']": ((d,), f32, 0),
        m + "['in_x']['w']": ((d // 32, di), jnp.uint32, d),
        m + "['in_z']['w']": ((d // 32, di), jnp.uint32, d),
        m + "['conv_w']": ((arch.w, di), f32, 0),
        m + "['conv_b']": ((di,), f32, 0),
        m + "['x_proj']['w']": ((di // 32, r + 2 * n), jnp.uint32, di),
        m + "['dt_proj']['w']": ((-(-r // 32), di), jnp.uint32, r),
        m + "['dt_bias']": ((di,), f32, 0),
        m + "['A_log']": ((di, n), f32, 0),
        m + "['D']": ((di,), f32, 0),
        m + "['out_proj']['w']": ((di // 32, d), jnp.uint32, di),
    }


def layer(arch, w, h, pos, mode):
    del pos
    d, di, n, r = arch.d, arch.di, arch.n, arch.r
    S = h.shape[0]
    x = R._rms(h, w["norm1.scale"])
    xin = R._proj(x, w["in_x.w"], d, mode)
    z = R._proj(x, w["in_z.w"], d, mode)
    cw = w["conv_w"]
    xp = jnp.concatenate([jnp.zeros((arch.w - 1, di), jnp.float32), xin])
    xconv = sum(xp[i:i + S] * cw[i] for i in range(arch.w)) + w["conv_b"]
    xc = jax.nn.silu(xconv)
    dbc = R._proj(xc, w["x_proj.w"], di, mode)
    dt_r, bm, cm = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
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


def decode_work(cfg: dict) -> dict:
    """No attention."""
    d = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    di, n, r = (cfg["intermediate_size"], cfg["state_size"],
                cfg["time_step_rank"])
    return {"projections": [(d, di, layers), (d, di, layers),
                            (di, r + 2 * n, layers), (r, di, layers),
                            (di, d, layers)],
            "attention": None}
