"""The system under test, as the benchmark drives it: the only module of
the benchmark that imports the program (``repro``).

It turns a configuration file into the program's ``ModelConfig``, makes
the served parameter tree from the seed in one jitted call (the tree's
layout comes from the program's own packing, its values from
``weights.py``), and opens ``ServeEngine(packed=True).session()``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import weights as W

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file. Published keys
    map one to one; ``program`` holds what the published file does not
    say in the program's terms, and ``program.overrides`` any further
    ModelConfig field."""
    from repro.models import ModelConfig

    prog = cfg["program"]
    fam = prog["family"]
    kw = dict(name=cfg["name"], family=fam,
              n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
              vocab_size=cfg["vocab_size"])
    if fam == "dense":
        kw.update(n_heads=cfg["num_attention_heads"],
                  n_kv_heads=cfg["num_key_value_heads"],
                  d_ff=cfg["intermediate_size"],
                  rope_theta=float(cfg["rope_theta"]),
                  qkv_bias=bool(prog["qkv_bias"]),
                  head_dim=prog.get("head_dim", 0))
    elif fam == "ssm":
        kw.update(n_heads=1, n_kv_heads=1, d_ff=0,
                  ssm_state=cfg["state_size"],
                  d_inner=cfg["intermediate_size"],
                  dt_rank=cfg["time_step_rank"],
                  conv_width=cfg["conv_kernel"])
    else:
        raise ValueError(f"no family {fam!r}")
    over = dict(prog.get("overrides", {}))
    if "dtype" in over:
        over["dtype"] = DTYPES[over["dtype"]]
    mc = ModelConfig(**kw, **over)
    if fam == "dense" and mc.heads_padded() != prog["heads_padded"]:
        raise ValueError(f"program pads {mc.n_heads} heads to "
                         f"{mc.heads_padded()}, the file says "
                         f"{prog['heads_padded']}")
    if mc.vocab_padded != prog.get("vocab_padded", mc.vocab_size):
        raise ValueError(f"program pads the vocabulary to "
                         f"{mc.vocab_padded}; the file must say so")
    return mc


def make_params(mc, seed: int):
    """The served (packed) parameter tree, made on the device in one
    jitted call from ``seed``."""
    from repro.core import PackedBool
    from repro.models import lm_init
    from repro.serve.engine import pack_weights

    def is_packed(x):
        return isinstance(x, PackedBool)

    abstract = jax.eval_shape(
        lambda: pack_weights(lm_init(jax.random.PRNGKey(0), mc)[0]))
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        abstract, is_leaf=is_packed)
    leaves = [(jax.tree_util.keystr(p), x) for p, x in flat]
    d = mc.d_model

    def make(key):
        out = []
        for path, x in leaves:
            if is_packed(x):
                n, kw, cols = x.bits.shape
                out.append(PackedBool(W.make_stacked(
                    key, path, n, (kw, cols), jnp.uint32, d, k=x.k), x.k))
            elif path.startswith("['blocks']"):
                out.append(W.make_stacked(key, path, x.shape[0],
                                          x.shape[1:], x.dtype, d))
            else:
                out.append(W.top_leaf(key, path, x.shape, x.dtype, d))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(W.seed_key(seed))


def open_session(mc, params, serve: dict):
    """``ServeEngine(packed=True)`` over the made tree, and its session."""
    from repro.serve import ServeEngine

    engine = ServeEngine(mc, params, max_len=serve["max_len"], packed=True,
                         prefix_cache=serve["prefix_cache"])
    session = engine.session(lanes=serve["lanes"],
                             page_size=serve["page_size"],
                             segment=serve["segment"],
                             buckets=serve["buckets"])
    return engine, session


def sampling(max_tokens: int):
    from repro.serve import SamplingParams

    return SamplingParams(max_tokens=max_tokens)


def terminal(handle) -> bool:
    from repro.serve.scheduler import TERMINAL

    return handle.status in TERMINAL


def done(handle) -> bool:
    from repro.serve import RequestStatus

    return handle.status is RequestStatus.DONE


def queued(handle) -> bool:
    from repro.serve import RequestStatus

    return handle.status is RequestStatus.QUEUED


def counters(session) -> dict:
    """The session's host counters (prefix cache, when it has one)."""
    st = session.stats()
    return dict(st["prefix"] or {})
