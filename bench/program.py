"""The system under test, as the benchmark drives it: the only module of
the benchmark that imports the program (``repro``).

It turns a configuration file into the program's ``ModelConfig`` (the
family's fields from ``families/<family>.py``), makes the served parameter
tree from the seed in one jitted call (the tree's layout comes from the
program's own packing, its values from ``weights.py``), and opens
``ServeEngine(packed=True).session()``, on a serve mesh of the cell's
chips where it has more than one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import family
import weights as W

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_config(cfg: dict, fam=None):
    """The program's ModelConfig for a configuration file. Published keys
    map one to one; ``program`` holds what the published file does not
    say in the program's terms, and ``program.overrides`` any further
    ModelConfig field."""
    from repro.models import ModelConfig

    fam = fam if fam is not None else family.of(cfg)
    prog = cfg["program"]
    kw = dict(name=cfg["name"], family=prog["family"],
              n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
              vocab_size=cfg["vocab_size"])
    kw.update(fam.model_config(cfg))
    over = dict(prog.get("overrides", {}))
    if "dtype" in over:
        over["dtype"] = DTYPES[over["dtype"]]
    mc = ModelConfig(**kw, **over)
    if hasattr(fam, "validate"):
        fam.validate(mc, cfg)
    if mc.vocab_padded != prog.get("vocab_padded", mc.vocab_size):
        raise ValueError(f"program pads the vocabulary to "
                         f"{mc.vocab_padded}; the file must say so")
    return mc


def make_params(mc, seed: int, fam=None):
    """The served (packed) parameter tree, made on the device in one
    jitted call from ``seed``. ``fam`` gives the family's weight recipes
    (``weights.RECIPES`` alone if None)."""
    from repro.core import PackedBool
    from repro.models import lm_init
    from repro.serve.engine import pack_weights

    def is_packed(x):
        return isinstance(x, PackedBool)

    recipes = family.recipes(fam) if fam is not None else None
    abstract = jax.eval_shape(
        lambda: pack_weights(lm_init(jax.random.PRNGKey(0), mc)[0]))
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        abstract, is_leaf=is_packed)
    leaves = [(jax.tree_util.keystr(p), x) for p, x in flat]
    d = mc.d_model

    def make(key):
        out = []
        for path, x in leaves:
            if is_packed(x):          # (stack, ..., Kw, N) words
                n, *rest = x.bits.shape
                out.append(PackedBool(W.make_stacked(
                    key, path, n, tuple(rest), jnp.uint32, d, k=x.k,
                    recipes=recipes), x.k))
            elif path.startswith("['blocks']"):
                out.append(W.make_stacked(key, path, x.shape[0],
                                          x.shape[1:], x.dtype, d,
                                          recipes=recipes))
            else:
                out.append(W.top_leaf(key, path, x.shape, x.dtype, d,
                                      recipes))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(W.seed_key(seed))


def open_session(mc, params, serve: dict, chips: int = 1):
    """``ServeEngine(packed=True)`` over the made tree, and its session;
    with ``chips`` > 1 the engine serves on ``make_serve_mesh(chips)``."""
    from repro.serve import ServeEngine

    mesh = None
    if chips > 1:
        from repro.launch.mesh import make_serve_mesh

        mesh = make_serve_mesh(chips)
    engine = ServeEngine(mc, params, max_len=serve["max_len"], packed=True,
                         prefix_cache=serve["prefix_cache"], mesh=mesh)
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
