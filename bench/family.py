"""A model family's module, ``families/<family>.py``, found by the name a
configuration gives in ``program.family`` and loaded by file path, as a
per-layer metric's reader is. A new family is one new file; nothing here
changes.

A family module gives:

* ``model_config(cfg) -> dict``: the program's ``ModelConfig`` fields the
  family sets, as a plain dict (``program.py`` adds the name, depth, width
  and vocabulary, and ``family`` unless the module sets it). It imports
  nothing of the program.
* ``validate(mc, cfg)`` (optional): refuses a ``ModelConfig`` that departs
  from what the file says.
* ``sizes(cfg) -> dict``: the sizes the reference reads, set on its
  ``Arch``; ``stack`` (optional) is the number of stacked indices, one per
  layer unless the program stacks a group of blocks per index.
* ``layer_shapes(arch) -> {path: (shape, dtype, packed fan-in or 0)}``
  and ``layer(arch, w, h, pos, mode) -> h``: the leaves and the float32
  equations of one stacked index (a group's blocks ``b0..b{g-1}``).
* ``decode_work(cfg) -> dict``: what decoding one token asks at the
  published widths, for the per-layer readers: ``projections``, a list of
  ``(fan_in, fan_out, calls per token)``, and ``attention``, a dict of
  ``layers``, ``heads``, ``kv_heads`` and ``head_dim``, or None.
* ``RECIPES`` (optional): weight recipes for the real leaves the family
  adds, by the last name of the path, merged over ``weights.RECIPES``.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

FAMILIES = Path(__file__).resolve().parent / "families"
_LOADED = {}


def load(name: str, directory: Path = FAMILIES):
    """The module ``<directory>/<name>.py``, loaded once per path (its
    functions key compiled programs, so one path gives one module)."""
    path = (Path(directory) / f"{name}.py").resolve()
    if not path.is_file():
        raise ValueError(f"no family {name!r}: {path} does not exist")
    if path not in _LOADED:
        spec = importlib.util.spec_from_file_location(
            f"bench_family_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


def of(cfg: dict, directory: Path = FAMILIES):
    """The family module of a configuration."""
    return load(cfg["program"]["family"], directory)


def recipes(fam) -> dict:
    """``weights.RECIPES`` with the family's own recipes over it."""
    import weights as W

    return {**W.RECIPES, **getattr(fam, "RECIPES", {})}
