"""Process-wide mesh context.

The launcher (dryrun/train/serve) installs the active mesh here so model
internals that need manual collectives (shard_map flash-decode, 1-bit EF
all-reduce) can reference it without threading it through every signature.
"""
from __future__ import annotations

from typing import Optional

_MESH = None


def set_mesh(mesh) -> None:
    global _MESH
    _MESH = mesh


def get_mesh():
    if _MESH is None:
        raise RuntimeError("no mesh installed — launcher must call set_mesh()")
    return _MESH
