"""SPMD entry points the codebase uses (single home for jax API drift).

``jax.shard_map`` (with ``check_vma``) and the ambient-mesh
``jax.set_mesh``, as in the installed jax (0.9).  Call ``compat.shard_map``
/ ``compat.set_mesh`` instead of touching ``jax.*`` directly, so a future
API change lands in this file only.
"""

from __future__ import annotations

import jax

__all__ = ["shard_map", "set_mesh"]


def shard_map(f, *, mesh=None, in_specs, out_specs, check=False):
    kw = {} if mesh is None else {"mesh": mesh}
    return jax.shard_map(f, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check, **kw)


def set_mesh(mesh):
    return jax.set_mesh(mesh)
