"""shard_map with this repo's defaults.

Callers name the manual axes explicitly (all mesh axes when omitted) and
replication checking is off unless asked for.
"""

from __future__ import annotations

import jax

__all__ = ["shard_map"]


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None, check_vma=False):
    manual = frozenset(axis_names) if axis_names is not None else frozenset(mesh.axis_names)
    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names=manual,
        check_vma=check_vma,
    )
