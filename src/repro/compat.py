"""``shard_map`` with ``axis_names`` as any iterable of *manual* mesh axes.

Forwards to ``jax.shard_map`` (all axes manual if ``axis_names`` is None).
"""
from __future__ import annotations

from typing import Any

import jax

__all__ = ["shard_map"]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True,
              axis_names: Any = None):
    """``jax.shard_map``; ``axis_names`` is the set of manual mesh axes."""
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=check_vma)
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kwargs)
