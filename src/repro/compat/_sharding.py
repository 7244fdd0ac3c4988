"""``shard_map`` and mesh-axis helpers."""
from __future__ import annotations

from typing import Callable, Optional

import jax
from jax import lax


def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              check_vma: Optional[bool] = None) -> Callable:
    """``jax.shard_map``; ``check_vma`` is forwarded only when given."""
    kwargs = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def axis_size(axis_name) -> int:
    """Size of a mapped mesh axis, callable inside ``shard_map``.
    ``None`` means "not parallelized" and returns 1."""
    if axis_name is None:
        return 1
    return lax.axis_size(axis_name)
