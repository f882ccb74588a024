"""Guarded sharding-constraint helper usable inside model code.

`constrain(x, template)` applies jax.lax.with_sharding_constraint with the
given axis-name template (tuple entries may be None / "data" / "model" /
("pod","data")), but only when a mesh with those axes is active, each axis
is Auto, and the dim is divisible — so model code stays runnable on bare
CPU and inside partial-manual shard_map without special-casing.
"""
from __future__ import annotations

import jax


def constrain(x: jax.Array, template) -> jax.Array:
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return x
    from jax.sharding import AxisType, PartitionSpec as P
    auto = {n for n, t in zip(am.axis_names, am.axis_types)
            if t == AxisType.Auto}
    entries = []
    for dim, ax in zip(x.shape, tuple(template) + (None,) * (x.ndim - len(template))):
        if ax is None:
            entries.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        if not all(a in auto for a in axes):
            entries.append(None)
            continue
        size = 1
        for a in axes:
            size *= am.shape[a]
        entries.append(ax if (dim % size == 0 and dim >= size) else None)
    if all(e is None for e in entries):
        return x
    return jax.lax.with_sharding_constraint(x, P(*entries))
