"""Production mesh construction.

A function (not a module constant) so importing never touches jax device
state.  Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod: (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is pure
data parallelism over the slow inter-pod links (DCN/ICI-lite), which the
sharding rules use only for the batch axis and the hierarchical gradient
reduction (DESIGN.md §5).

Every mesh is built by :func:`make_mesh`, which gives all axes
``AxisType.Auto``: the sharding model (``repro.dist.sharding``) annotates
logical axes and lets the partitioner propagate the rest.  ``jax.make_mesh``
alone defaults to ``Explicit`` axes, under which sharding-in-types rejects
the model's gathers (the embedding lookup) instead of partitioning them.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax

SINGLE_POD = (16, 16)
SINGLE_POD_AXES = ("data", "model")
MULTI_POD = (2, 16, 16)
MULTI_POD_AXES = ("pod", "data", "model")


def make_mesh(
    shape: Sequence[int], axes: Sequence[str], *, devices: Optional[Sequence] = None
) -> jax.sharding.Mesh:
    """Mesh of ``shape`` over ``devices`` (default: all) with Auto axes."""
    return jax.make_mesh(
        tuple(shape),
        tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = MULTI_POD_AXES if multi_pod else SINGLE_POD_AXES
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int | None = None) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    mp = model_parallel or (2 if n % 2 == 0 and n > 1 else 1)
    return make_mesh((n // mp, mp), ("data", "model"))
