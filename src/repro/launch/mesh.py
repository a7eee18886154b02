"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — jax locks the device count on first use,
and smoke tests must see 1 device while the dry-run sees 512.

Every axis is ``AxisType.Auto``: the model code places arrays through
``NamedSharding``/``with_sharding_constraint`` and lets the partitioner
propagate the rest (``jax.make_mesh`` defaults to ``Explicit`` axes).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """Mesh of ``shape`` over the first devices, all axes Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
