"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (device count locks on first jax init, and smoke
tests must see 1 CPU device while the dry-run sees 512 placeholders).

Every axis is ``AxisType.Auto``: the model code places its arrays with
``NamedSharding`` and leaves propagation to the compiler, which an
``Explicit`` axis (``jax.make_mesh``'s default) refuses for ops such as
the embedding gather.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], *, devices=None):
    """Mesh of ``shape`` over ``axes`` with auto-sharded axes; ``devices``
    defaults to all of ``jax.devices()`` (tests use small shapes on
    forced host devices)."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: 'data' carries DP (+ sequence parallelism for the batch=1 long-
    context cells), 'model' carries TP/EP, 'pod' is the outer DP axis whose
    collectives cross the inter-pod DCN.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
