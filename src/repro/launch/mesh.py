"""Device meshes.  A FUNCTION (not module-level constant) so importing
never touches jax device state.

Every mesh is built with ``AxisType.Auto`` axes: sharding is propagated by
GSPMD from the ``NamedSharding``/``constrain`` annotations, which is what
the model code is written against (``jax.make_mesh`` defaults to Explicit
axes, under which e.g. the embedding gather refuses sharded operands)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axis types (see module doc)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_test_mesh(n_data: int = 2, n_model: int = 2, pods: int = 0,
                   devices=None):
    """Small mesh: fake-CPU-device subprocess tests, and tensor-parallel
    serving over the first ``n_data·n_model`` devices (``devices`` picks
    them explicitly)."""
    if pods:
        return make_mesh((pods, n_data, n_model), ("pod", "data", "model"),
                         devices)
    return make_mesh((n_data, n_model), ("data", "model"), devices)
