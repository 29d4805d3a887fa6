"""The repo's one mesh constructor, for jax 0.9.

``jax.make_mesh`` creates Explicit axes by default on this jax.  The
sharded paths of the paper stack (``core/distributed.py``, ``ShardedH``
in ``core/hsource.py``, the serving layer) were written for Auto axes,
where the partitioner places every intermediate; under Explicit axes a
plain gather on a row-sharded band must name its output sharding.  So
every mesh in the repo, tests and benches included, is built here with
Auto axis types — never by ``jax.make_mesh`` directly.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """A mesh with Auto axes over ``devices`` (default: all of them).

    Pass ``devices=jax.devices()[:k]`` to serve on the first ``k`` chips
    of a host from one process."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(axis_names), devices=devices,
    )
