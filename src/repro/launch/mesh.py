"""Production meshes (TPU v5e): 16x16 per pod, pods stacked on a DCN axis.

A FUNCTION, not a module constant — importing this module must never touch
jax device state (smoke tests see 1 device; only dryrun forces 512).

Mesh construction goes through repro.compat.make_mesh, which gives
every axis the Auto type the sharded code was written for.
"""

from __future__ import annotations

import jax

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=None, axes=("data", "model")):
    """Mesh over whatever devices exist (tests / single host)."""
    n = len(jax.devices())
    if shape is None:
        shape = (1, n)
    return make_mesh(shape, axes)
