"""repro.compat: the repo's mesh constructor, single-device."""

import jax

from repro.compat import make_mesh


def test_make_mesh_single_device():
    m = make_mesh((1,), ("data",))
    assert m.shape == {"data": 1}
    # every axis is Auto: the sharded paths were written for it
    assert m.axis_types == (jax.sharding.AxisType.Auto,)
    m2 = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    assert m2.shape == {"data": 1, "model": 1}
    assert m2.axis_types == (jax.sharding.AxisType.Auto,) * 2


def test_host_mesh_helper():
    from repro.launch.mesh import make_host_mesh

    m = make_host_mesh()
    assert m.shape == {"data": 1, "model": len(jax.devices())}
