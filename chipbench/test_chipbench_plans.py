"""Each cell's traffic takes the planner path its description states,
at the real frame sizes.  ``HistogramEngine.run`` validates its plan
before it dispatches anything, so stopping it there plans a request
without running a kernel."""

import collections

import numpy as np
import pytest

from chipbench import scene
from chipbench.conftest import cell


class _Planned(Exception):
    def __init__(self, plan):
        super().__init__(plan.representation)
        self.plan = plan


def planned(engine, frame, queries, prev=None):
    """The plan ``engine.run`` makes for one request; it stops at the
    validation that precedes every dispatch."""
    def stop(p, *_args, **_kwargs):
        raise _Planned(p)

    engine.validate = stop
    try:
        engine.run(frame, queries, prev=prev)
    except _Planned as e:
        return e.plan
    raise AssertionError("run dispatched without validating its plan")


def plan_kinds(name: str, frames: int, seed: int = 0) -> collections.Counter:
    """(later frame?, plan) counts over the first ``frames`` frames of up
    to 16 clients, each planned as the service plans it: a chained frame
    offers its predecessor with the predecessor's cached H."""
    import jax.numpy as jnp

    from repro.core.engine import HistogramEngine
    from repro.core.hsource import DenseH

    _, cfg, mix = cell(name)
    engine = HistogramEngine(cfg["bins"], value_range=cfg["value_range"],
                             backend="jnp", **cfg["engine"])
    # the predecessor's H as the service caches it after a dense or an
    # incremental run; only its geometry is read before the plan is made
    cached = DenseH(jnp.zeros((cfg["bins"], cfg["height"], cfg["width"]),
                              jnp.float32))
    kinds = collections.Counter()
    for s in scene.streams(cfg, mix, seed)[:16]:
        prev = None
        for t in range(frames):
            frame = s.frame(t)
            p = planned(engine, frame, s.queries(t, frame), prev)
            kinds[(t > 0, p.representation
                   + ("+incremental" if p.incremental else ""))] += 1
            # a fused frame caches only its corner rows, which cannot
            # seed an update
            prev = ((frame, cached) if mix["chain"]
                    and p.representation != "fused" else None)
    return kinds


@pytest.mark.parametrize("name", ["vga32.live", "vga32.fleet4"])
def test_chained_cameras_go_incremental_after_each_first_frame(name):
    kinds = plan_kinds(name, frames=6)
    first = sum(n for (later, _), n in kinds.items() if not later)
    later = {k: n for (lt, k), n in kinds.items() if lt}
    assert kinds[(False, "dense")] >= first - 1, kinds
    assert later.get("dense+incremental", 0) >= 0.75 * sum(later.values()), kinds


def test_hd_archive_is_all_dense():
    kinds = plan_kinds("hd32.archive", frames=3)
    assert set(k for _, k in kinds) == {"dense"}, kinds


def test_vga_archive_is_all_fused():
    kinds = plan_kinds("vga32.archive", frames=3)
    assert set(k for _, k in kinds) == {"fused"}, kinds


@pytest.mark.parametrize("name", ["vga32.live", "vga32.fleet4"])
def test_chained_layouts_share_one_set_of_dirty_bands(name):
    """Every seed gives the same set of dirty-band layouts, in another
    order, so every seed compiles the same shapes."""
    _, cfg, mix = cell(name)

    def layouts(seed):
        return sorted(tuple(o.top for o in s.objs) + tuple(
            o.h for o in s.objs) for s in scene.streams(cfg, mix, seed))

    assert layouts(0) == layouts(2**31 + 77)
    assert np.unique([len(s.objs) for s in
                      scene.streams(cfg, mix, 0)]).size > 1
