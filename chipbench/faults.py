"""Faults planted under the timed path, to show that the check catches them.

The benchmark's own runs never plant one.  ``control.py`` plants them on
the chip at a cell's size, and the CPU tests at a small size.  Each is a
context manager that patches the program for its duration, at its public
seams only (``HistogramEngine.run``, the query classes' ``apply``,
``DistributedAnalyticsService.replica_for``), so that a change inside the
program leaves the faults planted where they were:

* ``answer_altered``: every region, likelihood and multi-scale answer is
  off by one where the query produces it (its first count or value, or
  the best score);
* ``state_unchanged``: an incremental update hands back the cached
  predecessor's H unchanged, and the queries read that;
* ``chain_ignored``: the router places each frame by its index alone, so
  a camera's chain is split over replicas;
* ``shard_lost``: the exchange between chips left out: an H whose bins
  are sharded over chips answers with the last chip's share of the bins
  zeroed, as if that chip's part never reached the queries.
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(obj, name: str, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _bump(answer):
    """``answer`` one higher in its first element (an array), or in its
    best score (a multi-scale ``(rect, score, maps)``)."""
    import jax.numpy as jnp

    if isinstance(answer, tuple):
        rect, score, maps = answer
        return rect, score + 1, maps
    x = jnp.asarray(answer)
    return jnp.ravel(x).at[0].add(1).reshape(x.shape).astype(x.dtype)


@contextlib.contextmanager
def answer_altered():
    from repro.core import engine

    with contextlib.ExitStack() as stack:
        for cls in (engine.RegionQuery, engine.LikelihoodQuery,
                    engine.MultiScaleQuery):
            def apply(q, source, _real=cls.apply):
                return _bump(_real(q, source))

            stack.enter_context(_patched(cls, "apply", apply))
        yield


@contextlib.contextmanager
def state_unchanged():
    from repro.core import engine

    real = engine.HistogramEngine.run

    def run(self, frames, queries=(), *, prev=None):
        queries = list(queries)
        out = real(self, frames, queries, prev=prev)
        if not out.plan.incremental:
            return out
        old = prev[1]
        if isinstance(old, engine.EngineResult):
            old = old.source
        return engine.EngineResult(plan=out.plan, source=old,
                                   results=[q.apply(old) for q in queries])

    with _patched(engine.HistogramEngine, "run", run):
        yield


@contextlib.contextmanager
def chain_ignored():
    from repro.serve import distributed

    def by_index(self, frame_ref):
        return frame_ref[1] % len(self.replicas)

    with _patched(distributed.DistributedAnalyticsService, "replica_for",
                  by_index):
        yield


@contextlib.contextmanager
def shard_lost():
    from repro.core import engine
    from repro.core.hsource import ShardedH

    real = engine.HistogramEngine.run

    def run(self, frames, queries=(), *, prev=None):
        queries = list(queries)
        out = real(self, frames, queries, prev=prev)
        src = out.source
        if not isinstance(src, ShardedH) or src.kind != "bin":
            return out
        kept = src.num_bins - src.num_bins // src.mesh.shape[src.bin_axis]
        lost = ShardedH(src.H.at[..., kept:, :, :].set(0), src.mesh,
                        kind="bin", bin_axis=src.bin_axis,
                        row_axis=src.row_axis)
        return engine.EngineResult(plan=out.plan, source=lost,
                                   results=[q.apply(lost) for q in queries])

    with _patched(engine.HistogramEngine, "run", run):
        yield


FAULTS = {
    "answer_altered": answer_altered,
    "state_unchanged": state_unchanged,
    "chain_ignored": chain_ignored,
    "shard_lost": shard_lost,
}
