"""One asynchronous frame runtime behind every streaming loop in the repo.

Paper §4.4 overlaps (disk -> host), (host -> device), kernel execution
and (device -> host) across a frame sequence with two CUDA streams.  PRs
1-4 grew five independent host loops that each re-implemented a slice of
that overlap — ``pipeline.DoubleBufferedExecutor`` (dispatch-ahead +
microbatch), ``IntegralHistogram.map_frames`` / ``HistogramEngine
.map_frames`` (the same loop with planner-sized batches),
``bands.iter_banded_ih`` (a carry-threaded band loop with its own
prefetch), and ``FragmentTracker.track`` (a chunked carry loop over
tracker state).  This module is the one scheduler they are now thin
adapters over:

    FrameSource -> [microbatch] -> [H2D stage] -> [step] -> Sink
                        ^                ^           ^
                   fixed | adaptive   stage_ahead   depth-k in-flight
                                                    window + carry

  * **Bounded in-flight window** — the double buffer generalized to
    depth k: up to ``depth`` dispatches are enqueued before the oldest
    is retired (``depth=1`` degenerates to synchronous execution, the
    "no dual-buffering" baseline of Fig. 13).
  * **Microbatching** — ``microbatch`` frames are stacked per dispatch
    (the rank-polymorphic kernels accept (n, ...) stacks).  Sizes come
    from the planner (``ExecutionPlan.microbatch``); ``adaptive=True``
    retunes the size online from measured per-dispatch completion
    latency — the adaptive CUDA-stream batching of Koppaka et al.
    (arXiv:1011.0235) restated for XLA dispatch.
  * **Carry threading** — ``step(chunk, carry) -> (out, carry)``: the
    banded (b, w) bottom-row carry and the tracker's scan state are the
    same sequential dependency; the carry rides between dispatches as an
    async jax value, so dispatch-ahead still overlaps staging with
    compute.
  * **Device prefetch** — inputs are staged with ``jax.device_put``
    (async H2D); ``stage_ahead >= 1`` keeps that many chunks staged
    beyond the dispatch window (``bands.iter_banded_ih(prefetch=k)``).

Results are retired in order; ``block=True`` (default) blocks on the
oldest in-flight result at the window boundary — the D2H sync point that
gives backpressure and the latency measurements the adaptive controller
feeds on.  ``block=False`` hands back async arrays (band streaming,
where the consumer's ``np.asarray`` is the sync point).
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Iterable, Iterator

import jax
import numpy as np


# ---------------------------------------------------------------------------
# chunking (the one copy of what executor/tracker/map_frames each had)
# ---------------------------------------------------------------------------
def stack_chunks(
    frames: Iterable[np.ndarray], batch_size: int
) -> Iterator[np.ndarray]:
    """Group a frame stream into stacked (<= batch_size, ...) host arrays
    (ragged final chunk included)."""
    buf: list = []
    for frame in frames:
        # analysis: allow-host-sync(host-side frame staging before device dispatch, not a device readback)
        buf.append(np.asarray(frame))
        if len(buf) == batch_size:
            yield np.stack(buf)
            buf = []
    if buf:
        yield np.stack(buf)


def iter_chunks(frames, batch_size: int) -> Iterator:
    """Chunk a clip or stream: an array (n, ...) is sliced (device arrays
    stay on device, no per-frame host round-trip); any other iterable is
    stacked host-side via ``stack_chunks``."""
    if hasattr(frames, "shape") and hasattr(frames, "ndim"):
        for s in range(0, frames.shape[0], batch_size):
            yield frames[s : s + batch_size]
        return
    yield from stack_chunks(frames, batch_size)


def stage_stream(items: Iterable, size: int = 2, device=None) -> Iterator:
    """Stage host arrays onto the device ahead of consumption (async H2D
    ~ cudaMemcpyAsync).  Exactly ``size`` items are staged before the
    first yield and at most ``size`` are ever resident beyond the one in
    the consumer's hands.

    ``device`` is any ``jax.device_put`` placement: a single ``Device``
    (default: the current default device, which ``jax.default_device``
    sets per thread) or a ``jax.sharding.Sharding`` — a
    ``NamedSharding`` lays each staged item out across its mesh, which is
    how the sharded band streams commit their slices to the layout their
    shard_map expects instead of bouncing through one device."""
    queue: collections.deque = collections.deque()
    for item in items:
        queue.append(jax.device_put(item, device))
        # yield once exactly `size` items are staged — `> size` would
        # hold size + 1 on device before the first yield
        if len(queue) >= size:
            yield queue.popleft()
    while queue:
        yield queue.popleft()


# ---------------------------------------------------------------------------
# adaptive microbatch controller
# ---------------------------------------------------------------------------
class AdaptiveMicrobatch:
    """Online microbatch tuner: hill-climb the size against measured
    throughput (frames per second of dispatch completion).

    Koppaka et al. pick the CUDA batch size online against measured
    transfer/compute rates; here the signal is the per-dispatch
    completion latency the runtime observes at its D2H sync point.  The
    controller holds a size for ``settle`` completed dispatches, records
    the best observed throughput at that size, then moves one
    multiplicative step (x2 / /2) in the current direction; a move that
    measures worse than the best size seen so far reverses direction
    once, then locks in the best size.  Deterministic given the observed
    latencies — unit-tested with scripted timings."""

    def __init__(self, initial: int, max_size: int = 64, settle: int = 2):
        if initial < 1 or max_size < 1:
            raise ValueError("batch sizes must be >= 1")
        self.size = min(initial, max_size)
        self.max_size = max_size
        self.settle = settle
        self._counts: dict[int, int] = {}
        self._throughput: dict[int, float] = {}
        self._direction = 2.0            # multiplicative step, up first
        self._reversed = False
        self.locked = False

    def _best(self) -> tuple[int, float]:
        return max(self._throughput.items(), key=lambda kv: kv[1])

    def observe(self, count: int, seconds: float,
                size: int | None = None) -> None:
        """Feed one completed dispatch (count frames in ``seconds``).

        ``size`` is the batch size the dispatch was BUILT with — with a
        depth-k in-flight window, dispatches retire after the controller
        may have already moved, so the sample must be keyed by the size
        that produced it, not the current one.  Defaults to the current
        size for direct (synchronous) use."""
        if size is None:
            size = self.size
        if self.locked or seconds <= 0.0:
            return
        thr = count / seconds
        self._throughput[size] = max(
            self._throughput.get(size, 0.0), thr
        )
        self._counts[size] = self._counts.get(size, 0) + 1
        # Decisions only fire on samples from the CURRENT size once it
        # has settled — lagged samples from earlier sizes (still in the
        # in-flight window when the size moved) are recorded above but
        # never steer.
        if size != self.size or self._counts[size] < self.settle:
            return
        best_size, best_thr = self._best()
        if self._throughput[self.size] < best_thr:
            # the last move made things worse: go back to the best size
            # and either try the other direction or stop searching
            if self._reversed:
                self.size = best_size
                self.locked = True
                return
            self._reversed = True
            self._direction = 1.0 / self._direction
            self.size = best_size
        nxt = int(self.size * self._direction)
        nxt = max(1, min(nxt, self.max_size))
        if nxt == self.size or nxt in self._throughput:
            self.size = self._best()[0]
            self.locked = True
        else:
            self.size = nxt


# ---------------------------------------------------------------------------
# runtime
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class DispatchResult:
    """One retired dispatch: ``out`` covers ``count`` source items."""

    index: int
    count: int
    out: Any
    carry: Any
    meta: Any = None
    latency_s: float | None = None      # dispatch -> retire (block=True)


@dataclasses.dataclass
class RuntimeStats:
    """What one ``run()`` did — filled as dispatches retire."""

    items: int = 0
    dispatches: int = 0
    batch_sizes: list = dataclasses.field(default_factory=list)
    latencies_s: list = dataclasses.field(default_factory=list)
    wall_s: float = 0.0

    @property
    def items_per_s(self) -> float:
        return self.items / self.wall_s if self.wall_s > 0 else 0.0


class FrameRuntime:
    """The one async streaming scheduler (module docstring has the map).

    Args:
      step: ``step(chunk, carry) -> (out, carry)``.  Stateless computes
        wrap as ``lambda chunk, c: (fn(chunk), c)`` (``stateless()``).
      depth: dispatches kept in flight (1 = synchronous).
      microbatch: frames stacked per dispatch; with ``adaptive=True``
        this is the starting size and the controller retunes it online.
      adaptive: retune the microbatch from measured completion latency.
      carry_in: initial carry (``None`` for stateless pipelines); the
        final carry lands in ``self.last_carry`` when the run drains.
      device: staging placement — a ``Device`` (default: first device)
        or a ``jax.sharding.Sharding``.  A ``NamedSharding`` commits
        each chunk to the mesh layout a shard_map'd ``step`` consumes,
        so sharded plans stage exactly like single-device ones.
      stage_inputs: ``jax.device_put`` each chunk before ``step``.
      stage_ahead: chunks staged beyond the dispatch window (device
        prefetch; 0 = stage just-in-time, which is still async H2D).
      block: block on each result as it retires (the D2H sync point).
        Required by ``adaptive`` (that is where latency is measured).
      clock: injectable time source (tests script it).
    """

    def __init__(
        self,
        step: Callable,
        *,
        depth: int = 2,
        microbatch: int = 1,
        adaptive: bool = False,
        max_microbatch: int = 64,
        carry_in=None,
        device=None,
        stage_inputs: bool = True,
        stage_ahead: int = 0,
        block: bool = True,
        clock: Callable[[], float] = time.perf_counter,
    ):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        if microbatch < 1:
            raise ValueError("microbatch must be >= 1")
        if stage_ahead < 0:
            raise ValueError("stage_ahead must be >= 0")
        if adaptive and not block:
            raise ValueError(
                "adaptive microbatching needs block=True (latency is "
                "measured at the retire-time sync point)"
            )
        self.step = step
        self.depth = depth
        self.microbatch = microbatch
        self.adaptive = adaptive
        self.controller = (
            AdaptiveMicrobatch(microbatch, max_size=max_microbatch)
            if adaptive else None
        )
        self.carry_in = carry_in
        # None stages onto the current default device, so a serving
        # replica pinned with ``jax.default_device`` stays on its chip.
        self.device = device
        self.stage_inputs = stage_inputs
        self.stage_ahead = stage_ahead
        self.block = block
        self.clock = clock
        self.last_carry = carry_in
        self.last_stats = RuntimeStats()

    @staticmethod
    def stateless(fn: Callable) -> Callable:
        """Lift a carry-free compute into the step signature."""
        return lambda chunk, carry: (fn(chunk), carry)

    # -- source -> chunks ---------------------------------------------------
    def _chunk_size(self) -> int:
        return self.controller.size if self.controller else self.microbatch

    def _chunks(self, items: Iterable, batched: bool) -> Iterator:
        """(count, chunk, built_size) triples; size re-read per chunk so
        the adaptive controller's moves take effect mid-stream.
        ``built_size`` is the size the chunk was requested at (count can
        be smaller on the ragged tail) — the key the controller files
        the dispatch's latency sample under."""
        if not batched:
            for item in items:
                yield 1, item, 1
            return
        if hasattr(items, "shape") and hasattr(items, "ndim"):
            s = 0
            n = items.shape[0]
            while s < n:
                k = self._chunk_size()
                yield min(k, n - s), items[s : s + k], k
                s += k
            return
        it = iter(items)
        buf: list = []
        while True:
            k = self._chunk_size()
            while len(buf) < k:
                try:
                    # analysis: allow-host-sync(host-side microbatch stacking before staging, not a device readback)
                    buf.append(np.asarray(next(it)))
                except StopIteration:
                    if buf:
                        yield len(buf), np.stack(buf), k
                    return
            yield k, np.stack(buf), k
            buf = []

    def _staged(self, chunks: Iterator) -> Iterator:
        if not self.stage_inputs:
            yield from chunks
            return
        queue: collections.deque = collections.deque()
        # stage_ahead beyond the dispatch window: the deque holds staged
        # chunks the dispatch loop has not consumed yet
        for count, chunk, built in chunks:
            queue.append((count, jax.device_put(chunk, self.device), built))
            if len(queue) > self.stage_ahead:
                yield queue.popleft()
        while queue:
            yield queue.popleft()

    # -- the scheduler core -------------------------------------------------
    def run(
        self, items: Iterable, *, batched: bool | None = None,
        meta: Callable | None = None,
    ) -> Iterator[DispatchResult]:
        """Drive ``items`` through the pipeline; yield retired dispatches
        in order.

        ``batched=None`` infers: stack/slice into microbatches unless
        the runtime is fixed at ``microbatch == 1`` and not adaptive (in
        which case items pass through unstacked, preserving each item's
        own rank).  ``meta(index, count, chunk)`` optionally computes a
        per-dispatch tag carried onto the ``DispatchResult`` (band
        spans use this)."""
        if batched is None:
            batched = self.adaptive or self.microbatch > 1
        stats = RuntimeStats()
        self.last_stats = stats
        t_run = self.clock()
        inflight: collections.deque = collections.deque()
        carry = self.carry_in

        def retire(d):
            out = d.out
            if self.block:
                # analysis: allow-host-sync(retire-time sync IS the depth-k window contract; dispatch stays async)
                out = jax.block_until_ready(out)
                d.latency_s = self.clock() - d._t0
                stats.latencies_s.append(d.latency_s)
                if self.controller is not None:
                    # keyed by the size the dispatch was BUILT with: in a
                    # depth-k window the controller may have moved since
                    self.controller.observe(d.count, d.latency_s,
                                            size=d._built)
            d.out = out
            stats.items += d.count
            stats.dispatches += 1
            stats.batch_sizes.append(d.count)
            stats.wall_s = self.clock() - t_run
            return d

        for index, (count, chunk, built) in enumerate(
            self._staged(self._chunks(items, batched))
        ):
            tag = meta(index, count, chunk) if meta is not None else None
            t0 = self.clock()
            out, carry = self.step(chunk, carry)
            d = DispatchResult(index=index, count=count, out=out,
                               carry=carry, meta=tag)
            d._t0 = t0
            d._built = built
            inflight.append(d)
            if len(inflight) >= self.depth:
                yield retire(inflight.popleft())
        while inflight:
            yield retire(inflight.popleft())
        self.last_carry = carry

    # -- sinks --------------------------------------------------------------
    def map_frames(self, frames: Iterable) -> Iterator:
        """Yield one result per input frame, in order (the executor /
        map_frames sink: batched dispatches are unstacked into per-frame
        views of the already-materialized device array)."""
        batched = self.adaptive or self.microbatch > 1
        for d in self.run(frames, batched=batched):
            if batched:
                for i in range(d.out.shape[0]):
                    yield d.out[i]
            else:
                yield d.out

    def fold(self, frames: Iterable, *, batched: bool | None = None):
        """Collect every dispatch output and the final carry:
        ``(outs, last_carry)`` — the tracker's chunked-scan sink."""
        outs = [d.out for d in self.run(frames, batched=batched)]
        return outs, self.last_carry
