"""Double-buffered frame pipeline — paper §4.4 (dual-buffering, Fig. 12-14).

The paper overlaps (disk -> host), (host -> device), kernel execution and
(device -> host) across a frame sequence using two CUDA streams with
page-locked memory.  The JAX/TPU equivalent lives in ``core/runtime.py``
(one async scheduler: bounded in-flight window, microbatching, carry
threading, device prefetch); this module keeps the historical entry
points as thin adapters over it:

  * ``DoubleBufferedExecutor`` — ``depth`` dispatches in flight,
    ``batch_size`` frames stacked per dispatch.  depth=1 degenerates to
    fully synchronous execution (the "no dual-buffering" baseline of
    Fig. 13); on real TPUs the same code overlaps PCIe/DCN infeed with
    TPU compute, on CPU it overlaps host staging with XLA:CPU's async
    execution (benchmarks/bench_pipeline.py).
  * ``prefetch_to_device`` / ``prefetch_row_bands`` — the H2D staging
    half of the overlap, for consumers that drive their own compute.

Microbatch *sizing* lives in the planner (``core/engine.py``), which
owns ``auto_batch_size``; it is re-exported here for compatibility.
"""

from __future__ import annotations

from typing import Callable, Iterable, Iterator

import jax

from repro.core.runtime import FrameRuntime, stack_chunks, stage_stream

# Re-exports: sizing moved into the planner (core/engine.py) with PR 5;
# chunking moved into the runtime.  Import them from their new homes in
# new code.
from repro.core.engine import auto_batch_size  # noqa: F401

__all__ = [
    "DoubleBufferedExecutor",
    "auto_batch_size",
    "stack_chunks",
    "prefetch_to_device",
    "iter_row_bands",
    "prefetch_row_bands",
]


class DoubleBufferedExecutor:
    """Apply a jitted fn over a stream of host frames with dispatch-ahead.

    A thin adapter over ``runtime.FrameRuntime`` (the §4.4 scheduler).

    Args:
      fn: jitted callable.  With ``batch_size > 1`` it must accept stacked
        (k, *frame_shape) inputs and return outputs whose leading axis is
        the frame axis (``integral_histogram`` and ``IntegralHistogram``
        both do).
      depth: number of dispatches kept in flight (1 = synchronous).
      batch_size: frames stacked per dispatch.  The final chunk of a
        stream may be smaller (one extra compile for the ragged tail).
    """

    def __init__(
        self, fn: Callable, depth: int = 2, device=None, batch_size: int = 1
    ):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        if batch_size < 1:
            raise ValueError("batch_size must be >= 1")
        self.fn = fn
        self.depth = depth
        self.batch_size = batch_size
        self.device = device

    def _runtime(self) -> FrameRuntime:
        return FrameRuntime(
            FrameRuntime.stateless(self.fn),
            depth=self.depth,
            microbatch=self.batch_size,
            device=self.device,
        )

    def map(self, frames: Iterable) -> Iterator[jax.Array]:
        """Yield fn(frame) per input frame, `depth` dispatches in flight.

        With ``batch_size > 1`` each dispatch covers ``batch_size`` frames,
        but the iterator still yields one result per frame, in order.
        """
        return self._runtime().map_frames(frames)


def prefetch_to_device(
    frames: Iterable, size: int = 2, device=None
) -> Iterator[jax.Array]:
    """Stage host arrays onto the device ahead of consumption (training
    input pipeline building block).  Exactly ``size`` frames are staged
    before the first yield, and at most ``size`` frames are ever resident
    beyond the one in the consumer's hands.  Device-memory commitment is
    bounded by ``size``; for ``k`` transfers overlapping the consumer's
    compute in steady state, pass ``size=k + 1``."""
    return stage_stream(frames, size=size, device=device)


def iter_row_bands(image, spans) -> Iterator:
    """Host-side row-band slices ``image[..., r0:r1, :]`` of a frame or
    stack, one per (r0, r1) span (core/bands.py plans the spans)."""
    for r0, r1 in spans:
        yield image[..., r0:r1, :]


def prefetch_row_bands(image, spans, size: int = 2, device=None) -> Iterator:
    """Band-aware prefetch: stage the next band's image slice onto the
    device while the current band's kernel runs — the §4.4 dual-buffering
    idea applied inside one large frame instead of across a frame stream.
    Device commitment is bounded by ``size`` band slices (plus the one the
    consumer holds); the full frame never leaves the host."""
    return stage_stream(iter_row_bands(image, spans), size=size, device=device)
