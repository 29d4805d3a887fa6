"""The element type of an integral histogram, decided in one place.

Every count of H is an integer no larger than the frame's pixel count.
float32 holds every integer below 2**24 exactly and no longer holds odd
ones above it, so a frame of 2**24 pixels or more (the paper's §4.6
8192x8192 frame has 2**26) needs an integer H: one flat bin over a third
of such a frame counts past 2**24.  Below that bound H stays float32,
as it always was.

Only the ``wf_tis`` scan (its Pallas kernel and its jnp twin) accumulates
an int32 H; ``analysis/plancheck.py`` refuses every other representation
of a frame this size.
"""

from __future__ import annotations

import numpy as np

#: float32 represents consecutive integers exactly only below 2**24.
FP32_EXACT_COUNT = 1 << 24


def h_dtype(height: int, width: int) -> np.dtype:
    """H's element type for an (height, width) frame: float32 below
    2**24 pixels, int32 at or above it."""
    if height * width < FP32_EXACT_COUNT:
        return np.dtype(np.float32)
    return np.dtype(np.int32)


def exact_count_bound(dtype) -> int:
    """The largest count an H of ``dtype`` holds exactly."""
    if np.dtype(dtype) == np.float32:
        return FP32_EXACT_COUNT - 1
    return int(np.iinfo(dtype).max)
