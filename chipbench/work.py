"""Bytes each kernel has to move, computed from the shapes of its work.

The counts are of the algorithm, not of an implementation: a frame read
once as uint8 and its integral histogram written once as float32, so a
later kernel that pads less, or bins elsewhere, is held to the same work.
"""

from __future__ import annotations

H_ITEM = 4        # float32 counts
PIXEL = 1         # uint8 frames


def wf_tis_bytes(frames: float, h: int, w: int, bins: int) -> float:
    """Read ``frames`` (h, w) uint8 frames, write their (bins, h, w) H."""
    return frames * (h * w * PIXEL + bins * h * w * H_ITEM)


def fused_rows_bytes(frames: int, h_scanned: int, w: int, bins: int,
                     rows: int) -> int:
    """Read the scanned rows of each frame, write ``rows`` rows of H."""
    return frames * (h_scanned * w * PIXEL + bins * rows * w * H_ITEM)


def delta_apply_bytes(frames: int, rows: int, w: int, bins: int) -> int:
    """Read and write a (bins, rows, w) slab of H, read one delta row."""
    return frames * bins * w * H_ITEM * (2 * rows + 1)


def roofline_share(bytes_moved: float, seconds: float,
                   hbm_bytes_per_s: float) -> float | None:
    """Percent of the time the bytes need at peak bandwidth; ``None``
    when there is no time to divide by."""
    if seconds <= 0:
        return None
    return 100.0 * bytes_moved / hbm_bytes_per_s / seconds
