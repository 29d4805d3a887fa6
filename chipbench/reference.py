"""The plain reference: integral histograms and the answers read from them,
in NumPy, independent of the program under test.

The arithmetic follows the paper: H(r, c, b) is the count of pixels of
bin b in the rectangle [0..r] x [0..c] (Eq. 1, integer prefix sums), a
region's histogram is the four-corner difference of H (Eq. 2), a
likelihood map is the Swain-Ballard intersection of each window's
normalised histogram with the normalised target, and a multi-scale search
takes the best window over the scales.  Nothing here imports ``repro``.

``dtype`` selects the precision H is held in: an integer type is exact;
bfloat16 is the control (the nearest precision below the configuration's
float32 counts), which has to fail the comparison.
"""

from __future__ import annotations

import numpy as np


def bin_ids(frame: np.ndarray, bins: int, value_range: int) -> np.ndarray:
    idx = (frame.astype(np.int64) * bins) // value_range
    return np.clip(idx, 0, bins - 1)


def integral_histogram(frame: np.ndarray, bins: int, value_range: int,
                       dtype=np.int32) -> np.ndarray:
    """(bins, h, w) inclusive integral histogram by integer prefix sums
    (int32 holds every count of a frame below 2**31 pixels), then held in
    ``dtype`` (rounded, for a float control)."""
    idx = bin_ids(frame, bins, value_range)
    q = idx[None] == np.arange(bins)[:, None, None]
    H = q.cumsum(axis=1, dtype=np.int32).cumsum(axis=2, dtype=np.int32)
    if np.issubdtype(np.dtype(dtype), np.integer):
        return H.astype(dtype, copy=False)
    return H.astype(np.float32).astype(dtype).astype(np.float64)


def padded(H: np.ndarray) -> np.ndarray:
    """H with the virtual zero row and column in front: P[:, r+1, c+1]."""
    b, h, w = H.shape
    P = np.zeros((b, h + 1, w + 1), H.dtype)
    P[:, 1:, 1:] = H
    return P


def regions(P: np.ndarray, rects: np.ndarray) -> np.ndarray:
    """(k, bins) histograms of inclusive rects [r0, c0, r1, c1] (Eq. 2)."""
    r0, c0, r1, c1 = (np.asarray(rects)[:, i] for i in range(4))
    out = (P[:, r1 + 1, c1 + 1] - P[:, r0, c1 + 1]
           - P[:, r1 + 1, c0] + P[:, r0, c0])
    return out.T


def windows(P: np.ndarray, window, stride: int) -> np.ndarray:
    """(n_r, n_c, bins) histograms of every window at ``stride``: the
    four corners of window (i, j) are P[:, i*s (+wh), j*s (+ww)]."""
    b, hp, wp = P.shape
    wh, ww = window
    n_r = (hp - 1 - wh) // stride + 1
    n_c = (wp - 1 - ww) // stride + 1
    r0 = slice(0, (n_r - 1) * stride + 1, stride)
    r1 = slice(wh, wh + (n_r - 1) * stride + 1, stride)
    c0 = slice(0, (n_c - 1) * stride + 1, stride)
    c1 = slice(ww, ww + (n_c - 1) * stride + 1, stride)
    out = P[:, r1, c1] - P[:, r0, c1] - P[:, r1, c0] + P[:, r0, c0]
    return np.moveaxis(out, 0, -1)


def intersection(hists: np.ndarray, target: np.ndarray) -> np.ndarray:
    """Swain-Ballard intersection of normalised histograms, float64."""
    a = hists.astype(np.float64)
    a = a / a.sum(axis=-1, keepdims=True)
    t = np.asarray(target, np.float64)
    t = t / t.sum()
    return np.minimum(a, t).sum(axis=-1)


def likelihood(P: np.ndarray, target, window, stride: int) -> np.ndarray:
    return intersection(windows(P, window, stride), target)


def multiscale(P: np.ndarray, target, scales, stride: int):
    """Per-scale maps and the best score over all of them."""
    maps = [likelihood(P, target, s, stride) for s in scales]
    return maps, max(float(m.max()) for m in maps)


def score_at(maps, scales, stride: int, rect) -> float:
    """The reference score of the window ``rect`` = [r0, c0, r1, c1]."""
    r0, c0, r1, c1 = (int(v) for v in rect)
    for m, (wh, ww) in zip(maps, scales):
        if r1 - r0 + 1 == wh and c1 - c0 + 1 == ww:
            return float(m[r0 // stride, c0 // stride])
    return float("-inf")
