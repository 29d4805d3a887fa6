"""The plain reference: integral histograms and the answers read from them,
in NumPy, independent of the program under test.

The arithmetic follows the paper: H(r, c, b) is the count of pixels of
bin b in the rectangle [0..r] x [0..c] (Eq. 1, integer prefix sums), a
region's histogram is the four-corner difference of H (Eq. 2), a
likelihood map is the Swain-Ballard intersection of each window's
normalised histogram with the normalised target, and a multi-scale search
takes the best window over the scales.  Nothing here imports ``repro``.

The queries read H only at the corners of their rects and windows, so
the check computes H there and nowhere else (``corners``): one pass over
the frame with int64 running sums, in memory of the corners' size.
``integral_histogram`` builds the whole H, as the tests' oracle.

``dtype`` selects the precision H is held in: an integer type is exact;
a float type is a control (the precision below the configuration's
counts), which has to fail the comparison.
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
    return held(H, dtype)


def held(counts: np.ndarray, dtype) -> np.ndarray:
    """Exact ``counts`` as H holds them in ``dtype``: an integer type
    as they are, a float type rounded to it (via float32, as a float32
    or lower H would round them) and widened to float64."""
    if np.issubdtype(np.dtype(dtype), np.integer):
        return counts.astype(dtype, copy=False)
    return counts.astype(np.float32).astype(dtype).astype(np.float64)


#: pixels counted at a time by ``corners``: bounds the memory of a band.
_CHUNK_PIXELS = 1 << 20


def corners(frame: np.ndarray, bins: int, value_range: int, rows, cols,
            dtype=np.int32) -> "Corners":
    """The padded integral histogram P (``padded(integral_histogram(...))``)
    at every row in ``rows`` and column in ``cols`` (0..h and 0..w), and
    nowhere else, held in ``dtype``.

    One pass over the frame: for each band of rows between consecutive
    members of ``rows``, ``np.bincount`` over (the column's bucket
    between members of ``cols``) x bin adds to running sums in int64; P
    at the band's lower edge is their cumulative sum over the buckets.
    Memory is the corners' (``bins x len(rows) x len(cols)``) and one
    chunk of a band."""
    h, w = frame.shape
    rows = np.unique(np.asarray(rows, np.int64))
    cols = np.unique(np.asarray(cols, np.int64))
    for name, v, n in (("rows", rows, h), ("cols", cols, w)):
        if v.size and (v[0] < 0 or v[-1] > n):
            raise ValueError(f"{name} out of 0..{n}")
    m = cols.size
    # column j counts towards every corner column c > j: bucket k holds
    # the columns cols[k-1] <= j < cols[k], and bucket m counts nowhere
    key_col = np.searchsorted(cols, np.arange(w), side="right") * bins
    running = np.zeros((m + 1) * bins, np.int64)
    integer = np.issubdtype(np.dtype(dtype), np.integer)
    out = np.empty((bins, rows.size, m), dtype if integer else np.float64)
    step = max(1, _CHUNK_PIXELS // max(w, 1))
    top = 0
    for i, r in enumerate(rows):
        for a in range(top, int(r), step):
            band = frame[a:min(a + step, int(r))]
            key = key_col + bin_ids(band, bins, value_range)
            running += np.bincount(key.ravel(), minlength=running.size)
        top = int(r)
        out[:, i, :] = held(running[:m * bins].reshape(m, bins)
                            .cumsum(axis=0), dtype).T
    return Corners(out, rows, cols, h, w)


class Corners:
    """P at the rows and columns ``corners`` computed, indexed in P's
    own coordinates, so that ``regions``, ``windows`` and what reads them
    take it in P's place: ``C[:, r, c]`` with two index arrays reads
    pointwise, and with a slice reads the lattice, as NumPy indexes P.
    Reading a corner that was not computed raises ``KeyError``."""

    def __init__(self, values: np.ndarray, rows: np.ndarray,
                 cols: np.ndarray, h: int, w: int):
        self.values, self.rows, self.cols = values, rows, cols
        self.shape = (values.shape[0], h + 1, w + 1)

    def __getitem__(self, key):
        b, r, c = key
        if b != slice(None):
            raise IndexError("Corners reads every bin at once")
        ri = _positions(self.rows, r, self.shape[1])
        ci = _positions(self.cols, c, self.shape[2])
        if isinstance(ri, slice) and isinstance(ci, slice):
            return self.values[:, ri, ci]      # a view, as P's slices are
        if isinstance(r, slice) or isinstance(c, slice):
            # a lattice, C-contiguous as P's own arithmetic leaves it, so
            # that float sums over bins add in the same order
            return (self.values.take(_index(ri), axis=1)
                    .take(_index(ci), axis=2))
        return np.ascontiguousarray(self.values[:, ri, ci])


def _positions(members: np.ndarray, index, n: int):
    """Where each coordinate ``index`` names lies in ``members``: for a
    slice whose coordinates lie evenly spaced in ``members`` (as a window
    lattice's do), a slice of the same length; else an index array."""
    want = np.arange(n)[index] if isinstance(index, slice) else \
        np.asarray(index)
    pos = np.searchsorted(members, want)
    if want.size and (members.size == 0 or np.any(
            members[np.minimum(pos, members.size - 1)] != want)):
        raise KeyError("a corner the queries read was not computed")
    if isinstance(index, slice) and pos.size:
        step = int(pos[1] - pos[0]) if pos.size > 1 else 1
        if step > 0 and np.all(np.diff(pos) == step):
            return slice(int(pos[0]), int(pos[-1]) + 1, step)
    return pos


def _index(pos):
    return pos if isinstance(pos, np.ndarray) else np.arange(
        pos.start, pos.stop, pos.step)


def window_lattice(n: int, size: int, stride: int) -> np.ndarray:
    """The corner coordinates ``windows`` reads along an axis of ``n``
    pixels: each window's two edges, ``i * stride`` and ``+ size``."""
    starts = np.arange(max(0, (n - size) // stride + 1)) * stride
    return np.union1d(starts, starts + size)


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
    out = P[:, r1, c1] - P[:, r0, c1]
    out -= P[:, r1, c0]
    out += P[:, r0, c0]
    return np.moveaxis(out, 0, -1)


def intersection(hists: np.ndarray, target: np.ndarray) -> np.ndarray:
    """Swain-Ballard intersection of normalised histograms, float64."""
    a = hists.astype(np.float64)
    a /= a.sum(axis=-1, keepdims=True)
    t = np.asarray(target, np.float64)
    t = t / t.sum()
    return np.minimum(a, t, out=a).sum(axis=-1)


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
