"""O(1) region-histogram queries over an integral histogram (paper Eq. 2).

h(R, b) = H(r1, c1, b) - H(r0-1, c1, b) - H(r1, c0-1, b) + H(r0-1, c0-1, b)

for the inclusive region R = [r0..r1] x [c0..c1].  Corners with index -1
read as 0 (the virtual zero row/column of the inclusive integral image).

Also implements the paper's headline use case: multi-scale exhaustive
search — histograms of *every* sliding window extracted in constant time
per window — and target likelihood maps for tracking/detection.

Every entry point is rank-polymorphic over a frame-batch axis: an H of
shape ``(b, h, w)`` queries one frame, ``(n, b, h, w)`` (or any stack of
leading axes ``(..., b, h, w)``) queries every frame of the stack in ONE
dispatch, bit-exact with a per-frame Python loop.  Rects/windows are
shared across the frame axis; for per-frame rects, vmap
``region_histogram`` over the frame axis.

On a dense H (a raw array, or ``DenseH``) each query is one compiled
program per query shape, so a query costs a dispatch or two, not one per
``jax.numpy`` op:

  * ``_dense_windows`` — the window histograms, ``window`` and ``stride``
    static, as fp32 (an int32 H's field is cast after the four-corner
    sum, where every value is at most the window's area).  The regular
    window grid puts every corner of every window on a strided lattice
    of H, so the whole (n_rows, n_cols) field of Eq.-2 queries is
    strided ``lax.slice`` calls combined elementwise: the row lattices,
    then the column lattices of those, and the four corners as
    contiguous slices of them.  Where the stride divides the window
    along an axis, that axis's top (left) and bottom (right) corners lie
    on one lattice, so a dividing window reads H by one strided slice;
    otherwise by two.  Strided loads, no index arrays, no gather.
    Strided indexing (``H[..., r::s, c::s]``) would not do: jax turns a
    strided index into iota/mul/add index arrays and a ``gather``, which
    eagerly reads one bin column per window corner.
  * ``_score`` — ``metric(hists, target)``, ``metric`` static and the
    target traced.  Every representation scores through it, so a
    likelihood map is bit-identical whichever H answered.  It stays a
    program of its own: fused into the window program, XLA reorders
    the metric's float arithmetic.
  * ``_dense_regions`` — ``region_histogram`` with the rects traced
    (one compile per rect count), in H's element type, so an int32 H's
    regions of any size are exact.  Arbitrary corners stay a gather,
    inside the one program.

The window program takes any jax array H, a global array sharded over
its bins across chips included (``ShardedH``): the compiler keeps each
chip on its own bins, with no collective.  A gather of arbitrary
corners makes the TPU compiler lay the whole H out again, bins minor,
which a chip holding 8.59 GB of the paper's 8192x8192x128 H cannot
spare; ``regions_by_tiles`` answers region queries there with no gather
and no copy of H.

Called under ``jit`` or ``scan`` (``FragmentTracker``), the programs
inline into the caller's.  ``sliding_window_histograms(impl="gather")``
is one explicit Eq.-2 gather per window position, run eagerly: the
oracle for the slice path.

Every entry point also accepts an ``HSource`` (core/hsource.py) instead
of a raw array: the dense, banded, spilled, and sharded representations
all answer the same queries through one corner-row protocol — Eq. 2 only
ever reads corner *rows*, so a rect touches at most 2 bands and a
sliding-window field touches two strided row lattices.  Frames whose
full (b, h, w) H exceeds memory (paper §4.6: 32 GB at 64 MB x 128 bins)
still get exact O(1) queries and likelihood maps.

The ``banded_*`` entry points are deprecated shims over that dispatch
(``BandedH`` + the unified functions); see ``HistogramEngine``
(core/engine.py) for the planned successor to hand-routing any of this.
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _maybe_hsource(H):
    """Return H as an HSource when it is one, else None (raw array path)."""
    from repro.core import hsource  # deferred: hsource imports this module

    return H if isinstance(H, hsource.HSource) else None


def _corner(H: jnp.ndarray, r: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """H[..., :, r, c] with r/c == -1 reading as 0.

    H: (..., b, h, w); r, c: broadcastable int arrays (idx shape ``S``).
    Returns shape (..., *S, b) — bins moved last for query ergonomics.
    """
    r = jnp.asarray(r)
    c = jnp.asarray(c)
    rc, cc = jnp.broadcast_arrays(jnp.clip(r, 0, None), jnp.clip(c, 0, None))
    # Advanced indices on the two trailing axes are adjacent, so the index
    # dims land in place: (..., b, h, w) -> (..., b, *S).
    vals = H[..., rc, cc]
    if rc.ndim:
        vals = jnp.moveaxis(vals, -(rc.ndim + 1), -1)        # (..., *S, b)
    valid = ((r >= 0) & (c >= 0)).astype(H.dtype)
    return vals * valid[..., None]


def _four_corners(H: jnp.ndarray, rects: jnp.ndarray) -> jnp.ndarray:
    """Eq. 2 for each rect: the four-corner sum, bins last."""
    r0, c0, r1, c1 = (rects[..., i] for i in range(4))
    return (
        _corner(H, r1, c1)
        - _corner(H, r0 - 1, c1)
        - _corner(H, r1, c0 - 1)
        + _corner(H, r0 - 1, c0 - 1)
    )


@jax.jit
def _dense_regions(H: jnp.ndarray, rects: jnp.ndarray) -> jnp.ndarray:
    """``_four_corners`` as one program; compiles once per rect shape."""
    return _four_corners(H, rects)


def regions_by_tiles(H: jnp.ndarray, rects: jnp.ndarray) -> jnp.ndarray:
    """``region_histogram`` of H (..., b, h, w) without a gather: a loop
    over the rects reads each corner as the masked sum of the (8, 128)
    tile of H that holds it, over all bins at once.  Slices of H's own
    tiles keep H in its layout, where a gather of single points would
    have the compiler copy the whole H, bins minor.  The masked sum adds
    zeros to the one value, and the four corners combine in Eq. 2's
    order, so the result is bit-identical to ``_four_corners``."""
    lead = H.shape[:-2]                       # (..., b)
    h, w = H.shape[-2:]
    th, tw = min(8, h), min(128, w)
    zero = jnp.zeros((), H.dtype)

    def corner(r, c):
        rr, cc = jnp.maximum(r, 0), jnp.maximum(c, 0)
        r0 = jnp.minimum(rr - rr % th, h - th)
        c0 = jnp.minimum(cc - cc % tw, w - tw)
        tile = lax.dynamic_slice(H, (0,) * len(lead) + (r0, c0),
                                 lead + (th, tw))
        hit = ((lax.broadcasted_iota(jnp.int32, (th, tw), 0) == rr - r0)
               & (lax.broadcasted_iota(jnp.int32, (th, tw), 1) == cc - c0))
        v = jnp.sum(jnp.where(hit, tile, zero), axis=(-2, -1))
        return jnp.where((r >= 0) & (c >= 0), v, zero)

    def one(rect):
        r0, c0, r1, c1 = rect[0], rect[1], rect[2], rect[3]
        return (corner(r1, c1) - corner(r0 - 1, c1)
                - corner(r1, c0 - 1) + corner(r0 - 1, c0 - 1))

    out = lax.map(one, rects.reshape(-1, 4))              # (R, ..., b)
    out = jnp.moveaxis(out, 0, -2)                         # (..., R, b)
    return out.reshape(lead[:-1] + rects.shape[:-1] + lead[-1:])


def region_histogram(H: jnp.ndarray, rects: jnp.ndarray) -> jnp.ndarray:
    """Histograms of inclusive regions.

    Args:
      H: (b, h, w) integral histogram, or a stack (..., b, h, w).
      rects: (..., 4) int32 [r0, c0, r1, c1], inclusive coordinates,
        shared across any leading frame axes of H.

    Returns:
      (*H_lead, *rects_lead, b) region histograms.
    """
    src = _maybe_hsource(H)
    if src is not None:
        return src.region_histogram(rects)
    return _dense_regions(H, jnp.asarray(rects))


def _sliding_windows_gather(
    H: jnp.ndarray, window: tuple[int, int], stride: int
) -> jnp.ndarray:
    """One Eq.-2 gather per window position (the original path)."""
    h, w = H.shape[-2:]
    wh, ww = window
    rows = jnp.arange(0, h - wh + 1, stride)
    cols = jnp.arange(0, w - ww + 1, stride)
    r0 = rows[:, None]
    c0 = cols[None, :]
    rects = jnp.stack(
        jnp.broadcast_arrays(r0, c0, r0 + wh - 1, c0 + ww - 1), axis=-1
    )
    return _four_corners(H, rects)


def _lattice(x, axis: int, start: int, n: int, s: int):
    """x[start + i·s] for i < n along ``axis``: one strided ``lax.slice``
    (an empty lattice is a zero-size array, the whole axis is x itself)."""
    shape = list(x.shape)
    if n == 0:
        shape[axis] = 0
        return jnp.zeros(shape, x.dtype)
    if start == 0 and n == shape[axis]:
        return x
    starts = [0] * x.ndim
    strides = [1] * x.ndim
    starts[axis] = start
    shape[axis] = start + (n - 1) * s + 1
    strides[axis] = s
    return lax.slice(x, starts, shape, strides)


def _zero_first(x, axis: int):
    """Prepend the virtual zero row (or column) H(-1, ·) along ``axis``."""
    shape = list(x.shape)
    shape[axis] = 1
    return jnp.concatenate([jnp.zeros(shape, x.dtype), x], axis=axis)


def _shares_lattice(extent: int, stride: int) -> bool:
    """Do a window's near and far corners along an axis lie on one
    lattice?  They do when the stride divides the window's extent."""
    return extent % stride == 0


def row_lattices(window: tuple[int, int], stride: int) -> int:
    """How many strided row lattices ``_dense_windows`` takes from H:
    1 where the stride divides the window's height, else 2."""
    return 1 if _shares_lattice(window[0], stride) else 2


def _corner_lattices(x, axis: int, extent: int, n: int, s: int, *,
                     zero: bool):
    """The corner lattices of n windows of ``extent`` at stride s along
    ``axis``: the near corners x[i·s - 1] (x[-1] the virtual zero) and
    the far corners x[extent-1 + i·s], i < n.

    Returns ``(lattices, near, far)``, ``near`` and ``far`` each a
    (lattice index, start) pair for ``_corners``.  Where s divides the
    extent both lie on one lattice, x[s-1 + i·s] for i < n - 1 +
    extent/s, whose far corners start extent/s entries after the near
    ones: x is read by one strided slice.  Otherwise they are two
    lattices.  With ``zero`` the virtual zero is prepended to the
    near corners' lattice and counted in the starts; without, the near
    start is -1."""
    if _shares_lattice(extent, s):
        k = extent // s
        lattices = [_lattice(x, axis, s - 1, n - 1 + k, s)]
        near, far = (0, -1), (0, k - 1)
    else:
        lattices = [_lattice(x, axis, s - 1, n - 1, s),
                    _lattice(x, axis, extent - 1, n, s)]
        near, far = (0, -1), (1, 0)
    if zero:                    # every start on lattice 0 moves up one
        lattices[0] = _zero_first(lattices[0], axis)
        near = (0, 0)
        if far[0] == 0:
            far = (0, far[1] + 1)
    return lattices, near, far


def _corners(x, axis: int, start: int, n: int):
    """x[start : start + n] along ``axis``, where start -1 reads the
    virtual zero before x[0]."""
    if start < 0:
        return _zero_first(_lattice(x, axis, 0, n - 1, 1), axis)
    return _lattice(x, axis, start, n, 1)


@functools.partial(jax.jit, static_argnames=("window", "stride"))
def _dense_windows(
    H: jnp.ndarray, window: tuple[int, int], stride: int
) -> jnp.ndarray:
    """Strided-slice four-corner arithmetic over the regular window grid.

    The window lattice r0 = i·s, c0 = j·s puts all four Eq.-2 corners of
    every window on strided lattices of H itself:

      bottom-right  H[wh-1 + i·s, ww-1 + j·s]
      top-right     H[i·s - 1,    ww-1 + j·s]   H(-1, ·) = 0
      (and symmetrically for the left corners, on columns j·s - 1)

    The row lattices are taken first, on H's row axis: where the stride
    divides the window's height the top and bottom rows lie on one
    lattice, H[s-1 + i·s], wh/s entries apart, so H is read by one
    strided slice; otherwise they are two lattices.  The column axis is
    then moved to the front, so the column lattices are strided slices
    of a major axis, not of the minor (lane) axis: on a TPU v5e the
    1080p 32-bin 64x64 stride-2 field took 15.3 ms with both strides on
    H's last two axes, 3.75 ms this way with two row lattices, and
    1.66 ms with one.  The columns are taken the
    same way, one lattice where the stride divides the window's width,
    and the four corners are contiguous slices of the lattices.  The
    result is built as (columns, bins, rows), which is the layout the
    TPU compiler gives (rows, columns, bins), so the last moveaxis is
    free there.

    The virtual H(-1, ·) = H(·, -1) = 0 boundary is a one-element zero
    strip: prepended to the column lattices, and to the top corners once
    they are picked.  Prepended to the row lattice, which is the minor
    axis after the move, the compiler for the v5e spends a pass over the
    whole moved lattice on it.
    """
    h, w = H.shape[-2:]
    wh, ww = window
    s = stride
    n_r = (h - wh) // s + 1
    n_c = (w - ww) // s + 1
    rows, top, bottom = _corner_lattices(H, -2, wh, n_r, s, zero=False)
    rows = [jnp.moveaxis(r, -1, -3) for r in rows]           # (..., w, b, *)
    cols = [_corner_lattices(r, -3, ww, n_c, s, zero=True) for r in rows]

    def corner(row, side):                                   # (..., n_c, b, n_r)
        lattices, *sides = cols[row[0]]
        lattice, start = sides[side]
        x = _corners(lattices[lattice], -3, start, n_c)
        return _corners(x, -1, row[1], n_r)

    left, right = 0, 1
    d = corner(bottom, right)
    b = corner(top, right)
    c = corner(bottom, left)
    a = corner(top, left)
    # Same association order as the gather path (d - b - c + a) so the
    # fp32 arithmetic is bit-identical, not just allclose.  An int32 H's
    # field becomes fp32 only after the sum (a no-op for an fp32 H).
    field = (d - b - c + a).astype(jnp.float32)
    return jnp.moveaxis(field, -1, -3)                       # (..., n_r, n_c, b)


@functools.partial(jax.jit, static_argnames=("metric",))
def _score(hists: jnp.ndarray, target: jnp.ndarray, metric) -> jnp.ndarray:
    """``metric(hists, target)`` over a window field (..., n_r, n_c, b).

    ``target`` is (b,), or carries the field's leading frame axes (one
    target per frame, broadcast over window positions)."""
    if target.ndim > 1:
        target = target[..., None, None, :]
    return metric(hists, target)


def score(hists: jnp.ndarray, target, metric) -> jnp.ndarray:
    """Score a window field against ``target`` with the shared program
    (``_score``): every representation's likelihood maps go through it."""
    return _score(hists, jnp.asarray(target), metric)


def sliding_window_histograms(
    H: jnp.ndarray,
    window: tuple[int, int],
    stride: int = 1,
    *,
    impl: str = "slice",
    stats: dict | None = None,
) -> jnp.ndarray:
    """Histograms of every (wh, ww) window at the given stride.

    Returns (..., n_rows, n_cols, b) — one O(1) query per window position
    and frame; this is the constant-time multi-scale exhaustive search of
    the paper.  ``impl`` selects the strided-slice path (default) or the
    explicit per-window gather (see module docstring); both are bit-exact.
    An ``HSource`` H routes through the corner-row protocol (``impl`` is
    moot there; ``stats`` receives the peak-memory proxy).
    """
    if impl not in ("slice", "gather"):
        raise ValueError(f"unknown impl {impl!r} (want 'slice' or 'gather')")
    src = _maybe_hsource(H)
    if src is not None:
        return src.sliding_window_histograms(window, stride, stats=stats)
    if stats is not None:
        # Dense-array semantics: the whole H is the one live "band".
        nbytes = 4 * int(np.prod(H.shape, dtype=np.int64))
        stats.update(num_bands=1, band_bytes=nbytes, slab_bytes=0,
                     peak_bytes=nbytes, full_h_bytes=nbytes)
    h, w = H.shape[-2:]
    n_r = (h - window[0]) // stride + 1
    n_c = (w - window[1]) // stride + 1
    if n_r <= 0 or n_c <= 0:
        # window larger than the frame on some axis: no positions
        return jnp.zeros(
            H.shape[:-3] + (max(n_r, 0), max(n_c, 0), H.shape[-3]),
            jnp.float32,
        )
    if impl == "slice":
        return _dense_windows(H, window=tuple(int(v) for v in window),
                              stride=int(stride))
    return _sliding_windows_gather(H, window, stride)


def likelihood_map(H: jnp.ndarray, target_hist: jnp.ndarray,
                   window: tuple[int, int], metric, stride: int = 1,
                   *, stats: dict | None = None):
    """Feature likelihood map (abstract, ¶1): per-position similarity of the
    window histogram to the target histogram.

    ``target_hist`` is (b,) — one target for all frames — or carries the
    same leading frame axes as H (e.g. (n, b) against an (n, b, h, w)
    stack: one target per frame, broadcast over window positions).
    Returns (..., n_rows, n_cols).  H may be any ``HSource``.
    """
    src = _maybe_hsource(H)
    if src is not None:
        return src.likelihood_map(target_hist, window, metric, stride,
                                  stats=stats)
    hists = sliding_window_histograms(H, window, stride, stats=stats)
    return score(hists, target_hist, metric)


def reduce_scale_maps(maps, windows, stride: int, lead: tuple):
    """Per-frame argmax across a list of per-scale likelihood maps.

    Shared by the dense ``multi_scale_search`` and the ``HSource`` generic
    (core/hsource.py) so both reduce identically (bit-exact)."""
    best_rect = jnp.zeros(lead + (4,), jnp.int32)
    best_score = jnp.full(lead, -jnp.inf)
    for (wh, ww), scores in zip(windows, maps):
        if scores.shape[-2] == 0 or scores.shape[-1] == 0:
            continue                # window exceeds the frame at this scale
        flat = scores.reshape(lead + (-1,))
        idx = jnp.argmax(flat, axis=-1)
        score = jnp.take_along_axis(flat, idx[..., None], axis=-1)[..., 0]
        n_cols = scores.shape[-1]
        r0 = (idx // n_cols) * stride
        c0 = (idx % n_cols) * stride
        rect = jnp.stack(
            [r0, c0, r0 + wh - 1, c0 + ww - 1], axis=-1
        ).astype(jnp.int32)
        better = score > best_score
        best_rect = jnp.where(better[..., None], rect, best_rect)
        best_score = jnp.maximum(score, best_score)
    return best_rect, best_score


def multi_scale_search(
    H: jnp.ndarray,
    target_hist: jnp.ndarray,
    windows: tuple[tuple[int, int], ...],
    metric,
    stride: int = 1,
):
    """Best-matching window across scales, per frame.

    Returns (best_rect, best_score, per_scale_maps) where ``metric`` is a
    similarity (higher = better) from core/distances.py.  For an H stack
    (..., b, h, w) the rects are (..., 4) and scores (...,) — the argmax
    runs independently per frame, matching a per-frame loop bit-exactly.
    An ``HSource`` H fetches the union of every scale's corner-row
    lattices in one pass (one band stream serves all scales).
    """
    src = _maybe_hsource(H)
    if src is not None:
        return src.multi_scale_search(target_hist, windows, metric, stride)
    lead = H.shape[:-3]
    maps = [
        likelihood_map(H, target_hist, (wh, ww), metric, stride)
        for wh, ww in windows
    ]
    best_rect, best_score = reduce_scale_maps(maps, windows, stride, lead)
    return best_rect, best_score, maps


# ---------------------------------------------------------------------------
# Banded queries: Eq. 2 over a band stream (core/bands.py) — the full
# (b, h, w) H never materializes.
# ---------------------------------------------------------------------------
def compressed_region_histogram(
    Hc: jnp.ndarray, row_ids: jnp.ndarray, rects: jnp.ndarray
) -> jnp.ndarray:
    """Eq.-2 queries against a row-compressed H.

    ``Hc`` (..., b, k, w) holds only the full-frame H rows listed in
    ``row_ids`` (sorted, ascending).  Every rect corner row (r0 - 1 and
    r1) must appear in ``row_ids`` or be -1 (the virtual zero row).  The
    four-term association order matches ``region_histogram`` exactly, so
    fp32 results are bit-identical; integer-dtype Hc wraps modularly
    (the reduced-width spill policies rely on this).
    """
    r0, c0, r1, c1 = (rects[..., i] for i in range(4))

    def m(r):  # remap a frame row to its slot in Hc; keep -1 virtual
        return jnp.where(r >= 0, jnp.searchsorted(row_ids, r), -1)

    return (
        _corner(Hc, m(r1), c1)
        - _corner(Hc, m(r0 - 1), c1)
        - _corner(Hc, m(r1), c0 - 1)
        + _corner(Hc, m(r0 - 1), c0 - 1)
    )


def corner_rows(rects: np.ndarray) -> np.ndarray:
    """The distinct full-frame H rows Eq. 2 reads for ``rects``: r0 - 1
    and r1 per rect, deduplicated, the virtual -1 row dropped.  Shared by
    ``banded_region_histogram`` and ``bands.SpilledIH.region_histogram``."""
    rects = np.asarray(rects)
    needed = np.unique(
        np.concatenate([(rects[..., 0] - 1).ravel(), rects[..., 2].ravel()])
    )
    return needed[needed >= 0].astype(np.int64)


def _deprecated_banded(name: str, replacement: str):
    warnings.warn(
        f"{name} is deprecated and will be removed in 2.0: wrap the band "
        f"stream in an HSource and use the unified entry point instead — "
        f"{replacement} — or drive the whole request through "
        "repro.core.engine.HistogramEngine",
        DeprecationWarning,
        stacklevel=3,
    )


def banded_region_histogram(bands, rects: jnp.ndarray) -> jnp.ndarray:
    """Deprecated shim: ``region_histogram(BandedH(bands), rects)``.

    Streams the bands once, keeping only the corner rows the rects touch
    (each rect's four corners live on two rows, hence in <= 2 bands);
    memory is O(distinct corner rows x b x w), never O(b x h x w).
    """
    from repro.core.hsource import as_hsource

    _deprecated_banded(
        "banded_region_histogram", "region_histogram(BandedH(bands), rects)"
    )
    return region_histogram(as_hsource(bands), rects)


def banded_sliding_window_histograms(
    bands,
    window: tuple[int, int],
    stride: int = 1,
    *,
    stats: dict | None = None,
) -> jnp.ndarray:
    """Deprecated shim:
    ``sliding_window_histograms(BandedH(bands), window, stride)``.

    On the regular window grid all four Eq.-2 corners live on two strided
    row lattices, so the stream is consumed in one pass into corner-row
    slabs; peak memory is one band plus the slabs (``stats`` receives the
    proxy), never the full H.  At stride 1 the slabs match the full-H
    footprint and a UserWarning says banding cannot help.
    """
    from repro.core.hsource import as_hsource

    _deprecated_banded(
        "banded_sliding_window_histograms",
        "sliding_window_histograms(BandedH(bands), window, stride)",
    )
    return sliding_window_histograms(
        as_hsource(bands), window, stride, stats=stats
    )


def banded_likelihood_map(
    bands,
    target_hist: jnp.ndarray,
    window: tuple[int, int],
    metric,
    stride: int = 1,
    *,
    stats: dict | None = None,
):
    """Deprecated shim:
    ``likelihood_map(BandedH(bands), target, window, metric, stride)``."""
    from repro.core.hsource import as_hsource

    _deprecated_banded(
        "banded_likelihood_map",
        "likelihood_map(BandedH(bands), target, window, metric)",
    )
    return likelihood_map(
        as_hsource(bands), target_hist, window, metric, stride, stats=stats
    )
