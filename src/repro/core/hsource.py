"""One H-representation protocol for every way the repo holds an
integral histogram.

PRs 1-3 grew four representations of the same mathematical object — a
dense ``jax.Array`` H, a streamed band sequence (core/bands.py), a
host-spilled ``SpilledIH`` under a storage policy, and a mesh-sharded H
(core/distributed.py) — each with its own forked analytics entry points.
Eq. 2 only ever reads corner *rows* of H, so a single protocol suffices:

    class HSource:
        num_bins / height / width / lead     # metadata
        exact_region_bound                   # storage-policy count bound
        rows(row_ids) -> (..., b, k, w)      # host array, storage dtype
        dense() -> (..., b, h, w)            # assemble (when it fits)

Every analytics function (``region_histogram``,
``sliding_window_histograms``, ``likelihood_map``,
``multi_scale_search``) has ONE generic implementation against
``rows()`` — a rect touches two rows, a sliding-window field touches two
strided row lattices, and a multi-scale search touches the union of its
scales' lattices in a single pass.  Representations override only where
a genuinely faster path exists (the compiled programs of
core/region_query.py on a dense or bin-sharded H, which never leave the
device); results are bit-exact either way because all H arithmetic is
integer-valued (fp32 below 2**24 pixels, int32 past it — core/hdtype.py
— and modular for the integer storage policies).  Region histograms
keep an int32 H's type, so a region of any size is exact.

Every pull of H to the host goes through ``to_host``, which adds its
bytes to the tally ``counting_d2h`` opens: what ``HistogramEngine.run``
reports as ``d2h_bytes``.

``rows()``/``dense()`` return **host** (numpy) arrays by design: on
jax 0.4.37 ``jnp.concatenate`` over row-sharded device bands silently
mis-assembles (see CHANGES.md, PR 3), so cross-band and cross-shard
assembly always goes through ``np.asarray`` — regression-tested in
tests/test_distributed.py.
"""

from __future__ import annotations

import abc
import contextlib
import contextvars
import functools
import itertools
import warnings

import jax.numpy as jnp
import numpy as np

from repro.core import region_query as rq


_D2H_TALLY: contextvars.ContextVar = contextvars.ContextVar(
    "d2h_tally", default=None)


@contextlib.contextmanager
def counting_d2h():
    """Tally the bytes ``to_host`` pulls in this context (this thread):
    yields a one-element list holding the running total."""
    tally = [0]
    token = _D2H_TALLY.set(tally)
    try:
        yield tally
    finally:
        _D2H_TALLY.reset(token)


def to_host(x) -> np.ndarray:
    """``np.asarray(x)``, its bytes added to the open ``counting_d2h``."""
    out = np.asarray(x)
    tally = _D2H_TALLY.get()
    if tally is not None:
        tally[0] += out.nbytes
    return out


class MissingRowsError(KeyError):
    """A row-restricted source was asked for rows it does not hold.

    Raised by :class:`PrefetchedRowsH` (engine prefetch missed a query's
    rows — a caller bug) and :class:`FusedRowsH` (a fused result holds
    ONLY its request's corner rows; asking for more means the request
    changed and the engine must recompute — ``AnalyticsService`` catches
    exactly this to fall back from a fused cache hit)."""


class HSource(abc.ABC):
    """Corner-row access + metadata over any integral-histogram holder."""

    # -- metadata: concrete classes provide these as attributes, dataclass
    # fields (SpilledIH), or properties -------------------------------------
    num_bins: int
    height: int
    width: int
    lead: tuple      # leading frame axes of the H stack (() for a frame)

    @property
    def exact_region_bound(self) -> int | None:
        """Largest region pixel count a query is guaranteed exact for, or
        ``None`` when unbounded: an H in its frame's element type
        (core/hdtype.py) holds every count of the frame exactly — fp32
        below 2**24 pixels, int32 past it.  Only a storage policy that
        wraps (``SpilledIH``) bounds its regions."""
        return None

    @property
    def nbytes(self) -> int:
        """Size estimate for cache accounting (``AnalyticsService``'s
        byte-aware eviction).  The default is the planner's estimate —
        the full fp32 H footprint — which is exact for a materialized
        dense H and deliberately conservative for streamed/factory
        sources (what a replay can transiently pin); representations
        with a real resident footprint (SpilledIH, FusedRowsH)
        override it."""
        nlead = int(np.prod(self.lead, dtype=np.int64) or 1)
        return 4 * nlead * self.num_bins * self.height * self.width

    # -- the one representation primitive -----------------------------------
    @abc.abstractmethod
    def rows(self, row_ids) -> np.ndarray:
        """Full-frame H restricted to ``row_ids`` (sorted, ascending).

        Returns a host array (..., b, len(row_ids), w) in the source's
        storage dtype (integer policies keep their modular values)."""

    def dense(self):
        """Materialize (..., b, h, w) as fp32 — small frames only."""
        return jnp.asarray(
            self.rows(np.arange(self.height)).astype(np.float32)
        )

    # -- unified analytics (Eq. 2 against rows()) ---------------------------
    def _check_region_bound(self, max_area: int, what: str = "region") -> None:
        bound = self.exact_region_bound
        if bound is not None and max_area > bound:
            raise ValueError(
                f"{what} of {max_area} pixels exceeds the {self.storage} "
                f"storage policy's exact-count bound {bound}; spill with a "
                "wider policy"
            )

    def region_histogram(self, rects) -> jnp.ndarray:
        """``region_query.region_histogram`` semantics: int32 from an
        int32 H, fp32 otherwise (the storage policies' modular counts are
        true counts after the four-corner sum)."""
        rects = np.asarray(rects)
        area = (rects[..., 2] - rects[..., 0] + 1) * (
            rects[..., 3] - rects[..., 1] + 1
        )
        self._check_region_bound(int(np.max(area)))
        needed = rq.corner_rows(rects)
        Hc = self.rows(needed)
        out = rq.compressed_region_histogram(
            jnp.asarray(Hc), jnp.asarray(needed), jnp.asarray(rects)
        )
        return out if out.dtype == jnp.int32 else out.astype(jnp.float32)

    def _window_lattices(self, window, stride):
        """The two corner-row lattices of the regular window grid."""
        wh, ww = window
        n_r = (self.height - wh) // stride + 1
        n_c = (self.width - ww) // stride + 1
        bot = wh - 1 + np.arange(max(n_r, 0)) * stride
        top = np.arange(max(n_r, 0)) * stride - 1     # row -1 is virtual
        return n_r, n_c, bot, top

    def _windows_from_rows(self, R, needed, window, stride):
        """Four-corner arithmetic over prefetched corner rows.

        ``R`` is ``self.rows(needed)``; integer storage dtypes wrap
        modularly through the whole combination, so the result is exact
        whenever the window area fits the policy bound (validated by the
        caller)."""
        n_r, n_c, bot_rows, top_rows = self._window_lattices(window, stride)
        bot = R[..., np.searchsorted(needed, bot_rows), :]
        top = np.zeros_like(bot)
        real = top_rows >= 0
        top[..., real, :] = R[..., np.searchsorted(needed, top_rows[real]), :]
        # In-place difference (unsigned dtypes wrap modularly, as required)
        # and drop ``top`` immediately: peak memory stays at R + the two
        # n_r-row slabs — the proxy _fill_stats reports as 2 * R.nbytes.
        np.subtract(bot, top, out=bot)
        del top
        diff = bot                                     # (..., b, n_r, w)
        s = stride
        ww = window[1]
        d = diff[..., ww - 1 :: s][..., :n_c]
        c = np.zeros_like(d)                           # virtual zero column
        c[..., 1:] = diff[..., s - 1 :: s][..., : n_c - 1]
        out = d - c
        if out.dtype != np.float32:
            # Post-combination values are true counts (<= the validated
            # window area), so the cast out of the modular dtype is exact.
            out = out.astype(np.float32)
        return jnp.asarray(np.moveaxis(out, -3, -1))   # (..., n_r, n_c, b)

    def _empty_windows(self, n_r, n_c):
        return jnp.zeros(
            self.lead + (max(n_r, 0), max(n_c, 0), self.num_bins),
            jnp.float32,
        )

    def sliding_window_histograms(
        self, window, stride: int = 1, *, stats: dict | None = None
    ) -> jnp.ndarray:
        """``region_query.sliding_window_histograms`` semantics: one O(1)
        query per window position, one ``rows()`` pass total."""
        n_r, n_c, bot_rows, top_rows = self._window_lattices(window, stride)
        if n_r <= 0 or n_c <= 0:
            return self._empty_windows(n_r, n_c)
        self._check_region_bound(window[0] * window[1], "window")
        needed = np.unique(np.concatenate([bot_rows, top_rows[top_rows >= 0]]))
        self._warn_if_slabs_dominate(n_r, stride)
        R = self.rows(needed)
        out = self._windows_from_rows(R, needed, window, stride)
        if stats is not None:
            self._fill_stats(stats, R)
        return out

    def likelihood_map(
        self, target_hist, window, metric, stride: int = 1,
        *, stats: dict | None = None,
    ):
        hists = self.sliding_window_histograms(window, stride, stats=stats)
        return rq.score(hists, target_hist, metric)

    def multi_scale_search(
        self, target_hist, windows, metric, stride: int = 1
    ):
        """``region_query.multi_scale_search`` semantics — the union of all
        scales' corner-row lattices is fetched in ONE ``rows()`` pass, so a
        band-streamed source computes every scale from a single stream."""
        lattices = [self._window_lattices(wnd, stride) for wnd in windows]
        # Only scales that actually fit the frame query anything; larger
        # ones contribute an empty map (matching the dense path's skip),
        # so they must not trip the storage-policy bound either.
        live = [
            wh * ww for (wh, ww), (n_r, n_c, _, _) in zip(windows, lattices)
            if n_r > 0 and n_c > 0
        ]
        self._check_region_bound(max(live, default=0), "window")
        all_rows = [
            np.concatenate([bot, top[top >= 0]])
            for (n_r, n_c, bot, top) in lattices
            if n_r > 0 and n_c > 0
        ]
        needed = (
            np.unique(np.concatenate(all_rows))
            if all_rows else np.zeros((0,), np.int64)
        )
        R = self.rows(needed) if needed.size else None
        maps = []
        for wnd, (n_r, n_c, _, _) in zip(windows, lattices):
            if n_r <= 0 or n_c <= 0:
                hists = self._empty_windows(n_r, n_c)
            else:
                hists = self._windows_from_rows(R, needed, wnd, stride)
            maps.append(rq.score(hists, target_hist, metric))
        best_rect, best_score = rq.reduce_scale_maps(
            maps, windows, stride, self.lead
        )
        return best_rect, best_score, maps

    # -- stats / diagnostics -------------------------------------------------
    # (policy-backed sources — SpilledIH — carry a ``storage`` attribute;
    # it is only read when exact_region_bound is not None, i.e. by them.)

    def _warn_if_slabs_dominate(self, n_r: int, stride: int) -> None:
        """Streaming sources warn when the corner-row slabs are no smaller
        than the monolithic H they avoid (stride-1 sliding windows)."""

    def _fill_stats(self, stats: dict, R: np.ndarray) -> None:
        nlead = int(np.prod(self.lead, dtype=np.int64) or 1)
        stats.update(
            slab_bytes=2 * R.nbytes,
            full_h_bytes=4 * nlead * self.num_bins * self.height * self.width,
        )
        stats.setdefault("num_bands", 1)
        stats.setdefault("band_bytes", 0)
        stats["peak_bytes"] = stats["band_bytes"] + stats["slab_bytes"]


class DenseH(HSource):
    """A materialized (..., b, h, w) H — thin adapter over ``jax.Array``.

    Analytics delegate to the compiled dense programs of
    core/region_query.py (one program per query shape: regions, strided-
    slice window histograms, the score); ``rows()`` exists for protocol
    completeness and cross-representation tests."""

    def __init__(self, H):
        self.H = jnp.asarray(H)
        if self.H.ndim < 3:
            raise ValueError(f"DenseH wants (..., b, h, w), got {self.H.shape}")

    @property
    def num_bins(self) -> int:
        return self.H.shape[-3]

    @property
    def height(self) -> int:
        return self.H.shape[-2]

    @property
    def width(self) -> int:
        return self.H.shape[-1]

    @property
    def lead(self) -> tuple:
        return tuple(self.H.shape[:-3])

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.H.shape, dtype=np.int64)) \
            * self.H.dtype.itemsize

    def rows(self, row_ids) -> np.ndarray:
        return to_host(self.H[..., np.asarray(row_ids), :])

    def dense(self):
        return self.H

    def update_bands(self, next_frame, report, *, recompute,
                     apply_fn=None) -> "DenseH":
        """The incremental-video hook (core/delta.py): a new DenseH for
        ``next_frame``, recomputing only the report's dirty bands and
        carry-correcting the clean slabs below — bit-exact vs a full
        recompute."""
        from repro.core import delta as delta_mod

        return DenseH(delta_mod.update_dense_ih(
            self.H, next_frame, report,
            recompute=recompute, apply_fn=apply_fn,
        ))

    def region_histogram(self, rects) -> jnp.ndarray:
        return rq.region_histogram(self.H, jnp.asarray(rects))

    def sliding_window_histograms(
        self, window, stride: int = 1, *, stats: dict | None = None
    ) -> jnp.ndarray:
        return rq.sliding_window_histograms(self.H, window, stride,
                                            stats=stats)

    def multi_scale_search(self, target_hist, windows, metric,
                           stride: int = 1):
        return rq.multi_scale_search(self.H, target_hist, windows, metric,
                                     stride)


class BandedH(HSource):
    """An H held as a ``BandH`` stream (core/bands.py) — full H never
    materializes on device.

    ``bands`` is either an *iterable/iterator* of ``BandH`` (single-shot:
    a second query raises with a pointer to the factory form) or a
    zero-arg *callable* returning a fresh stream per query (replayable —
    what ``HistogramEngine`` builds).  ``rows()`` streams the bands once,
    keeping only the requested rows; each band is pulled to the host with
    ``np.asarray`` before any assembly (the jax-0.4.37 row-sharded
    concatenate hazard — bands from ``iter_banded_sharded_ih`` arrive
    device-sharded)."""

    def __init__(self, bands):
        self._factory = bands if callable(bands) else None
        self._tail = None if callable(bands) else iter(bands)
        self._meta = None
        self.last_stream_stats: dict = {}

    # -- stream management ---------------------------------------------------
    def _take_stream(self):
        # A stashed stream (from a meta peek) is used first; otherwise the
        # factory opens a fresh one, and a single-shot iterator that was
        # already taken has nothing left to give.
        if self._tail is not None:
            stream, self._tail = self._tail, None
        elif self._factory is not None:
            stream = self._factory()
        else:
            raise RuntimeError(
                "this BandedH wraps a single-shot band iterator that was "
                "already consumed; construct it with a zero-arg factory "
                "(e.g. BandedH(lambda: ih.map_bands(img, ...))) to run "
                "multiple queries"
            )
        first = next(stream)
        if self._meta is None:
            self._meta = (first.frame_h, first.H.shape)
        return itertools.chain([first], stream)

    def _peek_meta(self):
        if self._meta is None:
            # Hand the un-consumed stream back so the peek costs nothing:
            # the next query picks it up before asking the factory again.
            self._tail = self._take_stream()
        return self._meta

    # -- metadata ------------------------------------------------------------
    @property
    def num_bins(self) -> int:
        return self._peek_meta()[1][-3]

    @property
    def height(self) -> int:
        return self._peek_meta()[0]

    @property
    def width(self) -> int:
        return self._peek_meta()[1][-1]

    @property
    def lead(self) -> tuple:
        return tuple(self._peek_meta()[1][:-3])

    # -- protocol ------------------------------------------------------------
    def rows(self, row_ids) -> np.ndarray:
        row_ids = np.asarray(row_ids)
        out = None
        num_bands = 0
        peak_band = 0
        for band in self._take_stream():
            if out is None:
                out = np.zeros(
                    band.H.shape[:-2] + (len(row_ids), band.H.shape[-1]),
                    band.H.dtype,
                )
            num_bands = band.num_bands
            sel = (row_ids >= band.r0) & (row_ids < band.r1)
            # Host-side assembly: to_host pulls the (possibly sharded)
            # band off device before any indexing/concatenation happens.
            Hb = to_host(band.H)
            peak_band = max(peak_band, Hb.nbytes)
            if sel.any():
                out[..., sel, :] = Hb[..., row_ids[sel] - band.r0, :]
        self.last_stream_stats = {
            "num_bands": num_bands, "band_bytes": peak_band,
        }
        return out

    def dense(self):
        """Assemble full H host-side (np.concatenate over host bands —
        never ``jnp.concatenate`` over possibly-sharded device bands)."""
        return jnp.asarray(np.concatenate(
            [to_host(band.H) for band in self._take_stream()], axis=-2,
        ))

    def update_bands(self, next_frame, report, *, recompute,
                     apply_fn=None) -> "BandedH":
        """The incremental-video hook (core/delta.py): a new replayable
        BandedH whose stream replays this one's bands, recomputing dirty
        bands from ``next_frame`` and carry-correcting clean bands below.
        Only factory-backed (replayable) sources can be updated — a
        single-shot iterator has no stream left to replay."""
        from repro.core import delta as delta_mod

        if self._factory is None:
            raise RuntimeError(
                "cannot update a single-shot BandedH — only factory-"
                "backed (replayable) band streams support incremental "
                "updates; the engine falls back to a full recompute"
            )
        return BandedH(delta_mod.update_banded_factory(
            self._factory, next_frame, report,
            recompute=recompute, apply_fn=apply_fn,
        ))

    # -- stats / warnings ----------------------------------------------------
    def _warn_if_slabs_dominate(self, n_r: int, stride: int) -> None:
        nlead = int(np.prod(self.lead, dtype=np.int64) or 1)
        slab_bytes = 2 * 4 * nlead * self.num_bins * n_r * self.width
        full_bytes = 4 * nlead * self.num_bins * self.height * self.width
        if slab_bytes >= full_bytes:
            warnings.warn(
                f"banded sliding windows at stride {stride} need "
                f"{slab_bytes} B of corner-row slabs >= the {full_bytes} B "
                "monolithic H they avoid; increase the stride (slabs scale "
                "with 1/stride) or use the monolithic path for frames this "
                "size",
                stacklevel=4,
            )

    def _fill_stats(self, stats: dict, R: np.ndarray) -> None:
        stats.update(self.last_stream_stats)
        super()._fill_stats(stats, R)


class PrefetchedRowsH(HSource):
    """A view over corner rows already fetched from another source.

    ``HistogramEngine.run`` unions the rows every query of a request
    needs and fetches them in ONE ``rows()`` pass (one band stream for a
    banded plan, however many queries ride on it); this class then serves
    each query from that prefetched slab.  ``row_ids`` handed to
    ``rows()`` must be a subset of the prefetched set — anything else is
    a caller bug and raises."""

    def __init__(self, base: HSource, needed: np.ndarray, R: np.ndarray):
        self._base = base
        self._needed = np.asarray(needed)
        self._R = R

    @property
    def num_bins(self) -> int:
        return self._base.num_bins

    @property
    def height(self) -> int:
        return self._base.height

    @property
    def width(self) -> int:
        return self._base.width

    @property
    def lead(self) -> tuple:
        return self._base.lead

    @property
    def exact_region_bound(self) -> int | None:
        return self._base.exact_region_bound

    @property
    def storage(self) -> str:
        return getattr(self._base, "storage", "float32")

    def rows(self, row_ids) -> np.ndarray:
        row_ids = np.asarray(row_ids)
        idx = np.searchsorted(self._needed, row_ids)
        bad = (idx >= len(self._needed)) | (
            self._needed[np.minimum(idx, len(self._needed) - 1)] != row_ids
        ) if len(self._needed) else np.ones(row_ids.shape, bool)
        if row_ids.size and bad.any():
            raise MissingRowsError(
                f"rows {row_ids[bad].tolist()} were not prefetched; the "
                "engine's row-union must cover every query"
            )
        return self._R[..., idx, :]


class FusedRowsH(HSource):
    """The result of a query-fused dispatch: corner rows WITHOUT an H.

    A fused plan (``plan().representation == "fused"``) never builds the
    (n, b, h, w) integral histogram — ``kernels.ops.fused_corner_rows``
    emits exactly the rows the request's queries read (Eq. 2), and this
    source serves those queries from that slab.  Consequences the class
    enforces rather than papers over:

      * ``rows()`` outside the fused set raises :class:`MissingRowsError`
        — there is no H to go back to; the caller must re-run the engine
        with the larger request (``AnalyticsService`` does this on fused
        cache hits whose next request needs more rows);
      * ``dense()`` raises :class:`MissingRowsError` always: densifying
        is precisely what the plan promised not to do.

    ``nbytes`` is the whole footprint of the representation — the
    peak-memory proxy the fused tests assert stays << dense H.
    """

    def __init__(self, row_ids, R, *, height: int, width: int):
        self._row_ids = np.asarray(row_ids, np.int64).reshape(-1)
        self._R = np.asarray(R)
        if self._R.ndim < 3 or self._R.shape[-2] != self._row_ids.size:
            raise ValueError(
                f"R {self._R.shape} does not hold {self._row_ids.size} "
                "rows (want (..., b, k, w))"
            )
        self.height = height
        self.width = width

    @property
    def num_bins(self) -> int:
        return self._R.shape[-3]

    @property
    def lead(self) -> tuple:
        return tuple(self._R.shape[:-3])

    @property
    def row_ids(self) -> np.ndarray:
        return self._row_ids

    @property
    def nbytes(self) -> int:
        return self._R.nbytes

    def rows(self, row_ids) -> np.ndarray:
        row_ids = np.asarray(row_ids)
        idx = np.searchsorted(self._row_ids, row_ids)
        n = len(self._row_ids)
        bad = (
            (idx >= n) | (self._row_ids[np.minimum(idx, n - 1)] != row_ids)
            if n else np.ones(row_ids.shape, bool)
        )
        if row_ids.size and bad.any():
            raise MissingRowsError(
                f"rows {row_ids[bad].tolist()} were not part of the fused "
                "request; a fused plan computes only its declared corner "
                "rows — re-run the engine with the new queries"
            )
        return self._R[..., idx, :]

    def dense(self):
        raise MissingRowsError(
            "this H was query-fused: only the requested corner rows were "
            "ever computed and the dense (b, h, w) H does not exist; "
            "re-plan without query fusion to materialize it"
        )


@functools.lru_cache(maxsize=64)
def _rows_gather(mesh, kind, lead, bin_axis, row_axis, local_h):
    """Jitted (H, row_ids) -> slab gather for ShardedH.rows().

    Cached per (mesh, kind, geometry) with the row ids as a *dynamic*
    argument: every cached frame holds its own ShardedH, and serving
    traffic calls rows() once per request — rebuilding the shard_map
    per call would retrace and recompile every time (~seconds per query
    on a fake-device mesh), so the executable must outlive the source."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    if kind == "bin":
        fn = shard_map(
            lambda h_local, rid: jnp.take(h_local, rid, axis=-2),
            mesh=mesh,
            in_specs=(P(*([None] * lead), bin_axis, None, None), P(None)),
            out_specs=P(*([None] * lead), bin_axis, None, None),
            check_vma=False,
        )
        return jax.jit(fn)

    def shard_fn(h_local, rid):
        lo = lax.axis_index(row_axis) * local_h
        local = rid - lo
        own = (local >= 0) & (local < local_h)
        slab = jnp.take(
            h_local, jnp.clip(local, 0, local_h - 1), axis=-2
        )
        slab = jnp.where(own[:, None], slab, jnp.zeros((), slab.dtype))
        return lax.psum(slab, row_axis)

    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(*([None] * lead), None, row_axis, None), P(None)),
        out_specs=P(*([None] * lead), None, None, None),
        check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _region_sharded(mesh, h_lead, rects_ndim, bin_axis):
    """Jitted (H, rects) -> per-bin-shard region histograms (bin kind),
    read by ``region_query.regions_by_tiles``: no gather, so no copy of
    a chip's share of H."""
    import jax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    fn = shard_map(
        rq.regions_by_tiles,
        mesh=mesh,
        in_specs=(
            P(*([None] * h_lead), bin_axis, None, None), P(),
        ),
        out_specs=P(*([None] * (h_lead + rects_ndim - 1)), bin_axis),
        check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=16)
def _replicated(mesh):
    """Jitted copy of an array onto every device of ``mesh``: the gather
    is an all-gather inside one compiled program, over the chips' links
    (a ``device_put`` between two shardings is left to the runtime)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.jit(lambda x: x, out_shardings=NamedSharding(mesh, P()))


class ShardedH(HSource):
    """A mesh-sharded dense H (core/distributed.py).

    ``kind="bin"`` (the paper's multi-GPU scheme) answers every query on
    the devices: region queries through a shard_map, embarrassingly
    parallel over bins; window histograms, likelihood maps and the
    multi-scale search through the compiled dense programs of
    core/region_query.py applied to the global sharded array, where the
    compiler keeps each chip on its own bins; the one collective gathers
    the window field onto every chip for the score.  ``rows()`` gathers
    corner rows device-side for both kinds: bin shards index their
    (unsharded) row axis locally, row shards mask-select the rows they
    own and a ``psum`` assembles the slab — so the only readback is the
    (.., b, k, w) slab itself, never the whole H.  A device-side
    ``concatenate`` over shards would be the jax-0.4.37 hazard; the
    gather uses take/where/psum only."""

    def __init__(self, H, mesh, *, kind: str = "bin",
                 bin_axis: str = "model", row_axis: str = "data"):
        if kind not in ("bin", "spatial"):
            raise ValueError(f"unknown sharding kind {kind!r} (bin|spatial)")
        self.H = H
        self.mesh = mesh
        self.kind = kind
        self.bin_axis = bin_axis
        self.row_axis = row_axis

    @property
    def num_bins(self) -> int:
        return self.H.shape[-3]

    @property
    def height(self) -> int:
        return self.H.shape[-2]

    @property
    def width(self) -> int:
        return self.H.shape[-1]

    @property
    def lead(self) -> tuple:
        return tuple(self.H.shape[:-3])

    @property
    def nbytes(self) -> int:
        # The actual aggregate array footprint, like DenseH — the HSource
        # default re-derives a 4-byte-per-element planner estimate, which
        # mis-counts a sharded H the moment its dtype is not fp32.  The
        # service's byte-aware cache eviction (cache_bytes=) charges
        # sources by this number, so it must track the real storage.
        return int(np.prod(self.H.shape, dtype=np.int64)) * self.H.dtype.itemsize

    def rows(self, row_ids) -> np.ndarray:
        row_ids = np.asarray(row_ids)
        if row_ids.size == 0:
            return np.zeros(self.lead + (self.num_bins, 0, self.width),
                            self.H.dtype)
        if self.kind == "spatial" and self.height % self.mesh.shape[self.row_axis]:
            # Uneven row shards cannot compute local offsets statically;
            # fall back to the whole-H host pull (engine plans never
            # produce this — plan validation requires divisibility).
            return to_host(self.H)[..., row_ids, :]
        return self._rows_device(row_ids)

    def _rows_device(self, row_ids: np.ndarray) -> np.ndarray:
        """Device-side corner-row gather: select the k requested rows on
        the mesh and read back only the (.., b, k, w) slab — the
        sanctioned query-side sync, not the carry path.  No cross-shard
        concat happens: bin shards take rows locally (the row axis is
        unsharded within each shard), and row shards zero the rows they
        do not own and psum over the row axis."""
        lead = self.H.ndim - 3
        rid = jnp.asarray(row_ids, jnp.int32)
        local_h = (0 if self.kind == "bin"
                   else self.height // self.mesh.shape[self.row_axis])
        fn = _rows_gather(self.mesh, self.kind, lead,
                          self.bin_axis, self.row_axis, local_h)
        return to_host(fn(self.H, rid))

    def dense(self):
        return jnp.asarray(to_host(self.H))

    def region_histogram(self, rects) -> jnp.ndarray:
        if self.kind != "bin":
            return super().region_histogram(rects)
        rects = jnp.asarray(rects)
        h_lead = self.H.ndim - 3
        # Same executable-reuse story as _rows_gather: one cached jitted
        # shard_map per (mesh, geometry), rects as a dynamic argument.
        fn = _region_sharded(self.mesh, h_lead, rects.ndim, self.bin_axis)
        return fn(self.H, rects)

    def sliding_window_histograms(
        self, window, stride: int = 1, *, stats: dict | None = None
    ) -> jnp.ndarray:
        if self.kind != "bin":
            return super().sliding_window_histograms(window, stride,
                                                     stats=stats)
        return rq.sliding_window_histograms(self.H, window, stride,
                                            stats=stats)

    def likelihood_map(self, target_hist, window, metric, stride: int = 1,
                       *, stats: dict | None = None):
        if self.kind != "bin":
            return super().likelihood_map(target_hist, window, metric,
                                          stride, stats=stats)
        hists = self.sliding_window_histograms(window, stride, stats=stats)
        # Gathered onto every chip before the score: each then sums all
        # bins in the order one device does, so the map is bit-identical
        # to a DenseH's (a sum of per-chip partial sums would round
        # differently).
        return rq.score(_replicated(self.mesh)(hists), target_hist, metric)

    def multi_scale_search(self, target_hist, windows, metric,
                           stride: int = 1):
        if self.kind != "bin":
            return super().multi_scale_search(target_hist, windows, metric,
                                              stride)
        maps = [self.likelihood_map(target_hist, wnd, metric, stride)
                for wnd in windows]
        best_rect, best_score = rq.reduce_scale_maps(maps, windows, stride,
                                                     self.lead)
        return best_rect, best_score, maps


def as_hsource(H) -> HSource:
    """Coerce any representation to the protocol.

    Accepts an ``HSource`` (returned as-is), a dense (..., b, h, w) array,
    a ``BandH`` iterable/iterator, or a zero-arg band-stream factory."""
    if isinstance(H, HSource):
        return H
    if callable(H):
        return BandedH(H)
    if hasattr(H, "ndim") and hasattr(H, "shape"):
        return DenseH(H)
    if hasattr(H, "__iter__") or hasattr(H, "__next__"):
        return BandedH(H)
    raise TypeError(
        f"cannot interpret {type(H).__name__} as an integral-histogram "
        "source (want an HSource, a dense (..., b, h, w) array, or a "
        "BandH stream/factory)"
    )
