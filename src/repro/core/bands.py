"""Band-streamed integral histograms under a host memory budget.

The paper's headline scale scenario (§4.6) is a 64 MB frame at 128 bins
whose integral histogram is 32 GB — far beyond one device's memory.
``spatial_sharded_ih`` reaches that regime by sharding rows across a mesh;
this module reaches it on ONE host by streaming row bands through the
carry-aware kernels — the band/strip decomposition with boundary carries
and reduced-width accumulator storage of Ehsan et al. (arXiv:1510.05138,
arXiv:1510.05142), i.e. the WF-TiS column carry lifted from VMEM scratch
to a host-orchestrated (b, w) aggregate between bands.

The composition rule: an integral histogram is a prefix sum over rows, so
for a band starting at row r0,

    H[r, c, b] = H_band[r - r0, c, b] + H[r0 - 1, c, b]

The whole cross-band dependency is one (..., b, w) bottom-row carry.  All
arithmetic is integer-valued fp32 (exact below 2**24 counts), so banded
results are bit-exact vs the monolithic computation — asserted, not
approximated, in tests/test_bands.py.

Three consumption modes, none of which materializes the (b, h, w) H:

  * stream — ``iter_banded_ih`` yields ``BandH`` chunks to a consumer
    (the banded O(1) queries in core/region_query.py consume these);
  * spill  — ``spill_banded_ih`` stores bands host-side under a storage
    policy.  ``float32`` keeps counts exact below 2**24; the reduced-width
    integer policies wrap modularly (``uint16`` halves the footprint and
    any four-corner query over a region of <= 65535 pixels stays exact
    despite the wraparound — the embedded-systems accumulator trick of
    arXiv:1510.05142; validated at query time);
  * reduce — ``reduce_banded_ih`` folds bands into an accumulator while
    only ever holding one band.

``plan_bands`` turns ``memory_budget_bytes`` into band spans;
``kernels/ops.integral_histogram(memory_budget_bytes=...)`` uses the same
plan to bound its transient working set while still assembling full H.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Iterable, Iterator

import jax.numpy as jnp
import numpy as np

from repro.core.hsource import HSource
from repro.kernels.ops import integral_histogram

# fp32 represents consecutive integers exactly only below 2**24; beyond it
# the accumulated counts themselves (not just a storage cast) are wrong.
FP32_EXACT_COUNT = 1 << 24

# Storage policies for spilled bands: numpy dtype + the largest region
# pixel count a four-corner query is guaranteed exact for.  Integer
# policies wrap modulo 2**bits, and modular arithmetic cancels the wrap
# for any query whose true count fits — so the bound is on the *queried
# region*, not the frame.
STORAGE_POLICIES = {
    "float32": (np.float32, FP32_EXACT_COUNT - 1),
    "uint32": (np.uint32, (1 << 32) - 1),
    "uint16": (np.uint16, (1 << 16) - 1),
}


def validate_storage_policy(storage: str, h: int, w: int) -> None:
    """Validate a spill policy against the count bound of an (h, w) frame.

    The kernels accumulate in fp32, so any frame whose total pixel count
    reaches 2**24 has inexact counts before storage even starts — no
    policy can recover that; shard spatially (core/distributed.py)
    instead.  ``uint16``'s additional <= 65535-pixel *region* bound is
    enforced at query time (``SpilledIH.region_histogram``).
    """
    if storage not in STORAGE_POLICIES:
        raise ValueError(
            f"unknown storage policy {storage!r} "
            f"(valid: {sorted(STORAGE_POLICIES)})"
        )
    if h * w >= FP32_EXACT_COUNT:
        raise ValueError(
            f"{h}x{w} frame accumulates counts up to {h * w}, beyond the "
            f"fp32 exact-integer range 2**24; no storage policy recovers "
            "exactness — use spatial sharding (core/distributed.py)"
        )


@dataclasses.dataclass(frozen=True)
class BandPlan:
    """Row-band decomposition of an (h, w) frame under a memory budget."""

    spans: tuple[tuple[int, int], ...]  # [r0, r1) per band
    band_h: int                         # nominal rows per band
    band_bytes: int                     # largest band's H footprint
    full_h_bytes: int                   # the monolithic (n, b, h, w) H

    @property
    def num_bands(self) -> int:
        return len(self.spans)


def plan_bands(
    h: int,
    w: int,
    num_bins: int,
    *,
    band_h: int | None = None,
    memory_budget_bytes: int | None = None,
    num_frames: int = 1,
    itemsize: int = 4,
    row_multiple: int = 1,
) -> BandPlan:
    """Choose band spans from an explicit ``band_h`` or a byte budget.

    The budget caps the per-band H footprint
    ``itemsize * num_frames * num_bins * band_h * w``; ``row_multiple``
    rounds the band height down to a multiple (the spatially-sharded
    composition needs bands divisible by the row-shard count).
    """
    if band_h is None:
        if memory_budget_bytes is None:
            band_h = h
        else:
            per_row = itemsize * num_frames * num_bins * w
            band_h = memory_budget_bytes // per_row
            if band_h < max(1, row_multiple):
                raise ValueError(
                    f"memory_budget_bytes={memory_budget_bytes} below one "
                    f"{max(1, row_multiple)}-row band "
                    f"({per_row * max(1, row_multiple)} bytes at "
                    f"{num_frames}x{num_bins} bins x width {w})"
                )
    band_h = min(int(band_h), h)
    if row_multiple > 1:
        band_h -= band_h % row_multiple
    if band_h < 1:
        raise ValueError(f"band_h must be >= 1, got {band_h}")
    spans = tuple((r, min(r + band_h, h)) for r in range(0, h, band_h))
    per_row = itemsize * num_frames * num_bins * w
    return BandPlan(
        spans=spans,
        band_h=band_h,
        band_bytes=per_row * band_h,
        full_h_bytes=per_row * h,
    )


@dataclasses.dataclass(frozen=True)
class BandH:
    """One streamed band of an integral histogram.

    ``H`` holds the full-frame H restricted to rows [r0, r1): shape
    (..., b, r1 - r0, w).  ``carry`` is its bottom row (..., b, w) — the
    only state the next band needs.  ``frame_h`` is the full frame height
    so consumers can size window lattices without exhausting the iterator.
    """

    index: int
    num_bands: int
    r0: int
    r1: int
    frame_h: int
    H: jnp.ndarray
    carry: jnp.ndarray

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.H.shape)) * self.H.dtype.itemsize


def iter_banded_ih(
    image,
    num_bins: int,
    *,
    band_h: int | None = None,
    memory_budget_bytes: int | None = None,
    plan: BandPlan | None = None,
    carry_in: jnp.ndarray | None = None,
    compute_fn: Callable | None = None,
    prefetch: int = 0,
    device=None,
    method: str = "wf_tis",
    backend: str = "auto",
    tile: int = 128,
    bin_block: int = 8,
    use_mxu: bool = True,
    interpret: bool = False,
    value_range: int = 256,
) -> Iterator[BandH]:
    """Stream the integral histogram of ``image`` as row bands.

    ``image`` is (h, w) or (n, h, w) (numpy or jax; large frames stay on
    the host and only band slices are staged).  Bands follow ``plan`` or
    are planned from ``band_h`` / ``memory_budget_bytes``; the carry is
    threaded through the carry-aware kernels between dispatches.

    ``compute_fn(band_image, carry_in) -> H_band`` overrides the kernel
    call — core/distributed.py uses this to run every band bin- or
    spatially-sharded with the same carry chain.  ``prefetch >= 1`` keeps
    that many band image slices staged on device ahead of the one
    computing (the §4.4 overlap applied inside one large frame).
    ``device`` is any staging placement (``Device`` or ``Sharding``);
    when given, slices are staged even at ``prefetch=0`` — a
    ``NamedSharding`` commits each slice to the layout a sharded
    compute_fn's shard_map consumes.

    The loop itself is ``runtime.FrameRuntime`` with the (b, w)
    bottom-row carry threaded between dispatches; this function only
    shapes each retired dispatch into a ``BandH``.
    """
    from repro.core.runtime import FrameRuntime

    h, w = image.shape[-2:]
    num_frames = int(np.prod(image.shape[:-2], dtype=np.int64)) or 1
    if plan is None:
        plan = plan_bands(
            h, w, num_bins,
            band_h=band_h, memory_budget_bytes=memory_budget_bytes,
            num_frames=num_frames,
        )
    if compute_fn is None:
        def compute_fn(band_img, carry):
            return integral_histogram(
                band_img, num_bins, method=method, backend=backend,
                tile=tile, bin_block=bin_block, use_mxu=use_mxu,
                interpret=interpret, value_range=value_range,
                carry_in=carry,
            )

    def step(band_img, carry):
        H_band = compute_fn(band_img, carry)
        return H_band, H_band[..., H_band.shape[-2] - 1, :]

    # Band slices are staged whenever a placement is known or prefetch is
    # requested.  ``device`` may be a single ``Device`` or a ``Sharding``:
    # a sharded compute_fn (iter_banded_sharded_ih) passes the
    # ``NamedSharding`` its shard_map expects, so slices arrive already
    # committed to the mesh layout instead of bouncing through one device
    # — the old "stage only when prefetch >= 1" carve-out is gone.
    runtime = FrameRuntime(
        step, depth=1, carry_in=carry_in, device=device,
        stage_inputs=prefetch >= 1 or device is not None,
        stage_ahead=max(prefetch, 0),
        block=False,
    )
    slices: Iterable = (image[..., r0:r1, :] for r0, r1 in plan.spans)
    for d in runtime.run(slices, batched=False,
                         meta=lambda i, c, ch: plan.spans[i]):
        r0, r1 = d.meta
        yield BandH(
            index=d.index, num_bands=plan.num_bands, r0=r0, r1=r1,
            frame_h=h, H=d.out, carry=d.carry,
        )


def banded_integral_histogram(image, num_bins: int, **kwargs) -> jnp.ndarray:
    """Assemble full H from the band stream (parity oracle + the target of
    ``integral_histogram(memory_budget_bytes=...)``'s auto-banding: the
    result still materializes, but the per-dispatch working set — one-hot
    masks, transposes, scan intermediates — is bounded to a band).

    Assembly is host-side (each band pulled with ``np.asarray``, then one
    ``np.concatenate``): under jax 0.4.37 a device-side concat over bands
    whose donors live on different devices silently mis-assembles (the
    hazard core/hsource.py:28 documents and the sharded-concat lint rule
    enforces)."""
    pieces = [
        np.asarray(band.H)
        for band in iter_banded_ih(image, num_bins, **kwargs)
    ]
    return jnp.asarray(np.concatenate(pieces, axis=-2))


def reduce_banded_ih(image, num_bins: int, reduce_fn, init=None, **kwargs):
    """Fold ``reduce_fn(acc, band)`` over the band stream — O(band) memory."""
    acc = init
    for band in iter_banded_ih(image, num_bins, **kwargs):
        acc = reduce_fn(acc, band)
    return acc


@dataclasses.dataclass
class SpilledIH(HSource):
    """A banded integral histogram spilled host-side under a storage policy.

    ``bands[i]`` holds rows ``spans[i]`` as (..., b, bh, w) in the policy
    dtype.  Integer policies store H modulo 2**bits; four-corner queries
    run in the same modular arithmetic, so any region whose true count
    fits the dtype reads back exactly (``uint16``: <= 65535 pixels).

    An ``HSource`` (core/hsource.py): every unified analytics entry point
    — region queries, sliding windows, likelihood maps, multi-scale
    search — runs straight off the spill through ``rows()``, with the
    policy's exact-count bound enforced per query.
    """

    num_bins: int
    height: int
    width: int
    lead: tuple
    storage: str
    spans: tuple[tuple[int, int], ...]
    bands: list
    # Per-band true-valued fp32 bottom rows (..., b, w) — the carry
    # chain the incremental video path (core/delta.py) needs: integer
    # policies store H modularly, so the real carries cannot be
    # recovered from ``bands`` and are retained at spill time instead.
    # ``None`` on spills predating carry retention (not updatable).
    carries: list | None = None

    @property
    def nbytes(self) -> int:
        total = sum(b.nbytes for b in self.bands)
        if self.carries is not None:
            total += sum(c.nbytes for c in self.carries)
        return total

    @property
    def exact_region_bound(self) -> int:
        return STORAGE_POLICIES[self.storage][1]

    def _band_of(self, r: int) -> int:
        for i, (r0, r1) in enumerate(self.spans):
            if r0 <= r < r1:
                return i
        raise IndexError(f"row {r} outside frame of height {self.height}")

    def rows(self, row_ids) -> np.ndarray:
        """Gather full-frame H rows (..., b, len(row_ids), w), policy dtype."""
        dtype, _ = STORAGE_POLICIES[self.storage]
        out = np.empty(
            self.lead + (self.num_bins, len(row_ids), self.width), dtype
        )
        for k, r in enumerate(row_ids):
            i = self._band_of(int(r))
            out[..., k, :] = self.bands[i][..., int(r) - self.spans[i][0], :]
        return out

    # region_histogram / sliding windows / likelihood maps are inherited
    # from HSource: Eq. 2 against rows(), area-validated per query against
    # exact_region_bound, modular through the integer policies.

    def assemble(self) -> np.ndarray:
        """Materialize full (..., b, h, w) H as fp32 (small frames only)."""
        return np.concatenate(
            [b.astype(np.float32) for b in self.bands], axis=-2
        )

    def dense(self):
        return jnp.asarray(self.assemble())

    def update_bands(self, next_frame, report, *, recompute,
                     apply_fn=None) -> "SpilledIH":
        """The incremental-video hook (core/delta.py): a new SpilledIH
        for ``next_frame`` in the same storage policy — dirty bands
        recomputed and re-spilled, clean bands below corrected in the
        policy's own modular arithmetic (``apply_fn`` is accepted for
        hook-signature uniformity; the spill update is host-side)."""
        from repro.core import delta as delta_mod

        del apply_fn
        return delta_mod.update_spilled_ih(
            self, next_frame, report, recompute=recompute,
        )


def spill_banded_ih(
    image, num_bins: int, *, storage: str = "float32", **kwargs
) -> SpilledIH:
    """Compute the banded H and spill every band host-side under
    ``storage`` (validated against the count bound up front)."""
    h, w = image.shape[-2:]
    validate_storage_policy(storage, h, w)
    dtype, _ = STORAGE_POLICIES[storage]
    spans, bands, carries = [], [], []
    for band in iter_banded_ih(image, num_bins, **kwargs):
        arr = np.asarray(band.H)
        # The true-valued bottom row, BEFORE any storage cast — the
        # carry chain the incremental update path (core/delta.py)
        # threads through clean bands.
        carries.append(arr[..., -1, :].astype(np.float32))
        if dtype is not np.float32:
            # Counts are exact integers in fp32 here (validated above);
            # reduce the width by an explicit modular cast.
            arr = np.mod(arr.astype(np.int64), np.int64(np.iinfo(dtype).max) + 1)
            arr = arr.astype(dtype)
        else:
            arr = arr.astype(np.float32)
        spans.append((band.r0, band.r1))
        bands.append(arr)
    return SpilledIH(
        num_bins=num_bins, height=h, width=w,
        lead=tuple(image.shape[:-2]), storage=storage,
        spans=tuple(spans), bands=bands, carries=carries,
    )




