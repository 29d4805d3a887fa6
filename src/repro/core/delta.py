"""Dirty-band invalidation and incremental H updates for video streams.

The paper's target is real-time *video* analytics, and consecutive
frames from a fixed camera differ in a handful of rows.  Recomputing
the full integral histogram per frame throws that structure away; this
module exploits it — the compute-vs-reuse tradeoff of Ehsan et al.
(arXiv:1510.05142) applied across time.

The math rides the band-composition rule of core/bands.py: every
column of H is a prefix sum over rows, so for a band starting at r0

    H[r, c, b] = H_band[r - r0, c, b] + H[r0 - 1, c, b]

and editing frame rows inside a band changes H *below* the band only
through the band's bottom row.  The incremental walk over a band plan:

  * bands above the first dirty band are untouched (their inputs did
    not change and their carry-in chain is identical);
  * a dirty band is recomputed from the new frame rows with the
    re-threaded carry-in;
  * a clean band below a dirty one gets one broadcast correction,
    ``delta = new_bottom - old_bottom`` of the nearest dirty band
    above, added to every row (``kernels/ops.delta_apply``); its new
    bottom row is ``old_bottom + delta``, so consecutive clean bands
    reuse the same delta without any rescan.

All H arithmetic is integer-valued fp32, which the frame's element type
(core/hdtype.py) allows only below 2**24 pixels: the planner takes this
path only there, and plancheck refuses it past that.  So the updated H
is **bit-exact** against a monolithic recompute — asserted, not
approximated, in tests/test_delta.py.  The
integer spill policies update in the same modular arithmetic they
store in; their true-valued fp32 carry chain is retained on the
``SpilledIH`` (``carries``) precisely so the delta can be formed
without unwrapping stored bands.

``diff_bands`` is the detector (a cheap host-side per-row reduction);
``update_dense_ih`` / ``update_banded_factory`` / ``update_spilled_ih``
are the per-representation walks, reached through the sources'
``update_bands`` hooks; the planner decision (dirty fraction vs
threshold) lives in ``core/engine.plan``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bands import STORAGE_POLICIES, BandPlan

#: default dirty-row fraction above which an incremental update stops
#: paying (the planner's threshold; tunable per geometry through the
#: ``$REPRO_TUNED_CONFIGS`` priors key "delta_threshold").
DEFAULT_DIRTY_THRESHOLD = 0.35


@dataclasses.dataclass(frozen=True)
class DirtyReport:
    """Per-band dirtiness of one frame transition under one band plan.

    ``spans`` are the [r0, r1) row bands the update walks; ``dirty[i]``
    says band i's frame rows changed.  The *fraction* counts rows of
    dirty bands (what the update actually recomputes), not raw changed
    rows — it is the planner's cost input."""

    spans: tuple[tuple[int, int], ...]
    dirty: tuple[bool, ...]
    frame_h: int

    @property
    def dirty_rows(self) -> int:
        return sum(r1 - r0 for (r0, r1), d in zip(self.spans, self.dirty)
                   if d)

    @property
    def dirty_fraction(self) -> float:
        return self.dirty_rows / self.frame_h if self.frame_h else 0.0

    @property
    def num_dirty(self) -> int:
        return sum(self.dirty)

    @property
    def all_clean(self) -> bool:
        return not any(self.dirty)


def _spans_of(band_plan) -> tuple[tuple[int, int], ...]:
    spans = getattr(band_plan, "spans", band_plan)
    return tuple((int(r0), int(r1)) for r0, r1 in spans)


def diff_bands(prev_frame, next_frame, band_plan) -> DirtyReport:
    """Detect the dirty row bands between two frames (or frame stacks).

    A cheap host-side reduction: a row is dirty when any pixel of any
    frame in the stack differs; a band is dirty when any of its rows
    is.  ``band_plan`` is a :class:`~repro.core.bands.BandPlan` or a
    bare span sequence — the granularity the update will recompute at
    (a cached ``SpilledIH`` hands its own spans here).
    """
    prev = np.asarray(prev_frame)
    nxt = np.asarray(next_frame)
    if prev.shape != nxt.shape:
        raise ValueError(
            f"frame shapes differ: prev {prev.shape} vs next {nxt.shape}")
    if prev.ndim < 2:
        raise ValueError(f"expected (h, w) or (n, h, w), got {prev.shape}")
    spans = _spans_of(band_plan)
    h = prev.shape[-2]
    if not spans or spans[0][0] != 0 or spans[-1][1] != h or any(
            a1 != b0 for (_, a1), (b0, _) in zip(spans, spans[1:])):
        raise ValueError(
            f"band spans {spans[:4]}... do not tile [0, {h})")
    changed = prev != nxt
    axes = tuple(i for i in range(changed.ndim) if i != changed.ndim - 2)
    row_dirty = np.any(changed, axis=axes)
    dirty = tuple(bool(row_dirty[r0:r1].any()) for r0, r1 in spans)
    return DirtyReport(spans=spans, dirty=dirty, frame_h=h)


def _default_apply(slab, delta):
    """The jnp fallback of ``kernels/ops.delta_apply``: one fused add."""
    return slab + delta[..., None, :]


def _merged_runs(report: DirtyReport):
    """Coalesce consecutive equally-dirty spans into maximal runs.

    The dense walk has no per-band storage to respect, so one recompute
    dispatch covers a whole dirty run and one broadcast apply covers a
    whole clean run — detection granularity (fine, to localise the
    change) decouples from dispatch granularity (coarse, to amortise
    per-op overhead).  The banded/spilled walks keep per-band steps:
    their storage IS the band structure.
    """
    runs: list[list] = []
    for (r0, r1), d in zip(report.spans, report.dirty):
        if runs and runs[-1][2] == d:
            runs[-1][1] = r1
        else:
            runs.append([r0, r1, d])
    return [(r0, r1, d) for r0, r1, d in runs]


@jax.jit
def _assemble_dense(H, slabs, starts, stops, delta_steps):
    """ONE fused dispatch repairing a dense H from recomputed dirty-run
    slabs: broadcast the carry-correction steps below each dirty run,
    then splice the slabs in.  Row boundaries are traced scalars, so a
    moving dirty region re-uses the compiled executable (recompiles only
    when the run count or a slab height changes).

    ``delta_steps[i]`` is D_i - D_{i-1} (D_i = run i's new bottom minus
    its old bottom): clean rows between dirty runs i and i+1 accumulate
    exactly D_i, and dirty rows — corrupted by every step mask crossing
    them — are overwritten by their slab afterwards.
    """
    rows = jnp.arange(H.shape[-2])
    out = H
    for r1, step in zip(stops, delta_steps):
        below = (rows >= r1).astype(H.dtype)
        out = out + below[:, None] * step[..., None, :]
    for slab, r0 in zip(slabs, starts):
        out = jax.lax.dynamic_update_slice(
            out, slab.astype(out.dtype),
            (0,) * (out.ndim - 2) + (r0, 0))
    return out


def update_dense_ih(
    H,
    next_frame,
    report: DirtyReport,
    *,
    recompute: Callable,
    apply_fn: Callable | None = None,
):
    """Repair a dense (..., b, h, w) H for ``next_frame``.

    ``recompute(band_rows, carry_in) -> H_band`` runs the real kernel
    dispatch (the engine builds it from its plan's kernel kwargs);
    ``apply_fn(slab, delta) -> slab`` applies the broadcast correction.
    With ``apply_fn=None`` the whole repair — correction broadcasts plus
    slab splices — is ONE fused jit dispatch (``_assemble_dense``); an
    explicit ``apply_fn`` (the engine passes ``ops.delta_apply`` for
    Pallas plans) takes the per-run walk so the kernel does the adds.
    Returns the new dense H, bit-exact vs a full recompute either way.
    """
    H = jnp.asarray(H)
    if apply_fn is None:
        slabs, starts, stops, steps = [], [], [], []
        D_prev = None          # cumulative carry delta of dirty runs above
        for r0, r1, is_dirty in _merged_runs(report):
            if not is_dirty:
                continue
            carry = None
            if r0 > 0:
                carry = H[..., r0 - 1, :]
                if D_prev is not None:
                    carry = carry + D_prev
            slab = recompute(next_frame[..., r0:r1, :], carry)
            D = slab[..., slab.shape[-2] - 1, :] - H[..., r1 - 1, :]
            steps.append(D if D_prev is None else D - D_prev)
            slabs.append(slab)
            starts.append(r0)
            stops.append(r1)
            D_prev = D
        if not slabs:
            return H
        return _assemble_dense(H, slabs, starts, stops, steps)

    pieces = []           # per-run slabs, reassembled in ONE copy
    new_carry = None      # bottom row of the run above, updated values
    delta = None          # correction for clean runs below a dirty one
    for r0, r1, is_dirty in _merged_runs(report):
        old_bottom = H[..., r1 - 1, :]
        if is_dirty:
            slab = recompute(next_frame[..., r0:r1, :], new_carry)
            new_carry = slab[..., slab.shape[-2] - 1, :]
            delta = new_carry - old_bottom
        elif delta is None:
            new_carry = old_bottom          # untouched prefix of the frame
            slab = H[..., r0:r1, :]
        else:
            slab = apply_fn(H[..., r0:r1, :], delta)
            new_carry = old_bottom + delta
        if slab.dtype != H.dtype:
            slab = slab.astype(H.dtype)
        pieces.append(slab)
    if len(pieces) == 1:
        return pieces[0]
    return jnp.concatenate(pieces, axis=-2)


def update_banded_factory(
    factory: Callable,
    next_frame,
    report: DirtyReport,
    *,
    recompute: Callable,
    apply_fn: Callable | None = None,
) -> Callable:
    """Lift a replayable band-stream factory to the next frame.

    Returns a new zero-arg factory whose stream replays ``factory``'s
    bands, recomputing dirty ones from ``next_frame`` with the
    re-threaded carry and correcting clean ones below with the carry
    delta — each yielded ``BandH`` is exactly what a fresh banded
    compute of ``next_frame`` would yield, band for band.
    """
    if apply_fn is None:
        apply_fn = _default_apply

    def replay():
        new_carry = None
        delta = None
        for band in factory():
            i = band.index
            if i >= len(report.spans) or \
                    report.spans[i] != (band.r0, band.r1):
                raise ValueError(
                    f"band {i} spans [{band.r0}, {band.r1}) but the dirty "
                    f"report was built for "
                    f"{report.spans[i] if i < len(report.spans) else None} "
                    "— detection and update must share one band plan")
            if report.dirty[i]:
                Hb = recompute(next_frame[..., band.r0:band.r1, :],
                               new_carry)
                new_carry = Hb[..., Hb.shape[-2] - 1, :]
                delta = new_carry - band.carry
                yield dataclasses.replace(band, H=Hb, carry=new_carry)
            elif delta is None:
                new_carry = band.carry
                yield band
            else:
                new_carry = band.carry + delta
                yield dataclasses.replace(
                    band, H=apply_fn(band.H, delta), carry=new_carry)

    return replay


def _store(arr: np.ndarray, dtype) -> np.ndarray:
    """The spill cast of core/bands.spill_banded_ih: fp32 exact counts
    to the policy dtype, modular for the integer widths."""
    if dtype is np.float32:
        return arr.astype(np.float32)
    arr = np.mod(arr.astype(np.int64), np.int64(np.iinfo(dtype).max) + 1)
    return arr.astype(dtype)


def update_spilled_ih(src, next_frame, report: DirtyReport, *,
                      recompute: Callable):
    """Repair a host-spilled H (``core/bands.SpilledIH``) in its own
    storage policy.

    Dirty bands are recomputed in fp32 (true counts) and re-spilled
    through the policy cast; clean bands below take the delta in int64
    modular arithmetic, so wrapped uint16/uint32 values stay exactly
    what a fresh spill of the new frame would store.  The retained
    true-valued ``carries`` chain both supplies the old bottoms the
    delta needs and is updated alongside — a further update can chain
    off the result.
    """
    if src.carries is None:
        raise ValueError(
            "this SpilledIH predates carry retention (no `carries`); "
            "re-spill the frame before updating incrementally")
    if tuple(src.spans) != report.spans:
        raise ValueError(
            f"spill spans {tuple(src.spans)[:4]}... do not match the "
            f"dirty report's {report.spans[:4]}... — detection must run "
            "on the source's own band plan")
    dtype, _ = STORAGE_POLICIES[src.storage]
    bands_new, carries_new = [], []
    new_carry = None
    delta = None
    for i, ((r0, r1), is_dirty) in enumerate(zip(report.spans,
                                                 report.dirty)):
        if is_dirty:
            Hb = recompute(next_frame[..., r0:r1, :], new_carry)
            arr = np.asarray(Hb).astype(np.float32)
            bottom = arr[..., -1, :]
            delta = bottom - src.carries[i]
            bands_new.append(_store(arr, dtype))
            carries_new.append(bottom)
            new_carry = bottom
        elif delta is None:
            bands_new.append(src.bands[i])
            carries_new.append(src.carries[i])
            new_carry = src.carries[i]
        else:
            if dtype is np.float32:
                bands_new.append(src.bands[i] + delta[..., None, :])
            else:
                # Deltas are exact integers in fp32; add them in the
                # policy's modular ring so wrapped values stay aligned
                # with what a fresh spill would store.
                mod = np.int64(np.iinfo(dtype).max) + 1
                stepped = src.bands[i].astype(np.int64) \
                    + np.rint(delta[..., None, :]).astype(np.int64)
                bands_new.append(np.mod(stepped, mod).astype(dtype))
            carry = src.carries[i] + delta
            carries_new.append(carry)
            new_carry = carry
    return dataclasses.replace(src, bands=bands_new, carries=carries_new)
