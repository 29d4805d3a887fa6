"""Query-fused WF-TiS: emit ONLY the requested corner rows — H never
exists in HBM.

Eq. 2 answers every region/window query from corner *rows* of the
integral histogram, and Ehsan et al.'s embedded integral-image work
(arXiv:1510.05138, 1510.05142) makes the compute-vs-store decision
explicit: when the rows a request reads are small relative to H, storing
H at all is waste.  This kernel is the compute side of that decision —
the limit case of the paper's §4.6 memory-budget problem, where the
budget drops to the corner-row slab itself.

The scan is ``wf_tis.py``'s raster walk unchanged: grid
``(f, ih, iw, bb)`` bins innermost, row/column carries in VMEM scratch,
the band carry-in seeding the column scan at ``ih == 0``.  The one
change is the output stage.  Each tile's post-scan block ``vs`` already
IS the final H restricted to the tile (every dependency is an earlier
raster step), so instead of writing ``vs`` to an (n, b, h, w) output,
the kernel projects out the requested rows with a one-hot selection
matmul:

    sel[j, o] = 1  iff  slot j of this strip requests tile row o
    out[b, j, :] = sum_o sel[j, o] * vs[b, o, :]        (MXU, like the
                                                         scan matmuls)

``slots`` is a host-built (nth, kp) int32 table: for each tile-row
strip, the in-strip offsets of its requested rows, padded with -1
(matches no row, contributes zeros).  ``kp`` — the emission width — is
the max rows any strip requests, padded to a sublane multiple of 8.
The kernel reads it expanded to the one-hot ``sel`` operand
``(nth, kp, tile)``: its ``(kp, tile)`` block obeys the TPU's 8x128
block rule, which a ``(1, kp)`` row of the int32 table does not.
The output is ``(n, nb_pad, nth * kp, w_pad)``: one kp-row slab per
strip, written exactly once per grid step (the coverage discipline the
dense kernel has), gathered back to request order on the host by the
``pos`` indices ``slot_plan`` returns.

HBM traffic drops from (1/b read + 1 write of b*h*w) to
(1/b read + kp/tile write); peak device memory for the result is the
corner-row slab, not H.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

try:  # TPU-specific pallas helpers; interpret mode works without a TPU.
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

from repro.kernels.specs import (
    FusedRowsGeometry,
    KernelGeometry,
    KernelSpec,
    Operand,
    Scratch,
)
from repro.kernels.wf_tis import _col_scan_mxu, _left_matmul, _row_scan_mxu

#: default emission width when a geometry declares none (the
#: ``--check-kernels`` sweep runs plain KernelGeometry through here).
DEFAULT_KP = 8

#: fp32 sublane multiple the emission width is padded to.
_SUBLANE = 8


def slot_plan(row_ids, tile: int, height: int):
    """Host-side layout of requested rows onto per-strip emission slots.

    Args:
      row_ids: sorted unique frame rows in ``[0, height)``.
      tile: strip height (the kernel's tile size).
      height: logical frame height (pre-padding).

    Returns:
      ``(slots, kp, pos)`` — ``slots`` is the (nth, kp) int32 table of
      in-strip row offsets (-1 = empty slot), ``kp`` the padded emission
      width, and ``pos`` the (K,) indices into the flattened
      ``nth * kp`` output axis that recover the rows in request order.
    """
    # analysis: allow-host-sync(row ids are host-side request metadata, never device data)
    rows = np.asarray(row_ids, np.int64)
    if rows.size and (np.any(np.diff(rows) <= 0) or rows[0] < 0
                      or rows[-1] >= height):
        raise ValueError(
            f"row_ids must be sorted unique within [0, {height}), got "
            f"{rows.tolist()[:8]}...")
    nth = -(-height // tile)
    strips = rows // tile
    per_strip = np.bincount(strips, minlength=nth) if rows.size else \
        np.zeros(nth, np.int64)
    kp = max(int(per_strip.max(initial=0)), 1)
    kp = -(-kp // _SUBLANE) * _SUBLANE
    slots = np.full((nth, kp), -1, np.int32)
    pos = np.empty(rows.size, np.int64)
    fill = np.zeros(nth, np.int64)
    for i, (s, r) in enumerate(zip(strips, rows)):
        j = fill[s]
        slots[s, j] = r % tile
        pos[i] = s * kp + j
        fill[s] += 1
    return slots, kp, pos


def kernel_specs(geom: KernelGeometry) -> tuple[KernelSpec, ...]:
    """The declarative contract of ``fused_rows_pallas``'s one
    ``pallas_call`` (verified by ``repro.analysis.kernelcheck``; the
    conformance test in tests/test_fused.py pins it against the live
    call).

    The grid and carry edges are ``wf_tis.kernel_specs`` verbatim — the
    scan is the same wavefront.  What changes is the out-spec: block
    ``(1, bin_block, kp, tile)`` at index ``(f, bb, ih, iw)`` into the
    ``(n, nb_pad, nth * kp, w_pad)`` row-slab output (exactly-once
    coverage, like the dense kernel), plus the per-strip one-hot ``sel``
    table as a third input broadcast over ``iw``/``bb``.
    """
    kp = getattr(geom, "kp", DEFAULT_KP)
    n, nth, ntw, nbb = geom.n, geom.nth, geom.ntw, geom.nbb
    t, bb_blk = geom.tile, geom.bin_block
    hp, wp, nbp = geom.h_pad, geom.w_pad, geom.nb_pad

    def reads(g):
        edges = []
        if g["iw"] > 0:     # row carry from the tile to the left
            edges.append(
                (("row", g["bb"]), {**g, "iw": g["iw"] - 1}))
        if g["ih"] > 0:     # column carry from the strip above
            edges.append(
                (("col", g["bb"], g["iw"]), {**g, "ih": g["ih"] - 1}))
        return edges

    def writes(g):
        return [("row", g["bb"]), ("col", g["bb"], g["iw"])]

    return (
        KernelSpec(
            name="fused_rows",
            grid=(("f", n), ("ih", nth), ("iw", ntw), ("bb", nbb)),
            in_specs=(
                Operand("idx", (n, hp, wp), (1, t, t),
                        lambda f, ih, iw, bb: (f, ih, iw), dtype="int32"),
                Operand("carry", (n, nbp, wp), (1, bb_blk, t),
                        lambda f, ih, iw, bb: (f, bb, iw)),
                Operand("sel", (nth, kp, t), (1, kp, t),
                        lambda f, ih, iw, bb: (ih, 0, 0)),
            ),
            out_specs=(
                Operand("rows", (n, nbp, nth * kp, wp), (1, bb_blk, kp, t),
                        lambda f, ih, iw, bb: (f, bb, ih, iw)),
            ),
            scratch=(
                Scratch("row_carry", (nbb, bb_blk, t)),
                Scratch("col_carry", (nbb, bb_blk, wp)),
            ),
            carry_reads=reads,
            carry_writes=writes,
        ),
    )


def _fused_rows_kernel(
    idx_ref,      # (1, TH, TW) int32 bin indices (PAD_BIN outside the image)
    carry_ref,    # (1, BIN_BLOCK, TW) fp32 band carry-in (zeros = frame top)
    sel_ref,      # (1, KP, TH) fp32 one-hot: slot j emits tile row o
    out_ref,      # (1, BIN_BLOCK, KP, TW) fp32 emitted corner rows
    row_carry,    # VMEM scratch (NBB, BIN_BLOCK, TH) — right-edge carries
    col_carry,    # VMEM scratch (NBB, BIN_BLOCK, W_PAD) — bottom-edge carries
    *,
    bin_block: int,
    tile_w: int,
    use_mxu: bool,
):
    ih = pl.program_id(1)
    iw = pl.program_id(2)
    bb = pl.program_id(3)

    idx = idx_ref[0]
    th, tw = idx.shape

    # ---- the WF-TiS scan, unchanged from kernels/wf_tis.py ----
    bin_ids = bb * bin_block + jax.lax.broadcasted_iota(
        jnp.int32, (bin_block, th, tw), 0
    )
    mask = (idx[None, :, :] == bin_ids).astype(jnp.float32)

    if use_mxu:
        hs = _row_scan_mxu(mask)
    else:
        hs = jnp.cumsum(mask, axis=2)
    rc = jnp.where(iw == 0, 0.0, row_carry[bb])            # (BIN_BLOCK, TH)
    hs = hs + rc[:, :, None]
    row_carry[bb] = hs[:, :, tw - 1]

    if use_mxu:
        vs = _col_scan_mxu(hs)
    else:
        vs = jnp.cumsum(hs, axis=1)
    cols = pl.dslice(iw * tile_w, tile_w)
    cc = jnp.where(ih == 0, carry_ref[0], col_carry[bb, :, cols])
    vs = vs + cc[:, None, :]
    col_carry[bb, :, cols] = vs[:, th - 1, :]

    # ---- the fused output stage: project the requested rows ----
    # vs is the final H on this tile (all dependencies are earlier raster
    # steps), so the strip's requested rows can be emitted right now.
    sel = sel_ref[0]                                       # (KP, TH)
    # out[b, j, :] = sum_o sel[j, o] * vs[b, o, :]: the one-hot row gather
    # as a 0/1 matmul (dynamic sublane gathers are not a TPU primitive).
    if use_mxu:
        out_ref[0] = _left_matmul(sel, vs)
    else:
        out_ref[0] = jnp.sum(
            sel[None, :, :, None] * vs[:, None, :, :], axis=2
        )


def fused_rows_pallas(
    idx: jnp.ndarray,
    num_bins: int,
    slots: jnp.ndarray,
    *,
    tile: int = 128,
    bin_block: int = 8,
    use_mxu: bool = True,
    interpret: bool = False,
    carry: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Run the fused scan and emit the per-strip row slabs.

    Args:
      idx: (n, h, w) int32 bin indices, padded to tile multiples
        (PAD_BIN outside the image) — same contract as ``wf_tis_pallas``.
      num_bins: padded bin count, multiple of ``bin_block``.
      slots: (nth, kp) int32 table from ``slot_plan`` — in-strip offsets
        of the rows each strip emits, -1 for empty slots.
      carry: optional (n, num_bins, w) fp32 band carry-in.

    Returns:
      (n, num_bins, nth * kp, w) fp32 — strip-major row slabs; index
      with ``slot_plan``'s ``pos`` to recover request order.  The full
      (n, num_bins, h, w) H is never an output of this call.
    """
    n, h, w = idx.shape
    if h % tile or w % tile:
        raise ValueError(f"padded image {h}x{w} not divisible by tile {tile}")
    if num_bins % bin_block:
        raise ValueError(
            f"{num_bins} bins not divisible by bin_block {bin_block}")
    nth, ntw, nbb = h // tile, w // tile, num_bins // bin_block
    if slots.ndim != 2 or slots.shape[0] != nth:
        raise ValueError(
            f"slots shape {slots.shape} != ({nth}, kp) for {nth} strips")
    kp = slots.shape[1]
    # Expand to the one-hot selection the kernel multiplies by; -1 slots
    # match no row and emit zeros.
    sel = (jnp.asarray(slots)[:, :, None] == jnp.arange(tile)).astype(
        jnp.float32)
    if carry is None:
        carry = jnp.zeros((n, num_bins, w), jnp.float32)
    if carry.shape != (n, num_bins, w):
        raise ValueError(
            f"carry shape {carry.shape} != {(n, num_bins, w)} (frames, "
            "padded bins, padded width)"
        )

    kernel = functools.partial(
        _fused_rows_kernel, bin_block=bin_block, tile_w=tile,
        use_mxu=use_mxu,
    )
    scratch = [
        pltpu.VMEM((nbb, bin_block, tile), jnp.float32),  # row carries
        pltpu.VMEM((nbb, bin_block, w), jnp.float32),     # column carries
    ]
    return pl.pallas_call(
        kernel,
        grid=(n, nth, ntw, nbb),
        in_specs=[
            pl.BlockSpec((1, tile, tile), lambda f, ih, iw, bb: (f, ih, iw)),
            pl.BlockSpec(
                (1, bin_block, tile), lambda f, ih, iw, bb: (f, bb, iw)
            ),
            pl.BlockSpec((1, kp, tile), lambda f, ih, iw, bb: (ih, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, bin_block, kp, tile), lambda f, ih, iw, bb: (f, bb, ih, iw)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n, num_bins, nth * kp, w), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
        name="fused_rows",
    )(idx, carry.astype(jnp.float32), sel)


def fused_geometry(
    row_ids, n: int, h: int, w: int, num_bins: int,
    *, tile: int = 128, bin_block: int = 8,
) -> FusedRowsGeometry:
    """The :class:`FusedRowsGeometry` a fused dispatch for ``row_ids``
    launches with — what ``kernelcheck.plan_geometry`` hands the
    verifier."""
    _, kp, _ = slot_plan(row_ids, tile, h)
    return FusedRowsGeometry(n=n, h=h, w=w, num_bins=num_bins, tile=tile,
                             bin_block=bin_block, kp=kp)
