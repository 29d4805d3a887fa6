"""CW-TiS: Cross-weave Tiled horizontal/vertical Scan — Pallas TPU kernels.

Paper (§3.4): two custom kernels — a tiled horizontal strip scan over the
one-hot histogram, then a tiled vertical strip scan — eliminating CW-STS's
transpose.  Each pass reads and writes the full b*h*w tensor: 4 HBM passes
(vs WF-TiS's 2), which is exactly the gap the paper measures as the
CW-TiS -> WF-TiS 1.5x and we measure as the memory-roofline ratio.

Binning is fused into the horizontal pass (the init kernel's extra pass is
still avoided), so the measured gap vs WF-TiS isolates the h/v fusion —
same methodology as the paper's Fig. 8 breakdown.

Frame batching: both passes take the frame index as the outermost grid
dimension, so an (n, h, w) stack is two pallas_calls total, not 2n.  The
strip carries reset themselves at frame boundaries because their zeroing
predicates (iw == 0 / ih == 0) fire when the inner raster restarts.

Same MXU triangular-matmul scan trick as wf_tis.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

from repro.kernels.specs import KernelGeometry, KernelSpec, Operand, Scratch
from repro.kernels.wf_tis import _col_scan_mxu, _row_scan_mxu


def kernel_specs(geom: KernelGeometry) -> tuple[KernelSpec, ...]:
    """The declarative contracts of ``cw_tis_pallas``'s TWO
    ``pallas_call``s (verified by ``repro.analysis.kernelcheck``; a
    conformance test pins them against the live calls below).

    Pass 1 sweeps column tiles innermost (grid ``(f, bb, ih, iw)``), so
    the single row-carry scratch is always one step stale — its producer
    is exactly the previous grid step.  Pass 2 DELIBERATELY swaps the
    spatial dims (grid ``(f, bb, iw, ih)``, row tiles innermost): the
    column carry now chains down a vertical strip, and that order is a
    declared contract the verifier must *prove*, not assume row-major —
    re-declaring pass 2 with pass 1's order is the grid-reordering bug
    class kernelcheck exists to catch (its happens-before check fails:
    the last write to the shared scratch before ``(iw, ih)`` would come
    from ``(iw-1, nth-1)``, not the declared producer ``(iw, ih-1)``).
    """
    n, nth, ntw, nbb = geom.n, geom.nth, geom.ntw, geom.nbb
    t, bb_blk = geom.tile, geom.bin_block
    hp, wp, nbp = geom.h_pad, geom.w_pad, geom.nb_pad

    def h_reads(g):
        if g["iw"] > 0:
            return [(("rc",), {**g, "iw": g["iw"] - 1})]
        return []

    def v_reads(g):
        if g["ih"] > 0:
            return [(("cc",), {**g, "ih": g["ih"] - 1})]
        return []

    return (
        KernelSpec(
            name="cw_tis/hscan",
            grid=(("f", n), ("bb", nbb), ("ih", nth), ("iw", ntw)),
            in_specs=(
                Operand("idx", (n, hp, wp), (1, t, t),
                        lambda f, bb, ih, iw: (f, ih, iw), dtype="int32"),
            ),
            out_specs=(
                Operand("hh", (n, nbp, hp, wp), (1, bb_blk, t, t),
                        lambda f, bb, ih, iw: (f, bb, ih, iw)),
            ),
            scratch=(Scratch("row_carry", (bb_blk, t)),),
            carry_reads=h_reads,
            carry_writes=lambda g: [("rc",)],
        ),
        KernelSpec(
            name="cw_tis/vscan",
            grid=(("f", n), ("bb", nbb), ("iw", ntw), ("ih", nth)),
            in_specs=(
                Operand("hh", (n, nbp, hp, wp), (1, bb_blk, t, t),
                        lambda f, bb, iw, ih: (f, bb, ih, iw)),
                Operand("carry", (n, nbp, wp), (1, bb_blk, t),
                        lambda f, bb, iw, ih: (f, bb, iw)),
            ),
            out_specs=(
                Operand("out", (n, nbp, hp, wp), (1, bb_blk, t, t),
                        lambda f, bb, iw, ih: (f, bb, ih, iw)),
            ),
            scratch=(Scratch("col_carry", (bb_blk, t)),),
            carry_reads=v_reads,
            carry_writes=lambda g: [("cc",)],
        ),
    )


def _hscan_kernel(idx_ref, out_ref, row_carry, *, bin_block, use_mxu):
    """Grid (n, nbb, nth, ntw), column tiles innermost: strip sweep per bin
    block (the paper's vertical-strip schedule, Fig. 5 left)."""
    bb = pl.program_id(1)
    iw = pl.program_id(3)

    idx = idx_ref[0]
    th, tw = idx.shape
    bin_ids = bb * bin_block + jax.lax.broadcasted_iota(
        jnp.int32, (bin_block, th, tw), 0
    )
    mask = (idx[None, :, :] == bin_ids).astype(jnp.float32)

    hs = _row_scan_mxu(mask) if use_mxu else jnp.cumsum(mask, axis=2)
    rc = jnp.where(iw == 0, 0.0, row_carry[...])           # (BIN_BLOCK, TH)
    hs = hs + rc[:, :, None]
    row_carry[...] = hs[:, :, tw - 1]
    out_ref[0] = hs


def _vscan_kernel(hh_ref, carry_ref, out_ref, col_carry, *, use_mxu):
    """Grid (n, nbb, ntw, nth), row tiles innermost: horizontal-strip sweep
    (Fig. 5 right).  Input is the horizontally-scanned tensor.  The first
    tile row of each frame seeds its carry from the band carry-in (zeros
    unless this call computes a row band of a larger frame)."""
    ih = pl.program_id(3)

    hs = hh_ref[0]                                         # (BIN_BLOCK, TH, TW)
    th = hs.shape[1]
    vs = _col_scan_mxu(hs) if use_mxu else jnp.cumsum(hs, axis=1)
    cc = jnp.where(ih == 0, carry_ref[0], col_carry[...])  # (BIN_BLOCK, TW)
    vs = vs + cc[:, None, :]
    col_carry[...] = vs[:, th - 1, :]
    out_ref[0] = vs


def cw_tis_pallas(
    idx: jnp.ndarray,
    num_bins: int,
    *,
    tile: int = 128,
    bin_block: int = 8,
    use_mxu: bool = True,
    interpret: bool = False,
    carry: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Two-pass CW-TiS integral histogram (see wf_tis_pallas for contract).

    ``carry`` ([n,] num_bins, w) enters the vertical pass only: the
    horizontal scan is band-local, the band composition is a column offset.
    """
    squeeze = idx.ndim == 2
    if squeeze:
        idx = idx[None]
        if carry is not None:
            carry = carry[None]
    n, h, w = idx.shape
    if h % tile or w % tile:
        raise ValueError(f"padded image {h}x{w} not divisible by tile {tile}")
    if num_bins % bin_block:
        raise ValueError(f"{num_bins} bins not divisible by bin_block {bin_block}")
    if carry is None:
        carry = jnp.zeros((n, num_bins, w), jnp.float32)
    if carry.shape != (n, num_bins, w):
        raise ValueError(
            f"carry shape {carry.shape} != {(n, num_bins, w)} (frames, "
            "padded bins, padded width)"
        )
    nth, ntw, nbb = h // tile, w // tile, num_bins // bin_block

    hh = pl.pallas_call(
        functools.partial(_hscan_kernel, bin_block=bin_block, use_mxu=use_mxu),
        grid=(n, nbb, nth, ntw),
        in_specs=[
            pl.BlockSpec((1, tile, tile), lambda f, bb, ih, iw: (f, ih, iw))
        ],
        out_specs=pl.BlockSpec(
            (1, bin_block, tile, tile), lambda f, bb, ih, iw: (f, bb, ih, iw)
        ),
        out_shape=jax.ShapeDtypeStruct((n, num_bins, h, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bin_block, tile), jnp.float32)],
        interpret=interpret,
    )(idx)

    out = pl.pallas_call(
        functools.partial(_vscan_kernel, use_mxu=use_mxu),
        grid=(n, nbb, ntw, nth),
        in_specs=[
            pl.BlockSpec(
                (1, bin_block, tile, tile), lambda f, bb, iw, ih: (f, bb, ih, iw)
            ),
            pl.BlockSpec(
                (1, bin_block, tile), lambda f, bb, iw, ih: (f, bb, iw)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, bin_block, tile, tile), lambda f, bb, iw, ih: (f, bb, ih, iw)
        ),
        out_shape=jax.ShapeDtypeStruct((n, num_bins, h, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bin_block, tile), jnp.float32)],
        interpret=interpret,
    )(hh, carry.astype(jnp.float32))
    return out[0] if squeeze else out
