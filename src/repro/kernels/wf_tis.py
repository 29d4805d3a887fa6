"""WF-TiS: fused Wave-Front Tiled Scan integral histogram — Pallas TPU kernel.

Paper (§3.5): one kernel computes per-tile horizontal AND vertical scans,
tiles scheduled on anti-diagonal wavefronts so independent GPU thread
blocks can run as soon as their left+top neighbours finish; boundary
columns are spilled to global memory.  Net effect: the b*h*w tensor is
read/written exactly once each (2 HBM passes) instead of CW-TiS's 4.

TPU adaptation (DESIGN.md §2):
  * A TPU core executes the Pallas grid sequentially in row-major order, so
    left+top dependencies are satisfied without diagonal scheduling; the
    wavefront becomes a raster walk with carries in VMEM scratch that
    persist across grid steps (GPU shared memory cannot do this).
  * The per-tile prefix sums are computed on the MXU as triangular-ones
    matmuls: row-cumsum(X) = X @ triu(1), col-cumsum(X) = tril(1) @ X.
    A 128x128 tile cumsum is a single systolic pass — far cheaper than a
    log-depth shift-add ladder on the VPU (see DESIGN.md napkin math).
  * Binning is fused: the kernel reads the int32 bin-index image and forms
    the one-hot mask in VREGs — the paper's separate init kernel (a full
    extra write+read of b*h*w) never exists.  This is a beyond-paper win,
    reducing the HBM floor from 2 passes + init to (1/b read + 1 write).
  * Grid order is (frames, row_tiles, col_tiles, bin_blocks) with bins
    innermost: consecutive grid steps reuse the same image block, so Pallas
    fetches each image tile from HBM once, not once per bin block.
  * Frame batching rides the outermost grid dimension: the same kernel
    instance sweeps frame after frame, and the carry-reset predicates
    (iw == 0 for row carries, ih == 0 for column carries) fire at every
    frame boundary because the raster restarts — per-frame reset needs no
    extra state.  One pallas_call for the whole stack amortizes dispatch
    exactly like the paper's dual-stream frame pipeline (§4.4).

Accumulation: the in-tile row and column scans are float32 on the MXU at
``HIGHEST``; their values are at most tile x padded width (2**20 for a
128-row strip of an 8192-wide frame), so they are exact.  The column
carry, the band carry-in and the output are H's element type
(``core/hdtype.h_dtype``): float32 below 2**24 pixels, where the kernel
is what it always was, and int32 at or above it, where the tile's result
is converted to int32 before the column carry is added, so every count
of the frame is exact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # TPU-specific pallas helpers; interpret mode works without a TPU.
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

from repro.kernels.specs import KernelGeometry, KernelSpec, Operand, Scratch


def kernel_specs(geom: KernelGeometry) -> tuple[KernelSpec, ...]:
    """The declarative contract of ``wf_tis_pallas``'s one ``pallas_call``
    (verified by ``repro.analysis.kernelcheck``; a conformance test pins
    it against the live call below).

    Grid ``(f, ih, iw, bb)`` with bins innermost — the raster walk whose
    sequential order IS the wavefront: the row carry produced at
    ``(ih, iw-1)`` and the column carry produced at ``(ih-1, iw)`` are
    both earlier steps.  The carry edges restate the kernel's reset
    predicates: ``iw == 0`` consumes no row carry, ``ih == 0`` consumes
    the band carry-in operand instead of the column scratch — which is
    also why frame boundaries need no extra state (the raster restart
    fires both predicates).
    """
    n, nth, ntw, nbb = geom.n, geom.nth, geom.ntw, geom.nbb
    t, bb_blk = geom.tile, geom.bin_block
    hp, wp, nbp = geom.h_pad, geom.w_pad, geom.nb_pad
    dt = geom.dtype

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
            name="wf_tis",
            grid=(("f", n), ("ih", nth), ("iw", ntw), ("bb", nbb)),
            in_specs=(
                Operand("idx", (n, hp, wp), (1, t, t),
                        lambda f, ih, iw, bb: (f, ih, iw), dtype="int32"),
                Operand("carry", (n, nbp, wp), (1, bb_blk, t),
                        lambda f, ih, iw, bb: (f, bb, iw), dtype=dt),
            ),
            out_specs=(
                Operand("out", (n, nbp, hp, wp), (1, bb_blk, t, t),
                        lambda f, ih, iw, bb: (f, bb, ih, iw), dtype=dt),
            ),
            scratch=(
                Scratch("row_carry", (nbb, bb_blk, t)),
                Scratch("col_carry", (nbb, bb_blk, wp), dtype=dt),
            ),
            carry_reads=reads,
            carry_writes=writes,
        ),
    )


def _triu_ones(n: int, dtype=jnp.float32):
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return (r <= c).astype(dtype)


def _tril_ones(n: int, dtype=jnp.float32):
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return (r >= c).astype(dtype)


def _row_scan_mxu(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive cumsum along the last axis via MXU: X @ triu(1).

    Only for 0/1 operands (the one-hot mask): those are exact in the
    single bf16 pass the TPU makes at default precision."""
    tw = x.shape[-1]
    return jax.lax.dot_general(
        x,
        _triu_ones(tw, x.dtype),
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _col_scan_mxu(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive cumsum along axis -2 via MXU: tril(1) @ X per bin.

    out[b, i, j] = sum_r tril[i, r] * x[b, r, j] — one 2-D matmul per bin
    of the block, stacked, so the result keeps (batch, row, col) layout
    without a post-transpose.  A batched dot_general over a broadcast
    tril is the same arithmetic, but Mosaic aborts on a later broadcast
    add to its result.
    """
    return _left_matmul(_tril_ones(x.shape[-2], x.dtype), x)


def _left_matmul(a: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """out[b] = a @ x[b] for every b along the leading axis of ``x``.

    At ``HIGHEST`` precision: ``x`` holds counts up to the frame width or
    area, and the TPU's default single bf16 pass holds integers exactly
    only up to 256, which on a v5e got most entries of a 640-column scan
    wrong.  ``HIGHEST`` keeps f32 operands exact (to 2^24)."""
    return jnp.stack([
        jax.lax.dot_general(
            a,
            x[b],
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        for b in range(x.shape[0])
    ])


def _wf_tis_kernel(
    idx_ref,      # (1, TH, TW) int32 bin indices (PAD_BIN=-1 outside the image)
    carry_ref,    # (1, BIN_BLOCK, TW) H-typed band carry-in (zeros = topmost band)
    out_ref,      # (1, BIN_BLOCK, TH, TW) H-typed integral histogram block
    row_carry,    # VMEM scratch (NBB, BIN_BLOCK, TH) fp32 — right-edge carries
    col_carry,    # VMEM scratch (NBB, BIN_BLOCK, W_PAD) H-typed — bottom-edge carries
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

    # Fused binning: one-hot mask for this block of bins, formed in VREGs.
    bin_ids = bb * bin_block + jax.lax.broadcasted_iota(
        jnp.int32, (bin_block, th, tw), 0
    )
    mask = (idx[None, :, :] == bin_ids).astype(jnp.float32)

    # ---- horizontal scan within the tile (MXU triangular matmul) ----
    if use_mxu:
        hs = _row_scan_mxu(mask)
    else:
        hs = jnp.cumsum(mask, axis=2)

    # Add the running row carry (prefix of everything left of this tile in
    # the current row strip), zeroed at the first column of tiles — which
    # also resets it at every new frame, since the raster restarts there.
    rc = jnp.where(iw == 0, 0.0, row_carry[bb])            # (BIN_BLOCK, TH)
    hs = hs + rc[:, :, None]
    row_carry[bb] = hs[:, :, tw - 1]                       # new right edge

    # ---- vertical scan within the tile ----
    if use_mxu:
        vs = _col_scan_mxu(hs)
    else:
        vs = jnp.cumsum(hs, axis=1)

    # Add the running column carry (full integral at the last row of the
    # strip above).  On the first strip — of every frame, since the raster
    # restarts there — it is seeded from the band carry-in instead of zero:
    # the host-level band decomposition (core/bands.py) enters the kernel
    # here, exactly where the VMEM carry chain begins.  The strip's own
    # scan is exact in fp32; it takes H's type before the carry joins it
    # (a no-op for an fp32 H).
    cols = pl.dslice(iw * tile_w, tile_w)
    cc = jnp.where(ih == 0, carry_ref[0], col_carry[bb, :, cols])
    vs = vs.astype(out_ref.dtype) + cc[:, None, :]
    col_carry[bb, :, cols] = vs[:, th - 1, :]              # new bottom edge

    out_ref[0] = vs


def wf_tis_pallas(
    idx: jnp.ndarray,
    num_bins: int,
    *,
    tile: int = 128,
    bin_block: int = 8,
    use_mxu: bool = True,
    interpret: bool = False,
    carry: jnp.ndarray | None = None,
    dtype=jnp.float32,
) -> jnp.ndarray:
    """Fused WF-TiS integral histogram.

    Args:
      idx: (h, w) or (n, h, w) int32 bin indices, already padded so
        h % tile == 0 and w % tile == 0 (padding uses PAD_BIN so it matches
        no bin).
      num_bins: padded bin count, multiple of ``bin_block``.
      carry: optional ([n,] num_bins, w) band carry-in — the bottom row
        of the band above when this call computes one row band of a larger
        frame (core/bands.py).  ``None`` means a frame top (zero carry).
      dtype: H's element type, float32 or int32 (``core/hdtype``).

    Returns:
      (num_bins, h, w) inclusive integral histogram of ``dtype`` for a
      single frame, (n, num_bins, h, w) for a frame stack.
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
    dtype = jnp.dtype(dtype)
    if carry is None:
        carry = jnp.zeros((n, num_bins, w), dtype)
    if carry.shape != (n, num_bins, w):
        raise ValueError(
            f"carry shape {carry.shape} != {(n, num_bins, w)} (frames, "
            "padded bins, padded width)"
        )
    nth, ntw, nbb = h // tile, w // tile, num_bins // bin_block

    kernel = functools.partial(
        _wf_tis_kernel, bin_block=bin_block, tile_w=tile, use_mxu=use_mxu
    )
    scratch = [
        pltpu.VMEM((nbb, bin_block, tile), jnp.float32),  # row carries
        pltpu.VMEM((nbb, bin_block, w), dtype),           # column carries
    ]
    out = pl.pallas_call(
        kernel,
        grid=(n, nth, ntw, nbb),
        in_specs=[
            pl.BlockSpec((1, tile, tile), lambda f, ih, iw, bb: (f, ih, iw)),
            pl.BlockSpec(
                (1, bin_block, tile), lambda f, ih, iw, bb: (f, bb, iw)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, bin_block, tile, tile), lambda f, ih, iw, bb: (f, bb, ih, iw)
        ),
        out_shape=jax.ShapeDtypeStruct((n, num_bins, h, w), dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        name="wf_tis",
    )(idx, carry.astype(dtype))
    return out[0] if squeeze else out
