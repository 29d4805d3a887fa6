"""Carry-delta broadcast: the device half of the incremental video path.

Consecutive video frames from a fixed camera differ in a handful of
rows.  Because every column of H is a prefix sum, editing rows
``[r0, r1)`` changes H *below* ``r1`` only through the band's bottom
row: for any clean row ``r >= r1``,

    H_new[r, c, b] = H_old[r, c, b] + delta[c, b]
    delta          = H_new[r1 - 1]  -  H_old[r1 - 1]        # (bins, w)

so a cached H is repaired by recomputing just the dirty bands and
adding one broadcast ``(bins, w)`` delta to every clean slab below —
the compute-vs-reuse tradeoff of Ehsan et al. (arXiv:1510.05142)
applied across *time* instead of across queries.  All arithmetic is
integer-valued fp32 (exact below 2**24), so the repaired H is
bit-exact against a full recompute; ``core/delta.py`` owns that walk
and the exactness argument.

This kernel is the slab-repair primitive: stream a clean
``(n, bins, h, w)`` slab through VMEM tile by tile and add the delta
row to every row of each tile.  There is no carry chain and no
scratch — each grid step is independent (any grid order is valid; the
declared one just keeps the delta block resident while a frame's
spatial tiles stream by).  The interesting contract is pure coverage:
every output tile written exactly once, the delta block indexed by
``(f, bb, iw)`` only — which ``kernel_specs`` declares and
``repro.analysis.kernelcheck`` proves.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.specs import KernelGeometry, KernelSpec, Operand


def kernel_specs(geom: KernelGeometry) -> tuple[KernelSpec, ...]:
    """The declarative contract of ``delta_apply_pallas``'s one
    ``pallas_call`` (verified by ``repro.analysis.kernelcheck``; the
    conformance test in tests/test_delta.py pins it against the live
    call).

    No scratch and no carry edges — the add is pointwise per tile, so
    carry-order is trivially satisfied and the whole contract is
    exactly-once output coverage, in-bounds index maps, and the
    double-buffered VMEM fit of one H tile + one delta row block.
    """
    n, nth, ntw, nbb = geom.n, geom.nth, geom.ntw, geom.nbb
    t, bb_blk = geom.tile, geom.bin_block
    hp, wp, nbp = geom.h_pad, geom.w_pad, geom.nb_pad

    return (
        KernelSpec(
            name="delta_apply",
            grid=(("f", n), ("bb", nbb), ("ih", nth), ("iw", ntw)),
            in_specs=(
                Operand("h", (n, nbp, hp, wp), (1, bb_blk, t, t),
                        lambda f, bb, ih, iw: (f, bb, ih, iw)),
                Operand("delta", (n, nbp, wp), (1, bb_blk, t),
                        lambda f, bb, ih, iw: (f, bb, iw)),
            ),
            out_specs=(
                Operand("out", (n, nbp, hp, wp), (1, bb_blk, t, t),
                        lambda f, bb, ih, iw: (f, bb, ih, iw)),
            ),
        ),
    )


def _delta_apply_kernel(h_ref, delta_ref, out_ref):
    # (1, BB, T, T) += (1, BB, T) broadcast over the tile's rows.
    out_ref[0] = h_ref[0] + delta_ref[0][:, None, :]


def delta_apply_pallas(
    H: jnp.ndarray,
    delta: jnp.ndarray,
    *,
    tile: int = 128,
    bin_block: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """Add a broadcast ``(bins, w)`` delta to every row of an H slab.

    Args:
      H: (n, nb_pad, h_pad, w_pad) fp32 clean slab, spatial dims padded
        to tile multiples and bins to a bin_block multiple — the same
        padded layout the scan kernels write.
      delta: (n, nb_pad, w_pad) fp32 carry delta (new bottom row of the
        dirty band above, minus the old one).

    Returns:
      (n, nb_pad, h_pad, w_pad) fp32 — ``H + delta`` broadcast over the
      row axis, computed tile by tile in VMEM.
    """
    if H.ndim != 4:
        raise ValueError(f"expected (n, bins, h, w) slab, got {H.shape}")
    n, nb, h, w = H.shape
    if h % tile or w % tile:
        raise ValueError(f"padded slab {h}x{w} not divisible by tile {tile}")
    if nb % bin_block:
        raise ValueError(
            f"{nb} bins not divisible by bin_block {bin_block}")
    if delta.shape != (n, nb, w):
        raise ValueError(
            f"delta shape {delta.shape} != {(n, nb, w)} (frames, padded "
            "bins, padded width)")
    nth, ntw, nbb = h // tile, w // tile, nb // bin_block

    return pl.pallas_call(
        _delta_apply_kernel,
        grid=(n, nbb, nth, ntw),
        in_specs=[
            pl.BlockSpec((1, bin_block, tile, tile),
                         lambda f, bb, ih, iw: (f, bb, ih, iw)),
            pl.BlockSpec((1, bin_block, tile),
                         lambda f, bb, ih, iw: (f, bb, iw)),
        ],
        out_specs=pl.BlockSpec((1, bin_block, tile, tile),
                               lambda f, bb, ih, iw: (f, bb, ih, iw)),
        out_shape=jax.ShapeDtypeStruct((n, nb, h, w), jnp.float32),
        interpret=interpret,
        name="delta_apply",
    )(H.astype(jnp.float32), delta.astype(jnp.float32))
