"""The paper's four integral-histogram computation strategies, jnp level.

CW-B    — cross-weave baseline: unbatched per-bin scan/transpose/scan
          composition (faithful to the paper's "many tiny kernels" storm;
          on XLA the launch overhead becomes trace/HLO blow-up and lost
          fusion, and its HBM-traffic model keeps the 6-pass floor).
CW-STS  — single batched scan -> materialized 3-D transpose -> scan.
CW-TiS  — tiled horizontal strip scan then tiled vertical strip scan,
          no transpose (4 HBM passes).  Pallas kernel: kernels/cw_tis.py.
WF-TiS  — single fused pass: per-tile h-scan + v-scan with boundary
          carries (2 HBM passes).  Pallas kernel: kernels/wf_tis.py.

The jnp versions here are schedule-faithful restatements used as CPU
executables (wall-time benchmarks) and as shape/semantics references; the
TPU-native schedules live in repro/kernels/.

Every method accepts a single frame ``(h, w)`` -> ``(b, h, w)`` or a frame
stack ``(n, h, w)`` -> ``(n, b, h, w)``, identical to a loop of
single-frame calls.  For the cross-weave methods the frame axis simply
rides the leading batch dimensions of the same scan primitives (one fused
dispatch, no per-frame launches — the throughput model of Koppaka et
al.'s stream-batched histograms); WF-TiS is vmapped so its strip/carry
schedule stays frame-faithful while XLA widens the carries to (n, b, w).
All results are identical to kernels/ref.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.binning import bin_indices, one_hot_bins


# ---------------------------------------------------------------------------
# Band-carry composition.  An integral histogram is a prefix sum over rows,
# so the H of rows [r0, r1) of a frame equals the local H of that band plus
# the full-frame H's row r0-1 — an (..., b, w) aggregate, the WF-TiS column
# carry lifted out of the kernel.  All arithmetic is integer-valued (fp32
# below 2**24 pixels, int32 past it: core/hdtype.py), so post-adding the
# carry is bit-identical to seeding the scan with it; core/bands.py
# streams whole frames through this.
# ---------------------------------------------------------------------------
def apply_carry(H: jnp.ndarray, carry_in: jnp.ndarray | None) -> jnp.ndarray:
    """Compose a band's local H (..., b, bh, w) with the (..., b, w)
    aggregate of everything above the band (``None`` = topmost band)."""
    if carry_in is None:
        return H
    return H + carry_in.astype(H.dtype)[..., :, None, :]


# ---------------------------------------------------------------------------
# CW-B: naive baseline — bins processed one at a time, rows/cols as separate
# scan primitives (Algorithm 2 of the paper).
# ---------------------------------------------------------------------------
def cw_b(image: jnp.ndarray, num_bins: int, value_range: int = 256) -> jnp.ndarray:
    idx = bin_indices(image, num_bins, value_range)
    outs = []
    for b in range(num_bins):  # one "kernel launch" chain per bin (faithful)
        q = (idx == b).astype(jnp.float32)
        h_scanned = jnp.cumsum(q, axis=-1)         # horizontal prescan
        t = jnp.swapaxes(h_scanned, -2, -1)        # 2-D transpose (materialized)
        v_scanned = jnp.cumsum(t, axis=-1)         # vertical prescan (as rows)
        outs.append(jnp.swapaxes(v_scanned, -2, -1))
    return jnp.stack(outs, axis=-3)


# ---------------------------------------------------------------------------
# CW-STS: one batched scan, one 3-D transpose, one batched scan (Algorithm 3).
# A frame stack fuses into the scan's leading batch axes: (n, b, h, w) is one
# (n*b)-deep batched scan, not n dispatches.
# ---------------------------------------------------------------------------
def cw_sts(image: jnp.ndarray, num_bins: int, value_range: int = 256) -> jnp.ndarray:
    idx = bin_indices(image, num_bins, value_range)
    q = one_hot_bins(idx, num_bins)                          # (..., b, h, w) init pass
    h_scanned = jnp.cumsum(q, axis=-1)                       # batched row scan
    transposed = jnp.swapaxes(h_scanned, -2, -1).copy()      # 3-D transpose
    v_scanned = jnp.cumsum(transposed, axis=-1)              # batched "row" scan
    return jnp.swapaxes(v_scanned, -2, -1)                   # back to (..., b, h, w)


# ---------------------------------------------------------------------------
# Tiled building block: blocked inclusive cumsum along the last axis —
# per-tile local scan + exclusive carry of tile totals (the strip schedule
# of CW-TiS, Fig. 5 of the paper).
# ---------------------------------------------------------------------------
def _blocked_cumsum_last(x: jnp.ndarray, tile: int) -> jnp.ndarray:
    *lead, n = x.shape
    if n % tile:
        raise ValueError(f"axis {n} not divisible by tile {tile}")
    xt = x.reshape(*lead, n // tile, tile)
    local = jnp.cumsum(xt, axis=-1)                          # intra-tile scan
    totals = local[..., -1]                                  # per-tile sums
    carry = jnp.cumsum(totals, axis=-1) - totals             # exclusive carry
    return (local + carry[..., None]).reshape(*lead, n)


def _pad_idx(idx: jnp.ndarray, th: int, tw: int) -> jnp.ndarray:
    """Pad a bin-index image (or stack) to tile multiples on the spatial
    (last two) axes; padding matches no bin."""
    from repro.core.binning import PAD_BIN

    h, w = idx.shape[-2:]
    ph, pw = (-h) % th, (-w) % tw
    if ph or pw:
        pad = [(0, 0)] * (idx.ndim - 2) + [(0, ph), (0, pw)]
        idx = jnp.pad(idx, pad, constant_values=PAD_BIN)
    return idx


def cw_tis(
    image: jnp.ndarray, num_bins: int, value_range: int = 256, tile: int = 128
) -> jnp.ndarray:
    idx = bin_indices(image, num_bins, value_range)
    h, w = image.shape[-2:]
    th, tw = min(tile, h), min(tile, w)
    idx = _pad_idx(idx, th, tw)
    q = one_hot_bins(idx, num_bins)
    h_scanned = _blocked_cumsum_last(q, tw)                  # horizontal strips
    v_scanned = _blocked_cumsum_last(jnp.swapaxes(h_scanned, -2, -1), th)
    return jnp.swapaxes(v_scanned, -2, -1)[..., :h, :w]


# ---------------------------------------------------------------------------
# WF-TiS: fused single pass.  The jnp statement of "h-scan then v-scan with
# tile carries, one sweep" — XLA fuses it; the true 2-HBM-pass schedule is
# the Pallas kernel.  A lax.scan over row strips keeps the carry structure
# explicit (the (b, w) column carry is exactly the kernel's VMEM scratch).
# ---------------------------------------------------------------------------
def _wf_tis_single(
    image: jnp.ndarray,
    num_bins: int,
    value_range: int,
    tile: int,
    carry_in: jnp.ndarray | None = None,
    dtype=jnp.float32,
) -> jnp.ndarray:
    idx = bin_indices(image, num_bins, value_range)
    h, w = image.shape
    th = min(tile, h)
    idx = _pad_idx(idx, th, 1)
    hp = idx.shape[0]
    idx_strips = idx.reshape(hp // th, th, w)

    def strip_step(col_carry, idx_strip):
        # col_carry: (b, w) running column sums of everything above.
        # The strip's own scans are fp32 and exact (at most th x w); the
        # strip takes H's type before the carry joins it, as in the kernel.
        q = one_hot_bins(idx_strip, num_bins)                # (b, th, w)
        hs = jnp.cumsum(q, axis=2)                           # horizontal scan
        vs = jnp.cumsum(hs, axis=1)                          # vertical within strip
        out = vs.astype(dtype) + col_carry[:, None, :]
        return out[:, -1, :], out                            # new carry, strip H

    # A band's carry_in seeds the scan exactly where the previous band's
    # bottom row left off — the natural statement of band streaming.
    init = (
        jnp.zeros((num_bins, w), dtype=dtype)
        if carry_in is None
        else carry_in.astype(dtype)
    )
    _, strips = jax.lax.scan(strip_step, init, idx_strips)
    return jnp.moveaxis(strips, 1, 0).reshape(num_bins, hp, w)[:, :h, :]


def wf_tis(
    image: jnp.ndarray,
    num_bins: int,
    value_range: int = 256,
    tile: int = 128,
    carry_in: jnp.ndarray | None = None,
    dtype=jnp.float32,
) -> jnp.ndarray:
    """WF-TiS; ``dtype`` is H's element type, float32 or int32
    (``core/hdtype``), the same as the Pallas kernel's."""
    if image.ndim == 3:  # frame stack: widen the strip scan's carry to (n, b, w)
        if carry_in is None:
            return jax.vmap(lambda im: _wf_tis_single(
                im, num_bins, value_range, tile, dtype=dtype))(image)
        return jax.vmap(lambda im, c: _wf_tis_single(
            im, num_bins, value_range, tile, c, dtype))(image, carry_in)
    return _wf_tis_single(image, num_bins, value_range, tile, carry_in, dtype)


METHODS = {"cw_b": cw_b, "cw_sts": cw_sts, "cw_tis": cw_tis, "wf_tis": wf_tis}
