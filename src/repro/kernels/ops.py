"""Jit'd public wrappers around the integral-histogram kernels.

``integral_histogram`` is the framework's single entry point: it bins the
image, pads spatial dims to tile multiples and bins to bin-block multiples
(padding pixels get PAD_BIN so they match no bin), dispatches to the chosen
method/backend, and crops the result back.

Input rank is polymorphic over a frame batch axis:

  (h, w)    -> (num_bins, h, w)       single frame
  (n, h, w) -> (n, num_bins, h, w)    frame stack — identical to n
               single-frame calls, executed as ONE dispatch (the jnp
               methods fuse the frame axis into their batched scans; the
               Pallas kernels take it as the outermost grid dimension).

Backends:
  "pallas"  — the TPU kernels (on CPU only with interpret=True; tests do).
  "jnp"     — the schedule-faithful jnp restatements (XLA-compiled; used
              for CPU wall-time benchmarks and as the production path on
              non-TPU hosts).
  "auto"    — pallas on TPU, jnp elsewhere.

Band streaming (core/bands.py) enters here through two knobs:

  carry_in            — ([n,] num_bins, w) aggregate of everything above
                        this image slice; the result is the full-frame H
                        restricted to the slice's rows.  Threads into the
                        Pallas kernels' VMEM carry chain and the jnp
                        wf_tis scan seed; bit-exact either way (all
                        arithmetic is integer-valued fp32).
  memory_budget_bytes — cap on the per-dispatch H footprint: frames whose
                        (n, b, h, w) output exceeds it are computed band
                        by band with the carry threaded between dispatches
                        and reassembled.  Bounds the transient working set
                        (one-hot masks, transposes, scan intermediates) to
                        a band; use core/bands.py directly when even the
                        assembled H must never materialize.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scans
from repro.core.binning import PAD_BIN, bin_indices
from repro.kernels import cw_tis, delta_apply as delta_apply_mod, \
    fused_rows, wf_tis
from repro.kernels.cw_tis import cw_tis_pallas
from repro.kernels.delta_apply import delta_apply_pallas
from repro.kernels.fused_rows import fused_rows_pallas, slot_plan
from repro.kernels.wf_tis import wf_tis_pallas

PALLAS_METHODS = {"cw_tis": cw_tis_pallas, "wf_tis": wf_tis_pallas}

# method -> kernel_specs(geom) builder: the declarative contracts
# repro.analysis.kernelcheck verifies (grid order, carry happens-before,
# output coverage, in-bounds index maps, VMEM fit).  Every PALLAS_METHODS
# entry must have one — asserted by the kernelcheck conformance tests.
# "fused_rows" and "delta_apply" are spec-verified too but are NOT
# PALLAS_METHODS entries: they are not full-H methods you can name in
# integral_histogram(); they are the query-fused dispatch behind
# fused_corner_rows() and the slab-repair primitive behind delta_apply().
KERNEL_SPECS = {
    "cw_tis": cw_tis.kernel_specs,
    "wf_tis": wf_tis.kernel_specs,
    "fused_rows": fused_rows.kernel_specs,
    "delta_apply": delta_apply_mod.kernel_specs,
}


def _pad_to(x: jnp.ndarray, mult_h: int, mult_w: int, fill) -> jnp.ndarray:
    """Pad the spatial (last two) axes up to multiples; leading axes kept."""
    h, w = x.shape[-2:]
    ph = (-h) % mult_h
    pw = (-w) % mult_w
    if ph or pw:
        pad = [(0, 0)] * (x.ndim - 2) + [(0, ph), (0, pw)]
        x = jnp.pad(x, pad, constant_values=fill)
    return x


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_bins", "method", "backend", "tile", "bin_block", "use_mxu",
        "interpret", "value_range",
    ),
)
def _integral_histogram_jit(
    image: jnp.ndarray,
    carry_in: jnp.ndarray | None,
    num_bins: int,
    *,
    method: str,
    backend: str,
    tile: int,
    bin_block: int,
    use_mxu: bool,
    interpret: bool,
    value_range: int,
) -> jnp.ndarray:
    """The jit'd core: backend already resolved, inputs already validated."""
    if backend == "jnp":
        if method == "wf_tis":
            # Native carry seeding: the band scan starts from carry_in.
            return scans.wf_tis(
                image, num_bins, value_range, tile=tile, carry_in=carry_in
            )
        kw = {} if method in ("cw_b", "cw_sts") else {"tile": tile}
        H = scans.METHODS[method](image, num_bins, value_range, **kw)
        return scans.apply_carry(H, carry_in)

    h, w = image.shape[-2:]
    idx = bin_indices(image, num_bins, value_range)
    idx = _pad_to(idx, tile, tile, PAD_BIN)
    nb_pad = num_bins + (-num_bins) % bin_block
    carry = None
    if carry_in is not None:
        # Pad (..., num_bins, w) -> (..., nb_pad, w_pad): padded bins hold
        # no mass and padded columns are cropped, so zero-fill is exact.
        pad = [(0, 0)] * (carry_in.ndim - 2)
        pad += [(0, nb_pad - num_bins), (0, (-w) % tile)]
        carry = jnp.pad(carry_in.astype(jnp.float32), pad)
    out = PALLAS_METHODS[method](
        idx, nb_pad, tile=tile, bin_block=bin_block, use_mxu=use_mxu,
        interpret=interpret, carry=carry,
    )
    return out[..., :num_bins, :h, :w]


def integral_histogram(
    image: jnp.ndarray,
    num_bins: int,
    *,
    method: str = "wf_tis",
    backend: str = "auto",
    tile: int = 128,
    bin_block: int = 8,
    use_mxu: bool = True,
    interpret: bool = False,
    value_range: int = 256,
    carry_in: jnp.ndarray | None = None,
    memory_budget_bytes: int | None = None,
) -> jnp.ndarray:
    """Inclusive integral histogram of a frame or an (n, h, w) frame stack.

    See the module docstring for ``carry_in`` (band composition) and
    ``memory_budget_bytes`` (auto-banding).
    """
    if image.ndim not in (2, 3):
        raise ValueError(f"expected (h, w) or (n, h, w), got {image.shape}")
    if backend not in ("auto", "pallas", "jnp"):
        raise ValueError(f"unknown backend {backend!r}")
    if method not in scans.METHODS:
        raise ValueError(f"unknown method {method!r}")
    if backend == "pallas" and method not in PALLAS_METHODS:
        # An explicit backend request must not silently degrade: only
        # "auto" may fall back to the jnp scans.
        raise ValueError(
            f"method {method!r} has no Pallas kernel (Pallas methods: "
            f"{sorted(PALLAS_METHODS)}); use backend='auto' or 'jnp'"
        )
    if backend == "auto":
        backend = (
            "pallas" if _on_tpu() and method in PALLAS_METHODS else "jnp"
        )
    if carry_in is not None:
        want = image.shape[:-2] + (num_bins, image.shape[-1])
        if carry_in.shape != want:
            raise ValueError(
                f"carry_in shape {carry_in.shape} != {want} "
                "(leading frame axes, num_bins, width)"
            )

    if memory_budget_bytes is not None:
        # The banding decision lives in the planner (core/engine.py) —
        # this entry point just executes whatever plan it hands back.
        from repro.core import bands, engine  # deferred: both import us

        h, w = image.shape[-2:]
        num_frames = 1 if image.ndim == 2 else image.shape[0]
        p = engine.plan(engine.WorkloadSpec(
            height=h, width=w, num_bins=num_bins, num_frames=num_frames,
            method=method, backend=backend, tile=tile, bin_block=bin_block,
            use_mxu=use_mxu, interpret=interpret, value_range=value_range,
            memory_budget_bytes=memory_budget_bytes,
        ))
        if p.band_plan is not None:
            return bands.banded_integral_histogram(
                image, num_bins, plan=p.band_plan, carry_in=carry_in,
                method=method, backend=p.backend, tile=tile,
                bin_block=bin_block, use_mxu=use_mxu, interpret=interpret,
                value_range=value_range,
            )

    return _integral_histogram_jit(
        image, carry_in, num_bins, method=method, backend=backend,
        tile=tile, bin_block=bin_block, use_mxu=use_mxu,
        interpret=interpret, value_range=value_range,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_bins", "tile", "bin_block", "use_mxu", "interpret",
        "value_range",
    ),
)
def _fused_rows_jit(frames, carry_in, slots, *, num_bins: int, tile: int,
                    bin_block: int, use_mxu: bool, interpret: bool,
                    value_range: int):
    """The jit'd Pallas path of ``fused_corner_rows``: binning, padding,
    the fused kernel and the crop as one program, traced once per shape.
    The slot table is an operand, so requests that differ only in their
    rows reuse the compiled kernel; called eagerly, the kernel would be
    traced and compiled again on every call."""
    w = frames.shape[-1]
    idx = _pad_to(bin_indices(frames, num_bins, value_range), tile, tile,
                  PAD_BIN)
    nb_pad = num_bins + (-num_bins) % bin_block
    carry = None
    if carry_in is not None:
        pad = [(0, 0), (0, nb_pad - num_bins), (0, (-w) % tile)]
        carry = jnp.pad(carry_in.astype(jnp.float32), pad)
    out = fused_rows_pallas(
        idx, nb_pad, slots, tile=tile, bin_block=bin_block,
        use_mxu=use_mxu, interpret=interpret, carry=carry,
    )
    return out[:, :num_bins, :, :w]


def fused_corner_rows(
    image: jnp.ndarray,
    num_bins: int,
    row_ids,
    *,
    method: str = "wf_tis",
    backend: str = "auto",
    tile: int = 128,
    bin_block: int = 8,
    use_mxu: bool = True,
    interpret: bool = False,
    value_range: int = 256,
    carry_in: jnp.ndarray | None = None,
    stats: dict | None = None,
) -> jnp.ndarray:
    """Corner rows of H for a known request — without materializing H.

    The Ehsan compute-vs-store fusion (arXiv:1510.05138): when the rows a
    request reads (Eq. 2 corner rows) are known up front, run the scan and
    emit ONLY those rows.  Two properties distinguish this from computing
    H and slicing:

      * the full (n, b, h, w) H never exists — on the Pallas path the
        fused kernel (kernels/fused_rows.py) writes kp rows per strip
        straight from VMEM; on the jnp path the scan streams tile-high
        bands so the live set is one band plus the emitted rows;
      * compute stops at the band containing ``max(row_ids)`` — rows
        below the last requested one contribute to nothing and are never
        scanned.  Banded streaming of full H must touch every band.

    Args:
      image: (h, w) or (n, h, w) frame(s), same contract as
        ``integral_histogram``.
      row_ids: sorted unique frame rows to emit, each in ``[0, h)``.
      stats: optional dict filled with ``bands_computed``/``bands_total``
        (tile-high bands scanned vs in the frame), ``rows_bytes`` (the
        result slab), ``full_h_bytes`` (what dense H would have cost) and
        the resolved ``backend`` — the peak-memory proxy the fused tests
        assert on.

    Returns:
      (..., num_bins, K, w) fp32 — H restricted to ``row_ids``, in
      ``row_ids`` order.  Bit-exact against dense H sliced at the same
      rows (all arithmetic is integer-valued fp32).
    """
    if image.ndim not in (2, 3):
        raise ValueError(f"expected (h, w) or (n, h, w), got {image.shape}")
    squeeze = image.ndim == 2
    frames = image[None] if squeeze else image
    n, h, w = frames.shape
    # analysis: allow-host-sync(row ids are host-side request metadata, never device data)
    rows = np.asarray(row_ids, np.int64).reshape(-1)
    if rows.size == 0:
        raise ValueError("row_ids is empty — nothing to fuse")
    if np.any(np.diff(rows) <= 0) or rows[0] < 0 or rows[-1] >= h:
        raise ValueError(
            f"row_ids must be sorted unique within [0, {h})")
    if backend not in ("auto", "pallas", "jnp"):
        raise ValueError(f"unknown backend {backend!r}")
    if method not in scans.METHODS:
        raise ValueError(f"unknown method {method!r}")
    if backend == "pallas" and method != "wf_tis":
        raise ValueError(
            f"the fused kernel runs the wf_tis scan; method {method!r} "
            "has no fused Pallas path — use backend='auto' or 'jnp'"
        )
    if backend == "auto":
        backend = "pallas" if _on_tpu() and method == "wf_tis" else "jnp"
    if carry_in is not None:
        want = frames.shape[:-2] + (num_bins, w)
        got = carry_in[None] if squeeze and carry_in.ndim == 2 else carry_in
        if got.shape != want:
            raise ValueError(
                f"carry_in shape {carry_in.shape} incompatible with "
                f"{want} (frames, num_bins, width)"
            )
        carry_in = got

    # Early exit: nothing below the last requested row feeds any output.
    bands_total = -(-h // tile)
    bands_needed = int(rows[-1]) // tile + 1
    h_cut = min(h, bands_needed * tile)
    frames = frames[:, :h_cut]

    if backend == "pallas":
        slots, _, pos = slot_plan(rows, tile, bands_needed * tile)
        out = _fused_rows_jit(
            frames, carry_in, jnp.asarray(slots), num_bins=num_bins,
            tile=tile, bin_block=bin_block, use_mxu=use_mxu,
            interpret=interpret, value_range=value_range,
        )
        R = out[:, :, pos]
    else:
        # Stream tile-high bands through the scan, carry threaded between
        # dispatches; keep only the requested rows of each band.
        carry = carry_in
        kept = []
        for b in range(bands_needed):
            band = frames[:, b * tile:(b + 1) * tile]
            Hb = _integral_histogram_jit(
                band, carry, num_bins, method=method, backend="jnp",
                tile=tile, bin_block=bin_block, use_mxu=use_mxu,
                interpret=interpret, value_range=value_range,
            )
            carry = Hb[..., Hb.shape[-2] - 1, :]
            local = rows[(rows >= b * tile) & (rows < (b + 1) * tile)]
            if local.size:
                kept.append(Hb[..., local - b * tile, :])
        R = jnp.concatenate(kept, axis=-2)

    if stats is not None:
        stats.update(
            bands_computed=bands_needed,
            bands_total=bands_total,
            rows_bytes=n * num_bins * rows.size * w * 4,
            full_h_bytes=n * num_bins * h * w * 4,
            backend=backend,
        )
    return R[0] if squeeze else R


def delta_apply(
    H: jnp.ndarray,
    delta: jnp.ndarray,
    *,
    backend: str = "auto",
    tile: int = 128,
    bin_block: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """Repair a clean H slab with a broadcast carry delta.

    The incremental video path (core/delta.py): when rows above a slab
    were edited, the slab's correction is one ``(..., num_bins, w)``
    delta — the dirty band's new bottom row minus its old one — added
    to every row.  All arithmetic is integer-valued fp32, so the result
    is bit-exact against recomputing the slab from the new frame.

    Args:
      H: (num_bins, h, w) or (n, num_bins, h, w) fp32 clean slab.
      delta: (num_bins, w) or (n, num_bins, w) carry delta, leading
        frame axis matching ``H``.

    Returns:
      ``H + delta`` broadcast over the row axis, same logical shape as
      ``H``.  Pallas backend streams the slab tile-by-tile through VMEM
      (kernels/delta_apply.py); the jnp backend is one fused XLA add.
    """
    if H.ndim not in (3, 4):
        raise ValueError(
            f"expected (num_bins, h, w) or (n, num_bins, h, w), got "
            f"{H.shape}")
    if backend not in ("auto", "pallas", "jnp"):
        raise ValueError(f"unknown backend {backend!r}")
    squeeze = H.ndim == 3
    n, nb, h, w = (1,) + tuple(H.shape) if squeeze else H.shape
    d_shape = (1,) + tuple(delta.shape) if squeeze and delta.ndim == 2 \
        else tuple(delta.shape)
    if d_shape != (n, nb, w):
        raise ValueError(
            f"delta shape {delta.shape} incompatible with {(n, nb, w)} "
            "(frames, num_bins, width)")
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "jnp"
    return _delta_apply_jit(H, delta, backend=backend, tile=tile,
                            bin_block=bin_block, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("backend", "tile", "bin_block", "interpret"))
def _delta_apply_jit(H, delta, *, backend: str, tile: int, bin_block: int,
                     interpret: bool):
    """The jit'd core of ``delta_apply``: one program, so the reshapes,
    padding and cropping around the kernel cost no copies of the slab."""
    squeeze = H.ndim == 3
    slab = H[None] if squeeze else H
    d = delta.reshape((slab.shape[0],) + delta.shape[-2:])
    if backend == "jnp":
        out = slab + d[..., None, :]
        return out[0] if squeeze else out
    n, nb, h, w = slab.shape
    nb_pad = nb + (-nb) % bin_block
    pad_b = [(0, 0), (0, nb_pad - nb)]
    slab_p = jnp.pad(
        _pad_to(slab.astype(jnp.float32), tile, tile, 0.0),
        pad_b + [(0, 0), (0, 0)])
    d_p = jnp.pad(d.astype(jnp.float32), pad_b + [(0, (-w) % tile)])
    out = delta_apply_pallas(
        slab_p, d_p, tile=tile, bin_block=bin_block, interpret=interpret,
    )[:, :nb, :h, :w]
    return out[0] if squeeze else out


def fused_likelihood_map(
    image: jnp.ndarray,
    model: jnp.ndarray,
    metric,
    *,
    window: tuple[int, int],
    stride: int = 1,
    num_bins: int | None = None,
    stats: dict | None = None,
    **kwargs,
):
    """Likelihood-map tiles straight off the fused scan — the second
    output mode of the query-fused path.

    Computes the two corner-row lattices the (window, stride) sliding
    grid reads, fuses them out of the scan with ``fused_corner_rows``,
    and scores every window against ``model`` via the shared
    row-difference evaluator.  Dense H is never built.

    Returns the same (..., out_h, out_w) map as
    ``HSource.likelihood_map``.
    """
    from repro.core.hsource import FusedRowsH  # deferred: hsource imports us

    nb = int(model.shape[-1]) if num_bins is None else num_bins
    h, w = image.shape[-2:]
    probe = FusedRowsH(row_ids=(0,), R=np.zeros((nb, 1, w), np.float32),
                       height=h, width=w)
    _, _, bot, top = probe._window_lattices(window, stride)
    rows = np.unique(np.concatenate([bot, top[top >= 0]]))
    R = fused_corner_rows(image, nb, rows, stats=stats, **kwargs)
    # analysis: allow-host-sync(FusedRowsH stores host arrays by protocol — the K-row slab pull IS the result readback)
    source = FusedRowsH(row_ids=rows, R=np.asarray(R), height=h, width=w)
    return source.likelihood_map(model, window, metric, stride)
