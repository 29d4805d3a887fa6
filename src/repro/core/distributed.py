"""Distributed integral histograms: the paper's multi-GPU scheme at pod scale.

Paper §4.6: bins are grouped into tasks and dispatched over 4 GPUs through
a task queue (PCIe-attached, no peer communication).  On a TPU mesh the
"task queue" becomes a sharding spec:

  * **Bin sharding** (`bin_sharded_ih`) — the paper's scheme, verbatim:
    bins are an embarrassingly-parallel axis; every device computes the
    integral histogram of its own bin range from the (replicated or
    broadcast) frame.  Zero inter-device traffic after the frame broadcast.

  * **Spatial sharding** (`spatial_sharded_ih`) — beyond-paper: row strips
    are sharded across devices; each device computes its local strip IH and
    the 1-D bottom-boundary aggregate (b, w) is carried across devices with
    an exclusive prefix "wavefront" — the WF-TiS carry pattern lifted from
    VMEM scratch to ICI collectives.  This is what lets a single 8k x 8k x
    128-bin frame (32 GB of H, paper §4.6) live sharded across a pod
    instead of being serialized through one device's memory.

  * Both compose: rows over one mesh axis, bins over the other.

  * **Band streaming** (`iter_banded_sharded_ih`) — either scheme composed
    with core/bands.py: row bands of one huge frame stream through the
    sharded computation, the (b, w) band carry riding on top of the
    intra-band device carries.  Bounds per-device live memory to one
    sharded band.

The exclusive cross-device prefix is implemented two ways:
  - `allgather`: gather all carries, masked sum (one collective; XLA
    optimizes this well on ICI).
  - `ppermute`: log2(D) Hillis-Steele ladder of collective_permutes — the
    literal wavefront, cheaper at large D and the schedule used for the
    sequence-parallel SSM scan in models/ssm.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.binning import PAD_BIN, bin_indices
from repro.core.hdtype import h_dtype
from repro.core.scans import apply_carry
from repro.kernels.ops import integral_histogram


def exclusive_axis_scan(
    x: jnp.ndarray, axis_name: str, axis_size: int, impl: str = "allgather"
) -> jnp.ndarray:
    """Exclusive prefix-sum of ``x`` across a mesh axis (device i receives
    the sum of x from devices 0..i-1).  Runs inside shard_map."""
    if impl == "allgather":
        all_x = lax.all_gather(x, axis_name)                 # (D, ...)
        idx = lax.axis_index(axis_name)
        mask = (jnp.arange(axis_size) < idx).astype(x.dtype)
        return jnp.tensordot(mask, all_x, axes=1)
    if impl == "ppermute":
        # Shift right by one, then Hillis-Steele inclusive ladder.
        val = lax.ppermute(
            x, axis_name, [(i, i + 1) for i in range(axis_size - 1)]
        )
        d = 1
        while d < axis_size:
            recv = lax.ppermute(
                val, axis_name, [(i, i + d) for i in range(axis_size - d)]
            )
            val = val + recv
            d *= 2
        return val
    raise ValueError(f"unknown impl {impl!r}")


def band_input_sharding(
    mesh: Mesh,
    sharding: str,
    *,
    row_axis: str = "data",
    bin_axis: str = "model",
    lead: int = 0,
) -> NamedSharding:
    """The placement a band image slice should be staged with before it
    enters the sharded band compute: replicated for bin sharding (every
    device masks its own bin range out of the full band) and row strips
    over ``row_axis`` for spatial sharding.  ``lead`` counts leading
    frame axes — (n, h, w) stacks are bin-sharded only, so the lead axes
    are never split.  Handing this to ``FrameRuntime``/``stage_stream``
    as ``device=`` commits each slice to the exact layout the shard_map
    consumes, which is what removed the old "sharded plans skip staging"
    carve-out in ``bands.iter_banded_ih``."""
    if sharding == "bin":
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, P(*([None] * lead), row_axis, None))


def replica_meshes(mesh: Mesh, replica_axis: str) -> list:
    """Split a mesh into frame-parallel replica-group submeshes along
    ``replica_axis`` — the serving half of the planner's 2-D layout
    (replica groups x within-group sharding).  One entry per index on the
    axis, each a ``Mesh`` over the remaining axes (the within-group shard
    layout), or ``None`` when the group is a bare single device (a 1-D
    mesh has no remaining axes; callers hand ``None`` groups a plain
    single-device engine, which keeps the PR 9 incremental path alive).
    A mesh without the axis is one group: ``[mesh]``."""
    names = list(mesh.axis_names)
    if replica_axis not in names:
        return [mesh]
    ax = names.index(replica_axis)
    rest = tuple(names[:ax] + names[ax + 1:])
    out = []
    for i in range(mesh.shape[replica_axis]):
        devs = np.take(np.asarray(mesh.devices), i, axis=ax)
        out.append(Mesh(devs, rest) if rest else None)
    return out


def replica_devices(mesh: Mesh, replica_axis: str) -> list:
    """The devices of each replica group ``replica_meshes`` returns, in
    the same order: one tuple per index on ``replica_axis`` (the whole
    mesh as one group when the axis is absent)."""
    devs = np.asarray(mesh.devices)
    names = list(mesh.axis_names)
    if replica_axis not in names:
        return [tuple(devs.flat)]
    ax = names.index(replica_axis)
    return [tuple(np.ravel(np.take(devs, i, axis=ax)))
            for i in range(mesh.shape[replica_axis])]


def bin_sharded_ih(
    image: jnp.ndarray,
    num_bins: int,
    mesh: Mesh,
    *,
    bin_axis: str = "model",
    method: str = "wf_tis",
    backend: str = "jnp",
    value_range: int = 256,
    dtype=None,
) -> jnp.ndarray:
    """Paper's multi-GPU scheme: bins sharded over ``bin_axis``.

    Accepts an (h, w) frame or an (n, h, w) stack (one batched dispatch
    per shard).  Returns H ([n,] num_bins, h, w) sharded over bins, of
    ``dtype`` (``h_dtype`` of the image unless given: a band of a larger
    frame names the frame's).  The program is compiled once per mesh and
    geometry (``_bin_sharded_program``).
    """
    nshards = mesh.shape[bin_axis]
    if num_bins % nshards:
        raise ValueError(f"{num_bins} bins not divisible by {nshards} shards")
    dtype = h_dtype(*image.shape[-2:]) if dtype is None else np.dtype(dtype)
    fn = _bin_sharded_program(mesh, bin_axis, num_bins, image.ndim - 2,
                              method, backend, value_range, dtype)
    return fn(image)


@functools.lru_cache(maxsize=32)
def _bin_sharded_program(mesh, bin_axis, num_bins, lead, method, backend,
                         value_range, dtype):
    """``bin_sharded_ih``'s jitted shard_map, kept per (mesh, geometry):
    built per call, the shard_map would be traced and lowered again for
    every frame."""
    local_bins = num_bins // mesh.shape[bin_axis]

    def shard_fn(img):
        idx = bin_indices(img, num_bins, value_range)
        lo = lax.axis_index(bin_axis) * local_bins
        local_idx = jnp.where(
            (idx >= lo) & (idx < lo + local_bins), idx - lo, PAD_BIN
        )
        return integral_histogram(
            local_idx, local_bins, method=method, backend=backend,
            value_range=None, dtype=dtype,
        )

    return jax.jit(shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=P(),                       # frame(s) replicated
        out_specs=P(*([None] * lead), bin_axis, None, None),
        check_vma=False,
    ))


def spatial_sharded_ih(
    image: jnp.ndarray,
    num_bins: int,
    mesh: Mesh,
    *,
    row_axis: str = "data",
    bin_axis: str | None = None,
    method: str = "wf_tis",
    backend: str = "jnp",
    value_range: int = 256,
    scan_impl: str = "allgather",
    dtype=None,
) -> jnp.ndarray:
    """Beyond-paper: row strips over ``row_axis`` (+ optional bin sharding).

    Each device computes its strip's integral histogram, then the (b, w)
    bottom-boundary carries sweep down the mesh axis as an exclusive
    prefix — the WF-TiS column carry at ICI scale.

    Returns H (num_bins, h, w) sharded P(bin_axis, row_axis, None), of
    ``dtype`` (``h_dtype`` of the whole image unless given; every strip
    holds it).
    """
    d_rows = mesh.shape[row_axis]
    h = image.shape[0]
    dtype = h_dtype(*image.shape[-2:]) if dtype is None else np.dtype(dtype)
    if h % d_rows:
        raise ValueError(f"height {h} not divisible by {d_rows} row shards")
    local_bins = num_bins
    if bin_axis is not None:
        nb_shards = mesh.shape[bin_axis]
        if num_bins % nb_shards:
            raise ValueError(f"{num_bins} bins not divisible by {nb_shards}")
        local_bins = num_bins // nb_shards

    def shard_fn(img_strip):
        idx = bin_indices(img_strip, num_bins, value_range)
        if bin_axis is not None:
            lo = lax.axis_index(bin_axis) * local_bins
            idx = jnp.where(
                (idx >= lo) & (idx < lo + local_bins), idx - lo, PAD_BIN
            )
        local_h = integral_histogram(
            idx, local_bins, method=method, backend=backend, value_range=None,
            dtype=dtype,
        )
        carry = local_h[:, local_h.shape[1] - 1, :]         # (b_local, w)
        prefix = exclusive_axis_scan(carry, row_axis, d_rows, scan_impl)
        return local_h + prefix[:, None, :]

    in_spec = P(row_axis, None)
    out_spec = P(bin_axis, row_axis, None)
    fn = shard_map(
        shard_fn, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
        check_vma=False,
    )
    return fn(image)


def iter_banded_sharded_ih(
    image,
    num_bins: int,
    mesh: Mesh,
    *,
    sharding: str = "bin",
    band_h: int | None = None,
    memory_budget_bytes: int | None = None,
    bin_axis: str = "model",
    row_axis: str = "data",
    method: str = "wf_tis",
    backend: str = "jnp",
    value_range: int = 256,
    scan_impl: str = "allgather",
    prefetch: int = 0,
):
    """Band streaming composed with the sharded computations: each band
    runs bin- or spatially-sharded across the mesh, and the same (b, w)
    bottom-row carry threads between bands on top of the intra-band
    device carries.

    This is the paper-§4.6 scale story squared: ``spatial_sharded_ih``
    spreads one frame's H across a mesh; banding additionally bounds how
    much of it is ever live per device, so the 32 GB workload streams
    through a mesh whose total memory is far smaller.  ``sharding="bin"``
    accepts (h, w) or (n, h, w); ``"spatial"`` is single-frame and rounds
    the band height to the row-shard count.  Yields ``BandH`` chunks whose
    ``H`` stays sharded (``carry`` inherits the sharding — zero extra
    collectives for the band composition, it is one elementwise add).
    Assemble host-side (``np.asarray`` per band) when a materialized H is
    actually wanted; that doubles as the D2H spill.

    Band slices are staged with the ``band_input_sharding`` placement
    (replicated for bin sharding, row strips for spatial), so staging
    overlaps the sharded compute exactly like the single-device path and
    the between-band carry rides the shard layout end to end — no host
    round-trip anywhere in the carry chain.  ``prefetch >= 1`` keeps that
    many sharded slices staged ahead.
    """
    from repro.core import bands

    if sharding not in ("bin", "spatial"):
        raise ValueError(f"unknown sharding {sharding!r} (bin|spatial)")
    h, w = image.shape[-2:]
    row_multiple = 1
    if sharding == "spatial":
        if image.ndim != 2:
            raise ValueError("spatial banding is single-frame: (h, w)")
        row_multiple = mesh.shape[row_axis]
        if h % row_multiple:
            raise ValueError(
                f"height {h} not divisible by {row_multiple} row shards"
            )
    num_frames = 1 if image.ndim == 2 else image.shape[0]
    plan = bands.plan_bands(
        h, w, num_bins,
        band_h=band_h, memory_budget_bytes=memory_budget_bytes,
        num_frames=num_frames, row_multiple=row_multiple,
    )
    dtype = h_dtype(h, w)               # the frame's, not a band's

    def compute_fn(band_img, carry_in):
        if sharding == "bin":
            H_band = bin_sharded_ih(
                band_img, num_bins, mesh, bin_axis=bin_axis,
                method=method, backend=backend, value_range=value_range,
                dtype=dtype,
            )
        else:
            H_band = spatial_sharded_ih(
                band_img, num_bins, mesh, row_axis=row_axis,
                method=method, backend=backend, value_range=value_range,
                scan_impl=scan_impl, dtype=dtype,
            )
        # Band composition is an elementwise add: the carry carries
        # H_band's sharding, so no resharding or collective happens.
        return apply_carry(H_band, carry_in)

    staging = band_input_sharding(
        mesh, sharding, row_axis=row_axis, bin_axis=bin_axis,
        lead=image.ndim - 2,
    )
    return bands.iter_banded_ih(
        image, num_bins, plan=plan, compute_fn=compute_fn,
        device=staging, prefetch=prefetch,
    )


def distributed_region_query(H_sharded, rects, mesh, bin_axis="model"):
    """Region queries against a bin-sharded H: queries are local per bin
    shard; results concatenate over the bin axis (no collective needed —
    histograms over bins are embarrassingly parallel, paper §4.6).

    Thin dispatch over the unified H-representation protocol: ``ShardedH``
    (core/hsource.py) owns the shard_map fast path; this wrapper survives
    for callers that hold a raw sharded array.  Rank-polymorphic over
    frame batching like ``region_histogram``; returns
    (*H_lead, *rects_lead, b) with bins sharded over ``bin_axis``."""
    from repro.core.hsource import ShardedH

    return ShardedH(
        H_sharded, mesh, kind="bin", bin_axis=bin_axis
    ).region_histogram(rects)
