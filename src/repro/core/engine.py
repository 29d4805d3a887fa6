"""Plan/execute engine: one entry point over the four execution paths.

PRs 1-3 left callers hand-selecting among seven functions — monolithic
``kernels/ops.integral_histogram``, batched ``map_frames``, banded
``core/bands.py``, sharded ``core/distributed.py``, each with forked
analytics.  The paper treats these as ONE computation under different
resource mappings (§4's four kernel mappings, §4.4 double-buffering,
§4.6 multi-GPU bin mapping); this module makes that explicit:

    spec = WorkloadSpec(height=480, width=640, num_bins=32,
                        memory_budget_bytes=64 << 20)
    p = plan(spec)            # deterministic, inspectable, testable
    print(p.explain())        # why this method/backend/band/shard choice

``plan`` absorbs the decisions previously buried in call sites:

  * method/backend/tile resolution (``integral_histogram``'s "auto");
  * microbatch sizing (``auto_batch_size``, which now lives here —
    arXiv:1011.0235's adaptive batching; ``adaptive_microbatch=True``
    additionally lets the runtime retune the size online);
  * band planning + storage policy under ``memory_budget_bytes``
    (``bands.plan_bands`` — the auto-banding that lived inside
    ``integral_histogram``), following Ehsan et al.'s memory-efficient
    design (arXiv:1510.05138);
  * sharding layout when a mesh is given (bin sharding — the paper's
    multi-GPU scheme — when the bins divide the mesh axis, else spatial);
  * H's element type (``core/hdtype``): fp32 below 2**24 pixels, int32
    past it, where the fused and incremental paths (fp32 kernels) are
    not taken.

``HistogramEngine`` composes plan -> compute -> query: ``engine.run``
returns an ``HSource`` (core/hsource.py) plus the results of any queries,
and the representation behind it — dense array, band stream, host spill,
or mesh-sharded — is the planner's choice, not the caller's.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, Iterator

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import autotune
from repro.core import delta as delta_mod
from repro.core import hdtype
from repro.core.bands import (
    BandPlan,
    STORAGE_POLICIES,
    SpilledIH,
    plan_bands,
    validate_storage_policy,
)
from repro.core.hsource import (
    BandedH,
    DenseH,
    FusedRowsH,
    HSource,
    PrefetchedRowsH,
    ShardedH,
    counting_d2h,
    to_host,
)
from repro.core.region_query import row_lattices

REPRESENTATIONS = ("dense", "banded", "spilled", "sharded", "fused")

# Ehsan-style compute-vs-store bound: fuse the queries into the scan
# (never store H) when the request's corner-row union is at most this
# fraction of the frame height.  At 1/4 the fused row slab is at most
# per_frame_h_bytes / 4 and the early-exit scan skips whole bands, so
# fusion strictly dominates; past it, re-running the scan for follow-up
# queries starts losing to storing H once.
_FUSE_ROW_FRACTION = 4

# "auto" microbatching targets this per-dispatch output footprint — roughly
# an LLC's worth, the crossover between dispatch-bound and cache-bound
# regimes measured in benchmarks/bench_batched.py.
_AUTO_BATCH_BYTES = 4 << 20

# Dirty-row fraction above which an incremental update of a cached
# predecessor H stops paying and plan() recomputes (tunable per
# geometry via the "delta_threshold" priors key).
_DELTA_DIRTY_THRESHOLD = delta_mod.DEFAULT_DIRTY_THRESHOLD

# A run whose H takes more than this share of a device's memory holds one
# frame's H at a time: two frames in flight, each H with up to half an H
# of query transients (the window program's lattices), need three H's.
# Such a run takes one frame, and waits for the previous such run's
# answers (the last readers of its H) before it computes.  The paper's
# 8192x8192x128 frame over four v5e chips is 8.59 GB of a chip's
# 15.75 GB; a 1080p 32-bin frame is 265 MB.
_ONE_H_DEVICE_SHARE = 1 / 3


class PlanValidationError(ValueError):
    """A plan failed static validation (repro.analysis.plancheck) — the
    dispatch would have failed or silently produced invalid counts."""


def auto_batch_size(num_bins: int, h: int, w: int) -> int:
    """Frames per dispatch from the per-frame (num_bins, h, w) fp32 H
    footprint: ROI-scale frames are dispatch-bound and batch deep, full
    frames are cache-bound and stay near 1 (the adaptive-batching idea of
    Koppaka et al., arXiv:1011.0235, restated for XLA dispatch).  The
    planner owns this decision — it seeds every plan's ``microbatch``,
    and ``adaptive_microbatch`` plans use it as the starting size the
    runtime's online controller tunes from there."""
    per_frame_bytes = 4 * num_bins * h * w
    return max(1, min(16, _AUTO_BATCH_BYTES // per_frame_bytes))


# ---------------------------------------------------------------------------
# spec
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class WorkloadSpec:
    """Everything the planner needs to know about a request.

    ``num_frames`` is the request's batch/stream arity: frames per call
    for stacked requests, ``None`` for an open-ended stream (microbatch
    then comes purely from the per-frame footprint).  ``mesh`` switches
    to the multi-device mappings; ``memory_budget_bytes`` bounds the live
    H footprint (banding); ``storage`` selects a host spill policy
    (core/bands.py STORAGE_POLICIES) and implies the spilled
    representation."""

    height: int
    width: int
    num_bins: int = 32
    num_frames: int | None = 1
    dtype: str = "uint8"
    value_range: int = 256
    method: str = "wf_tis"
    backend: str = "auto"
    tile: int = 128
    bin_block: int = 8
    use_mxu: bool = True
    interpret: bool = False
    memory_budget_bytes: int | None = None
    storage: str | None = None
    adaptive_microbatch: bool = False   # retune batch size online
    mesh: object | None = None          # jax.sharding.Mesh
    sharding: str = "auto"              # "auto" | "bin" | "spatial"
    bin_axis: str = "model"
    row_axis: str = "data"
    # The corner-row union of the request's declared queries (sorted,
    # ascending, within [0, height)), or None when the queries are not
    # known up front.  This is the input to the Ehsan compute-vs-store
    # decision: a small-enough union lets plan() fuse the queries into
    # the scan and never store H.  engine.run() fills it automatically
    # from the queries' needed_rows declarations.
    query_rows: tuple[int, ...] | None = None
    # Fraction of frame rows in dirty bands vs a cached predecessor H
    # (core/delta.py diff_bands), or None when no predecessor is
    # available.  Small enough -> plan() chooses the incremental path:
    # update the cached H instead of recomputing.  engine.run(prev=...)
    # fills it automatically.
    dirty_fraction: float | None = None

    @property
    def per_frame_h_bytes(self) -> int:
        """The (num_bins, h, w) H footprint of one frame (fp32 and int32
        elements are both 4 bytes)."""
        return 4 * self.num_bins * self.height * self.width

    @property
    def h_dtype(self) -> np.dtype:
        """H's element type for this frame (``core/hdtype``)."""
        return hdtype.h_dtype(self.height, self.width)


# ---------------------------------------------------------------------------
# plan
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MeshLayout:
    """The planner's 2-D serving layout over a mesh (paper §4.6 run as a
    serving system): frame-parallel **replica groups** along every mesh
    axis the shard mapping does not consume, times bin/spatial sharding
    within each group.  ``explain()`` renders it, plancheck validates it
    (axes exist, disjoint, and the product covers the mesh), and
    ``serve.DistributedAnalyticsService`` executes it — one
    ``AnalyticsService`` per replica group over that group's submesh
    (``distributed.replica_meshes``)."""

    kind: str                        # "bin" | "spatial" (within-group)
    shard_axis: str                  # mesh axis the shard mapping uses
    shards_per_group: int            # devices per replica group
    replica_axes: tuple              # frame-parallel axes (may be empty)
    num_groups: int                  # product of the replica axes' sizes

    def describe(self) -> str:
        over = (" x ".join(repr(a) for a in self.replica_axes)
                or "(no free axis)")
        return (
            f"{self.num_groups} replica group(s) over {over} x "
            f"{self.kind} sharding over {self.shard_axis!r} "
            f"({self.shards_per_group} device(s)/group)"
        )


def choose_layout(mesh, kind: str, *, bin_axis: str = "model",
                  row_axis: str = "data") -> MeshLayout:
    """Derive the replica x shard layout from the mesh shape: the shard
    mapping consumes one axis (bins or row strips); every other axis is
    frame-parallel replication — the flax-imagenet scaling idiom
    (throughput = per-group rate x ``num_groups``) applied to frames
    instead of batch elements."""
    shape = dict(mesh.shape)
    shard_axis = bin_axis if kind == "bin" else row_axis
    replica_axes = tuple(a for a in mesh.axis_names if a != shard_axis)
    num_groups = 1
    for a in replica_axes:
        num_groups *= shape[a]
    return MeshLayout(
        kind=kind, shard_axis=shard_axis,
        shards_per_group=shape.get(shard_axis, 1),
        replica_axes=replica_axes, num_groups=num_groups,
    )


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """The planner's resolved decisions — inspectable and testable.

    ``representation`` names the HSource the engine will build; the
    remaining fields are the knobs the execute step passes down.  Plans
    are plain frozen dataclasses: equal specs produce equal plans
    (asserted in tests/test_engine.py)."""

    spec: WorkloadSpec
    representation: str        # dense | banded | spilled | sharded | fused
    method: str
    backend: str                        # resolved: "pallas" | "jnp"
    tile: int
    bin_block: int
    microbatch: int
    band_plan: BandPlan | None
    storage: str | None
    sharding: str | None                # None | "bin" | "spatial"
    microbatch_mode: str = "fixed"      # "fixed" | "adaptive"
    tuned: str | None = None            # autotune priors key, if applied
    incremental: bool = False           # update a cached predecessor H
    layout: MeshLayout | None = None    # replica x shard serving layout

    def explain(self, verdict=None) -> str:
        """Human-readable plan rationale (golden-snapshot tested).

        ``verdict`` (a ``repro.analysis.plancheck.PlanVerdict``, e.g.
        ``engine.last_verdict``) appends the static feasibility verdict
        to the rationale; the default output is unchanged."""
        s = self.spec
        per_frame = s.per_frame_h_bytes
        elem = "fp32" if s.h_dtype == np.float32 else str(s.h_dtype)
        lines = [
            "ExecutionPlan",
            f"  workload        : {s.height}x{s.width} {s.dtype} frames, "
            f"{s.num_bins} bins, "
            + ("open stream" if s.num_frames is None
               else f"{s.num_frames} frame(s)/request"),
            f"  full H          : {per_frame} B/frame "
            f"({per_frame / 2**20:.1f} MiB {elem})",
            f"  representation  : {self.representation}",
        ]
        if self.incremental:
            df = s.dirty_fraction or 0.0
            recomputed = int(round(df * per_frame))
            lines.append(
                f"  incremental     : update — dirty fraction {df:.2f} "
                f"within threshold; recompute ~{recomputed} B/frame, "
                f"reuse ~{per_frame - recomputed} B/frame of cached H"
            )
        if s.query_rows is not None:
            k = len(s.query_rows)
            nf = 1 if s.num_frames is None else s.num_frames
            if self.representation == "fused":
                rows_b = 4 * nf * s.num_bins * k * s.width
                lines.append(
                    f"  query fusion    : fuse — {k} corner row(s) "
                    f"({rows_b} B) << full H {per_frame} B; H never stored"
                )
            else:
                bound = s.height // _FUSE_ROW_FRACTION
                why = (
                    f"{k} corner row(s) exceed the fuse bound "
                    f"({bound} rows)"
                    if k > bound else
                    f"{k} corner row(s), but the request pins another path"
                )
                lines.append(
                    f"  query fusion    : store — {why}; fall back to "
                    f"{self.representation}"
                )
        lines += [
            f"  method/backend  : {self.method} / {self.backend}",
            f"  tile/bin_block  : {self.tile} / {self.bin_block}"
            + (f" (tuned prior {self.tuned})" if self.tuned else ""),
            f"  microbatch      : {self.microbatch} frame(s)/dispatch"
            + (" (adaptive start)" if self.microbatch_mode == "adaptive"
               else ""),
        ]
        if self.band_plan is None:
            budget = s.memory_budget_bytes
            why = ("no memory budget" if budget is None
                   else f"fits the {budget} B budget in one band")
            lines.append(f"  bands           : none ({why})")
        else:
            bp = self.band_plan
            lines.append(
                f"  bands           : {bp.num_bands} x {bp.band_h} rows "
                f"({bp.band_bytes} B/band <= "
                f"{s.memory_budget_bytes} B budget)"
            )
        if self.storage is None:
            lines.append(f"  storage         : device {elem}")
        else:
            bound = STORAGE_POLICIES[self.storage][1]
            lines.append(
                f"  storage         : host spill {self.storage} "
                f"(exact regions <= {bound} px)"
            )
        if self.sharding is None:
            lines.append("  sharding        : none")
        else:
            axis = s.bin_axis if self.sharding == "bin" else s.row_axis
            size = dict(s.mesh.shape)[axis]
            lines.append(
                f"  sharding        : {self.sharding} over mesh axis "
                f"{axis!r} ({size} devices)"
            )
            if self.layout is not None:
                lines.append(
                    f"  mesh layout     : {self.layout.describe()}"
                )
        if verdict is not None:
            lines.append("  " + verdict.render().replace("\n", "\n  "))
        return "\n".join(lines)


def _resolve_backend(backend: str, method: str) -> str:
    """The "auto" rule from kernels/ops.py, centralized."""
    from repro.kernels.ops import PALLAS_METHODS

    if backend == "auto":
        on_tpu = jax.default_backend() == "tpu"
        return "pallas" if on_tpu and method in PALLAS_METHODS else "jnp"
    if backend == "pallas" and method not in PALLAS_METHODS:
        raise ValueError(
            f"method {method!r} has no Pallas kernel (Pallas methods: "
            f"{sorted(PALLAS_METHODS)}); use backend='auto' or 'jnp'"
        )
    if backend not in ("pallas", "jnp"):
        raise ValueError(f"unknown backend {backend!r}")
    return backend


def plan(spec: WorkloadSpec) -> ExecutionPlan:
    """Deterministically map a workload onto an execution path.

    The decision tree (documented here because it IS the product):

      0. query_rows known and small (at most height/4 rows, no
         mesh/storage pinning another path, row slab within any budget)
         -> fused: compute ONLY those corner rows straight out of the
         scan, never store H (the Ehsan compute-vs-store decision,
         arXiv:1510.05138).
      1. mesh given        -> sharded.  "auto" picks the paper's bin
         mapping when num_bins divides the bin axis, else the spatial
         (row-strip) mapping.  A memory budget on top bands the stream
         (iter_banded_sharded_ih).
      2. budget given      -> band-plan the frame; > 1 band means the
         monolithic H breaks the budget: banded (stream) or spilled
         (host storage policy).  One band fits: dense.
      3. storage given     -> spilled even without a budget (single
         band), because the caller asked for host residency.
      4. otherwise         -> dense.

    Microbatch comes from the per-frame H footprint (auto_batch_size),
    capped by ``num_frames``; banded/spilled/fused paths stream whole
    requests, so their microbatch is the full request arity.

    A tuned-config priors file (core/autotune.py, opt-in via the
    ``REPRO_TUNED_CONFIGS`` environment variable) overrides the default
    tile/bin_block for geometries it has measured; the plan's ``tuned``
    field records the applied key.

    >>> p = plan(WorkloadSpec(height=64, width=64, num_bins=8))
    >>> p.representation, p.method
    ('dense', 'wf_tis')
    >>> fused = plan(WorkloadSpec(height=64, width=64, num_bins=8,
    ...                           query_rows=(15, 31)))
    >>> fused.representation
    'fused'
    >>> print(fused.explain().splitlines()[4])
      query fusion    : fuse — 2 corner row(s) (4096 B) << full H 131072 B; H never stored
    """
    backend = _resolve_backend(spec.backend, spec.method)
    if spec.method not in _known_methods():
        raise ValueError(f"unknown method {spec.method!r}")
    nf = spec.num_frames
    microbatch = auto_batch_size(spec.num_bins, spec.height, spec.width)
    if nf is not None:
        microbatch = max(1, min(microbatch, nf))

    tile, bin_block, tuned = spec.tile, spec.bin_block, None
    prior = autotune.prior_for(spec)
    if prior:
        tile = int(prior.get("tile", tile))
        bin_block = int(prior.get("bin_block", bin_block))
        tuned = autotune.config_key(spec.height, spec.width, spec.num_bins)

    # Decision "incremental" (the video-delta path, core/delta.py): a
    # cached predecessor H exists and few enough rows changed that
    # updating it (recompute dirty bands, carry-correct clean slabs
    # below) beats a full recompute.  The threshold is tunable per
    # geometry via the priors file ("delta_threshold").  Fusion is
    # skipped for incremental plans — it never stores H, so there is
    # nothing to update next frame; mesh plans reassemble cross-device
    # and are recomputed whole.
    # The fused and incremental kernels accumulate fp32: a frame whose H
    # must be int32 takes neither (plancheck refuses them there).
    fp32 = spec.h_dtype == np.float32
    incremental = False
    if spec.dirty_fraction is not None:
        if not 0.0 <= spec.dirty_fraction <= 1.0:
            raise ValueError(
                f"dirty_fraction must be within [0, 1], got "
                f"{spec.dirty_fraction}")
        threshold = float(
            (prior or {}).get("delta_threshold", _DELTA_DIRTY_THRESHOLD))
        incremental = (spec.mesh is None and fp32
                       and spec.dirty_fraction <= threshold)

    if spec.query_rows is not None and not incremental:
        rows = spec.query_rows
        k = len(rows)
        if not all(
            0 <= r < spec.height for r in rows
        ) or list(rows) != sorted(set(rows)):
            raise ValueError(
                f"query_rows must be sorted unique within "
                f"[0, {spec.height}), got {rows[:8]}"
            )
        nf_eff = 1 if nf is None else nf
        rows_bytes = 4 * nf_eff * spec.num_bins * k * spec.width
        fits = (
            spec.memory_budget_bytes is None
            or rows_bytes <= spec.memory_budget_bytes
        )
        if (
            0 < k <= spec.height // _FUSE_ROW_FRACTION
            and spec.storage is None
            and spec.mesh is None
            and fits
            and fp32
        ):
            return ExecutionPlan(
                spec=spec, representation="fused", method=spec.method,
                backend=backend, tile=tile, bin_block=bin_block,
                microbatch=(microbatch if nf is None else nf),
                band_plan=None, storage=None, sharding=None,
                microbatch_mode=(
                    "adaptive" if spec.adaptive_microbatch else "fixed"),
                tuned=tuned,
            )

    if spec.storage is not None:
        validate_storage_policy(spec.storage, spec.height, spec.width)
        if spec.mesh is not None:
            raise ValueError(
                "storage policies spill host-side; combine them with "
                "banding, not with a mesh"
            )

    band_frames = 1 if nf is None else nf
    sharding = None
    band_plan = None
    if spec.mesh is not None:
        mesh_shape = dict(spec.mesh.shape)
        sharding = spec.sharding
        if sharding == "auto":
            divisible = (
                spec.bin_axis in mesh_shape
                and spec.num_bins % mesh_shape[spec.bin_axis] == 0
            )
            sharding = "bin" if divisible else "spatial"
        if sharding not in ("bin", "spatial"):
            raise ValueError(
                f"unknown sharding {spec.sharding!r} (auto|bin|spatial)"
            )
        if sharding == "spatial" and nf is not None and nf != 1:
            # spatial_sharded_ih shards the *row* axis of a single (h, w)
            # frame; handing it an (n, h, w) stack would shard the frame
            # axis instead and silently return garbage.  (num_frames=None
            # — an open stream — is frames one at a time, which is fine;
            # map_frames itself rejects sharded plans with its own error.)
            raise ValueError(
                "spatial (row-strip) sharding is single-frame; this "
                f"request has num_frames={spec.num_frames} — make "
                f"num_bins divisible by the {spec.bin_axis!r} mesh axis "
                "for bin sharding, or submit frames one at a time"
            )
        row_multiple = (
            mesh_shape[spec.row_axis] if sharding == "spatial" else 1
        )
        if spec.memory_budget_bytes is not None:
            band_plan = plan_bands(
                spec.height, spec.width, spec.num_bins,
                memory_budget_bytes=spec.memory_budget_bytes,
                num_frames=band_frames, row_multiple=row_multiple,
            )
            if band_plan.num_bands == 1:
                band_plan = None
        return ExecutionPlan(
            spec=spec, representation="sharded", method=spec.method,
            backend=backend, tile=tile, bin_block=bin_block,
            microbatch=microbatch, band_plan=band_plan,
            storage=None, sharding=sharding,
            microbatch_mode=(
                "adaptive" if spec.adaptive_microbatch else "fixed"),
            tuned=tuned,
            layout=choose_layout(
                spec.mesh, sharding,
                bin_axis=spec.bin_axis, row_axis=spec.row_axis,
            ),
        )

    if spec.memory_budget_bytes is not None:
        band_plan = plan_bands(
            spec.height, spec.width, spec.num_bins,
            memory_budget_bytes=spec.memory_budget_bytes,
            num_frames=band_frames,
        )
        if band_plan.num_bands == 1 and spec.storage is None:
            band_plan = None
    elif spec.storage is not None:
        band_plan = plan_bands(spec.height, spec.width, spec.num_bins,
                               num_frames=band_frames)

    if spec.storage is not None:
        representation = "spilled"
    elif band_plan is not None:
        representation = "banded"
    else:
        representation = "dense"
    if representation in ("banded", "spilled") and nf is not None:
        microbatch = nf        # bands stream the whole request at once
    if representation == "dense" and spec.memory_budget_bytes is not None:
        # One band fits the budget, but the *dispatch* is microbatch
        # frames wide — cap it so the budget bounds the live H too.
        microbatch = max(
            1, min(microbatch,
                   spec.memory_budget_bytes // spec.per_frame_h_bytes)
        )

    return ExecutionPlan(
        spec=spec, representation=representation, method=spec.method,
        backend=backend, tile=tile, bin_block=bin_block,
        microbatch=microbatch, band_plan=band_plan,
        storage=spec.storage, sharding=None,
        microbatch_mode=("adaptive" if spec.adaptive_microbatch
                         else "fixed"),
        tuned=tuned, incremental=incremental,
    )


def _known_methods():
    from repro.core import scans

    return scans.METHODS


# ---------------------------------------------------------------------------
# queries
# ---------------------------------------------------------------------------
def _window_rows(source: HSource, window, stride) -> np.ndarray:
    """The corner rows a sliding-window field reads (empty if no fit)."""
    n_r, n_c, bot, top = source._window_lattices(window, stride)
    if n_r <= 0 or n_c <= 0:
        return np.zeros((0,), np.int64)
    return np.unique(np.concatenate([bot, top[top >= 0]]))


class _GeomView:
    """Just enough HSource surface for ``needed_rows`` declarations to
    run BEFORE any H exists — the planner asks the queries what rows
    they read from frame geometry alone (the fuse/store input)."""

    def __init__(self, height: int, width: int):
        self.height = height
        self.width = width

    _window_lattices = HSource._window_lattices


def _declared_rows(queries, height: int, width: int) -> tuple[int, ...] | None:
    """The corner-row union the request will read, from the queries'
    ``needed_rows`` declarations — or ``None`` when any query cannot
    declare its rows up front (then fusion is off the table)."""
    view = _GeomView(height, width)
    needs = []
    for q in queries:
        declare = getattr(q, "needed_rows", None)
        if declare is None:
            return None
        rows = declare(view)
        if rows is None:
            return None
        needs.append(np.asarray(rows))
    if not needs:
        return None
    rows = np.unique(np.concatenate(needs))
    rows = rows[(rows >= 0) & (rows < height)]
    if rows.size == 0:
        return None
    return tuple(int(r) for r in rows)


@dataclasses.dataclass(frozen=True)
class RegionQuery:
    """O(1) region histograms of ``rects`` (Eq. 2)."""

    rects: object

    def apply(self, source: HSource):
        return source.region_histogram(self.rects)

    def needed_rows(self, source: HSource) -> np.ndarray:
        from repro.core.region_query import corner_rows

        return corner_rows(np.asarray(self.rects))


@dataclasses.dataclass(frozen=True)
class SlidingWindowQuery:
    """Histograms of every (wh, ww) window at ``stride``."""

    window: tuple[int, int]
    stride: int = 1

    def apply(self, source: HSource):
        return source.sliding_window_histograms(self.window, self.stride)

    def needed_rows(self, source: HSource) -> np.ndarray:
        return _window_rows(source, self.window, self.stride)


@dataclasses.dataclass(frozen=True)
class LikelihoodQuery:
    """Per-position similarity of window histograms to ``target``."""

    target: object
    window: tuple[int, int]
    metric: object = None
    stride: int = 1

    def apply(self, source: HSource):
        from repro.core import distances

        metric = self.metric or distances.intersection
        return source.likelihood_map(
            self.target, self.window, metric, self.stride
        )

    def needed_rows(self, source: HSource) -> np.ndarray:
        return _window_rows(source, self.window, self.stride)


@dataclasses.dataclass(frozen=True)
class MultiScaleQuery:
    """Best-matching window across scales (rect, score, per-scale maps)."""

    target: object
    windows: tuple[tuple[int, int], ...]
    metric: object = None
    stride: int = 1

    def apply(self, source: HSource):
        from repro.core import distances

        metric = self.metric or distances.intersection
        return source.multi_scale_search(
            self.target, self.windows, metric, self.stride
        )

    def needed_rows(self, source: HSource) -> np.ndarray:
        rows = [_window_rows(source, wnd, self.stride)
                for wnd in self.windows]
        return (np.unique(np.concatenate(rows))
                if rows else np.zeros((0,), np.int64))


def _lattices(q, path: str) -> dict:
    """The ``lattices`` attribute of q's ``engine.query`` span, where the
    window program (``region_query._dense_windows``) answers q."""
    if path == "rows":
        return {}
    if isinstance(q, (SlidingWindowQuery, LikelihoodQuery)):
        windows = (q.window,)
    elif isinstance(q, MultiScaleQuery) and q.windows:
        windows = q.windows
    else:
        return {}
    return {"lattices": max(row_lattices(w, q.stride) for w in windows)}


@dataclasses.dataclass
class EngineResult:
    """What ``HistogramEngine.run`` hands back.  ``d2h_bytes`` counts what
    the run brought or hands to the host: every byte of H it pulled
    (``hsource.to_host``) and every byte of the answers."""

    plan: ExecutionPlan
    source: HSource
    results: list
    d2h_bytes: int = 0


def answer_bytes(results) -> int:
    """Bytes of every array in ``results`` (answers of any query kind)."""
    return sum(int(np.prod(np.shape(x), dtype=np.int64))
               * np.dtype(x.dtype).itemsize
               for x in jax.tree_util.tree_leaves(results)
               if hasattr(x, "dtype"))


@functools.lru_cache(maxsize=16)
def _device_memory_bytes(mesh) -> int | None:
    """One device's memory, where the backend reports it (not the CPU)."""
    device = (mesh.devices.flat[0] if mesh is not None
              else jax.devices()[0])
    return (device.memory_stats() or {}).get("bytes_limit")


def prefetch_rows(source: HSource, queries) -> PrefetchedRowsH | None:
    """Union the corner rows every query needs and fetch them in ONE
    ``rows()`` pass — a band stream runs once for the whole request.

    Returns ``None`` (caller falls back to per-query access) when any
    query cannot declare its rows up front or no rows are needed."""
    needs = []
    for q in queries:
        declare = getattr(q, "needed_rows", None)
        if declare is None:
            return None
        rows = declare(source)
        if rows is None:
            return None
        needs.append(np.asarray(rows))
    needed = (np.unique(np.concatenate(needs))
              if needs else np.zeros((0,), np.int64))
    if needed.size == 0:
        return None
    return PrefetchedRowsH(source, needed, source.rows(needed))


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------
class HistogramEngine:
    """Plan -> compute -> query facade.

    Holds the workload-independent configuration (bins, method prefs,
    budget, mesh); per-request geometry comes from the frames themselves:

        engine = HistogramEngine(num_bins=32,
                                 memory_budget_bytes=256 << 20)
        out = engine.run(frames, [RegionQuery(rects),
                                  LikelihoodQuery(target, (48, 48))])
        out.plan.explain()       # why this path
        out.results              # one entry per query

    ``engine.last_plan`` keeps the most recent plan for inspection.
    """

    def __init__(
        self,
        num_bins: int = 32,
        *,
        method: str = "wf_tis",
        backend: str = "auto",
        tile: int = 128,
        bin_block: int = 8,
        use_mxu: bool = True,
        interpret: bool = False,
        value_range: int = 256,
        memory_budget_bytes: int | None = None,
        storage: str | None = None,
        adaptive_microbatch: bool = False,
        mesh=None,
        sharding: str = "auto",
        bin_axis: str = "model",
        row_axis: str = "data",
    ):
        self.num_bins = num_bins
        self.method = method
        self.backend = backend
        self.tile = tile
        self.bin_block = bin_block
        self.use_mxu = use_mxu
        self.interpret = interpret
        self.value_range = value_range
        self.memory_budget_bytes = memory_budget_bytes
        self.storage = storage
        self.adaptive_microbatch = adaptive_microbatch
        self.mesh = mesh
        self.sharding = sharding
        self.bin_axis = bin_axis
        self.row_axis = row_axis
        self.last_plan: ExecutionPlan | None = None
        self.last_runtime = None        # FrameRuntime from map_frames
        self.last_verdict = None        # PlanVerdict from validate()
        self._last_answers = None       # answers of the last one-H run

    # -- planning -----------------------------------------------------------
    def spec_for(
        self, shape, dtype="uint8", *, num_frames: int | None = "infer"
    ) -> WorkloadSpec:
        """Derive the WorkloadSpec for an (h, w) / (n, h, w) request.

        ``num_frames`` overrides the inferred request arity — pass ``None``
        for an open-ended stream of (h, w) frames (map_frames does)."""
        shape = tuple(shape)
        if len(shape) == 2:
            nf = 1 if num_frames == "infer" else num_frames
        elif len(shape) == 3:
            nf = shape[0]
        else:
            raise ValueError(f"expected (h, w) or (n, h, w), got {shape}")
        return WorkloadSpec(
            height=shape[-2], width=shape[-1], num_bins=self.num_bins,
            num_frames=nf, dtype=str(dtype), value_range=self.value_range,
            method=self.method, backend=self.backend, tile=self.tile,
            bin_block=self.bin_block, use_mxu=self.use_mxu,
            interpret=self.interpret,
            memory_budget_bytes=self.memory_budget_bytes,
            storage=self.storage,
            adaptive_microbatch=self.adaptive_microbatch,
            mesh=self.mesh, sharding=self.sharding,
            bin_axis=self.bin_axis, row_axis=self.row_axis,
        )

    def plan_for(self, frames) -> ExecutionPlan:
        p = plan(self.spec_for(np.shape(frames),
                               getattr(frames, "dtype", "uint8")))
        self.last_plan = p
        return p

    # -- static validation --------------------------------------------------
    def validate(self, p: ExecutionPlan | None = None, queries=(),
                 *, deep: bool = False):
        """Statically verify a plan (``repro.analysis.plancheck``):
        H shapes/dtypes by abstract evaluation, the cross-band carry
        chain, peak memory vs budget, Pallas VMEM fit, and the
        count-validity bounds for ``queries`` — no dispatch runs.

        ``deep=True`` additionally proves the Pallas kernel contracts
        (``repro.analysis.kernelcheck``: carry happens-before under the
        declared grid order, exactly-once output coverage, in-bounds
        index maps, spec-derived VMEM fit) and merges them into the
        verdict; shallow is the default so existing rendered verdicts
        are unchanged.

        Returns the ``PlanVerdict`` (also kept as ``last_verdict``;
        ``explain()`` surfaces it).  ``run()``/``map_frames()`` call
        this with ``deep=True`` before their first dispatch and raise
        ``PlanValidationError`` on a rejected plan."""
        from repro.analysis.plancheck import check_plan

        if p is None:
            p = self.last_plan
        if p is None:
            raise ValueError("no plan to validate — pass one or run "
                             "plan_for() first")
        verdict = check_plan(p, tuple(queries), deep=deep)
        self.last_verdict = verdict
        return verdict

    def _validate_or_raise(self, p: ExecutionPlan, queries=()) -> None:
        verdict = self.validate(p, queries, deep=True)
        if not verdict.ok:
            raise PlanValidationError(
                "plan rejected by static validation:\n" + verdict.render()
            )

    def explain(self) -> str:
        """``last_plan.explain()`` with the ``last_verdict`` appended."""
        if self.last_plan is None:
            raise ValueError("no plan yet — run plan_for()/run() first")
        return self.last_plan.explain(self.last_verdict)

    # -- execution ----------------------------------------------------------
    def _kernel_kwargs(self, p: ExecutionPlan) -> dict:
        return dict(
            method=p.method, backend=p.backend, tile=p.tile,
            bin_block=p.bin_block, use_mxu=p.spec.use_mxu,
            interpret=p.spec.interpret, value_range=p.spec.value_range,
        )

    def compute_dense(self, frames):
        """The raw (..., b, h, w) H — jit-traceable (no HSource wrapper);
        what jitted consumers like FragmentTracker call."""
        from repro.kernels.ops import integral_histogram

        return integral_histogram(
            frames, self.num_bins, method=self.method, backend=self.backend,
            tile=self.tile, bin_block=self.bin_block, use_mxu=self.use_mxu,
            interpret=self.interpret, value_range=self.value_range,
        )

    def compute(self, frames, p: ExecutionPlan | None = None) -> HSource:
        """Execute the plan: frames -> the planned H representation."""
        from repro.core import bands as bands_mod
        from repro.kernels.ops import integral_histogram

        if p is None:
            p = self.plan_for(frames)
        kw = self._kernel_kwargs(p)

        if p.representation == "fused":
            from repro.kernels.ops import fused_corner_rows

            rows = np.asarray(p.spec.query_rows, np.int64)
            stats: dict = {}
            R = fused_corner_rows(
                frames, self.num_bins, rows, stats=stats, **kw,
            )
            source = FusedRowsH(
                rows, to_host(R),
                height=p.spec.height, width=p.spec.width,
            )
            source.last_fused_stats = stats
            return source

        if p.representation == "sharded":
            from repro.core import distributed

            s = p.spec
            if p.band_plan is not None:
                return BandedH(lambda: distributed.iter_banded_sharded_ih(
                    frames, self.num_bins, s.mesh, sharding=p.sharding,
                    band_h=p.band_plan.band_h, bin_axis=s.bin_axis,
                    row_axis=s.row_axis, method=p.method, backend=p.backend,
                    value_range=s.value_range,
                ))
            if p.sharding == "bin":
                H = distributed.bin_sharded_ih(
                    frames, self.num_bins, s.mesh, bin_axis=s.bin_axis,
                    method=p.method, backend=p.backend,
                    value_range=s.value_range,
                )
            else:
                H = distributed.spatial_sharded_ih(
                    frames, self.num_bins, s.mesh, row_axis=s.row_axis,
                    method=p.method, backend=p.backend,
                    value_range=s.value_range,
                )
            return ShardedH(H, s.mesh, kind=p.sharding,
                            bin_axis=s.bin_axis, row_axis=s.row_axis)

        if p.representation == "spilled":
            return bands_mod.spill_banded_ih(
                frames, self.num_bins, storage=p.storage,
                plan=p.band_plan, **kw,
            )

        if p.representation == "banded":
            return BandedH(lambda: bands_mod.iter_banded_ih(
                frames, self.num_bins, plan=p.band_plan, **kw,
            ))

        return DenseH(integral_histogram(frames, self.num_bins, **kw))

    # -- incremental video path (core/delta.py) -----------------------------
    def _delta_spans(self, spec: WorkloadSpec, prev_source: HSource):
        """The band granularity dirty detection and update share: a
        spilled source's own spans, the spec's budget bands otherwise,
        tile-high bands for a dense plan (no bands of its own)."""
        spans = getattr(prev_source, "spans", None)
        if spans is not None:
            return tuple(spans)
        nf = spec.num_frames
        band_frames = 1 if nf is None else nf
        if spec.memory_budget_bytes is not None:
            bp = plan_bands(
                spec.height, spec.width, spec.num_bins,
                memory_budget_bytes=spec.memory_budget_bytes,
                num_frames=band_frames,
            )
        else:
            # Dense plans have no bands of their own: detect finely (the
            # dense walk merges adjacent spans back into maximal runs, so
            # fine detection costs dispatches nothing and recomputes less)
            # while keeping at least ~8 bands on small frames.
            band_h = max(1, min(16, -(-spec.height // 8)))
            bp = plan_bands(spec.height, spec.width, spec.num_bins,
                            band_h=band_h)
        return bp.spans

    def _delta_report(self, frames, prev_frame, prev_source: HSource,
                      spec: WorkloadSpec):
        """Dirty-band detection against a cached predecessor, or None
        when the predecessor cannot seed an update (geometry/bin/shape
        mismatch, mesh plan, or a representation without the hook)."""
        if self.mesh is not None:
            return None
        if not hasattr(prev_source, "update_bands"):
            return None
        if np.shape(prev_frame) != np.shape(frames):
            return None
        if (prev_source.height, prev_source.width) != (spec.height,
                                                       spec.width):
            return None
        if prev_source.num_bins != self.num_bins:
            return None
        return delta_mod.diff_bands(
            prev_frame, frames, self._delta_spans(spec, prev_source))

    def _updatable(self, prev_source: HSource, p: ExecutionPlan) -> bool:
        """Does the cached representation match the plan well enough to
        take the update in place?  (Policy mismatch -> full recompute.)"""
        if p.representation == "dense":
            return isinstance(prev_source, DenseH)
        if p.representation == "banded":
            return (isinstance(prev_source, BandedH)
                    and prev_source._factory is not None)
        if p.representation == "spilled":
            return (isinstance(prev_source, SpilledIH)
                    and prev_source.storage == p.storage
                    and prev_source.carries is not None)
        return False

    def _update(self, prev_source: HSource, frames, report,
                p: ExecutionPlan) -> HSource:
        """Drive the cached source's ``update_bands`` hook with the
        plan's kernel dispatch and the delta_apply slab repair."""
        from repro.kernels import ops

        kw = self._kernel_kwargs(p)

        def recompute(band_rows, carry):
            return ops.integral_histogram(
                band_rows, self.num_bins, carry_in=carry, **kw)

        # Pallas plans route the broadcast correction through the
        # delta_apply kernel; jnp plans leave apply_fn unset so the
        # dense walk takes its fused single-dispatch assembly.
        apply_fn = None
        if p.backend == "pallas":
            def apply_fn(slab, d):
                return ops.delta_apply(
                    slab, d, backend=p.backend, tile=p.tile,
                    bin_block=p.bin_block, interpret=p.spec.interpret)

        return prev_source.update_bands(
            frames, report, recompute=recompute, apply_fn=apply_fn)

    def run(self, frames, queries: Iterable = (), *,
            prev=None) -> EngineResult:
        """Plan, compute, and answer ``queries`` in order.

        The queries shape the plan: their declared corner-row union goes
        into the spec as ``query_rows``, and when it is small the planner
        fuses the queries into the scan (``representation == "fused"``)
        so H is never stored.  Multiple queries against a band-streamed
        plan share ONE stream: the union of every query's corner rows is
        fetched in a single ``rows()`` pass (``prefetch_rows``) instead
        of re-running the banded kernel per query.

        ``prev=(prev_frame, prev_source)`` offers a predecessor frame
        and its H (an ``HSource`` or ``EngineResult``) to the planner:
        when few enough rows changed (core/delta.py), the plan goes
        ``incremental`` and the cached H is *updated* — only dirty
        bands recomputed, clean slabs below carry-corrected — instead
        of rebuilt, bit-exactly.  High motion, geometry/policy
        mismatches, and non-updatable representations (fused, sharded,
        single-shot banded) fall back to a full recompute.

        >>> import numpy as np
        >>> from repro.core.engine import HistogramEngine, RegionQuery
        >>> frame = np.arange(64, dtype=np.uint8).reshape(8, 8) % 4
        >>> eng = HistogramEngine(num_bins=4, value_range=4, backend="jnp")
        >>> out = eng.run(frame, [RegionQuery([[0, 0, 7, 7]])])
        >>> out.plan.representation      # 1 corner row -> query-fused
        'fused'
        >>> [float(v) for v in np.asarray(out.results[0]).ravel()]
        [16.0, 16.0, 16.0, 16.0]

        Each stage is a ``jax.profiler.TraceAnnotation`` inside
        ``engine.run`` (``representation``, ``incremental``):
        ``engine.plan``, ``engine.validate``, ``engine.update`` or
        ``engine.compute`` (the H dispatch, with a band stream's row
        prefetch), and one ``engine.query`` (``kind``, ``path``) per
        query: ``path`` is ``compiled`` where a dense H answered through
        the compiled query programs, ``sharded`` where a bin-sharded H
        answered through them (and a shard_map for regions), ``rows``
        where the corner-row protocol answered.  On those two paths a
        window, likelihood or multi-scale query's span also carries
        ``lattices``: how many strided row lattices the window program
        takes from H (1 shared, 2 split; the most over a search's
        scales).

        A run whose H takes more than ``_ONE_H_DEVICE_SHARE`` of a
        device's memory takes one frame, and first waits for the answers
        of the previous such run, so that frame's H is no longer read
        when this one is written.  The caller lets go of the previous
        source (``AnalyticsService`` with no cache does).
        """
        queries = list(queries)
        with TraceAnnotation("engine.run") as span, counting_d2h() as pulled:
            with TraceAnnotation("engine.plan"):
                p, prev_source, report = self._plan_run(frames, queries,
                                                        prev)
            span.set_metadata(representation=p.representation,
                              incremental=int(p.incremental))
            with TraceAnnotation("engine.validate"):
                self._validate_or_raise(p, queries)
            one_h = self._one_h_at_a_time(p)
            with TraceAnnotation("engine.update" if p.incremental
                                 else "engine.compute"):
                if one_h:
                    self._after_last_answers(frames)
                if p.incremental:
                    source = self._update(prev_source, frames, report, p)
                else:
                    source = self.compute(frames, p)
                target = source
                if len(queries) > 1 and isinstance(source, BandedH):
                    target = prefetch_rows(source, queries) or source
            # A dense or bin-sharded H answers through the compiled query
            # programs; the other representations through the corner-row
            # protocol.
            path = ("compiled" if isinstance(target, DenseH) else
                    "sharded" if isinstance(target, ShardedH)
                    and target.kind == "bin" else "rows")
            results = []
            for q in queries:
                with TraceAnnotation("engine.query", kind=type(q).__name__,
                                     path=path, **_lattices(q, path)):
                    results.append(q.apply(target))
            if one_h:
                self._last_answers = results
        return EngineResult(plan=p, source=source, results=results,
                            d2h_bytes=pulled[0] + answer_bytes(results))

    def _one_h_at_a_time(self, p: ExecutionPlan) -> bool:
        """Does one device's share of this run's H exceed
        ``_ONE_H_DEVICE_SHARE`` of its memory?  Never where the backend
        reports no memory (the CPU)."""
        limit = _device_memory_bytes(p.spec.mesh)
        if limit is None:
            return False
        shards = p.layout.shards_per_group if p.layout is not None else 1
        per_device = p.spec.per_frame_h_bytes // shards
        return per_device > _ONE_H_DEVICE_SHARE * limit

    def _after_last_answers(self, frames) -> None:
        """One frame's H at a time: refuse a stack, and wait until the
        last such run's answers are computed."""
        if np.ndim(frames) == 3 and np.shape(frames)[0] > 1:
            raise ValueError(
                f"{np.shape(frames)[0]} frames of {np.shape(frames)[1:]} "
                "need their H at once, and one frame's H takes more than "
                f"{_ONE_H_DEVICE_SHARE:.0%} of a device: submit them one "
                "at a time")
        last, self._last_answers = self._last_answers, None
        jax.block_until_ready(last)

    def _plan_run(self, frames, queries: list, prev):
        """``run``'s plan: the spec with the queries' corner-row union,
        dirty-band detection against ``prev``, and the (re-)plan.
        Returns (plan, predecessor source, dirty report)."""
        spec = self.spec_for(np.shape(frames),
                             getattr(frames, "dtype", "uint8"))
        rows = _declared_rows(queries, spec.height, spec.width)
        if rows is not None:
            spec = dataclasses.replace(spec, query_rows=rows)

        prev_source = report = None
        if prev is not None:
            prev_frame, prev_source = prev
            if isinstance(prev_source, EngineResult):
                prev_source = prev_source.source
            report = self._delta_report(frames, prev_frame, prev_source,
                                        spec)
            if report is not None:
                spec = dataclasses.replace(
                    spec, dirty_fraction=report.dirty_fraction)

        p = plan(spec)
        if p.incremental and not self._updatable(prev_source, p):
            # The cached representation cannot take the update (policy
            # mismatch, single-shot stream, ...): re-plan for a full
            # recompute rather than fail.
            spec = dataclasses.replace(spec, dirty_fraction=None)
            p = plan(spec)
        self.last_plan = p
        return p, prev_source, report

    # -- streaming ----------------------------------------------------------
    def runtime_for(self, p: ExecutionPlan, step=None, *, depth: int = 2,
                    device=None, **kw):
        """A ``FrameRuntime`` (core/runtime.py) configured from a plan:
        microbatch size and fixed/adaptive mode come from the planner,
        the in-flight window from the caller.  ``step`` defaults to the
        engine's dense compute lifted to the runtime signature."""
        from repro.core.runtime import FrameRuntime

        if step is None:
            step = FrameRuntime.stateless(self.compute_dense)
        return FrameRuntime(
            step, depth=depth, microbatch=p.microbatch,
            adaptive=(p.microbatch_mode == "adaptive"),
            device=device, **kw,
        )

    def map_frames(
        self, frames: Iterable, *, depth: int = 2, device=None
    ) -> Iterator[jax.Array]:
        """Stream per-frame H's with planner-chosen microbatching and
        ``depth`` dispatches in flight (paper §4.4 double-buffering) —
        the planner-driven successor of ``IntegralHistogram.map_frames``.
        An ``adaptive_microbatch`` engine hands the runtime the plan's
        size as a starting point and lets its online controller retune
        it from measured per-dispatch latency."""
        import itertools

        frames = iter(frames)
        try:
            first = next(frames)
        except StopIteration:
            return iter(())
        p = plan(self.spec_for(np.shape(first),
                               getattr(first, "dtype", "uint8"),
                               num_frames=None))
        self.last_plan = p
        if p.representation != "dense":
            # Streaming yields one dense (b, h, w) H per frame; executing
            # a banded/spilled/sharded plan here would silently ignore
            # the budget/mesh/storage the engine was configured with.
            raise ValueError(
                f"map_frames streams dense per-frame H's, but the plan "
                f"chose {p.representation!r} for {p.spec.height}x"
                f"{p.spec.width}x{p.spec.num_bins}; run each frame "
                "through engine.run()/compute() instead"
            )
        self._validate_or_raise(p)
        runtime = self.runtime_for(p, depth=depth, device=device)
        self.last_runtime = runtime
        return runtime.map_frames(itertools.chain([first], frames))
