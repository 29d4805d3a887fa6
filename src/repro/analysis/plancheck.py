"""Static plan validation: decide feasibility before a dispatch runs.

Ehsan et al.'s embedded integral-image work (arXiv:1510.05138) frames
compute-vs-store feasibility as a *decidable, up-front* check.  This
module is that check for an :class:`~repro.core.engine.ExecutionPlan`:
an abstract interpretation over the plan using ``jax.eval_shape`` (no
FLOPs, no device memory) plus the planner's own metadata.

``check_plan(plan, queries=())`` verifies, without executing:

  * **representation** — the plan's decision is internally consistent
    (known representation, mesh-axis divisibility for sharded plans);
  * **h-shape** — the kernel the plan selects produces the (..., b, h, w)
    H the representation expects, in the frame's element type
    (``core/hdtype.h_dtype``), via ``jax.eval_shape``;
  * **carry-chain** — every band height in the band plan accepts and
    re-emits the (..., b, w) bottom-row carry (again by eval_shape);
  * **memory-budget** — the peak *live* H footprint (microbatch x
    per-frame H for dense, the largest band for banded/spilled) fits
    ``memory_budget_bytes``;
  * **vmem-fit** — Pallas plans: the per-core VMEM working set
    (double-buffered in/out blocks + carry + scratch) fits the ~16 MiB
    budget, from the kernels' block specs;
  * **count-validity** — the §4.6 exactness regime: a frame of 2**24
    pixels or more needs an int32 H, which only ``wf_tis`` on the dense,
    banded or sharded representations accumulates; a plan that would
    hold such a frame in fp32 (a storage policy, the fused or
    incremental paths, another method) fails, and an int32 plan is
    held to the bound that keeps its in-tile fp32 scans exact
    (tile x padded width < 2**24);
  * **query-validity** — when queries are supplied: each query's
    largest region/window area fits the plan's exact-count bound
    (``uint16``: 65535 px of modular arithmetic);
  * **incremental** — video-delta plans only: the dirty-fraction
    decision input is present and in range, the representation can
    update in place, and the line prices the recomputed-vs-reused
    bytes per frame.

The structural verdict is cached per plan (plans are frozen,
hashable dataclasses), so ``HistogramEngine.validate`` — run before
every dispatch — costs a dict lookup after the first call.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import numpy as np

from repro.core.bands import STORAGE_POLICIES
from repro.core.hdtype import FP32_EXACT_COUNT, exact_count_bound

#: per-core VMEM budget the Pallas kernels must fit (bytes).
VMEM_LIMIT_BYTES = 16 << 20

_STATUS_ORDER = ("fail", "warn", "ok", "skip")


@dataclasses.dataclass(frozen=True)
class PlanCheck:
    """One verified property: ``status`` is ok | warn | fail | skip."""

    name: str
    status: str
    detail: str

    def render(self) -> str:
        return f"{self.status.upper():4s} {self.name:15s} {self.detail}"


@dataclasses.dataclass(frozen=True)
class PlanVerdict:
    """The static feasibility verdict for one plan."""

    checks: tuple[PlanCheck, ...]

    @property
    def ok(self) -> bool:
        return not self.failures

    @property
    def failures(self) -> tuple[PlanCheck, ...]:
        return tuple(c for c in self.checks if c.status == "fail")

    def render(self) -> str:
        head = "plan verdict    : " + (
            "OK (statically feasible)" if self.ok
            else f"REJECTED ({len(self.failures)} infeasible)"
        )
        lines = [head]
        lines += [f"  {c.render()}" for c in self.checks]
        return "\n".join(lines)

    def summary(self) -> str:
        counts = {s: 0 for s in _STATUS_ORDER}
        for c in self.checks:
            counts[c.status] = counts.get(c.status, 0) + 1
        return ", ".join(f"{v} {k}" for k, v in counts.items() if v)


# ---------------------------------------------------------------------------
# the checks
# ---------------------------------------------------------------------------
def _lead(plan) -> tuple:
    nf = plan.spec.num_frames
    return () if nf is None or nf == 1 else (int(nf),)


def _eval_kernel(plan, h: int, w: int, *, with_carry: bool):
    """``jax.eval_shape`` the plan's kernel on an abstract (lead, h, w)
    frame; returns the output ShapeDtypeStruct."""
    from repro.kernels.ops import integral_histogram

    s = plan.spec
    lead = _lead(plan)
    img = jax.ShapeDtypeStruct((*lead, h, w), np.dtype(s.dtype))
    carry = (
        jax.ShapeDtypeStruct((*lead, s.num_bins, w), s.h_dtype)
        if with_carry else None
    )

    def fn(image, carry_in):
        return integral_histogram(
            image, s.num_bins, method=plan.method, backend=plan.backend,
            tile=plan.tile, bin_block=plan.bin_block, use_mxu=s.use_mxu,
            interpret=s.interpret, value_range=s.value_range,
            carry_in=carry_in, dtype=s.h_dtype,
        )

    return jax.eval_shape(fn, img, carry)


def _check_representation(plan) -> PlanCheck:
    name = "representation"
    s = plan.spec
    known = ("dense", "banded", "spilled", "sharded", "fused")
    if plan.representation not in known:
        return PlanCheck(name, "fail",
                         f"unknown representation {plan.representation!r}")
    if plan.representation == "fused":
        if not s.query_rows:
            return PlanCheck(
                name, "fail",
                "fused plan without query_rows — nothing declares which "
                "corner rows to emit")
        return PlanCheck(
            name, "ok",
            f"fused: {len(s.query_rows)} corner row(s), H never stored")
    if plan.representation == "sharded":
        if s.mesh is None:
            return PlanCheck(name, "fail", "sharded plan without a mesh")
        shape = dict(s.mesh.shape)
        axis = s.bin_axis if plan.sharding == "bin" else s.row_axis
        size = shape.get(axis)
        if size is None:
            return PlanCheck(
                name, "fail",
                f"mesh has no {axis!r} axis (axes: {sorted(shape)})")
        extent = s.num_bins if plan.sharding == "bin" else s.height
        what = "num_bins" if plan.sharding == "bin" else "height"
        if extent % size != 0:
            return PlanCheck(
                name, "fail",
                f"{what}={extent} not divisible by mesh axis "
                f"{axis!r} ({size} devices)")
        return PlanCheck(
            name, "ok",
            f"sharded[{plan.sharding}]: {what}={extent} over "
            f"{size} devices")
    if plan.storage is not None and plan.representation != "spilled":
        return PlanCheck(
            name, "fail",
            f"storage policy {plan.storage!r} on a "
            f"{plan.representation!r} plan (must spill)")
    return PlanCheck(name, "ok", plan.representation)


def _eval_fused(plan):
    """``jax.eval_shape`` the fused corner-row dispatch."""
    from repro.kernels.ops import fused_corner_rows

    s = plan.spec
    lead = _lead(plan)
    img = jax.ShapeDtypeStruct((*lead, s.height, s.width), np.dtype(s.dtype))
    rows = np.asarray(s.query_rows, np.int64)

    def fn(image):
        return fused_corner_rows(
            image, s.num_bins, rows, method=plan.method,
            backend=plan.backend, tile=plan.tile, bin_block=plan.bin_block,
            use_mxu=s.use_mxu, interpret=s.interpret,
            value_range=s.value_range,
        )

    return jax.eval_shape(fn, img)


def _check_h_shape(plan) -> PlanCheck:
    name = "h-shape"
    s = plan.spec
    if plan.representation == "fused":
        try:
            out = _eval_fused(plan)
        except Exception as e:
            return PlanCheck(name, "fail", f"fused abstract eval: {e}")
        expect = (*_lead(plan), s.num_bins, len(s.query_rows), s.width)
        if tuple(out.shape) != expect:
            return PlanCheck(
                name, "fail",
                f"fused dispatch yields {tuple(out.shape)}, plan expects "
                f"the corner-row slab {expect}")
        if out.dtype != np.float32:
            return PlanCheck(
                name, "fail",
                f"fused dispatch yields {out.dtype}, engine arithmetic "
                "is fp32")
        return PlanCheck(
            name, "ok",
            f"corner-row slab {expect} float32 via fused "
            f"{plan.method}/{plan.backend}")
    try:
        out = _eval_kernel(plan, s.height, s.width, with_carry=False)
    except Exception as e:  # abstract eval surfaces kernel/shape errors
        return PlanCheck(name, "fail", f"kernel abstract eval: {e}")
    expect = (*_lead(plan), s.num_bins, s.height, s.width)
    if tuple(out.shape) != expect:
        return PlanCheck(
            name, "fail",
            f"kernel yields {tuple(out.shape)}, plan expects {expect}")
    if out.dtype != s.h_dtype:
        return PlanCheck(
            name, "fail",
            f"kernel yields {out.dtype}, the frame's H is {s.h_dtype}")
    return PlanCheck(
        name, "ok", f"{expect} {out.dtype} via {plan.method}/{plan.backend}")


def _check_carry_chain(plan) -> PlanCheck:
    name = "carry-chain"
    s = plan.spec
    if plan.band_plan is None:
        return PlanCheck(name, "skip", "single-band plan has no carry")
    heights = sorted({r1 - r0 for r0, r1 in plan.band_plan.spans})
    carry_shape = (*_lead(plan), s.num_bins, s.width)
    for bh in heights:
        try:
            out = _eval_kernel(plan, bh, s.width, with_carry=True)
        except Exception as e:
            return PlanCheck(
                name, "fail",
                f"{bh}-row band rejects the {carry_shape} carry: {e}")
        band_expect = (*_lead(plan), s.num_bins, bh, s.width)
        if tuple(out.shape) != band_expect:
            return PlanCheck(
                name, "fail",
                f"{bh}-row band yields {tuple(out.shape)}, "
                f"expected {band_expect}")
        # next carry = H_band[..., -1, :]; shape follows from band_expect
        emitted = band_expect[:-2] + band_expect[-1:]
        if emitted != carry_shape:
            return PlanCheck(
                name, "fail",
                f"{bh}-row band re-emits carry {emitted}, "
                f"chain needs {carry_shape}")
    return PlanCheck(
        name, "ok",
        f"{plan.band_plan.num_bands} bands (heights {heights}) thread a "
        f"{carry_shape} carry")


def _check_memory_budget(plan) -> PlanCheck:
    name = "memory-budget"
    s = plan.spec
    budget = s.memory_budget_bytes
    if budget is None:
        return PlanCheck(name, "skip", "no memory budget declared")
    if plan.representation == "fused":
        k = len(s.query_rows)
        nf = 1 if s.num_frames is None else s.num_frames
        live = 4 * nf * s.num_bins * k * s.width
        what = f"fused corner-row slab ({k} row(s))"
    elif plan.band_plan is not None:
        live = plan.band_plan.band_bytes
        what = f"largest band ({plan.band_plan.band_h} rows)"
    else:
        live = plan.microbatch * s.per_frame_h_bytes
        what = f"microbatch of {plan.microbatch} frame(s)"
    if live > budget:
        return PlanCheck(
            name, "fail",
            f"{what} holds {live} B of live H > {budget} B budget")
    return PlanCheck(name, "ok", f"{what}: {live} B <= {budget} B budget")


def _vmem_estimate(plan) -> tuple[int, str] | None:
    """Per-core VMEM bytes for the plan's Pallas kernel, or ``None`` for
    methods without one.  Delegates to the kernel's own
    :class:`~repro.kernels.specs.KernelSpec` via ``kernelcheck`` — ONE
    model, priced from the same metadata the deep kernel checks verify,
    instead of the hand-maintained per-method formula this function used
    to duplicate (which had already drifted: it omitted the
    double-buffering of the carry operand)."""
    from repro.analysis import kernelcheck

    return kernelcheck.vmem_required(
        kernelcheck.plan_method(plan), kernelcheck.plan_geometry(plan))


def _check_vmem_fit(plan) -> PlanCheck:
    name = "vmem-fit"
    if plan.backend != "pallas":
        return PlanCheck(name, "skip", f"{plan.backend} backend uses HBM")
    if plan.spec.interpret:
        return PlanCheck(name, "skip", "interpret mode runs on host")
    est = _vmem_estimate(plan)
    if est is None:
        return PlanCheck(
            name, "skip", f"no VMEM model for method {plan.method!r}")
    nbytes, detail = est
    if nbytes > VMEM_LIMIT_BYTES:
        return PlanCheck(
            name, "fail",
            f"~{nbytes} B ({detail}) exceeds the {VMEM_LIMIT_BYTES} B "
            f"per-core VMEM budget — shrink tile/bin_block")
    return PlanCheck(
        name, "ok", f"~{nbytes} B of {VMEM_LIMIT_BYTES} B ({detail})")


def _plan_exact_bound(plan) -> int:
    """Largest region pixel count queries on this plan read back exactly."""
    if plan.storage is not None:
        return int(STORAGE_POLICIES[plan.storage][1])
    return exact_count_bound(plan.spec.h_dtype)


def _fp32_path(plan) -> str | None:
    """What of the plan holds H in fp32 whatever the frame's size, or
    ``None`` when the plan follows the frame's element type."""
    if plan.storage is not None:
        return f"the {plan.storage} spill (bands and carries held in fp32)"
    if plan.representation == "fused":
        return "the fused corner-row kernel (fused_rows accumulates fp32)"
    if plan.incremental:
        return "the incremental update (delta_apply repairs H in fp32)"
    if plan.method != "wf_tis":
        return f"the {plan.method} scan (only wf_tis accumulates int32)"
    return None


def _check_count_validity(plan) -> PlanCheck:
    name = "count-validity"
    s = plan.spec
    px = s.height * s.width
    if px < FP32_EXACT_COUNT:
        if plan.storage is not None:
            return PlanCheck(
                name, "ok",
                f"{plan.storage} spill: regions <= "
                f"{_plan_exact_bound(plan)} px exact (modular arithmetic)")
        return PlanCheck(
            name, "ok", f"{px}-px frame within fp32 exact range")
    fp32 = _fp32_path(plan)
    if fp32 is not None:
        return PlanCheck(
            name, "fail",
            f"{s.height}x{s.width} frame accumulates up to {px} counts, "
            f"beyond the fp32 exact range {FP32_EXACT_COUNT}, and {fp32} "
            "would round them — plan the dense, banded or sharded wf_tis "
            "path, whose H is int32 at this size")
    strip = plan.tile * (-(-s.width // plan.tile) * plan.tile)
    if strip >= FP32_EXACT_COUNT:
        return PlanCheck(
            name, "fail",
            f"the in-tile fp32 scans reach tile x padded width = {strip}, "
            "not below 2**24")
    return PlanCheck(
        name, "ok",
        f"{px}-px frame in an int32 H; in-tile fp32 scans <= {strip} "
        "< 2**24")


def _check_incremental(plan) -> PlanCheck:
    """Price and validate an incremental (video-delta) plan: the
    dirty-fraction decision input must be present and sane, and the
    representation must expose the ``update_bands`` hook (fused plans
    never store H; sharded plans re-shard per frame)."""
    name = "incremental"
    s = plan.spec
    df = s.dirty_fraction
    if df is None:
        return PlanCheck(
            name, "fail",
            "incremental plan without a dirty_fraction — nothing measured "
            "the frame delta that justifies an update")
    if not 0.0 <= df <= 1.0:
        return PlanCheck(
            name, "fail", f"dirty_fraction {df} outside [0, 1]")
    if plan.representation in ("fused", "sharded"):
        return PlanCheck(
            name, "fail",
            f"{plan.representation!r} representation cannot update in "
            "place (no cached H to repair)")
    per_frame = s.per_frame_h_bytes
    recomputed = int(round(df * per_frame))
    return PlanCheck(
        name, "ok",
        f"dirty fraction {df:.2f}: recompute ~{recomputed} B/frame, "
        f"reuse ~{per_frame - recomputed} B/frame of cached H")


def _check_layout(plan) -> PlanCheck:
    """Validate the planner's replica x shard mesh layout: the shard
    axis and every replica axis must exist in the mesh, be disjoint, and
    their product must cover the whole device set — a layout that
    silently strands devices would report phantom scaling headroom."""
    name = "mesh-layout"
    s = plan.spec
    lay = plan.layout
    if plan.representation != "sharded" or s.mesh is None:
        return PlanCheck(
            name, "fail",
            f"layout on a {plan.representation!r} plan without a mesh")
    shape = dict(s.mesh.shape)
    if lay.shard_axis not in shape:
        return PlanCheck(
            name, "fail",
            f"shard axis {lay.shard_axis!r} not in mesh axes "
            f"{tuple(shape)}")
    if lay.kind != plan.sharding:
        return PlanCheck(
            name, "fail",
            f"layout kind {lay.kind!r} disagrees with plan sharding "
            f"{plan.sharding!r}")
    if lay.shard_axis in lay.replica_axes:
        return PlanCheck(
            name, "fail",
            f"shard axis {lay.shard_axis!r} doubles as a replica axis")
    missing = [a for a in lay.replica_axes if a not in shape]
    if missing:
        return PlanCheck(
            name, "fail", f"replica axes {missing} not in mesh")
    mesh_devices = 1
    for v in shape.values():
        mesh_devices *= v
    covered = lay.num_groups * lay.shards_per_group
    if covered != mesh_devices or lay.shards_per_group != shape[lay.shard_axis]:
        return PlanCheck(
            name, "fail",
            f"layout covers {covered} of {mesh_devices} mesh devices")
    return PlanCheck(name, "ok", lay.describe())


def _query_area(query) -> int | None:
    """Largest region/window pixel area a query touches, else None."""
    rects = getattr(query, "rects", None)
    if rects is not None:
        r = np.asarray(rects).reshape(-1, 4)
        if r.size == 0:
            return 0
        return int(((r[:, 2] - r[:, 0] + 1)
                    * (r[:, 3] - r[:, 1] + 1)).max())
    windows = getattr(query, "windows", None)
    if windows is not None:
        return max((int(wh) * int(ww) for wh, ww in windows), default=0)
    window = getattr(query, "window", None)
    if window is not None:
        wh, ww = window
        return int(wh) * int(ww)
    return None


def _check_queries(plan, queries) -> PlanCheck:
    name = "query-validity"
    bound = _plan_exact_bound(plan)
    worst = 0
    opaque = 0
    for q in queries:
        area = _query_area(q)
        if area is None:
            opaque += 1
            continue
        if area > bound:
            return PlanCheck(
                name, "fail",
                f"{type(q).__name__} touches a {area}-px region, beyond "
                f"the plan's exact-count bound {bound} px"
                + (f" ({plan.storage} modular arithmetic wraps)"
                   if plan.storage else f" ({plan.spec.h_dtype} H)"))
        worst = max(worst, area)
    detail = f"largest region {worst} px <= {bound} px bound"
    if opaque:
        detail += f" ({opaque} query(ies) undeclared — checked at run time)"
    return PlanCheck(name, "ok", detail)


# ---------------------------------------------------------------------------
# deep checks: kernelcheck's grid/carry/coverage proofs, as PlanChecks
# ---------------------------------------------------------------------------
#: kernelcheck check name -> the PlanCheck name it merges under.
_KERNEL_CHECK_NAMES = {
    "carry-order": "kernel-carry",
    "out-coverage": "kernel-coverage",
    "in-bounds": "kernel-bounds",
    "vmem-fit": "kernel-vmem",
}


@functools.lru_cache(maxsize=256)
def _kernel_checks(plan) -> tuple[PlanCheck, ...]:
    """The four kernelcheck properties for the plan's Pallas kernel,
    folded across passes (a multi-pass method fails a property when any
    pass does).  One skip line when the plan dispatches no Pallas
    kernel."""
    from repro.analysis import kernelcheck

    if plan.backend != "pallas":
        return (PlanCheck(
            "kernel-checks", "skip",
            f"{plan.backend} backend dispatches no Pallas kernel"),)
    geom = kernelcheck.plan_geometry(plan)
    try:
        verdict = kernelcheck.check_method(
            kernelcheck.plan_method(plan), geom)
    except KeyError as e:
        return (PlanCheck(
            "kernel-checks", "fail",
            f"pallas plan without a KernelSpec contract: {e}"),)
    merged = []
    for kname, pname in _KERNEL_CHECK_NAMES.items():
        per_pass = [c for c in verdict.checks if c.name == kname]
        bad = [c for c in per_pass if not c.ok]
        if bad:
            merged.append(PlanCheck(pname, "fail", "; ".join(
                f"[{c.kernel}] {c.detail}" for c in bad)))
        else:
            merged.append(PlanCheck(pname, "ok", "; ".join(
                f"[{c.kernel}] {c.detail}" for c in per_pass)))
    return tuple(merged)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=256)
def _structural_checks(plan) -> tuple[PlanCheck, ...]:
    checks = (
        _check_representation(plan),
        _check_h_shape(plan),
        _check_carry_chain(plan),
        _check_memory_budget(plan),
        _check_vmem_fit(plan),
        _check_count_validity(plan),
    )
    # Only incremental plans carry the extra line, so rendered verdicts
    # for every pre-existing plan stay byte-identical.
    if getattr(plan, "incremental", False):
        checks = checks + (_check_incremental(plan),)
    # Same pattern for the mesh layout: only sharded plans carry one.
    if getattr(plan, "layout", None) is not None:
        checks = checks + (_check_layout(plan),)
    return checks


def check_plan(plan, queries=(), *, deep: bool = False) -> PlanVerdict:
    """Statically verify a plan (and optionally its queries).

    ``deep=True`` additionally runs ``repro.analysis.kernelcheck``'s
    symbolic-grid proofs (carry happens-before, output coverage,
    in-bounds index maps, spec-derived VMEM fit) for Pallas plans and
    merges them into the verdict.  The default stays shallow so
    ``validate()``'s rendered verdict is unchanged for existing callers;
    the engine's pre-dispatch gate (``_validate_or_raise``) always runs
    deep.

    Structural and deep checks are cached per plan; the query check is
    cheap arithmetic computed fresh (queries carry unhashable arrays)."""
    checks = _structural_checks(plan)
    if deep:
        checks = checks + _kernel_checks(plan)
    queries = tuple(queries) if not isinstance(queries, tuple) else queries
    if queries:
        checks = checks + (_check_queries(plan, queries),)
    return PlanVerdict(checks=checks)
