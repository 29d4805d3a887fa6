"""Static verification of the Pallas kernels' grid/carry/VMEM contracts.

The WF-TiS wavefront is only *correct* because each tile's carries are
produced by its up/left predecessors under the sequential grid walk
(arXiv:1711.01919 §4.2-4.5), and only *fast* because the tile +
bin-block working set fits per-core VMEM (the memory-budget framing of
Ehsan et al., arXiv:1510.05142).  Those invariants used to live in
comments in ``kernels/wf_tis.py``/``cw_tis.py`` and a hand-maintained
VMEM formula in ``plancheck.py``; this module proves them from the
declarative :class:`~repro.kernels.specs.KernelSpec` each kernel module
exports next to its ``pallas_call``.

Four checks, each evaluated by symbolically enumerating the grid in the
spec's declared sequential order (last dimension innermost — Pallas TPU
execution order):

  * **carry-order** — every VMEM-scratch value a grid step consumes was
    last written by exactly the producer step the spec declares.  This
    is strictly stronger than "written earlier": a shared scratch cell
    overwritten every step (cw_tis's single strip carry) is "written
    earlier" under ANY grid order, but only the declared order makes
    the *last* writer the declared producer.  Catches the
    grid-dimension-reordering bug class — cw_tis pass 2 deliberately
    swaps ``ntw``/``nth`` and the verifier proves that order rather
    than assuming row-major.
  * **out-coverage** — the out-spec index maps write every output block
    exactly once over the whole grid.  A gap is garbage rows in the
    result; an overlap is a write race on backends that run grid steps
    concurrently (the GPU wavefront this kernel family comes from).
  * **in-bounds** — every in/out block index stays inside the padded
    operand at every grid point (block-index units: ``0 <= i`` and
    ``(i + 1) * block <= shape`` per dimension).
  * **vmem-fit** — the double-buffered operand blocks + persistent
    scratch fit the 16 MiB per-core budget, derived from the spec
    (``KernelSpec.vmem_bytes``).  ``plancheck``'s vmem-fit check
    delegates here, so the engine-level and kernel-level estimates
    cannot diverge (a conformance test asserts equality anyway).

Enumeration runs on ``KernelGeometry.canonical()`` — every grid
dimension clamped to 3 blocks and the frame count pinned to 2 (the
frame-boundary carry resets need a second frame to exercise) — so the
walk is O(100) steps at any frame size; vmem-fit uses the real
geometry.  Entry points: ``check_method`` (one method, one geometry),
``check_kernels`` (the whole registry — the ``--check-kernels`` CLI),
and ``plan_geometry``/``vmem_required`` (the plancheck bridge).
"""

from __future__ import annotations

import dataclasses
import functools

from repro.kernels.specs import KernelGeometry, KernelSpec

#: per-core VMEM budget the kernels must fit (bytes).
VMEM_LIMIT_BYTES = 16 << 20

#: how many violations a failing check reports before truncating.
_MAX_VIOLATIONS = 3


@dataclasses.dataclass(frozen=True)
class KernelCheck:
    """One verified kernel property: ``status`` is ok | fail."""

    kernel: str                 # KernelSpec name, e.g. "cw_tis/vscan"
    name: str                   # carry-order | out-coverage | in-bounds | vmem-fit
    status: str
    detail: str

    @property
    def ok(self) -> bool:
        return self.status == "ok"

    def render(self) -> str:
        return (f"{self.status.upper():4s} {self.name:12s} "
                f"[{self.kernel}] {self.detail}")

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class KernelVerdict:
    """All checks for one method at one geometry."""

    method: str
    geometry: KernelGeometry
    checks: tuple[KernelCheck, ...]

    @property
    def ok(self) -> bool:
        return not self.failures

    @property
    def failures(self) -> tuple[KernelCheck, ...]:
        return tuple(c for c in self.checks if c.status == "fail")

    def render(self) -> str:
        g = self.geometry
        head = (
            f"kernelcheck {self.method} @ {g.n}x{g.h}x{g.w}/{g.num_bins} "
            f"bins (tile {g.tile}, bin_block {g.bin_block}): "
            + ("OK" if self.ok else f"REJECTED ({len(self.failures)})")
        )
        return "\n".join([head] + [f"  {c.render()}" for c in self.checks])

    def to_json(self) -> dict:
        g = self.geometry
        return {
            "method": self.method,
            "geometry": {
                "n": g.n, "h": g.h, "w": g.w, "num_bins": g.num_bins,
                "tile": g.tile, "bin_block": g.bin_block,
            },
            "ok": self.ok,
            "checks": [c.to_json() for c in self.checks],
        }


# ---------------------------------------------------------------------------
# grid enumeration
# ---------------------------------------------------------------------------
def iter_grid(spec: KernelSpec):
    """Grid points as {dim: index} dicts, in the spec's declared
    sequential order (last dimension innermost)."""
    names = spec.dim_names
    sizes = spec.grid_sizes
    total = 1
    for s in sizes:
        total *= s
    for flat in range(total):
        rev = []
        rem = flat
        for size in reversed(sizes):
            rev.append(rem % size)
            rem //= size
        yield dict(zip(names, reversed(rev)))


def _step_key(spec: KernelSpec, g) -> tuple[int, ...]:
    """A grid point as a comparable tuple in grid order."""
    return tuple(g[name] for name in spec.dim_names)


def _fmt_point(g) -> str:
    return "(" + ", ".join(f"{k}={v}" for k, v in g.items()) + ")"


# ---------------------------------------------------------------------------
# check (1): carry happens-before
# ---------------------------------------------------------------------------
def check_carry_order(spec: KernelSpec) -> KernelCheck:
    """Walk the grid in declared order; every declared scratch read must
    see a value whose *last* writer is exactly the declared producer."""
    name = "carry-order"
    if spec.carry_reads is None:
        return KernelCheck(spec.name, name, "ok",
                           "no scratch carries declared")
    last_writer: dict[tuple, tuple[int, ...]] = {}
    violations: list[str] = []
    steps = 0
    edges = 0
    for g in iter_grid(spec):
        steps += 1
        here = _step_key(spec, g)
        for cell, producer in spec.carry_reads(g):
            edges += 1
            want = tuple(producer[n] for n in spec.dim_names)
            got = last_writer.get(cell)
            if got is None:
                violations.append(
                    f"step {_fmt_point(g)} reads scratch cell {cell!r} "
                    f"before any write (declared producer "
                    f"{tuple(want)})")
            elif got != want:
                violations.append(
                    f"step {_fmt_point(g)} reads scratch cell {cell!r} "
                    f"expecting the value from step {want}, but the "
                    f"last write under this grid order was at {got} — "
                    "the declared sequential order does not realize "
                    "the carry chain")
            if len(violations) >= _MAX_VIOLATIONS:
                return KernelCheck(
                    spec.name, name, "fail",
                    "; ".join(violations) + " ... (truncated)")
        if spec.carry_writes is not None:
            for cell in spec.carry_writes(g):
                last_writer[cell] = here
    if violations:
        return KernelCheck(spec.name, name, "fail", "; ".join(violations))
    order = " > ".join(spec.dim_names)
    return KernelCheck(
        spec.name, name, "ok",
        f"{edges} carry edge(s) proven over {steps} sequential steps "
        f"(grid order {order}, last innermost)")


# ---------------------------------------------------------------------------
# check (2): output coverage / race-freedom
# ---------------------------------------------------------------------------
def check_out_coverage(spec: KernelSpec) -> KernelCheck:
    """Every out-spec must tile its output exactly once: the multiset of
    block indices over the grid equals the output's block grid."""
    name = "out-coverage"
    problems: list[str] = []
    for op in spec.out_specs:
        blocks_per_dim = []
        for dim, (size, blk) in enumerate(zip(op.shape, op.block)):
            if size % blk:
                problems.append(
                    f"{op.name}: dim {dim} size {size} not a multiple "
                    f"of block {blk}")
            blocks_per_dim.append(max(1, size // blk))
        seen: dict[tuple, int] = {}
        for g in iter_grid(spec):
            idx = tuple(op.index_map(*_step_key(spec, g)))
            seen[idx] = seen.get(idx, 0) + 1
        total = 1
        for b in blocks_per_dim:
            total *= b
        overlaps = {i: c for i, c in seen.items() if c > 1}
        gaps = total - len(seen)
        if overlaps:
            worst = sorted(overlaps.items())[:_MAX_VIOLATIONS]
            problems.append(
                f"{op.name}: {len(overlaps)} output block(s) written "
                f"more than once (a write race on concurrent-grid "
                f"backends), e.g. "
                + ", ".join(f"{i} x{c}" for i, c in worst))
        if gaps > 0:
            missing = [
                i for i in _iter_block_grid(blocks_per_dim)
                if i not in seen
            ][:_MAX_VIOLATIONS]
            problems.append(
                f"{op.name}: {gaps} of {total} output block(s) never "
                f"written (garbage rows), e.g. {missing}")
    if problems:
        return KernelCheck(spec.name, name, "fail", "; ".join(problems))
    covered = ", ".join(
        f"{op.name}: {_num_blocks(op)} blocks exactly once"
        for op in spec.out_specs)
    return KernelCheck(spec.name, name, "ok", covered)


def _num_blocks(op) -> int:
    total = 1
    for size, blk in zip(op.shape, op.block):
        total *= max(1, size // blk)
    return total


def _iter_block_grid(blocks_per_dim):
    idx = [0] * len(blocks_per_dim)
    while True:
        yield tuple(idx)
        for d in range(len(idx) - 1, -1, -1):
            idx[d] += 1
            if idx[d] < blocks_per_dim[d]:
                break
            idx[d] = 0
        else:
            return


# ---------------------------------------------------------------------------
# check (3): in-bounds index maps
# ---------------------------------------------------------------------------
def check_in_bounds(spec: KernelSpec) -> KernelCheck:
    """Every operand's block index must stay inside the padded operand
    for all grid points: ``0 <= i`` and ``(i + 1) * block <= shape``."""
    name = "in-bounds"
    violations: list[str] = []
    operands = spec.in_specs + spec.out_specs
    points = 0
    for g in iter_grid(spec):
        points += 1
        key = _step_key(spec, g)
        for op in operands:
            idx = tuple(op.index_map(*key))
            if len(idx) != len(op.block):
                violations.append(
                    f"{op.name}: index map yields rank {len(idx)} for a "
                    f"rank-{len(op.block)} block")
            else:
                for d, (i, blk, size) in enumerate(
                        zip(idx, op.block, op.shape)):
                    if i < 0 or (i + 1) * blk > size:
                        violations.append(
                            f"{op.name}: step {_fmt_point(g)} maps dim "
                            f"{d} to block {i} — elements "
                            f"[{i * blk}, {(i + 1) * blk}) outside the "
                            f"padded extent {size}")
            if len(violations) >= _MAX_VIOLATIONS:
                return KernelCheck(
                    spec.name, name, "fail",
                    "; ".join(violations) + " ... (truncated)")
    if violations:
        return KernelCheck(spec.name, name, "fail", "; ".join(violations))
    return KernelCheck(
        spec.name, name, "ok",
        f"{len(operands)} operand(s) in bounds at all {points} grid "
        "points")


# ---------------------------------------------------------------------------
# check (4): VMEM fit
# ---------------------------------------------------------------------------
def check_vmem_fit(spec: KernelSpec) -> KernelCheck:
    name = "vmem-fit"
    nbytes = spec.vmem_bytes()
    detail = f"{nbytes} B ({spec.vmem_detail()})"
    if nbytes > VMEM_LIMIT_BYTES:
        return KernelCheck(
            spec.name, name, "fail",
            f"{detail} exceeds the {VMEM_LIMIT_BYTES} B per-core VMEM "
            "budget — shrink tile/bin_block")
    return KernelCheck(
        spec.name, name, "ok", f"{detail} of {VMEM_LIMIT_BYTES} B")


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------
def check_spec(spec: KernelSpec, *, enum_spec: KernelSpec | None = None
               ) -> tuple[KernelCheck, ...]:
    """All four checks for one pass.  ``enum_spec`` (the same pass built
    at the canonical clamped geometry) runs the enumeration checks;
    ``spec`` (real geometry) prices vmem-fit."""
    e = enum_spec if enum_spec is not None else spec
    return (
        check_carry_order(e),
        check_out_coverage(e),
        check_in_bounds(e),
        check_vmem_fit(spec),
    )


def specs_for(method: str, geom: KernelGeometry) -> tuple[KernelSpec, ...]:
    from repro.kernels.ops import KERNEL_SPECS

    builder = KERNEL_SPECS.get(method)
    if builder is None:
        raise KeyError(
            f"method {method!r} has no registered KernelSpec "
            f"(registry: {sorted(KERNEL_SPECS)})")
    return builder(geom)


@functools.lru_cache(maxsize=64)
def check_method(method: str, geom: KernelGeometry) -> KernelVerdict:
    """Verify every pass of ``method`` at ``geom``: enumeration on the
    canonical clamped geometry, vmem on the real one."""
    real = specs_for(method, geom)
    canon = specs_for(method, geom.canonical())
    checks: list[KernelCheck] = []
    for spec, enum_spec in zip(real, canon):
        checks.extend(check_spec(spec, enum_spec=enum_spec))
    return KernelVerdict(method=method, geometry=geom,
                         checks=tuple(checks))


def check_kernels(methods=None, geometries=None) -> list[KernelVerdict]:
    """The ``--check-kernels`` sweep: every registered method (or
    ``methods``) at each geometry (default: the 640x480/32-bin serving
    shape and the paper's §4.6 8192x8192/128-bin scale)."""
    from repro.kernels.ops import KERNEL_SPECS

    if methods is None:
        methods = sorted(KERNEL_SPECS)
    if geometries is None:
        geometries = DEFAULT_GEOMETRIES
    return [
        check_method(m, g) for g in geometries for m in methods
    ]


DEFAULT_GEOMETRIES = (
    KernelGeometry(n=2, h=480, w=640, num_bins=32),
    KernelGeometry(n=1, h=8192, w=8192, num_bins=128),
)


# ---------------------------------------------------------------------------
# plancheck bridge
# ---------------------------------------------------------------------------
def plan_method(plan) -> str:
    """The kernel a plan actually dispatches: a query-fused plan runs
    the fused-rows kernel (kernels/fused_rows.py) no matter which scan
    method it names — verify THAT spec, not the full-H one."""
    return "fused_rows" if plan.representation == "fused" else plan.method


def plan_geometry(plan) -> KernelGeometry:
    """The launch geometry an ExecutionPlan's dispatches use: microbatch
    frames per dispatch (floor 2 — the canonical enumeration needs the
    frame-boundary resets exercised either way), band height rather than
    frame height when the plan streams bands.  Fused plans get a
    :class:`~repro.kernels.specs.FusedRowsGeometry` carrying the real
    per-strip emission width and the early-exit height (the scan stops
    after the strip holding the last requested row)."""
    s = plan.spec
    n = max(plan.microbatch, 1)
    if plan.representation == "fused":
        from repro.kernels.fused_rows import fused_geometry

        rows = s.query_rows
        h_cut = min(s.height, (max(rows) // plan.tile + 1) * plan.tile)
        return fused_geometry(
            rows, n, h_cut, s.width, s.num_bins,
            tile=plan.tile, bin_block=plan.bin_block,
        )
    h = s.height
    if plan.band_plan is not None:
        h = plan.band_plan.band_h
    return KernelGeometry(n=n, h=h, w=s.width, num_bins=s.num_bins,
                          tile=plan.tile, bin_block=plan.bin_block,
                          dtype=s.h_dtype.name)


def vmem_required(method: str, geom: KernelGeometry
                  ) -> tuple[int, str] | None:
    """Peak per-core VMEM bytes across the method's passes (passes run
    sequentially, so the peak is the max), with a detail string — what
    ``plancheck``'s vmem-fit check prices.  ``None`` when the method has
    no registered KernelSpec (no Pallas kernel to model)."""
    from repro.kernels.ops import KERNEL_SPECS

    if method not in KERNEL_SPECS:
        return None
    specs = specs_for(method, geom)
    peak = max(specs, key=lambda sp: sp.vmem_bytes())
    label = f" (peak pass {peak.name})" if len(specs) > 1 else ""
    return peak.vmem_bytes(), peak.vmem_detail() + label
