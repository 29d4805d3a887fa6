"""Declarative kernel contracts: the metadata ``kernelcheck`` verifies.

The Pallas kernels' correctness rests on invariants that the
``pallas_call`` arguments *imply* but nothing checks: the sequential
grid order that makes the VMEM carry chain a happens-before relation,
index maps that tile the output exactly once, block indices that stay
inside the padded operands, and a working set that fits per-core VMEM.
Each kernel module exports a ``kernel_specs(geom)`` builder (right next
to its ``pallas_call``) returning the :class:`KernelSpec` restatement of
those arguments; :mod:`repro.analysis.kernelcheck` enumerates the grid
symbolically and proves all four properties, and a conformance test
cross-checks the spec against the live ``pallas_call`` so the metadata
cannot drift from the code.

This module is deliberately stdlib-only (no jax import): a spec is data
— shapes, index maps as plain Python callables over named grid indices,
and carry-edge functions describing which scratch cells a grid step
reads (and from which producer step) and writes.

Grid-order semantics (the property check (1) leans on): a TPU core
executes the Pallas grid *sequentially* with the **last** grid dimension
innermost — ``grid=(a, b, c)`` iterates c fastest, exactly nested-loop
order.  The spec's ``grid`` tuple therefore both names the dimensions
(for the carry-edge functions) and declares the execution order the
carry chain depends on.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Mapping, Sequence

#: bytes per element for the dtypes the kernels use.
DTYPE_BYTES = {"int32": 4, "float32": 4, "uint32": 4, "uint16": 2}


@dataclasses.dataclass(frozen=True)
class KernelGeometry:
    """One concrete kernel launch geometry (pre-padding sizes).

    ``h``/``w``/``num_bins`` are the *logical* sizes; the padded sizes
    the ``pallas_call`` actually sees (tile/bin-block multiples, the
    padding rule of ``kernels/ops.py``) are derived properties.
    """

    n: int                      # frames (outermost grid dimension)
    h: int
    w: int
    num_bins: int
    tile: int = 128
    bin_block: int = 8
    dtype: str = "float32"      # H's element type (core/hdtype.h_dtype)

    @property
    def h_pad(self) -> int:
        return math.ceil(self.h / self.tile) * self.tile

    @property
    def w_pad(self) -> int:
        return math.ceil(self.w / self.tile) * self.tile

    @property
    def nb_pad(self) -> int:
        return math.ceil(self.num_bins / self.bin_block) * self.bin_block

    @property
    def nth(self) -> int:
        return self.h_pad // self.tile

    @property
    def ntw(self) -> int:
        return self.w_pad // self.tile

    @property
    def nbb(self) -> int:
        return self.nb_pad // self.bin_block

    def canonical(self, max_blocks: int = 3) -> "KernelGeometry":
        """The reduced geometry grid enumeration runs on: every grid
        dimension clamped to ``max_blocks`` and the frame count to 2.

        The bug classes the enumeration targets (reordered grid dims,
        overlapping/gapped index maps, off-by-one block indices, missed
        carry resets at frame/strip boundaries) all manifest within 2-3
        steps per dimension, so clamping keeps the walk O(100) steps at
        any frame size.  Frame count 2 is a floor as well as a cap: the
        frame-boundary carry resets only exercise with a second frame.
        """
        return KernelGeometry(
            n=2,
            h=min(self.nth, max_blocks) * self.tile,
            w=min(self.ntw, max_blocks) * self.tile,
            num_bins=min(self.nbb, max_blocks) * self.bin_block,
            tile=self.tile,
            bin_block=self.bin_block,
            dtype=self.dtype,
        )


@dataclasses.dataclass(frozen=True)
class FusedRowsGeometry(KernelGeometry):
    """Launch geometry of the query-fused kernel (kernels/fused_rows.py).

    ``kp`` is the per-strip emission width: the padded count of row
    slots each tile-row strip may emit (the maximum corner rows any
    strip of the request carries, rounded up to a sublane multiple of
    8).  The fused output is ``(n, nb_pad, nth * kp, w_pad)`` — never
    the full H."""

    kp: int = 8

    def canonical(self, max_blocks: int = 3) -> "FusedRowsGeometry":
        base = super().canonical(max_blocks)
        return FusedRowsGeometry(
            n=base.n, h=base.h, w=base.w, num_bins=base.num_bins,
            tile=base.tile, bin_block=base.bin_block,
            kp=min(self.kp, self.tile),
        )


@dataclasses.dataclass(frozen=True)
class Operand:
    """One blocked ``pallas_call`` operand (an in_spec or out_spec).

    ``index_map`` mirrors the BlockSpec lambda: positional grid indices
    (in the spec's grid order) -> block-index tuple.
    """

    name: str
    shape: tuple[int, ...]          # full padded operand shape
    block: tuple[int, ...]          # BlockSpec block shape
    index_map: Callable[..., tuple[int, ...]]
    dtype: str = "float32"

    @property
    def block_bytes(self) -> int:
        return math.prod(self.block) * DTYPE_BYTES[self.dtype]


@dataclasses.dataclass(frozen=True)
class Scratch:
    """One VMEM scratch buffer (``scratch_shapes`` entry)."""

    name: str
    shape: tuple[int, ...]
    dtype: str = "float32"

    @property
    def nbytes(self) -> int:
        return math.prod(self.shape) * DTYPE_BYTES[self.dtype]


#: a scratch cell key: hashable, first element names the buffer.
Cell = tuple
#: carry reads at one grid step: (cell, producer grid point) pairs.
#: The producer is the step whose write the read value must come from.
CarryReads = Callable[[Mapping[str, int]], Sequence[tuple[Cell, Mapping[str, int]]]]
#: carry writes at one grid step: cells (re)written.
CarryWrites = Callable[[Mapping[str, int]], Sequence[Cell]]


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """The declarative contract of one ``pallas_call``.

    ``grid`` is ``((dim_name, size), ...)`` in launch order (last dim
    innermost — the sequential order property (1) is proved under).
    ``carry_reads(g)`` returns the scratch values grid step ``g``
    *consumes* (value-flow reads: a buffered read whose value a reset
    predicate discards, e.g. ``jnp.where(iw == 0, 0, row_carry[bb])`` at
    ``iw == 0``, is NOT a read) together with the grid point that must
    have produced each value.  ``carry_writes(g)`` returns the cells
    ``g`` (re)writes.  Cells model whole regions written atomically —
    e.g. the ``row_carry[bb]`` slice is one cell keyed ``("row", bb)``.
    """

    name: str
    grid: tuple[tuple[str, int], ...]
    in_specs: tuple[Operand, ...]
    out_specs: tuple[Operand, ...]
    scratch: tuple[Scratch, ...] = ()
    carry_reads: CarryReads | None = None
    carry_writes: CarryWrites | None = None

    @property
    def grid_sizes(self) -> tuple[int, ...]:
        return tuple(size for _, size in self.grid)

    @property
    def dim_names(self) -> tuple[str, ...]:
        return tuple(name for name, _ in self.grid)

    def vmem_bytes(self) -> int:
        """The per-core VMEM working set this launch needs: every
        blocked operand double-buffered (Pallas overlaps the next
        block's DMA with the current step) plus the scratch, which is
        single-buffered because it persists across grid steps."""
        blocks = sum(op.block_bytes for op in self.in_specs + self.out_specs)
        scratch = sum(s.nbytes for s in self.scratch)
        return 2 * blocks + scratch

    def vmem_detail(self) -> str:
        ops = " + ".join(
            f"{op.name}{list(op.block)}"
            for op in self.in_specs + self.out_specs
        )
        scratch = sum(s.nbytes for s in self.scratch)
        return f"2x({ops}) blocks + {scratch} B scratch"
