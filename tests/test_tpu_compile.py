"""The main-path Pallas kernels compile for a described TPU v5e.

Interpret mode runs the kernels' arithmetic on the CPU but never meets the
chip's compiler, which refuses what the interpreter accepts: negative
static indices (they trace to ``dynamic_slice``), blocks that break the
8x128 rule, Mosaic layouts it cannot lower, and scratch beyond the VMEM
limit.  These tests compile each kernel through its public wrapper for one
chip of a described ``v5e:2x2`` at the two sizes the chip smoke check runs:
a 4-frame 480x640 uint8 stack with 32 bins, and a 1024x8192 band with 128
bins and a carry-in.  Nothing runs; the topology is described inside a
fixture, so importing this file never loads the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops

os.environ.setdefault("TPU_LOG_DIR", "disabled")

KERNELS = ("wf_tis", "cw_tis", "fused_rows", "delta_apply")
SHAPES = {
    "stack_480x640x32": (4, 480, 640, 32),
    "band_1024x8192x128": (1, 1024, 8192, 128),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _lowered(kernel, n, h, w, bins, sharding):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    frames = sds((n, h, w), jnp.uint8)
    carry = sds((n, bins, w), jnp.float32)
    if kernel in ("wf_tis", "cw_tis"):
        return jax.jit(lambda f, c: ops.integral_histogram(
            f, bins, method=kernel, backend="pallas", carry_in=c,
        )).lower(frames, carry)
    if kernel == "fused_rows":
        rows = np.arange(3, h, 37)           # a row in every strip
        return jax.jit(lambda f, c: ops.fused_corner_rows(
            f, bins, rows, backend="pallas", carry_in=c,
        )).lower(frames, carry)
    return jax.jit(lambda H, d: ops.delta_apply(H, d, backend="pallas")) \
        .lower(sds((n, bins, h, w), jnp.float32), carry)


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(kernel, shape, one_chip, no_compile_cache):
    n, h, w, bins = SHAPES[shape]
    compiled = _lowered(kernel, n, h, w, bins, one_chip).compile()
    # the Pallas kernel is in the program, not a fallback
    assert "tpu_custom_call" in compiled.as_text()


def _reads_of_h(hlo: str) -> list:
    """The instructions of a compiled program's entry computation that
    take its first parameter, H, as an operand."""
    entry = hlo[hlo.index("\nENTRY"):]
    [h] = re.findall(r"(%[\w.]+) = \S+ parameter\(0\)", entry)
    return re.findall(r"= \S+ ([\w-]+)\([^)]*" + re.escape(h) + r"[,)]",
                      entry)


def test_dense_window_program_compiles_for_v5e(one_chip, no_compile_cache):
    """The 1080p 64x64 stride-2 window field compiles to strided slices:
    no gather in the chip's program, and H is read by one slice (the
    stride divides the window, so both row lattices are one)."""
    from repro.core import region_query as rq

    H = jax.ShapeDtypeStruct((32, 1080, 1920), jnp.float32,
                             sharding=one_chip)
    compiled = rq._dense_windows.lower(H, window=(64, 64), stride=2).compile()
    assert "gather" not in compiled.as_text()
    assert _reads_of_h(compiled.as_text()) == ["slice"]


def test_bin_sharded_window_program_reads_h_once(topo, no_compile_cache):
    """The paper's 8192x8192 int32 H with its 128 bins over the four
    chips of a v5e 2x2: the stride-16 64x64 window program reads each
    chip's share of H by one slice and moves nothing between chips."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import region_query as rq

    mesh = Mesh(np.array(topo.devices).reshape(4), ("model",))
    H = jax.ShapeDtypeStruct(
        (1, 128, 8192, 8192), jnp.int32,
        sharding=NamedSharding(mesh, P(None, "model", None, None)))
    hlo = rq._dense_windows.lower(H, window=(64, 64), stride=16) \
        .compile().as_text()
    assert "s32[1,32,8192,8192]" in hlo          # each chip holds 32 bins
    assert _reads_of_h(hlo) == ["slice"]
    for collective in ("all-gather", "all-to-all", "all-reduce",
                       "collective-permute", "reduce-scatter"):
        assert collective not in hlo


def test_int32_wf_tis_compiles_for_v5e(one_chip, no_compile_cache):
    """A band of the 8192-wide frame with an int32 carry past 2**24
    compiles to the int32 kernel."""
    n, h, w, bins = SHAPES["band_1024x8192x128"]
    frames = jax.ShapeDtypeStruct((n, h, w), jnp.uint8, sharding=one_chip)
    carry = jax.ShapeDtypeStruct((n, bins, w), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda f, c: ops.integral_histogram(
        f, bins, backend="pallas", carry_in=c, dtype=np.int32,
    )).lower(frames, carry).compile()
    kernel = [line for line in compiled.as_text().splitlines()
              if "tpu_custom_call" in line and "wf_tis" in line]
    assert kernel and "s32[1,128,1024,8192]" in kernel[0]


def test_region_loop_keeps_a_chips_h_in_place(one_chip, no_compile_cache):
    """A chip's share of the 8192x8192x128 int32 H (32 bins, 8 GiB) is
    answered for 288 fragments with no copy of H: a gather there has the
    compiler lay H out again, which does not fit the chip."""
    from repro.core import region_query as rq

    H = jax.ShapeDtypeStruct((32, 8192, 8192), jnp.int32, sharding=one_chip)
    rects = jax.ShapeDtypeStruct((288, 4), jnp.int32, sharding=one_chip)
    compiled = jax.jit(rq.regions_by_tiles).lower(H, rects).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
