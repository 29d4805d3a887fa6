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
