"""Binning function Q(I, b) of the integral histogram (paper Eq. 1).

Q(I(r, c), b) evaluates to 1 iff pixel value I(r, c) falls in bin b.  We
support uint8-style integer images (values in [0, value_range)) and float
images in [0, 1).  ``bin_indices`` maps each pixel to its bin id; the
one-hot expansion (the b-fold data blow-up the paper's init kernel pays a
full memory pass for) is either materialized (`one_hot_bins`, used by the
oracle and the generic scan methods) or fused into the Pallas kernels
(kernels/wf_tis.py) where it never touches HBM.
"""

from __future__ import annotations

import jax.numpy as jnp

# Pixels mapped to this sentinel never match any bin: padding contributes 0.
PAD_BIN: int = -1


def bin_indices(
    image: jnp.ndarray, num_bins: int, value_range: int | None = 256
) -> jnp.ndarray:
    """Map pixel values to integer bin ids in [0, num_bins).

    Integer images are assumed to lie in [0, value_range); float images in
    [0, 1).  Out-of-range values are clipped into the valid bin range, which
    matches the saturating behaviour of the paper's CPU reference.

    ``value_range=None`` means the input already holds bin indices (int32,
    PAD_BIN sentinel allowed) — used by the distributed bin-sharded path,
    where each shard re-bases global indices into its local bin range.
    """
    if value_range is None:
        return image.astype(jnp.int32)
    if jnp.issubdtype(image.dtype, jnp.floating):
        idx = jnp.floor(image * num_bins).astype(jnp.int32)
    else:
        idx = (image.astype(jnp.int32) * num_bins) // value_range
    return jnp.clip(idx, 0, num_bins - 1)


def one_hot_bins(idx: jnp.ndarray, num_bins: int, dtype=jnp.float32) -> jnp.ndarray:
    """Materialized Q: (..., h, w) int32 -> (..., b, h, w) {0,1}.

    The bin axis is inserted just before the two spatial axes, so a single
    frame maps (h, w) -> (b, h, w) and a frame stack maps
    (n, h, w) -> (n, b, h, w).

    The 0/1 mask itself is exact in any type; what the scans accumulate
    from it takes H's element type (core/hdtype.py: fp32 below 2**24
    pixels, int32 past it).
    """
    b = jnp.arange(num_bins, dtype=jnp.int32)
    return (idx[..., None, :, :] == b[:, None, None]).astype(dtype)
