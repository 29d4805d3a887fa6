"""Gradient machinery: global-norm clipping, microbatch accumulation,
int8 error-feedback compression.

Compression (beyond-paper, §5 of DESIGN.md): gradients quantized to int8
with a persistent error-feedback buffer.  Two uses:
  * `compress_grads` inside the accumulation loop — models compressed
    gradient exchange (the quantization error is re-injected next step,
    so long-run training is unbiased);
  * `compressed_psum` — an explicit shard_map collective that all-reduces
    int8-quantized blocks over a mesh axis (4x fewer DCN bytes on the pod
    axis than bf16); used by the multi-pod experiments.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# Clipping
# ---------------------------------------------------------------------------
def global_norm(tree) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(grads, max_norm: float):
    """Returns (clipped_grads, pre_clip_norm)."""
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads), norm


# ---------------------------------------------------------------------------
# int8 error-feedback quantization
# ---------------------------------------------------------------------------
def _quantize(x: jnp.ndarray):
    """Symmetric per-tensor int8. Returns (q, scale)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def init_error_buffer(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compress_grads(grads, err):
    """Quantize grads to int8 (error feedback). Returns (deq_grads, new_err).

    deq_grads are the dequantized fp32 values actually applied; the
    residual (g + e) - deq is carried to the next step.
    """
    def leaf(g, e):
        g32 = g.astype(jnp.float32) + e
        q, scale = _quantize(g32)
        deq = q.astype(jnp.float32) * scale
        return deq, g32 - deq

    flat = jax.tree.map(leaf, grads, err)

    def istup(x):
        return isinstance(x, tuple)
    deq = jax.tree.map(lambda t: t[0], flat, is_leaf=istup)
    new_err = jax.tree.map(lambda t: t[1], flat, is_leaf=istup)
    return deq, new_err


def compressed_psum(partials: jnp.ndarray, mesh, axis: str) -> jnp.ndarray:
    """All-reduce of int8-quantized per-rank partials over a mesh axis.

    partials: (|axis|, ...) — row i is rank i's contribution (e.g. its
    local gradient).  Returns the dequantized sum, replicated.

    Wire bytes: 1 per element + one fp32 scale per shard, vs 4 (fp32) or
    2 (bf16) — the gradient-compression primitive for the DCN pod axis.
    Quantization is per-sender; accuracy is per-tensor int8 (validated
    against the exact sum in tests).
    """
    d = mesh.shape[axis]
    assert partials.shape[0] == d, (partials.shape, d)

    def inner(xs):
        q, scale = _quantize(xs[0].astype(jnp.float32))
        qg = lax.all_gather(q, axis)                 # int8 on the wire
        sg = lax.all_gather(scale, axis)
        return jnp.tensordot(sg, qg.astype(jnp.float32), axes=((0,), (0,)))

    from jax import shard_map

    return shard_map(
        inner, mesh=mesh,
        in_specs=P(axis, *([None] * (partials.ndim - 1))),
        out_specs=P(*([None] * (partials.ndim - 1))),
        check_vma=False,
    )(partials)


# ---------------------------------------------------------------------------
# Microbatch accumulation
# ---------------------------------------------------------------------------
def accumulate_grads(loss_fn, params, batch, num_microbatches: int):
    """Split batch dim into microbatches; lax.scan-accumulate fp32 grads.

    loss_fn: params, batch -> (loss, metrics).  Returns (loss, metrics,
    grads) averaged over microbatches.
    """
    if num_microbatches <= 1:
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return loss, metrics, grads

    def reshape(x):
        b = x.shape[0]
        assert b % num_microbatches == 0, (b, num_microbatches)
        return x.reshape(num_microbatches, b // num_microbatches, *x.shape[1:])

    micro = jax.tree.map(reshape, batch)
    gfn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(acc, mb):
        (loss, metrics), grads = gfn(params, mb)
        acc_g, acc_l = acc
        acc_g = jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32), acc_g, grads)
        return (acc_g, acc_l + loss), metrics

    zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (sum_g, sum_l), metrics_all = jax.lax.scan(
        step, (zero_g, jnp.zeros((), jnp.float32)), micro)
    inv = 1.0 / num_microbatches
    grads = jax.tree.map(lambda g: g * inv, sum_g)
    metrics = jax.tree.map(lambda m: jnp.mean(m), metrics_all)
    return sum_l * inv, metrics, grads
