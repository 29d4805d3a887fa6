"""Mixture-of-Experts block: sort-based capacity dispatch + expert parallel.

Design notes (DESIGN.md §4/§5):
  * The paper's multi-GPU *bin task queue* maps onto expert parallelism —
    both distribute an embarrassingly-parallel channel axis (bins/experts)
    over devices and rebalance work via capacity limits.
  * Dispatch is sort-based (argsort over token->expert assignments, ragged
    positions via bincount prefix), NOT the GShard (T, E, C) one-hot einsum:
    at kimi-k2 scale (T=32k/device, E=384) the one-hot dispatch tensor
    would be ~10^10 elements; the sort path is O(T k log(T k)).
  * Expert parallelism runs inside shard_map: each model-rank owns
    E/|model| experts, computes its share of every token's top-k, and the
    partial outputs are summed with one psum over 'model' (the same
    collective shape as a Megatron TP all-reduce).  FSDP shards of the
    expert weights are re-gathered per layer with lax.all_gather (ZeRO-3).
  * A mesh-free local path (same math, no collectives) backs smoke tests.

Capacity: cap = ceil(T * k / E * capacity_factor), rounded up to 8.
Tokens over capacity are dropped (scatter mode="drop"), matching
GShard/Switch semantics.  A load-balance aux loss is returned.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init
from repro.sharding.rules import current_context


def moe_params(key, cfg, dtype=jnp.float32) -> dict:
    d, fe, e = cfg.d_model, cfg.expert_d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e), in_axis=0, dtype=jnp.float32),
        "we_gate": dense_init(ks[1], (e, d, fe), in_axis=1, dtype=dtype),
        "we_up": dense_init(ks[2], (e, d, fe), in_axis=1, dtype=dtype),
        "we_down": dense_init(ks[3], (e, fe, d), in_axis=1, dtype=dtype),
    }
    if cfg.num_shared_experts:
        fs = fe * cfg.num_shared_experts
        sk = jax.random.split(ks[4], 3)
        p["ws_gate"] = dense_init(sk[0], (d, fs), in_axis=0, dtype=dtype)
        p["ws_up"] = dense_init(sk[1], (d, fs), in_axis=0, dtype=dtype)
        p["ws_down"] = dense_init(sk[2], (fs, d), in_axis=0, dtype=dtype)
    return p


def _capacity(num_tokens: int, cfg) -> int:
    cap = num_tokens * cfg.num_experts_per_token / cfg.num_experts
    cap = int(cap * cfg.capacity_factor) + 1
    return max(8, -(-cap // 8) * 8)


def _route(x_flat: jnp.ndarray, router_w: jnp.ndarray, cfg):
    """Returns (weights (T,k), experts (T,k), aux_loss scalar)."""
    logits = jnp.einsum(
        "td,de->te", x_flat.astype(jnp.float32), router_w.astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = lax.top_k(probs, cfg.num_experts_per_token)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    e = cfg.num_experts
    dispatch_frac = jnp.mean(
        jax.nn.one_hot(experts[:, 0], e, dtype=jnp.float32), axis=0
    )
    prob_frac = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(dispatch_frac * prob_frac)
    return weights, experts, aux


def _expert_ffn(buf: jnp.ndarray, wg, wu, wd) -> jnp.ndarray:
    """buf: (E_local, cap, d) -> (E_local, cap, d); batched SwiGLU."""
    gate = jnp.einsum("ecd,edf->ecf", buf, wg)
    up = jnp.einsum("ecd,edf->ecf", buf, wu)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(buf.dtype) * up
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _dispatch_compute_combine(
    x_flat, weights, experts, wg, wu, wd, cfg, *, lo: int, e_local: int
):
    """Sort-based dispatch for experts [lo, lo+e_local); returns (T, d)."""
    t, d = x_flat.shape
    k = cfg.num_experts_per_token
    n = t * k
    cap = _capacity(t, cfg)

    e_flat = experts.reshape(-1)
    w_flat = weights.reshape(-1).astype(x_flat.dtype)
    tok_of = jnp.arange(n, dtype=jnp.int32) // k

    # position of each assignment within its expert's buffer
    perm = jnp.argsort(e_flat)
    ranks = jnp.zeros((n,), jnp.int32).at[perm].set(jnp.arange(n, dtype=jnp.int32))
    counts = jnp.bincount(e_flat, length=cfg.num_experts)
    starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)
    pos = ranks - starts[e_flat]

    local_e = e_flat - lo
    valid = (local_e >= 0) & (local_e < e_local) & (pos < cap)
    slot = jnp.where(valid, local_e * cap + pos, e_local * cap)  # OOB -> drop

    buf = jnp.zeros((e_local * cap, d), x_flat.dtype)
    buf = buf.at[slot].set(x_flat[tok_of], mode="drop")
    out = _expert_ffn(buf.reshape(e_local, cap, d), wg, wu, wd)
    out_flat = out.reshape(e_local * cap, d)

    y = jnp.where(
        valid[:, None],
        out_flat[jnp.clip(slot, 0, e_local * cap - 1)],
        jnp.zeros((), x_flat.dtype),
    ) * w_flat[:, None]
    return jax.ops.segment_sum(y, tok_of, num_segments=t)


def _shared_expert(x_flat, p):
    gate = jnp.einsum("td,df->tf", x_flat, p["ws_gate"])
    up = jnp.einsum("td,df->tf", x_flat, p["ws_up"])
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x_flat.dtype) * up
    return jnp.einsum("tf,fd->td", h, p["ws_down"])


def moe_block(x: jnp.ndarray, p: dict, cfg):
    """MoE FFN. x: (B, S, d). Returns (out, aux_loss).

    Under a sharding_context, runs expert-parallel via shard_map (experts
    over 'model', tokens over batch axes, FSDP re-gather over 'data');
    otherwise runs the identical math on one device.
    """
    ctx = current_context()
    b, s, d = x.shape

    if ctx is None:
        x_flat = x.reshape(b * s, d)
        weights, experts, aux = _route(x_flat, p["router"], cfg)
        out = _dispatch_compute_combine(
            x_flat, weights, experts, p["we_gate"], p["we_up"], p["we_down"],
            cfg, lo=0, e_local=cfg.num_experts,
        )
        if cfg.num_shared_experts:
            out = out + _shared_expert(x_flat, p)
        return out.reshape(b, s, d), aux

    mesh, rules = ctx.mesh, ctx.rules
    batch_axes = rules.present(mesh, rules.batch_axes)
    model_ax = rules.present(mesh, rules.tp_axes)[0]
    fsdp_axes = rules.present(mesh, rules.fsdp_axes)
    fsdp_ax = fsdp_axes[0] if fsdp_axes else None
    m = mesh.shape[model_ax]
    e_local = cfg.num_experts // m
    fsdp_n = mesh.shape[fsdp_ax] if fsdp_ax else 1

    shard_d = d % fsdp_n == 0 and fsdp_n > 1
    fe = cfg.expert_d_ff
    fs = fe * cfg.num_shared_experts
    shard_fs = cfg.num_shared_experts and fs % m == 0

    def inner(x_blk, router_w, wg, wu, wd, shared):
        tl = x_blk.shape[0] * x_blk.shape[1]
        x_flat = x_blk.reshape(tl, d)
        weights, experts, aux = _route(x_flat, router_w, cfg)
        if shard_d:  # ZeRO-3: re-gather the FSDP shard of expert weights
            wg = lax.all_gather(wg, fsdp_ax, axis=1, tiled=True)
            wu = lax.all_gather(wu, fsdp_ax, axis=1, tiled=True)
            wd = lax.all_gather(wd, fsdp_ax, axis=2, tiled=True)
        lo = lax.axis_index(model_ax) * e_local
        out = _dispatch_compute_combine(
            x_flat, weights, experts, wg, wu, wd, cfg,
            lo=lo, e_local=e_local,
        )
        if cfg.num_shared_experts:
            sh = _shared_expert(x_flat, shared)
            if shard_fs:
                out = lax.psum(out + sh, model_ax)  # both are partials
            else:
                out = lax.psum(out, model_ax) + sh  # sh replicated per rank
        else:
            out = lax.psum(out, model_ax)
        aux = lax.pmean(aux, model_ax)
        for ax in batch_axes:       # average the per-DP-shard estimates
            aux = lax.pmean(aux, ax)
        return out.reshape(x_blk.shape), aux[None]

    x_spec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0], None, None)
    wg_spec = P(model_ax, fsdp_ax if shard_d else None, None)
    wd_spec = P(model_ax, None, fsdp_ax if shard_d else None)
    shared_specs = {
        "ws_gate": P(None, model_ax if shard_fs else None),
        "ws_up": P(None, model_ax if shard_fs else None),
        "ws_down": P(model_ax if shard_fs else None, None),
    }
    shared = {k: p[k] for k in shared_specs if k in p}
    # lo/e_local handled inside via axis_index; experts sharded over model.
    out, aux = shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            x_spec, P(None, None), wg_spec, wg_spec, wd_spec,
            {k: shared_specs[k] for k in shared} if shared else P(),
        ),
        out_specs=(x_spec, P(None)),
        check_vma=False,
    )(x, p["router"], p["we_gate"], p["we_up"], p["we_down"], shared or {})
    return out, jnp.sum(aux) / aux.shape[0]
