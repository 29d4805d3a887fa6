"""Mamba-2 (SSD, state-space duality) — the assigned attention-free arch.

The SSD chunked algorithm is the 1-D analogue of the paper's WF-TiS tiled
scan (DESIGN.md §4): the sequence is split into chunks; each chunk computes
a local (intra-tile) result with dense matmuls, produces a boundary state,
and the states are propagated by a short sequential carry scan — exactly
"intra-tile scan + carry propagation", with the MXU-friendly quadratic
intra-chunk form playing the role of the triangular-matmul tile scan in
kernels/wf_tis.py.

Shapes: d_inner = expand * d_model; H = d_inner / ssm_head_dim heads;
B/C projections are per-group (ssm_groups, ssm_state).  fp32 state math.
"""

from __future__ import annotations

import jax
from jax import lax
import jax.numpy as jnp

from repro.models import layers as L
from repro.sharding.rules import constrain


def _dims(cfg):
    d_in = cfg.ssm_expand * cfg.d_model
    nheads = d_in // cfg.ssm_head_dim
    conv_ch = d_in + 2 * cfg.ssm_groups * cfg.ssm_state
    return d_in, nheads, conv_ch


def layer_params(key, cfg, dtype=jnp.float32) -> dict:
    d = cfg.d_model
    d_in, nheads, conv_ch = _dims(cfg)
    proj_out = 2 * d_in + 2 * cfg.ssm_groups * cfg.ssm_state + nheads
    ks = jax.random.split(key, 4)
    return {
        "norm": L.norm_params(d, False, dtype),
        "in_proj": L.dense_init(ks[0], (d, proj_out), in_axis=0, dtype=dtype),
        "conv_w": L.dense_init(ks[1], (cfg.conv_kernel, conv_ch), in_axis=0,
                               dtype=dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.zeros((nheads,), jnp.float32),          # A = -exp(0) = -1
        "D": jnp.ones((nheads,), jnp.float32),
        "dt_bias": jnp.zeros((nheads,), jnp.float32),
        "ssm_norm": L.norm_params(d_in, False, dtype),
        "out_proj": L.dense_init(ks[2], (d_in, d), in_axis=0, dtype=dtype),
    }


def init_params(key, cfg, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 3)
    params = {
        "embed": L.embed_init(ks[0], (cfg.padded_vocab, cfg.d_model), dtype),
        "final_norm": L.norm_params(cfg.d_model, False, dtype),
        "layers": jax.vmap(lambda k: layer_params(k, cfg, dtype))(
            jax.random.split(ks[1], cfg.num_layers)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(
            ks[2], (cfg.d_model, cfg.padded_vocab), in_axis=0, dtype=dtype)
    return params


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 tail: jnp.ndarray | None = None):
    """Depthwise causal conv1d. x: (B, S, C); w: (K, C).

    tail: (B, K-1, C) previous inputs (decode); returns (y, new_tail).
    """
    k = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)
    y = sum(xp[:, i : i + x.shape[1], :] * w[i] for i in range(k))
    new_tail = xp[:, -(k - 1) :, :] if k > 1 else tail
    return y + b, new_tail


def _segsum_decay(a_cum: jnp.ndarray) -> jnp.ndarray:
    """L[i, j] = exp(a_cum_i - a_cum_j) for j <= i else 0.  a_cum: (..., Q)."""
    q = a_cum.shape[-1]
    diff = a_cum[..., :, None] - a_cum[..., None, :]
    tri = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(tri, jnp.exp(diff), 0.0)


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, h0=None):
    """Chunked SSD scan (fp32).

    x:  (B, S, H, P) values            dt: (B, S, H) positive step sizes
    A:  (H,) negative decay rates      Bm/Cm: (B, S, G, N)
    h0: optional (B, H, N, P) initial state (prefill-into-state).
    Returns (y (B, S, H, P), h_last (B, H, N, P)).
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T;  y_t = C_t h_t.
    """
    b, s, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = s + pad
    nc = sp // chunk
    hg = h // g                                        # heads per group

    def to_chunks(t):
        return t.reshape(t.shape[0], nc, chunk, *t.shape[2:]).swapaxes(0, 1)

    xc, dtc, Bc, Cc = map(to_chunks, (x, dt, Bm, Cm))   # (nc, B, Q, ...)

    def chunk_step(hstate, blk):
        xq, dtq, Bq, Cq = blk                           # (B,Q,H,P),(B,Q,H),(B,Q,G,N)
        a = dtq * A                                     # (B,Q,H) log-decays <= 0
        a_cum = jnp.cumsum(a, axis=1)                   # (B,Q,H)
        # intra-chunk: scores[q1,q2] = C_{q1} . B_{q2} per group
        scores = jnp.einsum("bqgn,bsgn->bgqs", Cq, Bq,
                            preferred_element_type=jnp.float32)
        Lmask = _segsum_decay(a_cum.swapaxes(1, 2))     # (B,H,Q,Q)
        Lmask = Lmask.reshape(b, g, hg, chunk, chunk)
        M = scores[:, :, None] * Lmask                  # (B,G,hg,Q,Q)
        xdt = xq * dtq[..., None]                       # (B,Q,H,P)
        xdtg = xdt.reshape(b, chunk, g, hg, p)
        y_intra = jnp.einsum("bghqs,bsghp->bqghp", M, xdtg,
                             preferred_element_type=jnp.float32)
        # inter-chunk: contribution of the carried state
        decay_in = jnp.exp(a_cum)                       # (B,Q,H)
        y_inter = jnp.einsum("bqgn,bghnp->bqghp",
                             Cq, hstate.reshape(b, g, hg, n, p),
                             preferred_element_type=jnp.float32)
        y_inter = y_inter * decay_in.reshape(b, chunk, g, hg)[..., None]
        y = (y_intra + y_inter).reshape(b, chunk, h, p)
        # new boundary state (the carry): decayed old + this chunk's input
        total = a_cum[:, -1]                            # (B,H)
        decay_out = jnp.exp(total[:, None] - a_cum)     # (B,Q,H)
        state_new = jnp.einsum("bqgn,bqghp->bghnp",
                               Bq, (xdtg * decay_out.reshape(
                                   b, chunk, g, hg)[..., None]),
                               preferred_element_type=jnp.float32)
        hstate = hstate * jnp.exp(total).reshape(
            b, h)[..., None, None] + state_new.reshape(b, h, n, p)
        return hstate, y

    if h0 is None:
        h0 = jnp.zeros((b, h, n, p), jnp.float32)
    h_last, ys = jax.lax.scan(chunk_step, h0, (xc, dtc, Bc, Cc))
    y = ys.swapaxes(0, 1).reshape(b, sp, h, p)
    return y[:, :s], h_last


def ssd_seq_parallel(xh, dt, A, Bm, Cm, chunk: int, mesh, rules, h0=None):
    """Sequence-parallel SSD: sequence sharded over the model axis.

    Each rank runs the chunked scan on its sequence shard from a zero
    state; shard-boundary (log-decay, state) summaries then propagate
    across ranks with an exclusive Hillis-Steele ppermute ladder — the
    WF-TiS boundary-carry pattern lifted from VMEM scratch to ICI
    (identical in structure to core/distributed.spatial_sharded_ih) —
    and each rank folds the incoming prefix state into its outputs.

    Returns (y, h_final) with y sequence-sharded like the input.
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    seq_ax = rules.present(mesh, rules.tp_axes)[0]
    batch_axes = rules.present(mesh, rules.batch_axes)
    b_ax = batch_axes if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    d = mesh.shape[seq_ax]

    def inner(xh, dt, Bm, Cm, h_init):
        b, s, h, pdim = xh.shape
        g, n = Bm.shape[2], Bm.shape[3]
        hg = h // g
        # an incoming initial state seeds rank 0's local scan only; its
        # effect reaches later ranks through the boundary-carry prefix.
        first = (lax.axis_index(seq_ax) == 0).astype(h_init.dtype)
        y, h_last = ssd_chunked(xh, dt, A, Bm, Cm, chunk,
                                h0=h_init * first)
        a = dt * A                                      # (B, S_loc, H)
        a_sum = jnp.sum(a, axis=1)                      # (B, H)

        # exclusive prefix of (log-decay, state) across seq ranks.
        # ppermute fills non-destinations with zeros == the identity
        # (decay exp(0)=1, state 0).
        ld = lax.ppermute(a_sum, seq_ax,
                          [(i, i + 1) for i in range(d - 1)])
        hs = lax.ppermute(h_last, seq_ax,
                          [(i, i + 1) for i in range(d - 1)])
        step = 1
        while step < d:
            perm = [(i, i + step) for i in range(d - step)]
            ld_in = lax.ppermute(ld, seq_ax, perm)
            hs_in = lax.ppermute(hs, seq_ax, perm)
            # compose earlier-interval (in) then current: the incoming
            # state decays through the current interval.
            hs = jnp.exp(ld)[..., None, None] * hs_in + hs
            ld = ld + ld_in
            step *= 2

        # fold the prefix state into this shard's outputs
        a_cum = jnp.cumsum(a, axis=1)                   # (B, S_loc, H)
        y_corr = jnp.einsum(
            "bsgn,bghnp->bsghp", Cm,
            hs.reshape(b, g, hg, n, pdim),
            preferred_element_type=jnp.float32)
        y = y + (y_corr * jnp.exp(a_cum).reshape(
            b, s, g, hg)[..., None]).reshape(b, s, h, pdim)

        # global final state (inclusive prefix on the last rank)
        h_inc = jnp.exp(a_sum)[..., None, None] * hs + h_last
        is_last = (lax.axis_index(seq_ax) == d - 1).astype(h_inc.dtype)
        h_fin = lax.psum(h_inc * is_last, seq_ax)
        return y, h_fin

    if h0 is None:
        b, h = xh.shape[0], xh.shape[2]
        n, pdim = Bm.shape[-1], xh.shape[-1]
        h0 = jnp.zeros((b, h, n, pdim), jnp.float32)
    return shard_map(
        inner, mesh=mesh,
        in_specs=(P(b_ax, seq_ax, None, None), P(b_ax, seq_ax, None),
                  P(b_ax, seq_ax, None, None), P(b_ax, seq_ax, None, None),
                  P(b_ax, None, None, None)),
        out_specs=(P(b_ax, seq_ax, None, None), P(b_ax, None, None, None)),
        check_vma=False,
    )(xh, dt, Bm, Cm, h0)


def _mixer(x, p, cfg, state_layer=None):
    """Mamba-2 mixer. x: (B, S, d). Returns (out, new_state_layer)."""
    b, s, d = x.shape
    d_in, nheads, conv_ch = _dims(cfg)
    g, n, phd = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_head_dim

    proj = jnp.einsum("bsd,df->bsf", x, p["in_proj"])
    z = proj[..., :d_in]
    xbc = proj[..., d_in : d_in + conv_ch]
    dt = proj[..., d_in + conv_ch :]
    conv_tail = state_layer["conv"] if state_layer is not None else None
    xbc, new_tail = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_tail)
    xbc = jax.nn.silu(xbc.astype(jnp.float32)).astype(x.dtype)
    xs = xbc[..., : d_in]
    Bm = xbc[..., d_in : d_in + g * n].reshape(b, s, g, n)
    Cm = xbc[..., d_in + g * n :].reshape(b, s, g, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B,S,H)
    A = -jnp.exp(p["A_log"])                                       # (H,)
    xh = xs.reshape(b, s, nheads, phd).astype(jnp.float32)
    Bm32, Cm32 = Bm.astype(jnp.float32), Cm.astype(jnp.float32)

    if state_layer is None:
        from repro.sharding.rules import current_context
        ctx = current_context()
        use_sp = (cfg.ssm_seq_parallel and ctx is not None
                  and s % ctx.mesh.shape[
                      ctx.rules.present(ctx.mesh, ctx.rules.tp_axes)[0]] == 0)
        if use_sp:
            y, _ = ssd_seq_parallel(xh, dt, A, Bm32, Cm32, cfg.ssm_chunk,
                                    ctx.mesh, ctx.rules)
        else:
            y, _ = ssd_chunked(xh, dt, A, Bm32, Cm32, cfg.ssm_chunk)
        new_state = None
    elif s > 1:
        # prefill into an existing state: chunked scan seeded with it.
        # Note: prefill assumes an empty conv tail (fresh sequence).
        from repro.sharding.rules import current_context
        ctx = current_context()
        use_sp = (cfg.ssm_seq_parallel and ctx is not None
                  and s % ctx.mesh.shape[
                      ctx.rules.present(ctx.mesh, ctx.rules.tp_axes)[0]] == 0)
        if use_sp:
            y, h_last = ssd_seq_parallel(
                xh, dt, A, Bm32, Cm32, cfg.ssm_chunk, ctx.mesh, ctx.rules,
                h0=state_layer["h"].swapaxes(-1, -2))
        else:
            y, h_last = ssd_chunked(xh, dt, A, Bm32, Cm32, cfg.ssm_chunk,
                                    h0=state_layer["h"].swapaxes(-1, -2))
        new_state = {"h": h_last.swapaxes(-1, -2), "conv": new_tail}
    else:
        # decode: s == 1 single-step recurrence
        h0 = state_layer["h"]                          # (B,H,P,N)
        a = jnp.exp(dt[:, 0] * A)                      # (B,H)
        hg = nheads // g
        xdt = (xh[:, 0] * dt[:, 0][..., None]).reshape(b, g, hg, phd)
        binp = jnp.einsum("bgn,bghp->bghpn", Bm32[:, 0], xdt)
        h1 = h0 * a[..., None, None] + binp.reshape(b, nheads, phd, n)
        y = jnp.einsum("bgn,bghpn->bghp", Cm32[:, 0],
                       h1.reshape(b, g, hg, phd, n)).reshape(b, 1, nheads, phd)
        new_state = {"h": h1, "conv": new_tail}
    y = y + xh * p["D"][None, None, :, None]
    y = y.reshape(b, s, d_in).astype(x.dtype)
    y = L.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                   p["ssm_norm"]["scale"], cfg.norm_eps)
    y = constrain(y, "batch", None, "tp")
    return jnp.einsum("bsf,fd->bsd", y, p["out_proj"]), new_state


def _block(x, p, cfg, state_layer=None):
    if cfg.ssm_seq_parallel and x.shape[1] > 1:
        # seq-shard the whole block's activations over the model axis so
        # the projections/conv/gating around the SP scan are also 1/|tp|
        # per chip (conv halo = collective-permute of K-1=3 rows).
        x = constrain(x, "batch", "tp", None)
    h = L.rms_norm(x, p["norm"]["scale"], cfg.norm_eps)
    h, new_state = _mixer(h, p, cfg, state_layer)
    x = x + h
    x = constrain(x, "batch", "tp" if cfg.ssm_seq_parallel and
                  x.shape[1] > 1 else None, None)
    return x, new_state


def forward(params, tokens, cfg, *, prefix_embeds=None, cache=None,
            positions=None):
    """Returns (logits, aux=0, new_cache). cache = ssm_state pytree."""
    params = L.cast_params(params, cfg.dtype)
    b, s = tokens.shape
    x = params["embed"][tokens].astype(cfg.dtype)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(cfg.dtype), x], axis=1)
    x = constrain(x, "batch", None, None)

    if cache is None:
        def body(h, p_layer):
            h, _ = _block(h, p_layer, cfg)
            return h, None
        if cfg.remat == "dots":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.checkpoint_dots)
        elif cfg.remat == "full":
            body = jax.checkpoint(body)
        x, _ = L.scan_or_unroll(body, x, params["layers"], cfg.scan_layers)
        new_cache = None
    else:
        ln = cache["len"]
        def body(h, xs):
            p_layer, c = xs
            h, new_state = _block(h, p_layer, cfg, c)
            return h, new_state
        kv = {"h": cache["h"], "conv": cache["conv"]}
        x, new_kv = L.scan_or_unroll(body, x, (params["layers"], kv),
                                     cfg.scan_layers)
        new_cache = {"h": new_kv["h"], "conv": new_kv["conv"], "len": ln + s}

    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(cfg.dtype))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(cfg.dtype))
    logits = constrain(logits.astype(jnp.float32), "batch", None, "tp")
    return logits, jnp.zeros((), jnp.float32), new_cache


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    from repro.models.cache import ssm_state

    d_in, nheads, conv_ch = _dims(cfg)
    return ssm_state(cfg.num_layers, batch, nheads, cfg.ssm_head_dim,
                     cfg.ssm_state, conv_ch, cfg.conv_kernel)
