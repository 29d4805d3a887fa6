"""Griffin / RecurrentGemma: RG-LRU recurrent blocks + local attention.

Layer pattern (cfg.block_pattern, e.g. ("rec", "rec", "attn")) tiles the
depth; full pattern-groups are stacked and lax.scan'ed, the remainder is a
short unstacked tail (38 = 12 x (rec,rec,attn) + (rec,rec)).

The RG-LRU prefill is a 1-D gated linear recurrence computed CHUNK-WISE:
intra-chunk associative scan + a sequential carry over chunk boundaries —
the same tiled-scan-plus-carry structure as the paper's WF-TiS kernel
(DESIGN.md §4).  Decode is a single-step recurrence; the local-attention
layers use ring-buffer KV caches of exactly `sliding_window` slots, which
is what makes long_500k decode runnable (2k live keys at position 512k).

Gates are per-channel (diagonal) rather than block-diagonal dense — noted
in DESIGN.md §7 deviations; parameter counts follow config.param_count.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.sharding.rules import constrain

_C = 8.0  # RG-LRU exponent scale (Griffin paper)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------
def _rec_mixer_params(key, cfg, dtype) -> dict:
    d, w = cfg.d_model, cfg.rnn_width
    ks = jax.random.split(key, 3)
    return {
        "w_branch_gate": L.dense_init(ks[0], (d, w), in_axis=0, dtype=dtype),
        "w_branch_x": L.dense_init(ks[1], (d, w), in_axis=0, dtype=dtype),
        "conv_w": L.dense_init(jax.random.fold_in(key, 7), (cfg.conv_kernel, w),
                               in_axis=0, dtype=dtype),
        "conv_b": jnp.zeros((w,), dtype),
        # a = sigmoid(lam); init so a^c ~ 0.9..0.999 (long memory)
        "lam": jnp.linspace(2.0, 6.0, w, dtype=jnp.float32),
        "g_a": jnp.zeros((w,), jnp.float32),
        "b_a": jnp.zeros((w,), jnp.float32),
        "g_x": jnp.zeros((w,), jnp.float32),
        "b_x": jnp.zeros((w,), jnp.float32),
        "w_out": L.dense_init(ks[2], (w, d), in_axis=0, dtype=dtype),
    }


def _layer_params(key, cfg, kind: str, dtype) -> dict:
    k_mix, k_mlp = jax.random.split(key)
    d = cfg.d_model
    p = {
        "norm1": L.norm_params(d, False, dtype),
        "norm2": L.norm_params(d, False, dtype),
        "mlp": L.mlp_params(k_mlp, d, cfg.d_ff, dtype=dtype),
    }
    if kind == "rec":
        p["rec"] = _rec_mixer_params(k_mix, cfg, dtype)
    else:
        p["attn"] = L.attention_params(k_mix, cfg, dtype=dtype)
    return p


def _pattern(cfg):
    p = cfg.block_pattern or ("rec", "rec", "attn")
    n_groups = cfg.num_layers // len(p)
    rem = cfg.num_layers - n_groups * len(p)
    return p, n_groups, p[:rem]


def init_params(key, cfg, dtype=jnp.float32) -> dict:
    pat, n_groups, tail = _pattern(cfg)
    ks = jax.random.split(key, 4)

    def group_params(k):
        gks = jax.random.split(k, len(pat))
        return {f"b{j}": _layer_params(gks[j], cfg, kind, dtype)
                for j, kind in enumerate(pat)}

    params = {
        "embed": L.embed_init(ks[0], (cfg.padded_vocab, cfg.d_model), dtype),
        "final_norm": L.norm_params(cfg.d_model, False, dtype),
        "groups": {"layers": jax.vmap(group_params)(
            jax.random.split(ks[1], n_groups))},
    }
    if tail:
        tks = jax.random.split(ks[2], len(tail))
        params["tail"] = {f"b{j}": _layer_params(tks[j], cfg, kind, dtype)
                          for j, kind in enumerate(tail)}
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(
            ks[3], (cfg.d_model, cfg.padded_vocab), in_axis=0, dtype=dtype)
    return params


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------
def _rglru_gates(u, p):
    """u: (B, S, w) fp32. Returns (log_a, b) of the linear recurrence
    h_t = exp(log_a_t) h_{t-1} + b_t."""
    r = jax.nn.sigmoid(u * p["g_a"] + p["b_a"])
    i = jax.nn.sigmoid(u * p["g_x"] + p["b_x"])
    log_a = -_C * r * jax.nn.softplus(-p["lam"])          # <= 0
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i * u)
    return log_a, b


def _rglru_chunked(u, p, chunk: int, h0):
    """Chunked linear scan. u: (B, S, w) fp32; h0: (B, w).

    Returns (h_seq (B, S, w), h_last).  Intra-chunk associative scan,
    sequential carry across chunks (tiled-scan-with-carry pattern).
    """
    bsz, s, w = u.shape
    # gates BEFORE padding: padded steps get (log_a=0, b=0) = identity,
    # so the carried state is exact for any (s % chunk).
    log_a, bgate = _rglru_gates(u, p)
    pad = (-s) % chunk
    if pad:
        log_a = jnp.pad(log_a, ((0, 0), (0, pad), (0, 0)))
        bgate = jnp.pad(bgate, ((0, 0), (0, pad), (0, 0)))
    nc = (s + pad) // chunk

    def to_chunks(t):
        return t.reshape(bsz, nc, chunk, w).swapaxes(0, 1)   # (nc, B, Q, w)

    def combine(x, y):
        (la1, b1), (la2, b2) = x, y
        return la1 + la2, jnp.exp(la2) * b1 + b2

    def chunk_step(h, blk):
        la_blk, b_blk = blk
        la_cum, b_cum = jax.lax.associative_scan(
            combine, (la_blk, b_blk), axis=1)
        h_seq = b_cum + jnp.exp(la_cum) * h[:, None, :]
        return h_seq[:, -1, :], h_seq

    h_last, hs = jax.lax.scan(chunk_step, h0,
                              (to_chunks(log_a), to_chunks(bgate)))
    hs = hs.swapaxes(0, 1).reshape(bsz, nc * chunk, w)
    return hs[:, :s], h_last


def _rglru_seq_parallel(u, p, chunk: int, mesh, rules, h0=None):
    """Sequence-parallel RG-LRU: S sharded over the model axis.

    Same structure as models/ssm.ssd_seq_parallel — each rank scans its
    shard locally, then (log-decay, state) boundary summaries propagate
    with an exclusive ppermute Hillis-Steele ladder (the WF-TiS carry at
    ICI scale; states here are the diagonal (B, w) RG-LRU hiddens).
    Returns (h_seq, h_last), h_seq sequence-sharded like u.
    """
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    seq_ax = rules.present(mesh, rules.tp_axes)[0]
    batch_axes = rules.present(mesh, rules.batch_axes)
    b_ax = batch_axes if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    d = mesh.shape[seq_ax]

    def inner(u_shard, h_init):
        first = (lax.axis_index(seq_ax) == 0).astype(h_init.dtype)
        # local scan needs per-position cumulative decay for the prefix
        # correction, so run the gate+scan here rather than reusing the
        # chunked helper's outputs alone.
        log_a, bgate = _rglru_gates(u_shard, p)
        la_cum = jnp.cumsum(log_a, axis=1)               # (B, S_loc, w)

        def combine(xc, yc):
            (la1, b1), (la2, b2) = xc, yc
            return la1 + la2, jnp.exp(la2) * b1 + b2

        _, b_cum = jax.lax.associative_scan(
            combine, (log_a, bgate), axis=1)
        hs = b_cum + jnp.exp(la_cum) * (h_init * first)[:, None, :]
        h_last = hs[:, -1, :]
        la_sum = la_cum[:, -1, :]                        # (B, w)

        # exclusive prefix of (log-decay, state) across seq ranks
        ld = lax.ppermute(la_sum, seq_ax,
                          [(i, i + 1) for i in range(d - 1)])
        hp = lax.ppermute(h_last, seq_ax,
                          [(i, i + 1) for i in range(d - 1)])
        step = 1
        while step < d:
            perm = [(i, i + step) for i in range(d - step)]
            ld_in = lax.ppermute(ld, seq_ax, perm)
            hp_in = lax.ppermute(hp, seq_ax, perm)
            hp = jnp.exp(ld) * hp_in + hp
            ld = ld + ld_in
            step *= 2

        hs = hs + jnp.exp(la_cum) * hp[:, None, :]
        h_fin_local = hs[:, -1, :]
        is_last = (lax.axis_index(seq_ax) == d - 1).astype(hs.dtype)
        h_fin = lax.psum(h_fin_local * is_last, seq_ax)
        return hs, h_fin

    if h0 is None:
        h0 = jnp.zeros((u.shape[0], u.shape[-1]), jnp.float32)
    return shard_map(
        inner, mesh=mesh,
        in_specs=(P(b_ax, seq_ax, None), P(b_ax, None)),
        out_specs=(P(b_ax, seq_ax, None), P(b_ax, None)),
        check_vma=False,
    )(u, h0)


def _rec_mixer(x, p, cfg, state_layer=None):
    """Griffin recurrent block mixer. Returns (out, new_state)."""
    gate = jax.nn.gelu(
        jnp.einsum("bsd,dw->bsw", x, p["w_branch_gate"]).astype(jnp.float32))
    u = jnp.einsum("bsd,dw->bsw", x, p["w_branch_x"])
    conv_tail = state_layer["conv"] if state_layer is not None else None
    from repro.models.ssm import _causal_conv
    u, new_tail = _causal_conv(u, p["conv_w"], p["conv_b"], conv_tail)
    u = u.astype(jnp.float32)
    u = constrain(u, "batch", None, "tp")

    from repro.sharding.rules import current_context
    ctx = current_context()
    s_len = u.shape[1]
    use_sp = (cfg.rnn_seq_parallel and ctx is not None and s_len > 1
              and s_len % ctx.mesh.shape[
                  ctx.rules.present(ctx.mesh, ctx.rules.tp_axes)[0]] == 0)

    if state_layer is None:
        h0 = jnp.zeros((x.shape[0], u.shape[-1]), jnp.float32)
        if use_sp:
            h, _ = _rglru_seq_parallel(u, p, cfg.rnn_scan_chunk,
                                       ctx.mesh, ctx.rules, h0)
        else:
            h, _ = _rglru_chunked(u, p, cfg.rnn_scan_chunk, h0)
        new_state = None
    elif u.shape[1] > 1:
        # prefill into an existing state: scan seeded with it
        if use_sp:
            h, h_last = _rglru_seq_parallel(u, p, cfg.rnn_scan_chunk,
                                            ctx.mesh, ctx.rules,
                                            state_layer["h"])
        else:
            h, h_last = _rglru_chunked(u, p, cfg.rnn_scan_chunk,
                                       state_layer["h"])
        new_state = {"h": h_last, "conv": new_tail}
    else:
        log_a, b = _rglru_gates(u, p)                      # (B, 1, w)
        h1 = jnp.exp(log_a[:, 0]) * state_layer["h"] + b[:, 0]
        h = h1[:, None, :]
        new_state = {"h": h1, "conv": new_tail}
    out = (h * gate).astype(x.dtype)
    out = constrain(out, "batch", None, "tp")
    return jnp.einsum("bsw,wd->bsd", out, p["w_out"]), new_state


# ---------------------------------------------------------------------------
# Blocks / forward
# ---------------------------------------------------------------------------
def _layer(x, p, cfg, kind, *, positions, cache_layer=None):
    h = L.rms_norm(x, p["norm1"]["scale"], cfg.norm_eps)
    if kind == "rec":
        h, new_cache = _rec_mixer(h, p["rec"], cfg, cache_layer)
    else:
        h, new_cache = L.attention_block(
            h, p["attn"], cfg, positions=positions, causal=True,
            sliding_window=cfg.sliding_window, cache=cache_layer,
        )
    x = x + h
    h = L.rms_norm(x, p["norm2"]["scale"], cfg.norm_eps)
    x = x + L.geglu(h, p["mlp"])
    return constrain(x, "batch", None, None), new_cache


def forward(params, tokens, cfg, *, prefix_embeds=None, cache=None,
            positions=None):
    params = L.cast_params(params, cfg.dtype)
    pat, n_groups, tail = _pattern(cfg)
    b, s = tokens.shape
    x = params["embed"][tokens].astype(cfg.dtype)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(cfg.dtype), x], axis=1)
        s = x.shape[1]
    if cfg.scale_embeddings:
        x = x * jnp.sqrt(cfg.d_model).astype(cfg.dtype)
    if positions is None:
        base = cache["len"] if cache is not None else 0
        positions = jnp.broadcast_to(base + jnp.arange(s)[None, :], (b, s))
    x = constrain(x, "batch", None, None)

    if cache is None:
        def body(h, p_group):
            for j, kind in enumerate(pat):
                h, _ = _layer(h, p_group[f"b{j}"], cfg, kind,
                              positions=positions)
            return h, None
        if cfg.remat == "dots":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.checkpoint_dots)
        elif cfg.remat == "full":
            body = jax.checkpoint(body)
        x, _ = L.scan_or_unroll(body, x, params["groups"]["layers"],
                                cfg.scan_layers)
        for j, kind in enumerate(tail):
            x, _ = _layer(x, params["tail"][f"b{j}"], cfg, kind,
                          positions=positions)
        new_cache = None
    else:
        ln = cache["len"]
        def body(h, xs):
            p_group, c_group = xs
            new_c = {}
            for j, kind in enumerate(pat):
                cl = dict(c_group[f"b{j}"])
                if kind == "attn":
                    cl["len"] = ln
                h, nc = _layer(h, p_group[f"b{j}"], cfg, kind,
                               positions=positions, cache_layer=cl)
                if kind == "attn":
                    nc = {k: v for k, v in nc.items() if k != "len"}
                new_c[f"b{j}"] = nc
            return h, new_c
        group_cache = cache["groups"]
        x, new_groups = L.scan_or_unroll(
            body, x, (params["groups"]["layers"], group_cache),
            cfg.scan_layers)
        new_cache = {"groups": new_groups, "len": ln + s}
        if tail:
            new_cache["tail"] = {}
            for j, kind in enumerate(tail):
                cl = dict(cache["tail"][f"b{j}"])
                if kind == "attn":
                    cl["len"] = ln
                x, nc = _layer(x, params["tail"][f"b{j}"], cfg, kind,
                               positions=positions, cache_layer=cl)
                if kind == "attn":
                    nc = {k: v for k, v in nc.items() if k != "len"}
                new_cache["tail"][f"b{j}"] = nc

    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(cfg.dtype))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(cfg.dtype))
    logits = L.softcap(logits.astype(jnp.float32), cfg.logits_softcap)
    logits = constrain(logits, "batch", None, "tp")
    return logits, jnp.zeros((), jnp.float32), (
        new_cache if cache is not None else None)


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    from repro.models.cache import ring_kv_cache, rglru_state

    pat, n_groups, tail = _pattern(cfg)
    window = min(cfg.sliding_window or max_len, max_len)

    def layer_cache(kind, n):
        if kind == "attn":
            c = ring_kv_cache(n, batch, window, cfg.num_kv_heads,
                              cfg.head_dim, dtype)
            return {k: v for k, v in c.items() if k != "len"}
        c = rglru_state(n, batch, cfg.rnn_width, cfg.conv_kernel)
        return c

    cache = {
        "groups": {f"b{j}": layer_cache(kind, n_groups)
                   for j, kind in enumerate(pat)},
        "len": jnp.zeros((), jnp.int32),
    }
    if tail:
        cache["tail"] = {f"b{j}": jax.tree.map(lambda t: t[0], layer_cache(kind, 1))
                         for j, kind in enumerate(tail)}
    return cache
