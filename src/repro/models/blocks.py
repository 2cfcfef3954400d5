"""Per-layer blocks: attention (global/local), dense & MoE FFN, RG-LRU,
RWKV6 time-mix.  Every block owns its FFN (Griffin-style residual pair:
temporal mixing + MLP), so a layer == one block.

Block interface (uniform so the backbone can ``lax.scan`` over periods):

    init_block(key, cfg, blk)                          -> params
    apply_block(p, cfg, blk, x, ctx, cache)            -> (x, cache)
    init_block_cache(cfg, blk, batch, capacity, dtype) -> cache | {}

``cache`` is {} during training; during serving it carries the family's
state (KV ring buffer / RG-LRU hidden+conv state / RWKV6 matrix state) and
is threaded through scan.  ``ctx``: dict(sin, cos, q_offset, impl,
positions) shared across layers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.nn import Linear, LayerNorm, RMSNorm, ACTIVATIONS, normal_init, zeros_init
from repro.nn.attention import (
    attention_core, chunked_attention_core, make_attention_mask)
from repro.nn.rope import apply_rope


def _norm_init(cfg, d=None):
    d = d or cfg.d_model
    return (RMSNorm if cfg.norm == "rms" else LayerNorm).init(None, d)


def _norm_apply(cfg, p, x):
    return (RMSNorm if cfg.norm == "rms" else LayerNorm).apply(p, x)


# ===========================================================================
# FFN: dense (GLU / plain) and MoE (sort-based dispatch with capacity)
# ===========================================================================

def init_ffn(key, cfg):
    if cfg.moe is not None:
        return init_moe(key, cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    p = {"up": Linear.init(k1, d, f, use_bias=False),
         "down": Linear.init(k2, f, d, use_bias=False)}
    if cfg.glu:
        p["gate"] = Linear.init(k3, d, f, use_bias=False)
    return p


def apply_ffn(p, cfg, x, ctx=None):
    if cfg.moe is not None:
        return apply_moe(p, cfg, x, ctx)
    act = ACTIVATIONS[cfg.activation]
    u = Linear.apply(p["up"], x)
    if cfg.glu:
        u = act(Linear.apply(p["gate"], x)) * u
    else:
        u = act(u)
    return Linear.apply(p["down"], u)


def init_moe(key, cfg):
    m = cfg.moe
    d, f = cfg.d_model, m.d_expert
    ks = jax.random.split(key, 7)
    std = 0.02
    p = {
        "router": Linear.init(ks[0], d, m.n_experts, use_bias=False),
        # stacked expert weights: (E, d, f) / (E, f, d)
        "w_up": normal_init(ks[1], (m.n_experts, d, f), stddev=std),
        "w_down": normal_init(ks[2], (m.n_experts, f, d), stddev=std),
    }
    if cfg.glu:
        p["w_gate"] = normal_init(ks[3], (m.n_experts, d, f), stddev=std)
    if m.n_shared:
        fs = (m.d_shared or m.d_expert) * m.n_shared
        p["shared_up"] = Linear.init(ks[4], d, fs, use_bias=False)
        p["shared_down"] = Linear.init(ks[5], fs, d, use_bias=False)
        if cfg.glu:
            p["shared_gate"] = Linear.init(ks[6], d, fs, use_bias=False)
    return p


def moe_capacity(n_tokens: int, cfg) -> int:
    m = cfg.moe
    c = int(n_tokens * m.top_k / m.n_experts * m.capacity_factor) + 1
    return max(8, -(-c // 8) * 8)   # round up to 8 for layout friendliness


def apply_moe(p, cfg, x, ctx=None):
    if cfg.moe.impl == "local_group":
        return apply_moe_grouped(p, cfg, x, ctx)
    return apply_moe_global(p, cfg, x)


def _ep_constrain(x, ctx, expert_axis: int | None):
    """Pin the EP layout: batch rows on the DP axes; the expert dim (if
    given) on 'model'.  Without this GSPMD lets the dispatch scatter's
    destination sharding float and resolves it with full all-gathers of
    the (B, E·cap, d) buffers (measured 3.9 TB/device on granite —
    EXPERIMENTS.md §Perf iteration 1)."""
    mesh = (ctx or {}).get("mesh")
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.runtime.sharding import data_axes
    dp = data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    spec = [None] * x.ndim
    if dp_size > 1 and x.shape[0] % dp_size == 0:
        spec[0] = dp
    if expert_axis is not None and mesh.shape.get("model", 1) > 1 and \
            x.shape[expert_axis] % mesh.shape["model"] == 0:
        spec[expert_axis] = "model"
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def _shared_experts(p, cfg, xt):
    act = ACTIVATIONS[cfg.activation]
    u = Linear.apply(p["shared_up"], xt)
    if cfg.glu:
        u = act(Linear.apply(p["shared_gate"], xt)) * u
    else:
        u = act(u)
    return Linear.apply(p["shared_down"], u)


def apply_moe_grouped(p, cfg, x, ctx=None):
    """Locality-aware dispatch (§Perf): routing, sort and capacity are
    computed PER BATCH ROW, so under GSPMD they never leave the row's
    data shard; the only cross-device traffic is the (B, E, cap, d)
    activation redistribution to the expert ('model') shards and back —
    the canonical expert-parallel all-to-all pair.

    The baseline ``apply_moe_global`` sorts all B·L·K assignments
    globally: a sharded sort plus global scatters, which the dry-run
    showed costs ~20x the EP all-to-all bytes (EXPERIMENTS.md §Perf).
    """
    m = cfg.moe
    b, l, d = x.shape
    cap = moe_capacity(l, cfg)                 # per row

    gates = jax.nn.softmax(
        Linear.apply(p["router"], x).astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(gates, m.top_k)           # (B, L, K)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    lk = l * m.top_k

    # --- routing plan: GATHER-ONLY (no forward scatters).  Batched
    # gathers (take_along_axis on axis 1) carry explicit batch dims that
    # GSPMD partitions over 'data'; scatters with computed 2-D indices do
    # NOT partition and fall back to replicated sort-expander machinery
    # on the global batch (measured: 5.8 TB/layer u32 traffic — §Perf).
    e_flat = topi.reshape(b, lk)
    order = jnp.argsort(e_flat, axis=1, stable=True)      # sort by expert
    inv_order = jnp.argsort(order, axis=1, stable=True)   # inverse perm
    e_sorted = jnp.take_along_axis(e_flat, order, axis=1)
    onehot_counts = (e_flat[:, :, None] ==
                     jnp.arange(m.n_experts)[None, None]).sum(1)  # (B, E)
    group_start = jnp.cumsum(onehot_counts, 1) - onehot_counts
    rank_sorted = jnp.arange(lk)[None] - jnp.take_along_axis(
        group_start, e_sorted, axis=1)
    pos = jnp.take_along_axis(rank_sorted, inv_order, axis=1)  # (B, LK)
    keep = pos < cap
    slot = jnp.minimum(e_flat * cap + jnp.minimum(pos, cap - 1),
                       m.n_experts * cap - 1)

    # dispatch: x sorted by expert, then fixed-capacity slots per expert
    tok = jnp.repeat(jnp.arange(l), m.top_k)[None]            # (1, LK)
    tok_sorted = jnp.take_along_axis(
        jnp.broadcast_to(tok, (b, lk)), order, axis=1)
    x = _ep_constrain(x, ctx, None)
    x_sorted = jnp.take_along_axis(x, tok_sorted[..., None], axis=1)
    idx = group_start[:, :, None] + jnp.arange(cap)[None, None]  # (B,E,cap)
    valid = jnp.arange(cap)[None, None] < jnp.minimum(
        onehot_counts, cap)[:, :, None]
    idx = jnp.clip(idx, 0, lk - 1).reshape(b, -1)
    xe = jnp.take_along_axis(x_sorted, idx[..., None], axis=1)
    xe = jnp.where(valid.reshape(b, -1, 1), xe, 0)
    xe = xe.reshape(b, m.n_experts, cap, d)
    xe = _ep_constrain(xe, ctx, 1)           # expert dim -> 'model' (EP)

    act = ACTIVATIONS[cfg.activation]
    up = jnp.einsum("becd,edf->becf", xe, p["w_up"].astype(x.dtype))
    if cfg.glu:
        up = act(jnp.einsum("becd,edf->becf", xe,
                            p["w_gate"].astype(x.dtype))) * up
    else:
        up = act(up)
    ye = jnp.einsum("becf,efd->becd", up, p["w_down"].astype(x.dtype))

    # combine: every token gathers its k expert outputs back (the second
    # EP collective is the resharding behind this constraint), then a
    # reshape-sum — no scatter (tok order is contiguous by construction)
    ye = _ep_constrain(ye, ctx, None)
    ye = ye.reshape(b, m.n_experts * cap, d)
    yk = jnp.take_along_axis(ye, slot[..., None], axis=1)
    yk = yk * (keep * topv.reshape(b, -1)).astype(x.dtype)[..., None]
    out = yk.reshape(b, l, m.top_k, d).sum(2)

    if m.n_shared:
        out = out + _shared_experts(p, cfg, x.reshape(b * l, d)
                                    ).reshape(b, l, d)

    density = onehot_counts.astype(jnp.float32).sum(0) / (b * l)
    aux = m.n_experts * jnp.sum(density / m.top_k * gates.mean((0, 1)))
    return out, aux


def apply_moe_global(p, cfg, x):
    """Sort-based token dispatch with static per-expert capacity.

    x: (B, L, D).  Tokens beyond an expert's capacity are dropped (their
    contribution is only from other selected experts / shared experts) —
    standard GShard-style behaviour; the aux loss keeps load balanced.
    """
    m = cfg.moe
    b, l, d = x.shape
    t = b * l
    xt = x.reshape(t, d)
    cap = moe_capacity(t, cfg)

    gates = jax.nn.softmax(
        Linear.apply(p["router"], xt).astype(jnp.float32), axis=-1)  # (T,E)
    topv, topi = jax.lax.top_k(gates, m.top_k)                        # (T,K)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert via stable sort
    e_flat = topi.reshape(-1)                                  # (T*K,)
    order = jnp.argsort(e_flat)                                # stable
    e_sorted = e_flat[order]
    group_start = jnp.searchsorted(e_sorted, jnp.arange(m.n_experts))
    pos_sorted = jnp.arange(t * m.top_k) - group_start[e_sorted]
    pos = jnp.zeros_like(e_flat).at[order].set(pos_sorted)     # (T*K,)
    keep = pos < cap
    slot = jnp.where(keep, e_flat * cap + pos, m.n_experts * cap)

    # scatter tokens to (E*cap [+1 overflow], d); slots are unique when kept
    tok_idx = jnp.repeat(jnp.arange(t), m.top_k)
    xe = jnp.zeros((m.n_experts * cap + 1, d), x.dtype).at[slot].set(xt[tok_idx])
    xe = xe[:-1].reshape(m.n_experts, cap, d)

    act = ACTIVATIONS[cfg.activation]
    up = jnp.einsum("ecd,edf->ecf", xe, p["w_up"].astype(x.dtype))
    if cfg.glu:
        up = act(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(x.dtype))) * up
    else:
        up = act(up)
    ye = jnp.einsum("ecf,efd->ecd", up, p["w_down"].astype(x.dtype))

    # gather back with gate weights
    yk = ye.reshape(m.n_experts * cap, d)[jnp.minimum(slot, m.n_experts * cap - 1)]
    yk = yk * (keep * topv.reshape(-1)).astype(x.dtype)[:, None]
    out = jnp.zeros((t, d), x.dtype).at[tok_idx].add(yk)

    if m.n_shared:
        u = Linear.apply(p["shared_up"], xt)
        if cfg.glu:
            u = act(Linear.apply(p["shared_gate"], xt)) * u
        else:
            u = act(u)
        out = out + Linear.apply(p["shared_down"], u)

    # load-balancing aux loss (Switch): E * sum_e f_e * p_e
    density = jnp.zeros((m.n_experts,), jnp.float32).at[e_flat].add(1.0) / t
    mean_gate = gates.mean(axis=0)
    aux = m.n_experts * jnp.sum(density / m.top_k * mean_gate)
    return out.reshape(b, l, d), aux


# ===========================================================================
# Attention block ('attn' global, 'local' windowed)
# ===========================================================================

def _seq_shard(x, ctx, *, on_model: bool):
    """§Perf: when the head axes don't divide the TP mesh axis, shard the
    attention core along L instead (queries L-sharded on 'model'; K/V
    replicated across 'model' — one all-gather per layer instead of
    partial-logit all-reduces).  No-op without a mesh in ctx."""
    mesh = ctx.get("mesh")
    if mesh is None:
        return x
    import jax as _jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.runtime.sharding import data_axes
    dp = data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    model = mesh.shape.get("model", 1)
    b, l = x.shape[0], x.shape[1]
    bspec = dp if (dp_size > 1 and b % dp_size == 0) else None
    lspec = "model" if (on_model and model > 1 and l % model == 0) else None
    spec = P(bspec, lspec, *([None] * (x.ndim - 2)))
    return _jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _want_seq_shard(cfg, ctx) -> bool:
    """Auto policy: head-sharded attention (the TP default) only works
    when BOTH head axes divide the model axis; otherwise GSPMD shards the
    head_dim contraction and pays partial-logit all-reduces per KV chunk
    (measured 22x step-time on qwen2-1.5b prefill — §Perf).  Under a mesh
    whose model axis the heads don't divide, switch the attention core to
    sequence sharding."""
    if cfg.attn_seq_shard:
        return True
    mesh = ctx.get("mesh")
    if mesh is None:
        return False
    model = mesh.shape.get("model", 1)
    return model > 1 and (cfg.n_heads % model != 0 or
                          cfg.n_kv_heads % model != 0)


def init_attention(key, cfg):
    d, h, hk, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 5)
    return {
        "ln1": _norm_init(cfg),
        "wq": Linear.init(ks[0], d, (h, hd), use_bias=cfg.qkv_bias),
        "wk": Linear.init(ks[1], d, (hk, hd), use_bias=cfg.qkv_bias),
        "wv": Linear.init(ks[2], d, (hk, hd), use_bias=cfg.qkv_bias),
        "wo": Linear.init(ks[3], h * hd, d, use_bias=False),
        "ln2": _norm_init(cfg),
        "ffn": init_ffn(ks[4], cfg),
    }


def init_kv_cache(cfg, batch: int, capacity: int, dtype):
    hk, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, capacity, hk, hd), dtype),
        "v": jnp.zeros((batch, capacity, hk, hd), dtype),
        "pos": jnp.full((capacity,), -1, jnp.int32),   # position held per slot
        "idx": jnp.zeros((), jnp.int32),               # next absolute position
    }


def init_paged_kv_cache(cfg, batch: int, num_blocks: int, block_size: int,
                        max_blocks: int, dtype, quant=None):
    """Paged layout (DESIGN.md): a shared block pool per layer plus a
    per-row block table.  The table rows are driven by the host-side
    ``serve.kvpool.KVPool`` allocator via ``serve.set_block_tables``.
    quant: 'int8'/'fp8' stores quantized pages + per-slot scales."""
    from repro.serve import kvpool
    c = kvpool.init_pages(num_blocks, block_size, cfg.n_kv_heads,
                          cfg.head_dim, dtype, quant=quant)
    c["bt"] = jnp.full((batch, max_blocks), -1, jnp.int32)
    return c


def _paged_positions(ctx, batch: int, l: int):
    """Per-row absolute positions (B, L) from ctx['q_offset'] (scalar or
    (B,) vector; -1 marks an inactive row -> all positions invalid).
    ctx['q_end'] (scalar or (B,)), if present, invalidates positions at
    or past it — chunked prefill pads the last chunk of a prompt to a
    shape bucket, and the padded tail must neither write real KV nor
    attend (its writes route to the trash block, its queries are fully
    masked)."""
    qo = jnp.asarray(ctx.get("q_offset", 0))
    if qo.ndim == 0:
        qo = jnp.full((batch,), qo)
    pos = qo[:, None] + jnp.arange(l)[None]
    q_end = ctx.get("q_end")
    if q_end is not None:
        qe = jnp.asarray(q_end)
        if qe.ndim == 0:
            qe = jnp.full((batch,), qe)
        pos = jnp.where(pos >= qe[:, None], -1, pos)
    return jnp.where(qo[:, None] < 0, -1, pos)


def _cache_write(cache, k, v, q_offset):
    """Write L new entries at absolute positions q_offset..q_offset+L-1,
    ring-buffered modulo capacity.  Works for prefill (L>1) and decode."""
    cap = cache["k"].shape[1]
    l = k.shape[1]
    if l > cap:          # window prefill: only the last `cap` entries survive
        k, v = k[:, -cap:], v[:, -cap:]
        q_offset = q_offset + (l - cap)
        l = cap
    pos = q_offset + jnp.arange(l)
    slots = pos % cap
    ck = cache["k"].at[:, slots].set(k)
    cv = cache["v"].at[:, slots].set(v)
    cpos = cache["pos"].at[slots].set(pos)
    return {"k": ck, "v": cv, "pos": cpos, "idx": cache["idx"] + l}


def apply_attention(p, cfg, blk, x, ctx, cache):
    b, l, d = x.shape
    h = _norm_apply(cfg, p["ln1"], x)
    q = Linear.apply(p["wq"], h)          # (B, L, H, hd)
    k = Linear.apply(p["wk"], h)          # (B, L, Hkv, hd)
    v = Linear.apply(p["wv"], h)
    window = cfg.local_window if blk == "local" else cfg.window

    if ctx.get("sin") is not None:
        q = apply_rope(q, ctx["sin"], ctx["cos"])
        k = apply_rope(k, ctx["sin"], ctx["cos"])

    q_offset = ctx.get("q_offset", 0)
    paged = bool(cache) and "bt" in cache
    if cache and l == 1 and ctx.get("rows") is None:
        # decode: attend over the cache (current token already written).
        # A row-subset prefill (ctx['rows']) of a 1-token prompt is NOT
        # a decode — its batch maps to a block-table subset and its
        # attention runs over the fresh K/V in the else-branch below
        if paged:
            from repro.serve.kvpool import paged_write, paged_view
            posm = _paged_positions(ctx, b, l)                  # (B, 1)
            cache = paged_write(cache, k, v, posm, trash=ctx.get("trash"))
            if ctx.get("use_kernels") and cfg.logit_softcap is None:
                from repro.kernels import ops as kops
                mesh = ctx.get("mesh")
                # quantized pages: hand the kernels the per-slot scales so
                # dequant fuses into the page loads (paged_view below
                # would materialize fp32 pages outside the kernel)
                scale_kw = ({"k_scales": cache["ksc"],
                             "v_scales": cache["vsc"]}
                            if "ksc" in cache else {})
                if mesh is not None:
                    # shard_map (GSPMD cannot partition a Mosaic kernel):
                    # each data shard runs the kernel over its resident
                    # pages only (block tables are shard-local by the
                    # ShardedKVPool invariant) — no cross-device page
                    # gathers on the decode path
                    o = kops.sharded_paged_attention(
                        mesh, q, cache["kp"], cache["vp"], cache["bt"],
                        cache["ppos"], posm[:, 0], window=window,
                        causal=cfg.causal,
                        rows_sharded=b % mesh.shape["data"] == 0,
                        **scale_kw)
                else:
                    o = kops.paged_attention(
                        q, cache["kp"], cache["vp"], cache["bt"],
                        cache["ppos"], posm[:, 0], window=window,
                        causal=cfg.causal, **scale_kw)
            else:
                kc, vc, kvpos = paged_view(cache)
                mask = make_attention_mask(
                    posm, kvpos, causal=cfg.causal, window=window,
                    kv_valid=kvpos >= 0)
                mask &= (posm >= 0)[..., None]        # inactive rows
                o = attention_core(q, kc, vc, mask=mask,
                                   logit_softcap=cfg.logit_softcap)
        elif ctx.get("use_kernels") and cfg.logit_softcap is None:
            cache = _cache_write(cache, k, v, q_offset)
            from repro.kernels import ops as kops
            o = kops.decode_attention(
                q, cache["k"], cache["v"], cache["pos"],
                q_pos=q_offset, window=window, causal=cfg.causal)
        else:
            cache = _cache_write(cache, k, v, q_offset)
            q_pos = q_offset + jnp.arange(l)
            mask = make_attention_mask(
                q_pos, cache["pos"], causal=cfg.causal, window=window,
                kv_valid=cache["pos"] >= 0)[None]
            o = attention_core(q, cache["k"], cache["v"], mask=mask,
                               logit_softcap=cfg.logit_softcap)
    elif paged and ctx.get("chunked"):
        # chunked prefill: scatter this chunk's K/V into the rows' pages,
        # then attend over EVERY previously written block plus the
        # chunk's own entries (mid-sequence chunks depend on earlier
        # chunks' KV, unlike the single-shot prefill below which only
        # ever sees its own fresh K/V).
        from repro.serve.kvpool import paged_write, paged_view
        rows = ctx.get("rows")
        bt = cache["bt"] if rows is None else cache["bt"][rows]
        posm = _paged_positions(ctx, b, l)                  # (B, L)
        cache = paged_write(cache, k, v, posm, block_tables=bt,
                            trash=ctx.get("trash"))
        if ctx.get("use_kernels") and cfg.logit_softcap is None:
            from repro.kernels import ops as kops
            q_start = posm[:, 0]                            # -1 iff inactive
            q_len = (posm >= 0).sum(-1)
            mesh = ctx.get("mesh")
            scale_kw = ({"k_scales": cache["ksc"], "v_scales": cache["vsc"]}
                        if "ksc" in cache else {})
            if mesh is not None:
                # rows split over 'data' only for FULL-GRID chunk
                # batches: a rows= subset has no guaranteed row->shard
                # alignment, so its rows replicate and the owning
                # shard's output is kept (psum)
                o = kops.sharded_paged_prefill_attention(
                    mesh, q, cache["kp"], cache["vp"], bt, cache["ppos"],
                    q_start, q_len, window=window, causal=cfg.causal,
                    rows_sharded=(rows is None
                                  and b % mesh.shape["data"] == 0),
                    **scale_kw)
            else:
                o = kops.paged_prefill_attention(
                    q, cache["kp"], cache["vp"], bt, cache["ppos"],
                    q_start, q_len, window=window, causal=cfg.causal,
                    **scale_kw)
        else:
            kc, vc, kvpos = paged_view({**cache, "bt": bt})
            mask = make_attention_mask(
                posm, kvpos, causal=cfg.causal, window=window,
                kv_valid=kvpos >= 0)
            mask &= (posm >= 0)[..., None]       # padded / inactive queries
            o = attention_core(q, kc, vc, mask=mask,
                               logit_softcap=cfg.logit_softcap)
    else:
        if paged:
            # paged prefill: scatter the joining rows' K/V into their
            # freshly allocated blocks (ctx['rows'] selects the block-table
            # rows when prefilling a subset of the grid); attention still
            # runs over the fresh K/V below.
            from repro.serve.kvpool import paged_write
            rows = ctx.get("rows")
            bt = cache["bt"] if rows is None else cache["bt"][rows]
            posm = _paged_positions(ctx, b, l)
            cache = paged_write(cache, k, v, posm, block_tables=bt,
                                trash=ctx.get("trash"))
        elif cache:
            # single-shot prefill: cache is write-only; attention runs over
            # the fresh K/V (correct for any window / capacity relation).
            cache = _cache_write(cache, k, v, q_offset)
        seq_shard = _want_seq_shard(cfg, ctx)
        if seq_shard:
            q = _seq_shard(q, ctx, on_model=True)
            k = _seq_shard(k, ctx, on_model=False)
            v = _seq_shard(v, ctx, on_model=False)
        impl = ctx.get("impl", "naive")
        if impl == "chunked":
            o = chunked_attention_core(
                q, k, v, causal=cfg.causal, window=window,
                q_offset=q_offset, chunk_size=cfg.attn_chunk,
                logit_softcap=cfg.logit_softcap)
        elif impl == "flash":
            from repro.kernels import ops as kops
            o = kops.flash_attention(q, k, v, causal=cfg.causal,
                                     window=window,
                                     logit_softcap=cfg.logit_softcap)
        else:
            mask = None
            if cfg.causal or window is not None:
                pos = q_offset + jnp.arange(l)
                mask = make_attention_mask(pos, pos, causal=cfg.causal,
                                           window=window)[None]
            o = attention_core(q, k, v, mask=mask,
                               logit_softcap=cfg.logit_softcap)
        if seq_shard:
            o = _seq_shard(o, ctx, on_model=True)

    x = x + Linear.apply(p["wo"], o.reshape(b, l, -1))
    h = _norm_apply(cfg, p["ln2"], x)
    y = apply_ffn(p["ffn"], cfg, h, ctx)
    aux = 0.0
    if isinstance(y, tuple):
        y, aux = y
    return x + y, cache, aux


# ===========================================================================
# RG-LRU block (Griffin / RecurrentGemma temporal mixing + MLP)
# ===========================================================================

def init_rglru(key, cfg):
    d = cfg.d_model
    w = d                                   # lru width = d_model
    ks = jax.random.split(key, 8)
    return {
        "ln1": _norm_init(cfg),
        "w_in": Linear.init(ks[0], d, w, use_bias=False),
        "w_gate": Linear.init(ks[1], d, w, use_bias=False),
        "conv_w": normal_init(ks[2], (4, w), stddev=0.02),   # depthwise, 4 taps
        "conv_b": zeros_init(None, (w,)),
        "w_a": Linear.init(ks[3], w, w, use_bias=True),      # recurrence gate
        "w_i": Linear.init(ks[4], w, w, use_bias=True),      # input gate
        "lam": normal_init(ks[5], (w,), stddev=0.5),         # Λ (a = exp(-8·softplus(Λ)·r))
        "w_out": Linear.init(ks[6], w, d, use_bias=False),
        "ln2": _norm_init(cfg),
        "ffn": init_ffn(ks[7], cfg),
    }


def init_rglru_cache(cfg, batch: int, dtype):
    w = cfg.d_model
    return {"h": jnp.zeros((batch, w), jnp.float32),
            "conv": jnp.zeros((batch, 3, w), dtype)}        # last 3 inputs


def _causal_depthwise_conv(y, w, b, conv_state=None):
    """y: (B, L, W); 4-tap causal depthwise conv.  conv_state: (B, 3, W)."""
    if conv_state is None:
        ypad = jnp.pad(y, ((0, 0), (3, 0), (0, 0)))
    else:
        ypad = jnp.concatenate([conv_state.astype(y.dtype), y], axis=1)
    out = sum(ypad[:, i:i + y.shape[1]] * w[i].astype(y.dtype)
              for i in range(4)) + b.astype(y.dtype)
    new_state = ypad[:, -3:]
    return out, new_state


def apply_rglru(p, cfg, blk, x, ctx, cache):
    b, l, d = x.shape
    h = _norm_apply(cfg, p["ln1"], x)
    y = Linear.apply(p["w_in"], h)
    gate = Linear.apply(p["w_gate"], h)
    y, conv_state = _causal_depthwise_conv(
        y, p["conv_w"], p["conv_b"], cache.get("conv") if cache else None)

    r = jax.nn.sigmoid(Linear.apply(p["w_a"], y).astype(jnp.float32))
    i = jax.nn.sigmoid(Linear.apply(p["w_i"], y).astype(jnp.float32))
    log_a = -8.0 * jax.nn.softplus(p["lam"].astype(jnp.float32)) * r   # (B,L,W)
    a = jnp.exp(log_a)
    gated_in = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) \
        * i * y.astype(jnp.float32)

    h0 = cache["h"] if cache else jnp.zeros((b, d), jnp.float32)
    # first-order linear recurrence h_t = a_t h_{t-1} + u_t  (assoc. scan)
    u = gated_in.at[:, 0].add(a[:, 0] * h0)

    def op(c1, c2):
        a1, u1 = c1
        a2, u2 = c2
        return a1 * a2, a2 * u1 + u2

    a_s, h_seq = jax.lax.associative_scan(op, (a, u), axis=1)
    new_cache = {}
    if cache:
        new_cache = {"h": h_seq[:, -1], "conv": conv_state}

    out = (h_seq.astype(x.dtype) * jax.nn.gelu(gate))
    x = x + Linear.apply(p["w_out"], out)
    hh = _norm_apply(cfg, p["ln2"], x)
    y2 = apply_ffn(p["ffn"], cfg, hh)
    aux = 0.0
    if isinstance(y2, tuple):
        y2, aux = y2
    return x + y2, new_cache, aux


# ===========================================================================
# RWKV6 block (Finch: data-dependent decay linear attention + channel mix)
# ===========================================================================

def init_rwkv(key, cfg):
    d = cfg.d_model
    nh = cfg.rwkv_heads or d // 64
    hd = d // nh
    ks = jax.random.split(key, 12)
    lora = 64
    return {
        "ln1": _norm_init(cfg),
        # token-shift lerp coefficients for r,k,v,g
        "mu": normal_init(ks[0], (4, d), stddev=0.02),
        "w_r": Linear.init(ks[1], d, (nh, hd), use_bias=False),
        "w_k": Linear.init(ks[2], d, (nh, hd), use_bias=False),
        "w_v": Linear.init(ks[3], d, (nh, hd), use_bias=False),
        "w_g": Linear.init(ks[4], d, d, use_bias=False),
        # data-dependent decay: w = exp(-exp(w0 + tanh(x A) B))
        "dec_w0": normal_init(ks[5], (d,), stddev=0.02),
        "dec_a": normal_init(ks[6], (d, lora), stddev=0.02),
        "dec_b": normal_init(ks[7], (lora, d), stddev=0.02),
        "u": normal_init(ks[8], (nh, hd), stddev=0.02),      # bonus
        "gn_scale": jnp.ones((d,), jnp.float32),             # per-head groupnorm
        "gn_bias": jnp.zeros((d,), jnp.float32),
        "w_o": Linear.init(ks[9], d, d, use_bias=False),
        "ln2": _norm_init(cfg),
        # channel mix (squared-relu MLP with token shift)
        "mu_cm": normal_init(ks[10], (d,), stddev=0.02),
        "cm_k": Linear.init(ks[11], d, cfg.d_ff, use_bias=False),
        "cm_v": Linear.init(jax.random.fold_in(key, 99), cfg.d_ff, d,
                            use_bias=False),
    }


def init_rwkv_cache(cfg, batch: int, dtype):
    d = cfg.d_model
    nh = cfg.rwkv_heads or d // 64
    hd = d // nh
    return {"s": jnp.zeros((batch, nh, hd, hd), jnp.float32),
            "shift_tm": jnp.zeros((batch, d), dtype),
            "shift_cm": jnp.zeros((batch, d), dtype)}


def _token_shift(x, prev):
    """x: (B, L, D); prev: (B, D) last token of previous segment."""
    if prev is None:
        prev = jnp.zeros_like(x[:, 0])
    return jnp.concatenate([prev[:, None], x[:, :-1]], axis=1)


def rwkv_chunked(r, k, v, logw, u, s0, chunk: int,
                 intra_dtype=jnp.float32, remat_inner: bool = False):
    """Chunkwise-parallel RWKV6 recurrence.

    r,k,v: (B, L, H, hd); logw: (B, L, H, hd) (log decay, < 0);
    u: (H, hd) bonus; s0: (B, H, hd, hd) carry.
    Returns out (B, L, H, hd), sT.

    Within a chunk the pairwise decay exp(la_{t-1} - la_j) is materialized
    as a (c, c, hd) tensor per (B, H) — bounded because c is small; across
    chunks the (hd x hd) state is carried by ``lax.scan``.
    """
    b, l, h, hd = r.shape
    nc = l // chunk
    c = chunk

    def reshape_c(x):
        return x.reshape(b, nc, c, h, hd).transpose(1, 0, 3, 2, 4)  # (nc,B,H,c,hd)

    rc, kc, vc, lwc = map(reshape_c, (r, k, v, logw))

    def step(s, xs):
        rj, kj, vj, lw = xs                       # (B,H,c,hd)
        la = jnp.cumsum(lw, axis=2)               # (B,H,c,hd) log decay incl. t
        la_prev = la - lw                         # log decay up to t-1
        # inter-chunk: r_t ⊙ exp(la_prev) applied to carried state
        r_in = rj * jnp.exp(la_prev)
        out = jnp.einsum("bhck,bhkv->bhcv", r_in, s).astype(jnp.float32)
        # intra-chunk: sum_{j<t} (r_t ⊙ exp(la_prev_t - la_j)) · k_j  v_j
        # (the (c, c, hd) decay tensor dominates HBM traffic; §Perf casts
        # it to `intra_dtype` — the Pallas kernel keeps it in VMEM)
        decay = jnp.exp(
            la_prev[:, :, :, None, :] - la[:, :, None, :, :])
        tri = jnp.tril(jnp.ones((c, c)), -1)[None, None, :, :, None]
        decay = (decay * tri).astype(intra_dtype)
        att = jnp.einsum("bhtk,bhjk,bhtjk->bhtj",
                         rj.astype(intra_dtype), kj.astype(intra_dtype),
                         decay)
        # bonus diagonal: (r_t ⊙ u) · k_t
        bonus = jnp.einsum("bhtk,bhtk->bht", rj * u[None, :, None, :], kj)
        out = out + jnp.einsum(
            "bhtj,bhjv->bhtv", att,
            vj.astype(intra_dtype)).astype(jnp.float32) \
            + bonus[..., None] * vj
        # carry: s' = diag(exp(la_c)) s + sum_j exp(la_c - la_j) k_j v_j^T
        la_end = la[:, :, -1:, :]
        k_scaled = kj * jnp.exp(la_end - la)
        s = jnp.exp(la_end[:, :, 0, :])[..., None] * s + \
            jnp.einsum("bhck,bhcv->bhkv", k_scaled, vj)
        return s, out

    # nested remat: without it the chunk scan stores every chunk's
    # (c, c, hd) decay tensor for backward — the dominant HBM traffic of
    # rwkv training (measured 36 TB/device on rwkv6-7b train_4k, §Perf);
    # the decay is an exp of a cumsum and is far cheaper to recompute
    fn = jax.checkpoint(step, prevent_cse=False) if remat_inner else step
    sT, outs = jax.lax.scan(fn, s0, (rc, kc, vc, lwc))
    out = outs.transpose(1, 0, 3, 2, 4).reshape(b, l, h, hd)
    return out, sT


def apply_rwkv(p, cfg, blk, x, ctx, cache):
    b, l, d = x.shape
    nh = cfg.rwkv_heads or d // 64
    hd = d // nh
    h = _norm_apply(cfg, p["ln1"], x)

    prev_tm = cache.get("shift_tm") if cache else None
    hs = _token_shift(h, prev_tm)
    mu = p["mu"].astype(h.dtype)
    hr, hk, hv, hg = (h + (hs - h) * mu[i] for i in range(4))

    r = Linear.apply(p["w_r"], hr)                   # (B,L,H,hd)
    k = Linear.apply(p["w_k"], hk)
    v = Linear.apply(p["w_v"], hv)
    g = jax.nn.silu(Linear.apply(p["w_g"], hg))      # (B,L,D)

    dec = p["dec_w0"].astype(jnp.float32) + jnp.tanh(
        h.astype(jnp.float32) @ p["dec_a"]) @ p["dec_b"]
    logw = -jnp.exp(dec).reshape(b, l, nh, hd)       # log decay < 0

    s0 = cache["s"] if cache else jnp.zeros((b, nh, hd, hd), jnp.float32)
    chunk = min(l, cfg.rwkv_chunk if l % cfg.rwkv_chunk == 0 else l)
    if cfg.rwkv_intra_dtype == "bf16":
        intra = jnp.bfloat16
        rf, kf, vf = r, k, v          # keep TP boundaries in bf16
    else:
        intra = jnp.float32
        rf, kf, vf = (t.astype(jnp.float32) for t in (r, k, v))
    out, sT = rwkv_chunked(rf, kf, vf, logw, p["u"].astype(jnp.float32),
                           s0, chunk, intra_dtype=intra,
                           remat_inner=not cache)
    out = out.astype(x.dtype)

    new_cache = {}
    if cache:
        new_cache = {"s": sT, "shift_tm": h[:, -1], "shift_cm": None}

    # per-head groupnorm, then gate and project
    o = out.reshape(b, l, nh, hd)
    mu_ = o.mean(-1, keepdims=True)
    var = o.var(-1, keepdims=True)
    o = (o - mu_) * jax.lax.rsqrt(var + 1e-5)
    o = o.reshape(b, l, d) * p["gn_scale"] + p["gn_bias"]
    x = x + Linear.apply(p["w_o"], o.astype(x.dtype) * g)

    # channel mix with token shift
    h2 = _norm_apply(cfg, p["ln2"], x)
    prev_cm = cache.get("shift_cm") if cache else None
    h2s = _token_shift(h2, prev_cm)
    if cache:
        new_cache["shift_cm"] = h2[:, -1]
    mu_cm = p["mu_cm"].astype(h2.dtype)
    hk2 = h2 + (h2s - h2) * mu_cm
    kk = jnp.square(jax.nn.relu(Linear.apply(p["cm_k"], hk2)))
    x = x + Linear.apply(p["cm_v"], kk)
    return x, new_cache, 0.0


# ===========================================================================
# Cross-attention decoder block (whisper): self-attn + cross-attn + FFN
# ===========================================================================

def init_xattn(key, cfg):
    d, h, hk, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 9)
    return {
        "ln1": _norm_init(cfg),
        "wq": Linear.init(ks[0], d, (h, hd), use_bias=cfg.qkv_bias),
        "wk": Linear.init(ks[1], d, (hk, hd), use_bias=cfg.qkv_bias),
        "wv": Linear.init(ks[2], d, (hk, hd), use_bias=cfg.qkv_bias),
        "wo": Linear.init(ks[3], h * hd, d, use_bias=False),
        "lnx": _norm_init(cfg),
        "xwq": Linear.init(ks[4], d, (h, hd), use_bias=cfg.qkv_bias),
        "xwk": Linear.init(ks[5], d, (hk, hd), use_bias=cfg.qkv_bias),
        "xwv": Linear.init(ks[6], d, (hk, hd), use_bias=cfg.qkv_bias),
        "xwo": Linear.init(ks[7], h * hd, d, use_bias=False),
        "ln2": _norm_init(cfg),
        "ffn": init_ffn(ks[8], cfg),
    }


def init_xattn_cache(cfg, batch: int, capacity: int, enc_len: int, dtype):
    hk, hd = cfg.n_kv_heads, cfg.head_dim
    c = init_kv_cache(cfg, batch, capacity, dtype)
    c["xk"] = jnp.zeros((batch, enc_len, hk, hd), dtype)
    c["xv"] = jnp.zeros((batch, enc_len, hk, hd), dtype)
    return c


def apply_xattn(p, cfg, blk, x, ctx, cache):
    """Whisper-style decoder layer.  ctx['enc_out'] (B, Lenc, D) must be
    present during training and prefill; during decode the projected
    cross-K/V come from the cache (filled at prefill)."""
    b, l, d = x.shape
    q_offset = ctx.get("q_offset", 0)
    enc_out = ctx.get("enc_out")

    # --- causal self-attention (same logic as apply_attention) -----------
    h = _norm_apply(cfg, p["ln1"], x)
    q = Linear.apply(p["wq"], h)
    k = Linear.apply(p["wk"], h)
    v = Linear.apply(p["wv"], h)
    if ctx.get("sin") is not None:
        q = apply_rope(q, ctx["sin"], ctx["cos"])
        k = apply_rope(k, ctx["sin"], ctx["cos"])
    if cache and l == 1:
        sub = {"k": cache["k"], "v": cache["v"], "pos": cache["pos"],
               "idx": cache["idx"]}
        sub = _cache_write(sub, k, v, q_offset)
        cache = {**cache, **sub}
        mask = make_attention_mask(
            q_offset + jnp.arange(l), cache["pos"], causal=True,
            kv_valid=cache["pos"] >= 0)[None]
        o = attention_core(q, cache["k"], cache["v"], mask=mask)
    else:
        if cache:
            sub = {"k": cache["k"], "v": cache["v"], "pos": cache["pos"],
                   "idx": cache["idx"]}
            sub = _cache_write(sub, k, v, q_offset)
            cache = {**cache, **sub}
        seq_shard = _want_seq_shard(cfg, ctx)
        if seq_shard:
            q = _seq_shard(q, ctx, on_model=True)
            k = _seq_shard(k, ctx, on_model=False)
            v = _seq_shard(v, ctx, on_model=False)
        if ctx.get("impl") == "chunked":
            o = chunked_attention_core(q, k, v, causal=True,
                                       q_offset=q_offset,
                                       chunk_size=cfg.attn_chunk)
        else:
            pos = q_offset + jnp.arange(l)
            mask = make_attention_mask(pos, pos, causal=True)[None]
            o = attention_core(q, k, v, mask=mask)
        if seq_shard:
            o = _seq_shard(o, ctx, on_model=True)
    x = x + Linear.apply(p["wo"], o.reshape(b, l, -1))

    # --- cross-attention ---------------------------------------------------
    h = _norm_apply(cfg, p["lnx"], x)
    xq = Linear.apply(p["xwq"], h)
    if l > 1 and _want_seq_shard(cfg, ctx):
        xq = _seq_shard(xq, ctx, on_model=True)
    if enc_out is not None:
        xk = Linear.apply(p["xwk"], enc_out.astype(x.dtype))
        xv = Linear.apply(p["xwv"], enc_out.astype(x.dtype))
        if cache:
            cache = {**cache, "xk": xk, "xv": xv}
    else:
        xk, xv = cache["xk"], cache["xv"]
    if l > 2048:
        o = chunked_attention_core(xq, xk, xv, causal=False,
                                   chunk_size=cfg.attn_chunk)
    else:
        o = attention_core(xq, xk, xv, mask=None)
    x = x + Linear.apply(p["xwo"], o.reshape(b, l, -1))

    h = _norm_apply(cfg, p["ln2"], x)
    y = apply_ffn(p["ffn"], cfg, h)
    aux = 0.0
    if isinstance(y, tuple):
        y, aux = y
    return x + y, cache, aux


# ===========================================================================
# dispatch
# ===========================================================================

_INIT = {"attn": init_attention, "local": init_attention,
         "rglru": init_rglru, "rwkv": init_rwkv, "xattn": init_xattn}
_APPLY = {"attn": apply_attention, "local": apply_attention,
          "rglru": apply_rglru, "rwkv": apply_rwkv, "xattn": apply_xattn}


def init_block(key, cfg, blk: str):
    return _INIT[blk](key, cfg) if blk in ("rglru", "rwkv") else _INIT[blk](key, cfg)


def apply_block(p, cfg, blk: str, x, ctx, cache):
    return _APPLY[blk](p, cfg, blk, x, ctx, cache)


def init_block_cache(cfg, blk: str, batch: int, capacity: int, dtype, *,
                     layout: str = "ring", block_size: int = 16,
                     num_blocks: int | None = None, kv_quant=None):
    if layout not in ("ring", "paged"):
        raise ValueError(f"unknown cache layout {layout!r}")
    if layout == "paged":
        if blk in ("attn", "local"):
            # windowed layers keep full-capacity tables and mask with the
            # window (simpler than per-layer pools; see DESIGN.md)
            if num_blocks is None:
                # pool sizing has a single source of truth:
                # serve.engine.ServeConfig.pool_blocks — a second default
                # here could drift and corrupt cross-row KV silently
                raise ValueError("paged layout requires num_blocks "
                                 "(see ServeConfig.pool_blocks)")
            from repro.serve.kvpool import blocks_for
            max_blocks = blocks_for(capacity, block_size)
            return init_paged_kv_cache(cfg, batch, num_blocks, block_size,
                                       max_blocks, dtype, quant=kv_quant)
        if blk == "xattn":
            raise NotImplementedError("paged layout: decoder-only families")
        # recurrent state (rglru / rwkv) is O(1) per row — unchanged
    if blk == "attn":
        cap = capacity if cfg.window is None else min(capacity, cfg.window)
        return init_kv_cache(cfg, batch, cap, dtype)
    if blk == "local":
        return init_kv_cache(cfg, batch, min(capacity, cfg.local_window), dtype)
    if blk == "rglru":
        return init_rglru_cache(cfg, batch, dtype)
    if blk == "rwkv":
        return init_rwkv_cache(cfg, batch, dtype)
    if blk == "xattn":
        enc_len = cfg.encoder.frontend_len if cfg.encoder else 1500
        return init_xattn_cache(cfg, batch, capacity, enc_len, dtype)
    raise ValueError(blk)
