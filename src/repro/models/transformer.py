"""TransformerLM — the unified backbone for every assigned architecture.

Layers are grouped into *periods* (one repetition of ``cfg.block_pattern``)
and scanned with ``lax.scan`` over stacked period params — one period of
HLO regardless of depth (38-layer recurrentgemma lowers the same code as
12-layer whisper), which keeps dry-run compiles tractable and is the
standard production trick.  Leftover layers (pattern not dividing
n_layers) are unrolled as ``tail``.

Data multiplexing (the paper's technique) is integrated between embedding
and backbone via ``MuxEngine``; with ``mux.n == 1`` the engine is a no-op
and this is a vanilla LM.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import MuxSpec, MuxEngine
from repro.nn import Embedding, LayerNorm, RMSNorm, Linear, normal_init
from repro.nn.rope import rope_frequencies
from repro.models.config import ModelConfig
from repro.models.blocks import (
    init_block, apply_block, init_block_cache)


def _stack_init(key, n: int, init_fn):
    ps = [init_fn(k) for k in jax.random.split(key, max(n, 1))[:n]]
    if not ps:
        return None
    return jax.tree.map(lambda *xs: jnp.stack(xs), *ps)


class TransformerLM:
    # ------------------------------------------------------------------ init
    @staticmethod
    def init(key, cfg: ModelConfig, mux: MuxSpec = MuxSpec()):
        ks = jax.random.split(key, 8)
        d = cfg.d_model
        params = {"embed": Embedding.init(ks[0], cfg.vocab_size, d)}
        if cfg.positions == "learned":
            params["pos_emb"] = normal_init(
                ks[1], (cfg.max_seq_len, d), stddev=0.02)
        pat = cfg.block_pattern
        params["periods"] = tuple(
            _stack_init(jax.random.fold_in(ks[2], i), cfg.n_periods,
                        lambda k, b=blk: init_block(k, cfg, b))
            for i, blk in enumerate(pat))
        params["tail"] = tuple(
            init_block(jax.random.fold_in(ks[3], i), cfg, blk)
            for i, blk in enumerate(cfg.tail_blocks))
        params["final_norm"] = (RMSNorm if cfg.norm == "rms"
                                else LayerNorm).init(None, d)
        if not cfg.tie_embeddings:
            params["lm_head"] = Linear.init(ks[4], d, cfg.vocab_size,
                                            use_bias=False)
        if mux.enabled:
            params["mux_engine"] = MuxEngine.init(ks[5], mux, d)
        return params

    # ----------------------------------------------------------------- cache
    @staticmethod
    def init_cache(cfg: ModelConfig, batch: int, capacity: int,
                   dtype=jnp.bfloat16, *, layout: str = "ring",
                   block_size: int = 16, num_blocks: int | None = None,
                   kv_quant: str | None = None):
        """batch = backbone batch (already divided by mux N).

        layout='paged' replaces each attention layer's contiguous ring
        buffer with a shared block pool + per-row block table (DESIGN.md);
        tables are installed via ``serve.set_block_tables``.
        kv_quant='int8'/'fp8' (paged only) stores quantized pages with
        per-slot scales (``dtype`` is then the storage dtype handed in
        by ``ServeConfig.page_dtype``).
        """
        pat = cfg.block_pattern

        def one(blk):
            return init_block_cache(cfg, blk, batch, capacity, dtype,
                                    layout=layout, block_size=block_size,
                                    num_blocks=num_blocks, kv_quant=kv_quant)

        periods = tuple(
            jax.tree.map(lambda *xs: jnp.stack(xs),
                         *[one(blk) for _ in range(cfg.n_periods)])
            if cfg.n_periods else None
            for blk in pat)
        tail = tuple(one(blk) for blk in cfg.tail_blocks)
        return {"periods": periods, "tail": tail}

    # ----------------------------------------------------------------- apply
    @staticmethod
    def apply(params, cfg: ModelConfig, tokens=None, *, embeds=None,
              mux: MuxSpec = MuxSpec(), cache=None, q_offset=0,
              dtype=jnp.bfloat16, logits_out: bool = True,
              use_kernels: bool = False, demux: bool = True,
              extra_ctx: dict | None = None):
        """Forward pass.

        tokens: (NB, L) int32 — NB is the *instance* batch (mux N × device
        batch).  embeds: optional precomputed (NB, L, D) (VLM/audio stubs).
        cache: from ``init_cache`` (serving); None for training.
        Returns dict(logits | hidden, aux, cache).
        """
        d = cfg.d_model
        # The mux entry/exit kernels take whole (unsharded) weights, and
        # GSPMD cannot partition a Mosaic kernel: on a multi-device mesh
        # they run as XLA ops (the paged attention kernels shard_map).
        mesh = (extra_ctx or {}).get("mesh")
        mux_kernels = use_kernels and (mesh is None or mesh.size == 1)
        # Fused decode entry: embed-gather + embedding-scale + Gaussian
        # mux-combine as ONE Pallas launch (kernels/mux_embed.py) — the
        # (N*B, L, D) embeddings never materialize.  Gated to the
        # gaussian/rsa mux config (contextual mux runs transformer
        # layers; the prefix demux splices extra positions in combine).
        fuse_entry = (mux_kernels and embeds is None and mux.enabled
                      and mux.mux_kind == "gaussian"
                      and mux.demux_kind != "prefix"
                      and "mux_engine" in params)
        if fuse_entry:
            from repro.kernels import ops as kops
            nb, l_in = tokens.shape
            bb = nb // mux.n
            x = kops.mux_embed_combine(
                jnp.maximum(tokens, 0).reshape(mux.n, bb * l_in),
                params["embed"]["table"],
                params["mux_engine"]["mux"]["v"],
                scale=math.sqrt(d) if cfg.embedding_scale else 1.0,
                out_dtype=dtype)
            x = x.reshape(bb, l_in, d)
        else:
            if embeds is None:
                x = Embedding.apply(params["embed"], tokens, dtype=dtype)
            else:
                x = embeds.astype(dtype)
            if cfg.embedding_scale:
                x = x * jnp.asarray(math.sqrt(d), dtype)

            # --- multiplex --------------------------------------------
            x = MuxEngine.combine(params.get("mux_engine", {}), mux, x)
        b, l, _ = x.shape

        # --- positions --------------------------------------------------
        # q_offset: scalar, or a (B,) vector of per-row offsets (paged
        # continuous serving — rows sit at different decode positions;
        # -1 marks an inactive row, clamped to 0 for the embeddings and
        # masked at the cache/attention level).  Chunked prefill passes
        # a mid-sequence start offset with L > 1 (plus 'q_end' in
        # extra_ctx bounding the valid positions of a bucket-padded
        # chunk): RoPE/learned positions below are offset-correct for
        # both shapes, and the paged write/attend path masks the tail.
        qo = jnp.asarray(q_offset)
        if qo.ndim:
            pos = jnp.maximum(qo, 0)[:, None] + jnp.arange(l)[None]  # (B, L)
        else:
            pos = qo + jnp.arange(l)
        ctx = {"sin": None, "cos": None, "q_offset": q_offset}
        if cfg.positions == "rope":
            sin, cos = rope_frequencies(cfg.head_dim, pos,
                                        theta=cfg.rope_theta)
            ctx["sin"], ctx["cos"] = ((sin, cos) if qo.ndim
                                      else (sin[None], cos[None]))
        elif cfg.positions == "learned":
            pe = params["pos_emb"].astype(dtype)[pos]
            x = x + (pe if qo.ndim else pe[None])
        impl = cfg.attn_impl
        if impl == "auto":
            # long inputs (training or single-shot prefill) take the
            # online-softmax chunked path; decode (l==1) stays naive
            impl = "chunked" if l > 2048 else "naive"
        ctx["impl"] = impl
        ctx["use_kernels"] = use_kernels
        if extra_ctx:
            ctx.update(extra_ctx)

        pat = cfg.block_pattern
        decode = cache is not None
        aux_total = jnp.zeros((), jnp.float32)

        # --- scanned periods -------------------------------------------
        def period_fn(carry, xs):
            x, aux = carry
            pparams, pcache = xs
            new_caches = []
            for i, blk in enumerate(pat):
                c = pcache[i] if decode else {}
                x, c, a = apply_block(pparams[i], cfg, blk, x, ctx, c)
                new_caches.append(c)
                aux = aux + a
            return (x, aux), tuple(new_caches) if decode else None

        n_per = cfg.n_periods
        new_pc = None
        if n_per:
            if decode:
                (x, aux_total), new_pc = jax.lax.scan(
                    period_fn, (x, aux_total),
                    (tuple(params["periods"]), tuple(cache["periods"])))
            else:
                def fn(carry, pparams):
                    return period_fn(carry, (pparams, None))
                scan_fn = (jax.checkpoint(fn, prevent_cse=False)
                           if cfg.remat else fn)
                (x, aux_total), _ = jax.lax.scan(
                    scan_fn, (x, aux_total), tuple(params["periods"]))

        # --- tail layers (unrolled) -------------------------------------
        new_tail = []
        for i, blk in enumerate(cfg.tail_blocks):
            c = cache["tail"][i] if decode else {}
            x, c, a = apply_block(params["tail"][i], cfg, blk, x, ctx, c)
            new_tail.append(c)
            aux_total = aux_total + a

        # Fused decode exit: backbone final norm + RSA demux + demux-LN
        # as ONE Pallas launch (kernels/demux_rsa.py epilogue fusion).
        fuse_exit = (mux_kernels and demux and mux.enabled
                     and mux.demux_kind == "rsa" and "mux_engine" in params)
        if fuse_exit:
            x = MuxEngine.separate_fused(
                params["mux_engine"], mux, x,
                final_norm=params["final_norm"],
                norm_kind="rms" if cfg.norm == "rms" else "ln")
        else:
            x = (RMSNorm if cfg.norm == "rms" else LayerNorm).apply(
                params["final_norm"], x)

            # --- demultiplex ---------------------------------------------
            if demux:
                x = MuxEngine.separate(params.get("mux_engine", {}), mux, x,
                                       use_kernel=mux_kernels)

        out = {"aux": aux_total}
        if decode:
            out["cache"] = {"periods": new_pc, "tail": tuple(new_tail)}
        if logits_out:
            out["logits"] = TransformerLM.logits(params, cfg, x)
        else:
            out["hidden"] = x
        return out

    @staticmethod
    def logits(params, cfg: ModelConfig, hidden):
        if cfg.tie_embeddings:
            return Embedding.attend(params["embed"], hidden)
        return Linear.apply(params["lm_head"], hidden)
