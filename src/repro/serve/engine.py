"""Serving engine: prefill / decode steps for every model family, with
data multiplexing as the throughput feature.

The mux'd decode path is the beyond-paper extension: with mux level N the
backbone processes B/N streams, so the KV cache (the decode bottleneck)
holds B/N × L entries — cache bytes AND attention read-bandwidth per
stream are divided by N.  ``decode_step`` signatures are uniform across
families; the cache pytree encodes the family (KV ring buffer / RG-LRU
state / RWKV6 matrix state / whisper cross-KV).

Two cache layouts (see DESIGN.md):

  * ``ring``  — one contiguous (B, capacity, Hkv, Dh) buffer per layer
                with a shared slot-position vector; positions are uniform
                across rows (fill-drain batches).
  * ``paged`` — a shared block pool per layer addressed through per-row
                block tables (``serve.kvpool``); rows decode at
                independent positions (``decode_step`` takes a (B,) pos
                vector) and ``prefill(..., rows=[j])`` writes a single
                joining row's KV into freshly allocated blocks without
                touching sibling rows — the basis of continuous mux
                serving (``launch.serve --continuous --cache paged``).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import MuxSpec
from repro.core import quant as quantlib
from repro.models import TransformerLM, EncDecLM, VLM
from repro.models.config import ModelConfig
from repro.serve.kvpool import KVPool, ShardedKVPool, blocks_for
from repro.serve.kvpool import copy_pages as kvpool_copy_pages


def backbone_batch(global_batch: int, mux: MuxSpec) -> int:
    if global_batch % max(mux.n, 1):
        raise ValueError(f"batch {global_batch} not divisible by N={mux.n}")
    return global_batch // max(mux.n, 1)


@dataclass(frozen=True)
class ServeConfig:
    cfg: ModelConfig
    kind: str                  # lm | vlm | encdec
    mux: MuxSpec
    capacity: int              # KV capacity (max context)
    dtype: object = jnp.bfloat16
    cache_layout: str = "ring"      # ring | paged
    block_size: int = 16            # paged: tokens per block
    num_blocks: int | None = None   # paged: pool size (default: worst case)
    n_shards: int = 1               # paged: data-shard count (mesh serving);
                                    # rows and pool blocks segment per shard
    kv_dtype: str | None = None     # paged: page storage — fp32 | bf16 |
                                    # int8 | fp8 (None = serve dtype)

    @property
    def max_blocks_per_seq(self) -> int:
        return blocks_for(self.capacity, self.block_size)

    @property
    def kv_quant(self) -> str | None:
        """Quantization kind for the page store ('int8'/'fp8'), or None
        for plain floating-point pages."""
        kind = quantlib.resolve_kv_dtype(self.kv_dtype)
        return kind if kind in quantlib.KV_QUANT_KINDS else None

    @property
    def page_dtype(self):
        """Storage dtype of the KV pages under this config."""
        kind = quantlib.resolve_kv_dtype(self.kv_dtype)
        if kind is None:
            return self.dtype
        return quantlib.kv_store_dtype(kind)

    def kv_bytes_per_token(self) -> int:
        """Pool bytes one token occupies across all attention layers
        (payload + scales + the shared slot-position entry)."""
        cfg = self.cfg
        n_attn = sum(1 for b in (list(cfg.block_pattern) * cfg.n_periods
                                 + list(cfg.tail_blocks))
                     if b in ("attn", "local"))
        hd = cfg.n_kv_heads * cfg.head_dim
        per_layer = 2 * hd * jnp.dtype(self.page_dtype).itemsize
        if self.kv_quant is not None:
            per_layer += 2 * cfg.n_kv_heads * 4          # fp32 ksc/vsc
        per_layer += 4                                   # int32 ppos entry
        return n_attn * per_layer

    def pool_bytes(self, global_batch: int) -> int:
        """Total device bytes of the page pool for ``global_batch``."""
        return (self.pool_blocks(global_batch) * self.block_size
                * self.kv_bytes_per_token())

    def pool_blocks(self, global_batch: int) -> int:
        """Pool size: explicit, or worst case (every row at capacity) +
        one reserved trash block per shard."""
        if self.num_blocks is not None:
            if self.num_blocks % self.n_shards:
                raise ValueError(
                    f"num_blocks={self.num_blocks} not divisible by "
                    f"n_shards={self.n_shards}")
            return self.num_blocks
        b = backbone_batch(global_batch, self.mux)
        if b % self.n_shards:
            raise ValueError(f"backbone batch {b} not divisible by "
                             f"n_shards={self.n_shards}")
        return b * self.max_blocks_per_seq + self.n_shards


def lane_config(sc: ServeConfig, n_mux: int) -> ServeConfig:
    """Derive one serving lane's ``ServeConfig`` from a base config
    (width-lane serving, DESIGN.md §width lanes): same model, capacity,
    dtype, block size and shard count — only the mux width changes.
    ``num_blocks`` is reset to None so each lane sizes its own pool
    partition from its own row count (the router's global ``budget``
    then caps live usage via per-lane quotas)."""
    import dataclasses
    if n_mux < 1:
        raise ValueError(f"lane mux width must be >= 1, got {n_mux}")
    return dataclasses.replace(
        sc, mux=dataclasses.replace(sc.mux, n=n_mux), num_blocks=None)


def make_pool(sc: ServeConfig, global_batch: int):
    """Host-side allocator matching ``init_cache(sc, global_batch)``.
    With ``sc.n_shards > 1`` the pool is a ``ShardedKVPool`` whose block
    segments line up with the device pages' 'data'-axis sharding."""
    if sc.n_shards > 1:
        return ShardedKVPool(num_blocks=sc.pool_blocks(global_batch),
                             block_size=sc.block_size,
                             max_blocks_per_seq=sc.max_blocks_per_seq,
                             n_shards=sc.n_shards,
                             n_rows=backbone_batch(global_batch, sc.mux))
    return KVPool(num_blocks=sc.pool_blocks(global_batch),
                  block_size=sc.block_size,
                  max_blocks_per_seq=sc.max_blocks_per_seq)


def init_cache(sc: ServeConfig, global_batch: int):
    b = backbone_batch(global_batch, sc.mux)
    if sc.cache_layout == "paged":
        if sc.kind != "lm":
            raise NotImplementedError(
                "paged cache layout: decoder-only LM families")
        # quantized pools pick their storage dtype from kv_quant inside
        # init_pages; the dtype arg then only types non-attention state
        # (rglru/rwkv), which must stay floating-point
        dt = sc.dtype if sc.kv_quant is not None else sc.page_dtype
        return TransformerLM.init_cache(
            sc.cfg, b, sc.capacity, dt, layout="paged",
            block_size=sc.block_size, num_blocks=sc.pool_blocks(global_batch),
            kv_quant=sc.kv_quant)
    model = {"lm": TransformerLM, "vlm": VLM, "encdec": EncDecLM}[sc.kind]
    return model.init_cache(sc.cfg, b, sc.capacity, sc.dtype)


def set_block_tables(cache, block_tables):
    """Install a host (B, max_blocks_per_seq) block-table array into every
    paged layer of a cache pytree (period-stacked layers broadcast over
    the period axis).  Call after KVPool alloc/append/free changed any
    row's table.  Every layer gets a buffer of its own: the jitted steps
    donate the cache, and a buffer shared by two leaves cannot be donated
    twice."""
    def upd(c):
        if isinstance(c, dict) and "bt" in c:
            bt = jnp.array(block_tables, jnp.int32)        # always a copy
            return {**c, "bt": jnp.broadcast_to(bt, c["bt"].shape)}
        return c

    return {"periods": tuple(upd(c) for c in cache["periods"]),
            "tail": tuple(upd(c) for c in cache["tail"])}


def reset_blocks(cache, block_ids):
    """Mark pool blocks as empty (position entries -1) in every paged
    layer of a cache pytree.  MUST be called for blocks handed out by
    ``KVPool.allocate``/``append`` before the first write: the pool
    reuses freed blocks without clearing, and a reused block's stale
    position entries would otherwise pass the attention validity mask
    and leak a retired request's KV into the new owner."""
    ids = jnp.asarray(list(block_ids), jnp.int32)
    if ids.size == 0:
        return cache

    def upd(c):
        if isinstance(c, dict) and "ppos" in c:
            if c["ppos"].ndim == 3:        # period-stacked (P, NB, BS)
                return {**c, "ppos": c["ppos"].at[:, ids].set(-1)}
            return {**c, "ppos": c["ppos"].at[ids].set(-1)}
        return c

    return {"periods": tuple(upd(c) for c in cache["periods"]),
            "tail": tuple(upd(c) for c in cache["tail"])}


def copy_cache_pages(src_cache, dst_cache, src_ids, dst_ids):
    """Migrate whole pool pages between two cache pytrees (disaggregated
    serving, DESIGN.md §disaggregated): pages ``src_ids`` of every paged
    layer in ``src_cache`` are copied into slots ``dst_ids`` of the
    matching layer in ``dst_cache`` — payload, quant scales, and
    position entries (``kvpool.copy_pages`` per layer).  The two caches
    must share layer structure, page shape, and ``kv_dtype``; they may
    be the same pytree for a cross-shard move inside one pool.  Like
    ``reset_blocks`` this is a host-orchestrated functional edit, never
    a jit input — the compile-once contract is untouched."""
    ids_s = jnp.asarray(list(src_ids), jnp.int32)
    ids_d = jnp.asarray(list(dst_ids), jnp.int32)
    if ids_s.shape != ids_d.shape:
        raise ValueError("page migration needs equal-length id lists")
    if ids_s.size == 0:
        return dst_cache

    def upd(s, d):
        if not (isinstance(d, dict) and "ppos" in d):
            return d
        if d["ppos"].ndim == 3:            # period-stacked (P, NB, BS)
            out = dict(d)
            for key in ("kp", "vp", "ksc", "vsc", "ppos"):
                if key in d:
                    out[key] = d[key].at[:, ids_d].set(s[key][:, ids_s])
            return out
        return kvpool_copy_pages(s, d, ids_s, ids_d)

    return {"periods": tuple(upd(s, d) for s, d in
                             zip(src_cache["periods"], dst_cache["periods"])),
            "tail": tuple(upd(s, d) for s, d in
                          zip(src_cache["tail"], dst_cache["tail"]))}


def prefill(params, sc: ServeConfig, cache, tokens, *, extra=None,
            rows=None, extra_ctx=None):
    """tokens: (NB, L_prompt).  extra: patch/frame embeddings for
    vlm/encdec.  Returns (last-position logits (NB, V), cache).

    rows: paged layout only — backbone-row indices the (partial) batch
    maps to; the joining rows' KV is scattered into their freshly
    allocated blocks and no other row's cache is touched.
    extra_ctx: extra layer-context entries (e.g. 'mesh' for sharding
    constraints, 'trash' for per-row trash-block routing)."""
    kw = dict(mux=sc.mux, cache=cache, dtype=sc.dtype)
    ctx = dict(extra_ctx or {})
    if rows is not None:
        if sc.cache_layout != "paged":
            raise ValueError("rows= requires the paged cache layout")
        ctx["rows"] = jnp.asarray(rows, jnp.int32)
    if ctx:
        kw["extra_ctx"] = ctx
    if sc.kind == "vlm":
        out = VLM.apply(params, sc.cfg, tokens, extra, **kw)
    elif sc.kind == "encdec":
        out = EncDecLM.apply(params, sc.cfg, tokens, extra, **kw)
    else:
        out = TransformerLM.apply(params, sc.cfg, tokens, **kw)
    return out["logits"][:, -1], out["cache"]


def prefill_chunk(params, sc: ServeConfig, cache, tokens, *, rows, start,
                  length, use_kernels: bool = False, extra_ctx=None):
    """Chunked prefill (paged layout only): one fixed-size prompt chunk
    for the backbone rows in ``rows``.

    tokens: (len(rows) * N_mux, C) bucket-padded chunk; KV is written at
    absolute positions ``start .. start + length - 1`` into the rows'
    pages (the padded tail routes to the trash block) and each query
    attends causally over the rows' previously written blocks plus the
    chunk's own entries.  ``start``/``length`` may be traced scalars (or
    (len(rows),) vectors for heterogeneous rows), so a jitted wrapper
    compiles once per chunk bucket C.  Returns (logits at the last valid
    chunk position (len(rows) * N_mux, V), cache).
    """
    if sc.cache_layout != "paged":
        raise ValueError("prefill_chunk requires the paged cache layout")
    if sc.kind != "lm":
        raise NotImplementedError(
            "chunked prefill supports decoder-only LM families")
    start = jnp.asarray(start, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    ctx = dict(extra_ctx or {})
    ctx.update({"rows": jnp.asarray(rows, jnp.int32), "chunked": True,
                "q_end": start + length})
    out = TransformerLM.apply(
        params, sc.cfg, tokens, mux=sc.mux, cache=cache, q_offset=start,
        dtype=sc.dtype, logits_out=False, use_kernels=use_kernels,
        extra_ctx=ctx)
    # logits only at the chunk's last valid position (dynamic under jit):
    # the bucket-padded tail positions carry garbage hidden states
    h = out["hidden"]                                        # (NB, C, D)
    if length.ndim:          # heterogeneous rows, mux-major instance order
        last = jnp.tile(length, h.shape[0] // length.shape[0]) - 1
    else:
        last = jnp.full((h.shape[0],), length - 1)
    h_last = jnp.take_along_axis(h, last[:, None, None], axis=1)
    return TransformerLM.logits(params, sc.cfg, h_last)[:, 0], out["cache"]


def decode_step(params, sc: ServeConfig, cache, tokens, pos, *,
                extra_ctx=None, use_kernels: bool = False):
    """One decode step.  tokens: (NB, 1); pos: static int, traced scalar,
    or — paged layout — a (B,) int32 vector of per-row positions (-1 =
    inactive row).  extra_ctx: extra layer-context entries ('mesh',
    'trash').  Returns (logits (NB, 1, V), new cache)."""
    kw = dict(mux=sc.mux, cache=cache, q_offset=pos, dtype=sc.dtype,
              use_kernels=use_kernels)
    if extra_ctx:
        kw["extra_ctx"] = extra_ctx
    if sc.kind == "encdec":
        out = EncDecLM.apply(params, sc.cfg, tokens, **kw)
    elif sc.kind == "vlm":
        out = VLM.apply(params, sc.cfg, tokens, **kw)
    else:
        out = TransformerLM.apply(params, sc.cfg, tokens, **kw)
    return out["logits"], out["cache"]


def greedy_generate(params, sc: ServeConfig, prompt, *, steps: int,
                    extra=None):
    """Host-loop greedy decoding (tests/examples; production uses the
    jitted decode_step inside the request loop).  Works for both cache
    layouts; under ``paged`` every row's blocks are allocated up front
    from a fresh pool."""
    cache = init_cache(sc, prompt.shape[0])
    if sc.cache_layout == "paged":
        b = backbone_batch(prompt.shape[0], sc.mux)
        pool = make_pool(sc, prompt.shape[0])
        for j in range(b):
            pool.allocate(j, prompt.shape[1] + steps)
        cache = set_block_tables(cache, pool.table_array(range(b)))
    from repro.serve import sampling
    logits, cache = prefill(params, sc, cache, prompt, extra=extra)
    tok = sampling.greedy(logits)[:, None]
    out = [tok]
    pos = prompt.shape[1]
    for t in range(steps - 1):
        logits, cache = decode_step(params, sc, cache, tok, pos + t)
        tok = sampling.greedy(logits[:, -1])[:, None]
        out.append(tok)
    return jnp.concatenate(out, axis=1)
