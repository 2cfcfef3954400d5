"""Pallas TPU kernels: paged attention over a block-table-addressed KV
pool (vLLM-style) — flash-decode (``paged_attention``) and the
chunked-prefill variant (``paged_prefill_attention``).

Same math as ``kernels/decode_attention.py`` (an online softmax over
the keys, page by page), but the cache is not contiguous per row: each
batch row owns a *block table* of page ids into a shared
``(num_blocks, block_size, Hkv, Dh)`` pool.  The block table and the
per-row query positions are scalar-prefetched
(``PrefetchScalarGridSpec``), so page DMAs are issued directly against
the pages the table names — the gather never materializes a contiguous
copy of the row's cache in HBM.

The decode kernel takes one grid step per row and walks, inside the
kernel, only the row's *live* table blocks: block j holds positions
[j*BS, (j+1)*BS) (``serve/kvpool.py:paged_write``), so a causal query
at ``q_pos`` reads blocks ``max(0, q_pos - window + 1) // BS`` through
``q_pos // BS`` and nothing else.  The pool operands stay in HBM
(``memory_space=pl.ANY``); each live page (K, V, its slot positions
and, for int8/fp8 pools, its per-slot scales) is copied with
``make_async_copy`` into one of two VMEM slots, the next page's copy
starting before the current page is computed — and, at a row's last
page, the next row's first.  So the kernel's cost follows the live
pages, not the table width.  Further:
  * an inactive row (``q_pos = -1``) walks no page and writes zeros
    (its output is discarded by the caller);
  * unallocated table entries (id -1) inside the range are never
    fetched; a non-causal call walks the whole table;
  * within a walked page, slot validity comes from the pool's per-slot
    position map ((P, BS), -1 = empty), the paged analogue of the
    ring's position vector, and the causal / window masks apply per
    slot as before.

``paged_prefill_attention`` generalizes the query axis to a chunk of
Lq > 1 tokens at per-row start offsets (chunked prefill: the chunk's KV
has already been scattered into the row's pages, and each query attends
causally over every previously written block plus the chunk's own
entries).  Queries past a row's valid length (bucket padding) are fully
masked and produce discarded output.  It keeps a grid step per (row,
KV head, table block), its page DMA issued by BlockSpecs: unallocated
table entries are clamped to page 0 for the DMA and masked via the
prefetched table.

``sharded_paged_attention`` / ``sharded_paged_prefill_attention`` run
the same kernels under ``shard_map`` over a mesh's 'data' axis: the
pool's blocks axis partitions per shard, global block ids are rebased
to the shard's local page segment, and each shard's kernel issues page
DMAs only against resident pages.  Rows partition too where they split
evenly over 'data' (the ``ShardedKVPool`` row->shard invariant makes
that exact) — the decode grid, which needs NO collectives (DESIGN.md
§sharded serving).  A one-row prefill chunk lives on one shard, so its
rows replicate and a psum keeps the owning shard's output.  Mosaic
kernels cannot be partitioned by GSPMD, so on a mesh these wrappers are
the only way the paged kernels run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _live_range(q_pos, *, bs: int, mb: int, window, causal: bool):
    """(first table block, blocks to walk) for a row whose query sits at
    ``q_pos``.  Block j of a row holds positions [j*bs, (j+1)*bs)
    (``serve/kvpool.py:paged_write``), so a causal query reads blocks up
    to ``q_pos // bs`` and, under a window, from the block holding
    ``q_pos - window + 1``: every block outside that range is fully
    masked.  An inactive row (``q_pos < 0``) walks none; a non-causal
    row walks the whole table."""
    if not causal:
        return 0, jnp.where(q_pos >= 0, mb, 0)
    hi = jnp.minimum(jax.lax.div(jnp.maximum(q_pos, 0), bs), mb - 1)
    lo = (0 if window is None else
          jax.lax.div(jnp.maximum(q_pos - window + 1, 0), bs))
    return lo, jnp.where(q_pos >= 0, jnp.maximum(hi - lo + 1, 0), 0)


def _kernel(bt_ref, qp_ref, q_ref, *refs, nsrc: int, mb: int,
            scale: float, window, causal: bool, quantized: bool):
    """One grid step per row: walk the row's live table blocks with
    double-buffered page DMA.  ``refs``: the pool operands in HBM (K, V,
    [K scales, V scales,] slot positions), the output block, a 2-slot
    VMEM copy of one page of each pool operand, their DMA semaphores, the
    SMEM walk state and the online-softmax scratch."""
    srcs, o_ref = refs[:nsrc], refs[nsrc]
    bufs = refs[nsrc + 1:2 * nsrc + 1]
    sem, state, m_ref, l_ref, acc_ref = refs[2 * nsrc + 1:]
    kbuf, vbuf, posbuf = bufs[0], bufs[1], bufs[-1]
    ksbuf, vsbuf = bufs[2:4] if quantized else (None, None)
    hkv, bs = kbuf.shape[1:3]
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    q_pos = qp_ref[b]
    live = functools.partial(_live_range, bs=bs, mb=mb, window=window,
                             causal=causal)
    lo, n = live(q_pos)

    def copies(row, j, slot):
        page = bt_ref[row, j]
        return [pltpu.make_async_copy(src.at[page], buf.at[slot],
                                      sem.at[slot])
                for src, buf in zip(srcs, bufs)]

    def fetch(row, j, slot):
        @pl.when(bt_ref[row, j] >= 0)          # holes are never fetched
        def _():
            for c in copies(row, j, slot):
                c.start()

    # state: [slot of this row's first page, whether the previous grid
    # step already started it] — page DMA runs ahead across rows
    first = jnp.where(b == 0, 0, state[0])
    ahead = jnp.where(b == 0, 0, state[1])

    @pl.when((ahead == 0) & (n > 0))
    def _():
        fetch(b, lo, first)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(i, carry):
        j = lo + i
        slot = jax.lax.rem(first + i, 2)

        @pl.when(i + 1 < n)
        def _():
            fetch(b, j + 1, 1 - slot)

        @pl.when((i + 1 == n) & (b + 1 < rows))
        def _():
            lo_next, n_next = live(qp_ref[b + 1])

            @pl.when(n_next > 0)
            def _():
                fetch(b + 1, lo_next, 1 - slot)

        @pl.when(bt_ref[b, j] >= 0)
        def _():
            for c in copies(b, j, slot):
                c.wait()
            at = lambda buf: None if buf is None else buf.at[slot]
            _attend_page(q_ref, at(kbuf), at(vbuf), at(posbuf), at(ksbuf),
                         at(vsbuf), m_ref, l_ref, acc_ref, q_pos=q_pos,
                         scale=scale, window=window, causal=causal)
        return carry

    jax.lax.fori_loop(0, n, step, 0)
    state[0] = jax.lax.rem(first + n, 2)
    state[1] = (n > 0).astype(jnp.int32)
    for h in range(hkv):
        o_ref[0, h] = (acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)).astype(
            o_ref.dtype)


def _attend_page(q_ref, k_ref, v_ref, pos_ref, ks_ref, vs_ref, m_ref,
                 l_ref, acc_ref, *, q_pos, scale: float, window,
                 causal: bool):
    """Online-softmax update of every KV head's grouped queries with one
    page in VMEM.  Quantized pages fold their per-slot scales into the
    scores (K) and the probabilities (V): the same ``payload * scale``
    dequant, fused, without scaling the page itself."""
    hkv, bs = k_ref.shape[:2]
    pos = pos_ref[:, :bs]                          # (1, bs) slot positions
    mask = pos >= 0
    if causal:
        mask &= pos <= q_pos
    if window is not None:
        mask &= pos > q_pos - window
    for h in range(hkv):
        q = q_ref[0, h].astype(jnp.float32) * scale          # (G, dh)
        s = jax.lax.dot_general(q, k_ref[h].astype(jnp.float32),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if ks_ref is not None:
            s = s * ks_ref[h, :, :bs]              # (1, bs) K scales
        s = jnp.where(mask, s, NEG_INF)            # (G, bs)
        m_prev = m_ref[h]                          # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * alpha + p.sum(-1, keepdims=True)
        if vs_ref is not None:
            p = p * vs_ref[h, :, :bs]              # (1, bs) V scales
        acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
            p, v_ref[h].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new


def _whole_lane_tiles(a):
    """``a`` with its minor axis zero-padded to a multiple of 128."""
    pad = -a.shape[-1] % 128
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)]) if pad else a


def _pool_operands(k_pages, v_pages, page_pos, k_scales, v_scales):
    """Operands for the pool side of both paged kernels, and the prefill
    kernel's BlockSpecs for them, addressed through the scalar-prefetched
    block table (the first prefetch ref).  Mosaic tiles the last two dims of every block, so
    each operand is laid out such that those dims are either whole or
    (8, 128)-aligned: pages go head-major (P, Hkv, BS, dh), and the
    per-slot vectors — slot positions and quantization scales — carry a
    unit axis so that a (1, bs) slice is a whole (1, BS) minor tile."""
    p, bs, hkv = k_pages.shape[:3]

    def page(b_, h_, j, bt, *_):
        return (jnp.maximum(bt[b_, j], 0), h_, 0, 0)

    def slot_pos(b_, h_, j, bt, *_):
        return (jnp.maximum(bt[b_, j], 0), 0, 0)

    specs = [pl.BlockSpec((1, 1, bs, k_pages.shape[3]), page)] * 2
    args = [k_pages.transpose(0, 2, 1, 3), v_pages.transpose(0, 2, 1, 3)]
    if k_scales is not None:
        specs += [pl.BlockSpec((1, 1, 1, bs), page)] * 2
        args += [s.transpose(0, 2, 1).reshape(p, hkv, 1, bs)
                 for s in (k_scales, v_scales)]
    specs.append(pl.BlockSpec((1, 1, bs), slot_pos))
    args.append(page_pos.reshape(p, 1, bs))
    return specs, args


@functools.partial(jax.jit, static_argnames=("window", "causal", "interpret"))
def paged_attention(q, k_pages, v_pages, block_tables, page_pos, q_pos, *,
                    k_scales=None, v_scales=None, window=None,
                    causal: bool = True, interpret: bool = False):
    """q: (B, 1, H, Dh); k_pages/v_pages: (P, BS, Hkv, Dh) shared pool;
    block_tables: (B, MB) int32 page ids (-1 = unallocated);
    page_pos: (P, BS) int32 absolute position per pool slot (-1 = empty);
    q_pos: (B,) int32 per-row query position (-1 = inactive row).
    k_scales/v_scales: (P, BS, Hkv) fp32 per-slot quantization scales for
    int8/fp8 pages — when given, dequantization fuses into the kernel's
    page loads (the pool's low-precision payload is the only HBM-resident
    form of the cache).  Returns (B, 1, H, Dh)."""
    b, _, h, dh = q.shape
    bs, hkv = k_pages.shape[1], k_pages.shape[2]
    g = h // hkv
    mb = block_tables.shape[1]
    block_tables = block_tables.astype(jnp.int32)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    quantized = k_scales is not None

    qt = q.reshape(b, hkv, g, dh)                  # group queries per kv head
    _, pool_args = _pool_operands(k_pages, v_pages, page_pos, k_scales,
                                  v_scales)
    # a page DMA slices the pool's leading axis, which Mosaic allows only
    # where the minor axis is whole 128-lane tiles: pad each minor axis
    # (the head size of queries and pages, the slots of the per-slot
    # vectors) up to them.  Zero lanes add nothing to a score, and the
    # output's padded lanes are dropped.
    qt, *pool_args = map(_whole_lane_tiles, [qt, *pool_args])
    dhp = qt.shape[-1]
    row = pl.BlockSpec((1, hkv, g, dhp), lambda b_, bt, qp: (b_, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # block_tables, q_pos
        grid=(b,),
        in_specs=[row] + [pl.BlockSpec(memory_space=pl.ANY)] * len(pool_args),
        out_specs=row,
        scratch_shapes=[
            *[pltpu.VMEM((2, *a.shape[1:]), a.dtype) for a in pool_args],
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, dhp), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, nsrc=len(pool_args), mb=mb,
                          scale=dh ** -0.5, window=window, causal=causal,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dhp), q.dtype),
        interpret=interpret,
    )(block_tables, q_pos, qt, *pool_args)
    return out[..., :dh].reshape(b, 1, h, dh)


def _prefill_kernel(bt_ref, qs_ref, ql_ref, q_ref, k_ref, v_ref, *rest,
                    mb: int, lq: int, g: int, window, causal: bool,
                    quantized: bool = False):
    if quantized:
        ks_ref, vs_ref, pos_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        pos_ref, o_ref, m_ref, l_ref, acc_ref = rest
    bi = pl.program_id(0)
    ji = pl.program_id(2)

    @pl.when(ji == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (G*Lq, dh)
    k = k_ref[0, 0].astype(jnp.float32)            # (bs, dh) one page
    v = v_ref[0, 0].astype(jnp.float32)
    if quantized:
        k = k * ks_ref[0, 0, 0][:, None]           # fused dequant (bs,)
        v = v * vs_ref[0, 0, 0][:, None]
    pos = pos_ref[0, 0]                            # (bs,) slot positions
    dh = q.shape[-1]
    bs = k.shape[0]

    s = jnp.dot(q * dh ** -0.5, k.T)               # (G*Lq, bs)
    # per-query absolute positions: start + 0..Lq-1; entries past the
    # row's valid length (bucket padding) are fully masked
    li = jax.lax.broadcasted_iota(jnp.int32, (lq, bs), 0)
    q_pos = qs_ref[bi] + li                        # (Lq, bs)
    mask = (pos[None, :] >= 0) & (bt_ref[bi, ji] >= 0) \
        & (li < ql_ref[bi]) & (qs_ref[bi] >= 0)
    if causal:
        mask &= pos[None, :] <= q_pos
    if window is not None:
        mask &= pos[None, :] > q_pos - window
    # (Lq, bs) -> broadcast over the G grouped queries -> (G*Lq, bs)
    mask = jnp.broadcast_to(mask[None], (g, lq, bs)).reshape(g * lq, bs)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ji == mb - 1)
    def _fin():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(
            l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "causal", "interpret"))
def paged_prefill_attention(q, k_pages, v_pages, block_tables, page_pos,
                            q_start, q_len, *, k_scales=None, v_scales=None,
                            window=None, causal: bool = True,
                            interpret: bool = False):
    """Chunked-prefill attention over the pool: Lq queries per row.

    q: (B, Lq, H, Dh) one prompt chunk per row (KV already written to
    the row's pages); k_pages/v_pages: (P, BS, Hkv, Dh) shared pool;
    block_tables: (B, MB) int32 page ids (-1 = unallocated);
    page_pos: (P, BS) int32 absolute position per pool slot (-1 = empty);
    q_start: (B,) int32 chunk start offset per row (-1 = inactive row);
    q_len: (B,) int32 valid queries per row (entries >= q_len are bucket
    padding whose output is discarded).
    k_scales/v_scales: (P, BS, Hkv) fp32 per-slot scales for quantized
    pages (fused dequant, as in ``paged_attention``).
    Returns (B, Lq, H, Dh).
    """
    b, lq, h, dh = q.shape
    bs, hkv = k_pages.shape[1], k_pages.shape[2]
    g = h // hkv
    mb = block_tables.shape[1]
    block_tables = block_tables.astype(jnp.int32)
    q_start = jnp.asarray(q_start, jnp.int32)
    q_len = jnp.asarray(q_len, jnp.int32)
    quantized = k_scales is not None

    # (B, Lq, Hkv, G, Dh) -> (B, Hkv, G*Lq, Dh): G-major so the (Lq, bs)
    # mask broadcasts over groups with one reshape
    qt = q.reshape(b, lq, hkv, g, dh).transpose(0, 2, 3, 1, 4)
    qt = qt.reshape(b, hkv, g * lq, dh)
    pool_specs, pool_args = _pool_operands(k_pages, v_pages, page_pos,
                                           k_scales, v_scales)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                     # bt, q_start, q_len
        grid=(b, hkv, mb),
        in_specs=[pl.BlockSpec((1, 1, g * lq, dh),
                               lambda b_, h_, j, bt, qs, ql: (b_, h_, 0, 0)),
                  *pool_specs],
        out_specs=pl.BlockSpec((1, 1, g * lq, dh),
                               lambda b_, h_, j, bt, qs, ql: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g * lq,), jnp.float32),
            pltpu.VMEM((g * lq,), jnp.float32),
            pltpu.VMEM((g * lq, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, mb=mb, lq=lq, g=g,
                          window=window, causal=causal, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g * lq, dh), q.dtype),
        interpret=interpret,
    )(block_tables, q_start, q_len, qt, *pool_args)
    return out.reshape(b, hkv, g, lq, dh).transpose(0, 3, 1, 2, 4) \
              .reshape(b, lq, h, dh)


# ===========================================================================
# shard_map wrappers: shard-local kernels over a (data, ...) mesh
# ===========================================================================

def _head_axis(mesh, h: int, hkv: int):
    """Tensor-parallel head split inside the shard_map: only when BOTH
    head counts divide the 'model' axis (splitting q heads without their
    kv heads would break GQA grouping); otherwise heads replicate over
    'model' and every model shard computes all heads."""
    m = mesh.shape.get("model", 1)
    return "model" if m > 1 and h % m == 0 and hkv % m == 0 else None


def _shard_mapped(kernel, mesh, q, k_pages, v_pages, block_tables,
                  page_pos, row_vecs, *, k_scales, v_scales, axis: str,
                  rows_sharded: bool, **kw):
    """Run ``kernel`` under ``shard_map``: pool blocks (axis 0 of the
    pages, scales and ``page_pos``) partition over ``axis``, heads over
    'model' where ``_head_axis`` allows.  Shard s owns global block ids
    [s*bps, (s+1)*bps) (the ShardedKVPool convention); each shard keeps
    the table entries in its own segment, rebased to local ids, and masks
    the rest (-1), so every page DMA hits a resident page.

    rows_sharded: the rows (axis 0 of q, the tables and ``row_vecs``)
    partition over ``axis`` too, which is exact when each shard's rows
    only reference its own segment — the decode grid, collective-free.
    Otherwise (a one-row prefill chunk lives on one shard) the rows are
    replicated, every shard runs them against its own pages, and a psum
    over ``axis`` keeps each row's output from the shard owning its
    blocks."""
    from jax.sharding import PartitionSpec as P
    bps = k_pages.shape[0] // mesh.shape[axis]
    head = _head_axis(mesh, q.shape[2], k_pages.shape[2])
    row = axis if rows_sharded else None
    q_sp = P(row, None, head, None)
    quantized = k_scales is not None
    scales = (k_scales, v_scales) if quantized else ()

    def local(qs, kp, vp, bt, pp, *rest):
        ks, vs = rest[:2] if quantized else (None, None)
        off = jax.lax.axis_index(axis) * bps
        mine = (bt >= off) & (bt < off + bps)
        o = kernel(qs, kp, vp, jnp.where(mine, bt - off, -1), pp,
                   *rest[len(scales):], k_scales=ks, v_scales=vs, **kw)
        if rows_sharded:
            return o
        owner = mine.any(axis=1)[:, None, None, None]
        return jax.lax.psum(jnp.where(owner, o, 0), axis)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(q_sp, P(axis, None, head, None), P(axis, None, head, None),
                  P(row, None), P(axis, None),
                  *[P(axis, None, head)] * len(scales),
                  *[P(row)] * len(row_vecs)),
        out_specs=q_sp, check_vma=False,
    )(q, k_pages, v_pages, block_tables, page_pos, *scales, *row_vecs)


def sharded_paged_attention(mesh, q, k_pages, v_pages, block_tables,
                            page_pos, q_pos, *, k_scales=None,
                            v_scales=None, window=None,
                            causal: bool = True, interpret: bool = False,
                            axis: str = "data", rows_sharded: bool = True):
    """``paged_attention`` under ``shard_map`` (``_shard_mapped``): every
    shard runs the single-device kernel against its local page segment.
    With ``rows_sharded`` (the default) rows split over ``axis`` and the
    call is collective-free; this needs the ShardedKVPool invariant (a
    row's table references only its own shard's segment).  Quantized
    pools pass their (P, BS, Hkv) scales, which shard exactly like the
    pages (blocks on ``axis``, Hkv on the head axis)."""
    return _shard_mapped(paged_attention, mesh, q, k_pages, v_pages,
                         block_tables, page_pos, (q_pos,),
                         k_scales=k_scales, v_scales=v_scales, axis=axis,
                         rows_sharded=rows_sharded, window=window,
                         causal=causal, interpret=interpret)


def sharded_paged_prefill_attention(mesh, q, k_pages, v_pages,
                                    block_tables, page_pos, q_start,
                                    q_len, *, k_scales=None, v_scales=None,
                                    window=None, causal: bool = True,
                                    interpret: bool = False,
                                    axis: str = "data",
                                    rows_sharded: bool = True):
    """``paged_prefill_attention`` under ``shard_map`` — same partitioning
    and shard-locality contract (including the conditional 'model' head
    split, quantized-scale handling and ``rows_sharded``) as
    ``sharded_paged_attention``."""
    return _shard_mapped(paged_prefill_attention, mesh, q, k_pages, v_pages,
                         block_tables, page_pos, (q_start, q_len),
                         k_scales=k_scales, v_scales=v_scales, axis=axis,
                         rows_sharded=rows_sharded, window=window,
                         causal=causal, interpret=interpret)
