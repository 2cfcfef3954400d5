"""jit'd public wrappers for the Pallas kernels.

On a TPU the kernels compile with Mosaic.  On the CPU they run in
interpret mode (the kernel body runs through the Pallas interpreter — the
same program, for tests and development).  Any other backend is an
error: no kernel silently runs interpreted on an accelerator.
"""
from __future__ import annotations

import jax

from repro.kernels import mux_combine as _mux
from repro.kernels import mux_embed as _mux_embed
from repro.kernels import demux_rsa as _demux
from repro.kernels import flash_attention as _flash
from repro.kernels import rwkv6 as _rwkv
from repro.kernels import decode_attention as _dec
from repro.kernels import paged_attention as _paged


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"the Pallas kernels target TPU (Mosaic) or "
                           f"the CPU interpreter, not {backend!r}")
    return backend == "cpu"


def mux_combine(x, v, **kw):
    kw.setdefault("interpret", _interpret())
    return _mux.mux_combine(x, v, **kw)


def mux_embed_combine(tokens, emb, v, **kw):
    """Fused embed + embedding-scale + Gaussian mux-combine (the decode
    entry prologue as one launch)."""
    kw.setdefault("interpret", _interpret())
    return _mux_embed.mux_embed_combine(tokens, emb, v, **kw)


def demux_rsa(h, k, w1h, w1k, b1, w2, b2, **kw):
    """Batched wrapper: h may be (B, L, D) or (T, D)."""
    kw.setdefault("interpret", _interpret())
    if h.ndim == 3:
        b, l, d = h.shape
        out = _demux.demux_rsa(h.reshape(b * l, d), k, w1h, w1k, b1, w2,
                               b2, **kw)
        return out.reshape(out.shape[0], b, l, d)
    return _demux.demux_rsa(h, k, w1h, w1k, b1, w2, b2, **kw)


def flash_attention(q, k, v, **kw):
    kw.setdefault("interpret", _interpret())
    return _flash.flash_attention(q, k, v, **kw)


def rwkv6_chunked(r, k, v, logw, u, s0, **kw):
    kw.setdefault("interpret", _interpret())
    return _rwkv.rwkv6_chunked(r, k, v, logw, u, s0, **kw)


def decode_attention(q, k_cache, v_cache, slot_pos, **kw):
    kw.setdefault("interpret", _interpret())
    return _dec.decode_attention(q, k_cache, v_cache, slot_pos, **kw)


def paged_attention(q, k_pages, v_pages, block_tables, page_pos, q_pos, **kw):
    kw.setdefault("interpret", _interpret())
    return _paged.paged_attention(q, k_pages, v_pages, block_tables,
                                  page_pos, q_pos, **kw)


def paged_prefill_attention(q, k_pages, v_pages, block_tables, page_pos,
                            q_start, q_len, **kw):
    kw.setdefault("interpret", _interpret())
    return _paged.paged_prefill_attention(q, k_pages, v_pages, block_tables,
                                          page_pos, q_start, q_len, **kw)


def sharded_paged_attention(mesh, q, k_pages, v_pages, block_tables,
                            page_pos, q_pos, **kw):
    """shard_map'd paged decode kernel: per-shard pages + rebased tables
    (collective-free; DESIGN.md §sharded serving)."""
    kw.setdefault("interpret", _interpret())
    return _paged.sharded_paged_attention(mesh, q, k_pages, v_pages,
                                          block_tables, page_pos, q_pos,
                                          **kw)


def sharded_paged_prefill_attention(mesh, q, k_pages, v_pages,
                                    block_tables, page_pos, q_start,
                                    q_len, **kw):
    kw.setdefault("interpret", _interpret())
    return _paged.sharded_paged_prefill_attention(
        mesh, q, k_pages, v_pages, block_tables, page_pos, q_start, q_len,
        **kw)
