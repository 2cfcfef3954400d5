"""Operations and bytes of one ``paged_attention`` call (one layer of a
decode step, ``kernels/paged_attention.py``), from shapes.

Each live row attends its one query per head over the keys it may see:
its whole KV length, or the last ``window`` entries.  The least the call
can move is those keys' pages, K and V for every KV head, plus the slot
positions of those pages, the queries and the outputs.
"""
from __future__ import annotations

import re


def pages(ctx: int, window, block: int) -> int:
    """Pages holding the keys a query at position ctx - 1 attends."""
    first = 0 if window is None else max(0, ctx - window)
    return (ctx - 1) // block - first // block + 1


def cost(ctx_lens, *, heads: int, kv_heads: int, head_dim: int, block: int,
         window=None, kv_bytes: int = 2, act_bytes: int = 2):
    """(flops, bytes) of one call over live rows with KV lengths
    ``ctx_lens`` (the new token included)."""
    flops = nbytes = 0
    for c in ctx_lens:
        keys = c if window is None else min(c, window)
        flops += 4 * heads * head_dim * keys        # q.k and p.v, 2 per MAC
        nbytes += pages(c, window, block) * block * (
            2 * kv_heads * head_dim * kv_bytes + 4)  # K, V and slot positions
        nbytes += 2 * heads * head_dim * act_bytes    # query in, output out
    return flops, nbytes



def in_trace(name: str, meta: str) -> bool:
    """Whether a device operation of the profiler trace is this kernel.
    On a v5e trace the operation's name is its HLO instruction, named
    after the Pallas kernel: ``%paged_attention.8 = ... custom-call(...)``
    with ``custom_call_target="tpu_custom_call"``."""
    return (re.match(r"%paged_attention(\.\d+)? = ", name) is not None
            and "tpu_custom_call" in name)
