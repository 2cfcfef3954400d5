"""Operations and bytes of one ``paged_prefill_attention`` call (one
layer of one prefill chunk of one row, ``kernels/paged_attention.py``),
from shapes.

The chunk's ``length`` valid queries sit at positions start ..
start + length - 1; each attends causally to every earlier key of its
row (or the last ``window`` of them).  The least the call can move is
the pages that hold any of those keys, once, for every KV head, plus the
slot positions, the queries and the outputs of the valid positions.
"""
from __future__ import annotations

import re


def keys_attended(start: int, length: int, window=None) -> int:
    """Sum over the chunk's queries of the keys each one attends."""
    if window is None:
        return length * start + length * (length + 1) // 2
    return sum(min(q + 1, window) for q in range(start, start + length))


def cost(start: int, length: int, *, heads: int, kv_heads: int,
         head_dim: int, block: int, window=None, kv_bytes: int = 2,
         act_bytes: int = 2):
    """(flops, bytes) of one call."""
    flops = 4 * heads * head_dim * keys_attended(start, length, window)
    end = start + length                      # keys 0 .. end - 1 written
    first = 0 if window is None else max(0, start + 1 - window)
    n_pages = (end - 1) // block - first // block + 1
    nbytes = n_pages * block * (2 * kv_heads * head_dim * kv_bytes + 4)
    nbytes += 2 * length * heads * head_dim * act_bytes
    return flops, nbytes



def in_trace(name: str, meta: str) -> bool:
    """Whether a device operation of the profiler trace is this kernel:
    the HLO instruction named after the Pallas kernel,
    ``%paged_prefill_attention.8 = ... custom-call(...)`` with
    ``custom_call_target="tpu_custom_call"``."""
    return (re.match(r"%paged_prefill_attention(\.\d+)? = ", name)
            is not None and "tpu_custom_call" in name)
