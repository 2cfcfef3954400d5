"""Share of the traced slice in which the device idles while the host is
inside a ``cache_edit`` span (KV cache edits, ``serve/engine.py``): the
eager ``reset_blocks`` / ``set_block_tables`` calls (and, under a mesh,
re-commits) that the step runtime makes outside the jitted steps.
``idle_by_span["cache_edit"]`` of ``bench/trace_spans.py`` over the
traced window."""
from bench.trace_spans import idle_share

UNIT = "%"


def read(run):
    return idle_share(run.trace, "cache_edit")
