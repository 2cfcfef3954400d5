"""Mean device time of one execution of a jitted prefill chunk step
(model step, ``models/transformer.py``), over every bucket: the
``jit__chunk_impl`` executions on the trace's ``XLA Modules`` line that
start in the traced window (``modules`` of ``bench/trace_spans.py``)."""
from bench.trace_spans import module_ms

UNIT = "ms"


def read(run):
    return module_ms(run.trace, "_chunk_impl")
