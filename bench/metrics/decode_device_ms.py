"""Mean device time of one execution of the jitted decode step (model
step, ``models/transformer.py``): the ``jit__decode_impl`` executions on
the trace's ``XLA Modules`` line that start in the traced window
(``modules`` of ``bench/trace_spans.py``).  Unlike ``decode_step_ms``
it leaves out dispatch, the read-back and device work queued ahead of
the step."""
from bench.trace_spans import module_ms

UNIT = "ms"


def read(run):
    return module_ms(run.trace, "_decode_impl")
