"""Share of the traced slice in which the device idles while the host is
inside a ``step_inputs`` span (step runtime, host: ``serve/runtime.py``,
``serve/scheduler.py``): scheduler planning, pool allocation and
appends, and building the sampling vectors and token buffers of a step.
``idle_by_span["step_inputs"]`` of ``bench/trace_spans.py`` over the
traced window."""
from bench.trace_spans import idle_share

UNIT = "%"


def read(run):
    return idle_share(run.trace, "step_inputs")
