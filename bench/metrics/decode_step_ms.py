"""Mean time of one decode call (step runtime, ``serve/runtime.py``):
the program's ``decode`` spans that start in the window, which cover
the dispatch of the jitted decode step and the read-back of its tokens."""
UNIT = "ms"


def read(run):
    d = [dur for name, _, dur, _ in run.window_spans if name == "decode"]
    return 1e3 * sum(d) / len(d) if d else None
