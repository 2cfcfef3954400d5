"""Mean time of one prefill chunk call (step runtime,
``serve/runtime.py``): the program's ``prefill_chunk`` spans that start
in the window, which close after the chunk is dispatched (and, for a
prompt's last chunk, after its first tokens are read back)."""
UNIT = "ms"


def read(run):
    d = [dur for name, _, dur, _ in run.window_spans if name == "prefill_chunk"]
    return 1e3 * sum(d) / len(d) if d else None
