"""95th percentile of queue wait (scheduler, ``serve/scheduler.py``):
from each request's due time to its admission into a row, stamped by
the program (``Request.t_admit``), over every request due in the window;
one not admitted by the window's end counts until then."""
from bench import stats

UNIT = "s"


def read(run):
    t0, t1 = run.window
    return stats.percentile(
        stats.censored_waits(run.rec.due, run.rec.admit, t0, t1), 95)
