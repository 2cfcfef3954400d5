"""95th percentile of time to first token in a traced run (harness, open
loop): from each request's due time to the host stamp of its first
token, over every request due in the window; one without a first token
by the window's end counts until then.  The same statistic a plain run
prints; read here because its spread between runs is too wide for an
end-to-end bound (PERF.md).  The profiler's start and stop fall inside
the traced window and hold the loop for seconds, so this reads higher
than a plain run."""
from bench import stats

UNIT = "s"


def read(run):
    t0, t1 = run.window
    first = [s[0] if s else None for s in run.rec.stamps]
    return stats.percentile(stats.censored_waits(run.rec.due, first, t0, t1),
                            95)
