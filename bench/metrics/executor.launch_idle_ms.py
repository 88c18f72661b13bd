"""``executor.launch_idle_ms``: device-idle milliseconds per query while
the executor dispatches a program and waits for it.

Layer: the executor (``pipeline/executor.py``): the host's round trips
around the embedding, each stage and the head.  Read from the profiler
trace (``progspans.idle_split``): inside the ``engine.query`` spans, the
time in which no operation ran, outside every XLA module run and inside
an ``executor.embed``, ``executor.stage`` or ``executor.head`` span,
per ``engine.query`` span.  The emulated slowdown's sleep
(``executor.interference``) lies outside those spans, so the metric
reads in interference cells too.  Should move ``latency_p50_ms``.
"""
import progspans


def read(run):
    ps = progspans.of(run)
    if ps is None or not run.profile.busy:
        return None
    split = progspans.idle_split(ps, run.profile.busy[0],
                                 ps.intervals(progspans.QUERY))
    return 1e-6 * split["launch"] / ps.queries
